"""Paged attention over the KV cache's HBM page layout (ISSUE 10).

The serving stack's KV lives in fixed-size refcounted pages carved out
of leased HBM blocks (``kvcache/pages.py``); the engine already gathers
a fixed-shape per-slot page table every step.  This module is the
kernel that CONSUMES that layout: queries attend over K/V gathered
through the page table, so prefix-shared pages, copy-on-write forks and
radix-cached chunks all feed the model without ever being flattened
into per-sequence contiguous buffers.

Two backends, one contract:

  * ``gather`` — pure jax (``jnp.take`` over the arena + one masked
    softmax).  Runs anywhere; the CPU-valid default, so tier-1 under
    ``JAX_PLATFORMS=cpu`` exercises exactly this path.
  * ``pallas`` — a ``pallas_call`` TPU kernel using
    ``PrefetchScalarGridSpec``: the page table is a SCALAR-PREFETCH
    argument, so each grid step's K/V block is DMA'd straight from the
    arena row the table names (the classic paged-attention pattern —
    the gather never materializes).  Online-softmax accumulation over
    the page axis, exactly the flash discipline of
    ``ops/attention.py``.  The TPU default; compiled by Mosaic there
    (``tests/test_chip_compile.py`` keeps it lowering for a v5e), and
    ``interpret=True`` off-TPU keeps it testable on the virtual CPU
    mesh.

Shapes (one query per row — decode steps batch rows across slots,
prefill batches rows across suffix positions):

  q        [N, H, D]        query vectors
  k_pages  [P, T, Hkv, D]   the arena view: P pages of T token slots
  v_pages  [P, T, Hkv, D]
  tables   [N, MP] int32    per-row page table: FLAT arena indices
                            (``PagePool.flat_ids``), -1 padded
  lengths  [N] int32        per-row valid KEY positions: key j of row i
                            participates iff j < lengths[i] — causal
                            masking IS the lengths vector
  extra_k/extra_v [N, Hkv, D] optional one-key append per row: the
                            decode step's own just-computed K/V, merged
                            into the same softmax (its key position is
                            lengths[i], i.e. always visible)
  local_k/local_v [G, W, Hkv, D] optional LOCAL KEY BLOCK (ISSUE 11):
                            rows reshape into G groups of W queries
                            (N == G*W — a speculative-verify batch is
                            one group per decode slot, W = draft rows),
                            and every query in group g may additionally
                            attend over that group's W in-call keys —
                            the draft positions' K/V, computed in the
                            same forward pass, never materialized into
                            pages.  Visibility is the boolean
  local_mask      [G, W, W]  ancestry mask: query row i of group g sees
                            local key j iff ``local_mask[g, i, j]`` —
                            lower-triangular for a linear draft chain,
                            the tree mask for branching drafts.  The
                            fold is one more online-softmax merge, so a
                            row with one visible local key (itself) is
                            numerically the ``extra_k`` decode-step
                            fold.  Mutually exclusive with extra_k.

GQA/MQA: fewer K/V heads than query heads are expanded per group, the
``_expand_kv`` contract of ops/attention.py.

Rows with lengths <= 0 and no extra key yield zeros (never NaN), so
inactive decode slots cost nothing to mask upstream.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["paged_attention", "paged_attention_gather",
           "paged_attention_pallas", "arena_kv_view", "default_backend"]


def arena_kv_view(arena_u8, page_tokens: int, n_layers: int,
                  n_kv_heads: int, head_dim: int):
    """Bitcast a PagePool :meth:`~brpc_tpu.kvcache.pages.PagePool.arena`
    byte array ``[P, page_bytes]`` into the packed K/V view
    ``[P, T, L, 2, Hkv, D]`` f32 — the token-major slot layout the
    ModelRunner writes (``models/runner.py``): one token's slot holds
    all layers' K then V vectors contiguously, so a decode step
    materializes a position with ONE page splice."""
    p = arena_u8.shape[0]
    flat = arena_u8.reshape(p, page_tokens, n_layers, 2, n_kv_heads,
                            head_dim, 4)
    return jax.lax.bitcast_convert_type(flat, jnp.float32)


def _expand_heads(x, n_heads: int):
    """[..., Hkv, D] -> [..., H, D] by repeating each K/V head across
    its query-head group (GQA; the broadcast fuses into the einsum)."""
    hkv = x.shape[-2]
    if hkv == n_heads:
        return x
    if n_heads % hkv:
        raise ValueError(f"n_heads ({n_heads}) must be a multiple of "
                         f"n_kv_heads ({hkv})")
    return jnp.repeat(x, n_heads // hkv, axis=-2)


# ---- gather backend (pure jax; the CPU-valid default) ----------------------

def _check_local(extra_k, local_k, local_v, local_mask, n):
    if local_k is None:
        return
    if extra_k is not None:
        raise ValueError("extra_k and local_k are mutually exclusive")
    if local_v is None or local_mask is None:
        raise ValueError("local_k needs local_v and local_mask")
    g, w = local_mask.shape[0], local_mask.shape[1]
    if g * w != n:
        raise ValueError(f"local block groups {g}x{w} != {n} query rows")


def paged_attention_gather(q, k_pages, v_pages, tables, lengths,
                           extra_k=None, extra_v=None,
                           local_k=None, local_v=None, local_mask=None):
    n, h, d = q.shape
    p, t, hkv, _ = k_pages.shape
    mp = tables.shape[1]
    _check_local(extra_k, local_k, local_v, local_mask, n)
    scale = 1.0 / math.sqrt(d)
    safe = jnp.clip(tables, 0, p - 1)
    # [N, MP, T, Hkv, D] -> [N, MP*T, H, D]; clipped -1 rows are masked
    # below (key position >= lengths), so their values never matter
    k = jnp.take(k_pages, safe, axis=0).reshape(n, mp * t, hkv, d)
    v = jnp.take(v_pages, safe, axis=0).reshape(n, mp * t, hkv, d)
    k = _expand_heads(k, h)
    v = _expand_heads(v, h)
    qf = q.astype(jnp.float32) * scale
    s = jnp.einsum("nhd,nkhd->nhk", qf, k.astype(jnp.float32),
                   preferred_element_type=jnp.float32)  # [N, H, MP*T]
    kpos = jnp.arange(mp * t, dtype=jnp.int32)
    # a key participates iff its position is visible AND its table
    # entry names a real page — same contract as the pallas kernel's
    # tab >= 0 mask; without it a -1 entry mid-table (a page freed
    # between the engine's gather and this call) would fold page 0's
    # K/V into the softmax through the clip above
    mask = (kpos[None, None, :] < lengths[:, None, None]) \
        & jnp.repeat(tables >= 0, t, axis=1)[:, None, :]
    s = jnp.where(mask, s, -jnp.inf)
    if extra_k is not None:
        ek = _expand_heads(extra_k, h).astype(jnp.float32)  # [N, H, D]
        ev = _expand_heads(extra_v, h)
        es = jnp.einsum("nhd,nhd->nh", qf, ek)[..., None]   # [N, H, 1]
        s = jnp.concatenate([s, es], axis=-1)
        v = jnp.concatenate([v, ev[:, None]], axis=1)       # [N, K+1, H, D]
    if local_k is not None:
        g, w = local_mask.shape[0], local_mask.shape[1]
        lk = _expand_heads(local_k, h).astype(jnp.float32)  # [G, W, H, D]
        lv = _expand_heads(local_v, h)
        qg = qf.reshape(g, w, h, d)
        # [G, Wq, H, Wk]: every query row of the group scores every
        # local key; the ancestry mask decides visibility (a masked
        # entry folds in as exp(-inf)=0, bit-preserving the visible sum)
        ls = jnp.einsum("gihd,gjhd->gihj", qg, lk,
                        preferred_element_type=jnp.float32)
        ls = jnp.where(local_mask[:, :, None, :], ls, -jnp.inf)
        s = jnp.concatenate([s, ls.reshape(n, h, w)], axis=-1)
        lvb = jnp.broadcast_to(lv[:, None], (g, w, w, h, d))
        v = jnp.concatenate([v, lvb.reshape(n, w, h, d)], axis=1)
    # -inf-safe softmax: rows with no visible key yield zeros, not NaN
    m = s.max(axis=-1, keepdims=True)
    m = jnp.where(jnp.isneginf(m), 0.0, m)
    pr = jnp.exp(s - m)
    pr = jnp.where(jnp.isneginf(s), 0.0, pr)
    l = pr.sum(axis=-1, keepdims=True)
    pr = pr / jnp.where(l == 0.0, 1.0, l)
    return jnp.einsum("nhk,nkhd->nhd",
                      pr.astype(jnp.float32),
                      v.astype(jnp.float32)).astype(q.dtype)


# ---- pallas backend --------------------------------------------------------

def _paged_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, *, page_tokens: int, scale: float):
    """One (row, page) program: fold page ``tables[n, m]``'s K/V block
    into row n's online-softmax accumulator.  The page table and
    lengths ride SCALAR PREFETCH, so the BlockSpec index_map DMA'd
    k_ref/v_ref straight from the arena row the table names — no
    gathered copy of the K/V ever exists.  Outputs stay UNNORMALIZED
    (o, m, l); the wrapper merges the optional self-key and divides.

    Written for Mosaic (the chip's compiler), which tiles the LAST TWO
    dims of every block over (8 sublanes, 128 lanes): the page block
    keeps the arena's own ``[T, Hkv, D]`` layout and every value in
    here has ``[Hkv, D]`` (or ``[Hkv, 1]``) as its minor dims.  One
    query per head makes the score a matrix-VECTOR product, which the
    MXU cannot batch over heads — so the head contraction is a VPU
    multiply + lane reduce (``keepdims``: a squeezed ``[T, Hkv]``
    would need a relayout), the page fold reduces over the untiled
    leading T axis, and GQA is a static loop over the query heads of
    each K/V head (``q_ref`` arrives ``[G, Hkv, D]``) instead of a
    ``jnp.repeat`` of the page.  m/l are ``[G, Hkv, 1]``: a block
    whose minor dims equal the array's is the form Mosaic accepts for
    an accumulator this narrow."""
    from jax.experimental import pallas as pl
    n = pl.program_id(0)
    m_i = pl.program_id(1)

    @pl.when(m_i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    k = k_ref[...].astype(jnp.float32)                  # [T, Hkv, D]
    v = v_ref[...].astype(jnp.float32)
    # mask: global key position of slot t in page m is m*T + t; valid
    # iff < lengths[n] AND the table entry is a real page (>= 0)
    kpos = m_i * page_tokens + jax.lax.broadcasted_iota(
        jnp.int32, (k.shape[0], k.shape[1], 1), 0)
    valid = (kpos < len_ref[n]) & (tab_ref[n, m_i] >= 0)
    for g in range(q_ref.shape[0]):
        q = q_ref[g].astype(jnp.float32) * scale        # [Hkv, D]
        s = jnp.sum(k * q[None], axis=-1, keepdims=True)  # [T, Hkv, 1]
        s = jnp.where(valid, s, -jnp.inf)
        m_prev = m_ref[g]                               # [Hkv, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=0))
        # all-masked-so-far rows keep -inf maxima; guard every exp
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.where(jnp.isneginf(m_prev), 0.0,
                          jnp.exp(m_prev - m_safe))
        p = jnp.exp(s - m_safe[None])
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        m_ref[g] = m_new
        l_ref[g] = l_ref[g] * alpha + p.sum(axis=0)
        o_ref[g] = o_ref[g] * alpha + jnp.sum(p * v, axis=0)


def paged_attention_pallas(q, k_pages, v_pages, tables, lengths,
                           extra_k=None, extra_v=None,
                           local_k=None, local_v=None, local_mask=None,
                           interpret: Optional[bool] = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, h, d = q.shape
    p, t, hkv, _ = k_pages.shape
    mp = tables.shape[1]
    _check_local(extra_k, local_k, local_v, local_mask, n)
    if h % hkv:
        raise ValueError(f"n_heads ({h}) must be a multiple of "
                         f"n_kv_heads ({hkv})")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = 1.0 / math.sqrt(d)
    g = h // hkv
    # query head j attends K/V head j // g (the _expand_heads order):
    # hand the kernel [G, Hkv, D] so each group index is one K/V-shaped
    # slab, and undo the split on the way out

    def split(x):
        return x.reshape(n, hkv, g, -1).transpose(0, 2, 1, 3)

    def merge(x):
        return x.transpose(0, 2, 1, 3).reshape(n, h, -1)

    def row(i, m, tab, ln):
        return (i, 0, 0, 0)

    def page(i, m, tab, ln):
        return (jnp.clip(tab[i, m], 0, p - 1), 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,           # tables, lengths
        grid=(n, mp),
        in_specs=[
            pl.BlockSpec((None, g, hkv, d), row),
            pl.BlockSpec((None, t, hkv, d), page),
            pl.BlockSpec((None, t, hkv, d), page),
        ],
        out_specs=[
            pl.BlockSpec((None, g, hkv, d), row),
            pl.BlockSpec((None, g, hkv, 1), row),
            pl.BlockSpec((None, g, hkv, 1), row),
        ],
    )
    o, mx, l = pl.pallas_call(
        functools.partial(_paged_kernel, page_tokens=t, scale=scale),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n, g, hkv, d), jnp.float32),
            jax.ShapeDtypeStruct((n, g, hkv, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, g, hkv, 1), jnp.float32),
        ],
        interpret=interpret,
        name="paged_attention",
    )(tables, lengths, split(q), k_pages, v_pages)
    o, mx, l = merge(o), merge(mx)[..., 0], merge(l)[..., 0]
    if extra_k is not None:
        # merge the self key into the accumulated (o, m, l) — one more
        # online-softmax fold, in plain jax
        ek = _expand_heads(extra_k, h).astype(jnp.float32)  # [N, H, D]
        ev = _expand_heads(extra_v, h).astype(jnp.float32)
        es = jnp.einsum("nhd,nhd->nh",
                        q.astype(jnp.float32) * scale, ek)  # [N, H]
        m_new = jnp.maximum(mx, es)
        alpha = jnp.where(jnp.isneginf(mx), 0.0, jnp.exp(mx - m_new))
        pe = jnp.exp(es - m_new)
        o = o * alpha[..., None] + pe[..., None] * ev
        l = l * alpha + pe
    if local_k is not None:
        # fold the whole local key block at once — the multi-key
        # generalization of the extra_k merge, masked by ancestry
        g, w = local_mask.shape[0], local_mask.shape[1]
        lk = _expand_heads(local_k, h).astype(jnp.float32)
        lv = _expand_heads(local_v, h).astype(jnp.float32)
        qg = (q.astype(jnp.float32) * scale).reshape(g, w, h, d)
        ls = jnp.einsum("gihd,gjhd->gihj", qg, lk,
                        preferred_element_type=jnp.float32)
        ls = jnp.where(local_mask[:, :, None, :], ls,
                       -jnp.inf).reshape(n, h, w)
        m_new = jnp.maximum(mx, ls.max(axis=-1))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.where(jnp.isneginf(mx), 0.0, jnp.exp(mx - m_safe))
        pe = jnp.exp(ls - m_safe[..., None])
        pe = jnp.where(jnp.isneginf(ls), 0.0, pe)
        lvb = jnp.broadcast_to(lv[:, None],
                               (g, w, w, h, d)).reshape(n, w, h, d)
        o = o * alpha[..., None] + jnp.einsum(
            "nhw,nwhd->nhd", pe, lvb,
            preferred_element_type=jnp.float32)
        l = l * alpha + pe.sum(axis=-1)
    l = jnp.where(l == 0.0, 1.0, l)
    return (o / l[..., None]).astype(q.dtype)


# ---- dispatcher ------------------------------------------------------------

def default_backend() -> str:
    """The backend ``paged_attention(backend=None)`` runs: the kernel
    on a TPU, the gather everywhere else."""
    return "pallas" if jax.default_backend() == "tpu" else "gather"


def paged_attention(q, k_pages, v_pages, tables, lengths,
                    extra_k=None, extra_v=None,
                    local_k=None, local_v=None, local_mask=None,
                    backend: Optional[str] = None,
                    interpret: Optional[bool] = None):
    """Paged attention (see module docstring).  ``backend`` picks
    "gather" (pure jax — the default off-TPU so the CPU tier-1 path
    never touches the pallas interpreter) or "pallas" (the TPU kernel;
    ``interpret=True`` runs it on CPU for equivalence tests).  On a
    TPU the kernel is compiled for the chip or the call fails with the
    compiler's refusal: nothing here substitutes the gather backend or
    interpret mode for a kernel that would not lower."""
    if backend is None:
        backend = default_backend()
    if backend not in ("gather", "pallas"):
        raise ValueError(f"unknown paged_attention backend {backend!r}")
    # every operation of the call (the kernel, the gathers, the self-key
    # fold) is named under one scope, inside whatever scope the caller
    # traces it in: a trace can tell the call's device time from the
    # rest of the step
    with jax.named_scope("ops.paged_attention"):
        if backend == "gather":
            return paged_attention_gather(q, k_pages, v_pages, tables,
                                          lengths, extra_k, extra_v,
                                          local_k, local_v, local_mask)
        return paged_attention_pallas(q, k_pages, v_pages, tables,
                                      lengths, extra_k, extra_v,
                                      local_k, local_v, local_mask,
                                      interpret=interpret)

"""Learned block-sparse attention over paged K/V (ISSUE 32): the
selection of InfLLM-V2 as MiniCPM4 publishes it, and paged attention
over the page table the selection IS.

One selection block is one page, so the set of blocks a query attends
to is a per-row page table of ``topk`` entries; every entry carries its
LOGICAL block index beside the arena index, because a selected table is
not a contiguous sequence and the causal mask needs each key's position.

  :func:`compress_keys`   ``kc[j] = mean(k[stride*j : stride*j + 2*stride])``
  :func:`select_blocks`   scores over the compressed keys -> the blocks
                          to attend (block 0, the window, the best of
                          the rest), as logical block indices
  :func:`sparse_attend`   attention of one query a row over its selected
                          pages; on a TPU the ``sparse_attend`` Pallas
                          kernel (page table, block indices and lengths
                          ride scalar prefetch, each page is DMA'd from
                          the arena row the table names, ``q k^T`` and
                          ``p v`` run on the MXU for the whole query
                          group of a K/V head), elsewhere a gather.

Geometry (fixed ratios, checked): a page holds ``4 * stride`` tokens,
a kernel spans ``2 * stride`` tokens and starts every ``stride``: four
kernels start in a page, five overlap it.

K/V arena layout: ``kv [layers, 2, Hkv, P, T, D]`` (one ``[T, D]`` tile
a page a head: what the MXU wants), compressed keys
``kc [layers, P, 4, Hkv, D]`` (kernel ``j`` at slot ``(j + 1) % 4`` of
page ``(j + 1) // 4`` of the SEQUENCE's table: the page its last key
lies in, so a shared prefix page holds only kernels of the prefix).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from brpc_tpu.ops.paged_attention import default_backend

__all__ = ["compress_keys", "select_blocks", "sparse_attend",
           "sparse_attend_gather", "sparse_attend_pallas",
           "cache_write", "page_keys",
           "default_backend", "KERNELS_PER_PAGE"]

KERNELS_PER_PAGE = 4
NEG = -1.0            # score of a block no complete kernel overlaps


def compress_keys(k16):
    """``k16 [n16, ...]``: means over consecutive runs of ``stride``
    keys.  Kernel ``i`` (of ``n16 - 1``) is the mean of runs ``i`` and
    ``i + 1``: 2*stride keys, starting every stride."""
    return 0.5 * (k16[:-1] + k16[1:])


def select_blocks(q, kc, qpos, *, page_tokens: int, topk: int,
                  init_blocks: int, window: int):
    """The blocks each query attends to.

    ``q``    ``[N, Hkv, G, D]`` float32 (the query heads of each K/V head)
    ``kc``   ``[N, J, Hkv, D]`` the compressed keys of each row's sequence
             in kernel order (``J = 4 * pages``)
    ``qpos`` ``[N]`` the query's 0-based position ``t``

    Returns ``blocks [N, Hkv, topk]`` int32: logical block indices,
    best first, ``-1`` where fewer than ``topk`` blocks exist.  A kernel
    takes part when it is complete and ends at or before ``t``; block
    scores are the max over the (up to five) kernels that overlap the
    block of the group-summed softmax; block 0..init_blocks-1 and the
    blocks that overlap the last ``window`` positions are always taken
    and count toward ``topk``."""
    n, hkv, g, d = q.shape
    j = kc.shape[1]
    stride = page_tokens // KERNELS_PER_PAGE
    nb = j // KERNELS_PER_PAGE
    f32 = jnp.float32
    with jax.named_scope("ops.sparse_select"):
        s = jnp.einsum("nhgd,njhd->nhgj", q.astype(f32), kc.astype(f32),
                       precision="highest") / math.sqrt(d)
        jid = jnp.arange(j, dtype=jnp.int32)
        kvalid = (stride * jid[None, :] + 2 * stride - 1
                  <= qpos[:, None])                          # [N, J]
        s = jnp.where(kvalid[:, None, None, :], s, -jnp.inf)
        m = s.max(axis=-1, keepdims=True)
        m = jnp.where(jnp.isneginf(m), 0.0, m)
        p = jnp.where(kvalid[:, None, None, :], jnp.exp(s - m), 0.0)
        z = p.sum(axis=-1, keepdims=True)
        p = p / jnp.where(z == 0.0, 1.0, z)
        grp = p.sum(axis=2)                                  # [N, Hkv, J]
        grp = jnp.where(kvalid[:, None, :], grp, NEG)
        s4 = grp.reshape(n, hkv, nb, KERNELS_PER_PAGE)
        prev = jnp.concatenate(
            [jnp.full((n, hkv, 1), NEG, f32), s4[:, :, :-1, -1]], axis=2)
        score = jnp.maximum(s4.max(axis=-1), prev)           # [N, Hkv, nb]
        bid = jnp.arange(nb, dtype=jnp.int32)[None, :]
        t = qpos[:, None]
        exists = bid * page_tokens <= t
        first_w = jnp.maximum(t - (window - 1), 0) // page_tokens
        forced = (bid < init_blocks) | (bid >= first_w)
        prio = jnp.where(forced[:, None, :], jnp.inf, score)
        prio = jnp.where(exists[:, None, :], prio, -jnp.inf)
        kk = min(topk, nb)
        top, idx = jax.lax.top_k(prio, kk)
        idx = jnp.where(jnp.isneginf(top), -1, idx).astype(jnp.int32)
        if kk < topk:
            idx = jnp.concatenate(
                [idx, jnp.full((n, hkv, topk - kk), -1, jnp.int32)], axis=-1)
    return idx


# ---- attention over the selected pages -------------------------------------

def _finish(o, m, l, q, extra_k, extra_v, scale):
    """Fold the optional self key into the unnormalised (o, m, l) and
    divide.  ``o [N, G, D]``, ``m``/``l`` ``[N, G]``."""
    if extra_k is not None:
        es = jnp.einsum("ngd,nd->ng", q * scale,
                        extra_k.astype(jnp.float32), precision="highest")
        m_new = jnp.maximum(m, es)
        alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_new))
        pe = jnp.exp(es - m_new)
        o = o * alpha[..., None] + pe[..., None] \
            * extra_v.astype(jnp.float32)[:, None, :]
        l = l * alpha + pe
    l = jnp.where(l == 0.0, 1.0, l)
    return o / l[..., None]


def sparse_attend_gather(q, kv, layer: int, heads, tables, block_ids,
                         lengths, extra_k=None, extra_v=None):
    n, g, d = q.shape
    t = kv.shape[4]
    p = kv.shape[3]
    mp = tables.shape[1]
    scale = 1.0 / math.sqrt(d)
    f32 = jnp.float32
    safe = jnp.clip(tables, 0, p - 1)
    k = kv[layer, 0][heads[:, None], safe].astype(f32)   # [N, MP, T, D]
    v = kv[layer, 1][heads[:, None], safe].astype(f32)
    qf = q.astype(f32)
    s = jnp.einsum("ngd,nmtd->ngmt", qf * scale, k, precision="highest")
    kpos = block_ids[:, :, None] * t + jnp.arange(t, dtype=jnp.int32)
    valid = (kpos < lengths[:, None, None]) & (tables >= 0)[:, :, None]
    s = jnp.where(valid[:, None], s, -jnp.inf).reshape(n, g, mp * t)
    m = s.max(axis=-1)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    pr = jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - m_safe[..., None]))
    o = jnp.einsum("ngk,nkd->ngd", pr, v.reshape(n, mp * t, d),
                   precision="highest")
    return _finish(o, m, pr.sum(axis=-1), qf, extra_k, extra_v, scale)


def _dot_split(a, b, dims):
    """A float32 ``a`` against a ``b`` the cache holds: where ``b`` is
    bfloat16, ``a`` is split into two bfloat16 terms (``a = hi + lo`` to
    2^-17) and each multiplies ``b`` in ONE pass of the MXU with a
    float32 sum: the product of float32 ``highest`` (six passes, which
    made this kernel compute-bound: 0.4 us a page where its two 16 KB
    fetches take 0.04) at a third of the passes and no cast of the
    page.  Any other ``b`` multiplies as it is."""
    f32 = jnp.float32
    if b.dtype != jnp.bfloat16:
        return jax.lax.dot_general(a, b.astype(f32), dims,
                                   preferred_element_type=f32)
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(f32)).astype(jnp.bfloat16)
    one = jax.lax.Precision.DEFAULT
    return jax.lax.dot_general(hi, b, dims, preferred_element_type=f32,
                               precision=one) \
        + jax.lax.dot_general(lo, b, dims, preferred_element_type=f32,
                              precision=one)


def _sparse_kernel(tab_ref, blk_ref, len_ref, q_ref, *refs, pps: int,
                   page_tokens: int, scale: float):
    from jax.experimental import pallas as pl
    k_refs, v_refs = refs[:pps], refs[pps:2 * pps]
    o_ref, m_ref, l_ref = refs[2 * pps:]
    r = pl.program_id(0)
    mi = pl.program_id(1)

    @pl.when(mi == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...] * scale                                   # [G, D]
    for i in range(pps):
        e = mi * pps + i
        k, v = k_refs[i][...], v_refs[i][...]                # [T, D] bf16
        s = _dot_split(q, k, (((1,), (1,)), ((), ())))
        kpos = blk_ref[r, e] * page_tokens + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        valid = (kpos < len_ref[r]) & (tab_ref[r, e] >= 0)
        s = jnp.where(valid, s, -jnp.inf)                    # [G, T]
        m_prev = m_ref[...]                                  # [G, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.where(jnp.isneginf(m_prev), 0.0,
                          jnp.exp(m_prev - m_safe))
        p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        o_ref[...] = o_ref[...] * alpha + _dot_split(
            p, v, (((1,), (0,)), ((), ())))


def sparse_attend_pallas(q, kv, layer: int, heads, tables, block_ids,
                         lengths, extra_k=None, extra_v=None, *,
                         pages_per_step: int = 4,
                         interpret: Optional[bool] = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, g, d = q.shape
    _, _, hkv, p, t, _ = kv.shape
    mp = tables.shape[1]
    pps = pages_per_step if mp % pages_per_step == 0 else 1
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = 1.0 / math.sqrt(d)
    f32 = jnp.float32
    # the row's K/V head rides scalar prefetch inside the table: fold it
    # into a fourth prefetch operand
    heads = heads.astype(jnp.int32)

    def row3(r, m, tab, blk, ln, hd):
        return (r, 0, 0)

    def page(which, i):
        def index(r, m, tab, blk, ln, hd):
            return (layer, which, hd[r],
                    jnp.clip(tab[r, m * pps + i], 0, p - 1), 0, 0)
        return pl.BlockSpec((None, None, None, None, t, d), index)

    def kernel(tab, blk, ln, hd, *refs):
        _sparse_kernel(tab, blk, ln, *refs, pps=pps, page_tokens=t,
                       scale=scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(n, mp // pps),
        in_specs=[pl.BlockSpec((None, g, d), row3)]
        + [page(0, i) for i in range(pps)]
        + [page(1, i) for i in range(pps)],
        out_specs=[pl.BlockSpec((None, g, d), row3),
                   pl.BlockSpec((None, g, 1), row3),
                   pl.BlockSpec((None, g, 1), row3)])
    o, m, l = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, g, d), f32),
                   jax.ShapeDtypeStruct((n, g, 1), f32),
                   jax.ShapeDtypeStruct((n, g, 1), f32)],
        interpret=interpret, name="sparse_attend",
    )(tables.astype(jnp.int32), block_ids.astype(jnp.int32),
      lengths.astype(jnp.int32), heads, q.astype(f32),
      *([kv] * (2 * pps)))
    return _finish(o, m[..., 0], l[..., 0], q.astype(f32), extra_k,
                   extra_v, scale)


def sparse_attend(q, kv, layer: int, heads, tables, block_ids, lengths,
                  extra_k=None, extra_v=None, *,
                  backend: Optional[str] = None):
    """Attention of one query GROUP a row over its selected pages.

    ``q``         ``[N, G, D]``: a row is (query position, K/V head),
                  its G query heads share the head's keys
    ``kv``        the arena ``[layers, 2, Hkv, P, T, D]``
    ``layer``     static index into it
    ``heads``     ``[N]`` each row's K/V head
    ``tables``    ``[N, MP]`` arena page of each selected block (-1: none)
    ``block_ids`` ``[N, MP]`` its logical block: key ``i`` of the page
                  sits at position ``block * T + i``
    ``lengths``   ``[N]`` keys at positions ``< lengths`` take part
    ``extra_k/v`` ``[N, D]`` optional self key, always visible
    Returns ``[N, G, D]`` float32; a row with no visible key gives 0."""
    if backend is None:
        backend = default_backend()
    with jax.named_scope("ops.sparse_attend"):
        if backend == "gather":
            return sparse_attend_gather(q, kv, layer, heads, tables,
                                        block_ids, lengths, extra_k, extra_v)
        # "mosaic": compiled for the chip whatever the default backend
        # is (a chip-less compile for a described TPU)
        return sparse_attend_pallas(
            q, kv, layer, heads, tables, block_ids, lengths, extra_k,
            extra_v, interpret=False if backend == "mosaic" else None)


# ---- writing and reading the arena -----------------------------------------
#
# On the chip every access to the K/V arena is a Pallas call.  The chip's
# compiler assigns an XLA gather, scatter or dynamic-update-slice over the
# arena's middle dimensions a layout of its own and copies the WHOLE arena
# there and back around it (0.3 GB each way, inside loops once an
# iteration); a custom call takes the array as it lies.

def _write_kernel(page_ref, slot_ref, ok_ref, new_ref, kv_ref, out_ref, *,
                  width: int):
    from jax.experimental import pallas as pl
    i = pl.program_id(0)
    # a block is [..., T, D]: [2, Hkv, T, D] of the K/V arena, [T, C] of
    # the latent one (``ops.latent_attention`` calls this kernel too)
    old = kv_ref[...].astype(jnp.float32)
    new = new_ref[...].astype(jnp.float32)                   # [..., W, D]
    rows = jax.lax.broadcasted_iota(jnp.int32, old.shape, old.ndim - 2)
    hit = (rows >= slot_ref[i]) & (rows < slot_ref[i] + width) \
        & (ok_ref[i] > 0)
    if width != old.shape[-2]:
        new = jnp.broadcast_to(new, old.shape)
    out_ref[...] = jnp.where(hit, new, old).astype(out_ref.dtype)


def cache_write(kv, layer: int, pages, slots, k, v, *,
                backend: Optional[str] = None):
    """K and V blocks ``[N, Hkv, W, D]`` (``W`` 1: a decode position;
    ``W = T``: a whole page) into the arena ``[layers, 2, Hkv, P, T, D]``
    at ``(pages[i], slots[i])``, IN PLACE (donate ``kv``); a page index
    ``>= P`` or ``< 0`` writes nothing (an idle slot, padding).  Values
    are cast to the arena's type."""
    if backend is None:
        backend = default_backend()
    n, hkv, w, d = k.shape
    p, t = kv.shape[3], kv.shape[4]
    if w not in (1, t):
        raise ValueError(f"a block is one position or a page, not {w}")
    ok = ((pages >= 0) & (pages < p)).astype(jnp.int32)
    at = jnp.clip(pages, 0, p - 1).astype(jnp.int32)
    new = jnp.stack([k, v], axis=1).astype(kv.dtype)         # [N,2,Hkv,W,D]
    with jax.named_scope("ops.cache_write"):
        if backend == "gather":
            shape = (1, 2, hkv, 1, w, d)

            def one(i, kv):
                start = (layer, 0, 0, at[i], slots[i], 0)
                old = jax.lax.dynamic_slice(kv, start, shape)
                return jax.lax.dynamic_update_slice(
                    kv, jnp.where(ok[i] > 0, new[i].reshape(shape), old),
                    start)
            return jax.lax.fori_loop(0, n, one, kv)
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        interpret = False if backend == "mosaic" \
            else jax.default_backend() != "tpu"

        def arena(i, pg, sl, okk):
            return (layer, 0, 0, pg[i], 0, 0)
        block = pl.BlockSpec((None, 2, hkv, None, t, d), arena)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n,),
            in_specs=[pl.BlockSpec((None, 2, hkv, w, d),
                                   lambda i, pg, sl, okk: (i, 0, 0, 0, 0)),
                      block],
            out_specs=block)
        return pl.pallas_call(
            functools.partial(_write_kernel, width=w), grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(kv.shape, kv.dtype),
            # operands: pages, slots, ok, new, kv -> kv
            input_output_aliases={4: 0}, interpret=interpret,
            name="cache_write",
        )(at, slots.astype(jnp.int32), ok, new, kv)


def page_keys(kv, layer: int, pages, *, backend: Optional[str] = None):
    """The keys of ``pages [N]``: ``[N, Hkv, T, D]`` in the arena's
    type (indices are clipped into the arena)."""
    if backend is None:
        backend = default_backend()
    hkv, p, t, d = kv.shape[2], kv.shape[3], kv.shape[4], kv.shape[5]
    n = pages.shape[0]
    at = jnp.clip(pages, 0, p - 1).astype(jnp.int32)
    with jax.named_scope("ops.page_keys"):
        if backend == "gather":
            return jax.vmap(lambda pg: jax.lax.dynamic_slice(
                kv, (layer, 0, 0, pg, 0, 0),
                (1, 1, hkv, 1, t, d))[0, 0, :, 0])(at)
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        interpret = False if backend == "mosaic" \
            else jax.default_backend() != "tpu"

        def copy(pg_ref, src_ref, dst_ref):
            dst_ref[...] = src_ref[...]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n,),
            in_specs=[pl.BlockSpec((None, None, hkv, None, t, d),
                                   lambda i, pg: (layer, 0, 0, pg[i], 0, 0))],
            out_specs=pl.BlockSpec((None, hkv, t, d),
                                   lambda i, pg: (i, 0, 0, 0)))
        return pl.pallas_call(
            copy, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n, hkv, t, d), kv.dtype),
            interpret=interpret, name="page_keys")(at, kv)

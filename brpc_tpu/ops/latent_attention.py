"""Paged attention over LATENT pages (ISSUE 34): multi-head latent
attention in its absorbed form, as the decode step and the prefill
chunk of a model that caches ``[c_kv; k_rope]`` a token compute it.

The cache holds ONE row a token a layer (``kv_lora_rank + rope``
values, nothing per head): ``latent [layers, P, T, C]`` bfloat16, page
``p`` = flat arena index ``p`` of the store's pages.  With the
up-projections absorbed into the query and the output,

    q~_h  = [W_UK_h q_nope_h ; q_rope_h]               (C values)
    s_h,t = q~_h . latent[t]                           (the row IS the key)
    o~_h  = sum_t softmax(s_h)_t latent[t]             (and the value)

so every head of a position attends to the SAME rows: the heads are the
rows of one matmul against the page, and key and value are one fetch.
The caller takes ``o~[..., :kv_lora_rank]`` and applies ``W_UV``.

  :func:`latent_write`         rows into the arena in place: one position
                               a slot (decode) or whole pages (prefill)
  :func:`latent_attend`        attention of ``M`` query rows a grid row
                               (the heads of one decode position; the
                               heads of a block of prefill positions)
                               over the pages of a sequence's table, each
                               query row between its own lower bound and
                               causal length; normalised, or as the parts
                               ``(o, m, l)`` of a softmax over more keys
  :func:`latent_join`          one softmax of such parts
  :func:`latent_attend_slots`  one decode position a slot (ISSUE 35): the
                               run of pages the slots hold in common
                               (:func:`shared_run`, on the host from the
                               step's page tables) attended ONCE with
                               every slot's heads in one pass, each
                               slot's own tail after it, joined

On a TPU both are Pallas kernels (``latent_write``, ``latent_attend``):
XLA never gathers from, scatters into or slices the arena (it would give
the arena another layout and copy it whole, PERF.md section 6, PR 32).
Elsewhere plain ``jax.numpy``.  ``latent_attend``'s grid is a flat list
of the (grid row, key block) pairs that hold a visible key
(:func:`work_list`), its length the grid's dynamic bound: a block no
query row can see is neither fetched nor stepped through.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from brpc_tpu.ops.paged_attention import default_backend
from brpc_tpu.ops.sparse_attention import (_write_kernel, first_of_row,
                                           flat_work_list)

__all__ = ["latent_write", "latent_attend", "latent_attend_slots",
           "latent_join", "shared_run", "page_visits", "default_backend"]

PAGES_PER_STEP = 8       # pages of one grid step: one [8 T, C] key block
# slots that must hold a key block in common before the shared pass takes
# it: a block costs the pass of all slots' rows 3.6 us and a slot's own
# pass 1.2 us on a v5e (320 and 32 rows; PERF.md section 6, PR 35)
SHARERS = 3


def _interpret(backend: str) -> bool:
    # "mosaic": compiled for the chip whatever the default backend is
    return False if backend == "mosaic" else jax.default_backend() != "tpu"


# ---- writing ---------------------------------------------------------------

def latent_write(latent, layer: int, pages, slots, rows, *,
                 backend: Optional[str] = None):
    """``rows [N, W, C]`` (``W`` 1: a decode position; ``W = T``: a
    whole page) into ``latent [layers, P, T, C]`` at ``(pages[i],
    slots[i])``, IN PLACE (donate ``latent``); a page index ``>= P`` or
    ``< 0`` writes nothing (an idle slot, padding).  Values are cast to
    the arena's type."""
    if backend is None:
        backend = default_backend()
    n, w, c = rows.shape
    p, t = latent.shape[1], latent.shape[2]
    if w not in (1, t):
        raise ValueError(f"a block is one position or a page, not {w}")
    ok = ((pages >= 0) & (pages < p)).astype(jnp.int32)
    at = jnp.clip(pages, 0, p - 1).astype(jnp.int32)
    new = rows.astype(latent.dtype)
    with jax.named_scope("ops.latent_write"):
        if backend == "gather":
            shape = (1, 1, w, c)

            def one(i, lat):
                start = (layer, at[i], slots[i], 0)
                old = jax.lax.dynamic_slice(lat, start, shape)
                return jax.lax.dynamic_update_slice(
                    lat, jnp.where(ok[i] > 0, new[i].reshape(shape), old),
                    start)
            return jax.lax.fori_loop(0, n, one, latent)
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        block = pl.BlockSpec((None, None, t, c),
                             lambda i, pg, sl, okk: (layer, pg[i], 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n,),
            in_specs=[pl.BlockSpec((None, w, c),
                                   lambda i, pg, sl, okk: (i, 0, 0)),
                      block],
            out_specs=block)
        return pl.pallas_call(
            functools.partial(_write_kernel, width=w), grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(latent.shape, latent.dtype),
            # operands: pages, slots, ok, new, latent -> latent
            input_output_aliases={4: 0}, interpret=_interpret(backend),
            name="latent_write",
        )(at, slots.astype(jnp.int32), ok, new, latent)


# ---- attending -------------------------------------------------------------

def latent_attend_gather(q, qlen, latent, layer: int, tix, tables, qlo=None,
                         parts: bool = False):
    """Float32 at ``highest``, no kernel."""
    r, m, c = q.shape
    p, t = latent.shape[1], latent.shape[2]
    mp = tables.shape[1]
    f32 = jnp.float32
    qlo = jnp.zeros_like(qlen) if qlo is None else qlo
    tab = tables[tix]                                        # [R, MP]
    k = latent[layer][jnp.clip(tab, 0, p - 1)].astype(f32)   # [R,MP,T,C]
    k = k.reshape(r, mp * t, c)
    s = jnp.einsum("rmc,rkc->rmk", q.astype(f32), k, precision="highest")
    kpos = jnp.arange(mp * t, dtype=jnp.int32)[None, None, :]
    valid = (kpos >= qlo) & (kpos < qlen)
    s = jnp.where(valid, s, -jnp.inf)
    mx = s.max(axis=-1, keepdims=True)
    pr = jnp.where(valid, jnp.exp(s - jnp.where(jnp.isneginf(mx), 0.0, mx)),
                   0.0)
    z = pr.sum(axis=-1, keepdims=True)
    if parts:
        return jnp.einsum("rmk,rkc->rmc", pr, k, precision="highest"), mx, z
    # normalised BEFORE the product, as the tests' references were read:
    # dividing after it moves a float32 in its last place, and one such
    # ahead of a bfloat16 rounding of a cached row is 1e-4 in a logit
    return jnp.einsum("rmk,rkc->rmc", pr / jnp.where(z == 0.0, 1.0, z), k,
                      precision="highest")


def _attend_kernel(tix_ref, tab_ref, wrow_ref, wblk_ref, q_ref, qlo_ref,
                   qlen_ref, *refs, pps: int, page_tokens: int):
    from jax.experimental import pallas as pl
    page_refs = refs[:pps]
    o_ref, m_ref, l_ref = refs[pps:]
    w = pl.program_id(0)
    base = wblk_ref[w] * (pps * page_tokens)

    @pl.when(first_of_row(wrow_ref, w))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    k = jnp.concatenate([ref[...] for ref in page_refs], axis=0) \
        if pps > 1 else page_refs[0][...]                    # [pps T, C]
    # one MXU pass, named so: the caller may trace under
    # default_matmul_precision("highest")
    one = jax.lax.Precision.DEFAULT
    s = jax.lax.dot_general(q_ref[...], k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=one)
    kpos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = (kpos >= qlo_ref[...]) & (kpos < qlen_ref[...])  # [M, 1] bounds
    s = jnp.where(valid, s, -jnp.inf)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    alpha = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
    p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    o_ref[...] = o_ref[...] * alpha + jax.lax.dot_general(
        p.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=one)


def work_list(qlo, qlen, n_blocks: int, block_keys: int):
    """The key blocks each grid row must visit, flat: ``(rows [W], blocks
    [W], n)``, ``W = R n_blocks + 1`` static, the first ``n`` entries
    live (a device scalar, the kernel's grid).  Row ``r`` visits the blocks
    that hold a key some query row of it can see, ``min qlo <= k < max
    qlen`` over the rows with any; a row with none visits one block all
    the same (it gives the parts of no key: its output is written)."""
    i32 = jnp.int32
    some = qlen > qlo
    lo = jnp.where(some, qlo, jnp.iinfo(i32).max).min(axis=(1, 2))
    hi = jnp.where(some, qlen, 0).max(axis=(1, 2))
    first = jnp.minimum(lo // block_keys, n_blocks - 1)
    count = jnp.maximum(-(-hi // block_keys) - first, 1)
    return flat_work_list(first, count, n_blocks)


def latent_attend_pallas(q, qlen, latent, layer: int, tix, tables, qlo=None,
                         *, interpret: bool):
    """The parts ``(o, m, l)`` from the kernel: a grid over the flat
    work list, its length the kernel's (dynamic) grid, so a block no
    query row can see is neither fetched nor stepped through."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    r, m, c = q.shape
    p, t = latent.shape[1], latent.shape[2]
    pps = PAGES_PER_STEP
    f32, i32 = jnp.float32, jnp.int32
    qlo = jnp.zeros_like(qlen) if qlo is None else qlo
    # rows in whole (16, 128) tiles of the keys' type; the table in
    # whole steps of pages
    m_pad = -m % 16
    if m_pad:
        pad = ((0, 0), (0, m_pad), (0, 0))
        q, qlo, qlen = jnp.pad(q, pad), jnp.pad(qlo, pad), jnp.pad(qlen, pad)
    if tables.shape[1] % pps:
        tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % pps)),
                         constant_values=-1)
    mm = m + m_pad
    rows, blocks, n = work_list(qlo, qlen, tables.shape[1] // pps, pps * t)

    def row(w, tix_, tab, wrow, wblk):
        return (wrow[w], 0, 0)

    def page(i):
        def index(w, tix_, tab, wrow, wblk):
            at = tab[tix_[wrow[w]], wblk[w] * pps + i]
            return (layer, jnp.clip(at, 0, p - 1), 0, 0)
        return pl.BlockSpec((None, None, t, c), index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(n,),
        in_specs=[pl.BlockSpec((None, mm, c), row),
                  pl.BlockSpec((None, mm, 1), row),
                  pl.BlockSpec((None, mm, 1), row)]
        + [page(i) for i in range(pps)],
        out_specs=[pl.BlockSpec((None, mm, c), row),
                   pl.BlockSpec((None, mm, 1), row),
                   pl.BlockSpec((None, mm, 1), row)])
    o, mx, l = pl.pallas_call(
        functools.partial(_attend_kernel, pps=pps, page_tokens=t),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r, mm, c), f32),
                   jax.ShapeDtypeStruct((r, mm, 1), f32),
                   jax.ShapeDtypeStruct((r, mm, 1), f32)],
        interpret=interpret, name="latent_attend",
    )(tix.astype(i32), tables.astype(i32), rows, blocks,
      q.astype(latent.dtype), qlo.astype(i32), qlen.astype(i32),
      *([latent] * pps))
    return o[:, :m], mx[:, :m], l[:, :m]


def latent_join(parts):
    """One softmax of several: each part ``(o, m, l)`` is the
    unnormalised sum, the running maximum and the sum of weights over
    its own keys (float32); a part with no key (``l`` 0, ``m`` -inf)
    drops out, and rows with no key at all give 0."""
    top = functools.reduce(jnp.maximum, [m for _, m, _ in parts])
    top = jnp.where(jnp.isneginf(top), 0.0, top)
    o = l = 0.0
    for o_i, m_i, l_i in parts:
        scale = jnp.exp(m_i - top)          # exp(-inf) = 0: no key
        o, l = o + o_i * scale, l + l_i * scale
    return o / jnp.where(l == 0.0, 1.0, l)


def latent_attend(q, qlen, latent, layer: int, tix, tables, *, qlo=None,
                  parts: bool = False, backend: Optional[str] = None):
    """Attention of ``M`` query rows a grid row over a sequence's pages.

    ``q``       ``[R, M, C]`` the absorbed queries, already scaled
    ``qlen``    ``[R, M, 1]`` int32: row ``m`` sees the keys at
                positions ``< qlen`` (0: none, the row gives 0)
    ``latent``  the arena ``[layers, P, T, C]``; ``layer`` static
    ``tix``     ``[R]`` the row of ``tables`` a grid row reads
    ``tables``  ``[n, MP]`` arena pages in sequence order (-1: none)
    ``qlo``     ``[R, M, 1]`` int32 or None (0): and at positions
                ``>= qlo``; key blocks below every row's are neither
                fetched nor computed
    ``parts``   return ``(o [R, M, C], m [R, M, 1], l [R, M, 1])``
                float32, the unnormalised sum with its running maximum
                and sum of weights, for :func:`latent_join` with the
                parts over the sequence's other keys
    Returns ``[R, M, C]`` float32: the softmax-weighted sum of the rows
    (its first ``kv_lora_rank`` values are what ``W_UV`` takes).  On the
    kernel path the queries and the probabilities multiply at the
    arena's type (one MXU pass), sums and statistics float32."""
    if backend is None:
        backend = default_backend()
    with jax.named_scope("ops.latent_attend"):
        if backend == "gather":
            return latent_attend_gather(q, qlen, latent, layer, tix, tables,
                                        qlo, parts)
        got = latent_attend_pallas(q, qlen, latent, layer, tix, tables, qlo,
                                   interpret=_interpret(backend))
        return got if parts else latent_join([got])


# ---- one decode position a slot, over a prefix the slots share -------------

def shared_run(tables, seen, page_tokens: int):
    """On the host, from a step's own page tables (numpy ``[S, MP]``
    arena pages, ``seen [S]`` keys a slot attends to, 0 idle): the
    leading run of pages that the most decoding slots hold IN COMMON,
    ``(leader, shared [S])``.  The leader is the longest of the live
    slots on the most common first page; ``shared[r]`` the keys at the
    head of slot ``r``'s table that are the leader's pages too, in whole
    key blocks, never more than ``seen[r]`` (a fork's shared tail page
    may be part filled) nor than what ``SHARERS`` slots share (a block
    that fewer would bring is cheaper in their own passes); 0 for an
    idle slot and for one on another prefix, and everywhere where fewer
    than ``SHARERS`` slots share a block."""
    shared = np.zeros(len(seen), np.int32)
    live = np.flatnonzero(seen > 0)
    if len(live) < SHARERS:
        return 0, shared
    ids, counts = np.unique(tables[live, 0], return_counts=True)
    group = live[tables[live, 0] == ids[np.argmax(counts)]]
    if len(group) < SHARERS:
        return 0, shared
    leader = int(group[np.argmax(seen[group])])
    same = (tables[group] == tables[leader]) & (tables[group] >= 0)
    keys = np.minimum(np.logical_and.accumulate(same, axis=1).sum(axis=1)
                      * page_tokens, seen[group])
    block = PAGES_PER_STEP * page_tokens
    shared[group] = np.minimum(keys, np.sort(keys)[-SHARERS]) \
        // block * block
    return leader, shared


def page_visits(seen, shared, page_tokens: int):
    """What :func:`latent_attend_slots` fetches a layer, counted on the
    host as :func:`work_list` lays it out: ``(pages fetched by either
    pass, whole key blocks; pages of a slot-by-slot pass's fetches that
    the shared pass stood in for)``."""
    block = PAGES_PER_STEP * page_tokens
    blocks = np.maximum(-(-seen // block) - shared // block, 1).sum() \
        + max(int(shared.max()) // block, 1)
    return int(blocks) * PAGES_PER_STEP, int(shared.sum()) // page_tokens


def latent_attend_slots(q, seen, shared, leader, latent, layer: int, tables,
                        *, backend: Optional[str] = None):
    """Attention of one decode position a slot, the run of pages the
    slots hold in common read ONCE.

    ``q``       ``[S, H, C]``; ``seen [S]`` the keys slot ``s`` sees
    ``shared``  ``[S]`` of them, at the head of its table, the keys that
                lie in the pages of ``tables[leader]`` too, whole key
                blocks (:func:`shared_run`); ``leader [1]``
    The shared pass attends the leader's table with every slot's heads
    stacked as the rows of ONE grid row, each bounded by its slot's
    ``shared``; the own pass each slot's table from ``shared`` to
    ``seen``; :func:`latent_join` makes one softmax of the two.  Every
    key below ``seen`` is attended exactly once; where nothing is
    shared the own pass is the whole of it.  Returns ``[S, H, C]``."""
    s_n, h, _ = q.shape
    shared = jnp.minimum(shared, seen)

    def a_head(x):
        return jnp.broadcast_to(x[:, None, None], (s_n, h, 1))
    run = latent_attend(
        q.reshape(1, s_n * h, -1), a_head(shared).reshape(1, s_n * h, 1),
        latent, layer, leader, tables, parts=True, backend=backend)
    own = latent_attend(
        q, a_head(seen), latent, layer, jnp.arange(s_n, dtype=jnp.int32),
        tables, qlo=a_head(shared), parts=True, backend=backend)
    return latent_join([[x.reshape(s_n, h, -1) for x in run], own])

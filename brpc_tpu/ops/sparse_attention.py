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
                          ride scalar prefetch, each page is DMA'd by
                          hand from the arena row the table names, the
                          next step's while this one computes; a grid
                          step takes ``PAGES_PER_STEP`` pages as ONE key
                          tile: one ``q k^T``, one softmax update and
                          one ``p v`` on the MXU for the whole query
                          group of a K/V head; the grid is the flat list
                          of the steps that hold a page a row sees,
                          :func:`flat_work_list`), elsewhere a gather.

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
import numpy as np

from brpc_tpu.ops.paged_attention import default_backend

__all__ = ["compress_keys", "select_blocks", "sparse_attend",
           "sparse_attend_gather", "sparse_attend_pallas", "steps_visited",
           "live_pages", "flat_work_list", "first_of_row", "cache_write",
           "page_keys", "default_backend", "KERNELS_PER_PAGE",
           "PAGES_PER_STEP"]

KERNELS_PER_PAGE = 4
PAGES_PER_STEP = 8    # pages of one grid step of sparse_attend: one key tile
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
    """A float32 ``a [M, K]`` against a ``b`` the cache holds: where
    ``b`` is bfloat16, ``a`` is split into two bfloat16 terms (``a = hi
    + lo`` to 2^-17), the two STACKED into one ``[2 M, K]`` operand that
    multiplies ``b`` in ONE pass of the MXU (``b``'s tiles are loaded as
    weights once for both), and the halves of the float32 result added:
    the product of float32 ``highest`` (six passes, which made this
    kernel compute-bound) at a third of the passes and no cast of the
    page.  Any other ``b`` multiplies as it is."""
    f32 = jnp.float32
    if b.dtype != jnp.bfloat16:
        return jax.lax.dot_general(a, b.astype(f32), dims,
                                   preferred_element_type=f32)
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(f32)).astype(jnp.bfloat16)
    # one pass, named so: the caller may trace under
    # default_matmul_precision("highest")
    both = jax.lax.dot_general(
        jnp.concatenate([hi, lo], axis=0), b, dims,
        preferred_element_type=f32, precision=jax.lax.Precision.DEFAULT)
    return both[:a.shape[0]] + both[a.shape[0]:]


def flat_work_list(first, count, n_blocks: int):
    """The grid of a kernel over ragged rows, flat: row ``r`` visits the
    ``count[r] >= 1`` key blocks from ``first[r]`` on.  Returns ``(rows
    [W], blocks [W], n)`` int32, ``W = R n_blocks + 1`` static, the
    first ``n`` entries live (``n`` a device scalar: the kernel's dynamic
    grid bound) and every other a valid (row, block) too: the chip's
    pipeline evaluates the index maps of step ``n`` while it runs step
    ``n - 1``, so a list of exactly ``n`` entries is read one past its
    end where every row visits every block (the core halted there, my
    chip run, PR 37).  Rows in order, so a row's first step is the one
    whose predecessor names another row (:func:`first_of_row`).  What no row
    visits is neither fetched nor stepped through (a step ``pl.when``
    skips costs 0.5 us on a v5e, PERF.md section 6, PR 35).
    ``sparse_attend`` and ``ops.latent_attention`` both grid so."""
    i32 = jnp.int32
    r = count.shape[0]
    end = jnp.cumsum(count)
    w = jnp.arange(r * n_blocks + 1, dtype=i32)
    rows = jnp.minimum((w[:, None] >= end[None, :]).sum(axis=1), r - 1)
    blocks = first[rows] + w - (end - count)[rows]
    return (rows.astype(i32),
            jnp.clip(blocks, 0, n_blocks - 1).astype(i32),
            end[-1].astype(i32))


def first_of_row(wrow_ref, w):
    """Whether step ``w`` of a :func:`flat_work_list` grid opens its row's
    run (the kernel resets the row's running softmax there)."""
    return (w == 0) | (wrow_ref[jnp.maximum(w - 1, 0)] != wrow_ref[w])


def live_pages(tables, block_ids, lengths, page_tokens: int):
    """``[N]`` int32: how far into its table each row must be read, 1 +
    the index of its last entry that names a page with a key under the
    row's length (0: the row sees nothing).  Selected tables are
    best-first with -1 at the tail and dense tables in block order, so
    this is the count of such entries."""
    seen = (tables >= 0) & (block_ids * page_tokens < lengths[:, None])
    return (seen * jnp.arange(1, tables.shape[1] + 1,
                              dtype=jnp.int32)).max(axis=1)


def steps_visited(pages):
    """Grid steps ``sparse_attend`` takes for rows that see ``pages``
    pages each (numpy, on the host, as :func:`flat_work_list` lays them
    out): a row with none is visited once (its zero output is written)."""
    return int(np.maximum(-(-np.asarray(pages) // PAGES_PER_STEP), 1).sum())


def _sparse_kernel(tab_ref, blk_ref, len_ref, hd_ref, wrow_ref, wblk_ref,
                   n_ref, q_ref, kv_ref, o_ref, m_ref, l_ref, k_buf, v_buf,
                   sem, *, layer: int, scale: float):
    """One entry of the work list: ``PAGES_PER_STEP`` pages of row
    ``wrow[w]`` as ONE key tile and one value tile.  ``kv_ref`` is the
    whole arena where it lies (HBM); the step's pages are copied by hand,
    one DMA a page and tensor, straight into ``k_buf`` / ``v_buf [2, pps,
    T, D]`` (two slots: step ``w + 1``'s copies start before step ``w``
    computes).  As block operands of the grid the same 16 fetches cost
    0.93 us a step in the pipeline's own bookkeeping, twice what the
    step computes (PERF.md section 6, PR 37)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _, pps, t, d = k_buf.shape
    n_pages = kv_ref.shape[3]
    w = pl.program_id(0)
    slot = jax.lax.rem(w, 2)

    def copies(step, slot):
        r = wrow_ref[step]
        e0, head = wblk_ref[step] * pps, hd_ref[r]
        for i in range(pps):
            # an entry that names no page fetches page 0 and is masked
            at = jnp.clip(tab_ref[r, e0 + i], 0, n_pages - 1)
            for which, buf in ((0, k_buf), (1, v_buf)):
                yield pltpu.make_async_copy(
                    kv_ref.at[layer, which, head, at], buf.at[slot, i],
                    sem.at[slot])

    @pl.when(w == 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    @pl.when(w + 1 < n_ref[0])
    def _next():
        for c in copies(w + 1, 1 - slot):
            c.start()

    @pl.when(first_of_row(wrow_ref, w))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    # each key's position from its page's logical block (lane ``j`` of
    # page ``i`` is key ``block_i T + j - i T``); an entry that names no
    # page lies behind every length
    r = wrow_ref[w]
    e0 = wblk_ref[w] * pps
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, pps * t), 1)
    off = jnp.zeros_like(lane)
    for i in range(pps):
        at = jnp.where(tab_ref[r, e0 + i] >= 0, blk_ref[r, e0 + i] * t,
                       jnp.iinfo(jnp.int32).max // 2)
        off = jnp.where(lane >= i * t, at - i * t, off)
    valid = off + lane < len_ref[r]                          # [1, pps T]
    for c in copies(w, slot):
        c.wait()
    k = k_buf[slot].reshape(pps * t, d)
    v = v_buf[slot].reshape(pps * t, d)
    s = _dot_split(q_ref[...] * scale, k, (((1,), (1,)), ((), ())))
    s = s + jnp.where(valid, 0.0, -jnp.inf)                  # [G, pps T]
    m_prev = m_ref[...]                                      # [G, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    alpha = jnp.where(jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - m_safe))
    p = jnp.exp(s - m_safe)                                  # masked: 0
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    o_ref[...] = o_ref[...] * alpha + _dot_split(
        p, v, (((1,), (0,)), ((), ())))


def sparse_attend_pallas(q, kv, layer: int, heads, tables, block_ids,
                         lengths, extra_k=None, extra_v=None, *,
                         interpret: Optional[bool] = None):
    """The kernel: a grid step takes ``PAGES_PER_STEP`` entries of a
    row's table as one key tile, and the grid is the flat list of the
    steps that hold a page some key of which the row sees
    (:func:`flat_work_list`): a row whose last such entry is its ``n``-th
    takes ``max(1, ceil(n / PAGES_PER_STEP))`` steps."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, g, d = q.shape
    t = kv.shape[4]
    pps = PAGES_PER_STEP
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = 1.0 / math.sqrt(d)
    f32, i32 = jnp.float32, jnp.int32
    qf = q.astype(f32)
    tables, block_ids = tables.astype(i32), block_ids.astype(i32)
    lengths = lengths.astype(i32)
    if tables.shape[1] % pps:         # the table in whole steps of pages
        pad = ((0, 0), (0, -tables.shape[1] % pps))
        tables = jnp.pad(tables, pad, constant_values=-1)
        block_ids = jnp.pad(block_ids, pad, constant_values=-1)
    live = live_pages(tables, block_ids, lengths, t)
    rows, blocks, n_steps = flat_work_list(
        jnp.zeros((n,), i32), jnp.maximum(-(-live // pps), 1),
        tables.shape[1] // pps)

    def row3(w, tab, blk, ln, hd, wrow, wblk, n_):
        return (wrow[w], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7, grid=(n_steps,),
        in_specs=[pl.BlockSpec((None, g, d), row3),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[pl.BlockSpec((None, g, d), row3),
                   pl.BlockSpec((None, g, 1), row3),
                   pl.BlockSpec((None, g, 1), row3)],
        scratch_shapes=[pltpu.VMEM((2, pps, t, d), kv.dtype),
                        pltpu.VMEM((2, pps, t, d), kv.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    o, m, l = pl.pallas_call(
        functools.partial(_sparse_kernel, layer=layer, scale=scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, g, d), f32),
                   jax.ShapeDtypeStruct((n, g, 1), f32),
                   jax.ShapeDtypeStruct((n, g, 1), f32)],
        interpret=interpret, name="sparse_attend",
    )(tables, block_ids, lengths, heads.astype(i32), rows, blocks,
      n_steps.reshape(1), qf, kv)
    return _finish(o, m[..., 0], l[..., 0], qf, extra_k, extra_v, scale)


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

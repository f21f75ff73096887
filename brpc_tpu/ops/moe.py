"""Routed experts on the serving path (ISSUE 34): the router of
``noaux_tc`` as GLM-4.7-Flash and Nemotron 3 publish it, and the
experts' bodies over the assignments sorted by expert, with no capacity
and no dropped token.  An expert's body is one of two: the silu gated
MLP of three matrices, or (ISSUE 40) two matrices around ``relu(.)^2``;
either in whatever width it is handed (the model's, or a latent width
the caller projects down to and up from).

  :func:`route`       ``s = sigmoid(x W_g)`` in float32; the top ``k`` of
                      ``s + b`` are CHOSEN (``b``: the per-expert
                      correction bias, selection only); the weights are
                      the chosen ``s`` (without ``b``), divided by their
                      sum where the family normalises, times the routed
                      scaling factor
  :func:`expert_ffn`  ``sum_i w_i E_i(x)`` over the experts HELD here
                      (``held = (first, count)`` of the router's
                      ``n_experts``: this chip's share; what the other
                      experts add is theirs to compute).  Of 64 slots x
                      22 choices over 512 experts with 128 held, a
                      quarter of the sorted rows are groups and the rest
                      sort behind the last.

Which body takes which path (ISSUE 41).  The GATED body is three
``lax.ragged_dot`` calls over the stacked weights ``[E, K, N]``: on a TPU
one Mosaic kernel of the compiler's an operand, which reads only the
experts that were hit, measured at 82 % of the matrices' HBM time at
GLM's shapes (64 sorted rows, 2,048 x 1,536; ledger, PR 40) and so left
as it is.  The same call ran at 36 % at Nemotron's (1,408 sorted rows in
groups of ~2.5, 1,024 x 2,688: 21 us an expert a product where the bytes
take 6.7), so the TWO-MATRIX body is, on a TPU, the ``expert_ffn`` Pallas
kernel below (:func:`expert_ffn_pallas`): a flat work list of (expert
hit, row tile) made from the group sizes, each hit expert's two matrices
copied whole into VMEM once by the kernel's own double-buffered copies
(the next expert's in flight while this one multiplies), ``relu(.)^2``
and its one rounding to bfloat16 in VMEM between the two products, the
result rows written once.  Off a TPU (``backend="gather"``) both bodies
are ``lax.ragged_dot``: the kernel's oracle.

The training-side layer with a capacity and an exchange across chips is
``brpc_tpu.models.moe`` (top-1, ``shard_map``); nothing on the serving
path calls it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from brpc_tpu.ops.lightning import _round
from brpc_tpu.ops.paged_attention import default_backend

__all__ = ["route", "expert_ffn", "expert_ffn_pallas", "work_list",
           "tile_visits", "ROW_TILE"]

# rows of one grid step of the ``expert_ffn`` kernel: a decode step's
# groups are 1-6 rows, a 512-position chunk's ~23, and a group that
# straddles two tiles is visited twice.  The copies bound the kernel, so
# the tile hardly matters: 16, 32, 64 and 128 read within 1 % of each
# other at a decode step's rows and 16 is 4 % behind at the largest
# chunk's (PERF.md section 6, PR 41)
ROW_TILE = 32


def route(s, bias, k: int, *, norm: bool, scale: float):
    """``s [N, E]`` float32 scores (after the sigmoid), ``bias [E]``:
    ``(experts [N, k] int32, weights [N, k] float32)``."""
    _, idx = jax.lax.top_k(s + bias[None, :], k)
    w = jnp.take_along_axis(s, idx, axis=1)
    if norm:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def tile_visits(sizes, tile: int = ROW_TILE):
    """``[count]`` int32: the row tiles each group of the sorted rows
    lies in (0 of an expert nobody chose): the kernel's visits."""
    end = jnp.cumsum(sizes)
    return jnp.where(sizes > 0,
                     (end - 1) // tile - (end - sizes) // tile + 1,
                     0).astype(jnp.int32)


def work_list(sizes, n_rows: int, tile: int = ROW_TILE):
    """The grid of the ``expert_ffn`` kernel: one item a (held expert
    HIT, row tile its group lies in), experts in order and so tiles in
    order.  ``sizes [count]`` the group sizes of the sorted rows.

    Returns ``(items [6, W] int32, n)``: of item ``w`` the expert
    ``items[0, w]``, the row tile ``[1]``, the sorted rows ``[2] .. [3]``
    (first, one past the last) that are the expert's in that tile, the
    expert's ordinal among those hit ``[4]`` and the next expert hit
    ``[5]`` (-1: none).  ``W`` is static, the worst case of the shape and
    one more (the chip's pipeline evaluates the index maps of step ``n``
    while it runs step ``n - 1``); ``n`` (a device scalar, the dynamic
    grid bound) items are live, at least one: where nothing held was hit
    the one item has no row.  The tail repeats the last live item, so
    what reads past ``n`` names the blocks already there."""
    i32 = jnp.int32
    count = sizes.shape[0]
    sizes = sizes.astype(i32)
    n_tiles = -(-n_rows // tile)
    end = jnp.cumsum(sizes)
    start = end - sizes
    visits = tile_visits(sizes, tile)
    vend = jnp.cumsum(visits)
    n = jnp.maximum(vend[-1], 1)
    w = jnp.minimum(jnp.arange(n_tiles + count + 1, dtype=i32), n - 1)
    e = jnp.minimum((w[:, None] >= vend[None, :]).sum(axis=1), count - 1)
    t = jnp.clip(start[e] // tile + w - (vend - visits)[e], 0, n_tiles - 1)
    hit = sizes > 0
    ordinal = jnp.cumsum(hit) - 1
    ids = jnp.nonzero(hit, size=count + 1, fill_value=-1)[0]
    items = jnp.stack([
        e, t, jnp.maximum(start[e], t * tile),
        jnp.minimum(end[e], (t + 1) * tile), ordinal[e],
        ids[jnp.clip(ordinal[e] + 1, 0, count)]]).astype(i32)
    return items, n.astype(i32)


def _panel(f: int) -> int:
    """Columns of ``hidden`` a pass of the kernel's body makes and
    multiplies: the widest divisor of ``f`` in whole lane tiles up to
    1,024 (2,688 = 3 x 896), or all of a width that has none."""
    wide = [p for p in range(128, 1025, 128) if f % p == 0]
    return max(wide) if wide else f


def _ffn_kernel(items, x_ref, u_hbm, v_hbm, o_ref, u_buf, v_buf, sem, *,
                round_acc, interpret: bool):
    """One item of the work list: the rows of tile ``items[1, w]``
    through expert ``items[0, w]``'s two matrices, the expert's own rows
    kept.  ``u_hbm`` / ``v_hbm`` are the stacked matrices where they lie
    (HBM); an expert's pair is copied whole into slot ``ordinal % 2`` of
    ``u_buf`` / ``v_buf`` at the first item that names it, by the copy
    the expert before it started: its wait is the kernel's one stall,
    and the next expert's copies start as it ends."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    w = pl.program_id(0)
    tile = x_ref.shape[0]
    f = u_buf.shape[2]
    panel = _panel(f)
    e, lo, hi = items[0, w], items[2, w], items[3, w]
    ordinal, nxt = items[4, w], items[5, w]
    slot = jax.lax.rem(ordinal, 2)
    first = (w == 0) | (items[4, jnp.maximum(w - 1, 0)] != ordinal)
    one_pass = dict(preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.DEFAULT
                    if u_buf.dtype == jnp.bfloat16
                    else jax.lax.Precision.HIGHEST)

    def rounded(x, to_dtype=None):
        # Mosaic lowers a cast as written; XLA (interpret mode) may drop
        # one as excess precision, not ``reduce_precision``
        x = x.astype(jnp.bfloat16).astype(jnp.float32) if not interpret \
            else _round(x, "bfloat16")
        return x if to_dtype is None else x.astype(to_dtype)

    def copies(expert, slot):
        return [pltpu.make_async_copy(src.at[expert], buf.at[slot],
                                      sem.at[i, slot])
                for i, (src, buf) in enumerate(((u_hbm, u_buf),
                                                (v_hbm, v_buf)))]

    @pl.when(hi > lo)       # not the one item of an empty list
    def _item():
        @pl.when(w == 0)
        def _():
            for c in copies(e, slot):
                c.start()

        @pl.when(first)
        def _():
            for c in copies(e, slot):
                c.wait()

        @pl.when(first & (nxt >= 0))
        def _():
            for c in copies(nxt, 1 - slot):
                c.start()

        x = x_ref[...]
        y = jnp.zeros(o_ref.shape, jnp.float32)
        for p in range(0, f, panel):
            h = jnp.dot(x, u_buf[slot, :, p:p + panel], **one_pass)
            if round_acc is not None:
                h = rounded(h)
            h = jnp.square(jnp.maximum(h, 0.0))
            if u_buf.dtype == jnp.bfloat16:
                h = rounded(h, jnp.bfloat16)
            y = y + jnp.dot(h, v_buf[slot, p:p + panel, :], **one_pass)
        rows = items[1, w] * tile + jax.lax.broadcasted_iota(
            jnp.int32, (tile, 1), 0)
        o_ref[...] = jnp.where((rows >= lo) & (rows < hi), y, o_ref[...])


def expert_ffn_pallas(xs, w_up, w_down, sizes, *, round_acc=None,
                      interpret: Optional[bool] = None):
    """``w_down relu(w_up xs)^2`` of the sorted rows ``xs [M, K]`` in
    groups of ``sizes [count]`` against ``w_up [count, K, F]``, ``w_down
    [count, F, K]``: ``[M, K]`` float32.  Rows past the last group come
    back as whatever they were.  bfloat16 matrices take ``xs`` at
    bfloat16 and one MXU pass with float32 sums, ``hidden`` rounded to
    bfloat16 once; float32 ones multiply at ``highest``.  ``round_acc``
    (``"bfloat16"``, the low-precision control) rounds each product's
    float32 sums."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    m, k = xs.shape
    f = w_up.shape[2]
    tile = ROW_TILE
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if w_up.dtype == jnp.bfloat16:
        xs = _round(xs, "bfloat16").astype(jnp.bfloat16)
    if m % tile:
        xs = jnp.pad(xs, ((0, -m % tile), (0, 0)))
    items, n = work_list(sizes, xs.shape[0], tile)

    def rows(w, items):
        return (items[1, w], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(n,),
        in_specs=[pl.BlockSpec((tile, k), rows),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tile, k), rows),
        scratch_shapes=[pltpu.VMEM((2,) + w_up.shape[1:], w_up.dtype),
                        pltpu.VMEM((2,) + w_down.shape[1:], w_down.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))])
    # two experts' pairs of matrices (22 MB at 1,024 x 2,688 bfloat16)
    # and the row tiles: over the 16 MiB a kernel has by default
    resident = 4 * k * f * w_up.dtype.itemsize + 4 * tile * k * 4
    ys = pl.pallas_call(
        functools.partial(_ffn_kernel, round_acc=round_acc,
                          interpret=interpret),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(xs.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=resident + (16 << 20)),
        interpret=interpret, name="expert_ffn",
    )(items, xs, w_up, w_down)
    if round_acc is not None:
        ys = _round(ys, round_acc)
    return ys[:m]


def expert_ffn(x, experts, weights, valid, w_gate, w_up, w_down, *,
               held: tuple, mm, backend: Optional[str] = None,
               round_acc=None):
    """The held experts' share of the routed sum.

    ``x``        ``[N, dm]`` the tokens (float32)
    ``experts``  ``[N, k]`` each token's chosen experts, of the router's
                 whole range
    ``weights``  ``[N, k]`` their weights
    ``valid``    ``[N]`` bool: a row that is no token (an idle slot, a
                 bucket's padding) is routed nowhere
    ``w_gate``, ``w_up`` ``[count, dm, ff]``, ``w_down`` ``[count, ff,
                 dm]``: the held experts' matrices, ``dm`` the width of
                 ``x``; ``w_gate`` None is the second body,
                 ``w_down relu(w_up x)^2``
    ``held``     ``(first, count)``
    ``mm``       ``mm(lhs [M, K], rhs [count, K, N], group_sizes)``: the
                 ragged product at the caller's precision
    ``backend``  of the second body: ``"gather"`` (off a TPU) two calls
                 of ``mm``; ``"pallas"`` (on one) the ``expert_ffn``
                 kernel, ``"mosaic"`` the same compiled for the chip
                 whatever the default backend is; ``round_acc`` what
                 ``mm`` does to a float32 sum under the low-precision
                 control (``"bfloat16"``), for the kernel to do the same
    Returns ``(y [N, dm] float32, group sizes [count] int32)``."""
    if backend is None:
        backend = default_backend()
    n, k = experts.shape
    first, count = held
    e = experts.reshape(-1) - first
    mine = (e >= 0) & (e < count) & jnp.repeat(valid, k)
    key = jnp.where(mine, e, count)          # what is not ours sorts last
    with jax.named_scope("ops.expert_ffn"):
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
        xs = x[order // k]                                   # [N * k, dm]
        if w_gate is None and backend != "gather":
            ys = expert_ffn_pallas(
                xs, w_up, w_down, sizes, round_acc=round_acc,
                interpret=False if backend == "mosaic" else None)
        else:
            if w_gate is None:
                hidden = jnp.square(jax.nn.relu(mm(xs, w_up, sizes)))
            else:
                hidden = jax.nn.silu(mm(xs, w_gate, sizes)) \
                    * mm(xs, w_up, sizes)
            ys = mm(hidden, w_down, sizes)
        # rows past the last group are no product of anything: masked,
        # not multiplied (they may hold anything)
        w_sorted = weights.reshape(-1)[order]
        ys = jnp.where(mine[order][:, None], ys * w_sorted[:, None], 0.0)
        y = ys[jnp.argsort(order)].reshape(n, k, -1).sum(axis=1)
    return y, sizes

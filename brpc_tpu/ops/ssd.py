"""The Mamba-2 (state-space dual, SSD) mixer's recurrences (ISSUE 40): a
scalar decay a head over a MATRIX state, ``B`` and ``C`` shared by a
group of heads, and the causal depthwise convolution with its carried
tail over the mixer's convolved channels.

Per token ``t`` and head ``h`` of ``H`` (``P`` values a head, ``N`` state
values, group ``g = h // (H / G)``; Nemotron 3 Super: 128 heads of 64,
``N`` 128, 8 groups, 10,240 convolved channels, 4 taps):

    c_t    = silu(b + sum_{j<K} w[j] * xBC_{t-K+1+j})            (conv)
    a_t^h  = exp(-delta_t^h exp(A_log^h))                 a scalar a head
    S_t^h  = a_t^h S_{t-1}^h + delta_t^h xs_t^h (x) B_t^g      S: [P, N]
    y_t^h  = S_t^h C_t^g

(``D xs``, the gate and the group norm are the caller's: elementwise.)

What a sequence keeps between calls is ONE block a layer of the cache's
state row, ``[state rows + tail rows, 128]`` float32, one lane tile
wide:

  * rows ``0 .. (H / hpt) N - 1``: the scan state in tiles of ``hpt = 128
    / P`` heads, tile ``i`` rows ``i N .. (i + 1) N - 1``: row ``n``,
    lane ``(h % hpt) P + p`` holds ``S^h[p, n]``.  So every per-channel
    operand (the decay, ``delta xs``, ``y``) is lane-dense as the
    projections make it, ``B`` and ``C`` are the one column a group, and
    the products with ``C`` and ``B^T`` contract over rows: plain
    ``[.., N] x [N, 128]`` matmuls (:func:`pack_state` /
    :func:`unpack_state` are the layout, for tests and the fallback)
  * then the convolution's tail: its last ``K - 1`` inputs, oldest
    first, each ``channels / 128`` rows, padded with zero rows to a
    block that divides the state rows (:func:`tail_block_rows`), so
    that a kernel names it by a block index.

``ops.mamba``'s ``conv_step`` is not reused: its tail is ``K - 1`` ROWS
of a ``[N + 8, channels]`` block, channels minor; a matrix state a head
does not fit rows of 10,240 (128 x 64 x 128 is no whole number of them),
so this row is 128 wide and a tap is 80 rows of it.  ``conv_chunk`` (plain
jax over ``[K - 1, channels]``) is reused as it is.

Two forms of each recurrence, as ``ops.mamba`` and ``ops.lightning``
have them:

  * a prefill CHUNK of one sequence: :func:`ssd_scan`, on a TPU the
    ``ssd_scan`` Pallas kernel in the chunked (dual) form: inside a
    chunk of ``chunk`` positions the decay-masked ``(C B^T)`` product
    against ``delta xs``, across chunks the carried state of one group
    in VMEM, first state in, last state out; float32 products (the
    reference's recurrence has no rounding to state).  Positions ``>=
    n_valid`` (a bucket's padding) neither decay the state nor add to
    it, and whole padded chunks are not computed.
  * ONE position for every decode slot, the slots' rows of the
    persistent ``[R, L, rows, 128]`` array updated IN PLACE:
    :func:`conv_step` and :func:`ssd_step`, on a TPU the ``ssd_conv`` and
    ``ssd_step`` Pallas kernels (the array aliased to the output, each
    grid step reads and writes the one block its slot's row names
    through scalar prefetch); elsewhere a gather / scatter in plain jax.

The state and the tail are float32 always; ``round_state``
("bfloat16") is the benchmark's low-precision CONTROL only.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from brpc_tpu.ops.lightning import _round
from brpc_tpu.ops.mamba import _interpret, _silu
from brpc_tpu.ops.paged_attention import default_backend

__all__ = ["LANES", "state_rows", "tail_rows", "tail_block_rows",
           "state_block_rows", "pack_state", "unpack_state", "conv_step",
           "ssd_scan", "ssd_step", "default_backend"]

LANES = 128
_HI = jax.lax.Precision.HIGHEST


# ---- the layout -------------------------------------------------------------

def _hpt(head_dim: int) -> int:
    if LANES % head_dim:
        raise ValueError(f"a lane tile holds whole heads: head_dim "
                         f"{head_dim} does not divide {LANES}")
    return LANES // head_dim


def state_rows(heads: int, head_dim: int, d_state: int) -> int:
    """Rows of one layer's scan state."""
    hpt = _hpt(head_dim)
    if heads % hpt or d_state % 8:
        raise ValueError(f"the scan state is whole tiles of {hpt} heads "
                         f"and whole 8-row tiles of state: {heads} heads, "
                         f"d_state {d_state}")
    return heads // hpt * d_state


def tail_rows(channels: int, taps: int) -> int:
    if channels % LANES or taps < 2:
        raise ValueError(f"the tail is whole lane tiles of {taps} - 1 "
                         f"inputs: {channels} convolved channels")
    return (taps - 1) * channels // LANES


def tail_block_rows(n_state: int, n_tail: int) -> int:
    """The tail's block: the fewest rows, whole 8-row tiles, that hold
    it and divide the state's rows before it."""
    for t in range(-(-n_tail // 8) * 8, n_state + 1, 8):
        if n_state % t == 0:
            return t
    raise ValueError(f"no block of {n_tail} tail rows divides {n_state} "
                     f"state rows")


def state_block_rows(heads: int, head_dim: int, groups: int, d_state: int,
                     taps: int) -> int:
    """Rows of one layer's block of a state row."""
    hpt = _hpt(head_dim)
    if heads % groups or (heads // groups) % hpt:
        raise ValueError(f"a tile's heads share one group: {heads} heads, "
                         f"{groups} groups, {hpt} heads a tile")
    n = state_rows(heads, head_dim, d_state)
    channels = heads * head_dim + 2 * groups * d_state
    return n + tail_block_rows(n, tail_rows(channels, taps))


def pack_state(s):
    """``[.., H, P, N]`` -> ``[.., (H / hpt) N, 128]`` (module
    docstring)."""
    *lead, h, p, n = s.shape
    hpt = _hpt(p)
    k = len(lead)
    s = s.reshape(*lead, h // hpt, hpt, p, n)
    s = jnp.transpose(s, (*range(k), k, k + 3, k + 1, k + 2))
    return s.reshape(*lead, h // hpt * n, LANES)


def unpack_state(rows, head_dim: int, d_state: int):
    """``[.., (H / hpt) N, 128]`` -> ``[.., H, P, N]``."""
    *lead, r, _ = rows.shape
    hpt = _hpt(head_dim)
    k = len(lead)
    s = rows.reshape(*lead, r // d_state, d_state, hpt, head_dim)
    s = jnp.transpose(s, (*range(k), k, k + 2, k + 3, k + 1))
    return s.reshape(*lead, r // d_state * hpt, head_dim, d_state)


def _per_channel(x, head_dim: int):
    """``[.., H]`` a head -> ``[.., H P]`` a channel."""
    return jnp.repeat(x, head_dim, axis=-1)


# ---- the convolution's slot update ------------------------------------------

def _conv_step_kernel(rows_ref, x_ref, w_ref, b_ref, t_ref, xc_ref, o_ref, *,
                      k: int, r: int, round_state):
    x = x_ref[...]                                           # [r, 128]
    acc = b_ref[...] + w_ref[k - 1] * x
    for j in range(k - 1):
        acc = acc + w_ref[j] * t_ref[j * r:(j + 1) * r, :]
    xc_ref[...] = _silu(acc)
    o_ref[...] = jnp.zeros_like(o_ref)
    for j in range(k - 2):
        o_ref[j * r:(j + 1) * r, :] = t_ref[(j + 1) * r:(j + 2) * r, :]
    o_ref[(k - 2) * r:(k - 1) * r, :] = _round(x, round_state,
                                               in_kernel=True)


def _conv_step_pallas(state, rows, layer: int, xs, w, b, *, n_state: int,
                      round_state, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    s, ch = xs.shape
    k = w.shape[0]
    r = ch // LANES
    tb = state.shape[2] - n_state
    f32 = jnp.float32

    def at_slot(i, rows):
        return (i, 0, 0)

    def at_tail(i, rows):
        return (rows[i], layer, n_state // tb, 0)
    tail = pl.BlockSpec((None, None, tb, LANES), at_tail)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(s,),
        in_specs=[pl.BlockSpec((None, r, LANES), at_slot),
                  pl.BlockSpec((k, r, LANES), lambda i, rows: (0, 0, 0)),
                  pl.BlockSpec((r, LANES), lambda i, rows: (0, 0)), tail],
        out_specs=[pl.BlockSpec((None, r, LANES), at_slot), tail])
    xc, state = pl.pallas_call(
        functools.partial(_conv_step_kernel, k=k, r=r,
                          round_state=round_state),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, r, LANES), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: rows, xs, w, b, state -> outputs xc, state
        input_output_aliases={4: 1}, interpret=interpret, name="ssd_conv",
    )(rows.astype(jnp.int32), xs.astype(f32).reshape(s, r, LANES),
      w.astype(f32).reshape(k, r, LANES), b.astype(f32).reshape(r, LANES),
      state)
    return xc.reshape(s, ch), state


def conv_step(state, rows, layer: int, xs, w, b, *, n_state: int,
              round_state=None, backend: Optional[str] = None):
    """One position for each of S slots: ``state [R, L, rows, 128]``
    (donate it), ``rows [S]`` the slots' state rows (idle slots name a
    scratch row), ``xs [S, ch]`` the inputs, ``w [K, ch]``, ``b [ch]``,
    ``n_state`` the scan state's rows ahead of the tail.  Returns ``(xc
    [S, ch], state)`` with every named row's tail moved on by its
    input."""
    if backend is None:
        backend = default_backend()
    k, ch = w.shape
    with jax.named_scope("ops.ssd_conv"):
        if backend in ("pallas", "mosaic"):
            return _conv_step_pallas(
                state, rows, layer, xs, w, b, n_state=n_state,
                round_state=round_state, interpret=_interpret(backend))
        n_tail = tail_rows(ch, k)
        s = xs.shape[0]
        tail = state[rows, layer, n_state:n_state + n_tail].reshape(
            s, k - 1, ch)
        xs = xs.astype(jnp.float32)
        acc = b[None, :] + w[k - 1][None, :] * xs
        for j in range(k - 1):
            acc = acc + w[j][None, :] * tail[:, j]
        new = jnp.concatenate(
            [tail[:, 1:], _round(xs, round_state)[:, None]], axis=1)
        return _silu(acc), state.at[
            rows, layer, n_state:n_state + n_tail].set(
                new.reshape(s, n_tail, LANES))


# ---- the recurrence's slot update -------------------------------------------

def _column(row):
    """``[1, N]`` (lanes) -> ``[N, 1]`` (rows): the diagonal of its
    broadcast, summed over lanes."""
    n = row.shape[-1]
    eye = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _step_kernel(rows_ref, a_ref, dx_ref, b_ref, c_ref, s_ref, y_ref, o_ref,
                 *, n: int, round_state):
    bcol, ccol = _column(b_ref[...]), _column(c_ref[...])
    for t in range(a_ref.shape[0]):
        tile = slice(t * n, (t + 1) * n)
        s = a_ref[t:t + 1, :] * s_ref[tile, :] + bcol * dx_ref[t:t + 1, :]
        s = _round(s, round_state, in_kernel=True)
        o_ref[tile, :] = s
        y_ref[t:t + 1, :] = jnp.sum(s * ccol, axis=0, keepdims=True)


def _step_pallas(state, rows, layer: int, a, dx, bmat, cmat, *, groups: int,
                 round_state, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    s, di = dx.shape
    n = bmat.shape[-1] // groups
    tiles = di // LANES // groups            # a group's tiles of heads
    f32 = jnp.float32

    def at_slot(i, g, rows):
        return (i, g, 0, 0)

    def at_state(i, g, rows):
        return (rows[i], layer, g, 0)
    chan = pl.BlockSpec((None, None, tiles, LANES), at_slot)
    vec = pl.BlockSpec((None, None, 1, n), at_slot)
    block = pl.BlockSpec((None, None, tiles * n, LANES), at_state)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(s, groups),
        in_specs=[chan, chan, vec, vec, block], out_specs=[chan, block])
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, n=n, round_state=round_state),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, groups, tiles, LANES), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: rows, a, dx, B, C, state -> outputs y, state
        input_output_aliases={5: 1}, interpret=interpret, name="ssd_step",
    )(rows.astype(jnp.int32), a.reshape(s, groups, tiles, LANES),
      dx.reshape(s, groups, tiles, LANES),
      bmat.astype(f32).reshape(s, groups, 1, n),
      cmat.astype(f32).reshape(s, groups, 1, n), state)
    return y.reshape(s, di), state


def ssd_step(state, rows, layer: int, xs, delta, bmat, cmat, a_log, *,
             groups: int, round_state=None, backend: Optional[str] = None):
    """One position for each of S slots: ``state [R, L, rows, 128]``
    (donate it), ``rows [S]``, ``xs [S, H P]``, ``delta [S, H]``,
    ``bmat``/``cmat`` ``[S, G N]``, ``a_log [H]``.  Returns ``(y [S, H
    P], state)``: every named row's scan state read once and written
    once."""
    if backend is None:
        backend = default_backend()
    f32 = jnp.float32
    s, di = xs.shape
    h = a_log.shape[0]
    p, n = di // h, bmat.shape[-1] // groups
    delta = delta.astype(f32)
    a = jnp.exp(-delta * jnp.exp(a_log.astype(f32))[None, :])     # [S, H]
    dx = xs.astype(f32) * _per_channel(delta, p)
    with jax.named_scope("ops.ssd_step"):
        if backend in ("pallas", "mosaic"):
            return _step_pallas(
                state, rows, layer, _per_channel(a, p), dx, bmat, cmat,
                groups=groups, round_state=round_state,
                interpret=_interpret(backend))
        n_state = state_rows(h, p, n)
        old = unpack_state(state[rows, layer, :n_state], p, n)  # [S,H,P,N]
        per_head = h // groups
        bh = jnp.repeat(bmat.astype(f32).reshape(s, groups, n), per_head, 1)
        ch = jnp.repeat(cmat.astype(f32).reshape(s, groups, n), per_head, 1)
        new = a[:, :, None, None] * old \
            + dx.reshape(s, h, p)[..., None] * bh[:, :, None, :]
        new = _round(new, round_state)
        y = jnp.einsum("shpn,shn->shp", new, ch, precision="highest")
        return y.reshape(s, di), state.at[rows, layer, :n_state].set(
            pack_state(new))


# ---- the recurrence over a prefill chunk ------------------------------------

def _scan_kernel(nv_ref, dx_ref, lc_ref, lr_ref, bt_ref, c_ref, h0_ref,
                 y_ref, hn_ref, s_scr, *, q: int, n: int, p: int,
                 round_state):
    from jax.experimental import pallas as pl
    t = pl.program_id(1)
    f32 = jnp.float32
    hpt = LANES // p

    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=f32, precision=_HI)

    @pl.when(t == 0)
    def _first():
        s_scr[...] = h0_ref[...]

    @pl.when(t * q < nv_ref[0])
    def _run():
        cm, bt = c_ref[...], bt_ref[...]                 # [q, N], [N, q]
        cb = dot(cm, bt)                                 # [q, q]
        tri = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) \
            >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
        lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) // p
        for i in range(s_scr.shape[0] // n):             # a tile of heads
            tile = slice(i * n, (i + 1) * n)
            lanes = slice(i * LANES, (i + 1) * LANES)
            dx = dx_ref[:, lanes]                        # [q, 128]
            s0 = s_scr[tile, :]                          # [N, 128]
            y = jnp.zeros((q, LANES), f32)
            from_before = jnp.zeros((q, LANES), f32)
            to_end = jnp.zeros((q, LANES), f32)
            keep = jnp.zeros((1, LANES), f32)
            for k in range(hpt):
                h = i * hpt + k
                mine = lane_head == k                    # [1, 128]
                lc = lc_ref[:, h:h + 1]                  # [q, 1]
                lr = lr_ref[h:h + 1, :]                  # [1, q]
                end = lc_ref[q - 1:q, h:h + 1]           # [1, 1]
                # inside the chunk: sum_{s<=t} exp(L_t - L_s) (C_t.B_s)
                # (delta xs)_s
                decay = jnp.where(tri, jnp.exp(jnp.minimum(lc - lr, 0.0)),
                                  0.0)
                y = y + dot(cb * decay, jnp.where(mine, dx, 0.0))
                from_before = jnp.where(mine, jnp.exp(lc), from_before)
                to_end = jnp.where(mine, jnp.exp(end - lc), to_end)
                keep = jnp.where(mine, jnp.exp(end), keep)
            # from before the chunk: exp(L_t) C_t S_prev
            y_ref[:, lanes] = y + from_before * dot(cm, s0)
            s_scr[tile, :] = _round(keep * s0 + dot(bt, to_end * dx),
                                    round_state, in_kernel=True)

    @pl.when(t * q >= nv_ref[0])
    def _padding():
        # nothing of the bucket's padding is computed; its rows hold
        # zeros and not what the buffer held
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(t == pl.num_programs(1) - 1)
    def _last():
        hn_ref[...] = s_scr[...]


def _scan_pallas(dx, la, bmat, cmat, h0, n_valid, *, groups: int, p: int,
                 q: int, round_state, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    c, di = dx.shape
    h = la.shape[1]
    n = bmat.shape[-1] // groups
    hg = h // groups
    gw = di // groups                         # a group's channels
    nq = c // q
    f32 = jnp.float32
    # the cumulative log decay INCLUDING position t, inside its chunk,
    # as a column and as a row a head
    lc = jnp.cumsum(la.reshape(nq, q, groups, hg), axis=1)
    lc = lc.transpose(0, 2, 1, 3)                          # [nq, G, q, hg]
    lr = lc.transpose(0, 1, 3, 2)                          # [nq, G, hg, q]
    b4 = bmat.astype(f32).reshape(nq, q, groups, n)
    bt = b4.transpose(0, 2, 3, 1)                          # [nq, G, N, q]
    c4 = cmat.astype(f32).reshape(nq, q, groups, n).transpose(0, 2, 1, 3)

    def seq(g, t, nv):
        return (t, g)

    def per_gt(g, t, nv):
        return (t, g, 0, 0)

    def per_g(g, t, nv):
        return (g, 0, 0)
    rows_g = h0.shape[0] // groups
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(groups, nq),
        in_specs=[pl.BlockSpec((q, gw), seq),
                  pl.BlockSpec((None, None, q, hg), per_gt),
                  pl.BlockSpec((None, None, hg, q), per_gt),
                  pl.BlockSpec((None, None, n, q), per_gt),
                  pl.BlockSpec((None, None, q, n), per_gt),
                  pl.BlockSpec((None, rows_g, LANES), per_g)],
        out_specs=[pl.BlockSpec((q, gw), seq),
                   pl.BlockSpec((None, rows_g, LANES), per_g)],
        scratch_shapes=[pltpu.VMEM((rows_g, LANES), f32)])
    y, hn = pl.pallas_call(
        functools.partial(_scan_kernel, q=q, n=n, p=p,
                          round_state=round_state),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((c, di), f32),
                   jax.ShapeDtypeStruct((groups, rows_g, LANES), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name="ssd_scan",
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), dx, lc, lr, bt, c4,
      h0.astype(f32).reshape(groups, rows_g, LANES))
    return y, hn.reshape(h0.shape)


def ssd_scan(xs, delta, bmat, cmat, h0, a_log, n_valid, *, groups: int,
             chunk: int, round_state=None, backend: Optional[str] = None):
    """A chunk of C positions of ONE sequence: ``xs [C, H P]``, ``delta
    [C, H]``, ``bmat``/``cmat`` ``[C, G N]``, ``h0 [state rows, 128]``
    the packed state before it, ``a_log [H]``, ``n_valid`` how many
    leading positions are real, ``chunk`` the dual form's block
    (positions; ``C`` is whole blocks of it, or smaller).  Returns ``(y
    [C, H P], the packed state after the last valid position)``; ``y``
    of the padding is not to be read."""
    if backend is None:
        backend = default_backend()
    f32 = jnp.float32
    c, di = xs.shape
    h = a_log.shape[0]
    p, n = di // h, bmat.shape[-1] // groups
    q = min(chunk, c)
    if c % q:
        raise ValueError(f"a chunk of {c} positions is not whole blocks "
                         f"of {q}")
    valid = jnp.arange(c, dtype=jnp.int32) < n_valid
    # a padded position: a = exp(0) = 1 and delta xs = 0, S stays
    delta = jnp.where(valid[:, None], delta.astype(f32), 0.0)
    la = -delta * jnp.exp(a_log.astype(f32))[None, :]             # [C, H]
    dx = xs.astype(f32) * _per_channel(delta, p)
    with jax.named_scope("ops.ssd_scan"):
        if backend in ("pallas", "mosaic"):
            return _scan_pallas(
                dx, la, bmat, cmat, h0, n_valid, groups=groups, p=p, q=q,
                round_state=round_state, interpret=_interpret(backend))
        per_head = h // groups

        def one(s, xs):
            dx_t, la_t, b_t, c_t = xs
            bh = jnp.repeat(b_t.reshape(groups, n), per_head, 0)  # [H, N]
            ch = jnp.repeat(c_t.reshape(groups, n), per_head, 0)
            s = jnp.exp(la_t)[:, None, None] * s \
                + dx_t.reshape(h, p)[..., None] * bh[:, None, :]
            s = _round(s, round_state)
            return s, jnp.einsum("hpn,hn->hp", s, ch,
                                 precision="highest").reshape(di)
        s_end, y = jax.lax.scan(
            one, unpack_state(h0.astype(f32), p, n),
            (dx, la, bmat.astype(f32), cmat.astype(f32)))
    return y, pack_state(s_end)

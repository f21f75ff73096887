"""The Mamba-1 state-space mixer's two recurrences (ISSUE 38): a causal
depthwise convolution with a carried tail and the selective scan.

Per token ``t`` and channel ``c`` (``N`` state values a channel, ``K``
taps; Jamba: 5,120 channels, ``N`` 16, ``K`` 4):

    xc_t   = silu(b_c + sum_{j<K} w[j, c] * xs_{t-K+1+j})       (conv)
    h_t    = exp(delta_t A) * h_{t-1} + (delta_t xc_t) (x) B_t   (scan)
    y_t    = (h_t . C_t + D xc_t) * silu(z_t)

``A = -exp(a_log)`` ``[N, channels]``, ``delta`` the step size every
token computes, ``B_t``/``C_t`` ``[N]``.  Nothing here is a matmul: the
scan is elementwise over ``[N, channels]`` with a reduction over ``N``.

What a sequence keeps between calls is ONE block a layer of the
cache's state row, ``[N + TAIL_ROWS, channels]`` float32, channels
minor (whole 128-lane tiles): rows ``0 .. N-1`` the scan state ``h``,
rows ``N .. N+K-2`` the convolution's tail (the last ``K - 1`` inputs
``xs``, oldest first), the rest of the last 8-row tile zero.

Two forms of each recurrence, as ``ops.lightning`` has them:

  * a prefill CHUNK of one sequence: :func:`conv_chunk` (plain jax:
    shifted adds) and :func:`mamba_scan`, on a TPU the ``mamba_scan``
    Pallas kernel: the state of every channel block stays in VMEM
    across the chunk's time blocks, first state in, last state out.
    Positions ``>= n_valid`` (a bucket's padding) neither decay the
    state nor add to it, and leave the tail alone.
  * ONE position for every decode slot, the slots' rows of the
    persistent ``[R, L, N + TAIL_ROWS, channels]`` array updated IN
    PLACE: :func:`conv_step` and :func:`mamba_step`, on a TPU the
    ``mamba_conv`` and ``mamba_step`` Pallas kernels (the array aliased
    to the output, each grid step reads and writes the one block its
    slot's row names through scalar prefetch: never a gather XLA
    makes); elsewhere a gather / scatter in plain jax.

The state and the tail are float32 always; ``round_state``
("bfloat16") is the benchmark's low-precision CONTROL only.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from brpc_tpu.ops.lightning import _round
from brpc_tpu.ops.paged_attention import default_backend

__all__ = ["TAIL_ROWS", "LANES", "state_block_rows", "conv_chunk",
           "conv_step", "mamba_scan", "mamba_step", "lane_copies",
           "default_backend"]

TAIL_ROWS = 8         # the tail's share of a layer's block: one sublane tile
LANES = 128
SCAN_CHANNELS = 512   # channels a grid step of mamba_scan
SCAN_TIME = 128       # positions a grid step of mamba_scan


def state_block_rows(d_state: int, d_conv: int) -> int:
    """Rows of one layer's block of a state row."""
    if d_state % 8 or not 1 < d_conv <= TAIL_ROWS + 1:
        raise ValueError(
            f"a state block holds whole 8-row tiles of scan state and "
            f"up to {TAIL_ROWS} tail rows: d_state {d_state}, d_conv "
            f"{d_conv}")
    return d_state + TAIL_ROWS


def lane_copies(x):
    """``B`` or ``C`` ``[n, N]`` as the kernels take them: ``[n, N,
    128]``, each value across a whole lane tile (a kernel then
    multiplies it against channels with no broadcast across lanes)."""
    return jnp.broadcast_to(x.astype(jnp.float32)[..., None],
                            x.shape + (LANES,))


def _interpret(backend: str) -> bool:
    """"mosaic": the kernel compiled for the chip whatever the default
    backend is (a chip-less compile for a described TPU); "pallas"
    interprets it off the chip."""
    return backend == "pallas" and jax.default_backend() != "tpu"


def _silu(x):
    return x * jax.nn.sigmoid(x)


# ---- the convolution --------------------------------------------------------

def conv_chunk(xs, tail, w, b, n_valid, round_state=None):
    """A chunk of one sequence: ``xs [C, ch]`` float32, ``tail [K-1,
    ch]`` the inputs before it, ``w [K, ch]``, ``b [ch]``.  Returns
    ``(xc [C, ch], the tail after the chunk's first n_valid inputs)``."""
    k = w.shape[0]
    c = xs.shape[0]
    with jax.named_scope("ops.mamba_conv_chunk"):
        ext = jnp.concatenate([tail.astype(jnp.float32), xs], axis=0)
        acc = b[None, :].astype(jnp.float32)
        for j in range(k):
            acc = acc + w[j][None, :] * ext[j:j + c]
        new_tail = jax.lax.dynamic_slice_in_dim(ext, n_valid, k - 1, 0)
    return _silu(acc), _round(new_tail, round_state)


def _conv_step_kernel(rows_ref, x_ref, w_ref, b_ref, t_ref, xc_ref, o_ref, *,
                      k: int, round_state):
    x = x_ref[...]                                           # [1, ch]
    acc = b_ref[...] + w_ref[k - 1:k, :] * x
    for j in range(k - 1):
        acc = acc + w_ref[j:j + 1, :] * t_ref[j:j + 1, :]
    xc_ref[...] = _silu(acc)
    o_ref[...] = jnp.zeros_like(o_ref)
    for j in range(k - 2):
        o_ref[j:j + 1, :] = t_ref[j + 1:j + 2, :]
    o_ref[k - 2:k - 1, :] = _round(x, round_state, in_kernel=True)


def _conv_step_pallas(state, rows, layer: int, xs, w, b, *, d_state: int,
                      round_state, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    s, ch = xs.shape
    k = w.shape[0]
    f32 = jnp.float32

    def at_slot(i, rows):
        return (i, 0, 0)

    def whole(i, rows):
        return (0, 0)

    def at_tail(i, rows):
        return (rows[i], layer, d_state // TAIL_ROWS, 0)
    tail = pl.BlockSpec((None, None, TAIL_ROWS, ch), at_tail)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(s,),
        in_specs=[pl.BlockSpec((None, 1, ch), at_slot),
                  pl.BlockSpec((k, ch), whole), pl.BlockSpec((1, ch), whole),
                  tail],
        out_specs=[pl.BlockSpec((None, 1, ch), at_slot), tail])
    xc, state = pl.pallas_call(
        functools.partial(_conv_step_kernel, k=k, round_state=round_state),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, 1, ch), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: rows, xs, w, b, state -> outputs xc, state
        input_output_aliases={4: 1}, interpret=interpret, name="mamba_conv",
    )(rows.astype(jnp.int32), xs.astype(f32)[:, None, :], w.astype(f32),
      b.astype(f32)[None, :], state)
    return xc[:, 0, :], state


def conv_step(state, rows, layer: int, xs, w, b, *, d_state: int,
              round_state=None, backend: Optional[str] = None):
    """One position for each of S slots: ``state [R, L, N + TAIL_ROWS,
    ch]`` (donate it), ``rows [S]`` the slots' state rows (idle slots
    name a scratch row), ``xs [S, ch]``.  Returns ``(xc [S, ch],
    state)`` with every named row's tail moved on by its input."""
    if backend is None:
        backend = default_backend()
    k = w.shape[0]
    with jax.named_scope("ops.mamba_conv"):
        if backend in ("pallas", "mosaic"):
            return _conv_step_pallas(
                state, rows, layer, xs, w, b, d_state=d_state,
                round_state=round_state,
                interpret=_interpret(backend))
        tail = state[rows, layer, d_state:d_state + k - 1]   # [S, K-1, ch]
        xs = xs.astype(jnp.float32)
        acc = b[None, :] + w[k - 1][None, :] * xs
        for j in range(k - 1):
            acc = acc + w[j][None, :] * tail[:, j]
        new = jnp.concatenate(
            [tail[:, 1:], _round(xs, round_state)[:, None]], axis=1)
        return _silu(acc), state.at[
            rows, layer, d_state:d_state + k - 1].set(new)


# ---- the scan ---------------------------------------------------------------

def _update(h, a, dl, x, bt, ct, d, z, round_state, in_kernel):
    """One position: ``h``/``a``/``bt``/``ct`` ``[N, ch]``, the rest
    ``[1, ch]``.  Returns ``(h_t, gated y_t [1, ch])``."""
    h = jnp.exp(dl * a) * h + (dl * x) * bt
    h = _round(h, round_state, in_kernel=in_kernel)
    y = jnp.sum(h * ct, axis=0, keepdims=True) + d * x
    return h, y * _silu(z)


def _widen(v, width: int):
    """``[N, 128]`` lane copies -> ``[N, width]``."""
    return jnp.concatenate([v] * (width // LANES), axis=1) \
        if width > LANES else v


def _scan_kernel(nv_ref, x_ref, dl_ref, z_ref, b_ref, c_ref, h0_ref, a_ref,
                 d_ref, y_ref, hn_ref, h_scr, *, tb: int, round_state):
    from jax.experimental import pallas as pl
    t, j = pl.program_id(0), pl.program_id(1)
    cb = h_scr.shape[-1]

    @pl.when(t == 0)
    def _first():
        h_scr[j] = h0_ref[...]

    @pl.when(t * tb < nv_ref[0])
    def _run():
        a = -jnp.exp(a_ref[...])
        d = d_ref[...]

        def one(i, h):
            row = pl.ds(i, 1)
            h, y = _update(h, a, dl_ref[row, :], x_ref[row, :],
                           _widen(b_ref[i], cb), _widen(c_ref[i], cb), d,
                           z_ref[row, :], round_state, True)
            y_ref[row, :] = y
            return h
        h_scr[j] = jax.lax.fori_loop(0, tb, one, h_scr[j])

    @pl.when(t * tb >= nv_ref[0])
    def _padding():
        # nothing of the bucket's padding is computed; its rows hold
        # zeros and not what the buffer held (a later reader multiplies
        # masked values by them)
        y_ref[...] = jnp.zeros_like(y_ref)

    hn_ref[...] = h_scr[j]


def _scan_pallas(xc, delta, z, bmat, cmat, h0, a_log, d, n_valid, *,
                 round_state, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    c, ch = xc.shape
    n = h0.shape[0]
    tb, cb = min(SCAN_TIME, c), min(SCAN_CHANNELS, ch)
    if c % tb or ch % cb or cb % LANES:
        raise ValueError(f"a chunk of {c} x {ch} is not whole blocks of "
                         f"{tb} x {cb}")
    nj = ch // cb
    f32 = jnp.float32

    def seq(t, j, nv):
        return (t, j)

    def per_t(t, j, nv):
        return (t, 0, 0)

    def per_j(t, j, nv):
        return (j, 0, 0)

    def chan(t, j, nv):
        return (0, j)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(c // tb, nj),
        in_specs=[pl.BlockSpec((tb, cb), seq), pl.BlockSpec((tb, cb), seq),
                  pl.BlockSpec((tb, cb), seq),
                  pl.BlockSpec((tb, n, LANES), per_t),
                  pl.BlockSpec((tb, n, LANES), per_t),
                  pl.BlockSpec((None, n, cb), per_j),
                  pl.BlockSpec((n, cb), chan), pl.BlockSpec((1, cb), chan)],
        out_specs=[pl.BlockSpec((tb, cb), seq),
                   pl.BlockSpec((None, n, cb), per_j)],
        scratch_shapes=[pltpu.VMEM((nj, n, cb), f32)])

    def blocks(h):         # [N, ch] <-> [nj, N, cb]
        return h.reshape(n, nj, cb).transpose(1, 0, 2)
    y, hn = pl.pallas_call(
        functools.partial(_scan_kernel, tb=tb, round_state=round_state),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((c, ch), f32),
                   jax.ShapeDtypeStruct((nj, n, cb), f32)],
        interpret=interpret, name="mamba_scan",
    )(jnp.asarray(n_valid, jnp.int32).reshape(1), xc, delta, z,
      lane_copies(bmat), lane_copies(cmat), blocks(h0.astype(f32)),
      a_log.astype(f32), d.astype(f32)[None, :])
    return y, hn.transpose(1, 0, 2).reshape(n, ch)


def mamba_scan(xc, delta, z, bmat, cmat, h0, a_log, d, n_valid, *,
               round_state=None, backend: Optional[str] = None):
    """A chunk of C positions of ONE sequence: ``xc``/``delta``/``z``
    ``[C, ch]`` float32, ``bmat``/``cmat`` ``[C, N]``, ``h0 [N, ch]``
    the state before it, ``a_log [N, ch]``, ``d [ch]``, ``n_valid`` how
    many leading positions are real.  Returns ``(gated y [C, ch], the
    state after the last valid position)``; ``y`` of the padding is
    not to be read."""
    if backend is None:
        backend = default_backend()
    f32 = jnp.float32
    c = xc.shape[0]
    valid = jnp.arange(c, dtype=jnp.int32) < n_valid
    # a padded position: exp(0 A) h + 0 = h
    delta = jnp.where(valid[:, None], delta.astype(f32), 0.0)
    xc, z = xc.astype(f32), z.astype(f32)
    with jax.named_scope("ops.mamba_scan"):
        if backend in ("pallas", "mosaic"):
            return _scan_pallas(
                xc, delta, z, bmat, cmat, h0, a_log, d, n_valid,
                round_state=round_state,
                interpret=_interpret(backend))
        a = -jnp.exp(a_log.astype(f32))
        drow = d.astype(f32)[None, :]

        def one(h, xs):
            x, dl, zz, bt, ct = xs
            h, y = _update(h, a, dl[None, :], x[None, :], bt[:, None],
                           ct[:, None], drow, zz[None, :], round_state,
                           False)
            return h, y[0]
        h_end, y = jax.lax.scan(
            one, h0.astype(f32),
            (xc, delta, z, bmat.astype(f32), cmat.astype(f32)))
    return y, h_end


def _step_kernel(rows_ref, x_ref, dl_ref, z_ref, b_ref, c_ref, a_ref, d_ref,
                 s_ref, y_ref, o_ref, *, round_state):
    ch = s_ref.shape[-1]
    h, y = _update(s_ref[...], -jnp.exp(a_ref[...]), dl_ref[...], x_ref[...],
                   _widen(b_ref[...], ch), _widen(c_ref[...], ch),
                   d_ref[...], z_ref[...], round_state, True)
    o_ref[...] = h
    y_ref[...] = y


def _step_pallas(state, rows, layer: int, xc, delta, z, bmat, cmat, a_log,
                 d, *, round_state, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    s, ch = xc.shape
    n = a_log.shape[0]
    f32 = jnp.float32

    def at_slot(i, rows):
        return (i, 0, 0)

    def whole(i, rows):
        return (0, 0)

    def at_state(i, rows):
        return (rows[i], layer, 0, 0)
    slot = pl.BlockSpec((None, 1, ch), at_slot)
    lanes = pl.BlockSpec((None, n, LANES), at_slot)
    block = pl.BlockSpec((None, None, n, ch), at_state)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(s,),
        in_specs=[slot, slot, slot, lanes, lanes,
                  pl.BlockSpec((n, ch), whole), pl.BlockSpec((1, ch), whole),
                  block],
        out_specs=[slot, block])

    def rowed(x):
        return x.astype(f32)[:, None, :]
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, round_state=round_state),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((s, 1, ch), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: rows, xc, delta, z, B, C, a_log, d, state -> y, state
        input_output_aliases={8: 1}, interpret=interpret, name="mamba_step",
    )(rows.astype(jnp.int32), rowed(xc), rowed(delta), rowed(z),
      lane_copies(bmat), lane_copies(cmat), a_log.astype(f32),
      d.astype(f32)[None, :], state)
    return y[:, 0, :], state


def mamba_step(state, rows, layer: int, xc, delta, z, bmat, cmat, a_log, d,
               *, round_state=None, backend: Optional[str] = None):
    """One position for each of S slots: ``state [R, L, N + TAIL_ROWS,
    ch]`` (donate it), ``rows [S]``, ``xc``/``delta``/``z`` ``[S,
    ch]``, ``bmat``/``cmat`` ``[S, N]``.  Returns ``(gated y [S, ch],
    state)``: every named row's scan state read once and written
    once."""
    if backend is None:
        backend = default_backend()
    n = a_log.shape[0]
    with jax.named_scope("ops.mamba_step"):
        if backend in ("pallas", "mosaic"):
            return _step_pallas(
                state, rows, layer, xc, delta, z, bmat, cmat, a_log, d,
                round_state=round_state,
                interpret=_interpret(backend))
        f32 = jnp.float32
        a = -jnp.exp(a_log.astype(f32))
        h, y = jax.vmap(lambda h, dl, x, bt, ct, zz: _update(
            h, a, dl[None, :], x[None, :], bt[:, None], ct[:, None],
            d.astype(f32)[None, :], zz[None, :], round_state, False))(
            state[rows, layer, :n], delta.astype(f32), xc.astype(f32),
            bmat.astype(f32), cmat.astype(f32), z.astype(f32))
        return y[:, 0], state.at[rows, layer, :n].set(h)

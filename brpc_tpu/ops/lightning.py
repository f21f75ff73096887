"""Lightning (linear) attention with a recurrent state (ISSUE 32).

Per head, with a fixed decay ``a`` (``log_decay = log a < 0``):

    S_t = a * S_{t-1} + k_t^T v_t          S: [D, D] float32
    o_t = scale * q_t S_t

Two forms of the same recurrence:

  * :func:`lightning_decode` — ONE position for every decode slot, the
    state rows of a persistent ``[R, L, H, D, D]`` array updated IN
    PLACE.  On a TPU it is the ``lightning_state`` Pallas kernel (the
    state array is aliased to the output and each grid step reads and
    writes the one ``[heads, D, D]`` block its slot's row names through
    scalar prefetch: the state crosses HBM once each way); elsewhere
    a gather / scatter in plain jax.
  * :func:`lightning_chunk` — a prefill chunk, blockwise: inside a
    block the decay-masked ``q k^T`` product, between blocks the state.
    Padded positions (``>= n_valid``) neither decay the state nor add
    to it, so a bucket-padded chunk leaves the state of its valid
    prefix.

The state is float32 always; ``round_state`` ("bfloat16") is the
benchmark's low-precision CONTROL only: the state then holds the values
a bfloat16 state would.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from brpc_tpu.ops.paged_attention import default_backend

__all__ = ["log_decays", "lightning_decode", "lightning_chunk",
           "lightning_decode_pallas", "default_backend"]


def log_decays(n_heads: int):
    """``log a_h = -2^(-8 (h+1) / H)`` (Lightning Attention-2's slopes),
    float32 ``[H]``."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return -jnp.exp2(-8.0 * h / n_heads)


def _round(x, round_state, in_kernel: bool = False):
    if round_state is None:
        return x
    if jnp.dtype(round_state) != jnp.bfloat16:
        raise ValueError("the control rounds the state to bfloat16")
    if in_kernel:       # Mosaic lowers the casts as written
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    # XLA may drop a pair of casts as excess precision; not this
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


# ---- decode: one position a slot, state rows in place ----------------------

def _decode_gather(state, rows, layer, q, k, v, log_decay, scale,
                   round_state):
    s = state[rows, layer]                                  # [N, H, D, D]
    a = jnp.exp(log_decay)[None, :, None, None]
    s_new = _round(a * s + k[..., :, None] * v[..., None, :], round_state)
    o = scale * jnp.einsum("nhd,nhde->nhe", q, s_new,
                           precision="highest")
    return o, state.at[rows, layer].set(s_new)


def _state_kernel(rows_ref, layer_ref, q_ref, k_ref, v_ref, a_ref, s_ref,
                  o_ref, s_out_ref, *, scale: float, round_state):
    s = s_ref[...]                                          # [hb, D, D]
    s_new = a_ref[...] * s + k_ref[...] * v_ref[...]        # col * row
    s_new = _round(s_new, round_state, in_kernel=True)
    s_out_ref[...] = s_new
    # o[e] = sum_d q[d] S[d, e]: a sublane reduce of (q column * S)
    o_ref[...] = scale * jnp.sum(q_ref[...] * s_new, axis=1, keepdims=True)


def lightning_decode_pallas(state, rows, layer, q, k, v, log_decay, *,
                            scale: float, round_state=None,
                            heads_per_block: int = 8,
                            interpret: Optional[bool] = None):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, h, d = q.shape
    hb = min(heads_per_block, h)
    if h % hb:
        raise ValueError(f"heads ({h}) must divide into blocks of {hb}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    f32 = jnp.float32
    col = (None, hb, d, 1)
    row = (None, hb, 1, d)

    def at_slot(i, j, rows, layer):
        return (i, j, 0, 0)

    def at_state(i, j, rows, layer):
        return (rows[i], layer[0], j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n, h // hb),
        in_specs=[pl.BlockSpec(col, at_slot), pl.BlockSpec(col, at_slot),
                  pl.BlockSpec(row, at_slot),
                  pl.BlockSpec((hb, 1, 1), lambda i, j, r, l: (j, 0, 0)),
                  pl.BlockSpec((None, None, hb, d, d), at_state)],
        out_specs=[pl.BlockSpec(row, at_slot),
                   pl.BlockSpec((None, None, hb, d, d), at_state)])
    o, state = pl.pallas_call(
        functools.partial(_state_kernel, scale=scale,
                          round_state=round_state),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n, h, 1, d), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands: rows, layer, q, k, v, decay, state -> outputs o, state
        input_output_aliases={6: 1},
        interpret=interpret, name="lightning_state",
    )(rows.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q.astype(f32)[..., None], k.astype(f32)[..., None],
      v.astype(f32)[:, :, None, :],
      jnp.exp(log_decay).astype(f32)[:, None, None], state)
    return o[:, :, 0, :], state


def lightning_decode(state, rows, layer: int, q, k, v, log_decay, *,
                     scale: float, round_state=None,
                     backend: Optional[str] = None):
    """One position for each of N slots.  ``state`` ``[R, L, H, D, D]``
    float32 (donate it), ``rows`` ``[N]`` the slots' state rows (rows
    of idle slots name a scratch row: every row is read and written),
    ``layer`` the lightning layer's index, ``q``/``k``/``v``
    ``[N, H, D]``.  Returns ``(o [N, H, D] float32, state)``."""
    if backend is None:
        backend = default_backend()
    with jax.named_scope("ops.lightning_state"):
        if backend in ("pallas", "mosaic"):
            # "mosaic": the kernel compiled for the chip whatever the
            # default backend is (a chip-less compile for a described
            # TPU); "pallas" interprets it off the chip
            return lightning_decode_pallas(
                state, rows, layer, q, k, v, log_decay, scale=scale,
                round_state=round_state,
                interpret=False if backend == "mosaic" else None)
        return _decode_gather(state, rows, layer, q.astype(jnp.float32),
                              k.astype(jnp.float32), v.astype(jnp.float32),
                              log_decay, scale, round_state)


# ---- prefill: one chunk, blockwise -----------------------------------------

def lightning_chunk(q, k, v, state0, log_decay, n_valid, *, scale: float,
                    block: int = 256, round_state=None):
    """A chunk of C positions of ONE sequence: ``q``/``k``/``v``
    ``[C, H, D]`` float32, ``state0`` ``[H, D, D]``, ``n_valid`` how
    many leading positions are real.  Returns ``(o [C, H, D],
    state [H, D, D])``, the state after the last valid position."""
    c, h, d = q.shape
    blk = min(block, c)
    if c % blk:
        raise ValueError(f"chunk {c} is not a multiple of block {blk}")
    nb = c // blk
    f32 = jnp.float32
    hp = "highest"

    def to_blocks(x):
        return x.astype(f32).reshape(nb, blk, h, d)

    pos = jnp.arange(c, dtype=jnp.int32).reshape(nb, blk)

    def body(s, xs):
        qb, kb, vb, pb = xs
        valid = (pb < n_valid)                               # [blk]
        kb = jnp.where(valid[:, None, None], kb, 0.0)
        # cumulative log decay INCLUDING position i: G_i = sum_{m<=i} g_m
        g = jnp.where(valid, 1.0, 0.0)[:, None] * log_decay[None, :]
        gc = jnp.cumsum(g, axis=0)                           # [blk, H]
        # inside the block: o_i += sum_{j<=i} exp(G_i - G_j) (q_i.k_j) v_j
        diff = gc[:, None, :] - gc[None, :, :]               # [i, j, H]
        tri = (jnp.arange(blk)[:, None] >= jnp.arange(blk)[None, :])
        dmat = jnp.where(tri[..., None], jnp.exp(jnp.minimum(diff, 0.0)),
                         0.0)
        sc = jnp.einsum("ihd,jhd->ijh", qb, kb, precision=hp) * dmat
        o = jnp.einsum("ijh,jhe->ihe", sc, vb, precision=hp)
        # from before the block: o_i += exp(G_i) q_i S_prev
        o = o + jnp.exp(gc)[..., None] * jnp.einsum(
            "ihd,hde->ihe", qb, s, precision=hp)
        # the state after the block
        tail = jnp.exp(gc[-1][None, :] - gc)                 # [blk, H]
        s_new = jnp.exp(gc[-1])[:, None, None] * s + jnp.einsum(
            "jhd,jhe->hde", kb * tail[..., None], vb, precision=hp)
        return _round(s_new, round_state), scale * o

    with jax.named_scope("ops.lightning_chunk"):
        s_end, o = jax.lax.scan(
            body, state0.astype(f32),
            (to_blocks(q), to_blocks(k), to_blocks(v), pos))
    return o.reshape(c, h, d), s_end

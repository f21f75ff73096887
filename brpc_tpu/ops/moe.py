"""Routed experts on the serving path (ISSUE 34): the router of
``noaux_tc`` as GLM-4.7-Flash and Nemotron 3 publish it, and the
experts' bodies as ONE ragged grouped matmul an operand, with no
capacity and no dropped token.  An expert's body is one of two: the
silu gated MLP of three matrices, or (ISSUE 40) two matrices around
``relu(.)^2``; either in whatever width it is handed (the model's, or
a latent width the caller projects down to and up from).

  :func:`route`       ``s = sigmoid(x W_g)`` in float32; the top ``k`` of
                      ``s + b`` are CHOSEN (``b``: the per-expert
                      correction bias, selection only); the weights are
                      the chosen ``s`` (without ``b``), divided by their
                      sum where the family normalises, times the routed
                      scaling factor
  :func:`expert_ffn`  ``sum_i w_i E_i(x)`` over the experts HELD here
                      (``held = (first, count)`` of the router's
                      ``n_experts``: this chip's share; what the other
                      experts add is theirs to compute).  Assignments
                      are sorted by expert and the matrices of every
                      expert multiplied by ``lax.ragged_dot`` over the
                      stacked weights ``[E, K, N]``: on a TPU one
                      Mosaic kernel an operand that visits the groups in
                      turn and reads only the experts that were hit; 64
                      rows in a decode step and 4,096 in a prefill chunk
                      alike.  Of 64 slots x 22 choices over 512 experts
                      with 128 held, a quarter of the sorted rows are
                      groups and the rest sort behind the last.

The training-side layer with a capacity and an exchange across chips is
``brpc_tpu.models.moe`` (top-1, ``shard_map``); nothing on the serving
path calls it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["route", "expert_ffn"]


def route(s, bias, k: int, *, norm: bool, scale: float):
    """``s [N, E]`` float32 scores (after the sigmoid), ``bias [E]``:
    ``(experts [N, k] int32, weights [N, k] float32)``."""
    _, idx = jax.lax.top_k(s + bias[None, :], k)
    w = jnp.take_along_axis(s, idx, axis=1)
    if norm:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * scale


def expert_ffn(x, experts, weights, valid, w_gate, w_up, w_down, *,
               held: tuple, mm):
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
    Returns ``(y [N, dm] float32, group sizes [count] int32)``."""
    n, k = experts.shape
    first, count = held
    e = experts.reshape(-1) - first
    mine = (e >= 0) & (e < count) & jnp.repeat(valid, k)
    key = jnp.where(mine, e, count)          # what is not ours sorts last
    with jax.named_scope("ops.expert_ffn"):
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
        xs = x[order // k]                                   # [N * k, dm]
        if w_gate is None:
            hidden = jnp.square(jax.nn.relu(mm(xs, w_up, sizes)))
        else:
            hidden = jax.nn.silu(mm(xs, w_gate, sizes)) * mm(xs, w_up, sizes)
        ys = mm(hidden, w_down, sizes)
        # rows past the last group are no product of anything: masked,
        # not multiplied (they may hold anything)
        w_sorted = weights.reshape(-1)[order]
        ys = jnp.where(mine[order][:, None], ys * w_sorted[:, None], 0.0)
        y = ys[jnp.argsort(order)].reshape(n, k, -1).sum(axis=1)
    return y, sizes

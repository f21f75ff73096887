"""Mixture-of-Experts block with expert parallelism over the device mesh:
the TRAINING-side layer (top-1 routing, a fixed capacity an expert, tokens
over it dropped, the exchange across chips inside ``shard_map``).  No
serving path calls it: the served models' routed experts (a sigmoid
router with a correction bias, top-k, no capacity and no dropped token,
ragged grouped matmuls inside the decode step) are ``brpc_tpu.ops.moe``,
called from ``brpc_tpu.models.hybrid``.

A Switch-style top-1 MoE layer whose
experts shard over an ``ep`` mesh axis and whose token dispatch rides
``lax.all_to_all`` inside ``shard_map`` — the canonical TPU MoE recipe
(GShard/Switch): static-shape one-hot dispatch einsums (no dynamic
shapes, so XLA tiles everything onto the MXU), capacity-bounded expert
buffers, and ICI all_to_alls for the token exchange in both directions.

Everything is a pure function over parameters; the sharded layer is
validated against the identical-math single-device reference in
tests/test_moe.py.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class MoEConfig:
    d_model: int = 64
    d_ff: int = 128
    n_experts: int = 8          # global expert count (divisible by ep)
    capacity: int = 16          # per-expert token slots PER SHARD
    seq: int = 32               # tokens per shard


def init_moe_params(cfg: MoEConfig, key=None):
    key = key if key is not None else jax.random.PRNGKey(0)
    kr, k1, k2 = jax.random.split(key, 3)
    scale = 1.0 / np.sqrt(cfg.d_model)
    return {
        "router": jax.random.normal(kr, (cfg.d_model, cfg.n_experts),
                                    jnp.float32) * scale,
        # per-expert FFN stacks: [E, d_model, d_ff] / [E, d_ff, d_model]
        "wup": jax.random.normal(k1, (cfg.n_experts, cfg.d_model, cfg.d_ff),
                                 jnp.float32) * scale,
        "wdown": jax.random.normal(k2, (cfg.n_experts, cfg.d_ff,
                                        cfg.d_model), jnp.float32) * scale,
    }


def _dispatch_tensors(x, router_w, n_experts: int, capacity: int):
    """Switch-style top-1 routing with static shapes.

    Returns (dispatch[S,E,C] one-hot, combine[S,E,C] gated) — the GShard
    einsum pair.  Tokens overflowing an expert's capacity are DROPPED
    (their combine weights are zero), exactly the reference behavior of
    capacity-factor MoEs.
    """
    logits = x @ router_w                         # [S, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)           # [S]
    gate = jnp.max(probs, axis=-1)                # [S]
    onehot = jax.nn.one_hot(expert, n_experts, dtype=x.dtype)   # [S, E]
    # position of each token within its expert's buffer
    pos = jnp.cumsum(onehot, axis=0) * onehot - onehot
    keep = pos < capacity
    onehot = onehot * keep
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                            dtype=x.dtype)        # [S, E, C]
    dispatch = onehot[..., None] * pos_oh         # [S, E, C]
    combine = dispatch * gate[:, None, None]
    return dispatch, combine


def _expert_ffn(inp, wup, wdown):
    """[E, C, D] tokens through per-expert FFNs (batched matmul — one
    MXU-friendly einsum per projection)."""
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", inp, wup))
    return jnp.einsum("ecf,efd->ecd", h, wdown)


def moe_layer_reference(params, x, cfg: MoEConfig):
    """Single-device reference: the exact math the sharded layer must
    reproduce (dispatch -> all experts locally -> combine)."""
    dispatch, combine = _dispatch_tensors(x, params["router"],
                                          cfg.n_experts, cfg.capacity)
    expert_in = jnp.einsum("sec,sd->ecd", dispatch, x)
    expert_out = _expert_ffn(expert_in, params["wup"], params["wdown"])
    return jnp.einsum("sec,ecd->sd", combine, expert_out)


def make_ep_mesh(n_devices: int) -> Mesh:
    return Mesh(np.array(jax.devices()[:n_devices]), ("ep",))


def _check_divisible(cfg: MoEConfig, ep: int) -> None:
    if cfg.n_experts % ep:
        raise ValueError(f"n_experts {cfg.n_experts} must divide by ep={ep}")


def _shard_forward(router_w, wup, wdown, x, cfg: MoEConfig):
    """ONE per-shard forward shared by the inference layer and the train
    step (training and serving must compute identical math): dispatch,
    all_to_all out, local expert FFN, all_to_all back, combine."""
    dispatch, combine = _dispatch_tensors(x, router_w, cfg.n_experts,
                                          cfg.capacity)
    expert_in = jnp.einsum("sec,sd->ecd", dispatch, x)       # [E, C, D]
    # exchange: split the expert axis across ep, concat the slots —
    # each chip ends with ITS experts' buffers from EVERY shard
    gathered = lax.all_to_all(expert_in, "ep", split_axis=0,
                              concat_axis=1, tiled=True)
    out = _expert_ffn(gathered, wup, wdown)   # [E/ep, ep*C, D] locally
    # reverse exchange: send each shard its tokens back
    returned = lax.all_to_all(out, "ep", split_axis=1, concat_axis=0,
                              tiled=True)                    # [E, C, D]
    return jnp.einsum("sec,ecd->sd", combine, returned)


def make_sharded_moe_layer(mesh: Mesh, cfg: MoEConfig):
    """The expert-parallel layer: tokens sharded over ``ep``, experts
    sharded over ``ep``, two ICI all_to_alls exchanging capacity
    buffers.  Per shard:

      [S,E,C] dispatch -> expert_in [E,C,D]
      all_to_all(E -> local experts, gathering every shard's slots)
      local expert FFN on [E/ep, ep*C, D]
      all_to_all back -> combine locally

    Drop-in identical math to moe_layer_reference when the same tokens
    flow through (each shard routes ITS tokens with the full router).
    """
    ep = mesh.shape["ep"]
    _check_divisible(cfg, ep)

    def shard_fn(router_w, wup, wdown, x):
        # x: [S_local, D]; wup/wdown: [E/ep, ...] (this shard's experts)
        return _shard_forward(router_w, wup, wdown, x, cfg)

    from brpc_tpu.ici.collective import shard_map
    return jax.jit(shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P("ep", None, None), P("ep", None, None),
                  P("ep", None)),
        out_specs=P("ep", None)))


def make_sharded_moe_train_step(mesh: Mesh, cfg: MoEConfig,
                                lr: float = 1e-2):
    """One SGD step through the expert-parallel layer: the loss runs the
    sharded forward (all_to_alls included) and jax.grad differentiates
    THROUGH the collectives — the backward pass's token returns are the
    transposed all_to_alls, which XLA emits as ICI traffic exactly like
    the forward.  Router gradients flow through the gate weights (the
    dispatch one-hots are straight-through: argmax itself has no
    gradient, matching Switch)."""
    ep = mesh.shape["ep"]
    _check_divisible(cfg, ep)

    def shard_loss(router_w, wup, wdown, x, target):
        y = _shard_forward(router_w, wup, wdown, x, cfg)
        # this shard's CONTRIBUTION to the global mean — the psum is
        # deliberately OUTSIDE the differentiated function: psum
        # transposes to psum, so a psum'd loss inflates every cotangent
        # by ep (measured exactly ep x vs the single-device reference)
        local = jnp.sum((y - target) ** 2)
        # normalize by the ACTUAL global element count (the layer is
        # shape-polymorphic in S; cfg.seq here would silently mis-scale
        # loss and gradients for any other batch length)
        return local / (y.size * ep)

    def shard_step(router_w, wup, wdown, x, target):
        contrib, grads = jax.value_and_grad(shard_loss,
                                            argnums=(0, 1, 2))(
            router_w, wup, wdown, x, target)
        gr, gu, gd = grads
        # report the GLOBAL loss; gradients through the all_to_alls are
        # already the true global-mean grads (the collectives transpose
        # cotangents back to the experts that produced them)
        loss = lax.psum(contrib, "ep")
        # router is REPLICATED: each shard's gr is its tokens'
        # contribution — the true grad is their sum
        gr = lax.psum(gr, "ep")
        return (router_w - lr * gr, wup - lr * gu, wdown - lr * gd, loss)

    from brpc_tpu.ici.collective import shard_map
    return jax.jit(shard_map(
        shard_step, mesh=mesh,
        in_specs=(P(), P("ep", None, None), P("ep", None, None),
                  P("ep", None), P("ep", None)),
        out_specs=(P(), P("ep", None, None), P("ep", None, None), P())))


def place_moe_params(params, mesh: Mesh):
    """Router replicated; expert stacks sharded over ep."""
    return {
        "router": jax.device_put(params["router"],
                                 NamedSharding(mesh, P())),
        "wup": jax.device_put(params["wup"],
                              NamedSharding(mesh, P("ep", None, None))),
        "wdown": jax.device_put(params["wdown"],
                                NamedSharding(mesh, P("ep", None, None))),
    }

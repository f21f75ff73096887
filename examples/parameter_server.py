"""Parameter-server / sharded-embedding demo — the BASELINE.json north
star: the flagship model served through the RPC surface AND trained with
sharded steps over the mesh."""
import os, sys, time
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

import jax.numpy as jnp

import brpc_tpu as brpc
from brpc_tpu.ici import IciChannel
from brpc_tpu.models import (PSConfig, init_params, register_ps_services,
                             make_sharded_train_step)
from brpc_tpu.models.parameter_server import (make_example_batch, make_mesh,
                                              param_shardings,
                                              data_shardings)


def serve_lookups():
    register_ps_services()
    n = len(jax.devices())
    ch = IciChannel(f"ici://slice0/{n - 1}")
    tokens = jnp.arange(8) % 256
    emb = ch.call_sync("ParameterServer", "EmbedLookup", tokens)
    print(f"embedding lookup via IciChannel on chip {n-1}: {emb.shape}")
    logits = ch.call_sync("ParameterServer", "Forward",
                          tokens.reshape(1, 8))
    print(f"full forward via RPC: {logits.shape}")


def train_sharded():
    n = len(jax.devices())
    cfg = PSConfig(vocab=512, d_model=64, d_ff=128, n_layers=2, seq=16,
                   batch=max(4, n))
    mesh = make_mesh(n)
    params = jax.tree_util.tree_map(
        lambda p, s: jax.device_put(p, s), init_params(cfg),
        param_shardings(mesh))
    ts, gs = data_shardings(mesh)
    tokens, targets = make_example_batch(cfg)
    tokens, targets = jax.device_put(tokens, ts), jax.device_put(targets, gs)
    step = make_sharded_train_step(mesh, cfg, lr=2.0)
    losses = []
    for i in range(10):
        params, loss = step(params, tokens, targets)
        losses.append(float(loss))
    print(f"sharded training over {mesh.shape}: "
          f"loss {losses[0]:.3f} -> {losses[-1]:.3f}")


if __name__ == "__main__":
    serve_lookups()
    train_sharded()

"""Expert-parallel MoE served through the framework.

Two things in one demo:
  1. the Switch-style MoE layer with experts sharded over an `ep` mesh
     axis and `lax.all_to_all` token exchanges (models/moe.py) — run
     directly and validated against the single-device reference;
  2. the same layer registered as a DEVICE SERVICE and invoked through
     `IciChannel` — an inference endpoint whose handler IS the sharded
     program, the framework's device-RPC surface over the MoE math.

Run on the virtual mesh:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  JAX_PLATFORMS=cpu python examples/moe_expert_parallel.py
"""
import os, sys
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

import jax.numpy as jnp
import numpy as np

from brpc_tpu.models.moe import (MoEConfig, init_moe_params, make_ep_mesh,
                                 make_sharded_moe_layer,
                                 moe_layer_reference, place_moe_params)


def main():
    n = len(jax.devices())
    cfg = MoEConfig(d_model=64, d_ff=128, n_experts=n, capacity=64, seq=32)
    params = init_moe_params(cfg, jax.random.PRNGKey(0))
    mesh = make_ep_mesh(n)
    layer = make_sharded_moe_layer(mesh, cfg)
    placed = place_moe_params(params, mesh)

    from jax.sharding import NamedSharding, PartitionSpec as P
    tokens = jax.random.normal(jax.random.PRNGKey(1),
                               (n * cfg.seq, cfg.d_model), jnp.float32)
    xs = jax.device_put(tokens, NamedSharding(mesh, P("ep", None)))

    out = layer(placed["router"], placed["wup"], placed["wdown"], xs)
    ref = moe_layer_reference(params, tokens[:cfg.seq], cfg)
    np.testing.assert_allclose(np.asarray(out)[:cfg.seq], np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    print(f"MoE layer: {n} experts over {n} chips, "
          f"{n * cfg.seq} tokens exchanged via all_to_all — matches the "
          f"single-device reference")

    # ---- serve it: the sharded program as a device service ----
    from brpc_tpu.ici import IciChannel, register_device_service

    def moe_service(x):
        # requests arrive on the target chip; the service re-shards them
        # over the ep mesh and runs the sharded program — the endpoint
        # takes plain tokens, the parallelism is its implementation
        xs_ = jax.device_put(x, NamedSharding(mesh, P("ep", None)))
        return layer(placed["router"], placed["wup"], placed["wdown"], xs_)

    register_device_service("MoE", "Forward", moe_service, jit=False)
    ch = IciChannel("ici://slice0/0")
    served = ch.call_sync("MoE", "Forward", tokens)
    np.testing.assert_allclose(np.asarray(served), np.asarray(out),
                               rtol=1e-6, atol=1e-6)
    print("served through IciChannel: identical output — the inference "
          "endpoint IS the sharded program")


if __name__ == "__main__":
    main()

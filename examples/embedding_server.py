"""Train the seed parameter-server model THROUGH the sharded embedding
service (ISSUE 12): the embedding table lives in N EmbeddingShardServer
partitions behind real RPC servers, the trainer routes every gather and
sparse gradient through PSClient's PartitionChannel fan-out, and dense
params round-trip Pull/Push.  Loss goes down; the table the shards hold
is the one being trained.

    python examples/embedding_server.py [n_shards]
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

import jax.numpy as jnp
import numpy as np

import brpc_tpu as brpc
from brpc_tpu.models.parameter_server import (PSConfig, _block,
                                              init_params,
                                              make_example_batch)
from brpc_tpu.psserve import (EmbeddingShardServer, PSClient,
                              register_psserve, unregister_psserve)
from brpc_tpu.rpc.combo_channels import PartitionChannel


def main(n_shards: int = 4):
    cfg = PSConfig(vocab=128, d_model=32, d_ff=64, n_layers=2, seq=16,
                   batch=8)
    params = init_params(cfg, key=jax.random.PRNGKey(0))
    embed0 = np.asarray(params["embed"], np.float32)

    # ---- the service: N shards over real loopback RPC servers ----
    servers, svcs, shards = [], [], []
    pc = PartitionChannel(n_shards)
    for i in range(n_shards):
        sh = EmbeddingShardServer(i, n_shards, cfg.vocab, cfg.d_model,
                                  table=embed0, name="example")
        shards.append(sh)
        s = brpc.Server()
        svcs.append(register_psserve(s, sh, max_delay_us=500,
                                     name=f"example_{i}"))
        s.start("127.0.0.1", 0)
        servers.append(s)
        pc.add_partition(i, brpc.Channel(f"127.0.0.1:{s.port}",
                                         timeout_ms=10_000))
    cli = PSClient(pc, vocab=cfg.vocab, dim=cfg.d_model)
    print(f"serving {cfg.vocab}x{cfg.d_model} embedding over "
          f"{n_shards} shards "
          f"({', '.join(str(sh.n_rows) + ' rows' for sh in shards)})")

    # dense (non-embedding) params live in the service too: push the
    # initial values, pull the working copy (owner = name hash)
    dense = {k: v for k, v in params.items() if k != "embed"}
    for k, v in dense.items():
        cli.push(k, np.asarray(v, np.float32))
    dense = {k: jnp.asarray(cli.pull(k)) for k in dense}
    print(f"dense params pushed + pulled through PS.Pull/PS.Push: "
          f"{sorted(dense)}")

    # ---- loss as a function of GATHERED rows + dense params ----
    def loss_from_rows(rows, dense, targets):
        x = rows.astype(jnp.bfloat16)          # [B, S, D]

        def body(x, layer):
            wqk, wup, wdown = layer
            return _block(x, wqk, wup, wdown), None

        d = {k: v.astype(jnp.bfloat16) for k, v in dense.items()}
        x, _ = jax.lax.scan(body, x, (d["w_qk"], d["w_up"], d["w_down"]))
        logits = (x @ d["w_out"]).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return -jnp.mean(ll)

    grad_fn = jax.jit(jax.value_and_grad(loss_from_rows, argnums=(0, 1)))
    tokens, targets = make_example_batch(cfg, key=jax.random.PRNGKey(1))
    keys = np.asarray(tokens).reshape(-1).astype(np.int64)
    lr = 0.5

    # ---- the training loop: every gather and every sparse gradient
    # rides the RPC service ----
    for step in range(8):
        rows = cli.lookup(keys).reshape(cfg.batch, cfg.seq, cfg.d_model)
        loss, (g_rows, g_dense) = grad_fn(jnp.asarray(rows), dense,
                                          targets)
        # sparse scatter-add through PS.Update: duplicate tokens in the
        # batch accumulate, exactly like the dense .at[].add would
        cli.update(keys, np.asarray(-lr * g_rows.reshape(-1, cfg.d_model),
                                    np.float32))
        dense = {k: v - lr * g_dense[k] for k, v in dense.items()}
        print(f"  step {step}: loss {float(loss):.4f}  "
              f"(shard versions {[sh.version for sh in shards]})")

    # push the trained dense params back so the service owns the whole
    # model again
    for k, v in dense.items():
        cli.push(k, np.asarray(v - jnp.asarray(cli.pull(k)), np.float32))
    print(f"client stats: {cli.stats()}")
    print(f"shard 0 hot keys: {shards[0].hot_keys(5)}")

    for svc in svcs:
        unregister_psserve(svc)
    for s in servers:
        s.stop()
        s.join()
    cli.close()


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 4)

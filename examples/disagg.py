"""Disaggregated prefill/decode across two REAL processes
(brpc_tpu/migrate; ISSUE 7).

Spawns a DECODE process (KV store + DecodeEngine + the migration
splice) and a PREFILL process (KV store + PrefillReplica shipping
pages to the decode address), then drives ONE generation across the
split from this process: the DisaggCoordinator runs Prefill on the
prefill process — whose finished pages stream over the `_kvmig` plane
— and streams the tokens from the decode process, which prefix-hits
the migrated pages instead of re-prefilling.

Run:  python examples/disagg.py
"""
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPO = os.path.join(os.path.dirname(__file__), "..")

DECODE = f"""
import sys
sys.path.insert(0, {REPO!r})
import jax
jax.config.update("jax_platforms", "cpu")
from brpc_tpu.kvcache import KVCacheStore
from brpc_tpu.migrate import register_disagg_decode
from brpc_tpu.rpc.server import Server
from brpc_tpu.serving import DecodeEngine

@jax.jit
def step(tokens, positions, pages):
    return (tokens * 7 + positions) % 997

store = KVCacheStore(page_tokens=4, page_bytes=256, max_blocks=32,
                     name="decode")
engine = DecodeEngine(step, num_slots=4, store=store,
                      max_pages_per_slot=32, name="decode")
srv = Server(enable_dcn=True)
register_disagg_decode(srv, store, engine)
srv.start("127.0.0.1", 0)
print(f"PORT={{srv.port}}", flush=True)
srv.run_until_interrupt()
"""

PREFILL = f"""
import sys
sys.path.insert(0, {REPO!r})
import jax
jax.config.update("jax_platforms", "cpu")
from brpc_tpu.kvcache import KVCacheStore
from brpc_tpu.migrate import register_disagg_prefill
from brpc_tpu.rpc.server import Server

store = KVCacheStore(page_tokens=4, page_bytes=256, max_blocks=32,
                     name="prefill")
srv = Server(enable_dcn=True)
register_disagg_prefill(srv, store, sys.argv[1])
srv.start("127.0.0.1", 0)
print(f"PORT={{srv.port}}", flush=True)
srv.run_until_interrupt()
"""


def spawn(code, *args):
    env = dict(os.environ)
    # one process per chip: the parent may hold it, so children get the CPU
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen([sys.executable, "-c", code, *args],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, env=env, text=True)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if line.startswith("PORT="):
            return proc, int(line.strip().split("=", 1)[1])
        if proc.poll() is not None:
            raise RuntimeError("child died during startup")
    proc.kill()
    raise RuntimeError("child never printed its port")


def main():
    print("starting decode process...")
    dec, dec_port = spawn(DECODE)
    print(f"  decode on 127.0.0.1:{dec_port}")
    print("starting prefill process (shipping pages to decode)...")
    pre, pre_port = spawn(PREFILL, f"127.0.0.1:{dec_port}")
    print(f"  prefill on 127.0.0.1:{pre_port}")
    try:
        from brpc_tpu.migrate import DisaggCoordinator
        co = DisaggCoordinator(f"127.0.0.1:{pre_port}",
                               f"127.0.0.1:{dec_port}")
        ta, tb = co.pair()
        print(f"paired: prefill pid {ta['pid']}, decode pid {tb['pid']}")
        prompt = list(range(50, 63))
        print(f"generate({prompt}, 8) across the split:")
        out = co.generate(prompt, 8,
                          emit=lambda t: print(f"  token {t}"))
        info = out["prefill"]
        print(f"prefill handoff: {json.dumps(info)}")
        print(f"tokens: {out['tokens']}")
        assert out["error"] is None
        assert not info["recompute_fallback"], \
            "page stream fell back to recompute"
        print(f"OK — {info['migrated_pages']} pages moved process-to-"
              f"process; the decode side never re-prefilled them")
    finally:
        pre.terminate()
        dec.terminate()
        pre.wait(timeout=10)
        dec.wait(timeout=10)


if __name__ == "__main__":
    main()

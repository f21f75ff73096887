"""Real model serving demo (ISSUE 10): a TransformerRunner — an actual
transformer whose K/V live in the paged KV cache's HBM pages — behind
``Serving.Generate``, with prefix reuse VISIBLY skipping prefill.

What it shows:

  1. the runner's paged-attention decode streaming real greedy tokens
     over the RPC stream layer (identical to the cache-less dense
     reference — printed side by side);
  2. a second identical prompt prefix-HITTING the radix tree: same
     tokens, measurably fewer prompt tokens computed (the server's
     advisory ``prefix_hit`` and the store's hit-rate both show it);
  3. a third prompt sharing only the system-prompt prefix still skips
     that shared portion.

Browse http://127.0.0.1:<port>/kvcache while it runs for pages/hit
rate, or /serving for the slot map.

With JAX_PLATFORMS=cpu the paged attention runs its gather backend;
on a TPU the same code takes the pallas scalar-prefetch kernel.
"""
import json
import os
import sys
import threading

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

import brpc_tpu as brpc
from brpc_tpu.models.runner import (TransformerConfig, TransformerRunner,
                                    dense_generate, init_runner_params,
                                    make_store_for)
from brpc_tpu.serving import DecodeEngine, register_serving


class _Collector(brpc.StreamHandler):
    def __init__(self):
        self.tokens = []
        self.done = threading.Event()

    def on_received_messages(self, stream, messages):
        for m in messages:
            d = json.loads(m)
            if "token" in d:
                self.tokens.append(d["token"])
            if d.get("done"):
                self.done.set()

    def on_closed(self, stream):
        self.done.set()


def main():
    cfg = TransformerConfig()
    params = init_runner_params(cfg)
    store = make_store_for(cfg, page_tokens=4, max_blocks=32,
                           name="llm")
    runner = TransformerRunner(params, cfg, store=store, name="llm")
    engine = DecodeEngine(runner=runner, num_slots=4, store=store,
                          max_pages_per_slot=32,
                          prefill_buckets=(8, 16, 32), name="llm")
    server = brpc.Server()
    register_serving(server, engine=engine)
    server.start("127.0.0.1", 0)
    print(f"LLM server on 127.0.0.1:{server.port} "
          f"(console: /kvcache, /serving)")
    ch = brpc.Channel(f"127.0.0.1:{server.port}", timeout_ms=10_000)

    def generate(prompt, n=8):
        col = _Collector()
        cntl = brpc.Controller()
        brpc.stream_create(cntl, col)
        resp = ch.call_sync("Serving", "Generate",
                            {"prompt": prompt, "max_new_tokens": n},
                            serializer="json", cntl=cntl)
        col.done.wait(120)
        return col.tokens, resp["prefix_hit"]

    system = [7, 99, 23, 54]                    # "system prompt" prefix
    prompt = system + [5, 17, 42, 9]

    toks, hit = generate(prompt)
    print(f"\n[1] cold generate   prefix_hit={hit:2d}  tokens={toks}")
    ref = dense_generate(params, cfg, prompt, 8)
    print(f"    dense reference (no cache, full recompute): {ref}")
    assert toks == ref, "paged decode diverged from the dense model!"

    toks2, hit2 = generate(prompt)
    print(f"[2] same prompt     prefix_hit={hit2:2d}  tokens={toks2}"
          f"   <- identical output, prefill skipped")
    assert toks2 == toks and hit2 > 0

    other = system + [61, 33, 88, 2]
    toks3, hit3 = generate(other)
    print(f"[3] shared system   prefix_hit={hit3:2d}  tokens={toks3}"
          f"   <- only the system prefix reused")

    st = store.stats()
    print(f"\nkvcache: hit_rate={st['hit_rate']}  "
          f"pages_in_use={st['pages']['pages_in_use']}  "
          f"radix_nodes={st['radix_nodes']}  cow={st['cow_forks']}")

    server.stop()
    server.join()
    engine.close()
    store.clear()
    store.close()


if __name__ == "__main__":
    main()

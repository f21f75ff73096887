"""Paged KV-cache demo (brpc_tpu/kvcache): a shared-system-prompt
workload whose radix hit-rate CLIMBS as the cache warms.

Every request opens with the same 32-token "system prompt" plus a
unique user suffix.  The first request prefills everything; once it
retires, its full pages live in the radix tree, so every later request
admits with the system prompt already cached — prefill runs only on
the suffix, and the store's hit-rate gauge climbs wave by wave.

Browse http://127.0.0.1:<port>/kvcache while it runs for hit-rate,
page occupancy, radix-tree size, and eviction/COW counters — or press
the server yourself:

    python -m brpc_tpu.tools.rpc_press --server 127.0.0.1:<port> \
        --service Serving --method Generate --streaming \
        --input '{"max_new_tokens": 4}' --shared-prefix-ratio 0.9
"""
import os
import sys
import threading

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

import brpc_tpu as brpc
from brpc_tpu.kvcache import KVCacheStore
from brpc_tpu.serving import DecodeEngine, register_serving


def main():
    store = KVCacheStore(page_tokens=16, page_bytes=1024, max_blocks=16,
                         name="demo")

    @jax.jit
    def prefill(tokens, start):        # toy prefill: just touch the suffix
        return tokens.sum()

    @jax.jit
    def step(tokens, positions, pages):  # toy LM over the page table
        return tokens + 1

    engine = DecodeEngine(step, num_slots=4, store=store,
                          prefill_fn=prefill, name="demo")
    server = brpc.Server()
    register_serving(server, engine=engine)
    server.start("127.0.0.1", 0)
    print(f"console: http://127.0.0.1:{server.port}/kvcache")

    system_prompt = list(range(500, 532))      # 2 pages of 16 tokens
    waves = 5
    per_wave = 4
    for wave in range(waves):
        done = [threading.Event() for _ in range(per_wave)]
        for i in range(per_wave):
            user = [1000 * wave + 10 * i + j for j in range(6)]
            engine.submit(system_prompt + user, 4, lambda t: None,
                          (lambda err, d=done[i]: d.set()))
        for d in done:
            d.wait(60)
        st = store.stats()
        print(f"wave {wave + 1}: hit_rate={st['hit_rate']:.2f} "
              f"hit_tokens={st['hit_tokens']} "
              f"radix_nodes={st['radix_nodes']} "
              f"pages_in_use={st['pages']['pages_in_use']}")

    print("done — later waves admit with the system prompt cached "
          "(hit-rate climbs), only the user suffix prefills")
    engine.close()
    store.close()
    server.stop()
    server.join()


if __name__ == "__main__":
    main()

"""Speculative decoding demo (ISSUE 11): the DecodeEngine's
propose -> verify -> commit mode, side by side with plain decode.

What it shows:

  1. a speculative engine (real TransformerRunner target + host-side
     NGramProposer draft) streaming EXACTLY the tokens plain greedy
     decode streams — identity is the contract, speed is the point;
  2. the speed: tokens/s plain vs speculative at draft depth 4 on the
     same machinery (the draft accepts heavily once the output
     self-repeats, so several tokens commit per verify call);
  3. the acceptance telemetry: per-generation accept_rate /
     draft_depth / tokens_per_step from the generations ring plus the
     aggregate the ``/serving/generations`` console page renders
     (printed here directly — behind a Server, the same numbers are
     one HTTP GET away; see examples/llm_server.py for the served
     variant).

With JAX_PLATFORMS=cpu the paged attention runs its gather backend;
on a TPU the same code takes the pallas scalar-prefetch kernel.
"""
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax

from brpc_tpu.models.runner import (TransformerConfig, TransformerRunner,
                                    dense_generate, init_runner_params,
                                    make_store_for)
from brpc_tpu.serving import DecodeEngine, NGramProposer
from brpc_tpu import serving as srv


def generate(eng, prompt, n):
    toks, ev = [], threading.Event()
    eng.submit(prompt, n, toks.append, lambda e: ev.set())
    assert ev.wait(600), "generation hung"
    return toks


def build(cfg, params, tag, draft=None):
    store = make_store_for(cfg, page_tokens=8, max_blocks=64,
                           name=f"{tag}_kv")
    runner = TransformerRunner(params, cfg, store=store, name=f"{tag}_m")
    kw = dict(draft_runner=draft, draft_len=4) if draft else {}
    eng = DecodeEngine(runner=runner, num_slots=2, store=store,
                       max_pages_per_slot=24, prefill_buckets=(16, 32),
                       name=f"{tag}_eng", **kw)
    return store, eng


def main():
    cfg = TransformerConfig()
    params = init_runner_params(cfg)
    prompt = [5, 17, 42, 9, 77, 3]
    n = 48

    print("=== 1. identity: speculative == plain greedy ===")
    oracle = dense_generate(params, cfg, prompt, 12)
    sp_store, sp_eng = build(cfg, params, "spec", NGramProposer())
    pl_store, pl_eng = build(cfg, params, "plain")
    spec = generate(sp_eng, prompt, 12)
    print(f"  plain greedy : {oracle}")
    print(f"  speculative  : {spec}")
    assert spec == oracle, "speculation changed the output!"
    print("  identical — the draft changes cost, never output\n")

    print(f"=== 2. speed: {n}-token generation, plain vs depth-4 draft ===")
    generate(pl_eng, prompt, n)        # warm both jit paths
    generate(sp_eng, prompt, n)
    t0 = time.monotonic()
    generate(pl_eng, prompt, n)
    plain_s = time.monotonic() - t0
    t0 = time.monotonic()
    generate(sp_eng, prompt, n)
    spec_s = time.monotonic() - t0
    print(f"  plain       : {n / plain_s:7.1f} tok/s")
    print(f"  speculative : {n / spec_s:7.1f} tok/s "
          f"({plain_s / spec_s:.2f}x)\n")

    print("=== 3. acceptance telemetry ===")
    rec = [r for r in srv.recent_generations(64)
           if r.get("engine") == "spec_eng" and "accept_rate" in r][-1]
    print(f"  accept_rate={rec['accept_rate']} "
          f"draft_depth={rec['draft_depth']} "
          f"tokens_per_step={rec['tokens_per_step']} "
          f"({rec['spec_accepted']}/{rec['spec_proposed']} drafts "
          f"accepted)")
    agg = srv.generations_snapshot()["aggregates"]["speculative"]
    print(f"  /serving/generations aggregate: {agg}")

    for store, eng in ((sp_store, sp_eng), (pl_store, pl_eng)):
        eng.close()
        store.clear()
        store.close()


if __name__ == "__main__":
    main()

"""chip_smoke.py — the three served paths, once, on the chip.

The quickest proof that the system still starts on a TPU: one process
drives the entry points a user calls (``brpc.Server``, ``brpc.Channel``,
``register_serving``, ``PSClient``) over loopback at the full width of
the serving stand-in and a device-sized embedding table, and checks
what comes out against the repo's own references ON THE CHIP:

  1. tensor echo and stream  a 64 MB unary ``serializer="tensor"`` echo
                             and 32 x 4 MB stream chunks both ways over
                             the rail: byte-equal, device-resident,
                             zero host copies, distinct buffers;
  2. LLM serving             ``TransformerRunner`` behind
                             ``Serving.Generate`` at d_model 2048,
                             16 x 128 heads, 16 layers, vocab 50304:
                             cold / repeated / shared-prefix /
                             speculative generations equal to
                             ``dense_generate``, first-step logits
                             within ``LOGITS_ATOL`` of the dense
                             forward, kernel backend against gather
                             backend on the same pages;
  3. parameter server        one ``EmbeddingShardServer`` holding 1 GiB
                             of rows plus both Adam slots behind
                             ``register_psserve``: Zipf ``Lookup``s and
                             two optimizer ``Update``s equal to the
                             dense oracle, applied exactly once.

``--chips 4`` runs ONLY what exists across chips and what it is
compared with: a rail echo between two different chips, a 4-way
fan-out lowered to a collective against the same fan-out over sockets,
four PS shards one per chip against the lowered table and the oracle,
and the runner tensor-parallel over four chips against one chip.

Each phase is a plain function of its sizes (``tests/test_chip_smoke.py``
calls them at toy size on the virtual CPU mesh).  ``main`` refuses any
platform but a TPU, and any phase that raises, times out or ends a
generation with an ``"error"`` terminal makes the exit code non-zero.
The last line of stdout is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time

import numpy as np

# first-step logits of the paged path against the dense forward, both at
# full float32 matmul precision (models/runner.py:_float32_matmuls):
# what is left is accumulation order over 2048..8192-term dot products
# through 16 layers, on logits of magnitude ~4
LOGITS_ATOL = 1e-3
# kernel backend against gather backend on the same pages, one layer
KERNEL_ATOL = 1e-5
# arguments + temporaries + the un-stacked page buffers must leave this
# share of the device's memory free
MEMORY_HEADROOM = 0.10

PHASE_TIMEOUT_S = 900.0


class SmokeError(AssertionError):
    """A phase saw something wrong (assertions are stripped by -O)."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds jax spent in backend compiles (persistent-cache hits
    included: a hit is what makes the second run's number collapse)."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> tuple:
        return self.seconds, self.cache_hits


def device_memory(device) -> dict:
    """bytes_in_use / peak / limit where the backend reports them."""
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


# ---------------------------------------------------------------------------
# phase 1 (and four-chip a): tensor echo and stream over the rail
# ---------------------------------------------------------------------------

def phase_tensor_echo(*, client_device, server_device, unary_bytes: int,
                      n_chunks: int, chunk_bytes: int,
                      seed: int = 0) -> dict:
    """The examples/streaming_echo.py flow at the given sizes: a unary
    tensor echo and ``n_chunks`` stream chunks both ways between
    ``client_device`` and ``server_device`` (the same chip on a
    one-chip host, two chips for the cross-chip rung)."""
    import jax
    import jax.numpy as jnp

    import brpc_tpu as brpc
    from brpc_tpu.ici import endpoint, rail

    window = max(4 * chunk_bytes, 2 * 1024 * 1024)

    class TensorEcho(brpc.Service):
        NAME = "SmokeTensorEcho"

        @brpc.method(request="tensor", response="tensor")
        def Echo(self, cntl, req):
            return req

        @brpc.method(request="json", response="json")
        def OpenTensor(self, cntl, req):
            cntl.accept_stream(lambda stream, payload: stream.write(payload),
                               max_buf_size=window, device=server_device)
            return {"accepted": True}

    server = brpc.Server(ici_device=server_device)
    server.add_service(TensorEcho())
    server.start("127.0.0.1", 0)
    stream = None
    try:
        ch = brpc.Channel(f"127.0.0.1:{server.port}", timeout_ms=300_000)
        x = jax.device_put(
            jax.random.normal(jax.random.PRNGKey(seed),
                              (unary_bytes // 4,), jnp.float32),
            client_device)
        chunks = [jax.device_put(
            jnp.full((chunk_bytes // 4,), float(i), jnp.float32),
            client_device) for i in range(n_chunks)]
        jax.block_until_ready([x, chunks])
        links0 = endpoint.link_stats()
        host0 = rail.host_copy_count()

        # -- the fence: does block_until_ready wait for the device? --
        fence = _fence_check(client_device)

        # -- unary echo --
        t0 = time.monotonic()
        out = ch.call_sync("SmokeTensorEcho", "Echo", x, serializer="tensor")
        jax.block_until_ready(out)
        unary_s = time.monotonic() - t0
        check(isinstance(out, jax.Array), "unary echo returned no device "
              f"array but {type(out).__name__}")
        check(out.devices() == {client_device},
              f"unary echo landed on {out.devices()}, not the caller's "
              f"{client_device}")
        check(out.shape == x.shape and out.dtype == x.dtype
              and bool(jnp.array_equal(out, x)),
              "unary echo is not byte-equal to what was sent")
        check(out.unsafe_buffer_pointer() != x.unsafe_buffer_pointer(),
              "unary echo aliases the request buffer: nothing moved")

        # -- stream, both ways --
        back: list = []
        done = threading.Event()

        def on_chunk(_stream, payload):
            back.append(payload)
            if len(back) == n_chunks:
                done.set()

        cntl = brpc.Controller()
        stream = brpc.stream_create(cntl, on_chunk, max_buf_size=window,
                                    device=client_device)
        ch.call_sync("SmokeTensorEcho", "OpenTensor", {}, serializer="json",
                     cntl=cntl)
        t0 = time.monotonic()
        for c in chunks:
            stream.write(c, timeout_s=120.0)
        check(done.wait(300.0),
              f"stream returned {len(back)}/{n_chunks} chunks in 300 s")
        jax.block_until_ready(back)
        stream_s = time.monotonic() - t0
        pointers = set()
        for i, (sent, got) in enumerate(zip(chunks, back)):
            check(isinstance(got, jax.Array)
                  and got.devices() == {client_device},
                  f"chunk {i} came back off the caller's device")
            check(bool(jnp.array_equal(got, sent)),
                  f"chunk {i} is not byte-equal to what was sent")
            pointers.add(got.unsafe_buffer_pointer())
            pointers.add(sent.unsafe_buffer_pointer())
        check(len(pointers) == 2 * n_chunks,
              f"{2 * n_chunks} sent+returned chunks share "
              f"{len(pointers)} buffers")
        host_copies = rail.host_copy_count() - host0
        check(host_copies == 0, f"{host_copies} host copies on the rail")
        links = endpoint.link_stats()
        return {
            "unary_bytes": int(x.nbytes), "unary_s": unary_s,
            "stream_bytes": n_chunks * chunk_bytes, "stream_s": stream_s,
            "host_copies": host_copies,
            "same_device_copies": links["same_device_copies"]
            - links0["same_device_copies"],
            "cross_device_moves": links["cross_device_moves"]
            - links0["cross_device_moves"],
            "fence": fence,
        }
    finally:
        if stream is not None:
            stream.close()
        server.stop()
        server.join()


def _fence_check(device) -> dict:
    """Queue a chain of matmuls, then time dispatch, block_until_ready
    and a scalar readback AFTER it.  Where block_until_ready fences,
    the readback finds the work done and returns at once."""
    import jax
    import jax.numpy as jnp
    n = 2048 if device.platform == "tpu" else 128

    @jax.jit
    def chain(x, w):
        for _ in range(32):
            x = jnp.tanh(x @ w)
        return x

    with jax.default_device(device):
        w = jnp.full((n, n), 1.0 / n, jnp.float32)
        x = jnp.ones((n, n), jnp.float32)
    float(chain(x, w)[0, 0])          # compile + warm, readback included
    t0 = time.monotonic()
    y = chain(chain(chain(x, w), w), w)
    t_dispatch = time.monotonic() - t0
    y.block_until_ready()
    t_block = time.monotonic() - t0
    float(y[0, 0])
    t_read = time.monotonic() - t0 - t_block
    return {"dispatch_s": t_dispatch, "block_until_ready_s": t_block,
            "readback_after_s": t_read}


# ---------------------------------------------------------------------------
# phase 2 (and four-chip d): LLM serving
# ---------------------------------------------------------------------------

def smoke_prompts(vocab: int, prompt_len: int, page_tokens: int,
                  seed: int) -> dict:
    """Four seeded prompts: ``cold`` (random), ``shared`` (cold's first
    two pages, then its own tokens) and ``spec`` (a repeated motif, so
    the n-gram proposer always has an earlier occurrence to draft
    from)."""
    rng = np.random.default_rng(seed)
    cold = rng.integers(1, vocab, prompt_len).tolist()
    shared = cold[:2 * page_tokens] + rng.integers(
        1, vocab, prompt_len - 2 * page_tokens).tolist()
    motif = rng.integers(1, vocab, 8).tolist()
    spec = (motif * (prompt_len // 8 + 1))[:prompt_len]
    return {"cold": cold, "shared": shared, "spec": spec}


class _Collector:
    """Stream handler for one generation: tokens, and the terminal."""

    def __init__(self):
        self.tokens: list = []
        self.terminal = None
        self.done = threading.Event()

    def on_received_messages(self, stream, messages):
        for m in messages:
            d = json.loads(m)
            if "token" in d:
                self.tokens.append(int(d["token"]))
            if d.get("done"):
                self.terminal = d
                self.done.set()

    def on_idle_timeout(self, stream):
        pass

    def on_closed(self, stream):
        self.done.set()


def generate(channel, prompt, max_new_tokens: int, *,
             speculative: bool, timeout_s: float = 600.0) -> tuple:
    """One ``Serving.Generate`` call; returns (tokens, prefix_hit).  A
    terminal that carries ``"error"``, a stream that closes without a
    terminal, or a timeout is a failure, never an empty generation."""
    import brpc_tpu as brpc
    col = _Collector()
    cntl = brpc.Controller()
    brpc.stream_create(cntl, col)
    resp = channel.call_sync(
        "Serving", "Generate",
        {"prompt": [int(t) for t in prompt],
         "max_new_tokens": int(max_new_tokens),
         "speculative": bool(speculative)},
        serializer="json", cntl=cntl)
    check(resp.get("accepted") is True, f"Generate not accepted: {resp}")
    check(col.done.wait(timeout_s),
          f"generation still running after {timeout_s:.0f} s "
          f"({len(col.tokens)} tokens so far)")
    check(col.terminal is not None,
          "stream closed without a terminal message")
    check("error" not in col.terminal,
          f"generation ended in an error terminal: {col.terminal}")
    check(len(col.tokens) == max_new_tokens,
          f"{len(col.tokens)} tokens streamed, {max_new_tokens} asked")
    return col.tokens, int(resp["prefix_hit"])


def phase_llm_serving(*, cfg, seed: int, page_tokens: int, num_slots: int,
                      max_pages_per_slot: int, cache_blocks: int,
                      prompt_len: int, new_tokens: int,
                      prefill_buckets: tuple, attn_backend=None,
                      mesh=None, requests=("cold", "warm", "shared",
                                           "spec")) -> dict:
    """The examples/llm_server.py flow: seeded weights, a
    ``TransformerRunner`` behind ``Serving.Generate``, each generation
    compared with ``dense_generate`` token for token, first-step logits
    with ``dense_logits``, and the kernel with the gather backend on
    the pages the run left behind.  ``mesh`` places the parameters
    tensor-parallel (the four-chip rung)."""
    import jax
    import jax.numpy as jnp

    import brpc_tpu as brpc
    from brpc_tpu.models.runner import (TransformerRunner, dense_generate,
                                        dense_logits, init_runner_params,
                                        make_store_for, run_prefill)
    from brpc_tpu.ops.paged_attention import (arena_kv_view,
                                              default_backend,
                                              paged_attention)
    from brpc_tpu.serving import DecodeEngine, register_serving
    from brpc_tpu.serving import engine as engine_mod
    from brpc_tpu.serving.speculative import NGramProposer

    device = jax.devices()[0]
    t0 = time.monotonic()
    params = init_runner_params(cfg, jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    param_bytes = sum(int(v.nbytes) for v in params.values())
    store = make_store_for(cfg, page_tokens=page_tokens,
                           max_blocks=cache_blocks, device=device,
                           name="smoke_kv")
    runner = TransformerRunner(params, cfg, store=store, mesh=mesh,
                               attn_backend=attn_backend, name="smoke_llm")
    backend = attn_backend or default_backend()
    cache_bytes = cache_blocks * store.pagepool.page_bytes \
        * store.pagepool.pages_per_block
    log(f"  params {param_bytes / 2**30:.2f} GiB, cache "
        f"{cache_bytes / 2**20:.0f} MiB ({cache_blocks} block(s) of "
        f"{store.pagepool.pages_per_block} x {page_tokens}-token pages), "
        f"attention backend: {backend}, init {time.monotonic() - t0:.1f} s")

    # size check BEFORE the first step: what the compiler says the
    # decode step needs beside what is already resident
    t0 = time.monotonic()
    mem = runner.compile_step(num_slots, max_pages_per_slot) \
        .memory_analysis()
    need = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes + cache_bytes
    limit = device_memory(device)["bytes_limit"]
    log(f"  decode step compiled in {time.monotonic() - t0:.1f} s: "
        f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB + "
        f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB + page "
        f"buffers under the restack {cache_bytes / 1e9:.2f} GB = "
        f"{need / 1e9:.2f} GB of "
        f"{'unreported' if limit is None else f'{limit / 1e9:.2f} GB'}")
    if limit is not None:
        check(need <= (1.0 - MEMORY_HEADROOM) * limit,
              f"decode step needs {need / 1e9:.2f} GB, over "
              f"{1.0 - MEMORY_HEADROOM:.0%} of the device's "
              f"{limit / 1e9:.2f} GB: shrink the cache")

    engine = DecodeEngine(runner=runner, num_slots=num_slots, store=store,
                          max_pages_per_slot=max_pages_per_slot,
                          prefill_buckets=prefill_buckets,
                          draft_runner=NGramProposer(), draft_len=4,
                          name="smoke_llm")
    server = brpc.Server()
    register_serving(server, engine=engine)
    server.start("127.0.0.1", 0)
    out: dict = {"backend": backend, "param_bytes": param_bytes,
                 "cache_bytes": cache_bytes, "step_need_bytes": int(need),
                 "generations": {}}
    try:
        ch = brpc.Channel(f"127.0.0.1:{server.port}", timeout_ms=600_000)
        prompts = smoke_prompts(cfg.vocab, prompt_len, page_tokens, seed)
        prompts["warm"] = prompts["cold"]
        refs: dict = {}
        for name in requests:
            prompt = prompts[name]
            spec = name == "spec"
            proposed0 = engine_mod.SPEC_PROPOSED.get_value()
            t0 = time.monotonic()
            tokens, hit = generate(ch, prompt, new_tokens,
                                   speculative=spec)
            gen_s = time.monotonic() - t0
            key = tuple(prompt)
            if key not in refs:
                refs[key] = dense_generate(params, cfg, prompt, new_tokens)
            ref = refs[key]
            if tokens != ref:
                first = next(i for i, (a, b) in
                             enumerate(zip(tokens, ref)) if a != b)
                lg = np.asarray(dense_logits(
                    params, cfg, list(prompt) + ref[:first]))
                raise SmokeError(
                    f"{name}: token {first} is {tokens[first]} on the "
                    f"paged path, {ref[first]} on the dense reference "
                    f"(dense logits there: {lg[tokens[first]]:.6f} vs "
                    f"{lg[ref[first]]:.6f})")
            proposed = engine_mod.SPEC_PROPOSED.get_value() - proposed0
            if name == "warm":
                check(hit > 0, "repeated prompt reported prefix_hit 0")
            if name == "shared":
                check(hit == 2 * page_tokens,
                      f"shared-prefix prompt hit {hit} tokens, expected "
                      f"its first two pages ({2 * page_tokens})")
            if spec:
                check(proposed > 0, "the speculative generation never "
                      "proposed a draft: verify did not run")
            else:
                check(proposed == 0, f"{name}: a plain generation "
                      f"proposed {proposed} draft tokens")
            out["generations"][name] = {
                "seconds": gen_s, "prefix_hit": hit,
                "draft_tokens_proposed": proposed}
            log(f"  {name:6s} {len(prompt)}+{new_tokens} tokens in "
                f"{gen_s:.2f} s, prefix_hit {hit}, drafts proposed "
                f"{proposed}: equal to dense_generate")

        # first-step logits, paged against dense, on the pages the cold
        # generation committed
        prompt = prompts["cold"]
        seq = store.admit(prompt)
        try:
            run_prefill(runner, seq, prompt, buckets=prefill_buckets,
                        max_pages=max_pages_per_slot)
            pages = np.full((num_slots, max_pages_per_slot), -1, np.int32)
            ids = seq.page_ids()
            pages[0, :len(ids)] = ids
            tok = np.zeros((num_slots,), np.int32)
            pos = np.zeros((num_slots,), np.int32)
            tok[0], pos[0] = prompt[-1], len(prompt)
            paged = np.asarray(runner.step_logits(tok, pos, pages))[0]
            dense = np.asarray(dense_logits(params, cfg, prompt))
            check(paged.shape == (cfg.vocab,) and np.isfinite(paged).all(),
                  "paged first-step logits are not finite [vocab]")
            diff = float(np.max(np.abs(paged - dense)))
            top2 = np.sort(dense)[-2:]
            out["logits_max_abs_diff"] = diff
            out["dense_top2_gap"] = float(top2[1] - top2[0])
            log(f"  first-step logits: max |paged - dense| = {diff:.3e} "
                f"(tolerance {LOGITS_ATOL:g}; dense top-2 gap "
                f"{top2[1] - top2[0]:.3e})")
            check(diff <= LOGITS_ATOL,
                  f"first-step logits differ by {diff:.3e} > "
                  f"{LOGITS_ATOL:g}")
            check(int(paged.argmax()) == int(dense.argmax()),
                  "first-step argmax differs between paged and dense")

            # kernel backend against gather backend, same pages
            kv = arena_kv_view(store.pagepool.arena(), page_tokens,
                                  cfg.n_layers, cfg.n_kv_heads,
                                  cfg.head_dim)
            flat = np.asarray(store.pagepool.flat_ids(pages.ravel()),
                              np.int32).reshape(pages.shape)
            q = jax.random.normal(
                jax.random.PRNGKey(seed + 1),
                (num_slots, cfg.n_heads, cfg.head_dim), jnp.float32)
            lengths = np.zeros((num_slots,), np.int32)
            lengths[0] = len(prompt)

            def attend(backend_name):
                with jax.default_matmul_precision("highest"):
                    return np.asarray(jax.jit(
                        paged_attention,
                        static_argnames=("backend",))(
                            q, kv[:, :, 0, 0], kv[:, :, 0, 1],
                            jnp.asarray(flat), jnp.asarray(lengths),
                            backend=backend_name))
            kern, gath = attend("pallas"), attend("gather")
            kdiff = float(np.max(np.abs(kern - gath)))
            out["kernel_vs_gather_max_abs_diff"] = kdiff
            log(f"  paged kernel against gather backend on the same "
                f"pages: max |diff| = {kdiff:.3e} "
                f"(tolerance {KERNEL_ATOL:g})")
            check(np.isfinite(kern).all() and kdiff <= KERNEL_ATOL,
                  f"kernel and gather backends differ by {kdiff:.3e}")
            check(float(np.abs(kern[0]).max()) > 0.0
                  and float(np.abs(kern[1:]).max()) == 0.0,
                  "kernel rows: the live row must attend, empty rows "
                  "must be zeros")
        finally:
            store.retire(seq, cache=False)
        out["tokens"] = {k: refs[tuple(prompts[k])] for k in requests}
        return out
    finally:
        server.stop()
        server.join()
        engine.close()
        store.clear()
        store.close()


# ---------------------------------------------------------------------------
# phase 3 (and four-chip c): the parameter server
# ---------------------------------------------------------------------------

def _ps_oracle(table: np.ndarray, updates: list, spec) -> tuple:
    """The dense oracle of tests/test_psserve.py and test_train.py on
    the rows the updates touch: ``oracle_apply`` is per-row math, so
    the touched rows of the full table equal the oracle run on just
    those rows.  Returns (unique keys, their expected rows)."""
    from brpc_tpu.train.optimizer import oracle_apply, zero_slots
    uniq = np.unique(np.concatenate([k for k, _ in updates]))
    rows = table[uniq].copy()
    slots = zero_slots(spec, uniq.shape[0], table.shape[1]) \
        if spec is not None else None
    for keys, grads in updates:
        local = np.searchsorted(uniq, keys)
        if spec is None:
            np.add.at(rows, local, grads)
        else:
            rows, slots = oracle_apply(rows, slots, local, grads, spec)
    return uniq, rows


def _lookup_all(lookup, keys: np.ndarray, dim: int) -> np.ndarray:
    """``lookup`` over any number of keys, one request per largest key
    bucket (a request past it is refused, by design)."""
    from brpc_tpu.psserve.shard import DEFAULT_KEY_BUCKETS
    step = DEFAULT_KEY_BUCKETS[-1]
    rows = np.empty((keys.shape[0], dim), np.float32)
    for i in range(0, keys.shape[0], step):
        rows[i:i + step] = lookup(keys[i:i + step])
    return rows


def phase_parameter_server(*, vocab: int, dim: int, seed: int,
                           key_counts: tuple, devices: list,
                           lowered_mesh=None) -> dict:
    """``len(devices)`` ``EmbeddingShardServer``s, shard i on
    ``devices[i]``, behind ``register_psserve`` and a ``PSClient`` on
    the tensorframe wire: Zipf lookups across the key buckets, one
    integer-gradient scatter-add (exact) and two Adam updates, all
    equal to the dense oracle, each applied exactly once.  With
    ``lowered_mesh`` the same operations also run on a
    ``ShardedEmbeddingTable`` over that mesh."""
    from brpc_tpu.psserve import (PSClient, ShardedEmbeddingTable,
                                  init_embedding_table)
    from brpc_tpu.tools.rpc_press import (spin_up_psserve,
                                          tear_down_psserve,
                                          zipf_key_sampler)
    from brpc_tpu.train import OptimizerSpec

    n = len(devices)
    t0 = time.monotonic()
    table = init_embedding_table(vocab, dim, seed)
    servers, svcs, shards, pc = spin_up_psserve(
        n, vocab=vocab, dim=dim, devices=devices, table=table,
        name_prefix="smoke")
    placed = [next(iter(sh._rows.devices())) for sh in shards]
    log(f"  {n} shard(s) of {vocab} x {dim} float32 "
        f"({table.nbytes / 2**30:.2f} GiB) on {placed}, "
        f"load {time.monotonic() - t0:.1f} s")
    check(len(set(placed)) == n and placed == list(devices),
          f"shards landed on {placed}, asked for {list(devices)}")
    cli = PSClient(pc, vocab=vocab, dim=dim, timeout_ms=300_000,
                   name="smoke_ps")
    spec = OptimizerSpec("adam", lr=0.01)
    sample = zipf_key_sampler(vocab, 1.0, seed)
    rng = np.random.default_rng(seed + 1)
    out = {"devices": [str(d) for d in placed]}
    try:
        # -- lookups across the key buckets --
        t0 = time.monotonic()
        for kc in key_counts:
            keys = sample(kc)
            rows = cli.lookup(keys)
            check(rows.shape == (kc, dim) and rows.dtype == np.float32,
                  f"lookup of {kc} keys returned {rows.shape}")
            check(np.array_equal(rows, table[keys]),
                  f"lookup of {kc} keys differs from the table")
        out["lookup_s"] = time.monotonic() - t0

        # -- one exact scatter-add, then two Adam updates --
        kc = key_counts[-1]
        plain = (sample(kc),
                 rng.integers(-3, 4, (kc, dim)).astype(np.float32))
        t0 = time.monotonic()
        acks = cli.update(*plain)
        check(set(acks.values()) == {1},
              f"first update acked versions {acks}, expected 1")
        uniq, want = _ps_oracle(table, [plain], None)
        check(np.array_equal(_lookup_all(cli.lookup, uniq, dim), want),
              "rows after the scatter-add differ from the oracle")
        base = table.copy()
        base[uniq] = want
        waves = [(sample(kc),
                  rng.standard_normal((kc, dim)).astype(np.float32))
                 for _ in range(2)]
        # our own update tokens (minted ones stay below 2**48), so the
        # last wave can be replayed under the same ids
        tokens = [(1 << 49) + i for i in range(len(waves))]
        for token, (keys, grads) in zip(tokens, waves):
            cli.update(keys, grads, update_token=token, optimizer=spec)
        out["update_s"] = time.monotonic() - t0
        uniq, want = _ps_oracle(base, waves, spec)
        got = _lookup_all(cli.lookup, uniq, dim)
        err = float(np.max(np.abs(got - want)))
        out["adam_max_abs_err"] = err
        check(err <= 1e-6,
              f"rows after two Adam updates differ from the oracle by "
              f"{err:.3e}")
        # exactly once: replaying the last logical update changes
        # nothing and advances no version
        versions = [sh.version for sh in shards]
        cli.update(*waves[-1], update_token=tokens[-1], optimizer=spec)
        check([sh.version for sh in shards] == versions,
              "a replayed update advanced a shard's version")
        check(np.array_equal(_lookup_all(cli.lookup, uniq, dim), got),
              "a replayed update changed the rows")
        untouched = np.setdiff1d(sample(kc), uniq)[:64]
        check(np.array_equal(cli.lookup(untouched), base[untouched]),
              "rows no update touched have changed")
        for sh in shards:
            check(sh.stats()["opt_slots"] == ["m", "t", "v"],
                  f"shard {sh.shard_index} holds slots "
                  f"{sh.stats()['opt_slots']}, expected both Adam slots")
        check(cli.n_stale_reads == 0,
              f"{cli.n_stale_reads} stale reads after acked updates")
        log(f"  lookups {out['lookup_s']:.2f} s, updates "
            f"{out['update_s']:.2f} s, Adam max |err| {err:.2e}, "
            f"versions {versions}")

        if lowered_mesh is not None:
            lowered = ShardedEmbeddingTable(vocab, dim, mesh=lowered_mesh,
                                            table=table, name="smoke_low")
            keys = sample(kc)
            rows, _ver = lowered.lookup(keys)
            check(np.array_equal(np.asarray(rows), table[keys]),
                  "lowered lookup differs from the table")
            lowered.update(*plain)
            for keys, grads in waves:
                lowered.update(keys, grads, optimizer=spec)
            rows = _lookup_all(lambda k: np.asarray(lowered.lookup(k)[0]),
                               uniq, dim)
            lerr = float(np.max(np.abs(rows - want)))
            out["lowered_max_abs_err"] = lerr
            check(lerr <= 1e-6,
                  f"lowered table differs from the oracle by {lerr:.3e}")
            log(f"  lowered table over {dict(lowered_mesh.shape)}: "
                f"max |err| {lerr:.2e}")
        return out
    finally:
        cli.close()
        tear_down_psserve(servers, svcs, pc)


# ---------------------------------------------------------------------------
# four-chip b: fan-out lowered to a collective against sockets
# ---------------------------------------------------------------------------

def phase_collective_fanout(*, devices: list, n_elems: int) -> dict:
    """The same ``ParallelChannel`` fan-out twice — over ``IciChannel``s
    (lowered through ``CollectiveGroup`` to one compiled program) and
    over loopback sockets to one server per chip — and
    ``CollectiveGroup.partition_apply`` against a ``PartitionChannel``
    that slices the request over the same servers."""
    import jax
    import jax.numpy as jnp

    import brpc_tpu as brpc
    from brpc_tpu.ici import IciChannel, collective, register_device_service
    from brpc_tpu.rpc.combo_channels import (CallMapper, PartitionChannel,
                                             ResponseMerger, SubCall,
                                             _collective_group_for)

    n = len(devices)

    def fn(x):
        return jnp.tanh(x) * 3.0 + 1.0

    register_device_service("SmokeFan", "Apply", fn)

    class Fan(brpc.Service):
        NAME = "SmokeFan"

        @brpc.method(request="tensor", response="tensor")
        def Apply(self, cntl, req):
            return fn(req)

    class Slice(CallMapper):
        def map(self, i, count, request):
            step = request.shape[0] // count
            return SubCall(request[i * step:(i + 1) * step])

    class Concat(ResponseMerger):
        def merge(self, responses):
            return jnp.concatenate(
                [jax.device_put(r, devices[0]) for r in responses])

    servers = []
    try:
        lowered_sum = brpc.ParallelChannel(response_merger=brpc.SumMerger())
        lowered_stack = brpc.ParallelChannel()
        socket_sum = brpc.ParallelChannel(response_merger=brpc.SumMerger())
        socket_stack = brpc.ParallelChannel()
        socket_part = PartitionChannel(n, call_mapper=Slice(),
                                       response_merger=Concat())
        for i, dev in enumerate(devices):
            lowered_sum.add_channel(IciChannel(f"ici://slice0/{i}"))
            lowered_stack.add_channel(IciChannel(f"ici://slice0/{i}"))
            s = brpc.Server(ici_device=dev)
            s.add_service(Fan())
            s.start("127.0.0.1", 0)
            servers.append(s)
            for pc in (socket_sum, socket_stack):
                pc.add_channel(brpc.Channel(f"127.0.0.1:{s.port}",
                                            timeout_ms=120_000))
            socket_part.add_partition(
                i, brpc.Channel(f"127.0.0.1:{s.port}", timeout_ms=120_000))
        x = jax.device_put(
            jnp.linspace(-2.0, 2.0, n_elems, dtype=jnp.float32),
            devices[0])
        calls0 = collective._lowered_calls.get_value()
        want = np.asarray(fn(x))

        def same(a, b):
            # one fused program against op-by-op dispatch of the same
            # function: equal up to the last float32 digit
            return np.allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)

        got = lowered_sum.call_sync("SmokeFan", "Apply", x)
        sock = socket_sum.call_sync("SmokeFan", "Apply", x,
                                    serializer="tensor")
        check(same(got, n * want),
              "psum-lowered fan-out differs from n x fn(x)")
        check(same(got, sock),
              "psum-lowered fan-out differs from the socket fan-out")

        got = lowered_stack.call_sync("SmokeFan", "Apply", x)
        sock = socket_stack.call_sync("SmokeFan", "Apply", x,
                                      serializer="tensor")
        check(len(got) == n and len(sock) == n,
              f"stacked fan-out returned {len(got)} / {len(sock)} "
              f"responses for {n} chips")
        for i in range(n):
            check(same(got[i], sock[i]) and same(got[i], want),
                  f"stacked fan-out: chip {i} differs from fn(x)")

        group = _collective_group_for(list(devices))
        part = group.partition_apply(fn, x, merge="concat")
        sock = socket_part.call_sync("SmokeFan", "Apply", x,
                                     serializer="tensor")
        check(same(part, want) and same(sock, want),
              "partitioned fan-out (lowered or socket) differs from fn(x)")
        lowered_calls = collective._lowered_calls.get_value() - calls0
        check(lowered_calls == 3,
              f"{lowered_calls} collective programs ran, expected 3")
        return {"chips": n, "lowered_calls": lowered_calls,
                "elements": n_elems}
    finally:
        for s in servers:
            s.stop()
            s.join()


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------

def full_width_config():
    from brpc_tpu.models.runner import TransformerConfig
    return TransformerConfig(vocab=50304, d_model=2048, n_layers=16,
                             n_heads=16, n_kv_heads=16, head_dim=128,
                             d_ff=8192)


# KV cache, in blocks of one 16-token page (4 MiB).  Compiled for a v5e
# the decode step's temporaries are a STEP function of the page count
# (the uint8 -> float32 view of the token-major arena puts the page
# axis on a 128-wide tile, ROADMAP S1): 2.7 GB up to 128 pages, 8.6 GB
# from 129 to 256.  Beside 3.6 GB of float32 weights, the stacked arena
# and the page buffers under it, 128 pages (512 MiB) make 7.4 GB of the
# chip's 16 and 256 pages (1 GiB) 14.4 GB, which leaves the allocator
# no room; so 128.
CACHE_BLOCKS = 128
SERVING_SIZES = dict(page_tokens=16, num_slots=8, max_pages_per_slot=64,
                     prompt_len=72, new_tokens=32,
                     prefill_buckets=(16, 64, 128))
PS_SIZES = dict(vocab=2_097_152, dim=128, key_counts=(8, 100, 512))


def run_one_chip(seed: int, timed) -> None:
    import jax
    dev = jax.devices()[0]
    r = timed("tensor echo and stream", phase_tensor_echo,
              client_device=dev, server_device=dev,
              unary_bytes=64 * 2**20, n_chunks=32, chunk_bytes=4 * 2**20,
              seed=seed)
    check(r["same_device_copies"] > 0,
          "the rail made no same-device copy on a one-chip host")
    log(f"  unary {r['unary_bytes'] / 2**20:.0f} MiB in {r['unary_s']:.3f}"
        f" s, stream {r['stream_bytes'] / 2**20:.0f} MiB in "
        f"{r['stream_s']:.3f} s, host copies {r['host_copies']}, "
        f"same-device copies {r['same_device_copies']}, fence {r['fence']}")
    timed("LLM serving at full width", phase_llm_serving,
          cfg=full_width_config(), seed=seed, cache_blocks=CACHE_BLOCKS,
          **SERVING_SIZES)
    timed("parameter server", phase_parameter_server, seed=seed,
          devices=[dev], **PS_SIZES)


def run_four_chips(seed: int, timed) -> None:
    import jax

    from brpc_tpu.models.runner import make_tp_mesh
    devs = jax.devices()[:4]
    r = timed("rail echo between two chips", phase_tensor_echo,
              client_device=devs[0], server_device=devs[1],
              unary_bytes=64 * 2**20, n_chunks=32, chunk_bytes=4 * 2**20,
              seed=seed)
    check(r["cross_device_moves"] > 0,
          "no cross-device move between two different chips")
    log(f"  cross_device_moves {r['cross_device_moves']}, host copies "
        f"{r['host_copies']}, unary {r['unary_s']:.3f} s, stream "
        f"{r['stream_s']:.3f} s")
    timed("fan-out lowered to a collective", phase_collective_fanout,
          devices=devs, n_elems=1 << 20)
    timed("four PS shards, one per chip", phase_parameter_server,
          seed=seed, devices=devs, lowered_mesh=make_tp_mesh(4),
          **PS_SIZES)
    # last in line: the longest compile, and nothing after it depends
    # on it
    sizes = dict(SERVING_SIZES, new_tokens=16)
    common = dict(cfg=full_width_config(), seed=seed, cache_blocks=64,
                  requests=("cold",), **sizes)
    one = timed("runner on one chip", phase_llm_serving, **common)
    tp = timed("runner tensor-parallel over four chips", phase_llm_serving,
               mesh=make_tp_mesh(4), **common)
    check(tp["tokens"] == one["tokens"],
          "tensor-parallel tokens differ from one chip's")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the cross-chip phases")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax found {first.platform!r} "
              f"({first.device_kind}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from brpc_tpu.ici import rail
    from brpc_tpu.ici.mesh import COMPILE_CACHE_ENV, ensure_compile_cache
    cache_dir = ensure_compile_cache() or os.environ[COMPILE_CACHE_ENV]
    log(f"chip_smoke: {len(devices)} x {first.device_kind}, seed "
        f"{args.seed}, compile cache {cache_dir}")
    clock = CompileClock()

    def timed(title: str, phase, **sizes):
        """Run one phase under the wall-clock limit; print its seconds,
        compile seconds and the device's peak memory."""
        log(f"[{title}]")
        timer = threading.Timer(PHASE_TIMEOUT_S, _timed_out, (title,))
        timer.daemon = True
        timer.start()
        c0, h0 = clock.mark()
        t0 = time.monotonic()
        try:
            result = phase(**sizes)
        finally:
            timer.cancel()
        gc.collect()
        mem = device_memory(first)
        log(f"  done in {time.monotonic() - t0:.1f} s (compile "
            f"{clock.seconds - c0:.1f} s, {clock.cache_hits - h0} "
            f"persistent-cache hits), peak_bytes_in_use "
            f"{mem['peak_bytes_in_use']}, bytes_in_use "
            f"{mem['bytes_in_use']}")
        return result

    t0 = time.monotonic()
    try:
        (run_four_chips if args.chips == 4 else run_one_chip)(
            args.seed, timed)
    except Exception as e:
        import traceback
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        return 1
    finally:
        # nothing of ours may still be inside the runtime when the
        # interpreter exits: a drainer there aborts the process after
        # the result is out
        rail.close_endpoints()
    log(f"chip_smoke: all phases passed in {time.monotonic() - t0:.1f} s "
        f"(compile {clock.seconds:.1f} s, {clock.cache_hits} "
        f"persistent-cache hits)")
    print(json.dumps({"ok": True, "device": {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


def _timed_out(title: str) -> None:
    print(f"chip_smoke: FAILED: phase {title!r} still running after "
          f"{PHASE_TIMEOUT_S:.0f} s", file=sys.stderr, flush=True)
    os._exit(3)


if __name__ == "__main__":
    sys.exit(main())

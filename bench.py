"""Benchmark driver entry — prints ONE JSON line on stdout.

Headline metric: **tensor-pipe throughput through the framework transport**
(TensorStream -> IciEndpoint), where every chunk provably lands in a
distinct destination buffer (same-device sends go through a compiled copy
kernel; device_put-to-self would alias and move zero bytes).  This is the
streaming_echo config re-targeted at the TPU's native transport (ICI /
HBM), compared against the reference's best published transport number,
2.3 GB/s same-host multi-connection over 10GbE (docs/cn/benchmark.md:104)
— different link technologies, same "bytes through the framework's
streaming path" methodology.  Raw on-chip HBM read+write bandwidth is
reported separately as `hbm_stream` (a chip sanity number, NOT the
framework).

Every published number passes sanity gates: wall time must exceed timer
confidence, and bandwidth must be below the device's published HBM peak
(``DEVICE_PEAKS``, keyed by ``device_kind``) — anything failing the gate
is published as null with the reason.

The device rungs need a TPU: a run that finds none, or whose device rung
raises, ends non-zero — it never publishes a skip.  The host rungs and
the forced-CPU subcommands run in children that never hold the chip.
All progress goes to stderr; stdout is exactly one JSON object.
"""
import gc
import json
import math
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_GBPS = 2.3
# Published peaks of one chip, keyed by jax's device_kind.  No stream
# through HBM exceeds the HBM peak; anything above is a measurement
# artifact and must not be published.  A kind that is not here is an
# error, not a default.
DEVICE_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 16 GB HBM2e at 819 GB/s,
    # 197 TFLOP/s bf16, 1,600 Gbit/s of chip-to-chip interconnect
    "TPU v5 lite": {"hbm_gbps": 819.0, "bf16_tflops": 197.0,
                    "ici_gbit_s": 1600.0},
}


def device_peaks(device_kind=None) -> dict:
    """The peaks of ``device_kind`` (default: the first device jax
    reports).  Raises for a kind the table does not know."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise RuntimeError(
            f"no published peaks for device kind {device_kind!r}: add "
            f"it to bench.DEVICE_PEAKS with its source") from None
# Published latencies below 100x timer resolution are noise.
_TIMER_CONFIDENCE_S = max(
    100 * time.get_clock_info("perf_counter").resolution, 2e-6)


def _gated(nbytes_moved, wall_s):
    """Return (gbps or None, issues list) applying the integrity gates."""
    issues = []
    if wall_s < _TIMER_CONFIDENCE_S:
        issues.append(
            f"wall {wall_s:.2e}s below timer confidence "
            f"{_TIMER_CONFIDENCE_S:.2e}s")
    gbps = nbytes_moved / wall_s / 1e9 if wall_s > 0 else float("inf")
    cap = device_peaks()["hbm_gbps"]
    if gbps > cap:
        issues.append(f"{gbps:.3g} GB/s exceeds the device's HBM peak "
                      f"{cap} GB/s")
    return (None if issues else round(gbps, 3)), issues


# streaming_tensor's mid-batch liveness deadline, measured from the start
# of the CURRENT batch (ADVICE r5 — against the whole timed region's t0 a
# healthy late batch would be misflagged once the region outgrows it).
WEDGE_TIMEOUT_S = 120.0


def _batch_wedged(batch_t0, now, timeout_s=WEDGE_TIMEOUT_S):
    """True when the current batch has made no complete delivery for
    `timeout_s` — a per-batch bound, independent of how long the whole
    timed region has run."""
    return now - batch_t0 > timeout_s


# Native sockets hold raw pointers to ctypes trampolines; pin every callback
# for process lifetime (EOF callbacks fire after the bench function returns).
_KEEP = []


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def bench_unary_echo(duration_s=2.0, threads=4):
    """example/echo_c++ + multi_threaded_echo_c++ analog over loopback."""
    import brpc_tpu as brpc

    class Echo(brpc.Service):
        @brpc.method(request="raw", response="raw")
        def Echo(self, cntl, req):
            return req

    server = brpc.Server()
    server.add_service(Echo())
    server.start("127.0.0.1", 0)
    ch = brpc.Channel(f"127.0.0.1:{server.port}", timeout_ms=5000)
    payload = b"x" * 128
    # warmup
    for _ in range(50):
        ch.call_sync("Echo", "Echo", payload, serializer="raw")
    counts = [0] * threads
    lats = []
    lat_lock = threading.Lock()
    stop = time.monotonic() + duration_s

    def worker(i):
        my_lats = []
        while time.monotonic() < stop:
            t0 = time.monotonic()
            ch.call_sync("Echo", "Echo", payload, serializer="raw")
            my_lats.append(time.monotonic() - t0)
            counts[i] += 1
        with lat_lock:
            lats.extend(my_lats)

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(threads)]
    t0 = time.monotonic()
    [t.start() for t in ts]
    [t.join() for t in ts]
    wall = time.monotonic() - t0
    lats.sort()
    qps = sum(counts) / wall
    p99 = lats[int(len(lats) * 0.99)] * 1e6 if lats else 0
    p50 = lats[len(lats) // 2] * 1e6 if lats else 0
    server.stop()
    server.join()
    return {"qps": round(qps, 1), "p50_us": round(p50, 1),
            "p99_us": round(p99, 1), "threads": threads}


def bench_echo_scaling(conn_counts=(1, 4, 16, 64), per_conn_frames=15_000,
                       trials=3, budget_ms=3.0):
    """PYTHON-HANDLER scaling under the native C++ client pump — the
    reference's methodology (C++ client, docs/cn/benchmark.md:110-121)
    pointed at user handlers.  Each connection keeps one frame in flight,
    so N conns model N concurrent synchronous clients and the measured
    cost is the SERVER's dispatch + Python handler path only.

    Admission control (VERDICT r4 #4): the server runs with a usercode
    latency budget, so when the GIL lane's estimated wait exceeds
    `budget_ms` the excess load is shed natively with ELIMIT instead of
    queueing.  qps counts SUCCESSES only; sheds surface as err_frac.
    p50/p99 are success latencies.  Each rung runs `trials` times,
    median + spread reported (same jitter discipline as the native
    ladder)."""
    import ctypes

    import brpc_tpu as brpc
    from brpc_tpu._core import core, core_init

    class Echo(brpc.Service):
        NAME = "ScaleEcho"

        @brpc.method(request="raw", response="raw")
        def Echo(self, cntl, req):
            return req

    server = brpc.Server(brpc.ServerOptions(
        usercode_latency_budget_ms=budget_ms,
        # echo never blocks: run it on the dispatcher (single-threaded
        # event loop) — on a core-starved box the executor hop's
        # cross-thread GIL convoy dominated the tail
        usercode_inline=True))
    server.add_service(Echo())
    server.start("127.0.0.1", 0)
    core_init()
    out = {}
    try:
        for c in conn_counts:
            rs = []
            for _ in range(trials):
                qps = ctypes.c_double()
                p50 = ctypes.c_double()
                p99 = ctypes.c_double()
                ef = ctypes.c_double()
                rc = core.brpc_bench_pump(
                    server.port, b"ScaleEcho", b"Echo", c, 1,
                    per_conn_frames * c, 128,
                    ctypes.byref(qps), ctypes.byref(p50),
                    ctypes.byref(p99), ctypes.byref(ef))
                rs.append({"qps": qps.value, "p50_us": p50.value,
                           "p99_us": p99.value, "err_frac": ef.value,
                           "completed": rc == 0})
            qs = sorted(r["qps"] for r in rs)
            p50s = sorted(r["p50_us"] for r in rs)
            p99s = sorted(r["p99_us"] for r in rs)
            mid = len(rs) // 2
            out[f"{c}c"] = {
                "qps": round(qs[mid], 1), "p50_us": p50s[mid],
                "p99_us": p99s[mid],
                "qps_spread": [round(qs[0], 1), round(qs[-1], 1)],
                "p99_spread": [p99s[0], p99s[-1]],
                "shed_frac": round(
                    sorted(r["err_frac"] for r in rs)[mid], 4),
                "trials": trials,
                "completed": all(r["completed"] for r in rs)}
    finally:
        server.stop()
        server.join()
    base = out[f"{conn_counts[0]}c"]["qps"]
    peak = max(out[f"{c}c"]["qps"] for c in conn_counts)
    out["speedup_at_peak"] = round(peak / base, 2) if base else None
    out["usercode_budget_ms"] = budget_ms
    out["cpu_cores"] = os.cpu_count()
    out["note"] = ("native C++ client pump vs Python handlers; success-"
                   "qps only, ELIMIT sheds in shed_frac; handlers stay "
                   "GIL-bound so per-core saturation is the ceiling, but "
                   "added load must not DEGRADE throughput or tails")
    return out


def bench_grpc_echo(total=8000, inflight=32, payload_len=128,
                    stream_items=2000):
    """gRPC (h2) unary + server-streaming on the shared port.  Round 5
    moved the server data plane to C++ (src/cc/net/h2.cc: framing,
    HPACK, flow control, gRPC dispatch — the reference's native
    http2_rpc_protocol.cpp slot), so this rung now has three tiers:
    Python client end-to-end (interop proof; client-bound), native pump
    -> Python handler (bridge dispatch cost), and native pump -> native
    method — the pure-C++ path, target >= 100k qps on the 1-core box
    (measured ~235k vs ~9k for the round-4 all-Python plane)."""
    import time as _t
    from collections import deque

    import brpc_tpu as brpc
    from brpc_tpu.rpc.h2 import GrpcChannel

    class Echo(brpc.Service):
        NAME = "bench.Grpc"

        @brpc.method(request="raw", response="raw")
        def Echo(self, cntl, req):
            return bytes(req)

        @brpc.method(request="raw", response="raw")
        def Stream(self, cntl, req):
            n = int(bytes(req) or b"1")
            payload = b"s" * 128
            return (payload for _ in range(n))

    server = brpc.Server()
    server.add_service(Echo())
    server.start("127.0.0.1", 0)
    out = {}
    try:
        ch = GrpcChannel(f"127.0.0.1:{server.port}")
        payload = b"x" * payload_len
        for _ in range(100):
            ch.call("bench.Grpc", "Echo", payload)

        def one_trial():
            lat = []
            pend = deque()
            t0 = _t.perf_counter()
            for _ in range(total):
                pend.append((ch.acall("bench.Grpc", "Echo", payload),
                             _t.perf_counter()))
                if len(pend) >= inflight:
                    f, ts = pend.popleft()
                    f.result(30)
                    lat.append(_t.perf_counter() - ts)
            while pend:
                f, ts = pend.popleft()
                f.result(30)
                lat.append(_t.perf_counter() - ts)
            wall = _t.perf_counter() - t0
            lat.sort()
            return (total / wall, lat[len(lat) // 2] * 1e6,
                    lat[int(len(lat) * 0.99)] * 1e6)

        trials = sorted(one_trial() for _ in range(3))
        qps = trials[1][0]
        out["unary"] = {
            "qps": round(qps, 1), "inflight": inflight,
            "p50_us": round(trials[1][1], 1),
            "p99_us": round(trials[1][2], 1),
            "qps_spread": [round(trials[0][0], 1), round(trials[2][0], 1)],
            "target_qps": 4000,
            "met": qps >= 4000}
        # server-streaming: one call, many items (message throughput)
        got = 0
        t0 = _t.perf_counter()
        for item in ch.call_stream("bench.Grpc", "Stream",
                                   str(stream_items).encode()):
            got += 1
        wall = _t.perf_counter() - t0
        out["streaming"] = {"items": got,
                            "items_per_s": round(got / wall, 1)}
        ch.close()
        # Native-client pump tiers (round 5: the h2 data plane moved to
        # C++ — src/cc/net/h2.cc; the Python-client number above is now
        # CLIENT-bound).  Tier 1: pump -> Python handler through the
        # h2_native bridge (server dispatch cost only).  Tier 2: pump ->
        # native-registered method — the pure-C++ gRPC path, ZERO Python
        # per request (the reference's native h2, benchmark.md basis).
        import ctypes

        from brpc_tpu._core.lib import core as _core

        def pump(path, n):
            qps = ctypes.c_double()
            p50 = ctypes.c_double()
            p99 = ctypes.c_double()
            rc = _core.brpc_bench_pump_h2(server.port, path.encode(), 4, 32,
                                          n, payload_len, ctypes.byref(qps),
                                          ctypes.byref(p50),
                                          ctypes.byref(p99))
            return rc, qps.value, p50.value, p99.value

        trials = sorted(pump("/bench.Grpc/Echo", 30_000)
                        for _ in range(3))
        rc, q, p50v, p99v = trials[1]
        out["unary_pump_python"] = {
            "rc": rc, "qps": round(q, 1), "p50_us": round(p50v, 1),
            "p99_us": round(p99v, 1),
            "qps_spread": [round(trials[0][1], 1), round(trials[2][1], 1)]}
        _core.brpc_bench_register_native_echo(b"bench.NativeGrpc", b"Echo",
                                              1)
        try:
            trials = sorted(pump("/bench.NativeGrpc/Echo", 200_000)
                            for _ in range(3))
            rc, q, p50v, p99v = trials[1]
            out["unary_native"] = {
                "rc": rc, "qps": round(q, 1), "p50_us": round(p50v, 1),
                "p99_us": round(p99v, 1),
                "qps_spread": [round(trials[0][1], 1),
                               round(trials[2][1], 1)],
                "target_qps": 100_000, "met": q >= 100_000}
        finally:
            _core.brpc_unregister_method(b"bench.NativeGrpc", b"Echo")
    finally:
        server.stop()
        server.join()
    return out


def bench_native_echo_scaling(conn_counts=(1, 2, 4, 8, 16),
                              per_conn_frames=150_000, trials=3):
    """QPS vs connection count for the native unary hot path (the
    multi-connection half of the reference's same-host chart,
    docs/cn/benchmark.md:104).

    Jitter discipline (VERDICT r4 weak #3): each rung runs `trials` times
    and publishes the MEDIAN with the min-max spread alongside — on the
    shared 1-core driver box a single foreign process or 4ms OS stall can
    poison one trial's p99 by 100x, and a median over independent runs
    separates environment spikes from real queueing."""
    out = {}
    for c in conn_counts:
        rs = [bench_native_echo(conns=c, inflight=32,
                                total=per_conn_frames * c)
              for _ in range(trials)]
        qs = sorted(r["qps"] for r in rs)
        p50s = sorted(r["p50_us"] for r in rs)
        p99s = sorted(r["p99_us"] for r in rs)
        mid = len(rs) // 2
        out[f"{c}c"] = {"qps": qs[mid], "p50_us": p50s[mid],
                        "p99_us": p99s[mid],
                        "qps_spread": [qs[0], qs[-1]],
                        "p99_spread": [p99s[0], p99s[-1]],
                        "trials": trials,
                        "completed": all(r["completed"] for r in rs)}
    base = out[f"{conn_counts[0]}c"]["qps"]
    peak = max(out[f"{c}c"]["qps"] for c in conn_counts)
    out["speedup_at_peak"] = round(peak / base, 2) if base else None
    # the r3 gate, computed on medians: qps monotone non-decreasing (5%
    # tolerance for run-to-run noise) and p99 within 10x of p50 per rung
    out["monotone_qps"] = all(
        out[f"{b}c"]["qps"] >= out[f"{a}c"]["qps"] * 0.95
        for a, b in zip(conn_counts, conn_counts[1:]))
    out["tail_ok"] = all(
        out[f"{c}c"]["p99_us"] <= 10 * max(out[f"{c}c"]["p50_us"], 1)
        for c in conn_counts)
    # the curve is only as good as the cores under it: on a 1-core driver
    # box every config shares one CPU and the curve is flat by physics
    out["cpu_cores"] = os.cpu_count()
    return out


def bench_native_echo(conns=8, inflight=32, total=500_000, payload_len=128):
    """C++ client pump against the native unary hot path: meta parse,
    FlatMap method lookup, handler, response pack all in C++ (net/rpc.h,
    net/bench.cc).  p50/p99 from send-timestamp correlation ids.  Round 1's
    number timed a Python ctypes write loop — the client, not the server;
    this measures the framework's actual dispatch path."""
    import ctypes
    import os

    from brpc_tpu._core import core, core_init
    core_init()
    qps = ctypes.c_double()
    p50 = ctypes.c_double()
    p99 = ctypes.c_double()
    rc = core.brpc_bench_echo(conns, inflight, total, payload_len, 1,
                              ctypes.byref(qps), ctypes.byref(p50),
                              ctypes.byref(p99))
    return {"qps": round(qps.value, 1), "p50_us": p50.value,
            "p99_us": p99.value, "conns": conns, "inflight": inflight,
            "frames": total, "completed": rc == 0,
            "cpu_cores": os.cpu_count()}


def _per_pass_seconds(x, k_small=8, k_large=108, trials=3):
    """Per-pass time of a non-foldable HBM read+write over x, measured
    differentially (subtracts the fixed dispatch cost; the result is
    pure on-chip streaming time).  The loop ends in a scalar the host
    reads, which also waits for the device."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def make(k):
        def body(i, b):
            return jnp.roll(b, 128) + jnp.bfloat16(1.0)
        return jax.jit(lambda a: lax.fori_loop(0, k, body, a).sum())

    def best_time(fn):
        float(fn(x))  # warm/compile
        best = None
        for _ in range(trials):
            t0 = time.monotonic()
            float(fn(x))
            dt = time.monotonic() - t0
            best = dt if best is None or dt < best else best
        return best

    d_small = best_time(make(k_small))
    d_large = best_time(make(k_large))
    return max(1e-9, (d_large - d_small) / (k_large - k_small)), d_small


def bench_serving(batch_sizes=(1, 4, 16), threads_per_slot=3,
                  duration_s=1.0, trials=3):
    """Serving rung: dynamic-batcher qps and p99 queue delay vs
    max_batch_size through `brpc_tpu/serving` on jit scoring (a 2-layer
    MLP).  Same jitter discipline as the other rungs: `trials` runs per
    batch size, median + spread."""
    import threading

    import numpy as np
    import jax
    import jax.numpy as jnp

    from brpc_tpu.serving import DynamicBatcher

    D, H = 256, 4096
    rng = np.random.default_rng(0)
    w1 = jnp.asarray(rng.standard_normal((D, H)).astype(np.float32))
    w2 = jnp.asarray(rng.standard_normal((H, H)).astype(np.float32))
    w3 = jnp.asarray(rng.standard_normal((H, 1)).astype(np.float32))

    @jax.jit
    def score(x):
        return jnp.tanh(jnp.tanh(x @ w1) @ w2) @ w3

    item = np.ones((D,), np.float32)
    max_delay_us = 20_000

    def one_trial(bs: int, k: int):
        threads = max(4, threads_per_slot * bs)
        b = DynamicBatcher(score, max_batch_size=bs,
                           max_delay_us=max_delay_us,
                           batch_buckets=(bs,), length_buckets=(D,),
                           name=f"bench_bs{bs}_{k}")
        try:
            b.submit_wait(item, timeout_s=300)   # compile outside timing
            stop = time.monotonic() + duration_s
            counts = [0] * threads

            def worker(i):
                while time.monotonic() < stop:
                    b.submit_wait(item, timeout_s=60)
                    counts[i] += 1

            ts = [threading.Thread(target=worker, args=(i,))
                  for i in range(threads)]
            t0 = time.monotonic()
            [t.start() for t in ts]
            [t.join(120) for t in ts]
            wall = time.monotonic() - t0
            return (sum(counts) / wall,
                    b.queue_delay_rec.latency_percentile(0.99))
        finally:
            b.close()

    out = {}
    for bs in batch_sizes:
        rs = sorted(one_trial(bs, k) for k in range(trials))
        mid = len(rs) // 2
        out[f"bs{bs}"] = {
            "qps": round(rs[mid][0], 1),
            "queue_p99_us": round(rs[mid][1], 1),
            "qps_spread": [round(rs[0][0], 1), round(rs[-1][0], 1)],
            "trials": trials,
        }
    base = out[f"bs{batch_sizes[0]}"]["qps"]
    peak = max(out[f"bs{bs}"]["qps"] for bs in batch_sizes)
    out["speedup_at_peak"] = round(peak / base, 2) if base else None
    out["max_delay_us"] = max_delay_us
    out["note"] = ("dynamic-batcher rung (brpc_tpu/serving): per-item "
                   "qps through bucket-padded jit scoring vs "
                   "max_batch_size; queue_p99_us is time queued before "
                   "batch formation")
    return out


def bench_kvcache(shared_ratios=(0.0, 0.5, 0.9), n_requests=24,
                  prefix_tokens=32, suffix_tokens=16, new_tokens=8,
                  trials=3):
    """Paged-KV-cache rung: decode tokens/s and prefill-skip ratio vs
    shared-prefix ratio through `brpc_tpu/kvcache` + the DecodeEngine.

    Workload: `n_requests` prompts; a `shared_ratios` fraction open
    with ONE fixed `prefix_tokens`-token prefix (the shared-system-
    prompt shape) plus a unique suffix, the rest are fully distinct.
    The radix tree warms as early requests retire, so later admits of
    the shared prefix reuse its pages and skip that prefill — the
    prefill_skip ratio is the store's own hit-rate gauge, and tokens/s
    is end-to-end through admit/prefill/decode/retire.  Same jitter
    discipline as the other rungs: `trials` runs per ratio, median +
    spread."""
    import threading

    import jax

    from brpc_tpu.kvcache import KVCacheStore
    from brpc_tpu.serving import DecodeEngine

    pt = 16

    @jax.jit
    def step(tokens, positions, pages):
        return tokens + 1

    @jax.jit
    def prefill(tokens, start):
        return tokens.sum()

    def one_trial(ratio: float, k: int):
        store = KVCacheStore(page_tokens=pt, page_bytes=pt * 64,
                             max_blocks=32,
                             name=f"bench_r{int(ratio * 100)}_{k}")
        eng = DecodeEngine(step, num_slots=4, store=store,
                           prefill_fn=prefill,
                           name=f"bench_kv_r{int(ratio * 100)}_{k}")
        shared = list(range(1000, 1000 + prefix_tokens))
        n_shared = int(n_requests * ratio)
        prompts = []
        for i in range(n_requests):
            suffix = [2000 + i * suffix_tokens + j
                      for j in range(suffix_tokens)]
            head = shared if i < n_shared else \
                [3000 + i * prefix_tokens + j
                 for j in range(prefix_tokens)]
            prompts.append(head + suffix)
        try:
            # warm the jit caches outside timing — with a THROWAWAY
            # prompt disjoint from the measured set, so the shared0
            # rung really sees 0% prefix reuse
            eng.submit([9_000_000 + j for j in range(prefix_tokens)],
                       1, lambda t: None)
            assert eng.join_idle(60)
            # measure the skip ratio over the TIMED workload only (the
            # warm-up request's tokens would dilute the denominator)
            h0 = store.hit_tokens.get_value()
            p0 = store.prompt_tokens.get_value()
            done = [threading.Event() for _ in prompts]
            t0 = time.monotonic()
            for i, p in enumerate(prompts):
                eng.submit(p, new_tokens, lambda t: None,
                           (lambda err, d=done[i]: d.set()))
            for d in done:
                assert d.wait(120), "kvcache bench request hung"
            wall = time.monotonic() - t0
            toks = n_requests * new_tokens
            dp = store.prompt_tokens.get_value() - p0
            skip = (store.hit_tokens.get_value() - h0) / dp if dp else 0.0
            return toks / wall, skip
        finally:
            eng.close()
            store.close()

    out = {}
    for ratio in shared_ratios:
        rs = sorted(one_trial(ratio, k) for k in range(trials))
        mid = len(rs) // 2
        out[f"shared{int(ratio * 100)}"] = {
            "tokens_per_s": round(rs[mid][0], 1),
            "prefill_skip_ratio": round(rs[mid][1], 4),
            "tokens_per_s_spread": [round(rs[0][0], 1),
                                    round(rs[-1][0], 1)],
            "trials": trials,
        }
    out["note"] = ("paged-KV rung (brpc_tpu/kvcache): decode tokens/s "
                   "and prefill-skip (radix hit-rate) vs shared-prefix "
                   "ratio; skip ratio climbs with sharing because "
                   "admits reuse cached pages instead of prefilling")
    return out


def bench_recovery(committed_ratios=(0.0, 0.5, 0.9), n_requests=6,
                   total_prompt_tokens=40, new_tokens=10, trials=3):
    """Recovery rung: supervised engine-crash failover through
    `brpc_tpu/serving/supervisor.py` + the paged KV cache.

    Workload: `n_requests` concurrent generations whose prompts share a
    COMMITTED prefix covering `committed_ratios` of the prompt (the
    prefix is committed to the radix tree by a clean completion before
    the wave; the rest of each prompt is unique).  A seeded
    `serving.step` fault crashes the engine mid-decode; the supervisor
    detects it, rebuilds against the surviving store, and re-admits
    every generation from its last emitted token.  Reported per ratio:

      * time-to-recover: crash detection -> first post-restart token
        (the supervisor's own detect_to_first_token_ms);
      * re-decoded-token ratio: (prompt tokens prefilled - cache-hit
        tokens) / prompt tokens over the wave+recovery window — 1.0
        means recovery replayed everything from scratch, lower means
        the committed prefix pages did their job.

    Same jitter discipline as the other rungs: `trials` runs per
    ratio, median + spread."""
    import threading

    import jax

    from brpc_tpu import fault
    from brpc_tpu.kvcache import KVCacheStore
    from brpc_tpu.serving import DecodeEngine, EngineSupervisor

    pt = 8

    @jax.jit
    def step(tokens, positions, pages):
        return (tokens * 7 + positions) % 997

    calm = ({"queue_delay_us": float("inf"), "pool_ratio": 9.9,
             "queue_depth": 1e9},) * 3

    def one_trial(ratio: float, k: int):
        tag = f"rec_r{int(ratio * 100)}_{k}"
        store = KVCacheStore(page_tokens=pt, page_bytes=pt * 64,
                             max_blocks=64, name=f"bench_{tag}")
        sup = EngineSupervisor(
            lambda: DecodeEngine(step, num_slots=4, store=store,
                                 max_pages_per_slot=64,
                                 name=f"bench_{tag}_eng"),
            store=store, heartbeat_deadline_s=10.0,
            check_interval_s=0.01, ladder=calm, name=f"bench_{tag}_sup")
        # page-align the committed share so "committed" means whole
        # pages the radix tree can actually serve
        shared_n = int(total_prompt_tokens * ratio) // pt * pt
        shared = [5000 + k * 1000 + j for j in range(shared_n)]
        prompts = []
        for i in range(n_requests):
            uniq = [7000 + k * 1000 + i * total_prompt_tokens + j
                    for j in range(total_prompt_tokens - shared_n)]
            prompts.append(shared + uniq)
        try:
            # warm the jit cache AND commit the shared prefix
            done = threading.Event()
            warm = (shared + [9]) if shared else [9_000_000 + k, 1, 2]
            sup.submit(warm, 1, lambda t: None, lambda e: done.set())
            assert done.wait(120)
            assert sup.join_idle(60)
            h0 = store.hit_tokens.get_value()
            p0 = store.prompt_tokens.get_value()
            plan = fault.FaultPlan(900 + k).on(
                "serving.step", fault.ERROR, times=1, after=3)
            events = [threading.Event() for _ in prompts]
            with fault.injected(plan):
                for p, ev in zip(prompts, events):
                    sup.submit(p, new_tokens, lambda t: None,
                               (lambda err, d=ev: d.set()))
                for ev in events:
                    assert ev.wait(120), "recovery bench request hung"
            assert sup.stats()["restarts"] == 1, "crash never fired"
            rec = sup.stats()["last_recovery"] or {}
            ttr_ms = rec.get("detect_to_first_token_ms")
            dp = store.prompt_tokens.get_value() - p0
            dh = store.hit_tokens.get_value() - h0
            redecode = (dp - dh) / dp if dp else 1.0
            return ttr_ms, redecode
        finally:
            sup.close()
            store.clear()
            store.close()

    out = {}
    for ratio in committed_ratios:
        rs = []
        for k in range(trials):
            rs.append(one_trial(ratio, k))
        ttrs = sorted(r[0] for r in rs if r[0] is not None)
        reds = sorted(r[1] for r in rs)
        out[f"committed{int(ratio * 100)}"] = {
            "time_to_recover_ms": (round(ttrs[len(ttrs) // 2], 2)
                                   if ttrs else None),
            "time_to_recover_spread_ms": ([round(ttrs[0], 2),
                                           round(ttrs[-1], 2)]
                                          if ttrs else None),
            "redecoded_token_ratio": round(reds[len(reds) // 2], 4),
            "redecoded_token_ratio_spread": [round(reds[0], 4),
                                             round(reds[-1], 4)],
            "trials": trials,
        }
    out["note"] = ("recovery rung (brpc_tpu/serving/supervisor.py): "
                   "detect->first-post-restart-token latency and "
                   "re-decoded-token ratio vs committed-prefix share; "
                   "the ratio falls as committed pages turn recovery "
                   "prefill into cache hits")
    return out


def bench_trace_overhead(duration_s=1.0, threads=8, trials=3):
    """Tracing-overhead rung (ISSUE 5): serving qps through the dynamic
    batcher with rpcz OFF (the NULL_SPAN fast path every production
    default rides), ON at sample rate 1.0 (every trace kept), and ON at
    0.01 (per-trace head sampling).  Same jitter discipline as the
    other rungs: `trials` runs per mode, median + spread.  The claim
    under test: the disabled path costs nothing measurable, and
    sampling bounds the enabled cost."""
    import threading

    import numpy as np
    import jax
    import jax.numpy as jnp

    from brpc_tpu import rpcz
    from brpc_tpu.serving import DynamicBatcher

    D = 128
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((D, D)).astype(np.float32))

    @jax.jit
    def score(x):
        return jnp.tanh(x @ w).sum(axis=-1)

    item = np.ones((D,), np.float32)
    modes = (("off", False, 1.0), ("on_1.0", True, 1.0),
             ("on_0.01", True, 0.01))

    def one_trial(mode_k, on, rate, k):
        b = DynamicBatcher(score, max_batch_size=16, max_delay_us=500,
                           batch_buckets=(16,), length_buckets=(D,),
                           name=f"bench_trace_{mode_k}_{k}")
        try:
            b.submit_wait(item, timeout_s=300)   # compile outside timing
            rpcz.set_enabled(on, rate)
            stop = time.monotonic() + duration_s
            counts = [0] * threads

            def worker(i):
                while time.monotonic() < stop:
                    b.submit_wait(item, timeout_s=60)
                    counts[i] += 1

            ts = [threading.Thread(target=worker, args=(i,))
                  for i in range(threads)]
            t0 = time.monotonic()
            [t.start() for t in ts]
            [t.join(120) for t in ts]
            return sum(counts) / (time.monotonic() - t0)
        finally:
            rpcz.set_enabled(False)
            b.close()

    out = {}
    for mode_k, on, rate in modes:
        qps = sorted(one_trial(mode_k, on, rate, k) for k in range(trials))
        out[mode_k] = {
            "qps": round(qps[len(qps) // 2], 1),
            "qps_spread": [round(qps[0], 1), round(qps[-1], 1)],
            "trials": trials,
        }
    base = out["off"]["qps"]
    if base:
        for mode_k, _, _ in modes[1:]:
            out[mode_k]["overhead_pct_vs_off"] = round(
                (base - out[mode_k]["qps"]) / base * 100.0, 2)
    out["note"] = ("trace-overhead rung (brpc_tpu/rpcz): batcher qps "
                   "with rpcz off / on@1.0 / on@0.01; 'off' rides the "
                   "NULL_SPAN fast path — its spread vs the other "
                   "modes bounds the cost of shipping the tracing "
                   "hooks disabled")
    return out


def bench_hbm_stream(chunk_mb=64):
    """SECONDARY chip sanity number: raw on-chip HBM read+write bandwidth
    of a jitted roll+add loop.  No framework code runs here — this bounds
    what the transport could reach, it is not the transport."""
    import jax.numpy as jnp

    n = chunk_mb * 1024 * 1024 // 2  # bf16 elements
    x = jnp.ones((n,), jnp.bfloat16)
    per_pass, dispatch = _per_pass_seconds(x)
    traffic = 2 * x.nbytes
    gbps, issues = _gated(traffic, per_pass)
    return {"gbps": gbps, "chunk_mb": chunk_mb,
            "per_pass_us": round(per_pass * 1e6, 1),
            "dispatch_overhead_ms": round(dispatch * 1e3, 1),
            "note": "raw HBM loop, not framework code",
            **({"invalid": issues} if issues else {})}


def _fence(arr):
    """Wait for the device to finish ``arr``.  block_until_ready is the
    fence: on the chip a scalar readback after it returns at once
    (chip_smoke.py's fence observation, PR 21), so a timed region ends
    here with nothing to subtract."""
    arr.block_until_ready()


def bench_tensor_pipe(chunk_mb=64, iter_chunks=80, max_total_gb=96):
    """HEADLINE: TensorStream -> IciEndpoint framework path.  Same-device
    chunks go through the endpoint's compiled copy kernel, so every chunk
    provably lands in a distinct destination buffer; cross-device
    (multi-chip) chunks ride device_put ICI DMA.

    Timing: ITERATIONS of `iter_chunks` chunks, each sized to fit the
    credit window (no mid-measurement stalls on completion observation)
    and each ending in a fence; the copy phases are SUMMED across
    iterations until they clear a 10 ms floor.  Accumulation keeps
    in-flight memory bounded by the window while moving enough total
    bytes to measure."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.ici import TensorStream
    from brpc_tpu.ici.endpoint import link_stats

    dev = jax.devices()[0]
    n = chunk_mb * 1024 * 1024 // 2
    chunk = jnp.ones((n,), jnp.bfloat16)
    _fence(chunk)
    outs = []
    def consume(a):
        outs[:] = [a]
        consume.n += 1
    consume.n = 0
    # window covers ONE iteration; iterations drain (untimed) in between
    ts = TensorStream(dev, consumer=consume,
                      window_bytes=(iter_chunks + 2) * chunk.nbytes)
    stats0 = link_stats()
    # batch size bounded by per-dispatch live memory (in + out <= 512MB
    # total): 16x64MB batches kept 1GB live per dispatch and the
    # allocator churn depressed the measured bandwidth (r3 weak #4)
    bs = max(1, min(16, iter_chunks, (256 << 20) // chunk.nbytes))
    # warmup: drainer thread + EVERY batch arity the timed loop will use
    # (jit caches per arity — warming one arity and then paying a
    # different-arity compile INSIDE the timed region is the classic
    # mistake).  iter_chunks % bs != 0 means the loop's final batch has
    # a remainder arity: warm that too.
    warm_target = bs
    ts.write_many([chunk] * bs)
    rem = iter_chunks % bs
    if rem:
        ts.write_many([chunk] * rem)
        warm_target += rem
    deadline = time.monotonic() + 60
    while consume.n < warm_target and time.monotonic() < deadline:
        time.sleep(0.005)    # deterministic: wait until warmup delivered
    # the transfer must not alias the source — this is the "really moved
    # bytes" proof the r1 bench lacked.  Two proofs, strongest available:
    # (a) buffer pointers when the plugin exposes them; (b) a device-side
    # donation sentinel for a plugin that does not: copy a probe
    # through the endpoint, then overwrite the
    # probe's buffer in place (donated jit) and re-read the destination —
    # if the "copy" had aliased the source, the destination would now
    # read the sentinel value.
    aliased = False
    alias_check = "unavailable"
    if outs:
        try:
            aliased = (outs[0].unsafe_buffer_pointer()
                       == chunk.unsafe_buffer_pointer())
            alias_check = "pointer-checked"
        except Exception:
            pass
    if alias_check == "unavailable":
        probe = jnp.full((1 << 20,), 3, jnp.bfloat16)
        probe.block_until_ready()
        dst = ts.endpoint.send(probe)
        dst.block_until_ready()
        overwrite = jax.jit(lambda v: v * 0 + 7, donate_argnums=0)
        sentinel = overwrite(probe)   # reuses probe's buffer on TPU
        sentinel.block_until_ready()
        if float(dst[0]) == 3.0:
            alias_check = "donation-sentinel-passed"
        else:
            aliased = True
            alias_check = "DONATION-SENTINEL-FAILED"
        del sentinel, dst, probe
    delivered_before = consume.n
    copy_sum = 0.0
    wall_sum = 0.0
    moved = 0
    iters = 0
    max_total = max_total_gb << 30
    issues = []
    while True:
        # untimed inter-iteration drain: the next timed run must start
        # with full window credit, or it measures stalls, not the pipe
        deadline = time.monotonic() + 120
        want = delivered_before + iters * iter_chunks
        while consume.n < want and time.monotonic() < deadline:
            time.sleep(0.002)
        if consume.n < want:
            # a timed run without full window credit measures stalls,
            # not the pipe — never publish that as a valid number
            issues.append(
                f"drainer wedged: {consume.n - delivered_before} of "
                f"{want - delivered_before} chunks delivered after 120s")
            break
        t0 = time.perf_counter()
        # batched dispatch: bs chunks per pre-compiled multi-copy program
        # (endpoint.send_batch) — one Python->PJRT call per <=256MB.  The
        # timed region ends when the LAST transfer completed (a fence
        # on the final destination buffer); consumer delivery overlaps
        # on the drainer thread.
        last = None
        for i in range(0, iter_chunks, bs):
            last = ts.write_many([chunk] * min(bs, iter_chunks - i))[-1]
        _fence(last)
        wall = time.perf_counter() - t0
        copy_sum += wall
        wall_sum += wall
        moved += iter_chunks * chunk.nbytes
        iters += 1
        if copy_sum >= 0.010:
            break
        if moved >= max_total:
            issues.append(
                f"copy phase {copy_sum * 1e3:.1f}ms under the 10ms floor "
                f"after {iters} iters at traffic cap {max_total_gb}GB")
            break
    ts.close(wait=True)
    stats1 = link_stats()
    gbps, gate_issues = _gated(moved, max(copy_sum, 1e-9))
    issues += gate_issues
    if aliased:
        issues.append("destination buffer aliased the source")
    if issues:
        gbps = None
    return {"gbps": gbps, "chunk_mb": chunk_mb,
            # hbm_stream counts READ+WRITE traffic; each pipe chunk also
            # reads the source and writes the destination, so the
            # traffic-basis number (2x moved bytes) is the one comparable
            # to hbm_stream.  Same-run measurement: 584 vs 715 GB/s = 82%
            # of raw HBM through the full framework pipe.
            "hbm_traffic_gbps": round(gbps * 2, 3) if gbps else None,
            "chunks": consume.n - delivered_before,   # timed deliveries
            "iterations": iters, "moved_gb": round(moved / (1 << 30), 2),
            "wall_s": round(wall_sum, 4),
            "copy_s": round(copy_sum, 4),
            "alias_check": alias_check,
            "same_device_copies":
                stats1["same_device_copies"] - stats0["same_device_copies"],
            "cross_device_moves":
                stats1["cross_device_moves"] - stats0["cross_device_moves"],
            **({"invalid": issues} if issues else {})}


def bench_streaming_tensor(chunk_mb=4, iter_chunks=32, max_total_gb=32):
    """Unified StreamWrite carrying device tensors (VERDICT r3 #1): a
    REAL loopback RPC server accepts a stream on the chip, the client's
    stream.write() pushes device arrays, and each chunk rides the rail
    (stage -> IciEndpoint -> claim ticket on the socket -> unstage).
    Unlike tensor_pipe this pays the full framework cost per message:
    block staging, registry deposit/claim, control frames, CONSUMED
    feedback.  host_copy_count() is asserted unchanged — the number is
    only published if the path stayed zero-copy."""
    import jax
    import jax.numpy as jnp

    import brpc_tpu as brpc
    from brpc_tpu.ici import rail

    dev = jax.devices()[0]
    n = chunk_mb * 1024 * 1024 // 2
    chunk = jnp.ones((n,), jnp.bfloat16)
    _fence(chunk)

    # count + most-recent only: retaining every delivered chunk would
    # pin up to max_total_gb of HBM for the whole run
    class _Sink:
        count = 0
        last = None
    def on_msg(stream, payload):
        _Sink.last = payload
        _Sink.count += 1

    class StreamSink(brpc.Service):
        @brpc.method(request="json", response="json")
        def Open(self, cntl, req):
            # 1GB window: the stream credit loop prices its releases at a
            # delivery round-trip, so the window must cover the link's
            # bandwidth-delay product or the writer stalls once per batch
            cntl.accept_stream(on_msg, max_buf_size=1 << 30, device=dev)
            return {"ok": True}

    server = brpc.Server(brpc.ServerOptions(ici_device=dev))
    server.add_service(StreamSink())
    server.start("127.0.0.1", 0)
    ch = brpc.Channel(f"127.0.0.1:{server.port}", timeout_ms=120000)
    cntl = brpc.Controller()
    stream = brpc.stream_create(cntl, None, max_buf_size=1 << 30,
                                device=dev)
    issues = []
    try:
        ch.call_sync("StreamSink", "Open", {}, serializer="json", cntl=cntl)
        host_copies0 = rail.host_copy_count()
        # warmup: compile the stage/slice/unstage kernels
        stream.write(chunk)
        deadline = time.monotonic() + 120
        while _Sink.count == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        if _Sink.count == 0:
            return {"error": "warmup chunk never delivered"}
        # warm the coalesced-dispatch programs: the stream sender batches
        # adjacent writes into power-of-2 send_batch arities, each a
        # distinct XLA program — compile them OUTSIDE the timed region
        # (VERDICT r4 #1a: warm every arity before measuring)
        for k in (2, 4, 8, 16, 32):
            for tk in rail.ship_many([chunk] * k, dev):
                rail.withdraw(tk)
        warm = _Sink.count
        moved = 0
        iters = 0
        max_total = max_total_gb << 30
        # ONE timed region, ONE fence at the very end.  Between batches,
        # delivery is confirmed by the framework's own CONSUMED feedback
        # (_Sink.count) — part of the path being measured — and the
        # elapsed check needs no fence.  At least 1s of timed streaming.
        floor = 1.0
        t0 = time.perf_counter()
        while True:
            # the wedge deadline is PER BATCH (ADVICE r5): measured from
            # the start of the whole timed region, a healthy late batch
            # would be misflagged once the region outgrows 120s
            batch_t0 = time.perf_counter()
            for _ in range(iter_chunks):
                stream.write(chunk, timeout_s=120)
            # completion = delivery through the whole framework path
            want = warm + (iters + 1) * iter_chunks
            wedged = False
            while _Sink.count < want:
                if _batch_wedged(batch_t0, time.perf_counter()):
                    wedged = True
                    break
                time.sleep(0.001)
            if wedged:
                # a timed-out batch must invalidate the WHOLE result —
                # crediting its bytes would publish a bogus valid number
                issues.append(
                    f"stream wedged mid-batch: "
                    f"{_Sink.count - warm - iters * iter_chunks}"
                    f"/{iter_chunks} delivered")
                break
            moved += iter_chunks * chunk.nbytes
            iters += 1
            if time.perf_counter() - t0 >= floor:
                break
            if moved >= max_total:
                # byte cap first: fine (a fast link outruns the 1s
                # target) UNLESS the phase is still under 10ms — then
                # the number is noise
                if time.perf_counter() - t0 < 0.010:
                    issues.append(
                        f"copy phase {time.perf_counter() - t0:.4f}s "
                        f"under the 10ms floor ({iters} iters)")
                break
        if not any("wedged" in i for i in issues):
            _fence(_Sink.last)
        copy_sum = time.perf_counter() - t0
        host_copies = rail.host_copy_count() - host_copies0
        if host_copies:
            issues.append(f"{host_copies} host copies on the tensor path")
        gbps, gate_issues = _gated(moved, max(copy_sum, 1e-9))
        issues += gate_issues
        if issues:
            gbps = None
        return {"gbps": gbps, "chunk_mb": chunk_mb,
                "chunks": _Sink.count - warm, "iterations": iters,
                "moved_gb": round(moved / (1 << 30), 2),
                "copy_s": round(copy_sum, 4),
                "host_copies": host_copies,
                **({"invalid": issues} if issues else {})}
    finally:
        stream.close()
        server.stop()
        server.join()


def bench_ici_ladder(sizes=(64, 4096, 65536, 1 << 20, 1 << 24, 1 << 26)):
    """rdma_performance 64B-64MB ladder over the REAL endpoint path, now
    through the pre-compiled batched transfer program (send_batch: k copy
    HLOs in ONE XLA program, one dispatch) instead of k Python dispatches.
    Sizes are exact byte counts (uint8 payloads).  Each rung: m batched
    dispatches of k chunks ending in a fence on the last batch's tail.
    Rungs whose copy phase stays under the confidence floor are
    published as null — never as a fantasy number.

    A 65536B cliff (68us @4KB -> 1520us @64KB) was once credit-window
    exhaustion: window_bytes=8*size meant batch 64 filled the window at
    64KB and every further send stalled on completion observation.
    Batched dispatch + a window sized for the whole trial removes the
    stall entirely."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.ici import IciEndpoint

    dev = jax.devices()[0]
    out = {}
    for size in sizes:
        x = jnp.ones((size,), jnp.uint8)     # exactly `size` bytes
        # chunks per dispatch: big enough to amortize the program call,
        # small enough to keep per-dispatch live memory <= 256MB in +
        # 256MB out AND the multi-copy program's arity compile-cheap —
        # the small rungs are overhead-dominated either way.
        # NO floor above the memory cap: the old k floor of 8 made the
        # 64MB rung dispatch 512MB batches (1GB live each), and the
        # allocator churn showed up as the r3 "ladder dip".
        k = max(1, min(32, (256 << 20) // size))
        # the window bounds destination HBM held by unobserved transfers
        # (the drainer frees in bulk, once per cycle); 6GB keeps a
        # comfortable margin on a 16GB chip while letting rungs push
        # enough traffic to clear the confidence floor
        window = 6 << 30
        ep = IciEndpoint(dev, window_bytes=window)
        warm = ep.send_batch([x] * k)        # compile the k-copy program
        warm[-1].block_until_ready()
        # Total-traffic cap — NOT in-flight memory: destinations are
        # freed as the trial proceeds.  A single timed run is bounded by
        # the WINDOW (m_window dispatches) so the writer never stalls on
        # completion observation mid-measurement (letting the 64MB rung
        # outrun the window once halved its published bandwidth — the
        # "non-monotonic" artifact); rungs needing more traffic than one
        # window accumulate ITERATED timed runs with untimed drains
        # between.
        m_cap = max(1, (96 << 30) // (k * size))
        m_window = max(1, (window - k * size) // (k * size))

        def run_trial(m):
            """One timed trial of m dispatches, split into window-bounded
            iterations.  Returns (copy_sum, iters); copy_sum None on a
            wedged drainer."""
            iters = 0
            remaining = m
            copy_sum = 0.0
            while remaining > 0:
                mi = min(remaining, m_window)
                # untimed drain: start each timed run with full credit
                deadline = time.monotonic() + 120
                while ep.inflight_bytes > 0 and \
                        time.monotonic() < deadline:
                    time.sleep(0.002)
                if ep.inflight_bytes > 0:
                    return None, iters
                last = None
                t0 = time.perf_counter()
                for _ in range(mi):
                    last = ep.send_batch([x] * k)[-1]
                _fence(last)
                copy_sum += time.perf_counter() - t0
                remaining -= mi
                iters += 1
            return copy_sum, iters

        m = 1
        rung = None
        escalations = 0
        rung_deadline = time.monotonic() + 45
        while True:
            copy_sum, iters = run_trial(m)
            if copy_sum is None:
                rung = {"lat_us": None, "gbps": None, "batch": k,
                        "dispatches": m,
                        "invalid": ["drainer wedged: window credit not "
                                    "released within 120s"]}
                break
            floor = 0.004
            if copy_sum >= floor:
                # Re-measure at the accepted size.  A retrial BELOW the
                # floor is evidence the first trial only cleared it via a
                # one-off spike (an allocator stall, a descheduled host
                # thread).  In that case
                # the honest response is MORE TRAFFIC (double m), never
                # keeping the inflated number; when all trials clear the
                # floor, the minimum is the standard bandwidth estimator.
                # Escalation is BOUNDED (2 doublings + the rung budget)
                # so one noisy rung can't eat the whole bench window;
                # confirmation trials run only on the >=16MB rungs, where
                # a spike-induced dip would break the monotonic gate (the
                # sub-MB rungs are overhead-dominated and cheap to trust).
                trials = [copy_sum]
                spiked = False
                if size >= (1 << 24):
                    for _ in range(2):
                        if time.monotonic() > rung_deadline:
                            break
                        c2, _ = run_trial(m)
                        if c2 is None:
                            continue
                        if c2 < floor:
                            spiked = True
                        trials.append(c2)
                if spiked and m < m_cap and escalations < 2 \
                        and time.monotonic() < rung_deadline:
                    escalations += 1
                    m = min(m_cap, m * 2)
                    continue
                note = None
                copy_sum = min(trials)
                if copy_sum < floor:
                    # escalation exhausted with sub-floor trials: the
                    # MEDIAN is the low-bias estimator here (min would
                    # overstate bandwidth by up to the jitter)
                    copy_sum = sorted(trials)[len(trials) // 2]
                    note = "jitter-limited: median of trials"
                gbps, issues = _gated(m * k * size, max(copy_sum, 1e-9))
                rung = {"lat_us": round(copy_sum / (m * k) * 1e6, 2),
                        "gbps": gbps, "batch": k, "dispatches": m,
                        "iterations": iters,
                        **({"note": note} if note else {}),
                        **({"invalid": issues} if issues else {})}
                if issues:
                    rung["lat_us"] = None
                break
            if m >= m_cap or time.monotonic() > rung_deadline:
                rung = {"lat_us": None, "gbps": None, "batch": k,
                        "dispatches": m,
                        "invalid": [
                            f"copy phase {copy_sum * 1e3:.1f}ms below "
                            f"confidence floor {floor * 1e3:.1f}ms at "
                            f"dispatches {m} "
                            f"({'rung budget' if m < m_cap else 'cap'})"]}
                break
            m = min(m_cap, m * 2)
        ep.close()
        out[f"{size}B"] = rung
    # sanity gate (VERDICT r2 weak #3): the physical invariant of a
    # transfer ladder is BANDWIDTH monotone non-decreasing with size until
    # plateau — bigger chunks amortize fixed per-dispatch cost over more
    # bytes.  Per-chunk *latency* is NOT monotone in the overhead-
    # dominated regime (below ~1MB a rung's cost is Python dispatch,
    # roughly flat per batch, so per-chunk latency wobbles with batch
    # geometry rather than byte count); gating on it was the wrong
    # invariant.  Tolerance 0.5 still catches genuine cliffs — the 64KB
    # credit stall was 22x, and a window-overrun stall halved a rung
    # (0.48 < 0.5).
    bws = [(s, out[f"{s}B"].get("gbps")) for s in sizes]
    bad = [f"{a}B({ga}GB/s) > {b}B({gb}GB/s)"
           for (a, ga), (b, gb) in zip(bws, bws[1:])
           if ga is not None and gb is not None and gb < ga * 0.5]
    out["monotonic_bandwidth"] = not bad
    if bad:
        out["monotonic_violations"] = bad
    return out


_DCN_SERVER_SRC = """
import sys
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
from brpc_tpu.ici.channel import register_device_service
from brpc_tpu.rpc.server import Server
register_device_service("Bench", "Echo", lambda x: x)
srv = Server(enable_dcn=True)
srv.start("127.0.0.1", 0)
print(f"PORT={{srv.port}}", flush=True)
srv.run_until_interrupt()
"""

_DCN_CLIENT_SRC = """
import json, sys, time
sys.path.insert(0, {repo!r})
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from brpc_tpu.ici import dcn
ch = dcn.DcnChannel("ici://127.0.0.1:{port}/0")
topo = ch.handshake()
mode = "zero-copy" if topo.get("xfer") else "host-serialized"
mb = {mb}
x = np.random.default_rng(0).standard_normal(mb * 262144,
                                             dtype=np.float32)  # mb MiB
assert x.nbytes == mb * 1024 * 1024
import jax.numpy as jnp
xd = jnp.asarray(x)
out = ch.call_sync("Bench", "Echo", xd)       # warm both directions
best = None
for _ in range(5):
    t0 = time.perf_counter()
    out = ch.call_sync("Bench", "Echo", xd)
    jax.block_until_ready(out)   # async dispatch: force the pulled
    dt = time.perf_counter() - t0  # bytes to LAND inside the timing
    best = dt if best is None or dt < best else best
np.testing.assert_allclose(np.asarray(out)[:8], x[:8])
# request + response both move mb MB
print(json.dumps({{"mode": mode, "gbps": round(2 * mb / 1024 / best, 3),
                   "roundtrip_s": round(best, 4)}}))
"""


def bench_dcn(mb: int = 32) -> dict:
    """DCN data-plane rung (VERDICT r4 #10): two PROCESSES over loopback
    TCP, echoing a device array through the `_dcn` service — zero-copy
    fabric pull (jax.experimental.transfer) vs the host-serialized
    fallback (BRPC_DCN_DISABLE_XFER=1).  Both processes run forced-CPU:
    the rung measures the TRANSPORT path (control frames, fabric pulls,
    serializer), not HBM — chip-side numbers live in tensor_pipe.  A
    chip belongs to one process at a time, so the CPU is also what
    keeps two processes runnable while the parent holds the chip."""
    import subprocess
    import tempfile

    repo = os.path.dirname(os.path.abspath(__file__))
    out = {"payload_mb": mb, "platform": "cpu (forced; transport-path rung)"}
    env_base = dict(os.environ)
    env_base["JAX_PLATFORMS"] = "cpu"
    env_base.pop("BRPC_DCN_DISABLE_XFER", None)
    for label, extra in (("zero_copy", {}),
                         ("host_fallback", {"BRPC_DCN_DISABLE_XFER": "1"})):
        env = dict(env_base, **extra)
        server = subprocess.Popen(
            [sys.executable, "-c", _DCN_SERVER_SRC.format(repo=repo)],
            stdout=subprocess.PIPE, env=env, text=True)
        try:
            port = None
            deadline = time.monotonic() + 90
            import selectors
            sel = selectors.DefaultSelector()
            sel.register(server.stdout, selectors.EVENT_READ)
            while time.monotonic() < deadline and port is None:
                if server.poll() is not None:
                    break  # crashed before printing PORT=
                # bounded-wait poll: EOF would make readline() return ""
                # in a hot spin, a wedged-but-alive child would block it
                # past the deadline
                if not sel.select(timeout=1.0):
                    continue
                line = server.stdout.readline()
                if not line:
                    break
                if line.startswith("PORT="):
                    port = int(line.strip().split("=")[1])
            sel.close()
            if port is None:
                out[label] = {"error": "dcn server never came up"}
                continue
            r = subprocess.run(
                [sys.executable, "-c",
                 _DCN_CLIENT_SRC.format(repo=repo, port=port, mb=mb)],
                capture_output=True, text=True, env=env, timeout=240)
            if r.returncode != 0:
                tail = (r.stderr or "").strip().splitlines()[-1:]
                out[label] = {"error": tail[0] if tail else "client failed"}
            else:
                out[label] = json.loads(r.stdout.strip().splitlines()[-1])
        except subprocess.TimeoutExpired:
            out[label] = {"error": "dcn client timed out"}
        finally:
            server.terminate()
            try:
                server.wait(timeout=10)
            except subprocess.TimeoutExpired:
                # a child wedged in PJRT teardown must not discard the
                # measurements already collected
                server.kill()
                server.wait(timeout=10)
    zc = out.get("zero_copy", {})
    fb = out.get("host_fallback", {})
    if isinstance(zc, dict) and zc.get("gbps") and \
            isinstance(fb, dict) and fb.get("gbps"):
        out["zero_copy_speedup"] = round(zc["gbps"] / fb["gbps"], 2)
    return out


def _med_spread(vals, key: str, nd: int = 1) -> dict:
    """median + min/max spread over trials — the rung family's shared
    jitter discipline (and the shape tools/perf_diff.py gates on)."""
    vs = sorted(vals)
    return {key: round(vs[len(vs) // 2], nd),
            f"{key}_spread": [round(vs[0], nd), round(vs[-1], nd)],
            "trials": len(vs)}


def bench_microbench(trials=3, duration_s=0.4, quick=False):
    """Per-stage host micro-benchmark suite (ISSUE 6; in the spirit of
    PAPERS.md "Designing a Micro-Benchmark Suite to Evaluate gRPC for
    TensorFlow": attribute the RPC path's host overhead PER STAGE
    before optimizing any of it).  Each rung isolates ONE serving
    stage on the host:

      * frame_pump        — the native C++ client pump -> native echo
                            loop (the non-Python ceiling);
      * batch_assembly    — DynamicBatcher formation/scatter with a
                            trivial numpy batch_fn (no jit, no device);
      * radix_prefix_match — KVCacheStore.probe longest-prefix match
                            against a warmed radix tree;
      * page_alloc_release — store admit/retire cycles of uncached
                            prompts (page alloc, splice bookkeeping,
                            release);
      * emit_fanout       — emit-buffer push/pop through producer/
                            consumer pairs (the per-token delivery
                            path), plus a 4-pair concurrency probe;
      * span_submit       — rpcz span create/annotate/submit + drain to
                            the recent-span store;
      * host_us_per_token — serving_host_us_per_token over a real
                            DecodeEngine decode (the de-GIL headline);
      * sampler_overhead  — window-limited batcher qps with the
                            always-on profiler stopped vs running at its
                            default rate (the <2% always-on claim).

    Every number is CPU-valid by construction: no rung touches an
    accelerator (the kvcache rungs run on the jax CPU backend), so the
    suite publishes on every round and the de-GIL trajectory
    (ROADMAP item 4) never goes blind.  3-trial median + spread, like
    every other rung family.

    ISSUE 9: the de-GIL'd stages (batch_assembly, emit_fanout,
    span_submit, host_us_per_token) publish an explicit A/B — the
    headline metric rides the NATIVE path (the shipped configuration),
    with the pure-Python fallback (`native_hot_path_enabled` off)
    alongside as `*_python` and the per-round `native_speedup` interval
    ([min_native/max_python, max_native/min_python]): a lower bound
    above 1.0 is a beyond-spread win, no cross-round baseline needed."""
    import threading

    import numpy as np

    from brpc_tpu import flags as _flags, native_path, rpcz
    from brpc_tpu.serving import DynamicBatcher

    if quick:
        trials, duration_s = 2, 0.15
    out = {}
    have_native = native_path._core_lib() is not None

    def _with_flag(native, fn):
        was = _flags.get_flag("native_hot_path_enabled", True)
        _flags.set_flag("native_hot_path_enabled", bool(native))
        try:
            return fn()
        finally:
            _flags.set_flag("native_hot_path_enabled", was)

    def _ab(trial, unit):
        """The per-stage A/B: `trial(k, tag)` under the flag OFF
        (python fallback) and ON (native).  Headline `qps` = native
        median when the core is available, else the python median."""
        py = [_with_flag(False, lambda k=k: trial(k, "py"))
              for k in range(trials)]
        pm = _med_spread(py, "qps")
        entry = {}
        if have_native:
            nat = [_with_flag(True, lambda k=k: trial(k, "nat"))
                   for k in range(trials)]
            entry.update(_med_spread(nat, "qps"))
            entry["qps_python"] = pm["qps"]
            entry["qps_python_spread"] = pm["qps_spread"]
            if pm["qps"]:
                entry["native_speedup"] = round(
                    entry["qps"] / pm["qps"], 2)
                entry["native_speedup_spread"] = [
                    round(min(nat) / max(py), 2),
                    round(max(nat) / min(py), 2)]
        else:
            entry.update(pm)
            entry["note_native"] = ("native core unavailable: "
                                    "python path only")
        entry["unit"] = unit
        return entry

    # ---- frame_pump ----
    frames = 30_000 if quick else 100_000
    rs = []
    for _ in range(trials):
        r = bench_native_echo(conns=2, inflight=16, total=frames)
        if r["completed"]:
            rs.append(r["qps"])
    if rs:
        out["frame_pump"] = {**_med_spread(rs, "qps"),
                             "unit": "frames/s", "frames": frames}
    else:
        # the rung discipline: a rung that cannot run must SAY so —
        # a 0.0 wearing the metric's name would read as a real
        # collapse to perf_diff and poison the round as a baseline
        out["frame_pump"] = {"error": "native echo pump completed no "
                                      "trial", "frames": frames}

    # shared batcher-hammer: `threads` workers submit_wait against a
    # numpy-fn batcher for duration_s, returns items/s (used by the
    # batch_assembly and sampler_overhead rungs)
    def batcher_hammer(name, *, max_batch_size, max_delay_us, length,
                       threads):
        b = DynamicBatcher(lambda x: x.sum(axis=1),
                           max_batch_size=max_batch_size,
                           max_delay_us=max_delay_us,
                           batch_buckets=(max_batch_size,),
                           length_buckets=(length,), name=name)
        item = np.ones((length,), np.float32)
        try:
            b.submit_wait(item, timeout_s=30)
            stop = time.monotonic() + duration_s
            counts = [0] * threads

            def w(i):
                while time.monotonic() < stop:
                    b.submit_wait(item, timeout_s=30)
                    counts[i] += 1

            ts = [threading.Thread(target=w, args=(i,))
                  for i in range(threads)]
            t0 = time.monotonic()
            [t.start() for t in ts]
            [t.join(60) for t in ts]
            return sum(counts) / (time.monotonic() - t0)
        finally:
            b.close()

    # ---- batch_assembly (A/B: native GIL-released formation vs numpy
    # scatter loop, through the batcher's real _form_batch) ----
    #
    # The end-to-end batcher hammer above is WINDOW-bound (condvar
    # round-trips dominate at ~1ms/item), so formation cost is
    # invisible in it; this rung isolates the formation stage itself at
    # a prefill-realistic shape (64 prompts x 4k int32 tokens = 1MB of
    # scatter per formation).  4 concurrent formers is the headline —
    # the shipped shape is formation racing submitters for the GIL —
    # with the 1-thread A/B and the 4t/1t thread-scaling ratio
    # alongside (the speedup_at_peak plateau BENCH_r03-r05 tracked).
    from brpc_tpu.serving.batcher import _Pending

    ba_bs, ba_len = 64, 4096
    ba_live = [_Pending(np.arange(ba_len - (i % 129), dtype=np.int32),
                        ba_len - (i % 129), None,
                        lambda code, text, result: None)
               for i in range(ba_bs)]
    ba_b = DynamicBatcher(lambda x: x, max_batch_size=ba_bs,
                          max_delay_us=200, batch_buckets=(ba_bs,),
                          length_buckets=(ba_len,), dtype=np.int32,
                          name="microbench_ba_form")

    def ba_trial(k, tag, threads):
        iters = 40 if quick else 150
        barrier = threading.Barrier(threads + 1)

        def w():
            barrier.wait()
            for _ in range(iters):
                ba_b._form_batch(ba_live, ba_bs, ba_len)

        ts = [threading.Thread(target=w) for _ in range(threads)]
        [t.start() for t in ts]
        barrier.wait()
        t0 = time.monotonic()
        [t.join(120) for t in ts]
        return threads * iters / (time.monotonic() - t0)

    try:
        ba = _ab(lambda k, tag: ba_trial(k, tag, 4),
                 "batch formations/s (64x4096 int32 prompt scatter "
                 "through DynamicBatcher._form_batch, 4 concurrent "
                 "formers)")
        ba1 = _ab(lambda k, tag: ba_trial(k, tag, 1), "")
    finally:
        ba_b.close()
    ba["qps_1t"] = ba1["qps"]
    ba["qps_1t_spread"] = ba1.get("qps_spread")
    if ba1["qps"]:
        ba["speedup_at_peak"] = round(ba["qps"] / ba1["qps"], 2)
        lo1, hi1 = ba1.get("qps_spread", [ba1["qps"], ba1["qps"]])
        lo4, hi4 = ba.get("qps_spread", [ba["qps"], ba["qps"]])
        ba["speedup_at_peak_spread"] = [round(lo4 / hi1, 2),
                                        round(hi4 / lo1, 2)]
    if have_native and ba1.get("qps_python"):
        ba["qps_python_1t"] = ba1["qps_python"]
        ba["speedup_at_peak_python"] = round(
            ba["qps_python"] / ba1["qps_python"], 2)
    out["batch_assembly"] = ba

    # ---- radix_prefix_match + page_alloc_release (share a store) ----
    from brpc_tpu.kvcache import KVCacheStore

    def radix_trial(k):
        pt = 16
        store = KVCacheStore(page_tokens=pt, page_bytes=pt * 64,
                             max_blocks=64,
                             name=f"microbench_radix_{k}")
        try:
            # warm the tree with 8 cached prompts
            prompts = [[1000 * j + i for i in range(4 * pt)]
                       for j in range(8)]
            for p in prompts:
                store.retire(store.admit(p), cache=True)
            probe = np.asarray(prompts[3] + [7] * pt)
            n = 500 if quick else 3000
            t0 = time.monotonic()
            for _ in range(n):
                store.probe(probe)
            return n / (time.monotonic() - t0)
        finally:
            store.clear()
            store.close()

    out["radix_prefix_match"] = {
        **_med_spread([radix_trial(k) for k in range(trials)], "qps"),
        "unit": "longest-prefix probes/s (warm radix, 64-token prompts)"}

    def page_trial(k):
        pt = 16
        store = KVCacheStore(page_tokens=pt, page_bytes=pt * 64,
                             max_blocks=64,
                             name=f"microbench_page_{k}")
        try:
            n = 30 if quick else 120
            t0 = time.monotonic()
            for i in range(n):
                # unique prompts: every admit allocs+splices 2 fresh
                # pages, every retire releases them (cache=False)
                seq = store.admit([9_000_000 + i * 2 * pt + j
                                   for j in range(2 * pt)])
                store.retire(seq, cache=False)
            return n / (time.monotonic() - t0)
        finally:
            store.clear()
            store.close()

    out["page_alloc_release"] = {
        **_med_spread([page_trial(k) for k in range(trials)], "qps"),
        "unit": "admit+retire cycles/s (2 pages alloc/release each)"}

    # ---- emit_fanout (A/B: native token ring vs Python _EmitBuf) ----
    from brpc_tpu.serving.engine import _NativeEmitBuf, _make_emit_buf

    def emit_trial(k, pairs=1):
        # buffer type decided by the flag at construction, like the
        # engine's per-request choice
        bufs = [_make_emit_buf(1024) for _ in range(pairs)]
        n = 3000 if quick else 20_000
        drained = [0] * pairs

        def consume(i, buf):
            if isinstance(buf, _NativeEmitBuf):
                while True:
                    cnt, term, _err = buf.pop_batch(5.0)
                    drained[i] += cnt
                    if term:
                        return
            else:
                while True:
                    item = buf.pop(5.0)
                    if item is None or item[0] == "done":
                        return
                    drained[i] += 1

        def produce(buf):
            pushed = 0
            while pushed < n:
                if buf.push(pushed):
                    pushed += 1
                else:
                    time.sleep(0)   # full: yield instead of spinning
            buf.push_terminal(None)

        ts = []
        for i, buf in enumerate(bufs):
            ts.append(threading.Thread(target=consume, args=(i, buf)))
            ts.append(threading.Thread(target=produce, args=(buf,)))
        t0 = time.monotonic()
        [t.start() for t in ts]
        [t.join(120) for t in ts]
        return sum(drained) / (time.monotonic() - t0)

    out["emit_fanout"] = _ab(
        lambda k, tag: emit_trial(k),
        "tokens/s through one bounded emit buffer pair")

    # concurrency probe: 4 producer/consumer pairs side by side (4
    # concurrent token streams).  The Python _EmitBuf pays a GIL'd lock
    # round-trip per token so added pairs DEGRADE its aggregate; native
    # pairs hold aggregate flat — one sub-microsecond GIL-held push per
    # token (the ctypes-per-token variant collapsed 14x here: every
    # push's GIL release/reacquire became a handoff convoy under 4
    # producers, which is why tokring_push rides the C extension).
    # speedup_at_peak carries a spread so perf_diff gates a future
    # convoy regression.
    scaling = {"pairs": 4}
    py1 = out["emit_fanout"].get("qps_python",
                                 out["emit_fanout"]["qps"])
    py4m = _med_spread([_with_flag(False,
                                   lambda k=k: emit_trial(k, pairs=4))
                        for k in range(trials)], "qps_python_4p")
    scaling["qps_python_4p"] = py4m["qps_python_4p"]
    scaling["speedup_at_peak_python"] = (
        round(py4m["qps_python_4p"] / py1, 2) if py1 else None)
    if have_native:
        nat4 = _med_spread([_with_flag(True,
                                       lambda k=k: emit_trial(k, pairs=4))
                            for k in range(trials)], "qps_native_4p")
        scaling["qps_native_4p"] = nat4["qps_native_4p"]
        scaling["qps_native_4p_spread"] = nat4["qps_native_4p_spread"]
        nat1 = out["emit_fanout"]["qps"]
        n1lo, n1hi = out["emit_fanout"].get("qps_spread", [nat1, nat1])
        if nat1:
            n4lo, n4hi = nat4["qps_native_4p_spread"]
            scaling["speedup_at_peak"] = round(
                nat4["qps_native_4p"] / nat1, 2)
            scaling["speedup_at_peak_spread"] = [
                round(n4lo / n1hi, 2), round(n4hi / n1lo, 2)]
    out["emit_fanout_scaling"] = scaling

    # ---- span_submit (A/B: native MPSC queue vs collector submit) ----
    def span_trial(k, tag):
        was = (rpcz.enabled(), rpcz.sample_rate())
        rpcz.set_enabled(True, 1.0)
        try:
            n = 500 if quick else 2000
            t0 = time.monotonic()
            for i in range(n):
                sp = rpcz.new_span("client", "Micro", "Bench")
                sp.annotate("microbench span")
                rpcz.submit(sp)
            # land every span whichever path it took (native queue or
            # collector family) — submit-only would time pushes into an
            # unbounded queue and flatter the native number
            rpcz.flush()
            return n / (time.monotonic() - t0)
        finally:
            rpcz.set_enabled(*was)

    # cold-start warmup OUTSIDE the timed trials (span dataclass +
    # collector import + drainer-thread spinup land on the first call
    # and were making trial 1 read 3x slower than trials 2-3)
    _with_flag(False, lambda: span_trial(-1, "warm"))
    _with_flag(True, lambda: span_trial(-1, "warm"))
    out["span_submit"] = _ab(
        span_trial,
        "spans/s (create+annotate+submit+drain to the recent-span "
        "store; the 2000/s rpcz speed limit applies beyond it)")

    # ---- host_us_per_token (the de-GIL headline, ISSUE 9) ----
    from brpc_tpu.butil import hostcpu
    from brpc_tpu.serving import DecodeEngine

    def hupt_trial(k, tag):
        R, T = (4, 64) if quick else (8, 192)
        eng = DecodeEngine(lambda t, p: t + 1, num_slots=8,
                           kv_bytes_per_slot=256,
                           name=f"mb_hupt_{tag}_{k}")
        try:
            before = hostcpu.snapshot()
            dones = []
            for r in range(R):
                ev = threading.Event()
                dones.append(ev)
                eng.submit([r + 1], T, lambda tok: None,
                           lambda err, ev=ev: ev.set())
            for ev in dones:
                ev.wait(120)
        finally:
            eng.close()
        after = hostcpu.snapshot()
        toks = after["tokens"] - before["tokens"]
        host = sum(after["per_stage_us"][s] - before["per_stage_us"][s]
                   for s in hostcpu.HOST_STAGES)
        return host / max(1, toks)

    pm = _med_spread([_with_flag(False, lambda k=k: hupt_trial(k, "py"))
                      for k in range(trials)],
                     "serving_host_us_per_token_python", nd=2)
    hupt = {"serving_host_us_per_token_python":
            pm["serving_host_us_per_token_python"],
            "serving_host_us_per_token_python_spread":
            pm["serving_host_us_per_token_python_spread"]}
    if have_native:
        nm = _med_spread([_with_flag(True,
                                     lambda k=k: hupt_trial(k, "nat"))
                          for k in range(trials)],
                         "serving_host_us_per_token", nd=2)
        hupt["serving_host_us_per_token"] = \
            nm["serving_host_us_per_token"]
        hupt["serving_host_us_per_token_spread"] = \
            nm["serving_host_us_per_token_spread"]
        if pm["serving_host_us_per_token_python"]:
            hupt["reduction_pct"] = round(
                100.0 * (1 - nm["serving_host_us_per_token"]
                         / pm["serving_host_us_per_token_python"]), 1)
    else:
        hupt["serving_host_us_per_token"] = \
            hupt["serving_host_us_per_token_python"]
        hupt["serving_host_us_per_token_spread"] = \
            hupt["serving_host_us_per_token_python_spread"]
    hupt["unit"] = ("python-host CPU us per emitted token across the "
                    "serving stages (model_compute excluded), real "
                    "DecodeEngine decode, 8 concurrent requests")
    hupt["trials"] = trials
    out["host_us_per_token"] = hupt

    # ---- stream_scaling (the ≥1.5x thread-scaling criterion, ISSUE 9)
    #
    # The real shipped concurrency shape: ONE decode step loop fanning
    # tokens out to N concurrent streams, each with its own emitter.
    # Aggregate tokens/s at 4 streams over 1 stream is speedup_at_peak
    # — the number BENCH_r03–r05 watched plateau at 1.06–1.25x on the
    # GIL-bound path.  With native rings the emitters park OFF the GIL
    # (pop waits in native code) and the step loop pushes all slots in
    # one GIL-released call, so added streams stop convoying the loop.
    # (The synthetic per-stage rungs above can't carry this criterion
    # honestly: their producers are Python loops — GIL-serialized by
    # construction — and the 64x4096 formation shape saturates DRAM
    # bandwidth near 30 GB/s, capping ANY implementation's scaling.)
    def stream_trial(k, tag, streams):
        T = 400 if quick else 1500
        eng = DecodeEngine(lambda t, p: t + 1, num_slots=4,
                           kv_bytes_per_slot=256,
                           name=f"mb_ss_{tag}_{streams}_{k}")
        try:
            evs = []
            t0 = time.monotonic()
            for r in range(streams):
                ev = threading.Event()
                evs.append(ev)
                eng.submit([r + 1], T, lambda tok: None,
                           lambda err, ev=ev: ev.set())
            for ev in evs:
                ev.wait(300)
            return streams * T / (time.monotonic() - t0)
        finally:
            eng.close()

    ss4 = _ab(lambda k, tag: stream_trial(k, tag, 4),
              "aggregate tokens/s, 4 concurrent streams through one "
              "DecodeEngine step loop (trivial step_fn)")
    ss1 = _ab(lambda k, tag: stream_trial(k, tag, 1), "")
    ss4["streams"] = 4
    ss4["qps_1s"] = ss1["qps"]
    ss4["qps_1s_spread"] = ss1.get("qps_spread")
    if ss1["qps"]:
        ss4["speedup_at_peak"] = round(ss4["qps"] / ss1["qps"], 2)
        lo1, hi1 = ss1.get("qps_spread", [ss1["qps"], ss1["qps"]])
        lo4, hi4 = ss4.get("qps_spread", [ss4["qps"], ss4["qps"]])
        ss4["speedup_at_peak_spread"] = [round(lo4 / hi1, 2),
                                         round(hi4 / lo1, 2)]
    if have_native and ss1.get("qps_python"):
        ss4["speedup_at_peak_python"] = round(
            ss4["qps_python"] / ss1["qps_python"], 2)
    out["stream_scaling"] = ss4

    # ---- sampler_overhead ----
    from brpc_tpu.builtin.sampler import HotspotSampler

    def window_limited_qps(k, label):
        # threads << max_batch_size: every batch forms at WINDOW
        # expiry, so qps ~ threads/window — nearly deterministic, which
        # is what makes a small overhead measurable at all
        return batcher_hammer(f"microbench_so_{label}_{k}",
                              max_batch_size=64, max_delay_us=2000,
                              length=16, threads=4)

    samp = HotspotSampler.instance()
    was_running = samp.running
    samp.stop()
    off = [window_limited_qps(k, "off") for k in range(trials)]
    samp.start()
    try:
        on = [window_limited_qps(k, "on") for k in range(trials)]
    finally:
        if not was_running:
            samp.stop()
    off_med = sorted(off)[len(off) // 2]
    on_med = sorted(on)[len(on) // 2]
    out["sampler_overhead"] = {
        "qps_off": round(off_med, 1),
        "qps_off_spread": [round(min(off), 1), round(max(off), 1)],
        "qps_on": round(on_med, 1),
        "qps_on_spread": [round(min(on), 1), round(max(on), 1)],
        "overhead_pct": round((off_med - on_med) / off_med * 100.0, 2)
        if off_med else None,
        "trials": trials,
        "unit": "window-limited batcher qps, always-on sampler off vs "
                "on at its default rate",
    }

    # ---- flight_recorder overhead (ISSUE 15 acceptance) ----
    # The recorder is ALWAYS-ON; this rung proves it can be: the echo
    # pump (per-frame socket/executor events) and the emit fan-out
    # (per-batch TokenRing events) re-run with recording off, and the
    # on/off delta must stay within 2% beyond spread.
    from brpc_tpu.butil import flight as _flight
    if _flight.available():
        def _fl_ab(trial, unit):
            _flight.set_enabled(True)
            on = [trial(k) for k in range(trials)]
            _flight.set_enabled(False)
            try:
                off = [trial(k) for k in range(trials)]
            finally:
                _flight.set_enabled(True)
            on_m = _med_spread(on, "qps_on")
            off_m = _med_spread(off, "qps_off")
            entry = {**on_m, **off_m, "unit": unit}
            if off_m["qps_off"]:
                entry["overhead_pct"] = round(
                    (off_m["qps_off"] - on_m["qps_on"])
                    / off_m["qps_off"] * 100.0, 2)
            return entry

        fl = {}
        fl["emit_fanout"] = _fl_ab(
            lambda k: _with_flag(True, lambda: emit_trial(k)),
            "tokens/s through one native emit buffer pair, "
            "recorder on vs off")
        ec_frames = 10_000 if quick else 40_000
        def _echo_trial(k):
            r = bench_native_echo(conns=2, inflight=16, total=ec_frames)
            return r["qps"] if r["completed"] else 0.0
        fl["echo"] = _fl_ab(
            _echo_trial, "native echo frames/s, recorder on vs off")
        out["flight_recorder"] = fl

    out["cpu_valid"] = True
    out["note"] = ("per-stage host microbenches (ISSUE 6): every rung "
                   "isolates one serving stage on the host with no "
                   "accelerator dependency, so these numbers publish "
                   "on every round; 3-trial median+spread")
    return out


def _run_cpu_subcommand(name: str, timeout_s: float = 900) -> dict:
    """Run a CPU-valid rung family (`python bench.py <name>`) in a
    FRESH forced-CPU subprocess: these rungs are statements about host
    code, and a child of the process that holds the chip must stay off
    it."""
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), name],
        capture_output=True, text=True, env=env, timeout=timeout_s)
    if r.returncode != 0:
        tail = (r.stderr or "").strip().splitlines()[-1:] or ["no stderr"]
        return {"error": f"{name} subprocess rc={r.returncode}: "
                         f"{tail[0]}"}
    for line in reversed(r.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    return {"error": f"{name} subprocess produced no JSON"}


def _run_microbench_subprocess(timeout_s: float = 900) -> dict:
    return _run_cpu_subcommand("microbench", timeout_s)


def bench_migrate(shared_ratios=(0.0, 0.5, 0.9), n_requests=12,
                  prompt_tokens=64, trials=3):
    """Migration rung (ISSUE 7): migrate-vs-recompute ADMIT latency and
    re-decoded-token ratio at 0/50/90% shared prefix, through the real
    ``_kvmig`` wire path (loopback server, host-serialized envelope —
    the in-process fallback data plane).

    Workload per ratio: the shared prefix is committed on a SOURCE
    store and migrated to a destination store behind a loopback
    migration service; then `n_requests` prompts opening with that
    prefix admit on the destination (migrated path) and on a COLD
    store (recompute path).  Reported per ratio:

      * migrated_admit_us / recompute_admit_us — mean per-admit wall
        time; at >=50% shared prefix the migrated path must win with
        NON-OVERLAPPING spread intervals (the ISSUE 7 acceptance gate,
        and perf_diff gates both series across rounds);
      * redecoded_token_ratio — (prompt tokens - cache-hit tokens) /
        prompt tokens at the destination: 1.0 means migration bought
        nothing, 1-ratio means every migrated page was a hit.

    CPU-valid by construction (page splices are jit CPU ops; no
    accelerator is touched), 3-trial median+spread."""
    import brpc_tpu as brpc
    from brpc_tpu.kvcache import KVCacheStore
    from brpc_tpu.migrate import PageMigrator, register_migration

    pt = 8

    def mk_store(tag):
        return KVCacheStore(page_tokens=pt, page_bytes=pt * 64,
                            max_blocks=128, name=tag)

    def admit_wave(store, reqs):
        # warm admit outside timing: the first splice compiles the
        # dynamic_update_slice shapes
        seq = store.admit([123456789, 2, 3])
        store.retire(seq, cache=False)
        t0 = time.monotonic()
        for p in reqs:
            seq = store.admit(p)
            store.retire(seq, cache=False)
        return (time.monotonic() - t0) / len(reqs) * 1e6

    def one_trial(ratio, k):
        tag = f"bench_mig_r{int(ratio * 100)}_{k}"
        shared_n = int(prompt_tokens * ratio) // pt * pt
        shared = [5000 + k * 7919 + j for j in range(shared_n)]

        def prompts(base):
            return [shared
                    + [base + i * prompt_tokens + j
                       for j in range(prompt_tokens - shared_n)]
                    for i in range(n_requests)]

        src = mk_store(f"{tag}_src")
        dst = mk_store(f"{tag}_dst")
        cold = mk_store(f"{tag}_cold")
        srv = brpc.Server(enable_dcn=True)
        register_migration(srv, dst)
        srv.start("127.0.0.1", 0)
        try:
            if shared_n:
                seq = src.admit(shared + [1])
                src.retire(seq, cache=True)
                m = PageMigrator(src, name=f"{tag}_m")
                pages = m.migrate(shared, f"127.0.0.1:{srv.port}")
                assert pages == shared_n // pt, (pages, shared_n)
            h0, p0 = dst.hit_tokens.get_value(), \
                dst.prompt_tokens.get_value()
            mig_us = admit_wave(dst, prompts(1_000_000))
            dp = dst.prompt_tokens.get_value() - p0
            dh = dst.hit_tokens.get_value() - h0
            redecode = (dp - dh) / dp if dp else 1.0
            rec_us = admit_wave(cold, prompts(2_000_000))
            return mig_us, rec_us, redecode
        finally:
            srv.stop()
            srv.join()
            for st in (src, dst, cold):
                st.clear()
                st.close()

    out = {}
    for ratio in shared_ratios:
        rs = [one_trial(ratio, k) for k in range(trials)]
        migs = sorted(r[0] for r in rs)
        recs = sorted(r[1] for r in rs)
        reds = sorted(r[2] for r in rs)
        out[f"shared{int(ratio * 100)}"] = {
            "migrated_admit_us": round(migs[len(migs) // 2], 1),
            "migrated_admit_us_spread": [round(migs[0], 1),
                                         round(migs[-1], 1)],
            "recompute_admit_us": round(recs[len(recs) // 2], 1),
            "recompute_admit_us_spread": [round(recs[0], 1),
                                          round(recs[-1], 1)],
            "redecoded_token_ratio": round(reds[len(reds) // 2], 4),
            "redecoded_token_ratio_spread": [round(reds[0], 4),
                                             round(reds[-1], 4)],
            "migrated_beats_recompute_beyond_spread":
                migs[-1] < recs[0],
            "trials": trials,
        }
    out["cpu_valid"] = True
    out["note"] = ("migration rung (brpc_tpu/migrate): per-admit "
                   "latency on a store that received the shared "
                   "prefix over the _kvmig wire vs a cold store that "
                   "recomputes, plus the re-decoded-token ratio; the "
                   "ISSUE 7 gate is migrated beating recompute beyond "
                   "spread at >=50% shared prefix")
    return out


def bench_model(shared_ratios=(0.0, 0.5, 0.9), n_requests=6,
                prompt_tokens=32, gen_tokens=12, trials=3):
    """Real-model serving rung (ISSUE 10): the TransformerRunner — a
    real transformer whose K/V live in the paged HBM layout and whose
    attention reads through the engine's page tables — vs the
    token-id HARNESS (the PR 2/3 stand-in step function) on the same
    engine/kvcache machinery, at 0/50/90% shared prefix.

    Per ratio, per mode:

      * tokens_per_s — generated tokens over the wave's wall time
        (3-trial median + spread; perf_diff gates both series);
      * prefill_skip_ratio — prompt tokens served by the radix cache /
        prompt tokens seen (higher = prefill compute actually skipped;
        for the REAL runner this is genuine attention-K/V reuse, not
        token bookkeeping — the prefill-skip savings ROADMAP item 1
        asked the bench to measure);
      * runner_vs_harness — runner/harness tokens_per_s (informational:
        the gap IS the model's FLOPs + kernel cost on this backend).

    CPU-valid by construction (the gather backend of the paged kernel
    is jax CPU ops); the full bench shells out here exactly like the
    microbench/migrate rungs."""
    import jax

    from brpc_tpu.models.runner import (TransformerConfig,
                                        TransformerRunner,
                                        init_runner_params,
                                        make_store_for)
    from brpc_tpu.kvcache import KVCacheStore
    from brpc_tpu.serving import DecodeEngine

    cfg = TransformerConfig()
    params = init_runner_params(cfg)
    pt = 8
    buckets = (16, 32, 64)

    def mk_real(tag):
        store = make_store_for(cfg, page_tokens=pt, max_blocks=64,
                               name=f"{tag}_rkv")
        runner = TransformerRunner(params, cfg, store=store,
                                   name=f"{tag}_m")
        eng = DecodeEngine(runner=runner, num_slots=4, store=store,
                           max_pages_per_slot=16,
                           prefill_buckets=buckets, name=f"{tag}_re")
        return store, eng

    def mk_harness(tag):
        store = KVCacheStore(page_tokens=pt, page_bytes=pt * 64,
                             max_blocks=64, name=f"{tag}_hkv")

        @jax.jit
        def step(tokens, positions, pages):
            return (tokens * 7 + positions) % 997

        eng = DecodeEngine(step, num_slots=4, store=store,
                           max_pages_per_slot=16,
                           prefill_buckets=buckets, name=f"{tag}_he")
        return store, eng

    def wave(eng, prompts):
        evs = []
        for p in prompts:
            ev = threading.Event()
            evs.append(ev)
            eng.submit(p, gen_tokens, lambda t: None,
                       lambda e, ev=ev: ev.set())
        for ev in evs:
            if not ev.wait(600):
                raise RuntimeError("model bench wave hung")

    def one_trial(ratio, k, mk):
        tag = f"bench_model_r{int(ratio * 100)}_{k}"
        shared_n = int(prompt_tokens * ratio) // pt * pt
        shared = [(5000 + k * 131 + j) % 997 for j in range(shared_n)]

        def prompts(base):
            return [shared
                    + [(base + i * prompt_tokens + j) % 997
                       for j in range(prompt_tokens - shared_n)]
                    for i in range(n_requests)]

        store, eng = mk(tag)
        try:
            # warm: compiles the bucket shapes AND seeds the radix
            # tree with the shared prefix (the steady-state the ratio
            # models), outside the timed window
            wave(eng, prompts(900_000)[:2])
            h0 = store.hit_tokens.get_value()
            p0 = store.prompt_tokens.get_value()
            t0 = time.monotonic()
            wave(eng, prompts(1_000_000))
            dt = time.monotonic() - t0
            dp = store.prompt_tokens.get_value() - p0
            dh = store.hit_tokens.get_value() - h0
            skip = dh / dp if dp else 0.0
            return n_requests * gen_tokens / dt, skip
        finally:
            eng.close()
            store.clear()
            store.close()

    def series(mk):
        out = {}
        for ratio in shared_ratios:
            rs = [one_trial(ratio, k, mk) for k in range(trials)]
            tps = sorted(r[0] for r in rs)
            skips = sorted(r[1] for r in rs)
            out[f"shared{int(ratio * 100)}"] = {
                "tokens_per_s": round(tps[len(tps) // 2], 1),
                "tokens_per_s_spread": [round(tps[0], 1),
                                        round(tps[-1], 1)],
                "prefill_skip_ratio": round(skips[len(skips) // 2], 4),
                "prefill_skip_ratio_spread": [round(skips[0], 4),
                                              round(skips[-1], 4)],
                "trials": trials,
            }
        return out

    out = {"runner": series(mk_real), "harness": series(mk_harness)}
    for key in out["runner"]:
        r = out["runner"][key]["tokens_per_s"]
        h = out["harness"][key]["tokens_per_s"]
        out["runner"][key]["runner_vs_harness"] = \
            round(r / h, 4) if h else None
    out["cpu_valid"] = True
    out["config"] = {"prompt_tokens": prompt_tokens,
                     "gen_tokens": gen_tokens,
                     "n_requests": n_requests,
                     "d_model": cfg.d_model, "n_layers": cfg.n_layers,
                     "n_heads": cfg.n_heads,
                     "kv_bytes_per_token": cfg.kv_bytes_per_token}
    out["note"] = ("real-model serving rung (ISSUE 10): tokens/s and "
                   "prefill-skip with the TransformerRunner's paged "
                   "attention over the HBM page tables vs the token-id "
                   "harness on identical machinery; CPU gather backend "
                   "— device rounds A/B the pallas kernel path")
    return out


def model_main(argv) -> None:
    """`python bench.py model`: run ONLY the real-model serving rung
    and print one JSON object on stdout (progress on stderr) — the
    `make model` bench entry and the subprocess the full bench run
    shells out to."""
    log("model: real-runner vs harness serving rung...")
    out = bench_model()
    for k, v in out.items():
        if isinstance(v, dict):
            log(f"  {k}: {json.dumps(v)}")
    print(json.dumps(out))


def bench_speculative(depths=(2, 4, 8), n_requests=4, prompt_tokens=16,
                      gen_tokens=48, trials=3):
    """Speculative-decoding rung (ISSUE 11): tokens/s of the
    TransformerRunner engine PLAIN vs SPECULATIVE at draft depths
    2/4/8, on the same store/engine machinery.

    Operating point: the draft is the host-side NGramProposer (prompt
    lookup) — draft cost ≪ target cost, the regime the ISSUE names —
    and the workload decodes long enough (``gen_tokens``) that the
    target's greedy output becomes self-repeating, so drafts actually
    accept (``accept_rate`` is published per depth; a rung whose
    drafts never accepted would be measuring nothing).  Per depth:
    tokens/s 3-trial median+spread (perf_diff gates the series) plus
    accept_rate / tokens_per_step medians and the ISSUE acceptance
    probe ``spec_beats_plain_beyond_spread`` (spread intervals
    disjoint in the faster direction at >=1 depth).

    CPU-valid by construction — the gather backend of the paged
    kernel; the full bench shells out here exactly like the
    microbench/migrate/model rungs."""
    from brpc_tpu.models.runner import (TransformerConfig,
                                        TransformerRunner,
                                        init_runner_params,
                                        make_store_for)
    from brpc_tpu.serving import DecodeEngine, NGramProposer
    from brpc_tpu.serving.engine import SPEC_ACCEPTED, SPEC_PROPOSED

    cfg = TransformerConfig()
    params = init_runner_params(cfg)
    pt = 8
    buckets = (16, 32)

    def prompts(k):
        return [[(100 + k * 131 + i * 37 + j) % 997
                 for j in range(prompt_tokens)]
                for i in range(n_requests)]

    def wave(eng, ps, n):
        evs = []
        errs: list = []
        for p in ps:
            ev = threading.Event()
            evs.append(ev)
            eng.submit(p, n, lambda t: None,
                       lambda e, ev=ev: (errs.append(e) if e is not None
                                         else None, ev.set()))
        for ev in evs:
            if not ev.wait(600):
                raise RuntimeError("speculative bench wave hung")
        if errs:
            # a failed generation must fail the TRIAL: counting its
            # full token budget over a shortened wall time would
            # inflate the series the acceptance gate reads
            raise RuntimeError(f"speculative bench wave errored: "
                               f"{errs[0]}")

    def one_trial(depth, k):
        tag = f"bench_spec_d{depth}_{k}"
        store = make_store_for(cfg, page_tokens=pt, max_blocks=64,
                               name=f"{tag}_kv")
        runner = TransformerRunner(params, cfg, store=store,
                                   name=f"{tag}_m")
        kw = {}
        if depth:
            kw = dict(draft_runner=NGramProposer(), draft_len=depth)
        eng = DecodeEngine(runner=runner, num_slots=n_requests,
                           store=store, max_pages_per_slot=24,
                           prefill_buckets=buckets, name=f"{tag}_e",
                           **kw)
        try:
            # full-length warm wave: the splice/verify jit shapes vary
            # with ACCEPT DEPTH (a kept-k commit splices k+1 rows), so
            # a short warm leaves compiles to fall inside the timing
            wave(eng, prompts(k), gen_tokens)
            a0, p0 = SPEC_ACCEPTED.get_value(), SPEC_PROPOSED.get_value()
            s0 = eng.steps.get_value()
            t0 = time.monotonic()
            wave(eng, prompts(k), gen_tokens)
            dt = time.monotonic() - t0
            da = SPEC_ACCEPTED.get_value() - a0
            dp = SPEC_PROPOSED.get_value() - p0
            ds = eng.steps.get_value() - s0
            # tokens_per_step is PER SLOT (emitted tokens per verify
            # iteration of one generation): the number the per-
            # generation span annotation carries, comparable across
            # slot counts
            return (n_requests * gen_tokens / dt,
                    da / dp if dp else 0.0,
                    gen_tokens / ds if ds else 0.0)
        finally:
            eng.close()
            store.clear()
            store.close()

    # trials INTERLEAVE across configs (round-robin plain/depths) so
    # load drift lands on every series instead of skewing whichever
    # config happened to run during the spike; one UNRECORDED warm
    # trial per config first retires every process-wide one-off
    # (arena growth, first-shape compiles) outside the measurement
    raw: dict = {0: []}
    for d in depths:
        raw[d] = []
    for d in raw:
        one_trial(d, 0)
    for k in range(trials):
        for d in raw:
            raw[d].append(one_trial(d, k))

    def series(depth):
        rs = raw[depth]
        tps = sorted(r[0] for r in rs)
        acc = sorted(r[1] for r in rs)
        tpstep = sorted(r[2] for r in rs)
        return {
            "tokens_per_s": round(tps[len(tps) // 2], 1),
            "tokens_per_s_spread": [round(tps[0], 1),
                                    round(tps[-1], 1)],
            "accept_rate": round(acc[len(acc) // 2], 4),
            "tokens_per_step": round(tpstep[len(tpstep) // 2], 2),
            "trials": trials,
        }

    out = {"plain": series(0)}
    plain_hi = out["plain"]["tokens_per_s_spread"][1]
    any_beyond = False
    for d in depths:
        s = series(d)
        s["speedup_vs_plain"] = round(
            s["tokens_per_s"] / out["plain"]["tokens_per_s"], 3) \
            if out["plain"]["tokens_per_s"] else None
        s["beats_plain_beyond_spread"] = \
            s["tokens_per_s_spread"][0] > plain_hi
        any_beyond = any_beyond or s["beats_plain_beyond_spread"]
        out[f"depth{d}"] = s
    out["spec_beats_plain_beyond_spread"] = any_beyond
    out["cpu_valid"] = True
    out["config"] = {"prompt_tokens": prompt_tokens,
                     "gen_tokens": gen_tokens,
                     "n_requests": n_requests, "draft": "ngram"}
    out["note"] = ("speculative decoding rung (ISSUE 11): plain vs "
                   "draft-tree verify tokens/s at depths 2/4/8 with a "
                   "host-side ngram draft (draft cost << target cost); "
                   "accept_rate/tokens_per_step medians ride along; "
                   "the acceptance gate is beyond-spread faster at "
                   ">=1 depth")
    return out


def speculative_main(argv) -> None:
    """`python bench.py speculative`: run ONLY the speculative-decoding
    rung and print one JSON object on stdout (progress on stderr) —
    the `make speculative` bench entry and the subprocess the full
    bench run shells out to."""
    log("speculative: plain vs draft-verify tokens/s rung...")
    out = bench_speculative()
    for k, v in out.items():
        if isinstance(v, dict):
            log(f"  {k}: {json.dumps(v)}")
        else:
            log(f"  {k}: {v}")
    print(json.dumps(out))


def bench_embedding(trials=3, duration_s=1.0, vocab=4096, dim=256,
                    n_keys=64, partitions=4, batch_size=32,
                    threads=64):
    """Sharded parameter-server rung (ISSUE 12), two questions:

    1. **Does batching pay?** lookups/s through the DynamicBatcher at
       max_batch_size=32 vs batch=1 issuance of the SAME jitted gather
       under the SAME offered load (equal thread counts — only the
       coalescing differs; the cleanest apples-to-apples form of the
       claim).  The coalescing win the service leans on; acceptance
       >= 3x.
    2. **What does the framework cost over raw collectives?** Per-
       lookup latency through the FULL stack (PSClient -> JSON RPC ->
       PartitionChannel fan-out -> server batcher -> jitted gather ->
       reassembly) vs the same keys through one compiled
       shard_map+psum program on the same mesh — the honest "framework
       tax" number PAPERS.md ("RPC Considered Harmful") demands,
       published with spread, not hidden.

    3-trial median+spread throughout; CPU-valid (the full bench runs it
    in a forced-CPU subprocess like microbench/migrate)."""
    import numpy as np

    from brpc_tpu.psserve import EmbeddingShardServer
    from brpc_tpu.serving import DynamicBatcher

    out = {"vocab": vocab, "dim": dim, "n_keys": n_keys}

    # ---- rung 1: batched-through-batcher vs unbatched issuance ----
    shard = EmbeddingShardServer(0, 1, vocab, dim, seed=0,
                                 key_buckets=(n_keys,),
                                 name="bench_emb")
    rng = np.random.default_rng(0)

    def one_trial(bs: int, k: int) -> float:
        nthreads = threads
        buckets = (bs,) if bs == 1 else (bs // 4, bs // 2, bs)
        b = DynamicBatcher(shard.lookup_batch_fn, max_batch_size=bs,
                           max_delay_us=20_000, batch_buckets=buckets,
                           length_buckets=(n_keys,), dtype=np.int64,
                           padded_output=True,
                           name=f"bench_emb_bs{bs}_{k}")
        keys = rng.integers(0, vocab, n_keys).astype(np.int64)
        try:
            b.submit_wait(keys, timeout_s=300)   # compile outside timing
            stop = time.monotonic() + duration_s
            counts = [0] * nthreads

            def worker(i):
                while time.monotonic() < stop:
                    b.submit_wait(keys, timeout_s=60)
                    counts[i] += 1

            ts = [threading.Thread(target=worker, args=(i,))
                  for i in range(nthreads)]
            t0 = time.monotonic()
            [t.start() for t in ts]
            [t.join(120) for t in ts]
            return sum(counts) / (time.monotonic() - t0)
        finally:
            b.close()

    un = [one_trial(1, k) for k in range(trials)]
    ba = [one_trial(batch_size, k) for k in range(trials)]
    rung1 = {}
    rung1.update(_med_spread(un, "unbatched_lookups_per_s"))
    rung1.update(_med_spread(ba, "batched_lookups_per_s"))
    rung1["batch_speedup"] = round(
        rung1["batched_lookups_per_s"]
        / max(rung1["unbatched_lookups_per_s"], 1e-9), 2)
    rung1["batch_size"] = batch_size
    out["batcher"] = rung1
    log(f"  batcher: {json.dumps(rung1)}")

    # ---- rung 2: framework vs raw collectives on the same mesh, with
    # the SERIALIZER AXIS (ISSUE 13: json vs tensorframe vs lowered) ----
    import jax
    if len(jax.devices()) < partitions:
        raise RuntimeError(
            f"{len(jax.devices())} devices < {partitions} partitions: "
            f"the collective rung needs the mesh embedding_main forces")
    from brpc_tpu.psserve import PSClient, ShardedEmbeddingTable
    from brpc_tpu.rpc import serialization as _ser
    from brpc_tpu.tools.rpc_press import (spin_up_psserve,
                                          tear_down_psserve)

    lowered = ShardedEmbeddingTable(vocab, dim, n_shards=partitions,
                                    seed=0, key_buckets=(n_keys,))
    servers, svcs, shards, pc = spin_up_psserve(
        partitions, vocab=vocab, dim=dim, max_delay_us=200,
        name_prefix="bench_emb")
    cli_j = PSClient(pc, vocab=vocab, dim=dim, serializer="json",
                     ici="off", name="bench_emb_cli_json")
    cli_t = PSClient(pc, vocab=vocab, dim=dim, serializer="tensorframe",
                     ici="off", name="bench_emb_cli_tf")
    try:
        keysets = [rng.integers(0, vocab, n_keys).astype(np.int64)
                   for _ in range(8)]
        # warm every path (compiles + negotiation) outside timing
        for ks in keysets[:2]:
            cli_j.lookup(ks)
            cli_t.lookup(ks)
            lowered.lookup(ks)

        def time_path(fn, k: int) -> tuple:
            """(median per-lookup us, lookups/s) over one trial window
            — the SAME closed-loop issuance for every serializer, so
            the axis compares equal offered load"""
            lats = []
            stop = time.monotonic() + duration_s
            i = 0
            t_start = time.monotonic()
            while time.monotonic() < stop:
                ks = keysets[(i + k) % len(keysets)]
                t0 = time.monotonic()
                fn(ks)
                lats.append((time.monotonic() - t0) * 1e6)
                i += 1
            elapsed = time.monotonic() - t_start
            return float(np.median(lats)), len(lats) / elapsed

        # the A/B axis runs 5 trials (vs 3 elsewhere): tax_reduction_x
        # is a RATIO OF PAIRINGS, so its spread is the most
        # noise-sensitive number the rung publishes and the ISSUE-13
        # acceptance gates on it.  INTERLEAVED json/tensorframe trials
        # so slow box drift (thermal, VM neighbors) hits both axes
        # equally instead of biasing whichever ran last.
        ab_trials = max(trials, 5)
        # the zero-copy claim, pinned: the tensorframe trials must not
        # grow the host-materializing tensor serializer's counters
        enc0 = _ser.tensor_host_encodes.get_value()
        dec0 = _ser.tensor_host_decodes.get_value()
        ft, fj = [], []
        for k in range(ab_trials):
            ft.append(time_path(cli_t.lookup, k))
            fj.append(time_path(cli_j.lookup, k))
        enc_delta = _ser.tensor_host_encodes.get_value() - enc0
        dec_delta = _ser.tensor_host_decodes.get_value() - dec0
        raw = [time_path(lambda ks: lowered.lookup(ks), k)
               for k in range(trials)]
        rung2 = {"partitions": partitions, "mode": lowered.mode}
        # framework_us continues the historical key: the DEFAULT wire
        # (tensorframe) through the full stack — its trajectory vs old
        # rounds IS the tax coming down
        rung2.update(_med_spread([x[0] for x in ft], "framework_us"))
        rung2.update(_med_spread([x[0] for x in fj],
                                 "framework_json_us"))
        rung2.update(_med_spread([x[0] for x in raw],
                                 "raw_collective_us"))
        rung2.update(_med_spread([x[1] for x in ft],
                                 "tensorframe_lookups_per_s"))
        rung2.update(_med_spread([x[1] for x in fj],
                                 "json_lookups_per_s"))
        rung2.update(_med_spread([x[1] for x in raw],
                                 "lowered_lookups_per_s"))
        # tax spreads from the worst/best pairings so the intervals are
        # honest about cross-path jitter, not just within-path
        def tax(nums, denoms):
            pairs = sorted(a / b for a, _ in nums for b, _ in denoms
                           if b > 0)
            med = round(np.median(pairs), 1)
            return med, [round(pairs[0], 1), round(pairs[-1], 1)]

        rung2["framework_tax_ratio"], rung2["framework_tax_spread"] = \
            tax(ft, raw)
        (rung2["framework_tax_ratio_json"],
         rung2["framework_tax_spread_json"]) = tax(fj, raw)
        # the acceptance number: how much the binary wire cut the tax
        # (raw cancels, so this is json-vs-tensorframe latency pairs);
        # >= 5x with a disjoint spread is the ISSUE-13 bar
        (rung2["tax_reduction_x"],
         rung2["tax_reduction_x_spread"]) = tax(fj, ft)
        rung2["tensor_host_encodes_delta"] = int(enc_delta)
        rung2["tensor_host_decodes_delta"] = int(dec_delta)
        # _med_spread stamps "trials" per call and the raw axis lands
        # last — record both counts explicitly so the published record
        # says what the gated A/B keys actually used
        rung2["trials"] = trials
        rung2["ab_trials"] = ab_trials
        out["collective"] = rung2
        log(f"  collective: {json.dumps(rung2)}")
    finally:
        tear_down_psserve(servers, svcs, pc)
        cli_j.close()
        cli_t.close()
    out["note"] = (
        "sharded parameter-server rung (ISSUE 12/13): batched-through-"
        "batcher vs batch=1 issuance of the same jitted gather "
        "(>=3x target), and per-lookup latency through the FULL RPC "
        "stack vs one compiled shard_map+psum collective on the same "
        "mesh, on BOTH wire formats — framework_tax_ratio (tensorframe,"
        " the default wire) and framework_tax_ratio_json are the honest"
        " overhead numbers; tax_reduction_x is the ISSUE-13 acceptance "
        "(json tax / tensorframe tax >= 5x beyond spread), and "
        "tensor_host_encodes_delta pins the zero-host-copy claim at 0 "
        "through transport on the binary path")
    return out


def embedding_main(argv) -> None:
    """`python bench.py embedding`: run ONLY the parameter-server rung
    and print one JSON object on stdout (progress on stderr) — the
    `make psserve` bench entry and the subprocess the full bench run
    shells out to.  Forces the virtual 8-device CPU mesh BEFORE jax
    loads so the collective rung has partitions to lower onto."""
    _force_virtual_mesh()
    log("embedding: sharded parameter-server rung...")
    out = bench_embedding()
    print(json.dumps(out))


def _force_virtual_mesh(n: int = 8) -> None:
    """Give this process n virtual CPU devices (no-op if jax already
    initialized with them)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _floor_spread(med, lo, hi, pad):
    """Widen a published [lo, hi] spread to at least ±``pad`` around
    the median (ISSUE 9 deflake): a deterministic workload's few-trial
    spread can collapse to ~0.2%, and perf_diff's disjoint-interval
    rule would then read sub-noise deltas as beyond-spread.  The floor
    encodes the known irreducible jitter the aggregate hides (for the
    cluster rung: engine admission quantization, ± half a step period
    per generation).  Rounds OUTWARD so publication can never narrow
    the interval back below the floor."""
    import math
    return [math.floor(min(lo, med - pad) * 100) / 100,
            math.ceil(max(hi, med + pad) * 100) / 100]


def bench_train(trials=3, vocab=65536, dim=32, n_shards=2,
                n_workers=4, wave_keys=2048, wave_duration_s=2.0,
                gen_vocab=512, gen_duration_s=1.0, gen_tokens=16):
    """Training-plane rung (ISSUE 17), two questions:

    1. **Does the co-located optimizer pay on the wire?** updates/s
       through the trainer's WAVE PATH (``_send_wave``: admit, retry,
       token discipline — the real machinery) from N worker threads
       against a BIG embedding table, mode="wire" (raw grads on the
       wire; the SHARD runs gradient scatter + slot step as ONE fused
       jitted program behind PS.Update, momentum never leaving the
       server) vs mode="pull_compute_push" (the classic loop: the
       HOST holds full-vocab adam slot tables and pays
       np.unique + unbuffered np.add.at + gather/slot-math/scatter
       into those tables per wave, shipping deltas back).  The big
       vocab is the point — co-location keeps slot state sharded
       device-side where the fused scatter absorbs it, while the
       host baseline's per-wave tax is row gather/scatter over
       vocab-sized host arrays.  The wave path is timed in isolation
       because everything else a training step does (dense pulls,
       lookups, grad compute) is byte-identical between modes and
       would only dilute the comparison.  Acceptance: wire >=
       baseline beyond spread.
    2. **What does a concurrent trainer cost serving?** decode
       tokens/s on a serving replica WITH vs WITHOUT a full trainer
       (grads and all) streaming waves against a PS fleet in the same
       process — the mixed-shape coexistence number the arbiter
       exists to protect (published as a ratio, not gated: the
       arbiter tests own the ordering proof).  Runs on its own small
       fleet (``gen_vocab``) so rung 1's big table does not inflate
       the trainer's grad compiles.

    3-trial median+spread throughout; jit compiles (trainer grad fn,
    shard fused apply) are warmed OUTSIDE timing so the rungs compare
    steady-state waves, not tracing.  CPU-valid (the full bench runs
    it in a forced-CPU subprocess like migrate/embedding)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import brpc_tpu as brpc
    from brpc_tpu.models.parameter_server import PSConfig
    from brpc_tpu.psserve import (EmbeddingShardServer, PSClient,
                                  register_psserve, unregister_psserve)
    from brpc_tpu.rpc.combo_channels import PartitionChannel
    from brpc_tpu.tools.rpc_press import (spin_up_replicas,
                                          tear_down_replicas)
    from brpc_tpu.train.optimizer import OptimizerSpec
    from brpc_tpu.train.trainer import DataParallelTrainer

    out = {"vocab": vocab, "dim": dim, "n_shards": n_shards,
           "workers": n_workers, "wave_keys": wave_keys}
    spec = OptimizerSpec("adam", lr=0.01)

    def mk_fleet(vocab_, buckets, table, prefix):
        servers, svcs, shards = [], [], []
        pc = PartitionChannel(n_shards)
        for i in range(n_shards):
            sh = EmbeddingShardServer(i, n_shards, vocab_, dim,
                                      seed=0, table=table,
                                      key_buckets=buckets,
                                      name=f"{prefix}_ps")
            shards.append(sh)
            s = brpc.Server()
            svcs.append(register_psserve(s, sh, name=f"{prefix}_{i}"))
            s.start("127.0.0.1", 0)
            servers.append(s)
            pc.add_partition(i, brpc.Channel(f"127.0.0.1:{s.port}",
                                             timeout_ms=10_000))
        cli = PSClient(pc, vocab=vocab_, dim=dim, name=f"{prefix}_cli")
        return servers, svcs, shards, pc, cli

    def tear_fleet(servers, svcs, pc):
        for svc in svcs:
            unregister_psserve(svc)
        for s in servers:
            try:
                s.stop()
                s.join()
            except Exception:
                pass
        pc.close()

    # ---- rung 1: wire-optimizer vs pull-compute-push wave-path
    # updates/s over the big table ----
    per_shard = wave_keys // n_shards
    servers, svcs, shards, pc, client = mk_fleet(
        vocab, (8, 32, 128, 512, per_shard), None, "bench_train")
    cfg1 = PSConfig(vocab=vocab, d_model=dim, d_ff=2 * dim,
                    n_layers=2, seq=16, batch=8)
    # fixed-size waves with a FIXED per-shard key count (equal draws
    # from each shard's contiguous ownership range), so every wave
    # pads to ONE bucket — a second bucket first seen mid-trial would
    # compile inside the timed window
    rng = np.random.default_rng(0)
    bounds = [(i * vocab // n_shards, (i + 1) * vocab // n_shards)
              for i in range(n_shards)]

    def mk_keys():
        ks = np.concatenate([rng.integers(lo, hi, per_shard)
                             for lo, hi in bounds]).astype(np.int64)
        return rng.permutation(ks)

    keysets = [mk_keys() for _ in range(8)]
    gradsets = [rng.standard_normal((per_shard * n_shards, dim))
                .astype(np.float32) for _ in range(4)]

    def wave_trial(mode: str, k: int) -> float:
        """updates/s of N worker threads driving ``_send_wave`` (the
        trainer's real wave path: per-worker client clones, retry +
        token discipline, and for pull_compute_push the host slot
        lock) for one timed window."""
        tr = DataParallelTrainer(
            client, cfg1, n_workers=n_workers, steps=1,
            optimizer=spec, mode=mode, seed=k,
            name=f"bench_wave_{mode}{k}")
        clis = [tr._clone_client(w) for w in range(n_workers)]
        # first wave outside timing: shard fused-apply/scatter compile
        # at this bucket, host slot allocation (pcp), negotiation on
        # the fresh clones
        tr._send_wave(clis[0], 0, 0, keysets[0], gradsets[0])
        stop_t = time.monotonic() + wave_duration_s
        counts = [0] * n_workers

        def worker(w):
            i = 0
            while time.monotonic() < stop_t:
                tr._send_wave(clis[w], w, i,
                              keysets[(w + i) % len(keysets)],
                              gradsets[(w + i) % len(gradsets)])
                counts[w] += 1
                i += 1

        ts = [threading.Thread(target=worker, args=(w,), daemon=True)
              for w in range(n_workers)]
        # GC paused for the window: a collection pass landing in one
        # mode's trial but not the other's is pure spread (pcp's host
        # slot tables are exactly the garbage that triggers one)
        gc.collect()
        gc.disable()
        try:
            t0 = time.monotonic()
            [t.start() for t in ts]
            [t.join(120) for t in ts]
            return sum(counts) / (time.monotonic() - t0)
        finally:
            gc.enable()

    try:
        # INTERLEAVED trials so box drift hits both modes equally
        wire, pcp = [], []
        for k in range(trials):
            wire.append(wave_trial("wire", k))
            pcp.append(wave_trial("pull_compute_push", k))
        rung1 = {"optimizer": "adam"}
        rung1.update(_med_spread(wire, "wire_updates_per_s"))
        rung1.update(_med_spread(pcp, "pcp_updates_per_s"))
        rung1["wire_speedup"] = round(
            rung1["wire_updates_per_s"]
            / max(rung1["pcp_updates_per_s"], 1e-9), 2)
        # the ISSUE 17 acceptance probe: disjoint spreads, wire above
        rung1["wire_beyond_spread"] = bool(
            rung1["wire_updates_per_s_spread"][0]
            > rung1["pcp_updates_per_s_spread"][1])
        out["optimizer_placement"] = rung1
        log(f"  optimizer_placement: {json.dumps(rung1)}")
    finally:
        tear_fleet(servers, svcs, pc)
        client.close()

    # ---- rung 2: serving tokens/s WITH vs WITHOUT a concurrent
    # trainer wave (one decode replica + a small PS fleet, same
    # process/CPUs) ----
    cfg2 = PSConfig(vocab=gen_vocab, d_model=dim, d_ff=2 * dim,
                    n_layers=2, seq=16, batch=8)
    embed0, dense0 = DataParallelTrainer.model_init(cfg2, seed=0)
    servers, svcs, shards, pc, client = mk_fleet(
        gen_vocab, (8, 32, 128, 512), embed0, "bench_mix")

    def make_trainer(steps_, seed):
        tr = DataParallelTrainer(
            client, cfg2, n_workers=n_workers, steps=steps_,
            optimizer=spec, mode="wire", seed=seed,
            name="bench_mix_trainer")
        tr.seed_dense(dense0)
        # warm the per-trainer jits (each trainer closes over its own
        # loss fn, so jax retraces per instance): compile outside the
        # timed window, exactly like the other rungs
        rows0 = jnp.zeros((cfg2.batch, cfg2.seq, cfg2.d_model),
                          jnp.float32)
        dense0j = {k: jnp.asarray(v) for k, v in dense0.items()}
        tr._grad_fn(rows0, dense0j, tr._eval_targets)
        tr._loss_fn(rows0, dense0j, tr._eval_targets)
        return tr

    # warm the small fleet's shard programs (fused apply + lookup at
    # the trainer's wave size) outside timing
    wk = rng.integers(0, gen_vocab, cfg2.batch * cfg2.seq).astype(
        np.int64)
    client.update(wk, rng.standard_normal(
        (wk.size, dim)).astype(np.float32), optimizer=spec)
    client.lookup(wk)

    replicas = spin_up_replicas(1, name_prefix="bench_train_srv")
    ch = brpc.Channel(replicas[0][3], timeout_ms=10_000)
    try:
        def gen_once(prompt) -> int:
            done = threading.Event()
            toks = []

            class _H(brpc.StreamHandler):
                def on_received_messages(self, stream, messages):
                    for m in messages:
                        d = json.loads(m)
                        if "token" in d:
                            toks.append(d["token"])
                        if d.get("done"):
                            done.set()

                def on_closed(self, stream):
                    done.set()

            cntl = brpc.Controller(timeout_ms=10_000)
            brpc.stream_create(cntl, _H())
            resp = ch.call_sync(
                "Serving", "Generate",
                {"prompt": prompt, "max_new_tokens": gen_tokens},
                serializer="json", cntl=cntl)
            if not resp.get("accepted") or not done.wait(30):
                return 0
            return len(toks)

        gen_once([1])        # warm the engine outside timing

        def gen_trial(k: int) -> float:
            stop = time.monotonic() + gen_duration_s
            tokens, t0 = 0, time.monotonic()
            while time.monotonic() < stop:
                tokens += gen_once([1 + k])
            return tokens / (time.monotonic() - t0)

        alone, mixed = [], []
        for k in range(trials):
            alone.append(gen_trial(k))
            # WITH: a long trainer streams waves for the whole
            # window; stop() drains it after the window closes
            tr = make_trainer(1_000_000, seed=100 + k)

            def bg_run(tr=tr):
                try:
                    tr.run()
                except Exception as e:
                    log(f"  bg trainer: {type(e).__name__}: {e}")

            bg = threading.Thread(
                target=bg_run,
                name=f"bench_train_bg{k}", daemon=True)
            bg.start()
            wait_s = time.monotonic() + 5
            while tr.n_waves == 0 and time.monotonic() < wait_s:
                time.sleep(0.005)
            mixed.append(gen_trial(k))
            tr.stop()
            bg.join(timeout=30)
        rung2 = {"gen_tokens": gen_tokens, "gen_vocab": gen_vocab}
        rung2.update(_med_spread(alone, "tokens_per_s_alone"))
        rung2.update(_med_spread(mixed, "tokens_per_s_mixed"))
        rung2["mixed_retention"] = round(
            rung2["tokens_per_s_mixed"]
            / max(rung2["tokens_per_s_alone"], 1e-9), 2)
        out["serving_coexistence"] = rung2
        log(f"  serving_coexistence: {json.dumps(rung2)}")
    finally:
        tear_down_replicas(replicas)
        tear_fleet(servers, svcs, pc)
        client.close()
    out["note"] = (
        "training-plane rung (ISSUE 17): wave-path updates/s with the "
        "optimizer CO-LOCATED on the shard (raw grads on the wire, "
        "fused scatter+slot-step jitted server-side over the sharded "
        "table) vs the pull-compute-push baseline (full-vocab adam "
        "slot tables at the host, np scatter-accumulate + slot math "
        "per wave, deltas on the wire) — wire_beyond_spread is the "
        "acceptance probe; plus decode tokens/s on a serving replica "
        "with vs without concurrent trainer waves in the same "
        "process (mixed_retention, published not gated — the arbiter "
        "tests own the shed-ordering proof)")
    return out


def train_main(argv) -> None:
    """`python bench.py train`: run ONLY the training-plane rung and
    print one JSON object on stdout (progress on stderr) — the
    `make train` bench entry and the subprocess the full bench run
    shells out to."""
    _force_virtual_mesh()
    log("train: training-plane rung...")
    out = bench_train()
    print(json.dumps(out))


def bench_cluster(n_replicas=2, trials=5, duration_s=2.0, threads=3,
                  step_delay_s=0.01, max_new=16):
    """Cluster front-door rung (ISSUE 8): generations/s DIRECT to one
    replica vs THROUGH the ClusterRouter, on a decode-bound workload
    (each step sleeps ``step_delay_s`` — 10ms is the realistic low end
    of an LLM decode step — so generation time is dominated by decode
    the way real serving is, and the router's extra hop reads as
    overhead against a realistic denominator; an instant-step workload
    would measure only the socket relay).

    Reported: direct_gens_per_s / router_gens_per_s (3-trial
    median+spread, both perf_diff-gated higher-is-better),
    router_overhead_pct (gated lower-is-better), TTFT through the
    router, and router_within_spread — the ISSUE 8 acceptance probe
    that at low load the router-vs-direct delta sits inside the
    measurement spread.  The probe compares PER-GENERATION latency
    interquartile ranges, not per-trial qps extremes: a deterministic
    workload's 3-trial qps spread collapses toward zero, which would
    read parity (~1-2ms fixed relay cost per generation, measured) as
    beyond-spread purely because the aggregate hides the real
    per-generation jitter (engine step-loop admission quantization,
    ±one step period).  CPU-valid by construction: the step function
    is plain numpy."""
    import threading as _threading

    import brpc_tpu as brpc
    from brpc_tpu.serving import RouterClient
    from brpc_tpu.tools.rpc_press import (spin_up_cluster,
                                          tear_down_cluster)

    PT = 8

    def drive(gen_fn, duration):
        """Run gen_fn in `threads` workers for `duration`; returns
        (gens_per_s, first-token latencies us, per-gen latencies us)."""
        stop = _threading.Event()
        mu = _threading.Lock()
        ok = [0]
        ttfts: list[int] = []
        lats: list[int] = []

        def worker(k):
            while not stop.is_set():
                t0 = time.monotonic()
                first = [None]

                def emit(tok, first=first):
                    if first[0] is None:
                        first[0] = time.monotonic()

                if not gen_fn(k, emit):
                    continue
                t1 = time.monotonic()
                with mu:
                    ok[0] += 1
                    lats.append(int((t1 - t0) * 1e6))
                    if first[0] is not None:
                        ttfts.append(int((first[0] - t0) * 1e6))

        ts = [_threading.Thread(target=worker, args=(k,), daemon=True)
              for k in range(threads)]
        t0 = time.monotonic()
        [t.start() for t in ts]
        time.sleep(duration)
        stop.set()
        [t.join(10) for t in ts]
        return ok[0] / (time.monotonic() - t0), ttfts, lats

    def one_trial(k):
        # replication deliberately OFF: the rung measures the router's
        # relay overhead, not page shipping (the press turns it on)
        replicas, router, rsrv, raddr = spin_up_cluster(
            n_replicas, page_tokens=PT, step_delay_s=step_delay_s,
            max_sessions=512, name_prefix=f"bench_cl_{k}")
        try:
            from brpc_tpu.migrate.disagg import _TokenCollector
            from brpc_tpu.rpc import Controller, stream_create

            def direct_gen(w, emit):
                # straight to replica 0's Serving.Generate stream —
                # the no-router baseline
                prompt = [w * 31 + j for j in range(PT)]
                col = _TokenCollector(emit)
                cntl = Controller(timeout_ms=20_000)
                stream_create(cntl, col)
                try:
                    dch = direct_chans[w % len(direct_chans)]
                    dch.call_sync(
                        "Serving", "Generate",
                        {"prompt": prompt, "max_new_tokens": max_new},
                        serializer="json", cntl=cntl)
                except brpc.RpcError:
                    return False
                return col.done.wait(20) and col.error is None

            direct_chans = [brpc.Channel(replicas[0][3],
                                         timeout_ms=20_000)
                            for _ in range(threads)]
            clients = [RouterClient(raddr, timeout_ms=20_000)
                       for _ in range(threads)]

            def router_gen(w, emit):
                prompt = [w * 31 + j for j in range(PT)]
                try:
                    res = clients[w % len(clients)].generate(
                        prompt, max_new, emit=emit, timeout_s=20)
                except brpc.RpcError:
                    return False
                return res["error"] is None

            # warm both paths (first-call setup outside timing)
            direct_gen(0, lambda t: None)
            router_gen(0, lambda t: None)
            d_qps, _, d_lats = drive(direct_gen, duration_s)
            r_qps, ttfts, r_lats = drive(router_gen, duration_s)
            resumes = router.resumes_total.get_value()
            return d_qps, r_qps, ttfts, resumes, d_lats, r_lats
        finally:
            tear_down_cluster(replicas, router, rsrv)

    rs = [one_trial(k) for k in range(trials)]
    ds = sorted(r[0] for r in rs)
    qs = sorted(r[1] for r in rs)
    all_ttft = sorted(t for r in rs for t in r[2])
    d_med, r_med = ds[len(ds) // 2], qs[len(qs) // 2]
    overheads = sorted((d - r) / d * 100.0
                       for d, r, _t, _n, _dl, _rl in rs if d > 0)
    d_lats = sorted(x for r in rs for x in r[4])
    r_lats = sorted(x for r in rs for x in r[5])

    def _iqr(xs):
        if not xs:
            return [0, 0]
        return [xs[len(xs) // 4], xs[(3 * len(xs)) // 4]]

    d_iqr, r_iqr = _iqr(d_lats), _iqr(r_lats)
    # minimum-spread floor (ISSUE 9 deflake): ± half a step period per
    # generation — the admission-quantization jitter a deterministic
    # workload's per-trial qps aggregate hides.  Without it, a ~0.2%
    # collapsed spread lets perf_diff flag a 5-6%-end run as a
    # beyond-spread regression (`make bench` crying wolf, PR 8 note).
    floor_frac = 1.0 / (2 * max_new)
    o_med = overheads[len(overheads) // 2] if overheads else None
    out = {
        "replicas": n_replicas,
        "threads": threads,
        "step_delay_ms": step_delay_s * 1e3,
        "direct_gens_per_s": round(d_med, 1),
        "direct_gens_per_s_spread": _floor_spread(
            d_med, ds[0], ds[-1], d_med * floor_frac),
        "router_gens_per_s": round(r_med, 1),
        "router_gens_per_s_spread": _floor_spread(
            r_med, qs[0], qs[-1], r_med * floor_frac),
        "router_overhead_pct": (round(o_med, 2)
                                if o_med is not None else None),
        "router_overhead_pct_spread": (
            _floor_spread(o_med, overheads[0], overheads[-1],
                          100.0 * floor_frac)
            if o_med is not None else None),
        "direct_gen_lat_p50_us": (d_lats[len(d_lats) // 2]
                                  if d_lats else None),
        "router_gen_lat_p50_us": (r_lats[len(r_lats) // 2]
                                  if r_lats else None),
        "direct_gen_lat_iqr_us": d_iqr,
        "router_gen_lat_iqr_us": r_iqr,
        # the ISSUE 8 acceptance probe: the router-vs-direct delta
        # sits inside the measurement spread at low load — compared at
        # per-generation latency granularity (IQR overlap), where the
        # real jitter lives; see the docstring
        "router_within_spread": bool(
            d_lats and r_lats and
            r_iqr[0] <= d_iqr[1] and d_iqr[0] <= r_iqr[1]),
        "router_ttft_p50_us": (all_ttft[len(all_ttft) // 2]
                               if all_ttft else None),
        "router_ttft_p99_us": (all_ttft[int(len(all_ttft) * 0.99)]
                               if all_ttft else None),
        "resumes": sum(r[3] for r in rs),
        "trials": trials,
        "cpu_valid": True,
        "note": ("cluster front-door rung (brpc_tpu/serving/router): "
                 "generations/s direct-to-replica vs through the "
                 "router on a decode-bound workload; perf_diff gates "
                 "direct/router gens_per_s (up) and "
                 "router_overhead_pct (down) on disjoint spread; "
                 f"{trials} trials with a ±{100 * floor_frac:.1f}% "
                 "minimum-spread floor (admission quantization) so a "
                 "collapsed deterministic spread cannot read noise as "
                 "beyond-spread"),
    }
    return out


def cluster_main(argv) -> None:
    """`python bench.py cluster`: run ONLY the cluster front-door rung
    and print one JSON object on stdout (progress on stderr) — the
    `make cluster`-adjacent bench entry and the subprocess the full
    bench run shells out to."""
    log("cluster: router-vs-direct generations rung...")
    out = bench_cluster()
    for k, v in out.items():
        if isinstance(v, (dict, list)):
            log(f"  {k}: {json.dumps(v)}")
        else:
            log(f"  {k}: {v}")
    print(json.dumps(out))


def bench_durable(n_replicas: int = 2, trials: int = 3,
                  duration_s: float = 2.0, threads: int = 3,
                  step_delay_s: float = 0.01, max_new: int = 16,
                  warm_fracs=(0.0, 0.5, 0.9)) -> dict:
    """Durable control-plane rung (ISSUE 16), two halves:

    A. **WAL tax** — generations/s through the router with the session
       WAL OFF vs ON, same decode-bound operating point as the cluster
       rung (step_delay dominates, so the WAL's file appends are the
       only delta).  Publishes ``wal_overhead_pct`` with the ISSUE 16
       acceptance claim ``wal_overhead_within_5pct``.

    B. **Crash -> first-token** — N generations stream over a
       WAL-backed router; the router AND the owner replica die
       mid-generation; a successor adopts the fleet from the WAL and
       every client resumes CONCURRENTLY (the adoption storm).  The
       latency to each session's first post-adoption token is taken at
       three buddy-warm operating points: 0% (replication off — every
       resume recomputes), 50% and 90% (that fraction of sessions had
       their pages shipped to the ring buddy, via the per-session
       ``Session.replicate`` opt-out, so the resume re-decodes only
       the unshipped tail).  All prompts share their first chunk so
       the affinity ring puts every session on ONE owner — killing it
       makes buddy warmth, not owner survival, the variable.

    Everything is CPU-valid: the step fn is plain numpy."""
    import tempfile as _tempfile
    import threading as _threading

    import brpc_tpu as brpc
    from brpc_tpu.serving import (ClusterRouter, ReplicaHandle,
                                  RouterClient, SessionTable,
                                  register_router)
    from brpc_tpu.tools.rpc_press import (spin_up_replicas,
                                          tear_down_replicas)

    PT = 8

    def handles(replicas, prefix):
        return [ReplicaHandle(addr, name=f"{prefix}_{i}", engine=eng,
                              store=store, server=srv)
                for i, (store, eng, srv, addr) in enumerate(replicas)]

    # ---- half A: WAL-off vs WAL-on generations/s ----

    def drive(raddr, duration):
        stop = _threading.Event()
        mu = _threading.Lock()
        ok = [0]
        clients = [RouterClient(raddr, timeout_ms=20_000)
                   for _ in range(threads)]

        def worker(w):
            while not stop.is_set():
                prompt = [w * 31 + j for j in range(PT)]
                try:
                    res = clients[w % len(clients)].generate(
                        prompt, max_new, timeout_s=20)
                except brpc.RpcError:
                    continue
                if res["error"] is None:
                    with mu:
                        ok[0] += 1

        ts = [_threading.Thread(target=worker, args=(w,), daemon=True)
              for w in range(threads)]
        t0 = time.monotonic()
        [t.start() for t in ts]
        time.sleep(duration)
        stop.set()
        [t.join(10) for t in ts]
        return ok[0] / (time.monotonic() - t0)

    def wal_trial(k):
        replicas = spin_up_replicas(
            n_replicas, page_tokens=PT, step_delay_s=step_delay_s,
            name_prefix=f"bench_dur_{k}")
        wal_dir = _tempfile.mkdtemp(prefix=f"bench_dur_{k}_")
        qps = {}
        wal_stats = None
        try:
            for mode, wal in (("off", None),
                              ("on", os.path.join(wal_dir, "s.wal"))):
                router = ClusterRouter(
                    handles(replicas, f"bd{k}{mode}"), wal=wal,
                    page_tokens=PT, max_sessions=512,
                    name=f"bench_dur_{k}_{mode}")
                rsrv = brpc.Server()
                register_router(rsrv, router)
                rsrv.start("127.0.0.1", 0)
                try:
                    raddr = f"127.0.0.1:{rsrv.port}"
                    drive(raddr, 0.2)            # warm both paths
                    qps[mode] = drive(raddr, duration_s)
                    if wal is not None:
                        wal_stats = router.sessions.wal.stats()
                finally:
                    router.close(timeout_s=3.0)
                    rsrv.stop()
                    rsrv.join()
        finally:
            tear_down_replicas(replicas)
            import shutil
            shutil.rmtree(wal_dir, ignore_errors=True)
        return qps["off"], qps["on"], wal_stats

    wal_rs = [wal_trial(k) for k in range(trials)]
    offs = sorted(r[0] for r in wal_rs)
    ons = sorted(r[1] for r in wal_rs)
    off_med, on_med = offs[len(offs) // 2], ons[len(ons) // 2]
    overheads = sorted((off - on) / off * 100.0
                       for off, on, _w in wal_rs if off > 0)
    o_med = overheads[len(overheads) // 2] if overheads else None
    last_wal = wal_rs[-1][2] or {}
    # same minimum-spread floor as the cluster rung: admission
    # quantization hides ±half a step period per generation
    floor_frac = 1.0 / (2 * max_new)

    # ---- half B: crash -> first post-adoption token ----

    N = 6
    budget = 28
    adopt_step = 0.02
    # real-model cost shape: prefill pays per uncached token, so a
    # buddy-warm resume (deep prefix hit) skips most of the re-decode
    # bill instead of re-paying one flat vectorized call
    prefill_cost = 0.003
    shared = [500 + j for j in range(PT)]    # one owner for the fleet

    def adoption_trial(frac, k):
        warm_n = int(round(frac * N))
        replicas = spin_up_replicas(
            2, page_tokens=PT, step_delay_s=adopt_step, num_slots=8,
            commit_live_pages=True, name_prefix=f"bench_ad{k}",
            prefill_cost_per_token_s=prefill_cost)
        addr_of = [addr for *_, addr in replicas]
        wal_dir = _tempfile.mkdtemp(prefix=f"bench_ad{k}_")
        wal_path = os.path.join(wal_dir, "s.wal")
        router = ClusterRouter(
            handles(replicas, f"ba{k}"), wal=wal_path,
            replicate_sessions=warm_n > 0, replication_factor=2,
            page_tokens=PT, chunk_tokens=PT, check_interval_s=0.02,
            name=f"bench_ad_{k}")
        rsrv = brpc.Server()
        register_router(rsrv, router)
        rsrv.start("127.0.0.1", 0)
        cli = RouterClient(f"127.0.0.1:{rsrv.port}", timeout_ms=20_000)
        successor = rsrv2 = None
        try:
            gens = []
            for i in range(N):
                prompt = shared + [600 + 17 * i + j for j in range(PT)]
                g = cli.start(prompt, budget)
                if i >= warm_n:
                    # cold: opt the session out before its first page
                    # commit (first token is >= one step away)
                    router.sessions.get(g.session_id).replicate = False
                gens.append(g)
            for g in gens:
                if not g.wait_tokens(16, timeout_s=30):
                    raise RuntimeError("bench_durable: no progress "
                                       "before the kill")
            rows = {r["session_id"]: r
                    for r in router.sessions.snapshot(limit=2 * N)}
            observed_warm = sum(
                1 for g in gens
                if rows[g.session_id]["replicated_pages"] > 2)
            owner = rows[gens[0].session_id]["replica"]
            sids = [g.session_id for g in gens]
            for g in gens:
                g.drop()

            # the crash: router and the one owner die together
            router.close(timeout_s=3.0)
            rsrv.stop()
            rsrv.join()
            vidx = addr_of.index(owner)
            vstore, veng, vsrv, _va = replicas[vidx]
            vsrv.stop()
            vsrv.join()
            veng.close(timeout_s=2.0)
            survivor = [replicas[i] for i in range(2) if i != vidx][0]

            t_adopt = time.monotonic()
            table = SessionTable.recover(wal_path)
            # resume at the DURABLE cursor: write-ahead means the
            # record is >= any client's view, so this is the
            # worst-case reconnect — zero replayed tokens, the first
            # emitted token is the first freshly RE-DECODED one (the
            # quantity buddy warmth actually moves)
            held = [(sid, table.get(sid).cursor) for sid in sids]
            successor = ClusterRouter(
                [ReplicaHandle(survivor[3], engine=survivor[1],
                               store=survivor[0], server=survivor[2])],
                sessions=table, page_tokens=PT, chunk_tokens=PT,
                check_interval_s=0.02, name=f"bench_ad_{k}_succ")
            rsrv2 = brpc.Server()
            register_router(rsrv2, successor)
            rsrv2.start("127.0.0.1", 0)
            adoption_ms = (time.monotonic() - t_adopt) * 1e3
            cli2 = RouterClient(f"127.0.0.1:{rsrv2.port}",
                                timeout_ms=30_000)

            # the adoption storm: every client resumes at once
            ttfts = []
            mu = _threading.Lock()

            def resume_one(sid, cursor):
                t0 = time.monotonic()
                first = [None]

                def emit(tok, first=first):
                    if first[0] is None:
                        first[0] = time.monotonic()

                g = cli2.resume(sid, cursor, emit=emit)
                g.wait(60)
                if g.error is None and first[0] is not None:
                    with mu:
                        ttfts.append((first[0] - t0) * 1e3)

            ts = [_threading.Thread(target=resume_one, args=h,
                                    daemon=True) for h in held]
            [t.start() for t in ts]
            [t.join(90) for t in ts]
            if len(ttfts) < N:
                raise RuntimeError(
                    f"bench_durable: only {len(ttfts)}/{N} resumes "
                    "produced a post-adoption token")
            ttfts.sort()
            return ttfts[len(ttfts) // 2], adoption_ms, observed_warm
        finally:
            if successor is not None:
                successor.close(timeout_s=3.0)
            if rsrv2 is not None:
                rsrv2.stop()
                rsrv2.join()
            tear_down_replicas(replicas)
            import shutil
            shutil.rmtree(wal_dir, ignore_errors=True)

    adopt = {}
    adoption_ms_all = []
    for frac in warm_fracs:
        meds = []
        warms = []
        for k in range(trials):
            med, ad_ms, ow = adoption_trial(frac, k)
            meds.append(med)
            warms.append(ow)
            adoption_ms_all.append(ad_ms)
        meds.sort()
        m = meds[len(meds) // 2]
        key = f"resume_ttft_warm{int(frac * 100)}_ms"
        adopt[key] = round(m, 1)
        # floor: first-token timing quantizes on a decode step plus
        # one prefill bucket (the suffix pads to 16-token buckets)
        adopt[key + "_spread"] = _floor_spread(
            m, meds[0], meds[-1], (adopt_step + 16 * prefill_cost) * 1e3)
        adopt[f"observed_warm_sessions_warm{int(frac * 100)}"] = (
            sorted(warms)[len(warms) // 2])
    adoption_ms_all.sort()
    ad_med = adoption_ms_all[len(adoption_ms_all) // 2]

    out = {
        "replicas": n_replicas,
        "threads": threads,
        "step_delay_ms": step_delay_s * 1e3,
        "wal_off_gens_per_s": round(off_med, 1),
        "wal_off_gens_per_s_spread": _floor_spread(
            off_med, offs[0], offs[-1], off_med * floor_frac),
        "wal_on_gens_per_s": round(on_med, 1),
        "wal_on_gens_per_s_spread": _floor_spread(
            on_med, ons[0], ons[-1], on_med * floor_frac),
        "wal_overhead_pct": (round(o_med, 2)
                             if o_med is not None else None),
        "wal_overhead_pct_spread": (
            _floor_spread(o_med, overheads[0], overheads[-1],
                          100.0 * floor_frac)
            if o_med is not None else None),
        # the ISSUE 16 acceptance claim: journaling every token
        # write-ahead costs <= 5% of WAL-off throughput at the median
        # (single trials swing ±3% on admission quantization alone —
        # the spread above says how much)
        "wal_overhead_within_5pct": bool(
            o_med is not None and o_med <= 5.0),
        "wal_appends": last_wal.get("appends"),
        "wal_size_bytes": last_wal.get("size_bytes"),
        "adopt_sessions": N,
        "adopt_step_delay_ms": adopt_step * 1e3,
        "adoption_ms": round(ad_med, 1),
        **adopt,
        "trials": trials,
        "cpu_valid": True,
        "note": ("durable control-plane rung (ISSUE 16): half A is "
                 "generations/s WAL-off vs WAL-on on the decode-bound "
                 "cluster operating point (wal_overhead_pct gated "
                 "down, <=5% acceptance); half B kills the router AND "
                 "the single owner replica mid-generation, adopts the "
                 "fleet from the WAL, and measures each session's "
                 "crash->first-token latency under a concurrent "
                 "resume storm at 0/50/90% buddy-warm (the warm "
                 "fraction had its pages on the ring buddy; resumes "
                 "re-decode only the unshipped tail, so the _ms "
                 "medians fall as warmth rises); "
                 f"{trials} trials, minimum-spread floors of "
                 f"±{100 * floor_frac:.1f}% (admission quantization) "
                 "and ±1 decode step (first-token quantization)"),
    }
    return out


def durable_main(argv) -> None:
    """`python bench.py durable`: run ONLY the durable control-plane
    rung and print one JSON object on stdout (progress on stderr) —
    the `make durable`-adjacent bench entry and the subprocess the
    full bench run shells out to."""
    log("durable: WAL tax + crash->first-token rung...")
    out = bench_durable()
    for k, v in out.items():
        if isinstance(v, (dict, list)):
            log(f"  {k}: {json.dumps(v)}")
        else:
            log(f"  {k}: {v}")
    print(json.dumps(out))


def bench_multimodel(n_replicas: int = 2, trials: int = 3,
                     duration_s: float = 2.0, threads: int = 3,
                     step_delay_s: float = 0.01, max_new: int = 16,
                     canary_sessions: int = 200) -> dict:
    """Multi-model plane rung (ISSUE 18), two halves:

    A. **Two-model tax** — generations/s through ONE router front door
       over the same replica fleet, single-deployment vs
       two-deployment (every request names its model; the only delta
       is the plane itself: catalog resolution, the (model, prefix)
       fingerprint fold, per-deployment engine dispatch).  Publishes
       ``two_model_overhead_pct`` with the ISSUE 18 acceptance claim
       ``two_model_overhead_within_5pct``.

    B. **Canary split** — one model_id behind two versioned
       deployments weighted 95/5; clients ask for the bare model_id
       and the router's smooth-WRR canary splitter picks the version.
       Publishes the observed v1 share with the acceptance claim
       ``canary_within_2pts`` (|observed - 95| <= 2 points; smooth WRR
       is deterministic to ±1 pick, so the band is generous).

    ``wrong_model_routes`` rides along and must be 0 — the plane's
    invariant, not a performance number.  CPU-valid: numpy step fns."""
    import threading as _threading

    import brpc_tpu as brpc
    from brpc_tpu.serving import RouterClient
    from brpc_tpu.serving.modelplane import WARM
    from brpc_tpu.tools.rpc_press import (spin_up_multimodel_cluster,
                                          tear_down_multimodel_cluster)

    PT = 8

    # ---- half A: single-deployment vs two-deployment gens/s ----

    def drive(raddr, duration, models):
        stop = _threading.Event()
        mu = _threading.Lock()
        ok = [0]
        clients = [RouterClient(raddr, timeout_ms=20_000)
                   for _ in range(threads)]

        def worker(w):
            n = 0
            while not stop.is_set():
                prompt = [w * 31 + j for j in range(PT)]
                m = models[(w + n) % len(models)]
                n += 1
                try:
                    res = clients[w % len(clients)].generate(
                        prompt, max_new, timeout_s=20, model=m)
                except brpc.RpcError:
                    continue
                if res["error"] is None:
                    with mu:
                        ok[0] += 1

        ts = [_threading.Thread(target=worker, args=(w,), daemon=True)
              for w in range(threads)]
        t0 = time.monotonic()
        [t.start() for t in ts]
        time.sleep(duration)
        stop.set()
        [t.join(10) for t in ts]
        return ok[0] / (time.monotonic() - t0)

    def tax_trial(k):
        qps = {}
        wrong = 0
        for mode, models in (("single", ["modela"]),
                             ("dual", ["modela", "modelb"])):
            replicas, _mults, router, rsrv, raddr = \
                spin_up_multimodel_cluster(
                    n_replicas, models, page_tokens=PT,
                    step_delay_s=step_delay_s, max_sessions=512,
                    name_prefix=f"bench_mm_{k}_{mode}")
            try:
                drive(raddr, 0.2, models)        # warm both paths
                qps[mode] = drive(raddr, duration_s, models)
                wrong += router.stats()["wrong_model_routes"]
            finally:
                tear_down_multimodel_cluster(replicas, router, rsrv)
        return qps["single"], qps["dual"], wrong

    tax_rs = [tax_trial(k) for k in range(trials)]
    singles = sorted(r[0] for r in tax_rs)
    duals = sorted(r[1] for r in tax_rs)
    s_med = singles[len(singles) // 2]
    d_med = duals[len(duals) // 2]
    overheads = sorted((s - d) / s * 100.0
                       for s, d, _w in tax_rs if s > 0)
    o_med = overheads[len(overheads) // 2] if overheads else None
    wrong_routes = sum(r[2] for r in tax_rs)
    # same minimum-spread floor as the cluster/durable rungs:
    # admission quantization hides ± half a step period per generation
    floor_frac = 1.0 / (2 * max_new)

    # ---- half B: 95/5 canary split over one model_id ----

    def canary_trial(k):
        replicas, _mults, router, rsrv, raddr = \
            spin_up_multimodel_cluster(
                1, ["orca@v1", "orca@v2"], page_tokens=PT,
                step_delay_s=0.0, max_sessions=1024,
                name_prefix=f"bench_can_{k}")
        try:
            # the canary weights: v1 holds 95, v2 holds 5
            replicas[0]["deps"].deploy("orca@v1", weight=95, state=WARM)
            replicas[0]["deps"].deploy("orca@v2", weight=5, state=WARM)
            router.catalog.note(replicas[0]["addr"],
                                replicas[0]["deps"].snapshot())
            cli = RouterClient(raddr, timeout_ms=20_000)
            for i in range(canary_sessions):
                prompt = [900 + 7 * i + j for j in range(PT)]
                res = cli.generate(prompt, 2, timeout_s=20,
                                   model="orca")
                if res["error"] is not None:
                    raise RuntimeError(
                        f"bench_multimodel: canary generation failed "
                        f"E{res['error']}")
            picks = router.stats()["canary"].get("orca", {})
            v1 = picks.get("orca@v1", 0)
            total = sum(picks.values())
            return 100.0 * v1 / total if total else 0.0
        finally:
            tear_down_multimodel_cluster(replicas, router, rsrv)

    shares = sorted(canary_trial(k) for k in range(trials))
    share_med = shares[len(shares) // 2]

    return {
        "replicas": n_replicas,
        "threads": threads,
        "step_delay_ms": step_delay_s * 1e3,
        "single_model_gens_per_s": round(s_med, 1),
        "single_model_gens_per_s_spread": _floor_spread(
            s_med, singles[0], singles[-1], s_med * floor_frac),
        "two_model_gens_per_s": round(d_med, 1),
        "two_model_gens_per_s_spread": _floor_spread(
            d_med, duals[0], duals[-1], d_med * floor_frac),
        "two_model_overhead_pct": (round(o_med, 2)
                                   if o_med is not None else None),
        "two_model_overhead_pct_spread": (
            _floor_spread(o_med, overheads[0], overheads[-1],
                          100.0 * floor_frac)
            if o_med is not None else None),
        # the ISSUE 18 acceptance claim: naming models costs <= 5% of
        # anonymous single-model throughput at the median
        "two_model_overhead_within_5pct": bool(
            o_med is not None and o_med <= 5.0),
        "canary_sessions": canary_sessions,
        "canary_v1_share_pct": round(share_med, 2),
        "canary_v1_share_pct_spread": _floor_spread(
            share_med, shares[0], shares[-1],
            100.0 / canary_sessions),
        "canary_within_2pts": bool(abs(share_med - 95.0) <= 2.0),
        "wrong_model_routes": wrong_routes,
        "trials": trials,
        "cpu_valid": True,
        "note": ("multi-model plane rung (ISSUE 18): half A is "
                 "generations/s through one router front door, "
                 "single- vs two-deployment on the same fleet and the "
                 "same decode-bound operating point (the plane's "
                 "catalog/fingerprint/dispatch cost is the only "
                 "delta; <=5% acceptance), half B drives one model_id "
                 "behind 95/5-weighted versioned deployments and "
                 "reads the router's smooth-WRR canary scoreboard "
                 "(±2-point acceptance; the splitter is deterministic "
                 f"to ±1 pick); {trials} trials, minimum-spread "
                 f"floors of ±{100.0 / (2 * max_new):.1f}% "
                 "(admission quantization) / ±1 pick (canary); "
                 "wrong_model_routes must read 0"),
    }


def bench_telemetry(n_replicas: int = 2, trials: int = 3,
                    duration_s: float = 2.0, threads: int = 3,
                    step_delay_s: float = 0.01,
                    max_new: int = 16) -> dict:
    """Fleet telemetry plane rung (ISSUE 20): generations/s through
    one router front door with the telemetry plane OFF
    (``telemetry_collect=False``: no collector, no pulls, no SLO
    engine) vs ON (every 20 Hz tick samples the router scoreboard into
    fleet series and runs an attached burn-rate SLO engine; every
    replica's ``_telemetry`` increment is pulled over the control
    channel on its own ``telemetry_pull_interval_s`` cadence).  Same
    fleet, same decode-bound operating point — the collection pass is
    the only delta.

    Publishes ``telemetry_overhead_pct`` with the ISSUE 20 acceptance
    claim ``telemetry_overhead_within_2pct``, plus the collection
    evidence that makes a ~0% result meaningful rather than vacuous:
    ``collector_pulls``/``slo_evaluations`` must be well above 0 and
    ``bytes_per_pull`` bounds the per-tick wire increment (the
    cursor-based Pull ships deltas, not whole snapshots).  CPU-valid:
    numpy step fns."""
    import threading as _threading

    import brpc_tpu as brpc
    from brpc_tpu.serving import RouterClient
    from brpc_tpu.serving.slo import Objective, SLOEngine
    from brpc_tpu.tools.rpc_press import (spin_up_multimodel_cluster,
                                          tear_down_multimodel_cluster)

    PT = 8
    MODELS = ["orca@v1", "orca@v2"]

    def drive(raddr, duration):
        stop = _threading.Event()
        mu = _threading.Lock()
        ok = [0]
        clients = [RouterClient(raddr, timeout_ms=20_000)
                   for _ in range(threads)]

        def worker(w):
            n = 0
            while not stop.is_set():
                prompt = [w * 31 + j for j in range(PT)]
                m = MODELS[(w + n) % len(MODELS)]
                n += 1
                try:
                    res = clients[w % len(clients)].generate(
                        prompt, max_new, timeout_s=20, model=m)
                except brpc.RpcError:
                    continue
                if res["error"] is None:
                    with mu:
                        ok[0] += 1

        ts = [_threading.Thread(target=worker, args=(w,), daemon=True)
              for w in range(threads)]
        t0 = time.monotonic()
        [t.start() for t in ts]
        time.sleep(duration)
        stop.set()
        [t.join(10) for t in ts]
        return ok[0] / (time.monotonic() - t0)

    def trial(k):
        out = {}
        evidence = {}
        for mode in ("off", "on"):
            replicas, _mults, router, rsrv, raddr = \
                spin_up_multimodel_cluster(
                    n_replicas, MODELS, page_tokens=PT,
                    step_delay_s=step_delay_s, max_sessions=512,
                    name_prefix=f"bench_tel_{k}_{mode}",
                    router_kw={"telemetry_collect": mode == "on"})
            try:
                if mode == "on":
                    # a real burn-rate engine in the loop: targets are
                    # unreachable and clean_windows is effectively
                    # infinite, so it evaluates every tick but never
                    # re-weights — the full observe cost, zero plane
                    # mutations mid-measurement
                    router.attach_slo(SLOEngine(
                        "orca", "orca@v1", "orca@v2",
                        [Objective("itl_p99_ms", 60_000.0),
                         Objective("ttft_p99_ms", 60_000.0)],
                        short_window_s=0.5, long_window_s=1.5,
                        clean_windows=10**9))
                drive(raddr, 0.2)            # warm both paths
                out[mode] = drive(raddr, duration_s)
                if mode == "on":
                    cs = router.collector.stats()
                    evidence = {
                        "pulls": cs["pulls"],
                        "pull_bytes": cs["pull_bytes"],
                        "pull_errors": cs["pull_errors"],
                        "slo_evaluations":
                            router.slo.snapshot()["evaluations"],
                    }
            finally:
                tear_down_multimodel_cluster(replicas, router, rsrv)
        return out["off"], out["on"], evidence

    rs = [trial(k) for k in range(trials)]
    offs = sorted(r[0] for r in rs)
    ons = sorted(r[1] for r in rs)
    off_med = offs[len(offs) // 2]
    on_med = ons[len(ons) // 2]
    overheads = sorted((off - on) / off * 100.0
                       for off, on, _e in rs if off > 0)
    o_med = overheads[len(overheads) // 2] if overheads else None
    pulls = sum(r[2].get("pulls", 0) for r in rs)
    pull_bytes = sum(r[2].get("pull_bytes", 0) for r in rs)
    pull_errors = sum(r[2].get("pull_errors", 0) for r in rs)
    slo_evals = sum(r[2].get("slo_evaluations", 0) for r in rs)
    # same minimum-spread floor as the cluster/multimodel rungs:
    # admission quantization hides ± half a step period per generation
    floor_frac = 1.0 / (2 * max_new)

    return {
        "replicas": n_replicas,
        "threads": threads,
        "step_delay_ms": step_delay_s * 1e3,
        "telemetry_off_gens_per_s": round(off_med, 1),
        "telemetry_off_gens_per_s_spread": _floor_spread(
            off_med, offs[0], offs[-1], off_med * floor_frac),
        "telemetry_on_gens_per_s": round(on_med, 1),
        "telemetry_on_gens_per_s_spread": _floor_spread(
            on_med, ons[0], ons[-1], on_med * floor_frac),
        "telemetry_overhead_pct": (round(o_med, 2)
                                   if o_med is not None else None),
        "telemetry_overhead_pct_spread": (
            _floor_spread(o_med, overheads[0], overheads[-1],
                          100.0 * floor_frac)
            if o_med is not None else None),
        # the ISSUE 20 acceptance claim: the whole plane — fleet
        # sampling + per-replica pulls + SLO burn evaluation — costs
        # <= 2% of front-door throughput at the median
        "telemetry_overhead_within_2pct": bool(
            o_med is not None and o_med <= 2.0),
        # collection evidence: a 0% overhead claim over a collector
        # that never pulled would be vacuous
        "collector_pulls": pulls,
        "collector_pull_bytes": pull_bytes,
        "collector_pull_errors": pull_errors,
        "bytes_per_pull": (round(pull_bytes / pulls, 1)
                           if pulls else None),
        "slo_evaluations": slo_evals,
        "telemetry_actually_collected": bool(pulls > 0
                                             and slo_evals > 0),
        "trials": trials,
        "cpu_valid": True,
        "note": ("fleet telemetry plane rung (ISSUE 20): "
                 "generations/s through one router front door with "
                 "the collection pass (20 Hz fleet series sampling + "
                 "SLO burn-rate evaluation, incremental per-replica "
                 "_telemetry pulls on their own cadence) off vs on "
                 "over the same fleet and operating point; <=2% "
                 "acceptance at the "
                 f"median over {trials} trials, minimum-spread floor "
                 f"of ±{100.0 / (2 * max_new):.1f}% (admission "
                 "quantization); collector_pulls/slo_evaluations "
                 "must be > 0 or the claim is vacuous, and "
                 "bytes_per_pull bounds the cursor-based wire "
                 "increment"),
    }


def telemetry_main(argv) -> None:
    """`python bench.py telemetry`: run ONLY the fleet telemetry
    overhead rung and print one JSON object on stdout (progress on
    stderr) — the `make telemetry`-adjacent bench entry and the
    subprocess the full bench run shells out to."""
    log("telemetry: fleet collection on/off overhead rung...")
    out = bench_telemetry()
    for k, v in out.items():
        if isinstance(v, (dict, list)):
            log(f"  {k}: {json.dumps(v)}")
        else:
            log(f"  {k}: {v}")
    print(json.dumps(out))


def multimodel_main(argv) -> None:
    """`python bench.py multimodel`: run ONLY the multi-model plane
    rung and print one JSON object on stdout (progress on stderr) —
    the `make multimodel`-adjacent bench entry and the subprocess the
    full bench run shells out to."""
    log("multimodel: two-model tax + canary split rung...")
    out = bench_multimodel()
    for k, v in out.items():
        if isinstance(v, (dict, list)):
            log(f"  {k}: {json.dumps(v)}")
        else:
            log(f"  {k}: {v}")
    print(json.dumps(out))


def migrate_main(argv) -> None:
    """`python bench.py migrate`: run ONLY the migration rung and
    print one JSON object on stdout (progress on stderr) — the
    `make migrate`-adjacent bench entry and the subprocess the full
    bench run shells out to."""
    log("migrate: migrate-vs-recompute admit rung...")
    out = bench_migrate()
    for k, v in out.items():
        if isinstance(v, dict):
            log(f"  {k}: {json.dumps(v)}")
    print(json.dumps(out))


def main():
    # the device rungs need a TPU, and a known one: say so before the
    # host rungs spend their minutes.  The children below are forced to
    # the CPU, so this process holding the chip costs them nothing.
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        log(f"bench: needs a TPU, jax found {dev.platform!r} "
            f"({dev.device_kind}); nothing was run")
        sys.exit(2)
    device_peaks(dev.device_kind)
    details = {"platform": dev.platform, "device_kind": dev.device_kind,
               "device_count": len(jax.devices())}
    log("bench: unary echo (python service)...")
    details["echo"] = bench_unary_echo()
    log(f"  {details['echo']}")
    log("bench: native echo...")
    details["native_echo"] = bench_native_echo()
    log(f"  {details['native_echo']}")
    log("bench: echo thread-scaling (python service)...")
    details["echo_scaling"] = bench_echo_scaling()
    log(f"  {details['echo_scaling']}")
    log("bench: native echo connection-scaling...")
    details["native_echo_scaling"] = bench_native_echo_scaling()
    log(f"  {details['native_echo_scaling']}")
    log("bench: grpc echo (h2 python data plane)...")
    try:
        details["grpc_echo"] = bench_grpc_echo()
    except Exception as e:
        details["grpc_echo"] = {"error": f"{type(e).__name__}: {e}"}
    log(f"  {details['grpc_echo']}")
    log("bench: dcn data plane (two processes, loopback)...")
    try:
        details["dcn"] = bench_dcn()
    except Exception as e:
        details["dcn"] = {"error": f"{type(e).__name__}: {e}"}
    log(f"  {details['dcn']}")
    log("bench: per-stage host microbenches (subprocess, forced CPU)...")
    try:
        details["microbench"] = _run_microbench_subprocess()
    except Exception as e:
        details["microbench"] = {"error": f"{type(e).__name__}: {e}"}
    log(f"  {details['microbench']}")
    log("bench: kv page migration (subprocess, forced CPU)...")
    try:
        details["migrate"] = _run_cpu_subcommand("migrate")
    except Exception as e:
        details["migrate"] = {"error": f"{type(e).__name__}: {e}"}
    log(f"  {details['migrate']}")
    log("bench: cluster front door (subprocess, forced CPU)...")
    try:
        details["cluster"] = _run_cpu_subcommand("cluster")
    except Exception as e:
        details["cluster"] = {"error": f"{type(e).__name__}: {e}"}
    log(f"  {details['cluster']}")
    log("bench: durable control plane (subprocess, forced CPU)...")
    try:
        details["durable"] = _run_cpu_subcommand("durable")
    except Exception as e:
        details["durable"] = {"error": f"{type(e).__name__}: {e}"}
    log(f"  {details['durable']}")
    log("bench: multi-model plane (subprocess, forced CPU)...")
    try:
        details["multimodel"] = _run_cpu_subcommand("multimodel")
    except Exception as e:
        details["multimodel"] = {"error": f"{type(e).__name__}: {e}"}
    log(f"  {details['multimodel']}")
    log("bench: fleet telemetry plane (subprocess, forced CPU)...")
    try:
        details["telemetry"] = _run_cpu_subcommand("telemetry")
    except Exception as e:
        details["telemetry"] = {"error": f"{type(e).__name__}: {e}"}
    log(f"  {details['telemetry']}")
    log("bench: real-model serving (subprocess, forced CPU)...")
    try:
        details["model"] = _run_cpu_subcommand("model")
    except Exception as e:
        details["model"] = {"error": f"{type(e).__name__}: {e}"}
    log(f"  {details['model']}")
    log("bench: speculative decoding (subprocess, forced CPU)...")
    try:
        details["speculative"] = _run_cpu_subcommand("speculative")
    except Exception as e:
        details["speculative"] = {"error": f"{type(e).__name__}: {e}"}
    log(f"  {details['speculative']}")
    log("bench: sharded parameter server (subprocess, forced CPU)...")
    try:
        details["embedding"] = _run_cpu_subcommand("embedding")
    except Exception as e:
        details["embedding"] = {"error": f"{type(e).__name__}: {e}"}
    log(f"  {details['embedding']}")
    log("bench: training plane (subprocess, forced CPU)...")
    try:
        details["train"] = _run_cpu_subcommand("train")
    except Exception as e:
        details["train"] = {"error": f"{type(e).__name__}: {e}"}
    log(f"  {details['train']}")
    # the device rungs: no isolation here — one that raises ends the run
    # non-zero instead of publishing an {"error": ...} entry under a
    # device metric's name
    for name, fn in (("serving", bench_serving),
                     ("kvcache", bench_kvcache),
                     ("recovery", bench_recovery),
                     ("trace_overhead", bench_trace_overhead),
                     ("tensor_pipe", lambda: bench_tensor_pipe(chunk_mb=64)),
                     ("streaming_tensor", bench_streaming_tensor),
                     ("hbm_stream", bench_hbm_stream),
                     ("ici_ladder", bench_ici_ladder)):
        log(f"bench: {name}...")
        details[name] = fn()
        log(f"  {details[name]}")
    headline = details["tensor_pipe"].get("gbps")
    if headline is None:
        # gated away (the rung says why under "invalid"): still no value
        # wearing the metric's name, and no exit 0
        details["headline_invalid"] = details["tensor_pipe"].get("invalid")
    # Details are deliberately NOT on stdout: one giant JSON line once
    # outgrew the driver's tail buffer and the headline was lost.
    # Per-bench results go to stderr line by line plus a sidecar file;
    # the LAST stdout line is the compact machine-readable headline only.
    for name, d in details.items():
        log(f"detail {name}: {json.dumps(d)}")
    try:
        with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "BENCH_DETAILS.json"), "w") as f:
            json.dump(details, f, indent=1)
    except OSError as e:
        log(f"could not write BENCH_DETAILS.json: {e}")
    print(json.dumps({
        "metric": "tensor_pipe_throughput",
        "value": headline,
        "unit": "GB/s",
        "vs_baseline": (round(headline / BASELINE_GBPS, 2)
                        if headline is not None else None),
        "platform": details["platform"],
        "device_kind": details["device_kind"],
        "device_count": details["device_count"],
    }))
    if headline is None:
        sys.exit(1)


def microbench_main(argv) -> None:
    """`python bench.py microbench [--quick]`: run ONLY the per-stage
    host microbench suite and print one JSON object on stdout (progress
    on stderr) — the `make microbench` entry and the subprocess the
    full bench run shells out to."""
    quick = "--quick" in argv
    log(f"microbench: per-stage host suite{' (quick)' if quick else ''}...")
    out = bench_microbench(quick=quick)
    for k, v in out.items():
        if isinstance(v, dict):
            log(f"  {k}: {json.dumps(v)}")
    print(json.dumps(out))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "microbench":
        microbench_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "migrate":
        migrate_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "cluster":
        cluster_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "durable":
        durable_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "multimodel":
        multimodel_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "telemetry":
        telemetry_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "model":
        model_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "speculative":
        speculative_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "embedding":
        embedding_main(sys.argv[2:])
    elif len(sys.argv) > 1 and sys.argv[1] == "train":
        train_main(sys.argv[2:])
    else:
        main()

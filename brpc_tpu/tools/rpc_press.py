"""rpc_press — protobuf-less load generator
(reference tools/rpc_press/rpc_press_impl.cpp: sends sample requests from
JSON at a target qps, reports qps + latency percentiles).

Unary example:
  python -m brpc_tpu.tools.rpc_press --server 127.0.0.1:8000 \
      --service EchoService --method Echo --input '{"msg":"hi"}' \
      --qps 5000 --duration 10 --threads 8

Streaming mode (--streaming) drives a method that streams items back
over the credit-windowed stream layer (e.g. Serving.Generate): each
worker attaches a client stream per call, counts delivered items, and
reports items/s plus time-to-first-item percentiles — the serving-path
analog of unary qps/latency.

Prefix-skewed load (--shared-prefix-ratio R): each call's "prompt"
field is regenerated — with probability R it opens with ONE fixed
shared prefix (--prefix-tokens long) followed by a random suffix,
otherwise it is fully random.  R=0.9 models a shared-system-prompt
workload and drives the paged KV cache's radix hit-rate (watch
/kvcache while pressing); R=0 is the worst case for prefix reuse.
The schedule is seeded per worker, so runs replay.

Trace dumping (--dump-traces N): rpcz is enabled in the press process
and every call runs under a client root span, so each press call is
one trace; after the run the N SLOWEST traces print as tree-ordered
indented timelines (relative offsets, annotations).  Against an
in-process or rpcz-enabled server the timelines include the server-side
stage spans — the fastest way from "it's slow" to WHICH stage is slow.

Hotspot attribution (--hotspots N, ISSUE 6): while the press runs, the
SERVER's /hotspots console is asked for a stage-tagged burst profile
covering the press duration, and the top-N folded stacks print
alongside the latency report — load test and CPU attribution in one
command ("it's slow" -> "decode_step is 60% lock-wait" without a
second tool).
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import brpc_tpu as brpc
from brpc_tpu import errors, rpcz
from brpc_tpu.bvar import LatencyRecorder


def dump_slowest_traces(n: int, trace_ids=None, out=sys.stderr) -> None:
    """Print the n slowest collected traces as indented timelines
    (--dump-traces).  ``trace_ids`` restricts ranking to THIS run's
    traces — the shared in-process span store may hold unrelated
    history (a co-located server's own traffic)."""
    spans = rpcz.recent_spans(limit=2048)
    if trace_ids is not None:
        spans = [s for s in spans if s.trace_id in trace_ids]
    groups = rpcz.slowest_traces(spans, n)
    if not groups:
        print("no traces collected (is rpcz enabled?)", file=out)
        return
    print(f"--- {len(groups)} slowest traces ---", file=out)
    for group in groups:
        print(rpcz.format_trace(group), end="", file=out)


class HotspotFetcher:
    """Background fetch of the target server's stage-tagged burst
    profile (``/hotspots?seconds=N&fmt=collapsed``) for the press
    window; ``report(top_n)`` prints the hottest folded stacks."""

    def __init__(self, server: str, seconds: float):
        self.server = server
        self.seconds = max(0.2, min(60.0, seconds))
        self.folded: str | None = None
        self.error: str | None = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self) -> "HotspotFetcher":
        self._thread.start()
        return self

    def _run(self) -> None:
        import http.client
        host, _, port = self.server.rpartition(":")
        try:
            c = http.client.HTTPConnection(host or "127.0.0.1", int(port),
                                           timeout=self.seconds + 60)
            c.request("GET", f"/hotspots?seconds={self.seconds}"
                             f"&fmt=collapsed")
            r = c.getresponse()
            body = r.read().decode("utf-8", "replace")
            c.close()
            if r.status != 200:
                self.error = f"/hotspots returned {r.status}"
            else:
                self.folded = body
        except Exception as e:
            self.error = f"{type(e).__name__}: {e}"

    def report(self, top_n: int, out=sys.stderr) -> None:
        self._thread.join(self.seconds + 90)
        if self.folded is None:
            print(f"(no server hotspot profile: "
                  f"{self.error or 'fetch still pending'})", file=out)
            return
        rows = []
        for line in self.folded.splitlines():
            stack, _, n = line.rpartition(" ")
            if stack and n.isdigit():
                rows.append((int(n), stack))
        rows.sort(reverse=True)
        total = sum(n for n, _ in rows) or 1
        print(f"--- server hotspots during press "
              f"({self.seconds:g}s burst @100Hz, {total} samples; "
              f"top {min(top_n, len(rows))} stage-tagged stacks) ---",
              file=out)
        for n, stack in rows[:top_n]:
            print(f"  [{n:>5} samples {100.0 * n / total:>5.1f}%] "
                  f"{stack}", file=out)


def make_prefix_skew(request, ratio: float, prefix_tokens: int = 32,
                     suffix_tokens: int = 8, vocab: int = 1000,
                     seed: int = 0):
    """Per-worker request factory for prefix-skewed generate load: with
    probability `ratio` the "prompt" opens with one fixed shared prefix
    (the page-aligned unit the KV radix tree caches), else it is fully
    random.  ``make_prefix_skew(...)(k)`` returns worker k's factory —
    each worker gets its own seeded rng so the schedule replays."""
    import random as _random
    shared = [(seed * 1009 + i * 37) % vocab for i in range(prefix_tokens)]

    def for_worker(k: int):
        rng = _random.Random((seed << 16) ^ k)

        def next_request():
            req = dict(request)
            suffix = [rng.randrange(vocab) for _ in range(suffix_tokens)]
            if rng.random() < ratio:
                req["prompt"] = shared + suffix
            else:
                req["prompt"] = [rng.randrange(vocab) for _ in
                                 range(prefix_tokens)] + suffix
            return req

        return next_request

    return for_worker


def run_press(server: str, service: str, method: str, request,
              qps: int = 0, duration_s: float = 10.0, threads: int = 4,
              serializer: str = "json", timeout_ms: int = 1000,
              connection_type: str = "single", request_factory=None,
              dump_traces: int = 0, hotspots: int = 0,
              out=sys.stderr) -> dict:
    """Drives the load; returns a summary dict (also printable).
    ``request_factory(k)`` (e.g. ``make_prefix_skew(...)``), when
    given, builds worker k's per-call request generator.
    ``dump_traces=N`` enables rpcz for the run (each call becomes one
    trace rooted at a press client span) and prints the N slowest
    traces as indented timelines afterwards.  ``hotspots=N`` runs the
    server-side burst profiler for the press duration and prints the
    top-N stage-tagged folded stacks alongside the latency report."""
    traced = dump_traces > 0
    rpcz_state = (rpcz.enabled(), rpcz.sample_rate())
    if traced:
        rpcz.set_enabled(True)
    try:
        return _run_press_body(server, service, method, request, qps,
                               duration_s, threads, serializer,
                               timeout_ms, connection_type,
                               request_factory, dump_traces, traced,
                               hotspots, out)
    finally:
        # restore BOTH knobs, even on a mid-run exception: a press must
        # not leave a co-located server force-traced at rate 1.0
        if traced:
            rpcz.set_enabled(*rpcz_state)


def _run_press_body(server, service, method, request, qps, duration_s,
                    threads, serializer, timeout_ms, connection_type,
                    request_factory, dump_traces, traced, hotspots,
                    out) -> dict:
    ch = brpc.Channel(server, timeout_ms=timeout_ms,
                      connection_type=connection_type)
    fetcher = HotspotFetcher(server, duration_s).start() \
        if hotspots > 0 else None
    rec = LatencyRecorder("rpc_press")
    # python-side latency reservoir: the native recorder pool is 512
    # slots process-wide, and deep in a churn-heavy suite a freshly
    # created recorder can transiently miss a slot (GC lag holds
    # freed-but-uncollected recorders' slots) — its percentiles then
    # read 0 despite real traffic.  The press must report honest
    # latency regardless, so it keeps a bounded sample of its own.
    lats: list = []          # GIL-atomic appends; bounded below
    _LATS_CAP = 200_000
    nerr = [0]
    nok = [0]
    press_tids: list = []   # this run's trace ids (GIL-atomic appends)
    stop = threading.Event()
    # per-thread qps budget; qps<=0 = unthrottled
    per_thread_interval = threads / qps if qps > 0 else 0.0

    def worker(k: int):
        gen = request_factory(k) if request_factory is not None else None
        next_at = time.monotonic()
        while not stop.is_set():
            if per_thread_interval > 0:
                now = time.monotonic()
                if now < next_at:
                    time.sleep(min(next_at - now, 0.05))
                    continue
                next_at += per_thread_interval
            req = gen() if gen is not None else request
            span = rpcz.new_span("client", service, method) if traced \
                else rpcz.NULL_SPAN
            if span is not rpcz.NULL_SPAN:
                span.remote_side = server
                press_tids.append(span.trace_id)
                rpcz.set_current_span(span)
            t0 = time.monotonic()
            try:
                ch.call_sync(service, method, req,
                             serializer=serializer)
                dt_us = int((time.monotonic() - t0) * 1e6)
                rec.add(dt_us)
                if len(lats) < _LATS_CAP:
                    lats.append(dt_us)
                nok[0] += 1
            except Exception as e:
                nerr[0] += 1
                span.error_code = getattr(e, "code", -1) or -1
            finally:
                if span is not rpcz.NULL_SPAN:
                    rpcz.set_current_span(None)
                    rpcz.submit(span)

    ts = [threading.Thread(target=worker, args=(k,), daemon=True)
          for k in range(threads)]
    t_start = time.monotonic()
    [t.start() for t in ts]
    try:
        time.sleep(duration_s)
    finally:
        stop.set()
    [t.join(2) for t in ts]
    elapsed = time.monotonic() - t_start
    srt = sorted(lats)

    def pctl(p: float) -> float:
        v = rec.latency_percentile(p)
        if v <= 0 and srt:
            # native recorder never got a slot: serve the percentile
            # from the press's own reservoir
            v = float(srt[min(len(srt) - 1, int(p * len(srt)))])
        return v

    avg = rec.latency()
    if avg <= 0 and srt:
        avg = sum(srt) / len(srt)
    mx = rec.max_latency()
    if mx <= 0 and srt:
        mx = srt[-1]
    summary = {
        "sent_ok": nok[0],
        "errors": nerr[0],
        "qps": round(nok[0] / elapsed, 1),
        "avg_us": round(avg, 1),
        "p50_us": pctl(0.5),
        "p90_us": pctl(0.9),
        "p99_us": pctl(0.99),
        "p999_us": pctl(0.999),
        "max_us": mx,
        "elapsed_s": round(elapsed, 2),
    }
    print(json.dumps(summary), file=out)
    if fetcher is not None:
        fetcher.report(hotspots, out=out)
    if traced:
        dump_slowest_traces(dump_traces, trace_ids=set(press_tids),
                            out=out)
    return summary


class _PressStreamHandler(brpc.StreamHandler):
    """Counts delivered items, stamps the first one, latches close."""

    def __init__(self):
        self.items = 0
        self.first_at = None
        self.closed = threading.Event()

    def on_received_messages(self, stream, messages):
        if self.first_at is None:
            self.first_at = time.monotonic()
        self.items += len(messages)

    def on_closed(self, stream):
        self.closed.set()


def run_streaming_press(server: str, service: str, method: str, request,
                        duration_s: float = 10.0, threads: int = 4,
                        serializer: str = "json", timeout_ms: int = 5000,
                        connection_type: str = "single",
                        request_factory=None, dump_traces: int = 0,
                        hotspots: int = 0, out=sys.stderr) -> dict:
    """Streaming load: one client stream per call, looped per worker for
    `duration_s`.  Reports aggregate items/s and time-to-first-item
    (TTFI) percentiles; a stream that never closes within the timeout
    counts as an error.  ``dump_traces=N`` prints the N slowest traces
    afterwards (each stream call is one trace)."""
    traced = dump_traces > 0
    rpcz_state = (rpcz.enabled(), rpcz.sample_rate())
    if traced:
        rpcz.set_enabled(True)
    try:
        return _run_streaming_body(server, service, method, request,
                                   duration_s, threads, serializer,
                                   timeout_ms, connection_type,
                                   request_factory, dump_traces, traced,
                                   hotspots, out)
    finally:
        if traced:
            rpcz.set_enabled(*rpcz_state)


def _run_streaming_body(server, service, method, request, duration_s,
                        threads, serializer, timeout_ms, connection_type,
                        request_factory, dump_traces, traced, hotspots,
                        out) -> dict:
    ch = brpc.Channel(server, timeout_ms=timeout_ms,
                      connection_type=connection_type)
    fetcher = HotspotFetcher(server, duration_s).start() \
        if hotspots > 0 else None
    ttfi = LatencyRecorder("rpc_press_ttfi")
    items = [0]
    streams_ok = [0]
    nerr = [0]
    press_tids: list = []
    mu = threading.Lock()
    stop = threading.Event()

    def worker(k: int):
        gen = request_factory(k) if request_factory is not None else None
        while not stop.is_set():
            h = _PressStreamHandler()
            cntl = brpc.Controller()
            stream = brpc.stream_create(cntl, h)
            req = gen() if gen is not None else request
            span = rpcz.new_span("client", service, method) if traced \
                else rpcz.NULL_SPAN
            if span is not rpcz.NULL_SPAN:
                span.remote_side = server
                press_tids.append(span.trace_id)
                rpcz.set_current_span(span)
            t0 = time.monotonic()
            try:
                ch.call_sync(service, method, req,
                             serializer=serializer, cntl=cntl)
            except Exception as e:
                with mu:
                    nerr[0] += 1
                span.error_code = getattr(e, "code", -1) or -1
                if span is not rpcz.NULL_SPAN:
                    rpcz.set_current_span(None)
                    rpcz.submit(span)
                stream.close()
                continue
            finally:
                if span is not rpcz.NULL_SPAN:
                    rpcz.set_current_span(None)
            ok = h.closed.wait(timeout_ms / 1e3)
            if span is not rpcz.NULL_SPAN:
                span.annotate(f"stream closed: items={h.items} ok={ok}")
                rpcz.submit(span)
            with mu:
                if ok:
                    streams_ok[0] += 1
                    items[0] += h.items
                    if h.first_at is not None:
                        ttfi.add(int((h.first_at - t0) * 1e6))
                else:
                    nerr[0] += 1
            if not ok:
                stream.close()

    ts = [threading.Thread(target=worker, args=(k,), daemon=True)
          for k in range(threads)]
    t_start = time.monotonic()
    [t.start() for t in ts]
    try:
        time.sleep(duration_s)
    finally:
        stop.set()
    [t.join(timeout_ms / 1e3 + 2) for t in ts]
    elapsed = time.monotonic() - t_start
    summary = {
        "streams_ok": streams_ok[0],
        "errors": nerr[0],
        "items": items[0],
        "items_per_s": round(items[0] / elapsed, 1),
        "ttfi_avg_us": round(ttfi.latency(), 1),
        "ttfi_p50_us": ttfi.latency_percentile(0.5),
        "ttfi_p90_us": ttfi.latency_percentile(0.9),
        "ttfi_p99_us": ttfi.latency_percentile(0.99),
        "elapsed_s": round(elapsed, 2),
    }
    print(json.dumps(summary), file=out)
    if fetcher is not None:
        fetcher.report(hotspots, out=out)
    if traced:
        dump_slowest_traces(dump_traces, trace_ids=set(press_tids),
                            out=out)
    return summary


def run_disagg_press(prefill_addr: str, decode_addr: str, request,
                     duration_s: float = 10.0, threads: int = 4,
                     timeout_ms: int = 20_000, request_factory=None,
                     out=sys.stderr) -> dict:
    """``--disagg`` mode: drive full generations through the SPLIT
    topology — each call runs Prefill on the prefill process (whose
    finished pages stream to the decode store over the ``_kvmig``
    plane) and then streams tokens from the decode process — so heavy
    traffic exercises the page stream under load.  Reports
    generations/s, tokens/s, time-to-first-token percentiles, and how
    many prefills fell back to recompute (failed migrations)."""
    from brpc_tpu.migrate import DisaggCoordinator
    rec_ttft = LatencyRecorder("rpc_press_disagg_ttft")
    mu = threading.Lock()
    gens_ok = [0]
    nerr = [0]
    tokens = [0]
    fallbacks = [0]
    stop = threading.Event()

    def worker(k: int):
        # one coordinator (its own channel pair) per worker: the page
        # stream and the token stream both scale with concurrency
        co = DisaggCoordinator(prefill_addr, decode_addr,
                               timeout_ms=timeout_ms)
        gen = request_factory(k) if request_factory is not None else None
        while not stop.is_set():
            req = gen() if gen is not None else request
            prompt = req.get("prompt") or [1]
            n = int(req.get("max_new_tokens", 16))
            first = [None]

            def emit(tok, first=first):
                if first[0] is None:
                    first[0] = time.monotonic()

            t0 = time.monotonic()
            try:
                res = co.generate(prompt, n, emit=emit,
                                  timeout_s=timeout_ms / 1e3)
            except Exception:
                with mu:
                    nerr[0] += 1
                continue
            with mu:
                if res["error"]:
                    nerr[0] += 1
                    continue
                gens_ok[0] += 1
                tokens[0] += len(res["tokens"])
                if res["prefill"].get("recompute_fallback"):
                    fallbacks[0] += 1
            if first[0] is not None:
                rec_ttft.add(int((first[0] - t0) * 1e6))

    ts = [threading.Thread(target=worker, args=(k,), daemon=True)
          for k in range(threads)]
    t_start = time.monotonic()
    [t.start() for t in ts]
    try:
        time.sleep(duration_s)
    finally:
        stop.set()
    [t.join(timeout_ms / 1e3 + 2) for t in ts]
    elapsed = time.monotonic() - t_start
    summary = {
        "generations_ok": gens_ok[0],
        "errors": nerr[0],
        "tokens": tokens[0],
        "generations_per_s": round(gens_ok[0] / elapsed, 1),
        "tokens_per_s": round(tokens[0] / elapsed, 1),
        "recompute_fallbacks": fallbacks[0],
        "ttft_avg_us": round(rec_ttft.latency(), 1),
        "ttft_p50_us": rec_ttft.latency_percentile(0.5),
        "ttft_p99_us": rec_ttft.latency_percentile(0.99),
        "elapsed_s": round(elapsed, 2),
    }
    print(json.dumps(summary), file=out)
    return summary


def spin_up_replicas(n_replicas: int, *, page_tokens: int = 8,
                     step_delay_s: float = 0.0, num_slots: int = 8,
                     max_blocks: int = 64, page_bytes: int = 512,
                     max_pages_per_slot: int = 64,
                     name_prefix: str = "cluster",
                     commit_live_pages: bool = False,
                     prefill_cost_per_token_s: float = 0.0):
    """N serving replicas (paged KV store + decode engine with a plain
    numpy step function, CPU-valid) each exposing the Serving,
    ``_kvmig`` AND ``_cluster`` services — so they work behind an
    in-process router (ISSUE 8 shape) or a remote-only SUBPROCESS
    router (ISSUE 16: address-only handles, floor pushes over the
    wire, prefix pulls between replicas).

    ``prefill_cost_per_token_s`` adds a prefill stage whose cost
    scales with the (bucket-padded) UNCACHED suffix — the real-model
    cost shape where a prefix-cache hit buys skipped compute, so
    warmth effects show at true proportions instead of one flat-priced
    vectorized call.

    Returns a list of ``(store, engine, server, addr)``; tear down
    with :func:`tear_down_replicas`."""
    import numpy as np

    from brpc_tpu.kvcache import KVCacheStore
    from brpc_tpu.migrate import make_prefix_fetcher, register_migration
    from brpc_tpu.serving import (DecodeEngine, register_cluster_control,
                                  register_serving, register_telemetry)

    def step(tokens, positions, pages=None):
        if step_delay_s:
            time.sleep(step_delay_s)
        return (np.asarray(tokens) * 7 + np.asarray(positions)) % 997

    prefill_fn = None
    if prefill_cost_per_token_s:
        def prefill_fn(tokens, prefill_from):
            time.sleep(prefill_cost_per_token_s * int(np.size(tokens)))

    replicas = []
    for i in range(n_replicas):
        store = KVCacheStore(page_tokens=page_tokens,
                             page_bytes=page_bytes,
                             max_blocks=max_blocks,
                             name=f"{name_prefix}_{i}",
                             commit_live_pages=commit_live_pages)
        eng = DecodeEngine(step, num_slots=num_slots, store=store,
                           max_pages_per_slot=max_pages_per_slot,
                           prefill_fn=prefill_fn,
                           name=f"{name_prefix}_eng_{i}")
        srv = brpc.Server(enable_dcn=True)
        serving_svc = register_serving(srv, engine=eng)
        mig_svc = register_migration(srv, store)
        register_cluster_control(srv, engine=eng, store=store,
                                 name=f"{name_prefix}_{i}")
        register_telemetry(srv, name=f"{name_prefix}_{i}")
        srv.start("127.0.0.1", 0)
        addr = f"127.0.0.1:{srv.port}"
        # the fetcher needs the replica's own addr, known only now
        serving_svc.prefix_fetcher = make_prefix_fetcher(
            mig_svc.migrator, addr)
        replicas.append((store, eng, srv, addr))
    return replicas


def tear_down_replicas(replicas) -> None:
    """Close what :func:`spin_up_replicas` built (replicas already
    killed mid-run tear down quietly)."""
    for store, eng, srv, _addr in replicas:
        try:
            eng.close(timeout_s=2.0)
        except Exception:
            pass
        try:
            srv.stop()
            srv.join()
        except Exception:
            pass
        store.clear()
        store.close()


# ---------------------------------------------------------------------------
# multi-model fleets (ISSUE 18)
# ---------------------------------------------------------------------------

# per-model step-function multipliers: model i's decode rule is
# (t * PRIME_i + pos) % 997, so every model's token stream is
# distinguishable from every other's — a generation that bit-matches
# the WRONG model's oracle is a mis-route, caught client-side
MODEL_STEP_PRIMES = (7, 11, 13, 17, 19, 23, 29)


def model_step_fn(mult: int, step_delay_s=0.0):
    """The numpy step function for one model deployment (CPU-valid).
    ``step_delay_s`` may be a float or a zero-arg callable evaluated
    per step — the knob the SLO rollback test turns mid-run to make
    ONE version's ITL burn while its tokens stay bit-exact."""
    import numpy as np

    def step(tokens, positions, pages=None):
        d = step_delay_s() if callable(step_delay_s) else step_delay_s
        if d:
            time.sleep(d)
        return (np.asarray(tokens) * int(mult)
                + np.asarray(positions)) % 997

    return step


def expected_model_tokens(prompt, n: int, mult: int = 7) -> list:
    """The bit-exact oracle for :func:`model_step_fn`: the n tokens a
    correct generation of ``prompt`` emits under multiplier ``mult``."""
    out = []
    last = int(prompt[-1])
    pos = len(prompt)
    for _ in range(int(n)):
        last = (last * int(mult) + pos) % 997
        out.append(last)
        pos += 1
    return out


def spin_up_multimodel_replicas(n_replicas: int, models, *, layout=None,
                                page_tokens: int = 8,
                                step_delay_s=0.0,
                                num_slots: int = 8, max_blocks: int = 64,
                                page_bytes: int = 512,
                                max_pages_per_slot: int = 64,
                                name_prefix: str = "mm",
                                commit_live_pages: bool = False,
                                warm: bool = True):
    """N serving replicas, each carrying one :class:`~brpc_tpu.serving.
    ReplicaDeployments` table over the given ``models`` (ISSUE 18):
    per-deployment store + engine (model i's step rule uses
    ``MODEL_STEP_PRIMES[i]``, so streams are model-attributable), the
    Serving service resolving the forwarded ``model`` field, the
    ``_cluster`` service publishing the catalog, and ``_kvmig`` bound
    to the FIRST deployment's store, model-tagged (a mismatched fetch
    is refused; other models fall back to recompute — fetch is an
    optimization, never a correctness dependency).

    ``layout[i]`` restricts replica i to a subset of ``models``
    (default: every replica serves all of them) — the knob chaos
    scenario 19 uses to build a fleet where exactly one replica is
    warm for model B.  ``warm=False`` starts deployments ``loading``
    (the first completed generation flips them warm).

    Returns ``(replicas, mults)``: ``replicas`` a list of dicts with
    keys ``deps``/``stores``/``engines``/``server``/``addr``/
    ``models``, ``mults`` the ``model -> multiplier`` oracle map.
    Tear down with :func:`tear_down_multimodel_replicas`."""
    from brpc_tpu.kvcache import KVCacheStore
    from brpc_tpu.migrate import make_prefix_fetcher, register_migration
    from brpc_tpu.serving import (DecodeEngine, ReplicaDeployments,
                                  register_cluster_control,
                                  register_serving, register_telemetry)
    from brpc_tpu.serving.modelplane import LOADING, WARM

    models = [str(m) for m in models]
    mults = {m: MODEL_STEP_PRIMES[i % len(MODEL_STEP_PRIMES)]
             for i, m in enumerate(models)}
    state0 = WARM if warm else LOADING
    replicas = []
    for i in range(n_replicas):
        served = models if layout is None \
            else [str(m) for m in layout[i]]
        deps = ReplicaDeployments(name=f"{name_prefix}_{i}")
        stores, engines = {}, {}
        srv = brpc.Server(enable_dcn=True)
        for m in served:
            store = KVCacheStore(page_tokens=page_tokens,
                                 page_bytes=page_bytes,
                                 max_blocks=max_blocks,
                                 name=f"{name_prefix}_{i}_{m}",
                                 commit_live_pages=commit_live_pages)
            # step_delay_s: scalar/callable for the whole fleet, or a
            # dict keyed by deployment key — per-VERSION latency
            # injection (the SLO rollback test slows only the canary)
            delay = step_delay_s.get(m, 0.0) \
                if isinstance(step_delay_s, dict) else step_delay_s
            eng = DecodeEngine(model_step_fn(mults[m], delay),
                               num_slots=num_slots, store=store,
                               max_pages_per_slot=max_pages_per_slot,
                               name=f"{name_prefix}_eng_{i}_{m}")
            stores[m], engines[m] = store, eng
            deps.deploy(m, engine=eng, store=store, state=state0)
        m0 = served[0] if served else None
        serving_svc = register_serving(
            srv, engine=engines.get(m0), deployments=deps)
        mig_svc = register_migration(srv, stores[m0], model=m0) \
            if m0 else None
        register_cluster_control(srv, engine=engines.get(m0),
                                 store=stores.get(m0),
                                 name=f"{name_prefix}_{i}",
                                 deployments=deps)
        register_telemetry(srv, name=f"{name_prefix}_{i}")
        srv.start("127.0.0.1", 0)
        addr = f"127.0.0.1:{srv.port}"
        if mig_svc is not None:
            # fetcher ONLY on the _kvmig-bound deployment: a shared
            # svc-level fetcher would splice other models' fetches
            # into m0's store
            deps.deploy(m0, prefix_fetcher=make_prefix_fetcher(
                mig_svc.migrator, addr, model=m0), state=state0)
        replicas.append({"deps": deps, "stores": stores,
                         "engines": engines, "server": srv,
                         "addr": addr, "models": list(served),
                         "serving": serving_svc})
    return replicas, mults


def tear_down_multimodel_replicas(replicas) -> None:
    for r in replicas:
        for eng in r["engines"].values():
            try:
                eng.close(timeout_s=2.0)
            except Exception:
                pass
        try:
            r["server"].stop()
            r["server"].join()
        except Exception:
            pass
        for store in r["stores"].values():
            store.clear()
            store.close()


def spin_up_multimodel_cluster(n_replicas: int, models, *, layout=None,
                               page_tokens: int = 8,
                               step_delay_s=0.0,
                               commit_live_pages: bool = False,
                               replicate_sessions: bool = False,
                               max_sessions: int = 256,
                               timeout_ms: int = 20_000,
                               name_prefix: str = "mm", warm: bool = True,
                               wal=None, router_kw=None, **replica_kw):
    """A multi-model fleet behind one :class:`~brpc_tpu.serving.
    ClusterRouter` front door: :func:`spin_up_multimodel_replicas` plus
    a router whose handles carry the deployment tables (the catalog
    seeds instantly; remote publication keeps it fresh).  Returns
    ``(replicas, mults, router, rsrv, raddr)``; tear down with
    :func:`tear_down_multimodel_cluster`."""
    from brpc_tpu.serving import (ClusterRouter, ReplicaHandle,
                                  register_router)

    replicas, mults = spin_up_multimodel_replicas(
        n_replicas, models, layout=layout, page_tokens=page_tokens,
        step_delay_s=step_delay_s, commit_live_pages=commit_live_pages,
        name_prefix=name_prefix, warm=warm, **replica_kw)
    handles = []
    for i, r in enumerate(replicas):
        m0 = r["models"][0] if r["models"] else None
        handles.append(ReplicaHandle(
            r["addr"], name=f"{name_prefix}_{i}",
            engine=r["engines"].get(m0), store=r["stores"].get(m0),
            server=r["server"], deployments=r["deps"]))
    kw = dict(router_kw or {})
    if wal is not None:
        kw["wal"] = wal
    router = ClusterRouter(
        handles, page_tokens=page_tokens,
        replicate_sessions=replicate_sessions,
        max_sessions=max_sessions, name=f"{name_prefix}_router",
        timeout_ms=timeout_ms, **kw)
    rsrv = brpc.Server()
    register_router(rsrv, router)
    rsrv.start("127.0.0.1", 0)
    return replicas, mults, router, rsrv, f"127.0.0.1:{rsrv.port}"


def tear_down_multimodel_cluster(replicas, router, rsrv,
                                 timeout_s: float = 3.0) -> None:
    router.close(timeout_s=timeout_s)
    rsrv.stop()
    rsrv.join()
    tear_down_multimodel_replicas(replicas)


def zipf_key_sampler(vocab: int, s: float, seed: int = 0):
    """Seeded zipf-skewed key sampler: key k's probability is
    proportional to 1/(rank+1)^s under a seeded permutation (so hot
    keys spread across shard ranges instead of piling on shard 0).
    s=0 is uniform; s~1 is classic web skew."""
    import numpy as np
    rng = np.random.default_rng(seed)
    ranks = rng.permutation(vocab)
    p = 1.0 / np.power(np.arange(vocab, dtype=np.float64) + 1.0,
                       max(float(s), 0.0))
    p /= p.sum()
    probs = np.empty(vocab)
    probs[ranks] = p

    def sample(n: int) -> np.ndarray:
        return rng.choice(vocab, size=n, p=probs).astype(np.int64)

    return sample


def spin_up_psserve(n_shards: int, *, vocab: int = 1024, dim: int = 32,
                    max_delay_us: int = 1000, name_prefix: str = "press",
                    devices=None, table=None):
    """In-process sharded parameter-server fleet + a PartitionChannel
    over it (shared by --embedding mode and chip_smoke.py's PS phase).
    ``devices`` places shard i on ``devices[i]`` — one shard per chip;
    without it every shard's rows land on the first device.  ``table``
    is the full [vocab, dim] table the shards slice (default: each
    shard draws the seed-0 table itself)."""
    if devices is not None and len(devices) != n_shards:
        raise ValueError(f"{n_shards} shards need {n_shards} devices, "
                         f"got {len(devices)}")
    from brpc_tpu.psserve import EmbeddingShardServer, register_psserve
    from brpc_tpu.rpc.combo_channels import PartitionChannel
    from brpc_tpu.serving.telemetry import register_telemetry

    servers, svcs, shards = [], [], []
    pc = PartitionChannel(n_shards)
    for i in range(n_shards):
        sh = EmbeddingShardServer(
            i, n_shards, vocab, dim, seed=0, table=table,
            device=None if devices is None else devices[i],
            name=f"{name_prefix}_ps")
        shards.append(sh)
        s = brpc.Server()
        svcs.append(register_psserve(s, sh, max_delay_us=max_delay_us,
                                     name=f"{name_prefix}_{i}"))
        register_telemetry(s, name=f"{name_prefix}_ps_{i}")
        s.start("127.0.0.1", 0)
        servers.append(s)
        pc.add_partition(i, brpc.Channel(f"127.0.0.1:{s.port}",
                                         timeout_ms=10_000))
    return servers, svcs, shards, pc


def tear_down_psserve(servers, svcs, pc) -> None:
    from brpc_tpu.psserve import unregister_psserve
    for svc in svcs:
        unregister_psserve(svc)
    for s in servers:
        try:
            s.stop()
            s.join()
        except Exception:
            pass
    pc.close()


def run_embedding_press(n_shards: int, *, vocab: int = 1024,
                        dim: int = 32, zipf_s: float = 1.0,
                        update_ratio: float = 0.1,
                        key_counts=(4, 16, 64),
                        duration_s: float = 10.0, threads: int = 4,
                        serializer: str = "json",
                        out=sys.stderr) -> dict:
    """``--embedding N`` mode (ISSUE 12): zipf-skewed key load over an
    in-process N-shard parameter-server service through PSClient's
    PartitionChannel fan-out.  Reports lookups/s, updates/s, the
    update/lookup mix actually served, and latency p50/p99 BY KEY-COUNT
    BUCKET (small lookups shouldn't pay big lookups' padding), plus the
    shards' version/dup counters so exactly-once holds under load.

    ``--serializer json|tensorframe`` (ISSUE 13) picks the wire format
    and the report adds WIRE BYTES/REQUEST — request-direction bytes
    exact from the psserve_wire_bytes_* server counters, response bytes
    measured by re-encoding one received response per key-count bucket
    (byte-identical to what the server sent: both wires' encodes are
    deterministic) — so the binary-vs-JSON A/B is reproducible outside
    the bench."""
    import numpy as np

    from brpc_tpu.psserve import PSClient
    from brpc_tpu.psserve import service as ps_service
    from brpc_tpu.rpc.serialization import get_serializer

    if serializer not in ("json", "tensorframe"):
        raise ValueError("--serializer must be json|tensorframe")
    servers, svcs, shards, pc = spin_up_psserve(
        n_shards, vocab=vocab, dim=dim, name_prefix="press_ps")
    if serializer == "json":
        req0 = ps_service.REQUESTS_JSON.get_value()
        wb0 = ps_service.WIRE_BYTES_JSON.get_value()
    else:
        req0 = ps_service.REQUESTS_TENSORFRAME.get_value()
        wb0 = ps_service.WIRE_BYTES_TENSORFRAME.get_value()
    # one decoded response per (kind, key-count), re-encoded after the
    # run to measure exact response wire bytes
    resp_samples: dict = {}
    counts = {"lookups": 0, "updates": 0}
    lat_by_bucket: dict[int, list] = {k: [] for k in key_counts}
    mu = threading.Lock()
    stop_t = time.monotonic() + duration_s

    counts["errors"] = 0

    def worker(widx: int):
        rng = np.random.default_rng(1000 + widx)
        sample = zipf_key_sampler(vocab, zipf_s, seed=widx)
        cli = PSClient(pc, vocab=vocab, dim=dim,
                       serializer=serializer, ici="off",
                       name=f"press_cli_{widx}")
        ones = {k: np.ones((k, dim), np.float32) for k in key_counts}
        while time.monotonic() < stop_t:
            n = int(rng.choice(key_counts))
            keys = sample(n)
            t0 = time.monotonic()
            try:
                if rng.random() < update_ratio:
                    cli.update(keys, ones[n])
                    kind = "updates"
                else:
                    cli.lookup(keys)
                    kind = "lookups"
            except errors.RpcError:
                # an exhausted-retries failure under load is DATA, not
                # a reason to silently lose this worker for the rest
                # of the run (which would understate throughput with
                # no trace): count it and keep pressing
                with mu:
                    counts["errors"] += 1
                continue
            dt_us = (time.monotonic() - t0) * 1e6
            with mu:
                counts[kind] += 1
                lat_by_bucket[n].append(dt_us)
                if kind == "lookups" and n not in resp_samples:
                    # keep one keyset per bucket for the exact
                    # response-bytes re-encode after the run
                    resp_samples[n] = keys

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(threads)]
    t0 = time.monotonic()
    [t.start() for t in ts]
    [t.join(duration_s + 60) for t in ts]
    elapsed = time.monotonic() - t0
    try:
        by_bucket = {}
        for k, lats in lat_by_bucket.items():
            if not lats:
                continue
            a = np.asarray(lats)
            by_bucket[str(k)] = {
                "n": int(a.size),
                "p50_us": round(float(np.percentile(a, 50)), 1),
                "p99_us": round(float(np.percentile(a, 99)), 1),
            }
        # wire bytes/request (ISSUE 13): request direction exact from
        # the per-serializer server Adders; response direction measured
        # by re-encoding one REAL per-partition response per key-count
        # (both wires' encodes are deterministic, so these are the
        # bytes the server actually sent for that shape)
        if serializer == "json":
            req_d = ps_service.REQUESTS_JSON.get_value() - req0
            wb_d = ps_service.WIRE_BYTES_JSON.get_value() - wb0
        else:
            req_d = ps_service.REQUESTS_TENSORFRAME.get_value() - req0
            wb_d = ps_service.WIRE_BYTES_TENSORFRAME.get_value() - wb0
        ser_obj = get_serializer(serializer)
        from brpc_tpu.psserve.shard import owners_for, shard_bounds
        bounds = shard_bounds(vocab, n_shards)
        resp_bytes = {}
        for k, keys in sorted(resp_samples.items()):
            owner = owners_for(keys, bounds)
            total_b = 0
            for part in np.unique(owner):
                pos = np.flatnonzero(owner == part)
                sub = keys[pos]
                # rows straight off the table snapshot — NOT
                # shard.lookup, which would pollute the hot-key
                # histogram and lookup counters the summary reports
                # with synthetic probe traffic
                sh = shards[int(part)]
                rows = sh.snapshot_rows()[sub - sh.lo]
                # the shard's REAL version: JSON response size varies
                # with its digit count, and the probe's claim is
                # byte-identical re-encoding
                if serializer == "json":
                    obj = {"rows": rows.tolist(),
                           "version": int(sh.version)}
                else:
                    obj = {"rows": np.ascontiguousarray(rows),
                           "version": int(sh.version)}
                total_b += len(ser_obj.encode(obj)[0])
            resp_bytes[str(k)] = int(total_b)
        total = counts["lookups"] + counts["updates"]
        summary = {
            "mode": "embedding",
            "shards": n_shards, "vocab": vocab, "dim": dim,
            "zipf_s": zipf_s,
            "serializer": serializer,
            "wire": {
                "req_bytes_per_call": round(wb_d / req_d, 1)
                if req_d else 0.0,
                "requests": int(req_d),
                "lookup_resp_bytes_by_key_count": resp_bytes,
            },
            "lookups_per_s": round(counts["lookups"] / elapsed, 1),
            "updates_per_s": round(counts["updates"] / elapsed, 1),
            "update_mix": round(counts["updates"] / total, 3)
            if total else 0.0,
            "errors": counts["errors"],
            "latency_by_key_count": by_bucket,
            "shard_versions": [sh.version for sh in shards],
            "dup_updates": sum(sh.n_dup_updates for sh in shards),
            "hot_keys": shards[0].hot_keys(5),
            "elapsed_s": round(elapsed, 2),
        }
        print(json.dumps(summary), file=out)
        return summary
    finally:
        tear_down_psserve(servers, svcs, pc)


def run_mixed_press(shapes, *, weights=None, n_shards: int = 2,
                    vocab: int = 128, dim: int = 16,
                    gen_tokens: int = 16, train_steps: int = 8,
                    duration_s: float = 10.0, seed: int = 0,
                    out=sys.stderr) -> dict:
    """``--mixed lookup,generate,train`` (ISSUE 17): ONE in-process
    fleet serving every requested traffic shape SIMULTANEOUSLY — zipf
    PS lookups, streamed generations, trainer update waves — with the
    :class:`~brpc_tpu.train.TrafficArbiter` arbitrating across shapes.
    ``weights`` scales worker counts per shape (matching the shape
    list's order; default 1 each).  The report prints per-shape qps
    and latency percentiles plus the arbiter ladder's fire counters —
    escalations and first-fired ticks per named rung — so the
    cheapest-first ordering (trainer paced/shed BEFORE any serving
    rung) is visible from the command line."""
    from brpc_tpu.train.arbiter import MixedWorkloadHarness
    shapes = [s.strip() for s in shapes if s.strip()]
    known = ("lookup", "generate", "train")
    bad = [s for s in shapes if s not in known]
    if bad:
        raise ValueError(f"unknown shapes {bad}; pick from {known}")
    if not shapes:
        raise ValueError("--mixed needs at least one shape")
    w = {s: 1 for s in shapes}
    for s, n in zip(shapes, weights or []):
        w[s] = int(n)
    h = MixedWorkloadHarness(
        n_shards=n_shards, vocab=vocab, dim=dim,
        lookup_workers=w.get("lookup", 0),
        gen_workers=w.get("generate", 0), gen_tokens=gen_tokens,
        train_workers=w.get("train", 0),
        train_steps=train_steps if "train" in w else 0,
        min_duration_s=duration_s, seed=seed, name="mixed_press")
    try:
        rep = h.run()
    finally:
        h.close()

    def ms(v):
        return "-" if v is None else f"{v / 1000.0:.2f}ms"

    print(f"--- mixed press: {'+'.join(shapes)} over {n_shards} PS "
          f"shards, {rep['elapsed_s']:.1f}s ---", file=out)
    for name in ("lookup", "generate"):
        st = rep["shapes"][name]
        if not (st["ok"] or st["err"]):
            continue
        extra = ""
        if name == "generate":
            extra = (f"  bit_exact={st['bit_exact']}/"
                     f"{st['ok']}")
        print(f"{name:>9}: {st['qps']:8.1f} qps  "
              f"p50 {ms(st['p50_us'])}  p99 {ms(st['p99_us'])}  "
              f"errors {st['err']}{extra}", file=out)
    tr = rep["train"]
    if tr["waves"]:
        print(f"{'train':>9}: {tr['updates_per_s']:8.1f} waves/s  "
              f"waves {tr['waves']}  retries {tr['wave_retries']}  "
              f"paced {tr['paced_waves']}  "
              f"loss {tr['loss_first']:.4f} -> {tr['loss_final']:.4f}",
              file=out)
    lad = rep["arbiter"]["ladder"]
    print("ladder fire counts (cheapest first):", file=out)
    for i, name in enumerate(lad["level_names"]):
        print(f"  L{i + 1} {name:<18} escalations "
              f"{lad['escalations'][i]:<4} first_fired "
              f"{lad['first_fired'][i]}", file=out)
    print(f"arbiter: admitted {rep['arbiter']['admitted_waves']}  "
          f"paced {rep['arbiter']['paced_waves']}  "
          f"shed {rep['arbiter']['shed_waves']}", file=out)
    print(f"invariants: exactly_once={all(rep['exactly_once'])}  "
          f"stale_reads={rep['stale_reads']}  "
          f"queues_drained={rep['queues_drained']}  "
          f"pools_at_baseline={rep['pools_at_baseline']}", file=out)
    return rep


def run_cluster_press(n_replicas: int, request,
                      duration_s: float = 10.0, threads: int = 4,
                      timeout_ms: int = 20_000, request_factory=None,
                      kill_replica_after: float | None = None,
                      slo: bool = False,
                      out=sys.stderr) -> dict:
    """``--cluster N`` mode: spin up N in-process serving replicas
    behind a :class:`~brpc_tpu.serving.ClusterRouter` and press full
    generations through the front door — ROADMAP item 3's "heavy
    traffic" scenario driver.  Reports generations/s, tokens/s,
    time-to-first-token percentiles, the RESUME count (replica
    failovers ridden by sessions), and the overload gradient's
    per-level shed counts.  ``kill_replica_after=S`` kills one replica
    mid-run so the resume path runs under load.  CPU-valid: the step
    function is plain numpy."""
    from brpc_tpu.serving import (ClusterRouter, ReplicaHandle,
                                  RouterClient, register_router)

    # live pages committed and sessions replicated, so that a replica
    # kill mid-run exercises resume
    replicas = spin_up_replicas(n_replicas, page_tokens=8,
                                name_prefix="press_cl",
                                commit_live_pages=True)
    router = ClusterRouter(
        [ReplicaHandle(addr, name=f"press_cl_{i}", engine=eng,
                       store=store, server=srv)
         for i, (store, eng, srv, addr) in enumerate(replicas)],
        page_tokens=8, replicate_sessions=True,
        max_sessions=max(64, 8 * threads), name="press_cl_router",
        timeout_ms=timeout_ms)
    rsrv = brpc.Server()
    register_router(rsrv, router)
    rsrv.start("127.0.0.1", 0)
    raddr = f"127.0.0.1:{rsrv.port}"
    if slo:
        # --slo (ISSUE 20): observe-only burn-rate evaluation riding
        # the collector ticks — a single-model press has no canary
        # pair to re-weight, so verdicts report, never act
        from brpc_tpu.serving import Objective, SLOEngine
        from brpc_tpu.serving.modelplane import DEFAULT_MODEL
        router.attach_slo(SLOEngine(
            DEFAULT_MODEL, DEFAULT_MODEL, DEFAULT_MODEL,
            [Objective("ttft_p99_ms", 500.0),
             Objective("itl_p99_ms", 50.0),
             Objective("error_rate", 0.05)],
            short_window_s=1.0, long_window_s=3.0, act=False))

    rec_ttft = LatencyRecorder("rpc_press_cluster_ttft")
    mu = threading.Lock()
    gens_ok = [0]
    nerr = [0]
    nshed = [0]
    tokens = [0]
    stop = threading.Event()

    def worker(k: int):
        cli = RouterClient(raddr, timeout_ms=timeout_ms)
        gen = request_factory(k) if request_factory is not None else None
        while not stop.is_set():
            req = gen() if gen is not None else request
            prompt = req.get("prompt") or [1]
            n = int(req.get("max_new_tokens", 16))
            first = [None]

            def emit(tok, first=first):
                if first[0] is None:
                    first[0] = time.monotonic()

            t0 = time.monotonic()
            try:
                res = cli.generate(prompt, n, emit=emit,
                                   timeout_s=timeout_ms / 1e3)
            except brpc.RpcError as e:
                with mu:
                    if e.code == brpc.errors.ELIMIT:
                        nshed[0] += 1   # shed-at-router, by design
                    else:
                        nerr[0] += 1
                continue
            except Exception:
                with mu:
                    nerr[0] += 1
                continue
            with mu:
                if res["error"]:
                    nerr[0] += 1
                    continue
                gens_ok[0] += 1
                tokens[0] += len(res["tokens"])
            if first[0] is not None:
                rec_ttft.add(int((first[0] - t0) * 1e6))

    ts = [threading.Thread(target=worker, args=(k,), daemon=True)
          for k in range(threads)]
    t_start = time.monotonic()
    [t.start() for t in ts]
    try:
        if kill_replica_after is not None and \
                kill_replica_after < duration_s:
            time.sleep(kill_replica_after)
            _store, keng, ksrv, kaddr = replicas[0]
            print(f"cluster press: killing replica {kaddr}",
                  file=sys.stderr)
            ksrv.stop()
            ksrv.join()
            keng.close(timeout_s=2.0)
            time.sleep(max(0.0, duration_s - kill_replica_after))
        else:
            time.sleep(duration_s)
    finally:
        stop.set()
    [t.join(timeout_ms / 1e3 + 2) for t in ts]
    elapsed = time.monotonic() - t_start
    rstats = router.stats()
    summary = {
        "replicas": n_replicas,
        "generations_ok": gens_ok[0],
        "errors": nerr[0],
        "client_sheds": nshed[0],
        "tokens": tokens[0],
        "generations_per_s": round(gens_ok[0] / elapsed, 1),
        "tokens_per_s": round(tokens[0] / elapsed, 1),
        "ttft_avg_us": round(rec_ttft.latency(), 1),
        "ttft_p50_us": rec_ttft.latency_percentile(0.5),
        "ttft_p90_us": rec_ttft.latency_percentile(0.9),
        "ttft_p99_us": rec_ttft.latency_percentile(0.99),
        "resumes": rstats["resumes"],
        "shed_counts": rstats["gradient_fired"],
        "router_level": rstats["ladder"]["level"],
        "elapsed_s": round(elapsed, 2),
    }
    tel = rstats.get("telemetry") or {}
    summary["telemetry"] = {k: tel.get(k, 0) for k in
                            ("pulls", "pull_bytes", "pull_errors",
                             "tombstones")}
    if slo and rstats.get("slo"):
        s = rstats["slo"]
        can = (s.get("last_eval") or {}).get("canary") or {}
        summary["slo"] = {
            "verdict": can.get("verdict"),
            "burns": can.get("burns"),
            "floor": s.get("floor"),
            "evaluations": s.get("evaluations"),
        }
        print("--- slo (observe-only burn rates) ---", file=sys.stderr)
        print(f"verdict={can.get('verdict')} floor={s.get('floor')} "
              f"evaluations={s.get('evaluations')}", file=sys.stderr)
        for met, b in sorted((can.get("burns") or {}).items()):
            print(f"  {met}: target={b.get('target')} "
                  f"burn_short={b.get('short')} "
                  f"burn_long={b.get('long')}"
                  + (" BURNING" if b.get("burning") else ""),
                  file=sys.stderr)
    print(json.dumps(summary), file=out)
    router.close(timeout_s=3.0)
    rsrv.stop()
    rsrv.join()
    tear_down_replicas(replicas)
    return summary


def run_multimodel_press(n_replicas: int, models,
                         duration_s: float = 10.0, threads: int = 4,
                         max_new_tokens: int = 12,
                         timeout_ms: int = 20_000,
                         out=sys.stderr) -> dict:
    """``--cluster N --models a,b[,c]`` mode (ISSUE 18): a multi-model
    fleet behind one router front door, workers alternating models per
    request.  Every finished stream is checked against ITS model's
    bit-exact oracle; a stream matching a DIFFERENT model's oracle is
    a wrong-model route.  The report carries per-model generations/s +
    TTFT percentiles and the wrong-model-route count — which must be 0
    (three independent witnesses: client oracles, the router's
    ``wrong_model_routes`` counter, the replicas' ``n_model_misroutes``
    counters)."""
    import random

    from brpc_tpu.serving import RouterClient

    models = [str(m) for m in models]
    replicas, mults, router, rsrv, raddr = spin_up_multimodel_cluster(
        n_replicas, models, commit_live_pages=True,
        replicate_sessions=True, max_sessions=max(64, 8 * threads),
        name_prefix="press_mm", timeout_ms=timeout_ms)

    mu = threading.Lock()
    per = {m: {"ok": 0, "err": 0, "sheds": 0, "tokens": 0,
               "mismatches": 0,
               "rec": LatencyRecorder(f"rpc_press_mm_ttft_{i}")}
           for i, m in enumerate(models)}
    wrong_route = [0]
    stop = threading.Event()

    def worker(k: int):
        cli = RouterClient(raddr, timeout_ms=timeout_ms)
        rng = random.Random(1000 + k)
        j = 0
        while not stop.is_set():
            m = models[(k + j) % len(models)]
            j += 1
            st = per[m]
            prompt = [rng.randrange(1, 97)]
            first = [None]

            def emit(tok, first=first):
                if first[0] is None:
                    first[0] = time.monotonic()

            t0 = time.monotonic()
            try:
                res = cli.generate(prompt, max_new_tokens, emit=emit,
                                   timeout_s=timeout_ms / 1e3, model=m)
            except brpc.RpcError as e:
                with mu:
                    if e.code == brpc.errors.ELIMIT:
                        st["sheds"] += 1
                    else:
                        st["err"] += 1
                continue
            except Exception:
                with mu:
                    st["err"] += 1
                continue
            with mu:
                if res["error"]:
                    st["err"] += 1
                    continue
                st["ok"] += 1
                st["tokens"] += len(res["tokens"])
                exp = expected_model_tokens(prompt, len(res["tokens"]),
                                            mults[m])
                if res["tokens"] != exp:
                    st["mismatches"] += 1
                    if any(res["tokens"] == expected_model_tokens(
                            prompt, len(res["tokens"]), mm)
                           for mo, mm in mults.items() if mo != m):
                        wrong_route[0] += 1
            if first[0] is not None:
                st["rec"].add(int((first[0] - t0) * 1e6))

    ts = [threading.Thread(target=worker, args=(k,), daemon=True)
          for k in range(threads)]
    t_start = time.monotonic()
    [t.start() for t in ts]
    try:
        time.sleep(duration_s)
    finally:
        stop.set()
    [t.join(timeout_ms / 1e3 + 2) for t in ts]
    elapsed = time.monotonic() - t_start
    rstats = router.stats()
    misroutes = sum(r["serving"].n_model_misroutes for r in replicas)
    summary = {
        "replicas": n_replicas,
        "models": {},
        "wrong_model_routes": (wrong_route[0]
                               + int(rstats["wrong_model_routes"])
                               + misroutes),
        "elapsed_s": round(elapsed, 2),
    }
    for m in models:
        st = per[m]
        rec = st["rec"]
        summary["models"][m] = {
            "generations_ok": st["ok"],
            "errors": st["err"],
            "client_sheds": st["sheds"],
            "mismatches": st["mismatches"],
            "generations_per_s": round(st["ok"] / elapsed, 1),
            "tokens_per_s": round(st["tokens"] / elapsed, 1),
            "ttft_p50_us": rec.latency_percentile(0.5),
            "ttft_p90_us": rec.latency_percentile(0.9),
            "ttft_p99_us": rec.latency_percentile(0.99),
        }
    print(json.dumps(summary), file=out)
    tear_down_multimodel_cluster(replicas, router, rsrv)
    return summary


def run_router_kill_press(n_replicas: int, request,
                          duration_s: float = 10.0, threads: int = 4,
                          kill_router_after: float = 3.0,
                          timeout_ms: int = 20_000,
                          request_factory=None,
                          out=sys.stderr) -> dict:
    """``--cluster N --kill-router-after S`` mode (ISSUE 16): the
    replicas stay in-process but the ROUTER runs as its own OS process
    over a session WAL.  S seconds in, the harness SIGKILLs it — no
    goodbye, no flush beyond the WAL's write-ahead discipline — and
    spawns a successor over the same WAL file.  Every generation that
    was mid-flight resumes against the successor from its client-held
    cursor; the report adds the resume count and resume-latency
    percentiles (client resume call -> generation complete) next to
    the usual press numbers."""
    import os
    import tempfile

    from brpc_tpu.serving import RouterClient
    from brpc_tpu.serving.router_proc import spawn_router

    replicas = spin_up_replicas(
        n_replicas, page_tokens=8, commit_live_pages=True,
        step_delay_s=0.002, name_prefix="press_kr")
    addrs = [addr for _, _, _, addr in replicas]
    wal_dir = tempfile.mkdtemp(prefix="rpc_press_wal_")
    wal_path = os.path.join(wal_dir, "sessions.wal")
    proc, raddr = spawn_router(
        wal_path, addrs, replicate_sessions=True, replication_factor=2,
        page_tokens=8, max_sessions=max(64, 8 * threads),
        timeout_ms=timeout_ms)

    rec_ttft = LatencyRecorder("rpc_press_krouter_ttft")
    rec_resume = LatencyRecorder("rpc_press_krouter_resume")
    mu = threading.Lock()
    gens_ok = [0]
    nerr = [0]
    nshed = [0]
    tokens = [0]
    resumes = [0]
    stop = threading.Event()
    router_up = threading.Event()
    router_up.set()
    cur_addr = [raddr]

    def worker(k: int):
        gen_req = request_factory(k) if request_factory is not None \
            else None
        while not stop.is_set():
            router_up.wait(1.0)
            if stop.is_set():
                return
            addr = cur_addr[0]
            cli = RouterClient(addr, timeout_ms=timeout_ms,
                               shed_retries=0)
            req = gen_req() if gen_req is not None else request
            prompt = req.get("prompt") or [1]
            n = int(req.get("max_new_tokens", 16))
            first = [None]

            def emit(tok, first=first):
                if first[0] is None:
                    first[0] = time.monotonic()

            t0 = time.monotonic()
            try:
                live = cli.start(prompt, n, emit=emit)
            except brpc.RpcError as e:
                with mu:
                    if e.code == brpc.errors.ELIMIT:
                        nshed[0] += 1
                    else:
                        nerr[0] += 1
                continue
            except Exception:
                with mu:
                    nerr[0] += 1
                continue
            done = live.wait(timeout_ms / 1e3)
            if done and live.error is None:
                with mu:
                    gens_ok[0] += 1
                    tokens[0] += len(live.tokens)
                if first[0] is not None:
                    rec_ttft.add(int((first[0] - t0) * 1e6))
                continue
            # mid-flight router death (or wedge): resume the SESSION on
            # whatever router holds the WAL now, from the client-held
            # cursor — the durable-control-plane acceptance path
            sid, cursor = live.session_id, live.cursor
            try:
                live.drop()
            except Exception:
                pass
            if not sid or stop.is_set():
                with mu:
                    nerr[0] += 1
                continue
            router_up.wait(timeout_ms / 1e3)
            r0 = time.monotonic()
            try:
                res = RouterClient(cur_addr[0], timeout_ms=timeout_ms,
                                   shed_retries=0).resume_wait(
                    sid, cursor, timeout_s=timeout_ms / 1e3)
            except Exception:
                with mu:
                    nerr[0] += 1
                continue
            rec_resume.add(int((time.monotonic() - r0) * 1e6))
            with mu:
                resumes[0] += 1
                if res["error"]:
                    nerr[0] += 1
                else:
                    gens_ok[0] += 1
                    tokens[0] += len(res["tokens"]) + cursor

    ts = [threading.Thread(target=worker, args=(k,), daemon=True)
          for k in range(threads)]
    t_start = time.monotonic()
    [t.start() for t in ts]
    adoption_ms = None
    replay = None
    try:
        time.sleep(min(kill_router_after, duration_s))
        print(f"cluster press: SIGKILL router pid={proc.pid}",
              file=sys.stderr)
        router_up.clear()
        k0 = time.monotonic()
        proc.kill()
        proc.wait()
        proc2, raddr2 = spawn_router(
            wal_path, addrs, replicate_sessions=True,
            replication_factor=2, page_tokens=8,
            max_sessions=max(64, 8 * threads), timeout_ms=timeout_ms)
        adoption_ms = round((time.monotonic() - k0) * 1e3, 1)
        cur_addr[0] = raddr2
        proc = proc2
        router_up.set()
        time.sleep(max(0.0, duration_s - kill_router_after))
    finally:
        stop.set()
        router_up.set()
    [t.join(timeout_ms / 1e3 + 2) for t in ts]
    elapsed = time.monotonic() - t_start
    try:
        from brpc_tpu.rpc.channel import Channel
        replay = Channel(cur_addr[0], timeout_ms=5000).call_sync(
            "Router", "Stats", {}, serializer="json",
            response_serializer="json").get("wal_replay")
    except Exception:
        replay = None
    summary = {
        "replicas": n_replicas,
        "generations_ok": gens_ok[0],
        "errors": nerr[0],
        "client_sheds": nshed[0],
        "tokens": tokens[0],
        "generations_per_s": round(gens_ok[0] / elapsed, 1),
        "tokens_per_s": round(tokens[0] / elapsed, 1),
        "ttft_avg_us": round(rec_ttft.latency(), 1),
        "ttft_p50_us": rec_ttft.latency_percentile(0.5),
        "ttft_p99_us": rec_ttft.latency_percentile(0.99),
        "router_resumes": resumes[0],
        "resume_p50_us": rec_resume.latency_percentile(0.5),
        "resume_p90_us": rec_resume.latency_percentile(0.9),
        "resume_p99_us": rec_resume.latency_percentile(0.99),
        "router_adoption_ms": adoption_ms,
        "wal_replay": replay,
        "elapsed_s": round(elapsed, 2),
    }
    print(json.dumps(summary), file=out)
    try:
        proc.kill()
        proc.wait()
    except Exception:
        pass
    tear_down_replicas(replicas)
    try:
        os.unlink(wal_path)
        os.rmdir(wal_dir)
    except OSError:
        pass
    return summary


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--server", help="host:port (unary/streaming modes)")
    ap.add_argument("--service")
    ap.add_argument("--method")
    ap.add_argument("--cluster", type=int, default=0, metavar="N",
                    help="spin up N in-process serving replicas behind "
                         "a ClusterRouter and press generations "
                         "through the front door (generations/s, TTFT "
                         "percentiles, resume count, per-level shed "
                         "counts)")
    ap.add_argument("--models", metavar="A,B[,C]",
                    help="with --cluster: serve a comma list of named "
                         "model deployments on every replica and press "
                         "them through one router front door; reports "
                         "per-model generations/s + TTFT percentiles "
                         "and the wrong-model-route count (must be 0)")
    ap.add_argument("--kill-replica-after", type=float, default=None,
                    metavar="S",
                    help="with --cluster: kill one replica S seconds "
                         "into the run so session resume runs under "
                         "load")
    ap.add_argument("--slo", action="store_true",
                    help="with --cluster: attach an observe-only SLO "
                         "burn-rate engine to the router and print its "
                         "verdict/burn summary block (ISSUE 20)")
    ap.add_argument("--kill-router-after", type=float, default=None,
                    metavar="S",
                    help="with --cluster: run the router as its own OS "
                         "process over a session WAL, SIGKILL it S "
                         "seconds in, spawn a successor over the same "
                         "WAL, and resume every mid-flight session "
                         "(reports resume count + resume-latency "
                         "percentiles)")
    ap.add_argument("--embedding", type=int, default=0, metavar="N",
                    help="spin up N in-process parameter-server shards "
                         "and press zipf-skewed Lookup/Update key load "
                         "through PSClient's PartitionChannel fan-out "
                         "(lookups/s, update mix, p99 by key-count "
                         "bucket)")
    ap.add_argument("--zipf", type=float, default=1.0, metavar="S",
                    help="with --embedding: zipf skew exponent for the "
                         "key distribution (0 = uniform)")
    ap.add_argument("--update-ratio", type=float, default=0.1,
                    help="with --embedding: fraction of requests that "
                         "are sparse Updates instead of Lookups")
    ap.add_argument("--vocab", type=int, default=1024,
                    help="with --embedding: embedding table rows")
    ap.add_argument("--dim", type=int, default=32,
                    help="with --embedding: embedding row width")
    ap.add_argument("--mixed", metavar="SHAPES",
                    help="comma list from lookup,generate,train: one "
                         "in-process fleet serving every shape at "
                         "once, TrafficArbiter arbitrating; reports "
                         "per-shape qps/p99 + ladder fire counts "
                         "(ISSUE 17)")
    ap.add_argument("--mixed-weights", metavar="W",
                    help="comma worker weights matching --mixed order "
                         "(default 1 each)")
    ap.add_argument("--shards", type=int, default=2,
                    help="--mixed: PS shard count")
    ap.add_argument("--train-steps", type=int, default=8,
                    help="--mixed: trainer steps per worker")
    ap.add_argument("--disagg", metavar="PREFILL_ADDR,DECODE_ADDR",
                    help="drive a disaggregated prefill/decode split: "
                         "each call runs DisaggPrefill.Prefill on the "
                         "first address (pages stream to the decode "
                         "store) then streams Serving.Generate tokens "
                         "from the second; reports generations/s, "
                         "tokens/s and TTFT percentiles")
    ap.add_argument("--input", default="{}",
                    help="JSON request body, or @file.json")
    ap.add_argument("--qps", type=int, default=0,
                    help="0 = unthrottled (unary mode only)")
    ap.add_argument("--duration", type=float, default=10.0)
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--timeout-ms", type=int, default=1000)
    ap.add_argument("--serializer", default="json",
                    help="request serializer; with --embedding: "
                         "json|tensorframe picks the PS wire format "
                         "and the report adds wire bytes/request")
    ap.add_argument("--connection-type", default="single",
                    choices=["single", "pooled", "short"])
    ap.add_argument("--streaming", action="store_true",
                    help="drive a streaming method: attach a client "
                         "stream per call, report items/s and "
                         "time-to-first-item percentiles")
    ap.add_argument("--shared-prefix-ratio", type=float, default=0.0,
                    help="regenerate each call's \"prompt\" field: with "
                         "this probability it opens with one fixed "
                         "shared prefix (prefix-skewed KV-cache load); "
                         "0 disables")
    ap.add_argument("--prefix-tokens", type=int, default=32,
                    help="shared-prefix length for --shared-prefix-ratio")
    ap.add_argument("--prefix-seed", type=int, default=0,
                    help="seed for the prefix-skew schedule")
    ap.add_argument("--dump-traces", type=int, default=0,
                    help="enable rpcz for the run and print the N "
                         "slowest traces as indented timelines after "
                         "the summary; 0 disables")
    ap.add_argument("--hotspots", type=int, default=0,
                    help="burst-profile the SERVER for the press "
                         "duration (/hotspots?seconds=) and print its "
                         "top-N stage-tagged folded stacks alongside "
                         "the latency report; 0 disables")
    a = ap.parse_args(argv)
    if a.mixed:
        weights = [int(x) for x in a.mixed_weights.split(",")] \
            if a.mixed_weights else None
        run_mixed_press(a.mixed.split(","), weights=weights,
                        n_shards=a.shards, vocab=a.vocab, dim=a.dim,
                        train_steps=a.train_steps,
                        duration_s=a.duration, out=sys.stdout)
        return
    if a.embedding:
        run_embedding_press(a.embedding, vocab=a.vocab, dim=a.dim,
                            serializer=a.serializer,
                            zipf_s=a.zipf, update_ratio=a.update_ratio,
                            duration_s=a.duration, threads=a.threads,
                            out=sys.stdout)
        return
    if a.disagg is None and not a.cluster:
        missing = [n for n, v in (("--server", a.server),
                                  ("--service", a.service),
                                  ("--method", a.method)) if not v]
        if missing:
            ap.error(f"{', '.join(missing)} required "
                     f"(unless --disagg or --cluster is used)")
    text = a.input
    if text.startswith("@"):
        with open(text[1:]) as f:
            text = f.read()
    req = json.loads(text)
    factory = None
    if a.shared_prefix_ratio > 0:
        factory = make_prefix_skew(req, a.shared_prefix_ratio,
                                   prefix_tokens=a.prefix_tokens,
                                   seed=a.prefix_seed)
    if a.cluster and a.models:
        run_multimodel_press(
            a.cluster, [m for m in a.models.split(",") if m],
            duration_s=a.duration, threads=a.threads,
            timeout_ms=max(a.timeout_ms, 5000), out=sys.stdout)
    elif a.cluster and a.kill_router_after is not None:
        run_router_kill_press(a.cluster, req, duration_s=a.duration,
                              threads=a.threads,
                              kill_router_after=a.kill_router_after,
                              timeout_ms=max(a.timeout_ms, 5000),
                              request_factory=factory, out=sys.stdout)
    elif a.cluster:
        run_cluster_press(a.cluster, req, duration_s=a.duration,
                          threads=a.threads,
                          timeout_ms=max(a.timeout_ms, 5000),
                          request_factory=factory,
                          kill_replica_after=a.kill_replica_after,
                          slo=a.slo,
                          out=sys.stdout)
    elif a.disagg:
        try:
            prefill_addr, decode_addr = a.disagg.split(",", 1)
        except ValueError:
            ap.error("--disagg needs PREFILL_ADDR,DECODE_ADDR")
        run_disagg_press(prefill_addr.strip(), decode_addr.strip(), req,
                         duration_s=a.duration, threads=a.threads,
                         timeout_ms=max(a.timeout_ms, 5000),
                         request_factory=factory, out=sys.stdout)
    elif a.streaming:
        run_streaming_press(a.server, a.service, a.method, req,
                            duration_s=a.duration, threads=a.threads,
                            serializer=a.serializer,
                            timeout_ms=a.timeout_ms,
                            connection_type=a.connection_type,
                            request_factory=factory,
                            dump_traces=a.dump_traces,
                            hotspots=a.hotspots,
                            out=sys.stdout)
    else:
        run_press(a.server, a.service, a.method, req, qps=a.qps,
                  duration_s=a.duration, threads=a.threads,
                  serializer=a.serializer, timeout_ms=a.timeout_ms,
                  connection_type=a.connection_type,
                  request_factory=factory, dump_traces=a.dump_traces,
                  hotspots=a.hotspots, out=sys.stdout)


if __name__ == "__main__":
    main()

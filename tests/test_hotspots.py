"""Host hot-path attribution tests (ISSUE 6): always-on sampling
profiler (+ its <2% overhead claim), lock-contention ledger, per-stage
host-CPU accounting, /brpc_metrics exposition hygiene, the /hotspots
console pages."""
import io
import json
import re
import threading
import time

import numpy as np
import pytest

import brpc_tpu as brpc


# ---------------------------------------------------------------------------
# stage tagging
# ---------------------------------------------------------------------------

def test_stagetag_explicit_override_nests_and_restores():
    from brpc_tpu.butil import stagetag
    base = stagetag.current_stage()
    with stagetag.stage("prefill"):
        assert stagetag.current_stage() == "prefill"
        with stagetag.stage("decode_step"):
            assert stagetag.current_stage() == "decode_step"
        assert stagetag.current_stage() == "prefill"
    assert stagetag.current_stage() == base


def test_stagetag_thread_name_map():
    from brpc_tpu.butil import stagetag
    assert stagetag.stage_of(0, "serving-batcher-x") == "batch_formation"
    assert stagetag.stage_of(0, "serving-emit-42") == "emit_fanout"
    assert stagetag.stage_of(0, "serving-emit-drain-engine") == "emit_fanout"
    assert stagetag.stage_of(0, "bvar-collector") == "span_submit"
    assert stagetag.stage_of(0, "Dummy-3") == "frame_pump"
    assert stagetag.stage_of(0, "nonsense") == "other"


# ---------------------------------------------------------------------------
# lock-contention ledger
# ---------------------------------------------------------------------------

def test_instrumented_lock_records_wait_hold_and_holder_stage():
    from brpc_tpu.butil.lockprof import InstrumentedLock
    lk = InstrumentedLock("test.unit_lock")
    st = lk.stats
    a0 = st.acquisitions.get_value()
    c0 = st.contentions.get_value()
    w0 = st.wait_rec.count()
    with lk:
        pass
    assert st.acquisitions.get_value() == a0 + 1
    assert st.contentions.get_value() == c0
    # forced contention: a holder naps while a second thread acquires
    entered = threading.Event()

    def holder():
        with lk:
            entered.set()
            time.sleep(0.05)

    t = threading.Thread(target=holder)
    t.start()
    assert entered.wait(5)
    t0 = time.monotonic()
    with lk:
        waited = time.monotonic() - t0
    t.join(5)
    assert waited > 0.02
    assert st.contentions.get_value() == c0 + 1
    assert st.wait_rec.count() == w0 + 1
    assert st.wait_rec.max_latency() >= 20_000   # >= 20ms recorded
    # hold time of the napping holder was recorded, and the last
    # holder's stage resolved (MainThread -> "main")
    assert st.hold_rec.max_latency() >= 40_000
    assert st.last_holder_stage == "main"
    snap = st.snapshot()
    assert snap["contention_ratio"] > 0
    assert snap["last_holder_stage"] == "main"


def test_instrumented_lock_nonblocking_and_reentrant():
    from brpc_tpu.butil.lockprof import InstrumentedLock
    lk = InstrumentedLock("test.unit_lock_nb")
    assert lk.acquire(blocking=False)
    got = []
    t = threading.Thread(
        target=lambda: got.append(lk.acquire(blocking=False)))
    t.start()
    t.join(5)
    assert got == [False]
    lk.release()
    # reentrant wrapper over an RLock: one ledger acquisition for the
    # OUTERMOST hold, inner re-acquires are free
    rlk = InstrumentedLock("test.unit_rlock", threading.RLock())
    a0 = rlk.stats.acquisitions.get_value()
    with rlk:
        with rlk:
            pass
    assert rlk.stats.acquisitions.get_value() == a0 + 1


def test_instrumented_lock_backs_a_condition():
    """The Condition protocol (wait/notify over the wrapper) stays
    correct — this is exactly how the batcher/engine use it."""
    from brpc_tpu.butil.lockprof import InstrumentedLock
    cv = threading.Condition(InstrumentedLock("test.unit_cv"))
    state = []

    def waiter():
        with cv:
            while not state:
                if not cv.wait(5):
                    return
            state.append("seen")

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    with cv:
        state.append("go")
        cv.notify()
    t.join(5)
    assert state == ["go", "seen"]
    # a timed wait that expires must also restore the lock cleanly
    with cv:
        assert not cv.wait(0.01)
    assert cv._lock.acquire(blocking=False)
    cv._lock.release()


def test_named_hot_locks_populate_ledger():
    """Exercising batcher/store/engine/rpcz lands rows for every named
    hot lock in locks_snapshot().  Runs with the native hot path OFF:
    the ledger's serving.emit_buf row belongs to the pure-Python
    _EmitBuf fallback — the native emit ring (ISSUE 9) has no Python
    lock to ledger, which is the point of the rewrite."""
    from brpc_tpu import flags, rpcz
    from brpc_tpu.butil.lockprof import locks_snapshot
    from brpc_tpu.kvcache import KVCacheStore
    from brpc_tpu.serving import DecodeEngine, DynamicBatcher

    b = DynamicBatcher(lambda x: x.sum(axis=1), max_batch_size=4,
                       max_delay_us=300, batch_buckets=(4,),
                       length_buckets=(8,), name="ledger_probe")
    store = KVCacheStore(page_tokens=4, page_bytes=256, max_blocks=16,
                         name="ledger_probe")
    eng = DecodeEngine(lambda t, p: t + 1, num_slots=2, store=store,
                       pass_page_table=False, name="ledger_probe")
    was = (rpcz.enabled(), rpcz.sample_rate())
    rpcz.set_enabled(True, 1.0)
    # flag flipped AFTER the constructors (the flag is read per
    # request/batch, not at construction) so a constructor exception
    # cannot strand the session on the python fallback
    was_native = flags.get_flag("native_hot_path_enabled", True)
    flags.set_flag("native_hot_path_enabled", False)
    try:
        b.submit_wait(np.ones(8, np.float32), timeout_s=30)
        done = threading.Event()
        eng.submit([1, 2, 3], 3, lambda t: None,
                   lambda e: done.set())
        assert done.wait(30)
        sp = rpcz.new_span("client", "Ledger", "Probe")
        rpcz.submit(sp)
        rpcz.recent_spans(5)
    finally:
        rpcz.set_enabled(*was)
        flags.set_flag("native_hot_path_enabled", was_native)
        eng.close()
        store.close()
        b.close()
    snap = locks_snapshot()
    for name in ("batcher.queue", "engine.slots", "kvcache.store",
                 "serving.emit_buf", "rpcz.collect"):
        assert name in snap, f"missing ledger row {name}"
        assert snap[name]["acquisitions"] > 0, name
        assert "last_holder_stage" in snap[name]


# ---------------------------------------------------------------------------
# always-on sampler
# ---------------------------------------------------------------------------

def _sampler_threads():
    return [t for t in threading.enumerate()
            if t.name == "hotspot-sampler" and t.is_alive()]


def test_sampler_stage_tags_stacks_and_stops_cleanly():
    from brpc_tpu.builtin.sampler import HotspotSampler
    samp = HotspotSampler.instance()
    was_running = samp.running
    stop = threading.Event()

    def busy():
        x = 0
        while not stop.is_set():
            x += 1

    t = threading.Thread(target=busy, name="serving-engine-samplerprobe")
    t.start()
    samp.start()
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            folded = samp.folded()
            if any(k.startswith("decode_step;") for k in folded):
                break
            time.sleep(0.05)
        folded = samp.folded()
        assert any(k.startswith("decode_step;") for k in folded), \
            "busy serving-engine thread never sampled under its stage"
        snap = samp.snapshot()
        assert snap["running"] and snap["samples"] > 0
        assert 0.0 <= snap["gil_wait_ratio"] <= 1.0
        assert "decode_step" in snap["stages"]
    finally:
        stop.set()
        t.join(5)
    # disabling removes the sampler thread CLEANLY (the satellite's
    # second claim): stop() joins, nothing named hotspot-sampler lives
    samp.stop()
    assert not samp.running
    assert not _sampler_threads()
    if was_running:
        samp.start()


def test_gil_wait_ratio_is_an_exposed_bvar():
    from brpc_tpu.bvar.variable import find_exposed
    var = find_exposed("gil_wait_ratio")
    assert var is not None
    v = var.get_value()
    assert isinstance(v, float) and 0.0 <= v <= 1.0


def test_burst_collects_stage_tagged_stacks():
    from brpc_tpu.builtin import sampler
    stop = threading.Event()

    def busy():
        x = 0
        while not stop.is_set():
            x += 1

    t = threading.Thread(target=busy, name="serving-batcher-burstprobe")
    t.start()
    try:
        stacks = sampler.burst(0.25, hz=100)
    finally:
        stop.set()
        t.join(5)
    assert sum(stacks.values()) > 0
    assert any(k.startswith("batch_formation;") for k in stacks)
    text = sampler.render_folded(stacks, "test burst")
    assert "batch_formation" in text and "lock-wait%" in text


def test_blocked_instrumented_lock_samples_as_lock_wait():
    """A thread parked inside InstrumentedLock.acquire — blocked on
    exactly the hot locks this layer ledgers — must classify as a
    lock-wait sample, or gil_wait_ratio undercounts where it matters."""
    from brpc_tpu.builtin import sampler
    from brpc_tpu.butil.lockprof import InstrumentedLock
    lk = InstrumentedLock("test.wait_marker")
    entered = threading.Event()
    release = threading.Event()

    def holder():
        with lk:
            entered.set()
            release.wait(10)

    def blocked():
        entered.wait(10)
        with lk:
            pass

    th = threading.Thread(target=holder)
    tb = threading.Thread(target=blocked, name="serving-emit-waitprobe")
    th.start()
    tb.start()
    try:
        assert entered.wait(5)
        time.sleep(0.05)   # let the blocked thread park in acquire
        stacks = sampler.burst(0.2, hz=100)
    finally:
        release.set()
        th.join(5)
        tb.join(5)
    waiting = [k for k in stacks
               if k.startswith("emit_fanout;")
               and k.endswith(";[lock-wait]")
               and "lockprof" in k]
    assert waiting, \
        ("blocked InstrumentedLock.acquire sampled as running: "
         + "\n".join(k for k in stacks if k.startswith("emit_fanout;")))


def test_native_hot_path_samples_fold_to_native_leaf():
    """A thread inside a GIL-released native call (the emit ring's pop
    wait) folds to a ``;[native]`` leaf — not Python run time, not
    lock-wait — so gil_wait_ratio and the per-stage table stay honest
    after the de-GIL rewrite (ISSUE 9)."""
    import ctypes

    from brpc_tpu import native_path
    from brpc_tpu.builtin import sampler
    ring = native_path.token_ring(8)
    if ring is None:
        pytest.skip("native core unavailable")
    out = (ctypes.c_int32 * 8)()
    stop = threading.Event()

    def consumer():
        # parks inside brpc_tokring_pop_many with the GIL released;
        # the sampled leaf Python frame is the ctypes binding call site
        while not stop.is_set():
            ring.pop_many(out, 0.2)

    t = threading.Thread(target=consumer,
                         name="serving-emit-nativeprobe")
    t.start()
    try:
        time.sleep(0.05)
        stacks = sampler.burst(0.25, hz=100)
    finally:
        stop.set()
        t.join(5)
    native = [k for k in stacks
              if k.startswith("emit_fanout;") and k.endswith(";[native]")
              and "_core/lib" in k]
    assert native, \
        ("native pop wait did not fold to a ;[native] leaf: "
         + "\n".join(k for k in stacks if k.startswith("emit_fanout;")))
    assert not any(k.startswith("emit_fanout;")
                   and k.endswith(";[lock-wait]") and "_core/lib" in k
                   for k in stacks), \
        "native pop wait misclassified as lock-wait"


def test_gil_held_binding_sites_not_classed_native():
    """TokenRing.push rides the _fastrpc C entry that deliberately
    HOLDS the GIL — a thread sampled there is GIL-bound run time, and
    classing it ``;[native]`` would overstate gil_wait_ratio's de-GIL
    story.  The GIL-released binding sites (pop_many's ctypes call)
    stay native."""
    from brpc_tpu import native_path
    from brpc_tpu.builtin import sampler
    if native_path._core_lib() is None:
        pytest.skip("native core unavailable")
    from brpc_tpu._core import lib
    assert not sampler._is_native_leaf(lib.TokenRing.push.__code__)
    assert not sampler._is_native_leaf(
        lib.TokenRing.push_terminal.__code__)
    assert sampler._is_native_leaf(lib.TokenRing.pop_many.__code__)


def test_stage_table_carries_native_column():
    from brpc_tpu.builtin.sampler import HotspotSampler, _Window
    samp = HotspotSampler()   # fresh, not the singleton
    win = samp._win
    win.run, win.wait, win.native = 6, 2, 2
    win.stage_run["decode_step"] = 6
    win.stage_wait["decode_step"] = 2
    win.stage_native["decode_step"] = 2
    table = samp.stage_table()
    assert table["decode_step"] == {
        "run": 6, "wait": 2, "native": 2, "wait_ratio": 0.2}
    # native samples are GIL-free progress: they stay in the ratio's
    # denominator (2 wait / 10 total), they don't vanish from it
    assert samp.gil_wait_ratio() == 0.2


def _window_limited_qps(name: str, duration_s: float = 0.7) -> float:
    """Batcher qps with threads << max_batch_size: every batch forms at
    WINDOW expiry, so throughput is set by the 2ms window, not compute
    — near-deterministic, which is what makes a small sampler overhead
    measurable (the PR 5 trace_overhead discipline)."""
    from brpc_tpu.serving import DynamicBatcher
    b = DynamicBatcher(lambda x: x.sum(axis=1), max_batch_size=64,
                       max_delay_us=2000, batch_buckets=(64,),
                       length_buckets=(16,), name=name)
    item = np.ones((16,), np.float32)
    try:
        b.submit_wait(item, timeout_s=30)
        stop = time.monotonic() + duration_s
        counts = [0] * 4

        def w(i):
            while time.monotonic() < stop:
                b.submit_wait(item, timeout_s=30)
                counts[i] += 1

        ts = [threading.Thread(target=w, args=(i,)) for i in range(4)]
        t0 = time.monotonic()
        [t.start() for t in ts]
        [t.join(60) for t in ts]
        return sum(counts) / (time.monotonic() - t0)
    finally:
        b.close()


def test_always_on_sampler_overhead_under_2pct(monkeypatch):
    """The tier-1 gate on shipping the profiler always-on, in what the
    sampler promises and a test can count: while a batcher serves
    traffic, the sampler walks the threads' frames no more often than
    ``hotspot_sampler_hz``, and doing so costs its own thread under 2%
    of the window's wall time.  The process is this test's: threads that
    stood before it (a full run leaves 62, and a pass over them costs
    2.9 ms, ROADMAP D19) are left out of the walk.  The batcher's qps
    with and without the sampler is a timing on a shared CPU (2.7-7.4%
    deep in a run, under 2% alone, for the same code)."""
    from brpc_tpu.builtin import sampler
    from brpc_tpu.flags import get_flag
    samp = sampler.HotspotSampler.instance()
    was_running = samp.running
    samp.stop()
    leftovers = frozenset(t.ident for t in threading.enumerate())
    passes = [0]
    sample_once = sampler.sample_once

    def counted(exclude=frozenset()):
        passes[0] += 1
        return sample_once(exclude=exclude | leftovers)

    monkeypatch.setattr(sampler, "sample_once", counted)
    try:
        samp.start()
        clock = time.pthread_getcpuclockid(samp._thread.ident)
        cpu0, t0 = time.clock_gettime(clock), time.monotonic()
        assert _window_limited_qps("sampler_ovh_on", duration_s=2.0) > 0
        cpu_s = time.clock_gettime(clock) - cpu0
        wall_s = time.monotonic() - t0
    finally:
        samp.stop()
        monkeypatch.undo()
        if was_running:
            samp.start()
    hz = float(get_flag("hotspot_sampler_hz", 10.0))
    # one pass at start, then at most one a period
    assert 2 <= passes[0] <= hz * wall_s + 1, (passes[0], hz, wall_s)
    assert cpu_s < 0.02 * wall_s, (cpu_s, wall_s, passes[0])


# ---------------------------------------------------------------------------
# per-stage host-CPU accounting
# ---------------------------------------------------------------------------

def test_host_cpu_per_token_accounting():
    """Python-path accounting mechanics (ISSUE 6).  Runs with the
    native hot path OFF: the de-GIL'd step loop's remaining Python
    bookkeeping per step can round to ZERO on a coarse thread_time
    clock, making the stage_us('decode_step') > d0 assert flaky —
    and the python fallback is the path whose accounting this test
    pins.  Native-path sampler visibility has its own tests above."""
    from brpc_tpu import flags
    from brpc_tpu.butil import hostcpu
    from brpc_tpu.kvcache import KVCacheStore
    from brpc_tpu.serving import DecodeEngine
    from brpc_tpu.bvar.variable import find_exposed

    d0 = hostcpu.stage_us("decode_step")
    t0 = hostcpu.tokens_total.get_value()
    store = KVCacheStore(page_tokens=4, page_bytes=256, max_blocks=32,
                         name="hostcpu_probe")
    eng = DecodeEngine(lambda t, p: (t * 3 + p) % 101, num_slots=2,
                       store=store, pass_page_table=False,
                       name="hostcpu_probe")
    was_native = flags.get_flag("native_hot_path_enabled", True)
    flags.set_flag("native_hot_path_enabled", False)
    try:
        done = [threading.Event() for _ in range(4)]
        for i, d in enumerate(done):
            eng.submit([10 + i, 20 + i, 30 + i], 24, lambda t: None,
                       lambda e, d=d: d.set())
        for d in done:
            assert d.wait(60)
    finally:
        flags.set_flag("native_hot_path_enabled", was_native)
        eng.close()
        store.close()
    assert hostcpu.tokens_total.get_value() >= t0 + 4 * 24
    assert hostcpu.stage_us("decode_step") > d0, \
        "decode-step host CPU never accounted"
    snap = hostcpu.snapshot()
    assert set(hostcpu.HOST_STAGES) <= set(snap["per_stage_us"])
    var = find_exposed("serving_host_us_per_token")
    assert var is not None and var.get_value() > 0


# ---------------------------------------------------------------------------
# console + metrics exposition
# ---------------------------------------------------------------------------

@pytest.fixture()
def server():
    s = brpc.Server()
    s.start("127.0.0.1", 0)
    yield s
    s.stop()
    s.join()


def _get(server, path):
    import http.client
    c = http.client.HTTPConnection("127.0.0.1", server.port, timeout=30)
    c.request("GET", path)
    r = c.getresponse()
    body = r.read()
    c.close()
    return r.status, body


def test_hotspots_pages_show_live_serving_attribution(server):
    """/hotspots burst + /hotspots/locks against live serving load:
    stage-tagged stacks and per-lock wait/hold rows, the acceptance
    shape."""
    from brpc_tpu.serving import DynamicBatcher
    b = DynamicBatcher(lambda x: x.sum(axis=1), max_batch_size=8,
                       max_delay_us=300, batch_buckets=(8,),
                       length_buckets=(16,), name="console_hotspots")
    stop = threading.Event()
    item = np.ones((16,), np.float32)

    def load():
        while not stop.is_set():
            try:
                b.submit_wait(item, timeout_s=10)
            except Exception:
                return

    ts = [threading.Thread(target=load) for _ in range(3)]
    [t.start() for t in ts]
    try:
        status, body = _get(server, "/hotspots?seconds=0.4")
        assert status == 200
        text = body.decode()
        assert "batch_formation" in text, text[:400]
        assert "lock-wait%" in text
        # ring view answers (always-on sampler was started by the server)
        status, body = _get(server, "/hotspots")
        assert status == 200 and b"gil_wait_ratio" in body
        # pprof-pb burst is gzipped profile.proto
        status, body = _get(server, "/hotspots?seconds=0.2&fmt=pb")
        assert status == 200 and body[:2] == b"\x1f\x8b"
        # collapsed burst is flamegraph input
        status, body = _get(server,
                            "/hotspots?seconds=0.2&fmt=collapsed")
        assert status == 200
        assert re.search(rb"^\S+ \d+$", body, re.M)
        # the lock ledger shows the batcher queue lock with real stats
        status, body = _get(server, "/hotspots/locks")
        assert status == 200
        text = body.decode()
        assert "batcher.queue" in text
        status, body = _get(server, "/hotspots/locks?fmt=json")
        payload = json.loads(body)
        # ISSUE 14: the json page nests the ledger beside the
        # lock-order witness (held sets, order edges, ABBA violations)
        snap = payload["ledger"]
        assert snap["batcher.queue"]["acquisitions"] > 0
        assert "wait_p99_us" in snap["batcher.queue"]
        assert "hold_avg_us" in snap["batcher.queue"]
        wit = payload["witness"]
        assert wit["enabled"] is True
        assert isinstance(wit["edges"], dict)
        assert wit["violations"] == []     # serving stack stays acyclic
    finally:
        stop.set()
        [t.join(15) for t in ts]
        b.close()


_METRIC_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [0-9eE+.\-]+$")
_HELP_LINE = re.compile(r"^# HELP [a-zA-Z_:][a-zA-Z0-9_:]* .+$")
_TYPE_LINE = re.compile(
    r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (gauge|counter|summary)$")


def test_brpc_metrics_exposition_hygiene(server):
    """Satellite: counters export as `counter`, LatencyRecorders as
    quantile-labeled `summary`, everything carries HELP, and the whole
    scrape parses as exposition format with one TYPE per family."""
    from brpc_tpu.bvar import Adder, LatencyRecorder
    rec = LatencyRecorder("hotspot_fmt_probe")
    ctr = Adder("hotspot_fmt_probe_events")
    try:
        for v in (100, 200, 300, 1000):
            rec.add(v)
        ctr.add(7)
        status, body = _get(server, "/brpc_metrics")
        assert status == 200
        lines = body.decode().splitlines()
        types = {}
        for ln in lines:
            if not ln:
                continue
            if ln.startswith("# HELP"):
                assert _HELP_LINE.match(ln), ln
                continue
            if ln.startswith("# TYPE"):
                assert _TYPE_LINE.match(ln), ln
                fam = ln.split()[2]
                assert fam not in types, f"duplicate TYPE for {fam}"
                types[fam] = ln.split()[3]
                continue
            assert _METRIC_LINE.match(ln), ln
        # the recorder is a summary family with quantiles + _sum/_count
        assert types.get("hotspot_fmt_probe") == "summary"
        text = body.decode()
        assert 'hotspot_fmt_probe{quantile="0.5"}' in text
        assert "hotspot_fmt_probe_sum" in text
        assert "hotspot_fmt_probe_count" in text
        # and its satellite percentile gauges are folded in, not
        # duplicated as separate families
        assert "hotspot_fmt_probe_latency_99 " not in text
        # the Adder is a counter with help
        assert types.get("hotspot_fmt_probe_events") == "counter"
        assert "# HELP hotspot_fmt_probe_events " in text
        # headline bvars of this PR ride the same scrape
        assert "gil_wait_ratio" in text
        assert "serving_host_us_per_token" in text
        # every summary family got exactly one TYPE (spot-check a
        # serving recorder that predates this PR)
        assert types.get("serving_ttft_us") == "summary"
    finally:
        rec.hide()
        ctr.hide()


def test_rpc_press_hotspots_flag():
    """--hotspots N: the press prints the server's top-N stage-tagged
    folded stacks alongside the latency report."""
    class Echo(brpc.Service):
        NAME = "PressEcho"

        @brpc.method(request="json", response="json")
        def Echo(self, cntl, req):
            return req

    s = brpc.Server()
    s.add_service(Echo())
    s.start("127.0.0.1", 0)
    try:
        from brpc_tpu.tools.rpc_press import run_press
        out = io.StringIO()
        summary = run_press(f"127.0.0.1:{s.port}", "PressEcho", "Echo",
                            {"x": 1}, qps=0, duration_s=0.6, threads=2,
                            hotspots=3, out=out)
        assert summary["sent_ok"] > 0
        text = out.getvalue()
        assert "server hotspots during press" in text
        assert "samples" in text
    finally:
        s.stop()
        s.join()

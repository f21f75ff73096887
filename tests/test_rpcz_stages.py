"""Stage spans inside the tensor call path (``rpcz.stage``), their two
sinks, the reader that puts an idle chip down to them
(``benchmarks/harness/program_spans.py``) and the per-layer metrics that
read it.

One scenario — a loopback ``serializer="tensor"`` echo, a 4 MiB
``StreamWrite`` echo and a lowered 4-way fan-out on four virtual CPU
devices — is driven three times: with nothing listening, under a
``jax.profiler`` session, and with rpcz on.  CPU only: what is checked
is names, nesting, identifiers and arithmetic, never a time.
"""
import http.client
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import brpc_tpu as brpc
from brpc_tpu import rpcz
from brpc_tpu.ici import IciChannel, register_device_service
from benchmarks.harness import loader, program_spans as ps, trace

WINDOW = 8 << 20
CHUNK_WORDS = 1 << 20          # 4 MiB of uint32
N_ECHO, N_CHUNKS, N_FAN = 3, 5, 2

UNARY = ("rpc.client.call", "rail.ship", "ici.endpoint.send", "net.write",
         "rpc.client.wait", "rpc.client.on_response", "rpc.server.process",
         "rail.claim", "rpc.server.handler", "rpc.server.respond")
STREAM = ("stream.write", "stream.credit_wait", "stream.send",
          "stream.on_data", "stream.handler", "stream.ack",
          "stream.on_feedback")
FANOUT = ("combo.call_lowered", "collective.place", "collective.run",
          "combo.merge")
# child -> the stages one of which must be its nearest ancestor
PARENTS = {
    "rail.ship": ("rpc.client.call", "rpc.server.respond", "stream.send"),
    "ici.endpoint.send": ("rail.ship",),
    "rpc.client.wait": ("rpc.client.call",),
    "rpc.server.handler": ("rpc.server.process",),
    "rpc.server.respond": ("rpc.server.process",),
    "stream.credit_wait": ("stream.write",),
    "stream.handler": ("stream.on_data",),
    "collective.place": ("combo.call_lowered",),
    "collective.run": ("combo.call_lowered",),
    "combo.merge": ("combo.call_lowered",),
}
NEW_METRICS = {
    "rpc.client_self_us_per_call", "rpc.server_self_us_per_call",
    "rail.host_us_per_call", "net.write_us_per_call",
    "net.queue_wait_p50_us", "host.span_wait_share",
    "device.idle_unattributed_share", "stream.host_us_per_chunk",
    "stream.credit_wait_share", "collective.place_p50_ms",
    "collective.run_p50_ms", "combo.merge_p50_ms"}


def _fan(x):
    return (x ^ (x >> 3)) + jnp.uint32(1)


class StageSvc(brpc.Service):
    NAME = "StageSvc"

    def __init__(self, device):
        super().__init__()
        self.device = device

    @brpc.method(request="tensor", response="tensor")
    def Echo(self, cntl, req):
        return req

    @brpc.method(request="json", response="json")
    def Open(self, cntl, req):
        def echo(stream, payload):
            time.sleep(0.02)     # so that the writer meets a full window
            stream.write(payload)
        cntl.accept_stream(echo, max_buf_size=WINDOW, device=self.device)
        return {"accepted": True}


class Scenario:
    """The three flows against one server; ``drive`` returns the replies
    as numpy arrays and the calls it made as benchmark-style records."""

    def __init__(self):
        devs = jax.devices()
        self.client_dev, self.server_dev = devs[0], devs[1]
        self.server = brpc.Server(ici_device=self.server_dev)
        self.server.add_service(StageSvc(self.server_dev))
        self.server.start("127.0.0.1", 0)
        self.ch = brpc.Channel(f"127.0.0.1:{self.server.port}",
                               timeout_ms=60_000, max_retry=0)
        register_device_service("StageFan", "Apply", _fan)
        self.fan = brpc.ParallelChannel()
        for i in range(4):
            self.fan.add_channel(IciChannel(f"ici://slice0/{i}"))
        with jax.default_device(self.client_dev):
            self.small = jnp.arange(1 << 16, dtype=jnp.uint32) * 3
            self.chunks = [jnp.arange(CHUNK_WORDS, dtype=jnp.uint32) + k
                           for k in range(N_CHUNKS)]
        jax.block_until_ready([self.small, self.chunks])

    def drive(self):
        replies, calls = {"echo": [], "chunk": [], "fan": []}, []

        def record(kind, t_issue, nbytes):
            calls.append({"kind": kind, "ok": True, "bytes": nbytes,
                          "t_issue": t_issue, "t_done": time.monotonic()})

        for _ in range(N_ECHO):
            t = time.monotonic()
            out = self.ch.call_sync("StageSvc", "Echo", self.small,
                                    serializer="tensor")
            jax.block_until_ready(out)
            record("echo", t, self.small.nbytes)
            replies["echo"].append(np.asarray(out))

        got, cv = [], threading.Condition()

        def on_chunk(_stream, payload):
            with cv:
                got.append(payload)
                cv.notify_all()

        cntl = brpc.Controller()
        stream = brpc.stream_create(cntl, on_chunk, max_buf_size=WINDOW,
                                    device=self.client_dev)
        self.ch.call_sync("StageSvc", "Open", {}, serializer="json",
                          cntl=cntl)
        t_sent = []
        for c in self.chunks:
            t_sent.append(time.monotonic())
            stream.write(c, timeout_s=60.0)
        with cv:
            assert cv.wait_for(lambda: len(got) == N_CHUNKS, 60.0)
        for t, c in zip(t_sent, got):
            record("chunk", t, c.nbytes)
        stream.close()
        replies["chunk"] = [np.asarray(c) for c in got]

        for _ in range(N_FAN):
            t = time.monotonic()
            out = self.fan.call_sync("StageFan", "Apply", self.small)
            jax.block_until_ready(out)
            record("fanout", t, self.small.nbytes * 4)
            replies["fan"].append([np.asarray(o) for o in out])
        return replies, calls

    def close(self):
        self.server.stop()
        self.server.join()


@pytest.fixture(scope="module")
def states(tmp_path_factory):
    """The scenario in its three states."""
    sc = Scenario()
    try:
        sc.drive()                                   # compiles, connects
        # -- nothing listens
        id0 = next(rpcz._span_counter)
        kept0 = len(rpcz.recent_spans(4096))
        noop_everywhere = rpcz.stage("rpc.client.call", 1) is rpcz.NOOP_STAGE
        off, _ = sc.drive()
        noop_everywhere &= rpcz.stage("net.write") is rpcz.NOOP_STAGE
        spans_made = next(rpcz._span_counter) - id0 - 1
        kept = len(rpcz.recent_spans(4096)) - kept0
        # -- under the profiler
        tdir = str(tmp_path_factory.mktemp("stages_trace"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        t0 = time.monotonic()
        try:
            profiled, calls = sc.drive()
        finally:
            t1 = time.monotonic()
            jax.profiler.stop_trace()
        # -- rpcz on
        rpcz.set_enabled(True)
        try:
            traced, _ = sc.drive()
            time.sleep(0.1)
            recent = rpcz.recent_spans(4096)
            port = sc.server.port
            client = next(s for s in reversed(recent) if s.kind == "client"
                          and s.method == "Echo")
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=5)
            conn.request("GET", f"/rpcz?trace_id={client.trace_id}")
            page = conn.getresponse().read().decode()
            conn.close()
        finally:
            rpcz.set_enabled(False)
        yield types.SimpleNamespace(
            off=off, profiled=profiled, traced=traced, calls=calls,
            noop=noop_everywhere, spans_made=spans_made, kept=kept,
            trace_dir=tdir, t0=t0, t1=t1, recent=recent, client=client,
            page=page)
    finally:
        sc.close()


@pytest.fixture(scope="module")
def reduction(states):
    red = ps.reduce(trace.find_xplane(states.trace_dir), states.t0,
                    states.t1)
    assert red is not None
    return red


# ---- sink 1: the profiler --------------------------------------------------

@pytest.mark.parametrize("name", UNARY + STREAM + FANOUT)
def test_every_stage_of_the_table_appears_in_the_trace(reduction, name):
    assert reduction["by_name"].get(name), \
        f"no {name}; the trace has {sorted(reduction['by_name'])}"


@pytest.mark.parametrize("child", sorted(PARENTS))
def test_children_nest_inside_their_parents_on_their_thread(reduction,
                                                            child):
    for s in reduction["by_name"][child]:
        if s.parent is None and child == "rpc.client.wait":
            continue      # the join on a fan-out the lowering completed
        assert s.parent is not None, f"{child} is a root on its thread"
        assert s.parent.name in PARENTS[child] + (child,), \
            f"{child} under {s.parent.name}"
        assert s.parent.thread == s.thread
        assert s.parent.start <= s.start and s.end <= s.parent.end


def test_the_stages_of_one_call_share_its_cid_on_both_sides(reduction):
    calls = [s for s in reduction["by_name"]["rpc.client.call"]
             if any(c.name == "rail.ship" for c in s.children)]
    assert len(calls) == N_ECHO
    for call in calls:
        cid = call.stats["cid"]
        same = [s for s in reduction["spans"] if s.stats.get("cid") == cid]
        names = {s.name for s in same}
        assert set(UNARY) <= names, sorted(set(UNARY) - names)
        server = next(s for s in same if s.name == "rpc.server.process")
        back = next(s for s in same if s.name == "rpc.client.on_response")
        # the caller parks while the upcall lane serves and completes it
        assert call.thread not in (server.thread, back.thread)
        # in order: issued, served, completed, all inside the call
        assert call.start <= server.start <= back.start <= call.end


def test_stream_stages_share_stream_and_seq_across_the_sides(reduction):
    writes = {s.stats["cid"] for s in reduction["by_name"]["stream.write"]}
    datas = {s.stats["cid"] for s in reduction["by_name"]["stream.on_data"]}
    # both directions: the chunk out and its echo back
    assert len(writes) == 2 * N_CHUNKS and writes == datas


def test_queue_wait_arrives_from_the_native_core(reduction):
    for name in ("rpc.server.process", "rpc.client.on_response",
                 "stream.on_data"):
        for s in reduction["by_name"][name]:
            assert s.stats["queue_wait_us"] >= 0, (name, s.stats)
            assert s.stats["queue_depth"] >= 0
            # an interval on one clock: never longer than the call
            assert s.stats["queue_wait_us"] < 60e6


def test_root_stages_join_the_monotonic_clock(states, reduction):
    roots = [s for s in reduction["spans"] if "mono_us" in s.stats]
    assert {s.name for s in roots} == set(rpcz.ROOT_STAGES)
    off = reduction["clock_offset_us"]
    for s in roots:
        assert abs(s.start / 1e3 - s.stats["mono_us"] - off) < 5_000
    # the benchmark's own records, placed through the offset, cover the
    # calls' root stages
    echo = [c for c in states.calls if c["kind"] == "echo"]
    calls = sorted((s for s in reduction["by_name"]["rpc.client.call"]
                    if any(c.name == "rail.ship" for c in s.children)),
                   key=lambda s: s.start)
    for rec, s in zip(echo, calls):
        assert rec["t_issue"] * 1e6 + off <= s.start / 1e3 + 100
        assert s.end / 1e3 <= rec["t_done"] * 1e6 + off + 100


def test_every_stage_stamps_its_cpu_time_and_waits_are_marked(reduction):
    for s in reduction["spans"]:
        # the stages outermost on their thread stamp CPU time, no other
        assert (s.cpu_us is not None) == (s.name in rpcz.CPU_STAGES), s.name
        if s.cpu_us is not None:
            assert 0 <= s.cpu_us <= s.dur / 1e3 + 1_000, (s.name, s.stats)
        assert s.wait == (s.name in rpcz.WAIT_STAGES)
    ship = reduction["by_name"]["rail.ship"][0]
    assert ship.stats["bytes"] > 0 and ship.stats["programs"] >= 1
    send_stats = reduction["by_name"]["ici.endpoint.send"][0].stats
    assert "waited_window_us" in send_stats and "overlap" in send_stats
    assert reduction["by_name"]["collective.run"][-1].stats["cache_hit"] == 1


def test_the_merge_stage_says_what_it_handed_the_merger(reduction):
    merges = reduction["by_name"]["combo.merge"]
    assert len(merges) == N_FAN
    for s in merges:
        assert s.parent.name == "combo.call_lowered"
        # four rows of the request's size, chip 0 in the mesh: none moved
        assert s.stats["rows"] == 4 and s.stats["moved"] == 0
        assert s.stats["bytes"] == 4 * (1 << 16) * 4


def test_a_caller_outside_the_mesh_reads_as_moved_rows(tmp_path):
    register_device_service("StageFan", "Apply", _fan)
    fan = brpc.ParallelChannel()
    for i in range(4, 8):
        fan.add_channel(IciChannel(f"ici://slice0/{i}"))
    x = jax.device_put(jnp.arange(1 << 10, dtype=jnp.uint32),
                       jax.devices()[0])
    fan.call_sync("StageFan", "Apply", x)                     # compile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    t0 = time.monotonic()
    try:
        out = fan.call_sync("StageFan", "Apply", x)
    finally:
        t1 = time.monotonic()
        jax.profiler.stop_trace()
    assert all(o.devices() == {jax.devices()[0]} for o in out)
    red = ps.reduce(trace.find_xplane(str(tmp_path)), t0, t1)
    (merge,) = red["by_name"]["combo.merge"]
    assert merge.parent.name == "combo.call_lowered"
    assert {"rows": 4, "moved": 4, "bytes": 4 * x.nbytes}.items() \
        <= merge.stats.items()


def test_the_fan_in_counter_counts_one_fan_in_a_lowered_call():
    from brpc_tpu.bvar import find_exposed
    fan_ins = find_exposed("ici_collective_fan_ins")
    calls = find_exposed("ici_collective_calls")
    register_device_service("StageFan", "Apply", _fan)
    fan = brpc.ParallelChannel()
    for i in range(4):
        fan.add_channel(IciChannel(f"ici://slice0/{i}"))
    x = jnp.arange(1 << 10, dtype=jnp.uint32)
    fan.call_sync("StageFan", "Apply", x)
    before, n0 = fan_ins.get_value(), calls.get_value()
    for _ in range(3):
        fan.call_sync("StageFan", "Apply", x)
    after = fan_ins.get_value()
    assert calls.get_value() - n0 == 3
    assert after["in_place"] - before["in_place"] == 3
    assert after["moved"] == before["moved"]


# ---- sink 2: the rpcz span -------------------------------------------------

def test_rpcz_has_a_client_and_a_server_span_with_their_phases(states):
    client = states.client
    server = next(s for s in states.recent if s.kind == "server"
                  and s.trace_id == client.trace_id)
    assert server.parent_span_id == client.span_id
    c_names = [p[0] for p in client.phases]
    s_names = [p[0] for p in server.phases]
    for n in ("rpc.client.call", "rail.ship", "net.write",
              "rpc.client.wait", "rpc.client.on_response"):
        assert n in c_names, c_names
    for n in ("rail.claim", "rpc.server.handler", "rpc.server.respond",
              "rail.ship", "net.write"):
        assert n in s_names, s_names
    for name, start_us, dur_us, cpu_us in client.phases + server.phases:
        assert dur_us >= 0
        assert (cpu_us is not None) == (name in rpcz.CPU_STAGES)
        assert start_us >= client.start_us - 1_000


def test_phases_round_trip_and_show_in_the_trace_page(states):
    client = states.client
    back = rpcz.span_from_dict(rpcz.span_to_dict(client))
    assert back.phases == client.phases and back.kind == "client"
    text = rpcz.format_trace([s for s in states.recent
                              if s.trace_id == client.trace_id])
    for needle in ("[client]", "[server]", "rail.ship", "rpc.server.handler",
                   "(cpu "):
        assert needle in text, text
    # /rpcz?trace_id= is that text
    for needle in ("[client] StageSvc.Echo", "[server] StageSvc.Echo",
                   "rpc.client.wait", "rpc.server.respond"):
        assert needle in states.page, states.page


# ---- off -------------------------------------------------------------------

def test_with_both_sinks_off_nothing_is_made(states):
    assert states.noop
    assert states.spans_made == 0 and states.kept == 0
    assert rpcz.stage("rail.ship") is rpcz.stage("stream.write", "1:2")
    with rpcz.stage("net.write") as stg:
        assert stg is rpcz.NOOP_STAGE
    assert rpcz.span_scope(rpcz.NULL_SPAN) is rpcz.NOOP_STAGE


@pytest.mark.parametrize("state", ["profiled", "traced"])
def test_replies_are_byte_equal_in_all_three_states(states, state):
    other = getattr(states, state)
    for kind in ("echo", "chunk"):
        assert len(other[kind]) == len(states.off[kind]) > 0
        for a, b in zip(states.off[kind], other[kind]):
            assert a.tobytes() == b.tobytes()
    for a, b in zip(states.off["fan"], other["fan"]):
        assert len(a) == len(b) == 4
        for x, y in zip(a, b):
            assert x.tobytes() == y.tobytes()


# ---- the reader, on hand-made intervals ------------------------------------

def _mk(name, start, end, thread=1, **stats):
    return ps.Span(name, start, end, stats, thread)


def test_self_time_is_the_duration_less_what_the_children_cover():
    a = _mk("rpc.server.process", 0, 100, cpu_us=50)
    b = _mk("rpc.server.handler", 10, 30, cpu_us=5)
    c = _mk("rpc.server.respond", 40, 90, cpu_us=20)
    d = _mk("net.write", 50, 60, cpu_us=1)
    other = _mk("net.write", 20, 70, thread=2)          # another thread
    roots = ps.nest([a, b, c, d, other])
    assert roots == [a, other] and c.children == [d] and d.parent is c
    assert a.self_ns == 100 - 20 - 50 and c.self_ns == 40
    assert other.self_ns == 50 and other.cpu_us is None
    parked = _mk("rpc.client.wait", 60, 80, wait=1)
    ps.nest([a, b, c, d, parked])
    assert c.parked_ns == 20 and a.parked_ns == 20 and b.parked_ns == 0


def test_spans_are_clipped_to_the_window():
    kept = ps.clip([_mk("a.", 0, 10), _mk("b.", 5, 25), _mk("c.", 30, 40),
                    _mk("d.", 18, 50)], 8, 20)
    assert [(s.name, s.start, s.end) for s in kept] == \
        [("a.", 8, 10), ("b.", 8, 20), ("d.", 18, 20)]


def test_a_parked_thread_takes_no_share_of_the_idle_time():
    call = _mk("rpc.client.call", 0, 100)
    wait = _mk("rpc.client.wait", 20, 90, wait=1)
    segs = ps.innermost_segments(ps.nest([call, wait]))
    assert segs == [(0, 20, 1, "rpc.client.call"),
                    (90, 100, 1, "rpc.client.call")]
    by = ps.attribute_idle([(0, 100)], segs)
    assert by == {"rpc.client.call": pytest.approx(30e-9),
                  ps.UNATTRIBUTED: pytest.approx(70e-9)}


def test_idle_is_shared_between_overlapping_threads_and_left_over_where_none():
    t1 = _mk("rpc.server.process", 0, 60, thread=1)
    t2 = _mk("rpc.client.on_response", 40, 100, thread=2)
    inner = _mk("rail.claim", 50, 55, thread=2)
    segs = ps.innermost_segments(ps.nest([t1, t2, inner]))
    busy = [(10, 20)]
    idle = ps.idle_intervals(busy, 0, 120)
    assert idle == [(0, 10), (20, 120)]
    by = ps.attribute_idle(idle, segs)
    # [0,10)+[20,40) alone: 30; [40,60) shared, 5 of it with rail.claim
    assert by["rpc.server.process"] == pytest.approx((30 + 10) * 1e-9)
    assert by["rpc.client.on_response"] == pytest.approx(
        (7.5 + 40) * 1e-9)
    assert by["rail.claim"] == pytest.approx(2.5e-9)
    assert by[ps.UNATTRIBUTED] == pytest.approx(20e-9)
    assert sum(by.values()) == pytest.approx(110e-9)


def test_a_trace_without_program_spans_reads_as_none():
    import os
    recorded = os.path.join(loader.ROOT, "benchmarks", "tests", "data",
                            "small.xplane.pb")
    assert ps.read_spans(recorded) == []
    assert ps.reduce(recorded) is None
    run = {"traced": None, "records": {"calls": []}}
    assert ps.load(run) is None
    assert ps.us_per(run, ("rpc.client.call",), "echo", own=True) is None
    assert ps.wait_share_percent(run) is None


def test_the_attribution_of_the_recorded_trace_adds_up(reduction):
    by = reduction["idle_by_stage"]
    assert sum(by.values()) == pytest.approx(reduction["idle_s"], rel=1e-6)
    assert set(by) <= set(reduction["by_name"]) | {ps.UNATTRIBUTED}
    assert not set(by) & set(rpcz.WAIT_STAGES)
    assert "rpc.client.call" in ps.describe(reduction)


# ---- one test per new metric file ------------------------------------------

@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_metric_reader_on_the_recorded_trace(states, monkeypatch, metric):
    monkeypatch.setattr(ps, "trace_dir", lambda run: states.trace_dir)
    run = {"cell": types.SimpleNamespace(name="recorded"),
           "records": {"calls": states.calls},
           "traced": {"t0": states.t0, "t1": states.t1,
                      "window_s": states.t1 - states.t0}}
    value = loader.load_metric(metric).compute(run)
    assert value is not None and value >= 0
    if metric.endswith("_share"):
        assert value <= 100.0
    # in an untraced run, and on a program without the stages (its trace
    # holds none), the reader finds nothing and says so
    assert loader.load_metric(metric).compute(
        {"traced": None, "records": {"calls": states.calls}}) is None
    assert loader.load_metric(metric).compute(
        {"traced": run["traced"], "records": run["records"],
         ps._CACHE_KEY: None}) is None


def test_the_new_metrics_are_declared_for_the_cells_that_have_their_spans():
    bench = loader.load_benchmark()
    # the parameter server's (``ps.*``) are tests/test_ps_reference.py's,
    # the served model's (``sala.*``) benchmarks/tests/test_sala_cell.py's
    declared = {m["name"]: m for m in bench["per_layer"]
                if m["source"] == "program_span"
                and not m["name"].startswith(("ps.", "sala."))}
    assert set(declared) == NEW_METRICS
    for name, m in declared.items():
        assert m["better"] == "lower"
        if name.startswith(("stream.",)):
            assert m["workloads"] == ["stream_4m"]
        if name.startswith(("collective.", "combo.")):
            assert m["workloads"] == ["rail_x4"]


# ---- stable names for device programs --------------------------------------

def test_rail_and_block_pool_programs_carry_names_of_their_own():
    from brpc_tpu.ici import block_pool, endpoint, rail
    x = jnp.zeros((2048,), jnp.uint32)
    raw = jnp.zeros((8192,), jnp.uint8)
    lowered = {
        "jit_rail_copy": endpoint._device_copy.lower(x),
        "jit_rail_multi_copy": endpoint._multi_copy.lower(x, x),
        "jit_rail_slice_chunk": rail._slice_chunk.lower(raw, 0, 1024),
        "jit_rail_cat": rail._cat.lower([raw, raw]),
        "jit_blockpool_stage": block_pool._stage.lower(x, 8192),
        "jit_blockpool_unstage": block_pool._unstage.lower(
            raw, "uint32", (2048,)),
        "jit_blockpool_slice_bytes": block_pool._slice_bytes.lower(
            raw, 0, 1024),
        "jit_blockpool_splice_bytes": block_pool._splice_bytes.lower(
            raw, raw[:16], 0),
    }
    for name, low in lowered.items():
        assert f"module @{name} " in low.as_text(), name


def test_the_serving_programs_are_named_by_scope():
    from brpc_tpu.models.runner import (TransformerConfig,
                                        TransformerRunner,
                                        init_runner_params, make_store_for)
    cfg = TransformerConfig(vocab=64, d_model=32, n_layers=2, n_heads=2,
                            n_kv_heads=2, head_dim=16, d_ff=64)
    store = make_store_for(cfg, page_tokens=4, max_blocks=8,
                           name="stages_kv")
    try:
        r = TransformerRunner(init_runner_params(cfg), cfg, store=store)
        slots = np.zeros((2,), np.int32)
        pages = np.full((2, 4), -1, np.int32)
        text = r._fns["step"].lower(
            *r._step_args(slots, slots, pages),
            **r._statics()).as_text(debug_info=True)
        assert "module @jit_runner_decode_step " in text
        assert "runner.decode_step/ops.paged_attention/" in text
        names = {k: f.__name__ for k, f in r._fns.items()}
        assert names == {"embed": "runner_prefill_embed",
                         "proj": "runner_prefill_proj",
                         "attend": "runner_prefill_attend",
                         "step": "runner_decode_step",
                         "verify": "runner_verify"}
    finally:
        store.clear()
        store.close()


# ---- the recorder on the lowered path --------------------------------------

def test_the_collective_recorder_times_the_call_to_its_result():
    """``ici_collective`` is fed the interval that ends when the merged
    result is ready, so a recorded latency can never be shorter than the
    program's own run."""
    from brpc_tpu.ici import collective
    from brpc_tpu.rpc.combo_channels import _collective_group_for
    group = _collective_group_for(jax.devices()[:4])

    def slow(x):
        return jax.lax.fori_loop(0, 200_000, lambda i, v: v * 1.0000001 + i,
                                 x)
    x = jnp.ones((256,), jnp.float32)
    jax.block_until_ready(group.parallel_apply(slow, x))      # compile
    n0, sum0, _ = collective._lowered_latency.snapshot()
    t = time.monotonic()
    out = group.parallel_apply(slow, x)
    took_us = (time.monotonic() - t) * 1e6
    ready = time.monotonic()
    jax.block_until_ready(out)
    # nothing was left to wait for, and the recorder got that interval
    assert (time.monotonic() - ready) * 1e6 < 0.2 * took_us + 2_000
    n1, sum1, _ = collective._lowered_latency.snapshot()
    assert n1 - n0 == 1
    assert 0.5 * took_us - 2_000 <= sum1 - sum0 <= took_us + 1

"""Native flight recorder tests (ISSUE 15; src/cc/butil/flight.{h,cc},
brpc_tpu/butil/flight.py, the /flightrecorder console page).

Covers the satellite checklist: ring semantics (wrap/overwrite-oldest,
concurrent writers, dump-while-writing consistency, the enabled-flag
no-op), the forced-stall wedge autopsy (a WedgeGuard deadline miss must
dump a flight tail that NAMES the stalled worker and its last event),
the /flightrecorder route matrix + ?fmt=json, the /brpc_metrics export,
and the syscall-attribution counters (ROADMAP 1(e)).
"""
import json
import http.client
import time

import pytest

import brpc_tpu as brpc
from brpc_tpu._core import core, core_init
from brpc_tpu.butil import flight
from tests.wedge_guard import WedgeGuard

RING_CAP = 2048  # butil::flight::kRingCap

guard = WedgeGuard("flight recorder native entry", deadline_s=60.0)


@pytest.fixture(scope="module", autouse=True)
def _core():
    core_init(num_workers=4, num_dispatchers=1)
    flight.set_enabled(True)
    yield
    flight.set_enabled(True)


def _emit_on_fresh_thread(n, tag):
    """Record n probe events on a brand-new thread — a fresh, empty
    ring whose contents the test fully controls.  Guarded: a wedged
    native entry must skip, not hang the suite."""
    t = guard.start_thread(core.brpc_flight_selftest_emit, n, tag)
    guard.join_thread(t, what="flight selftest emit")


# ---------------------------------------------------------------------------
# ring semantics
# ---------------------------------------------------------------------------

def test_events_parse_and_carry_known_kinds():
    guard.deadline(core.brpc_flight_selftest_emit, 10, 0xE1)
    evs = flight.events(4096)
    assert evs, "no events after an explicit emit"
    mine = [e for e in evs if e["kind"] == "probe" and e["a"] == 0xE1]
    assert len(mine) == 10
    assert [e["b"] for e in mine] == list(range(10))
    for e in evs:
        assert set(e) == {"ts_us", "tid", "thread", "kind", "a", "b"}
        assert e["kind"] != "?", e


def test_ring_wraps_overwriting_oldest():
    n = RING_CAP * 2 + RING_CAP // 2
    _emit_on_fresh_thread(n, 0x77)
    mine = [e for e in flight.events(4096) if e["a"] == 0x77]
    # only the newest kRingCap survive, and they are exactly the tail
    assert len(mine) == RING_CAP
    assert {e["b"] for e in mine} == set(range(n - RING_CAP, n))


def test_overwrite_accounting_in_thread_table():
    n = RING_CAP + 1000
    _emit_on_fresh_thread(n, 0x88)
    rows = [t for t in flight.threads() if t["events"] == n]
    assert rows, "no thread row with the emitted event count"
    assert rows[0]["dropped"] == n - RING_CAP
    assert rows[0]["last"] == "probe"
    # the emitter has exited, but Thread.join() returns before the OS
    # thread has run the destructor that retires its ring (TlsHolder)
    deadline = time.monotonic() + 5
    while rows[0]["live"] and time.monotonic() < deadline:
        time.sleep(0.005)
        rows = [t for t in flight.threads() if t["events"] == n]
    assert rows and not rows[0]["live"]
    assert rows[0]["age_us"] >= 0


def test_concurrent_writers_with_dump_while_writing():
    """4 writers at full tilt while this thread dumps continuously:
    every dump parses, every event is consistent (per-thread probe
    sequence numbers strictly increase within one dump), and the final
    accounting is exact."""
    before = flight.stats()["events"]
    per = 30_000
    tags = [0xC0 + i for i in range(4)]
    ts = [guard.start_thread(core.brpc_flight_selftest_emit, per, tg)
          for tg in tags]
    poll_deadline = time.monotonic() + 60
    while True:   # at least once: a starved poller can find them done
        evs = flight.events(512)
        by_tid = {}
        for e in evs:
            if e["kind"] != "probe" or e["a"] not in tags:
                continue
            prev = by_tid.get(e["tid"])
            assert prev is None or e["b"] > prev, \
                (f"torn/duplicated event in dump: tid {e['tid']} "
                 f"b={e['b']} after {prev}")
            by_tid[e["tid"]] = e["b"]
        flight.threads()   # table reads race the writers too
        if not any(t.is_alive() for t in ts) or \
                time.monotonic() >= poll_deadline:
            break
    for t in ts:
        guard.join_thread(t, what="flight concurrent writer")
    delta = flight.stats()["events"] - before
    assert delta >= len(tags) * per


def test_ring_recycling_bounds_population():
    """Serving spawns a thread per request today; thread CHURN must not
    grow the ring population (exited threads' rings recycle) and the
    cumulative event counter must survive recycling."""
    before = flight.stats()
    for _ in range(20):
        _emit_on_fresh_thread(100, 0x99)
    after = flight.stats()
    # sequential short-lived threads reuse retired rings rather than
    # registering 20 new ones
    assert after["threads"] <= before["threads"] + 2, (before, after)
    assert after["events"] >= before["events"] + 20 * 100


def test_disabled_flag_is_a_recording_no_op():
    flight.set_enabled(False)
    try:
        assert not flight.enabled()
        before = flight.stats()["events"]
        guard.deadline(core.brpc_flight_selftest_emit, 1000, 0xDD)
        assert flight.stats()["events"] == before
        assert not [e for e in flight.events(4096) if e["a"] == 0xDD]
    finally:
        flight.set_enabled(True)
    assert flight.enabled()


def test_reloadable_flag_drives_the_native_gate():
    from brpc_tpu.flags import set_flag
    try:
        set_flag("flight_recorder_enabled", False)
        flight.apply_flag()
        assert not flight.enabled()
    finally:
        set_flag("flight_recorder_enabled", True)
        flight.apply_flag()
    assert flight.enabled()


# ---------------------------------------------------------------------------
# wedge autopsy: a deadline miss names the stalled worker
# ---------------------------------------------------------------------------

def test_forced_stall_dump_names_stalled_worker(capsys, tmp_path,
                                                monkeypatch):
    """The acceptance path: a fault-injected native delay occupies one
    executor worker; the guarded entry blows its (deliberately short)
    deadline, and the wedge_guard dump must name the stalled worker
    thread and its last event (the 0x57a11 stall marker) — on stderr
    AND in the autopsy artifact file that survives pytest capture."""
    monkeypatch.setenv("BRPC_WEDGE_DUMP_DIR", str(tmp_path))
    g = WedgeGuard("forced native stall", deadline_s=0.8)
    with pytest.raises(pytest.skip.Exception):
        g.deadline(core.brpc_flight_stall_probe, 2500)
    err = capsys.readouterr().err
    assert "native flight recorder dump" in err
    assert "last event of every native thread" in err
    # the per-thread table: a live worker whose LAST event is the stall
    # marker probe, stalled for at least the guard deadline
    stalled = [ln for ln in err.splitlines()
               if "worker/" in ln and "last=probe" in ln]
    assert stalled, f"no stalled-worker row in dump:\n{err}"
    # the merged tail carries the marker event itself
    assert "a=0x57a11" in err
    # the lock witness still rides along (ISSUE 14 contract preserved)
    assert "lock-order witness dump" in err
    # the artifact survives capture: same dump, on disk
    arts = list(tmp_path.glob("wedge_*.log"))
    assert arts, "no autopsy artifact written"
    text = arts[0].read_text()
    assert "a=0x57a11" in text and "worker/" in text


def test_suite_stall_watchdog_dump(tmp_path, monkeypatch, capsys):
    """The conftest watchdog's dump path: when the suite stalls past
    the window (the hard-wedge class that outlives every per-call
    guard), the autopsy artifact lands on disk and names the test the
    run stalled inside."""
    import time
    from tests import conftest as cft
    monkeypatch.setenv("BRPC_WEDGE_DUMP_DIR", str(tmp_path))
    monkeypatch.setitem(cft._watchdog_state, "t", time.monotonic() - 42)
    monkeypatch.setitem(cft._watchdog_state, "test",
                        "tests/test_demo.py::test_wedged")
    cft._watchdog_dump()
    capsys.readouterr()
    arts = list(tmp_path.glob("wedge_*.log"))
    assert arts, "watchdog wrote no autopsy artifact"
    text = arts[0].read_text()
    assert "suite watchdog" in text
    assert "tests/test_demo.py::test_wedged" in text
    assert "native flight recorder dump" in text
    assert "worker/" in text


# ---------------------------------------------------------------------------
# syscall attribution (ROADMAP 1(e))
# ---------------------------------------------------------------------------

class Hello(brpc.Service):
    @brpc.method(request="json", response="json")
    def Say(self, cntl, req):
        return {"hello": (req or {}).get("name", "world")}


@pytest.fixture(scope="module")
def server():
    srv = brpc.Server()
    srv.add_service(Hello())
    srv.start("127.0.0.1", 0)
    ch = brpc.Channel(f"127.0.0.1:{srv.port}", timeout_ms=5000)
    ch.call_sync("Hello", "Say", {"name": "x"}, serializer="json")
    yield srv
    srv.stop()
    srv.join()   # Server.join is internally bounded (wedge-hygiene)


def _get(server, path):
    c = http.client.HTTPConnection("127.0.0.1", server.port, timeout=10)
    c.request("GET", path)
    r = c.getresponse()
    body = r.read()
    c.close()
    return r.status, body


def test_syscall_counters_attribute_rpc_traffic(server):
    before = flight.syscall_counters()
    hist_before = sum(flight.write_size_hist().values())
    ch = brpc.Channel(f"127.0.0.1:{server.port}", timeout_ms=5000)
    for _ in range(5):
        ch.call_sync("Hello", "Say", {"name": "sys"}, serializer="json")
    after = flight.syscall_counters()
    assert after["write_syscalls"] > before["write_syscalls"]
    assert after["read_syscalls"] > before["read_syscalls"]
    # every counted write landed in exactly one histogram bucket
    assert sum(flight.write_size_hist().values()) > hist_before
    assert set(flight.write_size_hist()) == set(flight.WRITE_HIST_LABELS)


def test_per_socket_syscalls(server):
    ch = brpc.Channel(f"127.0.0.1:{server.port}", timeout_ms=5000)
    ch.call_sync("Hello", "Say", {"name": "per-sock"}, serializer="json")
    sids = list(server.connections())
    assert sids
    stats = [flight.socket_syscalls(sid) for sid in sids]
    stats = [s for s in stats if s is not None]
    assert stats
    assert any(s["read_syscalls"] > 0 for s in stats)
    # a stale id yields None, not garbage
    assert flight.socket_syscalls(0xFFFFFFFF00000000) is None


# ---------------------------------------------------------------------------
# /flightrecorder console page + /brpc_metrics export
# ---------------------------------------------------------------------------

def test_flightrecorder_page_text(server):
    status, body = _get(server, "/flightrecorder")
    assert status == 200
    text = body.decode()
    assert "flight recorder: ENABLED" in text
    assert "per-thread state" in text
    assert "merged event tail" in text
    assert "worker/" in text
    assert "syscalls:" in text


def test_flightrecorder_page_json(server):
    status, body = _get(server, "/flightrecorder?fmt=json&limit=20")
    assert status == 200
    snap = json.loads(body)
    assert snap["available"] and snap["enabled"]
    assert snap["stats"]["events"] > 0
    assert snap["stats"]["threads"] > 0
    assert len(snap["events"]) <= 20
    names = {t["thread"] for t in snap["threads"]}
    assert any(n.startswith("worker/") for n in names)
    assert any(n.startswith("epoll/") for n in names)
    assert "timer" in names or "ext" in names
    assert snap["syscalls"]["write_syscalls"] > 0
    assert set(snap["bytes_per_write"]) == set(flight.WRITE_HIST_LABELS)
    for e in snap["events"]:
        assert set(e) == {"ts_us", "tid", "thread", "kind", "a", "b"}


def test_flightrecorder_limit_bounds_tail(server):
    _, b5 = _get(server, "/flightrecorder?fmt=json&limit=5")
    assert len(json.loads(b5)["events"]) <= 5
    # bad limit falls back instead of erroring
    status, _ = _get(server, "/flightrecorder?limit=bogus")
    assert status == 200


def test_flight_and_syscall_vars_on_metrics(server):
    status, body = _get(server, "/brpc_metrics")
    assert status == 200
    text = body.decode()
    assert "flight_events_recorded" in text
    assert "socket_write_syscalls" in text
    assert "socket_read_syscalls" in text
    assert "socket_write_batch_hits" in text
    assert 'socket_bytes_per_write{le="64"}' in text

"""The engine's ONE emit drainer (ISSUE 39): a decode step's tokens of
every ``Serving.Generate`` stream leave as one run of frames a
connection, written by one thread an engine; a plain callable keeps a
thread of its own.

What must hold exactly as with a thread a request: a request's tokens
in order, each log-probability with its own token, ``{"done": true}``
last and ``on_done`` once; the step loop never waits for a consumer,
and a stream whose window is full stalls only itself (its tokens wait
in its own ring, the ring overflows, the engine cuts it with
EOVERCROWDED); a write that fails retires just that request; no thread
and no native ring is left behind by a close, a cancel or a takeover.
"""
import contextlib
import gc
import json
import threading
import time

import numpy as np
import pytest

import brpc_tpu as brpc
from brpc_tpu import errors, fault, flags, native_path
from brpc_tpu.models.runner import ModelRunner
from brpc_tpu.rpc import stream as stream_mod
from brpc_tpu.rpc.controller import Controller
from brpc_tpu.rpc.transport import Transport
from brpc_tpu.serving import DecodeEngine, register_serving
from brpc_tpu.serving.engine import MessageSink
from brpc_tpu.serving.service import _GenerateSink

from testutil import wait_until


class _LpRunner(ModelRunner):
    """The next token is ``token + 1`` and its log-probability
    ``-(token + 1) / 1024`` (so a log-probability names its token); a
    step takes ``pace_s``."""

    def __init__(self, pace_s=0.002):
        self.pace_s = pace_s

    def dispatch_step(self, tokens, positions, pages, seqs=None, prev=None,
                      fed=None):
        return np.asarray(tokens, np.int32) + 1

    def complete_step(self, handle):
        time.sleep(self.pace_s)
        return handle, None, -handle.astype(np.float32) / 1024


class _Client(brpc.StreamHandler):
    """One generation at the client: every message with the time it
    came; ``block`` (an Event), where given, holds the handler (and so
    the stream's feedback) at the first message until it is set."""

    def __init__(self, block=None):
        self.msgs: list = []
        self.times: list = []
        self.block = block
        self.first = threading.Event()
        self.done = threading.Event()
        self.closed = threading.Event()
        self.stream = None

    def on_received_messages(self, stream, messages):
        for m in messages:
            self.msgs.append(json.loads(m))
            self.times.append(time.monotonic())
            self.first.set()
            if self.block is not None:
                self.block.wait(60)
            if self.msgs[-1].get("done"):
                self.done.set()

    def on_closed(self, stream):
        self.closed.set()
        self.done.set()

    @property
    def tokens(self):
        return [m["token"] for m in self.msgs if "token" in m]


@contextlib.contextmanager
def _served(engine):
    s = brpc.Server()
    register_serving(s, engine=engine)
    s.start("127.0.0.1", 0)
    ch = brpc.Channel(f"127.0.0.1:{s.port}", timeout_ms=20000, max_retry=0)
    try:
        yield ch
    finally:
        s.stop()
        s.join()  # brpc-check: allow(wedge-hygiene) — a stopped server's join is bounded by its own graceful_quit_timeout_s


def _generate(ch, prompt, n, client, logprobs=True):
    cntl = brpc.Controller()
    client.stream = brpc.stream_create(cntl, client)
    resp = ch.call_sync("Serving", "Generate",
                        {"prompt": prompt, "max_new_tokens": n,
                         "logprobs": logprobs},
                        serializer="json", cntl=cntl)
    assert resp["accepted"] is True
    return client


def _emit_threads():
    return {t for t in threading.enumerate()
            if t.name.startswith("serving-emit") and t.is_alive()}


def _rings():
    gc.collect()
    return native_path.tokring_live()


@pytest.fixture
def baseline():
    """The emit threads and native rings alive before the test are all
    that is left once it has returned (its engine dropped: a crashed
    engine keeps its slots in the exception it holds)."""
    threads, rings = _emit_threads(), _rings()
    yield
    assert wait_until(lambda: _emit_threads() <= threads, 10), \
        [t.name for t in _emit_threads() - threads]
    assert wait_until(lambda: _rings() <= rings, 10), \
        f"{_rings() - rings} native rings leaked"


def _check_stream(client, prompt, n, err=None):
    """Tokens in order, each log-probability its own token's, the
    terminal last and alone."""
    first = prompt[-1] + 1
    assert client.tokens == list(range(first, first + n))
    for m in client.msgs[:-1]:
        assert m["logprob"] == pytest.approx(-m["token"] / 1024)
    last = client.msgs[-1]
    assert last.get("done") is True and last.get("error") == err
    assert sum(1 for m in client.msgs if m.get("done")) == 1


# ---------------------------------------------------------------------------
# (1) a step's tokens leave as one run of frames a connection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("native", [True, False])
def test_streams_of_one_connection_leave_as_runs(native, baseline):
    was = flags.get_flag("native_hot_path_enabled", True)
    flags.set_flag("native_hot_path_enabled", native)
    runner = _LpRunner()
    eng = DecodeEngine(runner=runner, num_slots=8, kv_bytes_per_slot=1024,
                       name=f"t_drain_runs_{int(native)}")
    n_req, n_tok = 8, 60
    try:
        with _served(eng) as ch:
            clients = [_generate(ch, [100 * i], n_tok, _Client())
                       for i in range(n_req)]
            for c in clients:
                assert c.done.wait(30)
            for i, c in enumerate(clients):
                _check_stream(c, [100 * i], n_tok)
                assert c.closed.wait(10)    # the CLOSE after the last
            st = eng.stats()
            assert st["tokens"] == n_req * n_tok
            # about one socket write a step (a drainer that falls
            # behind takes two steps in one; of two requests that end
            # in one step the second's terminal may take a pass of its
            # own), never one a token
            assert 0 < st["emit_runs"] <= st["steps"] + n_req
            assert st["emit_tokens_per_run"] >= n_req / 2
            assert eng.join_idle(10)
    finally:
        flags.set_flag("native_hot_path_enabled", was)
        eng.close()


def test_churn_under_a_short_switch_interval_loses_nothing():
    """Lanes join and leave the drainer all the time (24 callers, 6
    slots, requests of 3-12 tokens) with the interpreter switching
    threads every 10 us: every stream is whole, in order and ended
    once, and no lane is left."""
    import sys
    runner = _LpRunner(pace_s=0.0)
    eng = DecodeEngine(runner=runner, num_slots=6, kv_bytes_per_slot=1024,
                       name="t_drain_churn")
    bad, n_done = [], [0]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with _served(eng) as ch:
            stop = time.monotonic() + 3.0

            def caller(k):
                j = 0
                while time.monotonic() < stop:
                    n = 3 + (7 * k + j) % 10
                    c = _generate(ch, [1000 * k + j], n, _Client())
                    if not c.done.wait(30):
                        bad.append((k, j, "hung"))
                        return
                    try:
                        _check_stream(c, [1000 * k + j], n)
                    except AssertionError as e:
                        bad.append((k, j, str(e)[:200]))
                    j += 1
                    n_done[0] += 1
            threads = [threading.Thread(target=caller, args=(k,))
                       for k in range(24)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert not any(t.is_alive() for t in threads)
            assert not bad, bad[:3]
            assert n_done[0] > 24
            assert eng.join_idle(10)
            assert wait_until(lambda: not eng._lanes, 10)
            st = eng.stats()
            assert st["emit_cut"] == 0 and st["retired"] == n_done[0]
    finally:
        sys.setswitchinterval(was)
        eng.close()


# ---------------------------------------------------------------------------
# (2) a stream whose window is full stalls only itself
# ---------------------------------------------------------------------------

@pytest.fixture
def small_window(monkeypatch):
    """``Serving.Generate`` accepts its stream with a window of 256
    bytes: half a dozen token messages."""
    accept = Controller.accept_stream
    monkeypatch.setattr(
        Controller, "accept_stream",
        lambda self, handler=None, max_buf_size=256, device=None:
        accept(self, handler, 256, device))


def test_full_window_is_cut_and_its_step_mates_go_on(
        small_window, monkeypatch, baseline):
    # the window may stay full for as long as this test likes
    monkeypatch.setattr(_GenerateSink, "STALL_S", 60.0)
    runner = _LpRunner()
    eng = DecodeEngine(runner=runner, num_slots=2, emit_buffer=8,
                       kv_bytes_per_slot=1024, name="t_drain_window")
    release = threading.Event()
    try:
        with _served(eng) as ch:
            # a consumer that holds its handler at the first message,
            # and a fast one beside it on the same connection
            slow = _generate(ch, [0], 10_000, _Client(block=release))
            assert slow.first.wait(20)
            fast = _generate(ch, [500], 200, _Client())
            assert fast.done.wait(30)
            _check_stream(fast, [500], 200)
            assert wait_until(lambda: eng.stats()["emit_cut"] == 1, 20)
            # the drainer never waited for the full window beside it
            # (the bound tests/testutil.py's scenario holds a fast
            # reader to)
            elapsed = fast.times[-1] - fast.times[0]
            assert elapsed < 5.0, \
                f"fast reader stalled {elapsed:.1f}s behind the full window"
            # the cut request's tokens were bounded by its own ring
            # and window, and its slot is free
            assert wait_until(lambda: eng.active_count() == 0, 10)
            held = len(slow.msgs)
            assert held == 1
            # once the consumer drains again, what was buffered comes
            # in order, and the terminal says why it ends there
            release.set()
            assert slow.done.wait(30)
            toks = slow.tokens
            assert toks == list(range(1, 1 + len(toks)))
            assert 8 <= len(toks) <= 8 + 32 + 8
            last = slow.msgs[-1]
            assert last["done"] and last["error"] == errors.EOVERCROWDED
            assert slow.closed.wait(10)
            st = eng.stats()
            assert st["emit_cut"] == 1 and st["retired"] == 2
    finally:
        release.set()
        eng.close()


def test_window_that_stays_full_gives_the_request_up(
        small_window, monkeypatch, baseline):
    monkeypatch.setattr(_GenerateSink, "STALL_S", 0.3)
    runner = _LpRunner()
    eng = DecodeEngine(runner=runner, num_slots=2, emit_buffer=4096,
                       kv_bytes_per_slot=1024, name="t_drain_stall")
    release = threading.Event()
    try:
        with _served(eng) as ch:
            slow = _generate(ch, [0], 1_000_000, _Client(block=release))
            assert slow.first.wait(20)
            # its ring never overflows (4,096): what ends it is the
            # window that took nothing for STALL_S
            assert wait_until(lambda: eng.active_count() == 0, 20)
            assert eng.stats()["emit_cut"] == 0
            release.set()
            assert slow.closed.wait(20)     # closed, not wedged
            assert not any(m.get("done") for m in slow.msgs)
            toks = slow.tokens
            assert toks == list(range(1, 1 + len(toks)))
    finally:
        release.set()
        eng.close()


# ---------------------------------------------------------------------------
# (3) a callable that blocks delays no stream
# ---------------------------------------------------------------------------

def test_blocking_callable_delays_no_stream(baseline):
    runner = _LpRunner()
    eng = DecodeEngine(runner=runner, num_slots=4, kv_bytes_per_slot=1024,
                       name="t_drain_blocking")
    gate = threading.Event()
    got, done = [], threading.Event()

    def blocking_emit(tok):
        gate.wait(60)       # a chunked HTTP reply to a slow reader
        got.append(tok)
    try:
        with _served(eng) as ch:
            eng.submit([7000], 20, blocking_emit, lambda e: done.set())
            assert wait_until(lambda: eng.active_count() == 1, 20)
            # three whole generations begin and end while the callable
            # sits in its first call
            clients = [_generate(ch, [100 * i], 60, _Client())
                       for i in range(3)]
            for c in clients:
                assert c.done.wait(30)
            assert got == [] and not done.is_set()
            for i, c in enumerate(clients):
                _check_stream(c, [100 * i], 60)
            gate.set()
            assert done.wait(30)
            assert got == list(range(7001, 7021))
    finally:
        gate.set()
        eng.close()


# ---------------------------------------------------------------------------
# (4) one thread for the streams; none and no ring left behind
# ---------------------------------------------------------------------------

def test_one_drainer_thread_for_thirty_two_streams(baseline):
    before = _emit_threads()
    runner = _LpRunner()
    eng = DecodeEngine(runner=runner, num_slots=32, kv_bytes_per_slot=1024,
                       name="t_drain_32")
    try:
        with _served(eng) as ch:
            clients = [_generate(ch, [1000 * i], 10_000, _Client())
                       for i in range(32)]
            assert wait_until(lambda: eng.active_count() == 32, 30)
            assert wait_until(
                lambda: all(len(c.msgs) > 5 for c in clients), 30)
            mine = _emit_threads() - before
            assert [t.name for t in mine] == ["serving-emit-drain-t_drain_32"]
            # a cancel: one client goes away, its request alone ends
            clients[3].stream.close()
            assert wait_until(lambda: eng.active_count() == 31, 20)
            n5 = len(clients[5].msgs)
            assert wait_until(lambda: len(clients[5].msgs) > n5 + 5, 20)
            assert len(_emit_threads() - before) == 1
            # close(): every stream is told, in order, and ends
            eng.close()
            for i, c in enumerate(clients):
                if i == 3:
                    continue
                assert c.done.wait(20)
                toks = c.tokens
                assert toks == list(range(1000 * i + 1,
                                          1000 * i + 1 + len(toks)))
                assert c.msgs[-1].get("error") == errors.ELOGOFF
    finally:
        eng.close()


def test_takeover_leaves_no_thread_and_no_ring(baseline):
    """A supervised crash: the engine stops with its slots intact, the
    new owner takes them over and ends each request through its ring
    (what ``EngineSupervisor._recover`` does); the drainer flushes what
    was decoded, tells every stream, and goes."""
    crashed = threading.Event()
    runner = _LpRunner()
    eng = DecodeEngine(runner=runner, num_slots=4, kv_bytes_per_slot=1024,
                       on_crash=lambda e, exc: crashed.set(),
                       name="t_drain_takeover")
    try:
        with _served(eng) as ch:
            clients = [_generate(ch, [100 * i], 10_000, _Client())
                       for i in range(4)]
            assert wait_until(
                lambda: all(len(c.msgs) > 5 for c in clients), 30)
            plan = fault.FaultPlan(39)
            plan.on("serving.step", fault.ERROR, times=1)
            with fault.injected(plan):
                assert crashed.wait(20)
            stolen, waiters = eng.takeover()
            assert len(stolen) == 4 and not waiters
            err = errors.RpcError(errors.ELOGOFF, "engine restarting")
            for slot in stolen:
                slot.block.free()
                slot.req.buf.push_terminal(err)
            del stolen, slot    # they hold the requests, and so the rings
            for i, c in enumerate(clients):
                assert c.done.wait(20)
                toks = c.tokens
                assert toks == list(range(100 * i + 1,
                                          100 * i + 1 + len(toks)))
                assert c.msgs[-1].get("error") == errors.ELOGOFF
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# (5) a write that fails retires just that request
# ---------------------------------------------------------------------------

def test_failed_write_retires_just_that_request(baseline):
    """A peer that is gone (its stream closed under the drainer) ends
    its own request and no other: its step-mate on the same connection
    streams to its end."""
    runner = _LpRunner()
    eng = DecodeEngine(runner=runner, num_slots=4, kv_bytes_per_slot=1024,
                       name="t_drain_deadpeer")
    try:
        with _served(eng) as ch:
            a = _generate(ch, [100], 10_000, _Client())
            b = _generate(ch, [200], 300, _Client())
            assert wait_until(lambda: len(a.msgs) > 5 and len(b.msgs) > 5,
                              30)
            # the peer of `a` goes away: its stream closes under the
            # drainer, whose next run for it fails
            a.stream.close()
            assert wait_until(lambda: eng.active_count() == 1, 20)
            assert b.done.wait(30)
            _check_stream(b, [200], 300)
            toks = a.tokens
            assert toks == list(range(101, 101 + len(toks)))
            assert eng.join_idle(10)
            st = eng.stats()
            assert st["retired"] == 2 and st["emit_cut"] == 0
    finally:
        eng.close()


def test_refused_socket_write_closes_the_run_s_streams(monkeypatch):
    """``write_runs``: a connection that refuses the run closes every
    stream that rode it and reports -1 for each; a stream of another
    connection in the same call is written."""
    calls = []

    class _T:
        def write_frames(self, sid, frames):
            calls.append((sid, len(frames)))
            return -1 if sid == 7 else 0
    monkeypatch.setattr(Transport, "instance", staticmethod(lambda: _T()))
    dead = [stream_mod.Stream(9001 + i, None) for i in range(2)]
    live = stream_mod.Stream(9003, None)
    for s, sid in ((dead[0], 7), (dead[1], 7), (live, 8)):
        s.remote_id = 5
        s._sid = sid
    taken, writes = stream_mod.write_runs(
        [(dead[0], [b"a", b"b"]), (live, [b"c"]), (dead[1], [b"d"])])
    assert taken == [-1, 1, -1] and writes == 2
    assert sorted(calls) == [(7, 3), (8, 1)]
    assert dead[0].closed and dead[1].closed and not live.closed
    # and a closed stream takes nothing more
    assert stream_mod.write_runs([(dead[0], [b"e"])]) == ([-1], 0)


# ---------------------------------------------------------------------------
# the batched entry of rpc/stream, and the sink's two forms
# ---------------------------------------------------------------------------

def test_write_runs_takes_what_the_window_holds_in_order(monkeypatch):
    sent = []

    class _T:
        def write_frames(self, sid, frames):
            sent.append((sid, frames))
            return 0
    monkeypatch.setattr(Transport, "instance", staticmethod(lambda: _T()))
    s1 = stream_mod.Stream(9101, None, max_buf_size=10)
    s2 = stream_mod.Stream(9102, None)
    unbound = stream_mod.Stream(9103, None)
    for s in (s1, s2):
        s.remote_id = 40 + s.stream_id
        s._sid = 3
    msgs = [b"aaaa", b"bbbb", b"cccc"]
    taken, writes = stream_mod.write_runs(
        [(s1, msgs), (unbound, [b"x"]), (s2, [b"dd", b"ee"])])
    # 10 bytes of window take two 4-byte messages; an unbound stream
    # takes none; ONE write for the connection, in the call's order
    assert taken == [2, 0, 2] and writes == 1
    (sid, frames), = sent
    assert sid == 3 and [b for _, b in frames] == [b"aaaa", b"bbbb",
                                                   b"dd", b"ee"]
    from brpc_tpu.rpc import meta as M
    seqs = [(M.RpcMeta.decode(m).stream_id, M.RpcMeta.decode(m).stream_seq)
            for m, _ in frames]
    assert seqs == [(s1.remote_id, 1), (s1.remote_id, 2),
                    (s2.remote_id, 1), (s2.remote_id, 2)]
    # feedback returns credit: the rest goes out, numbered on
    s1._on_feedback(8)
    taken, writes = stream_mod.write_runs([(s1, msgs[2:])])
    assert taken == [1] and writes == 1
    assert M.RpcMeta.decode(sent[-1][1][0][0]).stream_seq == 3
    # the blocking writer sees the same window and the same numbers
    assert s1._produced == 12 and s1._send_seq == 4


def test_generate_sink_is_byte_for_byte_the_old_wire():
    class _S:
        closed = False
        wrote, closes = [], 0

        def write(self, data, timeout_s=None):
            self.wrote.append((data, timeout_s))

        def close(self):
            self.closes += 1
            self.closed = True
    class _Plane:
        noted = []

        def note_generation(self, key):
            self.noted.append(key)
    st = _S()
    sink = _GenerateSink(st, True, _Plane(), "modela")
    assert isinstance(sink, MessageSink)
    assert sink.token_message(5, -0.25) == b'{"token": 5, "logprob": -0.25}'
    assert sink.token_message(5, None) == b'{"token": 5, "logprob": null}'
    assert _GenerateSink(st, False).token_message(5, -1.0) \
        == b'{"token": 5}'
    assert sink.done_message(None) == b'{"done": true}'
    err = errors.RpcError(errors.ELOGOFF, "engine closed")
    assert json.loads(sink.done_message(err)) == {
        "done": True, "error": errors.ELOGOFF, "error_text": "engine closed"}
    # the blocking form: what a supervisor's relay calls
    sink(6, -0.5)
    sink.on_done(None)
    assert st.wrote == [(b'{"token": 6, "logprob": -0.5}', 2.0),
                        (b'{"done": true}', 2.0)]
    assert st.closes == 1 and _Plane.noted == ["modela"]
    # after the drainer (the stream is closed): only the bookkeeping,
    # and a failed generation warms no deployment
    sink.on_done(err)
    sink.on_done(None)
    assert len(st.wrote) == 2 and st.closes == 1
    assert _Plane.noted == ["modela", "modela"]


def test_sink_behind_an_engine_shaped_relay_keeps_a_thread(baseline):
    """A supervisor hands the engine a callable of its own that calls
    the sink: a plain callable, so an emitter thread a request and the
    sink's blocking form; the stream reads the same."""
    before = _emit_threads()
    runner = _LpRunner()
    eng = DecodeEngine(runner=runner, num_slots=2, kv_bytes_per_slot=1024,
                       name="t_drain_relay")
    names = []

    class _Relay:
        store = None

        def submit(self, prompt, n, emit, on_done, **kw):
            def relay(tok, lp=None):
                names.append(threading.current_thread().name)
                emit(tok, lp)
            return eng.submit(prompt, n, relay, on_done, **kw)
    s = brpc.Server()
    register_serving(s, engine=_Relay())
    s.start("127.0.0.1", 0)
    try:
        ch = brpc.Channel(f"127.0.0.1:{s.port}", timeout_ms=20000)
        c = _generate(ch, [300], 30, _Client())
        assert c.done.wait(30)
        _check_stream(c, [300], 30)
        assert c.closed.wait(10)
        assert len(set(names)) == 1 and names[0].startswith("serving-emit-")
        assert "drain" not in names[0]
        assert eng.stats()["emit_runs"] == 0
    finally:
        s.stop()
        s.join()  # brpc-check: allow(wedge-hygiene) — a stopped server's join is bounded by its own graceful_quit_timeout_s
        eng.close()

"""Native token hot path (ISSUE 9): the de-GIL'd emit rings, batch
assembly, page-table gather and span queue.

Covers the contracts the rewrite must NOT change:

  * TokenRing preserves the PR 3 _EmitBuf semantics natively — bounded,
    push never blocks, tokens always flush before the terminal, the
    terminal is exactly-once (native marker and Python error object
    agree on the winner);
  * a wedged consumer on the NATIVE ring is cut with EOVERCROWDED while
    a fast reader beside it streams at full speed (the PR 3 guarantee,
    now native), and no ring leaks (global live-ring baseline);
  * the pure-Python fallback (`native_hot_path_enabled` off) produces
    BIT-EXACT identical streams, so platforms without the .so pass
    tier-1 and the flag is a safe live kill switch;
  * brpc_batch_pad / brpc_page_table_fill match their numpy reference
    implementations element-for-element;
  * the native span queue drains FIFO with no span lost or duplicated.
"""
import ctypes
import gc
import threading
import time

import numpy as np
import pytest

from brpc_tpu import errors, flags, native_path
from brpc_tpu.serving import DecodeEngine

from testutil import wait_until, wedged_consumer_is_cut

pytestmark = pytest.mark.skipif(
    native_path._core_lib() is None,
    reason="native core unavailable (pure-Python fallback platform)")


@pytest.fixture
def native_flag():
    was = flags.get_flag("native_hot_path_enabled", True)
    flags.set_flag("native_hot_path_enabled", True)
    yield
    flags.set_flag("native_hot_path_enabled", was)


class _Sink:
    def __init__(self):
        self.tokens: list = []
        self.done = threading.Event()
        self.err = "unset"

    def emit(self, tok):
        self.tokens.append(tok)

    def on_done(self, err):
        self.err = err
        self.done.set()


def _live():
    gc.collect()
    return native_path.tokring_live()


# ---------------------------------------------------------------------------
# TokenRing semantics
# ---------------------------------------------------------------------------

def test_tokring_fifo_bounded_and_nonblocking(native_flag):
    ring = native_path.token_ring(4)
    assert ring is not None
    for t in (10, 11, 12, 13):
        assert ring.push(t)
    assert not ring.push(14), "push into a full ring must fail, not block"
    assert len(ring) == 4
    out = (ctypes.c_int32 * 8)()
    n, term, err = ring.pop_many(out, 0.0)
    assert (n, term, err) == (4, False, None)
    assert [out[i] for i in range(4)] == [10, 11, 12, 13]


def test_tokring_tokens_flush_before_terminal(native_flag):
    ring = native_path.token_ring(8)
    ring.push(1)
    ring.push(2)
    ring.push_terminal(None)
    out = (ctypes.c_int32 * 1)()
    # draining one at a time: the terminal only surfaces once the ring
    # is EMPTY — the ordering half of the exactly-once contract
    n, term, _ = ring.pop_many(out, 0.0)
    assert (n, term) == (1, False) and out[0] == 1
    n, term, _ = ring.pop_many(out, 0.0)
    assert (n, term) == (1, True) and out[0] == 2
    n, term, _ = ring.pop_many(out, 0.0)
    assert (n, term) == (0, True)


def test_tokring_pop_each_takes_every_ring_in_one_call(native_flag):
    """The emit drainer's entry: up to ``per_cap`` tokens of each ring,
    ring i's at ``out[i * per_cap]``; a terminal shows only once its
    ring is empty; a null handle is skipped; nothing blocks."""
    from brpc_tpu._core.lib import core
    a, b, c = (native_path.token_ring(8) for _ in range(3))
    for t in (1, 2, 3):
        a.push(t)
    b.push(9)
    b.push_terminal(None)
    handles = (ctypes.c_void_p * 4)(a.handle, None, b.handle, c.handle)
    out = (ctypes.c_int32 * 8)()
    counts = (ctypes.c_int32 * 4)()
    terms = (ctypes.c_uint8 * 4)()

    def pop():
        n = core.brpc_tokring_pop_each(  # brpc-check: allow(wedge-hygiene) — never blocks: a mutex section a ring
            handles, 4, out, 2, counts, terms)
        return n, list(counts), list(terms)
    assert pop() == (3, [2, 0, 1, 0], [0, 0, 1, 0])
    assert out[0:2] == [1, 2] and out[4] == 9
    assert pop() == (1, [1, 0, 0, 0], [0, 0, 1, 0]) and out[0] == 3
    c.push_terminal(errors.RpcError(errors.ELOGOFF, "close"))
    assert pop() == (0, [0, 0, 0, 0], [0, 0, 1, 1])
    assert len(a) == len(b) == len(c) == 0


def test_tokring_terminal_exactly_once_first_wins(native_flag):
    ring = native_path.token_ring(8)
    first = errors.RpcError(errors.EOVERCROWDED, "cut")
    second = errors.RpcError(errors.ELOGOFF, "close")
    ring.push_terminal(first)
    ring.push_terminal(second)   # loser: must not replace the winner
    out = (ctypes.c_int32 * 4)()
    n, term, err = ring.pop_many(out, 0.0)
    assert (n, term) == (0, True)
    assert err is first, "second push_terminal overwrote the winner"


def test_tokring_pop_wait_parks_until_push(native_flag):
    ring = native_path.token_ring(8)
    out = (ctypes.c_int32 * 4)()
    got = []

    def consumer():
        got.append(ring.pop_many(out, 5.0))

    t = threading.Thread(target=consumer)
    t.start()
    time.sleep(0.05)           # let it park in the native wait
    ring.push(42)
    t.join(10)
    assert not t.is_alive()
    n, term, _ = got[0]
    assert n == 1 and out[0] == 42 and not term


def test_tokring_live_counter_tracks_rings(native_flag):
    base = _live()
    rings = [native_path.token_ring(4) for _ in range(5)]
    assert native_path.tokring_live() == base + 5
    del rings
    assert _live() == base


# ---------------------------------------------------------------------------
# engine over the native ring
# ---------------------------------------------------------------------------

def test_engine_uses_native_ring_and_streams_bit_exact(native_flag):
    base = _live()
    eng = DecodeEngine((lambda t, p: t + 1), num_slots=2,
                       kv_bytes_per_slot=1024, name="t_native_engine")
    try:
        a = _Sink()
        eng.submit([100], 8, a.emit, a.on_done)
        # the request's buffer really is a native ring, not _EmitBuf:
        # the global live-ring counter moved above the baseline
        assert wait_until(lambda: native_path.tokring_live() > base, 10)
        assert a.done.wait(30) and a.err is None
        assert a.tokens == list(range(101, 109))
    finally:
        eng.close()
    assert wait_until(lambda: _live() == base, 10), \
        f"leaked {_live() - base} native emit rings"


def test_native_ring_wedged_consumer_cut_fast_reader_streams(native_flag):
    """The PR 3 guarantee, now native: a consumer that stops draining
    its NATIVE ring is cut with EOVERCROWDED after its buffered tokens
    flush, while a fast reader beside it streams at full speed — and
    the cut request's ring is freed (no leak)."""
    base = _live()

    def rides_a_native_ring():      # the live-ring counter moved
        assert wait_until(lambda: native_path.tokring_live() > base, 10)
    wedged_consumer_is_cut("t_native_wedge", _Sink, rides_a_native_ring)
    assert wait_until(lambda: _live() == base, 10), \
        f"leaked {_live() - base} native emit rings after the cut"


def test_python_fallback_bit_exact_and_flag_flip_safe():
    """`native_hot_path_enabled` off serves the identical stream
    through the pure-Python _EmitBuf — and flipping the flag live only
    affects NEW requests (in-flight native rings keep draining)."""
    was = flags.get_flag("native_hot_path_enabled", True)

    def run(native: bool):
        flags.set_flag("native_hot_path_enabled", native)
        eng = DecodeEngine((lambda t, p: (t * 3 + p) % 251), num_slots=2,
                           kv_bytes_per_slot=1024,
                           name=f"t_flag_{int(native)}")
        try:
            s = _Sink()
            eng.submit([7, 8, 9], 12, s.emit, s.on_done)
            assert s.done.wait(30) and s.err is None
            return list(s.tokens)
        finally:
            eng.close()

    try:
        native_toks = run(True)
        python_toks = run(False)
        assert native_toks == python_toks, \
            "fallback stream diverged from the native one"
        # flip mid-flight: a request admitted natively finishes its
        # stream natively after the flag goes off
        flags.set_flag("native_hot_path_enabled", True)
        eng = DecodeEngine((lambda t, p: t + 1), num_slots=1,
                           kv_bytes_per_slot=1024, name="t_flag_flip")
        try:
            s = _Sink()
            eng.submit([100], 40, s.emit, s.on_done)
            assert wait_until(lambda: len(s.tokens) >= 3, 20)
            flags.set_flag("native_hot_path_enabled", False)
            assert s.done.wait(30) and s.err is None
            assert s.tokens == list(range(101, 141))
            # and a NEW request under the off flag takes the Python buf
            s2 = _Sink()
            eng.submit([200], 4, s2.emit, s2.on_done)
            assert s2.done.wait(30) and s2.err is None
            assert s2.tokens == list(range(201, 205))
        finally:
            eng.close()
    finally:
        flags.set_flag("native_hot_path_enabled", was)


# ---------------------------------------------------------------------------
# batch assembly + page-table gather
# ---------------------------------------------------------------------------

def test_batch_pad_matches_numpy_reference(native_flag):
    rng = np.random.default_rng(9)
    for dtype in (np.float32, np.int32):
        rows = [np.ascontiguousarray(rng.integers(0, 100, n).astype(dtype))
                for n in (3, 7, 1, 16)]
        out = np.empty((6, 16), dtype=dtype)
        native_path.batch_pad(out, rows, [len(r) for r in rows])
        ref = np.zeros((6, 16), dtype=dtype)
        for i, r in enumerate(rows):
            ref[i, : len(r)] = r
        np.testing.assert_array_equal(out, ref)


def test_page_table_fill_matches_numpy_reference(native_flag):
    lists = [np.arange(5, dtype=np.int32),
             np.arange(100, 103, dtype=np.int32),
             np.empty(0, dtype=np.int32),
             np.arange(50, 62, dtype=np.int32)]   # truncated to width 8
    idx = [0, 2, 3, 5]
    table = np.empty((6, 8), np.int32)
    native_path.page_table_fill(table, lists, idx)
    ref = np.full((6, 8), -1, np.int32)
    for k, i in enumerate(idx):
        ids = lists[k][:8]
        ref[i, : len(ids)] = ids
    np.testing.assert_array_equal(table, ref)


# ---------------------------------------------------------------------------
# native span queue
# ---------------------------------------------------------------------------

def test_spanq_drains_fifo_exactly_once(native_flag):
    from brpc_tpu import rpcz
    fb = native_path._fastrpc_mod()
    assert fb is not None
    # hold the pause lock: the queue is process-global and a live
    # rpcz-spanq drainer (started by any earlier native submit) would
    # otherwise steal our non-Span probes mid-test AND poison the
    # recent-span store with them
    with rpcz._spanq_pause:
        fb.spanq_drain()   # clear anything a prior test queued
        objs = [object() for _ in range(64)]
        for o in objs:
            fb.spanq_push(o)
        assert fb.spanq_pending() >= 64
        got = fb.spanq_drain()
        assert got == objs, "drain lost, duplicated or reordered spans"
        assert fb.spanq_drain() == []
        assert fb.spanq_pending() == 0


def test_spanq_concurrent_push_drain_no_loss(native_flag):
    from brpc_tpu import rpcz
    fb = native_path._fastrpc_mod()
    N, n_threads = 500, 4
    seen: list = []
    stop = threading.Event()

    def drainer():
        while not stop.is_set() or fb.spanq_pending() > 0:
            seen.extend(fb.spanq_drain())

    with rpcz._spanq_pause:       # keep the live drainer off the queue
        fb.spanq_drain()
        dt = threading.Thread(target=drainer)
        dt.start()

        def pusher(base):
            for i in range(N):
                fb.spanq_push(("span", base + i))

        ts = [threading.Thread(target=pusher, args=(k * N,))
              for k in range(n_threads)]
        [t.start() for t in ts]
        [t.join(30) for t in ts]
        stop.set()
        dt.join(30)
    assert len(seen) == N * n_threads
    assert len(set(seen)) == N * n_threads, "a span was duplicated"
    # per-producer FIFO: each pusher's spans arrive in its push order
    for k in range(n_threads):
        mine = [s for s in seen if k * N <= s[1] < (k + 1) * N]
        assert mine == [("span", k * N + i) for i in range(N)]


def test_rpcz_submit_rides_native_queue_and_flush_lands_spans(native_flag):
    from brpc_tpu import rpcz
    fb = native_path.spanq()
    assert fb is not None, "flag on + lib built must route spans natively"
    was = (rpcz.enabled(), rpcz.sample_rate())
    rpcz.set_enabled(True, 1.0)
    try:
        sp = rpcz.new_span("client", "NativeQ", "Probe")
        sp.annotate("native span queue probe")
        rpcz.submit(sp)
        rpcz.flush()
        assert any(s.span_id == sp.span_id for s in rpcz.recent_spans(200))
    finally:
        rpcz.set_enabled(*was)


def test_spanq_event_wakeup_drains_well_under_old_poll_period(native_flag):
    """ISSUE 10 satellite (PR 9 follow-on d): the rpcz-spanq drainer is
    EVENT-woken — drain when nonempty, park when empty — so a submitted
    span lands in the recent-span store in wakeup latency, not a fixed
    50ms poll.  Observed PASSIVELY (no flush/recent_spans call, which
    would drain synchronously and hide a polling drainer): the old
    fixed sleep averaged ~25ms and worst-cased 50ms+; the event path
    averages ~1ms.  The 10ms average bound cleanly separates the two
    without flaking on a loaded box."""
    from brpc_tpu import rpcz
    fb = native_path.spanq()
    assert fb is not None
    was = (rpcz.enabled(), rpcz.sample_rate())
    rpcz.set_enabled(True, 1.0)

    def landed(span_id):
        with rpcz._collect_lock:
            return any(getattr(s, "span_id", 0) == span_id
                       for s in rpcz._collected)

    try:
        # first submit starts (or finds) the drainer; wait until this
        # warm span lands so the measured probes see a PARKED drainer
        warm = rpcz.new_span("client", "SpanqWake", "Warm")
        rpcz.submit(warm)
        assert wait_until(lambda: landed(warm.span_id), 10)
        lats = []
        for i in range(10):
            time.sleep(0.004)     # let the drainer park again
            sp = rpcz.new_span("client", "SpanqWake", f"Probe{i}")
            t0 = time.monotonic()
            rpcz.submit(sp)
            deadline = t0 + 5.0
            while not landed(sp.span_id):
                assert time.monotonic() < deadline, \
                    "span never reached the store without a flush"
                time.sleep(0.0005)
            lats.append(time.monotonic() - t0)
        avg = sum(lats) / len(lats)
        assert avg < 0.010, (
            f"spanq drain averaged {avg * 1e3:.1f}ms — the drainer is "
            f"polling, not event-woken (lats={['%.1f' % (l * 1e3) for l in lats]}ms)")
    finally:
        rpcz.set_enabled(*was)

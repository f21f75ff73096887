"""Shared test helpers (pytest adds tests/ to sys.path for no-package
layouts, so `from testutil import wait_until` works under both bare
pytest and python -m pytest)."""
import contextlib
import time

import numpy as np


def wait_until(pred, timeout=10.0, interval=0.01):
    """Deadline poll: True once pred() holds, False at the deadline."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def wedged_consumer_is_cut(name, sink, once_submitted=lambda: None):
    """A consumer that stops draining its BOUNDED emit buffer (8) is cut
    with EOVERCROWDED after its buffered tokens flush, and the step loop
    never blocks on it: a fast reader beside it streams 200 tokens.
    ``sink`` makes a request's sink; ``once_submitted`` runs after the
    wedged request is in.  The model is ``t + 1`` PACED: the fast request
    (tokens from 500) gets 4 ahead of its reader and no further.  A reader
    is fast only against a model that takes time: a bare ``t + 1`` filled
    the ring in the 100 us a busy machine takes to wake the emitter
    thread, and the FAST reader was cut."""
    from brpc_tpu import errors
    from brpc_tpu.serving import DecodeEngine
    slow, fast = sink(), sink()

    def step(t, p):
        served = int(np.max(np.asarray(t))) - 500
        assert wait_until(lambda: len(fast.tokens) >= served - 4, 20)
        return t + 1

    def slow_emit(tok):
        time.sleep(0.25)
        slow.tokens.append(tok)
    eng = DecodeEngine(step, num_slots=2, emit_buffer=8,
                       kv_bytes_per_slot=1024, name=name)
    try:
        eng.submit([0], 10_000, slow_emit, slow.on_done)
        once_submitted()
        assert wait_until(lambda: len(slow.tokens) >= 1, 20)
        t0 = time.monotonic()
        eng.submit([500], 200, fast.emit, fast.on_done)
        assert fast.done.wait(20) and fast.err is None
        fast_elapsed = time.monotonic() - t0
        assert fast.tokens == list(range(501, 701))
        assert fast_elapsed < 5.0, \
            f"fast reader stalled {fast_elapsed:.1f}s behind the wedged one"
        assert slow.done.wait(30)
        assert slow.err is not None and slow.err.code == errors.EOVERCROWDED
        assert eng.stats()["emit_cut"] == 1
        assert eng.join_idle(10)
    finally:
        eng.close()


@contextlib.contextmanager
def batcher_slot_held(b, n_queued, timeout=20.0):
    """An eager DynamicBatcher's execution slot held while the body
    submits, and released once ``n_queued`` requests wait behind it: they
    leave as ONE batch."""
    assert b.try_claim_idle()
    try:
        yield
        assert wait_until(lambda: b.stats()["queued"] == n_queued, timeout)
    finally:
        b.release_idle()

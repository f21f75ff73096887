"""Shared test helpers (pytest adds tests/ to sys.path for no-package
layouts, so `from testutil import wait_until` works under both bare
pytest and python -m pytest)."""
import contextlib
import time


def wait_until(pred, timeout=10.0, interval=0.01):
    """Deadline poll: True once pred() holds, False at the deadline."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


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

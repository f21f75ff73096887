"""Test configuration.

Tests run on a virtual 8-device CPU mesh (the TPU-build analog of the
reference's 127.0.0.1 loopback servers, SURVEY.md §4): multi-chip sharding
logic is validated with ``xla_force_host_platform_device_count=8`` so no real
pod is needed.  What runs on the chip is chip_smoke.py, not these.

The driver cuts a whole run at 1,470 s and counts what it reached, so a
run stays under 1,000 s here (ROADMAP D12): a file compiles each program
once (a module-scoped fixture or module-level ``jax.jit``; cases differ
in their data) at the smallest shape that has every branch it names.
"""
import os
import sys

import pytest

# Force the CPU even where the environment selects a TPU: tests validate
# sharding logic on the virtual 8-device mesh, and chip_smoke.py is what
# runs on the chip.  Both are set before any backend initializes: the
# env vars for the child processes tests start, the config for this one.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# The persistent compile cache (brpc_tpu.ici.mesh.ensure_compile_cache) is
# for the chip, where one decode step is ~50 s of compile.  On the CPU it
# buys seconds and moves what several of these tests race against (a
# compile that is sometimes a cache read), so it is off here and in the
# children tests start.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)
assert len(jax.devices()) == 8, (
    "tests need the 8-device virtual CPU mesh; a jax backend was "
    "initialized before conftest could configure it")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    # tier-1 runs with -m 'not slow'; chaos scenarios that outgrow ~5s
    # carry this marker so the fast suite stays fast (make chaos runs
    # everything)
    config.addinivalue_line(
        "markers", "slow: long-running chaos/scenario tests excluded "
                   "from the tier-1 fast suite")


def pytest_collection_modifyitems(config, items):
    # tests/test_chip_compile.py loads the TPU's compiler library into
    # this process (dozens of threads, gigabytes of it).  It runs after
    # everything else, so that no timing- or signal-sensitive test (the
    # SIGPROF profiler's, the overhead gates) shares a process with it;
    # so its compiles of the served models are also what a run that
    # outgrows the driver's limit loses first.  The order stays
    # deterministic: every worker collects the same list.
    last = [i for i in items if i.path.name == "test_chip_compile.py"]
    items[:] = [i for i in items if i not in last] + last


@pytest.fixture(autouse=True, scope="session")
def _quiet_naming_refresh_noise():
    """Dead loopback registries from already-finished tests would spam
    '[naming] refresh failed' across the whole run."""
    from brpc_tpu import flags
    from brpc_tpu.policy import naming  # noqa: F401 — defines the flag
    flags.set_flag("naming_log_refresh_failures", False, force=True)
    yield


@pytest.fixture(autouse=True)
def _restore_process_globals():
    """Put back, around every test, the process-wide state one test
    leaves and a later one reads: whether rpcz records, and its span
    ring (emptied before a test that starts with rpcz off, so a module
    that turns it on for all its tests keeps its spans between them);
    the health checker's broken endpoints with their probe threads (one
    a dead port, waking every second for the rest of the run) and the
    circuit breaker's windows over them (ports come round again);
    ``tracemalloc``, which the console's heap pages start and leave on:
    under it a tight loop of every later test ran 25 times slower, and a
    heap page a thousand tests later outlasted its caller's timeout."""
    import tracemalloc
    from brpc_tpu import rpcz
    from brpc_tpu.policy import circuit_breaker, health_check
    was_on, rate = rpcz.enabled(), rpcz.sample_rate()
    was_tracing = tracemalloc.is_tracing()
    if not was_on:
        rpcz.flush()
        with rpcz._collect_lock:
            rpcz._collected.clear()
    yield
    rpcz.set_enabled(was_on, rate)
    if not was_tracing:
        tracemalloc.stop()
    health_check.reset_all()
    with circuit_breaker._breaker_mu:
        circuit_breaker._breaker = None


@pytest.fixture(autouse=True, scope="module")
def _collect_dead_servers():
    """A full pass of the collector after every module (after every
    test it cost 124 ms a test and a run twice its time): 512 native ``LatencyRecorder`` slots
    serve the process, a stopped server's recorders hold theirs in
    reference cycles until such a pass, 575-697 were alive from
    ``test_psserve`` on, and every new recorder then dropped its
    records and read zeros (ROADMAP D20)."""
    yield
    import gc
    gc.collect()


# ---------------------------------------------------------------------------
# suite-stall watchdog (ISSUE 15)
# ---------------------------------------------------------------------------
#
# The intermittent tier-1 wedge sometimes OUTLIVES every per-call
# WedgeGuard (the hang sits in an unguarded native path), so the run
# dies by the driver's outer `timeout -k` SIGKILL — and a Python signal
# handler can't help, because the main thread is blocked inside the
# wedged ctypes call and never returns to the interpreter.  This
# watchdog is a daemon THREAD instead: every test start refreshes a
# timestamp; if no test starts for BRPC_T1_WATCHDOG_S seconds
# (default 300, 0 disables), it writes the native flight-recorder
# autopsy + lock witness ONCE to the $BRPC_WEDGE_DUMP_DIR artifact
# file (default build/wedge_autopsy/ — the stderr copy is usually
# swallowed by capture), naming the test it stalled inside — so even a
# hard wedge leaves the evidence the outer kill would erase.

_watchdog_state = {"t": None, "test": "", "fired": False}


def _watchdog_dump() -> None:
    import time as _time
    try:
        from tests.wedge_guard import _witness_dump
    except Exception:
        return
    _witness_dump(f"suite watchdog: no test progress for "
                  f"{_time.monotonic() - _watchdog_state['t']:.0f}s "
                  f"(stalled inside {_watchdog_state['test']!r})")


def pytest_sessionstart(session):
    import threading
    import time as _time

    try:
        stall_s = float(os.environ.get("BRPC_T1_WATCHDOG_S", "300"))
    except ValueError:
        stall_s = 300.0
    if stall_s <= 0:
        return
    _watchdog_state["t"] = _time.monotonic()

    def run():
        while True:
            _time.sleep(5.0)
            t = _watchdog_state["t"]
            if t is None or _watchdog_state["fired"]:
                continue
            if _time.monotonic() - t > stall_s:
                _watchdog_state["fired"] = True
                _watchdog_dump()

    threading.Thread(target=run, daemon=True,
                     name="t1-stall-watchdog").start()


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    import time as _time
    _watchdog_state["t"] = _time.monotonic()
    _watchdog_state["test"] = item.nodeid
    yield
    _watchdog_state["t"] = _time.monotonic()

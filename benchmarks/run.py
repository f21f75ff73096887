"""Run one cell of the benchmark once, in this one process.

    python3 -m benchmarks.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Refuses any platform but a TPU, fewer chips than the cell asks for and
a device kind the peaks table lacks (exit 2, no result line).  Brings
the cell's servers up over loopback in this process, makes data and
weights on the device from ``--seed``, warms the cell's own shapes
(set-up, clocked from the moment the TPU runtime is up), measures for
``--seconds``, decides ``correct`` against the
plain reference, and prints ONE JSON object as the last line of stdout.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, ``device.busy_s`` / ``window_s`` and a ``breakdown``.

``--control <name>`` is for the control and fault runs only (see
PERF.md, "How correct is decided"): it breaks the timed path in the
named way, and the run must then print ``"correct": false``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()          # as near the process's start as we get

import argparse                      # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import shutil                        # noqa: E402
import sys                           # noqa: E402

from benchmarks.harness import loader  # noqa: E402
from benchmarks.harness.peaks import UnknownDevice, peaks_for  # noqa: E402

TRACE_SECONDS = 3.0       # length of the traced part of the window
TRACE_START_AFTER = 2.0   # seconds into the window before tracing starts


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Backend compile requests jax makes (a persistent-cache hit is one
    too: it is still a shape the warm-up did not cover)."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs


def setup_compile_cache() -> str:
    """jax's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` or the
    fixed ``<checkout>/.jax_cache`` (the program's
    ``ensure_compile_cache`` picks the same one); every program is
    cached, however quick its compile, so that a second run of a cell
    finds all of them."""
    import jax
    from brpc_tpu.ici.mesh import COMPILE_CACHE_ENV, ensure_compile_cache
    chosen = ensure_compile_cache() or os.environ[COMPILE_CACHE_ENV]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return chosen


class Tracer:
    """Profiles ``TRACE_SECONDS`` of the steady window and snapshots the
    driver's counters around exactly that part."""

    def __init__(self, driver, trace_dir: str, seconds: float):
        self.driver = driver
        self.dir = trace_dir
        self.start_after = min(TRACE_START_AFTER, max(0.0, seconds / 4))
        # a mix whose steps are long asks for a longer traced part
        want = float(driver.traffic.get("trace_seconds", TRACE_SECONDS))
        self.length = min(want, max(0.5, seconds / 2))
        self.result = None

    def during(self, _t0: float) -> None:
        import jax
        time.sleep(self.start_after)
        c0 = self.driver.counters()
        # device and TraceMe events only: the Python tracer would slow
        # the very host path the window measures
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        t0 = time.monotonic()
        time.sleep(self.length)
        t1 = time.monotonic()
        c1 = self.driver.counters()
        jax.profiler.stop_trace()
        self.result = {"t0": t0, "t1": t1, "window_s": t1 - t0,
                       "counters0": c0, "counters1": c1}


def memory_peak_bytes(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def compute_metrics(entries, run: dict, root: str = loader.ROOT) -> dict:
    out = {}
    for m in entries:
        if m["name"] == "setup_s":
            value = run["setup_s"]
        else:
            value = loader.load_metric(m["name"], root).compute(run)
        if value is None:
            continue             # nothing to read: left out, never 0
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell, *, seed: int, seconds: float, trace: bool, devices,
             peaks: dict, t_start: float, control: str | None = None,
             root: str = loader.ROOT, stdout=None,
             describe_trace: str | None = None) -> int:
    """Everything of a run but the look for a chip.  Returns the exit
    code; prints the result line to ``stdout``."""
    import jax
    stdout = stdout or sys.stdout
    cache_dir = setup_compile_cache()
    compiles = CompileCounter()
    log(f"benchmarks.run: cell {cell.name} seed {seed} seconds {seconds} "
        f"trace {int(trace)} on {len(devices)} x {devices[0].device_kind}, "
        f"compile cache {cache_dir}")
    log(f"  {time.monotonic() - t_start:.2f} s into set-up: the program is "
        f"imported")
    driver = loader.load_driver(cell.config["driver"], root).Driver(
        cell, seed=seed, devices=list(devices), control=control)
    trace_dir = os.path.join(root, ".bench_trace", cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        driver.setup()
        setup_s = time.monotonic() - t_start
        log(f"  set-up {setup_s:.2f} s ({compiles.count} compile requests, "
            f"{compiles.seconds:.1f} s in the compiler)")
        tracer = Tracer(driver, trace_dir, seconds) if trace else None
        compiles0, compile_s0 = compiles.count, compiles.seconds
        c0 = driver.counters()
        t0, t1 = driver.run(seconds, tracer.during if tracer else None)
        c1 = driver.counters()
        in_window = compiles.count - compiles0
        log(f"  compile requests inside the window: {in_window} "
            f"({compiles.seconds - compile_s0:.2f} s in the compiler)")
        result = {"compiles_in_window": in_window,
                  "compile_seconds_in_window": compiles.seconds - compile_s0}
        peak = memory_peak_bytes(devices)
        run = {"cell": cell, "config": cell.config, "traffic": cell.traffic,
               "seed": seed, "t0": t0, "t1": t1, "window_s": t1 - t0,
               "setup_s": setup_s, "counters0": c0, "counters1": c1,
               "records": driver.records(), "peaks": peaks,
               "n_devices": len(devices), "traced": None}
        log(f"  window {t1 - t0:.2f} s closed; peak device memory "
            f"{peak / 1e9:.2f} GB; checking")
        driver.release()
        checks = list(driver.check())
        checks.append(("compiles_in_window", in_window, 0))
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": peak}
        if trace:
            from benchmarks.harness import trace as trace_mod
            summary = trace_mod.reduce_trace(trace_dir)
            if describe_trace:
                with open(describe_trace, "w") as f:
                    f.write(trace_mod.describe(
                        trace_mod.find_xplane(trace_dir)))
            run["traced"] = dict(tracer.result, trace=summary)
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = tracer.result["window_s"]
            result["breakdown"] = {"device_ops": summary["device_ops"],
                                   "idle_gaps": summary["idle_gaps"]}
            metrics = compute_metrics(cell.per_layer, run, root)
        else:
            metrics = compute_metrics(cell.end_to_end, run, root)
        attempted, failed = driver.attempted_failed()
    finally:
        driver.close()
        shutil.rmtree(trace_dir, ignore_errors=True)
    correct = all(value <= limit for _n, value, limit in checks)
    compared = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device,
              **result, "compared": compared}
    for n, v, lim in checks:
        log(f"  compared {n}: {v} (limit {lim})"
            f"{'' if v <= lim else '  <-- OVER'}")
    log(f"  correct: {correct}")
    print(json.dumps(result), file=stdout, flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None,
                    help="break the timed path (control and fault runs)")
    ap.add_argument("--staged", action="store_true",
                    help="also find the cells of benchmarks/staged/ (by hand "
                         "only; the benchmark itself has none of them)")
    ap.add_argument("--describe-trace", default=None, metavar="FILE",
                    help="also write a by-hand description of the trace")
    args = ap.parse_args(argv)
    cell = loader.load_cell(
        args.workload,
        bench=loader.load_benchmark_with_staged() if args.staged else None)

    import jax
    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        log(f"benchmarks.run: needs a TPU, jax found {first.platform!r} "
            f"({first.device_kind}); nothing was run")
        return 2
    if len(devices) < cell.chips:
        log(f"benchmarks.run: cell {cell.name} needs {cell.chips} chip(s), "
            f"jax sees {len(devices)}")
        return 2
    try:
        peaks = peaks_for(first.device_kind)
    except UnknownDevice as e:
        log(f"benchmarks.run: {e}")
        return 2
    # set-up is clocked from here: what comes before is the interpreter,
    # ``import jax`` and the TPU runtime's own start, which read 10.4 s or
    # 14.6 s from one run to the next (PERF.md, PR 24) and are neither the
    # program's nor the benchmark's work; they are logged beside it
    t_ready = time.monotonic()
    log(f"benchmarks.run: platform up {t_ready - T_START:.2f} s after the "
        f"process started (not part of setup_s)")
    return run_cell(cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), devices=devices[:cell.chips],
                    peaks=peaks, t_start=t_ready, control=args.control,
                    describe_trace=args.describe_trace)


if __name__ == "__main__":
    sys.exit(main())

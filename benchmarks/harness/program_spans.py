"""Reduce the PROGRAM's own stage spans in a ``jax.profiler`` trace to
per-layer host time, and put the chip's idle time down to them.

The program stamps its call path with ``brpc_tpu.rpcz.stage``; while a
profiler session records, each stage is a ``TraceAnnotation`` in the
profiler's own trace, so it sits on the device trace's clock by
construction.  This module opens the run's xplane itself
(``<root>/.bench_trace/<cell>/``, which ``run.py`` removes only after
the metrics are computed), keeps the host events whose name starts with
one of ``PREFIXES``, clips them to the traced part, nests them per
thread, and gives

``self_ns``      a span's duration less what its children on the same
                 thread cover
``cpu_us``       the thread's CPU time inside the span, which the stages
                 outermost on their thread stamp at exit
``stats``        what the stage stamped (``cid``, ``bytes``,
                 ``queue_wait_us`` ...)
``idle``         the fullest chip's idle time, every nanosecond given to
                 the innermost program span open on any thread at that
                 instant, shared equally where several threads have one;
                 a thread whose innermost span is a ``wait`` stage is
                 parked and takes no share; ``unattributed`` where no
                 thread has a span open

A program without such spans (the parent of the PR that brought them)
gives ``None`` from ``load`` and from every reader below: the metric is
then left out of the result line, never reported as 0.
"""
from __future__ import annotations

import os
import statistics
import sys

from benchmarks.harness import loader, readers, trace

PREFIXES = ("rpc.", "rail.", "ici.", "net.", "stream.", "combo.",
            "collective.")
UNATTRIBUTED = "(no program span)"
_CACHE_KEY = "_program_spans"


class Span:
    __slots__ = ("name", "start", "end", "stats", "thread", "children",
                 "parent")

    def __init__(self, name, start, end, stats=None, thread=0):
        self.name = name
        self.start = start          # ns on the trace's clock
        self.end = end
        self.stats = stats or {}
        self.thread = thread
        self.children: list = []
        self.parent = None

    @property
    def dur(self) -> int:
        return self.end - self.start

    @property
    def wait(self) -> bool:
        return bool(self.stats.get("wait"))

    @property
    def self_ns(self) -> int:
        covered = trace.merge_intervals(
            (c.start, c.end) for c in self.children)
        return self.dur - sum(e - s for s, e in covered)

    @property
    def cpu_us(self):
        """The thread's CPU time inside the span, where the stage stamped
        it (the stages outermost on their thread do), else None."""
        return self.stats.get("cpu_us")

    @property
    def parked_ns(self) -> int:
        """Time of the ``wait`` stages under this span."""
        return sum(c.dur if c.wait else c.parked_ns for c in self.children)


# ---- reading ---------------------------------------------------------------

def read_spans(path: str) -> list:
    """Every program span of the trace, one ``Span`` each, ``thread``
    numbering the host lines."""
    from jax.profiler import ProfileData
    out = []
    thread = 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            continue
        for line in plane.lines:
            thread += 1
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    start = int(e.start_ns)
                    out.append(Span(e.name, start,
                                    start + int(e.duration_ns),
                                    dict(e.stats), thread))
    return out


def clip(spans: list, w0: int, w1: int) -> list:
    """Spans cut to the window; those wholly outside it dropped."""
    out = []
    for s in spans:
        if s.end <= w0 or s.start >= w1:
            continue
        s.start, s.end = max(s.start, w0), min(s.end, w1)
        out.append(s)
    return out


def nest(spans: list) -> list:
    """Link every span to the innermost span of its thread that
    contains it; returns the roots."""
    roots = []
    by_thread: dict = {}
    for s in spans:
        s.children, s.parent = [], None
        by_thread.setdefault(s.thread, []).append(s)
    for group in by_thread.values():
        group.sort(key=lambda s: (s.start, -s.end))
        stack: list = []
        for s in group:
            while stack and stack[-1].end <= s.start:
                stack.pop()
            if stack:
                s.parent = stack[-1]
                stack[-1].children.append(s)
            else:
                roots.append(s)
            stack.append(s)
    return roots


def innermost_segments(roots: list) -> list:
    """Per thread, the time line cut into ``(start, end, thread, name)``
    pieces, each named after the innermost span open there.  Pieces
    whose innermost span is a ``wait`` stage are left out: the thread
    is parked in them."""
    out = []

    def walk(s):
        cur = s.start
        for c in sorted(s.children, key=lambda c: c.start):
            if c.start > cur and not s.wait:
                out.append((cur, c.start, s.thread, s.name))
            walk(c)
            cur = max(cur, c.end)
        if s.end > cur and not s.wait:
            out.append((cur, s.end, s.thread, s.name))

    for r in roots:
        walk(r)
    return out


def attribute_idle(idle: list, segments: list) -> dict:
    """Seconds of each idle interval ``(start, end)`` by the name of the
    innermost program span open at that instant; where several threads
    have one open the instant is shared equally among them, where none
    has it goes to ``UNATTRIBUTED``."""
    events = []     # (time, order, kind, thread, name); ends sort first
    for s, e, thread, name in segments:
        events.append((s, 1, "open", thread, name))
        events.append((e, 0, "close", thread, name))
    for s, e in idle:
        events.append((s, 1, "idle", None, None))
        events.append((e, 0, "busy", None, None))
    events.sort(key=lambda ev: (ev[0], ev[1]))
    out: dict = {}
    open_by_thread: dict = {}
    in_idle = False
    last = None
    for t, _order, kind, thread, name in events:
        if in_idle and last is not None and t > last:
            dt = (t - last) / 1e9
            if open_by_thread:
                share = dt / len(open_by_thread)
                for n in open_by_thread.values():
                    out[n] = out.get(n, 0.0) + share
            else:
                out[UNATTRIBUTED] = out.get(UNATTRIBUTED, 0.0) + dt
        last = t
        if kind == "open":
            open_by_thread[thread] = name
        elif kind == "close":
            if open_by_thread.get(thread) == name:
                del open_by_thread[thread]
        else:
            in_idle = kind == "idle"
    return out


def idle_intervals(busy: list, w0: int, w1: int) -> list:
    """The window less the (merged, sorted) busy intervals."""
    out = []
    cur = w0
    for s, e in busy:
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if w1 > cur:
        out.append((cur, w1))
    return out


def clock_offset_us(spans: list):
    """Median of (trace clock - monotonic clock) over the root stages,
    which carry ``mono_us``; None where there is none.  Adding it to a
    ``time.monotonic()`` stamp (in us) places it on the trace's axis."""
    offs = [s.start / 1e3 - s.stats["mono_us"] for s in spans
            if "mono_us" in s.stats]
    return statistics.median(offs) if offs else None


# ---- one run ---------------------------------------------------------------

def reduce(path: str, t0: float | None = None,
           t1: float | None = None) -> dict | None:
    """Everything the readers below need of one trace, or None where it
    holds no program span.  ``t0``/``t1`` are the traced part's bounds
    on the monotonic clock (seconds); without them, or without a root
    stage to join the clocks by, the window is what the events span."""
    spans = read_spans(path)
    if not spans:
        return None
    planes = trace.read_planes(path)
    busy_by_dev = {}
    for name, dev in planes["devices"].items():
        ops = dev["ops"] or dev["modules"]
        busy_by_dev[name] = trace.merge_intervals(
            (s, s + d) for _n, s, d in ops if d > 0)
    offset = clock_offset_us(spans)
    if offset is not None and t0 is not None and t1 is not None:
        w0 = int((t0 * 1e6 + offset) * 1e3)
        w1 = int((t1 * 1e6 + offset) * 1e3)
    else:
        ends = [s.end for s in spans] + [iv[-1][1] for iv in
                                         busy_by_dev.values() if iv]
        starts = [s.start for s in spans] + [iv[0][0] for iv in
                                             busy_by_dev.values() if iv]
        w0, w1 = min(starts), max(ends)
    spans = clip(spans, w0, w1)
    if not spans:
        return None
    roots = nest(spans)
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    idle_by = {}
    idle_s = 0.0
    if busy_by_dev:
        def busy_ns(iv):
            return sum(min(e, w1) - max(s, w0) for s, e in iv
                       if min(e, w1) > max(s, w0))
        fullest = max(busy_by_dev, key=lambda k: busy_ns(busy_by_dev[k]))
        idle = idle_intervals(busy_by_dev[fullest], w0, w1)
        idle_s = sum(e - s for s, e in idle) / 1e9
        idle_by = attribute_idle(idle, innermost_segments(roots))
    return {"spans": spans, "by_name": by_name, "roots": roots,
            "window_ns": (w0, w1), "clock_offset_us": offset,
            "idle_s": idle_s, "idle_by_stage": idle_by}


def describe(red: dict) -> str:
    """The per-stage table and the idle attribution, for the log."""
    w0, w1 = red["window_ns"]
    rows = [f"  program spans over {(w1 - w0) / 1e9:.3f} s (clock offset "
            f"{red['clock_offset_us']} us):",
            f"    {'stage':28s} {'n':>7s} {'dur us':>10s} {'self us':>10s} "
            f"{'cpu us':>9s} {'idle s':>8s}"]
    for name in sorted(red["by_name"]):
        ss = red["by_name"][name]
        cpu = [s.cpu_us for s in ss if s.cpu_us is not None]
        rows.append(
            f"    {name:28s} {len(ss):7d} "
            f"{sum(s.dur for s in ss) / 1e3 / len(ss):10.1f} "
            f"{sum(s.self_ns for s in ss) / 1e3 / len(ss):10.1f} "
            + (f"{sum(cpu) / len(cpu):9.1f} " if cpu else f"{'-':>9s} ")
            + f"{red['idle_by_stage'].get(name, 0.0):8.3f}")
    rows.append(f"    idle {red['idle_s']:.3f} s on the fullest chip, "
                f"{red['idle_by_stage'].get(UNATTRIBUTED, 0.0):.3f} s of it "
                f"under no program span")
    return "\n".join(rows)


def call_cover(red: dict):
    """Per unary call, how much of the caller's parked wait the spans of
    the same ``cid`` explain: the server's ``rpc.server.process``, the
    client's ``rpc.client.on_response`` and the queue wait each of them
    stamped, over ``rpc.client.wait``.  (median share, calls matched),
    or None where there is no such call."""
    by_cid: dict = {}
    for name in ("rpc.client.wait", "rpc.server.process",
                 "rpc.client.on_response"):
        for s in red["by_name"].get(name, ()):
            by_cid.setdefault(s.stats.get("cid"), {})[name] = s
    shares = []
    for group in by_cid.values():
        if len(group) < 3 or group["rpc.client.wait"].dur <= 0:
            continue
        served = group["rpc.server.process"]
        back = group["rpc.client.on_response"]
        covered = served.dur + back.dur + 1e3 * (
            served.stats.get("queue_wait_us", 0)
            + back.stats.get("queue_wait_us", 0))
        shares.append(covered / group["rpc.client.wait"].dur)
    return (statistics.median(shares), len(shares)) if shares else None


def clock_join(red: dict, calls: list):
    """The benchmark's own records against the program's root stage of
    the same call, joined through ``mono_us``: (median of
    ``rpc.client.call`` duration over ``t_done - t_issue``, calls
    matched), or None.  A record is matched to the first root stage
    that starts within a millisecond after its ``t_issue``."""
    off = red["clock_offset_us"]
    roots = sorted(red["by_name"].get("rpc.client.call", ()),
                   key=lambda s: s.start)
    if off is None or not roots:
        return None
    ratios = []
    i = 0
    for c in sorted(calls, key=lambda c: c["t_issue"]):
        issued = (c["t_issue"] * 1e6 + off) * 1e3
        while i < len(roots) and roots[i].start < issued:
            i += 1
        if i < len(roots) and roots[i].start - issued < 1e6 \
                and c["t_done"] > c["t_issue"]:
            ratios.append(roots[i].dur / 1e9 / (c["t_done"] - c["t_issue"]))
            i += 1
    return (statistics.median(ratios), len(ratios)) if ratios else None


def on_cost_line(run: dict, path: str | None) -> str:
    """Completed operations per second inside the traced part against
    the rest of the window (the records carry ``t_done``): what the
    instrumentation and the profiler cost while they are on."""
    tr = run["traced"]
    done = [c["t_done"] for c in run["records"].get("calls", ())
            if c["ok"] and run["t0"] <= c["t_done"] <= run["t1"]]
    inside = sum(1 for t in done if tr["t0"] <= t <= tr["t1"])
    rest_s = (run["t1"] - run["t0"]) - (tr["t1"] - tr["t0"])
    size = f"{os.path.getsize(path) / 2**20:.1f} MiB" if path else "none"
    return (f"  traced part: {inside / (tr['t1'] - tr['t0']):.1f} ops/s "
            f"({inside} in {tr['t1'] - tr['t0']:.2f} s); rest of the "
            f"window: {(len(done) - inside) / rest_s:.1f} ops/s "
            f"({len(done) - inside} in {rest_s:.2f} s); xplane {size}")


def trace_dir(run: dict) -> str:
    return os.path.join(loader.ROOT, ".bench_trace", run["cell"].name)


def load(run: dict) -> dict | None:
    """The reduction of this run's trace, made once and kept in ``run``;
    None in an untraced run, where the trace is gone, and where it holds
    no program span."""
    if _CACHE_KEY in run:
        return run[_CACHE_KEY]
    red = None
    tr = readers.traced(run)
    if tr is not None:
        try:
            path = trace.find_xplane(trace_dir(run))
        except FileNotFoundError:
            path = None
        if "t0" in run:
            print(on_cost_line(run, path), file=sys.stderr, flush=True)
        if path is not None:
            red = reduce(path, tr["t0"], tr["t1"])
            if red is not None:
                print(describe(red), file=sys.stderr, flush=True)
                print(f"  clock join (rpc.client.call over t_done - "
                      f"t_issue, median, n): "
                      f"{clock_join(red, readers.traced_calls(run, 'echo'))}"
                      f"; rpc.client.wait explained by its cid's spans "
                      f"(median share, n): {call_cover(red)}",
                      file=sys.stderr, flush=True)
    run[_CACHE_KEY] = red
    return red


# ---- what the metric files read --------------------------------------------

def _spans(run: dict, names) -> list | None:
    red = load(run)
    if red is None:
        return None
    found = [s for n in names for s in red["by_name"].get(n, ())]
    return found or None


def us_per(run: dict, names, kind: str, *, own: bool):
    """Summed time of the named stages, in us, per completed call of
    ``kind`` in the traced part; ``own``: each span's time less what its
    children cover (self time), else its whole duration."""
    spans = _spans(run, names)
    n = len(readers.traced_calls(run, kind))
    if spans is None or not n:
        return None
    return sum(s.self_ns if own else s.dur for s in spans) / 1e3 / n


def dur_p50_ms(run: dict, name: str):
    spans = _spans(run, (name,))
    if spans is None:
        return None
    return statistics.median(s.dur for s in spans) / 1e6


def stat_p50(run: dict, names, stat: str):
    spans = _spans(run, names)
    vals = [s.stats[stat] for s in spans or () if stat in s.stats]
    return statistics.median(vals) if vals else None


def wait_share_percent(run: dict):
    """1 - (CPU time over wall time) of the spans that stamp ``cpu_us``
    and lie under no other that does (the stages outermost on their
    thread), the ``wait`` stages under them left out of the wall time,
    in percent: the share of the program's working time in which its
    thread did not run (it waited for the interpreter lock, a lock, the
    kernel)."""
    red = load(run)
    if red is None:
        return None
    wall = cpu = 0.0

    def walk(s):
        nonlocal wall, cpu
        if s.cpu_us is not None and not s.wait:
            wall += (s.dur - s.parked_ns) / 1e3
            cpu += s.cpu_us
        else:
            for c in s.children:
                walk(c)

    for r in red["roots"]:
        walk(r)
    return 100.0 * (1.0 - min(cpu, wall) / wall) if wall > 0 else None


def idle_unattributed_percent(run: dict):
    red = load(run)
    if red is None or red["idle_s"] <= 0:
        return None
    return 100.0 * red["idle_by_stage"].get(UNATTRIBUTED, 0.0) \
        / red["idle_s"]


def share_percent(run: dict, part: str, whole: str):
    """Summed duration of ``part`` over that of ``whole``, in percent."""
    a, b = _spans(run, (part,)), _spans(run, (whole,))
    if b is None:
        return None
    total = sum(s.dur for s in b)
    return 100.0 * sum(s.dur for s in a or ()) / total if total else None

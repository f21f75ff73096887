"""Reduce a ``jax.profiler`` trace (``*.xplane.pb``) to busy/idle time,
per-program device time, the operations that took most time, and the
longest idle gaps attributed to the benchmark's own
``jax.profiler.TraceAnnotation`` spans.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line has
one event per operation that ran, ``XLA Modules`` one per program.
Where a trace has no device plane (a CPU trace, as the recorded one the
tests use) every event that carries an ``hlo_module`` stat counts as a
device operation.
"""
from __future__ import annotations

import glob
import os

SPAN_PREFIX = "bench."


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def merge_intervals(intervals) -> list:
    """Union of (start, end) intervals as a sorted list of disjoint
    ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _stat(ev, key):
    for k, v in ev.stats:
        if k == key:
            return v
    return None


def read_planes(path: str) -> dict:
    """{"devices": {plane: {"ops": [(name, start, dur)], "modules":
    [...]}}, "spans": [(name, start, dur)]} with times in ns."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: dict = {}
    spans: list = []
    fallback_ops: list = []
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:") \
            and "CPU" not in plane.name
        for line in plane.lines:
            if is_dev and line.name in ("XLA Ops", "XLA Modules"):
                key = "ops" if line.name == "XLA Ops" else "modules"
                dev = devices.setdefault(plane.name,
                                         {"ops": [], "modules": []})
                dev[key].extend((e.name, e.start_ns, e.duration_ns)
                                for e in line.events)
            elif not is_dev:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns, e.duration_ns))
                    elif e.duration_ns > 0:
                        mod = _stat(e, "hlo_module")
                        if mod is not None:
                            fallback_ops.append(
                                (e.name, e.start_ns, e.duration_ns, mod))
    if not devices and fallback_ops:
        devices["host-xla"] = {
            "ops": [(n, s, d) for n, s, d, _ in fallback_ops],
            "modules": [(m, s, d) for _, s, d, m in fallback_ops]}
    return {"devices": devices, "spans": spans}


def short_op_name(name: str) -> str:
    """``%copy.1 = u32[16]{0:T(1024)} copy(u32[16]{...} %a.1)`` ->
    ``copy.1 u32[16]``: the operation and its result type, without the
    layout and the operands."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:80]
    return f"{lhs.lstrip('%')} {rhs.split(' ')[0].split('{')[0]}"[:80]


def _sum_by_name(events) -> dict:
    out: dict = {}
    for name, _s, d in events:
        name = short_op_name(name)
        c = out.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += d / 1e9
    return out


def reduce_planes(planes: dict, top: int = 10) -> dict:
    """The numbers the per-layer metrics read:

    ``busy_s``       mean over devices of the union of op intervals
    ``busy_s_max``   the same on the fullest-loaded device
    ``programs``     {module: [launches, seconds]} summed over devices
    ``n_programs``   program launches, all devices
    ``device_ops``   top operations [[name, seconds], ...]
    ``idle_gaps``    top [[span name, idle seconds], ...] on the fullest
                     device: each gap between operations goes to the
                     benchmark span that covers most of it
    """
    per_dev = {}
    for name, dev in planes["devices"].items():
        ops = dev["ops"] or dev["modules"]
        merged = merge_intervals((s, s + d) for _n, s, d in ops if d > 0)
        per_dev[name] = (sum(e - s for s, e in merged) / 1e9, merged)
    if not per_dev:
        return {"busy_s": 0.0, "busy_s_max": 0.0, "programs": {},
                "n_programs": 0, "device_ops": [], "idle_gaps": [],
                "n_devices": 0}
    busy = [b for b, _ in per_dev.values()]
    fullest = max(per_dev, key=lambda k: per_dev[k][0])
    programs: dict = {}
    ops_by_name: dict = {}
    for dev in planes["devices"].values():
        for k, (n, s) in _sum_by_name(dev["modules"]).items():
            c = programs.setdefault(k, [0, 0.0])
            c[0] += n
            c[1] += s
        for k, (n, s) in _sum_by_name(dev["ops"]).items():
            c = ops_by_name.setdefault(k, [0, 0.0])
            c[0] += n
            c[1] += s
    device_ops = sorted(([k, v[1]] for k, v in ops_by_name.items()),
                        key=lambda kv: -kv[1])[:top]
    gaps = attribute_gaps(per_dev[fullest][1], planes["spans"])
    idle = sorted(([k, v] for k, v in gaps.items()),
                  key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(busy) / len(busy), "busy_s_max": max(busy),
            "programs": programs,
            "n_programs": sum(v[0] for v in programs.values()),
            "device_ops": device_ops, "idle_gaps": idle,
            "n_devices": len(per_dev)}


def attribute_gaps(merged, spans) -> dict:
    """Seconds of idle between consecutive busy intervals, by the
    benchmark span overlapping each gap most (``(no span)`` where none
    does).  Spans may nest; the innermost (shortest) wins a tie."""
    out: dict = {}
    spans = sorted(spans, key=lambda s: s[1])
    for (_s0, e0), (s1, _e1) in zip(merged, merged[1:]):
        gap = s1 - e0
        if gap <= 0:
            continue
        best, best_ov, best_len = "(no span)", 0, 0
        for name, s, d in spans:
            if s >= s1:
                break
            ov = min(s + d, s1) - max(s, e0)
            if ov > best_ov or (ov == best_ov and ov > 0 and d < best_len):
                best, best_ov, best_len = name, ov, d
        out[best] = out.get(best, 0.0) + gap / 1e9
    return out


def reduce_trace(trace_dir: str) -> dict:
    return reduce_planes(read_planes(find_xplane(trace_dir)))


def describe(path: str, limit: int = 6) -> str:
    """A by-hand look at a trace: planes, lines, first event names."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    rows = []
    for plane in pd.planes:
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            rows.append(f"  LINE {line.name!r} events={len(evs)}")
            for e in evs[:limit]:
                rows.append(f"    {e.name[:90]} start={e.start_ns:.0f} "
                            f"dur={e.duration_ns:.0f} "
                            f"stats={dict(list(e.stats)[:6])}")
    return "\n".join(rows)

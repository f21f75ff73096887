"""The serving path's stages (``serve.*``, ``kvcache.*``) out of a run's
trace.  ``program_spans.PREFIXES`` names neither family and is not this
PR's to edit, so the readers of ``sala_doc_turns`` reduce them here:
every host event of those families in the traced part, by name, with
what the stage stamped.  A program that stamps none (the parent of the
PR that brought them) gives None.
"""
from __future__ import annotations

import sys

from benchmarks.harness import program_spans, readers, trace

PREFIXES = ("serve.", "kvcache.")
_KEY = "_sala_spans"


def read(path: str) -> dict:
    """{stage name: [(duration ns, stats)]}."""
    from jax.profiler import ProfileData
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    out.setdefault(e.name, []).append(
                        (int(e.duration_ns), dict(e.stats)))
    return out


def load(run: dict):
    if _KEY in run:
        return run[_KEY]
    found = None
    if readers.traced(run) is not None:
        try:
            found = read(trace.find_xplane(program_spans.trace_dir(run))) \
                or None
        except FileNotFoundError:
            found = None
    run[_KEY] = found
    if found:
        print(describe(found), file=sys.stderr, flush=True)
    return found


def describe(found: dict) -> str:
    """The stages by name, for the run's log: how many, mean length and,
    where the stage stamps it, the mean CPU time of its thread inside."""
    rows = [f"  serving stages in the traced part:",
            f"    {'stage':26s} {'n':>6s} {'mean ms':>9s} {'cpu ms':>8s} "
            f"{'total s':>8s}"]
    for name in sorted(found):
        spans = found[name]
        cpu = [st["cpu_us"] for _d, st in spans if "cpu_us" in st]
        rows.append(
            f"    {name:26s} {len(spans):6d} "
            f"{sum(d for d, _ in spans) / len(spans) / 1e6:9.3f} "
            + (f"{sum(cpu) / len(cpu) / 1e3:8.3f} " if cpu else f"{'-':>8s} ")
            + f"{sum(d for d, _ in spans) / 1e9:8.3f}")
    return "\n".join(rows)


def mean_ms(run: dict, name: str):
    spans = (load(run) or {}).get(name)
    if not spans:
        return None
    return sum(d for d, _ in spans) / len(spans) / 1e6


def total_ms(run: dict, name: str):
    spans = (load(run) or {}).get(name)
    if not spans:
        return None
    return sum(d for d, _ in spans) / 1e6

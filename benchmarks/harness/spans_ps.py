"""The parameter server's stage spans (``ps.*``) of a traced run.

``program_spans.PREFIXES`` names the tensor call path's stages only and
is not this PR's to edit, so this module has ``program_spans`` reduce
the xplane a second time with ``ps.`` beside them (nesting, self time,
the idle attribution are its own).  A trace without a ``ps.`` span (a
program from before the stages) gives None from ``load`` and from every
reader below.
"""
from __future__ import annotations

import sys

from benchmarks.harness import program_spans as ps
from benchmarks.harness import readers, trace

PREFIXES = ps.PREFIXES + ("ps.",)
SERVER_STAGES = ("ps.server.lookup", "ps.server.update", "ps.batcher.run")
SHARD_STAGES = ("ps.shard.lock_wait", "ps.shard.gather", "ps.shard.fetch",
                "ps.shard.apply", "ps.shard.note_hot")
_CACHE_KEY = "_ps_spans"


def reduce(path: str, t0: float, t1: float) -> dict | None:
    """``program_spans.reduce`` with ``ps.`` among its prefixes for the
    length of the call (nothing else runs then); None where the trace
    holds no ``ps.`` span."""
    tensor_only = ps.PREFIXES
    ps.PREFIXES = PREFIXES
    try:
        red = ps.reduce(path, t0, t1)
    finally:
        ps.PREFIXES = tensor_only
    if red is None or not any(n.startswith("ps.") for n in red["by_name"]):
        return None
    return red


def load(run: dict) -> dict | None:
    """This run's reduction, made once and kept in ``run``."""
    if _CACHE_KEY in run:
        return run[_CACHE_KEY]
    red = None
    tr = readers.traced(run)
    if tr is not None:
        try:
            path = trace.find_xplane(ps.trace_dir(run))
        except FileNotFoundError:
            path = None
        if path is not None:
            red = reduce(path, tr["t0"], tr["t1"])
            if red is not None:
                print(ps.describe(red), file=sys.stderr, flush=True)
    run[_CACHE_KEY] = red
    return red


def spans(run: dict, names) -> list:
    red = load(run)
    if red is None:
        return []
    return [s for n in names for s in red["by_name"].get(n, ())]


def us_per_call(run: dict, names, kinds, *, own: bool):
    """Summed time of the named stages, in us, per call of ``kinds``
    completed in the traced part; ``own``: self time."""
    found = spans(run, names)
    n = sum(len(readers.traced_calls(run, k)) for k in kinds)
    if not found or not n:
        return None
    return sum(s.self_ns if own else s.dur for s in found) / 1e3 / n


def program_seconds(run: dict, prefix: str):
    """Device seconds of the programs whose name starts with ``prefix``
    in the traced part, or None where none ran."""
    tr = readers.traced(run)
    if tr is None:
        return None
    secs = sum(v[1] for k, v in tr["trace"]["programs"].items()
               if k.startswith(prefix))
    return secs or None

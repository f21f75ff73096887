"""Percentile and rate arithmetic of the end-to-end metrics.

Owned by the benchmark: a later PR cannot change how a tail or a rate
is taken.  Every function takes ALL the samples of the window; none
trims, clips or drops.
"""
from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default).  Raises on an empty sample: a
    metric with nothing to read is left out, never reported as 0."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def in_window(calls, t0: float, t1: float):
    """The calls that completed inside the measured window."""
    return [c for c in calls if t0 <= c["t_done"] <= t1]


def goodput_gbps(calls, t0: float, t1: float) -> float:
    """Payload bytes of the calls that completed, without failing,
    inside [t0, t1], over the whole window, in GB/s (1e9)."""
    done = sum(c["bytes"] for c in in_window(calls, t0, t1) if c["ok"])
    return done / (t1 - t0) / 1e9


def latency_p95_ms(calls, t0: float, t1: float,
                   fail_ms: float = 3_600_000.0) -> float:
    """95th percentile over all calls that completed in the window; a
    failed call counts as ``fail_ms`` (over any limit)."""
    lat = [(c["t_done"] - c["t_issue"]) * 1e3 if c["ok"] else fail_ms
           for c in in_window(calls, t0, t1)]
    return percentile(lat, 95.0)


def token_gaps_ms(streams, t0: float, t1: float) -> list:
    """Every gap between consecutive tokens of one stream, as the client
    received them, whose later token arrived inside the window."""
    gaps = []
    for times in streams:
        for a, b in zip(times, times[1:]):
            if t0 <= b <= t1:
                gaps.append((b - a) * 1e3)
    return gaps


def tokens_per_s(streams, t0: float, t1: float) -> float:
    n = sum(1 for times in streams for t in times if t0 <= t <= t1)
    return n / (t1 - t0)


def iqr_share(values) -> float:
    """The contract's spread: (Q3 - Q1) / median with
    ``statistics.quantiles(values, n=4)``."""
    import statistics
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

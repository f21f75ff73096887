"""Small helpers the metric readers share."""
from __future__ import annotations


def traced(run: dict):
    """The traced part of the window, or None in an untraced run."""
    return run.get("traced")


def traced_calls(run: dict, kind: str | None = None) -> list:
    """Calls that completed, without failing, inside the traced part."""
    tr = traced(run)
    if tr is None:
        return []
    return [c for c in run["records"].get("calls", [])
            if c["ok"] and tr["t0"] <= c["t_done"] <= tr["t1"]
            and (kind is None or c["kind"] == kind)]


def counter_delta(run: dict, name: str, traced_part: bool = True):
    src = traced(run) if traced_part else run
    if src is None or name not in src["counters0"]:
        return None
    return src["counters1"][name] - src["counters0"][name]


def idle_share_percent(run: dict):
    """1 - (union of device-busy intervals over the traced part), on the
    fullest-loaded chip, in percent."""
    tr = traced(run)
    if tr is None or tr["trace"]["n_devices"] == 0:
        return None
    return 100.0 * (1.0 - tr["trace"]["busy_s_max"] / tr["window_s"])


def call_latency_p50_ms(run: dict, kind: str):
    """Median issue-to-done time of the traced part's calls of ``kind``."""
    from benchmarks.harness import stats
    calls = traced_calls(run, kind)
    if not calls:
        return None
    return stats.percentile(
        [(c["t_done"] - c["t_issue"]) * 1e3 for c in calls], 50.0)


def token_gap_percentile_ms(run: dict, q: float):
    """Percentile over all token gaps of the whole window."""
    from benchmarks.harness import stats
    gaps = stats.token_gaps_ms(run["records"]["streams"], run["t0"],
                               run["t1"])
    return stats.percentile(gaps, q) if gaps else None

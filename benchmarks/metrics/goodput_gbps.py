"""End to end: payload bytes delivered back to the callers' device by
calls that completed inside the window, over the whole window (GB/s).
Headers, tickets and failed calls count nothing."""
from benchmarks.harness import stats


def compute(run):
    return stats.goodput_gbps(run["records"]["calls"], run["t0"], run["t1"])

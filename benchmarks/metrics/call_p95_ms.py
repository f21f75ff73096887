"""End to end: 95th percentile over ALL calls that completed in the
window, host clock from issue to ``block_until_ready`` of the reply on
the caller's device; a failed call counts as over any limit."""
from benchmarks.harness import stats


def compute(run):
    return stats.latency_p95_ms(run["records"]["calls"], run["t0"],
                                run["t1"])

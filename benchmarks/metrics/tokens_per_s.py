"""End to end: all tokens that reached the clients inside the whole
window, over the whole window."""
from benchmarks.harness import stats


def compute(run):
    return stats.tokens_per_s(run["records"]["streams"], run["t0"], run["t1"])

"""Device: 1 - (union of device-busy intervals over the traced part), in
percent, the served-model cell."""
from benchmarks.harness import readers


def compute(run):
    return readers.idle_share_percent(run)

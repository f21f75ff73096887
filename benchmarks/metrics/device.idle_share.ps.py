"""Device: 1 - (union of device-busy intervals over the traced part), in
percent, in the parameter-server cells (the host path is expected to set
the pace there)."""
from benchmarks.harness import readers


def compute(run):
    return readers.idle_share_percent(run)

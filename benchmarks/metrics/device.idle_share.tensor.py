"""Device: 1 - (union of device-busy intervals over the traced part),
on the fullest-loaded chip, in percent."""
from benchmarks.harness import readers


def compute(run):
    return readers.idle_share_percent(run)

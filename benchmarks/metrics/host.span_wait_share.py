"""Host (GIL, locks): 1 - CPU time over wall time of the program
spans' self time, ``wait`` stages left out, in percent: the share of it
in which the thread did not run."""
from benchmarks.harness import program_spans


def compute(run):
    return program_spans.wait_share_percent(run)

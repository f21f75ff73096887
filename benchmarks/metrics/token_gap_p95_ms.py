"""End to end: 95th percentile over ALL gaps between consecutive tokens
of a stream as the client received them, all streams of the window (a
prefill that stalls the decoding slots shows here)."""
from benchmarks.harness import readers


def compute(run):
    return readers.token_gap_percentile_ms(run, 95.0)

"""Layer serving/engine: the median over ALL gaps between consecutive
tokens of a stream as the client received them, whole window: what a
decode step costs a user while nothing stalls it."""
from benchmarks.harness import readers


def compute(run):
    return readers.token_gap_percentile_ms(run, 50.0)

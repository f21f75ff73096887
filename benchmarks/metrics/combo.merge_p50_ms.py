"""Layer rpc/combo_channels: median ``combo.merge`` (the response
merger over the per-chip results of a lowered fan-out), in ms."""
from benchmarks.harness import program_spans


def compute(run):
    return program_spans.dur_p50_ms(run, "combo.merge")

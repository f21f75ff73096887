"""Device: share of the fullest chip's idle time in the traced part
that lies under no program span of any thread (native threads, the TPU
runtime, nothing), in percent."""
from benchmarks.harness import program_spans


def compute(run):
    return program_spans.idle_unattributed_percent(run)

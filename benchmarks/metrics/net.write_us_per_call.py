"""Layer src/cc/net + _core binding: time of ``net.write`` (Python into
the native write), both directions, per completed echo of the traced
part, in us."""
from benchmarks.harness import program_spans


def compute(run):
    return program_spans.us_per(run, ("net.write",), "echo", own=False)

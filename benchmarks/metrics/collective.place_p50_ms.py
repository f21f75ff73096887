"""Layer ici/collective: median ``collective.place`` (the
``device_put`` of the request onto the mesh sharding), in ms."""
from benchmarks.harness import program_spans


def compute(run):
    return program_spans.dur_p50_ms(run, "collective.place")

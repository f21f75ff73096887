"""Layer ici/collective: median ``collective.run`` (program lookup,
the jitted ``shard_map`` call and ``block_until_ready``), in ms."""
from benchmarks.harness import program_spans


def compute(run):
    return program_spans.dur_p50_ms(run, "collective.run")

"""Layer ici/rail + ici/block_pool + ici/endpoint, host side: time of
``rail.ship`` and ``rail.claim``, both directions, per completed echo of
the traced part, in us."""
from benchmarks.harness import program_spans


def compute(run):
    return program_spans.us_per(run, ("rail.ship", "rail.claim"), "echo",
                                own=False)

"""Layer serving/batcher: keys gathered per gather program launched in
the traced part: the ``psserve_lookup_keys`` counter's delta over the
lookup batcher's batches and idle bypasses (one device gather each)."""
from benchmarks.harness import readers


def compute(run):
    keys = readers.counter_delta(run, "psserve_lookup_keys")
    programs = readers.counter_delta(run, "lookup_programs")
    if not keys or not programs:
        return None
    return keys / programs

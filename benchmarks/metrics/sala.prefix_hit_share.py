"""Layer kvcache/store + kvcache/radix: prompt tokens served from the
prefix cache (pages shared AND the state snapshot restored) over prompt
tokens admitted, traced part."""
from benchmarks.harness import readers


def compute(run):
    hit = readers.counter_delta(run, "hit_tokens")
    asked = readers.counter_delta(run, "prompt_tokens")
    if not asked or hit is None:
        return None
    return 100.0 * hit / asked

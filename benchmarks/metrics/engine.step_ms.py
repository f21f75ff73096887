"""Layer serving/engine: the traced part of the window over the decode
steps the engine took in it (``serving_<engine>_steps`` delta): the
whole time over all its steps, prefills and host work included."""
from benchmarks.harness import readers


def compute(run):
    tr = readers.traced(run)
    steps = readers.counter_delta(run, "steps")
    if tr is None or not steps:
        return None
    return 1e3 * tr["window_s"] / steps

"""Layer serving/engine + models/runner.prefill: mean of the program's
own prefill stage timer (``serving_stage_prefill_us``) over the
prefills of the traced part."""
from benchmarks.harness import readers


def compute(run):
    n = readers.counter_delta(run, "prefill_count")
    total = readers.counter_delta(run, "prefill_us_sum")
    if not n or total is None:
        return None
    return total / n / 1e3

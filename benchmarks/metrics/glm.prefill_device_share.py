"""Layer serving/engine, admissions against decoding: device time of
``jit_runner_hybrid_prefill`` (one 256- or 1,024-token bucket through the
experts a request) over the device's busy time in the traced part, in
percent: the share of the chip that the decode steps of the other slots
wait for."""
from benchmarks.harness import readers

PROGRAM = "jit_runner_hybrid_prefill"


def compute(run):
    tr = readers.traced(run)
    secs = readers.program_seconds(run, PROGRAM)
    if tr is None or not secs or not tr["trace"]["busy_s_max"]:
        return None
    return 100.0 * secs / tr["trace"]["busy_s_max"]

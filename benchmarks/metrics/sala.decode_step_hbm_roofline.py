"""Layer models/hybrid, the decode program against the HBM roofline: the
bytes its steps of the traced part MUST move (the weights once a step,
and for every token decoded the K/V of its attended blocks, the
compressed keys it scores and every lightning state read and written)
over the chip's peak bandwidth, against the device time of
``jit_runner_hybrid_step`` there."""
from benchmarks.harness import loader, readers, work_sala

PROGRAM = "jit_runner_hybrid_step"


def compute(run):
    live = loader.load_metric("sala.decode_step_mfu").live_tokens(run)
    steps = readers.counter_delta(run, "steps")
    secs = readers.program_seconds(run, PROGRAM)
    if not live or not steps or not secs:
        return None
    need = steps * work_sala.weight_bytes(run["config"]) + sum(
        work_sala.decode_token_bytes(run["config"], n) for n in live)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / secs

"""Layer models/hybrid, the decode program against the HBM roofline: the
bytes its steps of the traced part MUST move (``work_jamba``: the
weights once a step, 6.06 GB; the state row of every live slot read
once and written once, 10.1 MB each way a slot-step,
``runner_*_mamba_steps``; every DISTINCT live K/V page of the two
attention layers, the shared system prompt's once a step) over the
chip's peak bandwidth, against the device time of
``jit_runner_hybrid_step`` there."""
from benchmarks.harness import loader, readers, work_jamba

PROGRAM = "jit_runner_hybrid_step"


def compute(run):
    steps = readers.counter_delta(run, "steps")
    slot_steps = readers.counter_delta(run, "mamba_steps")
    secs = readers.program_seconds(run, PROGRAM)
    if not steps or not slot_steps or not secs:
        return None
    live = loader.load_metric("sala.decode_step_mfu").live_tokens(run)
    cfg = run["config"]
    pages = work_jamba.distinct_kv_pages(
        cfg, live, steps, int(run["traffic"]["system_prompt_tokens"]))
    need = work_jamba.decode_steps_bytes(cfg, steps, slot_steps, pages)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / secs

"""Layer models/hybrid, the decode program against the HBM roofline: the
bytes its steps of the traced part MUST move (``work_nemotron``: the
fixed weights once a step, 2.0 GB; 11.0 MB a held expert HIT,
``runner_*_moe_experts_hit``; the state row of every live slot read once
and written once, 21.6 MB each way a slot-step, ``runner_*_ssd_steps``;
every DISTINCT live K/V page of the attention block, the shared system
prompt's once a step) over the chip's peak bandwidth, against the
device time of ``jit_runner_hybrid_step`` there."""
from benchmarks.harness import loader, readers, work_nemotron

PROGRAM = "jit_runner_hybrid_step"


def compute(run):
    steps = readers.counter_delta(run, "steps")
    slot_steps = readers.counter_delta(run, "ssd_steps")
    hit = readers.counter_delta(run, "moe_experts_hit")
    secs = readers.program_seconds(run, PROGRAM)
    if not steps or not slot_steps or hit is None or not secs:
        return None
    live = loader.load_metric("sala.decode_step_mfu").live_tokens(run)
    cfg = run["config"]
    pages = work_nemotron.distinct_kv_pages(
        cfg, live, steps, int(run["traffic"]["system_prompt_tokens"]))
    need = work_nemotron.decode_steps_bytes(cfg, steps, slot_steps, hit,
                                            pages)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / secs

"""Layer ops/moe, the routing: distinct HELD experts a decode step's
slots hit, an expert block (``runner_*_moe_experts_hit`` over steps x
expert blocks, traced part).  Of 128; 64 slots x 22 choices over 512
experts hit about 120 of the 128 held at even routing."""
from benchmarks.harness import readers, work_nemotron


def compute(run):
    hit = readers.counter_delta(run, "moe_experts_hit")
    steps = readers.counter_delta(run, "steps")
    if not steps or hit is None:
        return None
    return hit / (steps * work_nemotron.n_blocks(run["config"])[2])

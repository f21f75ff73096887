"""Layer ops/moe, the routing: distinct routed experts a decode step's
slots hit, a layer (``runner_*_moe_experts_hit`` over steps x expert
layers, traced part).  Of 64; 64 draws a layer at 16 slots hit about 41
at even routing."""
from benchmarks.harness import readers, work_glm


def compute(run):
    hit = readers.counter_delta(run, "moe_experts_hit")
    steps = readers.counter_delta(run, "steps")
    if not steps or hit is None:
        return None
    return hit / (steps * work_glm.n_moe_layers(run["config"]))

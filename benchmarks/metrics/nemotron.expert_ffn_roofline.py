"""Layer ops/moe, the held experts' two grouped products in the decode
step against their roofline: the least time they can take
(``work_nemotron.expert_ffn_seconds``: the two matrices of every held
expert HIT read once, 11.0 MB each, or the held assignments' FLOPs,
whichever is longer; at 64 slots the bytes) against the device time of
the ragged products (``lax.ragged_dot``'s kernels, ``ragged-dot-*`` in
the trace; a kernel named ``expert_ffn`` would read the same) inside
``jit_runner_hybrid_step`` in the traced part.  Prefill's are in neither
side.  The assignments are the traced part's decoded tokens x 22 x 5
blocks x the share of the routing that fell here in the same part
(``runner_*_moe_assignments_held`` over ``runner_*_moe_assignments``)."""
from benchmarks.harness import loader, readers, work_nemotron


def compute(run):
    glm = loader.load_metric("glm.expert_ffn_roofline")
    hit = readers.counter_delta(run, "moe_experts_hit")
    held = readers.counter_delta(run, "moe_assignments_held")
    routed = readers.counter_delta(run, "moe_assignments")
    tokens = readers.counter_delta(run, "tokens")
    secs = glm.kernel_seconds(run, glm.KERNELS)
    if not hit or not routed or held is None or secs <= 0:
        return None
    cfg = run["config"]
    assignments = (tokens or 0) * int(cfg["num_experts_per_tok"]) \
        * work_nemotron.n_blocks(cfg)[2] * held / routed
    least = work_nemotron.expert_ffn_seconds(cfg, hit, assignments,
                                             run["peaks"])
    return 100.0 * least / secs

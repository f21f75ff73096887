"""Layer ops/moe, the routed experts' grouped products in the decode
step against their roofline: the least time they can take
(``work_glm.expert_ffn_seconds``: the matrices of every expert HIT read
once, 18.9 MB each, or the assignments' FLOPs, whichever is longer; at
16 slots the bytes) against the device time of the ragged products
(``lax.ragged_dot``'s kernels, ``ragged-dot-*`` in the trace; a kernel
named ``expert_ffn`` would read the same) inside
``jit_runner_hybrid_step`` in the traced part.  Prefill's are in neither
side."""
from benchmarks.harness import loader, readers, work_glm

KERNELS = ("ragged-dot", "expert_ffn")


def kernel_seconds(run, kernels):
    """Device seconds of the named kernels' calls inside the decode
    program (the other served model's reader finds a kernel by name)."""
    one = loader.load_metric("sala.sparse_attend_roofline").kernel_seconds
    return sum(one(run, k) for k in kernels)


def compute(run):
    hit = readers.counter_delta(run, "moe_experts_hit")
    steps = readers.counter_delta(run, "steps")
    tokens = readers.counter_delta(run, "tokens")
    secs = kernel_seconds(run, KERNELS)
    if not hit or not steps or secs <= 0:
        return None
    cfg = run["config"]
    assignments = (tokens or 0) * int(cfg["num_experts_per_tok"]) \
        * work_glm.n_moe_layers(cfg)
    least = work_glm.expert_ffn_seconds(cfg, hit, assignments, run["peaks"])
    return 100.0 * least / secs

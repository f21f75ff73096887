"""Layer ops/moe, the routed experts' ragged products in the decode step
against their roofline at 64 experts top-8: the least time they can take
(``work_mellum.expert_ffn_seconds``: the three matrices of every expert
HIT read once, 12.4 MB each, or the assignments' FLOPs, whichever is
longer; at 32 slots the bytes) against the device time of the ragged
products (``lax.ragged_dot``'s kernels, ``ragged-dot-*`` in the trace;
a kernel named ``expert_ffn`` would read the same) inside
``jit_runner_hybrid_step`` in the traced part.  Prefill's are in neither
side.  The assignments are the traced part's decoded tokens x 8 x 12
layers."""
from benchmarks.harness import loader, readers, work_mellum


def compute(run):
    glm = loader.load_metric("glm.expert_ffn_roofline")
    hit = readers.counter_delta(run, "moe_experts_hit")
    tokens = readers.counter_delta(run, "tokens")
    secs = glm.kernel_seconds(run, glm.KERNELS)
    if not hit or secs <= 0:
        return None
    cfg = run["config"]
    assignments = (tokens or 0) * int(cfg["num_experts_per_tok"]) \
        * int(cfg["num_hidden_layers"])
    least = work_mellum.expert_ffn_seconds(cfg, hit, assignments,
                                           run["peaks"])
    return 100.0 * least / secs

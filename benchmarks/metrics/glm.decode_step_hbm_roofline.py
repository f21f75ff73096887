"""Layer models/hybrid, the decode program against the HBM roofline: the
bytes its steps of the traced part MUST move (``work_glm``: the fixed
weights once a step, every routed expert HIT once, every DISTINCT live
latent page once a layer, an embedding row a token) over the chip's peak
bandwidth, against the device time of ``jit_runner_hybrid_step``
there."""
from benchmarks.harness import readers, work_glm

PROGRAM = "jit_runner_hybrid_step"


def compute(run):
    steps = readers.counter_delta(run, "steps")
    hit = readers.counter_delta(run, "moe_experts_hit")
    pages = readers.counter_delta(run, "latent_pages_distinct")
    tokens = readers.counter_delta(run, "tokens")
    secs = readers.program_seconds(run, PROGRAM)
    if not steps or not secs or hit is None or pages is None:
        return None
    need = work_glm.decode_steps_bytes(run["config"], steps, hit, pages,
                                       tokens or 0)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / secs

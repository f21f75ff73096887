"""Layer models/hybrid, the decode program against the HBM roofline: the
bytes its steps of the traced part MUST move (``work_mellum``: the fixed
weights once a step, 1.0 GB; 12.4 MB a routed expert HIT,
``runner_*_moe_experts_hit``; the K/V pages the full layers' queries
read, ``runner_*_full_pages_read``, and the ring pages the window
layers' queries read, ``runner_*_window_pages_read``, 128 KB a page a
layer; an embedding row a token) over the chip's peak bandwidth, against
the device time of ``jit_runner_hybrid_step`` there."""
from benchmarks.harness import readers, work_mellum

PROGRAM = "jit_runner_hybrid_step"


def compute(run):
    steps = readers.counter_delta(run, "steps")
    hit = readers.counter_delta(run, "moe_experts_hit")
    full = readers.counter_delta(run, "full_pages_read")
    ring = readers.counter_delta(run, "window_pages_read")
    tokens = readers.counter_delta(run, "tokens")
    secs = readers.program_seconds(run, PROGRAM)
    if not steps or not secs or hit is None or full is None or ring is None:
        return None
    need = work_mellum.decode_steps_bytes(run["config"], steps, hit, full,
                                          ring, tokens or 0)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / secs

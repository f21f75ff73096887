"""Layer ops/mamba, the scan's slot update against the HBM roofline in
the decode step: every Mamba layer's scan state of each slot-step read
once and written once (``work_jamba.scan_step_bytes``: 26 layers x 16 x
5,120 x 4 B, twice; slot-steps from ``runner_*_mamba_steps``) over the
chip's peak bandwidth, against the device time of the ``mamba_step``
kernel calls inside ``jit_runner_hybrid_step`` in the traced part (the
convolution's update, ``mamba_conv``, is in neither side)."""
from benchmarks.harness import readers, work_jamba

PROGRAM = "jit_runner_hybrid_step"
KERNEL = "mamba_step"


def kernel_calls(run, program, kernel):
    """``(calls, device seconds)`` of the named kernel inside the named
    program in the traced part."""
    if not run.get("traced"):
        return 0, 0.0
    ops = run["traced"]["trace"]["ops"].get(program, {})
    mine = [v for op, v in ops.items() if kernel in op.split(" ")[0]]
    return sum(v[0] for v in mine), sum(v[1] for v in mine)


def compute(run):
    slot_steps = readers.counter_delta(run, "mamba_steps")
    _, secs = kernel_calls(run, PROGRAM, KERNEL)
    if not slot_steps or secs <= 0:
        return None
    need = work_jamba.scan_step_bytes(run["config"], slot_steps)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / secs

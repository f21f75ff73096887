"""Layer ops/ssd, the recurrence's slot update against the HBM roofline
in the decode step: every Mamba-2 block's scan state of each slot-step
read once and written once (``work_nemotron.ssd_step_bytes``: 5 blocks x
128 x 64 x 128 x 4 B, twice; slot-steps from ``runner_*_ssd_steps``)
over the chip's peak bandwidth, against the device time of the
``ssd_step`` kernel calls inside ``jit_runner_hybrid_step`` in the
traced part (the convolution's update, ``ssd_conv``, is in neither
side)."""
from benchmarks.harness import loader, readers, work_nemotron

PROGRAM = "jit_runner_hybrid_step"
KERNEL = "ssd_step"


def compute(run):
    slot_steps = readers.counter_delta(run, "ssd_steps")
    _, secs = loader.load_metric(
        "jamba.scan_step_roofline").kernel_calls(run, PROGRAM, KERNEL)
    if not slot_steps or secs <= 0:
        return None
    need = work_nemotron.ssd_step_bytes(run["config"], slot_steps)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / secs

"""Layer ops/lightning, the state kernel's share of the HBM roofline in
the decode step: every lightning layer's float32 state of each decoded
token read once and written once (``work_sala.lightning_state_bytes``:
12 layers x 32 heads x 128 x 128 x 4 B, twice) over the chip's peak
bandwidth, against the device time of the ``lightning_state`` kernel
calls inside ``jit_runner_hybrid_step`` in the traced part."""
from benchmarks.harness import loader, work_sala

KERNEL = "lightning_state"


def compute(run):
    mfu = loader.load_metric("sala.decode_step_mfu")
    live = mfu.live_tokens(run)
    if not live:
        return None
    secs = loader.load_metric("sala.sparse_attend_roofline").kernel_seconds(
        run, KERNEL)
    if secs <= 0:
        return None
    need = len(live) * work_sala.lightning_state_bytes(run["config"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / secs

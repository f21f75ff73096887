"""Layer ops/ssd, the chunk scan against its roofline in prefill: the
least time the scans of the traced part's prefill chunks can take
(``work_nemotron.ssd_prefill_seconds``: the dual form's matrix products
at ``chunk_size`` 128 at the bf16 peak, or ``xs``, ``delta``, ``B``,
``C`` in, ``y`` out and the state once each way a chunk at the peak
bandwidth, whichever is longer; positions from ``runner_*_ssd_tokens``,
chunks = the kernel's calls over the Mamba-2 blocks) against the device
time of ``ssd_scan`` inside ``jit_runner_hybrid_prefill``.  The kernel
multiplies in float32 (the reference's recurrence rounds nothing to the
state), six passes of the MXU where the peak counts one: the share says
how far the kernel is from one bf16 pass over the products it must make,
and a sixth of it is the most this precision allows."""
from benchmarks.harness import loader, readers, work_nemotron

PROGRAM = "jit_runner_hybrid_prefill"
KERNEL = "ssd_scan"


def compute(run):
    positions = readers.counter_delta(run, "ssd_tokens")
    calls, secs = loader.load_metric(
        "jamba.scan_step_roofline").kernel_calls(run, PROGRAM, KERNEL)
    if not positions or secs <= 0:
        return None
    cfg = run["config"]
    chunks = calls / max(1, work_nemotron.n_blocks(cfg)[0])
    least = work_nemotron.ssd_prefill_seconds(cfg, positions, chunks,
                                              run["peaks"])
    return 100.0 * least / secs

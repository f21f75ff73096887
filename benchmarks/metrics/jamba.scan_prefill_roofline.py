"""Layer ops/mamba, the chunk scan against the HBM roofline in prefill:
what the scans of the traced part's prefill chunks must move
(``work_jamba.scan_prefill_bytes``: ``xc``, ``delta``, ``z`` in and
``y`` out a valid position, ``B``, ``C``, the state once in and once
out a chunk, every Mamba layer; positions from
``runner_*_mamba_tokens``, chunks = the kernel's calls over the Mamba
layers) over the chip's peak bandwidth, against the device time of
``mamba_scan`` inside ``jit_runner_hybrid_prefill``.  The kernel is
bound by elementwise work on ``[16, channels]`` a position, not by
bandwidth: the share says how far from the bytes' time it runs, not
how far from what the vector unit allows (no such peak in
``harness/peaks.py``)."""
from benchmarks.harness import loader, readers, work_jamba

PROGRAM = "jit_runner_hybrid_prefill"
KERNEL = "mamba_scan"


def compute(run):
    positions = readers.counter_delta(run, "mamba_tokens")
    calls, secs = loader.load_metric(
        "jamba.scan_step_roofline").kernel_calls(run, PROGRAM, KERNEL)
    if not positions or secs <= 0:
        return None
    cfg = run["config"]
    chunks = calls / max(1, work_jamba.n_layers(cfg)[0])
    need = work_jamba.scan_prefill_bytes(cfg, positions, chunks)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / secs

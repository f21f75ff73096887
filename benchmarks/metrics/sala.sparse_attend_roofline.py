"""Layer ops/sparse_attention, the kernel's share of the HBM roofline in
the decode step: K and V of the blocks each decoded token attends to
(``work_sala.sparse_attend_bytes``: 64 blocks of 64 tokens, 2 K/V heads,
4 layers, bf16) over the chip's peak bandwidth, against the device time
of the ``sparse_attend`` kernel calls inside ``jit_runner_hybrid_step``
in the traced part.  Prefill's calls of the kernel are in neither
side."""
from benchmarks.harness import loader, work_sala

PROGRAM = "jit_runner_hybrid_step"
KERNEL = "sparse_attend"


def kernel_seconds(run, kernel):
    if not run.get("traced"):
        return 0.0
    ops = run["traced"]["trace"]["ops"].get(PROGRAM, {})
    return sum(v[1] for op, v in ops.items() if kernel in op.split(" ")[0])


def compute(run):
    live = loader.load_metric("sala.decode_step_mfu").live_tokens(run)
    if not live:
        return None
    secs = kernel_seconds(run, KERNEL)
    if secs <= 0:
        return None
    need = sum(work_sala.sparse_attend_bytes(run["config"], n) for n in live)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / secs

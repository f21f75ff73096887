"""Layer models/hybrid, the whole step: FLOPs the tokens delivered in
the traced part MUST cost (``work_jamba.decode_token_flops`` at each
token's own live length: every matrix of the 26 Mamba and 2 attention
layers, 28 gated MLPs and the tied head, scores and values over the
K/V of the two attention layers, the scan's update) over (traced
seconds x the chip's bf16 peak).  Needs no program name, so it bounds
every kernel's roofline below it."""
from benchmarks.harness import loader, readers, work_jamba


def compute(run):
    live = loader.load_metric("sala.decode_step_mfu").live_tokens(run)
    if not live:
        return None
    flops = sum(work_jamba.decode_token_flops(run["config"], n)
                for n in live)
    return 100.0 * flops / (readers.traced(run)["window_s"]
                            * run["peaks"]["flops_bf16"])

"""Layer models/hybrid, the whole step: FLOPs the tokens delivered in
the traced part MUST cost here (``work_nemotron.decode_token_flops`` at
each token's own live length: every matrix of the 5 Mamba-2 and the
attention block, of the 5 expert blocks the router, the latent
projections, the shared expert and the 22 x 128/512 chosen experts that
are held, the head over the held rows, scores and values over the K/V,
the recurrence's update) over (traced seconds x the chip's bf16 peak).
Needs no program name, so it bounds every kernel's roofline below
it."""
from benchmarks.harness import loader, readers, work_nemotron


def compute(run):
    live = loader.load_metric("sala.decode_step_mfu").live_tokens(run)
    if not live:
        return None
    flops = sum(work_nemotron.decode_token_flops(run["config"], n)
                for n in live)
    return 100.0 * flops / (readers.traced(run)["window_s"]
                            * run["peaks"]["flops_bf16"])

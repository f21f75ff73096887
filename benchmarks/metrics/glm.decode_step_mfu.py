"""Layer models/hybrid, the whole step: FLOPs the tokens delivered in
the traced part MUST cost (``work_glm.decode_token_flops`` at each
token's own live length: every attention matrix of the 8 layers, scores
and values over the latent rows at the published head sizes, the dense
MLP, the router, 4 routed + 1 shared expert of 7 layers, the head) over
(traced seconds x the chip's bf16 peak).  Needs no program name, so it
bounds every kernel's roofline below it."""
from benchmarks.harness import loader, readers, work_glm


def compute(run):
    live = loader.load_metric("sala.decode_step_mfu").live_tokens(run)
    if not live:
        return None
    flops = sum(work_glm.decode_token_flops(run["config"], n) for n in live)
    return 100.0 * flops / (readers.traced(run)["window_s"]
                            * run["peaks"]["flops_bf16"])

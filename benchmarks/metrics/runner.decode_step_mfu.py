"""Layer models/runner, the whole step: FLOPs the decode steps of the
traced part MUST do (``work.decode_step_flops`` at the batch actually
decoded: tokens delivered there, each attending to its sequence's live
tokens) over (traced seconds x the chip's bf16 peak).  Needs no program
name, so it bounds every kernel's roofline below it."""
from benchmarks.harness import readers, work


def compute(run):
    tr = readers.traced(run)
    if tr is None:
        return None
    cfg = run["config"]
    flops = 0.0
    for c in run["records"]["calls"]:
        n = len(c["prompt"])
        for j, t in enumerate(c.get("times", ())):
            if tr["t0"] <= t <= tr["t1"]:
                flops += work.decode_step_flops(cfg, 1, n + j)
    if flops <= 0:
        return None
    return 100.0 * flops / (tr["window_s"] * run["peaks"]["flops_bf16"])

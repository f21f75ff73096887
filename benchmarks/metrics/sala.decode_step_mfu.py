"""Layer models/hybrid, the whole step: FLOPs the tokens delivered in
the traced part MUST cost (``work_sala.decode_token_flops`` at each
token's own live length: every matrix of the 16 layers and the head, the
scores over the compressed keys, attention over the attended blocks, the
state update) over (traced seconds x the chip's bf16 peak).  Needs no
program name, so it bounds every kernel's roofline below it."""
from benchmarks.harness import readers, work_sala


def live_tokens(run):
    """For every token that reached a client inside the traced part, the
    tokens its sequence held when the step that made it ran."""
    tr = readers.traced(run)
    if tr is None:
        return []
    return [c["prompt_len"] + j for c in run["records"]["calls"]
            for j, t in enumerate(c.get("times", ()))
            if tr["t0"] <= t <= tr["t1"]]


def compute(run):
    live = live_tokens(run)
    if not live:
        return None
    flops = sum(work_sala.decode_token_flops(run["config"], n) for n in live)
    return 100.0 * flops / (readers.traced(run)["window_s"]
                            * run["peaks"]["flops_bf16"])

"""Per-stage host-CPU accounting (ISSUE 6).

``time.thread_time()`` deltas recorded by the serving hot paths —
batch formation (batcher drainer), prefill and decode-step bookkeeping
(engine thread, MINUS the model-fn calls, which are accounted
separately under ``model_compute``), emit fan-out (per-request emitter
threads), span submit (the bvar collector drainer) — accumulate into
per-stage Adders, and roll up into ONE honest headline:

    serving_host_us_per_token = python-host CPU microseconds spent
        across all serving stages / tokens emitted

The native frame pump runs no Python and cannot be thread_time()'d
from here.  ``model_compute`` (the jit'd
prefill/step calls) is deliberately EXCLUDED from the per-token
rollup: the metric exists to size the de-GIL prize (ROADMAP item 4),
which is host bookkeeping, not model math.
"""
from __future__ import annotations

from brpc_tpu.bvar.reducer import Adder, PassiveStatus

# stages that are python-host work (counted in the per-token rollup)
HOST_STAGES = ("batch_formation", "prefill", "decode_step",
               "emit_fanout", "span_submit")
# informational: CPU burned inside the user model fns (jit'd compute)
MODEL_STAGE = "model_compute"

_adders: dict[str, Adder] = {
    s: Adder(f"serving_host_cpu_{s}_us")
    for s in HOST_STAGES + (MODEL_STAGE,)
}

# total tokens emitted by every engine (the rollup's denominator)
tokens_total = Adder("serving_tokens_total")


def add(stage: str, us: float) -> None:
    """Record `us` microseconds of host CPU attributed to `stage`."""
    if us > 0:
        _adders[stage].add(int(us))


def stage_us(stage: str) -> int:
    return _adders[stage].get_value()


def host_us_per_token() -> float:
    toks = tokens_total.get_value()
    if not toks:
        return 0.0
    host = sum(_adders[s].get_value() for s in HOST_STAGES)
    return round(host / toks, 2)


def snapshot() -> dict:
    return {
        "per_stage_us": {s: _adders[s].get_value()
                         for s in HOST_STAGES + (MODEL_STAGE,)},
        "tokens": tokens_total.get_value(),
        "host_us_per_token": host_us_per_token(),
    }


PassiveStatus(host_us_per_token).expose("serving_host_us_per_token")

"""What Mellum 2's decode step MUST do, from the configuration's shapes
only (``configs/mellum2_12b_l12_1chip.json``): parameters, HBM bytes and
FLOPs of the whole step, of the window layers' attention over their
rings and of the routed experts.  The counts read the same work whatever
implements it: an expert's three matrices are read once a step where ANY
token chose it (the experts HIT, a program counter); a window query's
keys lie in the ring pages its window spans (at most 17 of 64 tokens for
1,024 positions), a full layer's in every page of its sequence (the
pages its queries read, ``runner_*_window_pages_read`` /
``_full_pages_read``, each summed over the layers of its kind).
"""
from __future__ import annotations

from benchmarks.harness.reference_mellum import FULL, WINDOW, model_cfg


def param_counts(cfg: dict) -> dict:
    """Parameters of one attention mixer (either kind), one routed
    expert, one router, the embedding, the head, the norms of a layer,
    and of everything held (norms included)."""
    m = model_cfg(cfg)
    dm = m["dm"]
    out = {"attention": 2 * dm * m["h"] * m["d"] + 2 * dm * m["hkv"] * m["d"],
           "expert": 3 * dm * m["fe"], "router": dm * m["experts"],
           "embedding": m["vocab"] * dm, "head": m["vocab"] * dm,
           "norms": 2 * dm}
    n = len(m["kinds"])
    out["held"] = n * (out["attention"] + m["experts"] * out["expert"]
                       + out["router"] + out["norms"]) \
        + out["embedding"] + out["head"] + dm
    return out


def n_layers(cfg: dict) -> tuple:
    """``(window layers, full layers)`` held."""
    kinds = model_cfg(cfg)["kinds"]
    return kinds.count(WINDOW), kinds.count(FULL)


def _item(m: dict) -> int:
    return 2 if m["param_dtype"] == "bfloat16" else 4


def fixed_weight_bytes(cfg: dict) -> float:
    """What one decode step reads of the weights whatever its batch and
    whatever is routed where: every layer's attention, the float32
    routers, the norms and the head once (the embedding is a row a
    token)."""
    m = model_cfg(cfg)
    p = param_counts(cfg)
    n = len(m["kinds"])
    return float(_item(m) * (n * p["attention"] + p["head"])
                 + 4 * (n * (p["router"] + p["norms"]) + m["dm"]))


def expert_bytes(cfg: dict) -> float:
    """One routed expert's three matrices (12.4 MB at the published
    widths, bfloat16)."""
    m = model_cfg(cfg)
    return float(_item(m) * param_counts(cfg)["expert"])


def page_bytes(cfg: dict) -> float:
    """K and V of one page of one layer: ``page_tokens`` x 2 x K/V heads
    x head size, bfloat16 (a ring page is the same)."""
    m = model_cfg(cfg)
    return float(int(cfg["page_tokens"]) * 2 * m["hkv"] * m["d"] * 2)


def decode_steps_bytes(cfg: dict, steps: int, experts_hit: int,
                       full_pages: int, ring_pages: int,
                       tokens: int) -> float:
    """HBM bytes ``steps`` decode steps must move: the fixed weights a
    step, the experts hit (summed over layers and steps), the full
    layers' pages and the window layers' ring pages the queries read
    (each summed over the layers of its kind), an embedding row a
    token."""
    m = model_cfg(cfg)
    return (steps * fixed_weight_bytes(cfg) + experts_hit * expert_bytes(cfg)
            + (full_pages + ring_pages) * page_bytes(cfg)
            + tokens * m["dm"] * _item(m))


def window_attend_seconds(cfg: dict, ring_pages: int, peaks: dict) -> float:
    """The least time the window layers' decode attention can take: the
    ring pages its queries visited read once, or scores and values over
    every key of them (32 heads x 128, two products, 2 FLOPs a
    multiply-add), whichever is longer."""
    m = model_cfg(cfg)
    keys = ring_pages * int(cfg["page_tokens"])
    flops = keys * m["h"] * m["d"] * 4.0
    return max(ring_pages * page_bytes(cfg) / peaks["hbm_bytes_per_s"],
               flops / peaks["flops_bf16"])


def expert_ffn_seconds(cfg: dict, experts_hit: int, assignments: int,
                       peaks: dict) -> float:
    """The least time the routed experts' products can take: their
    matrices read once an expert hit, or the assignments' FLOPs,
    whichever is longer."""
    p = param_counts(cfg)
    return max(experts_hit * expert_bytes(cfg) / peaks["hbm_bytes_per_s"],
               2.0 * assignments * p["expert"] / peaks["flops_bf16"])

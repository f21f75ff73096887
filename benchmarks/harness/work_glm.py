"""What GLM-4.7-Flash's decode step MUST do, from the configuration's
shapes only (``configs/glm47_flash_l8_1chip.json``): parameters, FLOPs
and HBM bytes of the whole step and of each new kernel.  The counts read
the same work whatever implements it: an expert's matrices are read once
a step where ANY token chose it (the experts HIT, a program counter),
and a latent page once a layer a step however many slots share it (the
DISTINCT live pages).
"""
from __future__ import annotations

from benchmarks.harness.reference_glm import MOE, model_cfg


def param_counts(cfg: dict) -> dict:
    """Parameters (the small norm weights and the correction bias left
    out) of one latent-attention mixer, the dense MLP, one routed
    expert, a layer's shared experts and router, the embedding, the
    head, and of all layers held."""
    m = model_cfg(cfg)
    dm, h = m["dm"], m["h"]
    out = {"mla": dm * m["ql"] + m["ql"] * h * (m["nope"] + m["rope"])
           + dm * (m["r"] + m["rope"]) + m["r"] * h * (m["nope"] + m["v"])
           + h * m["v"] * dm,
           "dense_ffn": 3 * dm * m["ff"],
           "expert": 3 * dm * m["fe"],
           "shared": 3 * dm * m["fe"] * m["shared"],
           "router": dm * m["experts"],
           "embedding": m["vocab"] * dm, "head": m["vocab"] * dm}
    n_moe = sum(1 for f in m["ffns"] if f == MOE)
    n_dense = len(m["ffns"]) - n_moe
    out["layers"] = len(m["ffns"]) * out["mla"] + n_dense * out["dense_ffn"] \
        + n_moe * (m["held"][1] * out["expert"] + out["shared"]
                   + out["router"])
    return out


def n_moe_layers(cfg: dict) -> int:
    return sum(1 for f in model_cfg(cfg)["ffns"] if f == MOE)


def _item(m: dict) -> int:
    return 2 if m["param_dtype"] == "bfloat16" else 4


def fixed_weight_bytes(cfg: dict) -> float:
    """What one decode step reads of the weights whatever its batch and
    whatever is routed where: every layer's attention, the dense MLP,
    the shared experts, the float32 routers, and the head once (the
    embedding is a row a token)."""
    m = model_cfg(cfg)
    p = param_counts(cfg)
    n_moe = n_moe_layers(cfg)
    n_dense = len(m["ffns"]) - n_moe
    return float(_item(m) * (len(m["ffns"]) * p["mla"]
                             + n_dense * p["dense_ffn"]
                             + n_moe * p["shared"] + p["head"])
                 + 4 * n_moe * p["router"])


def expert_bytes(cfg: dict) -> float:
    """One routed expert's three matrices."""
    m = model_cfg(cfg)
    return float(_item(m) * param_counts(cfg)["expert"])


def latent_page_bytes(cfg: dict) -> float:
    """One page of one layer: ``page_tokens`` rows of ``kv_lora_rank +
    rope`` bfloat16 values (what the model caches; lanes the arena pads a
    row with are no work)."""
    m = model_cfg(cfg)
    return float(int(cfg["page_tokens"]) * (m["r"] + m["rope"]) * 2)


def decode_steps_bytes(cfg: dict, steps: int, experts_hit: int,
                       latent_pages: int, tokens: int) -> float:
    """HBM bytes ``steps`` decode steps must move: the fixed weights a
    step, the experts hit (summed over layers and steps), the distinct
    latent pages (summed likewise), an embedding row a token."""
    m = model_cfg(cfg)
    return (steps * fixed_weight_bytes(cfg) + experts_hit * expert_bytes(cfg)
            + latent_pages * latent_page_bytes(cfg)
            + tokens * m["dm"] * _item(m))


def decode_token_flops(cfg: dict, live_tokens: int) -> float:
    """FLOPs one decoded position must cost (2 a multiply-add): the
    attention matrices of every layer (``kv_b``'s once: expanding one
    position's keys and values, or absorbing it into one query, is the
    same count), scores and values over ``live_tokens`` keys at the
    published head sizes, the dense MLP, the router, ``k`` routed and
    the shared experts of every expert layer, and the head."""
    m = model_cfg(cfg)
    p = param_counts(cfg)
    n_moe = n_moe_layers(cfg)
    n_dense = len(m["ffns"]) - n_moe
    mat = 2.0 * (len(m["ffns"]) * p["mla"] + n_dense * p["dense_ffn"]
                 + n_moe * (m["k"] * p["expert"] + p["shared"]
                            + p["router"]) + p["head"])
    attn = len(m["ffns"]) * m["h"] * 2.0 * live_tokens \
        * (m["nope"] + m["rope"] + m["v"])
    return float(mat + attn)


def expert_ffn_seconds(cfg: dict, experts_hit: int, assignments: int,
                       peaks: dict) -> float:
    """The least time the routed experts' products can take: their
    matrices read once an expert hit, or the assignments' FLOPs,
    whichever is longer."""
    p = param_counts(cfg)
    return max(experts_hit * expert_bytes(cfg) / peaks["hbm_bytes_per_s"],
               2.0 * assignments * p["expert"] / peaks["flops_bf16"])


def latent_attend_seconds(cfg: dict, latent_pages: int, tokens_read: int,
                          peaks: dict) -> float:
    """The least time attention over the latent pages can take: every
    distinct page read once a layer, or scores and values of every row
    read (``tokens_read``: rows attended, summed over layers), whichever
    is longer."""
    m = model_cfg(cfg)
    flops = tokens_read * m["h"] * 2.0 * (m["nope"] + m["rope"] + m["v"])
    return max(latent_pages * latent_page_bytes(cfg)
               / peaks["hbm_bytes_per_s"], flops / peaks["flops_bf16"])

"""What MiniCPM-SALA's decode step MUST do, from the configuration's
shapes only (``configs/minicpm_sala_l16_1chip.json``): parameters,
FLOPs and HBM bytes of the whole step and of each new kernel.  The
counts read the same work whatever implements it.
"""
from __future__ import annotations

from benchmarks.harness.reference_sala import LINEAR, SPARSE, model_cfg


def param_counts(cfg: dict) -> dict:
    """Parameters of one MLP, one mixer of each kind (the small norm
    weights left out), the embedding and the head, and of all layers
    held."""
    m = model_cfg(cfg)
    dm = m["dm"]
    attn = m["h"] * m["d"]
    lin = m["hl"] * m["dl"]
    out = {"mlp": 3 * dm * m["ff"],
           SPARSE: 3 * dm * attn + 2 * dm * m["hkv"] * m["d"],
           LINEAR: 5 * dm * lin,
           "embedding": m["vocab"] * dm, "head": m["vocab"] * dm}
    out["layers"] = sum(out["mlp"] + out[k] for k in m["mixers"])
    return out


def _n(m: dict) -> tuple:
    n_s = sum(1 for k in m["mixers"] if k == SPARSE)
    return n_s, len(m["mixers"]) - n_s


def kernels_live(m: dict, live_tokens: int) -> int:
    """Compressed keys a query at position ``live_tokens - 1`` scores."""
    return max(0, (live_tokens - m["kernel"]) // m["stride"] + 1)


def blocks_attended(m: dict, live_tokens: int) -> int:
    """Blocks a query at position ``live_tokens - 1`` attends to."""
    blocks = -(-live_tokens // m["block"])
    if live_tokens <= m["dense_len"]:
        return blocks
    return min(m["topk"], blocks)


def sparse_attend_bytes(cfg: dict, live_tokens: int) -> float:
    """K and V of the attended blocks, every attention layer and K/V
    head, one decode position (bf16 pages)."""
    m = model_cfg(cfg)
    n_s, _ = _n(m)
    return float(n_s * m["hkv"] * blocks_attended(m, live_tokens)
                 * m["block"] * m["d"] * 2 * 2)


def compressed_key_bytes(cfg: dict, live_tokens: int) -> float:
    m = model_cfg(cfg)
    n_s, _ = _n(m)
    if live_tokens <= m["dense_len"]:
        return 0.0
    return float(n_s * kernels_live(m, live_tokens) * m["hkv"] * m["d"] * 2)


def lightning_state_bytes(cfg: dict) -> float:
    """Every lightning layer's float32 state read once and written
    once, one decode position."""
    m = model_cfg(cfg)
    _, n_l = _n(m)
    return float(2 * n_l * m["hl"] * m["dl"] * m["dl"] * 4)


def weight_bytes(cfg: dict) -> float:
    """What one decode step reads of the weights whatever its batch:
    every layer and the head once (the embedding is a row a token)."""
    m = model_cfg(cfg)
    item = 2 if m["param_dtype"] == "bfloat16" else 4
    p = param_counts(cfg)
    return float((p["layers"] + p["head"]) * item)


def decode_token_bytes(cfg: dict, live_tokens: int) -> float:
    """HBM bytes one decoded position adds to its step."""
    m = model_cfg(cfg)
    item = 2 if m["param_dtype"] == "bfloat16" else 4
    return (sparse_attend_bytes(cfg, live_tokens)
            + compressed_key_bytes(cfg, live_tokens)
            + lightning_state_bytes(cfg) + m["dm"] * item)


def decode_token_flops(cfg: dict, live_tokens: int) -> float:
    """FLOPs one decoded position must cost (2 a multiply-add): every
    layer's matrices and the head, the scores over the compressed keys,
    attention over the attended blocks, the state update and read."""
    m = model_cfg(cfg)
    n_s, n_l = _n(m)
    p = param_counts(cfg)
    mat = 2.0 * (p["layers"] + p["head"])
    keys = min(live_tokens, blocks_attended(m, live_tokens) * m["block"])
    attn = n_s * m["h"] * m["d"] * 2 * 2 * keys
    if live_tokens > m["dense_len"]:
        attn += n_s * m["h"] * m["d"] * 2 * kernels_live(m, live_tokens)
    state = n_l * m["hl"] * m["dl"] * m["dl"] * (3 + 2)
    return float(mat + attn + state)

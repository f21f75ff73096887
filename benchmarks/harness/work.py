"""The operations and bytes each kernel and the whole step MUST do,
computed from the configuration's shapes only, never from what the
program happens to run.  A later PR that replaces a kernel or fuses a
step cannot move a roofline share by changing these counts.
"""
from __future__ import annotations


# ---- tensor rail -----------------------------------------------------------

def echo_hbm_bytes(payload_bytes: int) -> int:
    """HBM traffic an echo of ``payload_bytes`` needs whatever moves it:
    the request is read once and written once on its way to the server,
    and the reply is read once and written once on its way back."""
    return 4 * int(payload_bytes)


def echo_link_bytes(payload_bytes: int) -> int:
    """Bytes an echo between two chips puts on the link, both ways
    together (each direction carries the payload once)."""
    return 2 * int(payload_bytes)


# ---- dense transformer decode ----------------------------------------------

def transformer_params(cfg: dict) -> int:
    """Parameter count of the dense stand-in: tied embedding/head,
    per-layer q/k/v/o projections and a two-matrix MLP, no norm
    weights, no biases (``dense2048_standin.json`` states the layout)."""
    d, L = cfg["d_model"], cfg["n_layers"]
    h, hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    dff, v = cfg["d_ff"], cfg["vocab"]
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    mlp = 2 * d * dff
    return v * d + L * (attn + mlp)


def decode_step_flops(cfg: dict, batch: int, live_tokens: int) -> float:
    """FLOPs of one decode step that advances ``batch`` sequences by
    one token while ``live_tokens`` cached tokens (summed over the
    batch) are attended to.  2 FLOPs per multiply-add."""
    d, L = cfg["d_model"], cfg["n_layers"]
    h, hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    dff, v = cfg["d_ff"], cfg["vocab"]
    proj = 2 * d * (h + 2 * hkv) * hd + 2 * h * hd * d
    mlp = 2 * 2 * d * dff
    per_token = L * (proj + mlp) + 2 * d * v
    attn = L * 2 * 2 * h * hd * live_tokens      # q.k and p.v
    return float(batch * per_token + attn)


def kv_bytes_per_token(cfg: dict) -> int:
    item = cfg.get("kv_itemsize", 4)
    return cfg["n_layers"] * 2 * cfg["n_kv_heads"] * cfg["head_dim"] * item


def decode_step_bytes(cfg: dict, batch: int, live_tokens: int) -> float:
    """HBM bytes one decode step must move: every weight once, and the
    K and V of every live token once (activations are negligible
    beside them at these batches)."""
    item = cfg.get("param_itemsize", 4)
    return float(transformer_params(cfg) * item
                 + kv_bytes_per_token(cfg) * live_tokens)


def paged_attention_bytes(cfg: dict, live_tokens: int) -> float:
    """K/V read once per query, all layers, one decode step."""
    return float(kv_bytes_per_token(cfg) * live_tokens)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds the chip could take, which bound it is)."""
    tf = flops / peaks["flops_bf16"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tf, "compute") if tf >= tb else (tb, "memory")

"""What AI21-Jamba2-3B's programs MUST do, from the configuration's
shapes only (``configs/jamba2_3b_1chip.json``): parameters, FLOPs and
HBM bytes of the whole decode step and of the two state-space kernels.
The counts read the same work whatever implements it: a sequence's
recurrent state is what the MODEL keeps (16 scan values and 3 inputs of
the convolution a channel a Mamba layer), not what a layout pads it to.
"""
from __future__ import annotations

from benchmarks.harness.reference_jamba import ATTN, MAMBA, model_cfg


def param_counts(cfg: dict) -> dict:
    """Parameters of one Mamba mixer (everything its published count
    has: the convolution and its bias, ``dt_proj``'s bias, ``A_log``,
    ``D``, the dt/B/C norms), its matrices alone, one attention mixer,
    the gated MLP, the tied embedding, and of all layers."""
    m = model_cfg(cfg)
    dm, di, n, r = m["dm"], m["di"], m["n"], m["r"]
    mats = dm * 2 * di + di * (r + 2 * n) + r * di + di * dm
    out = {"mamba_matrices": mats,
           MAMBA: mats + di * (m["taps"] + 1) + di + di * n + di
           + r + 2 * n,
           ATTN: 2 * dm * m["h"] * m["d"] + 2 * dm * m["hkv"] * m["d"],
           "mlp": 3 * dm * m["ff"], "embedding": m["vocab"] * dm}
    out["layers"] = sum(out["mlp"] + out[k] for k in m["mixers"])
    return out


def n_layers(cfg: dict) -> tuple:
    """``(Mamba layers, attention layers)``."""
    mixers = model_cfg(cfg)["mixers"]
    n_m = sum(1 for k in mixers if k == MAMBA)
    return n_m, len(mixers) - n_m


def _item(m: dict) -> int:
    return 2 if m["param_dtype"] == "bfloat16" else 4


def weight_bytes(cfg: dict) -> float:
    """What one decode step reads of the weights whatever its batch:
    every layer once and the embedding once, as the head (a token's own
    embedding row lies in it)."""
    m = model_cfg(cfg)
    p = param_counts(cfg)
    return float((p["layers"] + p["embedding"]) * _item(m))


def scan_state_bytes(cfg: dict) -> float:
    """One sequence's scan state, every Mamba layer: ``[16, 5120]``
    float32 each."""
    m = model_cfg(cfg)
    return float(n_layers(cfg)[0] * m["n"] * m["di"] * 4)


def state_row_bytes(cfg: dict) -> float:
    """What a sequence keeps between positions: the scan state and the
    convolution's last ``taps - 1`` inputs of every Mamba layer."""
    m = model_cfg(cfg)
    return float(n_layers(cfg)[0] * (m["n"] + m["taps"] - 1) * m["di"] * 4)


def kv_page_bytes(cfg: dict) -> float:
    """One page of both attention layers: K and V of ``page_tokens``
    positions, bfloat16."""
    m = model_cfg(cfg)
    return float(n_layers(cfg)[1] * 2 * m["hkv"] * m["d"]
                 * int(cfg["page_tokens"]) * 2)


def distinct_kv_pages(cfg: dict, live: list, steps: int,
                      shared_tokens: int) -> int:
    """K/V pages the steps that made ``live`` (each token's sequence
    length at its step) must read, a page shared by every slot (the
    system prompt's) once a step."""
    t = int(cfg["page_tokens"])
    shared = shared_tokens // t
    return int(sum(max(0, -(-n // t) - shared) for n in live)
               + steps * shared)


def decode_steps_bytes(cfg: dict, steps: int, slot_steps: int,
                       kv_pages: int) -> float:
    """HBM bytes ``steps`` decode steps must move: the weights once a
    step, the state row of every live slot read once and written once,
    the distinct live K/V pages."""
    return (steps * weight_bytes(cfg)
            + slot_steps * 2 * state_row_bytes(cfg)
            + kv_pages * kv_page_bytes(cfg))


def decode_token_flops(cfg: dict, live_tokens: int) -> float:
    """FLOPs one decoded position must cost (2 a multiply-add): every
    matrix of the 28 layers and the head, scores and values of the two
    attention layers over ``live_tokens`` keys, and the scan's update
    and read (``exp``, three products and two sums a state value)."""
    m = model_cfg(cfg)
    p = param_counts(cfg)
    n_m, n_a = n_layers(cfg)
    mat = 2.0 * (n_m * p["mamba_matrices"] + n_a * p[ATTN]
                 + (n_m + n_a) * p["mlp"] + p["embedding"])
    attn = n_a * m["h"] * 2.0 * live_tokens * 2 * m["d"]
    scan = n_m * m["n"] * m["di"] * 6.0
    return float(mat + attn + scan)


def scan_step_bytes(cfg: dict, slot_steps: int) -> float:
    """What ``mamba_step`` must move: every Mamba layer's scan state of
    each slot-step read once and written once."""
    return slot_steps * 2 * scan_state_bytes(cfg)


def scan_prefill_bytes(cfg: dict, positions: int, chunks: int) -> float:
    """What ``mamba_scan`` must move for ``chunks`` prefill chunks of
    ``positions`` valid positions in all, every Mamba layer: ``xc``,
    ``delta`` and ``z`` in and ``y`` out a position (float32, 5,120
    wide), ``B`` and ``C``, and the state once in and once out a
    chunk."""
    m = model_cfg(cfg)
    n_m = n_layers(cfg)[0]
    a_position = (4 * m["di"] + 2 * m["n"]) * 4
    return float(n_m * positions * a_position
                 + chunks * 2 * scan_state_bytes(cfg))

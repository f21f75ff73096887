"""What one chip's share of Nemotron 3 Super's programs MUST do, from the
configuration's shapes only
(``configs/nemotron3_super_l11_ep4_1chip.json``): parameters, FLOPs and
HBM bytes of the whole decode step, of the held experts' products and of
the two Mamba-2 kernels.  The counts read the same work whatever
implements it: an expert's two matrices are read once a step where ANY
token chose it (the held experts HIT, a program counter), a sequence's
recurrent state is what the MODEL keeps (a ``[64, 128]`` state a head
and 3 inputs of the convolution a channel a Mamba-2 block), not what a
layout pads it to.
"""
from __future__ import annotations

from benchmarks.harness.reference_nemotron import (ATTN, EXPERTS, MAMBA2,
                                                   model_cfg)


def param_counts(cfg: dict) -> dict:
    """Parameters of one Mamba-2 mixer (everything its published count
    has: the convolution and its bias, ``A_log``, ``D``, ``dt_bias``, the
    gated norm), its two matrices alone, one attention mixer, ONE routed
    expert, the shared expert, the two latent projections, the router,
    the embedding and the head (the held rows)."""
    m = model_cfg(cfg)
    dm, di, ch, h = m["dm"], m["di"], m["ch"], m["mh"]
    mats = dm * (di + ch + h) + di * dm
    return {"mamba2_matrices": mats,
            MAMBA2: mats + ch * (m["taps"] + 1) + 3 * h + di,
            ATTN: 2 * dm * m["h"] * m["d"] + 2 * dm * m["hkv"] * m["d"],
            "expert": 2 * m["lat"] * m["fe"], "shared": 2 * dm * m["fs"],
            "latent": 2 * dm * m["lat"], "router": dm * m["experts"],
            "embedding": m["vocab"] * dm, "head": m["vocab"] * dm}


def n_blocks(cfg: dict) -> tuple:
    """``(Mamba-2, attention, expert)`` blocks held."""
    blocks = model_cfg(cfg)["blocks"]
    return tuple(blocks.count(k) for k in (MAMBA2, ATTN, EXPERTS))


def held_share(cfg: dict) -> float:
    """The share of the routed experts held here: of a token's choices,
    what falls to this chip under even routing."""
    m = model_cfg(cfg)
    return m["held"][1] / m["experts"]


def _item(m: dict) -> int:
    return 2 if m["param_dtype"] == "bfloat16" else 4


def fixed_weight_bytes(cfg: dict) -> float:
    """What one decode step reads of the weights whatever its batch and
    whatever is routed where: every mixer, every expert block's shared
    expert, latent projections and float32 router, and the head once
    (the embedding is a row a token)."""
    m = model_cfg(cfg)
    p = param_counts(cfg)
    n_m, n_a, n_e = n_blocks(cfg)
    return float(_item(m) * (n_m * p[MAMBA2] + n_a * p[ATTN]
                             + n_e * (p["shared"] + p["latent"])
                             + p["head"]) + 4 * n_e * p["router"])


def expert_bytes(cfg: dict) -> float:
    """One routed expert's two matrices."""
    return float(_item(model_cfg(cfg)) * param_counts(cfg)["expert"])


def scan_state_bytes(cfg: dict) -> float:
    """One sequence's scan state, every Mamba-2 block: 128 heads of
    ``[64, 128]`` float32 each."""
    m = model_cfg(cfg)
    return float(n_blocks(cfg)[0] * m["mh"] * m["mp"] * m["n"] * 4)


def state_row_bytes(cfg: dict) -> float:
    """What a sequence keeps between positions: the scan state and the
    convolution's last ``taps - 1`` inputs of every Mamba-2 block."""
    m = model_cfg(cfg)
    return scan_state_bytes(cfg) \
        + float(n_blocks(cfg)[0] * (m["taps"] - 1) * m["ch"] * 4)


def kv_page_bytes(cfg: dict) -> float:
    """One page of the attention blocks: K and V of ``page_tokens``
    positions, bfloat16."""
    m = model_cfg(cfg)
    return float(n_blocks(cfg)[1] * 2 * m["hkv"] * m["d"]
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
                       experts_hit: int, kv_pages: int) -> float:
    """HBM bytes ``steps`` decode steps must move: the fixed weights
    once a step, every held expert HIT once (summed over blocks and
    steps), the state row of every live slot read once and written once,
    the distinct live K/V pages, an embedding row a slot-step."""
    m = model_cfg(cfg)
    return (steps * fixed_weight_bytes(cfg)
            + experts_hit * expert_bytes(cfg)
            + slot_steps * (2 * state_row_bytes(cfg) + m["dm"] * _item(m))
            + kv_pages * kv_page_bytes(cfg))


def decode_token_flops(cfg: dict, live_tokens: int) -> float:
    """FLOPs one decoded position must cost here (2 a multiply-add):
    every matrix of the mixers, of every expert block the router, the
    latent projections, the shared expert and the ``k`` chosen experts'
    share that is held under even routing, the head over the held rows,
    scores and values of the attention block over ``live_tokens`` keys,
    and the recurrence's update and read (five operations a state
    value)."""
    m = model_cfg(cfg)
    p = param_counts(cfg)
    n_m, n_a, n_e = n_blocks(cfg)
    mat = 2.0 * (n_m * p["mamba2_matrices"] + n_a * p[ATTN]
                 + n_e * (p["router"] + p["latent"] + p["shared"]
                          + m["k"] * held_share(cfg) * p["expert"])
                 + p["head"])
    attn = n_a * m["h"] * 2.0 * live_tokens * 2 * m["d"]
    scan = n_m * m["mh"] * m["mp"] * m["n"] * 5.0
    return float(mat + attn + scan)


def expert_ffn_seconds(cfg: dict, experts_hit: int, assignments: float,
                       peaks: dict) -> float:
    """The least time the held experts' two products can take: their
    matrices read once an expert hit, or the held assignments' FLOPs,
    whichever is longer."""
    p = param_counts(cfg)
    return max(experts_hit * expert_bytes(cfg) / peaks["hbm_bytes_per_s"],
               2.0 * assignments * p["expert"] / peaks["flops_bf16"])


def ssd_step_bytes(cfg: dict, slot_steps: int) -> float:
    """What ``ssd_step`` must move: every Mamba-2 block's scan state of
    each slot-step read once and written once."""
    return slot_steps * 2 * scan_state_bytes(cfg)


def ssd_prefill_seconds(cfg: dict, positions: int, chunks: float,
                        peaks: dict) -> float:
    """The least time the chunk scans of ``chunks`` prefill chunks of
    ``positions`` valid positions in all can take, every Mamba-2 block.
    FLOPs: the dual form's products at the published ``chunk_size``
    ``Q``, the causal ones at the mean of ``(Q + 1) / 2`` keys a
    position: ``C B^T`` a group, its masked product with ``delta xs`` a
    head, ``C S`` and ``B^T (delta xs)`` a head (``2 N P`` each).  Bytes:
    ``xs`` in and ``y`` out a position, ``delta``, ``B`` and ``C``
    (float32), the state once in and once out a chunk."""
    m = model_cfg(cfg)
    n_m = n_blocks(cfg)[0]
    h, hp, g, n = m["mh"], m["mp"], m["g"], m["n"]
    keys = (int(cfg["chunk_size"]) + 1) / 2.0
    flops = n_m * positions * 2.0 * (keys * n * g + keys * hp * h
                                     + 2 * n * hp * h)
    nbytes = n_m * positions * (2 * m["di"] + h + 2 * g * n) * 4.0 \
        + chunks * 2 * scan_state_bytes(cfg)
    return max(flops / peaks["flops_bf16"],
               nbytes / peaks["hbm_bytes_per_s"])

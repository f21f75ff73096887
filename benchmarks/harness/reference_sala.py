"""Plain reference of MiniCPM-SALA (``configs/minicpm_sala_l16_1chip.json``):
the forward pass in straightforward ``jax.numpy``, float32 at
``jax.default_matmul_precision("highest")``, with no kernel, no paging,
no batching of requests and the recurrence as a plain scan.  Imports
nothing of the program.  The sequence is computed in blocks of positions
(``block_forward``) against a context that holds, of the earlier
positions, what the mathematics keeps of them: the keys and values of
the attention layers and the state of the lightning layers.

The equations, for published layer ``l`` (values marked * are ASSUMED,
the published ``config.json`` does not carry them; the configuration's
file lists each with its reason):

    h_0     = scale_emb * E[token]                                (12)
    h      += r * mixer_l(rms(h; w1_l)),   r = scale_depth / sqrt(32)
    h      += r * W_down (silu(W_gate x) * (W_up x)),  x = rms(h; w2_l)
    logits  = W_head (rms(h_L; w_f) / (hidden_size / dim_model_base))
    rms(x; w) = x / sqrt(mean(x^2) + 1e-6) * w

``scale_depth`` 1.4 and the 32 are the PUBLISHED ones whatever depth is
held.  Head untied, no biases.

``minicpm4`` mixer (learned sparse attention, InfLLM-V2 as MiniCPM4
publishes it): ``q = W_q x`` (32 heads of 128), ``k, v = W_k x, W_v x``
(2 heads of 128); an RMS norm with a weight of 128 on every head of q
and k; NO rotary; 16 query heads share a K/V head; scale 1/sqrt(128).
The query at 0-based position t with ``t + 1 <= dense_len*`` attends
causally to all keys.  Otherwise: compressed keys
``kc[g, j] = mean(k[g, stride* j : stride j + kernel*])`` for every j
whose kernel is complete and ends at or before t;
``s[h, :] = softmax_j(q[h] . kc[g, j] / sqrt(128))``;
``S[g, j] = sum`` of s[h, j] over the group's heads;
``B[g, b] = max`` of S[g, j] over the kernels that overlap block b
(tokens ``block* b .. block b + block - 1``); attended are block 0
(``init_blocks*``), the blocks that overlap the last ``window_size*``
positions and the highest B among the rest, ``topk*`` blocks in all
(lower block index first among equals); softmax attention over the
keys of those blocks at positions <= t, with the original k and v.
Then ``o * sigmoid(W_g x)`` and ``W_o``.  Dense or sparse is decided
PER POSITION* (the released code decides by the call's length; per
position is the reading under which prefill-then-decode equals one
forward pass).

``lightning-attn`` mixer: ``q, k, v = W x`` (32 heads of 128 each), the
same per-head q/k norms, rotary (theta 10,000, rotate-half*, over the
whole head) on q and k; per head ``S_t = a_h S_{t-1} + k_t^T v_t``
(128 x 128, float32), ``o_t = q_t S_t / sqrt(128)``;
``a_h = exp(-2^(-8 (h+1) / 32))``* in every layer; an RMS norm with a
weight over each head's output*, ``* sigmoid(W_g x)``, ``W_o``.

DEPARTURES (two, both the configuration's STATED precision and nothing
below it):

1. The configuration's cache stores K and V of the attention layers in
   bfloat16 and the compressed keys in bfloat16, so the reference ROUNDS
   k and v to bfloat16 where they enter the context, takes the
   compressed keys as the float32 mean of the rounded keys rounded to
   bfloat16, and attends to the rounded values (the current position's
   too: the system attends to what it has just cached).
2. The configuration states bfloat16 weights AND bfloat16 matmul inputs
   with float32 accumulation (one MXU pass: at a batch of 8 the step is
   bound by reading 9.5 GB of weights, and float32 inputs would cost 3
   to 6 passes of a systolic array that is already loading a weight
   tile a pass).  Where ``param_dtype`` is bfloat16 the reference rounds
   the INPUT of every weight matrix to bfloat16 values and multiplies
   those exactly (float32 at ``highest``).  Measured on the chip in
   PR 32 (PERF.md section 6): against a reference with float32 inputs
   the sound runs read 0.076-0.088 and a bfloat16 state with an int8
   cache 0.126, which no tolerance separates on every seed; the issue
   asked for the first departure alone, this one is the measurement's.

Nothing else is rounded: norms, q/k norms, rotary, selection scores,
softmax, attention products, the lightning state and its read-out, the
residual stream and the logits are float32.

Weights are ``normal(0, 1/fan_in)`` rounded to the configuration's
``param_dtype`` (bfloat16: the reference takes the same bfloat16-valued
weights, upcast), norm weights ``1 + 0.1 normal`` in float32, the head's
variance ``(hidden_size / dim_model_base)^2 / hidden_size`` so that
logits are of order one; one key a layer split from
``PRNGKey(folded seed)``, drawn on the device one layer a jitted call.
"""
from __future__ import annotations

import functools
import math

SPARSE, LINEAR = "minicpm4", "lightning-attn"


# ---- configuration ---------------------------------------------------------

def model_cfg(cfg: dict) -> dict:
    """The numbers the forward pass reads, from the configuration file's
    keys (the published ones verbatim, the held slice and ``assumed``)."""
    first = int(cfg.get("first_published_layer", 0))
    held = int(cfg["num_hidden_layers"])
    sp = cfg["assumed"]["sparse_config"]["value"]
    return {
        "mixers": tuple(cfg["mixer_types"][first:first + held]),
        "depth_published": int(cfg.get("published_num_hidden_layers",
                                       cfg["num_hidden_layers"])),
        "vocab": int(cfg["vocab_size"]), "dm": int(cfg["hidden_size"]),
        "ff": int(cfg["intermediate_size"]),
        "h": int(cfg["num_attention_heads"]),
        "hkv": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
        "hl": int(cfg["lightning_nh"]), "dl": int(cfg["lightning_head_dim"]),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "scale_emb": float(cfg["scale_emb"]),
        "scale_depth": float(cfg["scale_depth"]),
        "base": int(cfg["dim_model_base"]),
        "block": int(sp["block_size"]), "kernel": int(sp["kernel_size"]),
        "stride": int(sp["kernel_stride"]), "topk": int(sp["topk"]),
        "init_blocks": int(sp["init_blocks"]),
        "window": int(sp["window_size"]), "dense_len": int(sp["dense_len"]),
        "param_dtype": cfg.get("param_dtype", "bfloat16"),
    }


def cfg_key(m: dict) -> tuple:
    return tuple(sorted(m.items()))


def layer_shapes(m: dict, kind: str) -> dict:
    dm, ff = m["dm"], m["ff"]
    out = {"norm1": ((dm,), None), "norm2": ((dm,), None)}
    if kind == SPARSE:
        hd, kvd = m["h"] * m["d"], m["hkv"] * m["d"]
        out.update(wq=((dm, hd), dm), wk=((dm, kvd), dm), wv=((dm, kvd), dm),
                   wg=((dm, hd), dm), wo=((hd, dm), hd),
                   q_norm=((m["d"],), None), k_norm=((m["d"],), None))
    else:
        hd = m["hl"] * m["dl"]
        out.update(wq=((dm, hd), dm), wk=((dm, hd), dm), wv=((dm, hd), dm),
                   wg=((dm, hd), dm), wo=((hd, dm), hd),
                   q_norm=((m["dl"],), None), k_norm=((m["dl"],), None),
                   o_norm=((m["dl"],), None))
    out.update(w_gate=((dm, ff), dm), w_up=((dm, ff), dm),
               w_down=((ff, dm), ff))
    return out


def make_params(cfg: dict, seed32: int, device=None) -> dict:
    """The weights from the seed, on the device, a layer a jitted call."""
    import jax
    import jax.numpy as jnp
    m = model_cfg(cfg)
    dt = jnp.dtype(m["param_dtype"])

    def draw(key, shapes):
        ks = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, (shape, fan_in)) in zip(ks, shapes.items()):
            x = jax.random.normal(k, shape, jnp.float32)
            out[name] = (1.0 + 0.1 * x) if fan_in is None \
                else (x / math.sqrt(fan_in)).astype(dt)
        return out

    key = jax.random.PRNGKey(int(seed32) & 0x7FFFFFFF)
    if device is not None:
        key = jax.device_put(key, device)
    ks = jax.random.split(key, len(m["mixers"]) + 1)
    gain = m["dm"] / m["base"]
    top = jax.jit(lambda k: draw(k, {
        "emb": ((m["vocab"], m["dm"]), m["dm"]),
        "head": ((m["vocab"], m["dm"]), m["dm"] / gain ** 2),
        "norm_f": ((m["dm"],), None)}))(ks[0])
    fns = {kind: jax.jit(functools.partial(draw,
                                           shapes=layer_shapes(m, kind)))
           for kind in (SPARSE, LINEAR)}
    top["layers"] = [fns[kind](k) for kind, k in zip(m["mixers"], ks[1:])]
    return top


# ---- the mathematics -------------------------------------------------------

def _rms(x, w, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _f32(w):
    import jax.numpy as jnp
    return w.astype(jnp.float32)


def _bf16_values(x):
    """``x`` rounded to bfloat16 VALUES, still float32.  Not a pair of
    casts: the compiler may remove ``f32 -> bf16 -> f32`` as excess
    precision (on the chip it did: PERF.md section 6, PR 32), and
    ``reduce_precision`` is the operation it must keep."""
    import jax
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _lin(x, w):
    """``x W`` at the configuration's stated precision: a bfloat16
    weight takes its input at bfloat16 VALUES, the product exact and
    the sum float32 (departure 2); a float32 weight takes it as it is."""
    import jax.numpy as jnp
    if w.dtype == jnp.bfloat16:
        x = _bf16_values(x)
    return x @ w.astype(jnp.float32)


def _rope(x, pos, theta):
    import jax.numpy as jnp
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None, None] * inv
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


def selected_blocks(m: dict, q, k_all, pos):
    """``[B, Hkv, n_blocks]`` bool: the blocks each query of the block
    attends to on the sparse branch.  ``q [B, H, D]``, ``k_all [S, Hkv,
    D]`` the (rounded) keys of the whole context, ``pos [B]``."""
    import jax
    import jax.numpy as jnp
    s_max = k_all.shape[0]
    hkv, g = m["hkv"], m["h"] // m["hkv"]
    blk, ker, st = m["block"], m["kernel"], m["stride"]
    n_j = (s_max - ker) // st + 1
    n_b = -(-s_max // blk)
    starts = st * jnp.arange(n_j)
    win = starts[:, None] + jnp.arange(ker)[None, :]          # [J, ker]
    kc = _bf16_values(k_all[win].mean(axis=1))                # [J, Hkv, D]
    ends = starts + ker - 1
    live = ends[None, :] <= pos[:, None]                      # [B, J]
    qg = q.reshape(q.shape[0], hkv, g, m["d"])
    s = jnp.einsum("bhgd,jhd->bhgj", qg, kc) / math.sqrt(m["d"])
    s = jnp.where(live[:, None, None, :], s, -jnp.inf)
    p = jnp.where(live[:, None, None, :],
                  jnp.exp(s - jnp.max(jnp.where(live[:, None, None, :], s,
                                                -1e30), axis=-1,
                                      keepdims=True)), 0.0)
    z = p.sum(axis=-1, keepdims=True)
    p = p / jnp.where(z == 0.0, 1.0, z)
    grp = p.sum(axis=2)                                       # [B, Hkv, J]
    b_lo, b_hi = blk * jnp.arange(n_b), blk * jnp.arange(n_b) + blk - 1
    overlap = (starts[None, :] <= b_hi[:, None]) \
        & (ends[None, :] >= b_lo[:, None])                    # [n_b, J]
    use = overlap[None, :, :] & live[:, None, :]              # [B, n_b, J]
    score = jnp.max(jnp.where(use[:, None], grp[:, :, None, :], -1.0),
                    axis=-1)                                  # [B, Hkv, n_b]
    bid = jnp.arange(n_b)[None, :]
    exists = b_lo[None, :] <= pos[:, None]
    forced = (bid < m["init_blocks"]) \
        | (b_hi[None, :] >= pos[:, None] - (m["window"] - 1))
    prio = jnp.where(forced[:, None, :], jnp.inf, score)
    prio = jnp.where(exists[:, None, :], prio, -jnp.inf)
    kk = min(m["topk"], n_b)
    top, idx = jax.lax.top_k(prio, kk)
    picked = jnp.zeros(prio.shape, bool)
    picked = picked.at[jnp.arange(prio.shape[0])[:, None, None],
                       jnp.arange(hkv)[None, :, None], idx].set(
        ~jnp.isneginf(top))
    return picked


def _sparse_mixer(m, p, x, ctx_k, ctx_v, pos, n_valid):
    """Returns (mixer output, updated keys, updated values, the blocks
    each position would select on the sparse branch)."""
    import jax
    import jax.numpy as jnp
    b = x.shape[0]
    h, hkv, d = m["h"], m["hkv"], m["d"]
    g = h // hkv
    q = _rms(_lin(x, p["wq"]).reshape(b, h, d), p["q_norm"], m["eps"])
    k = _rms(_lin(x, p["wk"]).reshape(b, hkv, d), p["k_norm"], m["eps"])
    v = _lin(x, p["wv"]).reshape(b, hkv, d)
    ok = (jnp.arange(b) < n_valid)[:, None, None]
    start = pos[0]
    old_k = jax.lax.dynamic_slice_in_dim(ctx_k, start, b, 0)
    old_v = jax.lax.dynamic_slice_in_dim(ctx_v, start, b, 0)
    ctx_k = jax.lax.dynamic_update_slice_in_dim(
        ctx_k, jnp.where(ok, _bf16_values(k), old_k), start, 0)
    ctx_v = jax.lax.dynamic_update_slice_in_dim(
        ctx_v, jnp.where(ok, _bf16_values(v), old_v), start, 0)
    s_max = ctx_k.shape[0]
    kpos = jnp.arange(s_max)
    causal = kpos[None, :] <= pos[:, None]                    # [B, S]
    picked = selected_blocks(m, q, ctx_k, pos)                # [B,Hkv,nb]
    in_sel = jnp.repeat(picked, m["block"], axis=-1)[..., :s_max]
    dense = (pos + 1 <= m["dense_len"])[:, None, None]
    mask = causal[:, None, :] & (dense | in_sel)              # [B, Hkv, S]
    qg = q.reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,shd->bhgs", qg, ctx_k) / math.sqrt(d)
    s = jnp.where(mask[:, :, None, :], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,shd->bhgd", pr, ctx_v).reshape(b, h * d)
    o = o * jax.nn.sigmoid(_lin(x, p["wg"]))
    return _lin(o, p["wo"]), ctx_k, ctx_v, picked


def _lightning_mixer(m, p, x, state, pos, n_valid):
    import jax
    import jax.numpy as jnp
    b = x.shape[0]
    hl, dl = m["hl"], m["dl"]
    q = _rms(_lin(x, p["wq"]).reshape(b, hl, dl), p["q_norm"], m["eps"])
    k = _rms(_lin(x, p["wk"]).reshape(b, hl, dl), p["k_norm"], m["eps"])
    v = _lin(x, p["wv"]).reshape(b, hl, dl)
    q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
    a = jnp.exp(-jnp.exp2(-8.0 * jnp.arange(1, hl + 1) / hl))[:, None, None]

    def one(s, xs):
        qt, kt, vt, live = xs
        s_new = a * s + kt[:, :, None] * vt[:, None, :]
        s_new = jnp.where(live, s_new, s)
        return s_new, jnp.einsum("hd,hde->he", qt, s_new) / math.sqrt(dl)
    state, o = jax.lax.scan(one, state, (q, k, v, jnp.arange(b) < n_valid))
    o = _rms(o, p["o_norm"], m["eps"]).reshape(b, hl * dl)
    o = o * jax.nn.sigmoid(_lin(x, p["wg"]))
    return _lin(o, p["wo"]), state


def new_context(cfg: dict, s_max: int) -> dict:
    """An empty context for sequences of at most ``s_max`` positions."""
    import jax.numpy as jnp
    m = model_cfg(cfg)
    n_s = sum(1 for k in m["mixers"] if k == SPARSE)
    n_l = len(m["mixers"]) - n_s
    z = jnp.zeros
    return {"k": z((n_s, s_max, m["hkv"], m["d"]), jnp.float32),
            "v": z((n_s, s_max, m["hkv"], m["d"]), jnp.float32),
            "state": z((n_l, m["hl"], m["dl"], m["dl"]), jnp.float32)}


@functools.cache
def _block_fn(key: tuple, full: bool):
    import jax
    import jax.numpy as jnp
    m = dict(key)
    r = m["scale_depth"] / math.sqrt(m["depth_published"])

    def block(params, ctx, tokens, start, n_valid, targets):
        with jax.default_matmul_precision("highest"):
            b = tokens.shape[0]
            pos = start + jnp.arange(b)
            h = m["scale_emb"] * _f32(params["emb"][tokens])
            ks, vs, states, picks = [], [], [], []
            i_s = i_l = 0
            for p, kind in zip(params["layers"], m["mixers"]):
                x = _rms(h, p["norm1"], m["eps"])
                if kind == SPARSE:
                    o, k_new, v_new, picked = _sparse_mixer(
                        m, p, x, ctx["k"][i_s], ctx["v"][i_s], pos, n_valid)
                    ks.append(k_new)
                    vs.append(v_new)
                    picks.append(picked)
                    i_s += 1
                else:
                    o, s_new = _lightning_mixer(
                        m, p, x, ctx["state"][i_l], pos, n_valid)
                    states.append(s_new)
                    i_l += 1
                h = h + r * o
                x = _rms(h, p["norm2"], m["eps"])
                h = h + r * _lin(jax.nn.silu(_lin(x, p["w_gate"]))
                                 * _lin(x, p["w_up"]), p["w_down"])
            x = _rms(h, params["norm_f"], m["eps"]) / (m["dm"] / m["base"])
            logits = _lin(x, params["head"].T)
            new = {"k": jnp.stack(ks) if ks else ctx["k"],
                   "v": jnp.stack(vs) if vs else ctx["v"],
                   "state": jnp.stack(states) if states else ctx["state"]}
            if full:
                return logits, new, (jnp.stack(picks) if picks else None)
            lse = jax.nn.logsumexp(logits, axis=-1)
            at = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
            return (at - lse, logits.max(axis=-1) - at), new
    return jax.jit(block)


def block_forward(params, cfg: dict, ctx: dict, tokens, start: int,
                  n_valid: int, targets=None, full: bool = False):
    """One block of positions ``start .. start + len(tokens) - 1`` (the
    first ``n_valid`` real) after the context.  ``full``: ``(logits [B,
    vocab], context, selected blocks)``; else ``((log-softmax of
    targets, best logit - logit of targets) [B] each, context)``."""
    import jax.numpy as jnp
    fn = _block_fn(cfg_key(model_cfg(cfg)), bool(full))
    tg = jnp.zeros((len(tokens),), jnp.int32) if targets is None \
        else jnp.asarray(targets, jnp.int32)
    return fn(params, ctx, jnp.asarray(tokens, jnp.int32),
              jnp.int32(start), jnp.int32(n_valid), tg)


def full_logits(params, cfg: dict, tokens, block: int, s_max=None):
    """Logits ``[S, vocab]`` of a whole sequence (positions 0..S-1), a
    block of positions at a time, and per sparse layer the selected
    blocks ``[layers, S, Hkv, n_blocks]`` (numpy)."""
    import numpy as np
    s = len(tokens)
    s_max = s_max or -(-s // block) * block
    ctx = new_context(cfg, s_max)
    out, picks = [], []
    for at in range(0, s, block):
        n = min(block, s - at)
        toks = np.zeros((block,), np.int32)
        toks[:n] = tokens[at:at + n]
        logits, ctx, picked = block_forward(params, cfg, ctx, toks, at, n,
                                            full=True)
        out.append(np.asarray(logits)[:n])
        if picked is not None:
            picks.append(np.asarray(picked)[:, :n])
    return np.concatenate(out), \
        (np.concatenate(picks, axis=1) if picks else None), ctx

"""Plain reference of one chip's share of NVIDIA-Nemotron-3-Super-120B-A12B
(``configs/nemotron3_super_l11_ep4_1chip.json``, ``model_type:
nemotron_h``): the forward pass in straightforward ``jax.numpy``,
float32 at ``jax.default_matmul_precision("highest")``, with no kernel,
no paging, no batching of requests, the convolution as shifted adds, the
Mamba-2 recurrence as a plain ``lax.scan`` over time (one position, every
head's ``[64, 128]`` state, at a time; no chunked form) and the experts
as a plain loop over the held ones, each computing every row.  Imports
nothing of the program.  The sequence is computed in blocks of
positions (``block_forward``) against a context that holds, of the
earlier positions, what the mathematics keeps of them: the keys and
values of the attention blocks, and of every Mamba-2 block the scan
state and the convolution's last three inputs.

The equations (values marked * are ASSUMED, the published
``config.json`` does not carry them; the configuration's file lists each
with its reason).  ``x`` is the residual stream; block ``i`` is ONE
sublayer, ``hybrid_override_pattern[i]``:

    x_0     = E[token]
    x      += f_i(rms(x; w_i))        rms(x; w) = x / sqrt(mean(x^2) + 1e-5) * w
    logits  = W_head rms(x_L; w_f)    (untied; over the HELD rows of the vocabulary)

``M``, Mamba-2 (128 heads of 64, 8 groups of 16 heads, state 128; per
token ``t``, ``u`` the normed row): ``[z(8192); xBC(10240); dt(128)] =
W_in u`` (no bias*); ``c_t = silu(b_c + sum_{j<4} w_c[j] * xBC_{t-3+j})``
(depthwise, causal, a bias*; the three inputs before a block are the
context's tail); ``c -> [xs(128 x 64); B(8 x 128); C(8 x 128)]``, head
``h`` uses group ``h // 16``; ``delta_h = softplus(dt_h + dt_bias_h)``
(not clamped*); ``a_h = exp(-delta_h exp(A_log_h))``, a scalar a head;
``S_t^h = a_h S_{t-1}^h + delta_h xs_t^h (x) B_t^g`` (``[64, 128]``);
``y_t^h = S_t^h C_t^g + D_h xs_t^h``; ``g = y * silu(z)`` (the gate
BEFORE the norm*); ``o = w_n * g / rms_group(g)``, the mean square over
each of the 8 groups of 1,024 channels apart* (eps 1e-5); ``out =
W_out o``.

``*``, attention: 32 query heads of 128 on 2 K/V heads, ``q, k, v = W
u``, no bias, no q/k norm, NO rotary or other position signal*
(``rope_theta`` and ``partial_rotary_factor`` stand in the file unused),
``score = q . k / sqrt(128)``, causal softmax over every cached
position, ``W_o``.

``E``, latent experts: ``s = sigmoid(W_r u)`` (512, float32); the 22
largest of ``s + b`` are chosen (``b`` the router's correction bias*,
for the choice only; ``n_group`` 1: no group limit); ``w_i = 5.0 s_i /
sum of the chosen s``; ``l = W_dn u`` (1,024); ``r = sum_i w_i V_i
relu(U_i l)^2`` over the chosen experts HELD here (``U_i`` 1,024 ->
2,688, ``V_i`` back); ``out = W_up r + P relu(Q u)^2`` (the shared
expert, width 5,376, on the full width, every token).  What the experts
held elsewhere would add is left out, as the program leaves it out, and
the partial result goes on.

The multi-token-prediction module is not built: it adds nothing to the
next token's logits.

DEPARTURES (two, both the configuration's STATED precision and nothing
below it):

1. The configuration's cache stores K and V of the attention block in
   bfloat16, so the reference ROUNDS k and v to bfloat16 where they
   enter the context and attends to the rounded values.
2. The configuration states bfloat16 weights AND bfloat16 matmul inputs
   with float32 accumulation.  Where ``param_dtype`` is bfloat16 the
   reference rounds the INPUT of every weight matrix to bfloat16 values
   and multiplies those exactly.

Nothing else is rounded: norms, the convolution, softplus, ``exp``, the
router, the scan state and the tail, attention scores, softmax, the
residual stream and the logits are float32; the router and its bias,
the convolution's taps and bias, ``dt_bias``, ``A_log``, ``D`` and the
norm weights are float32 parameters.

Weights (the family's own init where it has one, so that the state's
memory spans a few tokens to a thousand and a stale or lost state is
seen): matrices ``normal(0, 1/fan_in)`` rounded to ``param_dtype``, norm
weights ``1 + 0.1 normal``, the router ``normal(0, 1/hidden_size)`` and
its correction bias ``normal(0, 0.01)`` (small beside the scores'
spread and large beside the 0.003 between the 22nd and the 23rd of 512:
it changes one or two of a token's 22 choices and leaves the experts'
loads near even, as a trained router's bias exists to do), the
convolution's taps ``normal(0, 1/4)`` and its bias ``normal(0, 0.1)``,
``A_log = log(uniform 1..16)`` a head, ``D`` ones, ``dt_bias`` the
inverse softplus of a step size log-uniform in ``time_step_min`` ..
``time_step_max`` and at least ``time_step_floor``; one key a block
split from ``PRNGKey(folded seed)``, drawn on the device one block a
jitted call.
"""
from __future__ import annotations

import functools
import math

# the stated precision's helpers are the first served model's: a float32
# RMS norm, and ``x W`` with a bfloat16 weight's input at bfloat16 values
# (departure 2)
from benchmarks.harness.reference_sala import (_bf16_values, _f32, _lin,
                                               _rms)

MAMBA2, ATTN, EXPERTS = "M", "*", "E"
TAPS, BIAS, A_LOG, ONES, DT_BIAS = "taps", "bias", "a_log", "ones", "dt_bias"
ROUTER_BIAS = "router_bias"


# ---- configuration ---------------------------------------------------------

def model_cfg(cfg: dict) -> dict:
    """The numbers the forward pass reads, from the configuration file's
    keys (the published ones verbatim; ``num_hidden_layers``,
    ``n_routed_experts`` and ``vocab_size`` are what is HELD, their
    published values beside them)."""
    first = int(cfg.get("first_published_layer", 0))
    held = int(cfg["n_routed_experts"])
    blocks = cfg["hybrid_override_pattern"][
        first:first + int(cfg["num_hidden_layers"])]
    if set(blocks) - {MAMBA2, ATTN, EXPERTS}:
        raise ValueError(f"blocks {blocks!r}: only M, * and E are described")
    h, p = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    g, n = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    return {
        "blocks": blocks, "vocab": int(cfg["vocab_size"]),
        "dm": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]),
        "hkv": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
        "mh": h, "mp": p, "g": g, "n": n, "di": h * p,
        "ch": h * p + 2 * g * n, "taps": int(cfg["conv_kernel"]),
        "eps": float(cfg["layer_norm_epsilon"]),
        "dt_range": (float(cfg["time_step_min"]), float(cfg["time_step_max"]),
                     float(cfg["time_step_floor"])),
        "experts": int(cfg.get("published_n_routed_experts", held)),
        "held": (int(cfg.get("first_expert_held", 0)), held),
        "k": int(cfg["num_experts_per_tok"]),
        "fe": int(cfg["moe_intermediate_size"]),
        "lat": int(cfg["moe_latent_size"]),
        "fs": int(cfg["moe_shared_expert_intermediate_size"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "param_dtype": cfg.get("param_dtype", "bfloat16"),
    }


def cfg_key(m: dict) -> tuple:
    return tuple(sorted(m.items()))


def layer_shapes(m: dict, kind: str) -> dict:
    dm = m["dm"]
    if kind == ATTN:
        hd, kvd = m["h"] * m["d"], m["hkv"] * m["d"]
        return {"norm1": ((dm,), None), "wq": ((dm, hd), dm),
                "wk": ((dm, kvd), dm), "wv": ((dm, kvd), dm),
                "wo": ((hd, dm), hd)}
    if kind == MAMBA2:
        h, di, ch = m["mh"], m["di"], m["ch"]
        return {"norm1": ((dm,), None), "w_in": ((dm, di + ch + h), dm),
                "conv_w": ((m["taps"], ch), TAPS), "conv_b": ((ch,), BIAS),
                "b_dt": ((h,), DT_BIAS), "a_log": ((h,), A_LOG),
                "d": ((h,), ONES), "g_norm": ((di,), None),
                "w_out": ((di, dm), di)}
    n, fe, lat, fs = m["held"][1], m["fe"], m["lat"], m["fs"]
    return {"norm2": ((dm,), None), "router": ((dm, m["experts"]), TAPS),
            "router_bias": ((m["experts"],), ROUTER_BIAS),
            "w_lat_in": ((dm, lat), dm), "we_up": ((n, lat, fe), lat),
            "we_down": ((n, fe, lat), fe), "w_lat_out": ((lat, dm), lat),
            "ws_up": ((dm, fs), dm), "ws_down": ((fs, dm), fs)}


def make_params(cfg: dict, seed32: int, device=None) -> dict:
    """The weights from the seed, on the device, a block a jitted call."""
    import jax
    import jax.numpy as jnp
    m = model_cfg(cfg)
    dt = jnp.dtype(m["param_dtype"])
    lo, hi, floor = m["dt_range"]

    def draw(key, shapes):
        ks = jax.random.split(key, len(shapes))
        out = {}
        for k, (name, (shape, fan_in)) in zip(ks, shapes.items()):
            x = jax.random.normal(k, shape, jnp.float32)
            if fan_in is None:
                out[name] = 1.0 + 0.1 * x
            elif fan_in == A_LOG:
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0))
            elif fan_in == ONES:
                out[name] = jnp.ones(shape, jnp.float32)
            elif fan_in == DT_BIAS:
                step = jnp.maximum(floor, jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(lo), math.log(hi))))
                out[name] = step + jnp.log(-jnp.expm1(-step))
            elif fan_in == BIAS:
                out[name] = 0.1 * x
            elif fan_in == ROUTER_BIAS:
                out[name] = 0.01 * x
            elif fan_in == TAPS:      # a float32 matrix: normal(0, 1/rows)
                out[name] = x / math.sqrt(shape[0])
            else:
                out[name] = (x / math.sqrt(fan_in)).astype(dt)
        return out

    key = jax.random.PRNGKey(int(seed32) & 0x7FFFFFFF)
    if device is not None:
        key = jax.device_put(key, device)
    ks = jax.random.split(key, len(m["blocks"]) + 1)
    top = jax.jit(lambda k: draw(k, {
        "emb": ((m["vocab"], m["dm"]), m["dm"]),
        "head": ((m["vocab"], m["dm"]), m["dm"]),
        "norm_f": ((m["dm"],), None)}))(ks[0])
    fns = {kind: jax.jit(functools.partial(draw,
                                           shapes=layer_shapes(m, kind)))
           for kind in set(m["blocks"])}
    top["layers"] = [fns[kind](k) for kind, k in zip(m["blocks"], ks[1:])]
    return top


# ---- the mathematics -------------------------------------------------------

def attention(m: dict, p: dict, x, ctx_k, ctx_v, pos, n_valid):
    """Full attention of a block ``x [B, dm]`` at positions ``pos`` over
    the context's keys and values ``[S, Hkv, D]`` (this block's own
    written first): ``(output [B, dm], updated keys, updated values)``."""
    import jax
    import jax.numpy as jnp
    b = x.shape[0]
    h, hkv, d = m["h"], m["hkv"], m["d"]
    q = _lin(x, p["wq"]).reshape(b, hkv, h // hkv, d)
    k = _bf16_values(_lin(x, p["wk"]).reshape(b, hkv, d))
    v = _bf16_values(_lin(x, p["wv"]).reshape(b, hkv, d))
    ok = (jnp.arange(b) < n_valid)[:, None, None]
    start = pos[0]
    old_k = jax.lax.dynamic_slice_in_dim(ctx_k, start, b, 0)
    old_v = jax.lax.dynamic_slice_in_dim(ctx_v, start, b, 0)
    ctx_k = jax.lax.dynamic_update_slice_in_dim(
        ctx_k, jnp.where(ok, k, old_k), start, 0)
    ctx_v = jax.lax.dynamic_update_slice_in_dim(
        ctx_v, jnp.where(ok, v, old_v), start, 0)
    s = jnp.einsum("bhgd,shd->bhgs", q, ctx_k) / math.sqrt(d)
    causal = jnp.arange(ctx_k.shape[0])[None, :] <= pos[:, None]
    s = jnp.where(causal[:, None, None, :], s, -jnp.inf)
    o = jnp.einsum("bhgs,shd->bhgd", jax.nn.softmax(s, axis=-1), ctx_v)
    return _lin(o.reshape(b, h * d), p["wo"]), ctx_k, ctx_v


def mamba2(m: dict, p: dict, x, state, tail, n_valid):
    """The Mamba-2 mixer of a block ``x [B, dm]`` after the scan state
    ``state [H, P, N]`` and the convolution's tail ``tail [3,
    channels]``: ``(output [B, dm], the state and the tail after the
    block's first n_valid positions)``."""
    import jax
    import jax.numpy as jnp
    b = x.shape[0]
    h, hp, g, n = m["mh"], m["mp"], m["g"], m["n"]
    di, ch, taps = m["di"], m["ch"], m["taps"]
    zxd = _lin(x, p["w_in"])
    z, xbc, dt = zxd[:, :di], zxd[:, di:di + ch], zxd[:, di + ch:]
    ext = jnp.concatenate([tail, xbc], axis=0)                # [3 + B, ch]
    conv = p["conv_b"][None, :] + sum(
        p["conv_w"][j][None, :] * ext[j:j + b] for j in range(taps))
    c = jax.nn.silu(conv)
    tail = jax.lax.dynamic_slice_in_dim(ext, n_valid, taps - 1, 0)
    xs = c[:, :di].reshape(b, h, hp)
    # head h reads its group's B and C
    bm = jnp.repeat(c[:, di:di + g * n].reshape(b, g, n), h // g, axis=1)
    cm = jnp.repeat(c[:, di + g * n:].reshape(b, g, n), h // g, axis=1)
    delta = jax.nn.softplus(dt + p["b_dt"][None, :])          # [B, H]
    a = jnp.exp(-delta * jnp.exp(p["a_log"])[None, :])        # a scalar a head

    def one(s, xs_t):
        x_t, a_t, dl, bt, ct, live = xs_t
        new = a_t[:, None, None] * s \
            + (dl[:, None] * x_t)[:, :, None] * bt[:, None, :]
        s = jnp.where(live, new, s)
        return s, (s * ct[:, None, :]).sum(axis=-1) + p["d"][:, None] * x_t
    state, y = jax.lax.scan(
        one, state, (xs, a, delta, bm, cm, jnp.arange(b) < n_valid))
    gated = (y.reshape(b, di) * jax.nn.silu(z)).reshape(b, g, di // g)
    normed = gated / jnp.sqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + m["eps"])
    return (_lin(normed.reshape(b, di) * p["g_norm"], p["w_out"]), state,
            tail)


def route(m: dict, p: dict, x):
    """``(chosen experts [B, k], their weights [B, k], scores [B, E])``
    of the normed rows ``x``."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.sigmoid(x @ p["router"])
    _, idx = jax.lax.top_k(s + p["router_bias"][None, :], m["k"])
    w = jnp.take_along_axis(s, idx, axis=1)
    if m["norm_topk"]:
        w = w / w.sum(axis=-1, keepdims=True)
    return idx, w * m["scale"], s


def experts_ffn(m: dict, p: dict, x):
    """``W_up sum_i w_i E_i(W_dn x)`` over the held experts + the shared
    expert: a loop over the experts, each computing EVERY row in the
    latent width and weighted by who chose it."""
    import jax
    import jax.numpy as jnp
    idx, w, _ = route(m, p, x)
    first, count = m["held"]
    lat = _lin(x, p["w_lat_in"])

    def one(acc, e):
        up = jax.lax.dynamic_index_in_dim(p["we_up"], e, 0, False)
        down = jax.lax.dynamic_index_in_dim(p["we_down"], e, 0, False)
        out = _lin(jnp.square(jax.nn.relu(_lin(lat, up))), down)
        mine = jnp.where(idx == first + e, w, 0.0).sum(axis=-1)
        return acc + mine[:, None] * out, None
    r, _ = jax.lax.scan(one, jnp.zeros_like(lat), jnp.arange(count))
    shared = _lin(jnp.square(jax.nn.relu(_lin(x, p["ws_up"]))), p["ws_down"])
    return _lin(r, p["w_lat_out"]) + shared


def new_context(cfg: dict, s_max: int) -> dict:
    """An empty context for sequences of at most ``s_max`` positions."""
    import jax.numpy as jnp
    m = model_cfg(cfg)
    n_a = m["blocks"].count(ATTN)
    n_m = m["blocks"].count(MAMBA2)
    z = jnp.zeros
    return {"k": z((n_a, s_max, m["hkv"], m["d"]), jnp.float32),
            "v": z((n_a, s_max, m["hkv"], m["d"]), jnp.float32),
            "state": z((n_m, m["mh"], m["mp"], m["n"]), jnp.float32),
            "tail": z((n_m, m["taps"] - 1, m["ch"]), jnp.float32)}


@functools.cache
def _block_fn(key: tuple, full: bool):
    import jax
    import jax.numpy as jnp
    m = dict(key)

    def block(params, ctx, tokens, start, n_valid, targets):
        with jax.default_matmul_precision("highest"):
            pos = start + jnp.arange(tokens.shape[0])
            h = _f32(params["emb"][tokens])
            ks, vs, states, tails = [], [], [], []
            for p, kind in zip(params["layers"], m["blocks"]):
                if kind == EXPERTS:
                    h = h + experts_ffn(m, p, _rms(h, p["norm2"], m["eps"]))
                    continue
                x = _rms(h, p["norm1"], m["eps"])
                if kind == ATTN:
                    o, k_new, v_new = attention(
                        m, p, x, ctx["k"][len(ks)], ctx["v"][len(ks)], pos,
                        n_valid)
                    ks.append(k_new)
                    vs.append(v_new)
                else:
                    o, s_new, t_new = mamba2(
                        m, p, x, ctx["state"][len(states)],
                        ctx["tail"][len(states)], n_valid)
                    states.append(s_new)
                    tails.append(t_new)
                h = h + o
            logits = _lin(_rms(h, params["norm_f"], m["eps"]),
                          params["head"].T)
            new = {"k": jnp.stack(ks) if ks else ctx["k"],
                   "v": jnp.stack(vs) if vs else ctx["v"],
                   "state": jnp.stack(states) if states else ctx["state"],
                   "tail": jnp.stack(tails) if tails else ctx["tail"]}
            if full:
                return logits, new
            lse = jax.nn.logsumexp(logits, axis=-1)
            at = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
            return (at - lse, logits.max(axis=-1) - at), new
    return jax.jit(block)


def block_forward(params, cfg: dict, ctx: dict, tokens, start: int,
                  n_valid: int, targets=None, full: bool = False):
    """One block of positions ``start .. start + len(tokens) - 1`` (the
    first ``n_valid`` real) after the context.
    ``full``: ``(logits [B, vocab], context)``; else ``((log-softmax of
    targets, best logit - logit of targets) [B] each, context)``."""
    import jax.numpy as jnp
    fn = _block_fn(cfg_key(model_cfg(cfg)), bool(full))
    tg = jnp.zeros((len(tokens),), jnp.int32) if targets is None \
        else jnp.asarray(targets, jnp.int32)
    return fn(params, ctx, jnp.asarray(tokens, jnp.int32),
              jnp.int32(start), jnp.int32(n_valid), tg)


def full_logits(params, cfg: dict, tokens, block: int, s_max=None):
    """Logits ``[S, vocab]`` of a whole sequence (positions 0..S-1), a
    block of positions at a time (numpy), and the context after it."""
    import numpy as np
    s = len(tokens)
    s_max = s_max or -(-s // block) * block
    ctx = new_context(cfg, s_max)
    out = []
    for at in range(0, s, block):
        n = min(block, s - at)
        toks = np.zeros((block,), np.int32)
        toks[:n] = tokens[at:at + n]
        logits, ctx = block_forward(params, cfg, ctx, toks, at, n, full=True)
        out.append(np.asarray(logits)[:n])
    return np.concatenate(out), ctx

"""Plain reference of one chip's share of Mellum2-12B-A2.5B-Instruct
(``configs/mellum2_12b_l12_1chip.json``, ``model_type: mellum``): the
forward pass in straightforward ``jax.numpy``, float32 at
``jax.default_matmul_precision("highest")``, with no kernel, no paging,
no ring, no batching of requests, and the routed experts as a plain loop
over all of them, each computing every row.  Imports nothing of the
program.  The sequence is computed in blocks of positions
(``block_forward``) against a context that holds, of the earlier
positions, what the mathematics keeps of them: the keys and values of
EVERY layer at every position (the window layers' too: the window is a
mask over them, not a cache).  Continuing a request from a repository
context's keys and values is the same forward pass as running the whole
prompt at once: a position's keys and values depend on the positions
before it only.

The equations (values marked * are ASSUMED, the published
``config.json`` does not carry them; the configuration's file lists each
with its reason).  ``x`` is the residual stream; layer ``i`` of the held
ones (published layers ``first .. first + n - 1``):

    x_0     = E[token]
    h       = x + Attn_kind(rms(x; w1))     rms(x; w) = x / sqrt(mean(x^2) + 1e-6) * w
    x'      = h + MoE(rms(h; w2))
    logits  = W_head rms(x_L; w_f)          (untied, the whole vocabulary)

Attention (``layer_types[i]``; 32 query heads on 4 K/V heads of 128, no
bias, no q/k norm*): ``q = rope_kind(x W_q)``, ``k = rope_kind(x W_k)``,
``v = x W_v``; ``o = softmax(q k^T / sqrt(128) + mask) v``, then ``o
W_o``.  ``full_attention``: key ``j`` is visible to query ``t`` iff ``j
<= t``; ``sliding_attention``: iff ``t - 1024 < j <= t`` (1,024 keys, its
own included: the family code's ``t - j < sliding_window``*).

Rotary, rotate-half over all 128 dimensions: window layers ``inv_i =
theta^(-2i/128)``, theta 500,000; full layers YaRN as the family's code
computes it*: ``low = floor(128 ln(8192 / (32 2 pi)) / (2 ln theta)) =
18``, ``high = ceil(128 ln(8192 / (1 2 pi)) / (2 ln theta)) = 35``,
``ramp_i = clip((i - low) / (high - low), 0, 1)``, ``inv_i = theta^(-2i/
128) ((1 - ramp_i) + ramp_i / 16)``, and cos and sin both multiplied by
the attention factor 1.2772588722239782.  The frequencies are computed
in float64 and rounded to float32 once; the angle is ``position x inv``
in float32.

MoE (64 routed experts, top 8, no shared expert*): ``p = softmax(x
W_r)`` (``W_r`` 2,304 x 64, float32, softmax scoring and no correction
bias*); ``E`` = the 8 largest ``p``; ``w_e = p_e / sum_E p``
(``norm_topk_prob``); ``MoE(x) = sum_E w_e W_down,e (silu(W_gate,e x) *
W_up,e x)``, each expert 896 wide.

The multi-token-prediction head has no configuration keys and adds
nothing to the next token's logits: it is not built.

DEPARTURES (two, both the configuration's STATED precision and nothing
below it):

1. The configuration's cache stores K and V in bfloat16, so the
   reference ROUNDS k (after its rotary) and v to bfloat16 where they
   enter the context and attends to the rounded values.
2. The configuration states bfloat16 weights AND bfloat16 matmul inputs
   with float32 accumulation.  Where ``param_dtype`` is bfloat16 the
   reference rounds the INPUT of every weight matrix to bfloat16 values
   and multiplies those exactly (two bfloat16 values' product is exact
   in float32, so such a product asks for one pass and loses nothing;
   its sum is float32).  The router's matrix is float32 and its input is
   not rounded.

Nothing else is rounded: norms, rotary, the router's scores and
weights, attention scores, softmax, the residual stream and the logits
are float32.

Weights are ``normal(0, 1/fan_in)`` rounded to ``param_dtype``, norm
weights ``1 + 0.1 normal``, the router ``normal(0, 1/hidden_size)`` in
float32 (scores of order one over 64 experts: the 8th and 9th lie apart,
and a dropped expert is seen); one key a layer split from
``PRNGKey(folded seed)``, drawn on the device one layer a jitted call.
"""
from __future__ import annotations

import functools
import math

from benchmarks.harness.reference_sala import _bf16_values, _f32, _rms

WINDOW, FULL = "sliding_attention", "full_attention"
ROUTER = "router"


# ---- configuration ---------------------------------------------------------

def model_cfg(cfg: dict) -> dict:
    """The numbers the forward pass reads, from the configuration file's
    keys (the published ones verbatim; ``num_hidden_layers``,
    ``layer_types`` and ``mlp_layer_types`` are what is HELD)."""
    held = int(cfg["num_hidden_layers"])
    kinds = tuple(cfg["layer_types"][:held])
    if set(kinds) - {WINDOW, FULL} or len(kinds) != held:
        raise ValueError(f"layer_types {kinds!r}: only {WINDOW} and {FULL}")
    if set(cfg["mlp_layer_types"][:held]) != {"sparse"}:
        raise ValueError("only sparse (expert) feed-forwards are described")
    rope = cfg["rope_parameters"]
    full = rope.get(FULL, {})
    yarn = ()
    if full.get("rope_type") == "yarn":
        yarn = (float(full["factor"]),
                float(full["original_max_position_embeddings"]),
                float(full["beta_fast"]), float(full["beta_slow"]),
                float(full["attention_factor"]))
    return {
        "kinds": kinds, "vocab": int(cfg["vocab_size"]),
        "dm": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]),
        "hkv": int(cfg["num_key_value_heads"]), "d": int(cfg["head_dim"]),
        "eps": float(cfg["rms_norm_eps"]),
        "window": int(cfg["sliding_window"]),
        "theta_full": float(full.get("rope_theta", 10000.0)),
        "theta_window": float(rope.get(WINDOW, {}).get("rope_theta",
                                                       10000.0)),
        "yarn": yarn, "experts": int(cfg["num_experts"]),
        "k": int(cfg["num_experts_per_tok"]),
        "fe": int(cfg["moe_intermediate_size"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "param_dtype": cfg.get("param_dtype", "bfloat16"),
    }


def cfg_key(m: dict) -> tuple:
    return tuple(sorted(m.items()))


def layer_shapes(m: dict) -> dict:
    dm, n, fe = m["dm"], m["experts"], m["fe"]
    hd, kvd = m["h"] * m["d"], m["hkv"] * m["d"]
    return {"norm1": ((dm,), None), "norm2": ((dm,), None),
            "wq": ((dm, hd), dm), "wk": ((dm, kvd), dm),
            "wv": ((dm, kvd), dm), "wo": ((hd, dm), hd),
            "router": ((dm, n), ROUTER), "we_gate": ((n, dm, fe), dm),
            "we_up": ((n, dm, fe), dm), "we_down": ((n, fe, dm), fe)}


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
            if fan_in is None:
                out[name] = 1.0 + 0.1 * x
            elif fan_in == ROUTER:    # a float32 matrix: normal(0, 1/rows)
                out[name] = x / math.sqrt(shape[0])
            else:
                out[name] = (x / math.sqrt(fan_in)).astype(dt)
        return out

    key = jax.random.PRNGKey(int(seed32) & 0x7FFFFFFF)
    if device is not None:
        key = jax.device_put(key, device)
    ks = jax.random.split(key, len(m["kinds"]) + 1)
    top = jax.jit(lambda k: draw(k, {
        "emb": ((m["vocab"], m["dm"]), m["dm"]),
        "head": ((m["vocab"], m["dm"]), m["dm"]),
        "norm_f": ((m["dm"],), None)}))(ks[0])
    layer = jax.jit(functools.partial(draw, shapes=layer_shapes(m)))
    top["layers"] = [layer(k) for k in ks[1:]]
    return top


# ---- the mathematics -------------------------------------------------------

def _lin(x, w):
    """``x W`` at the configuration's stated precision: a bfloat16
    weight takes its input at bfloat16 VALUES and the product of two
    bfloat16 values is exact, so one pass loses nothing (departure 2);
    a float32 weight takes its input as it is, at ``highest``."""
    import jax
    import jax.numpy as jnp
    if w.dtype == jnp.bfloat16:
        return jnp.dot(_bf16_values(x), w.astype(jnp.float32),
                       precision=jax.lax.Precision.DEFAULT)
    return x @ w


def inverse_frequencies(d: int, theta: float, yarn: tuple = ()):
    """``(inv [d/2] float32, cos/sin factor)``: plain rotary, or YaRN's
    frequencies as the equations give them."""
    import numpy as np
    i = np.arange(d // 2, dtype=np.float64)
    inv = theta ** (-2.0 * i / d)
    if not yarn:
        return inv.astype(np.float32), 1.0
    factor, original, fast, slow, attn = yarn
    low = math.floor(d * math.log(original / (fast * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(d * math.log(original / (slow * 2 * math.pi))
                     / (2 * math.log(theta)))
    low, high = max(low, 0), min(high, d - 1)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return (inv * ((1.0 - ramp) + ramp / factor)).astype(np.float32), attn


def rope(x, pos, table):
    """Rotate-half rotary of ``x [B, H, D]`` at positions ``pos [B]``."""
    import jax.numpy as jnp
    inv, factor = table
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(m: dict, p: dict, kind: str, x, ctx_k, ctx_v, pos, n_valid):
    """Attention of a block ``x [B, dm]`` at positions ``pos`` over the
    context's keys and values ``[S, Hkv, D]`` (bfloat16 values; this
    block's own written first): ``(output [B, dm], keys, values)``.  A
    window layer's queries see the band ``t - window < j <= t`` of the
    context: the positions from ``pos[0] - window + 1`` on, sliced out
    of it."""
    import jax
    import jax.numpy as jnp
    b = x.shape[0]
    h, hkv, d = m["h"], m["hkv"], m["d"]
    if kind == WINDOW:
        table = inverse_frequencies(d, m["theta_window"])
    else:
        table = inverse_frequencies(d, m["theta_full"], m["yarn"])
    q = rope(_lin(x, p["wq"]).reshape(b, h, d), pos, table)
    k = _bf16_values(rope(_lin(x, p["wk"]).reshape(b, hkv, d), pos, table))
    v = _bf16_values(_lin(x, p["wv"]).reshape(b, hkv, d))
    ok = (jnp.arange(b) < n_valid)[:, None, None]
    start = pos[0]
    old_k = jax.lax.dynamic_slice_in_dim(ctx_k, start, b, 0)
    old_v = jax.lax.dynamic_slice_in_dim(ctx_v, start, b, 0)
    ctx_k = jax.lax.dynamic_update_slice_in_dim(
        ctx_k, jnp.where(ok, k, old_k).astype(ctx_k.dtype), start, 0)
    ctx_v = jax.lax.dynamic_update_slice_in_dim(
        ctx_v, jnp.where(ok, v, old_v).astype(ctx_v.dtype), start, 0)
    keys, vals, kpos = ctx_k, ctx_v, jnp.arange(ctx_k.shape[0])
    if kind == WINDOW:
        span = min(m["window"] - 1 + b, ctx_k.shape[0])
        at = jnp.clip(start - (m["window"] - 1), 0, ctx_k.shape[0] - span)
        keys = jax.lax.dynamic_slice_in_dim(ctx_k, at, span, 0)
        vals = jax.lax.dynamic_slice_in_dim(ctx_v, at, span, 0)
        kpos = at + jnp.arange(span)
    seen = kpos[None, :] <= pos[:, None]
    if kind == WINDOW:
        seen = seen & (kpos[None, :] > pos[:, None] - m["window"])
    qg = q.reshape(b, hkv, h // hkv, d)

    def head(args):
        qh, kh, vh = args                     # [B, G, D], [S, D], [S, D]
        s = jnp.einsum("bgd,sd->bgs", qh, _f32(kh)) / math.sqrt(d)
        s = jnp.where(seen[:, None, :], s, -jnp.inf)
        return jnp.einsum("bgs,sd->bgd", jax.nn.softmax(s, axis=-1),
                          _f32(vh))
    o = jax.lax.map(head, (qg.transpose(1, 0, 2, 3), keys.transpose(1, 0, 2),
                           vals.transpose(1, 0, 2)))      # [Hkv, B, G, D]
    o = o.transpose(1, 0, 2, 3).reshape(b, h * d)
    return _lin(o, p["wo"]), ctx_k, ctx_v


def route(m: dict, p: dict, x):
    """``(chosen experts [B, k], their weights [B, k], scores [B, E])``
    of the normed rows ``x``."""
    import jax
    import jax.numpy as jnp
    s = jax.nn.softmax(x @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(s, m["k"])
    if m["norm_topk"]:
        top = top / top.sum(axis=-1, keepdims=True)
    return idx, top, s


def experts_ffn(m: dict, p: dict, x):
    """``sum_E w_e E_e(x)``: a loop over ALL the experts, each computing
    EVERY row and weighted by who chose it."""
    import jax
    import jax.numpy as jnp
    idx, w, _ = route(m, p, x)

    def one(acc, e):
        gate = jax.lax.dynamic_index_in_dim(p["we_gate"], e, 0, False)
        up = jax.lax.dynamic_index_in_dim(p["we_up"], e, 0, False)
        down = jax.lax.dynamic_index_in_dim(p["we_down"], e, 0, False)
        out = _lin(jax.nn.silu(_lin(x, gate)) * _lin(x, up), down)
        mine = jnp.where(idx == e, w, 0.0).sum(axis=-1)
        return acc + mine[:, None] * out, None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(m["experts"]))
    return y


def new_context(cfg: dict, s_max: int) -> dict:
    """An empty context for sequences of at most ``s_max`` positions:
    every layer's keys and values, at the bfloat16 values the
    configuration's cache holds (departure 1), kept as bfloat16."""
    import jax.numpy as jnp
    m = model_cfg(cfg)
    shape = (len(m["kinds"]), s_max, m["hkv"], m["d"])
    return {"k": jnp.zeros(shape, jnp.bfloat16),
            "v": jnp.zeros(shape, jnp.bfloat16)}


def copy_context(ctx: dict) -> dict:
    """A context of its own (``block_forward`` takes its context
    donated)."""
    import jax.numpy as jnp
    return {n: jnp.copy(a) for n, a in ctx.items()}


@functools.cache
def _block_fn(key: tuple, full: bool):
    import jax
    import jax.numpy as jnp
    m = dict(key)

    def block(params, ctx, tokens, start, n_valid, targets):
        with jax.default_matmul_precision("highest"):
            pos = start + jnp.arange(tokens.shape[0])
            h = _f32(params["emb"][tokens])
            ks, vs = [], []
            for i, (p, kind) in enumerate(zip(params["layers"], m["kinds"])):
                o, k_new, v_new = attention(
                    m, p, kind, _rms(h, p["norm1"], m["eps"]), ctx["k"][i],
                    ctx["v"][i], pos, n_valid)
                ks.append(k_new)
                vs.append(v_new)
                h = h + o
                h = h + experts_ffn(m, p, _rms(h, p["norm2"], m["eps"]))
            logits = _lin(_rms(h, params["norm_f"], m["eps"]),
                          params["head"].T)
            new = {"k": jnp.stack(ks), "v": jnp.stack(vs)}
            if full:
                return logits, new
            lse = jax.nn.logsumexp(logits, axis=-1)
            at = jnp.take_along_axis(logits, targets[:, None], axis=1)[:, 0]
            return (at - lse, logits.max(axis=-1) - at), new
    return jax.jit(block, donate_argnums=1)


def block_forward(params, cfg: dict, ctx: dict, tokens, start: int,
                  n_valid: int, targets=None, full: bool = False):
    """One block of positions ``start .. start + len(tokens) - 1`` (the
    first ``n_valid`` real) after the context, which it takes DONATED.
    ``full``: ``(logits [B, vocab], context)``; else ``((log-softmax of
    targets, best logit - logit of targets) [B] each, context)``."""
    import jax.numpy as jnp
    fn = _block_fn(cfg_key(model_cfg(cfg)), bool(full))
    tg = jnp.zeros((len(tokens),), jnp.int32) if targets is None \
        else jnp.asarray(targets, jnp.int32)
    return fn(params, ctx, jnp.asarray(tokens, jnp.int32),
              jnp.int32(start), jnp.int32(n_valid), tg)


def run_context(params, cfg: dict, ctx: dict, tokens, start: int,
                block: int) -> dict:
    """``tokens`` at positions ``start ..`` into the context, a block at
    a time (the last block padded): the context after them."""
    import numpy as np
    for at in range(0, len(tokens), block):
        n = min(block, len(tokens) - at)
        toks = np.zeros((block,), np.int32)
        toks[:n] = tokens[at:at + n]
        _, ctx = block_forward(params, cfg, ctx, toks, start + at, n)
    return ctx


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

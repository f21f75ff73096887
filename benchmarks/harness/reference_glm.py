"""Plain reference of GLM-4.7-Flash (``configs/glm47_flash_l8_1chip.json``,
``model_type: glm4_moe_lite``): the forward pass in straightforward
``jax.numpy``, float32 at ``jax.default_matmul_precision("highest")``,
with no kernel, no paging, no absorption (keys and values are EXPANDED
from the latents for every head), no grouping of tokens by expert (the
routed sum is a plain loop over the experts, each masked by who chose
it) and no batching of requests.  Imports nothing of the program.  The
sequence is computed in blocks of positions (``block_forward``) against
a context that holds, of the earlier positions, what the mathematics
keeps of them: the latent row ``[c_kv; k_rope]`` of every layer.

The equations, for a layer (values marked * are ASSUMED, the published
``config.json`` does not carry them; the configuration's file lists each
with its reason):

    h_0     = E[token]
    h      += Attn(rms(h; w1)),    h += FFN(rms(h; w2))
    logits  = W_head rms(h_L; w_f)             (untied head, no bias)
    rms(x; w) = x / sqrt(mean(x^2) + 1e-5) * w

Latent attention (20 heads; ranks 768 and 512; head sizes 192 + 64 and
256): ``c_q = rms(x W_qa)``; ``q = c_q W_qb``, per head ``[q_nope(192);
q_rope(64)]``, ``q_rope = rope(q_rope, t)``; ``[c_kv(512); k_r(64)] = x
W_kva``, ``c_kv = rms(c_kv)``, ``k_rope = rope(k_r, t)``: ONE rope key a
token, shared by all heads; ``k_nope_h = W_UK_h c_kv`` (192), ``v_h =
c_kv W_UV_h`` (256): ``kv_b_proj`` held as its two factors a head, the
same 512 x 8,960 numbers; ``score_h = (q_nope_h . k_nope_h + q_rope_h .
k_rope) / sqrt(256)``, causal softmax, ``o = concat_h(P_h v_h) W_o``.
Rotary: theta 1e6, no scaling, rotate-half pairing* over the 64.

Expert feed-forward (``noaux_tc`` with one group, so group limiting is
the identity): ``s = sigmoid(x W_g)`` in float32 over all 64 experts;
the top 4 of ``s + b`` are chosen (``b``: the correction bias, for the
choice ONLY); the weights are the chosen ``s_i`` (without ``b``) divided
by their sum, times 1.8; ``y = sum_i w_i E_i(x) + E_shared(x)``,
``E(x) = W_d (silu(W_g' x) * W_u x)`` at width 1,536.  Layer 0 (of
``first_k_dense_replace`` 1) is the same gated MLP at width 10,240 with
no router.  ``experts_held`` (first, count), where the configuration
holds a share, leaves the other experts' terms out of the sum.

DEPARTURES (two, both the configuration's STATED precision and nothing
below it):

1. The configuration's cache stores the latent rows in bfloat16, so the
   reference ROUNDS ``[c_kv; k_rope]`` to bfloat16 where it enters the
   context and expands keys and values from the rounded rows (the
   current position's too: the system attends to what it has just
   cached).
2. The configuration states bfloat16 weights AND bfloat16 matmul inputs
   with float32 accumulation.  Where ``param_dtype`` is bfloat16 the
   reference rounds the INPUT of every weight matrix to bfloat16 values
   and multiplies those exactly.  The router's matrix and bias are
   float32 and their input is not rounded.

Nothing else is rounded: norms, rotary, the router's scores and
weights, attention scores, softmax, the residual stream and the logits
are float32.

Weights are ``normal(0, 1/fan_in)`` rounded to ``param_dtype``, norm
weights ``1 + 0.1 normal``, the router ``normal(0, 1/hidden_size)`` and
its bias ``normal(0, 0.1)`` in float32 (scores lie around 0.5 +- 0.2, so
the bias changes the choice of about one expert in four and a dropped
bias is seen); one key a layer split from ``PRNGKey(folded seed)``, drawn
on the device one layer a jitted call.
"""
from __future__ import annotations

import functools
import math

# the stated precision's helpers are the other served model's: a float32
# RMS norm, rotate-half rotary positions, and ``x W`` with a bfloat16
# weight's input at bfloat16 values (departure 2)
from benchmarks.harness.reference_sala import (_bf16_values, _f32, _lin,
                                               _rms, _rope)

DENSE, MOE = "dense", "moe"
ROUTER, BIAS = "router", "bias"


# ---- configuration ---------------------------------------------------------

def model_cfg(cfg: dict) -> dict:
    """The numbers the forward pass reads, from the configuration file's
    keys (the published ones verbatim and the held slice)."""
    first = int(cfg.get("first_published_layer", 0))
    held = int(cfg["num_hidden_layers"])
    n_exp = int(cfg["n_routed_experts"])
    return {
        "ffns": tuple(DENSE if first + i < int(cfg["first_k_dense_replace"])
                      else MOE for i in range(held)),
        "vocab": int(cfg["vocab_size"]), "dm": int(cfg["hidden_size"]),
        "ff": int(cfg["intermediate_size"]),
        "h": int(cfg["num_attention_heads"]),
        "ql": int(cfg["q_lora_rank"]), "r": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]), "v": int(cfg["v_head_dim"]),
        "eps": float(cfg["rms_norm_eps"]), "theta": float(cfg["rope_theta"]),
        "experts": n_exp, "k": int(cfg["num_experts_per_tok"]),
        "shared": int(cfg["n_shared_experts"]),
        "fe": int(cfg["moe_intermediate_size"]),
        "scale": float(cfg["routed_scaling_factor"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "held": tuple(cfg.get("experts_held", (0, n_exp))),
        "param_dtype": cfg.get("param_dtype", "bfloat16"),
    }


def cfg_key(m: dict) -> tuple:
    return tuple(sorted(m.items()))


def layer_shapes(m: dict, ffn: str) -> dict:
    dm, h, r, ql = m["dm"], m["h"], m["r"], m["ql"]
    nope, rope, v = m["nope"], m["rope"], m["v"]
    out = {"norm1": ((dm,), None), "norm2": ((dm,), None),
           "wq_a": ((dm, ql), dm), "q_norm": ((ql,), None),
           "wq_b": ((ql, h * (nope + rope)), ql),
           "wkv_a": ((dm, r + rope), dm), "kv_norm": ((r,), None),
           "w_uk": ((h, nope, r), r), "w_uv": ((h, r, v), r),
           "wo": ((h * v, dm), h * v)}
    if ffn == MOE:
        n, fe, fs = m["held"][1], m["fe"], m["fe"] * m["shared"]
        out.update(router=((dm, m["experts"]), ROUTER),
                   router_bias=((m["experts"],), BIAS),
                   we_gate=((n, dm, fe), dm), we_up=((n, dm, fe), dm),
                   we_down=((n, fe, dm), fe), ws_gate=((dm, fs), dm),
                   ws_up=((dm, fs), dm), ws_down=((fs, dm), fs))
    else:
        out.update(w_gate=((dm, m["ff"]), dm), w_up=((dm, m["ff"]), dm),
                   w_down=((m["ff"], dm), m["ff"]))
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
            if fan_in is None:
                out[name] = 1.0 + 0.1 * x
            elif fan_in == BIAS:
                out[name] = 0.1 * x
            elif fan_in == ROUTER:
                out[name] = x / math.sqrt(shape[0])
            else:
                out[name] = (x / math.sqrt(fan_in)).astype(dt)
        return out

    key = jax.random.PRNGKey(int(seed32) & 0x7FFFFFFF)
    if device is not None:
        key = jax.device_put(key, device)
    ks = jax.random.split(key, len(m["ffns"]) + 1)
    top = jax.jit(lambda k: draw(k, {
        "emb": ((m["vocab"], m["dm"]), m["dm"]),
        "head": ((m["vocab"], m["dm"]), m["dm"]),
        "norm_f": ((m["dm"],), None)}))(ks[0])
    fns = {ffn: jax.jit(functools.partial(draw, shapes=layer_shapes(m, ffn)))
           for ffn in set(m["ffns"])}
    top["layers"] = [fns[ffn](k) for ffn, k in zip(m["ffns"], ks[1:])]
    return top


# ---- the mathematics -------------------------------------------------------

def attention(m: dict, p: dict, x, ctx_c, pos, n_valid):
    """Latent attention of a block ``x [B, dm]`` at positions ``pos``
    over the context's rows ``ctx_c [S, r + rope]`` (this block's own
    written first): ``(output [B, dm], updated rows)``.  Keys and values
    of EVERY position are expanded for every head."""
    import jax
    import jax.numpy as jnp
    b = x.shape[0]
    h, r, nope, rope, v = m["h"], m["r"], m["nope"], m["rope"], m["v"]
    cq = _rms(_lin(x, p["wq_a"]), p["q_norm"], m["eps"])
    q = _lin(cq, p["wq_b"]).reshape(b, h, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], pos, m["theta"])
    kv = _lin(x, p["wkv_a"])
    c_kv = _rms(kv[:, :r], p["kv_norm"], m["eps"])
    k_rope = _rope(kv[:, None, r:], pos, m["theta"])[:, 0]
    row = _bf16_values(jnp.concatenate([c_kv, k_rope], axis=-1))
    start = pos[0]
    ok = (jnp.arange(b) < n_valid)[:, None]
    old = jax.lax.dynamic_slice_in_dim(ctx_c, start, b, 0)
    ctx_c = jax.lax.dynamic_update_slice_in_dim(
        ctx_c, jnp.where(ok, row, old), start, 0)
    c_all, kr_all = ctx_c[:, :r], ctx_c[:, r:]
    k_nope = jnp.einsum("sc,hdc->shd", c_all, _f32(p["w_uk"]))
    val = jnp.einsum("sc,hcd->shd", c_all, _f32(p["w_uv"]))
    s = (jnp.einsum("bhd,shd->bhs", q_nope, k_nope)
         + jnp.einsum("bhd,sd->bhs", q_rope, kr_all)) \
        / math.sqrt(nope + rope)
    causal = jnp.arange(ctx_c.shape[0])[None, :] <= pos[:, None]
    s = jnp.where(causal[:, None, :], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhs,shd->bhd", pr, val).reshape(b, h * v)
    return _lin(o, p["wo"]), ctx_c


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
    """``sum_i w_i E_i(x)`` over the held experts (all 64 where the
    configuration holds all) + the shared expert: a loop over the
    experts, each computing EVERY row and weighted by who chose it."""
    import jax
    import jax.numpy as jnp
    idx, w, _ = route(m, p, x)
    first, count = m["held"]

    def one(acc, e):
        gate = jax.lax.dynamic_index_in_dim(p["we_gate"], e, 0, False)
        up = jax.lax.dynamic_index_in_dim(p["we_up"], e, 0, False)
        down = jax.lax.dynamic_index_in_dim(p["we_down"], e, 0, False)
        out = _lin(jax.nn.silu(_lin(x, gate)) * _lin(x, up), down)
        mine = jnp.where(idx == first + e, w, 0.0).sum(axis=-1)
        return acc + mine[:, None] * out, None
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(count))
    shared = _lin(jax.nn.silu(_lin(x, p["ws_gate"])) * _lin(x, p["ws_up"]),
                  p["ws_down"])
    return y + shared


def dense_ffn(p: dict, x):
    import jax
    return _lin(jax.nn.silu(_lin(x, p["w_gate"])) * _lin(x, p["w_up"]),
                p["w_down"])


def new_context(cfg: dict, s_max: int) -> dict:
    """An empty context for sequences of at most ``s_max`` positions."""
    import jax.numpy as jnp
    m = model_cfg(cfg)
    return {"c": jnp.zeros((len(m["ffns"]), s_max, m["r"] + m["rope"]),
                           jnp.float32)}


@functools.cache
def _block_fn(key: tuple, full: bool):
    import jax
    import jax.numpy as jnp
    m = dict(key)

    def block(params, ctx, tokens, start, n_valid, targets):
        with jax.default_matmul_precision("highest"):
            pos = start + jnp.arange(tokens.shape[0])
            h = _f32(params["emb"][tokens])
            rows = []
            for i, (p, ffn) in enumerate(zip(params["layers"], m["ffns"])):
                o, c_new = attention(m, p, _rms(h, p["norm1"], m["eps"]),
                                     ctx["c"][i], pos, n_valid)
                rows.append(c_new)
                h = h + o
                x = _rms(h, p["norm2"], m["eps"])
                h = h + (experts_ffn(m, p, x) if ffn == MOE
                         else dense_ffn(p, x))
            logits = _lin(_rms(h, params["norm_f"], m["eps"]),
                          params["head"].T)
            new = {"c": jnp.stack(rows)}
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

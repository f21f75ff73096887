"""Plain reference of AI21-Jamba2-3B (``configs/jamba2_3b_1chip.json``,
``model_type: jamba``): the forward pass in straightforward
``jax.numpy``, float32 at ``jax.default_matmul_precision("highest")``,
with no kernel, no paging, no batching of requests, the convolution as
shifted adds and the selective scan as a plain ``lax.scan`` over time.
Imports nothing of the program.  The sequence is computed in blocks of
positions (``block_forward``) against a context that holds, of the
earlier positions, what the mathematics keeps of them: the keys and
values of the attention layers, and of every Mamba layer the scan state
and the convolution's last three inputs.

The equations, for layer ``i`` of 28 (values marked * are ASSUMED, the
published ``config.json`` does not carry them; the configuration's file
lists each with its reason):

    h_0     = E[token]
    h      += mixer_i(rms(h; w1_i)),   h += W_down (silu(W_gate x) * (W_up x)),
                                       x = rms(h; w2_i), width 8,192
    logits  = E rms(h_L; w_f)          (the head IS the embedding: tied)
    rms(x; w) = x / sqrt(mean(x^2) + 1e-6) * w

``mixer_i`` is attention where ``i % attn_layer_period ==
attn_layer_offset``* (layers 7 and 21), else Mamba.  No rotary or any
other position signal anywhere*: order is the recurrence's.

Attention: 20 query heads of 128* on ONE K/V head, ``q, k, v = W x``,
no bias, no q/k norm, no gate, ``score = q . k / sqrt(128)``, causal
softmax over every cached position, ``W_o``.

Mamba-1 (5,120 channels = 2 x 2,560; per token ``t``, ``u`` the normed
row): ``[xs; z] = W_in u``; ``xc_t = silu(b_c + sum_{j<4} w_c[j] *
xs_{t-3+j})`` (depthwise, causal; the three inputs before a block are
the context's tail); ``[dt(160); B(16); C(16)] = W_x xc``, each through
its own learned RMS norm* (the family's addition to Mamba-1); ``delta =
softplus(W_dt dt + b_dt)``; ``A = -exp(A_log)`` ``[16, 5120]``;
``h_t = exp(delta (x) A) * h_{t-1} + (delta * xc) (x) B``;
``y = h_t . C + D * xc``; ``out = W_out (y * silu(z))``.  ``dt_proj``
has a bias and ``x_proj``, ``in_proj``, ``out_proj`` none
(``mamba_proj_bias`` false*), the convolution has one
(``mamba_conv_bias`` true).  What is per (state value, channel) or per
(tap, channel) is laid out channels minor: ``A_log [16, 5120]``,
``w_c [4, 5120]`` (tap 3 multiplies the current input).

DEPARTURES (two, both the configuration's STATED precision and nothing
below it):

1. The configuration's cache stores K and V of the attention layers in
   bfloat16, so the reference ROUNDS k and v to bfloat16 where they
   enter the context and attends to the rounded values (the current
   position's too: the system attends to what it has just cached).
2. The configuration states bfloat16 weights AND bfloat16 matmul inputs
   with float32 accumulation.  Where ``param_dtype`` is bfloat16 the
   reference rounds the INPUT of every weight matrix to bfloat16 values
   and multiplies those exactly.

Nothing else is rounded: norms, the convolution, softplus, ``exp``, the
scan state and the tail, attention scores, softmax, the residual stream
and the logits are float32; the convolution's taps and bias, ``b_dt``,
``A_log``, ``D`` and the norm weights are float32 parameters.

Weights (Mamba's own init where it has one, so that the state's memory
spans a few tokens to a thousand and a stale or lost state is seen):
matrices ``normal(0, 1/fan_in)`` rounded to ``param_dtype``, norm
weights ``1 + 0.1 normal``, the convolution's taps ``normal(0, 1/4)``
and its bias ``normal(0, 0.1)``, ``A_log = log(1..16)`` a channel,
``D`` ones, ``b_dt`` the inverse softplus of a step size log-uniform in
1e-3..1e-1; one key a layer split from ``PRNGKey(folded seed)``, drawn
on the device one layer a jitted call.
"""
from __future__ import annotations

import functools
import math

# the stated precision's helpers are the first served model's: a float32
# RMS norm, and ``x W`` with a bfloat16 weight's input at bfloat16 values
# (departure 2)
from benchmarks.harness.reference_sala import (_bf16_values, _f32, _lin,
                                               _rms)

MAMBA, ATTN = "mamba", "attention"
TAPS, BIAS, A_LOG, ONES, DT_BIAS = "taps", "bias", "a_log", "ones", "dt_bias"
DT_RANGE = (1e-3, 1e-1)


# ---- configuration ---------------------------------------------------------

def model_cfg(cfg: dict) -> dict:
    """The numbers the forward pass reads, from the configuration file's
    keys (the published ones verbatim)."""
    period, offset = int(cfg["attn_layer_period"]), int(cfg["attn_layer_offset"])
    dm, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "mixers": tuple(ATTN if i % period == offset else MAMBA
                        for i in range(int(cfg["num_hidden_layers"]))),
        "vocab": int(cfg["vocab_size"]), "dm": dm,
        "ff": int(cfg["intermediate_size"]), "h": h,
        "hkv": int(cfg["num_key_value_heads"]), "d": dm // h,
        "di": int(cfg["mamba_expand"]) * dm,
        "n": int(cfg["mamba_d_state"]), "taps": int(cfg["mamba_d_conv"]),
        "r": int(cfg["mamba_dt_rank"]), "eps": float(cfg["rms_norm_eps"]),
        "param_dtype": cfg.get("param_dtype", "bfloat16"),
    }


def cfg_key(m: dict) -> tuple:
    return tuple(sorted(m.items()))


def layer_shapes(m: dict, kind: str) -> dict:
    dm, ff = m["dm"], m["ff"]
    out = {"norm1": ((dm,), None), "norm2": ((dm,), None)}
    if kind == ATTN:
        hd, kvd = m["h"] * m["d"], m["hkv"] * m["d"]
        out.update(wq=((dm, hd), dm), wk=((dm, kvd), dm),
                   wv=((dm, kvd), dm), wo=((hd, dm), hd))
    else:
        di, n, r = m["di"], m["n"], m["r"]
        out.update(w_in=((dm, 2 * di), dm), conv_w=((m["taps"], di), TAPS),
                   conv_b=((di,), BIAS), w_x=((di, r + 2 * n), di),
                   dt_norm=((r,), None), b_norm=((n,), None),
                   c_norm=((n,), None), w_dt=((r, di), r),
                   b_dt=((di,), DT_BIAS), a_log=((n, di), A_LOG),
                   d=((di,), ONES), w_out=((di, dm), di))
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
            if fan_in is None:
                out[name] = 1.0 + 0.1 * x
            elif fan_in == A_LOG:
                out[name] = jnp.broadcast_to(jnp.log(jnp.arange(
                    1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
            elif fan_in == ONES:
                out[name] = jnp.ones(shape, jnp.float32)
            elif fan_in == DT_BIAS:
                lo, hi = (math.log(v) for v in DT_RANGE)
                step = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                                  lo, hi))
                out[name] = step + jnp.log(-jnp.expm1(-step))
            elif fan_in == BIAS:
                out[name] = 0.1 * x
            elif fan_in == TAPS:
                out[name] = x / math.sqrt(shape[0])
            else:
                out[name] = (x / math.sqrt(fan_in)).astype(dt)
        return out

    key = jax.random.PRNGKey(int(seed32) & 0x7FFFFFFF)
    if device is not None:
        key = jax.device_put(key, device)
    ks = jax.random.split(key, len(m["mixers"]) + 1)
    top = jax.jit(lambda k: draw(k, {
        "emb": ((m["vocab"], m["dm"]), m["dm"]),
        "norm_f": ((m["dm"],), None)}))(ks[0])
    fns = {kind: jax.jit(functools.partial(draw,
                                           shapes=layer_shapes(m, kind)))
           for kind in set(m["mixers"])}
    top["layers"] = [fns[kind](k) for kind, k in zip(m["mixers"], ks[1:])]
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


def mamba(m: dict, p: dict, x, state, tail, n_valid):
    """The Mamba-1 mixer of a block ``x [B, dm]`` after the scan state
    ``state [N, channels]`` and the convolution's tail ``tail [3,
    channels]``: ``(output [B, dm], the state and the tail after the
    block's first n_valid positions)``."""
    import jax
    import jax.numpy as jnp
    b = x.shape[0]
    di, n, r, taps = m["di"], m["n"], m["r"], m["taps"]
    xz = _lin(x, p["w_in"])
    xs, z = xz[:, :di], xz[:, di:]
    ext = jnp.concatenate([tail, xs], axis=0)                 # [3 + B, ch]
    conv = p["conv_b"][None, :] + sum(
        p["conv_w"][j][None, :] * ext[j:j + b] for j in range(taps))
    xc = jax.nn.silu(conv)
    tail = jax.lax.dynamic_slice_in_dim(ext, n_valid, taps - 1, 0)
    dbc = _lin(xc, p["w_x"])
    dt = _rms(dbc[:, :r], p["dt_norm"], m["eps"])
    bmat = _rms(dbc[:, r:r + n], p["b_norm"], m["eps"])
    cmat = _rms(dbc[:, r + n:], p["c_norm"], m["eps"])
    delta = jax.nn.softplus(_lin(dt, p["w_dt"]) + p["b_dt"][None, :])
    a = -jnp.exp(p["a_log"])                                  # [N, ch]

    def one(hs, xs):
        x_t, dl, bt, ct, live = xs
        new = jnp.exp(dl[None, :] * a) * hs \
            + (dl * x_t)[None, :] * bt[:, None]
        hs = jnp.where(live, new, hs)
        return hs, (hs * ct[:, None]).sum(axis=0) + p["d"] * x_t
    state, y = jax.lax.scan(
        one, state, (xc, delta, bmat, cmat, jnp.arange(b) < n_valid))
    return _lin(y * jax.nn.silu(z), p["w_out"]), state, tail


def new_context(cfg: dict, s_max: int) -> dict:
    """An empty context for sequences of at most ``s_max`` positions."""
    import jax.numpy as jnp
    m = model_cfg(cfg)
    n_a = sum(1 for k in m["mixers"] if k == ATTN)
    n_m = len(m["mixers"]) - n_a
    z = jnp.zeros
    return {"k": z((n_a, s_max, m["hkv"], m["d"]), jnp.float32),
            "v": z((n_a, s_max, m["hkv"], m["d"]), jnp.float32),
            "state": z((n_m, m["n"], m["di"]), jnp.float32),
            "tail": z((n_m, m["taps"] - 1, m["di"]), jnp.float32)}


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
            for p, kind in zip(params["layers"], m["mixers"]):
                x = _rms(h, p["norm1"], m["eps"])
                if kind == ATTN:
                    o, k_new, v_new = attention(
                        m, p, x, ctx["k"][len(ks)], ctx["v"][len(ks)], pos,
                        n_valid)
                    ks.append(k_new)
                    vs.append(v_new)
                else:
                    o, s_new, t_new = mamba(
                        m, p, x, ctx["state"][len(states)],
                        ctx["tail"][len(states)], n_valid)
                    states.append(s_new)
                    tails.append(t_new)
                h = h + o
                x = _rms(h, p["norm2"], m["eps"])
                h = h + _lin(jax.nn.silu(_lin(x, p["w_gate"]))
                             * _lin(x, p["w_up"]), p["w_down"])
            logits = _lin(_rms(h, params["norm_f"], m["eps"]),
                          params["emb"].T)
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

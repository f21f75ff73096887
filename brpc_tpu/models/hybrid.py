"""HybridRunner — a model described by its configuration (ISSUE 32).

Serves a :class:`~brpc_tpu.models.runner.TransformerConfig` whose
``mixer_types`` names each layer's mixer (MiniCPM-SALA's, GLM-4.7-Flash's,
Jamba's and Nemotron 3's kinds today):

  ``minicpm4``        learned block-sparse attention (InfLLM-V2): K/V of
                      these layers only live in bf16 pages, one selection
                      block a page; a query past ``dense_len`` scores the
                      compressed keys (an index beside the pages),
                      selects ``topk`` blocks and attends to THAT page
                      table (``ops.sparse_attention``); earlier queries
                      attend to every page
  ``lightning-attn``  linear attention: a float32 ``[H, D, D]`` state a
                      layer a sequence (``ops.lightning``), restored from
                      a snapshot on a radix hit
  ``mla``             latent attention (GLM-4.7-Flash, ISSUE 34): the
                      cache holds ``[c_kv; k_rope]`` a token, ONE row
                      and nothing per head; both programs attend in the
                      absorbed form (``W_UK`` folded into the query,
                      ``W_UV`` applied to the attended latents) over the
                      latent pages (``ops.latent_attention``); the decode
                      step reads the run of pages its slots hold in
                      common once for all of them (ISSUE 35)
  ``mamba``           the Mamba-1 state-space mixer (Jamba, ISSUE 38): a
                      causal depthwise convolution and a selective scan
                      (``ops.mamba``), the step size, ``B`` and ``C``
                      each through a learned RMS norm; a sequence keeps
                      a float32 scan state ``[N, channels]`` and the
                      convolution's last inputs a layer, both in its
                      state row, restored from a snapshot on a radix hit
  ``mamba2``          the Mamba-2 (SSD) mixer (Nemotron 3, ISSUE 40): ONE
                      projection to the gate, the convolved channels
                      (the heads' values, ``B`` and ``C``) and a step
                      size a head; a scalar decay a head over a ``[64,
                      128]`` matrix state, ``B`` and ``C`` shared by a
                      group of heads (``ops.ssd``), the gate BEFORE a
                      group RMS norm; a sequence keeps the packed scan
                      state and the convolution's last inputs a layer in
                      its state row (``ops.ssd``'s layout, 128 lanes
                      wide)
  ``attention``       full softmax attention over the layered cache's
                      K/V pages: ``minicpm4``'s kernel under a table of
                      ALL the sequence's pages, no compressed keys, no
                      selection, no rotary, q/k norm or gate unless the
                      family states them
  ``none``            no mixer: the block is its feed-forward alone

and ``ffn_types`` its feed-forward: ``dense`` (the silu gated MLP),
``moe`` (a float32 sigmoid router over all experts, the top ``k`` of
score + correction bias, the chosen scores normalised and scaled, the
held experts' gated MLPs as ragged grouped matmuls with no capacity,
``ops.moe``, and a shared expert), ``latent_moe`` (the same router;
the held experts two matrices around ``relu(.)^2`` in a LATENT width,
between one projection down and one up, on a TPU the ``expert_ffn``
kernel of ``ops.moe``; the shared expert of the same body in the
model's width) or ``none`` (the block is its mixer alone).
A block with a ``none`` is ONE sublayer and has one norm.  Around them
learned RMS norms,
per-head q/k norms, rotary positions, output gates, the muP scalings
where the family has them and an untied head, or the embedding as the
head where the family ties them; the vocabulary may be the chip's slice
of it.  The equations are those of
``benchmarks/harness/reference_sala.py``, ``reference_glm.py``,
``reference_jamba.py`` and ``reference_nemotron.py`` (the plain
references; the tier-1 tests hold this runner to them).

The cache is the store's :class:`~brpc_tpu.kvcache.layered.LayeredCache`:
persistent device arrays, one a kind of state (a kind the model has no
layer of is an array with no element), that the two jitted programs
here (``jit_runner_hybrid_step``, ``jit_runner_hybrid_prefill``) take
DONATED and return updated, fixed shapes throughout.

Position contract (the engine's): ``step(tok, pos)`` computes position
``pos - 1`` (the token newest in the sequence) and WRITES its K/V and
state; so prefill covers positions ``prefill_from .. len(prompt) - 2``
only: a recurrent state must see every position exactly once.

Precision: with ``param_dtype="bfloat16"`` weights and matmul inputs are
bfloat16 (one MXU pass), accumulation, norms, softmax, selection scores
and the recurrent state float32, cached K/V and compressed keys
bfloat16.  ``param_dtype="float32"`` (the CPU tests) multiplies at
``highest``.  ``control="low"`` is the benchmark's low-precision
control and nothing a deployment sets: everything the configuration
states in float32 and the program accumulates (every matmul's sum, the
residual stream, the lightning state, either scan state and the
convolution's tail) then holds bfloat16 values, and
the K/V and latent pages the values of an int8 cache (scale 1/16), the
router bfloat16.  ``control="drop"`` leaves the last chosen expert's
share out of every routed sum.
"""
from __future__ import annotations

import functools
import math
import threading
from typing import Optional

import numpy as np

from brpc_tpu import fault
from brpc_tpu.bvar import Adder
from brpc_tpu.models.runner import ModelRunner, TransformerConfig

SPARSE, LINEAR, MLA = "minicpm4", "lightning-attn", "mla"
MAMBA, MAMBA2, ATTN, NONE = "mamba", "mamba2", "attention", "none"
DENSE, MOE, LATENT_MOE = "dense", "moe", "latent_moe"
# what ``layer_shapes`` gives in place of a fan-in for what is neither a
# norm weight nor a matrix in the parameters' type, all float32: a
# matrix normal(0, 1/rows) (the router; the convolution's taps), a bias
# normal(0, 0.1) (the router's correction; the convolution's; over 512
# experts top-22 the correction is normal(0, 0.01): the 22nd and 23rd
# scores lie 0.003 apart, and 0.1 would choose half of every token's
# experts by the bias alone and leave half the experts idle), and
# Mamba's own init of the state-space mixer: ``A_log = log(1..N)`` a
# channel (Mamba-2: ``log(uniform 1..16)`` a head), ``D`` ones,
# ``dt_proj``'s bias the inverse softplus of a step size log-uniform in
# DT_RANGE (Mamba-2's ``time_step_min`` .. ``time_step_max``; its floor
# of 1e-4 lies under the range)
ROUTER, BIAS, SMALL_BIAS = "router", "bias", "small_bias"
A_LOG, A_LOG_U, ONES, DT_BIAS = "a_log", "a_log_uniform", "ones", "dt_bias"
DT_RANGE = (1e-3, 1e-1)
PREFILL_ROWS = 16     # positions a grid row of the latent prefill kernel
# a slot's "live" column of the step program's operand: its token comes
# from the host, or from the result of the step before on the device
LIVE, FED = 1, 2


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def layer_shapes(cfg: TransformerConfig, kind: str,
                 ffn: str = DENSE) -> dict:
    """``{name: (shape, fan_in or None for a norm weight)}`` of one
    layer, in the order the seeded init draws them."""
    dm, ff = cfg.d_model, cfg.d_ff
    # a norm a sublayer the block has
    out = {name: ((dm,), None) for name, has in
           (("norm1", kind != NONE), ("norm2", ffn != NONE)) if has}
    if kind == MLA:
        h, r, ql = cfg.n_heads, cfg.kv_lora_rank, cfg.q_lora_rank
        nope, rope, v = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
        # kv_b is held as its two factors a head, head-major: the
        # absorbed form contracts each over its own minor dimensions
        out.update(wq_a=((dm, ql), dm), q_norm=((ql,), None),
                   wq_b=((ql, h * (nope + rope)), ql),
                   wkv_a=((dm, r + rope), dm), kv_norm=((r,), None),
                   w_uk=((h, nope, r), r), w_uv=((h, r, v), r),
                   wo=((h * v, dm), h * v))
    elif kind == SPARSE:
        hd, kvd, d = (cfg.n_heads * cfg.head_dim,
                      cfg.n_kv_heads * cfg.head_dim, cfg.head_dim)
        out.update(wq=((dm, hd), dm), wk=((dm, kvd), dm),
                   wv=((dm, kvd), dm), wg=((dm, hd), dm),
                   wo=((hd, dm), hd), q_norm=((d,), None),
                   k_norm=((d,), None))
    elif kind == ATTN:
        hd, kvd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
        out.update(wq=((dm, hd), dm), wk=((dm, kvd), dm),
                   wv=((dm, kvd), dm), wo=((hd, dm), hd))
    elif kind == MAMBA:
        di, n, r, k = (cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank,
                       cfg.ssm_conv)
        # what is per (state value, channel) or per (tap, channel) lies
        # channels minor, as the state row does
        out.update(w_in=((dm, 2 * di), dm), conv_w=((k, di), ROUTER),
                   conv_b=((di,), BIAS), w_x=((di, r + 2 * n), di),
                   dt_norm=((r,), None), b_norm=((n,), None),
                   c_norm=((n,), None), w_dt=((r, di), r),
                   b_dt=((di,), DT_BIAS), a_log=((n, di), A_LOG),
                   d=((di,), ONES), w_out=((di, dm), di))
    elif kind == MAMBA2:
        h, di, ch = cfg.ssm_heads, cfg.ssd_inner, cfg.ssd_channels
        # one projection: the gate, the convolved channels, a step size
        # a head
        out.update(w_in=((dm, di + ch + h), dm),
                   conv_w=((cfg.ssm_conv, ch), ROUTER),
                   conv_b=((ch,), BIAS), b_dt=((h,), DT_BIAS),
                   a_log=((h,), A_LOG_U), d=((h,), ONES),
                   g_norm=((di,), None), w_out=((di, dm), di))
    elif kind == LINEAR:
        hd, d = cfg.lin_heads * cfg.lin_head_dim, cfg.lin_head_dim
        out.update(wq=((dm, hd), dm), wk=((dm, hd), dm),
                   wv=((dm, hd), dm), wg=((dm, hd), dm),
                   wo=((hd, dm), hd), q_norm=((d,), None),
                   k_norm=((d,), None), o_norm=((d,), None))
    if ffn == MOE:
        n, fe = cfg.experts_held[1], cfg.moe_d_ff
        fs = fe * cfg.n_shared_experts
        out.update(router=((dm, cfg.n_experts), ROUTER),
                   router_bias=((cfg.n_experts,), BIAS),
                   we_gate=((n, dm, fe), dm), we_up=((n, dm, fe), dm),
                   we_down=((n, fe, dm), fe), ws_gate=((dm, fs), dm),
                   ws_up=((dm, fs), dm), ws_down=((fs, dm), fs))
    elif ffn == LATENT_MOE:
        n, fe, lat, fs = (cfg.experts_held[1], cfg.moe_d_ff, cfg.moe_latent,
                          cfg.shared_d_ff)
        out.update(router=((dm, cfg.n_experts), ROUTER),
                   router_bias=((cfg.n_experts,), SMALL_BIAS),
                   w_lat_in=((dm, lat), dm), we_up=((n, lat, fe), lat),
                   we_down=((n, fe, lat), fe), w_lat_out=((lat, dm), lat),
                   ws_up=((dm, fs), dm), ws_down=((fs, dm), fs))
    elif ffn == DENSE:
        out.update(w_gate=((dm, ff), dm), w_up=((dm, ff), dm),
                   w_down=((ff, dm), ff))
    return out


def layer_kinds(cfg: TransformerConfig) -> list:
    """``[(mixer kind, feed-forward kind)]`` of the held layers."""
    ffns = cfg.ffn_types or (DENSE,) * len(cfg.mixer_types)
    return list(zip(cfg.mixer_types, ffns))


def top_shapes(cfg: TransformerConfig, head_gain: float = 1.0) -> dict:
    """The embedding, the final norm and, where the family does not tie
    it to the embedding, the head; in the order the init draws them."""
    out = {"emb": ((cfg.vocab, cfg.d_model), cfg.d_model)}
    if not cfg.tie_embeddings:
        out["head"] = ((cfg.vocab, cfg.d_model),
                       cfg.d_model / head_gain ** 2)
    out["norm_f"] = ((cfg.d_model,), None)
    return out


def init_hybrid_params(cfg: TransformerConfig, key=None) -> dict:
    """Seeded parameters: matrices normal(0, 1/fan_in) in
    ``cfg.param_dtype``, norm weights ``1 + 0.1 normal`` in float32,
    the head scaled by ``d_model / dim_model_base`` so that the muP
    division leaves logits of order one.  One jitted call a layer (the
    float32 draw of a whole stacked tensor would not fit beside 10 GB
    of weights)."""
    import jax
    import jax.numpy as jnp
    key = key if key is not None else jax.random.PRNGKey(0)
    dt = jnp.dtype(cfg.param_dtype)

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
            elif fan_in == A_LOG_U:
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0))
            elif fan_in == ONES:
                out[name] = jnp.ones(shape, jnp.float32)
            elif fan_in == DT_BIAS:
                lo, hi = (math.log(v) for v in DT_RANGE)
                step = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                                  lo, hi))
                out[name] = step + jnp.log(-jnp.expm1(-step))
            elif fan_in == BIAS:
                out[name] = 0.1 * x
            elif fan_in == SMALL_BIAS:
                out[name] = 0.01 * x
            elif fan_in == ROUTER:
                out[name] = x / math.sqrt(shape[0])
            else:
                out[name] = (x / math.sqrt(fan_in)).astype(dt)
        return out

    ks = jax.random.split(key, cfg.n_layers + 1)
    head_gain = cfg.d_model / cfg.dim_model_base if cfg.dim_model_base \
        else 1.0
    kinds = layer_kinds(cfg)
    # built once a model at start-up, one program a kind of layer
    # brpc-check: allow(jit-hot-path)
    init = {kind: jax.jit(functools.partial(draw, shapes=shapes))
            for kind, shapes in [
        ("top", top_shapes(cfg, head_gain))]
            + [(k, layer_shapes(cfg, *k)) for k in sorted(set(kinds))]}
    top = init["top"](ks[0])
    top["layers"] = [init[kind](k) for kind, k in zip(kinds, ks[1:])]
    return top


# ---------------------------------------------------------------------------
# the layer mathematics (traced inside the two programs below)
# ---------------------------------------------------------------------------

def _mm(x, w):
    """``x W`` at the stated precision.  A bfloat16 weight takes its
    input at bfloat16 and ONE pass, named so: the programs trace under
    ``default_matmul_precision("highest")`` (for their float32
    products), and a bfloat16 dot that inherits it is free to keep the
    float32 input the cast came from and multiply it in six passes
    (the compiler drops the cast as excess precision): slower, and not
    the values the configuration states (PERF.md section 6, PR 32)."""
    import jax
    import jax.numpy as jnp
    if w.dtype == jnp.bfloat16:
        out = jnp.dot(_to_bf16(x), w, preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.DEFAULT)
    else:
        out = jnp.dot(x, w, precision="highest")
    return _acc(getattr(_TRACING, "control", ""), out)


def _bmm(spec: str, x, w):
    """A batched product a head (``jnp.einsum``) at ``_mm``'s
    precision."""
    import jax
    import jax.numpy as jnp
    if w.dtype == jnp.bfloat16 and jax.default_backend() == "cpu" \
            and getattr(_TRACING, "backend", None) != "mosaic":
        # XLA's CPU backend has no batched bfloat16 dot: the same
        # products (exact in float32) of the same bfloat16 values
        out = jnp.einsum(spec, _to_bf16(x).astype(jnp.float32),
                         w.astype(jnp.float32), precision="highest")
    elif w.dtype == jnp.bfloat16:
        out = jnp.einsum(spec, _to_bf16(x), w,
                         preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.DEFAULT)
    else:
        out = jnp.einsum(spec, x, w, precision="highest")
    return _acc(getattr(_TRACING, "control", ""), out)


def _rmm(x, w, sizes):
    """The ragged product of ``ops.moe.expert_ffn`` at ``_mm``'s
    precision: rows of ``x`` in groups of ``sizes`` against ``w [E, K,
    N]``."""
    import jax
    import jax.numpy as jnp
    if w.dtype == jnp.bfloat16:
        out = jax.lax.ragged_dot(_to_bf16(x), w, sizes,
                                 preferred_element_type=jnp.float32,
                                 precision=jax.lax.Precision.DEFAULT)
    else:
        out = jax.lax.ragged_dot(x, w, sizes, precision="highest")
    return _acc(getattr(_TRACING, "control", ""), out)


def _to_bf16(x):
    """float32 -> bfloat16 at ONE defined point.  A bare cast lets the
    chip's compiler carry bfloat16 backwards into the float32
    arithmetic that made ``x`` (the norm's products, ``silu(g) * u``):
    several roundings where the configuration states one.
    ``reduce_precision`` is computed in float32 and kept; the cast after
    it is exact."""
    import jax
    import jax.numpy as jnp
    return jax.lax.reduce_precision(
        x, exponent_bits=8, mantissa_bits=7).astype(jnp.bfloat16)


def _rms(x, w, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope(x, pos, theta):
    """Rotary positions over the whole head, rotate-half convention:
    ``x [N, H, D]``, ``pos [N]``."""
    import jax.numpy as jnp
    d = x.shape[-1]
    inv = jnp.exp(-math.log(theta)
                  * jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]    # [N, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _cache_round(x, control):
    """What the K/V pages hold of ``x``: bfloat16 values (under the
    low-precision control, the values of an int8 cache)."""
    import jax.numpy as jnp
    if control == "low":
        x = jnp.clip(jnp.round(x * 16.0), -127.0, 127.0) / 16.0
    return _to_bf16(x)


# the control and the backend a program is being TRACED under (``step`` /
# ``prefill`` set them first thing; they are static arguments of both, so
# one value a trace)
_TRACING = threading.local()


def _acc(control, h):
    """A float32 accumulator as the configuration states it: the
    residual stream after an add, a matmul's float32 sum.  Under the
    low-precision control it holds bfloat16 values."""
    import jax
    if control != "low":
        return h
    return jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)


def _mlp(p, h, cfg, rs):
    import jax
    x = _rms(h, p["norm2"], cfg.rms_eps)
    return h + rs * _mm(jax.nn.silu(_mm(x, p["w_gate"]))
                        * _mm(x, p["w_up"]), p["w_down"])


def moe_share(p, x, cfg, valid, ffn: str = MOE):
    """One expert layer's feed-forward (of kind ``ffn``) of the normed
    rows ``x [N, dm]``: ``(the held experts' share of the routed sum,
    the shared expert's output, group sizes [held] of this call)``.
    The router scores ALL ``cfg.n_experts``; the share is of
    ``cfg.experts_held``.  ``latent_moe``'s share is the held experts'
    sum in the latent width through the projection up: the shares of
    every holder add up to the whole layer's routed output."""
    import jax
    import jax.numpy as jnp
    from brpc_tpu.ops.moe import expert_ffn, route
    control = getattr(_TRACING, "control", "")
    if control == "low":
        logit = _acc(control, jnp.dot(
            _to_bf16(x), _to_bf16(p["router"]),
            preferred_element_type=jnp.float32))
    else:
        logit = jnp.dot(x, p["router"], precision="highest")
    idx, w = route(jax.nn.sigmoid(logit), p["router_bias"],
                   cfg.experts_per_tok, norm=cfg.norm_topk,
                   scale=cfg.routed_scale)
    if control == "drop":
        w = w.at[:, -1].set(0.0)
    if ffn == LATENT_MOE:
        r, sizes = expert_ffn(
            _mm(x, p["w_lat_in"]), idx, w, valid, None, p["we_up"],
            p["we_down"], held=cfg.experts_held, mm=_rmm,
            backend=getattr(_TRACING, "backend", None),
            round_acc="bfloat16" if control == "low" else None)
        y = _mm(r, p["w_lat_out"])
        shared = _mm(jnp.square(jax.nn.relu(_mm(x, p["ws_up"]))),
                     p["ws_down"])
        return y, shared, sizes
    y, sizes = expert_ffn(x, idx, w, valid, p["we_gate"], p["we_up"],
                          p["we_down"], held=cfg.experts_held, mm=_rmm)
    shared = _mm(jax.nn.silu(_mm(x, p["ws_gate"])) * _mm(x, p["ws_up"]),
                 p["ws_down"])
    return y, shared, sizes


def ffn_kernel_blocks(cfg, backend) -> int:
    """The expert blocks of ``cfg`` that go through the ``expert_ffn``
    kernel under ``backend`` (``ops.moe``: the two-matrix body, on a
    TPU or interpreted)."""
    from brpc_tpu.ops.moe import default_backend
    if (backend or default_backend()) == "gather":
        return 0
    return sum(1 for f in cfg.ffn_types if f == LATENT_MOE)


def _moe(p, h, cfg, valid, ffn):
    """``h + routed share + shared expert``, ``[the held experts this
    call hit, the assignments that fell to them]`` and the group sizes
    they are counted from."""
    import jax.numpy as jnp
    y, shared, sizes = moe_share(p, _rms(h, p["norm2"], cfg.rms_eps), cfg,
                                 valid, ffn)
    return (h + y + shared, jnp.stack([(sizes > 0).sum(), sizes.sum()]),
            sizes)


def _mla_project(p, x, qpos, cfg, lanes: int, control):
    """Latent attention's two projections of the normed rows ``x [N,
    dm]`` at positions ``qpos``: ``(absorbed queries [N, H, lanes],
    scaled; the row the cache holds [N, lanes], at the cache's
    values)``; lanes past ``kv_lora_rank + rope`` are zero."""
    import jax.numpy as jnp
    n, h = x.shape[0], cfg.n_heads
    nope, rope, r = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.kv_lora_rank
    cq = _rms(_mm(x, p["wq_a"]), p["q_norm"], cfg.rms_eps)
    q = _mm(cq, p["wq_b"]).reshape(n, h, nope + rope)
    kv = _mm(x, p["wkv_a"])
    c_kv = _rms(kv[:, :r], p["kv_norm"], cfg.rms_eps)
    k_rope = _rope(kv[:, None, r:], qpos, cfg.rope_theta)[:, 0]
    pad = lanes - r - rope
    row = _cache_round(jnp.concatenate(
        [c_kv, k_rope, jnp.zeros((n, pad), jnp.float32)], axis=-1), control)
    qq = jnp.concatenate(
        [_bmm("nhd,hdc->nhc", q[..., :nope], p["w_uk"]),
         _rope(q[..., nope:], qpos, cfg.rope_theta),
         jnp.zeros((n, h, pad), jnp.float32)], axis=-1)
    qq = qq / math.sqrt(nope + rope)
    if p["wo"].dtype == jnp.bfloat16:
        qq = _to_bf16(qq)        # a matmul input, like any other
    return qq, row


def _mla_out(p, o_lat, cfg):
    """The attended latents ``[N, H, lanes]`` through ``W_UV`` and
    ``W_o``."""
    n = o_lat.shape[0]
    o = _bmm("nhc,hcd->nhd", o_lat[..., :cfg.kv_lora_rank], p["w_uv"])
    return _mm(o.reshape(n, cfg.n_heads * cfg.v_head_dim), p["wo"])


def _logits(params, h, cfg):
    x = _rms(h, params["norm_f"], cfg.rms_eps)
    if cfg.dim_model_base:
        x = x / (cfg.d_model / cfg.dim_model_base)
    # the head is kept [vocab, d_model] like the embedding: the
    # contraction then runs over the minor dimension of both operands,
    # which is the layout the chip's compiler wants (a [d_model, vocab]
    # head is copied transposed, 0.6 GB, every step)
    import jax
    w = params["emb"] if cfg.tie_embeddings else params["head"]
    if w.dtype == x.dtype:
        return jax.lax.dot_general(x, w, (((1,), (1,)), ((), ())),
                                   precision="highest")
    return jax.lax.dot_general(_to_bf16(x), w,
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=x.dtype,
                               precision=jax.lax.Precision.DEFAULT)


def _qkv(p, x, heads, kv_heads, d, cfg):
    n = x.shape[0]
    q = _mm(x, p["wq"]).reshape(n, heads, d)
    k = _mm(x, p["wk"]).reshape(n, kv_heads, d)
    v = _mm(x, p["wv"]).reshape(n, kv_heads, d)
    if cfg.qk_norm:
        q = _rms(q, p["q_norm"], cfg.rms_eps)
        k = _rms(k, p["k_norm"], cfg.rms_eps)
    return q, k, v


def _gate_out(p, x, o, gated):
    import jax
    if gated:
        o = o * jax.nn.sigmoid(_mm(x, p["wg"]))
    return _mm(o, p["wo"])


def _ssm_inputs(p, xc, cfg):
    """The state-space mixer's per-token inputs from the convolved rows
    ``xc [N, channels]``: ``(delta [N, channels], B [N, n], C [N, n])``,
    the low-rank step size, ``B`` and ``C`` each through its learned
    RMS norm (the family's addition to Mamba-1), ``delta = softplus(W_dt
    dt + b_dt)``."""
    import jax
    r, n = cfg.ssm_dt_rank, cfg.ssm_state
    dbc = _mm(xc, p["w_x"])
    dt = _rms(dbc[:, :r], p["dt_norm"], cfg.rms_eps)
    bm = _rms(dbc[:, r:r + n], p["b_norm"], cfg.rms_eps)
    cm = _rms(dbc[:, r + n:], p["c_norm"], cfg.rms_eps)
    return jax.nn.softplus(_mm(dt, p["w_dt"]) + p["b_dt"]), bm, cm


def _ssd_in(p, x, cfg):
    """The Mamba-2 mixer's ONE projection of the normed rows ``x``:
    ``(gate z [N, H P], the convolution's input [N, H P + 2 G N], the
    step size before its bias [N, H])``."""
    di, ch = cfg.ssd_inner, cfg.ssd_channels
    zxd = _mm(x, p["w_in"])
    return zxd[:, :di], zxd[:, di:di + ch], zxd[:, di + ch:]


def _ssd_split(c, cfg):
    """The convolved rows -> ``(xs [N, H P], B [N, G N], C [N, G N])``."""
    di, gn = cfg.ssd_inner, cfg.ssm_groups * cfg.ssm_state
    return c[:, :di], c[:, di:di + gn], c[:, di + gn:]


def _ssd_out(p, y, xs, z, cfg):
    """``y + D xs`` gated by ``silu(z)``, THEN the RMS norm over each
    group's channels apart, and the projection out."""
    import jax
    import jax.numpy as jnp
    n = y.shape[0]
    g = (y + jnp.repeat(p["d"], cfg.ssm_head_dim)[None, :] * xs) \
        * jax.nn.silu(z)
    gg = g.reshape(n, cfg.ssm_groups, -1)
    gg = gg * jax.lax.rsqrt(jnp.mean(gg * gg, axis=-1, keepdims=True)
                            + cfg.rms_eps)
    return _mm(gg.reshape(n, -1) * p["g_norm"], p["w_out"])


def _select_tables(q, kcs, qpos, table, cfg, page_tokens):
    """Selection for rows ``q [N, Hkv, G, D]`` of sequences whose page
    tables are ``table [N, MPs]`` and compressed keys ``kcs [N, J, Hkv,
    D]``: ``(arena pages, logical blocks) [N, Hkv, topk]``."""
    import jax.numpy as jnp
    from brpc_tpu.ops.sparse_attention import select_blocks
    blocks = select_blocks(q, kcs, qpos, page_tokens=page_tokens,
                           topk=cfg.sparse_topk,
                           init_blocks=cfg.sparse_init_blocks,
                           window=cfg.sparse_window)
    pages = jnp.take_along_axis(
        table[:, None, :], jnp.maximum(blocks, 0), axis=2)
    return jnp.where(blocks >= 0, pages, -1), blocks


def _kernel_order(kc_pages):
    """``[N, pages, 4, Hkv, D]`` as the sequence's table gathers them
    -> ``[N, J, Hkv, D]`` with kernel ``j`` at index ``j``: kernel ``j``
    is kept at slot ``(j + 1) % 4`` of page ``(j + 1) // 4``, the page
    its last key lies in."""
    import jax.numpy as jnp
    n, pages, per, hkv, d = kc_pages.shape
    flat = kc_pages.reshape(n, pages * per, hkv, d)
    return jnp.concatenate([flat[:, 1:], jnp.zeros_like(flat[:, :1])],
                           axis=1)


def _dense_tables(table, n_pages: int, width: int):
    """The dense branch as a page table: the sequence's first
    ``n_pages`` pages in order, padded to ``width``."""
    import jax.numpy as jnp
    n = table.shape[0]
    have = min(n_pages, table.shape[1], width)
    pad = jnp.full((n, width - have), -1, jnp.int32)
    pages = jnp.concatenate([table[:, :have], pad], axis=1)
    blocks = jnp.where(pages >= 0, jnp.arange(width, dtype=jnp.int32), -1)
    return pages, blocks


def _attend_rows(q, kv, ls, pages, blocks, lengths, backend):
    """``q [N, Hkv, G, D]``, ``pages``/``blocks`` ``[N, Hkv, MP]``:
    one kernel row a (position, K/V head)."""
    import jax.numpy as jnp
    from brpc_tpu.ops.sparse_attention import sparse_attend
    n, hkv, g, d = q.shape
    mp = pages.shape[-1]
    o = sparse_attend(
        q.reshape(n * hkv, g, d), kv, ls,
        jnp.tile(jnp.arange(hkv, dtype=jnp.int32), n),
        pages.reshape(n * hkv, mp), blocks.reshape(n * hkv, mp),
        jnp.repeat(lengths, hkv), backend=backend)
    return o.reshape(n, hkv * g * d)


def _attend_all(q4, kv, ls, tables, qpos, live, backend):
    """The ``attention`` mixer's attention for N positions: every page
    of the sequence's table ``tables [N, MPs]`` IS the position's page
    table, keys at positions ``<= qpos`` take part.  Keys are read from
    the arena only: write before you attend.  A row that is not live
    names no page (see :func:`_attend_positions`)."""
    import jax.numpy as jnp
    n, hkv = q4.shape[0], q4.shape[1]
    mps = tables.shape[1]
    pages, blocks = _dense_tables(jnp.where(live[:, None], tables, -1),
                                  mps, mps)
    return _attend_rows(
        q4, kv, ls, jnp.broadcast_to(pages[:, None, :], (n, hkv, mps)),
        jnp.broadcast_to(blocks[:, None, :], (n, hkv, mps)),
        jnp.where(live, qpos + 1, 0), backend)


def _attend_positions(q4, kv, ls, tables, kcs, qpos, live, cfg, backend):
    """The ``minicpm4`` mixer's attention for N positions, each with its
    sequence's page table ``tables [N, MPs]`` and compressed keys ``kcs
    [N, J, Hkv, D]``: positions with ``t + 1 <= dense_len`` attend to
    every page (the sequence's own table IS their page table), the
    others to the ``topk`` blocks they select.  Keys are read from the
    arena only, the position's own included: write before you attend.
    Returns ``(o [N, H * D], blocks selected a sparse position [N])``.
    The wider (dense) table is built only where a live position needs
    it."""
    import jax
    import jax.numpy as jnp
    n, hkv = q4.shape[0], q4.shape[1]
    t_page = kv.shape[4]
    dense_row = qpos + 1 <= cfg.sparse_dense_len
    dense_pages = -(-cfg.sparse_dense_len // t_page)
    width = max(cfg.sparse_topk, dense_pages)
    pages_s, blocks_s = _select_tables(q4, kcs, qpos, tables, cfg, t_page)
    # a row that is not live (an idle slot, a bucket's padding) names no
    # page: the kernel's work list then holds one step for it (its zero
    # output) where it would step through 64 pages to mask them all
    pages_s = jnp.where(live[:, None, None], pages_s, -1)
    tables = jnp.where(live[:, None], tables, -1)
    lengths = jnp.where(live, qpos + 1, 0)

    def with_dense(_):
        pd, bd = _dense_tables(tables, dense_pages, width)
        pad = jnp.full((n, hkv, width - cfg.sparse_topk), -1, jnp.int32)
        ps = jnp.concatenate([pages_s, pad], axis=-1)
        bs = jnp.concatenate([blocks_s, pad], axis=-1)
        sel = dense_row[:, None, None]
        return _attend_rows(q4, kv, ls, jnp.where(sel, pd[:, None, :], ps),
                            jnp.where(sel, bd[:, None, :], bs), lengths,
                            backend)

    def all_sparse(_):
        return _attend_rows(q4, kv, ls, pages_s, blocks_s, lengths, backend)
    o = jax.lax.cond(jnp.any(dense_row & live), with_dense, all_sparse, None)
    n_sel = jnp.where(dense_row | ~live, 0.0,
                      (blocks_s >= 0).sum(axis=(1, 2)) / hkv)
    return o, n_sel


@functools.cache
def _programs():
    """The two jitted programs, built at the first runner.  Neither
    reads or writes the K/V or the latent arena through XLA operations:
    on the chip ``cache_write``, ``page_keys``, ``latent_write`` and the
    attention kernels are Pallas calls (``ops.sparse_attention`` says
    why)."""
    import jax
    import jax.numpy as jnp
    from brpc_tpu.ops.latent_attention import (latent_attend,
                                               latent_attend_slots,
                                               latent_write)
    from brpc_tpu.ops.lightning import (lightning_chunk, lightning_decode,
                                        log_decays)
    from brpc_tpu.ops.mamba import (conv_chunk, conv_step, mamba_scan,
                                    mamba_step)
    from brpc_tpu.ops.sparse_attention import (KERNELS_PER_PAGE,
                                               cache_write, compress_keys,
                                               page_keys)
    from brpc_tpu.ops.moe import ROW_TILE, tile_visits
    from brpc_tpu.ops.ssd import ssd_scan, ssd_step, state_rows, tail_rows
    from brpc_tpu.ops.ssd import conv_step as ssd_conv_step

    # ---- one decode position a slot --------------------------------------

    def step(params, kv, kc, state, packed, prev, latent=None, *, cfg,
             backend, control, logits_out):
        """``packed [S, 4 + MPs]`` int32: a slot's token, position, state
        row, whether it is live (0 no, ``LIVE``, or ``FED``: live, and
        its token is row 0 of ``prev``, the step before's result, not
        ``packed[:, 0]``), then its page table; a model with latent
        layers has two columns more ahead of the table, the keys at the
        head of it that the slot shares with the leader row and that
        row (``ops.latent_attention.shared_run``).  ONE operand made on the
        host and one result (``[3, S]`` float32: next token, its
        log-probability, blocks selected; a model with expert layers
        adds two rows whose first value is the held experts its layers
        hit, and the assignments that fell to them) a
        step: every device array a
        step makes and drops costs the engine thread a hand-off of the
        interpreter lock under load (PERF.md section 7, entry 15).
        ``FED`` is data: a step dispatched before the one ahead of it
        was fetched is the same program as any other."""
        _TRACING.control, _TRACING.backend = control, backend
        positions, rows = packed[:, 1], packed[:, 2]
        tokens = jnp.where(packed[:, 3] == FED,
                           prev[0].astype(jnp.int32), packed[:, 0])
        active = packed[:, 3] > 0
        tables = packed[:, 4 + 2 * bool(cfg.n_latent):]
        t_page = kv.shape[4]
        n_arena = kv.shape[3]
        stride = t_page // KERNELS_PER_PAGE
        s_n = tokens.shape[0]
        hkv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
        rs = cfg.residual_scale
        qpos = jnp.maximum(positions - 1, 0)
        page = jnp.take_along_axis(tables, (qpos // t_page)[:, None],
                                   axis=1)[:, 0]
        page = jnp.where(active & (page >= 0), page, n_arena)
        # the kernel this position completes, if it does; it lives in
        # the page its LAST key lies in (this position's): the page
        # where it starts may be a shared prefix page, and what follows
        # that differs by sequence
        j = (qpos + 1) // stride - 2
        done = active & ((qpos + 1) % stride == 0) & (j >= 0)
        jc = jnp.maximum(j, 0)
        page_a = jnp.take_along_axis(
            tables, (jc // KERNELS_PER_PAGE)[:, None], axis=1)[:, 0]
        win_at = (jc % KERNELS_PER_PAGE) * stride
        round_state = "bfloat16" if control == "low" else None
        h = cfg.scale_emb * params["emb"][tokens].astype(jnp.float32)
        ls = ll = lm = 0
        n_sel = jnp.zeros((s_n,), jnp.float32)
        # where the expert blocks take the ``expert_ffn`` kernel, the
        # row tiles it visits for them
        kernel = bool(ffn_kernel_blocks(cfg, backend))
        n_moe, n_tile = jnp.zeros((2,), jnp.int32), 0
        for p, (kind, ffn) in zip(params["layers"], layer_kinds(cfg)):
            # a block of kind "none" takes no arm below
            x = _rms(h, p["norm1"], cfg.rms_eps) if kind != NONE else None
            if kind == MLA:
                qq, row = _mla_project(p, x, qpos, cfg, latent.shape[-1],
                                       control)
                latent = latent_write(latent, lm, page, qpos % t_page,
                                      row[:, None, :], backend=backend)
                o = latent_attend_slots(
                    qq, jnp.where(active, qpos + 1, 0), packed[:, 4],
                    packed[:1, 5], latent, lm, tables, backend=backend)
                h = _acc(control, h + rs * _mla_out(p, o, cfg))
                lm += 1
            elif kind == SPARSE:
                q, k, v = _qkv(p, x, cfg.n_heads, hkv, cfg.head_dim, cfg)
                kv = cache_write(
                    kv, ls, page, qpos % t_page,
                    _cache_round(k, control)[:, :, None],
                    _cache_round(v, control)[:, :, None], backend=backend)
                # the last 2 x stride keys, from the (one or two) pages
                # they lie in, as the cache now holds them
                two = page_keys(kv, ls, jnp.concatenate([page_a, page]),
                                backend=backend).astype(jnp.float32)
                two = jnp.concatenate([two[:s_n], two[s_n:]], axis=2)
                idx = win_at[:, None] + jnp.arange(2 * stride)[None, :]
                mean = jnp.take_along_axis(
                    two, idx[:, None, :, None], axis=2).mean(axis=2)
                kc = kc.at[ls, jnp.where(done, page, n_arena),
                           (jc + 1) % KERNELS_PER_PAGE].set(
                    mean.astype(jnp.bfloat16), mode="drop")
                kcs = _kernel_order(
                    kc[ls][jnp.clip(tables, 0, n_arena - 1)])
                o, sel = _attend_positions(
                    q.reshape(s_n, hkv, g, cfg.head_dim), kv, ls, tables,
                    kcs, qpos, active, cfg, backend)
                n_sel = n_sel + sel
                h = _acc(control, h + rs * _gate_out(
                    p, x, o, cfg.attn_output_gate))
                ls += 1
            elif kind == ATTN:
                q, k, v = _qkv(p, x, cfg.n_heads, hkv, cfg.head_dim, cfg)
                kv = cache_write(
                    kv, ls, page, qpos % t_page,
                    _cache_round(k, control)[:, :, None],
                    _cache_round(v, control)[:, :, None], backend=backend)
                o = _attend_all(q.reshape(s_n, hkv, g, cfg.head_dim), kv,
                                ls, tables, qpos, active, backend)
                h = _acc(control, h + rs * _mm(o, p["wo"]))
                ls += 1
            elif kind == MAMBA:
                di = cfg.ssm_inner
                xz = _mm(x, p["w_in"])
                xc, state = conv_step(
                    state, rows, ll, xz[:, :di], p["conv_w"], p["conv_b"],
                    d_state=cfg.ssm_state, round_state=round_state,
                    backend=backend)
                delta, bm, cm = _ssm_inputs(p, xc, cfg)
                y, state = mamba_step(
                    state, rows, ll, xc, delta, xz[:, di:], bm, cm,
                    p["a_log"], p["d"], round_state=round_state,
                    backend=backend)
                h = _acc(control, h + rs * _mm(y, p["w_out"]))
                ll += 1
            elif kind == MAMBA2:
                z, xbc, dt = _ssd_in(p, x, cfg)
                c, state = ssd_conv_step(
                    state, rows, ll, xbc, p["conv_w"], p["conv_b"],
                    n_state=state_rows(cfg.ssm_heads, cfg.ssm_head_dim,
                                       cfg.ssm_state),
                    round_state=round_state, backend=backend)
                xs, bm, cm = _ssd_split(c, cfg)
                y, state = ssd_step(
                    state, rows, ll, xs, jax.nn.softplus(dt + p["b_dt"]),
                    bm, cm, p["a_log"], groups=cfg.ssm_groups,
                    round_state=round_state, backend=backend)
                h = _acc(control, h + rs * _ssd_out(p, y, xs, z, cfg))
                ll += 1
            elif kind == LINEAR:
                hl, dl = cfg.lin_heads, cfg.lin_head_dim
                q, k, v = _qkv(p, x, hl, hl, dl, cfg)
                if cfg.lin_rope:
                    q = _rope(q, qpos, cfg.rope_theta)
                    k = _rope(k, qpos, cfg.rope_theta)
                o, state = lightning_decode(
                    state, rows, ll, q, k, v, log_decays(hl),
                    scale=1.0 / math.sqrt(dl), round_state=round_state,
                    backend=backend)
                if cfg.lin_output_norm:
                    o = _rms(o, p["o_norm"], cfg.rms_eps)
                h = _acc(control, h + rs * _gate_out(
                    p, x, o.reshape(s_n, hl * dl), cfg.lin_output_gate))
                ll += 1
            if ffn in (MOE, LATENT_MOE):
                h, hit, sizes = _moe(p, h, cfg, active, ffn)
                h, n_moe = _acc(control, h), n_moe + hit
                if kernel:
                    n_tile = n_tile + tile_visits(sizes).sum()
            elif ffn == DENSE:
                h = _acc(control, _mlp(p, h, cfg, rs))
        logits = _logits(params, h, cfg)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logprob = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), nxt[:, None], axis=1)[:, 0]
        out = [nxt.astype(jnp.float32), logprob,
               n_sel / max(1, cfg.n_sparse)]
        if cfg.n_moe:
            out += [jnp.zeros((s_n,), jnp.float32).at[0].set(v)
                    for v in n_moe.astype(jnp.float32)]
        if kernel and s_n > 1:
            # the kernel's rows ride a spare column of the experts' row
            out[3] = out[3].at[1].set(
                (n_tile * ROW_TILE).astype(jnp.float32))
        return (jnp.stack(out), logits if logits_out else None, kv, kc,
                state, latent)

    # ---- one prefill chunk of one sequence -------------------------------

    def prefill(params, kv, kc, state, packed, latent=None, *, cfg, backend,
                control, logits_out, max_pages):
        """``packed`` int32: the chunk's start, its valid length, the
        sequence's state row, its page table ``[MPs]``, then the
        bucket's tokens (``MPs`` is the store's, static)."""
        _TRACING.control, _TRACING.backend = control, backend
        mps = max_pages
        start, n_valid, row = packed[0], packed[1], packed[2]
        table, tokens = packed[3:3 + mps], packed[3 + mps:]
        t_page = kv.shape[4]
        n_arena = kv.shape[3]
        stride = t_page // KERNELS_PER_PAGE
        c = tokens.shape[0]
        hkv, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
        d = cfg.head_dim
        rs = cfg.residual_scale
        off = jnp.arange(c, dtype=jnp.int32)
        qpos = start + off
        valid = off < n_valid
        mps = table.shape[0]
        # the chunk's pages: it starts at a page boundary, and a page
        # is written whole where it holds a valid position (what lies
        # behind the last valid one is masked by every reader's length
        # and overwritten by the positions that come)
        n_pg = c // t_page
        pg_l = start // t_page + jnp.arange(n_pg, dtype=jnp.int32)
        page = table[jnp.clip(pg_l, 0, mps - 1)]
        page = jnp.where((jnp.arange(n_pg) * t_page < n_valid)
                         & (page >= 0), page, n_arena)
        page_before = table[jnp.clip(start // t_page - 1, 0, mps - 1)]
        round_state = "bfloat16" if control == "low" else None
        # kernels this chunk completes: j = start/stride - 1 + i, each
        # kept in the page its last key lies in
        n_k = c // stride
        jk = start // stride - 1 + jnp.arange(n_k, dtype=jnp.int32)
        k_done = (jk >= 0) & (stride * jk + 2 * stride - 1
                              < start + n_valid)
        jkc = jnp.maximum(jk, 0)
        k_page = table[jnp.clip((jkc + 1) // KERNELS_PER_PAGE, 0, mps - 1)]
        k_page = jnp.where(k_done & (k_page >= 0), k_page, n_arena)
        qs = min(c, 64)              # positions a call of the kernel
        h = cfg.scale_emb * params["emb"][tokens].astype(jnp.float32)
        ls = ll = lm = 0
        n_sel = jnp.zeros((c,), jnp.float32)
        kernel = bool(ffn_kernel_blocks(cfg, backend))
        n_held, n_tile = jnp.zeros((), jnp.int32), 0
        # the latent kernel's grid rows: blocks of positions, every head
        # of a block a row of ONE matmul against the page
        pr = PREFILL_ROWS if c % PREFILL_ROWS == 0 else 1
        for p, (kind, ffn) in zip(params["layers"], layer_kinds(cfg)):
            # a block of kind "none" takes no arm below
            x = _rms(h, p["norm1"], cfg.rms_eps) if kind != NONE else None
            if kind == MLA:
                nh = cfg.n_heads
                qq, row = _mla_project(p, x, qpos, cfg, latent.shape[-1],
                                       control)
                latent = latent_write(
                    latent, lm, page, jnp.zeros((n_pg,), jnp.int32),
                    row.reshape(n_pg, t_page, -1), backend=backend)
                seen = jnp.where(valid, qpos + 1, 0)
                o = latent_attend(
                    qq.reshape(c // pr, pr * nh, -1),
                    jnp.repeat(seen, nh).reshape(c // pr, pr * nh, 1),
                    latent, lm, jnp.zeros((c // pr,), jnp.int32),
                    table[None], backend=backend)
                h = _acc(control, h + rs * _mla_out(
                    p, o.reshape(c, nh, -1), cfg))
                lm += 1
            elif kind == SPARSE:
                q, k, v = _qkv(p, x, cfg.n_heads, hkv, d, cfg)
                before = page_keys(kv, ls, page_before[None],
                                   backend=backend)[0]       # [Hkv,T,D]
                kv = cache_write(
                    kv, ls, page, jnp.zeros((n_pg,), jnp.int32),
                    _cache_round(k, control).reshape(
                        n_pg, t_page, hkv, d).transpose(0, 2, 1, 3),
                    _cache_round(v, control).reshape(
                        n_pg, t_page, hkv, d).transpose(0, 2, 1, 3),
                    backend=backend)
                # the page before the chunk and the chunk's own keys, at
                # the cache's values, in runs of ``stride``
                ctx = jnp.concatenate(
                    [before.transpose(1, 0, 2),
                     _cache_round(k, control)], axis=0).astype(jnp.float32)
                kern = compress_keys(
                    ctx.reshape(-1, stride, hkv, d).mean(axis=1))
                kern = kern[KERNELS_PER_PAGE - 1:
                            KERNELS_PER_PAGE - 1 + n_k]
                kc = kc.at[ls, k_page, (jkc + 1) % KERNELS_PER_PAGE].set(
                    kern.astype(jnp.bfloat16), mode="drop")
                kcs = _kernel_order(
                    kc[ls][jnp.clip(table, 0, n_arena - 1)][None])

                def block(args, ls=ls, kcs=kcs, kv=kv):
                    qq, pp, vv = args
                    return _attend_positions(
                        qq, kv, ls, jnp.broadcast_to(table[None], (qs, mps)),
                        jnp.broadcast_to(kcs, (qs,) + kcs.shape[1:]), pp,
                        vv, cfg, backend)
                o, sel = jax.lax.map(block, (
                    q.reshape(c // qs, qs, hkv, g, d),
                    qpos.reshape(c // qs, qs), valid.reshape(c // qs, qs)))
                n_sel = n_sel + sel.reshape(c)
                h = _acc(control, h + rs * _gate_out(
                    p, x, o.reshape(c, hkv * g * d), cfg.attn_output_gate))
                ls += 1
            elif kind == ATTN:
                q, k, v = _qkv(p, x, cfg.n_heads, hkv, d, cfg)
                kv = cache_write(
                    kv, ls, page, jnp.zeros((n_pg,), jnp.int32),
                    _cache_round(k, control).reshape(
                        n_pg, t_page, hkv, d).transpose(0, 2, 1, 3),
                    _cache_round(v, control).reshape(
                        n_pg, t_page, hkv, d).transpose(0, 2, 1, 3),
                    backend=backend)

                def block(args, ls=ls, kv=kv):
                    qq, pp, vv = args
                    return _attend_all(
                        qq, kv, ls, jnp.broadcast_to(table[None], (qs, mps)),
                        pp, vv, backend)
                o = jax.lax.map(block, (
                    q.reshape(c // qs, qs, hkv, g, d),
                    qpos.reshape(c // qs, qs), valid.reshape(c // qs, qs)))
                h = _acc(control, h + rs * _mm(
                    o.reshape(c, hkv * g * d), p["wo"]))
                ls += 1
            elif kind == MAMBA:
                di, n_s, taps = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv
                xz = _mm(x, p["w_in"])
                # this layer's block of the sequence's state row: the
                # scan state, the convolution's tail, the tile's padding
                mine = state[row, ll]
                xc, tail = conv_chunk(
                    xz[:, :di], mine[n_s:n_s + taps - 1], p["conv_w"],
                    p["conv_b"], n_valid, round_state=round_state)
                delta, bm, cm = _ssm_inputs(p, xc, cfg)
                y, s_end = mamba_scan(
                    xc, delta, xz[:, di:], bm, cm, mine[:n_s], p["a_log"],
                    p["d"], n_valid, round_state=round_state,
                    backend=backend)
                state = state.at[row, ll].set(jnp.concatenate(
                    [s_end, tail, mine[n_s + taps - 1:]], axis=0))
                h = _acc(control, h + rs * _mm(y, p["w_out"]))
                ll += 1
            elif kind == MAMBA2:
                n_s = state_rows(cfg.ssm_heads, cfg.ssm_head_dim,
                                 cfg.ssm_state)
                n_t = tail_rows(cfg.ssd_channels, cfg.ssm_conv)
                z, xbc, dt = _ssd_in(p, x, cfg)
                # this layer's block of the sequence's state row: the
                # packed scan state, the convolution's tail, padding
                mine = state[row, ll]
                cc, tail = conv_chunk(
                    xbc, mine[n_s:n_s + n_t].reshape(cfg.ssm_conv - 1, -1),
                    p["conv_w"], p["conv_b"], n_valid,
                    round_state=round_state)
                xs, bm, cm = _ssd_split(cc, cfg)
                y, s_end = ssd_scan(
                    xs, jax.nn.softplus(dt + p["b_dt"]), bm, cm, mine[:n_s],
                    p["a_log"], n_valid, groups=cfg.ssm_groups,
                    chunk=cfg.ssm_chunk, round_state=round_state,
                    backend=backend)
                state = state.at[row, ll].set(jnp.concatenate(
                    [s_end, tail.reshape(n_t, -1), mine[n_s + n_t:]],
                    axis=0))
                h = _acc(control, h + rs * _ssd_out(p, y, xs, z, cfg))
                ll += 1
            elif kind == LINEAR:
                hl, dl = cfg.lin_heads, cfg.lin_head_dim
                q, k, v = _qkv(p, x, hl, hl, dl, cfg)
                if cfg.lin_rope:
                    q = _rope(q, qpos, cfg.rope_theta)
                    k = _rope(k, qpos, cfg.rope_theta)
                o, s_end = lightning_chunk(
                    q, k, v, state[row, ll], log_decays(hl), n_valid,
                    scale=1.0 / math.sqrt(dl), round_state=round_state)
                state = state.at[row, ll].set(s_end)
                if cfg.lin_output_norm:
                    o = _rms(o, p["o_norm"], cfg.rms_eps)
                h = _acc(control, h + rs * _gate_out(
                    p, x, o.reshape(c, hl * dl), cfg.lin_output_gate))
                ll += 1
            if ffn in (MOE, LATENT_MOE):
                h, hit, sizes = _moe(p, h, cfg, valid, ffn)
                h, n_held = _acc(control, h), n_held + hit[1]
                if kernel:
                    n_tile = n_tile + tile_visits(sizes).sum()
            elif ffn == DENSE:
                h = _acc(control, _mlp(p, h, cfg, rs))
        # blocks selected a sparse layer; a model with expert layers
        # adds the assignments that fell to the held experts (and, where
        # they take the ``expert_ffn`` kernel, the rows it multiplied)
        counts = n_sel.sum() / max(1, cfg.n_sparse)
        if cfg.n_moe:
            counts = [counts, n_held.astype(jnp.float32)]
            if kernel:
                counts.append((n_tile * ROW_TILE).astype(jnp.float32))
            counts = jnp.stack(counts)
        return (counts, _logits(params, h, cfg) if logits_out else None,
                kv, kc, state, latent)

    def named(fn, scope, *more_statics):
        @functools.wraps(fn)
        def scoped(*args, **kw):
            with jax.named_scope(scope), \
                    jax.default_matmul_precision("highest"):
                return fn(*args, **kw)
        scoped.__name__ = scoped.__qualname__ = scope.replace(".", "_")
        return jax.jit(scoped, static_argnames=(
            "cfg", "backend", "control", "logits_out") + more_statics,
            donate_argnames=("kv", "kc", "state", "latent"))
    return {"step": named(step, "runner.hybrid_step"),
            "prefill": named(prefill, "runner.hybrid_prefill", "max_pages")}


# ---------------------------------------------------------------------------
# the store and the runner
# ---------------------------------------------------------------------------

def layered_spec(cfg: TransformerConfig, state_rows: int):
    from brpc_tpu.kvcache.layered import LayeredSpec
    from brpc_tpu.ops import ssd
    from brpc_tpu.ops.mamba import state_block_rows
    recurrent = cfg.n_linear + cfg.n_mamba + cfg.n_mamba2
    if recurrent > max(cfg.n_linear, cfg.n_mamba, cfg.n_mamba2):
        raise ValueError("a state row holds one kind of recurrent layer: "
                         "lightning, Mamba or Mamba-2, no two of them")
    if cfg.n_mamba2:
        # a Mamba-2 layer's block is ops.ssd's: one lane tile wide
        ssm = (ssd.state_block_rows(cfg.ssm_heads, cfg.ssm_head_dim,
                                    cfg.ssm_groups, cfg.ssm_state,
                                    cfg.ssm_conv), ssd.LANES)
    elif cfg.n_mamba:
        ssm = (state_block_rows(cfg.ssm_state, cfg.ssm_conv), cfg.ssm_inner)
    else:
        ssm = (0, 0)
    return LayeredSpec(
        n_sparse=cfg.n_kv_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, n_linear=recurrent,
        n_lin_heads=cfg.lin_heads, lin_head_dim=cfg.lin_head_dim,
        state_rows=int(state_rows) if recurrent else 0,
        n_latent=cfg.n_latent, latent_dim=cfg.latent_dim,
        ssm_rows=ssm[0], ssm_channels=ssm[1],
        compressed=bool(cfg.n_sparse))


def make_layered_store(cfg: TransformerConfig, *, cache_pages: int,
                       state_rows: int = 0, page_tokens: int = 0,
                       device=None, name: str = "kv"):
    """A :class:`KVCacheStore` for a described architecture: page ids,
    refcounts and the radix tree as ever, ``cache_pages`` pages whose
    K/V (sparse- and full-attention layers), compressed keys, latent
    rows (latent-attention layers) and ``state_rows`` recurrent-state
    rows (lightning, Mamba or Mamba-2 layers) live in the store's
    :class:`LayeredCache`.  A page
    holds ``cfg.sparse_block`` tokens where the model selects blocks
    (one selection block a page), else ``page_tokens``.  The pool's own
    blocks hold the 4-byte token-id stand-in only."""
    from brpc_tpu.ici.block_pool import BlockPool
    from brpc_tpu.kvcache import KVCacheStore
    pt = cfg.sparse_block if cfg.n_sparse else int(page_tokens)
    if pt <= 0 or (page_tokens and pt != page_tokens):
        raise ValueError(
            f"page_tokens {page_tokens}: a model with sparse-attention "
            f"layers pages by its selection block ({cfg.sparse_block}), "
            f"any other states its page")
    if cfg.n_sparse and (cfg.sparse_kernel != 2 * cfg.sparse_stride
                         or pt != 4 * cfg.sparse_stride):
        raise ValueError(
            "the cache's compressed-key index needs kernel_size = 2 x "
            "kernel_stride and block_size = 4 x kernel_stride")
    per_block = 64
    blocks = -(-int(cache_pages) // per_block)
    pool = BlockPool(device, classes=(per_block * pt * 4,),
                     blocks_per_class=blocks)
    return KVCacheStore(pool, device, page_bytes=pt * 4, page_tokens=pt,
                        max_blocks=blocks, vector_kv=True,
                        layers=layered_spec(cfg, state_rows), name=name)


class HybridRunner(ModelRunner):
    """See the module docstring.  ``control`` is the benchmark's
    low-precision control (``"low"``) and otherwise empty."""

    wants_pages = True
    has_prefill = True
    feeds_tokens = True        # a step can take tokens from the one before
    chunked_prefill = True     # the engine cuts a long suffix to buckets
    kv_bytes_per_token = 0     # the engine writes no K/V rows for it

    def __init__(self, params: dict, cfg: TransformerConfig, *, store=None,
                 backend: Optional[str] = None, control: str = "",
                 name: str = "model"):
        from brpc_tpu.ici.mesh import ensure_compile_cache
        ensure_compile_cache()
        if not cfg.mixer_types:
            raise ValueError("HybridRunner serves a described "
                             "architecture (cfg.mixer_types)")
        self.cfg = cfg
        self.params = params
        self.name = name
        self.store = None
        self._backend = backend
        self._control = control
        self._mu = threading.Lock()
        self._fns = _programs()
        self._kernel_blocks = ffn_kernel_blocks(cfg, backend)
        self._table_cache: dict = {}  # seq id -> (table's key, arena indices)
        self._rows: list = []         # the last step's rows of it, a slot
        self._run = None              # (leader, keys shared a slot) of them
        self._no_prev: dict = {}      # slots -> zeros [3, S] on the device
        # prefill chunks dispatched and not counted yet: the engine
        # thread does not wait for a prefill to run (see prefill)
        self._uncounted: list = []
        safe = "".join(c if c.isalnum() else "_" for c in name)
        self.sparse_selected = Adder(f"runner_{safe}_sparse_selected_blocks")
        self.sparse_positions = Adder(f"runner_{safe}_sparse_positions")
        self.dense_positions = Adder(f"runner_{safe}_dense_positions")
        # grid steps of ``sparse_attend`` (PAGES_PER_STEP pages each): x 8
        # over the blocks selected, the pages stepped a page that is read
        self.sparse_steps = Adder(f"runner_{safe}_sparse_steps")
        self.lightning_tokens = Adder(f"runner_{safe}_lightning_tokens")
        # state-space layers: valid positions through ``mamba_scan`` (a
        # prefill chunk's) and slot-steps through ``mamba_step``
        self.mamba_tokens = Adder(f"runner_{safe}_mamba_tokens")
        self.mamba_steps = Adder(f"runner_{safe}_mamba_steps")
        names = ["sparse_selected_blocks", "sparse_positions",
                 "dense_positions", "sparse_steps", "lightning_tokens",
                 "mamba_tokens", "mamba_steps"]
        # expert layers: (token, expert) pairs routed, and the distinct
        # experts a layer a decode step hit, summed; latent layers: rows
        # a decode step attended to (a layer), and the distinct pages
        # they lie in, beside the pages the kernel fetched for them (a
        # layer, whole key blocks, by either pass) and how many of a
        # slot-by-slot pass's fetches the shared pass stood in for
        # Mamba-2 layers: valid positions through ``ssd_scan`` and
        # slot-steps through ``ssd_step``; ``moe_assignments_held``: the
        # pairs that fell to the experts held here (over
        # ``moe_assignments``: this chip's share of the routing);
        # ``moe_kernel_calls``: expert-block calls (a block a step, a
        # block a chunk) that went through the ``expert_ffn`` kernel, and
        # ``moe_tile_rows`` the rows it multiplied for them (visits x
        # ``ops.moe.ROW_TILE``; over the held assignments, 1 / the
        # tiles' fill)
        new = ("ssd_tokens", "ssd_steps") * bool(cfg.n_mamba2) \
            + ("moe_assignments", "moe_assignments_held",
               "moe_experts_hit", "moe_kernel_calls",
               "moe_tile_rows") * bool(cfg.n_moe) \
            + ("latent_tokens_read", "latent_pages_distinct",
               "latent_page_visits", "latent_page_visits_shared") \
            * bool(cfg.n_latent)
        for n in new:
            setattr(self, n, Adder(f"runner_{safe}_{n}"))
        self._bvar_names = [f"runner_{safe}_{n}" for n in names + list(new)]
        if store is not None:
            self.bind(store)

    def _statics(self) -> dict:
        return {"cfg": self.cfg, "backend": self._backend,
                "control": self._control}

    def bind(self, store) -> None:
        if store is None or getattr(store, "layers", None) is None:
            raise ValueError("HybridRunner needs a layered KVCacheStore "
                             "(make_layered_store)")
        with self._mu:
            if self.store is store:
                return
            if self.store is not None:
                raise ValueError("runner already bound to a store")
            if self.cfg.n_sparse \
                    and store.page_tokens != self.cfg.sparse_block:
                raise ValueError(
                    f"store pages hold {store.page_tokens} tokens, a "
                    f"selection block {self.cfg.sparse_block}")
            self.store = store

    # ---- helpers ----

    def _flat_tables(self, pages) -> np.ndarray:
        pages = np.asarray(pages, np.int32)
        flat = self.store.pagepool.flat_ids(pages.ravel().tolist())
        return np.asarray(flat, np.int32).reshape(pages.shape)

    def _slot_tables(self, pages, seqs) -> np.ndarray:
        """The step's page tables as arena indices.  A sequence's table
        only grows at its end while it decodes, so the translation of a
        slot's row is kept until the row's page count changes: 8 x 520
        lookups under the pool's lock every step were a millisecond of
        the engine thread, a step in 64 a page long is not."""
        pages = np.asarray(pages, np.int32)
        out = np.full(pages.shape, -1, np.int32)
        kept = self._table_cache
        live = {}
        for i, s in enumerate(seqs or ()):
            if s is None:
                continue
            # what a table changes by while it decodes: a page more, or
            # its tail page copied
            key = (len(s.pages), s.pages[-1].pid if s.pages else -1)
            hit = kept.get(s.seq_id)
            if hit is None or hit[0] != key:
                hit = (key, self._flat_tables(pages[i]))
            live[s.seq_id] = hit
            out[i] = hit[1]
        # the run the slots share is read off these rows (dispatch_step):
        # it stands while every slot holds the row it held
        rows = [live[s.seq_id][1] if s is not None else None
                for s in seqs or ()]
        if len(rows) != len(self._rows) or any(
                a is not b for a, b in zip(rows, self._rows)):
            self._rows, self._run = rows, None
        self._table_cache = live
        return out

    def _count(self, qpos, counts, dead: int, step: bool = False) -> None:
        """Counters of the positions a program just computed (``step``:
        the decode step, else a prefill chunk); ``counts`` what the
        program counted itself (blocks selected, then, of a model with
        expert layers, the assignments that fell to the held experts);
        ``dead`` the rows beside the positions that were not live (idle
        slots, a bucket's padding)."""
        cfg = self.cfg
        counts = np.atleast_1d(np.asarray(counts))
        n_sel = counts[0]
        if cfg.n_mamba:
            (self.mamba_steps if step else self.mamba_tokens).add(len(qpos))
        if cfg.n_mamba2:
            (self.ssd_steps if step else self.ssd_tokens).add(len(qpos))
        if cfg.n_sparse:
            from brpc_tpu.ops.sparse_attention import steps_visited
            dense = qpos + 1 <= cfg.sparse_dense_len
            n_dense = int(dense.sum())
            self.dense_positions.add(n_dense)
            self.sparse_positions.add(len(qpos) - n_dense)
            self.sparse_selected.add(float(n_sel))
            # a position's rows see its pages under ``dense_len`` and the
            # blocks it selects past it; a dead row is visited once
            pages = qpos // self.store.page_tokens + 1
            seen = np.where(dense, pages, np.minimum(pages, cfg.sparse_topk))
            self.sparse_steps.add((steps_visited(seen) + dead)
                                  * cfg.n_kv_heads * cfg.n_sparse)
        if cfg.n_linear:
            self.lightning_tokens.add(len(qpos))
        if cfg.n_moe:
            self.moe_assignments.add(
                len(qpos) * cfg.experts_per_tok * cfg.n_moe)
            self.moe_assignments_held.add(int(counts[1]))
            self.moe_kernel_calls.add(self._kernel_blocks)
            if len(counts) > 2:
                self.moe_tile_rows.add(int(counts[2]))

    # ---- the ModelRunner surface ----

    def prefill_cuts(self, seq) -> list:
        """Positions a prefill chunk must END at: where this sequence's
        state is to be snapshot."""
        b = self.store.snapshot_boundary(seq)
        return [b] if b else []

    def prefill(self, tokens, positions, pages, seq=None, n_valid=None,
                logits: bool = False):
        """One chunk: ``tokens`` bucket-padded, ``positions[0]`` its
        start (a whole number of pages), ``n_valid`` its real length.
        Never the prompt's last position: the first step computes
        it.  ``logits=True`` (tests) returns the chunk's logits."""
        import jax.numpy as jnp
        if seq is None:
            raise ValueError("HybridRunner.prefill needs the KVSeq")
        start = int(positions[0])
        limit = len(seq.tokens) - 1 - start
        n = limit if n_valid is None else min(int(n_valid), limit)
        if n <= 0:
            return None
        if start % self.store.page_tokens:
            raise ValueError("a prefill chunk starts at a page boundary")
        lay = self.store.layers
        table = self._flat_tables(pages)
        packed = np.concatenate([
            np.asarray([start, n, seq.state_row], np.int32), table,
            np.asarray(tokens, np.int32)])
        with lay.lock:
            n_sel, out, lay.kv, lay.kc, lay.state, lay.latent = \
                self._fns["prefill"](
                    self.params, lay.kv, lay.kc, lay.state,
                    jnp.asarray(packed), lay.latent,
                    logits_out=bool(logits), max_pages=len(table),
                    **self._statics())
        self.store.mark_filled(seq, start + n)
        # counted when the next step dispatched completes: the chunk has
        # run by then, and ``float(n_sel)`` here would hold the engine
        # thread until the device has caught up with everything queued
        self._uncounted.append((start + np.arange(n), n_sel, len(tokens) - n))
        if start + n == self.store.snapshot_boundary(seq):
            self.store.take_snapshot(seq, start + n)
        return out

    def dispatch_step(self, tokens, positions, pages, seqs=None, prev=None,
                      fed=None, logits: bool = False):
        """Dispatch one step and return its handle (for
        :meth:`complete_step`) without waiting for the device.  Where
        ``fed[i]``, slot ``i``'s token is not ``tokens[i]`` but the one
        the step ``prev`` (a handle, not yet completed perhaps) made for
        it, read on the device."""
        import jax
        import jax.numpy as jnp
        if fault.ENABLED and fault.hit(
                "model.step_compute", runner=self.name) is not None:
            raise RuntimeError("injected model step-compute failure")
        lay = self.store.layers
        n = len(tokens)
        head = 4 + 2 * bool(self.cfg.n_latent)
        packed = np.empty((n, head + np.shape(pages)[1]), np.int32)
        packed[:, 0], packed[:, 1] = tokens, positions
        packed[:, 2], packed[:, 3] = lay.scratch_row, 0
        for i, s in enumerate(seqs or ()):
            if s is not None and s.state_row is not None:
                packed[i, 2], packed[i, 3] = s.state_row, LIVE
        live = packed[:, 3] > 0
        if fed is not None:
            packed[live & np.asarray(fed, bool), 3] = FED
        tables = packed[:, head:] = self._slot_tables(pages, seqs)
        if self.cfg.n_latent:
            from brpc_tpu.ops.latent_attention import page_visits, shared_run
            t = self.store.page_tokens
            seen = np.where(live, np.maximum(packed[:, 1], 1), 0)
            # found again only when a table row changed: between those a
            # slot's ``seen`` only grows, so what it shares stays shared
            if self._run is None:
                self._run = shared_run(tables, seen, t)
            packed[:, 5], packed[:, 4] = self._run
        if prev is not None:
            before = prev["out"]
        else:
            before = self._no_prev.get(n)
            if before is None:
                # placed as a step's own result is (committed, beside the
                # cache): the same program whether a step came before
                before = self._no_prev[n] = jax.device_put(
                    np.zeros((3 + 2 * bool(self.cfg.n_moe), n), np.float32),
                    lay.kv.sharding)
        with lay.lock:
            out, lg, lay.kv, lay.kc, lay.state, lay.latent = \
                self._fns["step"](
                    self.params, lay.kv, lay.kc, lay.state,
                    jnp.asarray(packed), before, lay.latent,
                    logits_out=bool(logits), **self._statics())
        owed, self._uncounted = self._uncounted, []
        handle = {"out": out, "logits": lg, "live": live, "seqs": seqs,
                  "positions": np.asarray(positions), "prefills": owed}
        if self.cfg.n_latent:
            # the pages the live slots' rows lie in, each once however
            # many slots share it: what a step must read of the cache
            used = [tables[i, :-(-int(seen[i]) // t)]
                    for i in np.flatnonzero(live)]
            handle["latent_pages"] = len(np.unique(np.concatenate(used))) \
                if used else 0
            handle["latent_visits"] = page_visits(seen, packed[:, 4], t)
        return handle

    def complete_step(self, handle):
        """Fetch a dispatched step's result and book its positions
        (materialised, counted) and the prefill chunks dispatched before
        it: ``(next tokens, None, their log-probabilities)``."""
        out = np.asarray(handle["out"])
        live, positions = handle["live"], handle["positions"]
        for i, s in enumerate(handle["seqs"] or ()):
            if live[i] and not s.retired:
                self.store.mark_filled(s, int(positions[i]))
        for chunk in handle["prefills"]:
            self._count(*chunk)
        counts = [out[2][live].sum()]
        if self.cfg.n_moe:
            self.moe_experts_hit.add(int(out[3][0]))
            counts.append(out[4][0])
            if self._kernel_blocks and out.shape[1] > 1:
                counts.append(out[3][1])
        self._count(positions[live] - 1, counts,
                    len(live) - int(live.sum()), step=True)
        if self.cfg.n_latent:
            self.latent_tokens_read.add(
                int(positions[live].sum()) * self.cfg.n_latent)
            self.latent_pages_distinct.add(
                handle["latent_pages"] * self.cfg.n_latent)
            visits, stood_in = handle["latent_visits"]
            self.latent_page_visits.add(visits * self.cfg.n_latent)
            self.latent_page_visits_shared.add(stood_in * self.cfg.n_latent)
        return out[0].astype(np.int32), None, out[1]

    def step(self, tokens, positions, pages, seqs=None):
        nxt, rows, _ = self.complete_step(
            self.dispatch_step(tokens, positions, pages, seqs))
        return nxt, rows

    def step_logits(self, tokens, positions, pages, seqs=None):
        """The step's logits ``[slots, vocab]`` (it advances the cache
        as :meth:`step` does)."""
        handle = self.dispatch_step(tokens, positions, pages, seqs,
                                    logits=True)
        self.complete_step(handle)
        return handle["logits"]

    def verify(self, tokens, positions, tables, base_len, mask):
        raise NotImplementedError(
            "speculative verify needs a copy of the recurrent state a "
            "draft branch (ROADMAP R8)")

    def close(self) -> None:
        from brpc_tpu.bvar.variable import find_exposed
        for n in self._bvar_names:
            v = find_exposed(n)
            if v is not None:
                v.hide()

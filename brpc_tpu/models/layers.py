"""One record a layer kind: what a held layer of mixer kind K
(``TransformerConfig.mixer_types``) or feed-forward kind K
(``ffn_types``) draws, computes in each of the runner's two programs
(:mod:`brpc_tpu.models.hybrid`), keeps in which cache array of the
store's :class:`~brpc_tpu.kvcache.layered.LayeredCache` and counts, is
``MIXERS[K]`` or ``FFNS[K]`` here; no other module compares a kind's
name.  The mixers:

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
  ``mla``             latent attention (GLM-4.7-Flash): the cache holds
                      ``[c_kv; k_rope]`` a token, ONE row and nothing per
                      head; both programs attend in the absorbed form
                      (``W_UK`` folded into the query, ``W_UV`` applied
                      to the attended latents) over the latent pages
                      (``ops.latent_attention``); the decode step reads
                      the run of pages its slots hold in common once for
                      all of them
  ``mamba``           the Mamba-1 state-space mixer (Jamba): a causal
                      depthwise convolution and a selective scan
                      (``ops.mamba``), the step size, ``B`` and ``C``
                      each through a learned RMS norm; a sequence keeps
                      a float32 scan state ``[N, channels]`` and the
                      convolution's last inputs a layer, both in its
                      state row, restored from a snapshot on a radix hit
  ``mamba2``          the Mamba-2 (SSD) mixer (Nemotron 3): ONE
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
                      selection, no q/k norm or gate unless the family
                      states them; rotary (plain or YaRN-scaled,
                      :func:`rope_table`) where it states ``attn_rope``
  ``window``          sliding-window softmax attention (Mellum 2): the
                      ``attention`` kind's projections, each query sees
                      the last ``window`` positions, its own included;
                      the K/V live in the sequence's RING of the
                      layered cache (``kvcache.layered``: a few pages a
                      state row, logical block ``b`` at ring page ``b mod
                      R``), read by ``sparse_attend`` with a lower bound
                      under the name ``window_attend``; its rotary is the
                      window layers' own (``window_rope_theta``)
  ``none``            no mixer: the block is its feed-forward alone

and the feed-forwards: ``dense`` (the silu gated MLP), ``moe`` (a
float32 router over all experts, sigmoid scores with a correction bias
or, where ``router_scoring`` is ``softmax``, softmax scores and no bias;
the top ``k`` of score + bias, the chosen scores normalised and scaled,
the held experts' gated MLPs as ragged grouped matmuls with no capacity,
``ops.moe``, and the shared experts where ``n_shared_experts`` names
any), ``latent_moe`` (the same router; the
held experts two matrices around ``relu(.)^2`` in a LATENT width,
between one projection down and one up, on a TPU the ``expert_ffn``
kernel of ``ops.moe``; the shared expert of the same body in the
model's width) or ``none`` (the block is its mixer alone).  A block
with a ``none`` is ONE sublayer and has one norm.

A mixer's program functions take ``(p, x, i, c)``: the layer's
parameters, its normed rows, its index in its cache array and the
program's carry (the statics, the cache arrays, what the program
computed of its positions before the layers, what the layers count on
the device); they return the sublayer's output and leave the arrays they
write in ``c``.  A feed-forward's takes ``(p, h, c)`` and returns the
residual stream after it.  Each function imports the ``ops`` it calls.
"""
from __future__ import annotations

import functools
import math
import threading
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

SPARSE, LINEAR, MLA = "minicpm4", "lightning-attn", "mla"
MAMBA, MAMBA2, ATTN, NONE = "mamba", "mamba2", "attention", "none"
WINDOW = "window"
DENSE, MOE, LATENT_MOE = "dense", "moe", "latent_moe"
# what a record's shapes give in place of a fan-in for what is neither a
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
# (row, table entry) pairs of one call of the attention kernel where a
# row is a position: its tables and block indices ride scalar memory,
# two int32 a pair, and a v5e's is 1 MiB (a prefill block of 64
# positions x 4 K/V heads over tables of 816 pages asked for 1.96 MiB of
# it); past it a row is a block of positions
TABLE_ENTRIES = 32768


# ---------------------------------------------------------------------------
# the layer mathematics (traced inside the runner's two programs)
# ---------------------------------------------------------------------------

def _mm(x, w):
    """``x W`` at the stated precision.  A bfloat16 weight takes its
    input at bfloat16 and ONE pass, named so: the programs trace under
    ``default_matmul_precision("highest")`` (for their float32
    products), and a bfloat16 dot that inherits it is free to keep the
    float32 input the cast came from and multiply it in six passes
    (the compiler drops the cast as excess precision): slower, and not
    the values the configuration states (PERF.md section 6, PR 32)."""
    if w.dtype == jnp.bfloat16:
        out = jnp.dot(_to_bf16(x), w, preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.DEFAULT)
    else:
        out = jnp.dot(x, w, precision="highest")
    return _acc(getattr(_TRACING, "control", ""), out)


def _bmm(spec: str, x, w):
    """A batched product a head (``jnp.einsum``) at ``_mm``'s
    precision."""
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
    return jax.lax.reduce_precision(
        x, exponent_bits=8, mantissa_bits=7).astype(jnp.bfloat16)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _rope(x, pos, theta):
    """Rotary positions over the whole head, rotate-half convention:
    ``x [N, H, D]``, ``pos [N]``."""
    d = x.shape[-1]
    inv = jnp.exp(-math.log(theta)
                  * jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]    # [N, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


@functools.lru_cache(maxsize=None)
def rope_table(d: int, theta: float, yarn: tuple = ()) -> tuple:
    """``(inverse frequencies [d/2] float32, cos/sin factor)`` of rotary
    over a head of ``d`` at base ``theta``: ``theta^(-2i/d)``, or, where
    ``yarn`` = ``(factor, original positions, beta_fast, beta_slow,
    attention factor)``, YaRN's: dimension pairs below ``low`` keep their
    frequency, those above ``high`` are divided by ``factor``, a linear
    ramp between, with ``low = floor(d ln(original / (2 pi beta_fast)) /
    (2 ln theta))``, ``high`` the same of ``beta_slow`` rounded up; cos
    and sin are both multiplied by the attention factor.  Computed once
    (float64, then float32) a configuration and kind."""
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if not yarn:
        return inv.astype(np.float32), 1.0
    factor, original, fast, slow, attn = yarn

    def dim_of(rotations):
        return d * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(dim_of(fast)), 0)
    high = min(math.ceil(dim_of(slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (inv * ((1 - ramp) + ramp / factor)).astype(np.float32), \
        float(attn)


def _rotary(x, pos, table):
    """:func:`_rope` with a kind's ``(inverse frequencies, factor)``."""
    inv, factor = table
    d = x.shape[-1]
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _cache_round(x, control):
    """What the K/V pages hold of ``x``: bfloat16 values (under the
    low-precision control, the values of an int8 cache)."""
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
    if control != "low":
        return h
    return jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)


def moe_share(p, x, cfg, valid, ffn: str = MOE):
    """One expert layer's feed-forward (of kind ``ffn``) of the normed
    rows ``x [N, dm]``: ``(the held experts' share of the routed sum,
    the shared expert's output, group sizes [held] of this call)``.
    The router scores ALL ``cfg.n_experts`` (``cfg.router_scoring``);
    the share is of ``cfg.experts_held``; a model with no shared expert
    gives 0 for its output.  ``latent_moe``'s share is the held experts'
    sum in the latent width through the projection up: the shares of
    every holder add up to the whole layer's routed output."""
    from brpc_tpu.ops.moe import expert_ffn, route
    control = getattr(_TRACING, "control", "")
    if control == "low":
        logit = _acc(control, jnp.dot(
            _to_bf16(x), _to_bf16(p["router"]),
            preferred_element_type=jnp.float32))
    else:
        logit = jnp.dot(x, p["router"], precision="highest")
    if cfg.router_scoring == "softmax":
        # no correction bias: the choice is the scores' own top k
        idx, w = route(jax.nn.softmax(logit, axis=-1),
                       jnp.zeros((cfg.n_experts,), jnp.float32),
                       cfg.experts_per_tok, norm=cfg.norm_topk,
                       scale=cfg.routed_scale)
    else:
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
    if not cfg.n_shared_experts:
        return y, 0.0, sizes
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


def _mla_project(p, x, qpos, cfg, lanes: int, control):
    """Latent attention's two projections of the normed rows ``x [N,
    dm]`` at positions ``qpos``: ``(absorbed queries [N, H, lanes],
    scaled; the row the cache holds [N, lanes], at the cache's
    values)``; lanes past ``kv_lora_rank + rope`` are zero."""
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


def _qkv(p, x, heads, kv_heads, d, cfg):
    n = x.shape[0]
    q = _mm(x, p["wq"]).reshape(n, heads, d)
    k = _mm(x, p["wk"]).reshape(n, kv_heads, d)
    v = _mm(x, p["wv"]).reshape(n, kv_heads, d)
    if cfg.qk_norm:
        q = _rms(q, p["q_norm"], cfg.rms_eps)
        k = _rms(k, p["k_norm"], cfg.rms_eps)
    return q, k, v


def _project(p, x, c, kind):
    """An attention kind's ``q, k, v`` of the normed rows at positions
    ``c.qpos``, rotated where the family states rotary: a window layer
    by its own base, plain; any other by the model's, YaRN where it
    states it (and not under the ``plain_rope`` control)."""
    cfg = c.cfg
    q, k, v = _qkv(p, x, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg)
    if cfg.attn_rope:
        if kind == WINDOW:
            table = rope_table(cfg.head_dim, cfg.window_rope_theta)
        else:
            table = rope_table(cfg.head_dim, cfg.rope_theta,
                               () if c.control == "plain_rope"
                               else cfg.rope_yarn)
        q, k = _rotary(q, c.qpos, table), _rotary(k, c.qpos, table)
    return q, k, v


def _gate_out(p, x, o, gated):
    if gated:
        o = o * jax.nn.sigmoid(_mm(x, p["wg"]))
    return _mm(o, p["wo"])


def _ssm_inputs(p, xc, cfg):
    """The state-space mixer's per-token inputs from the convolved rows
    ``xc [N, channels]``: ``(delta [N, channels], B [N, n], C [N, n])``,
    the low-rank step size, ``B`` and ``C`` each through its learned
    RMS norm (the family's addition to Mamba-1), ``delta = softplus(W_dt
    dt + b_dt)``."""
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
    n, pages, per, hkv, d = kc_pages.shape
    flat = kc_pages.reshape(n, pages * per, hkv, d)
    return jnp.concatenate([flat[:, 1:], jnp.zeros_like(flat[:, :1])],
                           axis=1)


def _dense_tables(table, n_pages: int, width: int):
    """The dense branch as a page table: the sequence's first
    ``n_pages`` pages in order, padded to ``width``."""
    n = table.shape[0]
    have = min(n_pages, table.shape[1], width)
    pad = jnp.full((n, width - have), -1, jnp.int32)
    pages = jnp.concatenate([table[:, :have], pad], axis=1)
    blocks = jnp.where(pages >= 0, jnp.arange(width, dtype=jnp.int32), -1)
    return pages, blocks


def _attend_rows(q, kv, ls, pages, blocks, lengths, backend, starts=None,
                 name="sparse_attend"):
    """``q [N, Hkv, G, D]``, ``pages``/``blocks`` ``[N, Hkv, MP]``:
    one kernel row a (position, K/V head)."""
    from brpc_tpu.ops.sparse_attention import sparse_attend
    n, hkv, g, d = q.shape
    mp = pages.shape[-1]
    o = sparse_attend(
        q.reshape(n * hkv, g, d), kv, ls,
        jnp.tile(jnp.arange(hkv, dtype=jnp.int32), n),
        pages.reshape(n * hkv, mp), blocks.reshape(n * hkv, mp),
        jnp.repeat(lengths, hkv),
        starts=None if starts is None else jnp.repeat(starts, hkv),
        backend=backend, name=name)
    return o.reshape(n, hkv * g * d)


def window_pages(span: int, page_tokens: int) -> int:
    """The most pages ``span`` consecutive positions can lie in: a
    decode query's window (``span`` = the window), or a prefill block's
    windows together (the window + the block - 1)."""
    return -(-(span - 1) // page_tokens) + 1


def ring_pages(window: int, page_tokens: int, chunk_tokens: int) -> int:
    """R, the fewest pages a window layer's ring a sequence holds: the
    pages behind a prefill chunk's first query (its window less its own
    page) beside every page the chunk writes before its queries read."""
    return window_pages(window, page_tokens) - 1 \
        + -(-chunk_tokens // page_tokens)


def _window_table(first_pos, span: int, rows, live, r: int, t: int, cfg,
                  control):
    """The ring's table of the windows of ``span`` consecutive positions
    from ``first_pos [N]`` of the sequences in state rows ``rows [N]``:
    ``(pages, logical blocks) [N, width]`` (logical block ``b`` of row
    ``rr`` at ring page ``rr R + b mod R``, R = ``r``; -1 where there is
    none or the span is not ``live``) and the first position's lower
    bound ``[N]``.  Under the ``unbounded`` control the table is every
    page of the ring as the sequence's latest blocks, and no bound."""
    last = (first_pos + span - 1) // t
    if control == "unbounded":
        width, first, starts = r, jnp.maximum(last - (r - 1), 0), None
    else:
        width = window_pages(cfg.window + span - 1, t)
        first = jnp.maximum(first_pos - (cfg.window - 1), 0) // t
        starts = first_pos - (cfg.window - 1)
    b = first[:, None] + jnp.arange(width, dtype=jnp.int32)[None, :]
    ok = live[:, None] & (b <= last[:, None])
    return (jnp.where(ok, rows[:, None] * r + b % r, -1),
            jnp.where(ok, b, -1), starts)


def _attend_window(q4, ring, ls, r, rows, qpos, live, cfg, control,
                   backend):
    """The ``window`` mixer's decode attention for N positions of
    sequences whose state rows are ``rows [N]``: keys ``qpos - window <
    j <= qpos``, from the ring pages they lie in.  Keys are read from
    the ring only: write before you attend.  ``r`` is the ring's pages a
    row."""
    n, hkv = q4.shape[0], q4.shape[1]
    pages, blocks, starts = _window_table(qpos, 1, rows, live, r,
                                          ring.shape[4], cfg, control)
    width = pages.shape[1]
    return _attend_rows(
        q4, ring, ls, jnp.broadcast_to(pages[:, None, :], (n, hkv, width)),
        jnp.broadcast_to(blocks[:, None, :], (n, hkv, width)),
        jnp.where(live, qpos + 1, 0), backend, starts, "window_attend")


def _attend_block(q4, kv, ls, pages, blocks, first_pos, backend,
                  starts=None, name="sparse_attend"):
    """Attention of a block of consecutive positions ``q4 [B, Hkv, G,
    D]`` from ``first_pos`` under ONE table ``pages`` / ``blocks``
    ``[MP]``: a kernel row a K/V head, its ``B x G`` query rows the
    block's positions (``sparse_attend``'s ``heads_per_pos``), so that a
    page is read once a block and not once a position; keys ``< t + 1``
    (and ``>= starts + t - first_pos``) of position ``t``.  Returns ``[B,
    Hkv G D]``."""
    from brpc_tpu.ops.sparse_attention import sparse_attend
    b, hkv, g, d = q4.shape
    mp = pages.shape[0]
    rows = jnp.full((hkv,), 1, jnp.int32)
    o = sparse_attend(
        q4.transpose(1, 0, 2, 3).reshape(hkv, b * g, d), kv, ls,
        jnp.arange(hkv, dtype=jnp.int32),
        jnp.broadcast_to(pages[None], (hkv, mp)),
        jnp.broadcast_to(blocks[None], (hkv, mp)), rows * (first_pos + 1),
        starts=None if starts is None else rows * starts, heads_per_pos=g,
        backend=backend, name=name)
    return o.reshape(hkv, b, g, d).transpose(1, 0, 2, 3).reshape(b, -1)


def _attend_all(q4, kv, ls, tables, qpos, live, backend):
    """The ``attention`` mixer's attention for N positions: every page
    of the sequence's table ``tables [N, MPs]`` IS the position's page
    table, keys at positions ``<= qpos`` take part.  Keys are read from
    the arena only: write before you attend.  A row that is not live
    names no page (see :func:`_attend_positions`)."""
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


# ---------------------------------------------------------------------------
# the kinds: parameters, the decode step's and a prefill chunk's layer
# ---------------------------------------------------------------------------

def _sparse_shapes(cfg) -> dict:
    dm, d = cfg.d_model, cfg.head_dim
    hd, kvd = cfg.n_heads * d, cfg.n_kv_heads * d
    return dict(wq=((dm, hd), dm), wk=((dm, kvd), dm), wv=((dm, kvd), dm),
                wg=((dm, hd), dm), wo=((hd, dm), hd), q_norm=((d,), None),
                k_norm=((d,), None))


def _attn_shapes(cfg) -> dict:
    dm = cfg.d_model
    hd, kvd = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    return dict(wq=((dm, hd), dm), wk=((dm, kvd), dm), wv=((dm, kvd), dm),
                wo=((hd, dm), hd))


def _attn_step(p, x, i, c, kind=ATTN):
    """The attention kinds' projections, and the decode step's write of
    each slot's K/V into its page: the queries ``[S, H, D]``."""
    from brpc_tpu.ops.sparse_attention import cache_write
    q, k, v = _project(p, x, c, kind)
    c.kv = cache_write(c.kv, i, c.page, c.qpos % c.t_page,
                       _cache_round(k, c.control)[:, :, None],
                       _cache_round(v, c.control)[:, :, None],
                       backend=c.backend)
    return q


def _chunk_write(arr, i, pages, c, k, v):
    """The attention kinds' write of a prefill chunk's K/V into ``arr``
    (the K/V pages or the window layers' ring) at ``pages [chunk
    pages]``: a page is written whole where it holds a valid position
    (what lies behind the last valid one is masked by every reader's
    length and overwritten by the positions that come)."""
    from brpc_tpu.ops.sparse_attention import cache_write
    n_pg, t, hkv, d = c.n_pg, c.t_page, c.cfg.n_kv_heads, c.cfg.head_dim
    return cache_write(
        arr, i, pages, jnp.zeros((n_pg,), jnp.int32),
        _cache_round(k, c.control).reshape(
            n_pg, t, hkv, d).transpose(0, 2, 1, 3),
        _cache_round(v, c.control).reshape(
            n_pg, t, hkv, d).transpose(0, 2, 1, 3),
        backend=c.backend)


def _blockwise(attend, q, c):
    """``attend((queries, positions, live))`` over a prefill chunk in
    blocks of at most 64 positions (a call of the kernel each)."""
    n, hkv, qs = q.shape[0], c.cfg.n_kv_heads, min(q.shape[0], 64)
    return jax.lax.map(attend, (
        q.reshape(n // qs, qs, hkv, c.cfg.n_heads // hkv, c.cfg.head_dim),
        c.qpos.reshape(n // qs, qs), c.live.reshape(n // qs, qs)))


def _sparse_decode(p, x, i, c):
    from brpc_tpu.ops.sparse_attention import KERNELS_PER_PAGE, page_keys
    cfg = c.cfg
    s_n, hkv = x.shape[0], cfg.n_kv_heads
    q = _attn_step(p, x, i, c, SPARSE)
    # the last 2 x stride keys, from the (one or two) pages they lie in,
    # as the cache now holds them
    two = page_keys(c.kv, i, jnp.concatenate([c.page_a, c.page]),
                    backend=c.backend).astype(jnp.float32)
    two = jnp.concatenate([two[:s_n], two[s_n:]], axis=2)
    idx = c.win_at[:, None] + jnp.arange(2 * c.stride)[None, :]
    mean = jnp.take_along_axis(
        two, idx[:, None, :, None], axis=2).mean(axis=2)
    c.kc = c.kc.at[i, jnp.where(c.done, c.page, c.n_arena),
                   (c.jc + 1) % KERNELS_PER_PAGE].set(
        mean.astype(jnp.bfloat16), mode="drop")
    kcs = _kernel_order(c.kc[i][jnp.clip(c.tables, 0, c.n_arena - 1)])
    o, sel = _attend_positions(
        q.reshape(s_n, hkv, cfg.n_heads // hkv, cfg.head_dim), c.kv, i,
        c.tables, kcs, c.qpos, c.live, cfg, c.backend)
    c.n_sel = c.n_sel + sel
    return _gate_out(p, x, o, cfg.attn_output_gate)


def _sparse_prefill(p, x, i, c):
    from brpc_tpu.ops.sparse_attention import (KERNELS_PER_PAGE,
                                               compress_keys, page_keys)
    cfg = c.cfg
    n, hkv, d = x.shape[0], cfg.n_kv_heads, cfg.head_dim
    q, k, v = _qkv(p, x, cfg.n_heads, hkv, d, cfg)
    before = page_keys(c.kv, i, c.page_before[None],
                       backend=c.backend)[0]                 # [Hkv,T,D]
    c.kv = _chunk_write(c.kv, i, c.page, c, k, v)
    # the page before the chunk and the chunk's own keys, at the cache's
    # values, in runs of ``stride``
    ctx = jnp.concatenate(
        [before.transpose(1, 0, 2),
         _cache_round(k, c.control)], axis=0).astype(jnp.float32)
    kern = compress_keys(ctx.reshape(-1, c.stride, hkv, d).mean(axis=1))
    kern = kern[KERNELS_PER_PAGE - 1:KERNELS_PER_PAGE - 1 + c.n_k]
    c.kc = c.kc.at[i, c.k_page, (c.jkc + 1) % KERNELS_PER_PAGE].set(
        kern.astype(jnp.bfloat16), mode="drop")
    kcs = _kernel_order(
        c.kc[i][jnp.clip(c.table, 0, c.n_arena - 1)][None])
    kv, table = c.kv, c.table

    def block(args):
        qq, pp, vv = args
        qs = pp.shape[0]
        return _attend_positions(
            qq, kv, i, jnp.broadcast_to(table[None], (qs,) + table.shape),
            jnp.broadcast_to(kcs, (qs,) + kcs.shape[1:]), pp, vv, cfg,
            c.backend)
    o, sel = _blockwise(block, q, c)
    c.n_sel = c.n_sel + sel.reshape(n)
    return _gate_out(p, x, o.reshape(n, cfg.n_heads * d),
                     cfg.attn_output_gate)


def _attn_decode(p, x, i, c):
    cfg = c.cfg
    hkv = cfg.n_kv_heads
    q = _attn_step(p, x, i, c)
    o = _attend_all(q.reshape(x.shape[0], hkv, cfg.n_heads // hkv,
                              cfg.head_dim), c.kv, i, c.tables, c.qpos,
                    c.live, c.backend)
    return _mm(o, p["wo"])


def _attn_prefill(p, x, i, c):
    cfg = c.cfg
    q, k, v = _project(p, x, c, ATTN)
    c.kv = _chunk_write(c.kv, i, c.page, c, k, v)
    kv, table = c.kv, c.table
    mps = table.shape[0]
    if min(x.shape[0], 64) * cfg.n_kv_heads * mps <= TABLE_ENTRIES:
        def block(args):
            qq, pp, vv = args
            return _attend_all(
                qq, kv, i, jnp.broadcast_to(table[None], (pp.shape[0], mps)),
                pp, vv, c.backend)
    else:
        # a long table: a row a position would re-read every page of it
        # once a position, and its tables outgrow scalar memory; a row a
        # block of positions reads each page once
        blocks = jnp.where(table >= 0, jnp.arange(mps, dtype=jnp.int32), -1)

        def block(args):
            qq, pp, vv = args
            return _attend_block(qq, kv, i, jnp.where(vv.any(), table, -1),
                                 blocks, pp[0], c.backend)
    o = _blockwise(block, q, c)
    return _mm(o.reshape(x.shape[0], cfg.n_heads * cfg.head_dim), p["wo"])


def _ring_size(c) -> int:
    """Pages a state row holds of the window layers' ring (static: the
    ring's pages over the state array's rows)."""
    return c.ring.shape[3] // c.state.shape[0]


def _window_decode(p, x, i, c):
    """Each slot's K/V into its ring (block ``qpos // T``'s ring page),
    then its query over the window."""
    from brpc_tpu.ops.sparse_attention import cache_write
    cfg = c.cfg
    r = _ring_size(c)
    q, k, v = _project(p, x, c, WINDOW)
    page = jnp.where(c.live, c.rows * r + (c.qpos // c.t_page) % r,
                     c.ring.shape[3])
    c.ring = cache_write(c.ring, i, page, c.qpos % c.t_page,
                         _cache_round(k, c.control)[:, :, None],
                         _cache_round(v, c.control)[:, :, None],
                         backend=c.backend)
    hkv = cfg.n_kv_heads
    o = _attend_window(q.reshape(x.shape[0], hkv, cfg.n_heads // hkv,
                                 cfg.head_dim), c.ring, i, r, c.rows,
                       c.qpos, c.live, cfg, c.control, c.backend)
    return _mm(o, p["wo"])


def _window_prefill(p, x, i, c):
    """The chunk's pages into the sequence's ring, then its queries over
    their windows: the ring holds the window before the chunk's first
    position and every page the chunk writes at once."""
    cfg = c.cfg
    n, r = x.shape[0], _ring_size(c)
    if ring_pages(cfg.window, c.t_page, n) > r:
        raise ValueError(
            f"a prefill chunk of {c.n_pg} pages overruns a window ring of "
            f"{r} pages (make_layered_store's chunk_tokens)")
    q, k, v = _project(p, x, c, WINDOW)
    block = c.qpos[0] // c.t_page + jnp.arange(c.n_pg, dtype=jnp.int32)
    page = jnp.where(jnp.arange(c.n_pg) * c.t_page < c.n_valid,
                     c.row * r + block % r, c.ring.shape[3])
    c.ring = _chunk_write(c.ring, i, page, c, k, v)
    ring = c.ring

    def attend(args):
        qq, pp, vv = args
        pages, blocks, starts = _window_table(
            pp[:1], pp.shape[0], c.row[None], vv.any()[None], r, c.t_page,
            cfg, c.control)
        return _attend_block(qq, ring, i, pages[0], blocks[0], pp[0],
                             c.backend, None if starts is None else starts[0],
                             "window_attend")
    o = _blockwise(attend, q, c)
    return _mm(o.reshape(n, cfg.n_heads * cfg.head_dim), p["wo"])


def _mla_shapes(cfg) -> dict:
    dm, h, r, ql = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank, cfg.q_lora_rank
    nope, rope, v = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    # kv_b is held as its two factors a head, head-major: the absorbed
    # form contracts each over its own minor dimensions
    return dict(wq_a=((dm, ql), dm), q_norm=((ql,), None),
                wq_b=((ql, h * (nope + rope)), ql),
                wkv_a=((dm, r + rope), dm), kv_norm=((r,), None),
                w_uk=((h, nope, r), r), w_uv=((h, r, v), r),
                wo=((h * v, dm), h * v))


def _mla_decode(p, x, i, c):
    from brpc_tpu.ops.latent_attention import latent_attend_slots, latent_write
    qq, row = _mla_project(p, x, c.qpos, c.cfg, c.latent.shape[-1],
                           c.control)
    c.latent = latent_write(c.latent, i, c.page, c.qpos % c.t_page,
                            row[:, None, :], backend=c.backend)
    o = latent_attend_slots(
        qq, jnp.where(c.live, c.qpos + 1, 0), c.packed[:, 4],
        c.packed[:1, 5], c.latent, i, c.tables, backend=c.backend)
    return _mla_out(p, o, c.cfg)


def _mla_prefill(p, x, i, c):
    from brpc_tpu.ops.latent_attention import latent_attend, latent_write
    n, nh = x.shape[0], c.cfg.n_heads
    # the kernel's grid rows: blocks of positions, every head of a block
    # a row of ONE matmul against the page
    pr = PREFILL_ROWS if n % PREFILL_ROWS == 0 else 1
    qq, row = _mla_project(p, x, c.qpos, c.cfg, c.latent.shape[-1],
                           c.control)
    c.latent = latent_write(
        c.latent, i, c.page, jnp.zeros((c.n_pg,), jnp.int32),
        row.reshape(c.n_pg, c.t_page, -1), backend=c.backend)
    seen = jnp.where(c.live, c.qpos + 1, 0)
    o = latent_attend(
        qq.reshape(n // pr, pr * nh, -1),
        jnp.repeat(seen, nh).reshape(n // pr, pr * nh, 1),
        c.latent, i, jnp.zeros((n // pr,), jnp.int32), c.table[None],
        backend=c.backend)
    return _mla_out(p, o.reshape(n, nh, -1), c.cfg)


def _lightning_shapes(cfg) -> dict:
    dm = cfg.d_model
    hd, d = cfg.lin_heads * cfg.lin_head_dim, cfg.lin_head_dim
    return dict(wq=((dm, hd), dm), wk=((dm, hd), dm), wv=((dm, hd), dm),
                wg=((dm, hd), dm), wo=((hd, dm), hd), q_norm=((d,), None),
                k_norm=((d,), None), o_norm=((d,), None))


def _lightning_in(p, x, c):
    cfg = c.cfg
    hl = cfg.lin_heads
    q, k, v = _qkv(p, x, hl, hl, cfg.lin_head_dim, cfg)
    if cfg.lin_rope:
        q = _rope(q, c.qpos, cfg.rope_theta)
        k = _rope(k, c.qpos, cfg.rope_theta)
    return q, k, v


def _lightning_out(p, x, o, cfg):
    if cfg.lin_output_norm:
        o = _rms(o, p["o_norm"], cfg.rms_eps)
    return _gate_out(p, x, o.reshape(x.shape[0],
                                     cfg.lin_heads * cfg.lin_head_dim),
                     cfg.lin_output_gate)


def _lightning_decode(p, x, i, c):
    from brpc_tpu.ops.lightning import lightning_decode, log_decays
    q, k, v = _lightning_in(p, x, c)
    o, c.state = lightning_decode(
        c.state, c.rows, i, q, k, v, log_decays(c.cfg.lin_heads),
        scale=1.0 / math.sqrt(c.cfg.lin_head_dim),
        round_state=c.round_state, backend=c.backend)
    return _lightning_out(p, x, o, c.cfg)


def _lightning_prefill(p, x, i, c):
    from brpc_tpu.ops.lightning import lightning_chunk, log_decays
    q, k, v = _lightning_in(p, x, c)
    o, s_end = lightning_chunk(
        q, k, v, c.state[c.row, i], log_decays(c.cfg.lin_heads),
        c.n_valid, scale=1.0 / math.sqrt(c.cfg.lin_head_dim),
        round_state=c.round_state)
    c.state = c.state.at[c.row, i].set(s_end)
    return _lightning_out(p, x, o, c.cfg)


def _mamba_shapes(cfg) -> dict:
    dm, di, n, r, k = (cfg.d_model, cfg.ssm_inner, cfg.ssm_state,
                       cfg.ssm_dt_rank, cfg.ssm_conv)
    # what is per (state value, channel) or per (tap, channel) lies
    # channels minor, as the state row does
    return dict(w_in=((dm, 2 * di), dm), conv_w=((k, di), ROUTER),
                conv_b=((di,), BIAS), w_x=((di, r + 2 * n), di),
                dt_norm=((r,), None), b_norm=((n,), None),
                c_norm=((n,), None), w_dt=((r, di), r),
                b_dt=((di,), DT_BIAS), a_log=((n, di), A_LOG),
                d=((di,), ONES), w_out=((di, dm), di))


def _mamba_block(cfg) -> tuple:
    from brpc_tpu.ops.mamba import state_block_rows
    return state_block_rows(cfg.ssm_state, cfg.ssm_conv), cfg.ssm_inner


def _mamba_decode(p, x, i, c):
    from brpc_tpu.ops.mamba import conv_step, mamba_step
    cfg = c.cfg
    di = cfg.ssm_inner
    xz = _mm(x, p["w_in"])
    xc, c.state = conv_step(
        c.state, c.rows, i, xz[:, :di], p["conv_w"], p["conv_b"],
        d_state=cfg.ssm_state, round_state=c.round_state,
        backend=c.backend)
    delta, bm, cm = _ssm_inputs(p, xc, cfg)
    y, c.state = mamba_step(
        c.state, c.rows, i, xc, delta, xz[:, di:], bm, cm, p["a_log"],
        p["d"], round_state=c.round_state, backend=c.backend)
    return _mm(y, p["w_out"])


def _mamba_prefill(p, x, i, c):
    from brpc_tpu.ops.mamba import conv_chunk, mamba_scan
    cfg = c.cfg
    di, n_s, taps = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_conv
    xz = _mm(x, p["w_in"])
    # this layer's block of the sequence's state row: the scan state, the
    # convolution's tail, the tile's padding
    mine = c.state[c.row, i]
    xc, tail = conv_chunk(
        xz[:, :di], mine[n_s:n_s + taps - 1], p["conv_w"], p["conv_b"],
        c.n_valid, round_state=c.round_state)
    delta, bm, cm = _ssm_inputs(p, xc, cfg)
    y, s_end = mamba_scan(
        xc, delta, xz[:, di:], bm, cm, mine[:n_s], p["a_log"], p["d"],
        c.n_valid, round_state=c.round_state, backend=c.backend)
    c.state = c.state.at[c.row, i].set(jnp.concatenate(
        [s_end, tail, mine[n_s + taps - 1:]], axis=0))
    return _mm(y, p["w_out"])


def _ssd_shapes(cfg) -> dict:
    dm, h, di, ch = cfg.d_model, cfg.ssm_heads, cfg.ssd_inner, cfg.ssd_channels
    # one projection: the gate, the convolved channels, a step size a head
    return dict(w_in=((dm, di + ch + h), dm),
                conv_w=((cfg.ssm_conv, ch), ROUTER), conv_b=((ch,), BIAS),
                b_dt=((h,), DT_BIAS), a_log=((h,), A_LOG_U), d=((h,), ONES),
                g_norm=((di,), None), w_out=((di, dm), di))


def _ssd_block(cfg) -> tuple:
    # a Mamba-2 layer's block is ops.ssd's: one lane tile wide
    from brpc_tpu.ops import ssd
    return (ssd.state_block_rows(cfg.ssm_heads, cfg.ssm_head_dim,
                                 cfg.ssm_groups, cfg.ssm_state, cfg.ssm_conv),
            ssd.LANES)


def _ssd_decode(p, x, i, c):
    from brpc_tpu.ops.ssd import conv_step, ssd_step, state_rows
    cfg = c.cfg
    z, xbc, dt = _ssd_in(p, x, cfg)
    cc, c.state = conv_step(
        c.state, c.rows, i, xbc, p["conv_w"], p["conv_b"],
        n_state=state_rows(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
        round_state=c.round_state, backend=c.backend)
    xs, bm, cm = _ssd_split(cc, cfg)
    y, c.state = ssd_step(
        c.state, c.rows, i, xs, jax.nn.softplus(dt + p["b_dt"]), bm, cm,
        p["a_log"], groups=cfg.ssm_groups, round_state=c.round_state,
        backend=c.backend)
    return _ssd_out(p, y, xs, z, cfg)


def _ssd_prefill(p, x, i, c):
    from brpc_tpu.ops.mamba import conv_chunk
    from brpc_tpu.ops.ssd import ssd_scan, state_rows, tail_rows
    cfg = c.cfg
    n_s = state_rows(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
    n_t = tail_rows(cfg.ssd_channels, cfg.ssm_conv)
    z, xbc, dt = _ssd_in(p, x, cfg)
    # this layer's block of the sequence's state row: the packed scan
    # state, the convolution's tail, padding
    mine = c.state[c.row, i]
    cc, tail = conv_chunk(
        xbc, mine[n_s:n_s + n_t].reshape(cfg.ssm_conv - 1, -1),
        p["conv_w"], p["conv_b"], c.n_valid, round_state=c.round_state)
    xs, bm, cm = _ssd_split(cc, cfg)
    y, s_end = ssd_scan(
        xs, jax.nn.softplus(dt + p["b_dt"]), bm, cm, mine[:n_s],
        p["a_log"], c.n_valid, groups=cfg.ssm_groups, chunk=cfg.ssm_chunk,
        round_state=c.round_state, backend=c.backend)
    c.state = c.state.at[c.row, i].set(jnp.concatenate(
        [s_end, tail.reshape(n_t, -1), mine[n_s + n_t:]], axis=0))
    return _ssd_out(p, y, xs, z, cfg)


def _dense_shapes(cfg) -> dict:
    dm, ff = cfg.d_model, cfg.d_ff
    return dict(w_gate=((dm, ff), dm), w_up=((dm, ff), dm),
                w_down=((ff, dm), ff))


def _moe_shapes(cfg) -> dict:
    dm, n, fe = cfg.d_model, cfg.experts_held[1], cfg.moe_d_ff
    fs = fe * cfg.n_shared_experts
    # a softmax router has no correction bias, and a model with no
    # shared expert draws none
    out = dict(router=((dm, cfg.n_experts), ROUTER))
    if cfg.router_scoring != "softmax":
        out["router_bias"] = ((cfg.n_experts,), BIAS)
    out.update(we_gate=((n, dm, fe), dm), we_up=((n, dm, fe), dm),
               we_down=((n, fe, dm), fe))
    if fs:
        out.update(ws_gate=((dm, fs), dm), ws_up=((dm, fs), dm),
                   ws_down=((fs, dm), fs))
    return out


def _latent_moe_shapes(cfg) -> dict:
    dm, n, fe, lat, fs = (cfg.d_model, cfg.experts_held[1], cfg.moe_d_ff,
                          cfg.moe_latent, cfg.shared_d_ff)
    return dict(router=((dm, cfg.n_experts), ROUTER),
                router_bias=((cfg.n_experts,), SMALL_BIAS),
                w_lat_in=((dm, lat), dm), we_up=((n, lat, fe), lat),
                we_down=((n, fe, lat), fe), w_lat_out=((lat, dm), lat),
                ws_up=((dm, fs), dm), ws_down=((fs, dm), fs))


def _dense_apply(p, h, c):
    x = _rms(h, p["norm2"], c.cfg.rms_eps)
    return _acc(c.control, h + c.rs * _mm(jax.nn.silu(_mm(x, p["w_gate"]))
                                          * _mm(x, p["w_up"]), p["w_down"]))


def _routed_apply(p, h, c, ffn):
    """``h`` + the routed share + the shared expert; the program keeps
    ``c.hits`` of ``[the held experts this call hit, the assignments
    that fell to them]``, and the ``expert_ffn`` kernel's row tiles."""
    from brpc_tpu.ops.moe import tile_visits
    y, shared, sizes = moe_share(p, _rms(h, p["norm2"], c.cfg.rms_eps),
                                 c.cfg, c.live, ffn)
    h, hit = h + y + shared, jnp.stack([(sizes > 0).sum(), sizes.sum()])
    h, c.n_moe = _acc(c.control, h), c.n_moe + c.hits(hit)
    if c.kernel:
        c.n_tile = c.n_tile + tile_visits(sizes).sum()
    return h


def _named(*names) -> tuple:
    return tuple((n, n) for n in names)


def _count_sparse(r, qpos, counts, dead, step):
    from brpc_tpu.ops.sparse_attention import steps_visited
    cfg = r.cfg
    dense = qpos + 1 <= cfg.sparse_dense_len
    n_dense = int(dense.sum())
    r.dense_positions.add(n_dense)
    r.sparse_positions.add(len(qpos) - n_dense)
    r.sparse_selected.add(float(counts[0]))
    # a position's rows see its pages under ``dense_len`` and the blocks
    # it selects past it; a dead row is visited once
    pages = qpos // r.store.page_tokens + 1
    seen = np.where(dense, pages, np.minimum(pages, cfg.sparse_topk))
    r.sparse_steps.add((steps_visited(seen) + dead)
                       * cfg.n_kv_heads * cfg.n_sparse)


def _positions(prefilled: str, stepped: str):
    """A count of positions: a prefill chunk's valid ones into the
    counter ``prefilled``, a decode step's live slots into ``stepped``."""
    def count(r, qpos, counts, dead, step):
        getattr(r, stepped if step else prefilled).add(len(qpos))
    return count


def _count_window(r, qpos, counts, dead, step):
    """A decode step's window and full layers: the ring pages a window
    layer's queries visited (their table's entries that name a block),
    the positions, and the K/V pages a full layer's queries read."""
    if step is None:
        return
    cfg, t = r.cfg, r.store.page_tokens
    first = np.maximum(qpos - (cfg.window - 1), 0) // t
    r.window_pages_read.add(int((qpos // t - first + 1).sum())
                            * cfg.n_window)
    r.window_positions.add(len(qpos) * cfg.n_window)
    r.full_pages_read.add(int((qpos // t + 1).sum()) * cfg.n_attention)


def _count_latent(r, qpos, counts, dead, step):
    if step is None:
        return
    handle, n = step[0], r.cfg.n_latent
    r.latent_tokens_read.add(
        int(handle["positions"][handle["live"]].sum()) * n)
    r.latent_pages_distinct.add(handle["latent_pages"] * n)
    visits, stood_in = handle["latent_visits"]
    r.latent_page_visits.add(visits * n)
    r.latent_page_visits_shared.add(stood_in * n)


def _count_routed(r, qpos, counts, dead, step):
    cfg = r.cfg
    if step is not None:
        r.moe_experts_hit.add(int(step[1][3][0]))
    r.moe_assignments.add(len(qpos) * cfg.experts_per_tok * cfg.n_moe)
    r.moe_assignments_held.add(int(counts[1]))
    r.moe_kernel_calls.add(r._kernel_blocks)
    if len(counts) > 2:
        r.moe_tile_rows.add(int(counts[2]))


@dataclass(frozen=True)
class Mixer:
    """A mixer kind.  ``decode`` / ``prefill`` are None where the block
    has no mixer (nor its norm); ``store`` is the cache array its layers
    index (``"kv"``, ``"latent"``, ``"state"``, ``"ring"``),
    ``state_block`` the shape of its layer's block of a state row.  ``count(runner, qpos,
    counts, dead, step)`` adds a program's positions ``qpos`` to the
    runner's ``counters`` ((attribute, bvar suffix) pairs): ``counts``
    what the program counted itself (blocks selected, then, of a model
    with expert layers, the assignments that fell to the held experts
    and the kernel's rows), ``dead`` the rows beside the positions that
    were not live, ``step`` None for a prefill chunk and ``(handle,
    result rows)`` for a decode step."""
    shapes: Callable                    # cfg -> {name: (shape, fan_in)}
    decode: Optional[Callable] = None
    prefill: Optional[Callable] = None
    store: Optional[str] = None
    state_block: Optional[Callable] = None
    counters: tuple = ()
    count: Optional[Callable] = None


@dataclass(frozen=True)
class Ffn:
    """A feed-forward kind: ``apply`` is None where the block has none
    (nor its norm), ``routed`` that it has routed experts; counters as a
    mixer's."""
    shapes: Callable
    apply: Optional[Callable] = None
    routed: bool = False
    counters: tuple = ()
    count: Optional[Callable] = None


MIXERS = {
    # ``sparse_selected_blocks``: blocks selected a sparse position;
    # ``sparse_steps``: grid steps of ``sparse_attend`` (PAGES_PER_STEP
    # pages each): x 8 over the blocks selected, the pages stepped a page
    # that is read
    SPARSE: Mixer(_sparse_shapes, _sparse_decode, _sparse_prefill, "kv",
                  counters=(("sparse_selected", "sparse_selected_blocks"),)
                  + _named("sparse_positions", "dense_positions",
                           "sparse_steps"), count=_count_sparse),
    LINEAR: Mixer(_lightning_shapes, _lightning_decode, _lightning_prefill,
                  "state", lambda cfg: (cfg.lin_heads, cfg.lin_head_dim,
                                        cfg.lin_head_dim),
                  _named("lightning_tokens"),
                  _positions("lightning_tokens", "lightning_tokens")),
    # rows a decode step attended to (a layer), and the distinct pages
    # they lie in, beside the pages the kernel fetched for them (a layer,
    # whole key blocks, by either pass) and how many of a slot-by-slot
    # pass's fetches the shared pass stood in for
    MLA: Mixer(_mla_shapes, _mla_decode, _mla_prefill, "latent",
               counters=_named("latent_tokens_read", "latent_pages_distinct",
                               "latent_page_visits",
                               "latent_page_visits_shared"),
               count=_count_latent),
    # valid positions through ``mamba_scan`` (a prefill chunk's) and
    # slot-steps through ``mamba_step``
    MAMBA: Mixer(_mamba_shapes, _mamba_decode, _mamba_prefill, "state",
                 _mamba_block, _named("mamba_tokens", "mamba_steps"),
                 _positions("mamba_tokens", "mamba_steps")),
    # valid positions through ``ssd_scan`` and slot-steps through
    # ``ssd_step``
    MAMBA2: Mixer(_ssd_shapes, _ssd_decode, _ssd_prefill, "state",
                  _ssd_block, _named("ssd_tokens", "ssd_steps"),
                  _positions("ssd_tokens", "ssd_steps")),
    ATTN: Mixer(_attn_shapes, _attn_decode, _attn_prefill, "kv"),
    # ring pages a window layer's decode queries visited, the positions
    # they were of (both summed over the window layers), and the K/V
    # pages the full layers' decode queries read
    WINDOW: Mixer(_attn_shapes, _window_decode, _window_prefill, "ring",
                  counters=_named("window_pages_read", "window_positions",
                                  "full_pages_read"), count=_count_window),
    NONE: Mixer(lambda cfg: {}),
}
# (token, expert) pairs routed; the distinct experts a layer a decode
# step hit, summed; ``moe_assignments_held``: the pairs that fell to the
# experts held here (over ``moe_assignments``: this chip's share of the
# routing); ``moe_kernel_calls``: expert-block calls (a block a step, a
# block a chunk) that went through the ``expert_ffn`` kernel, and
# ``moe_tile_rows`` the rows it multiplied for them (visits x
# ``ops.moe.ROW_TILE``; over the held assignments, 1 / the tiles' fill)
_ROUTED_COUNTERS = _named("moe_assignments", "moe_assignments_held",
                          "moe_experts_hit", "moe_kernel_calls",
                          "moe_tile_rows")
FFNS = {
    DENSE: Ffn(_dense_shapes, _dense_apply),
    MOE: Ffn(_moe_shapes, functools.partial(_routed_apply, ffn=MOE), True,
             _ROUTED_COUNTERS, _count_routed),
    LATENT_MOE: Ffn(_latent_moe_shapes,
                    functools.partial(_routed_apply, ffn=LATENT_MOE), True,
                    _ROUTED_COUNTERS, _count_routed),
    NONE: Ffn(lambda cfg: {}),
}
ROUTED = tuple(k for k, f in FFNS.items() if f.routed)


def held(kinds, *names) -> int:
    """How many of ``kinds`` (a configuration's ``mixer_types`` or
    ``ffn_types``) are one of ``names``."""
    return sum(map(tuple(kinds).count, names))


def keeping(store: str) -> tuple:
    """The mixer kinds whose layers keep their state in ``store``."""
    return tuple(k for k, m in MIXERS.items() if m.store == store)


def layer_kinds(cfg) -> list:
    """``[(mixer kind, feed-forward kind)]`` of the held layers."""
    ffns = cfg.ffn_types or (DENSE,) * len(cfg.mixer_types)
    return list(zip(cfg.mixer_types, ffns))


def layer_records(cfg) -> list:
    """``[(mixer record, feed-forward record, the layer's index in its
    mixer's cache array)]`` of the held layers; a kind that has no record
    raises, naming it."""
    out, taken = [], {}
    for mixer, ffn in layer_kinds(cfg):
        if mixer not in MIXERS:
            raise ValueError(f"no record of the mixer kind {mixer!r}")
        if ffn not in FFNS:
            raise ValueError(f"no record of the feed-forward kind {ffn!r}")
        m = MIXERS[mixer]
        taken[m.store] = taken.get(m.store, -1) + 1
        out.append((m, FFNS[ffn], taken[m.store]))
    return out


def layer_shapes(cfg, kind: str, ffn: str = DENSE) -> dict:
    """``{name: (shape, fan_in or None for a norm weight)}`` of one
    layer, in the order the seeded init draws them."""
    mixer, f = MIXERS[kind], FFNS[ffn]
    # a norm a sublayer the block has
    out = {name: ((cfg.d_model,), None) for name, has in
           (("norm1", mixer.decode), ("norm2", f.apply)) if has}
    out.update(mixer.shapes(cfg))
    out.update(f.shapes(cfg))
    return out


def state_block(cfg) -> tuple:
    """The shape of one recurrent layer's block of a state row: a state
    row holds ONE kind of recurrent layer (a model with none keeps an
    array with no element, of a lightning block's shape)."""
    kinds = {k for k in cfg.mixer_types if MIXERS[k].store == "state"}
    if len(kinds) > 1:
        raise ValueError("a state row holds one kind of recurrent layer: "
                         "lightning, Mamba or Mamba-2, no two of them")
    return MIXERS[kinds.pop() if kinds else LINEAR].state_block(cfg)

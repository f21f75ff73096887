"""Plain reference of the dense stand-in (``configs/dense2048_standin.json``):
its forward pass in straightforward ``jax.numpy`` and float32, with no
kernel, cache or batching, and its weights from the seed.  Imports
nothing of the program.

The layer equations, as the configuration's file states them:

    h0      = emb[token] + sinusoid(position)            (no learned positions)
    x       = h / sqrt(mean(h^2) + 1e-6)                 (weightless RMS norm)
    q,k,v   = x Wq, x Wk, x Wv  -> heads of head_dim
    a       = softmax(q k^T / sqrt(head_dim), causal) v  (GQA: kv heads repeated)
    h       = h + a Wo
    h       = h + gelu_tanh(norm(h) W1) W2
    logits  = norm(h_L) emb^T                            (tied head)

Weights are normal(0, 1/sqrt(fan_in)), seven tensors from seven keys
split from ``PRNGKey(folded seed)``, made on the device in ONE jitted
call in float32, the type they are served in.
"""
from __future__ import annotations

import functools
import math

NAMES = ("emb", "wq", "wk", "wv", "wo", "w1", "w2")


def param_shapes(cfg: dict) -> dict:
    dm, h, hkv, d = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                     cfg["head_dim"])
    ff, L, v = cfg["d_ff"], cfg["n_layers"], cfg["vocab"]
    return {"emb": ((v, dm), dm), "wq": ((L, dm, h * d), dm),
            "wk": ((L, dm, hkv * d), dm), "wv": ((L, dm, hkv * d), dm),
            "wo": ((L, h * d, dm), h * d), "w1": ((L, dm, ff), dm),
            "w2": ((L, ff, dm), ff)}


def make_params(cfg: dict, seed32: int, device=None) -> dict:
    """All seven weight tensors in one jitted call, on the device."""
    import jax
    import jax.numpy as jnp
    shapes = param_shapes(cfg)

    def init(key):
        ks = jax.random.split(key, len(NAMES))
        return {n: jax.random.normal(k, shapes[n][0], jnp.float32)
                / math.sqrt(shapes[n][1]) for n, k in zip(NAMES, ks)}

    key = jax.random.PRNGKey(int(seed32) & 0x7FFFFFFF)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.jit(init)(key)


def _sinusoid(pos, dm: int):
    import jax.numpy as jnp
    half = dm // 2
    freq = jnp.exp(-math.log(10000.0)
                   * jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _norm(x):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)


def _gelu_tanh(x):
    import jax.numpy as jnp
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def forward(params: dict, tokens, cfg_t: tuple):
    """Logits ``[B, S, vocab]`` of ``tokens [B, S]`` (positions 0..S-1),
    full causal attention.  ``cfg_t`` is the hashable
    ``(n_layers, n_heads, n_kv_heads, head_dim, d_model)``."""
    import jax
    import jax.numpy as jnp
    L, H, Hkv, D, dm = cfg_t
    b, s = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    h = params["emb"][tokens] + _sinusoid(pos, dm)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    for l in range(L):
        x = _norm(h)
        q = (x @ params["wq"][l]).reshape(b, s, H, D)
        k = (x @ params["wk"][l]).reshape(b, s, Hkv, D)
        v = (x @ params["wv"][l]).reshape(b, s, Hkv, D)
        if Hkv != H:
            k = jnp.repeat(k, H // Hkv, axis=2)
            v = jnp.repeat(v, H // Hkv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, H * D)
        h = h + a @ params["wo"][l]
        h = h + _gelu_tanh(_norm(h) @ params["w1"][l]) @ params["w2"][l]
    return _norm(h) @ params["emb"].T


def cfg_tuple(cfg: dict) -> tuple:
    return (cfg["n_layers"], cfg["n_heads"], cfg["n_kv_heads"],
            cfg["head_dim"], cfg["d_model"])


@functools.cache
def _gap_fn(cfg_t: tuple):
    """jit of: logits of a block of rows at ``highest`` -> per position
    the best logit and the logit of the token that FOLLOWS it."""
    import jax
    import jax.numpy as jnp

    def gaps(params, tokens):
        with jax.default_matmul_precision("highest"):
            lg = forward(params, tokens, cfg_t)            # [B, S, V]
        nxt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        served = jnp.take_along_axis(lg, nxt[..., None], axis=-1)[..., 0]
        return lg.max(axis=-1), served
    return jax.jit(gaps)


def position_gaps(params, cfg: dict, rows):
    """For token rows ``[B, S]`` (prompt then served tokens, padded
    behind): (best logit, logit of the next token in the row) at every
    position, as numpy ``[B, S]`` each."""
    import numpy as np
    best, served = _gap_fn(cfg_tuple(cfg))(params, rows)
    return np.asarray(best), np.asarray(served)


def control_gaps(params, cfg: dict, rows, precision: str):
    """The control: at each position, the token the LOWER precision puts
    first, and how far its logit lies below the best in the reference's
    own (``highest``) logits.  ``[B, S]`` numpy."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    cfg_t = cfg_tuple(cfg)

    @jax.jit
    def both(params, tokens):
        with jax.default_matmul_precision("highest"):
            ref = forward(params, tokens, cfg_t)
        with jax.default_matmul_precision(precision):
            low = forward(params, tokens, cfg_t)
        pick = low.argmax(axis=-1)
        got = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
        return ref.max(axis=-1) - got
    return np.asarray(both(params, rows))

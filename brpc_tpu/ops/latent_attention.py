"""Paged attention over LATENT pages (ISSUE 34): multi-head latent
attention in its absorbed form, as the decode step and the prefill
chunk of a model that caches ``[c_kv; k_rope]`` a token compute it.

The cache holds ONE row a token a layer (``kv_lora_rank + rope``
values, nothing per head): ``latent [layers, P, T, C]`` bfloat16, page
``p`` = flat arena index ``p`` of the store's pages.  With the
up-projections absorbed into the query and the output,

    q~_h  = [W_UK_h q_nope_h ; q_rope_h]               (C values)
    s_h,t = q~_h . latent[t]                           (the row IS the key)
    o~_h  = sum_t softmax(s_h)_t latent[t]             (and the value)

so every head of a position attends to the SAME rows: the heads are the
rows of one matmul against the page, and key and value are one fetch.
The caller takes ``o~[..., :kv_lora_rank]`` and applies ``W_UV``.

  :func:`latent_write`   rows into the arena in place: one position a
                         slot (decode) or whole pages (prefill)
  :func:`latent_attend`  attention of ``M`` query rows a grid row (the
                         heads of one decode position; the heads of a
                         block of prefill positions) over the pages of
                         a sequence's table, each query row with its own
                         causal length

On a TPU both are Pallas kernels (``latent_write``, ``latent_attend``):
XLA never gathers from, scatters into or slices the arena (it would give
the arena another layout and copy it whole, PERF.md section 6, PR 32).
Elsewhere plain ``jax.numpy``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from brpc_tpu.ops.paged_attention import default_backend
from brpc_tpu.ops.sparse_attention import _write_kernel

__all__ = ["latent_write", "latent_attend", "default_backend"]

PAGES_PER_STEP = 8       # pages of one grid step: one [8 T, C] key block


def _interpret(backend: str) -> bool:
    # "mosaic": compiled for the chip whatever the default backend is
    return False if backend == "mosaic" else jax.default_backend() != "tpu"


# ---- writing ---------------------------------------------------------------

def latent_write(latent, layer: int, pages, slots, rows, *,
                 backend: Optional[str] = None):
    """``rows [N, W, C]`` (``W`` 1: a decode position; ``W = T``: a
    whole page) into ``latent [layers, P, T, C]`` at ``(pages[i],
    slots[i])``, IN PLACE (donate ``latent``); a page index ``>= P`` or
    ``< 0`` writes nothing (an idle slot, padding).  Values are cast to
    the arena's type."""
    if backend is None:
        backend = default_backend()
    n, w, c = rows.shape
    p, t = latent.shape[1], latent.shape[2]
    if w not in (1, t):
        raise ValueError(f"a block is one position or a page, not {w}")
    ok = ((pages >= 0) & (pages < p)).astype(jnp.int32)
    at = jnp.clip(pages, 0, p - 1).astype(jnp.int32)
    new = rows.astype(latent.dtype)
    with jax.named_scope("ops.latent_write"):
        if backend == "gather":
            shape = (1, 1, w, c)

            def one(i, lat):
                start = (layer, at[i], slots[i], 0)
                old = jax.lax.dynamic_slice(lat, start, shape)
                return jax.lax.dynamic_update_slice(
                    lat, jnp.where(ok[i] > 0, new[i].reshape(shape), old),
                    start)
            return jax.lax.fori_loop(0, n, one, latent)
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        block = pl.BlockSpec((None, None, t, c),
                             lambda i, pg, sl, okk: (layer, pg[i], 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n,),
            in_specs=[pl.BlockSpec((None, w, c),
                                   lambda i, pg, sl, okk: (i, 0, 0)),
                      block],
            out_specs=block)
        return pl.pallas_call(
            functools.partial(_write_kernel, width=w), grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(latent.shape, latent.dtype),
            # operands: pages, slots, ok, new, latent -> latent
            input_output_aliases={4: 0}, interpret=_interpret(backend),
            name="latent_write",
        )(at, slots.astype(jnp.int32), ok, new, latent)


# ---- attending -------------------------------------------------------------

def latent_attend_gather(q, qlen, latent, layer: int, tix, tables):
    r, m, c = q.shape
    p, t = latent.shape[1], latent.shape[2]
    mp = tables.shape[1]
    f32 = jnp.float32
    tab = tables[tix]                                        # [R, MP]
    k = latent[layer][jnp.clip(tab, 0, p - 1)].astype(f32)   # [R,MP,T,C]
    k = k.reshape(r, mp * t, c)
    s = jnp.einsum("rmc,rkc->rmk", q.astype(f32), k, precision="highest")
    valid = jnp.arange(mp * t, dtype=jnp.int32)[None, None, :] < qlen
    s = jnp.where(valid, s, -jnp.inf)
    mx = s.max(axis=-1, keepdims=True)
    mx = jnp.where(jnp.isneginf(mx), 0.0, mx)
    pr = jnp.where(valid, jnp.exp(s - mx), 0.0)
    z = pr.sum(axis=-1, keepdims=True)
    pr = pr / jnp.where(z == 0.0, 1.0, z)
    return jnp.einsum("rmk,rkc->rmc", pr, k, precision="highest")


def _attend_kernel(tix_ref, tab_ref, max_ref, q_ref, qlen_ref, *refs,
                   pps: int, page_tokens: int):
    from jax.experimental import pallas as pl
    page_refs = refs[:pps]
    o_ref, m_ref, l_ref = refs[pps:]
    r, mi = pl.program_id(0), pl.program_id(1)
    base = mi * (pps * page_tokens)

    @pl.when(mi == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    # a block no query row of this grid row can see costs nothing (its
    # pages are not fetched either: the table's index does not change)
    @pl.when(base < max_ref[r])
    def _block():
        k = jnp.concatenate([ref[...] for ref in page_refs], axis=0) \
            if pps > 1 else page_refs[0][...]                # [pps T, C]
        # one MXU pass, named so: the caller may trace under
        # default_matmul_precision("highest")
        one = jax.lax.Precision.DEFAULT
        s = jax.lax.dot_general(q_ref[...], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=one)
        kpos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos < qlen_ref[...]                         # [M, 1] lens
        s = jnp.where(valid, s, -jnp.inf)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.where(jnp.isneginf(m_prev), 0.0,
                          jnp.exp(m_prev - m_safe))
        p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        o_ref[...] = o_ref[...] * alpha + jax.lax.dot_general(
            p.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=one)


def latent_attend_pallas(q, qlen, latent, layer: int, tix, tables, *,
                         interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    r, m, c = q.shape
    p, t = latent.shape[1], latent.shape[2]
    pps = PAGES_PER_STEP
    f32, i32 = jnp.float32, jnp.int32
    # rows in whole (16, 128) tiles of the keys' type; the table in
    # whole steps of pages
    m_pad = -m % 16
    if m_pad:
        q = jnp.pad(q, ((0, 0), (0, m_pad), (0, 0)))
        qlen = jnp.pad(qlen, ((0, 0), (0, m_pad), (0, 0)))
    if tables.shape[1] % pps:
        tables = jnp.pad(tables, ((0, 0), (0, -tables.shape[1] % pps)),
                         constant_values=-1)
    mp = tables.shape[1]
    mm = m + m_pad

    def row(r_, mi, tix_, tab, mx):
        return (r_, 0, 0)

    def page(i):
        def index(r_, mi, tix_, tab, mx):
            return (layer, jnp.clip(tab[tix_[r_], mi * pps + i], 0, p - 1),
                    0, 0)
        return pl.BlockSpec((None, None, t, c), index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(r, mp // pps),
        in_specs=[pl.BlockSpec((None, mm, c), row),
                  pl.BlockSpec((None, mm, 1), row)]
        + [page(i) for i in range(pps)],
        out_specs=[pl.BlockSpec((None, mm, c), row),
                   pl.BlockSpec((None, mm, 1), row),
                   pl.BlockSpec((None, mm, 1), row)])
    o, _, l = pl.pallas_call(
        functools.partial(_attend_kernel, pps=pps, page_tokens=t),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((r, mm, c), f32),
                   jax.ShapeDtypeStruct((r, mm, 1), f32),
                   jax.ShapeDtypeStruct((r, mm, 1), f32)],
        interpret=interpret, name="latent_attend",
    )(tix.astype(i32), tables.astype(i32),
      qlen.max(axis=(1, 2)).astype(i32), q.astype(latent.dtype),
      qlen.astype(i32), *([latent] * pps))
    o = o / jnp.where(l == 0.0, 1.0, l)
    return o[:, :m]


def latent_attend(q, qlen, latent, layer: int, tix, tables, *,
                  backend: Optional[str] = None):
    """Attention of ``M`` query rows a grid row over a sequence's pages.

    ``q``       ``[R, M, C]`` the absorbed queries, already scaled
    ``qlen``    ``[R, M, 1]`` int32: row ``m`` sees the keys at
                positions ``< qlen`` (0: none, the row gives 0)
    ``latent``  the arena ``[layers, P, T, C]``; ``layer`` static
    ``tix``     ``[R]`` the row of ``tables`` a grid row reads
    ``tables``  ``[n, MP]`` arena pages in sequence order (-1: none)
    Returns ``[R, M, C]`` float32: the softmax-weighted sum of the rows
    (its first ``kv_lora_rank`` values are what ``W_UV`` takes).  On the
    kernel path the queries and the probabilities multiply at the
    arena's type (one MXU pass), sums float32."""
    if backend is None:
        backend = default_backend()
    with jax.named_scope("ops.latent_attend"):
        if backend == "gather":
            return latent_attend_gather(q, qlen, latent, layer, tix, tables)
        return latent_attend_pallas(q, qlen, latent, layer, tix, tables,
                                    interpret=_interpret(backend))

"""Sequence-parallel attention: ring (ppermute + online softmax), Ulysses
(all_to_all head/sequence reshard), and a Pallas flash kernel for the
local block computation.

Design notes (TPU-first):
- All matmuls are batched [B*H, blk, d] x [B*H, d, blk] — large enough to
  tile onto the MXU; bf16-friendly (accumulate in f32).
- Ring steps use `jax.lax.fori_loop` with static shapes; the per-step
  ppermute rides ICI while the current block's FLOPs overlap it when the
  compiler can (same overlap discipline as the reference's KeepWrite
  draining while callers keep appending, socket.cpp:1692-1800).
- Online softmax (running max m, normalizer l) keeps ring attention EXACT
  — not an approximation — with each chip holding 1/n of K/V.
- Causal masking is done with GLOBAL positions, so sharded and unsharded
  results match bit-for-bit up to reduction order.

Shapes: q, k, v are [batch, seq_shard, heads, head_dim] inside shard_map
(sequence axis sharded over `axis_name`), or [batch, seq, heads, head_dim]
for the local/single-device paths.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


# ---- GQA (grouped-query) broadcast ----------------------------------------

def _expand_kv(q, k, v):
    """Grouped-query attention: when K/V carry fewer heads than Q
    (n_kv_heads divides n_heads — LLaMA/Mistral-style GQA, MQA at
    n_kv_heads=1), repeat each K/V head across its query-head group.
    XLA lowers the repeat to a broadcast that fuses into the einsum, so
    the expanded tensors are a view of the computation, not 8x HBM."""
    h_q, h_kv = q.shape[2], k.shape[2]
    if h_kv == h_q:
        return k, v
    if h_q % h_kv:
        raise ValueError(
            f"n_heads ({h_q}) must be a multiple of n_kv_heads ({h_kv})")
    g = h_q // h_kv
    return jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)


# ---- local (single-chip) reference ----------------------------------------

def local_attention(q, k, v, causal: bool = False, q_offset: int = 0,
                    kv_offset: int = 0):
    """Plain softmax(QK^T/sqrt(d))V on one chip.  Offsets give the global
    sequence positions of the q and k/v blocks for causal masking; rows
    whose mask hides every key yield zeros (not NaN) so blockwise callers
    can fold partial blocks safely.  Supports GQA/MQA (fewer K/V heads
    than Q heads)."""
    k, v = _expand_kv(q, k, v)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    # [B,H,Sq,Sk]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = q_offset + jnp.arange(sq)[:, None]
        kpos = kv_offset + jnp.arange(sk)[None, :]
        s = jnp.where(qpos >= kpos, s, -jnp.inf)
    # -inf-safe softmax: all-masked rows produce 0 weights, not NaN
    m = s.max(axis=-1, keepdims=True)
    m = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(s - m)
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    l = p.sum(axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


# ---- pallas flash kernel (local block) ------------------------------------

def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, blk_k: int, scale: float,
                  causal: bool):
    """One (batch*head, q-block) program: stream K/V blocks through VMEM
    with an online-softmax accumulator.  Grid: (BH, n_q_blocks).

    Causal: the K-block loop's trip count is CUT at the q-block's
    diagonal (blocks entirely above it are never loaded or computed —
    the ~2x FLOP saving that makes flash causal attention pay), and the
    blocks straddling the diagonal get a per-element position mask."""
    from jax.experimental import pallas as pl

    q = q_ref[...].astype(jnp.float32) * scale          # [blk_q, d]
    blk_q, d = q.shape
    sk = k_ref.shape[0]
    n_kb = sk // blk_k
    q_start = pl.program_id(1) * blk_q if causal else 0

    def body(i, carry, masked: bool = False):
        o, m, l = carry
        # dynamic-slice the REF (pl.ds lowers to Mosaic vector loads);
        # slicing a loaded VALUE emits the dynamic_slice primitive, which
        # Mosaic's TC lowering rejects — interpret mode hides that, so
        # only a real-TPU run catches it
        k_blk = k_ref[pl.ds(i * blk_k, blk_k), :]
        v_blk = v_ref[pl.ds(i * blk_k, blk_k), :]
        s = jnp.dot(q, k_blk.astype(jnp.float32).T,
                    preferred_element_type=jnp.float32)  # [blk_q, blk_k]
        if masked:
            qpos = q_start + lax.broadcasted_iota(jnp.int32,
                                                  (blk_q, blk_k), 0)
            kpos = i * blk_k + lax.broadcasted_iota(jnp.int32,
                                                    (blk_q, blk_k), 1)
            s = jnp.where(qpos >= kpos, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l = l * alpha + p.sum(axis=-1)
        o = o * alpha[:, None] + jnp.dot(
            p, v_blk.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return o, m_new, l

    carry = (jnp.zeros((blk_q, d), jnp.float32),
             jnp.full((blk_q,), -jnp.inf, jnp.float32),
             jnp.zeros((blk_q,), jnp.float32))
    if causal:
        # split at the diagonal: blocks whose LAST key is visible to the
        # q block's FIRST row need no mask; only the straddling block(s)
        # pay the iota/compare/select VPU work, and blocks entirely above
        # the diagonal are never loaded at all
        n_full = lax.div(q_start + 1, blk_k)
        n_vis = lax.div(q_start + blk_q + blk_k - 1, blk_k)
        carry = lax.fori_loop(0, n_full, body, carry)
        carry = lax.fori_loop(
            n_full, n_vis,
            functools.partial(body, masked=True), carry)
    else:
        carry = lax.fori_loop(0, n_kb, body, carry)
    o, _, l = carry
    o_ref[...] = (o / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, blk_q: int = 256, blk_k: int = 256,
                    causal: bool = False,
                    interpret: Optional[bool] = None):
    """Blockwise (flash) attention as a Pallas TPU kernel; interpret
    mode off-TPU so the same code path tests on the virtual CPU mesh.
    Shapes [B, S, H, D] -> [B, S, H, D].  GQA/MQA K/V are expanded up
    front (the kernel's grid is per query-head).  causal=True skips K
    blocks above each q block's diagonal entirely (~2x fewer FLOPs)
    and position-masks only the straddling blocks.

    A length the blocks do not divide is PADDED when causal (padding
    sits behind every real position, so no real query can see it, and
    the padded rows are cut off again) and REFUSED when not: a padded
    key would need a mask this kernel does not have, and handing the
    call to ``local_attention`` instead would report a kernel that
    never ran."""
    from jax.experimental import pallas as pl

    k, v = _expand_kv(q, k, v)
    b, s_in, h, d = q.shape
    blk_q = min(blk_q, s_in)
    blk_k = min(blk_k, s_in)
    s = -(-s_in // math.lcm(blk_q, blk_k)) * math.lcm(blk_q, blk_k)
    if s != s_in:
        if not causal:
            raise ValueError(
                f"flash_attention: sequence length {s_in} is not a "
                f"multiple of blocks ({blk_q}, {blk_k}) and the call "
                f"is not causal; pad the inputs or pick dividing blocks")
        pad = ((0, 0), (0, s - s_in), (0, 0), (0, 0))
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    scale = 1.0 / math.sqrt(d)
    # [B,S,H,D] -> [B*H, S, D]
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, blk_k=blk_k, scale=scale,
                          causal=causal),
        grid=(b * h, s // blk_q),
        in_specs=[
            pl.BlockSpec((None, blk_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, blk_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(qt, kt, vt)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)[:, :s_in]


# ---- ring attention (sequence parallel, exact) -----------------------------

def ring_attention(q, k, v, axis_name: str, causal: bool = False):
    """Exact attention with the sequence sharded over `axis_name`.

    Each chip starts with its local K/V shard; n-1 ppermute steps rotate
    the shards around the ring while an online-softmax accumulator folds
    each block in.  Memory per chip stays O(S/n); the full S x S score
    matrix never materializes anywhere.  Must be called inside shard_map
    with q/k/v sequence-sharded on `axis_name`.  Supports GQA/MQA: K/V
    with fewer heads are expanded AFTER each ring hop, so the ring moves
    the small grouped shards (g-times less ICI traffic than expanded).
    """
    n = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    perm = [(i, (i + 1) % n) for i in range(n)]

    qf = q.astype(jnp.float32)
    qpos = my * sq + jnp.arange(sq)          # global q positions

    def step(i, carry):
        o, m, l, k_blk, v_blk = carry
        src = (my - i) % n                   # whose shard we now hold
        # expand grouped K/V heads AFTER the hop (ICI carries the small
        # tensors; the broadcast fuses into the einsum)
        ke, ve = _expand_kv(qf, k_blk, v_blk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, ke.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * scale
        if causal:
            kpos = src * sq + jnp.arange(k_blk.shape[1])
            mask = qpos[:, None] >= kpos[None, :]       # [sq, sk]
            s = jnp.where(mask[None, None, :, :], s, -jnp.inf)
        blk_max = s.max(axis=-1)                        # [b,h,sq]
        m_new = jnp.maximum(m, blk_max)
        # fully-masked rows produce -inf maxima; guard every exp
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p,
                        ve.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        o_new = o * alpha.transpose(0, 2, 1)[..., None] + pv
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return o_new, m_new, l_new, k_nxt, v_nxt

    o0 = jnp.zeros((b, sq, h, d), jnp.float32)
    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    # the loop's ppermute makes carries device-varying over the mesh axis;
    # mark the constant initials to match (shard_map vma typing)
    o0, m0, l0 = (lax.pcast(x, (axis_name,), to="varying")
                  for x in (o0, m0, l0))
    o, m, l, _, _ = lax.fori_loop(0, n, step, (o0, m0, l0, k, v))
    l = jnp.where(l == 0.0, 1.0, l)          # rows with no visible keys
    return (o / l.transpose(0, 2, 1)[..., None]).astype(q.dtype)


# ---- Ulysses (all_to_all) attention ---------------------------------------

def ulysses_attention(q, k, v, axis_name: str, causal: bool = False):
    """DeepSpeed-Ulysses style: all_to_all swaps the sharded axis from
    sequence to heads, each chip runs FULL-sequence attention for its head
    group, and a second all_to_all swaps back.  Heads must divide the axis
    size.  Exact; two collectives instead of n-1 ring hops — better when
    heads >= chips and the fabric favors all_to_all.  GQA/MQA K/V are
    expanded BEFORE the reshard (the head-split needs n to divide the
    head count; grouped counts usually don't — ring_attention keeps the
    traffic saving when that matters)."""
    k, v = _expand_kv(q, k, v)
    n = lax.psum(1, axis_name)
    b, sq, h, d = q.shape

    def seq_to_heads(x):
        # [b, sq, h, d] -> [b, n*sq, h/n, d]
        x = x.reshape(b, sq, n, h // n, d)
        x = lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                           tiled=False)
        return x.reshape(b, n * sq, h // n, d)

    def heads_to_seq(x):
        x = x.reshape(b, n, sq, h // n, d)
        # received (source-chip) axis must land OUTSIDE the local-head
        # axis: chip c computed global heads [c*h/n, (c+1)*h/n), so the
        # flatten below must see [n, h/n] in that order.  (concat_axis=3
        # would interleave heads for any n < h — invisible at n == h
        # where h/n == 1.)
        x = lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                           tiled=False)
        return x.reshape(b, sq, h, d)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    og = local_attention(qg, kg, vg, causal=causal)
    return heads_to_seq(og)

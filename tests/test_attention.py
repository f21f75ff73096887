"""Sequence-parallel attention on the virtual 8-device mesh: ring and
Ulysses must match single-device full attention exactly (long-context
first-class requirement; SURVEY.md §5.7 design slot)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from brpc_tpu.ops import (flash_attention, local_attention, ring_attention,
                          ulysses_attention)

B, S, H, D = 2, 256, 8, 32
N = 8


def _qkv(seed=0, dtype=jnp.float32, s=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, s if s is not None else S, H, D)
    return tuple(jax.random.normal(k, shape, dtype) * 0.5 for k in ks)


def _mesh():
    return Mesh(np.array(jax.devices()[:N]), ("sp",))


def _sharded(fn, **kw):
    """``fn`` jitted over the mesh, and its operands' sharding."""
    from jax import shard_map
    mesh = _mesh()
    spec = P(None, "sp", None, None)

    @jax.jit
    def run(q, k, v):
        return shard_map(lambda a, b, c: fn(a, b, c, axis_name="sp", **kw),
                         mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)(q, k, v)

    return run, NamedSharding(mesh, spec)


def _run_sharded(fn, q, k, v, **kw):
    run, sh = _sharded(fn, **kw)
    return run(jax.device_put(q, sh), jax.device_put(k, sh),
               jax.device_put(v, sh))


def test_ring_attention_matches_full():
    q, k, v = _qkv()
    ref = local_attention(q, k, v)
    out = _run_sharded(ring_attention, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_causal_matches_full():
    q, k, v = _qkv(seed=1)
    ref = local_attention(q, k, v, causal=True)
    out = _run_sharded(ring_attention, q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_attention_matches_full():
    q, k, v = _qkv(seed=2)
    ref = local_attention(q, k, v)
    out = _run_sharded(ulysses_attention, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_attention_causal_matches_full():
    q, k, v = _qkv(seed=3)
    ref = local_attention(q, k, v, causal=True)
    out = _run_sharded(ulysses_attention, q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_matches_full():
    q, k, v = _qkv(seed=4)
    ref = local_attention(q, k, v)
    out = flash_attention(q, k, v, blk_q=64, blk_k=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_causal_matches_full():
    """The causal kernel cuts the K-block loop at each q block's
    diagonal (trip count depends on program_id) and position-masks the
    straddling block; it must match the masked reference exactly —
    including q rows in the FIRST block, whose only visible key is the
    diagonal."""
    q, k, v = _qkv(seed=6)
    ref = local_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, blk_q=64, blk_k=64, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_indivisible_seq_pads_when_causal():
    """S not divisible by the block sizes is padded up to them and the
    padding cut off again: causal masking keeps every real position
    blind to it, so the KERNEL still runs and still matches."""
    q, k, v = _qkv(seed=8, s=100)
    ref = local_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, blk_q=64, blk_k=64, causal=True)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_indivisible_seq_raises_when_not_causal():
    """Without a causal mask a padded key would be attended to: the
    call is refused by name, never handed to local_attention."""
    q, k, v = _qkv(seed=8, s=100)
    with pytest.raises(ValueError, match="100"):
        flash_attention(q, k, v, blk_q=64, blk_k=64)


def test_flash_attention_causal_uneven_blocks():
    """blk_q != blk_k exercises diagonal blocks that straddle unevenly
    (the trip-count formula's rounding); both orderings must match."""
    q, k, v = _qkv(seed=7)
    ref = local_attention(q, k, v, causal=True)
    for bq, bk in ((32, 64), (64, 32)):
        out = flash_attention(q, k, v, blk_q=bq, blk_k=bk, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_ring_attention_bf16():
    q, k, v = _qkv(seed=5, dtype=jnp.bfloat16)
    ref = local_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32))
    out = _run_sharded(ring_attention, q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), rtol=2e-2, atol=2e-2)


def test_ring_attention_long_sequence_memory_shape():
    """8k tokens over 8 chips: each chip sees 1k, and its program holds
    two blocks of scores (16 MiB), never its band of the 8k x 8k score
    matrix (64 MiB), by the compiler's own account: 32k only scales it."""
    S_long, heads = 8192, 2
    run, sh = _sharded(ring_attention, causal=True)
    q = jax.device_put(jnp.ones((1, S_long, heads, 16), jnp.bfloat16) * 0.01,
                       sh)
    compiled = run.lower(q, q, q).compile()
    band = S_long // N * S_long * heads * 4
    assert compiled.memory_analysis().temp_size_in_bytes < band // 2
    out = compiled(q, q, q)
    assert out.shape == (1, S_long, heads, 16)
    # row 0 attends only to itself -> output == v row 0
    np.testing.assert_allclose(np.asarray(out[0, 0], dtype=np.float32),
                               np.asarray(q[0, 0], dtype=np.float32),
                               rtol=1e-2)


@pytest.mark.parametrize("n", [2, 4, 8])
@pytest.mark.parametrize("fn", [ring_attention, ulysses_attention])
def test_sequence_parallel_exact_across_mesh_sizes(n, fn):
    """Regression: Ulysses' head reassembly interleaved wrongly for any
    n < heads (invisible at n == heads where h/n == 1) — every op must be
    exact on every mesh size, causal on."""
    from jax import shard_map
    q, k, v = _qkv(seed=10 + n)
    mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))
    spec = P(None, "sp", None, None)
    sh = NamedSharding(mesh, spec)

    @jax.jit
    def run(q, k, v):
        return shard_map(
            lambda a, b, c: fn(a, b, c, axis_name="sp", causal=True),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)

    ref = local_attention(q, k, v, causal=True)
    out = run(jax.device_put(q, sh), jax.device_put(k, sh),
              jax.device_put(v, sh))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


# ---- grouped-query attention (GQA / MQA) ----

class TestGQA:
    def _qkv(self, h_q, h_kv, b=2, s=32, d=16, seed=3):
        key = jax.random.PRNGKey(seed)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, s, h_q, d), jnp.float32) * 0.3
        k = jax.random.normal(kk, (b, s, h_kv, d), jnp.float32) * 0.3
        v = jax.random.normal(kv, (b, s, h_kv, d), jnp.float32) * 0.3
        return q, k, v

    @pytest.mark.parametrize("h_q,h_kv", [(8, 2), (8, 1), (4, 4)])
    def test_local_gqa_matches_expanded(self, h_q, h_kv):
        q, k, v = self._qkv(h_q, h_kv)
        out = local_attention(q, k, v, causal=True)
        ke = jnp.repeat(k, h_q // h_kv, axis=2)
        ve = jnp.repeat(v, h_q // h_kv, axis=2)
        ref = local_attention(q, ke, ve, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-6, atol=1e-6)

    def test_bad_head_ratio_rejected(self):
        q, k, v = self._qkv(6, 4)
        with pytest.raises(ValueError):
            local_attention(q, k, v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_gqa_exact(self, causal):
        q, k, v = self._qkv(8, 2, s=8 * N)
        out = _run_sharded(ring_attention, q, k, v, causal=causal)
        ref = local_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)

    def test_ulysses_gqa_exact(self):
        q, k, v = self._qkv(8, 2, s=8 * N)
        out = _run_sharded(ulysses_attention, q, k, v)
        ref = local_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)

    def test_flash_gqa_matches_expanded(self):
        q, k, v = self._qkv(8, 2, b=1, s=64, d=16)
        out = flash_attention(q, k, v, blk_q=32, blk_k=32)
        ref = local_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

"""Sliding-window attention over the ring of the layered cache:
``ops.sparse_attention.sparse_attend`` with a per-row lower bound
(``starts``), the kernel interpreted against the gather, and the
``window`` mixer's ring tables (``models.layers._attend_window`` a
decode position a row, ``_attend_block`` a prefill block of positions a
row) against a plain window over the whole sequence: a ring that wraps,
a prefill chunk whose pages cross the ring's end, and queries before the
first window is full; and a block of positions a row under one long
table against a row a position.  One shape a signature; Mosaic's
verdict on the published widths is ``test_chip_compile.py``'s."""
import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.models import layers
from brpc_tpu.ops import sparse_attention as sa

HKV, G, T, D = 2, 4, 16, 32
WINDOW = 40                 # spans 4 pages of 16 at most
CHUNK_PAGES = 2
R = layers.ring_pages(WINDOW, T, CHUNK_PAGES * T)           # 5 ring pages
ROWS = 3                    # rows of the ring (a live row between others)
SEQ = 160                   # positions: the ring wraps twice
N = 16                      # queries a call


def _keys(seed):
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(SEQ, HKV, D)).astype(np.float32)
    v = rng.normal(size=(SEQ, HKV, D)).astype(np.float32)
    # the values a bfloat16 cache holds
    return (np.asarray(jnp.asarray(k, jnp.bfloat16), np.float32),
            np.asarray(jnp.asarray(v, jnp.bfloat16), np.float32))


def _ring(k, v, row, upto_block):
    """A ring as the programs leave it: every block ``0 .. upto_block``
    of the sequence written in order at ring page ``row R + b mod R``,
    the other rows' pages holding noise."""
    rng = np.random.default_rng(99)
    ring = rng.normal(size=(1, 2, HKV, ROWS * R, T, D)).astype(np.float32)
    for b in range(upto_block + 1):
        page = row * R + b % R
        ring[0, 0, :, page] = k[b * T:(b + 1) * T].transpose(1, 0, 2)
        ring[0, 1, :, page] = v[b * T:(b + 1) * T].transpose(1, 0, 2)
    return jnp.asarray(ring, jnp.bfloat16)


def _plain(q, k, v, qpos):
    """Each query over keys ``t - WINDOW < j <= t`` of the whole
    sequence: ``[N, Hkv, G, D]``."""
    out = np.zeros(q.shape, np.float64)
    for n, t in enumerate(qpos):
        lo = max(0, t - WINDOW + 1)
        for h in range(HKV):
            s = q[n, h] @ k[lo:t + 1, h].T / np.sqrt(D)
            p = np.exp(s - s.max(axis=-1, keepdims=True))
            out[n, h] = (p / p.sum(axis=-1, keepdims=True)) @ v[lo:t + 1, h]
    return out


@functools.partial(jax.jit, static_argnames=("backend", "control"))
def _window(q4, ring, rows, qpos, live, *, backend, control=""):
    return layers._attend_window(q4, ring, 0, R, rows, qpos, live,
                                 SimpleNamespace(window=WINDOW), control,
                                 backend)


@functools.partial(jax.jit, static_argnames=("backend", "control"))
def _block(q4, ring, first, *, backend, control=""):
    """A prefill block of ``q4.shape[0]`` positions from ``first`` of
    the sequence in row 1: one kernel row a K/V head."""
    pages, blocks, starts = layers._window_table(
        first[None], q4.shape[0], jnp.ones((1,), jnp.int32),
        jnp.ones((1,), bool), R, T, SimpleNamespace(window=WINDOW), control)
    return layers._attend_block(q4, ring, 0, pages[0], blocks[0], first,
                                backend, None if starts is None
                                else starts[0], "window_attend")


@jax.jit
def _bounded_kernel(q, kv, heads, tab, blk, lens, starts):
    return sa.sparse_attend_pallas(q, kv, 0, heads, tab, blk, lens,
                                   starts=starts, interpret=True,
                                   name="window_attend")


CASES = {
    # decode queries far into a sequence whose ring has wrapped twice
    "ring_wraps": (list(range(144, 160)), 9),
    # a prefill chunk of blocks 5 and 6 (ring pages 0 and 1: past the
    # ring's end) written before its queries read, the first of them
    # reaching back into blocks 2-4
    "chunk_crosses_the_ring_end": (list(range(80, 112, 2)), 6),
    # before the first window is full: from position 0, the bound at 0
    "before_a_full_window": [list(range(0, 64, 4)), 3],
}


@pytest.mark.parametrize("backend", ["gather", "pallas"])
@pytest.mark.parametrize("name", list(CASES))
def test_the_ring_reads_what_a_plain_window_over_the_sequence_reads(
        name, backend):
    qpos, upto = CASES[name]
    k, v = _keys(sum(map(ord, name)))
    ring = _ring(k, v, 1, upto)
    q = np.random.default_rng(4).normal(size=(N, HKV, G, D)).astype(
        np.float32)
    live = np.ones((N,), bool)
    live[3] = False                              # an idle slot between
    got = np.asarray(_window(jnp.asarray(q), ring,
                             jnp.full((N,), 1, jnp.int32),
                             jnp.asarray(qpos, jnp.int32),
                             jnp.asarray(live), backend=backend))
    want = _plain(q, k, v, qpos).reshape(N, -1)
    # bfloat16 keys and values either side; the kernel multiplies the
    # float32 query as two bfloat16 terms (2^-17 relative)
    assert np.abs(got[live] - want[live]).max() < 1e-4
    assert not got[3].any()
    # with no lower bound the oldest page's keys before the window
    # take part: another output (what the ``unbounded`` control reads)
    if name != "before_a_full_window":
        wide = np.asarray(_window(jnp.asarray(q), ring,
                                  jnp.full((N,), 1, jnp.int32),
                                  jnp.asarray(qpos, jnp.int32),
                                  jnp.asarray(live), backend=backend,
                                  control="unbounded"))
        assert np.abs(wide[live] - want[live]).max() > 1e-2


@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_a_prefill_block_reads_what_a_plain_window_reads(backend):
    """A chunk of blocks 5 and 6 (ring pages 0 and 1: past the ring's
    end) written before its queries read; each block of 16 positions is
    ONE kernel row a K/V head over the 4 ring pages its windows span,
    the bounds one later a position; and the first block of a sequence,
    whose windows begin at 0."""
    k, v = _keys(7)
    rng = np.random.default_rng(6)
    for first, upto in ((80, 6), (96, 6), (0, 1)):
        ring = _ring(k, v, 1, upto)
        q = rng.normal(size=(16, HKV, G, D)).astype(np.float32)
        got = np.asarray(_block(jnp.asarray(q), ring, jnp.int32(first),
                                backend=backend))
        want = _plain(q, k, v, list(range(first, first + 16)))
        assert np.abs(got - want.reshape(16, -1)).max() < 1e-4, first


@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_a_block_a_row_equals_a_row_a_position_under_a_long_table(backend):
    """The full layers' prefill over a long table: a block of positions
    a kernel row reads what a row a position reads, causal inside the
    block too; a block with no live position names no page and gives
    0."""
    rng = np.random.default_rng(12)
    pages = 20
    kv = jnp.asarray(rng.normal(size=(1, 2, HKV, pages, T, D)), jnp.bfloat16)
    table = np.full((12,), -1, np.int32)
    table[:9] = rng.permutation(pages)[:9]          # 9 pages: 144 keys
    blocks = np.where(table >= 0, np.arange(12), -1).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(N, HKV, G, D)), jnp.float32)
    first = 120                                     # 120 .. 135
    qpos = jnp.arange(first, first + N, dtype=jnp.int32)
    live = jnp.ones((N,), bool)
    rows = np.asarray(layers._attend_all(
        q, kv, 0, jnp.broadcast_to(jnp.asarray(table)[None], (N, 12)), qpos,
        live, backend))
    got = np.asarray(layers._attend_block(
        q, kv, 0, jnp.asarray(table), jnp.asarray(blocks), jnp.int32(first),
        backend))
    assert np.abs(got - rows).max() < 1e-5
    dead = np.asarray(layers._attend_block(
        q, kv, 0, jnp.full((12,), -1, jnp.int32), jnp.asarray(blocks),
        jnp.int32(first), backend))
    assert not dead.any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_bounded_kernel_equals_the_bounded_gather(dtype):
    """``starts`` in the kernel (interpreted) against the gather's:
    bounds inside a page, on a page's first key, past a row's last key
    (a row that sees nothing gives 0) and at 0; tables of pages in any
    order with -1 tails."""
    rng = np.random.default_rng(8)
    pages, width, n = 24, 12, 8
    kv = jnp.asarray(rng.normal(size=(1, 2, HKV, pages, T, D)), dtype)
    tab = np.full((n, width), -1, np.int32)
    blk = np.full((n, width), -1, np.int32)
    for r, live in enumerate([12, 5, 9, 3, 12, 1, 7, 10]):
        tab[r, :live] = rng.permutation(pages)[:live]
        blk[r, :live] = np.arange(live) + r
    lens = np.asarray([(12 + r) * T, 7 * T - 3, 13 * T, 5 * T + 1,
                       16 * T, 6 * T, 0, 19 * T], np.int32)
    starts = np.asarray([3 * T + 5, 2 * T + 3, 9 * T, 0, 15 * T + 2,
                         7 * T, 0, 17 * T], np.int32)
    args = (jnp.asarray(rng.normal(size=(n, G, D)), jnp.float32), kv,
            jnp.asarray(np.arange(n) % HKV, jnp.int32), jnp.asarray(tab),
            jnp.asarray(blk), jnp.asarray(lens))
    got = np.asarray(_bounded_kernel(*args, jnp.asarray(starts)))
    want = np.asarray(sa.sparse_attend_gather(
        *args[:1], args[1], 0, *args[2:], starts=jnp.asarray(starts)))
    tol = 1e-5 if dtype == "float32" else 2.0 ** -16
    assert np.abs(got - want).max() < tol
    # row 5's only page lies before its start, row 6 has no length
    assert not got[5].any() and not got[6].any()
    unbounded = np.asarray(sa.sparse_attend_gather(
        *args[:1], args[1], 0, *args[2:]))
    assert np.abs(unbounded[0] - want[0]).max() > 1e-3
    assert np.abs(unbounded[3] - want[3]).max() < tol      # start 0

"""``ops.sparse_attention.sparse_attend`` (ISSUE 37): the kernel in
interpret mode against the gather, a block of ``PAGES_PER_STEP`` pages a
grid step over the flat work list.  One shape a signature (every case of
it shares a compile); Mosaic's verdict on the published widths is
``test_chip_compile.py``'s."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.ops import sparse_attention as sa

LAYERS, HKV, PAGES, T, D = 2, 2, 48, 16, 32
N, G = 8, 4
BLOCKS = 24                      # logical blocks a sequence may hold
FULL = BLOCKS * T


@jax.jit                     # one compile a signature, not one a case
def _kernel(q, kv, heads, tab, blk, lens, *more):
    return sa.sparse_attend_pallas(q, kv, 1, heads, tab, blk, lens, *more,
                                   interpret=True)


@functools.cache
def _arena(dtype):
    rng = np.random.default_rng(11)
    return jnp.asarray(rng.normal(size=(LAYERS, 2, HKV, PAGES, T, D)),
                       dtype)


def _selected(rng, width, live):
    """A selected table: ``live`` distinct blocks in any order, then -1."""
    tab = np.full((width,), -1, np.int32)
    blk = np.full((width,), -1, np.int32)
    tab[:live] = rng.permutation(PAGES)[:live]
    blk[:live] = rng.permutation(BLOCKS)[:live]
    return tab, blk


def _case(name):
    """``(tables, block_ids, lengths, extra, rows that must give 0)``."""
    rng = np.random.default_rng(sum(map(ord, name)))
    width = 12 if name == "width_not_whole_steps" else 16
    live = {"full_rows": [width] * N,
            # fewer than a step, fewer than the table, none, and a step
            # and a bit
            "short_tails": [3, 1, 7, 8, 9, 12, 0, 5],
            "dead_rows_between": [16, 0, 0, 5, 0, 16, 9, 0],
            "width_not_whole_steps": [12, 12, 4, 0, 9, 12, 1, 8],
            "extra_key": [16, 0, 3, 9, 0, 16, 8, 1],
            "dense_beside_selected": [16, 9, 16, 3, 16, 0, 16, 12]}[name]
    tabs, blks = zip(*(_selected(rng, width, k) for k in live))
    tab, blk = np.stack(tabs), np.stack(blks)
    lens = np.where(np.asarray(live) > 0, FULL, 0).astype(np.int32)
    if name == "dense_beside_selected":
        # rows 0, 2, 4, 6: the sequence's first pages in block order, ALL
        # of them named, the length inside the third / the tenth block
        for r, n_keys in ((0, 2 * T + 5), (2, 9 * T + 1), (4, T), (6, FULL)):
            tab[r] = rng.permutation(PAGES)[:width]
            blk[r] = np.arange(width)
            lens[r] = n_keys
    zero = [r for r in range(N) if live[r] == 0]
    return tab, blk, lens, name == "extra_key", zero


CASES = ["full_rows", "short_tails", "dead_rows_between",
         "width_not_whole_steps", "extra_key", "dense_beside_selected"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", CASES)
def test_the_kernel_equals_the_gather(name, dtype):
    """To 1e-5 over float32 pages and to the bfloat16 split's 2^-16 over
    bfloat16 ones (a float32 operand is two bfloat16 terms there, each
    one MXU pass): full rows, -1 tails shorter than a step and than the
    table, dead rows between live ones (0, and the neighbours as the
    gather has them), a table that is not whole steps, the self key, a
    dense-branch table (every page named, the length cutting it) beside
    selected ones."""
    tab, blk, lens, extra, zero = _case(name)
    rng = np.random.default_rng(5)
    kv = _arena(jnp.dtype(dtype))
    q = jnp.asarray(rng.normal(size=(N, G, D)), jnp.float32)
    heads = jnp.asarray(np.arange(N) % HKV, jnp.int32)
    more = tuple(jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
                 for _ in range(2)) if extra else ()
    args = (q, kv, heads, jnp.asarray(tab), jnp.asarray(blk),
            jnp.asarray(lens)) + more
    got = np.asarray(_kernel(*args))
    want = np.asarray(sa.sparse_attend_gather(q, kv, 1, *args[2:]))
    tol = 1e-5 if dtype == "float32" else 2.0 ** -16
    assert np.abs(got - want).max() < tol
    assert np.isfinite(got).all()
    if not extra:
        assert zero == [r for r in range(N) if not got[r].any()]


@pytest.mark.parametrize("name", CASES)
def test_the_work_list_holds_the_steps_with_a_visible_page_and_no_other(
        name):
    """A row whose last entry with a visible key is its ``n``-th takes
    ``max(1, ceil(n / 8))`` steps, in order, from step 0; a dead row one;
    ``n`` live entries and nothing stepped behind them;
    ``steps_visited`` counts the same on the host."""
    tab, blk, lens, _, _ = _case(name)
    pps = sa.PAGES_PER_STEP
    width = -(-tab.shape[1] // pps) * pps
    live = np.asarray(sa.live_pages(jnp.asarray(tab), jnp.asarray(blk),
                                    jnp.asarray(lens), T))
    want_live = [max([i + 1 for i in range(tab.shape[1])
                      if tab[r, i] >= 0 and blk[r, i] * T < lens[r]] or [0])
                 for r in range(N)]
    assert live.tolist() == want_live
    count = np.maximum(-(-live // pps), 1)
    rows, blocks, n = sa.flat_work_list(
        jnp.zeros((N,), jnp.int32), jnp.asarray(count, jnp.int32),
        width // pps)
    assert int(n) == sa.steps_visited(live) == int(count.sum())
    got = list(zip(np.asarray(rows)[:int(n)].tolist(),
                   np.asarray(blocks)[:int(n)].tolist()))
    assert got == [(r, b) for r in range(N) for b in range(count[r])]
    assert rows.shape == blocks.shape == (N * width // pps + 1,)

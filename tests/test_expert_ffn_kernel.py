"""The ``expert_ffn`` Pallas kernel (ISSUE 41) against ``lax.ragged_dot``'s
body, its oracle: interpret mode on the CPU at toy widths that keep the
published shape in small (latent 128, hidden 384 = 3 x 128 where the
model has 1,024 and 2,688 = 21 x 128; 16 experts held of 64, top-4).
Every case has ONE shape (64 rows x 4 choices = 256 sorted rows, eight
row tiles), so each backend compiles once a type.  That Mosaic takes
the kernel at the published widths is ``tests/test_chip_compile.py``'s
to say, and what it costs a chip run's."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.models.hybrid import _rmm
from brpc_tpu.ops import moe

N, K, E, HELD = 64, 4, 64, (16, 16)
LATENT, FF = 128, 384
OUTSIDE = (0, 1, 2, 40)      # four experts another chip holds


def _routing(case: str):
    """``(experts [N, K], valid [N], rows that may hold NaN)``."""
    rng = np.random.default_rng(7)
    valid = np.ones((N,), bool)
    nan_rows = np.zeros((N,), bool)
    if case in ("even", "low_control"):
        experts = np.stack([rng.choice(E, K, replace=False)
                            for _ in range(N)])
    elif case == "one_expert":
        # 64 rows of one held expert: two row tiles on one resident pair
        experts = np.tile(np.asarray((21,) + OUTSIDE[:3]), (N, 1))
    elif case == "none_hit":
        experts = np.tile(np.asarray(OUTSIDE), (N, 1))
    elif case == "straddling":
        # 13 groups of 9-10 rows and one of 64: tile boundaries at every
        # 32nd row fall inside groups
        experts = np.stack([[16 + t % 13, 29, 16 + (t * 5 + 1) % 13 if
                             (t * 5 + 1) % 13 != t % 13 else 30, 3]
                            for t in range(N)])
    elif case in ("padded_bucket", "nan_past_the_groups"):
        experts = np.stack([rng.choice(E, K, replace=False)
                            for _ in range(N)])
        valid[40:] = False               # a chunk of 40 in a bucket of 64
        valid[5] = False                 # and a hole (an idle slot)
        nan_rows = ~valid if case == "nan_past_the_groups" else nan_rows
    else:
        raise AssertionError(case)
    return experts.astype(np.int32), valid, nan_rows


@functools.lru_cache(maxsize=None)
def _weights(dtype):
    rng = np.random.default_rng(11)
    up = rng.normal(size=(HELD[1], LATENT, FF)) / np.sqrt(LATENT)
    down = rng.normal(size=(HELD[1], FF, LATENT)) / np.sqrt(FF)
    return jnp.asarray(up, dtype), jnp.asarray(down, dtype)


@functools.lru_cache(maxsize=None)
def _ffn(backend, round_acc):
    # a bare call of a kernel in interpret mode compiles anew every time
    return jax.jit(functools.partial(
        moe.expert_ffn, held=HELD, mm=_rmm, backend=backend,
        round_acc=round_acc))


CASES = ["even", "one_expert", "none_hit", "straddling", "padded_bucket",
         "nan_past_the_groups", "low_control"]


# the control rounds a bfloat16 model's sums: no float32 case of it
@pytest.mark.parametrize("case,dtype", [
    (c, d) for c in CASES for d in ("float32", "bfloat16")
    if (c, d) != ("low_control", "float32")])
def test_the_kernel_gives_what_the_ragged_products_give(case, dtype):
    experts, valid, nan_rows = _routing(case)
    rng = np.random.default_rng(3)
    x = rng.normal(size=(N, LATENT)).astype(np.float32)
    x[nan_rows] = np.nan
    weights = rng.uniform(0.1, 1.0, size=(N, K)).astype(np.float32)
    up, down = _weights(jnp.dtype(dtype))
    round_acc = "bfloat16" if case == "low_control" else None
    args = (jnp.asarray(x), jnp.asarray(experts), jnp.asarray(weights),
            jnp.asarray(valid), None, up, down)
    if round_acc:
        # the oracle's ``mm`` under the control: each product's sums
        # hold bfloat16 values (``models/hybrid._acc``)
        low = lambda a, w, s: moe._round(_rmm(a, w, s), "bfloat16")
        want, sizes_w = jax.jit(functools.partial(
            moe.expert_ffn, held=HELD, mm=low, backend="gather"))(*args)
    else:
        want, sizes_w = _ffn("gather", None)(*args)
    got, sizes = _ffn("pallas", round_acc)(*args)
    got, want = np.asarray(got), np.asarray(want)
    assert (np.asarray(sizes) == np.asarray(sizes_w)).all()
    mine = (experts >= HELD[0]) & (experts < sum(HELD)) & valid[:, None]
    assert int(np.asarray(sizes).sum()) == int(mine.sum())
    assert np.isfinite(got).all()
    # a row nothing held was chosen for, and a row that is no token
    assert (got[~mine.any(axis=1)] == 0).all()
    if case in ("even", "straddling", "one_expert"):
        assert np.abs(want).max() > 0.5
    # the same bfloat16 inputs and float32 sums, added in another order:
    # float32's own rounding; under the control a sum within half a
    # bfloat16 step of a tie may round to the neighbour on one side
    tol = 3e-5 if round_acc is None else 2e-2
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())


@pytest.mark.parametrize("case", CASES[:6])
def test_the_work_list_covers_every_group_row_once(case):
    experts, valid, _ = _routing(case)
    e = experts.reshape(-1) - HELD[0]
    mine = (e >= 0) & (e < HELD[1]) & np.repeat(valid, K)
    sizes = np.bincount(e[mine], minlength=HELD[1]).astype(np.int32)
    tile, rows = moe.ROW_TILE, N * K
    items, n = jax.jit(functools.partial(
        moe.work_list, n_rows=rows, tile=tile))(jnp.asarray(sizes))
    items, n = np.asarray(items), int(n)
    # the worst case of the shape and one more, for the look-ahead
    assert items.shape == (6, rows // tile + HELD[1] + 1)
    visits = np.asarray(moe.tile_visits(jnp.asarray(sizes), tile))
    assert n == max(1, visits.sum())
    seen = np.zeros((rows,), np.int32)
    start = np.cumsum(sizes) - sizes
    hit = np.flatnonzero(sizes)
    for w in range(n if sizes.any() else 0):
        e_w, t, lo, hi, ordinal, nxt = items[:, w]
        assert t * tile <= lo < hi <= (t + 1) * tile
        assert start[e_w] <= lo and hi <= start[e_w] + sizes[e_w]
        assert hit[ordinal] == e_w
        assert nxt == (hit[ordinal + 1] if ordinal + 1 < len(hit) else -1)
        seen[lo:hi] += 1
    assert (seen[:sizes.sum()] == 1).all() and not seen[sizes.sum():].any()
    # experts in order, so an expert's items are one run (its matrices
    # are copied once) and the tiles never step back
    live = items[:, :n]
    assert (np.diff(live[4]) >= 0).all() and (np.diff(live[1]) >= 0).all()
    # what lies past ``n`` repeats the last live item: nothing new to fetch
    assert (items[:, n:] == items[:, n - 1:n]).all()
    if not sizes.any():
        assert items[2, 0] == items[3, 0]           # the one item, no row

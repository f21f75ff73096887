"""``ops.mamba`` (ISSUE 38): the chunk scan and the slot update, their
kernels interpreted, against a ``lax.scan`` of the equations written
here once more; a bucket's padding, two chunks against one, and the
convolution's tail across a chunk boundary.  One shape a function (a
bare call compiles anew), toy widths: 256 channels (two channel blocks
of the scan kernel would need 1,024; ``SCAN_CHANNELS`` is patched to
128 so that the toy has two), 8 state values, 4 taps."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.ops import mamba

CH, N, K, C = 256, 8, 4, 32
PER_POSITION = ("xs", "z", "delta", "b", "c")   # the rest is a layer's own
ROWS = mamba.state_block_rows(N, K)
TOL = 2e-5


@pytest.fixture(scope="module", autouse=True)
def _two_channel_blocks():
    was = mamba.SCAN_CHANNELS, mamba.SCAN_TIME
    mamba.SCAN_CHANNELS, mamba.SCAN_TIME = 128, 16
    yield
    mamba.SCAN_CHANNELS, mamba.SCAN_TIME = was


def rows_of(p, cut):
    """``p`` with ``cut`` applied to what it holds a position."""
    return {k: (cut(v) if k in PER_POSITION else v) for k, v in p.items()}


def draw(seed, c=C):
    r = np.random.default_rng(seed)
    f = np.float32
    return {"xs": r.normal(size=(c, CH)).astype(f),
            "z": r.normal(size=(c, CH)).astype(f),
            "delta": np.exp(r.uniform(np.log(1e-3), np.log(0.3),
                                      (c, CH))).astype(f),
            "b": r.normal(size=(c, N)).astype(f),
            "c": r.normal(size=(c, N)).astype(f),
            "w": (0.5 * r.normal(size=(K, CH))).astype(f),
            "bias": (0.1 * r.normal(size=(CH,))).astype(f),
            "a_log": np.log(np.broadcast_to(
                np.arange(1, N + 1, dtype=f)[:, None], (N, CH))).copy(),
            "d": np.ones((CH,), f),
            "h0": r.normal(size=(N, CH)).astype(f),
            "tail": r.normal(size=(K - 1, CH)).astype(f)}


@jax.jit
def equations(p, h0, tail):
    """The module docstring's equations, a position at a time."""
    a = -jnp.exp(p["a_log"])

    def one(carry, xs):
        h, tail = carry
        x, z, dl, bt, ct = xs
        win = jnp.concatenate([tail, x[None]], axis=0)        # [K, ch]
        xc = jax.nn.silu(p["bias"] + (p["w"] * win).sum(axis=0))
        h = jnp.exp(dl[None] * a) * h + (dl * xc)[None] * bt[:, None]
        y = (h * ct[:, None]).sum(axis=0) + p["d"] * xc
        return (h, win[1:]), (y * jax.nn.silu(z), xc)
    (h, tail), (y, xc) = jax.lax.scan(
        one, (h0, tail), (p["xs"], p["z"], p["delta"], p["b"], p["c"]))
    return y, xc, h, tail


@functools.partial(jax.jit, static_argnames=("backend",))
def chunk(p, h0, tail, n_valid, backend):
    xc, tail = mamba.conv_chunk(p["xs"], tail, p["w"], p["bias"], n_valid)
    y, h = mamba.mamba_scan(xc, p["delta"], p["z"], p["b"], p["c"], h0,
                            p["a_log"], p["d"], n_valid, backend=backend)
    return y, xc, h, tail


@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_the_chunk_scan_equals_the_equations(backend):
    p = draw(1)
    want = equations(p, p["h0"], p["tail"])
    got = chunk(p, p["h0"], p["tail"], C, backend)
    for g, w in zip(got, want):
        assert np.abs(np.asarray(g) - np.asarray(w)).max() < TOL


@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_a_buckets_padding_leaves_state_and_tail_alone(backend):
    """20 valid positions of 32 (the second time block half valid): the
    state and the tail are those after 20, whatever the padding holds;
    with no valid position at all they are the ones that came in."""
    p = draw(2)
    n = 20
    # the equations over the same 32 rows with the last 12 zeroed: a
    # step size of 0 leaves h alone there too
    short = rows_of(p, lambda v: np.concatenate([v[:n],
                                                 np.zeros_like(v[n:])]))
    want = equations(short, p["h0"], p["tail"])
    y, xc, h, tail = chunk(p, p["h0"], p["tail"], n, backend)
    assert np.abs(np.asarray(y)[:n] - np.asarray(want[0])[:n]).max() < TOL
    # ``want``'s state IS the state after 20; the tail is read off the
    # inputs
    assert np.abs(np.asarray(h) - np.asarray(want[2])).max() < TOL
    assert (np.asarray(tail) == p["xs"][n - K + 1:n]).all()
    assert np.isfinite(np.asarray(y)).all()
    _, _, h0, t0 = chunk(p, p["h0"], p["tail"], 0, backend)
    assert (np.asarray(h0) == p["h0"]).all()
    assert (np.asarray(t0) == p["tail"]).all()


def test_two_chunks_equal_one():
    """32 positions as one chunk and as 16 + 16 with the state and the
    tail carried: the convolution's window crosses the boundary."""
    p = draw(3)
    zeros_h, zeros_t = np.zeros_like(p["h0"]), np.zeros_like(p["tail"])
    whole = chunk(p, zeros_h, zeros_t, C, "pallas")

    def half(lo):
        return rows_of(p, lambda v: v[lo:lo + C // 2])
    halves = jax.jit(lambda a, b, h, t: (
        lambda first: (first, chunk.__wrapped__(b, first[2], first[3],
                                                C // 2, "pallas")))(
        chunk.__wrapped__(a, h, t, C // 2, "pallas")))
    first, second = halves(half(0), half(C // 2), zeros_h, zeros_t)
    y = np.concatenate([first[0], second[0]])
    assert np.abs(y - np.asarray(whole[0])).max() < TOL
    assert np.abs(np.asarray(second[2]) - np.asarray(whole[2])).max() < TOL
    assert (np.asarray(second[3]) == np.asarray(whole[3])).all()
    # position 16's window reaches back to 13, 14, 15 of the first chunk
    alone = chunk.__wrapped__(half(C // 2), zeros_h, zeros_t, C // 2,
                              "gather")
    assert np.abs(np.asarray(alone[1])[0]
                  - np.asarray(second[1])[0]).max() > 1e-2


@functools.partial(jax.jit, static_argnames=("backend", "round_state"),
                   donate_argnums=0)
def slots(state, rows, p, backend, round_state=None):
    xc, state = mamba.conv_step(state, rows, 1, p["xs"], p["w"], p["bias"],
                                d_state=N, round_state=round_state,
                                backend=backend)
    y, state = mamba.mamba_step(state, rows, 1, xc, p["delta"], p["z"],
                                p["b"], p["c"], p["a_log"], p["d"],
                                round_state=round_state, backend=backend)
    return y, state


@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_the_slot_update_equals_the_equations(backend):
    """Four slots on rows 3, 0, 5, 2 of a 6-row, 2-layer state array,
    two steps in a row: layer 1's blocks of those rows move on as the
    equations say, every other block and the padding rows stay."""
    s = 4
    p = draw(4, c=2 * s)
    r = np.random.default_rng(9)
    state = np.zeros((6, 2, ROWS, CH), np.float32)
    state[:, :, :N + K - 1] = r.normal(size=(6, 2, N + K - 1, CH))
    rows = np.asarray([3, 0, 5, 2], np.int32)
    now = jnp.asarray(state)
    ys = []
    for step in range(2):
        part = rows_of(p, lambda v: v[step * s:(step + 1) * s])
        y, now = slots(now, rows, part, backend)
        ys.append(np.asarray(y))
    now = np.asarray(now)
    for i, row in enumerate(rows):
        one = rows_of(p, lambda v: v[i::s])
        y, _, h, tail = equations(one, state[row, 1, :N],
                                  state[row, 1, N:N + K - 1])
        assert np.abs(np.stack([ys[0][i], ys[1][i]]) - np.asarray(y)).max() \
            < TOL
        assert np.abs(now[row, 1, :N] - np.asarray(h)).max() < TOL
        assert (now[row, 1, N:N + K - 1] == np.asarray(tail)).all()
    assert (now[:, 0] == state[:, 0]).all()
    assert (now[[1, 4], 1] == state[[1, 4], 1]).all()
    assert (now[rows, 1, N + K - 1:] == 0).all()


def test_the_control_rounds_the_state_to_bfloat16_values():
    p = draw(5, c=4)
    state = jnp.zeros((4, 2, ROWS, CH), jnp.float32)
    _, out = slots(state, np.arange(4, dtype=np.int32), p, "pallas",
                   round_state="bfloat16")
    h = np.asarray(out)[:, 1, :N + K - 1]
    assert (h == np.asarray(jnp.asarray(h).astype(jnp.bfloat16)
                            .astype(jnp.float32))).all()
    assert np.abs(h).max() > 0


def test_a_block_holds_whole_tiles():
    assert mamba.state_block_rows(16, 4) == 24
    with pytest.raises(ValueError, match="d_state"):
        mamba.state_block_rows(12, 4)
    with pytest.raises(ValueError, match="d_conv"):
        mamba.state_block_rows(16, 12)

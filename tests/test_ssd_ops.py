"""``ops.ssd`` (ISSUE 40): the chunk scan in the dual form and the slot
update, their kernels interpreted, against a ``lax.scan`` of the
equations written here once more; a bucket's padding, two chunks
against one, a chunk of two blocks of ``chunk`` positions and of one,
heads in groups that share ``B`` and ``C``, and the convolution's tail
across a chunk boundary.  One shape a function, toy widths: 8 heads of
64 in 2 groups (two tiles of two heads a group), 32 state values, 4
taps over 640 convolved channels, blocks of 16 positions."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.ops import mamba, ssd

H, P, G, N, K, C, Q = 8, 64, 2, 32, 4, 32, 16
DI = H * P
CH = DI + 2 * G * N
PER_POSITION = ("xbc", "delta")
N_STATE = ssd.state_rows(H, P, N)
N_TAIL = ssd.tail_rows(CH, K)
ROWS = ssd.state_block_rows(H, P, G, N, K)
TOL = 5e-5


def rows_of(p, cut):
    return {k: (cut(v) if k in PER_POSITION else v) for k, v in p.items()}


def draw(seed, c=C):
    r = np.random.default_rng(seed)
    f = np.float32
    return {"xbc": r.normal(size=(c, CH)).astype(f),
            "delta": np.exp(r.uniform(np.log(1e-3), np.log(0.3),
                                      (c, H))).astype(f),
            "w": (0.5 * r.normal(size=(K, CH))).astype(f),
            "bias": (0.1 * r.normal(size=(CH,))).astype(f),
            "a_log": np.log(r.uniform(1, 16, (H,))).astype(f),
            "s0": r.normal(size=(H, P, N)).astype(f),
            "tail": r.normal(size=(K - 1, CH)).astype(f)}


def split(c):
    return c[..., :DI], c[..., DI:DI + G * N], c[..., DI + G * N:]


@jax.jit
def equations(p, s0, tail):
    """The module docstring's equations, a position and a head at a
    time."""
    a_neg = -jnp.exp(p["a_log"])

    def one(carry, xs):
        s, tail = carry
        x, dl = xs
        win = jnp.concatenate([tail, x[None]], axis=0)        # [K, ch]
        c = jax.nn.silu(p["bias"] + (p["w"] * win).sum(axis=0))
        xs_, b, cc = split(c)
        bh = jnp.repeat(b.reshape(G, N), H // G, axis=0)      # [H, N]
        ch = jnp.repeat(cc.reshape(G, N), H // G, axis=0)
        s = jnp.exp(dl * a_neg)[:, None, None] * s \
            + (dl[:, None] * xs_.reshape(H, P))[..., None] * bh[:, None, :]
        y = (s * ch[:, None, :]).sum(axis=-1)
        return (s, win[1:]), (y.reshape(DI), c)
    (s, tail), (y, c) = jax.lax.scan(one, (s0, tail),
                                     (p["xbc"], p["delta"]))
    return y, c, s, tail


@functools.partial(jax.jit, static_argnames=("backend", "q"))
def chunk(p, s0, tail, n_valid, backend, q=Q):
    c, tail = mamba.conv_chunk(p["xbc"], tail, p["w"], p["bias"], n_valid)
    xs, b, cc = split(c)
    y, rows = ssd.ssd_scan(xs, p["delta"], b, cc, ssd.pack_state(s0),
                           p["a_log"], n_valid, groups=G, chunk=q,
                           backend=backend)
    return y, c, ssd.unpack_state(rows, P, N), tail


def test_the_layout_round_trips_and_puts_a_head_in_its_lanes():
    s = np.random.default_rng(0).normal(size=(3, H, P, N)).astype(np.float32)
    rows = np.asarray(ssd.pack_state(jnp.asarray(s)))
    assert rows.shape == (3, N_STATE, 128)
    # head 3 = tile 1, second half of the lanes; row n of the tile
    assert (rows[2, 1 * N + 5, 64:] == s[2, 3, :, 5]).all()
    assert (np.asarray(ssd.unpack_state(jnp.asarray(rows), P, N)) == s).all()


@pytest.mark.parametrize("backend,q", [("gather", Q), ("pallas", Q),
                                       ("pallas", 128)])
def test_the_chunk_scan_equals_the_equations(backend, q):
    """32 positions as two blocks of 16 (the state carried between
    them inside the kernel) and as one block shorter than ``chunk``."""
    p = draw(1)
    want = equations(p, p["s0"], p["tail"])
    got = chunk(p, p["s0"], p["tail"], C, backend, q)
    for g, w in zip(got, want):
        assert np.abs(np.asarray(g) - np.asarray(w)).max() < TOL


@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_a_buckets_padding_leaves_state_and_tail_alone(backend):
    """20 valid positions of 32 (the second block a quarter valid): the
    state and the tail are those after 20, whatever the padding holds;
    with no valid position at all they are the ones that came in."""
    p = draw(2)
    n = 20
    short = rows_of(p, lambda v: v[:n])
    want = equations(short, p["s0"], p["tail"])
    y, _, s, tail = chunk(p, p["s0"], p["tail"], n, backend)
    assert np.abs(np.asarray(y)[:n] - np.asarray(want[0])).max() < TOL
    assert np.abs(np.asarray(s) - np.asarray(want[2])).max() < TOL
    assert (np.asarray(tail) == p["xbc"][n - K + 1:n]).all()
    assert np.isfinite(np.asarray(y)).all()
    _, _, s_in, t_in = chunk(p, p["s0"], p["tail"], 0, backend)
    assert (np.asarray(s_in) == p["s0"]).all()
    assert (np.asarray(t_in) == p["tail"]).all()


def test_two_chunks_equal_one():
    """32 positions as one chunk and as 16 + 16 with the state and the
    tail carried: the convolution's window crosses the boundary."""
    p = draw(3)
    zeros_s, zeros_t = np.zeros_like(p["s0"]), np.zeros_like(p["tail"])
    whole = chunk(p, zeros_s, zeros_t, C, "pallas")

    def half(lo):
        return rows_of(p, lambda v: v[lo:lo + C // 2])
    halves = jax.jit(lambda a, b, s, t: (
        lambda first: (first, chunk.__wrapped__(b, first[2], first[3],
                                                C // 2, "pallas")))(
        chunk.__wrapped__(a, s, t, C // 2, "pallas")))
    first, second = halves(half(0), half(C // 2), zeros_s, zeros_t)
    y = np.concatenate([first[0], second[0]])
    assert np.abs(y - np.asarray(whole[0])).max() < TOL
    assert np.abs(np.asarray(second[2]) - np.asarray(whole[2])).max() < TOL
    assert (np.asarray(second[3]) == np.asarray(whole[3])).all()


def test_heads_of_a_group_share_b_and_c():
    """Group 1's ``B`` zeroed: heads 4-7 add nothing to their state,
    heads 0-3 are untouched."""
    p = draw(6)
    zeros_s = np.zeros_like(p["s0"])
    xs, b, cc = split(jnp.asarray(p["xbc"]))
    b = b.at[:, N:].set(0.0)
    _, rows = jax.jit(functools.partial(
        ssd.ssd_scan, groups=G, chunk=Q, backend="pallas"))(
        xs, p["delta"], b, cc, ssd.pack_state(zeros_s), p["a_log"], C)
    s = np.asarray(ssd.unpack_state(rows, P, N))
    assert (s[H // G:] == 0).all()
    assert (np.abs(s[:H // G]).max(axis=(1, 2)) > 0.1).all()


@functools.partial(jax.jit, static_argnames=("backend", "round_state"),
                   donate_argnums=0)
def slots(state, rows, p, backend, round_state=None):
    c, state = ssd.conv_step(state, rows, 1, p["xbc"], p["w"], p["bias"],
                             n_state=N_STATE, round_state=round_state,
                             backend=backend)
    xs, b, cc = split(c)
    y, state = ssd.ssd_step(state, rows, 1, xs, p["delta"], b, cc,
                            p["a_log"], groups=G, round_state=round_state,
                            backend=backend)
    return y, state


@pytest.mark.parametrize("backend", ["gather", "pallas"])
def test_the_slot_update_equals_the_equations(backend):
    """Four slots on rows 3, 0, 5, 2 of a 6-row, 2-layer state array,
    two steps in a row: layer 1's blocks of those rows move on as the
    equations say, every other block and the padding rows stay."""
    s = 4
    p = draw(4, c=2 * s)
    r = np.random.default_rng(9)
    state = np.zeros((6, 2, ROWS, 128), np.float32)
    state[:, :, :N_STATE + N_TAIL] = r.normal(
        size=(6, 2, N_STATE + N_TAIL, 128))
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
        s0 = ssd.unpack_state(jnp.asarray(state[row, 1, :N_STATE]), P, N)
        tail0 = state[row, 1, N_STATE:N_STATE + N_TAIL].reshape(K - 1, CH)
        y, _, s_end, tail = equations(one, s0, tail0)
        assert np.abs(np.stack([ys[0][i], ys[1][i]]) - np.asarray(y)).max() \
            < TOL
        assert np.abs(now[row, 1, :N_STATE]
                      - np.asarray(ssd.pack_state(s_end))).max() < TOL
        assert (now[row, 1, N_STATE:N_STATE + N_TAIL].reshape(K - 1, CH)
                == np.asarray(tail)).all()
    assert (now[:, 0] == state[:, 0]).all()
    assert (now[[1, 4], 1] == state[[1, 4], 1]).all()
    assert (now[rows, 1, N_STATE + N_TAIL:] == 0).all()


def test_the_control_rounds_the_state_to_bfloat16_values():
    p = draw(5, c=4)
    state = jnp.zeros((4, 2, ROWS, 128), jnp.float32)
    _, out = slots(state, np.arange(4, dtype=np.int32), p, "pallas",
                   round_state="bfloat16")
    s = np.asarray(out)[:, 1, :N_STATE + N_TAIL]
    assert (s == np.asarray(jnp.asarray(s).astype(jnp.bfloat16)
                            .astype(jnp.float32))).all()
    assert np.abs(s).max() > 0


def test_a_block_holds_whole_tiles():
    # the published widths: 8,192 rows of state, 240 of tail in a block
    # of 256
    assert ssd.state_block_rows(128, 64, 8, 128, 4) == 8192 + 256
    assert ROWS == 128 + 16
    with pytest.raises(ValueError, match="head_dim"):
        ssd.state_block_rows(8, 48, 2, 32, 4)
    with pytest.raises(ValueError, match="one group"):
        ssd.state_block_rows(8, 64, 8, 32, 4)
    with pytest.raises(ValueError, match="convolved channels"):
        ssd.state_block_rows(8, 64, 2, 16, 4)

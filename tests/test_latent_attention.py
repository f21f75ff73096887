"""``ops.latent_attention``: decode attention that reads the run of pages
the slots share once (ISSUE 35).  The Pallas path in interpret mode (the
shared pass, the own pass and the join, as ``models/hybrid.step`` calls
them through ``latent_attend_slots``) against one float32 pass of
``latent_attend_gather`` over each slot's whole table.  What Mosaic makes
of the kernel is ``tests/test_chip_compile.py``'s."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.ops import latent_attention as la

T, C = 4, 128                       # a key block is 8 pages = 32 keys
MP = 32             # whole blocks over the longest table (30 pages, qlo's)
BLOCK = la.PAGES_PER_STEP * T
PREFIX = np.arange(100, 100 + MP)   # the pages of one long prompt
OTHER = np.arange(20, 20 + MP)      # and of another
ARENA = 132                         # PREFIX's last page and no more
# x 4 heads: the shared pass stacks whole (16, 128) tiles, the own pass
# pads a slot's 4; cases of fewer slots are padded with idle ones
SLOTS = 8


# one compile a shape (4 or 5 heads), not one a case as when called bare
@functools.partial(jax.jit, static_argnames="backend")
def attend_slots(q, seen, shared, leader, arena, tables, backend):
    return la.latent_attend_slots(q, seen, shared, leader, arena, 1, tables,
                                  backend=backend)


def table(prefix, n_shared, own):
    """``n_shared`` pages of ``prefix``, then the slot's ``own``."""
    row = np.full((MP,), -1, np.int32)
    row[:n_shared] = prefix[:n_shared]
    row[n_shared:n_shared + len(own)] = own
    return row


# name -> (heads, [(table row, seen)] a slot, shared keys a slot expected)
def _cases():
    def own(slot, n):           # private pages, no two slots' alike
        return 70 + slot * 6 + np.arange(n)
    return {
        # 20 shared pages = 2.5 key blocks: two whole blocks are shared,
        # the half block goes with each slot's ragged tail
        "one_prefix_ragged_tails": (4, [
            (table(PREFIX, 20, own(0, 3)), 20 * T + 9),
            (table(PREFIX, 20, own(1, 0)), 20 * T),
            (table(PREFIX, 20, own(2, 5)), 25 * T),
            (table(PREFIX, 20, own(3, 1)), 20 * T + 1)],
            [64, 64, 64, 64]),
        "no_two_slots_share": (4, [
            (table(PREFIX, 0, own(0, 5)), 17),
            (table(PREFIX, 0, own(1, 6)), 6 * T),
            (table(PREFIX, 0, own(2, 2)), 5)],
            [0, 0, 0]),
        "another_prefix_and_an_idle_slot": (4, [
            (table(PREFIX, 16, own(0, 2)), 16 * T + 5),
            (table(OTHER, 16, own(1, 2)), 16 * T + 7),
            (np.full((MP,), -1, np.int32), 0),
            (table(PREFIX, 16, own(3, 4)), 20 * T - 1),
            (table(PREFIX, 16, own(4, 1)), 16 * T + 2)],
            [64, 0, 0, 64, 64]),
        # 13 shared pages: one whole block, 5 pages over
        "run_not_whole_blocks": (4, [
            (table(PREFIX, 13, own(0, 2)), 15 * T - 2),
            (table(PREFIX, 13, own(1, 4)), 17 * T),
            (table(PREFIX, 13, own(2, 1)), 13 * T + 1)],
            [32, 32, 32]),
        # a fork: slot 1 holds the leader's 24 pages and has seen 70
        # keys of them (its tail page part filled)
        "seen_ends_inside_the_run": (4, [
            (table(PREFIX, 24, own(0, 2)), 26 * T),
            (table(PREFIX, 24, []), 70),
            (table(PREFIX, 24, own(2, 1)), 24 * T + 3)],
            [64, 64, 64]),
        # the same fork among four: the third block has its three sharers
        "seen_ends_inside_the_run_of_three": (4, [
            (table(PREFIX, 24, own(0, 2)), 26 * T),
            (table(PREFIX, 24, []), 70),
            (table(PREFIX, 24, own(2, 1)), 24 * T + 3),
            (table(PREFIX, 24, own(3, 1)), 25 * T)],
            [96, 64, 96, 96]),
        # a block that two slots share is cheaper read twice in passes
        # of 32 rows than once in a pass of every slot's rows
        "two_sharers_are_too_few": (4, [
            (table(PREFIX, 16, own(0, 2)), 18 * T),
            (table(PREFIX, 16, own(1, 1)), 16 * T + 3),
            (table(OTHER, 16, own(2, 2)), 17 * T + 2)],
            [0, 0, 0]),
        # slot 0 ends with the shared run, to the key
        "nothing_of_its_own": (4, [
            (table(PREFIX, 16, []), 16 * T),
            (table(PREFIX, 16, own(1, 3)), 19 * T),
            (table(PREFIX, 16, own(2, 1)), 16 * T + 1)],
            [64, 64, 64]),
        # 5 heads: 5 rows a slot and 15 in the shared pass, not 16s
        "rows_not_sixteens": (5, [
            (table(PREFIX, 18, own(0, 2)), 19 * T + 1),
            (table(PREFIX, 18, own(1, 3)), 21 * T),
            (table(PREFIX, 18, own(2, 0)), 18 * T)],
            [64, 64, 64]),
        # two groups: the larger one's run is shared, the other's is not
        "two_prefixes_the_larger_wins": (4, [
            (table(OTHER, 16, own(0, 1)), 17 * T),
            (table(PREFIX, 16, own(1, 1)), 16 * T + 3),
            (table(OTHER, 16, own(2, 2)), 18 * T),
            (table(PREFIX, 16, own(3, 2)), 17 * T + 2),
            (table(PREFIX, 16, own(4, 3)), 19 * T)],
            [0, 64, 0, 64, 64]),
    }


CASES = _cases()


@pytest.fixture(scope="module")
def arena():
    rng = np.random.default_rng(35)
    return jnp.asarray(rng.normal(size=(2, ARENA, T, C)) * 0.5, jnp.bfloat16)


@pytest.mark.parametrize("name", sorted(CASES))
def test_shared_then_own_equals_one_pass(arena, name):
    heads, slots, want_shared = CASES[name]
    idle = [(np.full((MP,), -1, np.int32), 0)] * (SLOTS - len(slots))
    slots, want_shared = slots + idle, want_shared + [0] * len(idle)
    tables = np.stack([row for row, _ in slots])
    seen = np.asarray([n for _, n in slots], np.int32)
    leader, shared = la.shared_run(tables, seen, T)
    assert shared.tolist() == want_shared
    assert all(shared[i] <= seen[i] for i in range(len(slots)))
    rng = np.random.default_rng(len(name))
    # queries at bfloat16 values, as the kernel multiplies them
    q = jnp.asarray(rng.normal(size=(len(slots), heads, C)) * 0.4,
                    jnp.bfloat16).astype(jnp.float32)
    args = (q, jnp.asarray(seen), jnp.asarray(shared),
            jnp.asarray([leader], jnp.int32), arena, jnp.asarray(tables))
    got = attend_slots(*args, backend="pallas")
    qlen = jnp.broadcast_to(jnp.asarray(seen)[:, None, None],
                            (len(slots), heads, 1))
    want = la.latent_attend_gather(
        q, qlen, arena, 1, jnp.arange(len(slots)), jnp.asarray(tables))
    got, want = np.asarray(got), np.asarray(want)
    assert not np.isnan(got).any()
    # the probabilities multiply at bfloat16 on the kernel path
    assert np.abs(got - want).max() < 6e-3, np.abs(got - want).max()
    for i, n in enumerate(seen):
        assert n or not got[i].any()            # an idle slot gives 0
    # the same two passes without a kernel: float32 noise
    plain = attend_slots(*args, backend="gather")
    assert np.abs(np.asarray(plain) - want).max() < 2e-6
    # and what the counters will say
    visits, stood_in = la.page_visits(seen, shared, T)
    assert stood_in == sum(want_shared) // T
    own_blocks = [max(-(-int(n) // BLOCK) - int(s) // BLOCK, 1)
                  for n, s in zip(seen, shared)]
    assert visits == (sum(own_blocks) + max(max(want_shared) // BLOCK, 1)) \
        * la.PAGES_PER_STEP


@pytest.mark.parametrize("lower", [False, True])
def test_the_work_list_visits_every_block_with_a_visible_key_once(lower):
    """Rows of four: blocks ``[floor(min qlo), ceil(max qlen))`` over the
    rows with a key, one block for a row with none, nothing after the
    ``n`` live entries is stepped through."""
    qlen = np.asarray([[70, 33, 0, 1], [0, 0, 0, 0], [32, 32, 32, 32],
                       [200, 190, 180, 170]], np.int32)[:, :, None]
    qlo = np.asarray([[64, 0, 0, 0], [0, 0, 0, 0], [0, 0, 0, 0],
                      [96, 96, 128, 96]], np.int32)[:, :, None] \
        if lower else np.zeros_like(qlen)
    rows, blocks, n = la.work_list(jnp.asarray(qlo), jnp.asarray(qlen), 8,
                                   BLOCK)
    live = list(zip(np.asarray(rows)[:int(n)].tolist(),
                    np.asarray(blocks)[:int(n)].tolist()))
    want = [(0, 0), (0, 1), (0, 2), (1, 7), (2, 0)] \
        + [(3, b) for b in range(3 if lower else 0, 7)]
    assert live == want
    assert rows.shape == blocks.shape == (4 * 8 + 1,)
    assert int(np.asarray(blocks).max()) <= 7


@pytest.mark.parametrize("empty", ["neither", "first", "second", "both"])
def test_the_join_is_one_softmax(empty):
    """Two parts over disjoint keys joined against one pass over all of
    them; a part with no key drops out and rows with none give 0, no
    NaN."""
    rng = np.random.default_rng(3)
    s = jnp.asarray(rng.normal(size=(3, 5, 24)) * 4, jnp.float32)
    v = jnp.asarray(rng.normal(size=(3, 24, 16)), jnp.float32)
    cut = {"neither": (10, 24), "first": (0, 24), "second": (10, 10),
           "both": (0, 0)}[empty]

    def part(lo, hi):
        if lo == hi:
            return (jnp.zeros((3, 5, 16)), jnp.full((3, 5, 1), -jnp.inf),
                    jnp.zeros((3, 5, 1)))
        m = s[..., lo:hi].max(axis=-1, keepdims=True)
        p = jnp.exp(s[..., lo:hi] - m)
        return (jnp.einsum("rmk,rkc->rmc", p, v[:, lo:hi]), m,
                p.sum(axis=-1, keepdims=True))
    got = np.asarray(la.latent_join([part(0, cut[0]), part(cut[0], cut[1])]))
    assert not np.isnan(got).any()
    if cut[1] == 0:
        assert not got.any()
        return
    want = jnp.einsum("rmk,rkc->rmc",
                      jax.nn.softmax(s[..., :cut[1]], axis=-1), v[:, :cut[1]])
    assert np.abs(got - np.asarray(want)).max() < 1e-5


def test_a_lower_bound_alone_matches_the_gather(arena):
    """``qlo`` on a plain call (no join): keys below it are not
    attended, the blocks below every row's are not visited."""
    tables = np.stack([table(PREFIX, 30, [])] * 2)
    qlen = np.asarray([[117, 90, 117], [64, 64, 0]], np.int32)[:, :, None]
    qlo = np.asarray([[70, 64, 100], [0, 33, 0]], np.int32)[:, :, None]
    q = jnp.asarray(np.random.default_rng(4).normal(size=(2, 3, C)) * 0.4,
                    jnp.bfloat16).astype(jnp.float32)
    args = (q, jnp.asarray(qlen), arena, 0, jnp.asarray([0, 1]),
            jnp.asarray(tables))
    got = la.latent_attend(*args, qlo=jnp.asarray(qlo), backend="pallas")
    want = la.latent_attend(*args, qlo=jnp.asarray(qlo), backend="gather")
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 6e-3
    assert not np.asarray(got)[1, 2].any()
    whole = la.latent_attend(*args, backend="gather")
    assert np.abs(np.asarray(whole) - np.asarray(want)).max() > 1e-2

"""Sharded embedding / parameter-server service (brpc_tpu/psserve;
ISSUE 12 — ROADMAP item 1's PartitionChannel flagship).

The acceptance bar: sharded Lookup/Update through PSClient is
BIT-IDENTICAL to a single-host dense gather/scatter oracle at every
partition count in {1, 2, 4, 8} on the virtual 8-device mesh, including
keys that straddle shard boundaries and duplicate keys in one request.
Integer-valued float32 grads make scatter-add order-invariant, so the
comparisons are exact (a separate random-grads test bounds float
reassociation at allclose tolerance).
"""
import threading
import time

import numpy as np
import pytest

import brpc_tpu as brpc
from brpc_tpu import errors, fault
from brpc_tpu.psserve import (EmbeddingShardServer, PSClient,
                              ShardedEmbeddingTable, init_embedding_table,
                              owners_for, register_psserve, shard_bounds,
                              unregister_psserve)
from brpc_tpu.rpc.combo_channels import PartitionChannel

V, D = 64, 8
PARTS = (1, 2, 4, 8)
# duplicates, shard-boundary straddles (31|32 at p=2), first/last rows
KEYS = np.array([0, 5, 5, 31, 32, 63, 7, 5, 16, 48], np.int64)


def _oracle():
    import jax.numpy as jnp
    return jnp.asarray(init_embedding_table(V, D, seed=3))


def _int_grads(n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(-3, 4, (n, D)).astype(np.float32)


# ---- ownership map ----

def test_shard_bounds_cover_and_partition():
    for n in (1, 2, 3, 5, 8):
        b = shard_bounds(V, n)
        assert b[0][0] == 0 and b[-1][1] == V
        for (l0, h0), (l1, h1) in zip(b, b[1:]):
            assert h0 == l1 and h0 > l0
        sizes = [h - l for l, h in b]
        assert max(sizes) - min(sizes) <= 1


def test_owners_for_straddle_and_dups():
    b = shard_bounds(V, 4)      # 16 rows each
    owner = owners_for(np.array([0, 15, 16, 31, 32, 63, 16]), b)
    assert owner.tolist() == [0, 0, 1, 1, 2, 3, 1]


# ---- collective lowering (co-located mesh) ----

@pytest.mark.parametrize("p", PARTS)
@pytest.mark.parametrize("mode", ["psum", "ring"])
def test_lowered_bit_identical_to_dense_oracle(p, mode):
    import jax
    if len(jax.devices()) < 8:
        pytest.skip("needs the virtual 8-device mesh")
    dense = _oracle()
    grads = _int_grads(KEYS.size)
    t = ShardedEmbeddingTable(V, D, n_shards=p, seed=3, mode=mode)
    rows, _ = t.lookup(KEYS)
    np.testing.assert_array_equal(rows, np.asarray(dense[KEYS]))
    t.update(KEYS, grads)
    import jax.numpy as jnp
    want = np.asarray(dense.at[KEYS].add(jnp.asarray(grads)))
    np.testing.assert_array_equal(t.snapshot(), want)
    # read-your-writes: the lookup AFTER the update sees the new rows
    rows2, ver = t.lookup(KEYS)
    np.testing.assert_array_equal(rows2, want[KEYS])
    assert ver == 1


def test_lowered_one_compile_per_bucket():
    t = ShardedEmbeddingTable(V, D, n_shards=4, seed=3,
                              key_buckets=(8, 32))
    for n in (3, 5, 8, 2):      # all pad to the 8 bucket
        t.lookup(np.arange(n, dtype=np.int64))
    assert t._lookup_psum._cache_size() == 1
    t.lookup(np.arange(20, dtype=np.int64))   # the 32 bucket
    assert t._lookup_psum._cache_size() == 2


def test_lowered_random_grads_allclose():
    dense = _oracle()
    rng = np.random.default_rng(7)
    grads = rng.standard_normal((KEYS.size, D)).astype(np.float32)
    t = ShardedEmbeddingTable(V, D, n_shards=4, seed=3)
    t.update(KEYS, grads)
    import jax.numpy as jnp
    want = np.asarray(dense.at[KEYS].add(jnp.asarray(grads)))
    np.testing.assert_allclose(t.snapshot(), want, rtol=0, atol=1e-6)


# ---- shard server (direct, no RPC) ----

@pytest.mark.parametrize("p", PARTS)
def test_shard_servers_match_oracle(p):
    import jax.numpy as jnp
    dense = _oracle()
    grads = _int_grads(KEYS.size)
    shards = [EmbeddingShardServer(i, p, V, D, seed=3) for i in range(p)]
    owner = owners_for(KEYS, shard_bounds(V, p))
    rows = np.empty((KEYS.size, D), np.float32)
    for s in range(p):
        pos = np.flatnonzero(owner == s)
        if pos.size:
            r, _ = shards[s].lookup(KEYS[pos])
            rows[pos] = r
    np.testing.assert_array_equal(rows, np.asarray(dense[KEYS]))
    for s in range(p):
        pos = np.flatnonzero(owner == s)
        if pos.size:
            ver, dup = shards[s].update(KEYS[pos], grads[pos],
                                        update_id=100 + s)
            assert not dup and ver == 1
    got = np.concatenate([sh.snapshot_rows() for sh in shards])
    want = np.asarray(dense.at[KEYS].add(jnp.asarray(grads)))
    np.testing.assert_array_equal(got, want)


def test_shard_update_idempotent_by_update_id():
    sh = EmbeddingShardServer(0, 1, V, D, seed=3)
    grads = _int_grads(3, seed=5)
    keys = np.array([1, 2, 1], np.int64)
    v1, dup1 = sh.update(keys, grads, update_id=42)
    before = sh.snapshot_rows().copy()
    v2, dup2 = sh.update(keys, grads, update_id=42)   # retried sub-call
    assert (v1, dup1) == (1, False)
    assert (v2, dup2) == (1, True)        # original version, no re-add
    assert sh.version == 1
    np.testing.assert_array_equal(sh.snapshot_rows(), before)


def test_shard_rejects_unowned_keys():
    sh = EmbeddingShardServer(1, 2, V, D, seed=3)   # owns [32, 64)
    with pytest.raises(ValueError):
        sh.lookup(np.array([0], np.int64))


# ---- the RPC fan-out path (PartitionChannel + batchers) ----

def _spin_up(p, *, batch=True, max_delay_us=500, replicas=1, lb=None,
             table=None, eager=True):
    servers, svcs, shards = [], [], []
    pc = PartitionChannel(p, lb=lb)
    for i in range(p):
        sh = EmbeddingShardServer(i, p, V, D, seed=3, table=table,
                                  name=f"ps{id(pc)}")
        shards.append(sh)
        for _r in range(replicas):
            s = brpc.Server()
            svcs.append(register_psserve(s, sh, batch=batch,
                                         max_delay_us=max_delay_us,
                                         eager=eager,
                                         name=f"t{i}_{_r}_{id(pc)}"))
            s.start("127.0.0.1", 0)
            servers.append(s)
            # channel-level retry OFF: failures surface to
            # call_partitioned so the PARTITION-level retry (the new
            # machinery under test) is the one that heals them
            pc.add_partition(
                i, brpc.Channel(f"127.0.0.1:{s.port}", timeout_ms=5000,
                                max_retry=0),
                endpoint=f"127.0.0.1:{s.port}")
    cli = PSClient(pc, vocab=V, dim=D)
    return servers, svcs, shards, pc, cli


def _tear_down(servers, svcs, cli):
    for svc in svcs:
        unregister_psserve(svc)
    for s in servers:
        s.stop()
        s.join()
    cli.close()


@pytest.mark.parametrize("p", PARTS)
def test_psclient_bit_identical_through_rpc(p):
    import jax.numpy as jnp
    dense = _oracle()
    grads = _int_grads(KEYS.size)
    servers, svcs, shards, pc, cli = _spin_up(p)
    try:
        rows = cli.lookup(KEYS)
        np.testing.assert_array_equal(rows, np.asarray(dense[KEYS]))
        cli.update(KEYS, grads)
        got = np.concatenate([sh.snapshot_rows() for sh in shards])
        want = np.asarray(dense.at[KEYS].add(jnp.asarray(grads)))
        np.testing.assert_array_equal(got, want)
        # read-your-writes through the service: the next lookup serves
        # the updated rows and a version >= the acked one per shard
        rows2 = cli.lookup(KEYS)
        np.testing.assert_array_equal(rows2, want[KEYS])
        assert cli.n_stale_reads == 0
    finally:
        _tear_down(servers, svcs, cli)


@pytest.mark.parametrize("serializer", ["json", "tensorframe"])
def test_update_batcher_coalesces_and_applies_exactly_once(serializer):
    """Concurrent Update RPCs coalesce into shared scatter batches —
    the first non-generate workload the DynamicBatcher has coalesced —
    and every update applies exactly once, on BOTH wire formats (the
    float64-packed JSON batcher and the byte-record tensorframe one,
    ISSUE 13)."""
    import jax.numpy as jnp
    # INTEGER-valued base table: 32 sequential float32 adds onto a
    # non-integer base round differently than one base + 32g — with an
    # integer base every association is exact, so the comparison can
    # stay bit-identical
    base = np.round(init_embedding_table(V, D, seed=3) * 100)
    dense = jnp.asarray(base)
    # eager=False: these assertions pin the WINDOWED coalescing policy
    # (eager's cut-through makes batch counts timing-dependent)
    servers, svcs, shards, pc, cli = _spin_up(1, max_delay_us=20_000,
                                              table=base, eager=False)
    try:
        n_updates, n_threads = 4, 8
        grads = _int_grads(2, seed=9)
        keys = np.array([3, 9], np.int64)

        def worker():
            c = PSClient(pc, vocab=V, dim=D, serializer=serializer)
            for _ in range(n_updates):
                c.update(keys, grads)

        ts = [threading.Thread(target=worker) for _ in range(n_threads)]
        [t.start() for t in ts]
        [t.join(60) for t in ts]
        total = n_threads * n_updates
        assert shards[0].version == total
        want = np.asarray(dense.at[keys].add(
            jnp.asarray(grads) * float(total)))
        np.testing.assert_array_equal(shards[0].snapshot_rows(), want)
        # the batcher matching the wire format did the serving
        ub = svcs[0]._update_b if serializer == "json" \
            else svcs[0]._update_tb
        assert ub.n_completed.get_value() == total
        # coalescing actually happened: fewer batches than updates
        assert ub.n_batches.get_value() < total
    finally:
        _tear_down(servers, svcs, cli)


def test_lookup_batcher_coalesces_mixed_key_counts():
    dense = _oracle()
    servers, svcs, shards, pc, cli = _spin_up(2, max_delay_us=20_000,
                                              eager=False)
    try:
        results = {}

        def one(i, n):
            c = PSClient(pc, vocab=V, dim=D)
            ks = (np.arange(n, dtype=np.int64) * 7 + i) % V
            results[i] = (ks, c.lookup(ks))

        ts = [threading.Thread(target=one, args=(i, n))
              for i, n in enumerate((3, 8, 17, 5, 30, 2))]
        [t.start() for t in ts]
        [t.join(60) for t in ts]
        assert len(results) == 6
        for ks, rows in results.values():
            np.testing.assert_array_equal(rows, np.asarray(dense[ks]))
        lb_total = sum(svc._lookup_b.n_batches.get_value()
                       for svc in svcs)
        done = sum(svc._lookup_b.n_completed.get_value()
                   for svc in svcs)
        assert done >= 6 and lb_total < done
    finally:
        _tear_down(servers, svcs, cli)


# ---- what a served lookup owes the shard's books (PR 30) ----

def _book_calls(seed=11, n=9):
    """Seeded lookups with duplicates inside a call and across calls.
    Key 0 is never asked for: it is the row padding gathers."""
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 12, size=int(k)).astype(np.int64)
            for k in rng.integers(2, 30, size=n)]


def _queue_behind_held_slot(b, calls):
    """Run ``calls`` (thunks) so that they all wait behind the lookup
    batcher's held execution slot and leave as ONE batch."""
    from testutil import batcher_slot_held
    ts = [threading.Thread(target=c) for c in calls]
    with batcher_slot_held(b, len(calls)):
        [t.start() for t in ts]
    [t.join(60) for t in ts]
    assert not any(t.is_alive() for t in ts)


@pytest.mark.parametrize("path,wire", [
    ("batched", "json"), ("batched", "tensorframe"),
    ("bypass", "json"), ("bypass", "tensorframe"), ("direct", None)])
def test_lookup_books_equal_on_every_path_and_wire(path, wire):
    """The same lookups leave the same books however they were served:
    one batch (one vectorised pass), one idle bypass each, or straight
    ``shard.lookup``.  Every occurrence of a key is one read, every
    lookup one lookup, and padding (row 0) is never counted."""
    from brpc_tpu.bvar.variable import find_exposed
    from brpc_tpu.psserve.shard import LOOKUP_BATCH_COMPLETIONS

    def bvar(name):
        return find_exposed(name).get_value()

    calls = _book_calls()
    reads = {}
    for ks in calls:
        for k in ks.tolist():
            reads[k] = reads.get(k, 0) + 1
    want_hot = sorted(reads.items(), key=lambda kv: (-kv[1], kv[0]))
    dense = _oracle()
    servers, svcs, shards, pc, cli = _spin_up(1)
    sh, b = shards[0], svcs[0]._lookup_b
    before = {n: bvar(n) for n in ("psserve_lookups",
                                   "psserve_lookup_keys")}
    completions = LOOKUP_BATCH_COMPLETIONS.get_value()
    try:
        got = {}

        def served(i, ks):
            c = PSClient(pc, vocab=V, dim=D, serializer=wire)
            got[i] = c.lookup(ks)

        if path == "direct":
            for i, ks in enumerate(calls):
                got[i] = sh.lookup(ks)[0]
        elif path == "bypass":
            for i, ks in enumerate(calls):
                served(i, ks)
            assert b.n_bypassed.get_value() == len(calls)
            assert b.n_batches.get_value() == 0
        else:
            _queue_behind_held_slot(
                b, [lambda i=i, ks=ks: served(i, ks)
                    for i, ks in enumerate(calls)])
            assert b.n_batches.get_value() == 1
        for i, ks in enumerate(calls):
            np.testing.assert_array_equal(got[i], np.asarray(dense[ks]))
        assert sh.hot_keys(top=V) == want_hot
        assert sh.hot_keys(top=3) == want_hot[:3]
        assert sh.n_lookups == len(calls)
        assert bvar("psserve_lookups") - before["psserve_lookups"] \
            == len(calls)
        assert bvar("psserve_lookup_keys") - before["psserve_lookup_keys"] \
            == sum(ks.size for ks in calls)
        st = sh.stats()
        batches = 1 if path == "batched" else 0
        assert LOOKUP_BATCH_COMPLETIONS.get_value() - completions == batches
        assert st["lookup_batch_completions"] == batches
        assert st["lookups_per_completion"] == \
            (len(calls) if batches else None)
        assert st["hot_keys"] == want_hot[:10]
    finally:
        _tear_down(servers, svcs, cli)


@pytest.mark.parametrize("wire", ["json", "tensorframe"])
def test_update_between_gather_and_completion_keeps_the_batch_version(wire):
    """The version a batch's replies carry is the one its gather ran at
    (read under the shard lock), not the shard's at completion: an
    update that lands in between changes neither the rows nor the
    version the members are told."""
    table = init_embedding_table(V, D, seed=3)
    sh = EmbeddingShardServer(0, 1, V, D, seed=3, name=f"ps_gap_{wire}")
    gather = sh.lookup_batch_fn

    def gather_then_update(padded):
        out = gather(padded)
        sh.update(np.array([5], np.int64), np.ones((1, D), np.float32))
        return out

    sh.lookup_batch_fn = gather_then_update  # before the service takes it
    s = brpc.Server()
    svc = register_psserve(s, sh, name=f"gap_{wire}")
    s.start("127.0.0.1", 0)
    pc = PartitionChannel(1)
    pc.add_partition(0, brpc.Channel(f"127.0.0.1:{s.port}",
                                     timeout_ms=5000, max_retry=0))
    cli = PSClient(pc, vocab=V, dim=D, serializer=wire)
    try:
        got = {}

        def one(i):
            c = PSClient(pc, vocab=V, dim=D, serializer=wire)
            got[i] = c.lookup_versioned(np.array([5, 9, 5], np.int64))

        _queue_behind_held_slot(svc._lookup_b,
                                [lambda i=i: one(i) for i in range(3)])
        assert sh.version == 1          # the update did land
        for rows, versions in got.values():
            assert versions == {0: 0}
            np.testing.assert_array_equal(rows, table[[5, 9, 5]])
        # an idle lookup afterwards (bypass) sees the update
        rows, versions = cli.lookup_versioned(np.array([5], np.int64))
        assert versions == {0: 1}
        np.testing.assert_array_equal(rows, table[[5]] + 1.0)
    finally:
        _tear_down([s], [svc], cli)


def test_partition_retry_rotates_replica_under_lb():
    """lb= parity (ISSUE 8's SelectiveChannel surface on
    PartitionChannel): two replicas per partition, one dead — the
    fan-out retries the OTHER replica and the call succeeds."""
    dense = _oracle()
    servers, svcs, shards, pc, cli = _spin_up(2, lb="rr")
    try:
        # add a DEAD replica to each partition: some attempts pick it
        # first and must rotate
        for i in range(2):
            pc.add_partition(
                i, brpc.Channel("127.0.0.1:1", timeout_ms=300,
                                max_retry=0),
                endpoint="127.0.0.1:1")
        for _ in range(4):
            rows = cli.lookup(KEYS)
            np.testing.assert_array_equal(rows, np.asarray(dense[KEYS]))
        # pick/feedback surface answers per partition
        picked = pc.pick(0)
        assert picked is not None
        _i, _ch, ep = picked
        pc.feedback(0, ep, 0, 100)
    finally:
        _tear_down(servers, svcs, cli)


def test_retry_budget_exceeds_replica_count_via_rotation_reset():
    """ISSUE-13 regression (async round-based call_partitioned): a
    partition whose replicas ALL failed transiently must keep retrying
    up to max_retry+1 total attempts — the per-round exclusion set
    resets once every replica was tried, matching the old per-attempt
    driver's fresh-exclusion semantics (without the reset, the budget
    silently capped at the replica count)."""
    calls = {"n": 0}

    class Flaky(brpc.Service):
        NAME = "FlakyPS"

        @brpc.method(request="json", response="json")
        def Get(self, cntl, req):
            calls["n"] += 1
            if calls["n"] <= 3:
                cntl.set_failed(errors.EINTERNAL, "transient")
                return None
            return {"ok": True}

    svc = Flaky()       # ONE instance behind both replicas
    servers = []
    pc = PartitionChannel(1, lb="rr")
    for _ in range(2):
        s = brpc.Server()
        s.add_service(svc)
        s.start("127.0.0.1", 0)
        servers.append(s)
        pc.add_partition(0, brpc.Channel(f"127.0.0.1:{s.port}",
                                         timeout_ms=2000, max_retry=0),
                         endpoint=f"127.0.0.1:{s.port}")
    try:
        # 2 replicas, first 3 attempts fail: only a 4th attempt (a
        # SECOND rotation over the replicas) can succeed
        out = pc.call_partitioned("FlakyPS", "Get", {0: {}},
                                  timeout_ms=2000, max_retry=3)
        assert out[0]["ok"] is True
        assert calls["n"] == 4
    finally:
        for s in servers:
            s.stop()
            s.join()
        pc.close()


def test_injected_post_apply_fault_retries_without_double_add():
    """The chaos scenario's core in miniature: the ack drops AFTER the
    apply; the client's retried sub-call must dedup by update_id."""
    import jax.numpy as jnp
    dense = _oracle()
    servers, svcs, shards, pc, cli = _spin_up(2)
    grads = _int_grads(KEYS.size, seed=11)
    plan = fault.FaultPlan(seed=0)
    plan.on("psserve.update", "error", times=1,
            match=lambda ctx: ctx.get("stage") == "post")
    try:
        with fault.injected(plan):
            cli.update(KEYS, grads)
        got = np.concatenate([sh.snapshot_rows() for sh in shards])
        want = np.asarray(dense.at[KEYS].add(jnp.asarray(grads)))
        np.testing.assert_array_equal(got, want)
        assert cli.n_retries >= 1
        assert sum(sh.n_dup_updates for sh in shards) >= 1
        assert all(sh.version == 1 for sh in shards
                   if sh.n_updates + sh.n_dup_updates > 0)
    finally:
        _tear_down(servers, svcs, cli)


def test_dense_pull_push_idempotent():
    servers, svcs, shards, pc, cli = _spin_up(2)
    try:
        owner = cli._owner_of("w_out")
        shards[owner]._dense["w_out"] = np.zeros((4,), np.float32)
        cli.push("w_out", np.ones((4,), np.float32))
        np.testing.assert_array_equal(cli.pull("w_out"),
                                      np.ones((4,), np.float32))
        # unknown param is a definite error, not a hang
        with pytest.raises(errors.RpcError):
            cli.pull("nope")
    finally:
        _tear_down(servers, svcs, cli)


def test_psserve_snapshot_renders():
    servers, svcs, shards, pc, cli = _spin_up(2)
    try:
        cli.lookup(KEYS)
        from brpc_tpu.psserve import psserve_snapshot
        snap = psserve_snapshot()
        assert len(snap["shards"]) >= 2
        ours = [s for s in snap["shards"]
                if s["name"] == shards[0].name
                and s["shard_index"] == 0]
        assert len(ours) == 1 and ours[0]["rows"] == 32
        assert any("batchers" in s for s in snap["shards"])
        assert any(c["lookups"] >= 1 for c in snap["clients"])
        assert all(s["hot_keys"] == sorted(
            s["hot_keys"], key=lambda kv: -kv[1]) for s in snap["shards"])
    finally:
        _tear_down(servers, svcs, cli)


def test_intra_batch_duplicate_update_ids_apply_once():
    """Review fix: a retry can land in the SAME coalesced batch as its
    original (reply lost before the batch formed) — both rows pass the
    applied-set check, so dedup must also work WITHIN the batch."""
    base = np.round(init_embedding_table(V, D, seed=3) * 100)
    sh = EmbeddingShardServer(0, 1, V, D, table=base, key_buckets=(8,))
    grads = _int_grads(2, seed=13)
    keys = np.array([4, 9], np.int64)
    before = sh.snapshot_rows().copy()
    row = EmbeddingShardServer.pack_update(777, keys, grads)
    other = EmbeddingShardServer.pack_update(778, keys, grads)
    Lb = sh.update_length_buckets()[0]
    padded = np.zeros((4, Lb), np.float64)
    padded[0, :len(row)] = row
    padded[1, :len(row)] = row        # the in-window retry
    padded[2, :len(other)] = other    # an unrelated update
    acks = sh.update_batch_fn(padded)
    # original applied once, retry acked as duplicate with the SAME
    # version, unrelated row applied
    assert acks[0].tolist() == [1.0, 0.0]
    assert acks[1].tolist() == [1.0, 1.0]
    assert acks[2].tolist() == [2.0, 0.0]
    assert sh.version == 2 and sh.n_dup_updates == 1
    import jax.numpy as jnp
    want = np.asarray(jnp.asarray(before).at[keys].add(
        jnp.asarray(grads) * 2.0))
    np.testing.assert_array_equal(sh.snapshot_rows(), want)


def test_service_rejects_out_of_range_update_ids():
    """Review fix: update_id=0 is the batch-padding sentinel — a wire
    caller sending it must get a loud EREQUEST, not a success-shaped
    ack for an update that was silently discarded."""
    servers, svcs, shards, pc, cli = _spin_up(1)
    try:
        ch = brpc.Channel(f"127.0.0.1:{servers[0].port}",
                          timeout_ms=5000, max_retry=0)
        for bad in (0, -3, (1 << 53) + 2, "nope"):
            with pytest.raises(errors.RpcError) as ei:
                ch.call_sync("PS", "Update",
                             {"keys": [1], "grads": [[0.0] * D],
                              "update_id": bad}, serializer="json")
            assert ei.value.code == errors.EREQUEST, bad
        assert shards[0].version == 0
        # 2**53 itself is float64-exact and is PSClient's max mintable
        # id — the boundary is INCLUSIVE
        r = ch.call_sync("PS", "Update",
                         {"keys": [1], "grads": [[0.0] * D],
                          "update_id": 1 << 53}, serializer="json")
        assert r["version"] == 1 and not r["duplicate"]
    finally:
        _tear_down(servers, svcs, cli)


def test_client_update_validates_keys_locally():
    """Review fix: update() validates key range like lookup() — a
    clear local ValueError, not max_retry spins on a permanent server
    error (or ENODATA for a negative key's partition)."""
    servers, svcs, shards, pc, cli = _spin_up(2)
    try:
        for bad in (np.array([-1], np.int64), np.array([V], np.int64)):
            with pytest.raises(ValueError):
                cli.update(bad, np.zeros((1, D), np.float32))
        assert cli.n_retries == 0
    finally:
        _tear_down(servers, svcs, cli)


def test_update_ids_unique_across_many_clients():
    """Review fix: id sequence is process-wide (salt + counter), so
    client-construction churn can never reissue a live id."""
    from brpc_tpu.psserve.client import _next_uid_seq
    seen = {_next_uid_seq() for _ in range(5000)}
    assert len(seen) == 5000


def test_lowered_backend_not_bound_by_update_id_shard_cap():
    """Review fix: the 32-shard update_id cap protects the RPC path
    only — a lowered backend (which never mints ids) may span more
    chips."""
    class _FakeLowered:
        p = 64

        def lookup(self, keys):
            import numpy as _np
            return _np.zeros((len(keys), D), _np.float32), 0

    cli = PSClient(_FakeLowered(), vocab=V, dim=D)
    assert cli.n_shards == 64


def test_partial_fanout_failure_token_replay_no_double_add():
    """Review fix: one partition down past retries -> update() raises
    with ``update_token``; replaying the SAME logical update with the
    token dedups on the partition that already applied."""
    import jax.numpy as jnp
    base = np.round(init_embedding_table(V, D, seed=3) * 100)
    dense = jnp.asarray(base)
    servers, svcs, shards, pc, cli = _spin_up(2, table=base)
    grads = _int_grads(KEYS.size, seed=21)
    try:
        # partition 1 hard-down: every Update sub-call to it fails
        plan = fault.FaultPlan(seed=0)
        plan.on("psserve.update", fault.ERROR, times=-1,
                match=lambda ctx: ctx.get("shard") == 1
                and ctx.get("stage") == "pre")
        with fault.injected(plan):
            with pytest.raises(errors.RpcError) as ei:
                cli.update(KEYS, grads)
        token = ei.value.update_token
        assert token is not None
        assert 1 in getattr(ei.value, "failed_partitions", {})
        # partition 0 already applied exactly once
        assert shards[0].version == 1
        # caller replays the SAME logical update once healed
        acks = cli.update(KEYS, grads, update_token=token)
        assert set(acks) == {0, 1}
        assert shards[0].version == 1, "token replay double-applied!"
        assert shards[0].n_dup_updates >= 1
        assert shards[1].version == 1
        got = np.concatenate([sh.snapshot_rows() for sh in shards])
        want = np.asarray(dense.at[KEYS].add(jnp.asarray(grads)))
        np.testing.assert_array_equal(got, want)
    finally:
        _tear_down(servers, svcs, cli)


def test_permanent_errors_not_retried_and_code_preserved():
    """Review fix: EREQUEST/ENODATA are deterministic — call_partitioned
    must not burn retries on them, and the caller must see the REAL
    code, not a generic ETOOMANYFAILS."""
    servers, svcs, shards, pc, cli = _spin_up(2)
    try:
        with pytest.raises(errors.RpcError) as ei:
            cli.pull("no_such_param")
        assert ei.value.code == errors.ENODATA
        assert cli.n_retries == 0
    finally:
        _tear_down(servers, svcs, cli)


def test_oversize_key_set_is_erequest_not_einternal():
    """Review fix: more keys than the largest bucket is a bad request
    on BOTH server paths (batched: batcher admission; unbatched: the
    shard's bucket check), never an EINTERNAL crash retried to
    ETOOMANYFAILS."""
    big = np.arange(V, dtype=np.int64).repeat(10)[:600] % V   # > 512
    for batch in (True, False):
        servers, svcs, shards, pc, cli = _spin_up(1, batch=batch)
        try:
            ch = brpc.Channel(f"127.0.0.1:{servers[0].port}",
                              timeout_ms=5000, max_retry=0)
            with pytest.raises(errors.RpcError) as ei:
                ch.call_sync("PS", "Lookup", {"keys": big.tolist()},
                             serializer="json")
            assert ei.value.code == errors.EREQUEST, batch
            with pytest.raises(errors.RpcError) as ei:
                ch.call_sync("PS", "Update",
                             {"keys": big.tolist(),
                              "grads": [[0.0] * D] * big.size,
                              "update_id": 5},
                             serializer="json")
            assert ei.value.code == errors.EREQUEST, batch
        finally:
            _tear_down(servers, svcs, cli)

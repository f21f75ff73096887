"""Training plane tests (ISSUE 17).

Covers, in order:
  * OptimizerSpec: validation, json-wire and tensorframe-field
    round-trips;
  * BIT-IDENTITY of the fused co-located optimizer: sgdm AND adam
    driven through ``PS.Update`` against partitions {1, 2, 4} land
    EXACTLY the dense single-host oracle's table and slots — same for
    the lowered ShardedEmbeddingTable under its ownership mask;
  * retried-wave dedup: an ack dropped AFTER the fused apply
    (``psserve.opt_apply`` post stage) heals by update_token replay
    and the momentum steps exactly once;
  * DataParallelTrainer: loss decreases THROUGH the service
    (Pull-based eval), injected ``train.update_wave`` faults heal via
    wave retry with exactly-once counters intact, bounded-staleness
    gate excuses a dead worker;
  * TrafficArbiter: a synthetic pressure ramp fires the rungs
    cheapest-first (first_fired strictly ordered, trainer rungs before
    any serving action), admit_wave paces then sheds then releases,
    brownout/clamp actions apply and revert;
  * the mixed-shape harness end to end: zipf lookups + streamed
    generations + trainer waves on ONE fleet with every invariant
    green (exactly-once, RYW, bit-exact generations, queues drained,
    pools at baseline);
  * Score adopter: ScoreT on the binary wire, byte-identical to the
    json path, with sticky ENOMETHOD downgrade against an old peer.
"""
import threading
import time

import numpy as np
import pytest

import brpc_tpu as brpc
from brpc_tpu import errors, fault
from brpc_tpu.psserve import (EmbeddingShardServer, PSClient,
                              ShardedEmbeddingTable, init_embedding_table,
                              register_psserve, unregister_psserve)
from brpc_tpu.rpc.combo_channels import PartitionChannel
from brpc_tpu.train import OptimizerSpec, oracle_apply
from brpc_tpu.train.optimizer import zero_slots
from brpc_tpu.train.trainer import DataParallelTrainer
from brpc_tpu.train.arbiter import (ARBITER_LEVEL_NAMES,
                                    MixedWorkloadHarness, TrafficArbiter)

from testutil import wait_until

V, D = 48, 8


def _int_table(seed=3):
    # integer-valued float32 everywhere: float addition is exact, so
    # bit-identity claims are order-proof
    return np.round(init_embedding_table(V, D, seed=seed) * 100)


def _int_grads(rng, n):
    return rng.integers(-3, 4, (n, D)).astype(np.float32)


def _fleet(n_shards, table, max_retry=2):
    shards, servers, svcs = [], [], []
    pc = PartitionChannel(n_shards)
    for i in range(n_shards):
        sh = EmbeddingShardServer(i, n_shards, V, D, table=table,
                                  name="t17_ps")
        shards.append(sh)
        s = brpc.Server()
        svcs.append(register_psserve(s, sh, name=f"t17_{i}"))
        s.start("127.0.0.1", 0)
        servers.append(s)
        pc.add_partition(i, brpc.Channel(f"127.0.0.1:{s.port}",
                                         timeout_ms=5000))
    cli = PSClient(pc, vocab=V, dim=D, max_retry=max_retry,
                   name=f"t17_cli_{n_shards}")
    return shards, servers, svcs, pc, cli


def _tear_down(servers, svcs, pc):
    for svc in svcs:
        unregister_psserve(svc)
    for srv in servers:
        srv.stop()
        srv.join()
    pc.close()


# ---------------------------------------------------------------------------
# OptimizerSpec
# ---------------------------------------------------------------------------

def test_optimizer_spec_validation_and_wire_round_trips():
    with pytest.raises(ValueError):
        OptimizerSpec("rmsprop")
    with pytest.raises(ValueError):
        OptimizerSpec("sgdm", lr=float("nan"))
    with pytest.raises(ValueError):
        OptimizerSpec.from_wire({"kind": "sgdm", "lr": "fast"})

    sgdm = OptimizerSpec("sgdm", lr=0.25, momentum=0.75)
    assert OptimizerSpec.from_wire(sgdm.to_wire()) == sgdm
    assert sgdm.slot_names() == ("m",)

    adam = OptimizerSpec("adam", lr=0.01, beta1=0.8, beta2=0.99,
                         eps=1e-6)
    assert OptimizerSpec.from_wire(adam.to_wire()) == adam
    assert adam.slot_names() == ("m", "v", "t")

    # tensorframe flattening: flat opt_* scalar fields, no nesting
    frame = adam.to_frame_fields()
    assert frame["opt_kind"] == "adam"
    assert OptimizerSpec.from_frame_fields(frame) == adam
    assert OptimizerSpec.from_frame_fields({"keys": None}) is None


# ---------------------------------------------------------------------------
# bit-identity: fused co-located optimizer == dense single-host oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["sgdm", "adam"])
@pytest.mark.parametrize("p", [1, 2, 4])
def test_fused_optimizer_bit_identity_rpc(kind, p):
    """ISSUE 17 acceptance: sgdm/adam through PS.Update against
    {1,2,4} partitions land bit-identical table AND slots to the dense
    oracle — duplicate keys, padding and per-row adam step counts
    included."""
    spec = OptimizerSpec(kind, lr=0.5, momentum=0.5, beta1=0.5,
                         beta2=0.75, eps=1.0)
    base = _int_table()
    shards, servers, svcs, pc, cli = _fleet(p, base)
    rng = np.random.default_rng(17 + p)
    want_t, want_s = base.copy(), zero_slots(spec, V, D)
    try:
        for _ in range(4):
            # duplicate keys in-wave exercise the scatter accumulate
            keys = rng.integers(0, V, size=9).astype(np.int64)
            grads = _int_grads(rng, 9)
            cli.update(keys, grads, optimizer=spec)
            want_t, want_s = oracle_apply(want_t, want_s, keys, grads,
                                          spec)
        got_t = np.concatenate([sh.snapshot_rows() for sh in shards])
        np.testing.assert_array_equal(got_t, want_t)
        for name in spec.slot_names():
            got_s = np.concatenate(
                [sh.snapshot_slots()[name] for sh in shards])
            np.testing.assert_array_equal(
                got_s, want_s[name],
                err_msg=f"slot {name!r} diverged from oracle")
    finally:
        _tear_down(servers, svcs, pc)


@pytest.mark.parametrize("kind", ["sgdm", "adam"])
@pytest.mark.parametrize("p", [1, 2, 4])
def test_fused_optimizer_bit_identity_lowered(kind, p):
    """The same fused update under the lowered table's ownership mask
    (shard_map over the tp mesh) — bit-identical to the oracle, and a
    replayed update_id dedups without touching momentum."""
    spec = OptimizerSpec(kind, lr=0.5, momentum=0.5, beta1=0.5,
                         beta2=0.75, eps=1.0)
    base = _int_table()
    t = ShardedEmbeddingTable(V, D, n_shards=p, table=base,
                              name=f"t17_low_{kind}_{p}")
    rng = np.random.default_rng(34 + p)
    want_t, want_s = base.copy(), zero_slots(spec, V, D)
    for step in range(3):
        keys = rng.integers(0, V, size=7).astype(np.int64)
        grads = _int_grads(rng, 7)
        t.update(keys, grads, update_id=100 + step, optimizer=spec)
        want_t, want_s = oracle_apply(want_t, want_s, keys, grads, spec)
    # replay the last wave: the applied set must swallow it whole
    ver = t.version
    t.update(keys, grads, update_id=102, optimizer=spec)
    assert t.version == ver
    rows, _ = t.lookup(np.arange(V, dtype=np.int64))
    np.testing.assert_array_equal(np.asarray(rows), want_t)
    slots = t.snapshot_slots()
    for name in spec.slot_names():
        np.testing.assert_array_equal(slots[name], want_s[name])


def test_retried_wave_steps_momentum_exactly_once():
    """An ack dropped AFTER the fused apply (psserve.opt_apply post
    stage) surfaces as a failed wave carrying its update_token; the
    replay dedups on the applied-id set — version AND momentum advance
    exactly once, bit-identical to a single oracle apply."""
    spec = OptimizerSpec("sgdm", lr=0.5, momentum=0.5)
    base = _int_table()
    shards, servers, svcs, pc, cli = _fleet(2, base, max_retry=0)
    rng = np.random.default_rng(7)
    keys = rng.integers(0, V, size=8).astype(np.int64)
    grads = _int_grads(rng, 8)
    plan = fault.FaultPlan(0)
    plan.on("psserve.opt_apply", fault.ERROR, times=1,
            match=lambda ctx: ctx.get("stage") == "post")
    try:
        with fault.injected(plan):
            tok = None
            for _ in range(4):
                try:
                    cli.update(keys, grads, update_token=tok,
                               optimizer=spec)
                    break
                except errors.RpcError as e:
                    tok = e.update_token
            else:
                pytest.fail("wave never healed")
        assert sum(plan.injected.values()) == 1
        want_t, want_s = oracle_apply(base.copy(),
                                      zero_slots(spec, V, D),
                                      keys, grads, spec)
        got_t = np.concatenate([sh.snapshot_rows() for sh in shards])
        np.testing.assert_array_equal(got_t, want_t)
        got_m = np.concatenate(
            [sh.snapshot_slots()["m"] for sh in shards])
        np.testing.assert_array_equal(got_m, want_s["m"])
        # the replayed partition served its ack from the applied set
        assert sum(sh.version for sh in shards) == \
            sum(sh.n_updates for sh in shards)
        assert sum(sh.n_dup_updates for sh in shards) >= 1
    finally:
        _tear_down(servers, svcs, pc)


# ---------------------------------------------------------------------------
# DataParallelTrainer
# ---------------------------------------------------------------------------

def _trainer_fleet(n_shards=2, seed=0, **tr_kw):
    cfg_trainer = DataParallelTrainer
    embed0, dense0 = cfg_trainer.model_init(_cfg(), seed=seed)
    shards, servers, svcs, pc, cli = _fleet(n_shards, embed0)
    tr = DataParallelTrainer(cli, _cfg(), seed=seed, **tr_kw)
    tr.seed_dense(dense0)
    return tr, shards, servers, svcs, pc


def _cfg():
    from brpc_tpu.models.parameter_server import PSConfig
    return PSConfig(vocab=V, d_model=D, d_ff=2 * D, n_layers=2,
                    seq=8, batch=4)


def test_trainer_loss_decreases_through_service():
    tr, shards, servers, svcs, pc = _trainer_fleet(
        n_workers=2, steps=5,
        optimizer=OptimizerSpec("sgdm", lr=0.5, momentum=0.5))
    try:
        rep = tr.run()
        assert rep["loss_final"] < rep["loss_first"], rep
        assert rep["steps_done"] == 10 and rep["waves"] == 10
        assert rep["stale_reads"] == 0
        for sh in shards:
            assert sh.version == sh.n_updates + sh.n_pushes
    finally:
        _tear_down(servers, svcs, pc)


def test_trainer_wave_faults_heal_exactly_once():
    """Injected update_wave failures force token replays; every shard
    still advances once per DISTINCT wave and training completes."""
    tr, shards, servers, svcs, pc = _trainer_fleet(
        n_workers=2, steps=4, wave_max_retry=4, retry_backoff_s=0.01)
    plan = fault.FaultPlan(1)
    plan.on("train.update_wave", fault.ERROR, times=3)
    try:
        with fault.injected(plan):
            rep = tr.run()
        assert sum(plan.injected.values()) == 3
        assert rep["wave_retries"] >= 3
        assert rep["waves"] == 8
        assert rep["stale_reads"] == 0
        for sh in shards:
            assert sh.version == sh.n_updates + sh.n_pushes
    finally:
        _tear_down(servers, svcs, pc)


def test_trainer_gate_excuses_dead_worker():
    """max_lag=0 is a per-step barrier; a worker that dies mid-run is
    excused so the remaining workers drain instead of wedging."""
    tr, shards, servers, svcs, pc = _trainer_fleet(
        n_workers=2, steps=3, sync=True)
    plan = fault.FaultPlan(0)
    # worker 1 dies on its second wave (retries exhausted immediately)
    plan.on("train.update_wave", fault.ERROR, times=-1, after=1,
            match=lambda ctx: ctx.get("worker") == 1)
    tr.wave_max_retry = 0
    tr.retry_backoff_s = 0.0
    try:
        with fault.injected(plan):
            with pytest.raises(errors.RpcError):
                tr.run()
        # worker 0 drained to completion despite the barrier
        assert tr._progress[0] == 3
    finally:
        _tear_down(servers, svcs, pc)


# ---------------------------------------------------------------------------
# TrafficArbiter
# ---------------------------------------------------------------------------

class _FakeBatcher:
    brownout = 0


class _FakeEngine:
    degraded_clamp = None


def test_arbiter_ramp_fires_cheapest_first():
    """A pressure ramp walks the ladder pace_trainer -> shed_trainer
    -> brownout -> clamp; first_fired ticks are STRICTLY ordered, so
    the trainer rungs provably absorb overload before any serving
    component is touched."""
    b, e = _FakeBatcher(), _FakeEngine()
    arb = TrafficArbiter(batchers=[b], engines=[e],
                         hysteresis_ticks=2, pace_delay_s=0.0,
                         shed_poll_s=0.005)
    assert arb.tick({"queue_delay_us": 0.0}) == 0
    assert arb.admit_wave() is False            # calm: free admission
    assert arb.tick({"queue_delay_us": 20_000.0}) == 1
    assert arb.admit_wave() is True             # paced, not refused
    assert b.brownout == 0 and e.degraded_clamp is None
    assert arb.tick({"queue_delay_us": 60_000.0}) == 2
    assert b.brownout == 0, "serving touched before trainer shed"
    assert arb.tick({"queue_delay_us": 200_000.0}) == 3
    assert b.brownout >= 1 and e.degraded_clamp is None
    assert arb.tick({"queue_delay_us": 600_000.0}) == 4
    assert e.degraded_clamp is not None
    ff = arb.ladder.first_fired[1:]
    assert None not in ff and ff == sorted(ff) and len(set(ff)) == 4
    assert arb.ladder.level_names == ARBITER_LEVEL_NAMES
    # calm ticks de-escalate and REVERT the serving actions
    for _ in range(20):
        arb.tick({"queue_delay_us": 0.0})
    assert arb.ladder.level == 0
    assert b.brownout == 0 and e.degraded_clamp is None
    st = arb.stats()
    assert st["paced_waves"] == 1 and st["brownouts"] == 1 \
        and st["clamps"] == 1


def test_arbiter_shed_blocks_waves_until_calm():
    arb = TrafficArbiter(hysteresis_ticks=1, shed_poll_s=0.005,
                         pace_delay_s=0.0)
    arb.tick({"queue_delay_us": 60_000.0})
    assert arb.ladder.level == 2
    out = {}

    def wave():
        out["paced"] = arb.admit_wave()

    t = threading.Thread(target=wave, daemon=True)
    t.start()
    time.sleep(0.08)
    assert "paced" not in out, "wave admitted while shed"
    assert arb.stats()["shed_waves"] == 1
    while arb.ladder.level >= 2:        # hysteretic walk-down
        arb.tick({"queue_delay_us": 0.0})
    t.join(5)
    assert out.get("paced") is True
    assert arb.stats()["admitted_waves"] == 1


def test_arbiter_shed_timeout_surfaces_elimit():
    arb = TrafficArbiter(shed_poll_s=0.005, shed_timeout_s=0.05)
    arb.tick({"queue_delay_us": 60_000.0})
    with pytest.raises(errors.RpcError) as ei:
        arb.admit_wave()
    assert ei.value.code == errors.ELIMIT


# ---------------------------------------------------------------------------
# the mixed-shape fleet
# ---------------------------------------------------------------------------

def test_mixed_harness_all_shapes_one_fleet():
    """ISSUE 17 tentpole (c): zipf lookups + streamed generations +
    trainer waves on ONE fleet, arbitrated — every invariant green."""
    h = MixedWorkloadHarness(n_shards=2, vocab=V, dim=D, n_replicas=1,
                             lookup_workers=1, gen_workers=1,
                             gen_tokens=8, train_workers=2,
                             train_steps=3, seed=0, name="t17mix")
    try:
        rep = h.run()
    finally:
        h.close()
    assert all(rep["exactly_once"]), rep["shards"]
    assert rep["stale_reads"] == 0
    assert rep["queues_drained"] and rep["pools_at_baseline"]
    gen = rep["shapes"]["generate"]
    assert gen["ok"] > 0 and gen["mismatch"] == 0
    assert gen["bit_exact"] == gen["ok"]
    assert rep["shapes"]["lookup"]["ok"] > 0
    assert rep["train"]["waves"] == 6
    assert rep["train"]["loss_final"] < rep["train"]["loss_first"]


def test_harness_pressure_is_the_delay_since_the_last_look():
    """One slow batch reads over the shed rung ONCE; a recorder's standing
    p99 over a handful of samples read over it for good, and the trainer's
    waves were shed to their 30 s deadline (ROADMAP D21)."""
    from types import SimpleNamespace as NS
    from brpc_tpu.bvar.recorder import LatencyRecorder
    rec = LatencyRecorder()
    b = NS(stats=lambda: {"queued": 0}, queue_delay_rec=rec)
    h = NS(ps_svcs=[NS(_lookup_b=b)], replicas=[], _delay_seen={})

    def look():
        return MixedWorkloadHarness._pressures(h).get("queue_delay_us", 0.0)
    rec << 80_000
    assert look() > 50_000          # over the shed rung, once
    assert look() == 0.0            # nothing waited since
    rec << 1_000
    rec << 3_000
    assert look() == 2_000          # the new samples alone


# ---------------------------------------------------------------------------
# Score adopter (ISSUE 17 satellite a)
# ---------------------------------------------------------------------------

def test_score_binary_wire_byte_identical_and_negotiates():
    import jax

    from brpc_tpu.serving import (DynamicBatcher, ScoreClient,
                                  ServingService, register_serving)

    fn = jax.jit(lambda x: x * 2.0 + 1.0)
    b = DynamicBatcher(fn, max_batch_size=4, max_delay_us=500,
                       length_buckets=(16,), name="t17score")
    srv = brpc.Server()
    register_serving(srv, batcher=b)
    srv.start("127.0.0.1", 0)

    class _OldServing(ServingService):
        ScoreT = None       # an old peer: binary method unregistered

    b2 = DynamicBatcher(fn, max_batch_size=4, max_delay_us=500,
                        length_buckets=(16,), name="t17score_old")
    srv_old = brpc.Server()
    srv_old.add_service(_OldServing(b2))
    srv_old.start("127.0.0.1", 0)
    try:
        x = [1.5, -2.0, 3.25]
        ch = brpc.Channel(f"127.0.0.1:{srv.port}", timeout_ms=5000)
        sc = ScoreClient(ch)
        y_frame = sc.score(x)
        assert sc.wire_mode == "frame"
        assert sc.n_negotiation_fallbacks == 0
        y_json = np.asarray(
            ch.call_sync("Serving", "Score", {"x": x},
                         serializer="json")["y"], np.float32)
        # regression pin: both wire formats decode byte-identical rows
        assert y_frame.tobytes() == y_json.tobytes()

        ch_old = brpc.Channel(f"127.0.0.1:{srv_old.port}",
                              timeout_ms=5000)
        sc_old = ScoreClient(ch_old)
        y_old = sc_old.score(x)
        assert sc_old.wire_mode == "json"       # sticky downgrade
        assert sc_old.n_negotiation_fallbacks == 1
        assert y_old.tobytes() == y_frame.tobytes()
        sc_old.score(x)                         # stays downgraded
        assert sc_old.n_negotiation_fallbacks == 1
    finally:
        srv.stop()
        srv.join()
        srv_old.stop()
        srv_old.join()
        b.close()
        b2.close()

"""Engine supervision & crash recovery (brpc_tpu/serving/supervisor.py).

The serving stack's failure domain: a DecodeEngine step loop that
crashes or wedges mid-decode.  The EngineSupervisor must detect it
(crash handler / dead thread / stalled heartbeat), rebuild the engine
against the SAME KVCacheStore, and re-admit every in-flight request
resuming from its last emitted token — exactly-once emission, bit-exact
streams, prefill-skip over the committed prefix pages.  Plus the
overload degradation ladder and the flapping-replica quarantine wiring
into circuit_breaker/health_check.

`make recovery` runs exactly this file.
"""
import threading
import time

import numpy as np
import pytest

from brpc_tpu import errors, fault
from brpc_tpu.kvcache import KVCacheStore
from brpc_tpu.serving import DecodeEngine, DynamicBatcher, EngineSupervisor

from testutil import wait_until


@pytest.fixture(autouse=True)
def _hygiene():
    """Never leak an installed fault plan or broken-endpoint state."""
    from brpc_tpu.policy import health_check as hc
    fault.clear()
    yield
    fault.clear()
    hc.reset_all()


def _mk_step():
    """Position-dependent jitted step: the resumed decode is bit-exact
    iff the supervisor restores the exact (last token, position)."""
    import jax

    @jax.jit
    def step(tokens, positions, pages):
        return (tokens * 7 + positions) % 997
    return step


# ladder thresholds no realistic test burst can cross: the crash tests
# isolate RECOVERY behavior from the (separately-tested) overload ladder
CALM_LADDER = ({"queue_delay_us": float("inf"), "pool_ratio": 9.9,
                "queue_depth": 1e9},) * 3


def _expected(prompt, n):
    last, pos, out = prompt[-1], len(prompt), []
    for _ in range(n):
        last = (last * 7 + pos) % 997
        out.append(last)
        pos += 1
    return out


def _submit_wave(sup, prompts, max_new):
    """Submit prompts; returns (events, token-lists, error-box-lists)."""
    sinks = []
    for p in prompts:
        ev = threading.Event()
        toks: list = []
        errs: list = []
        sinks.append((ev, toks, errs))
        sup.submit(p, max_new, toks.append,
                   lambda e, ev=ev, errs=errs: (errs.append(e), ev.set()))
    return sinks


class TestCrashRecovery:
    def test_crash_mid_decode_recovers_bit_exact(self):
        store = KVCacheStore(page_tokens=4, page_bytes=256, max_blocks=32,
                             name="sup_cr_kv")
        step = _mk_step()
        sup = EngineSupervisor(
            lambda: DecodeEngine(step, num_slots=3, store=store,
                                 max_pages_per_slot=32, name="sup_cr_eng"),
            store=store, heartbeat_deadline_s=5.0, check_interval_s=0.02,
            ladder=CALM_LADDER, name="sup_cr")
        try:
            # warm the jit cache so the crash scheduling is deterministic
            done = threading.Event()
            sup.submit([1, 2, 3, 4, 5], 2, lambda t: None,
                       lambda e: done.set())
            assert done.wait(30)
            shared = list(range(20, 28))         # two full pages
            plan = fault.FaultPlan(11).on("serving.step", fault.ERROR,
                                          times=1, after=2)
            prompts = [shared + [100 + i] for i in range(6)]
            with fault.injected(plan):
                sinks = _submit_wave(sup, prompts, 6)
                for ev, _, _ in sinks:
                    assert ev.wait(30), "request hung across the restart"
            assert plan.injected["serving.step"] == 1
            # exactly-once, bit-exact: no dropped and no duplicated
            # token at the restart seam, terminal fired once each
            for (ev, toks, errs), p in zip(sinks, prompts):
                assert errs == [None], errs
                assert toks == _expected(p, 6), (toks, _expected(p, 6))
            st = sup.stats()
            assert st["restarts"] == 1
            assert st["state"] == "healthy"
            assert st["last_recovery"]["stolen_slots"] >= 1
            assert st["readmitted"] >= 1
            # recovery pins released, nothing live
            assert sup.join_idle(10)
        finally:
            sup.close()
            store.clear()
            assert store.pagepool.blocks_leased() == 0
            store.close()

    def test_wedge_detected_via_heartbeat_and_taken_over_live(self):
        """A loop that RUNS but reports no progress (serving.heartbeat
        suppressed) is indistinguishable from a wedge — the supervisor
        must take over the live loop without the old loop leaking a
        single duplicate token into the re-admitted stream."""
        store = KVCacheStore(page_tokens=4, page_bytes=256, max_blocks=32,
                             name="sup_wg_kv")

        def slow_step(tokens, positions, pages):
            time.sleep(0.03)            # ~30ms/step: decode outlives the
            return np.asarray(tokens) + 1   # watchdog deadline below

        sup = EngineSupervisor(
            lambda: DecodeEngine(slow_step, num_slots=2, store=store,
                                 max_pages_per_slot=64,
                                 pass_page_table=True, name="sup_wg_eng"),
            store=store, heartbeat_deadline_s=0.3, check_interval_s=0.05,
            ladder=CALM_LADDER, name="sup_wg")
        try:
            plan = fault.FaultPlan(5).on("serving.heartbeat", fault.ERROR,
                                         times=-1)
            toks: list = []
            ev = threading.Event()
            errbox: list = []
            with fault.injected(plan):
                sup.submit([5, 6, 7, 8], 20, toks.append,
                           lambda e: (errbox.append(e), ev.set()))
                assert ev.wait(60), "request hung under simulated wedge"
            assert errbox == [None]
            assert toks == list(range(9, 29)), toks   # exactly once each
            assert sup.stats()["restarts"] >= 1
            assert "wedged" in sup.stats()["last_recovery"]["reason"]
        finally:
            sup.close()
            store.clear()
            store.close()

    def test_raw_block_mode_full_replay_exactly_once(self):
        """Without a KV store there is nothing to re-attach: recovery
        degrades to a full replay (prompt + emitted re-prefilled) but
        the emission contract is identical — exactly once, bit-exact."""
        import jax

        @jax.jit
        def step(tokens, positions):      # 2-arg: raw-block contract
            return (tokens * 7 + positions) % 997

        sup = EngineSupervisor(
            lambda: DecodeEngine(step, num_slots=2, kv_bytes_per_slot=512,
                                 name="sup_rb_eng"),
            heartbeat_deadline_s=5.0, check_interval_s=0.02,
            ladder=CALM_LADDER, name="sup_rb")
        try:
            done = threading.Event()
            sup.submit([1, 2], 1, lambda t: None, lambda e: done.set())
            assert done.wait(30)
            plan = fault.FaultPlan(3).on("serving.step", fault.ERROR,
                                         times=1, after=1)
            prompts = [[40 + i, 41 + i, 42 + i] for i in range(4)]
            with fault.injected(plan):
                sinks = _submit_wave(sup, prompts, 5)
                for ev, _, _ in sinks:
                    assert ev.wait(30)
            for (ev, toks, errs), p in zip(sinks, prompts):
                assert errs == [None]
                assert toks == _expected(p, 5)
            assert sup.stats()["restarts"] == 1
        finally:
            sup.close()

    def test_gives_up_after_max_restarts_with_definite_errors(self):
        """A permanently-broken engine must fail fast: past the restart
        budget the supervisor stops rebuilding and every pending
        request gets a definite error — never an infinite
        crash/rebuild/crash loop, never a hang."""
        store = KVCacheStore(page_tokens=4, page_bytes=256, max_blocks=16,
                             name="sup_gu_kv")
        step = _mk_step()
        sup = EngineSupervisor(
            lambda: DecodeEngine(step, num_slots=2, store=store,
                                 max_pages_per_slot=32, name="sup_gu_eng"),
            store=store, heartbeat_deadline_s=5.0, check_interval_s=0.02,
            max_restarts=2, restart_window_s=60.0, ladder=CALM_LADDER,
            name="sup_gu")
        try:
            done = threading.Event()
            sup.submit([1, 2, 3], 1, lambda t: None, lambda e: done.set())
            assert done.wait(30)
            plan = fault.FaultPlan(9).on("serving.step", fault.ERROR,
                                         times=-1)   # crash EVERY step
            ev = threading.Event()
            errbox: list = []
            with fault.injected(plan):
                sup.submit([9, 9, 9, 9], 8, lambda t: None,
                           lambda e: (errbox.append(e), ev.set()))
                assert ev.wait(60), "request hung after supervisor gave up"
                # a request still queued at a takeover is answered ELOGOFF
                # before the budget is spent: keep the engine stepping
                deadline = time.monotonic() + 60
                while sup.stats()["state"] != "failed" and \
                        time.monotonic() < deadline:
                    again = threading.Event()
                    sup.submit([9, 9, 9, 9], 8, lambda t: None,
                               lambda e: again.set())
                    assert again.wait(60), "request hung mid-restart"
            assert errbox and errbox[0] is not None
            assert errbox[0].code in (errors.EINTERNAL, errors.ELOGOFF)
            assert sup.stats()["state"] == "failed"
            # and a NEW submission is refused definitively too
            ev2 = threading.Event()
            errs2: list = []
            sup.submit([1], 1, lambda t: None,
                       lambda e: (errs2.append(e), ev2.set()))
            assert ev2.wait(10)
            assert errs2[0] is not None
        finally:
            sup.close()
            store.clear()
            store.close()


class TestDegradationLadder:
    def _mk(self, **kw):
        store = KVCacheStore(page_tokens=4, page_bytes=256, max_blocks=16,
                             name=kw.pop("store_name", "sup_lad_kv"))
        batcher = DynamicBatcher(lambda x: np.asarray(x).sum(axis=1),
                                 max_batch_size=4, max_delay_us=500,
                                 length_buckets=(16,),
                                 name=kw.pop("batcher_name", "sup_lad_b"))
        step = _mk_step()
        sup = EngineSupervisor(
            lambda: DecodeEngine(step, num_slots=2, store=store,
                                 max_pages_per_slot=32,
                                 name=kw.pop("eng_name", "sup_lad_eng")),
            store=store, batcher=batcher, check_interval_s=10.0,
            clamp_new_tokens=7, hysteresis_ticks=2,
            name=kw.pop("name", "sup_lad"), **kw)
        return store, batcher, sup

    def test_ladder_escalates_and_applies_actions(self, monkeypatch):
        store, batcher, sup = self._mk()
        try:
            ev0 = store.evictions.get_value()
            pressure = {"queue_delay_us": 0.0, "pool_ratio": 0.0,
                        "queue_depth": 0.0}
            monkeypatch.setattr(sup, "_pressures", lambda: dict(pressure))
            sup._update_degradation()
            assert sup.level == 0 and batcher.brownout == 0
            assert sup.engine.degraded_clamp is None
            # level 1: queue delay crosses the shed threshold
            pressure["queue_delay_us"] = 60_000.0
            sup._update_degradation()
            assert sup.level == 1
            assert batcher.brownout == 1
            assert sup.engine.degraded_clamp is None
            assert sup.state == "degraded"
            # level 3 directly (escalation is immediate): pool pressure
            pressure["pool_ratio"] = 0.99
            sup._update_degradation()
            assert sup.level == 3
            assert sup.engine.degraded_clamp == 7
            # a new submission is clamped to the brownout budget
            ev = threading.Event()
            toks: list = []
            sup.submit([1, 2, 3, 4, 5], 50, toks.append,
                       lambda e: ev.set())
            assert ev.wait(30)
            assert len(toks) == 7, f"clamp not applied: {len(toks)} tokens"
            # level 3 evicts cached pages each tick (seed the cache
            # first so there is something to evict)
            done = threading.Event()
            sup.submit(list(range(60, 72)), 1, lambda t: None,
                       lambda e: done.set())
            assert done.wait(30)
            assert sup.join_idle(10)
            sup._update_degradation()
            assert store.evictions.get_value() > ev0, \
                "aggressive eviction never fired at level 3"
            # de-escalation needs hysteresis_ticks calm ticks PER level
            pressure.update(queue_delay_us=0.0, pool_ratio=0.0)
            sup._update_degradation()
            assert sup.level == 3, "de-escalated without hysteresis"
            sup._update_degradation()
            assert sup.level == 2
            for _ in range(4):
                sup._update_degradation()
            assert sup.level == 0
            assert batcher.brownout == 0
            assert sup.engine.degraded_clamp is None
            assert sup.state == "healthy"
        finally:
            sup.close()
            batcher.close()
            store.clear()
            store.close()

    def test_brownout_sheds_lowest_lane_only(self):
        """Level >= 1: deadline-less requests (the lowest EDF lane) are
        refused at admission with ELIMIT; deadlined requests sail
        through."""
        batcher = DynamicBatcher(lambda x: np.asarray(x).sum(axis=1),
                                 max_batch_size=4, max_delay_us=500,
                                 length_buckets=(16,), name="sup_bo_b")
        try:
            shed0 = batcher.brownout_shed.get_value()
            batcher.brownout = 1
            with pytest.raises(errors.RpcError) as ei:
                batcher.submit_wait([1.0, 2.0], timeout_s=5.0)
            assert ei.value.code == errors.ELIMIT
            assert "brownout" in ei.value.text
            assert batcher.brownout_shed.get_value() == shed0 + 1
            # the deadlined lane still serves
            out = batcher.submit_wait(
                [1.0, 2.0], timeout_s=5.0,
                deadline_s=time.monotonic() + 5.0)
            assert float(out) == 3.0
            batcher.brownout = 0
            assert float(batcher.submit_wait([2.0, 2.0],
                                             timeout_s=5.0)) == 4.0
        finally:
            batcher.close()


class TestFlappingQuarantine:
    def test_repeated_crashes_quarantine_endpoint_and_remap_share(self):
        """Crashes feed the circuit breaker; past quarantine_after the
        replica's endpoint is marked broken, and prefix_affinity remaps
        ONLY the quarantined replica's share of prefixes (consistent
        hashing keeps everyone else's warm caches)."""
        from brpc_tpu.butil.endpoint import str2endpoint
        from brpc_tpu.policy import health_check as hc
        from brpc_tpu.policy.circuit_breaker import global_breaker
        from brpc_tpu.policy.load_balancer import (PrefixAffinityLB,
                                                   ServerNode)

        eps = [str2endpoint(f"127.0.0.1:{41000 + i}") for i in range(3)]
        victim = eps[0]
        lb = PrefixAffinityLB()
        lb.reset_servers([ServerNode(ep) for ep in eps])
        prompts = [[i, i + 1, i + 2, i + 3] for i in range(60)]
        before = {tuple(p): lb.select_for_prompt(p) for p in prompts}
        assert set(before.values()) == set(eps), "ring did not spread"

        store = KVCacheStore(page_tokens=4, page_bytes=256, max_blocks=16,
                             name="sup_qr_kv")
        step = _mk_step()
        sup = EngineSupervisor(
            lambda: DecodeEngine(step, num_slots=2, store=store,
                                 max_pages_per_slot=32, name="sup_qr_eng"),
            store=store, heartbeat_deadline_s=5.0, check_interval_s=0.02,
            max_restarts=6, quarantine_after=3, endpoint=victim,
            ladder=CALM_LADDER, name="sup_qr")
        try:
            done = threading.Event()
            sup.submit([1, 2, 3], 1, lambda t: None, lambda e: done.set())
            assert done.wait(30)
            iso0 = global_breaker().isolation_count(victim)
            # three crashes: one per engine incarnation
            plan = fault.FaultPlan(17).on("serving.step", fault.ERROR,
                                          times=3)
            ev = threading.Event()
            toks: list = []
            errbox: list = []
            with fault.injected(plan):
                sup.submit([30, 31, 32, 33], 6, toks.append,
                           lambda e: (errbox.append(e), ev.set()))
                assert ev.wait(60)
            assert errbox == [None]
            assert toks == _expected([30, 31, 32, 33], 6)
            assert sup.stats()["restarts"] == 3
            # quarantined: breaker counted every crash, endpoint broken
            assert global_breaker().isolation_count(victim) >= iso0 + 3
            assert hc.is_broken(victim)
            assert sup.stats()["quarantined"] is True
            # prefix_affinity: every prefix previously on a HEALTHY
            # replica keeps its replica (warm caches intact); the
            # victim's share lands on survivors
            after = {tuple(p): lb.select_for_prompt(p) for p in prompts}
            for key, ep in before.items():
                if ep != victim:
                    assert after[key] == ep, \
                        "healthy replica's prefix remapped"
                else:
                    assert after[key] != victim, \
                        "quarantined replica still selected"
        finally:
            sup.close()
            store.clear()
            store.close()
            hc.reset_all()


class TestClaimRetryRegression:
    def test_claim_retry_is_atomic_per_attempt(self):
        """Two failure paths racing to retry the same attempt must
        resolve to exactly ONE retry chain (the cluster-retry deflake:
        the loser used to issue a doomed extra attempt that excluded
        every server and failed the call)."""
        from brpc_tpu.rpc.controller import Controller
        cntl = Controller()
        wins = []
        barrier = threading.Barrier(2)

        def claim():
            barrier.wait()
            wins.append(cntl.claim_retry(0))

        ts = [threading.Thread(target=claim) for _ in range(2)]
        [t.start() for t in ts]
        [t.join(5) for t in ts]
        assert sorted(wins) == [False, True]
        assert cntl.current_attempt == 1
        assert cntl.retried_count == 1
        # stale owners can never claim
        assert cntl.claim_retry(0) is False
        # completion closes the door entirely
        assert cntl._try_complete()
        assert cntl.claim_retry(1) is False

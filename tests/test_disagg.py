"""Disaggregated prefill/decode + cross-process failover tests
(ISSUE 7 tentpole b and c).

Covers, in order:
  * the split topology end-to-end: a DisaggCoordinator pairs a prefill
    server and a decode server over DcnChannel, the prefill replica
    admits+prefills and streams finished pages, and the decode engine's
    admission prefix-hits them — tokens bit-exact, only the admission
    cap's final positions re-decode;
  * the prefill side reuses the batching stack (concurrent Prefill
    RPCs coalesce through a DynamicBatcher);
  * migration failure mid-disagg is a RECOMPUTE FALLBACK: the decode
    side prefills the suffix itself and the generation still completes
    bit-exact;
  * cross-process failover: a StandbySync write-ahead-streams cursors
    + live radix state to a StandbyReplica; killing the primary engine
    mid-generation yields an exactly-once, bit-exact stream completed
    by the standby (with the migrated prefix hit making the resume a
    partial re-decode, not a replay);
  * assume is exactly-once (a second assume is refused) and replays
    precisely the tokens the client's cursor says it never saw;
  * rpc_press --disagg drives the split topology.
"""
import json
import threading
import time

import jax
import numpy as np
import pytest

import brpc_tpu as brpc
from brpc_tpu import errors, fault, rpcz
from brpc_tpu.kvcache import KVCacheStore
from brpc_tpu.migrate import (DisaggCoordinator, StandbySync,
                              register_disagg_decode,
                              register_disagg_prefill, register_standby)
from brpc_tpu.migrate.disagg import assume_stream
from brpc_tpu.serving import DecodeEngine, DynamicBatcher

from testutil import wait_until

PT = 4
PB = 256


@jax.jit
def _step(tokens, positions, pages):
    # position-dependent: bit-exactness across the split (and the
    # failover seam) requires the exact (token, position) cursor
    return (tokens * 7 + positions) % 997


def _expected(prompt, n):
    last, pos, out = prompt[-1], len(prompt), []
    for _ in range(n):
        last = (last * 7 + pos) % 997
        out.append(last)
        pos += 1
    return out


def _mk_store(name, **kw):
    kw.setdefault("page_tokens", PT)
    kw.setdefault("page_bytes", PB)
    kw.setdefault("max_blocks", 32)
    return KVCacheStore(name=name, **kw)


@pytest.fixture()
def disagg_pair():
    """One prefill server + one decode server, in-process loopback."""
    dstore = _mk_store("dg_dec")
    eng = DecodeEngine(_step, num_slots=4, store=dstore,
                       max_pages_per_slot=32, name="dg_eng")
    dsrv = brpc.Server(enable_dcn=True)
    register_disagg_decode(dsrv, dstore, eng)
    dsrv.start("127.0.0.1", 0)
    decode_addr = f"127.0.0.1:{dsrv.port}"

    pstore = _mk_store("dg_pre")
    psrv = brpc.Server(enable_dcn=True)
    replica = register_disagg_prefill(psrv, pstore, decode_addr)
    psrv.start("127.0.0.1", 0)
    prefill_addr = f"127.0.0.1:{psrv.port}"
    yield (prefill_addr, decode_addr, replica, pstore, dstore, eng)
    eng.close()
    psrv.stop()
    psrv.join()
    dsrv.stop()
    dsrv.join()
    pstore.clear()
    pstore.close()
    dstore.clear()
    dstore.close()


def test_disagg_generation_bit_exact_with_page_handoff(disagg_pair):
    prefill_addr, decode_addr, replica, pstore, dstore, _ = disagg_pair
    co = DisaggCoordinator(prefill_addr, decode_addr)
    ta, tb = co.pair()
    assert ta["magic"] == "DCN1" and tb["magic"] == "DCN1"
    h0 = dstore.hit_tokens.get_value()
    prompt = list(range(50, 63))            # 13 tokens, 3 full pages
    streamed = []
    out = co.generate(prompt, 6, emit=streamed.append)
    assert out["error"] is None
    assert out["tokens"] == _expected(prompt, 6)
    assert streamed == out["tokens"]
    assert out["prefill"]["migrated_pages"] == 3
    assert out["prefill"]["recompute_fallback"] is False
    assert out["prefill"]["cursor"] == len(prompt)
    # the decode side prefix-hit the migrated pages: the full-page
    # prefix was never re-prefilled there
    assert dstore.hit_tokens.get_value() - h0 == 3 * PT
    assert replica.stats()["fallbacks"] == 0


def test_disagg_repeat_prompts_skip_prefill_side_too(disagg_pair):
    """A repeated prompt prefix-hits on the PREFILL side as well (its
    radix tree kept the pages), and the decode side stays warm."""
    prefill_addr, decode_addr, replica, pstore, dstore, _ = disagg_pair
    co = DisaggCoordinator(prefill_addr, decode_addr)
    prompt = list(range(70, 83))
    assert co.generate(prompt, 3)["error"] is None
    p0 = pstore.hit_tokens.get_value()
    out = co.generate(prompt, 3)
    assert out["error"] is None
    assert out["tokens"] == _expected(prompt, 3)
    assert out["prefill"]["prefix_hit"] >= 2 * PT
    assert pstore.hit_tokens.get_value() > p0


def test_disagg_prefill_reuses_batcher():
    """Concurrent Prefill RPCs coalesce through the caller's
    DynamicBatcher — the batching stack rides on the prefill side."""
    calls = []

    @jax.jit
    def prefill_fn(x):
        return x.sum(axis=-1)

    def counting_fn(x):
        calls.append(np.asarray(x).shape[0])
        return prefill_fn(x)

    batcher = DynamicBatcher(counting_fn, max_batch_size=8,
                             max_delay_us=30_000,
                             length_buckets=(16,), name="dg_prefill_b")
    dstore = _mk_store("dg_dec_b")
    eng = DecodeEngine(_step, num_slots=4, store=dstore,
                       max_pages_per_slot=32, name="dg_eng_b")
    dsrv = brpc.Server(enable_dcn=True)
    register_disagg_decode(dsrv, dstore, eng)
    dsrv.start("127.0.0.1", 0)
    pstore = _mk_store("dg_pre_b")
    psrv = brpc.Server(enable_dcn=True)
    register_disagg_prefill(psrv, pstore, f"127.0.0.1:{dsrv.port}",
                            batcher=batcher)
    psrv.start("127.0.0.1", 0)
    try:
        co = DisaggCoordinator(f"127.0.0.1:{psrv.port}",
                               f"127.0.0.1:{dsrv.port}")
        threads, outs = [], [None] * 4
        prompts = [[90 + 100 * i + j for j in range(9)] for i in range(4)]

        def run(i):
            outs[i] = co.generate(prompts[i], 3)

        for i in range(4):
            t = threading.Thread(target=run, args=(i,))
            threads.append(t)
            t.start()
        for t in threads:
            t.join(60)
        for i, out in enumerate(outs):
            assert out is not None and out["error"] is None
            assert out["tokens"] == _expected(prompts[i], 3)
        st = batcher.stats()
        assert st["completed"] == 4
        assert st["batches"] >= 1
    finally:
        eng.close()
        batcher.close()
        psrv.stop()
        psrv.join()
        dsrv.stop()
        dsrv.join()
        pstore.clear()
        pstore.close()
        dstore.clear()
        dstore.close()


def test_disagg_migration_failure_recompute_fallback(disagg_pair):
    """A dead page stream degrades to recompute: the prefill reply
    says so, the decode side admits cold, and the generation is still
    bit-exact — migration moves work, it cannot lose it."""
    prefill_addr, decode_addr, replica, pstore, dstore, _ = disagg_pair
    co = DisaggCoordinator(prefill_addr, decode_addr)
    prompt = list(range(110, 123))
    h0 = dstore.hit_tokens.get_value()
    plan = fault.FaultPlan(3).on("dcn.migrate_send", fault.ERROR,
                                 times=-1)
    with fault.injected(plan):
        out = co.generate(prompt, 5)
    assert out["error"] is None
    assert out["tokens"] == _expected(prompt, 5)
    assert out["prefill"]["recompute_fallback"] is True
    assert out["prefill"]["migrated_pages"] == 0
    assert dstore.hit_tokens.get_value() == h0   # cold admit: no hit
    assert replica.stats()["fallbacks"] == 1


# ---------------------------------------------------------------------------
# cross-process failover
# ---------------------------------------------------------------------------

@pytest.fixture()
def standby_pair():
    sstore = _mk_store("sb_store")
    seng = DecodeEngine(_step, num_slots=4, store=sstore,
                        max_pages_per_slot=32, name="sb_eng")
    ssrv = brpc.Server(enable_dcn=True)
    replica = register_standby(ssrv, sstore, seng)
    ssrv.start("127.0.0.1", 0)
    standby_addr = f"127.0.0.1:{ssrv.port}"

    def paced_step(tokens, positions, pages):
        # the tests kill the primary between two tokens: at full speed
        # the rest of a generation is microseconds away and a starved
        # main thread found it finished ("errs[0] is None")
        time.sleep(0.02)
        return _step(tokens, positions, pages)

    pstore = _mk_store("pr_store", commit_live_pages=True)
    peng = DecodeEngine(paced_step, num_slots=4, store=pstore,
                        max_pages_per_slot=32, name="pr_eng")
    sync = StandbySync(pstore, standby_addr, submit_fn=peng.submit,
                       name="pr_sync")
    yield sync, peng, pstore, replica, sstore, standby_addr
    sync.close()
    try:
        peng.close()
    except Exception:
        pass
    seng.close()
    ssrv.stop()
    ssrv.join()
    pstore.clear()
    pstore.close()
    sstore.clear()
    sstore.close()


def test_failover_completes_exactly_once_bit_exact(standby_pair):
    """Primary dies mid-generation; the client assumes on the standby
    with its own cursor and receives EXACTLY the missing tail — no
    duplicate, no gap, bit-exact — with the migrated pages making the
    resume a prefix hit, not a replay."""
    sync, peng, pstore, replica, sstore, standby_addr = standby_pair
    prompt = list(range(30, 43))            # 13 tokens
    budget = 10
    got, errs = [], []
    done = threading.Event()
    mid = threading.Event()

    def emit(tok):
        got.append(tok)
        if len(got) == 4:
            mid.set()

    sid = sync.submit(prompt, budget, emit,
                      lambda e: (errs.append(e), done.set()))
    assert mid.wait(30)
    peng.close()                 # the "process death"
    assert done.wait(30)
    assert errs[0] is not None and errs[0].code == errors.ELOGOFF
    n_before = len(got)
    assert 0 < n_before < budget, "crash window missed"
    sync.flush(10)

    out = assume_stream(standby_addr, sid, n_before)
    assert out["error"] is None
    full = got + out["tokens"]
    assert full == _expected(prompt, budget), \
        "stream not bit-exact across the failover seam"
    # write-ahead + cursor: the standby replayed/decoded exactly the
    # missing tail
    assert len(out["tokens"]) == budget - n_before
    # the shipped pages made the resume a PARTIAL re-decode
    assert out.get("resume_prefix_hit", 0) >= PT, \
        "standby re-decoded from scratch (no migrated pages?)"
    st = replica.stats()
    assert st["assumed"] == 1


def test_failover_replays_only_what_the_client_missed(standby_pair):
    """The client's cursor is authoritative: tokens the write-ahead
    record holds beyond it are REPLAYED (they were synced but never
    delivered), then decode continues — exactly once end to end."""
    sync, peng, pstore, replica, sstore, standby_addr = standby_pair
    prompt = list(range(130, 143))
    budget = 8
    got, errs = [], []
    done = threading.Event()
    mid = threading.Event()

    def emit(tok):
        got.append(tok)
        if len(got) == 5:
            mid.set()

    sid = sync.submit(prompt, budget, emit,
                      lambda e: (errs.append(e), done.set()))
    assert mid.wait(30)
    peng.close()
    assert done.wait(30)
    sync.flush(10)
    # simulate a client that lost its last two deliveries (e.g. died
    # with them in a socket buffer): its cursor trails the record
    cursor = len(got) - 2
    out = assume_stream(standby_addr, sid, cursor)
    assert out["error"] is None
    assert got[:cursor] + out["tokens"] == _expected(prompt, budget)
    assert out["replayed"] >= 2

    # exactly-once: a second assume is refused
    with pytest.raises(errors.RpcError) as ei:
        assume_stream(standby_addr, sid, cursor)
    assert ei.value.code == errors.EREQUEST


def test_transient_sync_failure_self_heals_the_record(standby_pair):
    """A transient Append failure must NOT freeze the write-ahead
    record: the unacked tail rides along with the next token's Append,
    so the standby record catches back up and failover still covers
    the full stream (the cursor advances only on ack)."""
    sync, peng, pstore, replica, sstore, standby_addr = standby_pair
    real_call = sync._call
    dropped = []

    def flaky_call(method_name, body):
        # the standby "blips" exactly once, on the second token's sync
        if method_name == "Append" and int(body.get("cursor", 0)) == 1 \
                and not dropped:
            dropped.append(body)
            raise errors.RpcError(errors.EFAILEDSOCKET,
                                  "injected standby blip")
        return real_call(method_name, body)

    sync._call = flaky_call
    prompt = list(range(330, 343))
    budget = 8
    got = []
    done = threading.Event()
    mid = threading.Event()

    def emit(tok):
        got.append(tok)
        if len(got) == 5:
            mid.set()

    sid = sync.submit(prompt, budget, emit,
                      lambda e: done.set())
    assert mid.wait(30)
    peng.close()
    assert done.wait(30)
    sync._call = real_call
    assert dropped, "the blip never fired"
    assert sync.stats()["sync_errors"] == 1
    sync.flush(10)
    # the record self-healed: assume covers the WHOLE missing tail,
    # including the token whose own Append was dropped
    out = assume_stream(standby_addr, sid, len(got))
    assert out["error"] is None
    assert got + out["tokens"] == _expected(prompt, budget), \
        "record froze after a transient sync failure"


def test_failover_after_clean_finish_is_pure_replay(standby_pair):
    """A generation that FINISHED on the primary needs no decode on
    the standby: assume with an early cursor replays the recorded
    tail and terminates cleanly."""
    sync, peng, pstore, replica, sstore, standby_addr = standby_pair
    prompt = list(range(230, 239))
    budget = 5
    got = []
    done = threading.Event()
    sid = sync.submit(prompt, budget, got.append,
                      lambda e: done.set())
    assert done.wait(30)
    assert got == _expected(prompt, budget)
    # clean finish normally CLOSES the record; a crash right after the
    # last token is the one window where assume still matters — rebuild
    # it via the service to model a standby that outlived the Finish
    replica.begin(sid + 10_000, prompt, budget)
    replica.append(sid + 10_000, 0, got)
    replica.finish(sid + 10_000, 0)
    out = assume_stream(standby_addr, sid + 10_000, 2)
    assert out["error"] is None
    assert got[:2] + out["tokens"] == _expected(prompt, budget)
    assert out["replayed"] == budget - 2


def test_press_disagg_mode(disagg_pair):
    """tools/rpc_press --disagg drives the split topology and reports
    generations/s + tokens/s."""
    import io

    from brpc_tpu.tools.rpc_press import run_disagg_press
    prefill_addr, decode_addr, _, _, _, _ = disagg_pair
    out = io.StringIO()
    summary = run_disagg_press(
        prefill_addr, decode_addr,
        {"prompt": list(range(20, 33)), "max_new_tokens": 4},
        duration_s=0.8, threads=2, timeout_ms=20_000, out=out)
    assert summary["generations_ok"] > 0
    assert summary["errors"] == 0
    assert summary["tokens"] >= 4 * summary["generations_ok"]
    assert summary["tokens_per_s"] > 0
    assert json.loads(out.getvalue())

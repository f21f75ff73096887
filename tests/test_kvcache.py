"""Paged KV-cache subsystem tests (ISSUE 3 acceptance criteria).

Covers, in order:
  * page/refcount/block<->page-table discipline (pages.py) — a block
    returns to the BlockPool exactly when its last page frees;
  * radix prefix reuse (radix.py + store.py) — a request whose prompt
    extends a cached prefix reuses the SHARED pages, and the engine
    prefills only the suffix (pinned by a trace/compile counter);
  * copy-on-write forks isolate divergent continuations at the page-
    content level;
  * eviction never frees a page with refcount > 1, and pressure-driven
    eviction keeps allocation alive;
  * DecodeEngine occupancy returns to baseline after a mixed
    admit/fork/retire run; the gathered page table reaches a 3-arg
    step function with a fixed shape;
  * DynamicBatcher prefix_probe trims prefill to the uncached suffix
    (smaller length buckets, skip ratio on /vars);
  * earliest-deadline-first priority lanes in the batcher;
  * prefix-affinity load balancing (consistent-hash on the prefix
    fingerprint);
  * the /kvcache console page.
"""
import http.client
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import brpc_tpu as brpc
from brpc_tpu import errors
from brpc_tpu.kvcache import KVCacheStore, PagePool, RadixTree
from brpc_tpu.serving import DecodeEngine, DynamicBatcher

from testutil import wait_until

PT = 4          # page_tokens for most tests
PB = 64         # page_bytes (16B per token slot)


def _mk_store(name, max_blocks=8, page_bytes=PB, page_tokens=PT):
    return KVCacheStore(page_bytes=page_bytes, page_tokens=page_tokens,
                        max_blocks=max_blocks, name=name)


# ---------------------------------------------------------------------------
# pages: refcounts + block<->page table
# ---------------------------------------------------------------------------

def test_pages_refcount_and_block_baseline():
    pool = PagePool(page_bytes=PB, page_tokens=PT, max_blocks=2,
                    name="t_pages")
    base = {k: v["free"] for k, v in pool.pool.stats()["classes"].items()}
    pages = [pool.alloc_page() for _ in range(3)]
    assert pool.blocks_leased() == 1          # all carved from one block
    assert pool.pages_in_use() == 3
    pool.ref(pages[0])                        # shared now (refs=2)
    pool.unref(pages[1])
    pool.unref(pages[2])
    assert pool.pages_in_use() == 1
    assert pool.blocks_leased() == 1          # pages[0] still pins it
    pool.unref(pages[0])
    assert pool.blocks_leased() == 1          # one ref left
    pool.unref(pages[0])
    assert pool.blocks_leased() == 0          # last page freed -> released
    now = {k: v["free"] for k, v in pool.pool.stats()["classes"].items()}
    assert now == base, "block leaked past its last page"
    pool.assert_consistent()
    with pytest.raises(RuntimeError):
        pool.unref(pages[0])                  # double free is loud


def test_pages_write_read_roundtrip_and_isolation():
    pool = PagePool(page_bytes=PB, page_tokens=PT, max_blocks=2,
                    name="t_pages_rw")
    a, b = pool.alloc_page(), pool.alloc_page()
    pool.write(a, 0, [11, 12, 13, 14])
    pool.write(b, 0, [21, 22])
    pool.write(b, 2, [23, 24])
    # sibling pages share one block buffer: a's splice must not clobber b
    assert pool.read(a).tolist() == [11, 12, 13, 14]
    assert pool.read(b).tolist() == [21, 22, 23, 24]
    c = pool.alloc_page()
    pool.copy_page(c, a)
    assert pool.read(c).tolist() == [11, 12, 13, 14]
    for p in (a, b, c):
        pool.unref(p)
    assert pool.blocks_leased() == 0


# ---------------------------------------------------------------------------
# radix prefix reuse through the store
# ---------------------------------------------------------------------------

def test_store_prefix_reuse_shares_pages():
    st = _mk_store("t_reuse_store")
    try:
        prompt = list(range(10))
        s1 = st.admit(prompt)
        assert s1.prefix_hit_tokens == 0     # cold cache
        for t in (100, 101):                 # decode 2 tokens -> 12 total
            st.extend(s1, t)
        s1_ids = s1.page_ids()
        st.retire(s1)                        # full pages enter the tree
        assert st.radix.node_count() == 3    # 12 tokens / 4 per page
        ext = prompt + [100, 101, 7, 8]      # extends the cached prefix
        s2 = st.admit(ext)
        # the shared pages are THE SAME handles, not copies
        assert s2.prefix_hit_tokens == 12
        assert s2.page_ids()[:3] == s1_ids[:3]
        assert st.hit_rate() > 0
        # a diverging prompt shares only the chunks it matches
        s3 = st.admit(prompt[:4] + [999] * 6)
        assert s3.prefix_hit_tokens == 4
        assert s3.page_ids()[0] == s1_ids[0]
        assert s3.page_ids()[1] != s1_ids[1]
        st.retire(s2, cache=False)
        st.retire(s3, cache=False)
        st.pagepool.assert_consistent()
    finally:
        st.close()


def test_store_cow_fork_isolates_divergence():
    st = _mk_store("t_cow_store")
    try:
        s = st.admit([1, 2, 3, 4, 5, 6])     # 1.5 pages
        f = st.fork(s)
        shared_tail = s.pages[-1]
        assert shared_tail.refs == 2
        st.extend(s, 700)                    # tail shared -> COW copies
        st.extend(f, 800)
        assert s.pages[-1].pid != f.pages[-1].pid
        # content-level isolation: each side sees its own continuation,
        # and the common prefix survives in both
        assert st.pagepool.read(s.pages[-1], 3).tolist() == [5, 6, 700]
        assert st.pagepool.read(f.pages[-1], 3).tolist() == [5, 6, 800]
        assert st.stats()["cow_forks"] >= 1
        st.retire(s, cache=False)
        st.retire(f, cache=False)
        st.pagepool.assert_consistent()
        assert st.pagepool.blocks_leased() == 0
    finally:
        st.close()


def test_eviction_never_frees_referenced_pages():
    """LRU eviction under pool pressure frees only tree-held (refs==1)
    pages; a page a live sequence still references survives any demand,
    and allocation keeps succeeding off the reclaimed space."""
    # 8KB block / 2048B pages -> 4 pages per block; 1 block max = 4 pages
    st = KVCacheStore(page_bytes=2048, page_tokens=4, max_blocks=1,
                      name="t_evict")
    try:
        live = st.admit([1, 2, 3, 4, 5])     # 2 pages, held live
        live_ids = set(live.page_ids())
        cold = st.admit([9, 9, 9, 9, 9])     # 2 pages
        st.retire(cold)                      # 1 full page cached in tree
        # pool is now: 2 live + 1 tree + 1 free.  Demand 2 fresh pages:
        # the tree page must be evicted, the live ones must not.
        s = st.admit([7] * 8)                # needs 2 pages
        assert st.stats()["evictions"] >= 1
        assert live_ids <= set(live.page_ids())
        # the live sequence's content is intact post-eviction
        assert st.pagepool.read(live.pages[0]).tolist() == [1, 2, 3, 4]
        # and at TOTAL exhaustion (everything referenced) the failure is
        # a definite MemoryError, not a freed-in-use page
        with pytest.raises(MemoryError):
            st.admit([5] * 9)
        st.pagepool.assert_consistent()
        st.retire(s, cache=False)
        st.retire(live, cache=False)
    finally:
        st.close()


# ---------------------------------------------------------------------------
# engine integration: suffix-only prefill + page tables + baseline
# ---------------------------------------------------------------------------

def test_engine_prefill_only_suffix_trace_pinned():
    """ISSUE 3 acceptance: a prompt extending a cached prefix reuses the
    shared pages — prefill runs ONLY on the uncached suffix, and the
    jit cache sees bucket shapes only (one compile per bucket)."""
    st = _mk_store("t_prefill_store")
    prefill_traces = []
    prefill_calls = []

    @jax.jit
    def _prefill_jit(tokens, start):
        prefill_traces.append(tuple(tokens.shape))
        return tokens.sum()

    def prefill(tokens, start):
        prefill_calls.append((int(tokens.shape[0]), int(start)))
        return _prefill_jit(tokens, start)

    step_traces = []

    @jax.jit
    def step(tokens, positions, pages):
        step_traces.append(tuple(pages.shape))
        return tokens + 1

    eng = DecodeEngine(step, num_slots=2, store=st, prefill_fn=prefill,
                       prefill_buckets=(8, 32), max_pages_per_slot=8,
                       name="t_prefill_e")
    try:
        done = threading.Event()
        toks = []
        prompt = list(range(10))
        eng.submit(prompt, 2, toks.append, lambda err: done.set())
        assert done.wait(30) and len(toks) == 2
        # cold admit: the whole 10-token prompt prefilled (bucket 32)
        assert prefill_calls == [(32, 0)]
        assert eng.join_idle(10)
        # seq cached 12 tokens (10 prompt + 2 generated) = 3 full pages
        ext = prompt + toks + [77, 78]       # extends the cached prefix
        done2 = threading.Event()
        eng.submit(ext, 2, lambda t: None, lambda err: done2.set())
        assert done2.wait(30)
        # warm admit: 12 tokens hit -> ONLY the 2-token suffix prefills
        # (bucket 8, starting at position 12)
        assert prefill_calls == [(32, 0), (8, 12)]
        # compile-pinned: one trace per bucket, none per raw length
        assert sorted(prefill_traces) == [(8,), (32,)]
        # the step function received the fixed-shape page table
        assert step_traces == [(2, 8)]
        assert st.stats()["hit_tokens"] == 12
    finally:
        eng.close()
        st.close()


def test_engine_rejects_prompt_exceeding_page_table_at_admit():
    """A prompt needing more pages than max_pages_per_slot is rejected
    AT ADMIT with a definite ELIMIT — installing it would silently
    truncate the gathered page table and decode on wrong KV."""
    st = _mk_store("t_cap_store", max_blocks=16)

    @jax.jit
    def step(tokens, positions, pages):
        return tokens + 1

    eng = DecodeEngine(step, num_slots=2, store=st, max_pages_per_slot=3,
                       name="t_cap_e")
    try:
        done = threading.Event()
        errbox = []
        # 13 tokens / 4 per page = 4 pages > cap of 3
        eng.submit(list(range(13)), 2, lambda t: None,
                   lambda err: (errbox.append(err), done.set()))
        assert done.wait(20)
        assert errbox[0] is not None and errbox[0].code == errors.ELIMIT
        assert "pages" in errbox[0].text
        # the rejected admit leaked nothing and the engine still serves
        assert st.stats()["live_seqs"] == 0
        done2 = threading.Event()
        toks = []
        eng.submit(list(range(8)), 2, toks.append,
                   lambda err: done2.set())
        assert done2.wait(20) and len(toks) == 2
    finally:
        eng.close()
        st.close()


def test_engine_mixed_admit_fork_retire_occupancy_baseline():
    """ISSUE 3 acceptance: engine + store occupancy returns to baseline
    after a mixed admit/fork/retire run (forks at the store level ride
    alongside live engine traffic)."""
    st = _mk_store("t_mixed_store", max_blocks=16)
    device_pool = st.pagepool.pool
    base = {k: v["free"] for k, v in device_pool.stats()["classes"].items()}

    @jax.jit
    def step(tokens, positions, pages):
        return tokens + 1

    eng = DecodeEngine(step, num_slots=3, store=st, name="t_mixed_e")
    try:
        sinks = []
        shared = list(range(8))
        for i in range(9):
            done = threading.Event()
            errbox = []
            sinks.append((done, errbox))
            prompt = shared + [100 + i, 200 + i]
            eng.submit(prompt, 3, lambda t: None,
                       lambda err, d=done, eb=errbox: (eb.append(err),
                                                       d.set()))
            if i % 3 == 0:
                # store-level fork/extend/retire churn mid-decode
                s = st.admit(shared + [999, i])
                f = st.fork(s)
                st.extend(f, 31337)
                st.retire(s, cache=False)
                st.retire(f, cache=False)
        for done, errbox in sinks:
            assert done.wait(30), "request hung"
            assert errbox[0] is None
        assert eng.join_idle(10)
        assert st.stats()["live_seqs"] == 0
        st.pagepool.assert_consistent()
        st.clear()                       # drop the radix cache
        assert st.pagepool.blocks_leased() == 0
        now = {k: v["free"]
               for k, v in device_pool.stats()["classes"].items()}
        assert now == base, "HBM blocks leaked through the page cache"
        assert st.stats()["forks"] == 3
    finally:
        eng.close()
        st.close()


# ---------------------------------------------------------------------------
# batcher: prefix-aware prefill bucketing
# ---------------------------------------------------------------------------

def test_batcher_prefix_probe_trims_to_suffix():
    st = _mk_store("t_probe_store", page_tokens=16, page_bytes=256)
    traces = []

    def _fn(x):
        traces.append(tuple(x.shape))
        return x.sum(axis=1)

    b = DynamicBatcher(jax.jit(_fn), max_batch_size=2, max_delay_us=500,
                       batch_buckets=(2,), length_buckets=(16, 64),
                       prefix_cache=st, dtype=np.int32,
                       name="t_probe")
    try:
        # warm the cache: one retired 48-token sequence = 3 cached pages
        s = st.admit(list(range(48)) + [1])
        st.retire(s)
        # cold prompt (disjoint token range): full 40 tokens -> bucket 64
        cold = np.arange(40, dtype=np.int32) + 1000
        got = b.submit_wait(cold)
        assert int(got) == int(cold.sum())
        # warm prompt: 48 cached + 6 new -> only the suffix computes,
        # riding the SMALL bucket a 54-token item could never fit
        warm = np.asarray(list(range(48)) + [5, 5, 5, 5, 5, 5], np.int32)
        got = b.submit_wait(warm)
        assert int(got) == 30                 # suffix-only sum
        assert set(traces) == {(2, 64), (2, 16)}
        st_b = b.stats()
        assert st_b["prefix_skip_ratio"] > 0.4
        # acquire/release balanced: after the batches the tree pages
        # are held by the tree alone (no pin leaked by the batcher)
        st.pagepool.assert_consistent()
        assert st.stats()["pages"]["pages_in_use"] == \
            st.stats()["radix_nodes"]
    finally:
        b.close()
        st.close()


def test_batcher_prefix_offsets_reach_batch_fn():
    """A 2-arg batch_fn receives each row's start position — rows are
    suffixes, so position-dependent compute needs the offset."""
    st = _mk_store("t_offs_store", page_tokens=16, page_bytes=256)
    seen_offsets = []

    def fn(x, offsets):
        seen_offsets.append(np.asarray(offsets).tolist())
        return np.asarray(x).sum(axis=1) + np.asarray(offsets)

    b = DynamicBatcher(fn, max_batch_size=2, max_delay_us=500,
                       length_buckets=(16,), prefix_cache=st,
                       dtype=np.int32, name="t_offs")
    try:
        s = st.admit(list(range(32)) + [1])
        st.retire(s)
        warm = np.asarray(list(range(32)) + [5, 5, 5], np.int32)
        got = b.submit_wait(warm)
        assert int(got) == 15 + 32          # suffix sum + its offset
        assert any(32 in row for row in seen_offsets), seen_offsets
    finally:
        b.close()
        st.close()


def test_batcher_offsets_not_passed_into_optional_param():
    """A batch_fn whose second parameter has a DEFAULT (e.g. a
    temperature knob) must not silently receive the offsets array —
    only two REQUIRED positionals opt in."""
    st = _mk_store("t_noffs_store", page_tokens=16, page_bytes=256)

    def fn(x, temperature=1.0):
        assert temperature == 1.0, "offsets leaked into temperature"
        return np.asarray(x).sum(axis=1) * temperature

    b = DynamicBatcher(fn, max_batch_size=2, max_delay_us=500,
                       length_buckets=(16,), prefix_cache=st,
                       dtype=np.int32, name="t_noffs")
    try:
        assert not b._fn_wants_offsets
        s = st.admit(list(range(32)) + [1])
        st.retire(s)
        got = b.submit_wait(np.asarray(list(range(32)) + [5, 5], np.int32))
        assert int(got) == 10               # suffix-only sum, no offset
    finally:
        b.close()
        st.close()


# ---------------------------------------------------------------------------
# batcher: EDF priority lanes
# ---------------------------------------------------------------------------

def test_batcher_priority_lanes_edf():
    """With more queued than one batch holds, the FIFO head keeps one
    seat (no starvation) and the nearest deadlines fill the rest,
    counted as lane promotions."""
    gate = threading.Event()
    ncalls = [0]

    def fn(x):
        ncalls[0] += 1
        if ncalls[0] == 1:
            gate.wait(10)     # hold batch 1 while the queue builds up
        return np.asarray(x).sum(axis=1)

    b = DynamicBatcher(fn, max_batch_size=2, max_delay_us=1000,
                       length_buckets=(16,), name="t_lanes")
    order = []
    mu = threading.Lock()

    def fire_for(tag):
        def fire(code, text, result):
            with mu:
                order.append(tag)
        return fire

    try:
        b.enqueue(np.ones((4,), np.float32), fire_for("w1"))
        b.enqueue(np.ones((4,), np.float32), fire_for("w2"))
        # wait until batch 1 is actually executing so the next three
        # queue up behind it
        assert wait_until(lambda: ncalls[0] == 1, 10)
        now = time.monotonic()
        b.enqueue(np.ones((4,), np.float32), fire_for("no_deadline"))
        b.enqueue(np.ones((4,), np.float32), fire_for("late"),
                  deadline_s=now + 60)
        b.enqueue(np.ones((4,), np.float32), fire_for("urgent"),
                  deadline_s=now + 20)
        gate.set()
        assert wait_until(lambda: len(order) == 5, 15)
        # batch 2 = {no_deadline (FIFO head, starvation-proof), urgent
        # (EDF promoted over late)}; batch 3 = {late}
        assert set(order[2:4]) == {"no_deadline", "urgent"}
        assert order[4] == "late"
        assert b.stats()["lane_promotions"] == 1
    finally:
        b.close()


# ---------------------------------------------------------------------------
# prefix-affinity load balancing
# ---------------------------------------------------------------------------

def test_prefix_affinity_lb_routes_repeat_prefixes_together():
    from brpc_tpu.butil.endpoint import EndPoint
    from brpc_tpu.policy.load_balancer import (ServerNode,
                                               create_load_balancer,
                                               prefix_fingerprint)
    lb = create_load_balancer("prefix_affinity")
    lb.reset_servers([ServerNode(EndPoint("10.9.0.1", p))
                      for p in range(1, 6)])
    shared = list(range(40, 56))             # one 16-token page chunk
    # every continuation of the shared prefix lands on ONE replica —
    # the one whose radix tree will hold its pages
    eps = {lb.select_for_prompt(shared + [i, i + 1]) for i in range(30)}
    assert len(eps) == 1
    # distinct prefixes spread over the fleet
    spread = {lb.select_for_prompt([i * 17 + j for j in range(16)])
              for i in range(40)}
    assert len(spread) >= 3
    # fingerprints are stable and page-aligned: the suffix never matters
    assert prefix_fingerprint(shared + [1]) == \
        prefix_fingerprint(shared + [2, 3])
    # replica churn remaps ONLY the departed replica's share
    keys = [[i * 31 + j for j in range(16)] for i in range(60)]
    before = {tuple(k): lb.select_for_prompt(k) for k in keys}
    victim = next(iter(before.values()))
    lb.remove_server(victim)
    after = {tuple(k): lb.select_for_prompt(k) for k in keys}
    for k, ep in before.items():
        if ep != victim:
            assert after[k] == ep, "unrelated prefix lost its warm cache"


# ---------------------------------------------------------------------------
# /kvcache console page
# ---------------------------------------------------------------------------

def test_console_kvcache_page():
    st = _mk_store("t_console_store")
    s = brpc.Server()
    s.start("127.0.0.1", 0)
    try:
        seq = st.admit(list(range(9)))
        st.retire(seq)
        seq2 = st.admit(list(range(9)) + [1, 2])
        st.retire(seq2, cache=False)
        c = http.client.HTTPConnection("127.0.0.1", s.port, timeout=10)
        c.request("GET", "/kvcache")
        r = c.getresponse()
        body = r.read()
        c.close()
        assert r.status == 200
        snap = json.loads(body)
        stc = snap["stores"]["t_console_store"]
        assert stc["hit_rate"] > 0
        assert stc["radix_nodes"] == 2
        for key in ("pages", "evictions", "cow_forks", "cached_tokens"):
            assert key in stc
    finally:
        s.stop()
        s.join()
        st.close()


# ---------------------------------------------------------------------------
# fine-grained store locking (ISSUE 4 satellite / ROADMAP open item)
# ---------------------------------------------------------------------------

def test_slow_cold_admit_overlaps_concurrent_store_ops():
    """The cold-admit device splice must NOT serialize the store: while
    one thread admits a long uncached prompt through an artificially
    slow page-write path, a concurrent acquire_prefix (the batcher's
    formation-time trim) and a concurrent extend on a live sequence
    both finish orders of magnitude sooner than the admit."""
    st = _mk_store("t_finelock", max_blocks=32)
    real_write = st.pagepool.write
    try:
        # a cached prefix for acquire_prefix to pin, and a live seq to
        # extend, both created BEFORE the slow path is installed
        warm = st.admit(list(range(8)))          # two full pages
        st.retire(warm)                          # -> radix tree
        live = st.admit([50, 51, 52])

        slow_pages = 6

        def slow_write(page, slot, tokens):
            time.sleep(0.12)                     # "long device splice"
            real_write(page, slot, tokens)

        st.pagepool.write = slow_write
        admit_done = threading.Event()
        admitted = []

        def cold_admit():
            # 24 uncached tokens = 6 pages => >= 0.7s of device writes
            admitted.append(st.admit([900 + i for i in range(slow_pages
                                                             * PT)]))
            admit_done.set()

        t = threading.Thread(target=cold_admit)
        t.start()
        time.sleep(0.05)                         # admit is mid-splice
        t0 = time.monotonic()
        hit, pages = st.acquire_prefix(list(range(8)) + [77])
        acq_s = time.monotonic() - t0
        assert hit == 8 and len(pages) == 2
        st.release(pages)
        t1 = time.monotonic()
        st.extend(live, 53)
        ext_s = time.monotonic() - t1
        assert not admit_done.is_set(), \
            "admit finished too fast to prove overlap — slow path broken"
        # both ops overlapped the admit instead of queuing behind it
        assert acq_s < 0.35, \
            f"acquire_prefix serialized behind cold admit ({acq_s:.2f}s)"
        assert ext_s < 0.35, \
            f"extend serialized behind cold admit ({ext_s:.2f}s)"
        assert admit_done.wait(30)
        st.pagepool.write = real_write
        # the overlapped admit produced a correct sequence
        seq = admitted[0]
        assert seq.tokens == [900 + i for i in range(slow_pages * PT)]
        assert st.pagepool.read(seq.pages[0]).tolist() == [900, 901,
                                                           902, 903]
        st.retire(seq, cache=False)
        st.retire(live, cache=False)
        st.pagepool.assert_consistent()
    finally:
        st.pagepool.write = real_write
        st.close()


def test_detach_commits_prefix_and_pins_against_eviction():
    """KVCacheStore.detach (the crash-recovery re-attach API): a LIVE
    sequence's full pages land in the radix tree atomically with a
    recovery pin, so (a) a re-admit of the same tokens prefix-hits,
    and (b) pressure eviction cannot free the pinned prefix before the
    re-admit; releasing the pin makes the pages ordinarily
    evictable."""
    st = _mk_store("t_detach", max_blocks=4)
    try:
        seq = st.admit(list(range(10)))          # 2 full pages + tail
        pin = st.detach(seq)
        assert seq.retired and seq.pages == []
        assert len(pin) == 2 and pin.tokens == 8
        # committed: a re-admit hits the detached prefix
        re = st.admit(list(range(10)) + [99])
        assert re.prefix_hit_tokens == 8
        st.retire(re, cache=False)
        # pinned: refs==2 (tree + pin) -> eviction must skip them
        freed = st.evict_pages(1 << 20)
        assert freed == 0, "eviction freed a recovery-pinned page"
        assert st.radix.node_count() == 2
        pin.release()
        pin.release()                            # idempotent
        assert st.evict_pages(1 << 20) == 2
        assert st.pagepool.blocks_leased() == 0
        # detach on an already-retired seq is a no-op pin
        assert len(st.detach(re)) == 0
    finally:
        st.close()


# ---------------------------------------------------------------------------
# ISSUE 11: draft leases — speculate / rollback / commit_draft, and the
# fork lifecycle exercised in anger
# ---------------------------------------------------------------------------

def test_speculate_rollback_releases_pages_to_baseline():
    """The in-seq draft cursor: speculate appends across page
    boundaries WITHOUT materializing (kv_filled holds, nothing can
    cache), rollback releases exactly the rejected tail's pages, and
    commit_draft advances the cursor over an accepted prefix."""
    st = _mk_store("t_spec_rb")
    try:
        seq = st.admit([1, 2, 3, 4, 5])          # 1 full page + 1 slot
        assert seq.kv_filled == 5
        before = st.pagepool.pages_in_use()
        st.speculate(seq, [10, 11, 12, 13, 14, 15, 16])   # 3 pages now
        assert len(seq.tokens) == 12 and len(seq.pages) == 3
        assert seq.kv_filled == 5, "a draft must not materialize"
        # an unverified draft can never reach the radix tree
        st.retire(st.fork(seq), cache=True)
        assert st.probe([1, 2, 3, 4, 5, 10, 11, 12, 99]) == 4
        # accept 3 drafts, reject the rest: tokens truncate, the
        # rejected pages return, the cursor covers the accepted run
        st.rollback(seq, 8)
        st.commit_draft(seq, 8)
        assert seq.tokens == [1, 2, 3, 4, 5, 10, 11, 12]
        assert seq.kv_filled == 8 and len(seq.pages) == 2
        assert st.pagepool.pages_in_use() == before
        assert st.stats()["rolled_back_pages"] >= 1
        # guard rails: never below the materialized prefix, never past
        # the appended tokens
        with pytest.raises(ValueError):
            st.rollback(seq, 7)
        with pytest.raises(ValueError):
            st.commit_draft(seq, 99)
        st.retire(seq, cache=False)
        st.clear()      # drop the tree's ref from the fork's commit
        assert st.pagepool.blocks_leased() == 0
    finally:
        st.close()


def test_fork_extend_reject_release_refcount_math():
    """The fork lifecycle unit suite (ISSUE 11): fork -> speculate
    (COW isolates the shared tail) -> reject (retire) returns every
    refcount and block to baseline, and the base sequence's bytes
    survive untouched."""
    st = _mk_store("t_fork_math", max_blocks=16)
    try:
        seq = st.admit([1, 2, 3, 4, 5, 6])       # page0 full, page1 half
        tail = seq.pages[-1]
        assert tail.refs == 1
        f = st.fork(seq)
        assert tail.refs == 2, "fork must share the tail page"
        assert [p.pid for p in f.pages] == [p.pid for p in seq.pages]
        # divergence: the fork's first append COWs the shared tail
        st.speculate(f, [70, 71, 72])
        assert f.pages[1].pid != tail.pid, "no COW on shared tail"
        assert tail.refs == 1
        assert st.stats()["cow_forks"] >= 1
        # base unpolluted: its tail slot order/content unchanged
        assert st.pagepool.read(seq.pages[1], 2).tolist() == [5, 6]
        # reject the whole branch: fork pages all release
        st.retire(f, cache=False)
        assert tail.refs == 1 and seq.pages[0].refs == 1
        st.retire(seq, cache=False)
        st.pagepool.assert_consistent()
        assert st.pagepool.blocks_leased() == 0
    finally:
        st.close()


def test_fork_lifecycle_under_concurrent_load():
    """Fork in anger: a thread storm of fork -> speculate -> rollback
    -> retire churn against live base sequences — refcounts, the
    free list and block occupancy all return to baseline, and no
    base sequence's tokens are disturbed."""
    st = _mk_store("t_fork_storm", max_blocks=32)
    try:
        bases = [st.admit([100 * k + j for j in range(6)])
                 for k in range(4)]
        errs: list = []

        def storm(k):
            try:
                for i in range(25):
                    b = bases[(k + i) % len(bases)]
                    f = st.fork(b)
                    st.speculate(f, [1000 + k * 100 + i + j
                                     for j in range(5)])
                    if i % 3 == 0:
                        st.rollback(f, len(b.tokens))
                    st.retire(f, cache=False)
            except Exception as e:     # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=storm, args=(k,))
                   for k in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert errs == [], errs
        for k, b in enumerate(bases):
            assert b.tokens == [100 * k + j for j in range(6)]
            st.retire(b, cache=False)
        assert st.stats()["live_seqs"] == 0
        st.clear()
        st.pagepool.assert_consistent()
        assert st.pagepool.blocks_leased() == 0
    finally:
        st.close()


def test_speculate_vector_store_never_commits_unverified_tail():
    """vector_kv + commit_live_pages (the StandbySync pairing): a
    draft that fills whole pages must not stream-commit them — only
    write_kv_batch's final advance (the verify commit) publishes, and
    only over the accepted prefix."""
    st = KVCacheStore(page_tokens=PT, page_bytes=PB, max_blocks=8,
                      vector_kv=True, commit_live_pages=True,
                      name="t_spec_live")
    try:
        seq = st.admit([1, 2, 3, 4, 5])
        rows = np.arange(5 * 16, dtype=np.uint8).reshape(5, 16)
        assert st.write_kv_batch([(seq, 0, rows)]) == []
        assert seq.kv_filled == 5
        nodes0 = st.radix.node_count()
        st.speculate(seq, [10, 11, 12])          # fills page 2 exactly
        assert st.radix.node_count() == nodes0, \
            "an unverified draft page was live-committed"
        acc = np.arange(3 * 16, dtype=np.uint8).reshape(3, 16) + 7
        assert st.write_kv_batch([(seq, 5, acc)]) == []
        assert seq.kv_filled == 8
        assert st.radix.node_count() > nodes0, \
            "the verified commit should live-publish the filled page"
        st.retire(seq, cache=False)
        st.clear()
        assert st.pagepool.blocks_leased() == 0
    finally:
        st.close()


# ---------------------------------------------------------------------------
# a position reserved ahead of its token (ISSUE 33)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("how", ["live_commit", "retire", "detach",
                                 "export", "fills", "shared_tail",
                                 "exhausted"])
def test_a_reserved_position_is_a_page_and_nothing_else(how):
    """``reserve_next`` puts the next position's page into the table and
    is seen by nothing that goes by tokens: not the streaming commit,
    retire-time caching, a detach's pin or an export; the extend that
    follows finds the page in place."""
    st = KVCacheStore(page_bytes=PB, page_tokens=PT, max_blocks=1,
                      commit_live_pages=how == "live_commit",
                      name=f"t_reserve_{how}")      # 8 KB / 64 B: 128 pages
    try:
        prompt = list(range(1, 2 * PT + 1))         # two whole pages
        if how == "shared_tail":
            prompt = prompt[:-1]                    # the tail has room
        seq = st.admit(prompt)
        allocs = st.pagepool.stats()["page_allocs"]
        fork = st.fork(seq) if how == "shared_tail" else None
        st.reserve_next(seq)
        st.reserve_next(seq)                        # idempotent
        assert st.pagepool.stats()["page_allocs"] == allocs + 1
        assert len(seq.tokens) == len(prompt) == seq.kv_filled
        if how == "shared_tail":
            # the copy happened at the reservation, once; the fork keeps
            # the page it shared
            assert st.stats()["cow_forks"] == 1 and len(seq.pages) == 2
            assert seq.pages[-1] is not fork.pages[-1]
            st.extend(seq, 77)
            assert st.stats()["cow_forks"] == 1
            assert st.pagepool.read(seq.pages[-1]).tolist() \
                == prompt[PT:] + [77]
            assert st.pagepool.read(fork.pages[-1]).tolist()[:PT - 1] \
                == prompt[PT:]
            st.retire(fork, cache=False)
            return
        assert len(seq.pages) == 3                  # the page is there
        if how == "live_commit":
            # the tree holds the two full pages the admit committed, and
            # the reservation added nothing to it
            assert st.radix.cached_tokens() == 2 * PT
            st.commit_draft(seq, len(seq.tokens))
            assert st.radix.cached_tokens() == 2 * PT
        elif how == "retire":
            st.retire(seq)
            assert st.radix.cached_tokens() == 2 * PT
            assert st.pagepool.pages_in_use() == 2  # the third went back
        elif how == "detach":
            pin = st.detach(seq)
            assert pin.tokens == 2 * PT and len(pin) == 2
            assert st.pagepool.pages_in_use() == 2
            pin.release()
        elif how == "export":
            st.retire(seq)
            hit, pages = st.acquire_pages(prompt + [5, 5, 5, 5])
            assert hit == 2 * PT and len(pages) == 2
            st.release(pages)
        elif how == "fills":
            st.extend(seq, 77)                      # no second page
            assert st.pagepool.stats()["page_allocs"] == allocs + 1
            assert len(seq.pages) == 3 and seq.tokens[-1] == 77
            assert st.pagepool.read(seq.pages[2]).tolist()[0] == 77
        elif how == "exhausted":
            hog = st.admit(list(range(500, 500 + 124 * PT)))
            other = st.admit([9])                   # 128 of 128 in use
            for _ in range(PT):
                st.extend(seq, 77)                  # the reserved page, full
            with pytest.raises(MemoryError):
                st.reserve_next(seq)                # nothing evictable
            assert len(seq.pages) == 3 and not seq.retired
            st.extend(other, 10)                    # its neighbours go on
            st.retire(hog, cache=False)
            st.retire(other, cache=False)
        if not seq.retired:
            st.retire(seq, cache=False)
        st.pagepool.assert_consistent()
    finally:
        st.close()

"""End-to-end generation tracing (ISSUE 5; `make trace` runs this file).

rpcz grew from per-RPC spans into generation tracing: one trace_id
follows a request from RPC ingress through batch formation, prefill,
per-slot decode, KV-cache events and — across an engine crash — the
supervisor's re-admitted continuation.  These tests pin:

  * per-TRACE head sampling (the satellite fix): the decision is made
    once at the trace root and inherited, so a kept trace has no holes;
  * the timeline reconstruction math (span tree ordering, relative
    offsets, TTFT/ITL accounting);
  * stage spans and KV annotations joining one trace through the
    batcher, engine, store and DCN;
  * trace continuity across crash recovery (`recovered_from`);
  * the rpc_press --dump-traces tooling.
"""
import io
import threading
import time

import numpy as np
import pytest

import brpc_tpu as brpc
from brpc_tpu import errors, fault, rpcz
from brpc_tpu.rpc import meta as M


@pytest.fixture(autouse=True)
def _rpcz_hygiene():
    """Every test leaves rpcz off and no current span installed."""
    fault.clear()
    yield
    rpcz.set_current_span(None)
    rpcz.set_enabled(False)
    fault.clear()


def _trace_spans(tid, tries=40):
    """Collected spans of one trace, polling the collector handoff."""
    for _ in range(tries):
        spans = rpcz.recent_spans(limit=2048, trace_id=tid)
        if spans:
            return spans
        time.sleep(0.05)
    return []


def _wait_spans(tid, want, tries=40):
    for _ in range(tries):
        spans = rpcz.recent_spans(limit=2048, trace_id=tid)
        if len(spans) >= want:
            return spans
        time.sleep(0.05)
    return rpcz.recent_spans(limit=2048, trace_id=tid)


# ---------------------------------------------------------------------------
# per-trace head sampling (satellite: decide at the root, inherit)
# ---------------------------------------------------------------------------

class TestPerTraceSampling:
    def test_children_inherit_the_root_decision(self):
        rpcz.set_enabled(True, sample_rate=0.5)
        for _ in range(50):
            root = rpcz.new_span("server", "S", "m")
            rpcz.set_current_span(root)
            child = rpcz.child_span("batch", "S", "m")
            grandchild = rpcz.new_span(
                "decode", "S", "m", trace_id=child.trace_id,
                parent_span_id=child.span_id, sampled=child.sampled)
            rpcz.set_current_span(None)
            assert child.trace_id == root.trace_id
            assert child.sampled == root.sampled
            assert grandchild.sampled == root.sampled

    def test_no_partial_traces_at_any_rate(self):
        """A sampled trace arrives WHOLE; an unsampled one leaves
        nothing — never holes (the old per-span roll in submit())."""
        for rate in (0.5, 0.01):
            rpcz.set_enabled(True, sample_rate=rate)
            tids = []
            for _ in range(120):
                root = rpcz.new_span("server", "Samp", "m")
                rpcz.set_current_span(root)
                child = rpcz.child_span("batch", "Samp", "m")
                rpcz.set_current_span(None)
                rpcz.submit(child)
                rpcz.submit(root)
                tids.append(root.trace_id)
            from brpc_tpu.bvar.collector import Collector
            Collector.instance().flush(family="rpcz")
            spans = rpcz.recent_spans(limit=2048)
            per_trace = {}
            for s in spans:
                if s.trace_id in tids:
                    per_trace.setdefault(s.trace_id, []).append(s)
            for tid, group in per_trace.items():
                assert len(group) == 2, \
                    f"rate {rate}: trace {tid} collected with holes " \
                    f"({len(group)}/2 spans)"

    def test_rate_half_keeps_some_and_drops_some(self):
        rpcz.set_enabled(True, sample_rate=0.5)
        decisions = [rpcz.new_span("server", "S", "m").sampled
                     for _ in range(200)]
        assert any(decisions) and not all(decisions)

    def test_sampled_bit_rides_the_meta_flags(self):
        m = M.RpcMeta(msg_type=M.MSG_REQUEST, trace_id=7, span_id=3,
                      flags=M.FLAG_TRACE_SAMPLED)
        d = M.RpcMeta.decode(m.encode())
        assert d.flags & M.FLAG_TRACE_SAMPLED
        assert d.trace_id == 7
        m2 = M.RpcMeta(msg_type=M.MSG_REQUEST, trace_id=7, span_id=3)
        assert not (M.RpcMeta.decode(m2.encode()).flags
                    & M.FLAG_TRACE_SAMPLED)

    def test_server_span_inherits_wire_decision(self):
        rpcz.set_enabled(True)
        s_on = rpcz.new_span("server", "S", "m", trace_id=11,
                             parent_span_id=2,
                             sampled=bool(M.FLAG_TRACE_SAMPLED
                                          & M.FLAG_TRACE_SAMPLED))
        s_off = rpcz.new_span("server", "S", "m", trace_id=11,
                              parent_span_id=2, sampled=False)
        assert s_on.sampled is True
        assert s_off.sampled is False


# ---------------------------------------------------------------------------
# timeline reconstruction
# ---------------------------------------------------------------------------

class TestTimelineReconstruction:
    def _spans(self):
        a = rpcz.Span(trace_id=1, span_id=1, kind="server",
                      service="Svc", method="Gen",
                      start_us=1000, end_us=9000)
        b = rpcz.Span(trace_id=1, span_id=2, parent_span_id=1,
                      kind="batch", service="Serving", method="b",
                      start_us=1500, end_us=3000)
        c = rpcz.Span(trace_id=1, span_id=3, parent_span_id=2,
                      kind="decode", service="Serving", method="e",
                      start_us=1600, end_us=2800)
        d = rpcz.Span(trace_id=1, span_id=4, parent_span_id=1,
                      kind="prefill", service="Serving", method="e",
                      start_us=4000, end_us=5000)
        return a, b, c, d

    def test_tree_order_and_relative_offsets(self):
        a, b, c, d = self._spans()
        tree = rpcz.trace_tree([d, c, b, a])   # shuffled input
        assert [(dep, off, s.span_id) for dep, off, s in tree] == [
            (0, 0, 1), (1, 500, 2), (2, 600, 3), (1, 3000, 4)]

    def test_orphan_surfaces_as_extra_root(self):
        a, b, c, d = self._spans()
        orphan = rpcz.Span(trace_id=1, span_id=9, parent_span_id=777,
                           start_us=2000, end_us=2100)
        tree = rpcz.trace_tree([a, b, c, d, orphan])
        assert (0, 1000, orphan) in [(dep, off, s) for dep, off, s in tree]
        assert len(tree) == 5

    def test_format_trace_renders_links_and_annotations(self):
        a, b, c, d = self._spans()
        d.recovered_from = 3
        b.annotations = [(1700, "batch formed: queue_delay_us=200")]
        txt = rpcz.format_trace([a, b, c, d])
        assert "trace 1 — 4 spans" in txt
        assert "+500us [batch] Serving.b" in txt
        assert "@+700us batch formed: queue_delay_us=200" in txt
        assert "recovered_from=span 3" in txt
        # child indented deeper than its parent
        lines = txt.splitlines()
        b_line = next(ln for ln in lines if "[batch]" in ln)
        c_line = next(ln for ln in lines if "[decode]" in ln)
        assert (len(c_line) - len(c_line.lstrip())
                > len(b_line) - len(b_line.lstrip()))

    def test_slowest_traces_ranked_by_root_latency(self):
        fast = rpcz.Span(trace_id=1, span_id=1, start_us=0, end_us=100)
        slow = rpcz.Span(trace_id=2, span_id=2, start_us=0, end_us=900)
        mid = rpcz.Span(trace_id=3, span_id=3, start_us=0, end_us=500)
        ranked = rpcz.slowest_traces([fast, slow, mid], 2)
        assert [g[0].trace_id for g in ranked] == [2, 3]


# ---------------------------------------------------------------------------
# RPC ingress -> cascaded call joins one trace over the wire
# ---------------------------------------------------------------------------

class _Echo(brpc.Service):
    @brpc.method(request="json", response="json")
    def Say(self, cntl, req):
        return {"ok": True}


class TestWireTraceJoin:
    def test_server_span_joins_client_trace_and_sampling(self):
        rpcz.set_enabled(True)
        srv = brpc.Server()
        srv.add_service(_Echo())
        srv.start("127.0.0.1", 0)
        try:
            ch = brpc.Channel(f"127.0.0.1:{srv.port}", timeout_ms=5000)
            root = rpcz.new_span("client", "press", "Say")
            rpcz.set_current_span(root)
            ch.call_sync("_Echo", "Say", {}, serializer="json")
            rpcz.set_current_span(None)
            rpcz.submit(root)
            spans = _wait_spans(root.trace_id, 3)
            kinds = {s.kind for s in spans}
            assert "server" in kinds, spans
            # root -> the channel's client span -> the server span
            call_span = next(s for s in spans if s.kind == "client"
                             and s.parent_span_id == root.span_id)
            assert (call_span.service, call_span.method) == ("_Echo", "Say")
            server_span = next(s for s in spans if s.kind == "server")
            assert server_span.parent_span_id == call_span.span_id
            # and an UNSAMPLED root's trace leaves nothing server-side
            unroot = rpcz.new_span("client", "press", "Say",
                                   sampled=False)
            rpcz.set_current_span(unroot)
            ch.call_sync("_Echo", "Say", {}, serializer="json")
            rpcz.set_current_span(None)
            rpcz.submit(unroot)
            time.sleep(0.3)
            assert rpcz.recent_spans(
                limit=2048, trace_id=unroot.trace_id) == []
        finally:
            srv.stop()
            srv.join()


# ---------------------------------------------------------------------------
# generation tracing through batcher / engine / kvcache
# ---------------------------------------------------------------------------

def _mk_store(name, max_blocks=32):
    from brpc_tpu.kvcache import KVCacheStore
    return KVCacheStore(page_tokens=4, page_bytes=256,
                        max_blocks=max_blocks, name=name)


def _mk_traced_engine(store, name):
    import jax

    from brpc_tpu.serving import DecodeEngine

    @jax.jit
    def step(tokens, positions, pages):
        return (tokens * 7 + positions) % 997

    @jax.jit
    def prefill(tokens, start):
        return tokens.sum()

    return DecodeEngine(step, num_slots=2, store=store,
                        prefill_fn=prefill, max_pages_per_slot=32,
                        name=name)


def _generate(target, prompt, n):
    ev = threading.Event()
    toks, errs = [], []
    target.submit(prompt, n, toks.append,
                  lambda e: (errs.append(e), ev.set()))
    assert ev.wait(30), "generation hung"
    return toks, errs


class TestGenerationTrace:
    def test_decode_prefill_kv_spans_share_ingress_trace(self):
        rpcz.set_enabled(True)
        store = _mk_store("tr_gen_kv")
        eng = _mk_traced_engine(store, "tr_gen_eng")
        try:
            shared = list(range(50, 58))        # two full pages
            # wave 1 commits the prefix into the radix tree on retire
            _generate(eng, shared + [1], 3)
            assert eng.join_idle(10)
            # wave 2 under an explicit ingress span: prefix-hits
            root = rpcz.new_span("server", "Serving", "Generate")
            rpcz.set_current_span(root)
            toks, errs = _generate(eng, shared + [2], 3)
            rpcz.set_current_span(None)
            rpcz.submit(root)
            assert errs == [None]
            spans = _wait_spans(root.trace_id, 3)
            by_kind = {s.kind: s for s in spans}
            assert {"server", "decode", "prefill"} <= set(by_kind), spans
            dec = by_kind["decode"]
            assert dec.parent_span_id == root.span_id
            assert by_kind["prefill"].parent_span_id == dec.span_id
            notes = " | ".join(m for _, m in dec.annotations)
            assert "kv admit: prefix_hit=8/9" in notes
            assert "first token: ttft_us=" in notes
            assert "retired: generated=3" in notes
            pre = " | ".join(m for _, m in by_kind["prefill"].annotations)
            assert "cached=8" in pre and "uncached=1" in pre
        finally:
            eng.close()
            store.clear()
            store.close()

    def test_kv_cow_and_page_alloc_retry_annotations(self):
        rpcz.set_enabled(True)
        store = _mk_store("tr_kv_ann", max_blocks=1)
        try:
            # COW: fork shares the partially-filled tail page; the
            # child's next extend must copy, annotated on its span
            seq = store.admit([1, 2, 3, 4, 5, 6])
            child = store.fork(seq)
            child.span = rpcz.new_span("decode", "Serving", "tr_kv")
            store.extend(child, 7)
            notes = " | ".join(m for _, m in child.span.annotations)
            assert "kv cow: tail page" in notes
            store.retire(seq, cache=False)
            store.retire(child, cache=False)
            # page-alloc retry: seed the tree, then admit a prompt big
            # enough that allocation must evict the cached pages (but
            # small enough to fit once they are freed)
            seed = store.admit(list(range(100, 116)))
            store.retire(seed, cache=True)     # tree holds 4 pages
            span = rpcz.new_span("decode", "Serving", "tr_kv2")
            cap = store.pagepool.stats()["max_blocks"] \
                * store.pagepool.pages_per_block
            need = cap - 2                     # > cap-4 free, <= cap
            big = store.admit(list(range(200, 200 + need * 4)),
                              span=span)
            notes = " | ".join(m for _, m in span.annotations)
            assert "kv page_alloc retry" in notes
            assert "kv evict" in notes
            store.retire(big, cache=False)
        finally:
            store.clear()
            store.close()

    def test_batcher_span_queue_delay_shed_and_trim(self):
        rpcz.set_enabled(True)
        store = _mk_store("tr_b_kv")
        from brpc_tpu.serving import DynamicBatcher
        b = DynamicBatcher(lambda x, off: np.asarray(x).sum(axis=1),
                           max_batch_size=4, max_delay_us=500,
                           length_buckets=(16,), prefix_cache=store,
                           name="tr_batch")
        try:
            # commit a prefix so the trim path runs
            seq = store.admit([int(t) for t in range(9, 17)] + [1])
            store.retire(seq, cache=True)
            root = rpcz.new_span("server", "Serving", "Score")
            rpcz.set_current_span(root)
            out = b.submit_wait(
                np.asarray(list(range(9, 17)) + [2], np.float32),
                timeout_s=10.0)
            rpcz.set_current_span(None)
            rpcz.submit(root)
            assert out is not None
            spans = _wait_spans(root.trace_id, 2)
            batch = next(s for s in spans if s.kind == "batch")
            assert batch.parent_span_id == root.span_id
            notes = " | ".join(m for _, m in batch.annotations)
            assert "batch formed: queue_delay_us=" in notes
            assert "kv prefix trim: 8/9 tokens" in notes
            # shed path: brownout refuses the deadline-less lane and
            # the span records why
            b.brownout = 1
            root2 = rpcz.new_span("server", "Serving", "Score")
            rpcz.set_current_span(root2)
            with pytest.raises(errors.RpcError):
                b.submit_wait(np.ones((4,), np.float32), timeout_s=5.0)
            rpcz.set_current_span(None)
            rpcz.submit(root2)
            spans2 = _wait_spans(root2.trace_id, 2)
            shed = next(s for s in spans2 if s.kind == "batch")
            assert shed.error_code == errors.ELIMIT
            assert any("brownout" in m for _, m in shed.annotations)
        finally:
            b.close()
            store.clear()
            store.close()


# ---------------------------------------------------------------------------
# TTFT / ITL accounting
# ---------------------------------------------------------------------------

class TestLatencyAccounting:
    def test_ttft_itl_recorders_and_generation_record(self):
        from brpc_tpu import serving as serving_mod
        from brpc_tpu.serving.engine import ITL_REC, TTFT_REC
        store = _mk_store("tr_lat_kv")
        eng = _mk_traced_engine(store, "tr_lat_eng")
        try:
            ttft0, itl0 = TTFT_REC.count(), ITL_REC.count()
            n = 5
            toks, errs = _generate(eng, [3, 1, 4, 1, 5], n)
            assert errs == [None] and len(toks) == n
            assert TTFT_REC.count() == ttft0 + 1
            # n tokens -> n-1 inter-token gaps
            assert ITL_REC.count() == itl0 + n - 1
            recs = [r for r in serving_mod.recent_generations(50)
                    if r.get("engine") == "tr_lat_eng"]
            assert recs, "no generation record appended"
            r = recs[-1]
            assert r["generated"] == n
            assert r["prompt_len"] == 5
            assert r["ttft_us"] >= 0
            assert r["error_code"] == 0
            snap = serving_mod.generations_snapshot(10)
            assert snap["aggregates"]["ttft_us"]["count"] >= 1
            assert any(g.get("engine") == "tr_lat_eng"
                       for g in snap["recent"])
        finally:
            eng.close()
            store.clear()
            store.close()


# ---------------------------------------------------------------------------
# trace continuity across crash recovery (the chaos suite re-asserts
# this under the scenario-11 seeds; this is the single-seed unit)
# ---------------------------------------------------------------------------

class TestCrashTraceContinuity:
    def test_recovered_attempt_same_trace_with_link(self):
        from brpc_tpu.serving import EngineSupervisor
        rpcz.set_enabled(True)
        store = _mk_store("tr_cr_kv")
        calm = ({"queue_delay_us": float("inf"), "pool_ratio": 9.9,
                 "queue_depth": 1e9},) * 3
        sup = EngineSupervisor(
            lambda: _mk_traced_engine(store, "tr_cr_eng"),
            store=store, heartbeat_deadline_s=5.0, check_interval_s=0.02,
            ladder=calm, name="tr_cr_sup")
        try:
            _generate(sup, [1, 2, 3, 4, 5], 2)   # warm the jit cache
            shared = list(range(70, 78))
            plan = fault.FaultPlan(11).on("serving.step", fault.ERROR,
                                          times=1, after=2)
            with fault.injected(plan):
                toks, errs = _generate(sup, shared + [9], 6)
            assert errs == [None]
            assert sup.stats()["restarts"] == 1
            # find the generation's trace: the two attempt spans share
            # ONE trace_id; the second links the first
            spans = rpcz.recent_spans(limit=2048)
            gens = {}
            for s in spans:
                if s.kind == "generation" and s.method == "tr_cr_sup":
                    gens.setdefault(s.trace_id, []).append(s)
            linked = None
            for tid, group in gens.items():
                if len(group) >= 2:
                    group.sort(key=lambda s: s.span_id)
                    if group[1].recovered_from == group[0].span_id:
                        linked = (tid, group)
                        break
            assert linked, f"no recovered_from-linked trace: {gens}"
            tid, group = linked
            notes = " | ".join(m for _, m in group[1].annotations)
            assert "resume_cursor=" in notes
            assert "re_decoded_tokens=" in notes
            # the same trace holds BOTH decode attempts (pre-crash span
            # closed at takeover, post-crash span at retirement)
            decode_spans = [s for s in _trace_spans(tid)
                            if s.kind == "decode"]
            assert len(decode_spans) >= 2, decode_spans
            assert any(s.error_code == errors.ELOGOFF
                       for s in decode_spans), "pre-crash span missing"
        finally:
            sup.close()
            store.clear()
            store.close()


# ---------------------------------------------------------------------------
# DCN: cross-host span join through the call envelope
# ---------------------------------------------------------------------------

class TestDcnTraceJoin:
    def test_device_span_joins_caller_trace(self):
        from brpc_tpu.ici.channel import register_device_service
        from brpc_tpu.ici.dcn import DcnChannel
        rpcz.set_enabled(True)
        register_device_service("TraceSvc", "Inc", lambda x: x + 1.0)
        srv = brpc.Server(enable_dcn=True)
        srv.start("127.0.0.1", 0)
        try:
            root = rpcz.new_span("server", "caller", "handler")
            rpcz.set_current_span(root)
            ch = DcnChannel(f"ici://127.0.0.1:{srv.port}/0")
            out = ch.call_sync("TraceSvc", "Inc",
                               np.ones((4,), np.float32))
            rpcz.set_current_span(None)
            rpcz.submit(root)
            assert np.allclose(np.asarray(out), 2.0)
            spans = _wait_spans(root.trace_id, 3)
            kinds = {s.kind for s in spans}
            assert "client" in kinds, spans      # the DCN client span
            assert "device" in kinds, spans      # remote execution span
            dev = next(s for s in spans if s.kind == "device")
            assert dev.service == "TraceSvc" and dev.method == "Inc"
            # the DCN client span hangs off the caller's span (the host
            # channel's own client span hangs off the DCN one)
            assert any(s.kind == "client"
                       and s.parent_span_id == root.span_id for s in spans)
        finally:
            srv.stop()
            srv.join()

    def test_envelope_trace_fields_join_without_context(self):
        """The DCN call metadata alone (trace_id/parent_span_id/
        trace_sampled header fields) must join the device span to the
        caller's trace — the cross-host case where no in-process
        ingress span exists."""
        from brpc_tpu.ici import dcn as dcn_mod
        rpcz.set_enabled(True)
        hdr = {"trace_id": 4242, "parent_span_id": 17,
               "trace_sampled": True}
        tid = int(hdr.get("trace_id") or 0)
        span = rpcz.new_span("device", "S", "m", trace_id=tid,
                             parent_span_id=int(hdr["parent_span_id"]),
                             sampled=bool(hdr.get("trace_sampled", True)))
        assert span.trace_id == 4242
        assert span.parent_span_id == 17
        assert span.sampled is True
        assert dcn_mod is not None


# ---------------------------------------------------------------------------
# rpc_press --dump-traces
# ---------------------------------------------------------------------------

class TestPressDumpTraces:
    def test_dump_prints_slowest_timelines(self):
        from brpc_tpu.tools.rpc_press import run_press
        srv = brpc.Server()
        srv.add_service(_Echo())
        srv.start("127.0.0.1", 0)
        try:
            out = io.StringIO()
            summary = run_press(f"127.0.0.1:{srv.port}", "_Echo", "Say",
                                {}, qps=0, duration_s=0.4, threads=2,
                                dump_traces=2, out=out)
            assert summary["sent_ok"] > 0
            text = out.getvalue()
            assert "slowest traces" in text
            assert "[client] _Echo.Say" in text
            # the in-process server's stage spans joined the timelines
            assert "[server]" in text
        finally:
            srv.stop()
            srv.join()
            rpcz.set_enabled(False)

"""rpcz — per-RPC trace spans (reference src/brpc/span.h; SURVEY.md §5.1).

Span objects record the per-RPC timeline (recv/process/send timestamps,
sizes, error).  Server-side spans are installed in thread-local storage for
the duration of the handler, so nested client calls made inside it pick up
trace_id/parent_span automatically — the reference propagates the same way
through bthread-local storage (task_meta.h:44).  Collection rides the
shared bvar Collector (brpc_tpu/bvar/collector.py, reference
bvar/collector.{h,cpp}): submission is a speed-limited handoff; the
bounded recent-span store is filled on the collector thread.
"""
from __future__ import annotations

import itertools
import contextvars
import os
import random
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field

# The current span is a CONTEXT variable, not a thread-local: user code
# that hops executors/threads via butil.fiber_local.wrap()/spawn() (the
# bthread_key analog) carries its span with it — fiber-local span
# propagation, bthread/key.cpp:49 + the rpcz parent-span contract.
_current_span: contextvars.ContextVar = contextvars.ContextVar(
    "rpcz_span", default=None)
# pid-salted span ids (ISSUE 20): the fleet telemetry plane merges
# spans COLLECTED in several processes into one tree, so span ids must
# not collide across processes the way bare count(1) streams do.  The
# low 16 pid bits in bits 40..55 keep the id inside the uint64 the
# wire TLV carries while leaving 2^40 spans per process before overlap.
_span_counter = itertools.count(((os.getpid() & 0xFFFF) << 40) | 1)

_COLLECT_MAX = 2048
_collected: deque = deque(maxlen=_COLLECT_MAX)
# monotone collection cursor (ISSUE 20): every span landing in
# _collected gets the next seq, so a fleet collector can pull "finished
# spans since my last pull" incrementally without re-shipping the ring
_collect_seq = 0
# NAMED hot lock (ISSUE 6): every submitted span's collector handoff
# lands here — ledger row "rpcz.collect" on /hotspots/locks
from brpc_tpu.butil.lockprof import InstrumentedLock  # noqa: E402

_collect_lock = InstrumentedLock("rpcz.collect")
# Off by default, like the reference's FLAGS_enable_rpcz: span objects are
# only materialized when tracing is on; the hot path otherwise touches a
# shared null span (absorbs writes, reads as zeros).  Enable via
# set_enabled(True) or the reloadable `rpcz_enabled` flag (/flags).
_enabled = False
_sample_rate = 1.0   # 1.0 = keep all (rate-limit knob for hot servers)


def set_enabled(on: bool, sample_rate: float = 1.0) -> None:
    global _enabled, _sample_rate
    _enabled = on
    _sample_rate = sample_rate


@dataclass
class Span:
    trace_id: int = 0
    span_id: int = 0
    parent_span_id: int = 0
    service: str = ""
    method: str = ""
    remote_side: str = ""
    start_us: int = 0
    end_us: int = 0
    request_size: int = 0
    response_size: int = 0
    error_code: int = 0
    kind: str = "server"   # server | client | batch | prefill | decode |
    #                        generation | device (serving/DCN stage spans)
    annotations: list = field(default_factory=list)
    # stages of the call path that ran under this span (``stage``):
    # (name, start_us, dur_us, cpu_us or None), appended as each ends — the
    # received / start-callback / start-send / sent stamps of the
    # reference's span (src/brpc/span.h) as named intervals
    phases: list = field(default_factory=list)
    # head-sampling decision, made ONCE at the trace root and inherited
    # by every child (per-TRACE sampling: a kept trace has no holes)
    sampled: bool = True
    # crash-recovery link: the span_id of the pre-crash attempt this
    # span resumes (supervisor re-admission) — 0 when not a resumption
    recovered_from: int = 0
    # cross-host migration link (ISSUE 7), mirroring recovered_from:
    # the SOURCE process's migrate span whose pages this span spliced
    # in — 0 when this span is not a migration destination
    migrated_from: int = 0
    # collection cursor (ISSUE 20): position in THIS process's
    # recent-span store, assigned when the span lands there.  Purely
    # local bookkeeping for incremental _telemetry pulls — never
    # meaningful across processes and never persisted.
    seq: int = 0

    @property
    def latency_us(self) -> int:
        return max(0, self.end_us - self.start_us)

    def annotate(self, msg: str) -> None:
        self.annotations.append((int(time.time() * 1e6), msg))


class _NullSpan:
    """Stand-in when rpcz is off: absorbs attribute writes, reads as
    zeros/empties.  One shared instance; never collected."""
    __slots__ = ()
    trace_id = 0
    span_id = 0
    parent_span_id = 0
    start_us = 0
    end_us = 0
    request_size = 0
    response_size = 0
    error_code = 0
    latency_us = 0
    service = ""
    method = ""
    remote_side = ""
    kind = ""
    annotations = ()
    phases = ()
    sampled = True
    recovered_from = 0
    migrated_from = 0
    seq = 0

    def __setattr__(self, k, v):
        pass

    def annotate(self, msg):
        pass


NULL_SPAN = _NullSpan()


def now_us() -> int:
    return int(time.time() * 1e6)


def enabled() -> bool:
    return _enabled


def sample_rate() -> float:
    return _sample_rate


def new_span(kind: str, service: str = "", method: str = "",
             trace_id: int = 0, parent_span_id: int = 0,
             sampled: bool | None = None) -> Span:
    """Create a span.  Head sampling is PER-TRACE: a fresh root (no
    trace_id) rolls the sample-rate die exactly once; a span joining an
    existing trace inherits the root's decision — either from the
    explicit ``sampled`` argument (wire propagation: the
    FLAG_TRACE_SAMPLED meta bit, the DCN envelope) or from the current
    span when it belongs to the same trace.  A kept trace therefore
    arrives whole; a dropped one leaves nothing, never holes."""
    if not _enabled:
        return NULL_SPAN
    if sampled is None:
        if trace_id:
            cur = _current_span.get()
            sampled = cur.sampled if (cur is not None
                                      and cur.trace_id == trace_id) else True
        else:
            sampled = _sample_rate >= 1.0 or random.random() <= _sample_rate
    s = Span(kind=kind, service=service, method=method,
             trace_id=trace_id or random.getrandbits(63),
             span_id=next(_span_counter),
             parent_span_id=parent_span_id, start_us=now_us(),
             sampled=bool(sampled))
    return s


def child_span(kind: str, service: str = "", method: str = "") -> Span:
    """A span under the CURRENT span (trace id, parentage and sampling
    inherited); a fresh root when no span is current.  The serving
    layers use this to hang stage spans off the RPC ingress span."""
    if not _enabled:
        return NULL_SPAN
    tid, psid, smp = current_trace_ctx()
    return new_span(kind, service, method, trace_id=tid,
                    parent_span_id=psid, sampled=smp if tid else None)


def set_current_span(span: Span | None) -> None:
    _current_span.set(span)


def get_current_span() -> Span | None:
    return _current_span.get()


def current_trace() -> tuple[int, int]:
    """(trace_id, parent_span_id) to stamp on an outgoing request: inherits
    the server span when calling inside a handler (cascaded RPC)."""
    s = get_current_span()
    if s is None or not s.trace_id:
        return 0, 0
    return s.trace_id, s.span_id


def current_trace_ctx() -> tuple[int, int, bool]:
    """(trace_id, parent_span_id, sampled) — current_trace plus the
    root's head-sampling decision, for callers that carry trace context
    across threads (the batcher queue, the decode slot pool, DCN call
    metadata) where the contextvar does not follow."""
    s = get_current_span()
    if s is None or not s.trace_id:
        return 0, 0, True
    return s.trace_id, s.span_id, s.sampled


# ---- stages: ONE stamping primitive for the call path, two sinks ----
#
# ``with rpcz.stage("rail.ship", cid): ...`` names one interval of one
# call on the thread that runs it.  Where it goes depends on what is
# listening, never on a flag of its own:
#
#   * a ``jax.profiler`` session is active: the stage is a
#     ``TraceAnnotation`` in the profiler's own trace, so it sits on the
#     device trace's clock by construction and an idle chip can be put
#     down to the host layer that held it (benchmarks/harness/
#     program_spans.py reads them);
#   * rpcz is on and the current span is sampled: the stage is appended
#     to that span's ``phases`` and shows on /rpcz?trace_id=;
#   * neither (the state every end-to-end number is measured in): the
#     one shared ``NOOP_STAGE`` comes back — no object is made, no clock
#     is read.
#
# Every stage carries ``cid``: the call's correlation id (or
# ``"<stream id>:<seq>"``).  A stage opened without one takes its
# thread's enclosing stage's, so the layers under the RPC layer (rail,
# endpoint, transport) need not be handed an id to be found by it.

# Stages that open a request's timeline on their thread.  They also
# carry ``mono_us`` (``time.monotonic_ns() // 1000`` at entry), which
# lets a reader place anything stamped on the monotonic clock on the
# profiler's axis (median offset over the roots).
ROOT_STAGES = frozenset((
    "rpc.client.call", "rpc.server.process", "stream.write",
    "combo.call_lowered"))
# Stages opened at the top of a native upcall: they carry
# ``queue_wait_us`` / ``queue_depth``, the native core's sample of how
# long the frame waited between being cut from the read buffer and this
# line, and how many upcalls were queued at the cut (net/rpc.h).
UPCALL_STAGES = frozenset((
    "rpc.server.process", "rpc.client.on_response", "stream.on_data",
    "stream.on_feedback"))
# Stages in which the thread is parked, not working (``wait=1``): a
# reader leaves them out of a layer's time and of the idle attribution.
WAIT_STAGES = frozenset(("rpc.client.wait", "stream.credit_wait",
                         "ps.batcher.wait", "ps.shard.lock_wait"))
# Stages that stamp ``cpu_us`` (the thread's CPU time inside them): the
# ones that are outermost on their thread, so that together they cover
# the call path once.  Not every stage: ``time.thread_time_ns`` is a
# system call, 6 us a call on the v5e's host (PERF.md, PR 25), and two
# of them on each of an echo's 26 stages cost a third of the calls
# completed while a trace was on.
CPU_STAGES = frozenset((
    "rpc.client.call", "rpc.client.on_response", "rpc.server.process",
    "stream.write", "stream.send", "stream.on_data",
    "combo.call_lowered", "ps.client.call", "ps.batcher.run",
    "serve.engine.step"))
_ROOT, _UPCALL, _WAIT, _CPU = 1, 2, 4, 8
_STAGE_KIND: dict = {}       # name -> the flags of the sets it is in
for _flag, _names in ((_ROOT, ROOT_STAGES), (_UPCALL, UPCALL_STAGES),
                      (_WAIT, WAIT_STAGES), (_CPU, CPU_STAGES)):
    for _name in _names:
        _STAGE_KIND[_name] = _STAGE_KIND.get(_name, 0) | _flag

_stage_tls = threading.local()
_TraceAnnotation = None


def _profiling() -> bool:
    """Is a ``jax.profiler`` session recording?  Nobody can have started
    one before ``jax.profiler`` was imported, and this module imports
    nothing of jax itself (another thread may be in the middle of
    importing it); once the profiler's module is there the name is
    rebound to its own check (``TraceAnnotation.is_enabled``, tens of
    nanoseconds)."""
    global _profiling, _TraceAnnotation
    annotation = getattr(sys.modules.get("jax.profiler"),
                         "TraceAnnotation", None)
    if annotation is None:
        return False
    _TraceAnnotation = annotation
    _profiling = annotation.is_enabled
    return _profiling()


class _NoopStage:
    """What ``stage`` returns while nothing listens: one shared object,
    a context manager that does nothing."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **stats) -> None:
        pass


NOOP_STAGE = _NoopStage()


def _upcall_wait():
    from brpc_tpu import native_path
    fb = native_path._fastrpc_mod()
    return fb.upcall_wait() if fb is not None else None


class _Stage:
    __slots__ = ("name", "_ta", "_span", "_prev_cid", "_start_us", "_cpu0")

    def __init__(self, name: str, cid, stats: dict, profiling: bool):
        tls = _stage_tls
        prev = self._prev_cid = getattr(tls, "cid", 0)
        if cid:
            tls.cid = stats["cid"] = cid
        elif prev:
            stats["cid"] = prev
        self.name = name
        kind = _STAGE_KIND.get(name, 0)
        if kind:
            if kind & _ROOT:
                stats["mono_us"] = time.monotonic_ns() // 1000
            if kind & _UPCALL:
                sample = _upcall_wait()
                if sample is not None:
                    stats["queue_wait_us"], stats["queue_depth"] = sample
            if kind & _WAIT:
                stats["wait"] = 1
        self._cpu0 = kind & _CPU
        self._ta = _TraceAnnotation(name, **stats) if profiling else None
        self._span = None
        if _enabled:
            span = _current_span.get()
            if span is not None and span is not NULL_SPAN and span.sampled:
                self._span = span

    def __enter__(self):
        if self._span is not None:
            self._start_us = now_us()
        if self._ta is not None:
            self._ta.__enter__()
        if self._cpu0:      # truthy from here on: read again at exit
            self._cpu0 = time.thread_time_ns() or 1
        return self

    def set(self, **stats) -> None:
        """Stats known only once the work is under way (``cid`` among
        them, where the call is named inside its own stage)."""
        if "cid" in stats:
            _stage_tls.cid = stats["cid"]
        if self._ta is not None:
            self._ta.set_metadata(**stats)

    def __exit__(self, *exc):
        cpu_us = None
        if self._cpu0:
            cpu_us = (time.thread_time_ns() - self._cpu0) // 1000
            if self._ta is not None:
                self._ta.set_metadata(cpu_us=cpu_us)
        if self._ta is not None:
            self._ta.__exit__(*exc)
        if self._span is not None:
            self._span.phases.append(
                (self.name, self._start_us, now_us() - self._start_us,
                 cpu_us))
        _stage_tls.cid = self._prev_cid
        return False


def stage(name: str, cid=0, **stats):
    """One named interval of one call (see the section comment)."""
    profiling = _profiling()
    if not (profiling or _enabled):
        return NOOP_STAGE
    return _Stage(name, cid, stats, profiling)


class _SpanScope:
    """``span`` is the current span inside the block; what was current
    before is current again after it."""
    __slots__ = ("_span", "_token")

    def __init__(self, span: Span):
        self._span = span

    def __enter__(self):
        self._token = _current_span.set(self._span)
        return self._span

    def __exit__(self, *exc):
        _current_span.reset(self._token)
        return False


def span_scope(span):
    """Make ``span`` current for a block, so that the stages run in it
    land in its ``phases`` and nested client calls join its trace.  The
    shared no-op for the null span: with rpcz off no context variable
    is touched."""
    if span is NULL_SPAN:
        return NOOP_STAGE
    return _SpanScope(span)


def payload_bytes(obj) -> int:
    """Size of a request or reply for a stage's ``bytes``: device
    arrays by ``nbytes`` (lists summed), byte strings by length, else
    0.  Call it only where a stage is live."""
    if isinstance(obj, (list, tuple)):
        return sum(payload_bytes(o) for o in obj)
    n = getattr(obj, "nbytes", None)
    if n is not None:
        return int(n)
    return len(obj) if isinstance(obj, (bytes, bytearray, memoryview)) else 0


# ---- on-disk SpanDB (reference span.h:227-230 keeps rpcz spans in an
# on-disk database so traces survive the in-memory window/restarts; ours
# is recordio-framed json with size rotation, written on the COLLECTOR
# thread so the RPC path never touches disk) ----
_db_lock = threading.Lock()
_db_dir: str | None = None
_db_writer = None
_db_file = None
_db_bytes = 0
_DB_ROTATE_BYTES = 16 << 20
_DB_KEEP_FILES = 4


def set_database_dir(path: str | None) -> None:
    """Enable (or disable with None) span persistence under `path`."""
    global _db_dir, _db_writer, _db_file, _db_bytes
    import os
    with _db_lock:
        if _db_file is not None:
            try:
                _db_file.close()
            except OSError:
                pass
        _db_writer = _db_file = None
        _db_bytes = 0
        _db_dir = path or None
        if _db_dir:
            os.makedirs(_db_dir, exist_ok=True)


def _db_append_locked(span: Span) -> None:
    import json
    import os

    from brpc_tpu.butil.recordio import RecordWriter
    global _db_writer, _db_file, _db_bytes
    if _db_writer is None or _db_bytes >= _DB_ROTATE_BYTES:
        if _db_file is not None:
            try:
                _db_file.close()
            except OSError:
                pass
        # prune BEFORE creating the new segment (covers restart into a
        # dir full of old segments too): keep the newest KEEP-1 so the
        # steady state is KEEP files including the one about to open
        segs = sorted(f for f in os.listdir(_db_dir)
                      if f.startswith("spans-"))
        for old in segs[:-(_DB_KEEP_FILES - 1)] if _DB_KEEP_FILES > 1 \
                else segs:
            try:
                os.unlink(os.path.join(_db_dir, old))
            except OSError:
                pass
        name = os.path.join(_db_dir, f"spans-{now_us()}.rio")
        _db_file = open(name, "ab")
        _db_writer = RecordWriter(_db_file)
        _db_bytes = 0
    rec = json.dumps(span_to_dict(span)).encode()
    _db_writer.write(rec)
    # no per-span flush: a write(2) per span would defeat buffering; the
    # reader flushes the live writer before scanning, and RecordReader
    # resyncs past any torn tail after a crash
    _db_bytes += len(rec) + 20


def load_disk_spans(limit: int = 200,
                    trace_id: int | None = None) -> list[Span]:
    """Read persisted spans back (newest segments last; resyncs past
    torn tails via RecordReader)."""
    import json
    import os

    from brpc_tpu.butil.recordio import RecordReader
    with _db_lock:
        d = _db_dir
        if _db_writer is not None:
            try:
                _db_writer.flush()   # make the live segment readable
            except OSError:
                pass
    if not d or not os.path.isdir(d):
        return []
    # newest segments first, stop as soon as `limit` spans are found —
    # older 16MB segments are never parsed for the common recent-N query
    out: list[Span] = []
    for name in sorted((f for f in os.listdir(d)
                        if f.startswith("spans-")), reverse=True):
        seg: list[Span] = []
        try:
            with open(os.path.join(d, name), "rb") as f:
                for _meta, body in RecordReader(f):
                    try:
                        rec = json.loads(body.decode())
                    except ValueError:
                        continue
                    if trace_id is not None and \
                            rec.get("trace_id") != trace_id:
                        continue
                    span = span_from_dict(rec)
                    if span is not None:
                        seg.append(span)
        except OSError:
            continue
        out = seg + out
        if len(out) >= limit:
            break
    return out[-limit:]


class _SpanSample:
    """Collected wrapper: moves the store append (and on-disk SpanDB
    persistence) off the RPC thread — both run on the collector."""

    __slots__ = ("span",)

    def __init__(self, span: Span):
        self.span = span

    def dump_and_destroy(self) -> None:
        global _collect_seq
        with _collect_lock:
            _collect_seq += 1
            self.span.seq = _collect_seq
            _collected.append(self.span)
        with _db_lock:
            if _db_dir is not None:
                try:
                    _db_append_locked(self.span)
                except OSError:
                    pass  # disk trouble must never break collection


# ---- native span queue (ISSUE 9): the submit hot path is ONE
# lock-free push of the span object onto a native MPSC stack
# (_fastrpc.spanq_push); the rate-limit grab, recent-store append and
# SpanDB IO all run on the drainer thread, so tracing leaves the token
# path entirely.  The Collector path below remains the fallback when
# the native extension is unavailable or the flag is off. ----
_spanq_mu = threading.Lock()
_spanq_thread: threading.Thread | None = None
# SAFETY-NET park bound only (ISSUE 10): the drainer is event-woken —
# it drains while the queue is nonempty and parks on _spanq_wake when
# it runs dry, so a submitted span reaches the recent-span store in
# wakeup latency (~ms), not a fixed poll period.  The timeout below
# merely bounds the damage of a hypothetically missed wakeup.
_SPANQ_PARK_S = 0.5
_spanq_wake = threading.Event()
# written only by the drainer, read by submit(): True while the
# drainer is (about to be) parked — the ExecutionQueue idiom, so the
# token path pays one plain attribute read per span and an Event.set
# only on the empty->nonempty transition window
_spanq_parked = False
# exclusive access to the native queue for callers that need the
# drainer to keep its hands off (the spanq unit tests push non-Span
# probes; a concurrent drainer steal would both flake the test and
# poison _collected with foreign objects)
_spanq_pause = threading.Lock()


def _drain_native_spanq() -> None:
    """Move every natively queued span into the recent-span store
    (speed-limited, SpanDB-persisted).  Runs on the drainer thread and
    synchronously from flush(); spanq_drain's atomic exchange makes
    concurrent drains hand each span to exactly one caller."""
    from brpc_tpu import native_path
    fb = native_path._fastrpc_mod()
    if fb is None:
        return
    spans = fb.spanq_drain()
    if not spans:
        return
    from brpc_tpu.bvar.collector import get_or_create_limit
    from brpc_tpu.butil import hostcpu
    limit = get_or_create_limit("rpcz", 2000)
    t_cpu0 = time.thread_time()
    # same bounded-overhead contract as the Collector (the speed limit
    # drops the excess, keeping the EARLIEST spans — FIFO), but ONE
    # budget grab per drained batch: per-span grab() here held the GIL
    # for milliseconds on a 2000-span drain, stealing it from the very
    # token path this queue exists to protect
    kept = spans[:limit.grab_n(len(spans))]
    if kept:
        global _collect_seq
        with _collect_lock:
            for span in kept:
                _collect_seq += 1
                try:
                    span.seq = _collect_seq
                except AttributeError:
                    pass   # a foreign probe object on the native queue
            _collected.extend(kept)
        with _db_lock:
            if _db_dir is not None:
                for span in kept:
                    try:
                        _db_append_locked(span)
                    except OSError:
                        pass  # disk trouble must never break collection
    # span-submit host-CPU accounting (ISSUE 6) stays honest: the
    # heavyweight half now burns THIS thread, not the token path
    hostcpu.add("span_submit", (time.thread_time() - t_cpu0) * 1e6)


def _spanq_loop() -> None:
    """ExecutionQueue-style cadence (ISSUE 10, PR 9 follow-on d):
    drain while the native queue is nonempty, park on the wake event
    when it runs dry.  The parked/park-check ordering makes a missed
    wakeup impossible under the GIL's sequential consistency: the
    drainer publishes ``_spanq_parked = True`` BEFORE its final
    pending check, and submit() pushes BEFORE reading the flag — so
    either the drainer's check sees the span, or the submitter sees
    the flag and sets the event.  A spurious set (span drained between
    push and flag read) costs one empty drain."""
    global _spanq_parked
    from brpc_tpu import native_path
    while True:
        try:
            with _spanq_pause:
                _drain_native_spanq()
            fb = native_path._fastrpc_mod()
            if fb is not None and fb.spanq_pending():
                continue          # drain again: the queue refilled
            _spanq_parked = True
            try:
                if fb is not None and fb.spanq_pending():
                    continue      # raced a push; drain immediately
                _spanq_wake.wait(_SPANQ_PARK_S)
            finally:
                _spanq_parked = False
                _spanq_wake.clear()
        except Exception:
            time.sleep(0.05)   # a torn drain must never kill (or spin)
            #                    the drainer


def _ensure_spanq_drainer() -> None:
    global _spanq_thread
    with _spanq_mu:
        if _spanq_thread is None or not _spanq_thread.is_alive():
            _spanq_thread = threading.Thread(
                target=_spanq_loop, daemon=True, name="rpcz-spanq")
            _spanq_thread.start()


def submit(span: Span) -> None:
    if not _enabled or span is NULL_SPAN:
        return
    if not span.sampled:
        # the head-sampling decision was made at the TRACE root and
        # inherited (new_span); dropping here keeps whole traces —
        # re-rolling per span would leave a kept trace with holes
        return
    span.end_us = span.end_us or now_us()
    from brpc_tpu import native_path
    fb = native_path.spanq()
    if fb is not None:
        # ISSUE 9 hot path: one lock-free native push; everything
        # heavier happens on the rpcz-spanq drainer
        fb.spanq_push(span)
        # ISSUE 10: wake a parked drainer — one GIL-atomic flag read on
        # the common (drainer busy) path, an Event.set only on the
        # empty->nonempty transition (see _spanq_loop for the ordering
        # argument)
        if _spanq_parked:
            _spanq_wake.set()
        t = _spanq_thread
        if t is None or not t.is_alive():
            # covers first use AND a dead-but-non-None thread (a fork's
            # child inherits the module state but not the drainer)
            _ensure_spanq_drainer()
        return
    from brpc_tpu.bvar.collector import Collector, get_or_create_limit
    Collector.instance().submit(_SpanSample(span),
                                get_or_create_limit("rpcz", 2000),
                                family="rpcz")


def flush() -> None:
    """Synchronously land this thread's prior submissions in the
    recent-span store — drains the native span queue AND the rpcz
    Collector family (whichever path each span took)."""
    with _spanq_pause:
        _drain_native_spanq()
    from brpc_tpu.bvar.collector import Collector
    Collector.instance().flush(family="rpcz")


def recent_spans(limit: int = 100, trace_id: int | None = None) -> list[Span]:
    # observe our own prior submissions; flushing ONLY the rpcz family
    # (plus the native queue) keeps this (console) thread away from
    # other consumers' IO
    flush()
    with _collect_lock:
        spans = list(_collected)
    if trace_id is not None:
        spans = [s for s in spans if s.trace_id == trace_id]
    return spans[-limit:]


def spans_since(cursor: int, limit: int = 256,
                finished_only: bool = True) -> tuple[list[Span], int]:
    """Incremental pull for the fleet telemetry plane (ISSUE 20):
    collected spans with ``seq > cursor`` (oldest first, at most
    ``limit``) plus the store's current high-water seq.  A caller that
    re-pulls with the returned cursor sees each span exactly once —
    until the bounded ring evicts faster than it pulls, in which case
    the gap is simply skipped (the cursor is monotone, never rewound).
    ``finished_only`` drops still-open spans (end_us unset) — the
    telemetry contract ships only finished spans."""
    flush()
    with _collect_lock:
        hi = _collect_seq
        out = [s for s in _collected if getattr(s, "seq", 0) > cursor]
    if finished_only:
        out = [s for s in out if s.end_us]
    out.sort(key=lambda s: s.seq)
    return out[:max(0, int(limit))], hi


def span_to_dict(span: Span) -> dict:
    """The wire shape of one span — exactly the SpanDB record (so
    ``span_from_dict``/``load_disk_spans`` share one decode path)."""
    return {
        "trace_id": span.trace_id, "span_id": span.span_id,
        "parent_span_id": span.parent_span_id, "service": span.service,
        "method": span.method, "remote_side": span.remote_side,
        "start_us": span.start_us, "end_us": span.end_us,
        "request_size": span.request_size,
        "response_size": span.response_size,
        "error_code": span.error_code, "kind": span.kind,
        "recovered_from": span.recovered_from,
        "migrated_from": span.migrated_from,
        "annotations": list(span.annotations),
        "phases": [list(p) for p in span.phases]}


def span_from_dict(rec: dict) -> Span | None:
    """Inverse of :func:`span_to_dict`; ``None`` on a malformed record
    (one bad span from a remote process must not kill the merge)."""
    try:
        rec = dict(rec)
        ann = [tuple(a) for a in rec.pop("annotations", ())]
        phases = [tuple(ph) for ph in rec.pop("phases", ())]
        rec.pop("sampled", None)
        rec.pop("seq", None)
        return Span(annotations=ann, phases=phases, **rec)
    except (TypeError, ValueError, AttributeError):
        return None


def traceprintf(msg: str) -> None:
    """TRACEPRINTF analog: annotate the current span."""
    s = get_current_span()
    if s is not None:
        s.annotate(msg)


# ---- timeline reconstruction (the /rpcz?trace_id= tree view and
# rpc_press --dump-traces both render one trace as an indented,
# time-offset span tree) ----

def trace_tree(spans: list[Span]) -> list[tuple[int, int, Span]]:
    """Order one trace's spans as a tree: ``[(depth, offset_us, span)]``
    with offsets relative to the trace's earliest start.  Children sort
    under their parent by start time; a span whose parent was not
    collected (sampling off at that hop, eviction from the bounded
    store) surfaces as an extra root rather than disappearing."""
    spans = sorted(spans, key=lambda s: (s.start_us, s.span_id))
    if not spans:
        return []
    by_id = {s.span_id: s for s in spans}
    children: dict[int, list[Span]] = {}
    roots: list[Span] = []
    for s in spans:
        p = s.parent_span_id
        if p and p in by_id and p != s.span_id:
            children.setdefault(p, []).append(s)
        else:
            roots.append(s)
    t0 = spans[0].start_us
    out: list[tuple[int, int, Span]] = []

    def walk(s: Span, depth: int) -> None:
        out.append((depth, s.start_us - t0, s))
        for c in children.get(s.span_id, ()):
            walk(c, depth + 1)

    for r in roots:
        walk(r, 0)
    return out


def format_trace(spans: list[Span], indent: str = "  ") -> str:
    """Human-readable timeline for ONE trace: tree-ordered spans with
    relative start offsets, per-span latency, recovery links, the
    stages that ran under each span (``phases``) and the annotations,
    both at their offsets from the trace's start."""
    tree = trace_tree(spans)
    if not tree:
        return "no spans\n"
    t0 = min(s.start_us for _, _, s in tree)
    total = max(s.end_us for _, _, s in tree) - t0
    lines = [f"trace {tree[0][2].trace_id} — {len(tree)} spans, "
             f"{total}us total"]
    for depth, off, s in tree:
        pad = indent * depth
        link = f" recovered_from=span {s.recovered_from}" \
            if s.recovered_from else ""
        if s.migrated_from:
            link += f" migrated_from=span {s.migrated_from}"
        err = f" err={s.error_code}" if s.error_code else ""
        lines.append(
            f"{pad}+{off}us [{s.kind}] {s.service}.{s.method} "
            f"span={s.span_id} {s.latency_us}us{err}{link}"
            + (f" peer={s.remote_side}" if s.remote_side else ""))
        for name, start, dur, cpu in sorted(s.phases,
                                            key=lambda ph: ph[1]):
            lines.append(f"{pad}{indent}|+{max(0, start - t0)}us {name} "
                         f"{dur}us"
                         + (f" (cpu {cpu}us)" if cpu is not None else ""))
        for t, msg in s.annotations:
            lines.append(f"{pad}{indent}@+{max(0, t - t0)}us {msg}")
    return "\n".join(lines) + "\n"


def slowest_traces(spans: list[Span], n: int = 3) -> list[list[Span]]:
    """Group `spans` by trace and return the n slowest traces (by their
    root span's latency; widest span when no root was collected),
    slowest first — the rpc_press --dump-traces selection."""
    by_trace: dict[int, list[Span]] = {}
    for s in spans:
        by_trace.setdefault(s.trace_id, []).append(s)

    def root_latency(group: list[Span]) -> int:
        ids = {s.span_id for s in group}
        roots = [s for s in group
                 if not s.parent_span_id or s.parent_span_id not in ids]
        return max(s.latency_us for s in roots or group)

    ranked = sorted(by_trace.values(), key=root_latency, reverse=True)
    return ranked[:n]

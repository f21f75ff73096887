"""Continuous-batching autoregressive decode engine.

A fixed pool of decode slots steps together through ONE jitted step
function; requests join and leave the step loop mid-flight (continuous
batching — no waiting for the slowest member of a static batch), and
each generated token streams back to its caller per step.

KV-cache residency has two modes:

  * raw block leases (default, the PR 2 discipline): every admitted
    request leases one HBM block from `ici/block_pool.py`
    (``pool.alloc`` at admit, ``block.free`` at retire) — occupancy
    returns to baseline after drain, so the chaos suite can leak-check
    the engine exactly like the transport;
  * a paged KV cache (``store=`` a
    :class:`~brpc_tpu.kvcache.KVCacheStore`): admission goes through
    ``store.admit`` — the prompt's longest cached prefix is served by
    SHARED pages and only the suffix is prefilled (``prefill_fn``, if
    given, runs once per admit on the bucket-padded suffix, so the jit
    cache sees a handful of shapes however prompts vary); each
    generated token extends the sequence's page table (copy-on-write
    when a page is shared), and the step function — when it accepts a
    third argument — receives the gathered per-slot page tables as a
    fixed-shape int32 ``[num_slots, max_pages_per_slot]`` array (-1
    padded), compiled once for the life of the engine.

The step function sees FIXED shapes — ``step_fn(tokens[num_slots],
positions[num_slots])`` (+ optional page table) — so the jit cache
compiles once for the life of the engine regardless of how requests
churn through the slots.  Inactive slots carry zeros; their outputs
are ignored.

The MODEL surface is a :class:`~brpc_tpu.models.runner.ModelRunner`
(ISSUE 10): pass ``runner=`` for a real model — a
``TransformerRunner`` attends over THIS engine's gathered page tables
with the paged-attention kernel and returns packed K/V rows the step
loop splices back into the store's pages (``write_kv``), so prefix
reuse, COW forks and crash recovery operate on real attention state.
The legacy ``step_fn``/``prefill_fn`` protocols wrap in a
``LegacyFnRunner`` adapter with byte-identical behavior.

One step in flight (ISSUE 33): an iteration of the plain step loop is
(1) claim, admit and prefill the waiters, (2) DISPATCH the next step,
(3) FETCH the oldest step in flight, the one wait for the device an
iteration, (4) BOOK it (tokens into ``seq``, log-probabilities, gaps,
the emit push, retires).  Over a runner that ``feeds_tokens`` (it takes
a slot's token on the device from the step before) step k is fetched
and booked AFTER step k+1 was dispatched, so the host's work of a step
runs while the chip runs the next; over any other runner (2) and (3)
are the same step.  One loop body either way: the lag is read from the
runner.  What MAY lag the device by a step: everything the host keeps
of a step's result (a slot's ``last_token`` / ``position`` /
``generated``, ``seq.tokens``, ``kv_filled``, the radix tree, the
client's stream), and with an ``eos_token`` the sight of the end (the
one surplus step's token is dropped at its booking; its K/V write lies
behind the sequence's length).  What must NEVER: a step is dispatched
only with the page of every position it writes in the table
(``KVCacheStore.reserve_next``), never for a slot whose step in flight
makes its last token by count, and is booked at most once and only for
the slot object it was dispatched for; and a slot is handed on
(``close``, ``takeover``, a supervised crash) only once nothing is in
flight and as booked, so that whoever rebuilds a sequence from it
applies no step twice.

Emission: each admitted request gets a BOUNDED emit buffer (a native
token ring) that the step loop pushes into without ever blocking, one
``push_many`` a step across every slot.  WHO drains it is read from what
the request's sink is.  A :class:`MessageSink` (one encoded message a
token on an rpc stream: what ``Serving.Generate`` hands in) rides the
engine's ONE emit drainer: a thread that the engine thread wakes once a
booked step, that takes what every ring holds in one native call,
encodes the step's messages and hands each connection its run of frames
as one socket write (``rpc/stream.write_runs``: credit is taken from
each stream's window without waiting).  A stream whose window is full
keeps its tokens in its own ring, and nobody else waits for it.  Any
other callable may block for as long as it likes (a chunked HTTP reply,
a supervisor's relay), so it gets an emitter thread of its own.  Either
way a consumer that stops draining fills its buffer and is CUT with
EOVERCROWDED at the next step boundary while every other slot keeps
streaming; a failing sink retires just that request.  ``on_done(err)``
fires exactly once per request, success or failure, after its buffered
tokens flush.

Supervision (serving/supervisor.py): the step loop publishes a
step-progress HEARTBEAT every iteration (suppressible by the
``serving.heartbeat`` fault site so a wedged loop can be simulated
deterministically).  With an ``on_crash`` handler installed, a step
failure — including the ``serving.step`` fault site — does NOT retire
the in-flight requests with errors: the loop stops with every slot
intact and the handler is told, so a supervisor can ``takeover()`` the
slots/waiters, re-attach their KV to the store, and re-admit them into
a replacement engine.  Unsupervised engines keep the PR 2 behavior (a
broken step function fails its requests definitively).
"""
from __future__ import annotations

import ctypes
import itertools
import re
import threading
import time
from collections import deque
from typing import Callable, Optional, Sequence

import numpy as np

from brpc_tpu import errors, fault, native_path, rpcz
from brpc_tpu.butil import hostcpu, stagetag
from brpc_tpu.butil.lockprof import InstrumentedLock
from brpc_tpu.bvar import Adder, IntRecorder, LatencyRecorder, PassiveStatus
from brpc_tpu.rpc import stream as stream_mod

_req_ids = itertools.count(1)

# Serving-wide latency recorders (ISSUE 5): TTFT (submit -> first token
# reaching the emit buffer), inter-token latency, and the per-stage
# breakdown (queue = submit -> slot install, prefill, decode = install
# -> retire).  LatencyRecorder exposes *_latency/_qps/_count and the
# percentile ladder, so /brpc_metrics scrapes them with no extra glue.
TTFT_REC = LatencyRecorder("serving_ttft_us")
ITL_REC = LatencyRecorder("serving_itl_us")
STAGE_QUEUE_REC = LatencyRecorder("serving_stage_queue_us")
STAGE_PREFILL_REC = LatencyRecorder("serving_stage_prefill_us")
STAGE_DECODE_REC = LatencyRecorder("serving_stage_decode_us")

# speculative decoding (ISSUE 11): serving-wide draft acceptance.  The
# ratio rides /brpc_metrics as one scrapeable gauge; per-generation
# acceptance is annotated on the decode spans and the generation ring.
SPEC_PROPOSED = Adder("serving_spec_proposed_tokens")
SPEC_ACCEPTED = Adder("serving_spec_accepted_tokens")


def _spec_accept_rate() -> float:
    p = SPEC_PROPOSED.get_value()
    return round(SPEC_ACCEPTED.get_value() / p, 4) if p else 0.0


PassiveStatus(_spec_accept_rate).expose("serving_spec_accept_rate")


class _EmitBuf:
    """Bounded token buffer between the shared step loop and whoever
    drains one request (its emitter thread, or the engine's emit
    drainer).  ``push`` never blocks (the step loop must not stall on a
    slow consumer); the terminal marker is always accepted so a
    cut/finished request can flush and notify.  ``wake``, where set, is
    called after a terminal lands (the drainer's signal; the step loop
    signals a step's tokens itself, once)."""

    __slots__ = ("cap", "q", "cv", "terminal", "has_terminal", "wake")

    def __init__(self, cap: int):
        self.cap = cap
        self.q: deque = deque()
        # every request's emit buffer shares ONE ledger entry (ISSUE
        # 6): per-instance stats would churn native recorder slots,
        # and the actionable number is the class-wide step-loop-vs-
        # emitter contention anyway
        self.cv = threading.Condition(InstrumentedLock("serving.emit_buf"))
        self.terminal = None
        self.has_terminal = False
        self.wake = None

    def push(self, tok: int) -> bool:
        with self.cv:
            if len(self.q) >= self.cap:
                return False
            self.q.append(tok)
            self.cv.notify()
            return True

    def push_terminal(self, err) -> None:
        with self.cv:
            if not self.has_terminal:
                self.has_terminal = True
                self.terminal = err
            self.cv.notify()
        if self.wake is not None:
            self.wake()

    def pop_batch(self, timeout_s: float, limit: int = 512):
        """``(tokens, terminal_seen, err)``: up to ``limit`` buffered
        tokens, after waiting up to ``timeout_s`` for the first; the
        terminal shows only once no token is left behind it."""
        with self.cv:
            if not self.q and not self.has_terminal and timeout_s > 0:
                self.cv.wait(timeout_s)
            toks = [self.q.popleft()
                    for _ in range(min(limit, len(self.q)))]
            term = self.has_terminal and not self.q
            return toks, term, self.terminal if term else None


class _NativeEmitBuf:
    """Native bounded emit ring (ISSUE 9) with the _EmitBuf protocol.
    The step loop pushes through ONE GIL-released
    ``brpc_tokring_push_many`` call per step across all slots (the
    engine batches; ``push`` here is the single-slot/fallback entry);
    an emitter thread drains MANY tokens per wakeup via ``pop_batch``
    instead of a Python lock round-trip per token, and the emit drainer
    takes every ring's tokens in one ``brpc_tokring_pop_each``.
    Semantics are identical to _EmitBuf: push never blocks, a full ring
    means the consumer is cut with EOVERCROWDED, the terminal is always
    accepted and only surfaces after every buffered token."""

    __slots__ = ("ring", "cap", "popbuf", "wake")

    def __init__(self, ring, cap: int):
        self.ring = ring
        self.cap = cap
        # whoever drains this ring owns the scratch array (single
        # consumer); made at the first pop_batch (the drainer's rings
        # never need one)
        self.popbuf = None
        self.wake = None

    @property
    def handle(self):
        return self.ring.handle

    @property
    def terminal(self):
        return self.ring._terminal_obj

    def push(self, tok: int) -> bool:
        return self.ring.push(int(tok))

    def push_terminal(self, err) -> None:
        self.ring.push_terminal(err)
        if self.wake is not None:
            self.wake()

    def pop_batch(self, timeout_s: float):
        """``(tokens, terminal_seen, err)`` as _EmitBuf's; the wait
        parks in native code, off the GIL."""
        out = self.popbuf
        if out is None:
            out = self.popbuf = (ctypes.c_int32 * min(int(self.cap), 512))()
        n, term, err = self.ring.pop_many(out, timeout_s)
        return out[:n], term, err


def _make_emit_buf(cap: int):
    ring = native_path.token_ring(cap)
    if ring is not None:
        return _NativeEmitBuf(ring, cap)
    return _EmitBuf(cap)


class MessageSink:
    """A request's sink that needs no thread of its own: one encoded
    message a token, and one for the terminal, on an rpc ``Stream``.  A
    subclass says what the messages are (``token_message``,
    ``done_message``).  Handed to :meth:`DecodeEngine.submit` as
    ``emit`` (with ``on_done`` its ``on_done``) it rides the engine's
    emit drainer, which writes every such sink's messages of a step in
    one pass and never waits for a window; called like any ``emit`` (a
    supervisor's relay, another engine-shaped submitter) it is the
    blocking write it always was, bounded by ``STALL_S``."""

    # how long a stream's window may take nothing before its request
    # is given up (a dead-but-open peer must not hold a slot for ever)
    STALL_S = 2.0

    __slots__ = ("stream",)

    def __init__(self, stream):
        self.stream = stream

    def token_message(self, tok: int, logprob) -> bytes:
        raise NotImplementedError

    def done_message(self, err) -> bytes:
        raise NotImplementedError

    def __call__(self, tok: int, logprob=None) -> None:
        self.stream.write(self.token_message(tok, logprob),
                          timeout_s=self.STALL_S)

    def on_done(self, err) -> None:
        """The terminal: its message, then the stream's close.  After
        the drainer, which has sent both, there is nothing left to do."""
        if self.stream.closed:
            return
        try:
            self.stream.write(self.done_message(err),
                              timeout_s=self.STALL_S)
        except errors.RpcError:
            pass   # peer already gone; nothing to tell it
        self.stream.close()


class _Lane:
    """One request on the emit drainer: the messages its stream's
    window has not taken yet (while there are any its ring is left
    alone, so the ring still bounds what waits), since when none of
    them went out, and whether the terminal's message is the last of
    them."""

    __slots__ = ("req", "sink", "carry", "stalled_at", "term")

    def __init__(self, req: "_Request"):
        self.req = req
        self.sink: MessageSink = req.emit
        self.carry: list = []
        self.stalled_at = 0.0
        self.term = False


class _Request:
    __slots__ = ("req_id", "prompt", "max_new_tokens", "emit", "on_done",
                 "buf", "t_submit", "trace", "speculative", "logprobs",
                 "_done_fired", "_mu")

    def __init__(self, prompt: Sequence[int], max_new_tokens: int,
                 emit: Callable[[int], None],
                 on_done: Optional[Callable], emit_buffer: int,
                 trace_ctx: Optional[tuple] = None,
                 speculative: bool = True, logprobs: bool = False):
        self.req_id = next(_req_ids)
        # the served tokens' log-probabilities, one appended by the
        # engine thread BEFORE its token is pushed and popped by the
        # emitter as it delivers that token (None: not asked for)
        self.logprobs = deque() if logprobs else None
        self.prompt = [int(t) for t in prompt]
        self.max_new_tokens = int(max_new_tokens)
        self.emit = emit
        self.on_done = on_done
        # opt-out flag: a False request rides a speculative engine as
        # a plain (zero-draft) member of the verify batch
        self.speculative = bool(speculative)
        self.buf = _make_emit_buf(emit_buffer)
        self.t_submit = time.monotonic()
        # (trace_id, parent_span_id, sampled): captured at submit from
        # the caller's current span (the RPC ingress span when coming
        # through Serving.Generate) or handed down explicitly (the
        # supervisor's generation-attempt span) — the decode slot runs
        # on the engine thread where the contextvar does not follow
        self.trace = trace_ctx if trace_ctx is not None \
            else rpcz.current_trace_ctx()
        self._done_fired = False
        self._mu = threading.Lock()

    @property
    def done_fired(self) -> bool:
        return self._done_fired

    def finish(self, err: Optional[errors.RpcError]) -> None:
        """Exactly-once terminal notification."""
        with self._mu:
            if self._done_fired:
                return
            self._done_fired = True
        if self.on_done is not None:
            try:
                self.on_done(err)
            except Exception:
                # an on_done bug must not kill its thread, but it must
                # leave a trace — a silently-lost terminal message reads
                # as a hung client with no server-side evidence
                import logging
                logging.getLogger(__name__).exception(
                    "engine on_done callback raised")


class _Slot:
    __slots__ = ("req", "block", "seq", "last_token", "position",
                 "generated", "inflight", "span", "t_install", "t_first_tok",
                 "last_tok_t", "itl_n", "itl_sum_s", "itl_max_s",
                 "steps_run", "spec_steps", "spec_proposed",
                 "spec_accepted")

    def __init__(self, req: _Request, block=None, seq=None,
                 span=rpcz.NULL_SPAN):
        self.req = req
        self.block = block                    # leased KV-cache block, or
        self.seq = seq                        # paged KVSeq (store mode)
        self.last_token = req.prompt[-1] if req.prompt else 0
        self.position = len(req.prompt)
        self.generated = 0
        # steps dispatched for this slot and not booked yet (0 or 1):
        # last_token / position / generated say what is BOOKED, and
        # position + inflight, generated + inflight what is dispatched
        self.inflight = 0
        self.span = span                      # per-slot decode span
        self.t_install = time.monotonic()
        self.t_first_tok = 0.0
        self.last_tok_t = 0.0
        self.itl_n = 0                        # inter-token gaps recorded
        self.itl_sum_s = 0.0
        self.itl_max_s = 0.0
        self.steps_run = 0                    # engine iterations ridden
        self.spec_steps = 0                   # verify iterations of those
        self.spec_proposed = 0                # draft tokens proposed
        self.spec_accepted = 0                # draft tokens accepted


class _Flight:
    """One dispatched decode step until its result is booked: the slots
    that rode it, index AND object (a slot cancelled, retired or given
    to another request meanwhile fails the identity check at booking
    and is dropped), the runner's handle, and whether another step was
    in flight when this one was dispatched."""

    __slots__ = ("members", "handle", "ahead")

    def __init__(self, members: list, handle, ahead: bool):
        self.members = members
        self.handle = handle
        self.ahead = ahead


class _SpecPlan:
    """One slot's draft lease for one verify iteration: the proposed
    branches, the side-branch forks holding their pages, and the row
    layout inside the fixed-shape verify batch."""

    __slots__ = ("slot", "base", "branches", "forks", "rows",
                 "speculated")

    def __init__(self, slot: _Slot):
        self.slot = slot
        self.base = slot.position       # len(seq.tokens) pre-draft
        self.branches: list = []        # token chains (branch 0 in-seq)
        self.forks: list = []           # KVSeq per side branch
        self.rows: list = []            # per branch: its local row idxs
        self.speculated = False         # branch 0 appended to the seq


class DecodeEngine:
    """Continuous-decode loop over a fixed slot pool."""

    def __init__(self, step_fn: Optional[Callable] = None, *,
                 runner=None,
                 num_slots: int = 8,
                 kv_bytes_per_slot: int = 4096,
                 pool=None,
                 device=None,
                 store=None,
                 prefill_fn: Optional[Callable] = None,
                 prefill_buckets: Sequence[int] = (16, 64, 256, 1024,
                                                   4096),
                 max_pages_per_slot: int = 64,
                 pass_page_table: Optional[bool] = None,
                 emit_buffer: int = 256,
                 eos_token: Optional[int] = None,
                 max_new_tokens_cap: int = 65536,
                 on_crash: Optional[Callable] = None,
                 draft_runner=None,
                 draft_len: int = 4,
                 name: str = "engine"):
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if emit_buffer < 1:
            raise ValueError("emit_buffer must be >= 1")
        self.num_slots = int(num_slots)
        self.kv_bytes_per_slot = int(kv_bytes_per_slot)
        self.eos_token = eos_token
        # hard per-request ceiling: a hostile/buggy max_new_tokens must
        # not pin a decode slot effectively forever (the glue layers
        # pass client-supplied values straight through)
        self.max_new_tokens_cap = int(max_new_tokens_cap)
        self.emit_buffer = int(emit_buffer)
        self.name = name
        # the paged KV cache is CALLER-owned (it outlives engines so the
        # radix tree keeps serving prefix hits across engine restarts);
        # close() never touches it
        self.store = store
        self.prefill_buckets = tuple(sorted(prefill_buckets))
        self.max_pages_per_slot = int(max_pages_per_slot)
        if pool is None and store is None:
            from brpc_tpu.ici.block_pool import get_block_pool
            pool = get_block_pool(device)
        self.pool = pool
        # the MODEL surface is a ModelRunner (ISSUE 10): legacy
        # 2-arg/3-arg step_fn / prefill_fn protocols wrap in a
        # LegacyFnRunner adapter with byte-identical behavior
        # (required-positional detection, pass_page_table override),
        # while a real runner (TransformerRunner) brings paged
        # attention over this engine's gathered page tables and packed
        # K/V rows the step loop splices back into the store's pages
        from brpc_tpu.models.runner import as_runner
        self.runner = as_runner(step_fn, prefill_fn, runner=runner,
                                store=store,
                                pass_page_table=pass_page_table)
        self._wants_pages = self.runner.wants_pages
        # vector-KV mode: the runner produces REAL packed K/V rows per
        # step; they must land in a store whose page slots carry that
        # exact layout
        self._vector_kv = self.runner.kv_bytes_per_token > 0
        if self._vector_kv:
            if store is None:
                raise ValueError("a vector-KV runner needs store= "
                                 "(its K/V live in the paged cache)")
            self.runner.bind(store)
        # speculative decoding (ISSUE 11): a draft proposer turns the
        # step loop into propose -> verify -> commit; the plain path is
        # byte-identical when no draft is configured
        from brpc_tpu.serving.speculative import as_proposer
        self._draft = as_proposer(draft_runner)
        self.draft_len = int(draft_len)
        if self._draft is not None:
            if store is None:
                raise ValueError("speculative decoding needs store= "
                                 "(draft leases live in the paged "
                                 "KV cache)")
            if self.draft_len < 1:
                raise ValueError("draft_len must be >= 1")

        # how many steps the booking of a step may lag its dispatch:
        # one where the runner can take a step's tokens on the device
        # from the step before, else none (see _plain_step).  A draft
        # engine's verify needs every slot booked
        self._lag = 1 if self.runner.feeds_tokens \
            and self._draft is None else 0
        self._flying: deque = deque()   # steps dispatched, oldest first

        safe = re.sub(r"\W", "_", name)
        # record the EXACT names exposed here so close() hides only this
        # engine's variables — a prefix wildcard would also strip a
        # sibling component whose name merely starts with ours
        from brpc_tpu.bvar.variable import exposed_variables
        pre = set(exposed_variables(f"serving_{safe}*"))
        self.steps = Adder(f"serving_{safe}_steps")
        self.steps_ahead = Adder(f"serving_{safe}_steps_ahead")
        self.tokens_out = Adder(f"serving_{safe}_tokens")
        self.retired = Adder(f"serving_{safe}_retired")
        self.admit_errors = Adder(f"serving_{safe}_admit_errors")
        self.emit_cut = Adder(f"serving_{safe}_emit_cut")
        # the drainer's socket writes, and the tokens that left in them
        self.emit_runs = Adder(f"serving_{safe}_emit_runs")
        self.emit_run_tokens = Adder(f"serving_{safe}_emit_run_tokens")
        self.occupancy_rec = IntRecorder(f"serving_{safe}_occupancy")
        PassiveStatus(self.active_count).expose(
            f"serving_{safe}_active_slots")
        self._bvar_names = [n for n in exposed_variables(f"serving_{safe}*")
                            if n not in pre]

        # supervision state: the crash handler is told (with every slot
        # left intact) instead of failing in-flight requests; the
        # heartbeat lets a watchdog distinguish a busy loop from a
        # wedged or dead one; degraded_clamp is the overload ladder's
        # max_new_tokens brownout, applied to NEW submissions only
        self._on_crash = on_crash
        self._crashed: Optional[BaseException] = None
        self._taken_over = False
        self.degraded_clamp: Optional[int] = None
        self._prefill_fn_cpu_s = 0.0   # model-fn CPU of the last admit
        self._beat_steps = 0
        self._beat_t = time.monotonic()

        # the emit drainer (one an engine, started with its first
        # MessageSink request): its lanes, the step loop's signal, and
        # how many tokens of one ring a pass takes
        self._safe = safe
        self._emit_mu = InstrumentedLock("engine.emit_lanes")
        self._emit_wake = threading.Event()
        self._lanes: list[_Lane] = []
        self._emit_thread: Optional[threading.Thread] = None
        self._pop_cap = min(self.emit_buffer, 32)

        # scratch for the per-step batched native emit push (ISSUE 9):
        # sized once at the slot count — times the per-slot burst in
        # speculative mode (accepted drafts + bonus land in ONE
        # GIL-released push_many, consecutive entries per ring) —
        # owned by the engine thread
        pushcap = self.num_slots * \
            (self.draft_len + 1 if self._draft is not None else 1)
        self._push_handles = (ctypes.c_void_p * pushcap)()
        self._push_toks = (ctypes.c_int32 * pushcap)()
        self._push_ok = (ctypes.c_uint8 * pushcap)()

        # the engine slot lock is a NAMED hot lock (ISSUE 6): submit,
        # the step loop, emitter cancels and the console all meet here
        self._cv = threading.Condition(InstrumentedLock("engine.slots"))
        self._slots: list[Optional[_Slot]] = [None] * self.num_slots
        self._waiters: deque[_Request] = deque()
        # requests popped from _waiters but not yet installed in a slot
        # (admission runs outside the cv): counted so join_idle()/
        # stats() never report idle while an admit is mid-flight
        self._admitting = 0
        self._running = True
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name=f"serving-engine-{safe}")
        self._thread.start()
        from brpc_tpu import serving as _serving
        _serving._register_engine(self)

    # ---- submission ----

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               emit: Callable[[int], None],
               on_done: Optional[Callable] = None, *,
               clamp: bool = True,
               trace_ctx: Optional[tuple] = None,
               speculative: bool = True, logprobs: bool = False) -> int:
        """Queue a request; it is admitted into the step loop at the next
        step boundary with a free slot (in-flight requests are never
        restarted).  Returns the request id; terminal state arrives via
        ``on_done(err)`` exactly once.  ``clamp=False`` exempts the
        submission from the overload ladder's ``degraded_clamp`` — the
        supervisor's crash re-admissions use it so a restart cannot
        silently truncate a budget the request was already admitted
        with.  ``trace_ctx=(trace_id, parent_span_id, sampled)``
        overrides the rpcz trace context captured from the calling
        thread (the supervisor passes its generation-attempt span so
        pre- and post-crash decode spans share one trace).
        ``speculative=False`` opts this request out of draft proposals
        on a speculative engine (it rides the verify batch as a plain
        zero-draft member; a no-draft engine ignores the flag).
        ``logprobs=True`` asks for each served token's log-probability
        (float32 log-softmax, at the runner's stated precision):
        ``emit`` is then called ``emit(token, logprob)``; a runner that
        computes none gives None.  ``emit`` is any callable (it may
        block: it gets an emitter thread of its own) or a
        :class:`MessageSink` (with ``on_done`` its ``on_done``), whose
        messages the engine's one emit drainer writes."""
        limit = self.max_new_tokens_cap
        brownout = self.degraded_clamp
        if clamp and brownout is not None:
            # overload-ladder brownout: new requests get shorter
            # generations so slots churn faster; in-flight requests
            # keep the budget they were admitted with
            limit = min(limit, int(brownout))
        req = _Request(prompt, min(int(max_new_tokens), limit),
                       emit, on_done, self.emit_buffer,
                       trace_ctx=trace_ctx, speculative=speculative,
                       logprobs=logprobs)
        if req.max_new_tokens <= 0:
            req.finish(errors.RpcError(errors.EREQUEST,
                                       "max_new_tokens must be > 0"))
            return req.req_id
        if self.store is not None and not req.prompt:
            req.finish(errors.RpcError(errors.EREQUEST,
                                       "empty prompt (paged KV mode)"))
            return req.req_id
        with self._cv:
            if not self._running:
                closed = True
            else:
                closed = False
                self._waiters.append(req)
                self._cv.notify()
        if closed:
            req.finish(errors.RpcError(errors.ELOGOFF, "engine closed"))
        return req.req_id

    def _claim_waiters_locked(self) -> list:
        """Pop as many waiters as there are free slots (under the cv).
        Only the engine thread admits, so the free count can't shrink
        between the claim and the install — it can only grow if an
        emitter cancels a slot meanwhile."""
        free = sum(1 for s in self._slots if s is None)
        claimed = []
        while len(claimed) < free and self._waiters:
            claimed.append(self._waiters.popleft())
        self._admitting += len(claimed)
        return claimed

    def _admit(self, req: _Request):
        """Lease KV state for one claimed request OUTSIDE the cv — in
        store mode admit writes the whole prompt suffix to device, and
        holding the lock through that would stall submit()/stats() and
        the console exactly like an in-lock prefill would.  A failed
        lease completes THAT request with a definite error and leaves
        the loop healthy.  Returns the installed (index, slot) pair or
        None."""
        # per-slot decode span (ISSUE 5): child of the request's trace
        # (RPC ingress or supervisor attempt span); carries TTFT, ITL
        # and the KV-cache annotations for the whole slot residency.
        # NULL_SPAN when rpcz is off — every write below absorbs free.
        tid, psid, smp = req.trace
        span = rpcz.new_span("decode", "Serving", self.name,
                             trace_id=tid, parent_span_id=psid,
                             sampled=smp if tid else None)
        queue_us = int((time.monotonic() - req.t_submit) * 1e6)
        STAGE_QUEUE_REC.add(queue_us)
        seq = block = None
        try:
            if fault.ENABLED and fault.hit(
                    "serving.slot_alloc", name=self.name) is not None:
                raise MemoryError("injected KV slot alloc failure")
            if self.store is not None:
                # reject BEFORE admit writes anything: a prompt that
                # cannot fit the page table would otherwise burn device
                # splices (and evict healthy sequences' warm cache)
                # only to be rolled back — and installing it anyway
                # would silently truncate the gathered table and decode
                # on wrong KV
                need = -(-len(req.prompt) // self.store.page_tokens)
                if need > self.max_pages_per_slot:
                    raise MemoryError(
                        f"prompt needs {need} pages "
                        f"(> max_pages_per_slot="
                        f"{self.max_pages_per_slot})")
                seq = self.store.admit(req.prompt, span=span)
            else:
                block = self.pool.alloc(self.kv_bytes_per_slot)
        except Exception as e:
            if seq is not None:
                try:
                    self.store.retire(seq, cache=False)
                except Exception:
                    pass
            self.admit_errors.add(1)
            if span is not rpcz.NULL_SPAN:
                span.error_code = errors.ELIMIT
                span.annotate(f"kv admit failed: {type(e).__name__}: {e}")
                rpcz.submit(span)
            req.finish(errors.RpcError(
                errors.ELIMIT,
                f"KV admit failed: {type(e).__name__}: {e}"))
            return None
        if span is not rpcz.NULL_SPAN:
            span.annotate(f"slot install: queue_us={queue_us} "
                          f"prompt={len(req.prompt)} "
                          f"budget={req.max_new_tokens}")
        slot = _Slot(req, block=block, seq=seq, span=span)
        with self._cv:
            if self._running:
                for i in range(self.num_slots):
                    if self._slots[i] is None:
                        self._slots[i] = slot
                        return (i, slot)
        # the engine closed while we leased (close() already drained the
        # waiters deque, so nobody else will finish this request).
        # Under a TAKEOVER the prompt's pages are worth caching: the
        # supervisor will resubmit this exact prompt, and the committed
        # pages turn its re-admission into a prefix hit
        taken = self._taken_over
        try:
            if block is not None:
                block.free()
            if seq is not None:
                self.store.retire(seq, cache=taken)
        except Exception:
            pass
        if span is not rpcz.NULL_SPAN:
            span.error_code = errors.ELOGOFF
            span.annotate("engine closed mid-admit"
                          + (" (supervisor takeover)" if taken else ""))
            rpcz.submit(span)
        req.finish(errors.RpcError(
            errors.ELOGOFF,
            "engine restarting (supervisor takeover)" if taken
            else "engine closed"))
        return None

    # ---- emission: the drainer's lanes, or a thread a callable ----

    def _start_emitter(self, slot: _Slot) -> None:
        """Who drains ``slot``'s request is read from what its sink IS:
        a :class:`MessageSink` joins the engine's one emit drainer, any
        other callable (it may block for seconds) gets a thread of its
        own."""
        req = slot.req
        if isinstance(req.emit, MessageSink):
            req.buf.wake = self._emit_wake.set
            with self._emit_mu:
                self._lanes.append(_Lane(req))
                if self._emit_thread is None:
                    self._emit_thread = threading.Thread(
                        target=self._emit_drain, daemon=True,
                        name=f"serving-emit-drain-{self._safe}")
                    self._emit_thread.start()
            return
        threading.Thread(target=self._emit_pump, args=(req,), daemon=True,
                         name=f"serving-emit-{req.req_id}").start()

    def _emit_pump(self, req: _Request) -> None:
        """Drain one request's emit buffer into its callable, a BATCH of
        tokens a wakeup (a native ring's wait parks off the GIL).  Only
        THIS request stalls when its consumer blocks; emit failures
        retire just this request; the terminal marker flushes after the
        tokens and fires on_done exactly once."""
        while True:
            toks, term, err = req.buf.pop_batch(0.25)
            if not toks and not term:
                if req.done_fired:
                    return        # finished elsewhere (close timeout path)
                continue
            # emit fan-out host-CPU accounting (ISSUE 6): the pop wait
            # burns no thread_time, so measuring from here captures
            # exactly the delivery work
            t_cpu0 = time.thread_time()
            try:
                with rpcz.stage("serve.emit", tokens=len(toks)):
                    for tok in toks:
                        if req.logprobs is None:
                            req.emit(tok)
                        else:
                            req.emit(tok, req.logprobs.popleft())
            except Exception as e:
                self._cancel(req, errors.RpcError(
                    errors.EINTERNAL,
                    f"emit failed: {type(e).__name__}: {e}"))
                return
            finally:
                hostcpu.add("emit_fanout",
                            (time.thread_time() - t_cpu0) * 1e6)
            if term:
                req.finish(err)
                return

    def _emit_drain(self) -> None:
        """The engine's ONE emit drainer: every :class:`MessageSink`
        request's tokens leave through this thread.  It sleeps until the
        step loop has pushed a step's tokens (one signal a step, not one
        a ring) or a terminal lands, and ends once the engine has
        stopped and its last lane has flushed."""
        while True:
            self._emit_wake.wait(0.25)
            self._emit_wake.clear()
            with self._emit_mu:
                lanes = list(self._lanes)
                if not lanes and not self._running:
                    self._emit_thread = None
                    return
            while lanes and self._drain_lanes(lanes):
                # a ring held more than one pass takes: again at once
                with self._emit_mu:
                    lanes = list(self._lanes)

    def _drain_lanes(self, lanes: list) -> bool:
        """One pass over the drainer's lanes: take what every ring
        holds (a lane with unsent messages keeps its tokens in its
        ring), encode, and hand each connection its run of frames in
        one write.  True when some ring was left with more."""
        t_cpu0 = time.thread_time()
        now = time.monotonic()
        tokens = 0
        with rpcz.stage("serve.emit") as stg:
            more = self._pop_into_lanes(lanes)
            busy = [ln for ln in lanes if ln.carry]
            taken, writes = stream_mod.write_runs(
                [(ln.sink.stream, ln.carry) for ln in busy]) \
                if busy else ((), 0)
            for ln, n in zip(busy, taken):
                if n < 0:
                    self._drop_lane(ln, "the stream is closed")
                    continue
                if n:
                    del ln.carry[:n]
                    ln.stalled_at = 0.0
                    tokens += n
                if not ln.carry:
                    if ln.term:
                        tokens -= 1     # the last was the terminal's
                        ln.sink.stream.close()
                        self._detach_lane(ln)
                        ln.req.finish(ln.req.buf.terminal)
                elif not ln.stalled_at:
                    ln.stalled_at = now
                elif now - ln.stalled_at > ln.sink.STALL_S:
                    self._drop_lane(
                        ln, f"stream window full for {ln.sink.STALL_S} s")
            if stg is not rpcz.NOOP_STAGE:
                stg.set(tokens=tokens, streams=len(busy))
        if writes:
            self.emit_runs.add(writes)
            self.emit_run_tokens.add(tokens)
        hostcpu.add("emit_fanout", (time.thread_time() - t_cpu0) * 1e6)
        return more

    def _pop_into_lanes(self, lanes: list) -> bool:
        """Move the tokens (and, behind them, the terminal) of every
        lane that has nothing unsent from its ring into its carry,
        encoded: ONE native call over all the native rings.  True when
        a ring gave as many as a pass takes (it may hold more)."""
        ready = [ln for ln in lanes if not ln.carry and not ln.term]
        got = []                # (lane, tokens, terminal seen)
        native = [ln for ln in ready
                  if isinstance(ln.req.buf, _NativeEmitBuf)]
        cap = self._pop_cap
        if native:
            n = len(native)
            handles = (ctypes.c_void_p * n)(
                *[ln.req.buf.handle for ln in native])
            out = (ctypes.c_int32 * (n * cap))()
            counts = (ctypes.c_int32 * n)()
            terms = (ctypes.c_uint8 * n)()
            native_path._core_lib().core.brpc_tokring_pop_each(
                handles, n, out, cap, counts, terms)
            for k, ln in enumerate(native):
                c = counts[k]
                if c or terms[k]:
                    got.append((ln, out[k * cap:k * cap + c],
                                bool(terms[k])))
        for ln in ready:
            if not isinstance(ln.req.buf, _NativeEmitBuf):
                toks, term, _ = ln.req.buf.pop_batch(0, cap)
                if toks or term:
                    got.append((ln, toks, term))
        more = False
        for ln, toks, term in got:
            req, sink = ln.req, ln.sink
            more = more or len(toks) == cap
            try:
                lps = req.logprobs
                for tok in toks:
                    ln.carry.append(sink.token_message(
                        tok, None if lps is None else lps.popleft()))
                if term:
                    ln.carry.append(sink.done_message(req.buf.terminal))
                    ln.term = True
            except Exception as e:
                self._drop_lane(ln, f"{type(e).__name__}: {e}")
        for ln in ready:
            if not ln.carry and ln.req.done_fired:
                self._detach_lane(ln)   # finished elsewhere
        return more

    def _detach_lane(self, ln: _Lane) -> None:
        with self._emit_mu:
            if ln in self._lanes:
                self._lanes.remove(ln)

    def _drop_lane(self, ln: _Lane, why: str) -> None:
        """A lane whose sink failed (dead peer, a window that stays
        full): retire just that request, as an emitter thread's failed
        ``emit`` does."""
        ln.carry.clear()
        self._detach_lane(ln)
        ln.sink.stream.close()
        # a request the engine has ended already (cut for its full
        # ring, say) ends with that error, not with the sink's
        self._cancel(ln.req, ln.req.buf.terminal or errors.RpcError(
            errors.EINTERNAL, f"emit failed: {why}"))

    def _cancel(self, req: _Request, err) -> None:
        """Retire `req`'s slot from OFF the engine thread (emitter saw
        its consumer die).  The engine thread may retire it first —
        exactly-once on finish makes the race benign."""
        released = None
        with self._cv:
            for i, s in enumerate(self._slots):
                if s is not None and s.req is req:
                    released = self._release_slot_locked(i, cache_ok=False)
                    break
        if released is not None:
            self._finalize_slot(released, err.code)
        req.finish(err)

    # ---- prefill (store mode) ----

    def _prefill(self, i: int, slot: _Slot) -> None:
        """Run the user prefill on the UNCACHED suffix of the prompt,
        bucket-padded so the jit cache compiles once per bucket.  The
        cached prefix — ``seq.prefix_hit_tokens`` tokens — is skipped
        entirely: that compute is what a cache hit buys.  A raising
        prefill retires the request (its emitter still drains the
        terminal)."""
        self._prefill_fn_cpu_s = 0.0
        if not self.runner.has_prefill or slot.seq is None:
            return
        suffix = slot.req.prompt[slot.seq.prefill_from:]
        if not suffix:
            return
        n = len(suffix)
        chunked = getattr(self.runner, "chunked_prefill", False)
        pages_row = np.full((self.max_pages_per_slot,), -1, np.int32)
        ids = slot.seq.page_ids()
        pages_row[:len(ids)] = ids[:self.max_pages_per_slot]
        # the chunks: ONE bucket-padded call as ever; a chunked runner
        # (one that carries state from chunk to chunk) gets a suffix
        # longer than the largest bucket in pieces of at most that,
        # cut also where it asks (a state snapshot's boundary), and is
        # not given the prompt's last position (its first step computes
        # it: a recurrent state sees every position once)
        start = slot.seq.prefill_from
        if chunked:
            end = start + n - 1
            cuts = sorted(c for c in self.runner.prefill_cuts(slot.seq)
                          if start < c < end) + [end]
            pieces = []
            for cut in cuts:
                while start < cut:
                    k = min(cut - start, self.prefill_buckets[-1])
                    pieces.append((start, k))
                    start += k
        else:
            pieces = [(start, n)]
        # prefill child span: the cached/uncached split IS the story —
        # a cache hit is prefill compute skipped, and this span shows
        # exactly how much
        pspan = rpcz.NULL_SPAN
        if slot.span is not rpcz.NULL_SPAN:
            pspan = rpcz.new_span("prefill", "Serving", self.name,
                                  trace_id=slot.span.trace_id,
                                  parent_span_id=slot.span.span_id,
                                  sampled=slot.span.sampled)
            pspan.annotate(f"prefill: cached={slot.seq.prefill_from} "
                           f"uncached={n} chunks={len(pieces)}")
        t0 = time.monotonic()
        t_fn_cpu = time.thread_time()
        try:
            with rpcz.stage("serve.engine.prefill", tokens=n,
                            hit_tokens=slot.seq.prefill_from,
                            chunks=len(pieces)):
                for at, k in pieces:
                    bucket = next((b for b in self.prefill_buckets
                                   if k <= b), k)
                    padded = np.zeros((bucket,), np.int32)
                    padded[:k] = slot.req.prompt[at:at + k]
                    positions = at + np.arange(bucket, dtype=np.int32)
                    if chunked:
                        self.runner.prefill(padded, positions, pages_row,
                                            seq=slot.seq, n_valid=k)
                        self._touch_beat()    # a long prompt is progress
                    else:
                        self.runner.prefill(padded, positions, pages_row,
                                            seq=slot.seq)
            self._prefill_fn_cpu_s = time.thread_time() - t_fn_cpu
        except Exception as e:
            self._prefill_fn_cpu_s = time.thread_time() - t_fn_cpu
            if pspan is not rpcz.NULL_SPAN:
                pspan.error_code = errors.EINTERNAL
                pspan.annotate(f"prefill failed: {type(e).__name__}: {e}")
                rpcz.submit(pspan)
            self._retire(i, errors.RpcError(
                errors.EINTERNAL,
                f"prefill failed: {type(e).__name__}: {e}"))
            return
        STAGE_PREFILL_REC.add(int((time.monotonic() - t0) * 1e6))
        rpcz.submit(pspan)

    # ---- the step loop ----

    def _touch_beat(self) -> None:
        """Publish step-loop progress for the supervisor's watchdog.
        The ``serving.heartbeat`` fault site SUPPRESSES the update —
        the loop keeps running but reports no progress, which is
        exactly what a wedged loop looks like from outside (so wedge
        detection and takeover-from-a-live-loop are deterministically
        testable without actually wedging a thread)."""
        if fault.ENABLED and fault.hit(
                "serving.heartbeat", name=self.name) is not None:
            return
        self._beat_steps += 1
        self._beat_t = time.monotonic()

    def heartbeat(self) -> tuple:
        """(progress counter, monotonic time of the last beat)."""
        return self._beat_steps, self._beat_t

    def has_work(self) -> bool:
        with self._cv:
            return (self._admitting > 0 or bool(self._waiters)
                    or any(s is not None for s in self._slots))

    def set_crash_handler(self, fn: Optional[Callable]) -> None:
        self._on_crash = fn

    @property
    def crashed(self) -> Optional[BaseException]:
        return self._crashed

    def _crash(self, exc: BaseException) -> None:
        """Supervised step failure: stop the loop with every slot
        INTACT (their requests are neither finished nor their KV
        leases released — the supervisor takes both over) and tell the
        crash handler.  Runs on the engine thread; the handler must
        only signal (the supervisor's watchdog does the heavy
        lifting)."""
        with self._cv:
            self._crashed = exc
            self._running = False
            self._cv.notify_all()
        try:
            self._on_crash(self, exc)
        except Exception:
            import logging
            logging.getLogger(__name__).exception(
                "engine crash handler raised")

    def _gather_page_tables(self, active) -> Optional[np.ndarray]:
        if not self._wants_pages:
            return None
        if native_path.enabled():
            # fixed-shape gather as one GIL-released native fill
            # (ISSUE 9); the row arrays stay referenced until the call
            # returns so their buffers cannot move
            table = np.empty((self.num_slots, self.max_pages_per_slot),
                             np.int32)
            rows = [(i, np.asarray(s.seq.page_ids(), np.int32))
                    for i, s in active if s.seq is not None]
            native_path.page_table_fill(
                table, [r for _, r in rows], [i for i, _ in rows])
            return table
        table = np.full((self.num_slots, self.max_pages_per_slot), -1,
                        np.int32)
        for i, s in active:
            if s.seq is None:
                continue
            ids = s.seq.page_ids()
            table[i, : len(ids)] = ids[: self.max_pages_per_slot]
        return table

    def _loop(self) -> None:
        try:
            self._run()
        finally:
            # however the loop ends (close, takeover, a crash), no step
            # stays in flight behind it
            self._discard_flying()

    def _run(self) -> None:
        while True:
            self._touch_beat()
            with self._cv:
                if not self._running:
                    # close() retires in-flight slots (with ELOGOFF) after
                    # joining this thread — exit at the step boundary
                    return
                claimed = self._claim_waiters_locked()
            # admission, prefill, and emitter start all run OUTSIDE the
            # cv: both are device calls and must not stall
            # submit()/stats() or the console
            for req in claimed:
                # stage override for the sampler (ISSUE 6): admission
                # device splices + prefill are prefill-side work even
                # though they run on the engine thread, whose NAME maps
                # to decode_step
                with stagetag.stage("prefill"):
                    t_cpu0 = time.thread_time()
                    installed = self._admit(req)
                    with self._cv:
                        self._admitting -= 1
                    if installed is None:
                        hostcpu.add("prefill",
                                    (time.thread_time() - t_cpu0) * 1e6)
                        continue
                    i, s = installed
                    self._prefill(i, s)
                    hostcpu.add("prefill",
                                (time.thread_time() - t_cpu0
                                 - self._prefill_fn_cpu_s) * 1e6)
                    hostcpu.add("model_compute",
                                self._prefill_fn_cpu_s * 1e6)
                self._start_emitter(s)
                # a long cold prefill is PROGRESS, not a wedge
                self._touch_beat()
            with self._cv:
                if not self._running:
                    return
                active = [(i, s) for i, s in enumerate(self._slots)
                          if s is not None]
                if not active and not self._flying:
                    if not self._waiters:
                        # bounded idle wait so the heartbeat keeps
                        # ticking: an idle-but-alive loop must stay
                        # distinguishable from a wedged one
                        self._cv.wait(0.25)
                    continue
            if self._draft is not None:
                if not self._spec_step(active):
                    return
            elif not self._plain_step(active):
                return

    def _plain_step(self, active) -> bool:
        """One iteration of the plain decode path (no draft), in three
        parts: DISPATCH the next step for every slot that still has a
        token to make, FETCH the oldest step in flight once more than
        ``_lag`` are, and BOOK the fetched step (tokens into ``seq``,
        log-probabilities, gaps, the emit push, retires).  At lag 0 the
        step dispatched is the step fetched: the loop every runner had.
        At lag 1 step k is booked while the chip runs step k+1, whose
        operands are what the host knows without k's result: positions
        advance by one, the page of the position k's token will take is
        reserved, and the token itself is read from k's result on the
        device.  Returns False when the loop must stop (supervised
        crash)."""
        t_cpu0 = time.thread_time()
        flying = self._flying
        ahead = bool(flying)
        tok = np.zeros((self.num_slots,), np.int32)
        pos = np.zeros((self.num_slots,), np.int32)
        fed = np.zeros((self.num_slots,), bool)
        seqs = [None] * self.num_slots
        members = []
        for i, s in active:
            if s.generated + s.inflight >= s.req.max_new_tokens:
                continue    # the step in flight makes its last token
            if s.inflight:
                # its token is still on the device, and the step writes
                # K/V where that token will sit: the page goes into the
                # table now, the token at the booking
                if s.seq is not None and not self._grow_kv(i, s):
                    continue
                fed[i] = True
            else:
                tok[i] = s.last_token
            pos[i] = s.position + s.inflight
            seqs[i] = s.seq
            members.append((i, s))
        pages = self._gather_page_tables(members)
        due = None
        t_fn_cpu = time.thread_time()
        try:
            if fault.ENABLED and fault.hit(
                    "serving.step", name=self.name) is not None:
                raise RuntimeError("injected decode step crash")
            # one stage a device step, around its dispatch and the one
            # blocking fetch of the iteration
            with rpcz.stage("serve.engine.step", slots=len(members),
                            tokens=len(members), ahead=int(ahead)) \
                    if members else rpcz.NOOP_STAGE:
                if members:
                    handle = self.runner.dispatch_step(
                        tok, pos, pages, seqs=seqs,
                        prev=flying[-1].handle if flying else None, fed=fed)
                    for _, s in members:
                        s.inflight += 1
                    flying.append(_Flight(members, handle, ahead))
                if flying and (len(flying) > self._lag or not members):
                    out, kv_rows, step_lp = self.runner.complete_step(
                        flying[0].handle)
                    due = flying[0]
        except Exception as e:
            # a failed dispatch or fetch takes whatever else is in
            # flight with it: nothing is booked after a failure
            self._discard_flying()
            if self._on_crash is not None:
                # supervised: this is an ENGINE failure, not the
                # requests' — leave every slot intact for takeover
                # and re-admission into the replacement engine
                self._crash(e)
                return False
            # unsupervised: a broken step function must not wedge
            # callers — retire every active request with a definite
            # error
            err = errors.RpcError(
                errors.EINTERNAL,
                f"decode step failed: {type(e).__name__}: {e}")
            with self._cv:
                released = [self._release_slot_locked(i,
                                                      cache_ok=False)
                            for i, s in active]
            for s in filter(None, released):
                self._finalize_slot(s, errors.EINTERNAL)
                s.req.buf.push_terminal(err)
            return True
        fn_cpu_s = time.thread_time() - t_fn_cpu
        if due is not None:
            # in flight until it is booked: takeover() waits for that
            self._book(due, out, kv_rows, step_lp)
            flying.popleft()
        # per-stage host-CPU accounting (ISSUE 6): this iteration's
        # step-loop bookkeeping minus the model step itself
        hostcpu.add("decode_step",
                    (time.thread_time() - t_cpu0 - fn_cpu_s) * 1e6)
        hostcpu.add("model_compute", fn_cpu_s * 1e6)
        return True

    def _grow_kv(self, i: int, s: _Slot, token: Optional[int] = None) -> bool:
        """Grow slot ``i``'s sequence: by ``token``, or (None) by the
        page its next position needs, ahead of the token.  A failure
        retires THAT request (pool exhausted and nothing evictable, or
        the fixed page table outgrown: ELIMIT) and leaves the loop and
        its step-mates running."""
        try:
            if token is None:
                self.store.reserve_next(s.seq)
            else:
                self.store.extend(s.seq, token)
        except MemoryError as e:
            self._retire(i, errors.RpcError(
                errors.ELIMIT, f"KV page alloc failed: {e}"))
            return False
        except Exception as e:
            self._retire(i, errors.RpcError(
                errors.EINTERNAL,
                f"KV extend failed: {type(e).__name__}: {e}"))
            return False
        if len(s.seq.pages) > self.max_pages_per_slot:
            self._retire(i, errors.RpcError(
                errors.ELIMIT,
                f"page table overflow "
                f"(> {self.max_pages_per_slot} pages)"))
            return False
        return True

    def _discard_flying(self) -> None:
        """Drop every step in flight, unbooked: each is waited for, so
        that nothing of this engine still runs on the device when its
        slots are handed on, and its result reaches nobody (a slot's
        ``seq`` and counts say what was booked; a sequence that goes on
        elsewhere is rebuilt from them, so no step is applied twice)."""
        while self._flying:
            flight = self._flying.popleft()
            try:
                self.runner.complete_step(flight.handle)
            except Exception:
                pass    # the failure that brought us here, or its wake
            for _, s in flight.members:
                s.inflight = 0
        with self._cv:
            self._cv.notify_all()

    def _book(self, flight: _Flight, out, kv_rows, step_lp) -> None:
        """A fetched step's bookkeeping, slot by slot (see _plain_step).
        A slot that an emitter cancelled, a failed reservation retired
        or a new request took since the dispatch is dropped: its token
        is delivered nowhere, appended nowhere and counted nowhere (with
        an ``eos_token`` the end is seen one step late at lag 1, and
        this is where the surplus step goes)."""
        members = flight.members
        self.steps.add(1)
        if flight.ahead:
            self.steps_ahead.add(1)
        self.occupancy_rec.add(len(members))
        t_tok = time.monotonic()
        # the per-slot KV row writes ride ONE batched splice
        # (ISSUE 11): one H2D transfer + one I/O critical section
        # across every surviving slot instead of one per slot
        wrote_bad: set = set()
        if kv_rows is not None:
            items = [(i, s) for i, s in members
                     if self._slots[i] is s and s.seq is not None]
            fails = self.store.write_kv_batch(
                [(s.seq, s.position - 1, kv_rows[i:i + 1])
                 for i, s in items])
            for wi, e in fails:
                i, _ = items[wi]
                wrote_bad.add(i)
                self._retire(i, errors.RpcError(
                    errors.EINTERNAL,
                    f"KV write failed: {type(e).__name__}: {e}"))
        deliver: list = []   # (slot index, slot, (token,)) surviving
        for i, s in members:
            s.inflight -= 1
            if i in wrote_bad or self._slots[i] is not s:
                continue    # cancelled, retired or replaced mid-step
            nxt = int(out[i])
            if s.req.logprobs is not None:
                s.req.logprobs.append(
                    float(step_lp[i]) if step_lp is not None else None)
            s.last_token = nxt
            s.position += 1
            s.generated += 1
            s.steps_run += 1
            self.tokens_out.add(1)
            hostcpu.tokens_total.add(1)
            if s.last_tok_t:
                gap = t_tok - s.last_tok_t
                ITL_REC.add(int(gap * 1e6))
                s.itl_n += 1
                s.itl_sum_s += gap
                if gap > s.itl_max_s:
                    s.itl_max_s = gap
            else:
                s.t_first_tok = t_tok
                ttft_us = int((t_tok - s.req.t_submit) * 1e6)
                TTFT_REC.add(ttft_us)
                if s.span is not rpcz.NULL_SPAN:
                    s.span.annotate(f"first token: ttft_us={ttft_us}")
            s.last_tok_t = t_tok
            # pool exhausted and nothing evictable: THIS request
            # errors, the loop and its peers go on
            if s.seq is not None and not self._grow_kv(i, s, nxt):
                continue
            deliver.append((i, s, (nxt,)))
        self._deliver(deliver)

    # ---- speculative decoding (ISSUE 11) ----

    def _spec_release(self, plan: "_SpecPlan") -> None:
        """Return one slot's draft lease to baseline: roll the main
        sequence back to its pre-draft length (unless something else —
        an emitter cancel's retire, a supervisor detach — already
        owns/released it) and retire every side-branch fork.  Runs on
        every non-commit exit path, so a crashed or cancelled verify
        can never leak a draft page."""
        s = plan.slot
        try:
            # unconditional: a speculate that raised MID-APPEND left a
            # partial draft tail the `speculated` flag never saw
            if s.seq is not None and not s.seq.retired \
                    and len(s.seq.tokens) > plan.base:
                self.store.rollback(s.seq, plan.base)
        except Exception:
            pass
        plan.speculated = False
        for f in plan.forks:
            if f is None:
                continue
            try:
                self.store.retire(f, cache=False)
            except Exception:
                pass
        plan.forks = []

    def _spec_propose(self, s: _Slot) -> list:
        """Draft branches for one slot, clamped to the row budget, the
        remaining token budget, and the fixed page-table width.  Empty
        when the slot opted out, has no headroom, or the proposer has
        nothing to say — the slot then rides the verify batch as a
        plain zero-draft member."""
        rem = s.req.max_new_tokens - s.generated
        if not s.req.speculative or rem <= 1 or s.seq is None:
            return []
        # the drafts (plus the bonus token) must fit the FIXED page
        # table the verify rows gather — never speculate past it
        avail = self.max_pages_per_slot * self.store.page_tokens \
            - s.position - 1
        cap = min(self.draft_len, rem - 1, avail)
        if cap < 1:
            return []
        try:
            branches = self._draft.propose(s.seq.tokens, cap)
        except Exception:
            return []      # a broken proposer degrades, never crashes
        kept, total = [], 0
        for b in branches:
            b = [int(t) for t in b][:cap - total]
            if not b:
                break
            kept.append(b)
            total += len(b)
        return kept

    def _spec_step(self, active) -> bool:
        """One speculative iteration: PROPOSE draft branches per slot,
        lease their pages (branch 0 rides the in-sequence draft cursor,
        side branches ride ``fork`` — COW isolates the divergent
        tails), VERIFY every row of every slot in ONE runner call, then
        COMMIT the longest greedy-matching prefix per slot: accepted
        rows' K/V splice in one ``write_kv_batch`` (page commit —
        ``kv_filled`` advances), rejected tails roll back (pages return
        to the pool), and the accepted tokens plus the target's bonus
        token fan out in one batched ring push.  Slots at different
        accept depths — including zero-draft plain slots — coexist in
        the one fixed-shape batch.  Returns False when the loop must
        stop (supervised crash)."""
        t_cpu0 = time.thread_time()
        k1 = self.draft_len + 1
        mp = self.max_pages_per_slot
        # ---- propose + lease ----
        plans: dict[int, _SpecPlan] = {}
        for i, s in active:
            plan = _SpecPlan(s)
            plans[i] = plan
            branches = self._spec_propose(s)
            if not branches:
                continue
            try:
                # forks FIRST (they must share only the base pages);
                # the branch-0 speculate then COWs the shared tail
                for b in branches[1:]:
                    f = self.store.fork(s.seq)
                    plan.forks.append(f)
                    self.store.speculate(f, b)
                self.store.speculate(s.seq, branches[0])
                plan.speculated = True
                plan.branches = branches
            except Exception:
                # lease pressure (pool exhausted mid-speculate):
                # degrade THIS slot to a plain step, peers keep their
                # drafts
                self._spec_release(plan)
                plan.branches = []
        if not any(p.branches for p in plans.values()):
            # nobody proposed (cold context the proposer has no basis
            # for, or every slot opted out): a (draft_len+1)-wide
            # verify would pay ~k1x the model FLOPs to emit one token
            # per slot — run the plain step instead.  No leases were
            # taken (empty branches lease nothing), and both paths
            # keep the same position/kv_filled invariants, so
            # iterations can alternate freely within one generation.
            return self._plain_step(active)
        # ---- build the fixed-shape verify batch ----
        tok = np.zeros((self.num_slots, k1), np.int32)
        pos = np.zeros((self.num_slots, k1), np.int32)
        tables = np.full((self.num_slots * k1, mp), -1, np.int32)
        base_len = np.zeros((self.num_slots * k1,), np.int32)
        mask = np.zeros((self.num_slots, k1, k1), bool)
        for i, s in active:
            plan = plans[i]
            base = s.position - 1          # materialized arena keys
            main_ids = np.full((mp,), -1, np.int32)
            ids = s.seq.page_ids() if s.seq is not None else []
            main_ids[:min(len(ids), mp)] = ids[:mp]
            tok[i, 0] = s.last_token
            pos[i, 0] = s.position
            mask[i, 0, 0] = True
            tables[i * k1] = main_ids
            base_len[i * k1] = base
            r = 1
            plan.rows = []
            for bi, b in enumerate(plan.branches):
                if bi == 0:
                    owner_ids = main_ids
                else:
                    owner_ids = np.full((mp,), -1, np.int32)
                    fids = plan.forks[bi - 1].page_ids()
                    owner_ids[:min(len(fids), mp)] = fids[:mp]
                rows = []
                for c, t in enumerate(b):
                    tok[i, r] = t
                    pos[i, r] = s.position + c + 1
                    tables[i * k1 + r] = owner_ids
                    base_len[i * k1 + r] = base
                    mask[i, r, 0] = True          # the shared root
                    for pr in rows:
                        mask[i, r, pr] = True     # branch ancestors
                    mask[i, r, r] = True          # self (in-call key)
                    rows.append(r)
                    r += 1
                plan.rows.append(rows)
        # ---- verify: the whole draft tree, one call ----
        t_fn_cpu = time.thread_time()
        try:
            if fault.ENABLED and fault.hit(
                    "serving.spec_verify", name=self.name) is not None:
                raise RuntimeError("injected speculative verify crash")
            out, kv_rows = self.runner.verify(tok, pos, tables,
                                              base_len, mask)
        except Exception as e:
            # draft leases FIRST — a crashed verify must leave zero
            # draft pages behind whether the supervisor takes over or
            # the requests fail definitively
            for plan in plans.values():
                self._spec_release(plan)
            if self._on_crash is not None:
                self._crash(e)
                return False
            err = errors.RpcError(
                errors.EINTERNAL,
                f"speculative verify failed: {type(e).__name__}: {e}")
            with self._cv:
                released = [self._release_slot_locked(i, cache_ok=False)
                            for i, s in active]
            for s in filter(None, released):
                self._finalize_slot(s, errors.EINTERNAL)
                s.req.buf.push_terminal(err)
            return True
        fn_cpu_s = time.thread_time() - t_fn_cpu
        self.steps.add(1)
        self.occupancy_rec.add(len(active))
        t_tok = time.monotonic()
        # ---- accept + commit ----
        writes: list = []         # (seq, pos, rows) for the batch splice
        write_owner: list = []    # slot index per staged write
        staged: dict[int, dict] = {}
        for i, s in active:
            plan = plans[i]
            if self._slots[i] is not s:
                # an emitter cancelled it mid-verify (its retire
                # already released the main lease); forks remain ours
                self._spec_release(plan)
                continue
            # greedy tree walk: the true next token at each row is the
            # target's argmax there; the winning branch is the longest
            # chain whose tokens match truth step by step
            t_star = int(out[i, 0])
            path: list = []
            winner = -1
            for bi, rows in enumerate(plan.rows):
                if not rows or int(tok[i, rows[0]]) != t_star:
                    continue
                sel = [rows[0]]
                for nxt_row in rows[1:]:
                    if int(tok[i, nxt_row]) == int(out[i, sel[-1]]):
                        sel.append(nxt_row)
                    else:
                        break
                if len(sel) > len(path):
                    path, winner = sel, bi
            a = len(path)
            bonus = int(out[i, path[-1]]) if path else t_star
            raw = [int(tok[i, r]) for r in path] + [bonus]
            if self.eos_token is not None and self.eos_token in raw:
                raw = raw[:raw.index(self.eos_token) + 1]
            rem = s.req.max_new_tokens - s.generated
            raw = raw[:rem]
            n = len(raw)
            kept = min(n, a)
            bonus_emitted = n == a + 1
            proposed = sum(len(b) for b in plan.branches)
            try:
                if winner > 0:
                    # a side branch won: the slot ADOPTS its fork (the
                    # fork owns base refs + the branch's draft pages);
                    # the original — and branch 0's draft tail with it
                    # — retires uncached
                    f = plan.forks[winner - 1]
                    plan.forks[winner - 1] = None
                    f.prefill_from = s.seq.prefill_from
                    f.span = s.seq.span
                    self.store.retire(s.seq, cache=False)
                    s.seq = f
                    plan.speculated = True   # fork tail rolls back below
                # reject: truncate to the accepted prefix, releasing
                # the rejected tail's pages
                self.store.rollback(s.seq, plan.base + kept)
                plan.speculated = False
                for f in plan.forks:
                    if f is not None:
                        self.store.retire(f, cache=False)
                plan.forks = []
                if kv_rows is None:
                    # token-harness pages: the stand-in bytes landed at
                    # speculate time — accepting IS the cursor advance
                    self.store.commit_draft(s.seq, plan.base + kept)
            except Exception as e:
                self._spec_release(plan)
                self._retire(i, errors.RpcError(
                    errors.EINTERNAL,
                    f"spec commit failed: {type(e).__name__}: {e}"))
                continue
            if kv_rows is not None:
                # accepted rows' REAL K/V (row 0 = the query position,
                # exactly the plain step's write) — staged for ONE
                # batched splice across all slots
                rows_sel = np.take(kv_rows[i], [0] + path[:kept],
                                   axis=0)
                writes.append((s.seq, plan.base - 1, rows_sel))
                write_owner.append(i)
            staged[i] = {"emit": raw, "kept": kept,
                         "bonus_emitted": bonus_emitted,
                         "proposed": proposed}
        fails = self.store.write_kv_batch(writes) if writes else []
        for wi, e in fails:
            i = write_owner[wi]
            staged.pop(i, None)
            self._retire(i, errors.RpcError(
                errors.EINTERNAL,
                f"KV write failed: {type(e).__name__}: {e}"))
        # ---- bookkeeping + emission ----
        deliver: list = []        # (slot index, slot, [tokens])
        for i, s in active:
            st = staged.get(i)
            if st is None:
                continue
            if self._slots[i] is not s:
                # an emitter CANCELLED the slot mid-commit: its release
                # retired whichever seq the slot held when it ran — if
                # that was before a side-branch adopt swapped s.seq,
                # the adopted fork is still ours to release.  A
                # supervisor TAKEOVER instead keeps the seq alive for
                # detach/re-admission.
                if not self._taken_over:
                    try:
                        if s.seq is not None and not s.seq.retired:
                            self.store.retire(s.seq, cache=False)
                    except Exception:
                        pass
                continue
            raw, kept = st["emit"], st["kept"]
            n = len(raw)
            if st["bonus_emitted"]:
                try:
                    self.store.extend(s.seq, raw[-1])
                except MemoryError as e:
                    self._retire(i, errors.RpcError(
                        errors.ELIMIT, f"KV page alloc failed: {e}"))
                    continue
                except Exception as e:
                    self._retire(i, errors.RpcError(
                        errors.EINTERNAL,
                        f"KV extend failed: {type(e).__name__}: {e}"))
                    continue
            if len(s.seq.pages) > self.max_pages_per_slot:
                self._retire(i, errors.RpcError(
                    errors.ELIMIT,
                    f"page table overflow "
                    f"(> {self.max_pages_per_slot} pages)"))
                continue
            s.last_token = raw[-1]
            s.position = len(s.seq.tokens)
            s.generated += n
            s.steps_run += 1
            s.spec_steps += 1
            s.spec_proposed += st["proposed"]
            s.spec_accepted += kept
            SPEC_PROPOSED.add(st["proposed"])
            SPEC_ACCEPTED.add(kept)
            self.tokens_out.add(n)
            hostcpu.tokens_total.add(n)
            if s.last_tok_t:
                # one inter-BURST gap per verify: tokens genuinely
                # arrive together, so per-token zeros would only bury
                # the real cadence
                gap = t_tok - s.last_tok_t
                ITL_REC.add(int(gap * 1e6))
                s.itl_n += 1
                s.itl_sum_s += gap
                if gap > s.itl_max_s:
                    s.itl_max_s = gap
            else:
                s.t_first_tok = t_tok
                ttft_us = int((t_tok - s.req.t_submit) * 1e6)
                TTFT_REC.add(ttft_us)
                if s.span is not rpcz.NULL_SPAN:
                    s.span.annotate(f"first token: ttft_us={ttft_us}")
            s.last_tok_t = t_tok
            deliver.append((i, s, raw))
        self._deliver(deliver)
        hostcpu.add("decode_step",
                    (time.thread_time() - t_cpu0 - fn_cpu_s) * 1e6)
        hostcpu.add("model_compute", fn_cpu_s * 1e6)
        return True

    def _deliver(self, deliver: list) -> None:
        """A booked step's tokens to their consumers, and the end of
        what ends with them: each entry is ``(i, slot, [tokens])``, one
        token a slot from the plain step, a verify burst from the
        speculative one.  A consumer that stopped draining is cut HERE,
        without the step loop ever blocking in a write.  The emit
        drainer is woken once the tokens are in and the first request
        that ends with them has its terminal in (a terminal wakes it
        itself), so a request's last token and its ``{"done"}`` leave in
        one run."""
        pushed = self._push_token_runs(deliver)
        for (i, s, toks), ok in zip(deliver, pushed):
            if not ok:
                self.emit_cut.add(1)
                if s.span is not rpcz.NULL_SPAN:
                    s.span.annotate(
                        f"emit-buffer stall: {self.emit_buffer} "
                        f"buffered tokens undrained, consumer cut")
                self._retire(i, errors.RpcError(
                    errors.EOVERCROWDED,
                    "slow stream consumer: emit buffer overflow"))
            elif s.generated >= s.req.max_new_tokens or \
                    (self.eos_token is not None
                     and toks[-1] == self.eos_token):
                self._retire(i, None)
        if self._lanes:
            self._emit_wake.set()

    def _push_token_runs(self, deliver: list) -> list:
        """THE emit fan-out (ISSUE 9/11): each entry is ``(i, slot,
        [tokens])`` — one token per slot from the plain step, a verify
        burst from the speculative step.  Every native ring's run rides
        the one GIL-released ``push_many`` as consecutive (handle,
        token) pairs (the ring preserves call order), Python _EmitBufs
        push token by token.  An entry reads False when ANY of its
        tokens failed to land — the consumer is cut with EOVERCROWDED,
        so a partially-delivered burst only ever precedes an error
        terminal, never a silent gap in a healthy stream.  The slot
        objects in ``deliver`` hold their requests (and so the ring
        wrappers) alive across the native call — a racing emitter
        cancel can retire the slot but never free the ring under
        us."""
        if not deliver:
            return []
        ok = [True] * len(deliver)
        native = []               # flat (entry idx, token) pairs
        for k, (i, s, toks) in enumerate(deliver):
            buf = s.req.buf
            if isinstance(buf, _NativeEmitBuf):
                native.extend((k, t) for t in toks)
            else:
                for t in toks:
                    if not buf.push(t):
                        ok[k] = False
                        break
        if native:
            h, t = self._push_handles, self._push_toks
            for j, (k, tk) in enumerate(native):
                h[j] = deliver[k][1].req.buf.handle
                t[j] = tk
            native_path._core_lib().core.brpc_tokring_push_many(
                h, t, len(native), self._push_ok)
            for j, (k, _) in enumerate(native):
                if not self._push_ok[j]:
                    ok[k] = False
        return ok

    def _release_slot_locked(self, i: int, cache_ok: bool = True):
        """Release slot i under the cv: return the KV lease exactly once
        (raw block freed, or paged seq retired — cached into the radix
        tree only on clean completion) and return the SLOT for the
        CALLER to finalize (span/generation record) and finish (emit
        buffer's terminal marker) OUTSIDE the lock — collector handoff
        and the generation ring must not serialize the step loop."""
        s = self._slots[i]
        if s is None:
            return None
        self._slots[i] = None
        self.retired.add(1)
        try:
            if s.block is not None:
                s.block.free()
            if s.seq is not None:
                self.store.retire(s.seq, cache=cache_ok)
        except Exception:
            pass
        return s

    def _finalize_slot(self, s: _Slot, err_code: int) -> None:
        """Close out a retiring slot's observability state: the decode
        span (ITL summary annotation, error code) and one
        recent-generation record for the /serving/generations page."""
        now = time.monotonic()
        dur_us = int((now - s.t_install) * 1e6)
        STAGE_DECODE_REC.add(dur_us)
        ttft_us = int((s.t_first_tok - s.req.t_submit) * 1e6) \
            if s.t_first_tok else 0
        itl_avg_us = int(s.itl_sum_s / s.itl_n * 1e6) if s.itl_n else 0
        itl_max_us = int(s.itl_max_s * 1e6)
        # per-generation speculative-decoding summary (ISSUE 11):
        # acceptance and depth for the decode span and the
        # /serving/generations ring — the numbers that say whether the
        # draft is earning its keep for THIS traffic
        spec = None
        if self._draft is not None and s.spec_steps:
            spec = {
                "spec_proposed": s.spec_proposed,
                "spec_accepted": s.spec_accepted,
                "accept_rate": round(
                    s.spec_accepted / s.spec_proposed, 4)
                if s.spec_proposed else 0.0,
                "draft_depth": round(
                    s.spec_proposed / s.spec_steps, 2),
                # over ALL engine iterations, including the plain-step
                # fallbacks a cold context rides before drafts land —
                # the number that says what speculation bought the
                # whole generation
                "tokens_per_step": round(
                    s.generated / max(1, s.steps_run), 2),
            }
        span = s.span
        if span is not rpcz.NULL_SPAN:
            span.error_code = span.error_code or err_code
            span.annotate(
                f"retired: generated={s.generated} ttft_us={ttft_us} "
                f"itl_avg_us={itl_avg_us} itl_max_us={itl_max_us}")
            if spec is not None:
                span.annotate(
                    f"speculative: accept_rate={spec['accept_rate']} "
                    f"draft_depth={spec['draft_depth']} "
                    f"tokens_per_step={spec['tokens_per_step']} "
                    f"({spec['spec_accepted']}/{spec['spec_proposed']} "
                    f"drafts accepted over {s.spec_steps} verifies)")
            rpcz.submit(span)
        try:
            from brpc_tpu import serving as _serving
            _serving.record_generation({
                "engine": self.name,
                "req_id": s.req.req_id,
                "trace_id": span.trace_id,
                "prompt_len": len(s.req.prompt),
                "prefix_hit": s.seq.prefix_hit_tokens
                if s.seq is not None else 0,
                "generated": s.generated,
                "ttft_us": ttft_us,
                "itl_avg_us": itl_avg_us,
                "itl_max_us": itl_max_us,
                "duration_us": dur_us,
                "error_code": err_code,
                **(spec or {}),
            })
        except Exception:
            pass  # a console-ring bug must never break a retire

    def _retire(self, i: int, err) -> None:
        with self._cv:
            s = self._release_slot_locked(i, cache_ok=err is None)
        if s is not None:
            self._finalize_slot(s, err.code if err is not None else 0)
            s.req.buf.push_terminal(err)

    # ---- lifecycle / introspection ----

    def takeover(self) -> tuple:
        """Stop a crashed/wedged engine WITHOUT completing its
        requests: detach every in-flight slot and queued waiter so a
        supervisor can re-attach their KV to the store and re-admit
        them into a replacement engine.  Returns ``(slots, waiters)``
        — the caller now OWNS each slot's KV lease (block or seq) and
        each request's terminal notification.  Safe against a loop
        thread still stuck inside ``step_fn``: its post-step writes
        check slot identity, so a stolen slot's request can never
        receive another token from the old loop."""
        with self._cv:
            self._running = False
            self._taken_over = True
            self._cv.notify_all()
            stolen = [s for s in self._slots if s is not None]
            for i in range(self.num_slots):
                self._slots[i] = None
            waiters, self._waiters = list(self._waiters), deque()
            if threading.current_thread() is not self._thread:
                # the loop, if it still runs, ends the booking it is in
                # and discards its step in flight on its way out: the
                # slots are handed on as booked, with nothing of this
                # engine left on the device (a loop wedged inside the
                # device is not waited for long: what it comes back
                # with fails the identity check)
                self._cv.wait_for(
                    lambda: not self._flying
                    or not self._thread.is_alive(), timeout=1.0)
        return stolen, waiters

    def active_count(self) -> int:
        with self._cv:
            return sum(1 for s in self._slots if s is not None)

    def close(self, timeout_s: float = 10.0) -> None:
        """Stop the loop; in-flight and queued requests complete with
        ELOGOFF and every KV lease (block or paged seq) returns to its
        pool.  The KV store itself is caller-owned and stays up."""
        with self._cv:
            self._running = False
            self._cv.notify_all()
        self._thread.join(timeout_s)
        err = errors.RpcError(errors.ELOGOFF, "engine closed")
        with self._cv:
            released = [self._release_slot_locked(i, cache_ok=False)
                        for i in range(self.num_slots)]
            waiters, self._waiters = list(self._waiters), deque()
        for s in filter(None, released):
            # the emitter drains buffered tokens then fires on_done;
            # finish() is exactly-once so a racing emitter is benign
            self._finalize_slot(s, errors.ELOGOFF)
            s.req.buf.push_terminal(err)
        for req in waiters:
            req.finish(err)   # never admitted: no emitter exists
        # unpin exposed bvars (bound-method PassiveStatus would keep a
        # closed engine alive in the global registry forever)
        from brpc_tpu.bvar.variable import find_exposed
        for n in self._bvar_names:
            v = find_exposed(n)
            if v is not None:
                v.hide()

    def join_idle(self, timeout_s: float = 30.0) -> bool:
        """Block until no request is active or queued (drain helper for
        tests and graceful shutdown)."""
        import time
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._cv:
                if not self._waiters and not self._admitting and all(
                        s is None for s in self._slots):
                    return True
            time.sleep(0.005)
        return False

    def queue_depth(self) -> float:
        """Admission backlog per slot (queued waiters + mid-admit over
        num_slots) — the queue-depth pressure the degradation ladders
        (supervisor and cluster router) escalate on."""
        with self._cv:
            queued = len(self._waiters) + self._admitting
        return queued / max(1, self.num_slots)

    def stats(self) -> dict:
        with self._cv:
            slot_map = [
                None if s is None else {
                    "req_id": s.req.req_id,
                    "generated": s.generated,
                    "max_new_tokens": s.req.max_new_tokens,
                    "position": s.position,
                    **({"pages": len(s.seq.pages),
                        "prefix_hit": s.seq.prefix_hit_tokens}
                       if s.seq is not None else {}),
                } for s in self._slots]
            queued = len(self._waiters) + self._admitting
        out = {
            "num_slots": self.num_slots,
            "kv_bytes_per_slot": self.kv_bytes_per_slot,
            "slots": slot_map,
            "queued": queued,
            "steps": self.steps.get_value(),
            "steps_ahead": self.steps_ahead.get_value(),
            "tokens": self.tokens_out.get_value(),
            "retired": self.retired.get_value(),
            "admit_errors": self.admit_errors.get_value(),
            "emit_buffer": self.emit_buffer,
            "emit_cut": self.emit_cut.get_value(),
            "emit_runs": self.emit_runs.get_value(),
            "emit_tokens_per_run": round(
                self.emit_run_tokens.get_value()
                / max(1, self.emit_runs.get_value()), 2),
            "avg_step_occupancy": round(self.occupancy_rec.get_value(), 2),
            "heartbeat_steps": self._beat_steps,
            "heartbeat_age_s": round(time.monotonic() - self._beat_t, 3),
            "crashed": self._crashed is not None,
            "degraded_clamp": self.degraded_clamp,
            "runner": self.runner.name,
            "vector_kv": self._vector_kv,
            "speculative": self._draft is not None,
        }
        if self._draft is not None:
            out["draft"] = getattr(self._draft, "name", "draft")
            out["draft_len"] = self.draft_len
        if self.store is not None:
            out["kvcache"] = self.store.name
        return out

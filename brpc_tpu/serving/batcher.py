"""Deadline-aware dynamic batcher — coalesces concurrent unary RPCs into
batched tensor calls.

"RPC Considered Harmful" (PAPERS.md) quantifies why per-request tensor
RPC wastes the fabric: each call pays the full dispatch overhead for one
row of work.  The batcher gathers concurrent requests under a
``max_batch_size`` / ``max_delay_us`` policy, pads them to a SMALL FIXED
SET of bucket shapes so the jit cache is hit (never a per-shape
recompile), runs the batch through one user-supplied jitted function,
and scatters the rows back to each caller.

Admission is deadline-aware and rides the existing limiter/ELIMIT
machinery rather than a new error path: a queued request whose
Controller deadline would expire before the predicted batch completion
(window wait + EMA batch execution time x batches ahead) is shed
IMMEDIATELY with ELIMIT — the caller learns "would have missed" in
microseconds instead of burning a queue slot to learn it at its
deadline.  An optional concurrency limiter (the same
``create_limiter`` specs servers use: int, "auto", "timeout[:ms]")
gates queue depth the same way.

BROWNOUT (``brownout`` attribute, set by an EngineSupervisor's
degradation ladder): at level >= 1 the LOWEST-priority lane —
deadline-less requests, the ones EDF already ranks last — is shed at
admission with ELIMIT, so under overload the queue carries only work
someone is waiting on with a deadline.  Shedding at admission (not at
formation) keeps the refusal latency in microseconds, the same
philosophy as the deadline-aware shed.

PRIORITY LANES: batch formation is earliest-deadline-first within the
batching window, not FIFO.  When more requests are queued than one
batch holds, the FIFO head always takes one seat (bounded wait for
everyone — a deadline-less request can never be starved by a stream
of deadlined arrivals) and the nearest deadlines fill the rest
(deadline-less requests rank last, FIFO among themselves); a request
that jumps an earlier-enqueued one counts as a lane promotion on
/vars.

PREFIX-AWARE PREFILL (``prefix_cache=``, a
:class:`~brpc_tpu.kvcache.KVCacheStore`): token prompts whose prefix
the paged KV cache already holds are trimmed to their uncached SUFFIX
at batch formation — the batch computes (and pads) only what the
cache can't serve, so a 90%-shared workload rides smaller length
buckets and the skip ratio shows up per batcher on /vars.  The
matched pages are PINNED (``acquire_prefix``/``release``) for the
batch's lifetime, so eviction under pool pressure can never free the
prefix KV the trim relies on, and a ``batch_fn(padded, offsets)``
that accepts a second argument receives each row's start position
(rows are suffixes — a position-dependent scorer needs the offset).

Instrumented per batcher on /vars (and the /serving console page):
batch-size IntRecorder, queue-delay LatencyRecorder, pad-waste ratio,
shed counter, lane promotions, prefix-skip ratio.
"""
from __future__ import annotations

import re
import threading
import time
from typing import Callable, Optional, Sequence

import numpy as np

from brpc_tpu import errors, fault, native_path, rpcz
from brpc_tpu.butil import hostcpu
from brpc_tpu.butil.lockprof import InstrumentedLock
from brpc_tpu.bvar import Adder, IntRecorder, LatencyRecorder, PassiveStatus

# default sequence-length buckets: small fixed ladder so any raw length
# maps to one of a handful of compiled shapes
DEFAULT_LENGTH_BUCKETS = (16, 64, 256, 1024, 4096)


def required_positional_args(fn) -> int:
    """How many REQUIRED positional parameters `fn` takes (-1 when its
    signature is unreadable).  Used to decide whether a user function
    gets the optional extra array (batcher offsets / engine page
    table): a parameter WITH a default is not counted — passing the
    extra into e.g. ``temperature=1.0`` would silently corrupt compute
    — and ``*args`` counts for nothing (pass the explicit flag for
    those)."""
    import inspect
    try:
        params = inspect.signature(fn).parameters.values()
    except (TypeError, ValueError):
        return -1
    return sum(1 for p in params
               if p.kind in (p.POSITIONAL_ONLY,
                             p.POSITIONAL_OR_KEYWORD)
               and p.default is p.empty)


def _bucket_up(n: int, buckets: Sequence[int]) -> Optional[int]:
    for b in buckets:
        if n <= b:
            return b
    return None


def _default_batch_buckets(max_batch_size: int) -> tuple:
    out = []
    b = 1
    while b < max_batch_size:
        out.append(b)
        b *= 2
    out.append(max_batch_size)
    return tuple(out)


class _Pending:
    """One queued request: the padded-batch row it will occupy plus an
    exactly-once completion (error or result, never neither, never
    both)."""

    __slots__ = ("item", "length", "skip", "enqueue_t", "deadline_s",
                 "span", "_fire", "_fired", "_mu")

    def __init__(self, item: np.ndarray, length: int,
                 deadline_s: Optional[float],
                 fire: Callable[[int, str, object], None]):
        self.item = item
        self.length = length
        self.skip = 0              # prefix tokens served from KV cache
        self.enqueue_t = time.monotonic()
        self.deadline_s = deadline_s
        # per-request batch span (ISSUE 5): opened at enqueue under the
        # caller's trace (the RPC ingress span), so queue delay is the
        # span's head and shed/promotion/trim decisions annotate it;
        # NULL_SPAN when rpcz is off
        self.span = rpcz.NULL_SPAN
        self._fire = fire
        self._fired = False
        self._mu = threading.Lock()

    def complete(self, code: int, text: str, result) -> None:
        with self._mu:
            if self._fired:
                return
            self._fired = True
        span = self.span
        if span is not rpcz.NULL_SPAN:
            # exactly-once completion also finalizes the span exactly
            # once (the _fired guard above is the submission guard)
            if code:
                span.error_code = code
                span.annotate(f"completed with error {code}: {text}")
            rpcz.submit(span)
        try:
            self._fire(code, text, result)
        except Exception:
            # a raising completion callback must never kill the batch
            # drainer (it would wedge every other queued request); the
            # callback owner's bug is logged, the loop lives on
            import logging
            logging.getLogger(__name__).exception(
                "batcher completion callback raised")


class _Future:
    """Local (non-RPC) completion for submit_wait()."""

    def __init__(self):
        self._ev = threading.Event()
        self.code = 0
        self.text = ""
        self.result = None

    def fire(self, code: int, text: str, result) -> None:
        self.code, self.text, self.result = code, text, result
        self._ev.set()

    def wait(self, timeout_s: float):
        if not self._ev.wait(timeout_s):
            raise errors.RpcError(errors.ERPCTIMEDOUT,
                                  "batcher result not ready")
        if self.code:
            raise errors.RpcError(self.code, self.text)
        return self.result


class DynamicBatcher:
    """Per-method dynamic batcher.

    ``batch_fn(padded)`` receives a ``[batch_bucket, length_bucket]``
    array (row i = request i's item, zero-padded) and returns either a
    per-row vector (``[batch]``) or a padded matrix (``[batch,
    length_bucket]``, trimmed back to each request's raw length on
    scatter).  Supply a ``jax.jit``-wrapped function: because inputs are
    always bucket shapes, it compiles once per bucket and never again.

    ``batch_done(items, lengths)``, where the owner hands one in, runs
    once a batch on the thread that ran ``batch_fn``, after it returned
    and before any row is scattered: ``items`` are the live members'
    own arrays (no padding) and ``lengths`` their lengths, in row
    order.  What a batch owes as a whole (its counters, one read of the
    state its rows show) is paid there once, not once a member; each
    member's result is then ``(row, shared)``, ``shared`` being what
    ``batch_done`` returned.  If it raises, every live member completes
    once with EINTERNAL, as after a failed ``batch_fn``.
    """

    def __init__(self, batch_fn: Callable, *,
                 max_batch_size: int = 16,
                 max_delay_us: int = 2000,
                 batch_buckets: Optional[Sequence[int]] = None,
                 length_buckets: Sequence[int] = DEFAULT_LENGTH_BUCKETS,
                 limiter=None,
                 prefix_cache=None,
                 pass_offsets: Optional[bool] = None,
                 name: str = "default",
                 dtype=np.float32,
                 padded_output: Optional[bool] = None,
                 eager: bool = False,
                 stage_prefix: Optional[str] = None,
                 batch_done: Optional[Callable] = None):
        if max_batch_size < 1:
            raise ValueError("max_batch_size must be >= 1")
        # a ModelRunner instance cannot exist unless its module is
        # already imported — the sys.modules probe keeps plain-numpy
        # batchers from paying the models-package (jax) import
        import sys as _sys
        _runner_mod = _sys.modules.get("brpc_tpu.models.runner")
        if _runner_mod is not None and \
                isinstance(batch_fn, _runner_mod.ModelRunner):
            # Serving.Score over a REAL model (ISSUE 10): a ModelRunner
            # drops in as the batch_fn — its dense scoring path (the
            # flash-kernel forward) computes per-position next-token
            # ids, trimmed back per row by the padded-output scatter.
            # With a prefix cache the 2-arg offsets variant rides the
            # formation-time trim exactly like any other offset-aware
            # batch_fn.
            batch_fn = (batch_fn.score_with_offsets
                        if prefix_cache is not None else batch_fn.score)
        self.batch_fn = batch_fn
        self.batch_done = batch_done
        # the owner's prefix for this batcher's stages (rpcz.stage):
        # ``<prefix>.batcher.run`` around one batch on the thread that
        # runs it, ``<prefix>.batcher.wait`` a marker per member that
        # carries how long it was queued (``queue_delay_us``; no thread
        # is parked for a queued request, so there is no interval to
        # stamp).  None: no stages
        self._stage_run = stage_prefix and f"{stage_prefix}.batcher.run"
        self._stage_wait = stage_prefix and f"{stage_prefix}.batcher.wait"
        self.max_batch_size = int(max_batch_size)
        self.max_delay_us = int(max_delay_us)
        self.batch_buckets = tuple(sorted(
            batch_buckets or _default_batch_buckets(max_batch_size)))
        if self.batch_buckets[-1] < self.max_batch_size:
            raise ValueError("largest batch bucket must cover "
                             "max_batch_size")
        self.length_buckets = tuple(sorted(length_buckets))
        self.name = name
        self.dtype = np.dtype(dtype)
        # How to scatter batch_fn's output back to callers:
        #   True  — output is [batch, length_bucket]: trim row i to the
        #           request's raw length;
        #   False — output rows are per-request values of fixed width
        #           (or scalars): hand row i back whole;
        #   None  — infer per batch (trim iff the trailing dim equals
        #           the length bucket).  Pass it explicitly when a
        #           fixed-width output could COINCIDE with a length
        #           bucket — the heuristic cannot tell those apart and
        #           would silently truncate.
        self.padded_output = padded_output
        if limiter is not None:
            from brpc_tpu.policy.concurrency_limiter import create_limiter
            limiter = create_limiter(limiter)
        self.limiter = limiter
        # a KVCacheStore (or anything with probe/acquire_prefix/
        # release): items are trimmed to their uncached suffix at batch
        # formation with the matched pages pinned for the batch's
        # lifetime (see module docstring)
        self.prefix_cache = prefix_cache
        # a batch_fn with TWO required positionals receives per-row
        # start offsets alongside the suffix matrix (needed for
        # position-dependent compute); pass_offsets overrides the
        # detection for *args functions or optional-parameter shapes
        if pass_offsets is not None:
            self._fn_wants_offsets = bool(pass_offsets)
        else:
            self._fn_wants_offsets = (
                prefix_cache is not None
                and required_positional_args(batch_fn) >= 2)

        safe = re.sub(r"\W", "_", name)
        # record the EXACT names exposed below so close() hides only
        # this batcher's variables — a prefix wildcard would also strip
        # a sibling component whose name merely starts with ours
        from brpc_tpu.bvar.variable import exposed_variables
        _pre_bvars = set(exposed_variables(f"serving_{safe}*"))
        self.batch_size_rec = IntRecorder(f"serving_{safe}_batch_size")
        self.queue_delay_rec = LatencyRecorder(
            f"serving_{safe}_queue_delay")
        self.shed = Adder(f"serving_{safe}_shed")
        self.brownout_shed = Adder(f"serving_{safe}_brownout_shed")
        self.n_batches = Adder(f"serving_{safe}_batches")
        self.n_completed = Adder(f"serving_{safe}_completed")
        self.n_bypassed = Adder(f"serving_{safe}_bypassed")
        self.n_errors = Adder(f"serving_{safe}_errors")
        self.lane_promotions = Adder(f"serving_{safe}_lane_promotions")
        self._pad_elems = Adder()    # padded-but-unused elements
        self._real_elems = Adder()   # useful elements
        self._skip_elems = Adder()   # prefix elements served from cache
        self._seen_elems = Adder()   # total elements offered
        PassiveStatus(self._pad_waste).expose(
            f"serving_{safe}_pad_waste_ratio")
        PassiveStatus(self._prefix_skip_ratio).expose(
            f"serving_{safe}_prefix_skip_ratio")
        self._bvar_names = [n for n in exposed_variables(f"serving_{safe}*")
                            if n not in _pre_bvars]

        # EAGER mode (ISSUE 13, the PS surface's latency shape): the
        # batching WINDOW exists to gather concurrency, and when the
        # system is idle it is pure added latency — measured ~1ms per
        # request on CPU loopback (200us condvar timeout + GIL-contended
        # wakeups).  With eager=True:
        #   * an arrival finding the queue EMPTY and no batch executing
        #     runs INLINE on the submitting thread — batch of one, zero
        #     cross-thread hops (the cut-through);
        #   * the drainer forms whatever is queued IMMEDIATELY (no
        #     window wait) — coalescing comes from accumulation while
        #     the previous batch executes, the continuous-batching
        #     discipline (vLLM's shape): under load the drainer is
        #     always busy, so arrivals pile up and batches stay large.
        # Default False: generative scoring keeps the windowed policy.
        self.eager = bool(eager)
        # one batch in flight at a time in eager mode (inline OR
        # drainer — batch_fns keep the windowed mode's serial-execution
        # contract); guarded by self._cv's lock
        self._executing = False

        # overload-ladder level (0 = healthy), written by a supervisor;
        # read once per enqueue — plain attribute, GIL-atomic
        self.brownout = 0

        # the batcher queue lock is a NAMED hot lock (ISSUE 6): every
        # enqueue/formation contends here, so its wait/hold times ride
        # the lock-contention ledger (/hotspots/locks)
        self._cv = threading.Condition(InstrumentedLock("batcher.queue"))
        self._q: list[_Pending] = []
        self._exec_ema_s = 0.0
        self._running = True
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name=f"serving-batcher-{safe}")
        self._thread.start()
        from brpc_tpu import serving as _serving
        _serving._register_batcher(self)

    # ---- the idle cut-through claim (eager mode) ----

    def try_claim_idle(self) -> bool:
        """Claim the execution slot for ONE request a caller will serve
        OUTSIDE the batcher (the PS handler bypass): succeeds only in
        eager mode, with no queue, no batch in flight, no brownout
        (degraded batchers must route everything through admission so
        the shed policy applies), and the batcher still running.  While
        claimed, concurrent arrivals queue and coalesce behind the
        bypassed request exactly as behind an inline cut-through batch.
        Pair with :meth:`release_idle`."""
        if not self.eager or self.brownout >= 1:
            return False
        with self._cv:
            if not self._running or self._q or self._executing:
                return False
            self._executing = True
        self.n_bypassed.add(1)
        return True

    def release_idle(self) -> None:
        with self._cv:
            self._executing = False
            self._cv.notify_all()

    # ---- admission ----

    def submit(self, cntl, item, transform: Optional[Callable] = None,
               ) -> None:
        """Server-handler entry: defers the RPC, enqueues the item, and
        completes the call from the batch drainer.  The request's
        deadline is read off the Controller's request meta (timeout_ms);
        ``transform(row)`` maps the scattered row to the response
        object."""
        done = cntl.defer()

        def fire(code: int, text: str, result) -> None:
            if code:
                cntl.set_failed(code, text)
                done(None)
                return
            if transform is not None:
                # a raising transform must still complete the RPC — the
                # client gets a definite EINTERNAL instead of a timeout
                try:
                    result = transform(result)
                except Exception as e:
                    cntl.set_failed(errors.EINTERNAL,
                                    f"response transform failed: "
                                    f"{type(e).__name__}: {e}")
                    done(None)
                    return
            done(result)

        meta = cntl.request_meta
        tmo_ms = meta.timeout_ms if meta is not None else 0
        deadline_s = (time.monotonic() + tmo_ms / 1e3) if tmo_ms > 0 \
            else None
        self.enqueue(item, fire, deadline_s=deadline_s)

    def submit_wait(self, item, timeout_s: float = 30.0,
                    deadline_s: Optional[float] = None):
        """Local blocking submission (tests, tools, non-RPC callers):
        returns the scattered row or raises RpcError."""
        fut = _Future()
        self.enqueue(item, fut.fire, deadline_s=deadline_s)
        return fut.wait(timeout_s)

    def enqueue(self, item, fire: Callable[[int, str, object], None],
                deadline_s: Optional[float] = None) -> None:
        """Core admission: validates the item, predicts completion, and
        either queues or sheds.  ``fire(code, text, result)`` runs
        exactly once."""
        arr = np.asarray(item, dtype=self.dtype)
        if arr.ndim == 0:
            arr = arr.reshape(1)
        p = _Pending(arr, 0, deadline_s, fire)
        # spans inherit the enqueuing thread's trace (the RPC ingress
        # span when coming through submit()); assigned before ANY
        # complete() path so every outcome — shed, reject, scatter —
        # finalizes it
        p.span = rpcz.child_span("batch", "Serving", self.name)
        if arr.ndim != 1:
            p.complete(errors.EREQUEST,
                       f"batcher items must be 1-D, got shape {arr.shape}",
                       None)
            self.n_errors.add(1)
            return
        p.length = arr.shape[0]
        if _bucket_up(p.length, self.length_buckets) is None:
            # an over-length item is still admissible when the prefix
            # cache holds enough of it that the SUFFIX fits a bucket
            # (advisory probe here; the binding, page-pinning trim
            # happens at batch formation)
            fits = False
            if self.prefix_cache is not None and p.length > 1:
                try:
                    hit = int(self.prefix_cache.probe(arr))
                except Exception:
                    hit = 0
                hit = max(0, min(hit, p.length - 1))
                fits = _bucket_up(p.length - hit,
                                  self.length_buckets) is not None
            if not fits:
                p.complete(errors.EREQUEST,
                           f"item length {p.length} exceeds largest "
                           f"bucket {self.length_buckets[-1]}", None)
                self.n_errors.add(1)
                return
        shed_code = 0
        shed_text = ""
        brownout = 0
        inline = False
        with self._cv:
            if not self._running:
                shed_code, shed_text = errors.ELOGOFF, "batcher closed"
            elif self.brownout >= 1 and p.deadline_s is None:
                # degradation ladder level >= 1: the lowest-priority
                # lane (deadline-less — EDF already ranks it last) is
                # refused at the door so the queue drains toward work
                # with a deadline someone is actually waiting out
                shed_code = errors.ELIMIT
                shed_text = (f"brownout level {self.brownout}: "
                             f"lowest-priority lane shed")
                brownout = 1
            elif self.limiter is not None and not self.limiter.on_requested(
                    len(self._q) + 1):
                # the SAME admission machinery servers use: limiter said
                # no -> ELIMIT, counted as a shed
                shed_code = errors.ELIMIT
                shed_text = "batcher queue limiter rejected the request"
            elif p.deadline_s is not None:
                # predicted completion: the full batching window (worst
                # case for a fresh queue) plus one EMA execution per
                # batch already ahead of us, plus our own.  Eager mode
                # never waits the window (cut-through / immediate
                # formation), so charging it would spuriously shed
                # tight-deadline requests an idle batcher would serve
                # well inside their budget
                batches_ahead = len(self._q) // self.max_batch_size
                window_s = 0.0 if self.eager else self.max_delay_us / 1e6
                predicted_s = (window_s +
                               (batches_ahead + 1) *
                               max(self._exec_ema_s, 0.0))
                if p.deadline_s < p.enqueue_t + predicted_s:
                    shed_code = errors.ELIMIT
                    shed_text = (
                        f"deadline-aware shed: deadline in "
                        f"{(p.deadline_s - p.enqueue_t) * 1e3:.1f}ms but "
                        f"predicted batch completion in "
                        f"{predicted_s * 1e3:.1f}ms")
            if shed_code == 0:
                if self.eager and not self._q and not self._executing:
                    # cut-through: the system is idle, so this request
                    # IS the batch — run it on the submitting thread,
                    # zero cross-thread hops (claims the execution slot
                    # under the lock; concurrent arrivals queue for the
                    # drainer and coalesce behind us)
                    self._executing = True
                    inline = True
                else:
                    self._q.append(p)
                    self._cv.notify()
        if shed_code != 0:
            if shed_code == errors.ELIMIT:
                self.shed.add(1)
                if brownout:
                    self.brownout_shed.add(1)
                if self.limiter is not None and not brownout:
                    # a brownout shed never consumed a limiter slot
                    self.limiter.on_responded(errors.ELIMIT, 0)
            self.n_errors.add(1)
            p.complete(shed_code, shed_text, None)
            return
        if inline:
            try:
                self._run_batch([p])
            except Exception:
                import logging
                logging.getLogger(__name__).exception(
                    "inline batch execution failed")
                p.complete(errors.EINTERNAL, "batch drainer error", None)
            finally:
                with self._cv:
                    self._executing = False
                    self._cv.notify_all()

    # ---- the batch loop ----

    def _loop(self) -> None:
        while True:
            with self._cv:
                while True:
                    if self.eager and self._executing:
                        # park while an inline (or our own previous)
                        # batch executes — one batch in flight, arrivals
                        # accumulate into the NEXT batch.  Checked even
                        # during shutdown: the close() flush must not
                        # run batch_fn concurrently with an in-flight
                        # inline batch (the serial-execution contract);
                        # the inline finally-block always clears the
                        # slot and notifies, so this wait is bounded
                        self._cv.wait()
                        continue
                    if self._running and not self._q:
                        self._cv.wait()
                        continue
                    break
                if not self._q:
                    if not self._running:
                        return
                    continue
                if not self.eager:
                    # batch window: first-enqueued request anchors the
                    # delay.  Eager mode skips the window entirely —
                    # whatever queued while the last batch executed IS
                    # the batch (continuous-batching accumulation).
                    deadline_t = self._q[0].enqueue_t \
                        + self.max_delay_us / 1e6
                    while self._running and \
                            len(self._q) < self.max_batch_size:
                        rem = deadline_t - time.monotonic()
                        if rem <= 0:
                            break
                        self._cv.wait(rem)
                batch = self._form_batch_locked()
                if batch and self.eager:
                    self._executing = True
            if not batch:
                continue
            try:
                self._run_batch(batch)
            except Exception:
                # belt over _run_batch's own error handling: the drainer
                # thread must survive ANY failure or the batcher wedges
                import logging
                logging.getLogger(__name__).exception(
                    "batch drainer iteration failed")
                for p in batch:
                    p.complete(errors.EINTERNAL, "batch drainer error",
                               None)
            finally:
                if self.eager:
                    with self._cv:
                        self._executing = False
                        self._cv.notify_all()

    def _form_batch_locked(self) -> list[_Pending]:
        """Pick this batch's members: earliest-deadline-first among the
        queued requests (priority lanes), FIFO among equals and the
        deadline-less.  A member selected over an earlier-enqueued
        request that stays queued counts as one lane promotion."""
        if len(self._q) <= self.max_batch_size:
            batch, self._q = self._q, []
            return batch
        # the FIFO head ALWAYS takes one seat: the queue front advances
        # every batch, so a deadline-less request has bounded wait even
        # under a sustained stream of deadlined arrivals (EDF alone
        # would starve it)
        order = sorted(
            range(1, len(self._q)),
            key=lambda i: (self._q[i].deadline_s
                           if self._q[i].deadline_s is not None
                           else float("inf"), i))
        taken = {0} | set(order[: self.max_batch_size - 1])
        take = sorted(taken)
        first_left = min(i for i in range(len(self._q))
                         if i not in taken)
        promoted = sum(1 for i in take if i > first_left)
        if promoted:
            self.lane_promotions.add(promoted)
            for i in take:
                if i > first_left and \
                        self._q[i].span is not rpcz.NULL_SPAN:
                    self._q[i].span.annotate(
                        "lane promotion: EDF selected this request "
                        "ahead of an earlier-enqueued one")
        batch = [self._q[i] for i in take]
        for i in reversed(take):
            del self._q[i]
        return batch

    def _run_batch(self, batch: list[_Pending]) -> None:
        # per-stage host-CPU accounting (ISSUE 6): everything this
        # method burns on the drainer thread EXCEPT the user batch_fn
        # call (timed separately in _execute) is batch-formation host
        # work — the de-GIL target ROADMAP item 4 needs sized
        t_cpu0 = time.thread_time()
        self._fn_cpu_s = 0.0
        try:
            with (rpcz.stage(self._stage_run, members=len(batch))
                  if self._stage_run else rpcz.NOOP_STAGE):
                self._run_batch_inner(batch)
        finally:
            hostcpu.add("batch_formation",
                        (time.thread_time() - t_cpu0 - self._fn_cpu_s)
                        * 1e6)
            hostcpu.add("model_compute", self._fn_cpu_s * 1e6)

    def _run_batch_inner(self, batch: list[_Pending]) -> None:
        now = time.monotonic()
        live: list[_Pending] = []
        for p in batch:
            if p.deadline_s is not None and p.deadline_s < now:
                # expired while queued (a burst pushed it past its
                # deadline): shed at dequeue rather than computing a row
                # nobody is waiting for
                self.shed.add(1)
                self.n_errors.add(1)
                if self.limiter is not None:
                    self.limiter.on_responded(errors.ELIMIT, 0)
                p.complete(errors.ELIMIT,
                           "deadline expired before batch formation", None)
            else:
                qd_us = int((now - p.enqueue_t) * 1e6)
                self.queue_delay_rec.add(qd_us)
                if self._stage_wait:
                    with rpcz.stage(self._stage_wait, queue_delay_us=qd_us):
                        pass
                if p.span is not rpcz.NULL_SPAN:
                    p.span.annotate(f"batch formed: queue_delay_us={qd_us}"
                                    f" members={len(batch)}")
                live.append(p)
        if not live:
            return
        pinned: list = []
        try:
            live = self._trim_prefixes(live, pinned)
            if live:
                self._execute(live)
        finally:
            # the pinned prefix pages outlive the compute, never less:
            # eviction cannot free KV a row's trim relied on mid-batch
            if pinned and self.prefix_cache is not None:
                try:
                    self.prefix_cache.release(pinned)
                except Exception:
                    import logging
                    logging.getLogger(__name__).exception(
                        "prefix page release failed")

    def _trim_prefixes(self, live: list[_Pending], pinned: list) -> list:
        """Formation-time prefix trim: pin each item's cached prefix
        pages and keep only its uncached suffix for compute.  A row
        whose suffix no longer fits any bucket (the advisory enqueue
        probe's pages were evicted since) completes with a definite
        error instead of computing garbage."""
        if self.prefix_cache is None:
            return live
        kept = []
        for p in live:
            hit, pages = 0, []
            if p.length > 1:
                try:
                    hit, pages = self.prefix_cache.acquire_prefix(p.item)
                except Exception:
                    hit, pages = 0, []
            pinned.extend(pages)
            hit = max(0, min(hit, p.length - 1))
            if hit:
                if p.span is not rpcz.NULL_SPAN:
                    p.span.annotate(
                        f"kv prefix trim: {hit}/{p.length} tokens served "
                        f"from {len(pages)} pinned cached pages")
                p.skip = hit
                p.item = p.item[hit:]
                p.length -= hit
            if _bucket_up(p.length, self.length_buckets) is None:
                self.n_errors.add(1)
                if self.limiter is not None:
                    self.limiter.on_responded(errors.EREQUEST, 0)
                p.complete(errors.EREQUEST,
                           f"suffix length {p.length} exceeds largest "
                           f"bucket (cached prefix evicted since "
                           f"admission)", None)
            else:
                kept.append(p)
        return kept

    def _form_batch(self, live: list[_Pending], bshape: int,
                    lbucket: int) -> np.ndarray:
        """Formation gather/pad — MECHANISM only (bucket choice, EDF
        lanes and shed policy are decided above, in Python, where
        policy lives): returns the (bshape, lbucket) padded batch with
        live[i] scattered into row i.

        Native path (ISSUE 9): zero-fill + every row memcpy run as ONE
        GIL-released native pass, so concurrent submitters keep running
        through formation.  Fallback: the numpy per-row scatter loop.
        The `batch_assembly` microbench rung hammers THIS method."""
        if native_path.batch_pad_available():
            padded = np.empty((bshape, lbucket), dtype=self.dtype)
            # enqueue() already coerced every item to a 1-D array of
            # self.dtype, so ascontiguousarray is a no-op for the
            # common case (suffix trims of contiguous arrays stay
            # contiguous); it protects the native memcpy from a strided
            # array a caller snuck through
            rows = [np.ascontiguousarray(p.item) for p in live]
            native_path.batch_pad(padded, rows,
                                  [p.length for p in live])
            return padded
        padded = np.zeros((bshape, lbucket), dtype=self.dtype)
        for i, p in enumerate(live):
            padded[i, : p.length] = p.item
        return padded

    def _execute(self, live: list[_Pending]) -> None:
        n = len(live)
        bshape = _bucket_up(n, self.batch_buckets)
        lbucket = _bucket_up(max(p.length for p in live),
                             self.length_buckets)
        padded = self._form_batch(live, bshape, lbucket)
        real = 0
        skipped = 0
        for p in live:
            real += p.length
            skipped += p.skip
        self._real_elems.add(real)
        self._pad_elems.add(bshape * lbucket - real)
        # skip metrics count EXECUTED rows only (like pad-waste): a
        # shed or rejected request saved no compute
        self._skip_elems.add(skipped)
        self._seen_elems.add(real + skipped)
        self.batch_size_rec.add(n)
        self.n_batches.add(1)
        t0 = time.monotonic()
        t_fn_cpu = time.thread_time()
        try:
            if fault.ENABLED and fault.hit(
                    "serving.batch", name=self.name, batch=n) is not None:
                raise RuntimeError("injected mid-batch failure")
            if self._fn_wants_offsets:
                offsets = np.zeros((bshape,), np.int32)
                for i, p in enumerate(live):
                    offsets[i] = p.skip
                out = np.asarray(self.batch_fn(padded, offsets))
            else:
                out = np.asarray(self.batch_fn(padded))
        except Exception as e:
            self._fn_cpu_s = time.thread_time() - t_fn_cpu
            self._fail_batch(live, "batch execution", e)
            return
        self._fn_cpu_s = time.thread_time() - t_fn_cpu
        dt = time.monotonic() - t0
        self._exec_ema_s = dt if self._exec_ema_s == 0.0 \
            else 0.7 * self._exec_ema_s + 0.3 * dt
        if self.batch_done is not None:
            try:
                shared = self.batch_done([p.item for p in live],
                                         [p.length for p in live])
            except Exception as e:
                self._fail_batch(live, "batch completion", e)
                return
        trim = self.padded_output if self.padded_output is not None \
            else (out.ndim >= 2 and out.shape[-1] == lbucket)
        for i, p in enumerate(live):
            row = out[i, : p.length] if trim else out[i]
            lat_us = int((time.monotonic() - p.enqueue_t) * 1e6)
            if self.limiter is not None:
                self.limiter.on_responded(0, lat_us)
            self.n_completed.add(1)
            p.complete(0, "", row if self.batch_done is None
                       else (row, shared))

    def _fail_batch(self, live: list[_Pending], what: str,
                    e: Exception) -> None:
        """A failed batch completes EVERY member exactly once with a
        definite error — never a hang, never a partial scatter."""
        self.n_errors.add(len(live))
        for p in live:
            if self.limiter is not None:
                self.limiter.on_responded(errors.EINTERNAL, 0)
            p.complete(errors.EINTERNAL,
                       f"{what} failed: {type(e).__name__}: {e}", None)

    # ---- lifecycle / introspection ----

    def close(self, timeout_s: float = 10.0) -> None:
        """Stop accepting; the drainer flushes queued batches (no window
        wait) and exits."""
        with self._cv:
            self._running = False
            self._cv.notify_all()
        self._thread.join(timeout_s)
        # anything still queued (drainer died / timeout): definite error
        with self._cv:
            leftovers, self._q = self._q, []
        for p in leftovers:
            p.complete(errors.ELOGOFF, "batcher closed", None)
        # unpin from the global bvar registry: the exposed PassiveStatus
        # objects hold bound methods, which would keep a closed batcher
        # (and everything its batch_fn captures) alive forever and
        # defeat the serving registry's weakrefs
        from brpc_tpu.bvar.variable import find_exposed
        for n in self._bvar_names:
            v = find_exposed(n)
            if v is not None:
                v.hide()

    def _pad_waste(self) -> float:
        real = self._real_elems.get_value()
        pad = self._pad_elems.get_value()
        total = real + pad
        return round(pad / total, 4) if total else 0.0

    def _prefix_skip_ratio(self) -> float:
        seen = self._seen_elems.get_value()
        return round(self._skip_elems.get_value() / seen, 4) if seen \
            else 0.0

    def stats(self) -> dict:
        with self._cv:
            queued = len(self._q)
        return {
            "max_batch_size": self.max_batch_size,
            "max_delay_us": self.max_delay_us,
            "eager": self.eager,
            "batch_buckets": list(self.batch_buckets),
            "length_buckets": list(self.length_buckets),
            "queued": queued,
            "batches": self.n_batches.get_value(),
            "completed": self.n_completed.get_value(),
            "bypassed": self.n_bypassed.get_value(),
            "errors": self.n_errors.get_value(),
            "shed": self.shed.get_value(),
            "brownout": self.brownout,
            "brownout_shed": self.brownout_shed.get_value(),
            "lane_promotions": self.lane_promotions.get_value(),
            "avg_batch_size": round(self.batch_size_rec.get_value(), 2),
            "pad_waste_ratio": self._pad_waste(),
            "prefix_skip_ratio": self._prefix_skip_ratio(),
            "queue_delay_avg_us": round(self.queue_delay_rec.latency(), 1),
            "queue_delay_p99_us": round(
                self.queue_delay_rec.latency_percentile(0.99), 1),
            "exec_ema_ms": round(self._exec_ema_s * 1e3, 3),
        }

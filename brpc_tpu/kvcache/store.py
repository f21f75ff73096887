"""KVCacheStore — the engine-facing paged KV cache.

Ties :class:`~brpc_tpu.kvcache.pages.PagePool` (refcounted pages in
leased HBM blocks) and :class:`~brpc_tpu.kvcache.radix.RadixTree`
(longest-prefix reuse) behind the lifecycle the DecodeEngine drives:

  admit(prompt)  -> KVSeq whose cached-prefix pages are SHARED (the
                    engine prefills only the suffix — a cache hit is
                    compute skipped, not recomputed);
  extend(seq, t) -> one generated token's KV appended; allocates a new
                    page at page boundaries and copies-on-write when
                    the tail page is shared with the tree or a fork;
  reserve_next(seq) -> the page of the NEXT position is put into the
                    table (allocated, or copied if shared) before the
                    token there is known: a decode step dispatched ahead
                    writes K/V at it; the following extend fills it.  A
                    reserved position is not a token: nothing that goes
                    by ``tokens`` / ``kv_filled`` (the radix tree, retire,
                    detach, export) can see it;
  fork(seq)      -> a second sequence sharing every page (speculative /
                    divergent continuations); divergence is isolated by
                    the extend-path COW;
  retire(seq)    -> full-page chunks are offered to the radix tree
                    (future admits hit them), every seq ref drops, and
                    idle blocks return to the BlockPool.

Draft leases (ISSUE 11 — speculative decoding): the engine's
propose->verify->commit loop appends DRAFT tokens it may throw away:

  speculate(seq, toks) -> append draft tokens WITHOUT materializing
                    (``kv_filled`` does not advance, nothing
                    live-commits — the radix tree can never serve an
                    unverified draft);
  rollback(seq, n)  -> truncate back to `n` tokens, releasing the
                    rejected tail's pages to the pool (never below the
                    materialized prefix);
  commit_draft(seq, n) -> accept: materialization advances over the
                    verified prefix (vector-KV callers advance it via
                    ``write_kv_batch`` instead — splicing the verified
                    rows IS the commit).

Tree-shaped drafts put side branches on ``fork``: the fork shares the
base pages, its first speculate copies-on-write the shared tail, and a
rejected branch retires — refcounts return to baseline by the same
discipline every other holder uses.

Pool pressure: when the page pool is exhausted the store evicts
LRU-by-leaf from the radix tree and retries once — eviction can only
free pages nothing else references, so exhaustion under load degrades
hit-rate, never correctness.

Crash recovery (``detach``): a supervisor tearing down a crashed
engine detaches each in-flight sequence — its full-page chunks are
committed to the radix tree ATOMICALLY with a recovery pin (extra
refs), so re-admitting the request hits the committed prefix and
re-decodes only the uncommitted tail, and pressure eviction cannot
free that prefix in the detach->re-admit window.

Locking is fine-grained: the store-wide lock covers only the
match/ref/insert/evict compositions (where a ref must be taken before
eviction could observe the page) and the seq-lifecycle bookkeeping.
The cold-admit device splice — writing a long uncached suffix to HBM —
runs OUTSIDE it: the suffix pages are exclusively owned and the
PagePool serializes raw splices itself, so a long uncached prompt no
longer stalls concurrent ``acquire_prefix``/``extend``/batch
formation behind its device writes.

Instrumented on /vars (and the /kvcache console page): hit-rate
(prefix tokens reused / prompt tokens seen), pages in use, evictions,
copy-on-write forks, admit/retire/fork counters, radix-tree size.
"""
from __future__ import annotations

import itertools
import re
import threading
from typing import Optional, Sequence

import numpy as np

from brpc_tpu import fault, rpcz
from brpc_tpu.bvar import Adder, PassiveStatus
from brpc_tpu.kvcache.pages import KVPage, PagePool
from brpc_tpu.kvcache.radix import RadixTree

_seq_ids = itertools.count(1)


class MissingShippedPrefix(ValueError):
    """An incremental migration import (``import_prefix(have > 0)``)
    found the peer's already-shipped prefix chunks evicted — the peer
    must fall back to a full send."""


class RecoveryPin:
    """Refs taken by :meth:`KVCacheStore.detach` on a crashed
    sequence's committed prefix pages.  While held, pressure eviction
    cannot free that prefix; ``release()`` (idempotent) drops the refs
    once the request has been re-admitted (admission takes its own
    refs on the pages it matches)."""

    __slots__ = ("_store", "_pages", "tokens")

    def __init__(self, store, pages, tokens: int):
        self._store = store
        self._pages = list(pages)
        self.tokens = tokens          # committed prefix length pinned

    def release(self) -> None:
        pages, self._pages = self._pages, []
        if pages:
            self._store.release(pages)

    def __len__(self) -> int:
        return len(self._pages)


class KVSeq:
    """One sequence's view of the cache: its materialized tokens and
    the page table covering them.  ``prefill_from`` is where compute
    must start — everything before it was served from shared pages."""

    __slots__ = ("seq_id", "tokens", "pages", "prefill_from", "retired",
                 "span", "committed_full", "kv_filled", "state_row",
                 "snaps")

    def __init__(self):
        self.seq_id = next(_seq_ids)
        self.tokens: list[int] = []
        self.pages: list[KVPage] = []
        self.prefill_from = 0
        self.retired = False
        # full pages already committed LIVE to the radix tree (the
        # commit_live_pages streaming-commit cursor) — counts pages,
        # monotone, so each boundary commits only the new chunk
        self.committed_full = 0
        # MATERIALIZATION cursor (ISSUE 10): how many leading positions
        # hold real KV bytes.  Harness mode writes the token-id
        # stand-in at append, so it tracks len(tokens); vector mode
        # (a real ModelRunner) materializes a position only when
        # ``write_kv`` lands its packed K/V vectors — the final
        # generated token is never stepped, so its slot never fills,
        # and every caching path caps at this cursor so the radix tree
        # can never serve a page whose tail slot was never written
        self.kv_filled = 0
        # layered stores (ISSUE 32): the row of the recurrent-state
        # array this sequence's state lives in, and the snapshots its
        # prefill took and still owns: [(pages of prefix, snapshot row)]
        self.state_row = None
        self.snaps: list = []
        # the owning generation's rpcz span (ISSUE 5): KV events on this
        # sequence — COW, page-alloc retries, pressure evictions, detach
        # — annotate it.  NULL_SPAN when tracing is off: every annotate
        # below is a guarded no-op.
        self.span = rpcz.NULL_SPAN

    @property
    def prefix_hit_tokens(self) -> int:
        return self.prefill_from

    def page_ids(self) -> list[int]:
        return [p.pid for p in self.pages]


class KVCacheStore:
    """Paged KV cache with radix prefix reuse (see module docstring)."""

    def __init__(self, pool=None, device=None, *,
                 page_bytes: int = 1024, page_tokens: int = 16,
                 max_blocks: int = 8, commit_live_pages: bool = False,
                 vector_kv: bool = False,
                 layers=None,
                 name: str = "kv"):
        self.pagepool = PagePool(pool, device, page_bytes=page_bytes,
                                 page_tokens=page_tokens,
                                 max_blocks=max_blocks, name=name)
        self.radix = RadixTree(self.pagepool, name=name)
        # per-layer-kind state beside the pages (ISSUE 32): ``layers``
        # is a LayeredSpec; the K/V of the attention layers, their
        # compressed-key index and the linear layers' recurrent state
        # then live in ``self.layers``' persistent device arrays, page
        # p at the pool's flat arena index p, and the pool's own block
        # buffers hold nothing a model reads
        self.layers = None
        if layers is not None:
            if not vector_kv:
                raise ValueError("a layered store is vector_kv=True")
            from brpc_tpu.kvcache.layered import LayeredCache
            pp = self.pagepool
            self.layers = LayeredCache(
                layers, pp.max_blocks * pp.pages_per_block, pp.page_tokens,
                pp.pool.device, name=name)
            self.radix.snapshot_free = self.layers.free_row
        self.page_tokens = self.pagepool.page_tokens
        # vector-KV mode (ISSUE 10): pages hold REAL packed K/V vectors
        # written by a ModelRunner through write_kv, so the append path
        # skips the token-id stand-in splice (lifecycle/COW/radix
        # bookkeeping unchanged — the tree is keyed on token ids either
        # way) and materialization is tracked by seq.kv_filled instead
        # of len(tokens)
        self.vector_kv = bool(vector_kv)
        # streaming commit (ISSUE 7): every page a live sequence FILLS
        # is inserted into the radix tree right away instead of at
        # retire/detach, so a StandbySync (or a reader racing a long
        # generation) can acquire_prefix the finished pages while the
        # sequence is still decoding.  Safe: only FULL pages commit, the
        # tree takes its own refs, and the partially-written tail stays
        # exclusive — the next extend never COWs against the tree.
        self.commit_live_pages = bool(commit_live_pages)
        self.name = name
        # NAMED hot lock (ISSUE 6): acquire_prefix/extend/evict/retire
        # all serialize here — its wait/hold ledger row on
        # /hotspots/locks is the fine-grained-locking scorecard
        from brpc_tpu.butil.lockprof import InstrumentedLock
        self._mu = InstrumentedLock("kvcache.store", threading.RLock())
        self._live = 0                   # admitted-but-not-retired seqs

        safe = re.sub(r"\W", "_", name)
        # record the EXACT names exposed here so close() hides only this
        # store's variables (the serving-layer discipline)
        from brpc_tpu.bvar.variable import exposed_variables
        pre = set(exposed_variables(f"kvcache_{safe}*"))
        self.hit_tokens = Adder(f"kvcache_{safe}_hit_tokens")
        self.prompt_tokens = Adder(f"kvcache_{safe}_prompt_tokens")
        self.evictions = Adder(f"kvcache_{safe}_evictions")
        self.cow = Adder(f"kvcache_{safe}_cow_forks")
        self.admitted = Adder(f"kvcache_{safe}_admitted")
        self.retired = Adder(f"kvcache_{safe}_retired")
        self.forks = Adder(f"kvcache_{safe}_forks")
        self.speculated = Adder(f"kvcache_{safe}_speculated_tokens")
        self.rolled_back = Adder(f"kvcache_{safe}_rolled_back_pages")
        self.detached = Adder(f"kvcache_{safe}_detached")
        self.imported = Adder(f"kvcache_{safe}_imported_pages")
        PassiveStatus(self.hit_rate).expose(f"kvcache_{safe}_hit_rate")
        PassiveStatus(self.pagepool.pages_in_use).expose(
            f"kvcache_{safe}_pages_in_use")
        PassiveStatus(self.radix.node_count).expose(
            f"kvcache_{safe}_radix_nodes")
        self._bvar_names = [n for n in exposed_variables(f"kvcache_{safe}*")
                            if n not in pre]
        from brpc_tpu import kvcache as _kvcache
        _kvcache._register_store(self)

    # ---- lifecycle ----

    def admit(self, prompt: Sequence[int], *,
              span=None) -> KVSeq:
        """Start a sequence for `prompt`: its longest cached prefix is
        served by SHARED pages (capped at len(prompt)-1 so at least one
        token always computes — the model needs the last position's
        output), fresh pages hold the suffix's KV.  ``span`` (an rpcz
        span) becomes the sequence's owning span: prefix hit/miss, COW,
        eviction and page-alloc-retry events annotate it."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        with self._mu:
            # match+ref is the one composition that MUST be atomic
            # against eviction: between match returning a tree-only
            # page (refs==1) and our ref, an evict could free it
            max_chunks = (len(prompt) - 1) // self.page_tokens
            shared, snap = self._match_usable(prompt, max_chunks)
            seq = KVSeq()
            if span is not None:
                seq.span = span
            for p in shared:
                self.pagepool.ref(p)
                seq.pages.append(p)
        hit = len(shared) * self.page_tokens
        if self.layers is not None:
            try:
                self._install_state(seq, snap)
            except BaseException:
                for p in seq.pages:
                    self.pagepool.unref(p)
                raise
        seq.tokens = prompt[:hit]
        seq.prefill_from = hit
        seq.kv_filled = hit     # cached pages hold materialized KV
        if seq.span is not rpcz.NULL_SPAN:
            seq.span.annotate(
                f"kv admit: prefix_hit={hit}/{len(prompt)} tokens "
                f"({len(shared)} shared pages)" if hit else
                f"kv admit: prefix miss ({len(prompt)} tokens uncached)")
        try:
            # the cold-admit device splice runs OUTSIDE the store lock
            # (ROADMAP open item): the suffix pages are exclusively
            # ours and the PagePool serializes raw splices itself, so
            # a long uncached prompt cannot stall concurrent
            # acquire_prefix/extend/batch formation behind its writes
            self._append_run(seq, prompt[hit:])
        except BaseException:
            # a failed admit must not leak the refs already taken
            for p in seq.pages:
                self.pagepool.unref(p)
            self._free_state(seq)
            raise
        # count the hit only once the admit SUCCEEDS — a failed
        # admit skipped no compute and must not inflate hit-rate
        self.hit_tokens.add(hit)
        self.prompt_tokens.add(len(prompt))
        self.admitted.add(1)
        with self._mu:
            self._live += 1
        return seq

    def extend(self, seq: KVSeq, token: int) -> None:
        """Append one generated token's KV to `seq`."""
        with self._mu:
            if seq.retired:
                raise RuntimeError(f"extend on retired seq {seq.seq_id}")
            self._append(seq, int(token))

    def reserve_next(self, seq: KVSeq) -> None:
        """Make `seq`'s page table cover the position its NEXT token
        will take, exclusively: a fresh page where that position starts
        one, the tail page copied first where it is shared (the
        :meth:`extend` path's own rule, run early).  For a decode step
        dispatched before the token it reads is known on the host: the
        step writes K/V at that position.  Nothing else changes: no
        token, no ``kv_filled``, so no caching path can see the
        position, and the ``extend`` that brings the token finds the
        page in place.  Idempotent.  ``MemoryError`` as ``extend``'s
        (pool exhausted, nothing evictable)."""
        with self._mu:
            if seq.retired:
                raise RuntimeError(
                    f"reserve_next on retired seq {seq.seq_id}")
            self._own_page_of(seq, len(seq.tokens))

    def write_kv(self, seq: KVSeq, pos: int, rows, *,
                 final: bool = True) -> None:
        """Materialize REAL K/V vectors (ISSUE 10): splice ``rows`` —
        ``[n, kv_bytes_per_token]`` uint8, one packed K/V payload per
        token — into `seq`'s pages at positions ``[pos, pos + n)``.
        Positions must already be appended (admit/extend own the page
        table; this writes payloads, it never grows the table).  A
        target page shared with the radix tree or a fork is
        copied-on-write first, exactly like the extend-path tail COW —
        a runner rewriting a committed position can never corrupt
        another holder's KV.

        ``final=True`` (the default) declares the slots COMPLETE:
        ``seq.kv_filled`` advances (the caching cap) and the streaming
        commit runs.  A multi-pass writer — the runner's per-layer
        prefill, which rewrites the same slots once per layer — MUST
        pass ``final=False`` until its last pass, or a half-written
        slot (upper layers still zero) could be committed to the radix
        tree / pinned by a detach and served to a future admit as
        valid KV."""
        failures = self.write_kv_batch([(seq, pos, rows)], final=final)
        if failures:
            raise failures[0][1]

    def write_kv_batch(self, writes, *, final: bool = True) -> list:
        """The BATCHED decode-side write primitive (ISSUE 11): splice
        many sequences' K/V rows — ``writes`` is a sequence of
        ``(seq, pos, rows)`` with :meth:`write_kv` semantics — in ONE
        pool batch (one host-to-device transfer, one splice critical
        section; :meth:`~brpc_tpu.kvcache.pages.PagePool.write_slots_batch`)
        instead of a device round-trip per slot.  Both the plain decode
        step and the speculative verify-commit ride this.

        Per-item isolation: a write whose validation or COW fails is
        SKIPPED and reported — the healthy slots' rows still land, so
        one exhausted sequence cannot starve its step-mates.  Returns
        ``[(index, exception), ...]`` for the failed items (empty when
        all landed); a pool-level batch failure fails every surviving
        item."""
        staged = []               # (write index, seq, pos, rows, runs)
        failures: list = []
        with self._mu:
            for wi, (seq, pos, rows) in enumerate(writes):
                try:
                    rows = np.ascontiguousarray(rows, dtype=np.uint8)
                    n = rows.shape[0]
                    if seq.retired:
                        raise RuntimeError(
                            f"write_kv on retired seq {seq.seq_id}")
                    if pos < 0 or pos + n > len(seq.tokens):
                        raise ValueError(
                            f"write_kv [{pos},{pos + n}) exceeds "
                            f"materialized tokens ({len(seq.tokens)})")
                    runs = []
                    idx = 0
                    while idx < n:
                        p = pos + idx
                        pi = p // self.page_tokens
                        slot = p % self.page_tokens
                        page = seq.pages[pi]
                        if page.refs > 1:
                            # copy-on-write: the target page is shared
                            # (radix tree, fork, live commit) — writing
                            # in place would corrupt the other
                            # holder's view
                            if seq.span is not rpcz.NULL_SPAN:
                                seq.span.annotate(
                                    f"kv cow: page {page.pid} shared "
                                    f"(refs={page.refs}), copied "
                                    f"before KV write")
                            fresh = self._alloc_page(span=seq.span)
                            try:
                                self._copy_page(fresh, page)
                            except BaseException:
                                self.pagepool.unref(fresh)
                                raise
                            seq.pages[pi] = fresh
                            self.pagepool.unref(page)
                            self.cow.add(1)
                            page = fresh
                        k = min(self.page_tokens - slot, n - idx)
                        runs.append((page, slot, rows[idx:idx + k]))
                        idx += k
                except Exception as e:
                    failures.append((wi, e))
                    continue
                staged.append((wi, seq, pos, rows.shape[0], runs))
            if not staged:
                return failures
            try:
                self.pagepool.write_slots_batch(
                    [r for _, _, _, _, runs in staged for r in runs])
            except Exception as e:
                failures.extend((wi, e) for wi, _, _, _, _ in staged)
                return failures
            if final:
                for _, seq, pos, n, _ in staged:
                    seq.kv_filled = max(seq.kv_filled, pos + n)
                    self._commit_live(seq)
        return failures

    def fork(self, seq: KVSeq) -> KVSeq:
        """A second sequence sharing every page of `seq` (divergent
        continuations isolate via copy-on-write on extend)."""
        if self._has_state():
            self._no_state_copy("fork")
        with self._mu:
            if seq.retired:
                raise RuntimeError(f"fork on retired seq {seq.seq_id}")
            child = KVSeq()
            child.tokens = list(seq.tokens)
            child.prefill_from = len(seq.tokens)
            child.kv_filled = min(seq.kv_filled, len(seq.tokens))
            child.state_row = seq.state_row     # the stateless scratch row
            for p in seq.pages:
                self.pagepool.ref(p)
                child.pages.append(p)
            self.forks.add(1)
            self._live += 1
            return child

    # ---- draft leases (ISSUE 11: speculative decoding) ----

    def speculate(self, seq: KVSeq, tokens: Sequence[int]) -> None:
        """Append DRAFT tokens to `seq` without materializing them:
        pages are allocated (and a shared tail copies-on-write) exactly
        like :meth:`extend`, but ``kv_filled`` holds and nothing
        live-commits — verification decides whether these positions
        ever become real.  Pair with :meth:`rollback` (reject) and
        :meth:`commit_draft` / ``write_kv_batch`` (accept)."""
        if not tokens:
            return
        self._no_state_copy("speculate")
        with self._mu:
            if seq.retired:
                raise RuntimeError(
                    f"speculate on retired seq {seq.seq_id}")
            self._append_run(seq, tokens, materialize=False)
            self.speculated.add(len(tokens))

    def rollback(self, seq: KVSeq, keep_tokens: int) -> int:
        """Reject a draft tail: truncate `seq` back to its first
        `keep_tokens` tokens and release the pages past the boundary
        to the pool (the chaos suite's zero-leaked-draft-pages
        discipline).  Never cuts below the materialized prefix — real
        KV is not un-written by a rejected speculation.  Returns the
        pages released."""
        keep = int(keep_tokens)
        with self._mu:
            if seq.retired:
                raise RuntimeError(
                    f"rollback on retired seq {seq.seq_id}")
            if keep > len(seq.tokens):
                raise ValueError(
                    f"rollback to {keep} > {len(seq.tokens)} tokens")
            if keep < seq.kv_filled:
                raise ValueError(
                    f"rollback to {keep} would cut the materialized "
                    f"prefix (kv_filled={seq.kv_filled})")
            del seq.tokens[keep:]
            need = -(-keep // self.page_tokens)
            dropped, seq.pages = seq.pages[need:], seq.pages[:need]
            for p in dropped:
                self.pagepool.unref(p)
            if dropped:
                self.rolled_back.add(len(dropped))
            return len(dropped)

    def commit_draft(self, seq: KVSeq, upto: int) -> None:
        """Accept a verified draft prefix: the materialization cursor
        advances to `upto` tokens and the streaming commit runs.  The
        harness path's commit — the token-id stand-in bytes were
        already spliced at :meth:`speculate` time.  Vector-KV callers
        commit by splicing the verified rows through
        :meth:`write_kv_batch` instead (``final=True`` advances the
        cursor); calling this without real bytes in the slots would
        declare garbage attendable."""
        upto = int(upto)
        with self._mu:
            if seq.retired:
                raise RuntimeError(
                    f"commit_draft on retired seq {seq.seq_id}")
            if upto > len(seq.tokens):
                raise ValueError(
                    f"commit_draft to {upto} > {len(seq.tokens)} tokens")
            if upto > seq.kv_filled:
                seq.kv_filled = upto
                self._commit_live(seq)

    def retire(self, seq: KVSeq, *, cache: bool = True) -> None:
        """End a sequence.  With ``cache=True`` its full-page chunks
        are offered to the radix tree (the tree takes its own refs), so
        the next prompt sharing this prefix hits.  All of the
        sequence's refs drop either way; fully-idle blocks return to
        the BlockPool."""
        with self._mu:
            if seq.retired:
                return
            seq.retired = True
            if cache:
                nfull = self._cacheable_full(seq)
                if nfull:
                    self.radix.insert(seq.tokens[:nfull * self.page_tokens],
                                      seq.pages[:nfull])
                    self._hand_over_snapshots(seq, nfull)
            for p in seq.pages:
                self.pagepool.unref(p)
            seq.pages = []
            self._free_state(seq)
            self.retired.add(1)
            self._live -= 1

    def detach(self, seq: KVSeq) -> RecoveryPin:
        """Crash-recovery re-attach API: atomically commit a LIVE
        sequence's full-page chunks to the radix tree, take a recovery
        ref on the committed pages, and retire the sequence.  The next
        ``admit`` of ``seq.tokens + ...`` prefix-hits the committed
        pages (prefill-skip on recovery — only the uncommitted tail
        re-decodes), and the returned pin guarantees pressure eviction
        cannot free that prefix before the re-admit lands.  Atomicity
        matters: done as separate retire(cache=True) + acquire_prefix
        calls, eviction could strike between them and recovery would
        silently degrade to a full replay."""
        with self._mu:
            if seq.retired:
                return RecoveryPin(self, [], 0)
            nfull = self._cacheable_full(seq)
            pinned: list = []
            if nfull:
                toks = seq.tokens[:nfull * self.page_tokens]
                self.radix.insert(toks, seq.pages[:nfull])
                self._hand_over_snapshots(seq, nfull)
                # pin the pages the TREE actually holds (an already-
                # cached chunk keeps the tree's page, not this seq's
                # copy) — those are the ones a re-admit will match
                pinned = self.radix.match(toks, max_chunks=nfull)
                for p in pinned:
                    self.pagepool.ref(p)
            seq.retired = True
            for p in seq.pages:
                self.pagepool.unref(p)
            seq.pages = []
            self._free_state(seq)
            self.detached.add(1)
            self.retired.add(1)
            self._live -= 1
            if seq.span is not rpcz.NULL_SPAN:
                seq.span.annotate(
                    f"kv detach: {nfull} full pages committed to the "
                    f"radix tree, {len(pinned)} pinned for recovery "
                    f"({len(pinned) * self.page_tokens} tokens)")
            return RecoveryPin(self, pinned,
                               len(pinned) * self.page_tokens)

    def import_prefix(self, tokens: Sequence[int], payloads,
                      *, have: int = 0, span=None) -> int:
        """Migration splice (ISSUE 7): install `payloads` — one raw
        page of KV bytes per full-page chunk of `tokens` past the
        first `have`, exported by a PEER store's
        :meth:`~brpc_tpu.kvcache.pages.PagePool.page_slice` — as
        COMMITTED radix nodes, so the next ``admit`` of a prompt
        opening with `tokens` prefix-hits state this process never
        computed.  ``have`` is the incremental-shipping offset: the
        peer believes this store already holds the first `have`
        chunks; if eviction has since dropped any of them the import
        raises ``MissingShippedPrefix`` (a DEFINITE signal — the peer
        falls back to a full send) rather than splicing a chain whose
        head is gone.

        All-or-nothing: pages are allocated and spliced first, then
        the whole chunk chain inserts into the tree under the store
        lock (the `have`-prefix check is atomic with the insert); ANY
        failure (allocation pressure with a dry tree, a bad payload,
        the ``migrate.splice`` fault site) rolls every already-spliced
        page back to the pool — a half-imported radix chain would
        serve a prefix whose tail was never written.  Chunks the tree
        already holds keep their existing pages (the arriving copy is
        dropped — refcounts stay baseline).  Returns how many pages
        the tree newly retained."""
        self._no_state_copy("import_prefix")
        tokens = [int(t) for t in tokens]
        nfull = len(tokens) // self.page_tokens
        payloads = list(payloads)
        have = int(have)
        if have < 0 or have >= nfull or nfull == 0 \
                or len(payloads) != nfull - have:
            raise ValueError(
                f"import_prefix: {len(payloads)} payload pages for "
                f"chunks {have}..{nfull} ({len(tokens)} tokens at "
                f"{self.page_tokens}/page)")
        fresh: list[KVPage] = []
        try:
            for i in range(nfull - have):
                if fault.ENABLED and fault.hit(
                        "migrate.splice", store=self.name,
                        page=have + i) is not None:
                    raise MemoryError(
                        "injected migration splice failure")
                page = self._alloc_page(span=span)
                fresh.append(page)
                self.pagepool.write_raw(page, payloads[i])
            with self._mu:
                pre: list = []
                if have:
                    # the peer skipped these chunks as already-shipped;
                    # verify atomically with the insert — between its
                    # last send and now, eviction may have dropped them
                    pre = self.radix.match(tokens, max_chunks=have)
                    if len(pre) < have:
                        raise MissingShippedPrefix(
                            f"incremental import expected {have} "
                            f"resident chunks, found {len(pre)}")
                retained = self.radix.insert(
                    tokens[:nfull * self.page_tokens],
                    list(pre) + fresh)
        except BaseException:
            # rollback: every allocated page returns to the pool; the
            # tree never saw a partial chain
            for page in fresh:
                self.pagepool.unref(page)
            raise
        # drop the allocation refs — retained pages live on the tree's
        # own refs; duplicate chunks' pages go straight back to the pool
        for page in fresh:
            self.pagepool.unref(page)
        self.imported.add(retained)
        if span is not None and span is not rpcz.NULL_SPAN:
            span.annotate(
                f"kv import: {retained}/{nfull - have} migrated pages "
                f"spliced as committed radix nodes (chunks "
                f"{have}..{nfull}, {nfull * self.page_tokens} tokens)")
        return retained

    # ---- internals ----

    def _cacheable_full(self, seq: KVSeq) -> int:
        """Full pages eligible for the radix tree: bounded by the
        MATERIALIZED prefix (ISSUE 10) — in vector-KV mode the last
        generated token's slot never holds real vectors (it is never
        stepped), so a page it lands in must not be cached and later
        served as valid KV.  Harness mode: kv_filled == len(tokens),
        identical behavior to before.  A layered store caches no page
        past the deepest prefix it holds a state snapshot for (the one
        it restored from, or one its prefill took): a hit needs the
        recurrent state at its boundary, so deeper pages could never
        be served."""
        nfull = min(len(seq.tokens), seq.kv_filled) // self.page_tokens
        if self._has_state():
            deepest = max([seq.prefill_from // self.page_tokens]
                          + [n for n, _ in seq.snaps])
            nfull = min(nfull, deepest)
        return nfull

    # ---- layered stores: recurrent state beside the pages (ISSUE 32) ----

    def _match_usable(self, tokens, max_chunks: int,
                      count_miss: bool = True) -> tuple:
        """``(pages, snapshot)``: the longest cached prefix this store
        can SERVE.  A layered store serves a prefix only up to a node
        that holds a state snapshot; matched pages past it are dropped
        (their K/V is recomputed with the state), and a match that is
        cut short so is a miss that counts (an admission's; a probe
        counts nothing).  An admitting caller holds ``_mu``."""
        if not self._has_state():
            return self.radix.match(tokens, max_chunks=max_chunks), None
        pages, snaps = self.radix.match(tokens, max_chunks=max_chunks,
                                        snapshots=True)
        depth = max((i + 1 for i, sn in enumerate(snaps)
                     if sn is not None), default=0)
        if count_miss and depth < len(pages):
            self.layers.restore_misses.add(1)
        return pages[:depth], (snaps[depth - 1] if depth else None)

    def _install_state(self, seq: KVSeq, snapshot) -> None:
        """Give an admitted sequence its state row: the hit's snapshot
        restored into it, or zeros.  The snapshot's node cannot be
        evicted meanwhile: the sequence holds a ref on its page.  A
        cache that keeps no recurrent state gives every sequence the
        scratch row: it is live, and there is nothing to restore."""
        if not self.layers.spec.has_state:
            seq.state_row = self.layers.scratch_row
            return
        seq.state_row = self._alloc_state_row()
        if snapshot is not None:
            self.layers.restore(seq.state_row, snapshot)
        else:
            self.layers.reset_row(seq.state_row)

    def _alloc_state_row(self) -> int:
        """A live sequence's row, with pressure-driven eviction as
        :meth:`_alloc_page` has it: where snapshots hold every row,
        LRU cached prefixes go (a radix node that is evicted frees its
        snapshot's row) until one is free or the tree is dry.  The
        hit's own snapshot is safe: the sequence holds a ref on its
        page."""
        while True:
            try:
                return self.layers.alloc_row()
            except MemoryError:
                with self._mu:
                    freed = self.radix.evict(self.pagepool.pages_per_block)
                self.evictions.add(freed)
                if freed == 0:
                    raise

    def _has_state(self) -> bool:
        """Whether a hit needs a recurrent state's snapshot beside its
        pages."""
        return self.layers is not None and self.layers.spec.has_state

    def _free_state(self, seq: KVSeq) -> None:
        if not self._has_state():
            seq.state_row = None
            return
        self.layers.free_row(seq.state_row)
        seq.state_row = None
        for _n, row in seq.snaps:
            self.layers.free_row(row)
        seq.snaps = []

    def _hand_over_snapshots(self, seq: KVSeq, nfull: int) -> None:
        """The snapshots a sequence's prefill took go to the radix nodes
        that end their prefixes (now inserted); what the tree does not
        take stays the sequence's, to be freed with it."""
        kept = []
        for n, row in seq.snaps:
            if n <= nfull and self.radix.attach_snapshot(
                    seq.tokens, n, row):
                continue
            kept.append((n, row))
        seq.snaps = kept

    def _no_state_copy(self, what: str) -> None:
        if self.layers is not None:
            raise NotImplementedError(
                f"{what}: a layered store does not copy or ship "
                f"recurrent state or its own device arrays' pages yet "
                f"(ROADMAP R8)")

    def _copy_page(self, dst: KVPage, src: KVPage) -> None:
        self.pagepool.copy_page(dst, src)
        if self.layers is not None:
            flat = self.pagepool.flat_ids([dst.pid, src.pid])
            self.layers.copy_page(flat[0], flat[1])

    def snapshot_boundary(self, seq: KVSeq) -> int:
        """The position (a whole number of pages) at which this
        sequence's prefill should snapshot its state: the longest
        prefix a re-admit of the same prompt could hit; 0 where that
        lies at or before what was restored."""
        if not self._has_state():
            return 0
        b = (len(seq.tokens) - 1) // self.page_tokens * self.page_tokens
        return b if b > seq.prefill_from else 0

    def take_snapshot(self, seq: KVSeq, n_tokens: int) -> bool:
        """Snapshot `seq`'s state row as the state after ``n_tokens``
        (the runner calls this when its prefill stands exactly there).
        False where no row is free: the prefix is then not cached
        (``kvcache_*_state_snapshot_no_row`` counts them)."""
        try:
            row = self.layers.snapshot(seq.state_row)
        except MemoryError:
            self.layers.snapshot_no_row.add(1)
            return False
        seq.snaps.append((n_tokens // self.page_tokens, row))
        return True

    def mark_filled(self, seq: KVSeq, upto: int) -> None:
        """A runner that writes its own cache arrays (layered stores)
        declares positions ``< upto`` materialized."""
        if upto > seq.kv_filled:
            seq.kv_filled = min(int(upto), len(seq.tokens))

    def _append(self, seq: KVSeq, token: int) -> None:
        self._append_run(seq, [token])

    def _own_page_of(self, seq: KVSeq, pos: int) -> KVPage:
        """The page `seq` may write position ``pos`` (its next) in: a
        fresh one appended where ``pos`` starts a page the table lacks
        (a reservation may have brought it already), else the tail
        page, copied first where it is shared."""
        pi = pos // self.page_tokens
        if pi == len(seq.pages):
            seq.pages.append(self._alloc_page(span=seq.span))
            return seq.pages[pi]
        tail = seq.pages[pi]
        if tail.refs > 1:
            # copy-on-write: the tail page is shared (radix tree or a
            # forked sequence) — writing in place would corrupt the
            # other holder's KV.  Copy device-to-device, swap our table
            # entry, drop our ref on the shared page.
            if seq.span is not rpcz.NULL_SPAN:
                seq.span.annotate(
                    f"kv cow: tail page {tail.pid} shared "
                    f"(refs={tail.refs}), copied before write")
            fresh = self._alloc_page(span=seq.span)
            try:
                self._copy_page(fresh, tail)
            except BaseException:
                self.pagepool.unref(fresh)
                raise
            seq.pages[pi] = fresh
            self.pagepool.unref(tail)
            self.cow.add(1)
            return fresh
        return tail

    def _append_run(self, seq: KVSeq, tokens: Sequence[int],
                    materialize: bool = True) -> None:
        """Append tokens in PAGE-SIZED runs: one device splice per page
        touched, not one per token — the difference dominates cold-admit
        latency for long uncached suffixes."""
        idx, n = 0, len(tokens)
        while idx < n:
            pos = len(seq.tokens)
            slot = pos % self.page_tokens
            page = self._own_page_of(seq, pos)
            k = min(self.page_tokens - slot, n - idx)
            run = [int(t) for t in tokens[idx:idx + k]]
            if not self.vector_kv:
                # harness mode: the token-id stand-in IS the KV payload
                # — the splice materializes the slot.  Vector mode skips
                # it entirely: the ModelRunner's write_kv fills the slot
                # with real vectors (and skipping saves one splice per
                # appended page)
                self.pagepool.write(page, slot, run)
            seq.tokens.extend(run)
            idx += k
        if not materialize:
            # draft append (speculate): the token-id stand-in bytes are
            # in place (harness mode) but the MATERIALIZATION cursor
            # holds — an unverified draft must never live-commit, cache
            # at retire, or be pinned by a detach
            return
        if not self.vector_kv:
            seq.kv_filled = len(seq.tokens)
        self._commit_live(seq)

    def _commit_live(self, seq: KVSeq) -> None:
        if not self.commit_live_pages:
            return
        # streaming commit: every newly FILLED page joins the radix
        # tree now (the tree refs it; this seq keeps its own ref),
        # so acquire_prefix/export sees a live generation's finished
        # pages without waiting for retire/detach.  Capped at the
        # materialized prefix (vector mode: a page whose tail slot
        # lacks real vectors commits one write_kv later)
        nfull = self._cacheable_full(seq)
        if nfull > seq.committed_full:
            self.radix.insert(seq.tokens[:nfull * self.page_tokens],
                              seq.pages[:nfull])
            seq.committed_full = nfull

    def _alloc_page(self, span=None) -> KVPage:
        """Page allocation with pressure-driven eviction: on
        exhaustion, evict one block's worth of LRU leaves from the
        radix tree and retry — LOOPING while eviction keeps freeing,
        because with the cold-admit path outside the store lock a
        CONCURRENT allocator may steal the pages this thread's evict
        just freed (the thief made progress; this thread evicts more).
        Exhaustion degrades hit-rate, never correctness, until the
        tree is genuinely dry.  Each evict runs under the store lock —
        every eviction path does, so a concurrent
        admit/acquire_prefix can never ref a page eviction is mid-way
        through freeing.  ``span`` (the allocating sequence's owning
        rpcz span) gets one annotation per retry — a slow extend under
        pool pressure shows WHY on the timeline."""
        while True:
            try:
                return self.pagepool.alloc_page()
            except MemoryError:
                with self._mu:
                    freed = self.radix.evict(
                        self.pagepool.pages_per_block, span=span)
                self.evictions.add(freed)
                if span is not None and span is not rpcz.NULL_SPAN:
                    span.annotate(
                        f"kv page_alloc retry: pool exhausted, evicted "
                        f"{freed} LRU cached pages")
                if freed == 0:
                    raise

    # ---- probes / maintenance ----

    def probe(self, tokens: Sequence[int]) -> int:
        """Non-mutating prefix-hit length in TOKENS for `tokens` (an
        ADVISORY answer — admission decisions only; nothing is pinned,
        so the pages may be evicted a microsecond later).  Takes no
        refs; bumps LRU so hot prefixes stay."""
        tokens = [int(t) for t in tokens]
        if not tokens:
            return 0
        max_chunks = (len(tokens) - 1) // self.page_tokens
        return len(self._match_usable(tokens, max_chunks,
                                      count_miss=False)[0]) \
            * self.page_tokens

    def acquire_prefix(self, tokens: Sequence[int], *,
                       full_pages: bool = False) -> tuple:
        """PINNED prefix lookup for compute that relies on the cached
        KV staying resident (the batcher's formation-time trim): like
        :meth:`probe`, but takes a ref on every matched page so
        eviction cannot free them mid-batch.  The default match is
        capped one token short of the prompt — admission semantics, at
        least one position always computes; ``full_pages=True`` lifts
        the cap to cover a final exactly-full page (the migration
        export wants the complete committed prefix).  Returns
        ``(hit_tokens, pages)``; the caller MUST hand `pages` back to
        :meth:`release` once its compute finishes."""
        tokens = [int(t) for t in tokens]
        if not tokens:
            return 0, []
        with self._mu:
            max_chunks = (len(tokens) if full_pages
                          else len(tokens) - 1) // self.page_tokens
            pages = self.radix.match(tokens, max_chunks=max_chunks)
            for p in pages:
                self.pagepool.ref(p)
            return len(pages) * self.page_tokens, list(pages)

    def acquire_pages(self, tokens: Sequence[int]) -> tuple:
        """Sugar for ``acquire_prefix(tokens, full_pages=True)`` — the
        migration-export spelling."""
        return self.acquire_prefix(tokens, full_pages=True)

    def release(self, pages) -> None:
        """Drop the refs taken by :meth:`acquire_prefix`."""
        with self._mu:
            for p in pages:
                self.pagepool.unref(p)

    def evict_pages(self, n: int) -> int:
        """Evict up to `n` LRU cached pages (degradation-ladder
        pressure relief — an overloaded supervisor trades hit-rate for
        headroom).  Returns pages actually freed."""
        with self._mu:
            freed = self.radix.evict(n)
        self.evictions.add(freed)
        return freed

    def clear(self) -> int:
        """Evict every cached (tree-only) page — after all sequences
        retire this returns block-pool occupancy to baseline.  Returns
        pages freed."""
        with self._mu:
            freed = self.radix.evict_all()
            self.evictions.add(freed)
            return freed

    def hit_rate(self) -> float:
        seen = self.prompt_tokens.get_value()
        return round(self.hit_tokens.get_value() / seen, 4) if seen else 0.0

    def close(self) -> None:
        """Drop the cache and unpin this store's bvars (bound-method
        PassiveStatus would otherwise keep it alive in the registry)."""
        self.clear()
        if self.layers is not None:
            self.layers.close()
        from brpc_tpu.bvar.variable import find_exposed
        for n in self._bvar_names:
            v = find_exposed(n)
            if v is not None:
                v.hide()

    def stats(self) -> dict:
        # deliberately lock-free: every value is a thread-safe bvar,
        # a sub-lock'd component, or an atomic int read — the console
        # and registry snapshots must not stall behind a long admit's
        # device writes (which hold _mu)
        live = self._live
        return {
            "page_tokens": self.page_tokens,
            "live_seqs": live,
            "hit_rate": self.hit_rate(),
            "hit_tokens": self.hit_tokens.get_value(),
            "prompt_tokens": self.prompt_tokens.get_value(),
            "admitted": self.admitted.get_value(),
            "retired": self.retired.get_value(),
            "forks": self.forks.get_value(),
            "speculated_tokens": self.speculated.get_value(),
            "rolled_back_pages": self.rolled_back.get_value(),
            "detached": self.detached.get_value(),
            "imported_pages": self.imported.get_value(),
            "cow_forks": self.cow.get_value(),
            "evictions": self.evictions.get_value(),
            "radix_nodes": self.radix.node_count(),
            "cached_tokens": self.radix.cached_tokens(),
            "pages": self.pagepool.stats(),
            **({"layers": self.layers.stats()}
               if self.layers is not None else {}),
        }

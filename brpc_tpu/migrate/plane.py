"""Cross-host KV page migration — the data plane's page-shipping core.

"RPC Considered Harmful" (PAPERS.md) argues that tensor state should
move as one-sided bulk transfers, never be recomputed; the DMA
Streaming Framework argues for a dedicated bulk-buffer path beside the
RPC control plane.  This module is both, applied to the paged KV
cache: a radix prefix's pages (plus the tree metadata that makes them
meaningful — token runs, per-chunk fingerprints, refcounts at source)
ship over the DCN bridge's zero-copy offer/pull fabric and splice
into the destination
:class:`~brpc_tpu.kvcache.KVCacheStore` as COMMITTED radix nodes, so
the destination prefix-hits state it never computed.

Wire shape: the ``_kvmig`` service's ``Offer`` method takes the same
bounded-trust envelope the ``_dcn`` service uses (json header + tensor
bytes, never pickle).  With transfer fabrics on both sides the
envelope carries control only and the page bytes move device-to-device
(one stacked ``[n_pages, page_bytes]`` array per migration); without
one they ride the envelope host-serialized — wire-compatible, flagged
in the stats.

Offer-table discipline: a migration's offer is released the moment the
``Offer`` RPC returns — the destination pulls before it can splice,
so the reply IS the pull-completion ack.  The TTL sweeper remains the
backstop for peers that die mid-pull, never the steady state; a burst
of migrations leaves ``dcn.live_offer_count() == 0``.

Failure semantics (chaos scenario 13): ``dcn.migrate_send`` fires on
the source before anything leaves the process, ``dcn.migrate_recv``
on the destination before anything is pulled, ``migrate.splice``
(kvcache/store.py) mid-splice.  Whatever fires, the source's pinned
pages are released, the destination either fully splices or fully
rolls back, and the caller falls back to recompute — migration is an
optimization, never a correctness dependency.

Observability: migrations run under rpcz spans that JOIN the
generation's trace over the envelope's trace fields; the destination's
splice span links the source's migrate span via ``migrated_from``
(mirroring the supervisor's ``recovered_from``).  Migration threads
are stage-tagged ``migrate`` for /hotspots, and
``kvcache_migrate_{pages,bytes,splice_us}`` ride /brpc_metrics.  The
``/migration`` console page renders the route matrix.
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Optional, Sequence

import numpy as np

from brpc_tpu import errors, fault, rpcz
from brpc_tpu.butil import stagetag
from brpc_tpu.butil.lockprof import InstrumentedLock
from brpc_tpu.bvar import Adder, LatencyRecorder
from brpc_tpu.ici import dcn
from brpc_tpu.kvcache.store import MissingShippedPrefix
from brpc_tpu.rpc.service import Service, method

MIGRATE_SERVICE = "_kvmig"

# process-wide migration counters (ISSUE 7 satellite: the
# kvcache_migrate_* family on /brpc_metrics)
migrate_pages = Adder("kvcache_migrate_pages")
migrate_bytes = Adder("kvcache_migrate_bytes")
migrate_splice_rec = LatencyRecorder("kvcache_migrate_splice_us")
migrations_ok = Adder("kvcache_migrations_ok")
migrations_failed = Adder("kvcache_migrations_failed")
migrate_rollbacks = Adder("kvcache_migrate_rollbacks")
migrate_zero_copy = Adder("kvcache_migrate_zero_copy")
migrate_fallback = Adder("kvcache_migrate_fallback")
migrate_offer_frames = Adder("kvcache_migrate_offer_frames")

_mig_ids = itertools.count(1)


def _envelope_frame_fields(header: dict, arrays: list) -> dict:
    """The Offer envelope as tensorframe fields (ISSUE 17 adopter):
    the page METADATA that used to bloat the json header — token runs,
    chunk fingerprints, refcounts — rides as native little-endian
    tensors, the page payload as one uint8 tensor, and only the small
    irregular remainder (trace ids, zero-copy ticket/specs) stays as a
    json bytes field.  :func:`_frame_envelope` reconstructs EXACTLY
    the ``(header, arrays)`` the legacy json-header envelope decodes
    to, so both wire formats feed one splice path."""
    import json as _json
    hdr = dict(header)
    fields = {
        "tokens": np.asarray(hdr.pop("tokens", []), np.int64),
        # murmur-like 64-bit fingerprints may exceed int64: uint64
        "fingerprints": np.asarray(hdr.pop("fingerprints", []),
                                   np.uint64),
        "refcounts": np.asarray(hdr.pop("refcounts", []), np.int64),
        "hdr": _json.dumps(hdr).encode(),
    }
    if arrays:
        fields["pages"] = np.ascontiguousarray(arrays[0], np.uint8)
    return fields


def _frame_envelope(req: dict) -> tuple[dict, list]:
    """Inverse of :func:`_envelope_frame_fields`: back to the legacy
    decode's ``(header, arrays)`` shape — bit-for-bit the same header
    values and payload bytes (the regression test pins this)."""
    import json as _json
    hdr = _json.loads(bytes(req["hdr"]).decode())
    hdr["tokens"] = [int(t) for t in np.asarray(req["tokens"])]
    hdr["fingerprints"] = [int(f) for f in
                           np.asarray(req["fingerprints"])]
    hdr["refcounts"] = [int(r) for r in np.asarray(req["refcounts"])]
    arrays = [np.asarray(req["pages"], np.uint8)] \
        if "pages" in req else []
    return hdr, arrays


def chunk_fingerprints(tokens: Sequence[int], page_tokens: int) -> list:
    """Per-full-page-chunk 64-bit fingerprints of `tokens` — the tree
    metadata that travels with migrated pages.  The destination
    recomputes them from the token runs it received and refuses a
    migration whose fingerprints disagree (a torn or reordered payload
    must roll back, not serve wrong KV)."""
    from brpc_tpu.policy.load_balancer import _hash_murmur_like
    pt = page_tokens
    out = []
    for i in range(len(tokens) // pt):
        chunk = tokens[i * pt:(i + 1) * pt]
        out.append(_hash_murmur_like(b"".join(
            (int(t) & 0xFFFFFFFFFFFFFFFF).to_bytes(8, "little")
            for t in chunk)))
    return out


class PageMigrator:
    """Source half: exports a committed radix prefix from a local
    :class:`~brpc_tpu.kvcache.KVCacheStore` and ships it to a peer's
    ``_kvmig`` service (see module docstring).  One migrator per
    store; destination channels are cached per address."""

    # per-destination incremental-shipping memory: chains cached beyond
    # this are dropped wholesale (a clear only costs re-shipping)
    MAX_CACHED_CHAINS = 8192

    def __init__(self, store, *, name: str = "migrator",
                 timeout_ms: int = 10_000):
        self.store = store
        self.name = name
        self.timeout_ms = int(timeout_ms)
        self._mu = InstrumentedLock("migrate.plane")
        self._chans: dict[str, dcn.DcnChannel] = {}
        # per-destination route matrix for the /migration console page
        self.routes: dict[str, dict] = {}
        # dest -> set of fingerprint-chain tuples already shipped there:
        # a repeat prefix (the 90%-shared steady state) ships only its
        # UN-shipped suffix pages, not the whole chain again
        self._shipped: dict[str, set] = {}
        # per-source pull-fetch matrix (ISSUE 16, /migration page)
        self.fetch_routes: dict[str, dict] = {}
        # per-destination offer wire format: "frame" (tensorframe
        # OfferT) until a peer answers ENOMETHOD, then STICKY "legacy"
        # (json-header envelope) — the PS client's negotiation contract
        self._wire_mode: dict[str, str] = {}
        self.n_negotiation_fallbacks = 0
        from brpc_tpu import migrate as _migrate
        _migrate._register_migrator(self)

    def _channel(self, dest: str) -> dcn.DcnChannel:
        with self._mu:
            ch = self._chans.get(dest)
            if ch is None:
                ch = dcn.DcnChannel(dest, timeout_ms=self.timeout_ms)
                self._chans[dest] = ch
        return ch

    def _route(self, dest: str) -> dict:
        with self._mu:
            r = self.routes.get(dest)
            if r is None:
                r = {"migrations": 0, "pages": 0, "bytes": 0,
                     "failed": 0, "zero_copy": 0}
                self.routes[dest] = r
            return r

    def migrate(self, tokens: Sequence[int], dest: str, *,
                trace_ctx: Optional[tuple] = None) -> int:
        """Ship the longest COMMITTED full-page prefix of `tokens` to
        `dest`'s store; returns the number of pages migrated (0 when
        the local radix tree holds none of the prefix).  Raises
        RpcError on transport/splice failure — the source pages are
        released either way, and the caller's recompute path is the
        fallback.  ``trace_ctx=(trace_id, parent_span_id, sampled)``
        joins the migration to an existing generation trace; by
        default the calling thread's current span is inherited."""
        with stagetag.stage("migrate"):
            return self._migrate(tokens, dest, trace_ctx)

    def _migrate(self, tokens, dest, trace_ctx) -> int:
        tokens = [int(t) for t in tokens]
        if trace_ctx is not None:
            tid, psid, smp = trace_ctx
            span = rpcz.new_span("migrate", "KvMigrate", "Offer",
                                 trace_id=tid, parent_span_id=psid,
                                 sampled=smp if tid else None)
        else:
            span = rpcz.child_span("migrate", "KvMigrate", "Offer")
        span.remote_side = dest
        route = self._route(dest)
        hit, pages = self.store.acquire_pages(tokens)
        try:
            if not pages:
                span.annotate("nothing committed to migrate")
                return 0
            return self._ship(tokens, dest, span, route, hit, pages)
        except errors.RpcError as e:
            migrations_failed.add(1)
            with self._mu:
                route["failed"] += 1
            span.error_code = e.code
            span.annotate(f"migration failed: {e.text}")
            raise
        except Exception as e:
            migrations_failed.add(1)
            with self._mu:
                route["failed"] += 1
            span.error_code = errors.EINTERNAL
            span.annotate(f"migration failed: {type(e).__name__}: {e}")
            raise errors.RpcError(
                errors.EINTERNAL,
                f"page migration to {dest} failed: "
                f"{type(e).__name__}: {e}") from e
        finally:
            # the pins outlive the send, never more: whatever happened
            # on the wire, the SOURCE's refcounts return to baseline
            self.store.release(pages)
            rpcz.submit(span)

    def _shipped_prefix(self, dest: str, fps: list) -> int:
        """Longest fingerprint-chain prefix already shipped to `dest`
        (the incremental-shipping offset)."""
        with self._mu:
            chains = self._shipped.get(dest)
            if not chains:
                return 0
            have = 0
            for k in range(1, len(fps) + 1):
                if tuple(fps[:k]) not in chains:
                    break
                have = k
            return have

    def _remember_shipped(self, dest: str, fps: list) -> None:
        with self._mu:
            chains = self._shipped.setdefault(dest, set())
            if len(chains) > self.MAX_CACHED_CHAINS:
                chains.clear()
            for k in range(1, len(fps) + 1):
                chains.add(tuple(fps[:k]))

    def _ship(self, tokens, dest, span, route, hit, pages) -> int:
        if fault.ENABLED and fault.hit(
                "dcn.migrate_send", dest=dest) is not None:
            raise errors.RpcError(
                errors.EINTERNAL,
                f"injected migration send loss to {dest}")
        pt = self.store.page_tokens
        nfull = len(pages)
        toks = tokens[:nfull * pt]
        fps = chunk_fingerprints(toks, pt)
        have = self._shipped_prefix(dest, fps)
        if have >= nfull:
            # the whole chain already shipped: nothing to send.  If
            # the destination has since evicted it, the next admit
            # there degrades to recompute — correctness never depends
            # on this cache being right, only wire bytes do.
            span.annotate(f"already shipped: all {nfull} pages "
                          f"cached at {dest}")
            return nfull
        try:
            return self._ship_chunks(toks, dest, span, route, pages,
                                     fps, have)
        except errors.RpcError as e:
            if have and "missing shipped prefix" in (e.text or ""):
                # the destination evicted chunks we skipped: forget
                # the cached chains for this dest and send the full
                # chain once
                with self._mu:
                    self._shipped.pop(dest, None)
                span.annotate(
                    f"incremental send refused (dest evicted "
                    f"{have}-chunk prefix); retrying full")
                return self._ship_chunks(toks, dest, span, route,
                                         pages, fps, 0)
            raise

    def _ship_chunks(self, toks, dest, span, route, pages, fps,
                     have: int) -> int:
        pt = self.store.page_tokens
        pb = self.store.pagepool.page_bytes
        nfull = len(pages)
        send = pages[have:]
        ch = self._channel(dest)
        try:
            topo = ch.handshake()
        except errors.RpcError:
            # peer without the _dcn service: the control RPC still
            # works, only the zero-copy path is off the table
            topo = {}
        header = {
            "mig_id": next(_mig_ids),
            "tokens": toks,
            "page_tokens": pt,
            "page_bytes": pb,
            "have": have,
            "fingerprints": fps,
            "refcounts": [p.refs for p in pages],
            "src": self.store.name,
            "src_span_id": span.span_id,
        }
        if span.trace_id:
            # cross-host trace join: the destination's splice span
            # lands in THIS trace (and links us via migrated_from)
            header["trace_id"] = span.trace_id
            header["parent_span_id"] = span.span_id
            header["trace_sampled"] = span.sampled
        ticket = None
        arrays: list = []
        if topo.get("xfer") and topo.get("nonce") != dcn._PROCESS_NONCE \
                and dcn.transfer_server() is not None:
            # ZERO-COPY: page bytes stay device-resident, registered
            # for the peer's pull; the socket carries control only
            import jax.numpy as jnp
            stacked = jnp.stack(
                [self.store.pagepool.page_slice(p) for p in send])
            ticket, specs = dcn.offer([stacked])
            header["xfer"] = dcn.transfer_address()
            header["ticket"] = ticket
            header["specs"] = specs
            migrate_zero_copy.add(1)
            with self._mu:
                route["zero_copy"] += 1
            span.annotate(f"zero-copy offer: ticket {ticket}, pages "
                          f"{have}..{nfull} ({len(send) * pb}B stay "
                          f"on device)")
        else:
            arrays = [np.stack(
                [self.store.pagepool.read_raw(p) for p in send])]
            migrate_fallback.add(1)
            span.annotate(f"host-serialized fallback: pages "
                          f"{have}..{nfull} ({len(send) * pb}B on the "
                          f"envelope)")
        try:
            hdr = self._post_offer(ch, dest, header, arrays, span)
        finally:
            if ticket is not None:
                # ack-on-pull-completion (ISSUE 7 satellite): a reply
                # means the destination pulled before splicing, so the
                # offer unpins NOW — the TTL sweeper is the backstop
                # for a peer that died mid-pull, not the release path
                dcn.release_offer(ticket)
        retained = int(hdr.get("imported", 0))
        span.annotate(f"destination spliced: {retained}/{len(send)} "
                      f"sent pages newly retained (dst span "
                      f"{hdr.get('dst_span_id', 0)})")
        self._remember_shipped(dest, fps)
        migrations_ok.add(1)
        migrate_pages.add(len(send))
        migrate_bytes.add(len(send) * pb)
        with self._mu:
            route["migrations"] += 1
            route["pages"] += len(send)
            route["bytes"] += len(send) * pb
        return nfull

    def _post_offer(self, ch, dest: str, header: dict, arrays: list,
                    span) -> dict:
        """Send one Offer envelope, preferring the tensorframe method
        (``OfferT``, ISSUE 17 adopter) and downgrading STICKY per
        destination to the legacy json-header envelope when the peer
        answers ENOMETHOD — the same per-peer negotiation contract the
        PS client runs per shard.  Returns the reply header dict."""
        with self._mu:
            mode = self._wire_mode.get(dest)
        if mode != "legacy":
            fields = _envelope_frame_fields(header, arrays)
            span.request_size = sum(
                v.nbytes if isinstance(v, np.ndarray) else len(v)
                for v in fields.values())
            try:
                resp = ch.channel.call_sync(
                    MIGRATE_SERVICE, "OfferT", fields,
                    serializer="tensorframe")
                with self._mu:
                    self._wire_mode[dest] = "frame"
                migrate_offer_frames.add(1)
                return dict(resp or {})
            except errors.RpcError as e:
                if e.code != errors.ENOMETHOD:
                    raise
                with self._mu:
                    self._wire_mode[dest] = "legacy"
                    self.n_negotiation_fallbacks += 1
                span.annotate(f"peer {dest} lacks OfferT; sticky "
                              f"json-envelope downgrade")
        body = dcn._pack_envelope(header, arrays)
        span.request_size = len(body)
        raw = ch.channel.call_sync(
            MIGRATE_SERVICE, "Offer", body,
            serializer="raw", response_serializer="raw")
        hdr, _ = dcn._unpack_envelope(bytes(raw))
        span.response_size = len(raw)
        return hdr

    def fetch(self, tokens: Sequence[int], src: str, dest: str,
              model: Optional[str] = None) -> int:
        """PULL-based prefix warm-up (ISSUE 16): ask `src`'s
        ``_kvmig`` service to push `tokens`' committed prefix to
        `dest` — normally this process's own migration address, so a
        cache-MISS replica fetches the prefix from its owner instead
        of recomputing it.  Returns pages landed (0 when the owner
        holds none of the prefix); raises RpcError on a dead or
        refusing owner — the caller's recompute path is the fallback,
        exactly the ``migrate()`` contract in the other direction.
        ``model`` tags the request on the multi-model plane (ISSUE 18):
        a model-tagged ``_kvmig`` owner REFUSES a mismatched fetch, so
        a stale holder list can never splice one model's pages into
        another's store."""
        with stagetag.stage("migrate"):
            if fault.ENABLED and fault.hit(
                    "migrate.prefix_fetch", src=src) is not None:
                with self._mu:
                    self._fetch_route(src)["failed"] += 1
                raise errors.RpcError(
                    errors.EINTERNAL,
                    f"injected prefix fetch failure from {src}")
            ch = self._channel(str(src))
            req = {"tokens": [int(t) for t in tokens],
                   "dest": str(dest)}
            if model:
                req["model"] = str(model)
            try:
                out = ch.channel.call_sync(
                    MIGRATE_SERVICE, "PushTo", req,
                    serializer="json", response_serializer="json")
            except errors.RpcError:
                with self._mu:
                    self._fetch_route(src)["failed"] += 1
                raise
            pages = int((out or {}).get("migrated_pages", 0))
            with self._mu:
                r = self._fetch_route(src)
                r["fetches"] += 1
                r["pages"] += pages
            return pages

    def _fetch_route(self, src: str) -> dict:
        # caller holds self._mu
        r = self.fetch_routes.get(src)
        if r is None:
            r = {"fetches": 0, "pages": 0, "failed": 0}
            self.fetch_routes[src] = r
        return r

    def stats(self) -> dict:
        with self._mu:
            routes = {d: dict(r) for d, r in self.routes.items()}
            fetches = {s: dict(r) for s, r in self.fetch_routes.items()}
            modes = dict(self._wire_mode)
            fallbacks = self.n_negotiation_fallbacks
        return {"store": self.store.name, "routes": routes,
                "fetch_routes": fetches, "wire_modes": modes,
                "negotiation_fallbacks": fallbacks}


class MigrateService(Service):
    """Destination half: receives ``Offer`` envelopes, pulls (or
    unpacks) the page bytes, verifies the chunk fingerprints, and
    splices the pages into the local store as committed radix nodes —
    atomically, rolling back on any failure.  ``PushTo`` lets a remote
    coordinator (the prefix-affinity balancer's rebalance hook) ask
    THIS process to push one of its prefixes to a new owner."""

    NAME = MIGRATE_SERVICE

    def __init__(self, store, *, migrator: Optional[PageMigrator] = None,
                 model: str = ""):
        self.store = store
        self.migrator = migrator or PageMigrator(
            store, name=f"{store.name}_pusher")
        # multi-model plane (ISSUE 18): the deployment this store's
        # pages belong to.  "" (pre-plane) accepts anything; a tagged
        # service refuses a PushTo carrying a DIFFERENT model — the
        # same-model fetch constraint that makes cross-model page
        # splices structurally impossible.
        self.model = str(model or "")
        self.n_model_refusals = 0
        self._mu = InstrumentedLock("migrate.service")
        # per-source route matrix (the inbound half of /migration)
        self.inbound: dict[str, dict] = {}
        from brpc_tpu import migrate as _migrate
        _migrate._register_service(self)

    def _inbound(self, src: str) -> dict:
        with self._mu:
            r = self.inbound.get(src)
            if r is None:
                r = {"migrations": 0, "pages": 0, "bytes": 0,
                     "rolled_back": 0}
                self.inbound[src] = r
            return r

    @method(request="raw", response="raw")
    def Offer(self, cntl, req):
        with stagetag.stage("migrate"):
            try:
                hdr, arrays = dcn._unpack_envelope(bytes(req))
            except Exception as e:
                cntl.set_failed(errors.EREQUEST,
                                f"bad migration envelope: {e}")
                return None
            resp = self._splice(cntl, hdr, arrays)
            return None if resp is None \
                else dcn._pack_envelope(resp, [])

    @method(request="tensorframe", response="tensorframe")
    def OfferT(self, cntl, req):
        """The same Offer on the BINARY tensor wire (ISSUE 17
        adopter): page metadata and payload arrive as tensorframe
        fields, decode to exactly the legacy envelope's (header,
        arrays), and feed the one splice path.  Old sources never call
        this; new sources downgrade sticky on ENOMETHOD."""
        with stagetag.stage("migrate"):
            try:
                hdr, arrays = _frame_envelope(req or {})
            except Exception as e:
                cntl.set_failed(errors.EREQUEST,
                                f"bad migration envelope: {e}")
                return None
            return self._splice(cntl, hdr, arrays)

    def _splice(self, cntl, hdr, arrays):
        if fault.ENABLED and fault.hit(
                "dcn.migrate_recv", store=self.store.name) is not None:
            cntl.set_failed(errors.EINTERNAL,
                            "injected migration recv loss")
            return None
        try:
            toks = [int(t) for t in hdr["tokens"]]
            pt = int(hdr["page_tokens"])
            pb = int(hdr["page_bytes"])
            have = int(hdr.get("have", 0))
            fps = [int(f) for f in hdr.get("fingerprints") or []]
        except Exception as e:
            cntl.set_failed(errors.EREQUEST,
                            f"bad migration envelope: {e}")
            return None
        if pt != self.store.page_tokens \
                or pb != self.store.pagepool.page_bytes:
            cntl.set_failed(
                errors.EREQUEST,
                f"page geometry mismatch: peer ships {pt} tokens x "
                f"{pb}B pages, this store holds "
                f"{self.store.page_tokens} x "
                f"{self.store.pagepool.page_bytes}B")
            return None
        if fps != chunk_fingerprints(toks, pt):
            cntl.set_failed(errors.EREQUEST,
                            "chunk fingerprint mismatch: migration "
                            "metadata does not describe its token runs")
            return None
        # splice span: joins the SOURCE's trace over the envelope
        # fields and links its migrate span via migrated_from — the
        # cross-process mirror of the supervisor's recovered_from
        try:
            env_tid = int(hdr.get("trace_id") or 0)
            env_psid = int(hdr.get("parent_span_id") or 0)
        except (TypeError, ValueError):
            env_tid = env_psid = 0
        if env_tid:
            span = rpcz.new_span("migrate", "KvMigrate", "Splice",
                                 trace_id=env_tid,
                                 parent_span_id=env_psid,
                                 sampled=bool(hdr.get("trace_sampled",
                                                      True)))
        else:
            span = rpcz.new_span("migrate", "KvMigrate", "Splice")
        span.migrated_from = int(hdr.get("src_span_id") or 0)
        span.annotate(f"migration from store "
                      f"{hdr.get('src', '?')}: {len(toks)} tokens "
                      f"(chunks {have}..{len(toks) // pt} on the "
                      f"wire), source refcounts {hdr.get('refcounts')}")
        route = self._inbound(str(hdr.get("src", "?")))
        try:
            if hdr.get("xfer") and hdr.get("ticket") is not None:
                stacked = dcn.pull(hdr["xfer"], int(hdr["ticket"]),
                                   hdr.get("specs") or [],
                                   self.store.pagepool.pool.device)[0]
                span.annotate(f"zero-copy pull: ticket {hdr['ticket']}")
            elif arrays:
                stacked = arrays[0]
            else:
                raise ValueError("no page payload on the envelope")
            rows = np.asarray(stacked, np.uint8).reshape(-1, pb)
            if rows.shape[0] != len(toks) // pt - have:
                raise ValueError(
                    f"{rows.shape[0]} payload pages for chunks "
                    f"{have}..{len(toks) // pt}")
            t0 = time.monotonic()
            retained = self.store.import_prefix(toks, list(rows),
                                                have=have, span=span)
            migrate_splice_rec.add(int((time.monotonic() - t0) * 1e6))
        except MissingShippedPrefix as e:
            # NOT a rollback: the peer's incremental-send assumption
            # was stale (we evicted its earlier chunks).  A definite
            # refusal makes it fall back to a full send.
            span.error_code = errors.EREQUEST
            span.annotate(f"incremental import refused: {e}")
            rpcz.submit(span)
            cntl.set_failed(errors.EREQUEST,
                            f"missing shipped prefix: {e}")
            return None
        except Exception as e:
            # all-or-nothing: import_prefix already rolled its pages
            # back; the source gets a DEFINITE error and keeps serving
            # the prefix itself (recompute fallback)
            migrate_rollbacks.add(1)
            with self._mu:
                route["rolled_back"] += 1
            span.error_code = errors.EINTERNAL
            span.annotate(f"splice rolled back: {type(e).__name__}: {e}")
            rpcz.submit(span)
            cntl.set_failed(errors.EINTERNAL,
                            f"migration splice failed: "
                            f"{type(e).__name__}: {e}")
            return None
        with self._mu:
            route["migrations"] += 1
            route["pages"] += len(toks) // pt - have
            route["bytes"] += (len(toks) // pt - have) * pb
        resp = {"imported": retained, "pages": len(toks) // pt - have,
                "dst_span_id": span.span_id}
        rpcz.submit(span)
        return resp

    @method(request="json", response="json")
    def PushTo(self, cntl, req):
        """Coordinator-initiated push: migrate `tokens`' committed
        prefix FROM this process's store TO `dest` — the RPC the
        prefix-affinity balancer's ``migrate_on_rebalance`` hook sends
        to a prefix's old owner when the ring remaps it."""
        req = req or {}
        tokens = req.get("tokens") or []
        dest = req.get("dest")
        if not tokens or not dest:
            cntl.set_failed(errors.EREQUEST,
                            'PushTo needs "tokens" and "dest"')
            return None
        want = str(req.get("model") or "")
        if want and self.model and want != self.model:
            with self._mu:
                self.n_model_refusals += 1
            cntl.set_failed(
                errors.EREQUEST,
                f"model mismatch: this store holds {self.model!r} "
                f"pages, refusing a {want!r} fetch")
            return None
        try:
            pages = self.migrator.migrate(tokens, str(dest))
        except errors.RpcError as e:
            cntl.set_failed(e.code, f"push migration failed: {e.text}")
            return None
        return {"migrated_pages": pages}

    def stats(self) -> dict:
        with self._mu:
            inbound = {s: dict(r) for s, r in self.inbound.items()}
        return {"store": self.store.name, "model": self.model,
                "model_refusals": self.n_model_refusals,
                "inbound": inbound}


def register_migration(server, store,
                       migrator: Optional[PageMigrator] = None,
                       model: str = "") -> MigrateService:
    """Expose `store` as a migration destination (and PushTo source) on
    `server`.  Call before ``server.start()``.  ``model`` tags the
    store's deployment on the multi-model plane (see MigrateService)."""
    svc = MigrateService(store, migrator=migrator, model=model)
    server.add_service(svc)
    return svc


def make_prefix_fetcher(migrator: PageMigrator, self_addr: str,
                        model: Optional[str] = None):
    """Build the ``prefix_fetcher`` hook Serving.Generate calls on a
    cache miss (ISSUE 16): try each holder the router named (skipping
    this replica itself) until one push lands, returning pages fetched.
    Any holder failure falls through to the next; exhausting them
    returns 0 and the caller recomputes — fetch is an optimization,
    never a correctness dependency.  ``model`` tags every fetch on the
    multi-model plane so a mismatched owner refuses it (ISSUE 18)."""
    self_addr = str(self_addr)

    def fetch(prompt, holders) -> int:
        for h in holders:
            h = str(h)
            if h == self_addr:
                continue
            try:
                pages = migrator.fetch(prompt, h, self_addr,
                                       model=model)
            except Exception:
                continue
            if pages:
                return pages
        return 0

    return fetch


def rebalance_pusher(timeout_ms: int = 10_000):
    """The default ``migrate_on_rebalance`` hook: when the
    prefix-affinity ring remaps a prefix from `old_ep` to `new_ep`,
    ask the OLD owner (whose store holds the warm pages) to push them
    to the new one — ``PushTo`` over the old owner's ``_kvmig``
    service.  Returns pages migrated; swallows nothing (the balancer
    wraps hook calls so one dead replica cannot wedge the remap)."""
    from brpc_tpu.rpc.channel import Channel
    chans: dict[str, Channel] = {}
    mu = InstrumentedLock("migrate.rebalance")

    def hook(tokens, old_ep, new_ep) -> int:
        src = str(old_ep)
        with mu:
            ch = chans.get(src)
            if ch is None:
                ch = Channel(src, timeout_ms=timeout_ms)
                chans[src] = ch
        out = ch.call_sync(MIGRATE_SERVICE, "PushTo",
                           {"tokens": [int(t) for t in tokens],
                            "dest": str(new_ep)},
                           serializer="json", response_serializer="json")
        return int((out or {}).get("migrated_pages", 0))

    return hook

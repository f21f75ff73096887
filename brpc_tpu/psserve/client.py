"""PSClient — the embedding service's client-side router.

``lookup(keys)`` / ``update(keys, grads)`` take GLOBAL keys in request
order.  The client splits each request's key-set by shard ownership
(:func:`~brpc_tpu.psserve.shard.owners_for` over the contiguous range
map), fans the owned subsets out sub-call-per-partition through a
:class:`~brpc_tpu.rpc.combo_channels.PartitionChannel` (retry/backup:
failed partitions re-issue, rotating replicas under ``lb=``), and
reassembles responses IN KEY ORDER — duplicates and shard-straddling
key-sets fall out of the position bookkeeping naturally.

Updates are idempotent end-to-end: every sub-call carries a distinct
53-bit ``update_id`` (per-process random salt + process-wide counter +
partition), so a retry after a lost ack re-acks the ORIGINAL apply
instead of double scatter-adding; the shard's version counters prove
it.

With a co-located mesh the same client surface runs over a
:class:`~brpc_tpu.psserve.lowered.ShardedEmbeddingTable` instead: the
split/fan-out/merge plan is lowered to one compiled collective program
(all-to-all / ppermute key exchange + local gather) and never touches a
socket.  ``Pull``/``Push`` route dense parameters to an owner shard by
stable name hash.
"""
from __future__ import annotations

import threading
from brpc_tpu.butil.lockprof import InstrumentedLock
import weakref
import zlib
from typing import Optional

import numpy as np

from brpc_tpu import errors, rpcz
from brpc_tpu.bvar import Adder, LatencyRecorder
from brpc_tpu.psserve.shard import owners_for, shard_bounds

CLIENT_LOOKUPS = Adder("psserve_client_lookups")
CLIENT_UPDATES = Adder("psserve_client_updates")
CLIENT_RETRIES = Adder("psserve_client_retries")
CLIENT_STALE_READS = Adder("psserve_client_stale_reads")
# binary-wire negotiation (ISSUE 13): a partition answering ENOMETHOD
# to LookupT/UpdateT is an old peer — it falls back to JSON, sticky
# per partition, and this counts each such downgrade
CLIENT_NEGOTIATION_FALLBACKS = Adder(
    "psserve_client_negotiation_fallbacks")
# calls short-circuited to a co-located lowered table (the ICI fast
# path) instead of the RPC fan-out
CLIENT_ICI_CALLS = Adder("psserve_client_ici_calls")
LOOKUP_LATENCY = LatencyRecorder("psserve_client_lookup")

# update_id construction: ids must stay unique across every client in
# every process sharing the shards (a collision silently drops a fresh
# update as a "duplicate"), and must survive float64 packing exactly
# (<= 2^53, the largest float64-exact integer).  Layout: (18-bit
# per-process random salt << 30 | 30-bit process-wide counter)
# * n_shards + partition + 1 — 48 bits of sequence * up to 32 shards
# tops out at exactly 2^53 (saturated salt/counter/partition), which
# the service's inclusive bound accepts; the salt makes
# cross-process collisions ~2^-18 per process pair, and the counter is
# process-wide so client construction churn can never wrap it back
# onto a live id.
import os as _os

_uid_mu = InstrumentedLock("psserve.uid")
_uid_salt = int.from_bytes(_os.urandom(3), "big") & 0x3FFFF
_uid_counter = [0]


def _next_uid_seq() -> int:
    with _uid_mu:
        _uid_counter[0] += 1
        if _uid_counter[0] >= (1 << 30):
            # re-salt rather than wrap onto ids that may still sit in
            # a shard's applied window
            globals()["_uid_salt"] = \
                int.from_bytes(_os.urandom(3), "big") & 0x3FFFF
            _uid_counter[0] = 1
        return (_uid_salt << 30) | _uid_counter[0]


class PSClient:
    """Route Lookup/Update/Pull/Push over a partitioned embedding
    service.

    ``backend`` is either a PartitionChannel (RPC fan-out; needs
    ``n_shards`` partitions registered) or a ShardedEmbeddingTable
    (collective lowering, co-located mesh).
    """

    def __init__(self, backend, *, vocab: int, dim: int,
                 n_shards: Optional[int] = None,
                 timeout_ms: int = 5000, max_retry: int = 2,
                 name: str = "psclient",
                 serializer: str = "tensorframe",
                 ici: object = "auto", table_name: str = "ps"):
        from brpc_tpu.rpc.combo_channels import PartitionChannel
        if serializer not in ("tensorframe", "json"):
            raise ValueError("serializer must be tensorframe|json, got "
                             f"{serializer!r}")
        self.vocab = int(vocab)
        self.dim = int(dim)
        self.name = name
        self.timeout_ms = int(timeout_ms)
        self.max_retry = int(max_retry)
        # preferred wire format; per-partition negotiation downgrades
        # to "json" (sticky) when a partition answers ENOMETHOD to the
        # binary methods (an old peer)
        self.serializer = serializer
        self._wire_mode: dict[int, str] = {}
        # ICI fast path: "auto" engages when a ShardedEmbeddingTable
        # matching (table_name, vocab, dim) is registered locally
        # (psserve.register_local_table / serve_local=True); "off"
        # never; a table instance pins it explicitly
        self._ici_mode = ici
        self.table_name = str(table_name)
        self._ici_ref = None
        self._ici_gen = None        # registry generation of cached miss
        self._ici_acked_version = 0
        self._pc = None
        self._lowered = None
        if isinstance(backend, PartitionChannel):
            self._pc = backend
            self.n_shards = int(n_shards or backend.partition_count)
            # only the RPC path mints update_ids; the lowered backend
            # (which may legitimately span >32 chips) never does
            if self.n_shards > 32:
                raise ValueError("update_id space covers <= 32 shards")
        else:       # duck-typed lowered table (lookup/update/stats)
            self._lowered = backend
            self.n_shards = int(getattr(backend, "p", n_shards or 1))
        self.bounds = shard_bounds(self.vocab, self.n_shards)
        self._mu = InstrumentedLock("psserve.client")
        # read-your-writes bookkeeping: highest acked version per shard
        self.acked_version = [0] * self.n_shards
        self.n_lookups = 0
        self.n_updates = 0
        self.n_retries = 0
        self.n_stale_reads = 0
        self.n_negotiation_fallbacks = 0
        self.n_ici_calls = 0
        from brpc_tpu import psserve as _ps
        _ps._register_client(self)

    # ---- id + split helpers ----

    def _uid_for(self, token: int, part: int) -> int:
        """Per-partition update_id for one LOGICAL update: pure
        function of (token, partition), so replaying a token re-sends
        the same ids and already-applied partitions dedup."""
        return token * self.n_shards + part + 1

    def _split(self, keys: np.ndarray) -> dict[int, np.ndarray]:
        """partition -> positions (indices into the request) owned."""
        owner = owners_for(keys, self.bounds)
        return {int(s): np.flatnonzero(owner == s)
                for s in np.unique(owner)}

    # ---- the ICI fast path (ISSUE 13) ----

    def _ici_table(self):
        """The co-located lowered table this client short-circuits to,
        or None.  "auto" resolves against the psserve local-table
        registry (geometry must match); hits cache by weakref, misses
        cache by registry GENERATION — the common no-local-table case
        costs one plain attribute read per call, never the registry
        lock (a hot-path client must not serialize on a process-wide
        mutex that exists for the rare co-located case)."""
        if self._pc is None:
            return None         # already a lowered backend
        mode = self._ici_mode
        if mode in (None, False, "off"):
            return None
        if not isinstance(mode, str):   # an explicit table instance
            return mode
        from brpc_tpu import psserve as _ps
        gen = _ps._local_tables_gen     # plain int read, GIL-atomic
        if self._ici_gen == gen:
            # registry unchanged since the cached resolution — hit or
            # miss, the cache is authoritative (an unregister/replace
            # bumps the generation, so a stale hit can never keep
            # short-circuiting to an orphaned table)
            return self._ici_ref() if self._ici_ref is not None else None
        t = _ps.find_local_table(self.table_name, self.vocab, self.dim)
        self._ici_gen = gen
        self._ici_ref = weakref.ref(t) if t is not None else None
        return t

    def _note_ici(self, ver: int, acked: bool) -> None:
        """Fast-path read-your-writes bookkeeping — tracked apart from
        the per-shard RPC counters (the lowered table's version is one
        counter, not n_shards of them)."""
        with self._mu:
            self.n_ici_calls += 1
            if acked:
                if ver > self._ici_acked_version:
                    self._ici_acked_version = ver
            elif ver < self._ici_acked_version:
                self.n_stale_reads += 1
                CLIENT_STALE_READS.add(1)
        CLIENT_ICI_CALLS.add(1)

    # ---- Lookup ----

    def lookup(self, keys) -> np.ndarray:
        """rows [n, dim] for GLOBAL keys, reassembled in key order."""
        return self.lookup_versioned(keys)[0]

    def lookup_versioned(self, keys) -> tuple[np.ndarray, dict[int, int]]:
        """``lookup`` with the version each partition answered with:
        (rows, {partition: version}).  A partition's rows are its table
        after exactly that many updates; the lowered backends answer as
        partition 0."""
        import time
        keys = np.asarray(keys, np.int64)
        if keys.ndim != 1:
            raise ValueError("keys must be 1-D")
        if keys.size and (keys.min() < 0 or keys.max() >= self.vocab):
            raise ValueError(f"keys outside [0, {self.vocab})")
        t0 = time.monotonic()
        if self._lowered is not None:
            rows, ver = self._lowered.lookup(keys)
            versions = {0: int(ver)}
        else:
            tbl = self._ici_table()
            if tbl is not None:
                # co-located lowered table: one compiled collective
                # program, no socket — same client API, same rows
                rows, ver = tbl.lookup(keys)
                self._note_ici(ver, acked=False)
                versions = {0: int(ver)}
            else:
                with rpcz.stage("ps.client.call", keys=int(keys.size)):
                    rows, versions = self._lookup_rpc(keys)
        with self._mu:
            self.n_lookups += 1
        CLIENT_LOOKUPS.add(1)
        LOOKUP_LATENCY.add(int((time.monotonic() - t0) * 1e6))
        return rows, versions

    def _lookup_rpc(self, keys: np.ndarray) -> tuple[np.ndarray, dict]:
        split = self._split(keys)
        resp = self._fan_out(
            split, "Lookup",
            lambda part, pos: {"keys": keys[pos].tolist()},
            lambda part, pos: {"keys": keys[pos]})
        rows = np.empty((keys.shape[0], self.dim), np.float32)
        versions = {}
        for part, pos in split.items():
            r = resp[part]
            rows[pos] = np.asarray(r["rows"], np.float32)
            versions[part] = int(r.get("version", 0))
            self._note_version(part, versions[part])
        return rows, versions

    # ---- Update ----

    def update(self, keys, grads,
               update_token: Optional[int] = None,
               optimizer=None) -> dict[int, int]:
        """Sparse scatter-add; returns {partition: acked version}.
        Exactly-once per partition even across retries (update_ids).

        With ``optimizer`` (an :class:`OptimizerSpec` or its wire
        dict, ISSUE 17) ``grads`` are RAW gradients and each shard
        runs the FUSED scatter+slot-step program against its
        co-located momentum/Adam rows — the slots never cross the
        wire.  The spec rides the JSON wire as an ``"optimizer"``
        object and the binary wire as flattened ``opt_*`` fields.

        If the fan-out fails PARTIALLY (some partitions acked, some
        exhausted their retries), the raised RpcError carries
        ``update_token`` — replay the SAME logical update with
        ``update(keys, grads, update_token=e.update_token)`` and the
        partitions that already applied will dedup instead of double
        scatter-adding (or double-stepping momentum).  A retry WITHOUT
        the token mints fresh ids and re-applies everywhere."""
        keys = np.asarray(keys, np.int64)
        grads = np.asarray(grads, np.float32)
        if keys.ndim != 1:
            raise ValueError("keys must be 1-D")
        if keys.size and (keys.min() < 0 or keys.max() >= self.vocab):
            # same validation as lookup: a clear local error, not a
            # permanent server EREQUEST retried max_retry times (or a
            # baffling ENODATA for a negative key's partition)
            raise ValueError(f"keys outside [0, {self.vocab})")
        if grads.shape != (keys.shape[0], self.dim):
            raise ValueError(f"grads shape {grads.shape} != "
                             f"({keys.shape[0]}, {self.dim})")
        spec = None
        if optimizer is not None:
            from brpc_tpu.train.optimizer import OptimizerSpec
            spec = OptimizerSpec.from_wire(optimizer)
        if self._lowered is not None:
            ver = self._lowered.update(keys, grads, optimizer=spec) \
                if spec is not None else \
                self._lowered.update(keys, grads)
            with self._mu:
                self.n_updates += 1
            CLIENT_UPDATES.add(1)
            return {0: ver}
        token = update_token if update_token is not None \
            else _next_uid_seq()
        tbl = self._ici_table()
        if tbl is not None:
            # fast path: ONE atomic apply against the lowered table,
            # idempotent by the token itself (a replayed update_token
            # hits the table's applied set and acks the original —
            # the same discipline the RPC shards run per partition)
            ver = tbl.update(keys, grads, update_id=token,
                             optimizer=spec) \
                if spec is not None else \
                tbl.update(keys, grads, update_id=token)
            self._note_ici(ver, acked=True)
            with self._mu:
                self.n_updates += 1
            CLIENT_UPDATES.add(1)
            return {0: ver}
        with rpcz.stage("ps.client.call", keys=int(keys.size)):
            out = self._update_rpc(keys, grads, spec, token)
        with self._mu:
            self.n_updates += 1
        CLIENT_UPDATES.add(1)
        return out

    def _update_rpc(self, keys, grads, spec, token) -> dict[int, int]:
        split = self._split(keys)

        def make_json(part, pos):
            req = {"keys": keys[pos].tolist(),
                   "grads": grads[pos].tolist(),
                   "update_id": self._uid_for(token, part)}
            if spec is not None:
                req["optimizer"] = spec.to_wire()
            return req

        def make_frame(part, pos):
            # tensors ride as raw int64/float32 bytes (fancy-index
            # slices, one vectorized copy each), never Python lists;
            # the optimizer spec flattens to inline scalar fields
            req = {"keys": keys[pos], "grads": grads[pos],
                   "update_id": self._uid_for(token, part)}
            if spec is not None:
                req.update(spec.to_frame_fields())
            return req

        try:
            resp = self._fan_out(split, "Update", make_json, make_frame)
        except errors.RpcError as e:
            # stamp the token so the caller can replay THIS logical
            # update idempotently (partitions that acked will dedup)
            e.update_token = token
            raise
        out = {}
        for part, r in resp.items():
            ver = int(r["version"])
            out[part] = ver
            self._note_ack(part, ver)
        return out

    # ---- dense Pull/Push ----

    def _owner_of(self, pname: str) -> int:
        return zlib.crc32(pname.encode()) % self.n_shards

    def pull(self, pname: str) -> np.ndarray:
        if self._lowered is not None:
            raise errors.RpcError(errors.ENOMETHOD,
                                  "lowered backend serves embeddings only")
        part = self._owner_of(pname)
        r = self._call({part: {"name": pname}}, "Pull")[part]
        return np.asarray(r["value"], np.float32)

    def push(self, pname: str, delta) -> int:
        if self._lowered is not None:
            raise errors.RpcError(errors.ENOMETHOD,
                                  "lowered backend serves embeddings only")
        part = self._owner_of(pname)
        req = {part: {"name": pname,
                      "delta": np.asarray(delta, np.float32).tolist(),
                      "update_id": self._uid_for(_next_uid_seq(), part)}}
        r = self._call(req, "Push")[part]
        ver = int(r["version"])
        self._note_ack(part, ver)
        return ver

    # ---- fan-out plumbing ----

    def _call(self, sub_requests: dict, method: str,
              serializer: str = "json") -> dict:
        def on_retry(idx, err):
            with self._mu:
                self.n_retries += 1
            CLIENT_RETRIES.add(1)
        return self._pc.call_partitioned(
            "PS", method, sub_requests, serializer=serializer,
            timeout_ms=self.timeout_ms, max_retry=self.max_retry,
            on_retry=on_retry)

    def _mode_for(self, part: int) -> str:
        return self._wire_mode.get(part, self.serializer)

    def _mark_json(self, part: int) -> None:
        with self._mu:
            if self._wire_mode.get(part) == "json":
                return      # already downgraded (a concurrent fan-out
                            # won the race) — count the change once
            self._wire_mode[part] = "json"
            self.n_negotiation_fallbacks += 1
        CLIENT_NEGOTIATION_FALLBACKS.add(1)

    @staticmethod
    def _group_failures(e, parts, out) -> dict:
        """One group call raised: absorb its partial responses into
        ``out`` and return {part: error} for the parts that failed (an
        error with no per-partition detail blames every unanswered
        part)."""
        out.update(getattr(e, "partial_responses", {}) or {})
        fj = getattr(e, "failed_partitions", None)
        if fj:
            return dict(fj)
        return {p: e for p in parts if p not in out}

    def _fan_out(self, split: dict, base_method: str,
                 make_json, make_frame) -> dict:
        """Issue one sub-call per partition in each partition's
        negotiated wire format: ``base_method`` + JSON for "json"
        partitions, ``base_method + "T"`` + tensorframe for binary
        ones — the two groups run CONCURRENTLY (a steady-state mixed
        fleet after a rolling upgrade must pay max of the two
        fan-outs, not their sum).  A binary partition failing
        ENOMETHOD is an OLD PEER: it downgrades to JSON (sticky) and
        its sub-call re-issues — sub-requests are idempotent
        (per-partition update_ids are a pure function of the logical
        token), so the re-issue is safe even if the first attempt
        applied.  On any partition failing for real, ONE error
        aggregates the whole fan-out (single shared code preserved,
        else ETOOMANYFAILS; failed_partitions + partial_responses
        carry the detail)."""
        modes = {part: self._mode_for(part) for part in split}
        out: dict = {}
        failures: dict = {}
        bin_parts = [p for p in split if modes[p] == "tensorframe"]
        json_parts = [p for p in split if modes[p] == "json"]

        json_out: dict = {}
        json_exc: list = [None]

        def run_json(parts):
            sub = {p: make_json(p, split[p]) for p in parts}
            try:
                json_out.update(self._call(sub, base_method,
                                           serializer="json"))
            except errors.RpcError as e:
                json_exc[0] = e
            except Exception as e:     # a non-Rpc bug must not leave
                # the group silently unanswered (the caller would then
                # KeyError outside the RpcError/update_token contract)
                json_exc[0] = errors.RpcError(
                    errors.EINTERNAL,
                    f"json fan-out failed: {type(e).__name__}: {e}")

        jt = None
        if json_parts:
            if bin_parts:
                # one short-lived thread per MIXED-fleet call: mixed
                # wire modes are the rolling-upgrade transitional state
                # (steady fleets take one group and never spawn), and
                # the thread buys max-of-the-two-fan-outs latency
                jt = threading.Thread(target=run_json,
                                      args=(json_parts,), daemon=True)
                jt.start()
            else:
                run_json(json_parts)

        fallback = []
        if bin_parts:
            sub = {p: make_frame(p, split[p]) for p in bin_parts}
            try:
                out.update(self._call(sub, base_method + "T",
                                      serializer="tensorframe"))
            except errors.RpcError as e:
                for p, err in self._group_failures(
                        e, bin_parts, out).items():
                    if isinstance(err, errors.RpcError) \
                            and err.code == errors.ENOMETHOD:
                        self._mark_json(p)
                        fallback.append(p)
                    else:
                        failures[p] = err
        if fallback:
            # one-time re-issue for freshly-downgraded old peers
            # (first contact only; steady state rides the concurrent
            # JSON group above)
            sub = {p: make_json(p, split[p]) for p in fallback}
            try:
                out.update(self._call(sub, base_method,
                                      serializer="json"))
            except errors.RpcError as e:
                failures.update(self._group_failures(e, fallback, out))
        if jt is not None:
            jt.join()
        out.update(json_out)
        if json_exc[0] is not None:
            failures.update(self._group_failures(json_exc[0],
                                                 json_parts, out))
        if failures:
            codes = {err.code for err in failures.values()
                     if isinstance(err, errors.RpcError)}
            code = codes.pop() if len(codes) == 1 \
                else errors.ETOOMANYFAILS
            first_p = next(iter(failures))
            err = errors.RpcError(
                code, f"{len(failures)}/{len(split)} partitions "
                      f"failed (first: partition {first_p}: "
                      f"{failures[first_p]})")
            err.failed_partitions = dict(failures)
            err.partial_responses = dict(out)
            raise err
        return out

    def _note_ack(self, part: int, ver: int) -> None:
        with self._mu:
            if ver > self.acked_version[part]:
                self.acked_version[part] = ver

    def _note_version(self, part: int, ver: int) -> None:
        """Read-your-writes check: a lookup must observe every update
        THIS client already got acked on that shard."""
        with self._mu:
            if ver < self.acked_version[part]:
                self.n_stale_reads += 1
                CLIENT_STALE_READS.add(1)

    def close(self) -> None:
        if self._pc is not None:
            self._pc.close()

    def stats(self) -> dict:
        with self._mu:
            return {
                "name": self.name,
                "n_shards": self.n_shards,
                "backend": "lowered" if self._lowered is not None
                           else "partition_channel",
                "serializer": self.serializer,
                "wire_modes": dict(self._wire_mode),
                "negotiation_fallbacks": self.n_negotiation_fallbacks,
                "ici_calls": self.n_ici_calls,
                "lookups": self.n_lookups,
                "updates": self.n_updates,
                "stale_reads": self.n_stale_reads,
                "acked_versions": list(self.acked_version),
            }

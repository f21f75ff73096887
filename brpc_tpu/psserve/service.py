"""PS service registration — one shard's RPC surface.

``PS.Lookup`` / ``PS.Update`` / ``PS.Pull`` / ``PS.Push`` / ``PS.Stats``
ride the normal dispatch path (auth, interceptors, limiters,
MethodStatus all apply).  With ``batch=True`` (the default) concurrent
Lookup and Update RPCs COALESCE through two DynamicBatchers — the first
non-autoregressive traffic shape the batcher has ever coalesced:

  * lookups queue as int64 key vectors, bucket-padded by KEY COUNT; one
    jitted [B, Lb] -> [B, Lb, D] gather serves the whole batch (one
    compile per bucket pair, the serving discipline);
  * updates queue as packed float64 rows (update_id + interleaved
    key/grad groups, length buckets 1 + k*(1+D)); one jitted scatter-add
    applies the whole batch, with idempotence decided per row at apply
    time under the shard lock.

``PS.LookupT`` / ``PS.UpdateT`` (ISSUE 13) are the same semantics over
the BINARY tensor wire (rpc/tensorframe.py): requests arrive as frames
whose tensors are zero-copy views over the transport body, lookups
submit the int64 key view straight to the batcher, and updates pack
byte records (no float64 round-trip) into a third uint8-record
batcher — all three batchers default to EAGER mode (idle cut-through,
no window wait; see register_psserve), and an idle-batcher request
bypasses the defer machinery entirely.  Per-serializer request/wire-
byte Adders feed /psserve and /brpc_metrics.

Fault sites ``psserve.lookup`` / ``psserve.update`` cover the fan-out's
failure modes on BOTH wires: ``stage="pre"`` fails a sub-call before
any apply, ``stage="post"`` drops the ack AFTER the apply — the
retried sub-call must then dedup (chaos scenario 16 proves the version
counter advances exactly once).
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from brpc_tpu import errors, fault, rpcz
from brpc_tpu.bvar import Adder
from brpc_tpu.rpc.service import Service, method
from brpc_tpu.psserve.shard import EmbeddingShardServer

# per-serializer wire accounting (ISSUE 13): request counts and
# REQUEST-direction wire bytes per format, served from the decode
# phase's exact cntl.request_body_size — /psserve renders them and
# /brpc_metrics scrapes them; rpc_press --embedding turns the deltas
# into wire bytes/request for the reproducible A/B
REQUESTS_JSON = Adder("psserve_requests_json")
REQUESTS_TENSORFRAME = Adder("psserve_requests_tensorframe")
WIRE_BYTES_JSON = Adder("psserve_wire_bytes_json")
WIRE_BYTES_TENSORFRAME = Adder("psserve_wire_bytes_tensorframe")


def _coerce_uid(uid):
    """ONE update_id validation for BOTH wires (a retry may cross
    formats after a negotiation fallback and the dedup set is shared,
    so accept/reject must not differ): integers (and integral floats —
    some JSON encoders emit 123.0) in (0, 2**53]; strings and
    fractional floats are refused — int("123")/int(123.9) coercion
    would record the apply under an id the caller never sent, the
    exact rounded-onto-another-id hazard the bound exists to refuse.
    Returns (ok, value, error_text)."""
    if uid is None:
        return True, None, ""
    if isinstance(uid, bool) or not isinstance(uid, (int, float)):
        return False, None, "update_id must be an integer"
    if isinstance(uid, float):
        if not uid.is_integer():
            return False, None, "update_id must be an integer"
        uid = int(uid)
    if not (0 < uid <= (1 << 53)):
        # inclusive upper bound: 2**53 itself is exactly representable
        # in float64 (it's 2**53 + 1 that isn't), and PSClient's max
        # mintable id lands exactly there (salt/counter saturated at
        # n_shards=32)
        return False, None, "update_id must be in (0, 2**53]"
    return True, uid, ""


def wire_counters() -> dict:
    """The per-serializer counters as one dict (the /psserve page's
    "wire" section)."""
    return {
        "requests_json": REQUESTS_JSON.get_value(),
        "requests_tensorframe": REQUESTS_TENSORFRAME.get_value(),
        "wire_bytes_json": WIRE_BYTES_JSON.get_value(),
        "wire_bytes_tensorframe": WIRE_BYTES_TENSORFRAME.get_value(),
    }


class PSService(Service):
    NAME = "PS"

    def __init__(self, shard: EmbeddingShardServer,
                 lookup_batcher=None, update_batcher=None,
                 update_record_batcher=None):
        self.shard = shard
        self._lookup_b = lookup_batcher
        self._update_b = update_batcher
        # the BINARY update path's batcher (uint8 records, no float64
        # packing); None falls back to direct per-request apply
        self._update_tb = update_record_batcher

    def warm(self, optimizer=None) -> None:
        """Set-up's explicit entry (``EmbeddingShardServer.warm``) at
        the batch sizes this service's lookup batcher forms: after it a
        window of lookups, and of updates that carry ``optimizer`` (or,
        with None, plain scatter-adds), compiles nothing and allocates
        no slot."""
        b = self._lookup_b
        self.shard.warm(optimizer,
                        batch_buckets=b.batch_buckets if b else ())

    @staticmethod
    def _count_wire(cntl, binary: bool) -> None:
        n = int(getattr(cntl, "request_body_size", 0) or 0)
        if binary:
            REQUESTS_TENSORFRAME.add(1)
            WIRE_BYTES_TENSORFRAME.add(n)
        else:
            REQUESTS_JSON.add(1)
            WIRE_BYTES_JSON.add(n)

    @staticmethod
    def _claim_bypass(b) -> bool:
        """Idle bypass (ISSUE 13): with an EAGER batcher that has no
        queue and no batch in flight, this request would execute alone
        anyway — serve it straight on the handler thread and skip the
        defer/enqueue/scatter bookkeeping entirely (~300us on CPU
        loopback).  The claim (``DynamicBatcher.try_claim_idle``) holds
        the batcher's execution slot, so concurrent arrivals queue and
        coalesce behind the bypassed request; brownout refuses the
        claim so degraded batchers keep their shed policy."""
        return b is not None and b.try_claim_idle()

    @staticmethod
    def _release_bypass(b) -> None:
        b.release_idle()

    # ---- the fused co-located optimizer apply (ISSUE 17) ----
    #
    # An optimizer-carrying Update takes the DIRECT path: the wave is
    # already trainer-batched (one RPC per partition per step), its
    # semantics (slot step per touched row) can't coalesce with plain
    # scatter-adds in a batcher row, and the apply is one fused jitted
    # program either way.  Same lock, same version counter, same
    # applied-id dedup set as every other update — a retry on EITHER
    # wire acks the original apply and steps nothing.

    def _apply_opt(self, cntl, keys, grads, uid, spec):
        if fault.ENABLED and fault.hit(
                "psserve.opt_apply", shard=self.shard.shard_index,
                stage="pre") is not None:
            # pre-apply: no slot stepped, no row written; a retried
            # wave applies normally
            cntl.set_failed(errors.EINTERNAL,
                            "injected psserve.opt_apply fault "
                            "(pre-apply)")
            return None
        try:
            ver, dup = self.shard.update_opt(keys, grads, spec,
                                             update_id=uid)
        except ValueError as e:
            cntl.set_failed(errors.EREQUEST, str(e))
            return None
        if fault.ENABLED and fault.hit(
                "psserve.opt_apply", shard=self.shard.shard_index,
                stage="post") is not None:
            # post-apply ack drop: momentum DID step; the retried wave
            # must dedup by update_id or the slot double-steps (chaos
            # scenario 18 proves it doesn't)
            cntl.set_failed(errors.EINTERNAL,
                            "injected psserve.opt_apply fault "
                            "(post-apply)")
            return None
        return {"version": int(ver), "duplicate": bool(dup)}

    # ---- Lookup ----

    @method(request="json", response="json")
    def Lookup(self, cntl, req):
        with rpcz.stage("ps.server.lookup"):
            return self._lookup(cntl, req)

    def _lookup(self, cntl, req):
        self._count_wire(cntl, binary=False)
        keys = (req or {}).get("keys")
        if keys is None:
            cntl.set_failed(errors.EREQUEST, 'missing "keys"')
            return None
        if fault.ENABLED and fault.hit(
                "psserve.lookup", shard=self.shard.shard_index,
                n_keys=len(keys)) is not None:
            cntl.set_failed(errors.EINTERNAL,
                            "injected psserve.lookup fault")
            return None
        try:
            local = self.shard._to_local(np.asarray(keys, np.int64))
        except ValueError as e:
            cntl.set_failed(errors.EREQUEST, str(e))
            return None
        b = self._lookup_b
        claimed = self._claim_bypass(b)
        if b is None or claimed:
            try:
                try:
                    rows, ver = self.shard.lookup(keys)  # counts + hot
                except ValueError as e:
                    # e.g. a key-set larger than the biggest bucket: a
                    # deterministic bad request, never EINTERNAL
                    cntl.set_failed(errors.EREQUEST, str(e))
                    return None
                return {"rows": rows.tolist(), "version": ver}
            finally:
                if claimed:
                    self._release_bypass(b)

        self._submit_lookup(cntl, local, as_list=True)
        return None     # deferred: the batch drainer completes the RPC

    def _submit_lookup(self, cntl, local, *, as_list: bool) -> None:
        """Queue one lookup on the batcher.  Its transform only builds
        the reply from what the batcher hands each member: the rows,
        [n_keys, D] trimmed by the padded-output scatter, and the
        version ``lookup_batch_done`` read for the whole batch (only
        lookups that were served get there: a shed or ELIMIT reject
        never runs either).  ``as_list``: the JSON wire's rows; the
        binary wire's ride out as raw float32 bytes, never a list."""
        def reply(served):
            rows, ver = served
            rows = np.asarray(rows)
            return {"rows": rows.tolist() if as_list else rows,
                    "version": ver}

        self._lookup_b.submit(cntl, local, transform=reply)

    # ---- Update ----

    @method(request="json", response="json")
    def Update(self, cntl, req):
        with rpcz.stage("ps.server.update"):
            return self._update(cntl, req)

    def _update(self, cntl, req):
        self._count_wire(cntl, binary=False)
        req = req or {}
        keys = req.get("keys")
        grads = req.get("grads")
        uid = req.get("update_id")
        if keys is None or grads is None:
            cntl.set_failed(errors.EREQUEST, 'missing "keys"/"grads"')
            return None
        # the batched apply packs ids into float64 rows and uses 0 as
        # the padding sentinel — an id outside (0, 2^53] would be
        # silently discarded (acked but never applied) or rounded onto
        # another id; ONE validation shared with the binary wire
        ok, uid, msg = _coerce_uid(uid)
        if not ok:
            cntl.set_failed(errors.EREQUEST, msg)
            return None
        spec = None
        if req.get("optimizer") is not None:
            from brpc_tpu.train.optimizer import OptimizerSpec
            try:
                spec = OptimizerSpec.from_wire(req["optimizer"])
            except ValueError as e:
                cntl.set_failed(errors.EREQUEST, str(e))
                return None
        if fault.ENABLED and fault.hit(
                "psserve.update", shard=self.shard.shard_index,
                stage="pre") is not None:
            # pre-apply failure: nothing was written; a retry applies
            # normally
            cntl.set_failed(errors.EINTERNAL,
                            "injected psserve.update fault (pre-apply)")
            return None
        try:
            local = self.shard._to_local(np.asarray(keys, np.int64))
            g = np.asarray(grads, np.float32)
            if g.shape != (local.shape[0], self.shard.dim):
                raise ValueError(f"grads shape {g.shape} != "
                                 f"({local.shape[0]}, {self.shard.dim})")
        except ValueError as e:
            cntl.set_failed(errors.EREQUEST, str(e))
            return None
        if spec is not None:
            return self._apply_opt(cntl, keys, g, uid, spec)

        def ack(ver: int, dup: bool):
            if fault.ENABLED and fault.hit(
                    "psserve.update", shard=self.shard.shard_index,
                    stage="post") is not None:
                # post-apply ack drop: the update IS in the table; the
                # client's retry must be deduped by update_id or the
                # scatter-add doubles (chaos proves it doesn't)
                raise RuntimeError(
                    "injected psserve.update fault (post-apply)")
            return {"version": int(ver), "duplicate": bool(dup)}

        b = self._update_b
        claimed = False
        if b is not None and uid is not None:
            claimed = self._claim_bypass(b)
        if b is None or uid is None or claimed:
            try:
                try:
                    ver, dup = self.shard.update(keys, grads,
                                                 update_id=uid)
                except ValueError as e:
                    # oversize key-set etc.: deterministic bad request
                    cntl.set_failed(errors.EREQUEST, str(e))
                    return None
                try:
                    return ack(ver, dup)
                except RuntimeError as e:
                    cntl.set_failed(errors.EINTERNAL, str(e))
                    return None
            finally:
                if claimed:
                    self._release_bypass(b)
        row = EmbeddingShardServer.pack_update(int(uid), local, g)
        n_keys = int(local.shape[0])

        def transform(a):
            # a raising transform completes the RPC with EINTERNAL —
            # the post-apply ack-drop path above rides that contract.
            # UPDATE_KEYS counts here (the batch fn can't recover live
            # key counts from zero-padded rows), applied rows only
            if not bool(a[1]):
                from brpc_tpu.psserve.shard import UPDATE_KEYS
                UPDATE_KEYS.add(n_keys)
            return ack(int(a[0]), bool(a[1]))

        self._update_b.submit(cntl, row, transform=transform)
        return None

    # ---- the binary tensor wire (tensorframe, ISSUE 13) ----
    #
    # Same semantics as Lookup/Update — same fault sites, same dedup
    # set, same batchers' bucket discipline — but the request arrives
    # as a tensorframe whose tensors are ZERO-COPY views over the
    # transport body, and batches form directly from those views: the
    # lookup batcher takes the int64 key view as-is, and updates pack
    # byte records (pack_update_record) instead of the float64
    # 1+k*(1+D) rows.  A client that calls LookupT/UpdateT on an old
    # server gets ENOMETHOD and falls back to JSON per channel
    # (PSClient negotiation).

    @method(request="tensorframe", response="tensorframe")
    def LookupT(self, cntl, req):
        with rpcz.stage("ps.server.lookup"):
            return self._lookup_t(cntl, req)

    def _lookup_t(self, cntl, req):
        self._count_wire(cntl, binary=True)
        keys = (req or {}).get("keys")
        if keys is None or not isinstance(keys, np.ndarray) \
                or keys.dtype != np.int64 or keys.ndim != 1:
            cntl.set_failed(errors.EREQUEST,
                            'need int64[n] tensor field "keys"')
            return None
        if fault.ENABLED and fault.hit(
                "psserve.lookup", shard=self.shard.shard_index,
                n_keys=len(keys)) is not None:
            cntl.set_failed(errors.EINTERNAL,
                            "injected psserve.lookup fault")
            return None
        try:
            local = self.shard._to_local(keys)
        except ValueError as e:
            cntl.set_failed(errors.EREQUEST, str(e))
            return None
        b = self._lookup_b
        claimed = self._claim_bypass(b)
        if b is None or claimed:
            try:
                try:
                    rows, ver = self.shard.lookup(keys)
                except ValueError as e:
                    cntl.set_failed(errors.EREQUEST, str(e))
                    return None
                return {"rows": rows, "version": ver}
            finally:
                if claimed:
                    self._release_bypass(b)

        self._submit_lookup(cntl, local, as_list=False)
        return None

    @method(request="tensorframe", response="tensorframe")
    def UpdateT(self, cntl, req):
        with rpcz.stage("ps.server.update"):
            return self._update_t(cntl, req)

    def _update_t(self, cntl, req):
        self._count_wire(cntl, binary=True)
        req = req or {}
        keys = req.get("keys")
        grads = req.get("grads")
        uid = req.get("update_id")
        if keys is None or grads is None \
                or not isinstance(keys, np.ndarray) \
                or not isinstance(grads, np.ndarray) \
                or keys.dtype != np.int64 or keys.ndim != 1 \
                or grads.dtype != np.float32:
            cntl.set_failed(errors.EREQUEST,
                            'need int64[n] "keys" + float32[n,D] '
                            '"grads" tensor fields')
            return None
        # the SAME validation as the JSON path: dedup is one applied
        # set, and a retry may cross wire formats after a negotiation
        # fallback — accept/reject must not differ between wires
        ok, uid, msg = _coerce_uid(uid)
        if not ok:
            cntl.set_failed(errors.EREQUEST, msg)
            return None
        # the binary wire's optimizer spec rides as FLATTENED inline
        # fields (opt_kind + opt_* floats — tensorframe has no nested
        # dicts); same validation → EREQUEST contract as JSON
        from brpc_tpu.train.optimizer import OptimizerSpec
        try:
            spec = OptimizerSpec.from_frame_fields(req)
        except ValueError as e:
            cntl.set_failed(errors.EREQUEST, str(e))
            return None
        if fault.ENABLED and fault.hit(
                "psserve.update", shard=self.shard.shard_index,
                stage="pre") is not None:
            cntl.set_failed(errors.EINTERNAL,
                            "injected psserve.update fault (pre-apply)")
            return None
        try:
            local = self.shard._to_local(keys)
            if grads.shape != (local.shape[0], self.shard.dim):
                raise ValueError(f"grads shape {grads.shape} != "
                                 f"({local.shape[0]}, {self.shard.dim})")
        except ValueError as e:
            cntl.set_failed(errors.EREQUEST, str(e))
            return None
        if spec is not None:
            return self._apply_opt(cntl, keys, grads, uid, spec)

        def ack(ver: int, dup: bool):
            if fault.ENABLED and fault.hit(
                    "psserve.update", shard=self.shard.shard_index,
                    stage="post") is not None:
                raise RuntimeError(
                    "injected psserve.update fault (post-apply)")
            return {"version": int(ver), "duplicate": bool(dup)}

        b = self._update_tb
        claimed = False
        if b is not None and uid is not None:
            claimed = self._claim_bypass(b)
        if b is None or uid is None or claimed:
            try:
                try:
                    ver, dup = self.shard.update(keys, grads,
                                                 update_id=uid)
                except ValueError as e:
                    cntl.set_failed(errors.EREQUEST, str(e))
                    return None
                try:
                    return ack(ver, dup)
                except RuntimeError as e:
                    cntl.set_failed(errors.EINTERNAL, str(e))
                    return None
            finally:
                if claimed:
                    self._release_bypass(b)
        rec = EmbeddingShardServer.pack_update_record(int(uid), local,
                                                     grads)
        n_keys = int(local.shape[0])

        def transform(a):
            if not bool(a[1]):
                from brpc_tpu.psserve.shard import UPDATE_KEYS
                UPDATE_KEYS.add(n_keys)
            return ack(int(a[0]), bool(a[1]))

        self._update_tb.submit(cntl, rec, transform=transform)
        return None

    # ---- dense params ----

    @method(request="json", response="json")
    def Pull(self, cntl, req):
        pname = (req or {}).get("name")
        if not pname:
            cntl.set_failed(errors.EREQUEST, 'missing "name"')
            return None
        try:
            v = self.shard.pull(pname)
        except KeyError:
            cntl.set_failed(errors.ENODATA, f"no dense param {pname!r}")
            return None
        return {"name": pname, "value": v.tolist(),
                "shape": list(v.shape)}

    @method(request="json", response="json")
    def Push(self, cntl, req):
        req = req or {}
        pname = req.get("name")
        delta = req.get("delta")
        if not pname or delta is None:
            cntl.set_failed(errors.EREQUEST, 'missing "name"/"delta"')
            return None
        try:
            ver, dup = self.shard.push(pname, delta,
                                       update_id=req.get("update_id"))
        except ValueError as e:
            cntl.set_failed(errors.EREQUEST, str(e))
            return None
        return {"version": int(ver), "duplicate": bool(dup)}

    @method(request="json", response="json")
    def Stats(self, cntl, req):
        return self.shard.stats()


def lookup_batch_done(shard: EmbeddingShardServer):
    """The lookup batcher's per-batch completion for ``shard``: what a
    batch of served lookups owes the shard's books (counters, hot keys)
    paid in one pass over the members' live keys, and the one version
    every reply of the batch carries.  It runs on the thread that ran
    ``shard.lookup_batch_fn``, right after it, so that version is the
    one the batch's gather ran at (read under the shard lock beside the
    gather): the state these rows show, and it covers any update acked
    before a member was issued."""
    def done(items, lengths):
        with rpcz.stage("ps.server.lookup", keys=int(sum(lengths))):
            ver = shard.gathered_version()
            shard.note_lookups(
                len(items),
                items[0] if len(items) == 1 else np.concatenate(items),
                batched=True)
            return ver
    return done


def register_psserve(server, shard: EmbeddingShardServer, *,
                     batch: bool = True, max_batch_size: int = 16,
                     max_delay_us: int = 1000, eager: bool = True,
                     name: Optional[str] = None):
    """Expose one shard on an rpc Server; returns the PSService (its
    batchers close with ``unregister_psserve``).

    The PS batchers default to EAGER mode (ISSUE 13): an idle arrival
    cuts through inline (no window, no cross-thread hop) and batches
    form from whatever accumulated while the previous batch executed —
    small-request embedding traffic is latency-sensitive, and the
    batching window was measured costing ~1ms per request of pure idle
    latency on CPU loopback.  ``eager=False`` restores the windowed
    ``max_delay_us`` policy."""
    from brpc_tpu import psserve as _ps
    lookup_b = update_b = update_tb = None
    safe = name or f"{shard.name}_{shard.shard_index}"
    if batch:
        from brpc_tpu.serving.batcher import DynamicBatcher
        lookup_b = DynamicBatcher(
            shard.lookup_batch_fn,
            max_batch_size=max_batch_size, max_delay_us=max_delay_us,
            length_buckets=shard.key_buckets,
            dtype=np.int64, padded_output=True, eager=eager,
            name=f"ps_lookup_{safe}", stage_prefix="ps",
            batch_done=lookup_batch_done(shard))
        update_b = DynamicBatcher(
            shard.update_batch_fn,
            max_batch_size=max_batch_size, max_delay_us=max_delay_us,
            length_buckets=shard.update_length_buckets(),
            dtype=np.float64, padded_output=False, eager=eager,
            name=f"ps_update_{safe}", stage_prefix="ps")
        # the binary wire's update batcher: uint8 records, byte-length
        # buckets — coalesces UpdateT exactly like Update, against the
        # same shard lock and applied set
        update_tb = DynamicBatcher(
            shard.update_batch_fn_binary,
            max_batch_size=max_batch_size, max_delay_us=max_delay_us,
            length_buckets=shard.update_record_buckets(),
            dtype=np.uint8, padded_output=False, eager=eager,
            name=f"ps_updatet_{safe}", stage_prefix="ps")
    svc = PSService(shard, lookup_batcher=lookup_b,
                    update_batcher=update_b,
                    update_record_batcher=update_tb)
    server.add_service(svc)
    _ps._register_shard(shard, svc)
    return svc


def unregister_psserve(svc: PSService) -> None:
    """Close the service's batchers (flushes queued batches)."""
    for b in (svc._lookup_b, svc._update_b, svc._update_tb):
        if b is not None:
            b.close()

"""EmbeddingShardServer — one partition of a sharded embedding table.

The parameter-server ownership map is contiguous row ranges
(:func:`shard_bounds`): shard i of n owns global rows ``[lo, hi)``.  A
shard answers

  * ``Lookup(keys) -> rows`` — gather of OWNED rows (the client routed
    the keys; duplicates are legal and each occurrence is served),
  * ``Update(keys, grads)`` — sparse scatter-add into the owned rows,
    idempotent by ``update_id`` so a retried sub-call (lost ack, chaos
    fault mid-fanout) can never double-apply,
  * ``Pull/Push(name)`` — dense whole-parameter read / delta-add for
    the rest of the model (owner chosen by name hash, client-side).

Every applied update advances the shard's VERSION counter, and every
lookup response carries the counter: an Update acked at version v is
visible to any Lookup issued afterwards (the batchers swap the table
reference before completing the RPC), which is the read-your-writes
contract the chaos suite leans on to prove exactly-once apply.

The gather/scatter hot paths are jitted once per key-count bucket
(requests pad up to ``key_buckets``), which is also the shape contract
the DynamicBatcher coalesces under (service.py).
"""
from __future__ import annotations

import contextlib
import threading
from collections import OrderedDict
from typing import Optional, Sequence

import numpy as np

from brpc_tpu import rpcz
from brpc_tpu.bvar import Adder
from brpc_tpu.butil.lockprof import InstrumentedLock

DEFAULT_KEY_BUCKETS = (8, 32, 128, 512)

# process-wide counters (per-shard numbers live on the instance and the
# /psserve page; these feed /brpc_metrics as psserve_*)
LOOKUPS = Adder("psserve_lookups")
LOOKUP_KEYS = Adder("psserve_lookup_keys")
# batches of lookups whose books were paid in one pass (service.py's
# per-batch completion); psserve_lookups beside it counts the lookups
LOOKUP_BATCH_COMPLETIONS = Adder("psserve_lookup_batch_completions")
UPDATES = Adder("psserve_updates")
UPDATE_KEYS = Adder("psserve_update_keys")
DUP_UPDATES = Adder("psserve_dup_updates")
OPT_UPDATES = Adder("psserve_opt_updates")
PULLS = Adder("psserve_pulls")
PUSHES = Adder("psserve_pushes")


def shard_bounds(vocab: int, n_shards: int) -> list[tuple[int, int]]:
    """Contiguous ownership ranges: shard i owns rows [lo, hi).  The
    remainder spreads over the FIRST shards so every shard's size
    differs by at most one row."""
    if n_shards < 1 or vocab < n_shards:
        raise ValueError(f"need 1 <= n_shards <= vocab, got "
                         f"{n_shards}/{vocab}")
    base, rem = divmod(vocab, n_shards)
    bounds = []
    lo = 0
    for i in range(n_shards):
        hi = lo + base + (1 if i < rem else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


def owners_for(keys: np.ndarray, bounds: Sequence[tuple[int, int]]
               ) -> np.ndarray:
    """Owning shard index per key (vectorized over the range table)."""
    los = np.asarray([b[0] for b in bounds])
    return (np.searchsorted(los, np.asarray(keys), side="right") - 1
            ).astype(np.int64)


def init_embedding_table(vocab: int, dim: int, seed: int = 0) -> np.ndarray:
    """The deterministic full table every shard slices its rows from —
    also the test oracle's starting point."""
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((vocab, dim)) * 0.02).astype(np.float32)


def _dup_keys(keys: np.ndarray) -> int:
    """How many of ``keys`` repeat an earlier one (a stage's stat;
    computed only while something listens)."""
    return int(keys.size - np.unique(keys).size)


def _bucket_up(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"{n} keys exceed largest bucket {buckets[-1]}")


class EmbeddingShardServer:
    """One partition's state + the jitted gather/scatter hot paths."""

    def __init__(self, shard_index: int, n_shards: int, vocab: int,
                 dim: int, *, seed: int = 0,
                 table: Optional[np.ndarray] = None,
                 dense_params: Optional[dict] = None,
                 mesh=None, device=None,
                 key_buckets: Sequence[int] = DEFAULT_KEY_BUCKETS,
                 applied_cap: int = 65536,
                 name: str = "ps"):
        import jax
        import jax.numpy as jnp
        from brpc_tpu.ici.mesh import ensure_compile_cache
        ensure_compile_cache()
        if mesh is not None and device is not None:
            raise ValueError("pass mesh= or device=, not both")
        self._jax, self._jnp = jax, jnp
        self.shard_index = int(shard_index)
        self.n_shards = int(n_shards)
        self.vocab = int(vocab)
        self.dim = int(dim)
        self.name = name
        self.key_buckets = tuple(sorted(key_buckets))
        self.bounds = shard_bounds(vocab, n_shards)
        self.lo, self.hi = self.bounds[self.shard_index]
        full = table if table is not None else \
            init_embedding_table(vocab, dim, seed)
        rows = np.asarray(full[self.lo:self.hi], dtype=np.float32)
        if mesh is not None:
            # row-shard THIS partition's rows over the tp ICI mesh (the
            # PR 10 NamedSharding machinery): a co-located pod splits
            # each partition again across its chips
            from jax.sharding import NamedSharding, PartitionSpec as P
            tp = mesh.shape.get("tp", 1)
            if rows.shape[0] % tp == 0:
                self._rows = jax.device_put(
                    rows, NamedSharding(mesh, P("tp", None)))
            else:   # uneven rows: keep replicated rather than refuse
                self._rows = jax.device_put(
                    rows, NamedSharding(mesh, P()))
        else:
            # the rows (and the slots, which follow them) live on the
            # one device this shard was given — N shards of one
            # process are N chips only if each names its own
            self._rows = jax.device_put(rows, device or jax.devices()[0])
        self.mesh = mesh
        # dense parameters (the non-embedding rest of the model); the
        # CLIENT routes each name to its owner shard by hash
        self._dense: dict[str, np.ndarray] = {
            k: np.asarray(v, np.float32)
            for k, v in (dense_params or {}).items()}
        self._mu = InstrumentedLock("psserve.shard_apply",
                                    threading.RLock())
        self.version = 0
        self._applied: OrderedDict[int, int] = OrderedDict()  # uid -> ver
        self._applied_cap = int(applied_cap)
        # co-located optimizer slots (ISSUE 17): per-row momentum /
        # Adam m/v/step tables, lazily allocated on the first
        # optimizer-carrying update, living WITH the rows (same
        # sharding) so they never cross the wire
        self._slots: dict = {}
        # per-shard counters (process-wide Adders above aggregate)
        self.n_lookups = 0
        self.n_lookup_batches = 0       # per-batch completions ...
        self.n_batched_lookups = 0      # ... and the lookups they closed
        self.n_updates = 0
        self.n_opt_updates = 0
        self.n_dup_updates = 0
        self.n_pulls = 0
        self.n_pushes = 0
        # the lookups' books: reads per owned row (8 B a row, exact, on
        # the host) and the n_lookup* counters above, under a lock of
        # their own so that paying them never waits for an apply
        self._hot = np.zeros((self.n_rows,), np.int64)
        self._books_mu = InstrumentedLock("psserve.shard_books")

        # one jit each; bucket padding bounds the compile count.  The
        # functions are named so that the programs are (``jit_ps_gather``
        # / ``jit_ps_scatter`` in a device trace)
        def ps_gather(t, k):
            return t[k]

        def ps_scatter(t, k, g):
            return t.at[k].add(g)

        self._gather = jax.jit(ps_gather)
        self._scatter = jax.jit(ps_scatter)
        # the version the calling thread's last ``lookup_batch_fn``
        # gathered at (the batcher runs the response transforms on the
        # thread that ran the batch, right after it)
        self._gathered = threading.local()
        # CPU fast path (ISSUE 13): with no device mesh, a bucketed
        # gather is a plain numpy fancy-index over a zero-copy view of
        # the jax array — bit-identical to the jitted gather, without
        # ~200us of dispatch per call.  On a real mesh the jit path
        # stays (the gather must run where the rows live).
        #
        # Lock discipline (ISSUE 17): the fused optimizer apply DONATES
        # rows and slots, overwriting the old buffers in place, so the
        # swap-on-update immutability the zero-copy view used to rely
        # on no longer holds.  Every raw read of ``self._rows`` /
        # ``self._slots`` must COMPLETE under ``self._mu`` (the gather
        # result is a fresh array, so nothing aliasing the table
        # escapes the lock); snapshots hand out copies.
        self._cpu_fast = mesh is None and jax.default_backend() == "cpu"

    # ---- ownership helpers ----

    @property
    def n_rows(self) -> int:
        return self.hi - self.lo

    def owns(self, keys: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys)
        return (keys >= self.lo) & (keys < self.hi)

    def _to_local(self, keys) -> np.ndarray:
        keys = np.asarray(keys, dtype=np.int64)
        if keys.size and (keys.min() < self.lo or keys.max() >= self.hi):
            raise ValueError(
                f"shard {self.shard_index} owns [{self.lo},{self.hi}), "
                f"got keys outside the range")
        return keys - self.lo

    @contextlib.contextmanager
    def _locked(self):
        """``with self._mu``, the wait for it as its own stage."""
        with rpcz.stage("ps.shard.lock_wait"):
            self._mu.acquire()
        try:
            yield
        finally:
            self._mu.release()

    def _gather_rows(self, k: np.ndarray, **stats) -> np.ndarray:
        """The gather of (bucket-padded) local keys ``k`` as host rows,
        under ``self._mu``: the dispatch and the device-to-host pull are
        a stage each."""
        if self._cpu_fast:
            with rpcz.stage("ps.shard.gather", **stats):
                return np.asarray(self._rows)[k]
        with rpcz.stage("ps.shard.gather", **stats):
            out = self._gather(self._rows, k)
        with rpcz.stage("ps.shard.fetch"):
            return np.asarray(out)

    def _note_hot(self, local_keys: np.ndarray) -> None:
        """One read noted for every occurrence in ``local_keys``: one
        array pass whatever their number (one call's keys or a whole
        batch's, never padding)."""
        with rpcz.stage("ps.shard.note_hot",
                        keys=int(local_keys.size)) as st:
            if st is not rpcz.NOOP_STAGE:
                st.set(dup_keys=_dup_keys(local_keys))
            with self._books_mu:
                np.add.at(self._hot, local_keys, 1)

    def note_lookups(self, lookups: int, local_keys: np.ndarray, *,
                     batched: bool = False) -> None:
        """What ``lookups`` served lookups owe the shard's books, paid
        at once: ``local_keys`` are their live keys, concatenated.
        ``batched``: they were one batch of the lookup batcher (its
        per-batch completion calls this; ``lookup`` pays for itself)."""
        self._note_hot(local_keys)
        with self._books_mu:
            self.n_lookups += lookups
            if batched:
                self.n_lookup_batches += 1
                self.n_batched_lookups += lookups
        LOOKUPS.add(lookups)
        LOOKUP_KEYS.add(int(local_keys.size))
        if batched:
            LOOKUP_BATCH_COMPLETIONS.add(1)

    # ---- direct (unbatched) entry points ----

    def lookup(self, keys) -> tuple[np.ndarray, int]:
        """Gather owned rows for GLOBAL keys; returns (rows [n, dim],
        shard version at serve time)."""
        local = self._to_local(keys)
        n = local.shape[0]
        b = _bucket_up(max(n, 1), self.key_buckets)
        with self._locked():
            # the gather must FINISH under the lock: the fused
            # optimizer apply donates the table buffer and overwrites
            # it in place (see the lock-discipline note in __init__) —
            # the fancy-index / forced gather below returns a copy, so
            # nothing aliasing the table leaves the critical section
            if self._cpu_fast:
                rows = self._gather_rows(local, keys=n, bucket=b)
            else:
                padded = np.zeros((b,), np.int64)
                padded[:n] = local
                rows = self._gather_rows(padded, keys=n, bucket=b)[:n]
            ver = self.version
        self.note_lookups(1, local)
        return rows, ver

    def update(self, keys, grads, update_id: Optional[int] = None
               ) -> tuple[int, bool]:
        """Sparse scatter-add for GLOBAL keys; returns (version after
        the apply, was_duplicate).  A duplicate ``update_id`` acks with
        the ORIGINAL apply's version and touches nothing."""
        local = self._to_local(keys)
        grads = np.asarray(grads, np.float32)
        if grads.shape != (local.shape[0], self.dim):
            raise ValueError(f"grads shape {grads.shape} != "
                             f"({local.shape[0]}, {self.dim})")
        with self._locked():
            if update_id is not None and update_id in self._applied:
                self.n_dup_updates += 1
                DUP_UPDATES.add(1)
                return self._applied[update_id], True
            self._apply_locked(local, grads)
            ver = self.version
            if update_id is not None:
                self._record_applied_locked(update_id, ver)
            self.n_updates += 1
        UPDATES.add(1)
        UPDATE_KEYS.add(int(local.shape[0]))
        return ver, False

    def _apply_locked(self, local: np.ndarray, grads: np.ndarray) -> None:
        n = local.shape[0]
        b = _bucket_up(max(n, 1), self.key_buckets)
        pk = np.zeros((b,), np.int64)
        pg = np.zeros((b, self.dim), np.float32)
        pk[:n] = local
        pg[:n] = grads          # padded rows add 0 to row 0: a no-op
        with rpcz.stage("ps.shard.apply", keys=n, bucket=b) as st:
            if st is not rpcz.NOOP_STAGE:
                st.set(dup_keys=_dup_keys(local))
            self._rows = self._scatter(self._rows, pk, pg)
        self.version += 1

    # ---- the fused co-located optimizer apply (ISSUE 17) ----

    def _ensure_slots_locked(self, spec) -> None:
        jnp = self._jnp
        if "m" not in self._slots:
            # zeros_like preserves the rows' sharding: on a tp mesh
            # the momentum rows live exactly where their table rows do
            self._slots["m"] = jnp.zeros_like(self._rows)
        if spec.kind == "adam":
            if "v" not in self._slots:
                self._slots["v"] = jnp.zeros_like(self._rows)
            if "t" not in self._slots:
                self._slots["t"] = jnp.zeros((self.n_rows,), jnp.float32)

    def update_opt(self, keys, grads, spec,
                   update_id: Optional[int] = None) -> tuple[int, bool]:
        """``update`` with co-located optimizer state: the gradient
        scatter AND the slot step run as ONE jitted program per key
        bucket (train/optimizer.py), under the same lock, version
        counter and applied-id dedup as the plain scatter-add — so a
        retried wave acks the ORIGINAL version and can never
        double-step momentum.  The client sends RAW gradients; the
        slot rows never cross the wire."""
        from brpc_tpu.train.optimizer import fused_apply
        local = self._to_local(keys)
        grads = np.asarray(grads, np.float32)
        if grads.shape != (local.shape[0], self.dim):
            raise ValueError(f"grads shape {grads.shape} != "
                             f"({local.shape[0]}, {self.dim})")
        n = local.shape[0]
        b = _bucket_up(max(n, 1), self.key_buckets)
        pk = np.zeros((b,), np.int64)
        pg = np.zeros((b, self.dim), np.float32)
        # padding entries carry valid=0: they add no gradient AND do
        # not mark row 0 touched (a plain zero-grad pad would still
        # decay row 0's momentum — the mask is what makes padding a
        # true no-op under an optimizer)
        pv = np.zeros((b,), np.float32)
        pk[:n] = local
        pg[:n] = grads
        pv[:n] = 1.0
        with self._locked():
            if update_id is not None and update_id in self._applied:
                self.n_dup_updates += 1
                DUP_UPDATES.add(1)
                return self._applied[update_id], True
            with rpcz.stage("ps.shard.apply", keys=n, bucket=b) as st:
                if st is not rpcz.NOOP_STAGE:
                    st.set(dup_keys=_dup_keys(local))
                self._fused_apply_locked(spec, pk, pg, pv)
            self.version += 1
            ver = self.version
            if update_id is not None:
                self._record_applied_locked(update_id, ver)
            self.n_updates += 1
            self.n_opt_updates += 1
            # no _note_hot here: key heat feeds migration's hot-shard
            # detection and means READ traffic — lookups track it, the
            # plain update path doesn't, and a trainer hammering its
            # own rows every wave must not masquerade as serving heat
        UPDATES.add(1)
        OPT_UPDATES.add(1)
        UPDATE_KEYS.add(int(n))
        return ver, False

    def _fused_apply_locked(self, spec, pk, pg, pv) -> None:
        """One fused scatter+step over bucket-padded keys (``pv`` 0 on
        the padding), the slots allocated on first use."""
        from brpc_tpu.train.optimizer import fused_apply
        fn = fused_apply(spec.kind)
        self._ensure_slots_locked(spec)
        s = self._slots
        if spec.kind == "sgdm":
            self._rows, s["m"] = fn(
                self._rows, s["m"], pk, pg, pv,
                spec.lr, spec.momentum)
        else:
            self._rows, s["m"], s["v"], s["t"] = fn(
                self._rows, s["m"], s["v"], s["t"], pk, pg, pv,
                spec.lr, spec.beta1, spec.beta2, spec.eps)

    def warm(self, optimizer=None, batch_buckets: Sequence[int] = ()
             ) -> None:
        """Set-up's explicit entry: compile every program a serving
        window can meet and allocate the optimizer slots, changing
        nothing (the lazy paths stay for callers that never call this).
        Lookups: the gather at every key bucket, alone and as a batch
        of every ``batch_buckets`` size (the service passes its
        batcher's).  Updates: with ``optimizer`` (an ``OptimizerSpec``
        or its wire dict) the slots of that kind and its fused apply at
        every key bucket; without, the plain scatter-add at every key
        bucket, alone and flattened over every batch size.  Each
        program runs once on all-padding input (key 0, gradient 0,
        ``valid`` 0), which leaves rows, slots and version as they
        were."""
        spec = None
        if optimizer is not None:
            from brpc_tpu.train.optimizer import OptimizerSpec
            spec = OptimizerSpec.from_wire(optimizer)
        def padding(b):
            return (np.zeros((b,), np.int64),
                    np.zeros((b, self.dim), np.float32),
                    np.zeros((b,), np.float32))

        with self._mu:
            if spec is not None:
                # fresh slots are uncommitted arrays, an apply's outputs
                # committed ones, and jit keys on that: one apply first,
                # so that every bucket below compiles against the slots
                # a window will hand it
                self._fused_apply_locked(spec, *padding(self.key_buckets[0]))
            for b in self.key_buckets:
                shapes = [(b,)] + [(n, b) for n in batch_buckets]
                if not self._cpu_fast:
                    for shape in shapes:
                        self._gather(self._rows, np.zeros(shape, np.int64))
                if spec is not None:
                    self._fused_apply_locked(spec, *padding(b))
                    continue
                for shape in shapes:
                    flat = int(np.prod(shape))
                    self._rows = self._scatter(
                        self._rows, np.zeros((flat,), np.int64),
                        np.zeros((flat, self.dim), np.float32))
            self._jax.block_until_ready((self._rows, self._slots))

    def snapshot_slots(self) -> dict:
        """Current optimizer slot tables as numpy (tests compare
        against the dense oracle's slots)."""
        with self._mu:
            # np.array (not asarray): the caller keeps the snapshot
            # past the lock, and the next donated apply overwrites the
            # buffer a zero-copy view would still be pointing at
            return {k: np.array(v) for k, v in self._slots.items()}

    def _record_applied_locked(self, uid: int, ver: int) -> None:
        self._applied[uid] = ver
        while len(self._applied) > self._applied_cap:
            self._applied.popitem(last=False)

    # ---- dense Pull/Push ----

    def pull(self, pname: str) -> np.ndarray:
        with self._mu:
            if pname not in self._dense:
                raise KeyError(pname)
            self.n_pulls += 1
            out = self._dense[pname].copy()
        PULLS.add(1)
        return out

    def push(self, pname: str, delta, update_id: Optional[int] = None,
             ) -> tuple[int, bool]:
        delta = np.asarray(delta, np.float32)
        with self._mu:
            if update_id is not None and update_id in self._applied:
                self.n_dup_updates += 1
                DUP_UPDATES.add(1)
                return self._applied[update_id], True
            cur = self._dense.get(pname)
            if cur is None:
                self._dense[pname] = delta.copy()
            else:
                if cur.shape != delta.shape:
                    raise ValueError(f"push {pname}: shape {delta.shape} "
                                     f"!= {cur.shape}")
                self._dense[pname] = cur + delta
            self.version += 1
            ver = self.version
            if update_id is not None:
                self._record_applied_locked(update_id, ver)
            self.n_pushes += 1
        PUSHES.add(1)
        return ver, False

    # ---- DynamicBatcher batch_fns (service.py wires these) ----
    #
    # Lookup rows are int64 key vectors; the batch gather is ONE jitted
    # [B, Lb] -> [B, Lb, D] op per bucket pair (padded key 0 gathers
    # row 0 and is trimmed away by the batcher's padded-output scatter).

    def lookup_batch_fn(self, padded: np.ndarray) -> np.ndarray:
        # the batch's accounting (live-key counts, hot keys) happens in
        # the service's per-batch completion — this fn sees
        # bucket-padded rows and cannot tell live from padding
        k = np.asarray(padded, np.int64)
        with self._locked():
            # complete the gather under the lock — the fused optimizer
            # apply donates and overwrites the table in place, so the
            # zero-copy view must not be read outside the critical
            # section (the fancy-index result is a fresh array)
            self._gathered.version = self.version
            return self._gather_rows(k, keys=int(k.size),
                                     bucket=int(k.shape[-1]))

    def gathered_version(self) -> int:
        """The version the calling thread's last ``lookup_batch_fn``
        gathered at: what a batched lookup's rows show, exactly."""
        return self._gathered.version

    # Update rows pack (update_id, then per key [key, grad...]) into ONE
    # float64 vector: [uid, k0, g0_0..g0_{D-1}, k1, g1_0..].  float64
    # carries 53-bit update ids and float32 grads exactly; the length
    # buckets are 1 + k*(1+D) so the padded batch reshapes to
    # [B, kb, 1+D] (zero rows scatter grad 0 into row 0: a no-op).
    # Dedup is decided here, at APPLY time under the shard lock — the
    # only point where "already applied" is unambiguous.

    def update_length_buckets(self) -> tuple:
        return tuple(1 + k * (1 + self.dim) for k in self.key_buckets)

    @staticmethod
    def pack_update(update_id: int, local_keys: np.ndarray,
                    grads: np.ndarray) -> np.ndarray:
        n, d = grads.shape
        row = np.empty((1 + n * (1 + d),), np.float64)
        row[0] = float(update_id)
        body = row[1:].reshape(n, 1 + d)
        body[:, 0] = local_keys
        body[:, 1:] = grads
        return row

    def update_batch_fn(self, padded: np.ndarray) -> np.ndarray:
        """One coalesced scatter-add for every update row in the batch;
        returns per-row [version, dup_flag] acks."""
        B, Lb = padded.shape
        kb = (Lb - 1) // (1 + self.dim)
        body = np.ascontiguousarray(
            padded[:, 1:1 + kb * (1 + self.dim)]
        ).reshape(B, kb, 1 + self.dim)
        keys = body[:, :, 0].astype(np.int64)
        grads = body[:, :, 1:].astype(np.float32)
        uids = padded[:, 0].astype(np.int64)
        return self._apply_update_batch(uids, keys, grads)

    # The BINARY update path (tensorframe wire, ISSUE 13) packs bytes,
    # not float64: one record is [update_id u64][key i64, grad f32*D] x k
    # — vectorized byte views in and out, no per-element float64
    # conversion and no 53-bit packing ceiling on the row format.
    # Padding bytes are zero = key 0 grad 0 groups, a scatter no-op,
    # exactly the float64 scheme's discipline; both paths share
    # _apply_update_batch, so dedup is decided against ONE applied set
    # no matter which wire a retry arrives on.

    def update_record_buckets(self) -> tuple:
        return tuple(8 + k * (8 + 4 * self.dim) for k in self.key_buckets)

    @staticmethod
    def pack_update_record(update_id: int, local_keys: np.ndarray,
                           grads: np.ndarray) -> np.ndarray:
        """One uint8 record from int64 keys + float32 grads (views in:
        the frame's decoded tensors splice by vectorized byte copy)."""
        import struct as _struct
        n, d = grads.shape
        rec = np.empty((8 + n * (8 + 4 * d),), np.uint8)
        rec[:8] = np.frombuffer(_struct.pack("<Q", update_id), np.uint8)
        body = rec[8:].reshape(n, 8 + 4 * d)
        body[:, :8] = np.ascontiguousarray(
            local_keys, "<i8").view(np.uint8).reshape(n, 8)
        body[:, 8:] = np.ascontiguousarray(
            grads, "<f4").view(np.uint8).reshape(n, 4 * d)
        return rec

    def update_batch_fn_binary(self, padded: np.ndarray) -> np.ndarray:
        """update_batch_fn for uint8 records: reinterpret the byte
        columns as (uids, keys, grads) with three vectorized copies,
        then the shared apply."""
        B, Lb = padded.shape
        kb = (Lb - 8) // (8 + 4 * self.dim)
        uids = np.ascontiguousarray(
            padded[:, :8]).view("<u8").reshape(B).astype(np.int64)
        body = np.ascontiguousarray(
            padded[:, 8:8 + kb * (8 + 4 * self.dim)]
        ).reshape(B, kb, 8 + 4 * self.dim)
        keys = np.ascontiguousarray(
            body[:, :, :8]).view("<i8").reshape(B, kb)
        grads = np.ascontiguousarray(
            body[:, :, 8:]).view("<f4").reshape(B, kb, self.dim)
        return self._apply_update_batch(uids, keys, grads)

    def _apply_update_batch(self, uids: np.ndarray, keys: np.ndarray,
                            grads: np.ndarray) -> np.ndarray:
        """The ONE coalesced apply both wire formats feed: per-row
        dedup (applied set + intra-batch), one compiled scatter, acks
        [version, dup_flag] per row.  uid 0 marks batch padding."""
        B = keys.shape[0]
        acks = np.zeros((B, 2), np.float64)
        with self._locked():
            # dedup against the applied set AND within this batch: a
            # retry can land in the SAME batch as its original (reply
            # lost before the batch formed) — both rows would pass the
            # applied-set check, and double-applying here is exactly
            # the violation update_ids exist to prevent
            first_row: dict[int, int] = {}
            batch_dups: list[tuple[int, int]] = []   # (row, first row)
            for i in range(B):
                uid = int(uids[i])
                if uid == 0:
                    continue            # batch padding, not a request
                if uid in self._applied:
                    self.n_dup_updates += 1
                    DUP_UPDATES.add(1)
                    acks[i] = (self._applied[uid], 1.0)
                    # zero the row out of the scatter: served from the
                    # applied set, never re-added
                    keys[i] = 0
                    grads[i] = 0.0
                    continue
                if uid in first_row:
                    batch_dups.append((i, first_row[uid]))
                    keys[i] = 0
                    grads[i] = 0.0
                    continue
                first_row[uid] = i
            # ONE compiled scatter for the whole batch (compile per
            # (batch bucket, key bucket) pair); dup/padding rows are
            # zeroed above so they contribute nothing
            with rpcz.stage("ps.shard.apply", keys=int(keys.size),
                            bucket=int(keys.shape[-1])):
                self._rows = self._scatter(
                    self._rows, keys.reshape(-1),
                    grads.reshape(-1, self.dim))
            for uid, i in first_row.items():
                self.version += 1
                self._record_applied_locked(uid, self.version)
                acks[i] = (self.version, 0.0)
                self.n_updates += 1
                UPDATES.add(1)
            for i, j in batch_dups:
                # ack the retry with the ORIGINAL apply's version
                self.n_dup_updates += 1
                DUP_UPDATES.add(1)
                acks[i] = (acks[j, 0], 1.0)
        return acks

    # ---- introspection (/psserve) ----

    def hot_keys(self, top: int = 10) -> list[tuple[int, int]]:
        """The ``top`` most-read owned keys as (global key, reads), most
        read first and the lower key first among equals; exact."""
        with self._books_mu:
            hot = self._hot.copy()      # the selection runs unlocked
        top = min(int(top), hot.size)
        if top <= 0:
            return []
        kth = np.partition(hot, hot.size - top)[hot.size - top]
        idx = np.flatnonzero(hot > kth)
        if kth > 0:     # the ties at the cut, lowest keys first
            idx = np.concatenate(
                [idx, np.flatnonzero(hot == kth)[:top - idx.size]])
        reads = hot[idx]
        order = np.lexsort((idx, -reads))
        return list(zip((idx[order] + self.lo).tolist(),
                        reads[order].tolist()))

    def stats(self) -> dict:
        hot_keys = self.hot_keys()      # its own lock, not the shard's
        with self._mu:
            return {
                "name": self.name,
                "shard_index": self.shard_index,
                "n_shards": self.n_shards,
                "rows": self.n_rows,
                "range": [self.lo, self.hi],
                "dim": self.dim,
                "version": self.version,
                "lookups": self.n_lookups,
                "lookup_batch_completions": self.n_lookup_batches,
                "lookups_per_completion": (
                    round(self.n_batched_lookups / self.n_lookup_batches, 2)
                    if self.n_lookup_batches else None),
                "updates": self.n_updates,
                "opt_updates": self.n_opt_updates,
                "opt_slots": sorted(self._slots),
                "dup_updates": self.n_dup_updates,
                "pulls": self.n_pulls,
                "pushes": self.n_pushes,
                "dense_params": sorted(self._dense),
                "applied_ids": len(self._applied),
                "hot_keys": hot_keys,
                "mesh": (dict(self.mesh.shape) if self.mesh is not None
                         else None),
            }

    def snapshot_rows(self) -> np.ndarray:
        """The shard's current rows as numpy (tests compare against the
        dense oracle)."""
        with self._mu:
            # copy, not view: the donated optimizer apply overwrites
            # the table buffer in place after the lock is released
            return np.array(self._rows)

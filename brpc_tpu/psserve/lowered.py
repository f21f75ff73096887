"""ShardedEmbeddingTable — the PartitionChannel fan-out LOWERED to
collectives (SURVEY.md §5.8; the SNIPPETS.md [2] shard_map+ppermute
shape).

When every partition of the embedding service is a chip in the LOCAL
mesh, the client's split → N sub-calls → reassemble plan wastes the
fabric: the idiomatic lowering runs the whole exchange as ONE jitted
``shard_map`` over the ``tp`` axis.  The table lives row-sharded
(``P("tp", None)`` — each chip owns a contiguous row range; when
``vocab % p == 0`` this is exactly the
:func:`~brpc_tpu.psserve.shard.shard_bounds` ownership map the RPC
shards use, otherwise the table pads to even ``vocab/p`` blocks and
the two layouts differ — don't use ``shard_bounds`` to locate a key's
CHIP here), and a lookup is

  * ``mode="psum"``  — broadcast the keys, every chip gathers the rows
    it owns (masked local gather), ``psum`` over ``tp`` merges: one
    all-reduce instead of N socket round-trips;
  * ``mode="ring"``  — shard the keys, then ``ppermute`` the key block
    (and its accumulating rows) around the ring: after ``p`` hops every
    block visited every owner and is back home — the classic all-to-all
    embedding exchange, the exact SNIPPETS.md [2] pattern.

Updates scatter-add locally under an ownership mask (no collective on
the way out — the table STAYS sharded).  Key counts pad up to buckets
so each mode compiles once per bucket.  Both modes are bit-identical to
the dense single-host oracle: gathers are exact, and scatter-adds see
the same per-key operand order the dense op does (all duplicates of a
key land on its one owner, in request order).
"""
from __future__ import annotations

import threading
from typing import Optional, Sequence

import numpy as np

from brpc_tpu.bvar import Adder
from brpc_tpu.butil.lockprof import InstrumentedLock
from brpc_tpu.psserve.shard import (DEFAULT_KEY_BUCKETS, _bucket_up,
                                    init_embedding_table)

LOWERED_LOOKUPS = Adder("psserve_lowered_lookups")
LOWERED_UPDATES = Adder("psserve_lowered_updates")


class ShardedEmbeddingTable:
    """One logical [vocab, dim] table row-sharded over a ``tp`` mesh;
    lookup/update run as single compiled collective programs."""

    def __init__(self, vocab: int, dim: int, *, mesh=None,
                 n_shards: Optional[int] = None, seed: int = 0,
                 table: Optional[np.ndarray] = None,
                 key_buckets: Sequence[int] = DEFAULT_KEY_BUCKETS,
                 mode: str = "psum", serve_local: bool = False,
                 name: str = "ps", applied_cap: int = 65536):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from brpc_tpu.ici.collective import shard_map
        from brpc_tpu.ici.mesh import ensure_compile_cache
        ensure_compile_cache()
        if mode not in ("psum", "ring"):
            raise ValueError(f"mode must be psum|ring, got {mode!r}")
        if mesh is None:
            from brpc_tpu.models.runner import make_tp_mesh
            mesh = make_tp_mesh(n_shards)
        self.mesh = mesh
        self.p = int(mesh.shape["tp"])
        self.vocab = int(vocab)
        self.dim = int(dim)
        self.mode = mode
        self.key_buckets = tuple(sorted(key_buckets))
        full = table if table is not None else \
            init_embedding_table(vocab, dim, seed)
        full = np.asarray(full, np.float32)
        # pad rows so the shard axis divides evenly; the pad rows are
        # unaddressable (keys < vocab) and stay zero
        self.vpad = ((self.vocab + self.p - 1) // self.p) * self.p
        if self.vpad != self.vocab:
            full = np.concatenate(
                [full, np.zeros((self.vpad - self.vocab, self.dim),
                                np.float32)])
        self.rows_per = self.vpad // self.p
        self._table = jax.device_put(
            full, NamedSharding(mesh, P("tp", None)))
        self._mu = InstrumentedLock("psserve.table")
        self.version = 0
        self.n_lookups = 0
        self.n_updates = 0
        self.n_opt_updates = 0
        self.n_dup_updates = 0
        self.name = str(name)
        # the ICI fast path's idempotence (ISSUE 13): the same
        # update_id-checked-against-an-applied-set discipline the RPC
        # shards run, so a co-located client's replayed update_token
        # acks the ORIGINAL apply instead of double scatter-adding
        from collections import OrderedDict
        self._applied: "OrderedDict[int, int]" = OrderedDict()
        self._applied_cap = int(applied_cap)
        from brpc_tpu import psserve as _ps
        _ps._register_table(self)
        if serve_local:
            # explicit opt-in: THIS table serves co-located PSClients
            # (PSClient(ici="auto") short-circuits to it)
            _ps.register_local_table(self, name=self.name)

        jnp_ = jnp
        rows_per = self.rows_per
        p = self.p

        def _local_gather(tbl, keys):
            # tbl: this chip's [rows_per, dim] block; keys: global ids
            lo = jax.lax.axis_index("tp") * rows_per
            local = keys - lo
            mask = (local >= 0) & (local < rows_per)
            safe = jnp_.clip(local, 0, rows_per - 1)
            rows = tbl[safe]
            return jnp_.where(mask[:, None], rows, 0.0), mask

        def _lookup_psum(tbl, keys):
            rows, _ = _local_gather(tbl, keys)
            return jax.lax.psum(rows, "tp")

        def _lookup_ring(tbl, blk):
            # blk: this chip's key block [n/p]; rotate (block, acc)
            # around the ring — after p ppermute hops the block has
            # visited every owner and is back at its home chip
            acc = jnp_.zeros((blk.shape[0], self.dim), jnp_.float32)
            perm = [(i, (i + 1) % p) for i in range(p)]

            def hop(carry, _):
                b, a = carry
                rows, _ = _local_gather(tbl, b)
                a = a + rows
                b = jax.lax.ppermute(b, "tp", perm)
                a = jax.lax.ppermute(a, "tp", perm)
                return (b, a), None

            (blk, acc), _ = jax.lax.scan(hop, (blk, acc), None, length=p)
            return acc

        def _update(tbl, keys, grads):
            lo = jax.lax.axis_index("tp") * rows_per
            local = keys - lo
            mask = (local >= 0) & (local < rows_per)
            safe = jnp_.clip(local, 0, rows_per - 1)
            g = jnp_.where(mask[:, None], grads, 0.0)
            return tbl.at[safe].add(g)

        # the fused co-located optimizer updates (ISSUE 17): the SAME
        # ownership-mask discipline as _update, with the slot step
        # from train/optimizer.py running on each chip's block — the
        # whole train step stays ONE shard_map program and the slot
        # rows stay sharded exactly like their table rows.  Pad keys
        # (-1) are owned by nobody: mask-zeroed gradient AND zero
        # touch count, so padding can't decay row 0's momentum.
        from brpc_tpu.train.optimizer import adam_step, sgdm_step

        def _local_acc(tbl, keys, grads):
            lo = jax.lax.axis_index("tp") * rows_per
            local = keys - lo
            mask = (local >= 0) & (local < rows_per)
            safe = jnp_.clip(local, 0, rows_per - 1)
            g = jnp_.where(mask[:, None], grads, 0.0)
            g_acc = jnp_.zeros_like(tbl).at[safe].add(g)
            cnt = jnp_.zeros((tbl.shape[0],), jnp_.float32
                             ).at[safe].add(mask.astype(jnp_.float32))
            return g_acc, cnt > 0.0

        def _update_sgdm(tbl, m, keys, grads, lr, mu):
            g_acc, touched = _local_acc(tbl, keys, grads)
            return sgdm_step(jnp_, tbl, m, g_acc, touched, lr, mu)

        def _update_adam(tbl, m, v, t, keys, grads, lr, b1, b2, eps):
            g_acc, touched = _local_acc(tbl, keys, grads)
            return adam_step(jnp_, tbl, m, v, t, g_acc, touched,
                             lr, b1, b2, eps)

        self._lookup_psum = jax.jit(shard_map(
            _lookup_psum, mesh, in_specs=(P("tp", None), P()),
            out_specs=P()))
        self._lookup_ring = jax.jit(shard_map(
            _lookup_ring, mesh, in_specs=(P("tp", None), P("tp")),
            out_specs=P("tp", None)))
        self._update = jax.jit(shard_map(
            _update, mesh, in_specs=(P("tp", None), P(), P()),
            out_specs=P("tp", None)))
        self._update_sgdm = jax.jit(shard_map(
            _update_sgdm, mesh,
            in_specs=(P("tp", None), P("tp", None), P(), P(), P(), P()),
            out_specs=(P("tp", None), P("tp", None))))
        self._update_adam = jax.jit(shard_map(
            _update_adam, mesh,
            in_specs=(P("tp", None), P("tp", None), P("tp", None),
                      P("tp"), P(), P(), P(), P(), P(), P()),
            out_specs=(P("tp", None), P("tp", None), P("tp", None),
                       P("tp"))))
        self._slots: dict = {}

    # ---- client surface (PSClient's co-located backend) ----

    def _pad_keys(self, keys, multiple_of: int = 1) -> tuple:
        keys = np.asarray(keys, np.int64)
        n = keys.shape[0]
        b = _bucket_up(max(n, 1), self.key_buckets)
        if b % multiple_of:
            b = ((b + multiple_of - 1) // multiple_of) * multiple_of
        padded = np.full((b,), -1, np.int64)   # -1: owned by nobody
        padded[:n] = keys
        return padded, n

    def lookup(self, keys) -> tuple[np.ndarray, int]:
        """Gather rows for GLOBAL keys (any owner, duplicates legal):
        one compiled collective program per key bucket."""
        if self.mode == "ring":
            padded, n = self._pad_keys(keys, multiple_of=self.p)
            out = self._lookup_ring(self._table, padded)
        else:
            padded, n = self._pad_keys(keys)
            out = self._lookup_psum(self._table, padded)
        with self._mu:
            ver = self.version
            self.n_lookups += 1
        LOWERED_LOOKUPS.add(1)
        return np.asarray(out)[:n], ver

    def _ensure_slots_locked(self, spec) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        if "m" not in self._slots:
            self._slots["m"] = jnp.zeros_like(self._table)
        if spec.kind == "adam":
            if "v" not in self._slots:
                self._slots["v"] = jnp.zeros_like(self._table)
            if "t" not in self._slots:
                self._slots["t"] = jax.device_put(
                    np.zeros((self.vpad,), np.float32),
                    NamedSharding(self.mesh, P("tp")))

    def update(self, keys, grads, update_id: Optional[int] = None,
               optimizer=None) -> int:
        """Scatter-add grads into the sharded table; one compiled
        program, table stays sharded.  With ``update_id`` the apply is
        idempotent exactly like the RPC shards: a duplicate id acks
        the ORIGINAL apply's version and touches nothing.

        With ``optimizer`` (an :class:`OptimizerSpec`, ISSUE 17) the
        grads are RAW gradients and the apply is the fused
        scatter+slot-step shard_map program under the ownership mask —
        momentum/Adam slots stay sharded with their rows, and the dup
        check above covers them: a replayed wave steps nothing."""
        padded, n = self._pad_keys(keys)
        g = np.zeros((padded.shape[0], self.dim), np.float32)
        g[:n] = np.asarray(grads, np.float32)
        with self._mu:
            if update_id is not None and update_id in self._applied:
                self.n_dup_updates += 1
                return self._applied[update_id]
            if optimizer is None:
                self._table = self._update(self._table, padded, g)
            else:
                self._ensure_slots_locked(optimizer)
                s = self._slots
                f32 = np.float32
                if optimizer.kind == "sgdm":
                    self._table, s["m"] = self._update_sgdm(
                        self._table, s["m"], padded, g,
                        f32(optimizer.lr), f32(optimizer.momentum))
                else:
                    self._table, s["m"], s["v"], s["t"] = \
                        self._update_adam(
                            self._table, s["m"], s["v"], s["t"],
                            padded, g, f32(optimizer.lr),
                            f32(optimizer.beta1), f32(optimizer.beta2),
                            f32(optimizer.eps))
                self.n_opt_updates += 1
            self.version += 1
            ver = self.version
            if update_id is not None:
                self._applied[update_id] = ver
                while len(self._applied) > self._applied_cap:
                    self._applied.popitem(last=False)
            self.n_updates += 1
        LOWERED_UPDATES.add(1)
        return ver

    # ---- introspection / oracle ----

    def snapshot(self) -> np.ndarray:
        """Current table (vocab rows, pad stripped) as numpy."""
        with self._mu:
            return np.asarray(self._table)[:self.vocab]

    def snapshot_slots(self) -> dict:
        """Optimizer slots (vocab rows, pad stripped) as numpy."""
        with self._mu:
            return {k: np.asarray(v)[:self.vocab]
                    for k, v in self._slots.items()}

    def stats(self) -> dict:
        with self._mu:
            return {
                "name": self.name,
                "partitions": self.p,
                "vocab": self.vocab,
                "dim": self.dim,
                "mode": self.mode,
                "version": self.version,
                "lookups": self.n_lookups,
                "updates": self.n_updates,
                "opt_updates": self.n_opt_updates,
                "opt_slots": sorted(self._slots),
                "dup_updates": self.n_dup_updates,
                "applied_ids": len(self._applied),
                "mesh": dict(self.mesh.shape),
            }

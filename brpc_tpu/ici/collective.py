"""Collective lowering — fan-out/fan-in as ONE compiled program.

The reference's ParallelChannel sends N copies over N sockets and merges N
responses on the host (§2.5).  Inside a TPU slice that plan wastes the
fabric: the idiomatic lowering is a single jitted shard_map over the mesh
where the "fan-out" is a broadcast (or shard), every chip runs the service
function locally, and the fan-in is a collective inside the same program:
``psum`` for "sum", ``all_gather`` for "stack" (every chip then holds a
replica of every chip's result, and ``CollectiveGroup.fan_in`` hands out
one chip's replicas without a further program or a byte through host
memory).  "concat" and "none" run no collective: their result stays
sharded over the mesh, one piece a chip.  This module is that lowering;
combo channels use it automatically when all targets are ICI endpoints.
"""
from __future__ import annotations

import threading
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from brpc_tpu import rpcz
from brpc_tpu.bvar import Adder, LatencyRecorder, MultiDimension
from brpc_tpu.ici.mesh import get_mesh


def shard_map(f, mesh, in_specs, out_specs):
    # Replication of collective outputs (all_gather/psum) can't always be
    # statically inferred; disable the varying-manual-axes check.
    # This IS the constructor the callers hoist into their caches.
    # brpc-check: allow(jit-hot-path)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


_lowered_calls = Adder("ici_collective_calls")
# the time of a lowered call from program lookup to its result being
# ready on the mesh (``_run``): a latency, not the time of an enqueue
_lowered_latency = LatencyRecorder("ici_collective")
# fan-ins of a stacked result by where the caller's rows came from:
# "in_place" (the caller's chip is in the mesh and holds a replica) or
# "moved" (it is not: one chip's replicas went to it device to device)
_fan_ins = MultiDimension(["rows"], Adder, name="ici_collective_fan_ins")
# resolved once: ``get_stats`` takes a lock, and the fan-in is a hot path
_fan_in_place, _fan_in_moved = (_fan_ins.get_stats(k)
                                for k in ("in_place", "moved"))


class CollectiveGroup:
    """Fan-out execution over a mesh axis."""

    def __init__(self, mesh=None, axis: str = "chip"):
        self.mesh = mesh if mesh is not None else get_mesh()
        self.axis = axis
        self._devices = frozenset(self.mesh.devices.flat)
        self._cache: dict = {}
        self._mu = threading.Lock()

    @property
    def size(self) -> int:
        return self.mesh.shape[self.axis]

    def _get(self, key, build):
        with self._mu:
            f = self._cache.get(key)
            if f is None:
                f = build()
                self._cache[key] = f
            return f

    def _place(self, x, spec):
        """The request, laid out over the mesh as the program's in_spec
        says.  A caller's array is usually COMMITTED to the one chip
        that produced it, and a program spanning the mesh refuses such
        an argument; the broadcast (or the split) is the fan-out."""
        with rpcz.stage("collective.place") as stg:
            if stg is not rpcz.NOOP_STAGE:
                stg.set(bytes=rpcz.payload_bytes(x))
            return jax.device_put(x, NamedSharding(self.mesh, spec))

    def _run(self, key, build, placed):
        """Look the program up (building it on first use), launch it on
        the placed request and wait for the result: the lowered call's
        latency ends when the merged result is ready on the mesh."""
        import time
        t0 = time.monotonic()
        with rpcz.stage("collective.run") as stg:
            if stg is not rpcz.NOOP_STAGE:
                stg.set(cache_hit=int(key in self._cache))
            out = jax.block_until_ready(self._get(key, build)(placed))
        _lowered_calls.add(1)
        _lowered_latency.add(int((time.monotonic() - t0) * 1e6))
        return out

    # ---- ParallelChannel lowering: same request to every chip ----

    def parallel_apply(self, fn: Callable, x, merge: str = "stack"):
        """Broadcast x, run fn per chip, merge.  Returns when the result
        is ready:

        "stack"   a tuple of ``size`` arrays in mesh order, the i-th
                  chip i's ``fn(x)`` (its shape and dtype, a buffer of
                  its own), gathered inside the program and replicated
                  over the mesh; ``fan_in`` takes one chip's replicas
        "sum"     one replicated array, the ``psum`` of the results
        "concat"  the results joined along axis 0, sharded one a chip
        "none"    the same array as "concat": results left sharded
        """
        axis = self.axis
        n = self.size

        def build():
            def per_chip(xb):
                y = fn(xb)
                if merge == "sum":
                    return jax.lax.psum(y, axis)
                if merge == "stack":
                    rows = jax.lax.all_gather(y, axis)
                    return tuple(rows[i] for i in range(n))
                return y
            out_specs = {"sum": P(), "stack": (P(),) * n}.get(merge, P(axis))
            return jax.jit(shard_map(per_chip, self.mesh, in_specs=P(),
                                     out_specs=out_specs))

        # keyed by the fn OBJECT (kept alive by the cache): id() keys could
        # be reused after GC and serve a stale compiled program
        return self._run(("par", fn, merge), build, self._place(x, P()))

    def fan_in(self, rows, device):
        """The rows of a "stack" result as single-device arrays committed
        to ``device``, in mesh order; returns ``(rows, moved)``.  A chip
        of the mesh already holds a replica of every row: those buffers
        are handed out as they are (no program, no copy; ``moved`` 0).
        For a chip outside the mesh the first chip's replicas go to it
        device to device in one batched ``device_put`` (``moved`` =
        their number), waited for like the program's own result."""
        in_mesh = device in self._devices
        if in_mesh and device != self.mesh.devices.flat[0]:
            local = [next(s.data for s in r.addressable_shards
                          if s.device == device) for r in rows]
        else:
            # the first chip's replica, whatever the index: unlike
            # ``addressable_shards`` it makes no array object for the
            # other chips' replicas, and freeing one of those costs
            # the caller a hand-off of the interpreter lock under load
            local = [r.addressable_data(0) for r in rows]
        if in_mesh:
            _fan_in_place.add(1)
            return local, 0
        _fan_in_moved.add(1)
        return jax.block_until_ready(jax.device_put(local, device)), \
            len(local)

    # ---- PartitionChannel lowering: shard the request ----

    def partition_apply(self, fn: Callable, x, merge: str = "concat"):
        """Shard x along axis 0 across chips, run fn per shard, merge:
        "concat" | "sum" | "none" (keep sharded).  Returns when the
        result is ready."""
        axis = self.axis

        def build():
            def per_chip(xs):
                y = fn(xs)
                if merge == "sum":
                    return jax.lax.psum(y, axis)
                return y
            in_spec = P(axis)
            out_spec = P() if merge == "sum" else P(axis)
            return jax.jit(shard_map(per_chip, self.mesh,
                                     in_specs=in_spec, out_specs=out_spec))

        return self._run(("part", fn, merge), build,
                         self._place(x, P(axis)))

    # ---- primitives for the ici_performance ladder ----

    def ring_shift(self, x, steps: int = 1):
        """ppermute ring shift: chip i's shard moves to chip (i+steps)%n.
        The unit transfer of ring collectives (and the §5.8 ladder)."""
        axis = self.axis
        n = self.size

        def build():
            def shift(xs):
                perm = [(i, (i + steps) % n) for i in range(n)]
                return jax.lax.ppermute(xs, axis, perm)
            return jax.jit(shard_map(shift, self.mesh, in_specs=P(axis),
                                     out_specs=P(axis)))

        return self._get(("shift", steps), build)(x)

    def all_gather(self, x):
        axis = self.axis

        def build():
            def g(xs):
                return jax.lax.all_gather(xs, axis, tiled=True)
            return jax.jit(shard_map(g, self.mesh, in_specs=P(axis),
                                     out_specs=P()))

        return self._get(("gather",), build)(x)

    def all_reduce(self, x):
        axis = self.axis

        def build():
            def r(xs):
                return jax.lax.psum(xs, axis)
            return jax.jit(shard_map(r, self.mesh, in_specs=P(axis),
                                     out_specs=P()))

        return self._get(("reduce",), build)(x)

    def reduce_scatter(self, x):
        """Each chip contributes its full view of x; chip i receives the
        i-th slice of the summed result (classic reduce-scatter)."""
        axis = self.axis

        def build():
            def rs(xs):
                return jax.lax.psum_scatter(xs, axis, tiled=True)
            return jax.jit(shard_map(rs, self.mesh, in_specs=P(),
                                     out_specs=P(axis)))

        return self._get(("rscatter",), build)(x)

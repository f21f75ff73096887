"""Device-payload rail — ICI inside the ordinary RPC data path.

Reference: RdmaEndpoint::CutFromIOBufList replaces
cut_into_file_descriptor inside Socket::StartWrite/KeepWrite
(/root/reference/src/brpc/socket.cpp:1751-1757, rdma/rdma_endpoint.h:82):
once both peers complete the RDMA handshake, an ordinary RPC's IOBuf
payload rides the RC queue pair while TCP carries only control traffic —
call sites never change.

TPU build: when a Channel.call request (or a handler's response) is made
of jax device arrays and the target server has advertised an
ICI-reachable device, the payload is staged into BlockPool HBM slots
(on-device bitcast, no host bounce), moved through IciEndpoint's
credit-windowed send path, and parked in the process-wide payload
registry.  The TRPC frame then carries only a claim ticket in its user
fields; the receiving side claims the blocks and rebuilds device arrays
with an on-device unstage.  The payload never exists as host bytes —
`host_copy_count()` gives tests a provable zero.
"""
from __future__ import annotations

import itertools
import threading
import time
from dataclasses import dataclass

import jax
import numpy as np

from brpc_tpu import rpcz
from brpc_tpu.bvar import Adder
from brpc_tpu.ici.block_pool import (BLOCK_CLASSES, Block, _stage, _unstage,
                                     get_block_pool)
from brpc_tpu.ici.endpoint import IciEndpoint

rail_payloads = Adder("rail_payloads")
rail_bytes = Adder("rail_bytes")
rail_fallbacks = Adder("rail_fallbacks")
_ticket_counter = itertools.count(1)

_CHUNK = BLOCK_CLASSES[-1]

# user-field keys riding the TRPC meta (control plane only)
# canonical definitions live with the wire format (rpc/meta.py); aliased
# here so rail code reads naturally
from brpc_tpu.rpc.meta import F_SRC_DEV, F_TICKET  # noqa: E402,F401

# ---------------------------------------------------------------------------
# rail map: which endpoints are ICI-reachable
# ---------------------------------------------------------------------------

_map_lock = threading.Lock()
_advertised: dict[int, object] = {}       # port -> jax device
_LOCAL_HOSTS = {"127.0.0.1", "localhost", "0.0.0.0", "::1"}


def advertise(port: int, device) -> None:
    """Server-side: declare that the RPC server on `port` can receive
    payloads on `device` (the handshake-complete bit of the RDMA path)."""
    with _map_lock:
        _advertised[port] = device


def unadvertise(port: int) -> None:
    with _map_lock:
        _advertised.pop(port, None)


def lookup(endpoint) -> object | None:
    """Client-side: the device an endpoint receives on, or None when the
    payload must stay on the socket.  In-process only until the DCN
    handshake lands (SURVEY §5.8); remote hosts return None."""
    if getattr(endpoint, "host", None) not in _LOCAL_HOSTS:
        return None
    with _map_lock:
        return _advertised.get(endpoint.port)


# ---------------------------------------------------------------------------
# staging: device arrays <-> BlockPool slots, entirely on device
# ---------------------------------------------------------------------------

# named for the trace (``jit_rail_slice_chunk``, ``jit_rail_cat``), as
# the copy programs of ici/endpoint.py are

def rail_slice_chunk(flat, offset, size: int):
    return jax.lax.dynamic_slice(flat, (offset,), (size,))


def rail_cat(bufs):
    import jax.numpy as jnp
    return jnp.concatenate(bufs)


_slice_chunk = jax.jit(rail_slice_chunk, static_argnums=(2,))
_cat = jax.jit(rail_cat)


@dataclass
class _Entry:
    """One staged array: destination blocks + how to rebuild it."""
    blocks: list
    dtype: str
    shape: tuple
    nbytes: int

    def unstage(self, free: bool = True):
        if len(self.blocks) == 1:
            buf = self.blocks[0].view()
        else:
            buf = _cat([b.view() for b in self.blocks])
        out = _unstage(buf, self.dtype, self.shape)
        if free:
            for b in self.blocks:
                b.free()
        return out

    def free(self) -> None:
        for b in self.blocks:
            b.free()


@dataclass
class _DirectEntry:
    """One whole-array transfer: the moved device array itself.

    The fast path for arrays that fit the endpoint's credit window: the
    async copy's output (already the right dtype/shape on the target
    device) IS the deliverable — no block staging, no slice/concat, no
    unstage rebuild.  One XLA dispatch per array instead of ~6."""
    array: object
    nbytes: int

    def unstage(self, free: bool = True):
        out = self.array
        if free:
            self.array = None
        return out

    def free(self) -> None:
        self.array = None


def _stage_one(arr: jax.Array, pool) -> list[Block]:
    """Stage one device array into source-pool blocks without touching the
    host: small arrays pad into one slot (block_pool._stage), large ones
    flatten to uint8 on device and slice into 2MB chunks."""
    n = arr.nbytes
    if n <= _CHUNK:
        b = pool.alloc(n)
        b.put(arr)  # jax.Array branch: on-device _stage
        return [b]
    padded = ((n + _CHUNK - 1) // _CHUNK) * _CHUNK
    flat = _stage(arr, padded)  # uint8[padded] on the source device
    blocks = []
    try:
        for off in range(0, n, _CHUNK):
            piece = _slice_chunk(flat, off, _CHUNK)
            b = pool.alloc(_CHUNK)
            b.install(piece, min(_CHUNK, n - off))
            blocks.append(b)
    except Exception:
        for b in blocks:
            b.free()
        raise
    return blocks


def _is_device_array(x) -> bool:
    if not isinstance(x, jax.Array):
        return False
    try:
        return len(x.devices()) == 1
    except Exception:
        return False


def railable(obj) -> bool:
    """True when `obj` is a single-device jax array or a non-empty
    list/tuple of them — the payload shapes the rail can carry."""
    if isinstance(obj, (list, tuple)):
        return len(obj) > 0 and all(_is_device_array(a) for a in obj)
    return _is_device_array(obj)


def source_device(obj):
    first = obj[0] if isinstance(obj, (list, tuple)) else obj
    return next(iter(first.devices()))


def device_by_id(device_id: int):
    for d in jax.devices():
        if d.id == device_id:
            return d
    raise KeyError(f"no local device with id {device_id}")


# The rail's claim registry is PER-PROCESS: a ticket shipped to a peer in
# another process can never be claimed (its blocks would pin HBM until
# the TTL sweeper).  Device advertisements on the wire therefore carry
# this process token; resolution fails closed for any other process.
import uuid as _uuid

_PROCESS_TOKEN = _uuid.uuid4().hex[:16]


def device_advert(device) -> str:
    """Wire value advertising `device` as a tensor receive endpoint
    (stream settings F_SDEV): process token + device id."""
    return f"{_PROCESS_TOKEN}:{device.id}"


def device_from_wire(value):
    """Resolve a peer's device advertisement.  None unless the advert
    came from THIS process (token match) and names a local device — the
    single gate keeping rail tickets off cross-process streams."""
    if value is None:
        return None
    if isinstance(value, bytes):
        value = value.decode()
    token, _, dev_id = value.partition(":")
    if token != _PROCESS_TOKEN or not dev_id:
        return None
    try:
        return device_by_id(int(dev_id))
    except (KeyError, ValueError):
        return None


# ---------------------------------------------------------------------------
# payload registry: ticket -> staged entries (the claim table)
# ---------------------------------------------------------------------------

_REGISTRY_TTL_S = 60.0
_reg_lock = threading.Lock()
_registry: dict[str, tuple[list, bool, float]] = {}
_sweeper_started = False


def _purge_locked(now: float) -> None:
    dead = [t for t, (_, _, dl) in _registry.items() if dl < now]
    for t in dead:
        entries, _, _ = _registry.pop(t)
        for e in entries:
            e.free()


def _sweep_loop() -> None:
    # Orphaned tickets must not pin HBM blocks forever in a process that
    # stopped depositing — the TTL fires on its own clock, not on traffic.
    while True:
        time.sleep(_REGISTRY_TTL_S / 4)
        with _reg_lock:
            _purge_locked(time.monotonic())


def _ensure_sweeper() -> None:
    global _sweeper_started
    if not _sweeper_started:
        _sweeper_started = True
        threading.Thread(target=_sweep_loop, daemon=True,
                         name="rail-ttl-sweeper").start()


def deposit(entries: list, single: bool) -> str:
    # TTL purging belongs to the sweeper thread alone: purging inline
    # here scanned the WHOLE registry under the lock on every deposit —
    # O(pending) per message, measured at ~19us/msg with 2k outstanding
    # stream chunks (a quadratic drag exactly when streaming is busiest)
    ticket = f"t{next(_ticket_counter)}"
    with _reg_lock:
        _registry[ticket] = (entries, single,
                             time.monotonic() + _REGISTRY_TTL_S)
    _ensure_sweeper()
    return ticket


def _norm(ticket) -> str:
    # user-field values come off the wire as bytes (meta.py decode)
    return ticket.decode() if isinstance(ticket, bytes) else ticket


def claim(ticket):
    """Pop the ticket and rebuild device arrays (frees the blocks)."""
    with rpcz.stage("rail.claim"):
        ticket = _norm(ticket)
        with _reg_lock:
            item = _registry.pop(ticket, None)
        if item is None:
            raise KeyError(
                f"rail ticket {ticket!r} expired or already claimed")
        entries, single, _ = item
        arrays = [e.unstage() for e in entries]
        return arrays[0] if single else arrays


def withdraw(ticket) -> None:
    """Free an unclaimed ticket (failed/abandoned attempt).  Claim is an
    atomic pop, so racing the receiver cannot double-free."""
    ticket = _norm(ticket)
    with _reg_lock:
        item = _registry.pop(ticket, None)
    if item is None:
        return
    for e in item[0]:
        e.free()


def pending_tickets() -> int:
    with _reg_lock:
        return len(_registry)


# ---------------------------------------------------------------------------
# the send half: stage + ICI transfer + deposit
# ---------------------------------------------------------------------------

_ep_lock = threading.Lock()
_endpoints: dict[int, IciEndpoint] = {}


# Rail endpoints get a wider credit window than the 64MB transport
# default: stream writers burst whole messages of a hundred megabytes
# and more, and releasing credit costs a completion sync.  Only `window`
# bytes can be in flight while a completion is observed, which on a
# directly attached chip takes microseconds: 256MB never caps it, and
# bounds the HBM a burst can pin.
_RAIL_WINDOW_BYTES = 256 * 1024 * 1024

# Largest send_batch arity ship_many will emit: bounds both the XLA
# program cache (log2 entries per chunk shape) and single-program size.
_MAX_ARITY = 32


def _endpoint_for(device) -> IciEndpoint:
    with _ep_lock:
        ep = _endpoints.get(device.id)
        if ep is None:
            ep = IciEndpoint(device, window_bytes=_RAIL_WINDOW_BYTES)
            _endpoints[device.id] = ep
            _ensure_atexit()
        return ep


_atexit_registered = False


def close_endpoints() -> None:
    """Close every rail endpoint and join its completion drainer.  A
    daemon drainer still inside the runtime's block_until_ready when
    the interpreter finalizes aborts the process ('FATAL: exception
    not rethrown') — after the results are out, turning a clean run
    into a nonzero exit.  Registered atexit; a program that reports a
    final result calls it BEFORE reporting."""
    with _ep_lock:
        eps = list(_endpoints.values())
        _endpoints.clear()
    for ep in eps:
        try:
            ep.close(join=True)
        except Exception:
            pass


def _ensure_atexit() -> None:
    global _atexit_registered
    if _atexit_registered:
        return
    _atexit_registered = True
    import atexit
    atexit.register(close_endpoints)


def ship(obj, target_device) -> str:
    """Move a railable payload to `target_device` through the block pipe
    and park it in the registry; returns the claim ticket for the meta.

    This is the CutFromIOBufList moment: bytes that would have been
    serialized into the socket ride the ICI send path instead."""
    return ship_many([obj], target_device)[0]


def ship_many(objs, target_device) -> list[str]:
    """Ship several railable payloads with batched dispatch ACROSS
    payloads: the whole run of window-fitting arrays — regardless of
    which message they belong to — rides one send_batch (one compiled
    multi-copy program, one completion record), and each payload still
    gets its OWN registry ticket so per-message claim/withdraw semantics
    are unchanged.  Dispatch is host work per program, so this is the
    difference between per-message and per-batch transfer cost (the h2
    frame-coalescing story, applied to tensors)."""
    with rpcz.stage("rail.ship") as stg:
        tickets, nbytes, programs = _ship_many(objs, target_device)
        if stg is not rpcz.NOOP_STAGE:
            stg.set(bytes=nbytes, programs=programs, cross_device=int(
                source_device(objs[0]) != target_device))
        return tickets


def _ship_many(objs, target_device) -> tuple[list[str], int, int]:
    """``ship_many``'s work; also the bytes shipped and the dispatches
    (endpoint sends) they took."""
    ep = _endpoint_for(target_device)
    shipped = programs = 0
    # (payload idx, array, nbytes): jax.Array.nbytes is a COMPUTED
    # property (prod(shape) * itemsize per access) — cache it once per
    # array; the run-packing loop below reads it repeatedly
    flat: list[tuple[int, jax.Array, int]] = []
    singles = []
    for oi, obj in enumerate(objs):
        singles.append(not isinstance(obj, (list, tuple)))
        for a in (obj if isinstance(obj, (list, tuple)) else [obj]):
            flat.append((oi, a, a.nbytes))
    per_obj: list[list] = [[] for _ in objs]
    try:
        i = 0
        while i < len(flat):
            oi, a, a_nbytes = flat[i]
            if a_nbytes > ep.window_bytes:
                # oversize payloads still ride the block pipe so the
                # credit window keeps bounding in-flight HBM per chunk
                src_pool = get_block_pool(source_device(a))
                staged = _stage_one(a, src_pool)
                try:
                    moved = ep.send_blocks(staged)
                finally:
                    for b in staged:
                        b.free()
                per_obj[oi].append(_Entry(moved, str(np.dtype(a.dtype)),
                                          tuple(a.shape), a_nbytes))
                rail_bytes.add(a_nbytes)
                shipped += a_nbytes
                programs += 1
                i += 1
                continue
            # whole-array fast path: group a window-fitting run of arrays
            # into ONE batched dispatch (send_batch compiles k copy HLOs
            # into one program); the moved arrays are the deliverables
            run = [flat[i]]
            run_bytes = a_nbytes
            while (i + len(run) < len(flat)
                   and flat[i + len(run)][2] <= ep.window_bytes
                   and run_bytes + flat[i + len(run)][2]
                       <= ep.window_bytes):
                run.append(flat[i + len(run)])
                run_bytes += run[-1][2]
            # Power-of-2 sub-batches: send_batch compiles one XLA program
            # per (arity, shapes), and adaptive coalescing would otherwise
            # produce an unbounded set of arities — every new one a fresh
            # compile, worse than the per-message dispatches it
            # replaces.  Decomposing 27 chunks
            # as 16+8+2+1 bounds the program set to log2(cap) per shape.
            moved_run = []
            j = 0
            while j < len(run):
                k = min(1 << ((len(run) - j).bit_length() - 1), _MAX_ARITY)
                sub = [x for _, x, _ in run[j:j + k]]
                moved_run.extend(ep.send_batch(sub) if k > 1
                                 else [ep.send(sub[0])])
                programs += 1
                j += k
            for (roi, _, src_nb), m in zip(run, moved_run):
                per_obj[roi].append(_DirectEntry(m, src_nb))
                rail_bytes.add(src_nb)
            shipped += run_bytes
            i += len(run)
    except Exception:
        for es in per_obj:
            for e in es:
                e.free()
        raise
    rail_payloads.add(len(objs))
    return ([deposit(es, single) for es, single in zip(per_obj, singles)],
            shipped, programs)


# ---------------------------------------------------------------------------
# proof hooks
# ---------------------------------------------------------------------------

def host_copy_count() -> int:
    """Total payload-bytes-materialized-on-host events across the tensor
    serializer and the block pool.  A rail round-trip must leave this
    unchanged — the test's 'provably never bounced through host bytes'."""
    from brpc_tpu.ici import block_pool
    from brpc_tpu.rpc import serialization
    return (serialization.tensor_host_encodes.get_value()
            + serialization.tensor_host_decodes.get_value()
            + block_pool.host_stage_count.get_value()
            + block_pool.host_read_count.get_value())

"""OptimizerSpec + the fused scatter-and-slot-update math (ISSUE 17).

"RPC Considered Harmful" (PAPERS.md) argues distributed training dies
on per-update round trips unless updates are batched, co-located with
state, and fused into one device program.  This module is that fix on
our own wire: optimizer slot rows (momentum; Adam m/v/step) live WITH
the embedding shard that owns the parameter rows, and ``PS.Update``
carrying an optimizer spec runs

    gradient scatter  +  slot step  +  row step

as ONE jitted program per key-count bucket.  The slots never cross the
wire — the client sends RAW gradients, not deltas.

The math lives here ONCE (``sgdm_step`` / ``adam_step`` are pure
``jnp`` elementwise functions) and is shared by all three executors:

  * the RPC shard's fused apply (:meth:`EmbeddingShardServer.update_opt`),
  * the lowered ``shard_map`` apply under the ownership mask
    (:meth:`ShardedEmbeddingTable.update`),
  * the dense single-host oracle (:func:`oracle_apply`) the bit-identity
    tests compare both against.

One source of the formulas is what makes bit-identity across partition
counts provable rather than approximate: the scatter accumulates every
duplicate of a key on its one owner in request order (the dense
scatter's order), and everything after the scatter is elementwise.

Semantics per touched row r (rows with no key in the update keep ALL
state bit-for-bit, including Adam step counts):

    sgdm:  m_r    <- momentum * m_r + g_r
           row_r  <- row_r - lr * m_r
    adam:  t_r    <- t_r + 1
           m_r    <- beta1 * m_r + (1 - beta1) * g_r
           v_r    <- beta2 * v_r + (1 - beta2) * g_r^2
           row_r  <- row_r - lr * (m_r / (1 - beta1^t_r))
                              / (sqrt(v_r / (1 - beta2^t_r)) + eps)

where g_r is the SUM of that row's gradient contributions in the
update (duplicate keys accumulate first, then the slot steps once —
exactly what a dense ``.at[].add`` + host optimizer would do).
"""
from __future__ import annotations

import threading
from typing import Optional

import numpy as np

VALID_KINDS = ("sgdm", "adam")

# the flattened tensorframe field names (the binary wire has no nested
# dicts: the spec rides as inline scalar fields next to keys/grads)
_FRAME_FIELDS = ("opt_kind", "opt_lr", "opt_momentum", "opt_beta1",
                 "opt_beta2", "opt_eps")


class OptimizerSpec:
    """One wire-parseable optimizer description.

    ``kind`` is ``"sgdm"`` (momentum SGD; uses ``lr``/``momentum``) or
    ``"adam"`` (uses ``lr``/``beta1``/``beta2``/``eps``).  Hyper-
    parameters ride the wire as plain floats and reach the fused
    program as TRACED scalars, so the compile count stays one per
    (kind, key bucket) no matter how a schedule sweeps them.
    """

    __slots__ = ("kind", "lr", "momentum", "beta1", "beta2", "eps")

    def __init__(self, kind: str, *, lr: float = 0.1,
                 momentum: float = 0.9, beta1: float = 0.9,
                 beta2: float = 0.999, eps: float = 1e-8):
        if kind not in VALID_KINDS:
            raise ValueError(f"optimizer kind must be one of "
                             f"{VALID_KINDS}, got {kind!r}")
        for fname, val in (("lr", lr), ("momentum", momentum),
                           ("beta1", beta1), ("beta2", beta2),
                           ("eps", eps)):
            if isinstance(val, bool) or not isinstance(val, (int, float)):
                raise ValueError(f"optimizer {fname} must be a number")
            if not np.isfinite(float(val)):
                raise ValueError(f"optimizer {fname} must be finite")
        self.kind = kind
        self.lr = float(lr)
        self.momentum = float(momentum)
        self.beta1 = float(beta1)
        self.beta2 = float(beta2)
        self.eps = float(eps)

    # ---- wire forms ----

    def to_wire(self) -> dict:
        """The JSON form (``PS.Update``'s ``"optimizer"`` field)."""
        if self.kind == "sgdm":
            return {"kind": "sgdm", "lr": self.lr,
                    "momentum": self.momentum}
        return {"kind": "adam", "lr": self.lr, "beta1": self.beta1,
                "beta2": self.beta2, "eps": self.eps}

    @classmethod
    def from_wire(cls, obj) -> "OptimizerSpec":
        """Parse the JSON form (or pass through a spec).  Raises
        ValueError on anything malformed — the service maps that to
        EREQUEST, never EINTERNAL."""
        if isinstance(obj, cls):
            return obj
        if not isinstance(obj, dict):
            raise ValueError('"optimizer" must be an object')
        kind = obj.get("kind")
        if kind not in VALID_KINDS:
            raise ValueError(f'optimizer "kind" must be one of '
                             f"{VALID_KINDS}")
        kw = {}
        for fname in ("lr", "momentum", "beta1", "beta2", "eps"):
            if fname in obj:
                kw[fname] = obj[fname]
        return cls(kind, **kw)

    def to_frame_fields(self) -> dict:
        """The FLATTENED tensorframe form: inline scalar fields
        (``opt_kind`` + floats) merged next to keys/grads — the binary
        wire carries no nested dicts."""
        return {"opt_kind": self.kind, "opt_lr": self.lr,
                "opt_momentum": self.momentum, "opt_beta1": self.beta1,
                "opt_beta2": self.beta2, "opt_eps": self.eps}

    @classmethod
    def from_frame_fields(cls, req: dict) -> Optional["OptimizerSpec"]:
        """Reassemble from a decoded frame; None when the request
        carries no optimizer (no ``opt_kind`` field)."""
        kind = (req or {}).get("opt_kind")
        if kind is None:
            return None
        if not isinstance(kind, str):
            raise ValueError('"opt_kind" must be a string')
        kw = {}
        for fname in ("lr", "momentum", "beta1", "beta2", "eps"):
            v = req.get(f"opt_{fname}")
            if v is not None:
                kw[fname] = v
        return cls(kind, **kw)

    def slot_names(self) -> tuple:
        return ("m",) if self.kind == "sgdm" else ("m", "v", "t")

    def __repr__(self) -> str:
        return f"OptimizerSpec({self.to_wire()})"

    def __eq__(self, other) -> bool:
        return isinstance(other, OptimizerSpec) and \
            self.to_wire() == other.to_wire()


# ---------------------------------------------------------------------------
# the ONE slot-step math (pure jnp elementwise; jax passed in so this
# module imports without touching jax)
# ---------------------------------------------------------------------------

def sgdm_step(jnp, rows, m, g_acc, touched, lr, mu):
    """Momentum-SGD step over pre-accumulated per-row gradients.
    Untouched rows keep rows AND m bit-for-bit."""
    tmask = touched[:, None]
    m_new = jnp.where(tmask, mu * m + g_acc, m)
    rows_new = jnp.where(tmask, rows - lr * m_new, rows)
    return rows_new, m_new


def adam_step(jnp, rows, m, v, t, g_acc, touched, lr, b1, b2, eps):
    """Adam step with PER-ROW step counts (a row's bias correction
    depends on how many updates touched THAT row, not a global clock —
    sparse training's rows advance at wildly different rates)."""
    tmask = touched[:, None]
    t_new = t + touched.astype(t.dtype)
    m_new = jnp.where(tmask, b1 * m + (1.0 - b1) * g_acc, m)
    v_new = jnp.where(tmask, b2 * v + (1.0 - b2) * g_acc * g_acc, v)
    # untouched rows may still have t == 0; clamp so their (discarded)
    # branch never divides by zero
    ts = jnp.maximum(t_new, 1.0)
    bc1 = 1.0 - b1 ** ts
    bc2 = 1.0 - b2 ** ts
    step = lr * (m_new / bc1[:, None]) \
        / (jnp.sqrt(v_new / bc2[:, None]) + eps)
    rows_new = jnp.where(tmask, rows - step, rows)
    return rows_new, m_new, v_new, t_new


# ---------------------------------------------------------------------------
# the fused scatter+step programs (jitted once per kind; the bucket
# padding discipline bounds compiles per kind to the bucket count)
# ---------------------------------------------------------------------------

_fns_mu = threading.Lock()
_FUSED: dict = {}


def fused_apply(kind: str):
    """The jitted fused program for ``kind`` — built once per process
    (never per call: the shard's hot path must not construct jits).

    Signature (sgdm):  (rows, m, keys, grads, valid, lr, mu)
                       -> (rows', m')
    Signature (adam):  (rows, m, v, t, keys, grads, valid,
                        lr, b1, b2, eps) -> (rows', m', v', t')

    ``keys`` are LOCAL row indices padded to a bucket; ``valid`` is a
    float32 mask (0.0 on padding) so pad entries neither contribute
    gradient NOR mark row 0 touched.  Duplicate keys accumulate into
    ``g_acc`` first, then the slot steps once per touched row.

    The state arrays (rows + slots) are DONATED: the program writes
    them in place instead of materialising four table-sized outputs
    per wave.  A wave is still O(vocab): duplicate keys accumulate in
    ``g_acc``, a zeroed temporary of the table's own shape
    (``jnp.zeros_like(rows)``), and in ``cnt``, one float a row, both
    made and filled anew every wave, so each wave writes a table's
    worth of zeros beside the O(bucket) scatter and slot math (PERF.md
    section 7).  The programs are named (``jit_ps_sgdm_apply`` /
    ``jit_ps_adam_apply`` in a device trace).  Callers must treat the
    inputs as consumed and keep every other reader of those buffers
    behind the owner's lock (the shard does; ``oracle_apply`` passes
    throwaway copies).  The step math itself runs on the GATHERED
    bucket rows — bit-identical to the dense elementwise form because
    untouched rows are untouched either way, and duplicate key
    positions all compute the same post-accumulation value.
    """
    if kind not in VALID_KINDS:
        raise ValueError(f"optimizer kind must be one of {VALID_KINDS}")
    fn = _FUSED.get(kind)
    if fn is not None:
        return fn
    with _fns_mu:
        fn = _FUSED.get(kind)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        if kind == "sgdm":
            def ps_sgdm_apply(rows, m, keys, grads, valid, lr, mu):
                g_acc = jnp.zeros_like(rows).at[keys].add(
                    grads * valid[:, None])
                cnt = jnp.zeros((rows.shape[0],), jnp.float32
                                ).at[keys].add(valid)
                rk, mk = sgdm_step(jnp, rows[keys], m[keys],
                                   g_acc[keys], cnt[keys] > 0.0,
                                   lr, mu)
                return rows.at[keys].set(rk), m.at[keys].set(mk)
            # built ONCE per process under _fns_mu and cached in
            # _FUSED; the early return above keeps the hot path
            # construction-free
            # brpc-check: allow(jit-hot-path)
            fn = jax.jit(ps_sgdm_apply, donate_argnums=(0, 1))
        else:
            def ps_adam_apply(rows, m, v, t, keys, grads, valid,
                              lr, b1, b2, eps):
                g_acc = jnp.zeros_like(rows).at[keys].add(
                    grads * valid[:, None])
                cnt = jnp.zeros((rows.shape[0],), jnp.float32
                                ).at[keys].add(valid)
                rk, mk, vk, tk = adam_step(
                    jnp, rows[keys], m[keys], v[keys], t[keys],
                    g_acc[keys], cnt[keys] > 0.0, lr, b1, b2, eps)
                return (rows.at[keys].set(rk), m.at[keys].set(mk),
                        v.at[keys].set(vk), t.at[keys].set(tk))
            # once per process, cached in _FUSED (see the sgdm branch)
            # brpc-check: allow(jit-hot-path)
            fn = jax.jit(ps_adam_apply, donate_argnums=(0, 1, 2, 3))
        _FUSED[kind] = fn
        return fn


# ---------------------------------------------------------------------------
# the dense single-host oracle (tests)
# ---------------------------------------------------------------------------

def zero_slots(spec: OptimizerSpec, vocab: int, dim: int) -> dict:
    """Fresh host-side slot state matching what a shard lazily
    allocates (all zeros)."""
    slots = {"m": np.zeros((vocab, dim), np.float32)}
    if spec.kind == "adam":
        slots["v"] = np.zeros((vocab, dim), np.float32)
        slots["t"] = np.zeros((vocab,), np.float32)
    return slots


def oracle_apply(table: np.ndarray, slots: dict, keys, grads,
                 spec: OptimizerSpec) -> tuple:
    """ONE fused update applied to the DENSE single-host table: the
    bit-identity oracle.  Runs the exact fused program the shards run
    (same scatter, same elementwise step, GLOBAL keys, no padding),
    so any divergence on a sharded path is the sharding's fault, not
    a reimplementation's.  Returns (table', slots') as numpy; inputs
    are not mutated."""
    keys = np.asarray(keys, np.int64)
    grads = np.asarray(grads, np.float32)
    if grads.shape != (keys.shape[0], table.shape[1]):
        raise ValueError(f"grads shape {grads.shape} != "
                         f"({keys.shape[0]}, {table.shape[1]})")
    valid = np.ones((keys.shape[0],), np.float32)
    fn = fused_apply(spec.kind)
    # the fused program DONATES its state inputs — hand it fresh device
    # copies so the caller's arrays stay intact ("inputs are not
    # mutated" above is a promise)
    import jax.numpy as jnp
    tbl = jnp.array(np.asarray(table, np.float32))
    sl = {k: jnp.array(np.asarray(v, np.float32))
          for k, v in slots.items()}
    if spec.kind == "sgdm":
        rows, m = fn(tbl, sl["m"], keys, grads, valid,
                     spec.lr, spec.momentum)
        return np.asarray(rows), {"m": np.asarray(m)}
    rows, m, v, t = fn(tbl, sl["m"], sl["v"], sl["t"],
                       keys, grads, valid, spec.lr, spec.beta1,
                       spec.beta2, spec.eps)
    return np.asarray(rows), {"m": np.asarray(m), "v": np.asarray(v),
                              "t": np.asarray(t)}

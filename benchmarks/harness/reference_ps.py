"""Plain reference of the parameter-server deployments: an embedding
table under sparse Adam updates, in numpy float32, one row at a time in
meaning if not in code.  It imports nothing of the program.

The table a run starts from is a pure function of (seed, key, column),
so any row can be made again without the table; the gradients an update
carried are rows of a pool that is a pure function of the seed.  The
state (row, ``m``, ``v``, step count) is kept only for rows an update
has touched.

Semantics of one update with keys ``k`` and gradients ``g`` (what
``psserve``'s documentation states, written from that description and
not from its code): duplicate keys accumulate first, then every
distinct row steps once,

    t <- t + 1;  m <- b1 m + (1 - b1) G;  v <- b2 v + (1 - b2) G^2
    row <- row - lr (m / (1 - b1^t)) / (sqrt(v / (1 - b2^t)) + eps)

with ``G`` the sum of the row's gradients in this update and ``t`` the
row's own count of updates.  Rows no key names keep everything.
"""
from __future__ import annotations

import numpy as np

_M1 = np.uint32(2654435761)
_M2 = np.uint32(2246822519)
_M3 = np.uint32(3266489917)
_K1 = np.uint32(0x9E3779B1)
TABLE_SCALE = np.float32(0.1)     # rows lie in [-0.05, 0.05)
GRAD_STREAM = 5                   # the seed's stream of the gradient pool


def table_rows(seed32: int, keys, dim: int) -> np.ndarray:
    """float32[n, dim]: the table's rows at ``keys`` before any update.
    A mixed 32-bit hash of (seed, key, column), its top 24 bits taken
    as a fraction: every step is exact in float32 but the last
    scaling, which rounds the same way wherever numpy runs it."""
    keys = np.asarray(keys, np.int64)
    with np.errstate(over="ignore"):
        x = (keys.astype(np.uint32) * _K1 + np.uint32(seed32))[:, None] \
            + np.arange(dim, dtype=np.uint32)[None, :] * _M1
        x ^= x >> np.uint32(15)
        x *= _M2
        x ^= x >> np.uint32(13)
        x *= _M3
        x ^= x >> np.uint32(16)
    frac = (x >> np.uint32(8)).astype(np.float32) * np.float32(2.0 ** -24)
    return (frac - np.float32(0.5)) * TABLE_SCALE


def gradient_pool(seed32: int, rows: int, dim: int, scale: float
                  ) -> np.ndarray:
    """float32[rows, dim], normal with deviation ``scale``: an update's
    gradients are ``n`` consecutive rows of it."""
    rng = np.random.default_rng([int(seed32), GRAD_STREAM])
    return (rng.standard_normal((rows, dim), dtype=np.float32)
            * np.float32(scale))


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16 (nearest, ties to even), as float32:
    the nearest precision below the one the configuration states."""
    bits = np.ascontiguousarray(x, np.float32).view(np.uint32)
    with np.errstate(over="ignore"):
        bits = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                           & np.uint32(1))
    return (bits & np.uint32(0xFFFF0000)).view(np.float32)


class AdamTable:
    """The table after a sequence of updates, rows made on demand."""

    def __init__(self, seed32: int, dim: int, *, lr: float, beta1: float,
                 beta2: float, eps: float):
        self.seed32 = int(seed32)
        self.dim = int(dim)
        f = np.float32
        self.lr, self.b1, self.b2, self.eps = f(lr), f(beta1), f(beta2), \
            f(eps)
        self.version = 0
        self._slot: dict = {}              # key -> index into the arrays
        self._rows = np.zeros((0, dim), np.float32)
        self._m = np.zeros((0, dim), np.float32)
        self._v = np.zeros((0, dim), np.float32)
        self._t = np.zeros((0,), np.float32)

    def touched_keys(self) -> np.ndarray:
        return np.fromiter(self._slot, np.int64, len(self._slot))

    def _slots_for(self, uniq: np.ndarray) -> np.ndarray:
        new = [int(k) for k in uniq if int(k) not in self._slot]
        if new:
            base = len(self._slot)
            for i, k in enumerate(new):
                self._slot[k] = base + i
            grow = max(len(new), len(self._t))      # doubles: amortised
            if base + len(new) > len(self._t):
                pad = np.zeros((grow, self.dim), np.float32)
                self._rows = np.concatenate([self._rows, pad])
                self._m = np.concatenate([self._m, pad])
                self._v = np.concatenate([self._v, pad])
                self._t = np.concatenate(
                    [self._t, np.zeros((grow,), np.float32)])
            idx = np.arange(base, base + len(new))
            self._rows[idx] = table_rows(self.seed32, new, self.dim)
        return np.fromiter((self._slot[int(k)] for k in uniq), np.int64,
                           len(uniq))

    def apply(self, keys, grads) -> None:
        """One update: duplicates summed, one step a distinct row."""
        keys = np.asarray(keys, np.int64)
        grads = np.asarray(grads, np.float32)
        uniq, inv = np.unique(keys, return_inverse=True)
        g = np.zeros((len(uniq), self.dim), np.float32)
        np.add.at(g, inv, grads)
        idx = self._slots_for(uniq)
        one = np.float32(1.0)
        t = self._t[idx] + one
        m = self.b1 * self._m[idx] + (one - self.b1) * g
        v = self.b2 * self._v[idx] + (one - self.b2) * g * g
        bc1 = one - np.power(self.b1, t)
        bc2 = one - np.power(self.b2, t)
        step = self.lr * (m / bc1[:, None]) \
            / (np.sqrt(v / bc2[:, None]) + self.eps)
        self._rows[idx] = self._rows[idx] - step
        self._m[idx], self._v[idx], self._t[idx] = m, v, t
        self.version += 1

    def rows(self, keys) -> np.ndarray:
        """float32[n, dim]: the rows at ``keys`` as they stand now."""
        keys = np.asarray(keys, np.int64)
        out = table_rows(self.seed32, keys, self.dim)
        slot = self._slot
        hit = [(i, slot[k]) for i, k in enumerate(keys.tolist())
               if k in slot]
        if hit:
            at, idx = zip(*hit)
            out[list(at)] = self._rows[list(idx)]
        return out


def row_gaps(got, want, wrong_shape: float) -> np.ndarray:
    """Per row, the largest absolute difference; a reply of another
    shape or dtype reads ``wrong_shape`` on every row."""
    got = np.asarray(got)
    if got.shape != want.shape or got.dtype != np.float32:
        return np.full((want.shape[0],), wrong_shape, np.float64)
    if want.shape[0] == 0:
        return np.zeros((0,), np.float64)
    return np.abs(got.astype(np.float64) - want).max(axis=1)

"""Keyed traffic of the parameter-server cells (generator
``closed_loop_keyed``): what ``harness/generators.py`` lacks, beside
the driver that uses it.

One fixed block of calls, each a (kind, keys per call) pair drawn once
from the mix's own ``length_seed``; every seed and caller sends seeded
permutations of that block laid end to end
(``generators.block_permutations``), so the seed orders the mix and
never changes the work.  Keys are YCSB's scrambled Zipfian: a rank from
the Zipfian distribution (``ZipfianGenerator.nextLong``), the key its
FNV-1a 64 hash modulo the key space (``ScrambledZipfianGenerator``,
``Utils.fnvhash64``).  Written from knowledge of YCSB's source: no
network here.
"""
from __future__ import annotations

import numpy as np

from benchmarks.harness import generators as gen

FNV_OFFSET_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)


def zeta(n: int, theta: float) -> float:
    return float(np.sum(1.0 / np.arange(1, n + 1, dtype=np.float64)
                        ** theta))


class Zipfian:
    """Ranks 0..n-1, rank 0 the most popular, as YCSB draws them."""

    def __init__(self, n: int, theta: float):
        self.n, self.theta = int(n), float(theta)
        self.zetan = zeta(self.n, self.theta)
        self.alpha = 1.0 / (1.0 - self.theta)
        self.eta = (1.0 - (2.0 / self.n) ** (1.0 - self.theta)) \
            / (1.0 - zeta(2, self.theta) / self.zetan)

    def ranks(self, u: np.ndarray) -> np.ndarray:
        uz = u * self.zetan
        tail = (self.n * (self.eta * u - self.eta + 1.0) ** self.alpha)
        out = np.minimum(tail.astype(np.int64), self.n - 1)
        out[uz < 1.0 + 0.5 ** self.theta] = 1
        out[uz < 1.0] = 0
        return out


def fnv1a64(values: np.ndarray) -> np.ndarray:
    """``Utils.fnvhash64``: the value's eight octets, low first, each
    xored in and multiplied (wrap-around), the result's absolute value
    as a signed 64-bit number."""
    val = values.astype(np.uint64)
    h = np.full(val.shape, FNV_OFFSET_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h = (h ^ (val & np.uint64(0xFF))) * FNV_PRIME_64
            val = val >> np.uint64(8)
    return np.abs(h.view(np.int64))


def scrambled_zipfian_keys(zipf: Zipfian, count: int, rng) -> np.ndarray:
    """``count`` keys in [0, zipf.n): duplicates kept as drawn."""
    return fnv1a64(zipf.ranks(rng.random(count))) % zipf.n


def block(traffic: dict) -> list:
    """The mix's fixed block: ``block_calls`` (kind, keys, resend)
    triples.  Lengths come from ``length_seed`` alone; the updates sit
    at even spacing in the block as drawn (every seed permutes it), and
    the first ``resends_per_block`` of them are sent a second time with
    the same token."""
    n = int(traffic["block_calls"])
    updates = int(traffic["block_updates"])
    k = traffic["keys_per_call"]
    lengths = gen.lognormal_lengths(
        n, float(k["median"]), float(k["sigma"]), int(k["min"]),
        int(k["max"]), np.random.default_rng(int(traffic["length_seed"])))
    every = n // updates
    out = []
    for i, length in enumerate(lengths):
        is_update = i % every == every - 1 and i // every < updates
        resend = is_update and i // every < int(
            traffic.get("resends_per_block", 0))
        out.append(("update" if is_update else "lookup", length, resend))
    return out


class CallerPlan:
    """One caller's calls, all drawn before the window: for call ``i``
    its kind, its keys (a slice of the caller's pre-drawn key pool),
    whether it is re-sent, where its gradients start in the shared
    pool, and whether its reply is kept for the comparison."""

    def __init__(self, traffic: dict, seed: int, caller: int,
                 zipf: Zipfian, grad_pool_rows: int, horizon: int,
                 key_pool: int):
        blk = block(traffic)
        rng = gen.rng_for(seed, 1, caller)
        order = gen.block_permutations(range(len(blk)), horizon, rng)
        self.kind = [blk[j][0] for j in order]
        self.resend = [blk[j][2] for j in order]
        self.n = np.asarray([blk[j][1] for j in order], np.int64)
        longest = int(self.n.max())
        self.keys = scrambled_zipfian_keys(
            zipf, key_pool, gen.rng_for(seed, 3, caller))
        self.key_off = (np.cumsum(self.n) - self.n) % (key_pool - longest)
        self.grad_off = rng.integers(0, grad_pool_rows - longest, horizon)
        self.flag = rng.random(horizon) < float(
            traffic.get("sample_share", 0.125))
        self.horizon = horizon

    def call(self, i: int) -> tuple:
        """(kind, keys, resend, gradient offset, sampled) of call i."""
        j = i % self.horizon
        off, n = int(self.key_off[j]), int(self.n[j])
        return (self.kind[j], self.keys[off:off + n], self.resend[j],
                int(self.grad_off[j]), bool(self.flag[j]))

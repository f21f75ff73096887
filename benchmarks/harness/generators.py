"""Seeded traffic generation.  One general generator per loop kind; a
traffic mix is a data file of parameters (``benchmarks/traffic/*.json``).

Every seed gets the SAME multiset of sizes, in another order: a
sequence is a concatenation of seeded permutations of one fixed block,
so the work of a window does not swing with the seed.
"""
from __future__ import annotations

import threading
import time

import numpy as np

_MASK32 = 0xFFFFFFFF


def fold_seed(seed: int) -> int:
    """Any whole number (the driver's are over 2**31) to 32 bits."""
    seed = int(seed)
    return (seed ^ (seed >> 32) ^ (seed >> 64)) & _MASK32


def rng_for(seed: int, *stream) -> np.random.Generator:
    return np.random.default_rng([fold_seed(seed), *[int(s) for s in stream]])


def block_permutations(block, n: int, rng) -> list:
    """``n`` items: seeded permutations of ``block`` laid end to end."""
    out = []
    block = list(block)
    while len(out) < n:
        out.extend(block[i] for i in rng.permutation(len(block)))
    return out[:n]


def lognormal_lengths(n: int, median: float, sigma: float, lo: int, hi: int,
                      rng_fixed) -> list:
    """``n`` lengths from a clipped log-normal.  Drawn from a generator
    that does NOT depend on the run's seed (the caller passes one made
    from the mix's own ``length_seed``): the seed only orders them."""
    x = np.exp(rng_fixed.normal(np.log(median), sigma, n))
    return [int(v) for v in np.clip(np.rint(x), lo, hi)]


class ClosedLoop:
    """``n_callers`` threads; each issues its next call when the last
    one returned.  ``call(caller, index)`` does one call and returns a
    record (dict with ``t_issue``, ``t_done``, ``ok``, ``bytes``...).
    Stops issuing at the close of the window and waits for what is in
    flight (at most ``drain_s``)."""

    def __init__(self, n_callers: int, call):
        self.n_callers = n_callers
        self.call = call
        self.records: list = [[] for _ in range(n_callers)]
        self.errors: list = []
        self._stop = threading.Event()

    def _loop(self, caller: int) -> None:
        i = 0
        recs = self.records[caller]
        while not self._stop.is_set():
            try:
                recs.append(self.call(caller, i))
            except BaseException as e:   # a bug in the benchmark itself
                self.errors.append(e)
                self._stop.set()
                raise
            i += 1

    def run(self, seconds: float, during=None, drain_s: float = 60.0):
        """Returns (t0, t1).  ``during(t0)`` runs on this thread while
        the callers work (the traced run starts the profiler there)."""
        threads = [threading.Thread(target=self._loop, args=(c,),
                                    name=f"bench-caller-{c}", daemon=True)
                   for c in range(self.n_callers)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        if during is not None:
            during(t0)
        left = t0 + seconds - time.monotonic()
        if left > 0:
            self._stop.wait(left)
        t1 = time.monotonic()
        self._stop.set()
        deadline = t1 + drain_s
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))
        self.stuck = sum(t.is_alive() for t in threads)
        if self.errors:
            raise self.errors[0]
        return t0, t1

    def all_records(self) -> list:
        return [r for recs in self.records for r in recs]

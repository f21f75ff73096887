"""The bytes the parameter server's kernels MUST move, from the shapes
of the calls alone, never from what the program happens to run.  All
three are memory-bound by construction (a gather does no arithmetic, an
Adam step a dozen operations a value), so the least time is bytes over
the chip's HBM bandwidth.
"""
from __future__ import annotations

ITEM = 4          # float32 throughout


def gather_bytes(n_keys: int, dim: int) -> int:
    """A gather of ``n_keys`` rows reads each row once and writes it
    once into the reply (duplicates are served, so they count)."""
    return 2 * int(n_keys) * int(dim) * ITEM


def adam_apply_bytes(n_keys: int, n_distinct: int, dim: int) -> int:
    """One update of ``n_keys`` gradients over ``n_distinct`` rows: every
    gradient row is read once; each distinct key's row, ``m`` and ``v``
    (``dim`` floats each) and step count ``t`` (one float) are read once
    and written once."""
    state = int(n_distinct) * (3 * int(dim) + 1) * ITEM
    return int(n_keys) * int(dim) * ITEM + 2 * state


def least_seconds(nbytes: float, peaks: dict) -> float:
    return nbytes / peaks["hbm_bytes_per_s"]

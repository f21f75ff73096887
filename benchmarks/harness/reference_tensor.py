"""Plain reference of the tensor deployments: an echo returns the bytes
it was sent.  The payload a call carried is a pure function of
(seed, payload id, size), so the reference makes it again in numpy,
from the seed alone, and takes nothing the program or the device made.
"""
from __future__ import annotations

import numpy as np

_M1 = np.uint32(2654435761)
_M2 = np.uint32(2246822519)
_M3 = np.uint32(3266489917)


def payload_key(seed32: int, tid: int) -> int:
    """The 32-bit key of payload ``tid`` under a (folded) seed."""
    return (int(seed32) * 0x9E3779B1 + int(tid) * 0x85EBCA77 + 0x165667B1) \
        & 0xFFFFFFFF


def payload_numpy(seed32: int, tid: int, n_words: int) -> np.ndarray:
    """uint32[n_words]: a mixed hash of (key, index), wrap-around
    arithmetic throughout."""
    with np.errstate(over="ignore"):
        x = np.arange(n_words, dtype=np.uint32) * _M1 \
            + np.uint32(payload_key(seed32, tid))
        x ^= x >> np.uint32(15)
        x *= _M2
        x ^= x >> np.uint32(13)
        x *= _M3
        x ^= x >> np.uint32(16)
    return x


def mismatched_words(reply, seed32: int, tid: int, n_words: int) -> int:
    """How many words of ``reply`` (anything ``np.asarray`` takes) differ
    from the reference's payload; a wrong shape or dtype counts whole."""
    got = np.asarray(reply)
    if got.dtype != np.uint32 or got.shape != (n_words,):
        return n_words
    return int(np.count_nonzero(got != payload_numpy(seed32, tid, n_words)))

"""Device mesh management.

One place decides what "the local slice" is: real TPU chips when present,
the virtual CPU mesh under tests (conftest forces 8 CPU devices).  Channels
address chips as ici://<slice>/<chip> (EndPoint scheme "ici").
"""
from __future__ import annotations

import os
import threading
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh

_lock = threading.Lock()
_meshes: dict[tuple, Mesh] = {}


def local_devices():
    return jax.devices()


def device_for(chip_index: int):
    """The local device ``ici://<slice>/<chip_index>`` names.  An index
    past the device count raises: wrapping it would put chip 3's
    traffic on chip 0 of a one-chip host without a word."""
    devs = jax.devices()
    if not 0 <= chip_index < len(devs):
        raise ValueError(f"chip index {chip_index} out of range: this "
                         f"process sees {len(devs)} device(s)")
    return devs[chip_index]


# ---- persistent compile cache ----------------------------------------------

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# the cache directory is part of every entry's key, so it must be the
# same path run after run: never a temp name, a pid or a time
REPO_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def ensure_compile_cache() -> Optional[str]:
    """Give jax's persistent compilation cache a home before the first
    real compile (one full-width decode step is tens of seconds of it).  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set the cache was placed from
    outside — jax reads the variable itself and this sets NOTHING;
    otherwise the fixed, git-ignored directory inside the checkout.
    Returns the directory this call chose, None when it left the
    choice to the environment.  Idempotent; called where serving
    (``TransformerRunner``), the rail (``BlockPool``) and the PS
    (``EmbeddingShardServer``, ``ShardedEmbeddingTable``) first touch
    jax."""
    if os.environ.get(COMPILE_CACHE_ENV):
        return None
    if jax.config.jax_compilation_cache_dir != REPO_COMPILE_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir",
                          REPO_COMPILE_CACHE_DIR)
    return REPO_COMPILE_CACHE_DIR


def get_mesh(n_devices: Optional[int] = None,
             axis_names: tuple[str, ...] = ("chip",),
             shape: Optional[tuple[int, ...]] = None) -> Mesh:
    """Mesh over the first n local devices (default: all).  Multi-axis
    meshes (e.g. ("dp","tp")) reshape the device list row-major."""
    devs = jax.devices()
    if n_devices is None:
        n_devices = len(devs)
    if n_devices > len(devs):
        raise ValueError(f"want {n_devices} devices, have {len(devs)}")
    if shape is None:
        shape = (n_devices,)
    key = (n_devices, axis_names, shape)
    with _lock:
        m = _meshes.get(key)
        if m is None:
            arr = np.array(devs[:n_devices]).reshape(shape)
            m = Mesh(arr, axis_names)
            _meshes[key] = m
        return m

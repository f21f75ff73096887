"""brpc_tpu.kvcache — paged KV cache over the ICI BlockPool.

Three layers (see README "KV cache"):

  * :class:`PagePool` (pages.py) — fixed-size, refcounted KV pages
    carved from leased HBM blocks (block<->page table, copy-on-write
    copies, idle blocks return to the BlockPool);
  * :class:`RadixTree` (radix.py) — longest-prefix reuse at page
    granularity with LRU-by-leaf eviction under pool pressure;
  * :class:`KVCacheStore` (store.py) — the engine-facing
    admit/extend/fork/retire lifecycle with hit-rate/occupancy bvars.

Every live store self-registers here (weakly, by name) so the
``/kvcache`` builtin-console page can render hit-rate, page occupancy,
radix-tree size, and eviction counters without holding stores alive.
"""
from __future__ import annotations

import threading
from brpc_tpu.butil.lockprof import InstrumentedLock
import weakref

_reg_mu = InstrumentedLock("kvcache.registry")
_stores: "weakref.WeakValueDictionary[str, object]" = \
    weakref.WeakValueDictionary()


def _register_store(s) -> None:
    with _reg_mu:
        _stores[s.name] = s


def kvcache_snapshot() -> dict:
    """Live stores' stats — the /kvcache console page's data."""
    with _reg_mu:
        stores = dict(_stores)
    return {"stores": {name: s.stats()
                       for name, s in sorted(stores.items())}}


from brpc_tpu.kvcache.pages import KVPage, PagePool  # noqa: E402,F401
from brpc_tpu.kvcache.layered import (  # noqa: E402,F401
    LayeredCache, LayeredSpec,
)
from brpc_tpu.kvcache.radix import RadixTree  # noqa: E402,F401
from brpc_tpu.kvcache.store import (  # noqa: E402,F401
    KVCacheStore, KVSeq, RecoveryPin,
)

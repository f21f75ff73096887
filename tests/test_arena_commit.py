"""``PagePool.arena()`` stacks committed buffers only (PERF.md section 7,
entry 1, owed since PR 31): the zero row, a block's initial buffer and
every spliced buffer are all committed to the pool's device, so one
stacking program serves every pattern of leased rows."""
import jax
import jax.monitoring
import numpy as np

from brpc_tpu.ici.block_pool import BlockPool
from brpc_tpu.kvcache.pages import PagePool


class _Compiles:
    def __init__(self):
        self.names = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(kw.get("fun_name", "?"))


_COMPILES = _Compiles()          # listeners cannot be removed: one, shared


def test_arena_compiles_once_whatever_is_leased_spliced_or_released():
    dev = jax.devices()[0]
    blocks = BlockPool(dev, classes=(4096,), blocks_per_class=6)
    pool = PagePool(blocks, dev, page_bytes=1024, page_tokens=16,
                    max_blocks=6, name="arena_commit")
    rows = np.arange(64, dtype=np.uint8)[None].repeat(3, axis=0)

    def look():
        a = pool.arena()
        assert a.shape == (6 * 4, 1024) and a.committed
        return a

    first = look()                      # nothing leased: all zero rows
    assert not np.asarray(first).any()
    # the first lease, looked at BEFORE its first splice (the block's
    # initial buffer), then one splice: every program compiles here
    p0 = pool.alloc_page()
    look()
    pool.write_slots(p0, 0, rows)
    look()
    start = len(_COMPILES.names)
    held = [p0]
    rng = np.random.default_rng(7)
    for round_ in range(12):
        if held and rng.random() < 0.4:
            pool.unref(held.pop(int(rng.integers(len(held)))))
        else:
            for _ in range(int(rng.integers(1, 6))):
                held.append(pool.alloc_page())
                look()                   # between a lease and its splice
                if rng.random() < 0.7:
                    pool.write_slots(held[-1], int(rng.integers(0, 13)),
                                     rows)
        arena = look()
        flat = pool.flat_ids([p.pid for p in held])
        assert all(f >= 0 for f in flat)
        if len(blocks._free[4096]) == 0:
            break
    after = _COMPILES.names[start:]
    assert after == [], f"arena() or a splice compiled again: {after}"
    got = np.asarray(arena)[pool.flat_ids([p0.pid])[0]] \
        if p0 in held else None
    if got is not None:
        assert (got[:64] == rows[0]).all()
    for p in held:
        pool.unref(p)
    assert pool.pages_in_use() == 0

"""CollectiveGroup semantics on the virtual 8-device mesh
(ici/collective.py — the XLA-collective lowering behind
ParallelChannel/PartitionChannel and the §5.8 communication backend).
Each primitive is checked against its numpy definition."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from brpc_tpu.ici.collective import CollectiveGroup
from brpc_tpu.ici.mesh import get_mesh


@pytest.fixture(scope="module")
def group():
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    return CollectiveGroup()


def test_parallel_apply_stack_and_sum(group):
    n = group.size
    x = jnp.arange(12.0)

    def double(v):
        return v * 2.0

    stacked = group.parallel_apply(double, x, merge="stack")
    # one replicated row a chip, each of fn(x)'s shape
    assert [r.shape for r in stacked] == [(12,)] * n
    assert all(r.sharding.is_fully_replicated for r in stacked)
    np.testing.assert_allclose(np.stack([np.asarray(r) for r in stacked]),
                               np.tile(np.arange(12.0) * 2, (n, 1)))
    summed = group.parallel_apply(double, x, merge="sum")
    np.testing.assert_allclose(np.asarray(summed), np.arange(12.0) * 2 * n)


def test_partition_apply_concat_matches_local(group):
    n = group.size
    x = jnp.arange(n * 4.0).reshape(n * 4)

    def inc(v):
        return v + 1.0

    out = group.partition_apply(inc, x, merge="concat")
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) + 1.0)
    summed = group.partition_apply(lambda v: jnp.sum(v, keepdims=True), x,
                                   merge="sum")
    np.testing.assert_allclose(np.asarray(summed), [np.asarray(x).sum()])


def test_ring_shift_permutes_shards(group):
    n = group.size
    x = jnp.arange(n * 2.0)          # shard i holds [2i, 2i+1]
    out = np.asarray(group.ring_shift(x, steps=1))
    expect = np.roll(np.asarray(x).reshape(n, 2), 1, axis=0).reshape(-1)
    np.testing.assert_allclose(out, expect)
    # a full ring of shifts restores the input
    y = x
    for _ in range(n):
        y = group.ring_shift(y, steps=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x))


def test_all_gather_all_reduce_reduce_scatter(group):
    n = group.size
    x = jnp.arange(n * 3.0)
    gathered = np.asarray(group.all_gather(x))
    np.testing.assert_allclose(gathered, np.asarray(x))  # tiled re-assembly
    reduced = np.asarray(group.all_reduce(x))
    # psum over shards: result replicated = sum of per-shard views is the
    # full vector summed across the axis groups — each position summed n?
    # in_specs P(axis): each chip holds a distinct shard; psum adds the
    # SHARDS elementwise, output replicated with shard shape
    shards = np.asarray(x).reshape(n, 3)
    np.testing.assert_allclose(reduced, shards.sum(axis=0))
    rs = np.asarray(group.reduce_scatter(jnp.ones((n * 2,))))
    # every chip contributed the full ones-vector; chip i keeps slice i of
    # the n-fold sum
    np.testing.assert_allclose(rs, np.full((n * 2,), float(n)))


def test_compiled_programs_are_cached(group):
    def f(v):
        return v * 3.0

    x = jnp.arange(8.0)
    group.parallel_apply(f, x)
    before = len(group._cache)
    group.parallel_apply(f, x)     # same fn object: no rebuild
    assert len(group._cache) == before


# ---- the fan-in of a stacked result, on the device side --------------------

def _by_chip(v):
    """Another result on every chip: the row order is the mesh's."""
    return v * 2 + jax.lax.axis_index("chip").astype(v.dtype)


@pytest.fixture
def host_reads(monkeypatch):
    """Counts the reads of a device array's value on the host (what
    ``ArrayImpl.__iter__`` did to a mesh-sharded result)."""
    from jax._src.array import ArrayImpl
    reads = []
    value = ArrayImpl._value

    def counted(self):
        reads.append(self.shape)
        return value.fget(self)
    monkeypatch.setattr(ArrayImpl, "_value", property(counted))
    return reads


def _fan_ins():
    from brpc_tpu.bvar import find_exposed
    v = find_exposed("ici_collective_fan_ins").get_value()
    return v["in_place"], v["moved"]


@pytest.mark.parametrize("chip", [0, 3])
def test_fan_in_hands_out_the_chips_own_replicas(group, host_reads, chip):
    dev = group.mesh.devices.flat[chip]
    out = group.parallel_apply(_by_chip, jnp.arange(64, dtype=jnp.uint32))
    before = _fan_ins()
    rows, moved = group.fan_in(out, dev)
    assert moved == 0 and len(rows) == group.size
    assert _fan_ins() == (before[0] + 1, before[1])
    if chip == 0:
        # the first chip's rows come without an array object for every
        # other chip's replica (each costs the caller a hand-off of the
        # interpreter lock when it is freed under load)
        assert not any("addressable_shards" in vars(o) for o in out)
    for r, o in zip(rows, out):
        assert r.committed and r.devices() == {dev} and r.shape == (64,)
        mine = next(s.data for s in o.addressable_shards if s.device == dev)
        # the program's own output buffer: nothing ran, nothing was copied
        assert r.unsafe_buffer_pointer() == mine.unsafe_buffer_pointer()
    assert len({r.unsafe_buffer_pointer() for r in rows}) == len(rows)
    assert host_reads == []
    for i, r in enumerate(rows):
        np.testing.assert_array_equal(np.asarray(r), np.arange(64) * 2 + i)


def _lowered(chips, merger=None):
    import brpc_tpu as brpc
    from brpc_tpu.ici import IciChannel, register_device_service
    register_device_service("FanIn", "ByChip", _by_chip)
    pc = brpc.ParallelChannel(response_merger=merger)
    for i in chips:
        pc.add_channel(IciChannel(f"ici://slice0/{i}"))
    return pc


# channels -> the chip the request sits on (None: a numpy request)
FAN_INS = {
    "caller_first_in_mesh": ((0, 1, 2, 3), 0),
    "caller_inside_mesh": ((0, 1, 2, 3), 2),
    "channels_out_of_order": ((3, 1, 2, 0), 1),
    "caller_outside_mesh": ((4, 5, 6, 7), 0),
    "numpy_request": ((0, 1, 2, 3), None),
    "numpy_request_outside_mesh": ((4, 5, 6, 7), None),
}


@pytest.mark.parametrize("case", sorted(FAN_INS))
def test_lowered_stack_rows_land_committed_on_the_callers_chip(
        host_reads, case):
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device mesh")
    chips, at = FAN_INS[case]
    x = np.arange(256, dtype=np.uint32) * 7
    caller = jax.devices()[at or 0]      # the default device is chip 0
    request = x if at is None else jax.device_put(x, caller)
    pc = _lowered(chips)
    before = _fan_ins()
    del host_reads[:]
    out = pc.call_sync("FanIn", "ByChip", request)
    assert host_reads == [], "the result was read on the host"
    outside = caller.id not in chips
    assert _fan_ins() == (before[0] + (not outside), before[1] + outside)
    assert isinstance(out, list) and len(out) == len(chips)
    for i, r in enumerate(out):
        assert isinstance(r, jax.Array) and r.committed
        assert r.devices() == {caller}
        assert r.shape == x.shape and r.dtype == x.dtype
        np.testing.assert_array_equal(np.asarray(r), x * 2 + i)
    assert len({r.unsafe_buffer_pointer() for r in out}) == len(out)


def test_a_custom_merger_is_handed_the_rows_and_sum_still_lowers_to_psum(
        host_reads):
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    import brpc_tpu as brpc
    from brpc_tpu.bvar import find_exposed
    from brpc_tpu.rpc.combo_channels import ResponseMerger
    seen = []

    class Keep(ResponseMerger):
        def merge(self, responses):
            seen.append(responses)
            return len(responses)

    x = jax.device_put(jnp.arange(32, dtype=jnp.uint32), jax.devices()[1])
    assert _lowered((0, 1, 2, 3), Keep()).call_sync(
        "FanIn", "ByChip", x) == 4
    (rows,) = seen
    assert isinstance(rows, list) and len(rows) == 4
    assert all(r.committed and r.devices() == {jax.devices()[1]}
               for r in rows)

    calls = find_exposed("ici_collective_calls")
    before, n0 = _fan_ins(), calls.get_value()
    del host_reads[:]
    out = _lowered((0, 1, 2, 3), brpc.SumMerger()).call_sync(
        "FanIn", "ByChip", x)
    # one program, its psum the response: no fan-in, nothing on the host
    assert calls.get_value() == n0 + 1 and _fan_ins() == before
    assert host_reads == []
    assert out.shape == (32,) and out.sharding.is_fully_replicated
    np.testing.assert_array_equal(np.asarray(out),
                                  np.arange(32) * 8 + 0 + 1 + 2 + 3)

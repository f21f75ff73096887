"""ICI transport tests on the virtual 8-device CPU mesh (SURVEY.md §4:
single-host multi-device plays the role 127.0.0.1 plays in the reference).
"""
import threading
import time
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

import brpc_tpu as brpc
from brpc_tpu.ici import (BlockPool, CollectiveGroup, IciChannel,
                          IciEndpoint, TensorStream, get_block_pool,
                          get_mesh, link_stats, register_device_service)


def test_virtual_mesh_has_8_devices():
    assert len(jax.devices()) == 8
    mesh = get_mesh()
    assert mesh.shape["chip"] == 8


class TestDeviceFor:
    """ici://<slice>/<chip> names a chip this process has, or nothing:
    an index past the device count used to wrap onto chip 0."""

    def test_index_in_range_is_that_device(self):
        from brpc_tpu.ici import device_for
        assert device_for(3) == jax.devices()[3]

    @pytest.mark.parametrize("index", [8, 11, -1])
    def test_index_out_of_range_raises(self, index):
        from brpc_tpu.ici import device_for
        with pytest.raises(ValueError, match="8 device"):
            device_for(index)

    def test_ici_channel_to_a_missing_chip_raises(self):
        with pytest.raises(ValueError, match="out of range"):
            IciChannel("ici://slice0/8")


class TestCompileCache:
    """One function places jax's persistent compile cache: nowhere when
    the environment already did, else a fixed directory in the checkout."""

    @pytest.fixture()
    def cache_config(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_variable_set_means_the_code_sets_nothing(self, monkeypatch,
                                                      cache_config):
        from brpc_tpu.ici import mesh
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(mesh.COMPILE_CACHE_ENV, "/somewhere/else")
        assert mesh.ensure_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir is None

    def test_variable_unset_means_the_fixed_checkout_path(
            self, monkeypatch, cache_config):
        import os

        from brpc_tpu.ici import mesh
        monkeypatch.delenv(mesh.COMPILE_CACHE_ENV, raising=False)
        jax.config.update("jax_compilation_cache_dir", None)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert mesh.ensure_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert mesh.ensure_compile_cache() == want      # idempotent
        assert mesh.REPO_COMPILE_CACHE_DIR == want

    def test_the_first_touches_of_jax_place_it(self, monkeypatch,
                                               cache_config):
        """A BlockPool (the rail and the KV cache), a PS shard and a
        runner each call it; none needs a caller to remember."""
        from brpc_tpu.ici import mesh
        from brpc_tpu.psserve import EmbeddingShardServer
        monkeypatch.delenv(mesh.COMPILE_CACHE_ENV, raising=False)
        for first_touch in (BlockPool,
                            lambda: EmbeddingShardServer(0, 1, 64, 8)):
            jax.config.update("jax_compilation_cache_dir", None)
            first_touch()
            assert jax.config.jax_compilation_cache_dir \
                == mesh.REPO_COMPILE_CACHE_DIR

    def test_the_cache_directory_is_git_ignored(self):
        import os
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()


class TestBlockPool:
    def test_alloc_classes_and_roundtrip(self):
        pool = get_block_pool()
        b = pool.alloc(5000)
        assert b.nbytes == 8 * 1024
        data = bytes(range(256)) * 16
        b.put(data)
        assert b.get() == data
        b.free()
        big = pool.alloc(100_000)
        assert big.nbytes == 2 * 1024 * 1024
        big.free()

    def test_a_pool_cut_to_its_pages_takes_what_the_rail_classes_cannot(
            self):
        """A full-width KV page (4 MiB) outgrows the largest rail class;
        its cache brings a pool whose one class is a page."""
        from brpc_tpu.models.runner import TransformerConfig, make_store_for
        with pytest.raises(MemoryError):
            get_block_pool().alloc(4 * 1024 * 1024)
        pool = BlockPool(classes=(4 * 1024 * 1024,), blocks_per_class=3)
        blocks = [pool.alloc(4 * 1024 * 1024) for _ in range(3)]
        assert pool.stats()["classes"] == {
            str(4 * 1024 * 1024): {"free": 0, "total": 3}}
        with pytest.raises(MemoryError):
            pool.alloc(1)
        for b in blocks:
            b.free()
        cfg = TransformerConfig(d_model=64, n_layers=16, n_heads=16,
                                n_kv_heads=16, head_dim=128, d_ff=64)
        store = make_store_for(cfg, page_tokens=16, max_blocks=2,
                               name="t_ici_bigpage")
        try:
            pp = store.pagepool
            assert pp.page_bytes == 4 * 1024 * 1024
            assert (pp.block_class, pp.pages_per_block) == (pp.page_bytes, 1)
            assert pp.arena().shape == (2, pp.page_bytes)
        finally:
            store.close()

    def test_exhaustion_and_stats(self):
        pool = BlockPool()
        blocks = [pool.alloc(1024) for _ in range(64)]
        # 8KB class is exhausted; next alloc takes the 64KB class
        nxt = pool.alloc(1024)
        assert nxt.nbytes == 64 * 1024
        st = pool.stats()
        assert st["classes"]["8192"]["free"] == 0
        for b in blocks:
            b.free()
        nxt.free()
        assert pool.stats()["classes"]["8192"]["free"] == 64


class TestEndpointAndStream:
    def test_send_between_devices(self):
        dev = jax.devices()[1]
        ep = IciEndpoint(dev)
        x = jnp.arange(1024, dtype=jnp.float32)
        y = ep.send_sync(x)
        assert y.devices() == {dev}
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        # window credit returns on the completion drainer, asynchronously
        # to send_sync; poll until it settles
        deadline = time.monotonic() + 5
        while ep.inflight_bytes > 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert ep.inflight_bytes == 0

    def test_window_backpressure(self):
        dev = jax.devices()[2]
        ep = IciEndpoint(dev, window_bytes=1024)
        with pytest.raises(TimeoutError):
            # single send larger than the whole window can never fit
            ep.send(jnp.zeros(4096, jnp.uint8), timeout_s=0.2)

    @pytest.mark.parametrize("n_streams", [1, 2])
    def test_tensor_stream_ordered(self, n_streams):
        """Each stream's consumer sees its writer's order, also with a
        second stream's writer dispatching at the same time."""
        got = [[] for _ in range(n_streams)]
        streams = [TensorStream(jax.devices()[3 + k],
                                consumer=lambda a, g=got[k]: g.append(int(a[0])))
                   for k in range(n_streams)]
        start = threading.Barrier(n_streams)

        def writer(ts):
            start.wait(10)
            for i in range(20):
                ts.write(jnp.full((256,), i, jnp.int32))

        threads = [threading.Thread(target=writer, args=(ts,))
                   for ts in streams]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        for ts in streams:
            ts.close(wait=True)
        assert got == [list(range(20))] * n_streams

    def test_link_stats_exported(self):
        st = link_stats()
        assert st["send_count"] > 0
        assert "send_overlapped" in st
        assert len(st["devices"]) == 8


def _settles(ep, timeout_s=10.0):
    """Window credit returns on the drainer, asynchronously to the sends."""
    deadline = time.monotonic() + timeout_s
    while ep.inflight_bytes and time.monotonic() < deadline:
        time.sleep(0.01)
    return ep.inflight_bytes == 0


class _FakeTransfer:
    """A completion entry's output: records who looked at it, and is in
    flight until `complete()`."""

    def __init__(self, looked, name, ready=True):
        self._looked, self._name = looked, name
        self._ready = threading.Event()
        if ready:
            self._ready.set()

    def is_ready(self):
        self._looked.append(("is_ready", self._name))
        return self._ready.is_set()

    def block_until_ready(self):
        self._looked.append(("block", self._name))
        assert self._ready.wait(10)

    def complete(self):
        self._ready.set()


class TestUnserialisedSend:
    """Senders of one endpoint dispatch side by side (no endpoint-wide
    lock across the runtime call), so the completion queue is in no
    order and the drainer confirms every entry by itself."""

    N_THREADS, N_SENDS, CHUNK, WINDOW = 8, 50, 128 * 1024, 1 << 20

    def test_two_senders_are_inside_the_dispatch_at_once(self, monkeypatch):
        from brpc_tpu.ici import endpoint as endpoint_mod
        dev = jax.devices()[0]
        ep = IciEndpoint(dev)
        real_copy = endpoint_mod._device_copy
        both_inside = threading.Barrier(2)

        def copy_when_both_are_in(x):
            both_inside.wait(10)    # BrokenBarrierError under a send lock
            return real_copy(x)

        monkeypatch.setattr(endpoint_mod, "_device_copy",
                            copy_when_both_are_in)
        xs = [jax.device_put(jnp.full((512,), i, jnp.int32), dev)
              for i in range(2)]
        outs, errors = [None, None], []

        def sender(i):
            try:
                outs[i] = ep.send(xs[i])
            except Exception as e:      # noqa: BLE001 - reported below
                errors.append(e)

        before = link_stats()["send_overlapped"]
        threads = [threading.Thread(target=sender, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        try:
            assert not errors, errors
            for i in range(2):
                np.testing.assert_array_equal(np.asarray(outs[i]),
                                              np.asarray(xs[i]))
            # the second to reserve its credit found the first inside
            assert link_stats()["send_overlapped"] >= before + 1
            assert not ep._dispatching
            assert _settles(ep)
        finally:
            ep.close()

    def _hammer(self, ep, dev):
        """8 threads x 50 sends of 128 KiB into a 1 MiB window: even
        threads copy on the endpoint's own device, odd ones move from
        another, every fifth send of a thread is a batch of two.
        Returns (per-thread outputs, errors, highest in-flight read)."""
        devs = jax.devices()
        srcs = [jax.device_put(jnp.full((self.CHUNK,), t + 1, jnp.uint8),
                               dev if t % 2 == 0 else devs[1 + t % 7])
                for t in range(self.N_THREADS)]
        outs = [[] for _ in range(self.N_THREADS)]
        errors = [[] for _ in range(self.N_THREADS)]
        peaks = [0] * (self.N_THREADS + 1)
        done = threading.Event()

        def sender(t):
            for i in range(self.N_SENDS):
                try:
                    if i % 5 == 4:
                        outs[t].extend(ep.send_batch([srcs[t]] * 2))
                    else:
                        outs[t].append(ep.send(srcs[t]))
                except RuntimeError as e:
                    errors[t].append(e)
                peaks[t] = max(peaks[t], ep.inflight_bytes)

        def watcher():
            while not done.is_set():
                peaks[-1] = max(peaks[-1], ep.inflight_bytes)

        threads = [threading.Thread(target=sender, args=(t,))
                   for t in range(self.N_THREADS)]
        w = threading.Thread(target=watcher)
        w.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        done.set()
        w.join(10)
        return srcs, outs, errors, max(peaks)

    def test_window_holds_under_eight_concurrent_senders(self):
        dev = jax.devices()[0]
        ep = IciEndpoint(dev, window_bytes=self.WINDOW)
        try:
            srcs, outs, errors, peak = self._hammer(ep, dev)
            assert not any(errors), errors
            assert 0 < peak <= self.WINDOW
            assert _settles(ep)
            assert not ep._dispatching
            for t, src in enumerate(srcs):
                assert len(outs[t]) == self.N_SENDS + self.N_SENDS // 5
                src_ptr = src.unsafe_buffer_pointer()
                ptrs = set()
                for o in outs[t]:
                    assert o.devices() == {dev}
                    assert np.all(np.asarray(o) == t + 1)
                    ptrs.add(o.unsafe_buffer_pointer())
                # every reply in a buffer of its own
                assert src_ptr not in ptrs and len(ptrs) == len(outs[t])
        finally:
            ep.close()

    def test_injected_send_faults_leave_no_credit_behind(self):
        from brpc_tpu import fault
        dev = jax.devices()[0]
        ep = IciEndpoint(dev, window_bytes=self.WINDOW)
        plan = fault.FaultPlan(26).on("ici.send", fault.ERROR, times=-1,
                                      prob=0.3)
        try:
            with fault.injected(plan):
                _, outs, errors, peak = self._hammer(ep, dev)
            n_failed = sum(len(e) for e in errors)
            assert n_failed == plan.injected["ici.send"] > 0
            assert any(outs)
            assert peak <= self.WINDOW
            assert _settles(ep), f"{ep.inflight_bytes}B of credit leaked"
            assert not ep._dispatching
        finally:
            ep.close()

    def test_drainer_confirms_every_entry_whatever_the_queue_order(self):
        ep = IciEndpoint(jax.devices()[0], window_bytes=1 << 20)
        looked = []
        # dispatch order a, b, c, d; c is still in flight, and d's entry
        # never reaches the queue (its sender is still inside the runtime)
        a, b = (_FakeTransfer(looked, n) for n in "ab")
        c = _FakeTransfer(looked, "c", ready=False)
        ep._inflight = 100 + 200 + 300 + 400
        now = time.monotonic()
        # queued b, c, a: under a tail-sync, a ready `a` at the tail
        # would have vouched for c
        ep._completions.put(((b,), 200, now))
        ep._completions.put(((c,), 300, now))
        ep._completions.put(((a,), 100, now))
        ep._ensure_drainer()
        try:
            deadline = time.monotonic() + 5
            while ("block", "c") not in looked and time.monotonic() < deadline:
                time.sleep(0.01)
            # every queued entry was looked at by itself ...
            assert {n for _, n in looked} == {"a", "b", "c"}
            # ... the drainer parks on the one in flight, and only what
            # it saw complete went back to the window meanwhile
            assert ("block", "c") in looked
            assert ("block", "a") not in looked
            assert ("block", "b") not in looked
            time.sleep(0.1)
            assert ep.inflight_bytes == 300 + 400
            c.complete()
            deadline = time.monotonic() + 5
            while ep.inflight_bytes != 400 and time.monotonic() < deadline:
                time.sleep(0.01)
            # d's credit is still held: nobody saw it complete
            assert ep.inflight_bytes == 400
        finally:
            ep._inflight = 0
            ep.close()

    def test_full_window_sender_confirms_ready_entries_itself(self):
        """No drainer running: a sender that finds the window full takes
        the credit of the completed entries and leaves the others (and
        their credit) where they were."""
        dev = jax.devices()[0]
        ep = IciEndpoint(dev, window_bytes=1024)
        looked = []
        done = _FakeTransfer(looked, "done")
        flying = _FakeTransfer(looked, "flying", ready=False)
        ep._inflight = 1024
        now = time.monotonic()
        ep._completions.put(((flying,), 512, now))
        ep._completions.put(((done,), 512, now))
        ep._drainer = threading.current_thread()    # keep the real one away
        try:
            x = jax.device_put(jnp.zeros(512, jnp.uint8), dev)
            y = ep.send(x, timeout_s=5)
            y.block_until_ready()
            assert ("is_ready", "done") in looked
            assert not any(kind == "block" for kind, _ in looked)
            # flying's 512 and the new send's 512
            assert ep._inflight == 1024
            assert ep._completions.qsize() == 2     # flying, and y's entry
            assert not ep._dispatching
        finally:
            ep._drainer = None
            ep.close()


class TestRealByteMovement:
    """VERDICT r1 #1: transfers must provably copy — distinct destination
    buffers, checksummed end-to-end, BlockPool as the staging allocator
    (ref: rdma_endpoint.h:82 + socket.cpp:1751-1757, block_pool.cpp:52)."""

    def test_same_device_send_is_a_real_copy(self):
        dev = jax.devices()[0]
        ep = IciEndpoint(dev)
        x = jax.device_put(jnp.arange(4096, dtype=jnp.float32), dev)
        y = ep.send_sync(x)
        # loopback must not alias: a distinct destination buffer proves
        # bytes moved through the memory system
        assert y.unsafe_buffer_pointer() != x.unsafe_buffer_pointer()
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        ep.close()

    def test_cross_device_send_lands_on_target(self):
        src, dst = jax.devices()[1], jax.devices()[6]
        ep = IciEndpoint(dst)
        x = jax.device_put(jnp.arange(2048, dtype=jnp.int32), src)
        y = ep.send_sync(x)
        assert y.devices() == {dst}
        assert y.unsafe_buffer_pointer() != x.unsafe_buffer_pointer()
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        ep.close()

    def test_byte_pipe_checksum_across_devices(self):
        import hashlib
        src_dev, dst_dev = jax.devices()[0], jax.devices()[5]
        data = np.random.default_rng(7).bytes(5 * 1024 * 1024 + 333)
        src_pool = get_block_pool(src_dev)
        before = src_pool.stats()["allocated"]
        ep = IciEndpoint(dst_dev)
        dst_blocks = ep.send_bytes(data, src_pool)
        # staging went through the source pool's HBM slots
        assert src_pool.stats()["allocated"] > before
        got = b"".join(b.get() for b in dst_blocks)
        assert hashlib.sha256(got).digest() == hashlib.sha256(data).digest()
        assert dst_blocks[0].view().devices() == {dst_dev}
        for b in dst_blocks:
            b.free()
        ep.close()

    def test_block_put_keeps_device_source_on_device(self):
        dev = jax.devices()[2]
        pool = get_block_pool(dev)
        t = jax.device_put(
            jnp.arange(512, dtype=jnp.float32).reshape(16, 32), dev)
        blk = pool.alloc(t.nbytes).put(t)
        assert blk.view().devices() == {dev}
        back = blk.get_array()
        assert back.dtype == t.dtype and back.shape == t.shape
        np.testing.assert_array_equal(np.asarray(back), np.asarray(t))
        blk.free()

    def test_send_blocks_moves_tensor_with_meta(self):
        src_dev, dst_dev = jax.devices()[0], jax.devices()[4]
        pool = get_block_pool(src_dev)
        t = jax.device_put(jnp.arange(100, dtype=jnp.int16), src_dev)
        blk = pool.alloc(t.nbytes).put(t)
        ep = IciEndpoint(dst_dev)
        moved = ep.send_blocks([blk])
        out = moved[0].get_array()
        assert out.devices() == {dst_dev}
        np.testing.assert_array_equal(np.asarray(out), np.asarray(t))
        blk.free()
        moved[0].free()
        ep.close()

    def test_stream_write_bytes_checksum(self):
        import hashlib
        dst_dev = jax.devices()[7]
        chunks = []
        ts = TensorStream(dst_dev, consumer=lambda blk: chunks.append(blk))
        data = np.random.default_rng(11).bytes(3 * 1024 * 1024 + 99)
        ts.write_bytes(data, src_pool=get_block_pool(jax.devices()[0]))
        ts.close(wait=True)
        got = b"".join(b.get() for b in chunks)
        assert hashlib.sha256(got).digest() == hashlib.sha256(data).digest()
        for b in chunks:
            assert b.view().devices() == {dst_dev}
            b.free()


class TestCollective:
    def test_parallel_apply_stack_and_sum(self):
        g = CollectiveGroup()
        x = jnp.ones((4, 8), jnp.float32)
        stacked = g.parallel_apply(lambda t: t * 2, x, merge="stack")
        assert [r.shape for r in stacked] == [(4, 8)] * 8
        np.testing.assert_allclose(np.stack(stacked), 2.0)
        summed = g.parallel_apply(lambda t: t * 2, x, merge="sum")
        assert summed.shape == (4, 8)
        np.testing.assert_allclose(np.asarray(summed), 16.0)  # 8 chips × 2

    def test_partition_apply(self):
        g = CollectiveGroup()
        x = jnp.arange(16, dtype=jnp.float32).reshape(16, 1)
        out = g.partition_apply(lambda s: s + 100, x, merge="concat")
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) + 100)

    def test_ring_shift(self):
        g = CollectiveGroup()
        x = jnp.arange(8, dtype=jnp.int32)          # one element per chip
        y = g.ring_shift(x, steps=1)
        np.testing.assert_array_equal(np.asarray(y), np.roll(np.arange(8), 1))

    def test_all_gather_reduce_scatter(self):
        g = CollectiveGroup()
        x = jnp.arange(8, dtype=jnp.float32)
        gathered = g.all_gather(x)
        assert gathered.shape == (8,)
        red = g.all_reduce(x)
        # psum over 1-element shards: replicated result, per-shard shape
        np.testing.assert_allclose(np.asarray(red), [28.0])
        rs = g.reduce_scatter(x)
        # every chip contributed the same x: chip i holds 8*x[i]
        np.testing.assert_allclose(np.asarray(rs),
                                   8 * np.arange(8, dtype=np.float32))


class TestIciChannel:
    def test_device_service_call(self):
        register_device_service("MatSvc", "Double", lambda x: x * 2)
        ch = IciChannel("ici://slice0/3")
        x = jnp.arange(64, dtype=jnp.float32)
        y = ch.call_sync("MatSvc", "Double", x)
        assert y.devices() == {jax.devices()[3]}
        np.testing.assert_allclose(np.asarray(y), np.asarray(x) * 2)

    def test_unknown_service(self):
        ch = IciChannel("ici://slice0/0")
        with pytest.raises(brpc.RpcError) as ei:
            ch.call_sync("None", "None", jnp.zeros(4))
        assert ei.value.code == brpc.errors.ENOMETHOD

    def test_parallel_channel_lowering(self):
        register_device_service("MatSvc", "Square", lambda x: x * x)
        pc = brpc.ParallelChannel(response_merger=brpc.SumMerger())
        for i in range(8):
            pc.add_channel(IciChannel(f"ici://slice0/{i}"))
        x = jnp.full((4,), 3.0, jnp.float32)
        out = pc.call_sync("MatSvc", "Square", x)
        # 8 chips × 9.0 summed via psum
        np.testing.assert_allclose(np.asarray(out), 72.0)

    def test_parallel_channel_lowering_stack(self):
        register_device_service("MatSvc", "Inc", lambda x: x + 1)
        pc = brpc.ParallelChannel()
        for i in range(8):
            pc.add_channel(IciChannel(f"ici://slice0/{i}"))
        out = pc.call_sync("MatSvc", "Inc", jnp.zeros((2,), jnp.float32))
        assert len(out) == 8
        np.testing.assert_allclose(np.asarray(out[0]), 1.0)


class TestBatchedTransfer:
    """send_batch: k chunks through ONE pre-compiled multi-copy program
    (VERDICT r2 task 2 — amortize per-chunk dispatch)."""

    def test_send_batch_same_device_real_copies(self):
        import jax.numpy as jnp
        from brpc_tpu.ici import IciEndpoint
        dev = jax.devices()[0]
        ep = IciEndpoint(dev)
        xs = [jax.device_put(jnp.full((256,), float(i), jnp.float32), dev)
              for i in range(6)]
        outs = ep.send_batch(xs)
        try:
            for x, o in zip(xs, outs):
                o.block_until_ready()
                assert o.devices() == {dev}
                assert bool(jnp.array_equal(o, x))
                # distinct destination buffer — the copy really moved bytes
                assert (o.unsafe_buffer_pointer()
                        != x.unsafe_buffer_pointer())
        finally:
            ep.close()

    def test_send_batch_mixed_devices(self):
        import jax.numpy as jnp
        from brpc_tpu.ici import IciEndpoint
        devs = jax.devices()
        target = devs[2]
        ep = IciEndpoint(target)
        xs = [jax.device_put(jnp.full((64,), float(i), jnp.float32),
                             devs[i % 4]) for i in range(8)]
        outs = ep.send_batch(xs)
        try:
            for x, o in zip(xs, outs):
                o.block_until_ready()
                assert o.devices() == {target}
                np.testing.assert_array_equal(np.asarray(o), np.asarray(x))
        finally:
            ep.close()

    def test_send_batch_window_accounting(self):
        import jax.numpy as jnp
        from brpc_tpu.ici import IciEndpoint
        dev = jax.devices()[0]
        ep = IciEndpoint(dev, window_bytes=1 << 20)
        x = jnp.ones((1024,), jnp.uint8)
        outs = ep.send_batch([x] * 16)
        outs[-1].block_until_ready()
        deadline = time.monotonic() + 5
        while ep.inflight_bytes and time.monotonic() < deadline:
            time.sleep(0.01)
        assert ep.inflight_bytes == 0
        with pytest.raises(ValueError):
            ep.send_batch([jnp.ones((1 << 19,), jnp.uint8)] * 3)
        ep.close()

    def test_write_many_preserves_order(self):
        import jax.numpy as jnp
        from brpc_tpu.ici import TensorStream
        dev = jax.devices()[1]
        got = []
        done = threading.Event()
        def consume(a):
            got.append(int(a[0]))
            if len(got) == 12:
                done.set()
        ts = TensorStream(dev, consumer=consume)
        ts.write_many([jnp.full((16,), float(i), jnp.float32)
                       for i in range(8)])
        ts.write_many([jnp.full((16,), float(i), jnp.float32)
                       for i in range(8, 12)])
        assert done.wait(20)
        ts.close()
        assert got == list(range(12))

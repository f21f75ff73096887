"""The parameter-server deployment of the benchmark (`ps_embed_1chip`
under `ps_ycsb_b`) at a size a test run can hold: vocab 4,096, dim 250,
4 callers, on the CPU.

The served path (``PSClient`` -> ``PS.LookupT`` / ``PS.UpdateT`` ->
``EmbeddingShardServer``) is driven by the cell's own generator
(scrambled-Zipfian keys with duplicates, one update a block re-sent
with its token) and held to the plain reference
(``benchmarks/harness/reference_ps.py``) exactly as a chip run is: the
configuration's guarantees, on what the window returned.  Each control
breaks the timed path and must be caught; set-up's explicit warm entry
must leave nothing to compile.  What is checked is answers and counts,
never a time.
"""
import io
import json
import threading
import time

import jax
import numpy as np
import pytest

import brpc_tpu as brpc
from brpc_tpu import psserve, rpcz
from brpc_tpu.rpc.combo_channels import PartitionChannel
from brpc_tpu.train.optimizer import OptimizerSpec, oracle_apply, zero_slots
from benchmarks import run as runmod
from benchmarks.drivers import psserve_traffic as keyed
from benchmarks.harness import loader, reference_ps as ref

TOY = {"vocab": 4096}
TOY_TRAFFIC = {"callers": 4}
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
         "ici_bytes_per_s": 200e9}
ADAM = OptimizerSpec("adam", lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8)


def toy_cell():
    cell = loader.load_cell("ps_ycsb_b")
    cell.config.update(TOY)
    cell.traffic.update(TOY_TRAFFIC)
    return cell


def run_toy(monkeypatch, control=None, trace=False, seed=2**31 + 27):
    """Everything of a benchmark run but the look for a chip."""
    monkeypatch.setattr(runmod, "setup_compile_cache", lambda: "(off)")
    out = io.StringIO()
    rc = runmod.run_cell(toy_cell(), seed=seed, seconds=1.5, trace=trace,
                         devices=jax.devices()[:1], peaks=PEAKS,
                         t_start=time.monotonic(), control=control,
                         stdout=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def over(result):
    return {n for n, c in result["compared"].items()
            if c["value"] > c["limit"]}


# ---- the reference on its own ----------------------------------------------

def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        src = f.read()
    assert "brpc_tpu" not in src.replace("imports nothing of the", "")
    assert "import jax" not in src


def test_any_row_is_made_again_without_the_table():
    whole = ref.table_rows(1234, np.arange(64), 250)
    assert whole.dtype == np.float32 and whole.shape == (64, 250)
    assert np.array_equal(ref.table_rows(1234, [63, 5, 5], 250),
                          whole[[63, 5, 5]])
    assert not np.array_equal(ref.table_rows(1235, [5], 250), whole[[5]])
    assert -0.05 <= whole.min() and whole.max() < 0.05
    # rows carry float32 detail that bfloat16 cannot hold
    assert np.abs(ref.to_bfloat16(whole) - whole).max() > 5e-6


def test_duplicate_keys_are_summed_before_one_step_a_row():
    g = ref.gradient_pool(7, 8, 250, 0.01)
    dup = ref.AdamTable(7, 250, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8)
    dup.apply([3, 9, 3], g[:3])
    one = ref.AdamTable(7, 250, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8)
    one.apply([3, 9], np.stack([g[0] + g[2], g[1]]))
    assert np.array_equal(dup.rows([3, 9, 4]), one.rows([3, 9, 4]))
    assert sorted(dup.touched_keys()) == [3, 9] and dup.version == 1
    # a first Adam step moves every value by lr, against its gradient
    moved = dup.rows([9]) - ref.table_rows(7, [9], 250)
    assert np.allclose(moved, -1e-3 * np.sign(g[1]), atol=1e-6)
    assert np.array_equal(dup.rows([4]), ref.table_rows(7, [4], 250))


def test_the_reference_agrees_with_the_programs_dense_oracle():
    """Two independent writings of the same update semantics: the
    plain numpy reference and ``train/optimizer.oracle_apply``."""
    rng = np.random.default_rng(5)
    table = ref.table_rows(99, np.arange(512), 250)
    slots = zero_slots(ADAM, 512, 250)
    mine = ref.AdamTable(99, 250, lr=1e-3, beta1=0.9, beta2=0.999,
                         eps=1e-8)
    pool = ref.gradient_pool(99, 256, 250, 0.01)
    for _ in range(6):
        keys = rng.integers(0, 40, 24)          # many duplicates
        g = pool[rng.integers(0, 200):][:24]
        table, slots = oracle_apply(table, slots, keys, g, ADAM)
        mine.apply(keys, g)
    assert np.abs(mine.rows(np.arange(512)) - table).max() < 5e-7


# ---- the cell's generator --------------------------------------------------

def test_the_block_is_304_lookups_and_16_updates_one_of_them_resent():
    traffic = loader.load_cell("ps_ycsb_b").traffic
    blk = keyed.block(traffic)
    assert len(blk) == 320
    assert sum(1 for k, _n, _r in blk if k == "update") == 16
    assert sum(1 for _k, _n, r in blk if r) == 1
    assert all(r is False or k == "update" for k, _n, r in blk)
    assert all(8 <= n <= 512 for _k, n, _r in blk)
    assert blk == keyed.block(traffic)          # fixed by length_seed


def test_every_seed_and_caller_sends_a_permutation_of_the_block():
    traffic = loader.load_cell("ps_ycsb_b").traffic
    zipf = keyed.Zipfian(4096, 0.99)
    mixes = []
    for seed, caller in ((1, 0), (1, 1), (2**31 + 9, 0)):
        plan = keyed.CallerPlan(traffic, seed, caller, zipf, 4096, 640,
                                1 << 16)
        calls = [plan.call(i) for i in range(320)]
        mixes.append(sorted((k, len(keys), r) for k, keys, r, _g, _f
                            in calls))
        assert all(0 <= keys.min() and keys.max() < 4096
                   for _k, keys, _r, _g, _f in calls)
    assert mixes[0] == mixes[1] == mixes[2]
    assert mixes[0] == sorted(keyed.block(traffic))


def test_scrambled_zipfian_keys_are_skewed_hashed_and_repeat_in_a_call():
    zipf = keyed.Zipfian(2_097_152, 0.99)
    keys = keyed.scrambled_zipfian_keys(zipf, 200_000,
                                        np.random.default_rng(3))
    values, counts = np.unique(keys, return_counts=True)
    hottest = values[np.argmax(counts)]
    # rank 0 is drawn 1 / zeta(n, 0.99) of the time (~6 %) ...
    assert abs(counts.max() / keys.size - 1.0 / zipf.zetan) < 0.005
    # ... and sits where FNV-1a 64 of the rank puts it, not at key 0
    assert hottest == keyed.fnv1a64(np.asarray([0]))[0] % zipf.n != 0
    # so a 512-key call repeats it: duplicates are the rule
    assert np.count_nonzero(keys[:512] == hottest) > 10


def test_fnv1a64_is_the_published_function():
    # FNV-1a 64 of eight zero octets, by hand: offset basis times the
    # prime eight times (xor with 0 changes nothing), then |signed|
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = (h * 1099511628211) % 2**64
    signed = h - 2**64 if h >= 2**63 else h
    assert keyed.fnv1a64(np.asarray([0]))[0] == abs(signed)


# ---- the served path against the reference ---------------------------------

def test_the_served_path_agrees_with_the_reference(monkeypatch):
    r = run_toy(monkeypatch)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 50 and r["failed"] == 0
    c = r["compared"]
    assert c["rows_gap_max"]["value"] <= c["rows_gap_max"]["limit"] == 2e-5
    for name in ("rows_off_snapshot", "final_rows_off", "stale_reads",
                 "updates_lost_or_doubled", "replays_not_deduped",
                 "dup_counter_off", "compiles_in_window", "failed_calls"):
        assert c[name] == {"value": 0, "limit": 0}, name
    assert {"goodput_gbps", "call_p95_ms", "setup_s"} <= set(r["metrics"])


@pytest.mark.parametrize("control,numbers", [
    ("lost_update", {"updates_lost_or_doubled", "rows_off_snapshot"}),
    ("double_apply", {"updates_lost_or_doubled", "replays_not_deduped",
                      "dup_counter_off"}),
    ("stale_read", {"stale_reads", "final_rows_off"}),
    ("low_precision", {"rows_gap_max", "rows_off_snapshot",
                       "final_rows_off"}),
])
def test_a_broken_guarantee_is_caught(monkeypatch, control, numbers):
    r = run_toy(monkeypatch, control=control)
    assert r["correct"] is False
    assert numbers <= over(r), (control, over(r))


def test_a_traced_run_reports_the_cells_per_layer_metrics(monkeypatch):
    r = run_toy(monkeypatch, trace=True)
    assert r["correct"] is True, r["compared"]
    # on the CPU the shard gathers in numpy, so the two metrics that
    # read the gather's device program and pull are silent here
    assert {"ps.client_self_us_per_call", "ps.server_self_us_per_call",
            "ps.shard_lock_wait_share", "ps.keys_per_program",
            "ps.apply_roofline", "device.idle_share.ps",
            "ps.note_hot_us_per_lookup"} \
        <= set(r["metrics"])
    assert 0 < r["metrics"]["ps.apply_roofline"]["value"] < 100


def test_the_hot_key_reader_finds_nothing_in_an_untraced_run():
    """``ps.note_hot_us_per_lookup`` (PR 30) on a run with no trace, or
    of a program without the stage: None, never a raise."""
    run = {"traced": None, "records": {"calls": []}, "counters0": {},
           "counters1": {}, "config": {"dim": 250}, "peaks": PEAKS}
    metric = loader.load_metric("ps.note_hot_us_per_lookup")
    assert metric.compute(run) is None


# ---- the program's side: warm entry, versions, stages, names ---------------

@pytest.fixture(scope="module")
def compiles():
    return runmod.CompileCounter()      # the benchmark's own count


@pytest.fixture
def served():
    """One warm shard behind a server, on the device path (the numpy
    short cut of a CPU shard is switched off, as on a chip)."""
    table = ref.table_rows(11, np.arange(2048), 250)
    shard = psserve.EmbeddingShardServer(0, 1, 2048, 250, table=table)
    shard._cpu_fast = False
    server = brpc.Server()
    svc = psserve.register_psserve(server, shard)
    server.start("127.0.0.1", 0)
    svc.warm(ADAM)
    pc = PartitionChannel(1)
    pc.add_partition(0, brpc.Channel(f"127.0.0.1:{server.port}",
                                     timeout_ms=30_000))
    client = psserve.PSClient(pc, vocab=2048, dim=250, max_retry=0)
    try:
        yield shard, svc, client, table
    finally:
        client.close()
        psserve.unregister_psserve(svc)
        server.stop()
        server.join()


@pytest.mark.parametrize("bucket", psserve.shard.DEFAULT_KEY_BUCKETS)
def test_warm_leaves_no_compile_for_the_window(served, compiles, bucket):
    shard, svc, client, _table = served
    keys = np.arange(bucket, dtype=np.int64) % 97
    grads = np.full((bucket, 250), 0.01, np.float32)
    before = compiles.count
    client.lookup(keys)                               # alone ...
    for size in svc._lookup_b.batch_buckets:          # ... and batched
        shard.lookup_batch_fn(np.zeros((size, bucket), np.int64))
    client.update(keys, grads, optimizer=ADAM)
    threads = [threading.Thread(target=client.lookup, args=(keys,))
               for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert compiles.count == before


def test_warm_changes_neither_rows_nor_slots_nor_version(served):
    shard, _svc, _client, table = served
    assert shard.version == 0
    assert np.array_equal(shard.snapshot_rows(), table)
    slots = shard.snapshot_slots()
    assert sorted(slots) == ["m", "t", "v"]
    assert all(not s.any() for s in slots.values())


def test_a_lookup_says_which_version_its_rows_show(served):
    shard, _svc, client, table = served
    keys = np.asarray([5, 9, 5, 1000], np.int64)
    rows, versions = client.lookup_versioned(keys)
    assert versions == {0: 0} and np.array_equal(rows, table[keys])
    acks = client.update(keys, np.ones((4, 250), np.float32),
                         update_token=77, optimizer=ADAM)
    assert acks == {0: 1}
    # the replay the client's docstring describes: same token, same ack
    assert client.update(keys, np.ones((4, 250), np.float32),
                         update_token=77, optimizer=ADAM) == {0: 1}
    assert shard.n_dup_updates == 1 and shard.version == 1
    rows, versions = client.lookup_versioned(keys)
    assert versions == {0: 1}
    assert np.array_equal(rows, client.lookup(keys))
    mine = ref.AdamTable(11, 250, lr=1e-3, beta1=0.9, beta2=0.999,
                         eps=1e-8)
    mine.apply(keys, np.ones((4, 250), np.float32))
    assert np.abs(rows - mine.rows(keys)).max() < 5e-7


def test_the_batched_path_reports_the_version_its_gather_ran_at(served):
    shard, _svc, _client, _table = served
    shard.update_opt(np.asarray([1]), np.ones((1, 250), np.float32), ADAM)
    shard.lookup_batch_fn(np.zeros((2, 8), np.int64))
    shard.update_opt(np.asarray([2]), np.ones((1, 250), np.float32), ADAM)
    # the shard has moved on; the batch's rows show version 1
    assert shard.version == 2 and shard.gathered_version() == 1


def test_the_stages_exist_only_while_something_listens(served):
    _shard, _svc, client, _table = served
    assert rpcz.stage("ps.client.call") is rpcz.NOOP_STAGE
    keys = np.arange(40, dtype=np.int64)
    want = {"ps.server.lookup", "ps.server.update", "ps.shard.lock_wait",
            "ps.shard.gather", "ps.shard.fetch", "ps.shard.apply",
            "ps.shard.note_hot"}
    rpcz.set_enabled(True)
    try:
        client.lookup(keys)
        client.update(keys, np.ones((40, 250), np.float32), optimizer=ADAM)
        # the server submits its span after the reply has left: poll
        deadline = time.monotonic() + 10.0
        while True:
            phases = {p[0] for s in rpcz.recent_spans(64)
                      for p in getattr(s, "phases", ())}
            if want <= phases or time.monotonic() > deadline:
                break
            time.sleep(0.02)
    finally:
        rpcz.set_enabled(False)
    assert want <= phases


def test_the_device_programs_carry_names_of_their_own():
    import jax.numpy as jnp
    from brpc_tpu.train.optimizer import fused_apply
    shard = psserve.EmbeddingShardServer(
        0, 1, 64, 8, table=np.zeros((64, 8), np.float32))
    rows = jnp.zeros((64, 8))
    k = np.zeros((8,), np.int64)
    g = np.zeros((8, 8), np.float32)
    valid = np.zeros((8,), np.float32)
    lowered = {
        "jit_ps_gather": shard._gather.lower(rows, k),
        "jit_ps_scatter": shard._scatter.lower(rows, k, g),
        "jit_ps_adam_apply": fused_apply("adam").lower(
            rows, rows, rows, jnp.zeros((64,)), k, g, valid,
            1e-3, 0.9, 0.999, 1e-8),
        "jit_ps_sgdm_apply": fused_apply("sgdm").lower(
            rows, rows, k, g, valid, 0.1, 0.9),
    }
    for name, low in lowered.items():
        assert f"module @{name} " in low.as_text(), name


def test_the_cells_metrics_are_declared_for_the_cell_alone():
    bench = loader.load_benchmark()
    mine = {m["name"]: m for m in bench["per_layer"]
            if m["name"].startswith("ps.")
            or m["name"] == "device.idle_share.ps"}
    assert set(mine) == {
        "ps.client_self_us_per_call", "ps.server_self_us_per_call",
        "ps.shard_lock_wait_share", "ps.fetch_us_per_lookup",
        "ps.keys_per_program", "ps.gather_roofline", "ps.apply_roofline",
        "device.idle_share.ps", "ps.note_hot_us_per_lookup"}
    assert all(m["workloads"] == ["ps_ycsb_b"] for m in mine.values())
    cell = loader.load_cell("ps_ycsb_b")
    assert {m["name"] for m in cell.end_to_end} == {
        "goodput_gbps", "call_p95_ms", "setup_s"}
    assert cell.config["guarantees"].keys() == {
        "exactly_once", "ordered", "snapshot", "precision"}

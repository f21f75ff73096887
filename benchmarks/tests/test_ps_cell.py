"""The parameter-server cell's files: the configuration states its
source, sizes and guarantees, the traffic file is the mix as issued, the
work counts follow from shapes, the readers of the ``ps.*`` metrics read
nothing (and raise nothing) where there is nothing to read, and the cell
runs at toy size (``data/toy_ps.json``) against its plain reference."""
import io
import json
import os
import time

import pytest

from benchmarks.harness import loader, spans_ps, work_ps
from benchmarks.tests.conftest import DATA, TOY_PEAKS

PS_METRICS = ("ps.client_self_us_per_call", "ps.server_self_us_per_call",
              "ps.shard_lock_wait_share", "ps.fetch_us_per_lookup",
              "ps.keys_per_program", "ps.gather_roofline",
              "ps.apply_roofline", "device.idle_share.ps")


def test_the_configuration_states_its_source_sizes_and_guarantees():
    bench = loader.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "ps_embed_1chip")
    cfg = loader.load_cell("ps_ycsb_b").config
    assert cfg["source"] == entry["source"] and "YCSB core workload B" \
        in cfg["source"]
    assert entry["reduced"] == cfg["reduced"] == []
    assert cfg["driver"] == "psserve" and cfg["chips"] == 1
    # the source's record: 10 fields x 100 B = 250 float32
    assert cfg["dim"] * 4 == cfg["record_bytes"] == 1000
    assert (cfg["read_proportion"], cfg["update_proportion"]) == (0.95, 0.05)
    assert cfg["zipfian_constant"] == 0.99
    # rows + both Adam slots fill over a quarter of a 16 GB chip, and a
    # table twice the size would not fit beside fused_apply's temporary
    state = 3 * cfg["vocab"] * cfg["dim"] * 4
    assert 0.25 * 16e9 < state < 0.5 * 16e9
    assert 2 * state + 2 * cfg["vocab"] * cfg["dim"] * 4 > 16e9
    assert set(cfg["guarantees"]) == {"exactly_once", "ordered", "snapshot",
                                      "precision"}
    for key in ("vocab", "optimizer", "keys_per_call", "callers",
                "key_buckets", "dtype", "row_tolerance"):
        assert key in cfg["assumed"], key


def test_the_traffic_file_is_the_mix_as_issued():
    cell = loader.load_cell("ps_ycsb_b")
    t = cell.traffic
    assert (t["generator"], t["callers"]) == ("closed_loop_keyed", 16)
    assert (t["block_calls"], t["block_updates"],
            t["resends_per_block"]) == (320, 16, 1)
    assert t["keys_per_call"] == {"median": 64, "sigma": 1.0, "min": 8,
                                  "max": 512}
    assert isinstance(t["length_seed"], int)
    assert cell.chips == 1 and len(cell.why) <= 200


def test_the_cell_reports_its_own_metrics_and_the_shared_ones_that_read():
    cell = loader.load_cell("ps_ycsb_b")
    names = {m["name"] for m in cell.per_layer}
    assert set(PS_METRICS) <= names
    # no rail, stream or collective runs in this cell: their metrics
    # (and the per-echo ones, which divide by echoes) are left alone
    assert not {n for n in names
                if n.startswith(("rail.", "stream.", "collective.",
                                 "combo.", "rpc."))}
    for m in cell.per_layer:
        assert callable(loader.load_metric(m["name"]).compute)


def test_work_counts_follow_from_the_shapes_alone():
    assert work_ps.gather_bytes(512, 250) == 2 * 512 * 1000
    # 512 gradients over 100 distinct rows: the gradients read; row, m,
    # v (1,000 B each) and t (4 B) of each distinct row read and written
    assert work_ps.adam_apply_bytes(512, 100, 250) \
        == 512 * 1000 + 2 * 100 * (3 * 1000 + 4)
    assert work_ps.least_seconds(819e9, {"hbm_bytes_per_s": 819e9}) == 1.0


def test_a_trace_without_ps_spans_reads_as_nothing():
    path = os.path.join(DATA, "small.xplane.pb")
    assert spans_ps.reduce(path, 0.0, 1.0) is None


@pytest.mark.parametrize("metric", PS_METRICS)
def test_a_reader_finds_nothing_in_an_untraced_run_and_does_not_raise(
        metric):
    run = {"traced": None, "records": {"calls": []}, "counters0": {},
           "counters1": {}, "config": {"dim": 250}, "peaks": TOY_PEAKS}
    assert loader.load_metric(metric).compute(run) is None


def test_the_cell_runs_at_toy_size_against_its_reference(monkeypatch):
    import jax
    from benchmarks import run as runmod
    monkeypatch.setattr(runmod, "setup_compile_cache", lambda: "(off)")
    with open(os.path.join(DATA, "toy_ps.json")) as f:
        toy = json.load(f)
    cell = loader.load_cell("ps_ycsb_b")
    cell.config.update(toy[cell.config_name])
    cell.traffic.update(toy[cell.traffic_name])
    out = io.StringIO()
    assert runmod.run_cell(cell, seed=2**31 + 5, seconds=1.5, trace=True,
                           devices=jax.devices()[:1], peaks=TOY_PEAKS,
                           t_start=time.monotonic(), stdout=out) == 0
    r = json.loads(out.getvalue().strip().splitlines()[-1])
    assert r["correct"] is True, r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert "ps.server_self_us_per_call" in r["metrics"]

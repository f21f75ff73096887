"""``sala_doc_turns`` at toy size on the CPU: the cell decides ``correct``
against the plain reference on arbitrary seeds and reads ``false`` under
both controls; its readers read a toy trace; ``work_sala`` counts what
hand counts count."""
import io
import json
import os
import time

import pytest

from benchmarks.harness import loader, work_sala

from .conftest import DATA, TOY_PEAKS

CELL = "sala_doc_turns"


@pytest.fixture
def run_sala(monkeypatch):
    with open(os.path.join(DATA, "toy_sala.json")) as f:
        toy = json.load(f)

    def run(control=None, trace=False, seconds=1.5, seed=2**31 + 77):
        import jax
        from benchmarks import run as runmod
        monkeypatch.setattr(runmod, "setup_compile_cache", lambda: "(off)")
        cell = loader.load_cell(CELL)
        cell.config.update(toy[cell.config_name])
        cell.traffic.update(toy[cell.traffic_name])
        out = io.StringIO()
        rc = runmod.run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                             devices=jax.devices()[:1], peaks=TOY_PEAKS,
                             t_start=time.monotonic(), control=control,
                             stdout=out)
        assert rc == 0
        lines = out.getvalue().strip().splitlines()
        assert len(lines) == 1
        return json.loads(lines[0])
    return run


def _over(r):
    return {n for n, c in r["compared"].items() if c["value"] > c["limit"]}


@pytest.mark.parametrize("seed", [3, 2**31 + 77, 4_300_000_011])
def test_the_cell_is_correct_on_arbitrary_seeds(run_sala, seed):
    r = run_sala(seed=seed)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert set(r["metrics"]) == {"call_p95_ms", "setup_s"}
    assert r["compared"]["dense_positions_in_window"]["value"] == 0
    assert r["compared"]["requests_not_compared"]["value"] == 0


@pytest.mark.parametrize("control,number", [
    ("low_precision", "served_logprob_abs_err_max"),
    ("altered_token", "served_logit_gap_max")])
def test_both_controls_read_false(run_sala, control, number):
    r = run_sala(control=control)
    assert r["correct"] is False
    assert number in _over(r)
    assert r["failed"] == 0


def test_a_traced_run_reports_every_per_layer_metric_it_can(run_sala):
    """On the CPU there is no Pallas custom call in the trace, so the two
    kernels' rooflines find nothing to read and are left out (never 0);
    every other reader reads."""
    r = run_sala(trace=True, seconds=2.5)
    cell = loader.load_cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    silent = {"sala.sparse_attend_roofline", "sala.lightning_state_roofline"}
    assert names - silent <= set(r["metrics"]), \
        (names - silent) - set(r["metrics"])
    assert r["metrics"]["sala.prefix_hit_share"]["value"] > 80.0
    assert 0 < r["metrics"]["sala.selected_blocks_per_position"]["value"] <= 4
    assert r["metrics"]["sala.step_ms"]["value"] > 0
    assert r["metrics"]["sala.state_restore_ms_per_request"]["value"] > 0
    assert "busy_s" in r["device"]


def test_readers_return_nothing_on_a_program_without_the_stages():
    """What the parent's traced runs need: a run with no trace, no
    counters and no records reads None everywhere and raises nowhere."""
    cell = loader.load_cell(CELL)
    run = {"cell": cell, "config": cell.config, "traffic": cell.traffic,
           "records": {"calls": [], "streams": []}, "counters0": {},
           "counters1": {}, "peaks": TOY_PEAKS, "t0": 0.0, "t1": 1.0,
           "traced": {"t0": 0.0, "t1": 1.0, "window_s": 1.0,
                      "counters0": {}, "counters1": {},
                      "trace": {"ops": {}, "programs": {}, "n_devices": 0,
                                "busy_s_max": 0.0}}}
    for m in cell.per_layer:
        assert loader.load_metric(m["name"]).compute(run) is None, m["name"]


def test_work_sala_against_hand_counts():
    cfg = loader.load_cell(CELL).config
    p = work_sala.param_counts(cfg)
    assert p["mlp"] == 3 * 4096 * 16384 == 201_326_592
    assert p["minicpm4"] == 3 * 4096 * 4096 + 2 * 4096 * 256 == 52_428_800
    assert p["lightning-attn"] == 5 * 4096 * 4096 == 83_886_080
    assert p["embedding"] == p["head"] == 73448 * 4096
    assert p["layers"] == 16 * p["mlp"] + 4 * 52_428_800 + 12 * 83_886_080
    # weights a step: every layer and the head, bf16
    assert work_sala.weight_bytes(cfg) == 2.0 * (p["layers"] + p["head"])
    live = 32768 + 40
    # 64 blocks x 64 tokens x 128 x (K and V) x 2 B x 2 heads x 4 layers
    assert work_sala.sparse_attend_bytes(cfg, live) \
        == 64 * 64 * 128 * 2 * 2 * 2 * 4
    # kernels that END at or before the last position: (live - 32) // 16 + 1
    assert work_sala.compressed_key_bytes(cfg, live) \
        == 4 * ((live - 32) // 16 + 1) * 2 * 128 * 2
    assert work_sala.lightning_state_bytes(cfg) == 2 * 12 * 32 * 128 * 128 * 4
    # a dense position attends to every block it has and scores nothing
    assert work_sala.sparse_attend_bytes(cfg, 1000) \
        == 16 * 64 * 128 * 2 * 2 * 2 * 4
    assert work_sala.compressed_key_bytes(cfg, 1000) == 0.0
    flops = work_sala.decode_token_flops(cfg, live)
    mat = 2.0 * (p["layers"] + p["head"])
    # + q.k and p.v over 64 x 64 keys, the scores over the compressed
    # keys, and 5 FLOPs an element of every state
    assert flops == mat + 4 * 32 * 128 * 4 * 4096 \
        + 4 * 32 * 128 * 2 * ((live - 32) // 16 + 1) \
        + 12 * 32 * 128 * 128 * 5

"""``jamba_chat_churn`` at toy size on the CPU: the cell decides
``correct`` against the plain reference on arbitrary seeds and reads
``false`` under its three controls; its readers read a toy trace and
read nothing from a program without the counters; ``work_jamba`` counts
what hand counts count; the generator deals every seed the same work."""
import io
import json
import os
import time

import pytest

from benchmarks.harness import loader, validate, work_jamba

from .conftest import DATA, TOY_PEAKS

CELL = "jamba_chat_churn"


@pytest.fixture
def run_jamba(monkeypatch):
    with open(os.path.join(DATA, "toy_jamba.json")) as f:
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


def test_the_benchmark_files_fit_together():
    assert validate.problems() == []
    cell = loader.load_cell(CELL)
    assert cell.chips == 1 and cell.config["reduced"] == []
    assert {m["name"] for m in cell.end_to_end} == {"call_p95_ms", "setup_s"}


@pytest.mark.parametrize("seed", [3, 4_300_000_011])
def test_the_cell_is_correct_on_arbitrary_seeds(run_jamba, seed):
    r = run_jamba(seed=seed)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert set(r["metrics"]) == {"call_p95_ms", "setup_s"}
    assert r["compared"]["requests_not_compared"]["value"] == 0
    assert r["compared"]["served_logprob_abs_err_mean"]["value"] > 0


@pytest.mark.parametrize("control,number", [
    ("low_precision", "served_logprob_abs_err_median"),
    ("altered_token", "served_logit_gap_max"),
    ("stale_state", "first_token_logprob_abs_err_mean")])
def test_the_three_controls_read_false(run_jamba, control, number):
    r = run_jamba(control=control)
    assert r["correct"] is False
    assert number in _over(r), r["compared"]
    assert r["failed"] == 0


def test_a_traced_run_reports_every_per_layer_metric_it_can(run_jamba):
    """On the CPU there is no custom call in the trace, so the two
    kernels' rooflines find nothing to read and are left out (never 0);
    every other reader reads."""
    r = run_jamba(trace=True, seconds=2.5)
    cell = loader.load_cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"sala.step_ms", "sala.token_gap_p50_ms", "sala.step_ahead_share",
            "sala.prefix_hit_share", "device.idle_share.sala",
            "sala.state_restore_ms_per_request"} <= names
    silent = {"jamba.scan_step_roofline", "jamba.scan_prefill_roofline"}
    assert names - silent <= set(r["metrics"]), \
        (names - silent) - set(r["metrics"])
    assert 10.0 < r["metrics"]["sala.prefix_hit_share"]["value"] < 100.0
    assert 0 <= r["metrics"]["jamba.snapshots_per_request"]["value"] <= 1.5
    assert r["metrics"]["jamba.decode_step_mfu"]["value"] > 0
    assert r["metrics"]["jamba.decode_step_hbm_roofline"]["value"] > 0
    assert 0 < r["metrics"]["jamba.prefill_device_share"]["value"] < 100
    assert r["metrics"]["sala.state_restore_ms_per_request"]["value"] > 0
    assert "busy_s" in r["device"]


def test_readers_return_nothing_on_a_program_without_the_counters():
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


def test_the_scan_rooflines_find_their_kernels_by_name_and_program():
    cell = loader.load_cell(CELL)
    ops = {"jit_runner_hybrid_step": {
               "mamba_step.3 f32[32,1,5120]": [260, 0.25],
               "mamba_conv.2 f32[32,1,5120]": [260, 0.125],
               "fusion.9 f32[32,2560]": [10, 4.0]},
           "jit_runner_hybrid_prefill": {
               "mamba_scan.4 f32[512,5120]": [52, 0.5],
               "mamba_scan.7 f32[64,5120]": [26, 0.0625]}}
    run = {"config": cell.config, "traffic": cell.traffic,
           "peaks": {"hbm_bytes_per_s": 819e9, "flops_bf16": 197e12},
           "traced": {"counters0": {"mamba_steps": 0, "mamba_tokens": 0},
                      "counters1": {"mamba_steps": 320, "mamba_tokens": 1000},
                      "trace": {"ops": ops}}}
    step = loader.load_metric("jamba.scan_step_roofline")
    assert step.kernel_calls(run, "jit_runner_hybrid_step", "mamba_step") \
        == (260, 0.25)
    assert step.compute(run) == pytest.approx(
        100.0 * 320 * 2 * 26 * 16 * 5120 * 4 / 819e9 / 0.25)
    scan = loader.load_metric("jamba.scan_prefill_roofline")
    need = 26 * 1000 * (4 * 5120 + 32) * 4 + 3 * 2 * 26 * 16 * 5120 * 4
    assert scan.compute(run) == pytest.approx(
        100.0 * need / 819e9 / 0.5625)


def test_work_jamba_against_hand_counts():
    cfg = loader.load_cell(CELL).config
    p = work_jamba.param_counts(cfg)
    assert p["mamba"] == 41_241_792 and p["attention"] == 13_762_560
    assert p["mamba_matrices"] == 2560 * 10240 + 5120 * 192 + 160 * 5120 \
        + 5120 * 2560
    assert p["mlp"] == 62_914_560 and p["embedding"] == 167_772_160
    assert p["layers"] + p["embedding"] == 3_029_191_552    # 6.06 GB bf16
    assert work_jamba.n_layers(cfg) == (26, 2)
    assert work_jamba.weight_bytes(cfg) == 2 * 3_029_191_552
    assert work_jamba.scan_state_bytes(cfg) == 26 * 16 * 5120 * 4
    assert work_jamba.state_row_bytes(cfg) == 26 * 19 * 5120 * 4  # 10.1 MB
    assert work_jamba.kv_page_bytes(cfg) == 2 * 2 * 128 * 64 * 2 == 65_536
    # three slots at 600, 700 and 1,400 tokens over two steps: their own
    # pages past the 8 shared ones, and the shared ones once a step
    assert work_jamba.distinct_kv_pages(cfg, [600, 700, 1400], 2, 512) \
        == (10 - 8) + (11 - 8) + (22 - 8) + 2 * 8
    assert work_jamba.decode_steps_bytes(cfg, 10, 320, 700) \
        == 10 * 2 * 3_029_191_552 + 320 * 2 * 26 * 19 * 5120 * 4 \
        + 700 * 65_536
    live = 1400
    assert work_jamba.decode_token_flops(cfg, live) == 2.0 * (
        26 * p["mamba_matrices"] + 2 * p["attention"] + 28 * p["mlp"]
        + p["embedding"]) + 2 * 20 * 2.0 * live * 256 \
        + 26 * 16 * 5120 * 6.0


def test_every_seed_is_dealt_the_same_work():
    from benchmarks.drivers import jamba_serving
    t = loader.load_cell(CELL).traffic
    block, a = jamba_serving.closed_loop_chat_churn(t, 3)
    _, b = jamba_serving.closed_loop_chat_churn(t, 4_300_000_011)
    assert len(block) == 128 and a != b
    assert sorted(a[:128]) == sorted(b[:128]) == sorted(block)
    assert all(32 <= m <= 4096 and 24 <= o <= 256 for m, o in block)
    import statistics
    assert 300 <= statistics.median(m for m, _ in block) <= 480
    assert 80 <= statistics.median(o for _, o in block) <= 115

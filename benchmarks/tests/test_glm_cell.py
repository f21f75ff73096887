"""``glm_agent_turns`` at toy size on the CPU: the cell decides
``correct`` against the plain reference on arbitrary seeds and reads
``false`` under its three controls; its readers read a toy trace and
read nothing from a program without the counters; ``work_glm`` counts
what hand counts count."""
import io
import json
import os
import time

import pytest

from benchmarks.harness import loader, work_glm

from .conftest import DATA, TOY_PEAKS

CELL = "glm_agent_turns"


@pytest.fixture
def run_glm(monkeypatch):
    with open(os.path.join(DATA, "toy_glm.json")) as f:
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
def test_the_cell_is_correct_on_arbitrary_seeds(run_glm, seed):
    r = run_glm(seed=seed)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert set(r["metrics"]) == {"call_p95_ms", "setup_s"}
    assert r["compared"]["episodes_not_compared"]["value"] == 0
    assert r["compared"]["served_logprob_abs_err_mean"]["value"] > 0


@pytest.mark.parametrize("control,number", [
    ("low_precision", "served_logprob_abs_err_median"),
    ("altered_token", "served_tokens_far_from_best_per_1000"),
    ("dropped_expert", "served_logprob_abs_err_median")])
def test_the_three_controls_read_false(run_glm, control, number):
    r = run_glm(control=control)
    assert r["correct"] is False
    assert number in _over(r), r["compared"]
    assert r["failed"] == 0


def test_a_traced_run_reports_every_per_layer_metric_it_can(run_glm):
    """On the CPU there is no custom call in the trace, so the two
    kernels' rooflines find nothing to read and are left out (never 0);
    every other reader reads."""
    r = run_glm(trace=True, seconds=2.5)
    cell = loader.load_cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"sala.step_ms", "sala.token_gap_p50_ms", "sala.step_ahead_share",
            "sala.prefix_hit_share", "device.idle_share.sala"} <= names
    silent = {"glm.expert_ffn_roofline", "glm.latent_attend_roofline"}
    assert names - silent <= set(r["metrics"]), \
        (names - silent) - set(r["metrics"])
    assert r["metrics"]["sala.prefix_hit_share"]["value"] > 50.0
    assert 1 <= r["metrics"]["glm.experts_hit_per_layer"]["value"] <= 16
    assert r["metrics"]["glm.decode_step_mfu"]["value"] > 0
    assert r["metrics"]["glm.decode_step_hbm_roofline"]["value"] > 0
    assert 0 < r["metrics"]["glm.prefill_device_share"]["value"] < 100
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


def test_kernel_seconds_find_the_kernels_by_name():
    mod = loader.load_metric("glm.expert_ffn_roofline")
    run = {"traced": {"trace": {"ops": {"jit_runner_hybrid_step": {
        "ragged-dot-none.4 f32[64,1536]": [7, 0.5],
        "ragged-dot-metadata.1 (s32[65]": [7, 0.01],
        "latent_attend.3 f32[16,32,640]": [8, 0.25],
        "latent_write.2 bf16[8,1536,64,640]": [8, 0.125],
        "fusion.9 f32[16,2048]": [3, 4.0]},
        "jit_runner_hybrid_prefill": {
            "ragged-dot-none.4 f32[4096,1536]": [7, 9.0]}}}}}
    assert mod.kernel_seconds(run, mod.KERNELS) == 0.51
    assert mod.kernel_seconds(run, ("latent_attend",)) == 0.25


def test_work_glm_against_hand_counts():
    cfg = loader.load_cell(CELL).config
    p = work_glm.param_counts(cfg)
    assert p["mla"] == 2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 \
        + 5120 * 2048 == 21_757_952
    assert p["dense_ffn"] == 3 * 2048 * 10240 == 62_914_560
    assert p["expert"] == 3 * 2048 * 1536 == 9_437_184
    assert p["shared"] == p["expert"] and p["router"] == 2048 * 64
    assert p["embedding"] == p["head"] == 154880 * 2048
    assert p["layers"] == 8 * p["mla"] + p["dense_ffn"] \
        + 7 * (65 * p["expert"] + p["router"])
    assert p["layers"] + 2 * p["head"] == 5_166_202_880     # 10.33 GB bf16
    assert work_glm.n_moe_layers(cfg) == 7
    # every step: attention, the dense MLP, 7 shared experts and the
    # head in bf16, 7 float32 routers
    assert work_glm.fixed_weight_bytes(cfg) == 2 * (
        8 * p["mla"] + p["dense_ffn"] + 7 * p["expert"] + p["head"]) \
        + 4 * 7 * 2048 * 64
    assert work_glm.expert_bytes(cfg) == 18_874_368
    assert work_glm.latent_page_bytes(cfg) == 64 * 576 * 2 == 73_728
    assert work_glm.decode_steps_bytes(cfg, 10, 2870, 24000, 160) \
        == 10 * work_glm.fixed_weight_bytes(cfg) + 2870 * 18_874_368 \
        + 24000 * 73_728 + 160 * 4096
    live = 9000
    assert work_glm.decode_token_flops(cfg, live) == 2.0 * (
        8 * p["mla"] + p["dense_ffn"]
        + 7 * (5 * p["expert"] + p["router"]) + p["head"]) \
        + 8 * 20 * 2.0 * live * (256 + 256)
    peaks = {"hbm_bytes_per_s": 819e9, "flops_bf16": 197e12}
    # 16 slots: the experts' bytes bound, not the assignments' FLOPs
    assert work_glm.expert_ffn_seconds(cfg, 287, 448, peaks) \
        == 287 * 18_874_368 / 819e9
    assert work_glm.latent_attend_seconds(cfg, 2400, 16 * 9000 * 8, peaks) \
        == 2400 * 73_728 / 819e9

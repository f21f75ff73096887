"""``nemotron_reason_decode`` at toy size on the CPU: the cell decides
``correct`` against the plain reference on arbitrary seeds and reads
``false`` under its four controls; its readers read a toy trace and
read nothing from a program without the counters; ``work_nemotron``
counts what hand counts count; the generator deals every seed the same
work."""
import io
import json
import os
import time

import pytest

from benchmarks.harness import loader, validate, work_nemotron

from .conftest import DATA, TOY_PEAKS

CELL = "nemotron_reason_decode"


@pytest.fixture
def run_nemotron(monkeypatch):
    with open(os.path.join(DATA, "toy_nemotron.json")) as f:
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
    assert cell.chips == 1
    assert cell.config["reduced"] == ["num_hidden_layers",
                                      "n_routed_experts", "vocab_size"]
    assert {m["name"] for m in cell.end_to_end} == {"call_p95_ms", "setup_s"}


def test_the_configuration_carries_the_catalog_rows_keys():
    """Every key of the catalog row's ``config``, unchanged but the three
    in ``reduced``, each with its published value beside it."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    published = json.loads(next(
        line for line in open(catalog)
        if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line))["config"]
    c = loader.load_cell(CELL).config
    cut = {"num_hidden_layers": 11, "n_routed_experts": 128,
           "vocab_size": 32768}
    for key, value in published.items():
        assert c[key] == cut.get(key, value), key
        if key in cut:
            assert c["published_" + key] == value
    assert c["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert set(c["guarantees"]) >= {"served_logprob", "served_token",
                                    "all_tokens_delivered", "fresh_state",
                                    "no_dropped_token"}


@pytest.mark.parametrize("seed", [3, 4_300_000_011])
def test_the_cell_is_correct_on_arbitrary_seeds(run_nemotron, seed):
    r = run_nemotron(seed=seed)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert set(r["metrics"]) == {"call_p95_ms", "setup_s"}
    assert r["compared"]["requests_not_compared"]["value"] == 0
    assert r["compared"]["served_logprob_abs_err_mean"]["value"] > 0


@pytest.mark.parametrize("control,number", [
    ("low_precision", "served_logprob_abs_err_median"),
    ("altered_token", "served_tokens_far_from_best_per_1000"),
    ("stale_state", "first_token_logprob_abs_err_median"),
    ("dropped_expert", "served_logprob_abs_err_mean")])
def test_the_four_controls_read_false(run_nemotron, control, number):
    r = run_nemotron(control=control)
    assert r["correct"] is False
    assert number in _over(r), r["compared"]
    assert r["failed"] == 0


def test_a_traced_run_reports_every_per_layer_metric_it_can(run_nemotron):
    """On the CPU there is no custom call in the trace, so the three
    kernels' rooflines find nothing to read and are left out (never 0);
    every other reader reads."""
    r = run_nemotron(trace=True, seconds=2.5)
    cell = loader.load_cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"sala.step_ms", "sala.token_gap_p50_ms", "sala.step_ahead_share",
            "sala.prefix_hit_share", "device.idle_share.sala",
            "sala.state_restore_ms_per_request"} <= names
    assert len([n for n in names if n.startswith("nemotron.")]) == 7
    silent = {"nemotron.ssd_step_roofline", "nemotron.ssd_prefill_roofline",
              "nemotron.expert_ffn_roofline"}
    assert names - silent <= set(r["metrics"]), \
        (names - silent) - set(r["metrics"])
    assert 10.0 < r["metrics"]["sala.prefix_hit_share"]["value"] < 100.0
    assert r["metrics"]["nemotron.decode_step_mfu"]["value"] > 0
    assert r["metrics"]["nemotron.decode_step_hbm_roofline"]["value"] > 0
    assert 0 < r["metrics"]["nemotron.experts_hit_per_layer"]["value"] <= 4
    assert 0 < r["metrics"]["nemotron.prefill_device_share"]["value"] < 100
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


def test_the_kernel_rooflines_find_their_kernels_by_name_and_program():
    cell = loader.load_cell(CELL)
    ops = {"jit_runner_hybrid_step": {
               "ssd_step.3 f32[64,8,8,128]": [500, 0.25],
               "ssd_conv.2 f32[64,80,128]": [500, 0.125],
               "ragged-dot-fusion.1 f32[1408,2688]": [500, 0.5],
               "ragged-dot-fusion.2 f32[1408,1024]": [500, 0.5],
               "fusion.9 f32[64,4096]": [10, 4.0]},
           "jit_runner_hybrid_prefill": {
               "ssd_scan.4 f32[512,8192]": [20, 0.5],
               "ssd_scan.7 f32[64,8192]": [10, 0.0625],
               "ragged-dot-fusion.1 f32[11264,2688]": [30, 9.0]}}
    peaks = {"hbm_bytes_per_s": 819e9, "flops_bf16": 197e12}
    run = {"config": cell.config, "traffic": cell.traffic, "peaks": peaks,
           "traced": {"counters0": {k: 0 for k in (
                          "ssd_steps", "ssd_tokens", "moe_experts_hit",
                          "moe_assignments", "moe_assignments_held",
                          "tokens", "steps")},
                      "counters1": {"ssd_steps": 6400, "ssd_tokens": 1500,
                                    "moe_experts_hit": 60000,
                                    "moe_assignments": 880000,
                                    "moe_assignments_held": 220000,
                                    "tokens": 6400, "steps": 100},
                      "trace": {"ops": ops}}}
    step = loader.load_metric("nemotron.ssd_step_roofline")
    assert step.compute(run) == pytest.approx(
        100.0 * 6400 * 2 * 5 * 128 * 64 * 128 * 4 / 819e9 / 0.25)
    scan = loader.load_metric("nemotron.ssd_prefill_roofline")
    flops = 5 * 1500 * 2.0 * (64.5 * 128 * 8 + 64.5 * 64 * 128
                              + 2 * 128 * 64 * 128)
    nbytes = 5 * 1500 * (2 * 8192 + 128 + 2048) * 4.0 \
        + 6 * 2 * 5 * 128 * 64 * 128 * 4
    assert scan.compute(run) == pytest.approx(
        100.0 * max(flops / 197e12, nbytes / 819e9) / 0.5625)
    experts = loader.load_metric("nemotron.expert_ffn_roofline")
    # the bytes bind: 60,000 hits of 11.0 MB; the held assignments'
    # FLOPs (a quarter of 6,400 x 22 x 5) are 60 times shorter
    assert experts.compute(run) == pytest.approx(
        100.0 * 60000 * 2 * 1024 * 2688 * 2 / 819e9 / 1.0)
    hit = loader.load_metric("nemotron.experts_hit_per_layer")
    assert hit.compute(run) == pytest.approx(120.0)


def test_work_nemotron_against_hand_counts():
    cfg = loader.load_cell(CELL).config
    p = work_nemotron.param_counts(cfg)
    assert p["M"] == 109_635_968 and p["*"] == 35_651_584
    assert p["mamba2_matrices"] == 4096 * 18560 + 8192 * 4096
    assert p["expert"] == 2 * 1024 * 2688 == 5_505_024      # 11.0 MB bf16
    assert p["shared"] + p["latent"] + p["router"] == 54_525_952
    assert p["head"] == p["embedding"] == 32768 * 4096
    assert work_nemotron.n_blocks(cfg) == (5, 1, 5)
    assert work_nemotron.held_share(cfg) == 0.25
    # what the chip holds: 4,648 M parameters = 9.30 GB bf16
    held = 5 * p["M"] + p["*"] + 5 * (p["shared"] + p["latent"] + p["router"]
                                      + 128 * p["expert"]) + 2 * p["head"]
    assert round(held / 1e6) == 4648
    fixed = 2 * (5 * p["M"] + p["*"] + 5 * (p["shared"] + p["latent"])
                 + p["head"]) + 4 * 5 * p["router"]
    assert work_nemotron.fixed_weight_bytes(cfg) == fixed
    assert 1.9e9 < fixed < 2.1e9
    assert work_nemotron.scan_state_bytes(cfg) == 5 * 128 * 64 * 128 * 4
    assert work_nemotron.state_row_bytes(cfg) \
        == 5 * (128 * 64 * 128 + 3 * 10240) * 4              # 21.6 MB
    assert work_nemotron.kv_page_bytes(cfg) == 2 * 2 * 128 * 64 * 2 == 65_536
    assert work_nemotron.distinct_kv_pages(cfg, [300, 700, 1400], 2, 256) \
        == (5 - 4) + (11 - 4) + (22 - 4) + 2 * 4
    assert work_nemotron.decode_steps_bytes(cfg, 10, 640, 6000, 700) \
        == 10 * fixed + 6000 * 11_010_048 \
        + 640 * (2 * 5 * (128 * 64 * 128 + 3 * 10240) * 4 + 8192) \
        + 700 * 65_536
    live = 700
    assert work_nemotron.decode_token_flops(cfg, live) == 2.0 * (
        5 * p["mamba2_matrices"] + p["*"]
        + 5 * (p["router"] + p["latent"] + p["shared"] + 5.5 * p["expert"])
        + p["head"]) + 32 * 2.0 * live * 256 + 5 * 128 * 64 * 128 * 5.0


def test_every_seed_is_dealt_the_same_work():
    from benchmarks.drivers import nemotron_serving
    t = loader.load_cell(CELL).traffic
    assert (t["sessions"], t["system_prompt_tokens"], t["compare_every"],
            t["trace_seconds"], t["timeout_s"]) == (64, 256, 8, 6, 300)
    block, a = nemotron_serving.closed_loop_chat_churn(t, 3)
    _, b = nemotron_serving.closed_loop_chat_churn(t, 4_300_000_011)
    assert len(block) == 128 and a != b
    assert sorted(a[:128]) == sorted(b[:128]) == sorted(block)
    assert all(32 <= m <= 1024 and 48 <= o <= 512 for m, o in block)
    import statistics
    assert 150 <= statistics.median(m for m, _ in block) <= 240
    assert 160 <= statistics.median(o for _, o in block) <= 230

"""``mellum_code_mixed`` at toy size on the CPU: the cell decides
``correct`` against the plain reference on arbitrary seeds and reads
``false`` under its six controls; its readers read a toy trace and read
nothing from a program without the counters; ``work_mellum`` counts what
hand counts count; the plan deals every seed the same work."""
import io
import json
import os
import time

import pytest

from benchmarks.harness import loader, validate, work_mellum

from .conftest import DATA, TOY_PEAKS

CELL = "mellum_code_mixed"


@pytest.fixture
def run_mellum(monkeypatch):
    with open(os.path.join(DATA, "toy_mellum.json")) as f:
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
    assert cell.config["reduced"] == ["num_hidden_layers", "layer_types",
                                      "mlp_layer_types"]
    assert {m["name"] for m in cell.end_to_end} == {"call_p95_ms", "setup_s"}


S, F = "sliding_attention", "full_attention"
# the published ``config.json``'s keys (the configuration's source)
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [S, S, S, F] * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        F: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        S: {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}


def test_the_configuration_carries_the_published_keys():
    """Every published key, unchanged but the three in ``reduced``, each
    with its published value beside it; every width as published."""
    published = PUBLISHED
    c = loader.load_cell(CELL).config
    cut = {"num_hidden_layers": 12,
           "layer_types": published["layer_types"][:12],
           "mlp_layer_types": published["mlp_layer_types"][:12]}
    for key, value in published.items():
        assert c[key] == cut.get(key, value), key
        if key in cut:
            assert c["published_" + key] == value
    assert "".join("F" if t == "full_attention" else "S"
                   for t in c["layer_types"]) == "SSSF" * 3
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["sliding_window"],
            c["num_experts"], c["moe_intermediate_size"],
            c["num_experts_per_tok"], c["vocab_size"]) \
        == (2304, 32, 4, 128, 1024, 64, 896, 8, 98304)
    assert set(c["guarantees"]) >= {"served_logprob", "served_token",
                                    "all_tokens_delivered", "fresh_state",
                                    "window", "no_dropped_token"}
    assert all(isinstance(v, str) and v for k, v in c["assumed"].items()
               if k != "tolerances")


@pytest.mark.parametrize("seed", [3, 4_300_000_011])
def test_the_cell_is_correct_on_arbitrary_seeds(run_mellum, seed):
    r = run_mellum(seed=seed)
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    assert set(r["metrics"]) == {"call_p95_ms", "setup_s"}
    assert r["compared"]["requests_not_compared"]["value"] == 0
    assert r["compared"]["served_logprob_abs_err_mean"]["value"] > 0


@pytest.mark.parametrize("control,number", [
    ("low_precision", "served_logprob_abs_err_median"),
    ("altered_token", "served_tokens_far_from_best_per_1000"),
    ("stale_window", "first_token_logprob_abs_err_median"),
    ("unbounded_window", "served_logprob_abs_err_median"),
    ("plain_rope", "served_logprob_abs_err_median"),
    ("dropped_expert", "served_logprob_abs_err_mean")])
def test_the_six_controls_read_false(run_mellum, control, number):
    r = run_mellum(control=control)
    assert r["correct"] is False
    assert number in _over(r), r["compared"]
    assert r["failed"] == 0


def test_a_traced_run_reports_every_per_layer_metric_it_can(run_mellum):
    """On the CPU there is no custom call in the trace, so the kernels'
    rooflines find nothing to read and are left out (never 0); every
    other reader reads."""
    r = run_mellum(trace=True, seconds=2.5)
    cell = loader.load_cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert {"sala.step_ms", "sala.token_gap_p50_ms", "sala.step_ahead_share",
            "sala.prefix_hit_share", "device.idle_share.sala",
            "sala.state_restore_ms_per_request"} <= names
    assert len([n for n in names if n.startswith("mellum.")]) == 5
    silent = {"mellum.window_attend_roofline", "mellum.expert_ffn_roofline"}
    assert names - silent <= set(r["metrics"]), \
        (names - silent) - set(r["metrics"])
    assert 10.0 < r["metrics"]["sala.prefix_hit_share"]["value"] < 100.0
    assert r["metrics"]["mellum.decode_step_hbm_roofline"]["value"] > 0
    # a toy window of 24 over pages of 16 spans at most 3 pages
    assert 1 <= r["metrics"]["mellum.window_pages_per_query"]["value"] <= 3
    assert 0 < r["metrics"]["mellum.prefill_device_share"]["value"] < 100
    assert r["metrics"]["sala.state_restore_ms_per_request"]["value"] > 0
    assert "busy_s" in r["device"]


def test_readers_return_nothing_on_a_program_without_the_counters():
    """What an older program's traced runs give: a run with no trace, no
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
               "window_attend.3 f32[128,8,128]": [900, 0.25],
               "sparse_attend.2 f32[128,8,128]": [300, 2.0],
               "ragged-dot-fusion.1 f32[256,896]": [500, 0.5],
               "ragged-dot-fusion.2 f32[256,2304]": [500, 0.5],
               "fusion.9 f32[32,2304]": [10, 4.0]},
           "jit_runner_hybrid_prefill": {
               "window_attend.7 f32[2048,8,128]": [20, 0.5],
               "ragged-dot-fusion.1 f32[4096,896]": [30, 9.0]}}
    peaks = {"hbm_bytes_per_s": 819e9, "flops_bf16": 197e12}
    names = ("window_pages_read", "window_positions", "full_pages_read",
             "moe_experts_hit", "tokens", "steps")
    run = {"config": cell.config, "traffic": cell.traffic, "peaks": peaks,
           "traced": {"counters0": {k: 0 for k in names},
                      "counters1": {"window_pages_read": 9 * 3200 * 17,
                                    "window_positions": 9 * 3200,
                                    "full_pages_read": 3 * 3200 * 400,
                                    "moe_experts_hit": 12 * 100 * 63,
                                    "tokens": 3200, "steps": 100},
                      "trace": {"ops": ops}}}
    page = 64 * 2 * 4 * 128 * 2
    window = loader.load_metric("mellum.window_attend_roofline")
    assert window.compute(run) == pytest.approx(
        100.0 * 9 * 3200 * 17 * page / 819e9 / 0.25)
    experts = loader.load_metric("mellum.expert_ffn_roofline")
    # the bytes bind: 75,600 hits of 12.4 MB; the assignments' FLOPs
    # (3,200 x 8 x 12) are far shorter
    assert experts.compute(run) == pytest.approx(
        100.0 * 12 * 100 * 63 * 3 * 2304 * 896 * 2 / 819e9 / 1.0)
    per_query = loader.load_metric("mellum.window_pages_per_query")
    assert per_query.compute(run) == 17.0


def test_work_mellum_against_hand_counts():
    cfg = loader.load_cell(CELL).config
    p = work_mellum.param_counts(cfg)
    assert p["attention"] == 21_233_664
    assert p["expert"] == 3 * 2304 * 896 == 6_193_152      # 12.4 MB bf16
    assert p["router"] == 2304 * 64
    assert p["head"] == p["embedding"] == 98304 * 2304
    assert work_mellum.n_layers(cfg) == (9, 3)
    # what the chip holds: 12 layers of 417.7 M and the embedding and
    # head: 10.94 GB at bf16 (the router float32)
    assert p["held"] == 12 * 417_747_456 + 2 * 226_492_416 + 2304
    assert round((2 * p["held"] + 2 * 12 * p["router"]) / 1e9, 2) == 10.94
    fixed = 2 * (12 * p["attention"] + p["head"]) \
        + 4 * (12 * (p["router"] + 2 * 2304) + 2304)
    assert work_mellum.fixed_weight_bytes(cfg) == fixed
    assert 0.9e9 < fixed < 1.0e9
    assert work_mellum.page_bytes(cfg) == 64 * 2 * 4 * 128 * 2 == 131_072
    assert work_mellum.decode_steps_bytes(cfg, 10, 700, 5000, 3000, 320) \
        == 10 * fixed + 700 * 2 * 6_193_152 + 8000 * 131_072 + 320 * 4608
    peaks = {"hbm_bytes_per_s": 819e9, "flops_bf16": 197e12}
    assert work_mellum.window_attend_seconds(cfg, 17, peaks) \
        == pytest.approx(17 * 131_072 / 819e9)


def test_every_seed_is_dealt_the_same_work():
    from benchmarks.drivers import mellum_serving
    t = loader.load_cell(CELL).traffic
    assert (t["sessions"], t["system_prompt_tokens"], t["contexts"],
            t["compare_every"], t["trace_seconds"]) == (32, 512, 4, 8, 6)
    lengths, block, a = mellum_serving.code_mixed_plan(t, 3)
    again, _, b = mellum_serving.code_mixed_plan(t, 4_300_000_011)
    assert lengths == again and len(lengths) == 4
    assert all(16384 <= n <= 49152 for n in lengths)
    assert len(block) == 32 and a != b
    # every round is the block; the seed deals the same sequences of
    # turns over the sessions in another order
    for j in range(0, len(a), 32 * 97):
        assert sorted(a[j:j + 32]) == sorted(b[j:j + 32]) == sorted(block)

    def sequences(turns, rounds=6):
        return sorted(tuple(turns[j * 32 + s] for j in range(rounds))
                      for s in range(32))
    assert sequences(a) == sequences(b)
    assert [a[j * 32] for j in range(6)] != [b[j * 32] for j in range(6)]
    long = [x for x in block if x[0] >= 0]
    short = [x for x in block if x[0] < 0]
    assert len(long) == len(short) == 16
    assert sorted(c for c, _, _ in long) == sorted([0, 1, 2, 3] * 4)
    assert all(256 <= m <= 2048 and 32 <= o <= 384 for _, m, o in long)
    assert all(128 <= m <= 2048 and 16 <= o <= 256 for _, m, o in short)
    import statistics
    assert 550 <= statistics.median(m for _, m, _ in long) <= 1050
    assert 90 <= statistics.median(o for _, _, o in long) <= 180
    assert 350 <= statistics.median(m for _, m, _ in short) <= 750
    assert 45 <= statistics.median(o for _, _, o in short) <= 90
    # the longest request fits a slot's table, the cache holds the
    # contexts beside every slot's own pages
    c = loader.load_cell(CELL).config
    page = c["page_tokens"]
    most = 512 + max(lengths) + 2048 + 384
    assert -(-most // page) <= c["max_pages_per_slot"]
    assert sum(-(-(512 + n) // page) for n in lengths) \
        + c["num_slots"] * -(-(2048 + 384 + page) // page) <= c["cache_pages"]

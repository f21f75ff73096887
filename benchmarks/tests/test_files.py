"""Every file the benchmark names loads and fits the contract, and a
later PR can add a cell, a metric or a driver as files only."""
import json
import os
import shutil

import pytest

from benchmarks.harness import loader, validate


def test_benchmark_json_fits_the_contract():
    assert validate.problems() == []


def test_every_cell_loads_and_reports_what_its_metrics_move():
    bench = loader.load_benchmark()
    for w in bench["workloads"]:
        cell = loader.load_cell(w["name"])
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in reported
            assert callable(loader.load_metric(m["name"]).compute)
        assert loader.load_driver(cell.config["driver"]).Driver


def test_only_one_four_chip_cell():
    bench = loader.load_benchmark()
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] \
        == ["rail_x4"]


def test_a_later_pr_adds_a_cell_a_metric_and_a_driver_as_files(tmp_path):
    """Copy the benchmark, add one file of each kind and one entry each,
    edit nothing that was there: the loader finds them all."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(loader.ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = loader.load_benchmark()
    b = os.path.join(root, "benchmarks")
    with open(os.path.join(b, "configs", "toy_new.json"), "w") as f:
        json.dump({"driver": "new_driver", "chips": 1, "reduced": []}, f)
    with open(os.path.join(b, "traffic", "new_mix.json"), "w") as f:
        json.dump({"generator": "closed_loop_unary", "callers": 2}, f)
    with open(os.path.join(b, "metrics", "new.layer_metric.py"), "w") as f:
        f.write("def compute(run):\n    return run.get('x')\n")
    with open(os.path.join(b, "drivers", "new_driver.py"), "w") as f:
        f.write("class Driver:\n    pass\n")
    bench["configs"].append({"name": "toy_new", "source": "https://x.test/y",
                             "file": "benchmarks/configs/toy_new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy_new.new_mix", "config": "toy_new",
                               "traffic": "new_mix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == "goodput_gbps":
            m["workloads"].append("toy_new.new_mix")
    bench["per_layer"].append({
        "name": "new.layer_metric", "unit": "1", "better": "lower",
        "source": "program_counter", "layer": "new layer",
        "moves": "goodput_gbps", "workloads": ["toy_new.new_mix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    cell = loader.load_cell("toy_new.new_mix", root)
    assert [m["name"] for m in cell.per_layer] == ["new.layer_metric"]
    assert {m["name"] for m in cell.end_to_end} == {"goodput_gbps",
                                                    "setup_s"}
    assert loader.load_metric("new.layer_metric", root).compute(
        {"x": 3.0}) == 3.0
    assert loader.load_metric("new.layer_metric", root).compute({}) is None
    assert loader.load_driver("new_driver", root).Driver
    assert validate.problems(root) == []


def test_loader_refuses_a_metric_whose_moves_target_is_not_reported(tmp_path):
    root = str(tmp_path)
    shutil.copytree(os.path.join(loader.ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = loader.load_benchmark()
    for m in bench["per_layer"]:
        if m["name"] == "collective.lowered_call_p50_ms":   # moves call_p95_ms
            m["workloads"] = ["stream_4m"]
    with pytest.raises(loader.BenchmarkError):
        loader.load_cell("stream_4m", root, bench)


def test_staged_entries_load_and_are_no_part_of_the_benchmark():
    """``benchmarks/staged/``: measured once, taken out, kept with its
    code for the PR that brings it back.  BENCHMARK.json names none of
    it; laid over BENCHMARK.json every entry still finds its files."""
    bench = loader.load_benchmark()
    full = loader.load_benchmark_with_staged()
    mine = {w["name"] for w in bench["workloads"]}
    staged = [w["name"] for w in full["workloads"] if w["name"] not in mine]
    assert staged == ["chat_decode"]
    for name in staged:
        cell = loader.load_cell(name, bench=full)
        for m in cell.end_to_end + cell.per_layer:
            if m["name"] != "setup_s":
                assert callable(loader.load_metric(m["name"]).compute)
        assert loader.load_driver(cell.config["driver"]).Driver

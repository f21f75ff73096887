"""Find everything by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``; its configuration is the file its
``configs`` entry names; its traffic mix is
``benchmarks/traffic/<traffic>.json``; a metric's reader is
``benchmarks/metrics/<name>.py``; a configuration's driver is
``benchmarks/drivers/<driver>.py``.  No list in code names any of them,
so a later PR adds a cell, a configuration, a metric or a driver by
adding files and entries and edits nothing that is here.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class BenchmarkError(Exception):
    """The benchmark's own files do not fit together."""


@dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_benchmark_with_staged(root: str = ROOT) -> dict:
    """``BENCHMARK.json`` with the entries of ``benchmarks/staged/*.json``
    laid over it: cells that were measured once and taken out, kept with
    their code for the PR that brings them back.  For the tests and for
    by-hand runs (``run.py --staged``); the driver's check never sees
    them."""
    bench = load_benchmark(root)
    staged_dir = os.path.join(root, "benchmarks", "staged")
    for name in sorted(os.listdir(staged_dir)):
        if name.endswith(".json"):
            staged = _read_json(os.path.join(staged_dir, name))
            for kind in ("configs", "workloads", "end_to_end", "per_layer"):
                bench[kind] = bench[kind] + staged[kind]
    return bench


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, root: str = ROOT, bench: dict | None = None) -> Cell:
    bench = bench or load_benchmark(root)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchmarkError(
            f"no workload {name!r} in BENCHMARK.json; there are "
            f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next((c for c in bench["configs"]
                      if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise BenchmarkError(f"workload {name!r} names configuration "
                             f"{entry['config']!r}, which is not listed")
    config = _read_json(os.path.join(root, cfg_entry["file"]))
    traffic = _read_json(os.path.join(
        root, "benchmarks", "traffic", entry["traffic"] + ".json"))
    cell = Cell(name=name, chips=int(entry["chips"]), why=entry["why"],
                config_name=entry["config"], traffic_name=entry["traffic"],
                config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])
    reported = {m["name"] for m in cell.end_to_end}
    for m in cell.per_layer:
        if m["moves"] not in reported:
            raise BenchmarkError(
                f"cell {name!r} reports per-layer metric {m['name']!r} "
                f"but not the end-to-end metric it moves, {m['moves']!r}")
    return cell


def _load_module(kind: str, name: str, root: str):
    if not NAME_RE.match(name):
        raise BenchmarkError(f"{kind} name {name!r} is not a valid name")
    path = os.path.join(root, "benchmarks", kind, name + ".py")
    if not os.path.exists(path):
        raise BenchmarkError(f"no {kind} file {path}")
    modname = f"benchmarks.{kind}.{name.replace('.', '_dot_').replace('-', '_')}"
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str, root: str = ROOT):
    """The reader of one metric: a module with ``compute(run)`` that
    returns the number, or None where it finds nothing to read."""
    mod = _load_module("metrics", name, root)
    if not callable(getattr(mod, "compute", None)):
        raise BenchmarkError(f"metrics/{name}.py has no compute(run)")
    return mod


def load_driver(name: str, root: str = ROOT):
    mod = _load_module("drivers", name, root)
    if not hasattr(mod, "Driver"):
        raise BenchmarkError(f"drivers/{name}.py has no Driver class")
    return mod

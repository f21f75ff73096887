"""Static checks of ``BENCHMARK.json`` against the benchmark contract
(names, units, keys, limits) and against the files it names.  Run by the
tests, and by hand: ``python3 -m benchmarks.harness.validate``.
"""
from __future__ import annotations

import json
import os
import re
import sys

from benchmarks.harness import loader

NAME = loader.NAME_RE
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def _line(s, what, errs):
    if not isinstance(s, str) or not 1 <= len(s) <= 200 or "\n" in s \
            or "\t" in s:
        errs.append(f"{what}: must be 1-200 characters on one line")


def problems(root: str = loader.ROOT) -> list:
    errs: list = []
    path = os.path.join(root, "BENCHMARK.json")
    if os.path.getsize(path) > 64 * 1024:
        errs.append("BENCHMARK.json is over 64 KiB")
    b = json.load(open(path))
    if set(b) != TOP_KEYS:
        errs.append(f"top-level keys {sorted(b)} != {sorted(TOP_KEYS)}")
        return errs
    if not 1 <= len(b["command"]) <= 32:
        errs.append("command: 1-32 words")
    for w in b["command"]:
        _line(w, "command word", errs)
        if w.startswith("/") or ".." in w.split("/"):
            errs.append(f"command word {w!r} leaves the repo")
    if not 1 <= len(b["paths"]) <= 16:
        errs.append("paths: 1-16 directories")
    for p in b["paths"]:
        if not PATH.match(p) or not os.path.isdir(os.path.join(root, p)):
            errs.append(f"path {p!r} is not a valid directory")
    if not (isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51):
        errs.append("run_seconds: a whole number from 1 to 51")

    def under_paths(f):
        return any(f == p or f.startswith(p.rstrip("/") + "/")
                   for p in b["paths"])

    def names(xs):
        return [x["name"] for x in xs]

    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = names(b[kind])
        if len(ns) != len(set(ns)):
            errs.append(f"{kind}: duplicate names")
        for n in ns:
            if not NAME.match(n):
                errs.append(f"{kind}: bad name {n!r}")
    if set(names(b["end_to_end"])) & set(names(b["per_layer"])):
        errs.append("a metric name is both end-to-end and per-layer")
    if not 1 <= len(b["configs"]) <= 24 or not 1 <= len(b["workloads"]) <= 24:
        errs.append("1-24 configs and 1-24 workloads")
    files = set()
    for c in b["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            errs.append(f"config {c.get('name')}: keys {sorted(c)}")
            continue
        _line(c["source"], f"config {c['name']} source", errs)
        _line(c["why"], f"config {c['name']} why", errs)
        if not under_paths(c["file"]) or c["file"] in files \
                or not os.path.isfile(os.path.join(root, c["file"])):
            errs.append(f"config {c['name']}: file {c['file']!r}")
        files.add(c["file"])
        if len(c["reduced"]) > 16 or not all(NAME.match(k)
                                             for k in c["reduced"]):
            errs.append(f"config {c['name']}: reduced")
        if c["name"] not in {w["config"] for w in b["workloads"]}:
            errs.append(f"config {c['name']} is used by no cell")
    pairs = set()
    for w in b["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            errs.append(f"workload {w.get('name')}: keys {sorted(w)}")
            continue
        _line(w["why"], f"workload {w['name']} why", errs)
        if w["chips"] not in (1, 4):
            errs.append(f"workload {w['name']}: chips {w['chips']}")
        if w["config"] not in names(b["configs"]):
            errs.append(f"workload {w['name']}: unknown config")
        if not NAME.match(w["traffic"]):
            errs.append(f"workload {w['name']}: bad traffic name")
        if (w["config"], w["traffic"]) in pairs:
            errs.append(f"workload {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
    four = sum(1 for w in b["workloads"] if w.get("chips") == 4)
    if four > max(1, len(b["workloads"]) // 4):
        errs.append(f"{four} four-chip cells of {len(b['workloads'])}")
    cells = set(names(b["workloads"]))
    e2e = {m["name"]: m for m in b["end_to_end"]}
    if "setup_s" not in e2e:
        errs.append("end_to_end lacks setup_s")
    for m in b["end_to_end"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "bound",
                                      "source"}:
            errs.append(f"metric {m.get('name')}: keys {sorted(m)}")
            continue
        if not UNIT.match(m["unit"]) or m["better"] not in ("lower", "higher"):
            errs.append(f"metric {m['name']}: unit or better")
        if m["source"] not in ("host_clock", "device_trace"):
            errs.append(f"metric {m['name']}: source {m['source']}")
        if not 0.01 <= m["bound"] <= 0.1:
            errs.append(f"metric {m['name']}: bound {m['bound']}")
        if not set(m.get("workloads", ())) <= cells:
            errs.append(f"metric {m['name']}: unknown cell")
    for m in b["per_layer"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "source",
                                      "layer", "moves"}:
            errs.append(f"metric {m.get('name')}: keys {sorted(m)}")
            continue
        _line(m["layer"], f"metric {m['name']} layer", errs)
        if not UNIT.match(m["unit"]) or m["better"] not in ("lower", "higher"):
            errs.append(f"metric {m['name']}: unit or better")
        if m["source"] not in SOURCES:
            errs.append(f"metric {m['name']}: source {m['source']}")
        if m["moves"] not in e2e:
            errs.append(f"metric {m['name']}: moves {m['moves']!r}")
            continue
        if not set(m.get("workloads", ())) <= cells:
            errs.append(f"metric {m['name']}: unknown cell")
        if m["name"].endswith("_roofline") and m["unit"] != "%":
            errs.append(f"metric {m['name']}: a roofline share is in %")
    for w in b["workloads"]:
        try:
            cell = loader.load_cell(w["name"], root, b)
        except (loader.BenchmarkError, OSError, ValueError) as e:
            errs.append(f"cell {w['name']}: {e}")
            continue
        got = {m["name"] for m in cell.end_to_end}
        if "setup_s" not in got or len(got) < 2:
            errs.append(f"cell {w['name']}: needs setup_s and one more "
                        f"end-to-end metric")
        if not cell.per_layer:
            errs.append(f"cell {w['name']}: no per-layer metric")
        if cell.config.get("chips") != w["chips"]:
            errs.append(f"cell {w['name']}: chips differ from its config's")
        for m in cell.end_to_end + cell.per_layer:
            if m["name"] != "setup_s":
                try:
                    loader.load_metric(m["name"], root)
                except loader.BenchmarkError as e:
                    errs.append(str(e))
        try:
            loader.load_driver(cell.config["driver"], root)
        except (loader.BenchmarkError, KeyError) as e:
            errs.append(f"cell {w['name']}: driver: {e}")
    return errs


if __name__ == "__main__":
    found = problems()
    for e in found:
        print("PROBLEM:", e)
    print(f"{len(found)} problem(s)")
    sys.exit(1 if found else 0)

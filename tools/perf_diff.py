#!/usr/bin/env python3
"""perf_diff — compare two bench rounds and flag beyond-spread
regressions (ISSUE 6).

The bench rung families publish every metric as a 3-trial MEDIAN plus
a min-max SPREAD (`qps` + `qps_spread`, `gbps` + ..., bench.py).  That
spread is the per-round noise estimate, and it turns "is 5% slower
real?" into a decision rule with no magic tolerance constant:

    a metric REGRESSED when the two rounds' spread intervals are
    DISJOINT in the worse direction — the new median isn't just lower,
    the runs don't even overlap.

Usage:
    python tools/perf_diff.py BENCH_r03.json BENCH_DETAILS.json

Accepts either the driver's round wrapper ({"tail": "...detail name:
{...} lines..."}) or a plain details JSON (BENCH_DETAILS.json, or the
`bench.py microbench` output).  Exits 1 when any regression survives
the spread gate, 0 otherwise — `make bench` tails into it so a run
ends with a delta table instead of raw JSON only, and the de-GIL PR
can use it as its regression gate.
"""
from __future__ import annotations

import argparse
import json
import sys

# metric-key direction: larger-is-better unless the name says it's a
# latency/duration/overhead.  Ratios and counts are informational only.
_LOWER_BETTER_SUFFIXES = ("_us", "_ms", "_s")
_LOWER_BETTER_KEYS = {"overhead_pct", "overhead_pct_vs_off",
                      "lat_us", "shed_frac", "err_frac",
                      "router_overhead_pct", "wal_overhead_pct",
                      "telemetry_overhead_pct",
                      "serving_host_us_per_token"}
_HIGHER_BETTER_KEYS = {"qps", "gbps", "tokens_per_s", "items_per_s",
                       "hbm_traffic_gbps", "qps_off", "qps_on",
                       "speedup_at_peak", "zero_copy_speedup",
                       "prefill_skip_ratio",
                       "direct_gens_per_s", "router_gens_per_s",
                       "telemetry_off_gens_per_s",
                       "telemetry_on_gens_per_s",
                       "single_model_gens_per_s",
                       "two_model_gens_per_s",
                       "wal_off_gens_per_s", "wal_on_gens_per_s",
                       "native_speedup",
                       "batched_lookups_per_s",
                       "unbatched_lookups_per_s",
                       "tensorframe_lookups_per_s",
                       "json_lookups_per_s",
                       "lowered_lookups_per_s",
                       "tax_reduction_x",
                       "wire_updates_per_s",
                       "pcp_updates_per_s",
                       "tokens_per_s_alone",
                       "tokens_per_s_mixed"}


def direction(key: str) -> str | None:
    """'up' (bigger better), 'down' (smaller better), or None
    (not a gated metric)."""
    if key in _HIGHER_BETTER_KEYS:
        return "up"
    if key in _LOWER_BETTER_KEYS:
        return "down"
    if key.endswith(_LOWER_BETTER_SUFFIXES):
        return "down"
    return None


def load_round(path: str) -> dict:
    """A round's details dict, from either the driver wrapper (detail
    lines inside "tail") or a plain details/microbench JSON."""
    with open(path) as f:
        d = json.load(f)
    if not isinstance(d, dict):
        raise ValueError(f"{path}: not a JSON object")
    if "tail" in d and isinstance(d["tail"], str):
        details = {}
        for line in d["tail"].splitlines():
            if not line.startswith("detail "):
                continue
            name, sep, js = line[len("detail "):].partition(": ")
            if not sep:
                continue
            try:
                details[name] = json.loads(js)
            except json.JSONDecodeError:
                continue  # the driver's tail buffer may truncate lines
        if details:
            return details
        parsed = d.get("parsed")
        if isinstance(parsed, dict):
            return parsed
        raise ValueError(f"{path}: wrapper holds no parseable details")
    return d


def extract_metrics(details: dict) -> dict[str, tuple]:
    """Flatten a details tree into {dotted.path.key: (value, lo, hi)}
    for every gated numeric metric that carries a sibling
    `<key>_spread` [lo, hi] (a metric without a spread has no noise
    estimate and cannot be gated honestly)."""
    out: dict[str, tuple] = {}

    def walk(node, path):
        if not isinstance(node, dict):
            return
        if node.get("skipped") or node.get("error"):
            return  # an honest skip is not a zero
        for k, v in node.items():
            if isinstance(v, dict):
                walk(v, f"{path}.{k}" if path else k)
                continue
            if direction(k) is None or not isinstance(v, (int, float)):
                continue
            spread = node.get(f"{k}_spread")
            if (isinstance(spread, (list, tuple)) and len(spread) == 2
                    and all(isinstance(x, (int, float)) for x in spread)):
                lo, hi = sorted(spread)
                out[f"{path}.{k}" if path else k] = (float(v), float(lo),
                                                     float(hi))
        return

    walk(details, "")
    return out


def diff(old: dict[str, tuple], new: dict[str, tuple]) -> list[dict]:
    """Compare two extracted-metric maps.  One row per metric present
    in BOTH rounds; verdict 'regressed' only when the spread intervals
    are disjoint in the worse direction, 'improved' when disjoint in
    the better one, else 'ok'."""
    rows = []
    for key in sorted(set(old) & set(new)):
        ov, olo, ohi = old[key]
        nv, nlo, nhi = new[key]
        d = direction(key.rsplit(".", 1)[-1])
        if d == "up":
            regressed = nhi < olo
            improved = nlo > ohi
        else:
            regressed = nlo > ohi
            improved = nhi < olo
        delta_pct = ((nv - ov) / ov * 100.0) if ov else None
        rows.append({
            "metric": key, "dir": d,
            "old": ov, "old_spread": [olo, ohi],
            "new": nv, "new_spread": [nlo, nhi],
            "delta_pct": round(delta_pct, 2) if delta_pct is not None
            else None,
            "verdict": ("regressed" if regressed else
                        "improved" if improved else "ok"),
        })
    return rows


def render(rows: list[dict], old_name: str, new_name: str) -> str:
    lines = [f"--- perf diff: {old_name} -> {new_name} "
             f"({len(rows)} shared gated metrics) ---", ""]
    if not rows:
        lines.append("(no shared metrics with spreads — nothing to gate)")
        return "\n".join(lines) + "\n"
    w = max(len(r["metric"]) for r in rows)

    def cell(v, lo, hi):
        return f"{v:.6g} [{lo:.6g},{hi:.6g}]"

    cw = max([len(cell(r["old"], *r["old_spread"])) for r in rows]
             + [len(cell(r["new"], *r["new_spread"])) for r in rows]
             + [len("old (spread)")])
    lines.append(f"{'metric':<{w}}  {'old (spread)':>{cw}}  "
                 f"{'new (spread)':>{cw}}  {'delta':>9}  verdict")
    for r in rows:
        mark = {"regressed": "REGRESSED", "improved": "improved",
                "ok": ""}[r["verdict"]]
        delta = (f"{r['delta_pct']:+.2f}%" if r["delta_pct"] is not None
                 else "n/a")
        lines.append(
            f"{r['metric']:<{w}}  "
            f"{cell(r['old'], *r['old_spread']):>{cw}}  "
            f"{cell(r['new'], *r['new_spread']):>{cw}}  "
            f"{delta:>9}  {mark}")
    n_reg = sum(1 for r in rows if r["verdict"] == "regressed")
    n_imp = sum(1 for r in rows if r["verdict"] == "improved")
    lines.append("")
    lines.append(f"{n_reg} regressed beyond spread, {n_imp} improved "
                 f"beyond spread, {len(rows) - n_reg - n_imp} within "
                 f"noise")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("old", help="baseline round (BENCH_rNN.json or "
                                "details JSON)")
    ap.add_argument("new", help="candidate round")
    ap.add_argument("--no-fail", action="store_true",
                    help="always exit 0 (report-only mode)")
    ap.add_argument("--json", action="store_true",
                    help="emit the row list as JSON instead of a table")
    a = ap.parse_args(argv)
    try:
        old = extract_metrics(load_round(a.old))
        new = extract_metrics(load_round(a.new))
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"perf_diff: {e}", file=sys.stderr)
        return 2
    rows = diff(old, new)
    if a.json:
        print(json.dumps(rows, indent=1))
    else:
        print(render(rows, a.old, a.new), end="")
    regressed = any(r["verdict"] == "regressed" for r in rows)
    return 1 if (regressed and not a.no_fail) else 0


if __name__ == "__main__":
    sys.exit(main())

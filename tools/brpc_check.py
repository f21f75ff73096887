#!/usr/bin/env python3
"""brpc-check CLI (ISSUE 14) — run the repo-invariant analysis suite.

    python tools/brpc_check.py                 # human output, exit 1 on
                                               # non-baseline findings
    python tools/brpc_check.py --json          # machine output
    python tools/brpc_check.py --pass lock-order --pass lock-hygiene
    python tools/brpc_check.py --write-baseline
    python tools/brpc_check.py --write-fault-registry
    python tools/brpc_check.py --list-passes

`make check` runs the plain form.
Exit codes: 0 clean (baseline-frozen findings allowed), 1 new findings
or a broken parse.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from brpc_tpu.check import all_passes, run_checks  # noqa: E402
from brpc_tpu.check.baseline import (BASELINE_REL, load_baseline,  # noqa: E402
                                     split_findings, write_baseline)
from brpc_tpu.check.fault_sites import REGISTRY_REL, render_registry  # noqa: E402
from brpc_tpu.check.base import Repo  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    ap.add_argument("--pass", dest="passes", action="append", default=[],
                    help="run only the named pass(es)")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline path (default <root>/{BASELINE_REL})")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding as new (ignore baseline)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="freeze the current findings as the baseline")
    ap.add_argument("--write-fault-registry", action="store_true",
                    help=f"regenerate {REGISTRY_REL} and exit")
    ap.add_argument("--list-passes", action="store_true")
    args = ap.parse_args(argv)

    if args.list_passes:
        for p in all_passes():
            print(f"{p.pass_id:<16} {p.title}")
        return 0

    known = {p.pass_id for p in all_passes()}
    unknown = [p for p in args.passes if p not in known]
    if unknown:
        # a typo'd --pass must never read as "tree clean"
        print(f"unknown pass id(s): {', '.join(unknown)} "
              f"(known: {', '.join(sorted(known))})", file=sys.stderr)
        return 2

    if args.write_fault_registry:
        path = os.path.join(args.root, REGISTRY_REL)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        content = render_registry(Repo(args.root))
        with open(path, "w", encoding="utf-8") as f:
            f.write(content)
        print(f"wrote {path} ({content.count(chr(10)) - 11} sites)")
        return 0

    findings, timings = run_checks(args.root, set(args.passes) or None)
    findings.sort(key=lambda f: (f.pass_id, f.path, f.line))
    baseline_path = args.baseline or os.path.join(args.root, BASELINE_REL)

    if args.write_baseline:
        write_baseline(baseline_path, findings)
        print(f"froze {len(findings)} finding(s) into {baseline_path}")
        return 0

    baseline = {} if args.no_baseline else load_baseline(baseline_path)
    new, suppressed, stale = split_findings(findings, baseline)

    if args.json:
        print(json.dumps({
            "new": [f.to_dict() for f in new],
            "suppressed": [f.to_dict() for f in suppressed],
            "stale_baseline_keys": stale,
            "counts": {"new": len(new), "suppressed": len(suppressed),
                       "stale": len(stale)},
            "timings_s": {k: round(v, 3) for k, v in timings.items()},
        }, indent=1))
        return 1 if new else 0

    total_s = sum(timings.values())
    for f in new:
        print(f"NEW {f}")
    if stale:
        print(f"note: {len(stale)} stale baseline entr"
              f"{'y' if len(stale) == 1 else 'ies'} no longer fire — "
              f"burn them out with --write-baseline:")
        for k in stale:
            print(f"  stale: {k}")
    print(f"brpc-check: {len(new)} new, {len(suppressed)} baseline-frozen, "
          f"{len(stale)} stale baseline entries "
          f"({len(timings)} passes in {total_s:.1f}s)")
    if new:
        print("FAILED — fix the new findings above (or, for a "
              "deliberate exception, add `# brpc-check: allow(<pass>)` "
              "with a justification, or re-freeze with --write-baseline "
              "and justify in the PR)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

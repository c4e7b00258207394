#!/usr/bin/env python
"""Structural gate over the scaling harness's evidence (ISSUE 20).

Reads ``benchmark/opperf/scaling.py --json`` evidence and checks what a
CPU run can establish: the harness's own acceptance gates (efficiency
floor, zero post-warmup recompiles, live-vs-merged-trace attribution
match) must have passed, and no curve point may have recompiled after
warmup.  Counts only — the curve's samples/sec come from CPU child
processes and are not device metrics.  Device numbers are the driver's
``PERF_LEDGER.jsonl``; nothing here reads or baselines them.

Usage::

    python tools/perf_history.py --scaling EVIDENCE.json [--json]

Exit codes: 0 ok, 1 failed scaling gate, 2 unreadable input.
"""
from __future__ import annotations

import argparse
import json
import sys


def check_scaling(path):
    """Scaling-harness evidence: the gates the harness computed must have
    passed, and no point may have recompiled post-warmup."""
    with open(path) as f:
        ev = json.load(f)
    problems = []
    gates = ev.get("gates") or {}
    if not ev.get("pass"):
        problems.append(f"harness gates failed: {gates}")
    for pt in ev.get("points") or []:
        if pt.get("recompile_steady_state", 0) != 0:
            problems.append(
                f"point devices={pt.get('devices')} procs={pt.get('procs')}"
                f" recompiled post-warmup "
                f"({pt['recompile_steady_state']}x)")
    curve = [[pt.get("devices", 1) * pt.get("procs", 1),
              pt.get("samples_per_sec"), pt.get("efficiency")]
             for pt in ev.get("points") or []]
    return problems, {"curve": curve, "gates": gates,
                      "pass": not problems}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scaling", required=True,
                    help="scaling.py --json evidence to gate structurally")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    args = ap.parse_args(argv)

    try:
        problems, report = check_scaling(args.scaling)
    except (OSError, json.JSONDecodeError) as e:
        print(f"perf_history: scaling evidence unreadable: {e}",
              file=sys.stderr)
        return 2

    if args.json:
        print(json.dumps({"schema": 1, "scaling": report,
                          "failures": problems, "pass": not problems},
                         indent=1))
    else:
        print(f"perf_history: scaling curve {report['curve']} — "
              f"{'ok' if report['pass'] else 'FAILED'}")
        for p in problems:
            print(f"perf_history: FAIL scaling: {p}", file=sys.stderr)
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())

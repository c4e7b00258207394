#!/usr/bin/env python
"""Measurement-window corroboration for bench.py (VERDICT r4 item 2).

Runs the BERT bench at MXNET_TPU_BENCH_STEPS = 60/120/180/360 (or
--steps ...), recovers the measured wall time per run from the reported
throughput (dt = items_per_step·steps / (value·chips), where
items_per_step is B for samples/s metrics and 2·B·S for the transformer's
tokens/s — mirroring bench.py:305), and fits dt = intercept + slope·steps.  The claim under test: per-step time (the slope) is
window-invariant and the intercept equals the fence's fixed D2H cost —
i.e. the 180-step window amortizes measurement overhead without touching
the steady-state rate.  If the slope drifts with window, the gate number
reverts to the 60-step discipline.

Run on the chip, through the chip tool:
    python tools/bench_window_sweep.py
    MXNET_TPU_BENCH=transformer python tools/bench_window_sweep.py
This parent never touches JAX: a chip belongs to one process at a time, and
each ``bench.py`` child needs it — the chip count comes from the child's
own record (``n_devices``).
Emits a markdown table + fit for docs/PERF_NOTES.md, plus one JSON line.
"""
import argparse
import json
import os
import subprocess
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_once(steps, batch):
    env = dict(os.environ)
    env["MXNET_TPU_BENCH_STEPS"] = str(steps)
    env["MXNET_TPU_BENCH_BATCH"] = str(batch)  # keep bench and fit in sync
    r = subprocess.run([sys.executable, os.path.join(ROOT, "bench.py")],
                       capture_output=True, text=True, timeout=3600, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"bench.py exited {r.returncode} at steps={steps}:\n"
                           + r.stderr[-2000:])
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    n_chips = rec["n_devices"]
    # bench reports per-CHIP throughput (global/dt/n_chips); undo the chip
    # division or the intercept inflates n_chips-fold.  The transformer
    # config reports tokens/s = 2·B·S·steps/dt (src+tgt, bench.py:305), so
    # recover dt with the per-step token count or the fit's intercept is
    # off by 2·S and loses its D2H-fixed-cost reading.
    per_step = batch * 1.0
    unit = rec.get("unit", "samples/sec/chip")
    if "tokens" in unit:
        per_step *= 2 * int(os.environ.get("MXNET_TPU_BENCH_SEQ", "256"))
    dt = per_step * steps / (rec["value"] * n_chips)
    return rec["value"], dt, unit, per_step, n_chips


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, nargs="+", default=[60, 120, 180, 360])
    ap.add_argument("--batch", type=int,
                    default=int(os.environ.get("MXNET_TPU_BENCH_BATCH", "64")))
    ap.add_argument("--repeats", type=int, default=1)
    args = ap.parse_args()

    rows = []
    for s in args.steps:
        for _ in range(args.repeats):
            val, dt, unit, per_step, n_chips = run_once(s, args.batch)
            rows.append((s, val, dt))
            print(f"# steps={s}: {val} {unit}, dt={dt:.3f} s", flush=True)

    xs = np.array([r[0] for r in rows], float)
    ys = np.array([r[2] for r in rows], float)
    slope, intercept = np.polyfit(xs, ys, 1)
    resid = ys - (intercept + slope * xs)

    print(f"\n| steps | {unit} | dt (s) | fit residual (ms) |")
    print("|---|---|---|---|")
    for (s, val, dt), r in zip(rows, resid):
        print(f"| {s} | {val} | {dt:.3f} | {r * 1e3:+.1f} |")
    per_step_ms = slope * 1e3
    steady = per_step / slope / n_chips
    print(f"\nfit: dt = {intercept:.3f} s + {per_step_ms:.3f} ms/step "
          f"(window-invariant steady rate = {steady:.1f} {unit}; "
          f"intercept = fixed fence/D2H cost)")
    print(json.dumps({
        "metric": "bench_window_fit",
        "unit": unit,
        "slope_ms_per_step": round(per_step_ms, 4),
        "intercept_s": round(intercept, 4),
        "steady_per_sec_per_chip": round(steady, 1),
        "max_abs_residual_ms": round(float(np.abs(resid).max() * 1e3), 2),
    }))


if __name__ == "__main__":
    main()

"""Trainer-step microbenchmark: fused whole-group update vs per-tensor loop.

Measures optimizer steps/sec of ``gluon.Trainer.step`` on a model with many
SMALL parameters — the regime the fused step exists for (docs/
optimizer_fusion.md): the per-tensor loop pays one jitted kernel launch,
one buffer swap, and fresh outputs per tensor per step, while the fused
path updates each parameter group in ONE donated-buffer jitted dispatch.

* ``per_tensor`` — ``Optimizer.aggregate_num = 0`` (the pre-fusion path,
  with the PR 2 dispatch machinery still active: the honest baseline)
* ``fused``      — the default fused whole-group step

Runs on any backend (CI smoke uses ``JAX_PLATFORMS=cpu``) and prints ONE
JSON line so CI and BENCH harvesting can grep it::

    python benchmark/opperf/trainer_step.py [--n-params 200] [--iters 10]

Acceptance floor (ISSUE 3): fused >= 2x per_tensor steps/sec on the
200-small-parameter model (CPU backend).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def _build(n_params, shape, seed, aggregate_num, optimizer, opt_args):
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon import Parameter

    rs = np.random.RandomState(seed)
    params = []
    for k in range(n_params):
        p = Parameter(f"p{k}_weight", shape=shape, dtype="float32")
        p.initialize()
        p.set_data(mx.nd.array(rs.randn(*shape).astype(np.float32)))
        params.append(p)
    trainer = gluon.Trainer(params, optimizer, dict(opt_args), kvstore=None)
    trainer._optimizer.aggregate_num = aggregate_num
    grads = rs.randn(n_params, *shape).astype(np.float32)
    for p, g in zip(params, grads):
        p.grad()[:] = mx.nd.array(g)
    return trainer, params


def run(n_params=200, shape=(16, 4), iters=10, warmup=3, repeats=3,
        optimizer="sgd", opt_args=None):
    """Returns the result dict (also usable from tests as a smoke check).

    Measurement is PAIRED like benchmark/opperf/eager_dispatch.py: every
    timing round runs one ``step`` of each mode back-to-back and the
    per-mode score is the median round, so host drift hits both modes
    alike.  GC is paused during the timed rounds.  Both trainers share
    identical seeds/grads; their states advance in lockstep, so every
    round times the same mathematical step.
    """
    import gc

    import incubator_mxnet_tpu as mx

    opt_args = opt_args or {"learning_rate": 0.01, "momentum": 0.9, "wd": 1e-4}
    modes = {
        "per_tensor": _build(n_params, shape, 42, 0, optimizer, opt_args),
        "fused": _build(n_params, shape, 42, 1 << 20, optimizer, opt_args),
    }

    def one(mode):
        trainer, params = modes[mode]
        t0 = time.perf_counter()
        trainer.step(1)
        mx.nd.waitall()
        return time.perf_counter() - t0

    rounds = max(1, iters * repeats)
    for _ in range(max(1, warmup)):
        for m in modes:
            one(m)
    times = {m: [] for m in modes}
    gc.collect()
    gc_was_on = gc.isenabled()
    gc.disable()
    try:
        for _ in range(rounds):
            for m in modes:
                times[m].append(one(m))
    finally:
        if gc_was_on:
            gc.enable()

    medians = {m: _median(ts) for m, ts in times.items()}
    steps_per_sec = {m: 1.0 / v for m, v in medians.items()}
    from incubator_mxnet_tpu import config

    return {
        "bench": "trainer_step",
        **config.device_record(),
        "n_params": n_params,
        "shape": list(shape),
        "optimizer": optimizer,
        "iters": iters,
        "warmup": warmup,
        "repeats": repeats,
        "rounds": rounds,          # paired timing rounds behind each median
        "steps_per_sec": {m: round(v, 2) for m, v in steps_per_sec.items()},
        "median_s": medians,       # raw per-mode median round, seconds
        "speedup_fused": round(
            steps_per_sec["fused"] / steps_per_sec["per_tensor"], 2),
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--n-params", type=int, default=200)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--side", type=int, default=16,
                   help="parameter tensor leading dim (small by design: the "
                        "bench isolates per-tensor dispatch overhead)")
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--repeats", type=int, default=3,
                   help="multiplier on --iters for the number of paired "
                        "timing rounds (median round wins)")
    p.add_argument("--optimizer", default="sgd")
    p.add_argument("--json", dest="json_path", default=None, metavar="PATH",
                   help="also write the result object to PATH — the "
                        "machine-readable record (medians, round counts, "
                        "config) bench trajectory harvesting reads instead "
                        "of hand-copied numbers")
    args = p.parse_args(argv)
    from incubator_mxnet_tpu import config

    config.enable_compile_cache()
    line = run(n_params=args.n_params, iters=args.iters,
               shape=(args.side, 4), warmup=args.warmup,
               repeats=args.repeats, optimizer=args.optimizer)
    print(json.dumps(line))
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(line, f, indent=2)
            f.write("\n")
    return line


if __name__ == "__main__":
    main()

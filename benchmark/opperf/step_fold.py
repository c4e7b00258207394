"""Step-fold microbenchmark: one compiled program per training step.

Measures steps/sec of the SAME training step driven three ways on a
dispatch-bound model (many small Dense layers — the regime whole-program
folding exists for, docs/step_fold.md):

* ``eager``  — per-op dispatch: un-hybridized forward, tape backward, the
  (fused-group) ``Trainer.step``.  The honest pre-fold baseline.
* ``hybrid`` — the pre-fold BEST practice: hybridized forward (one
  CachedOp jit) + tape backward + fused ``Trainer.step`` — still several
  host dispatches per step.
* ``folded`` — ``Trainer.fold_step``: forward + loss + backward +
  optimizer tail as ONE donated-buffer compiled dispatch.

Measurement is PAIRED like the other opperf harnesses: every timing round
runs one step of each mode back-to-back, the per-mode score is the median
round, GC is off during rounds.  After warmup the harness ASSERTS the
fold's steady-state contract and exits non-zero on violation:

* exactly ONE host-issued device dispatch per folded step (the
  ``step_fold.DISPATCH_COUNTERS`` delta),
* zero steady-state recompiles (``recompile_steady_state`` delta — the
  fold arms the PR 9 compile guard after its first step).

``--dist`` adds the 2-process overlap experiment: workers launched via
``tools/launch_local.py`` train against a ``dist_sync`` store and time
``sequential`` (allreduce after backward: ``loss.backward()`` then
``Trainer.step``) vs ``overlap`` (``Trainer.backward``: each gradient
bucket's pushpull launches from the grad-readiness hook DURING backward),
with convergence parity between both modes asserted.  Paired medians ride
the evidence JSON (docs/STEP_FOLD_EVIDENCE_r15.json).

``--k [K ...]`` switches to the K-step fold sweep (``Trainer.fold_steps``,
docs/step_fold.md "Multi-step fold"): the same logical step timed at fold
widths K (default 1 vs 4 vs 16), paired per round, scored per LOGICAL
step.  After warmup it asserts dispatches/logical-step == 1/K exactly and
zero steady-state recompiles; non-smoke additionally requires the largest
K to beat K=1 by >= 1.3x (the ISSUE 17 acceptance floor).

Acceptance (ISSUE 15): folded >= 2x eager steps/sec on CPU; dist overlap
per-step wall < sequential.  (ISSUE 17): K=16 >= 1.3x the K=1 folded
step, dispatches per logical step exactly 1/K.

    python benchmark/opperf/step_fold.py [--smoke] [--dist] [--json PATH]
    python benchmark/opperf/step_fold.py --k            # 1 vs 4 vs 16
    python benchmark/opperf/step_fold.py --k 1 8 --smoke
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def _build(seed, hybrid, layers, width, batch, kvstore=None):
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon

    mx.random.seed(seed)
    np.random.seed(seed)
    net = gluon.nn.HybridSequential()
    for _ in range(layers):
        net.add(gluon.nn.Dense(width, activation="relu"))
    net.add(gluon.nn.Dense(8))
    net.initialize()
    if hybrid:
        net.hybridize()
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.rand(batch, 16).astype(np.float32))
    y = mx.nd.array(rs.rand(batch, 8).astype(np.float32))
    net(x)  # materialize deferred shapes
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01, "momentum": 0.9},
                            kvstore=kvstore)
    return net, trainer, x, y


def run(layers=12, width=32, batch=8, iters=10, warmup=4, repeats=3):
    """Local three-mode comparison + the steady-state assertions.
    Returns the result dict (smoke-checkable from tests)."""
    import gc

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, gluon, profiler
    from incubator_mxnet_tpu.gluon import step_fold

    L2 = gluon.loss.L2Loss()

    nets = {}
    for mode, hybrid in (("eager", False), ("hybrid", True),
                         ("folded", True)):
        nets[mode] = _build(42, hybrid, layers, width, batch)
    net_f, tr_f, x_f, y_f = nets["folded"]
    folded = tr_f.fold_step(lambda a, b: L2(net_f(a), b), block=net_f)

    def eager_like(mode):
        net, tr, x, y = nets[mode]
        with autograd.record():
            loss = L2(net(x), y)
        loss.backward()
        tr.step(batch)

    steps = {
        "eager": lambda: eager_like("eager"),
        "hybrid": lambda: eager_like("hybrid"),
        "folded": lambda: folded(x_f, y_f),
    }

    def one(mode):
        t0 = time.perf_counter()
        steps[mode]()
        mx.nd.waitall()
        return time.perf_counter() - t0

    for _ in range(max(1, warmup)):
        for m in steps:
            one(m)
    if not folded.folded:
        print(f"FOLD FELL BACK: {folded.fallback_reason}", file=sys.stderr)
        raise SystemExit(3)

    # steady-state contract, asserted BEFORE timing so a violation can't
    # hide behind a fast median
    c0 = profiler.counters()
    check_steps = 3
    for _ in range(check_steps):
        folded(x_f, y_f)
    mx.nd.waitall()
    c1 = profiler.counters()
    dispatches = (step_fold.host_dispatch_total(c1)
                  - step_fold.host_dispatch_total(c0)) / check_steps
    recompiles = c1["recompile_steady_state"] - c0["recompile_steady_state"]

    rounds = max(1, iters * repeats)
    times = {m: [] for m in steps}
    gc.collect()
    gc_was_on = gc.isenabled()
    gc.disable()
    try:
        for _ in range(rounds):
            for m in steps:
                times[m].append(one(m))
    finally:
        if gc_was_on:
            gc.enable()
    medians = {m: _median(ts) for m, ts in times.items()}
    steps_per_sec = {m: 1.0 / v for m, v in medians.items()}
    from incubator_mxnet_tpu import config

    return {
        "bench": "step_fold",
        **config.device_record(),
        "layers": layers, "width": width, "batch": batch,
        "rounds": rounds,
        "steps_per_sec": {m: round(v, 2) for m, v in steps_per_sec.items()},
        "median_s": medians,
        "speedup_folded_vs_eager": round(
            steps_per_sec["folded"] / steps_per_sec["eager"], 2),
        "speedup_folded_vs_hybrid": round(
            steps_per_sec["folded"] / steps_per_sec["hybrid"], 2),
        "folded_dispatches_per_step": dispatches,
        "recompiles_steady_state": recompiles,
    }


def run_k_sweep(ks=(1, 4, 16), layers=12, width=32, batch=8, iters=10,
                warmup=3, repeats=3):
    """K-step fold sweep (``Trainer.fold_steps``): time the SAME logical
    training step at several fold widths K and assert the dispatch
    contract — exactly one host dispatch per K logical steps (1/K per
    logical step) and zero steady-state recompiles.  K=1 is the PR 15
    single-step fold; larger K amortises the per-dispatch host cost over
    the in-program ``lax.scan``.  Measurement is paired per round (one
    window of each K back-to-back), score = median wall / K (per LOGICAL
    step).  Returns the result dict."""
    import gc

    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, profiler
    from incubator_mxnet_tpu.gluon import step_fold

    L2 = gluon.loss.L2Loss()
    folds = {}
    for k in ks:
        net, tr, x, y = _build(42, True, layers, width, batch)
        fold = tr.fold_steps(lambda a, b, n=net: L2(n(a), b), k=k,
                             block=net)
        if k == 1:
            folds[k] = (fold, (x, y))
        else:
            # [K, batch, ...] stacked window, the stage_window layout
            xw = mx.nd.array(np.repeat(np.asarray(x._data)[None],
                                       k, axis=0))
            yw = mx.nd.array(np.repeat(np.asarray(y._data)[None],
                                       k, axis=0))
            folds[k] = (fold, (xw, yw))

    def one(k):
        fold, nds = folds[k]
        t0 = time.perf_counter()
        fold(*nds)
        mx.nd.waitall()
        return (time.perf_counter() - t0) / k   # per LOGICAL step

    for _ in range(max(1, warmup)):
        for k in ks:
            one(k)
    for k in ks:
        fold, _ = folds[k]
        if not fold.folded:
            print(f"K={k} FOLD FELL BACK: {fold.fallback_reason}",
                  file=sys.stderr)
            raise SystemExit(3)

    # dispatch contract AFTER warmup: one window dispatch covers K logical
    # steps, so dispatches / logical step must be exactly 1/K
    c_base = profiler.counters()["recompile_steady_state"]
    dispatch_ratio = {}
    check_windows = 3
    for k in ks:
        c0 = profiler.counters()
        for _ in range(check_windows):
            one(k)
        c1 = profiler.counters()
        d = (step_fold.host_dispatch_total(c1)
             - step_fold.host_dispatch_total(c0))
        dispatch_ratio[k] = d / (check_windows * k)

    rounds = max(1, iters * repeats)
    times = {k: [] for k in ks}
    gc.collect()
    gc_was_on = gc.isenabled()
    gc.disable()
    try:
        for _ in range(rounds):
            for k in ks:
                times[k].append(one(k))
    finally:
        if gc_was_on:
            gc.enable()
    recompiles = (profiler.counters()["recompile_steady_state"] - c_base)
    medians = {k: _median(ts) for k, ts in times.items()}
    kmax, kmin = max(ks), min(ks)
    from incubator_mxnet_tpu import config

    return {
        "bench": "step_fold_k_sweep",
        **config.device_record(),
        "layers": layers, "width": width, "batch": batch,
        "rounds": rounds, "ks": list(ks),
        "logical_steps_per_sec": {str(k): round(1.0 / m, 2)
                                  for k, m in medians.items()},
        "median_logical_step_s": {str(k): m for k, m in medians.items()},
        "dispatches_per_logical_step": {str(k): round(r, 6)
                                        for k, r in dispatch_ratio.items()},
        "speedup_kmax_vs_k1": round(medians[kmin] / medians[kmax], 2),
        "k_max": kmax,
        "recompiles_steady_state": recompiles,
    }


# ---------------------------------------------------------------------------
# dist overlap experiment (2 processes over launch_local)
# ---------------------------------------------------------------------------


def dist_worker(layers, width, batch, iters, warmup, bucket_kb):
    """Worker body (run under tools/launch_local.py at n=2): time
    sequential allreduce-after-backward vs grad-readiness-hooked overlap
    on the SAME model against a dist_sync store, then assert convergence
    parity between the two modes.  Rank 0 prints one JSON marker line."""
    os.environ["MXNET_KVSTORE_BUCKET_BYTES"] = str(bucket_kb * 1024)
    import gc

    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, gluon

    L2 = gluon.loss.L2Loss()
    kv = mx.kv.create("dist_sync")
    rank = kv.rank

    # NON-hybridized on purpose: a hybridized block's backward is ONE tape
    # node, so every grad finalizes at once and there is nothing for the
    # readiness hook to overlap.  The per-op tape finalizes grads in
    # reverse-layer order — bucket k's pushpull rides the wire while the
    # earlier layers' VJPs still run.
    net, trainer, x, y = _build(7, False, layers, width, batch, kvstore=kv)

    def sequential():
        with autograd.record():
            loss = L2(net(x), y)
        loss.backward()          # full backward first ...
        trainer.step(batch)      # ... then every bucket's allreduce
        return loss

    def overlap():
        with autograd.record():
            loss = L2(net(x), y)
        trainer.backward(loss)   # buckets pushpull DURING backward
        trainer.step(batch)
        return loss

    modes = {"sequential": sequential, "overlap": overlap}

    def one(mode):
        kv.barrier()
        t0 = time.perf_counter()
        modes[mode]()
        mx.nd.waitall()
        return time.perf_counter() - t0

    for _ in range(max(1, warmup)):
        for m in modes:
            one(m)
    times = {m: [] for m in modes}
    gc.collect()
    gc.disable()
    for _ in range(iters):
        for m in modes:
            times[m].append(one(m))
    gc.enable()
    medians = {m: _median(ts) for m, ts in times.items()}

    # convergence parity: two fresh same-seeded models, N steps each mode
    net_a, tr_a, xa, ya = _build(13, True, layers, width, batch, kvstore=kv)
    net_b, tr_b, xb, yb = _build(13, True, layers, width, batch, kvstore=kv)
    la = lb = None
    for _ in range(10):
        with autograd.record():
            la = L2(net_a(xa), ya)
        la.backward()
        tr_a.step(batch)
        with autograd.record():
            lb = L2(net_b(xb), yb)
        tr_b.backward(lb)
        tr_b.step(batch)
    mx.nd.waitall()
    fa = float(la.mean().asscalar())
    fb = float(lb.mean().asscalar())
    conv_ok = bool(np.isfinite(fa) and np.isfinite(fb)
                   and abs(fa - fb) <= 1e-5 + 1e-3 * abs(fa))

    from incubator_mxnet_tpu import profiler as _p
    launched = _p.counters()["allreduce_overlap_launched"]
    if rank == 0:
        print("STEP_FOLD_DIST_JSON: " + json.dumps({
            "workers": kv.num_workers,
            "bucket_kb": bucket_kb,
            "median_s": medians,
            "overlap_speedup": round(
                medians["sequential"] / medians["overlap"], 3),
            "overlap_buckets_launched": launched,
            "convergence": {"sequential": fa, "overlap": fb,
                            "parity": conv_ok},
        }), flush=True)
    kv.barrier()
    if not conv_ok:
        raise SystemExit(4)


def run_dist(layers=12, width=256, batch=32, iters=8, warmup=3,
             bucket_kb=64):
    """Launch the 2-process overlap experiment; returns its JSON dict."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)     # workers boot their own CPU backend
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.join(ROOT, "tools", "launch_local.py"),
           "-n", "2", sys.executable, os.path.abspath(__file__),
           "--dist-worker", "--layers", str(layers), "--width", str(width),
           "--batch", str(batch), "--iters", str(iters),
           "--warmup", str(warmup), "--bucket-kb", str(bucket_kb)]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600)
    sys.stderr.write(proc.stderr[-2000:])
    for line in proc.stdout.splitlines():
        if line.startswith("STEP_FOLD_DIST_JSON: "):
            out = json.loads(line[len("STEP_FOLD_DIST_JSON: "):])
            out["returncode"] = proc.returncode
            return out
    sys.stderr.write(proc.stdout[-2000:])
    raise RuntimeError(
        f"dist workers produced no result (rc={proc.returncode})")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--layers", type=int, default=None)
    p.add_argument("--width", type=int, default=None)
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--iters", type=int, default=None)
    p.add_argument("--warmup", type=int, default=None)
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--smoke", action="store_true",
                   help="tiny config; the steady-state assertions ARE the "
                        "regression guard (non-zero exit on any violation)")
    p.add_argument("--k", dest="k_sweep", nargs="*", type=int, default=None,
                   metavar="K",
                   help="run the K-step fold sweep instead (default sweep "
                        "1 4 16, or the listed K values): times the same "
                        "logical step at each fold width and asserts "
                        "dispatches/logical-step == 1/K after warmup")
    p.add_argument("--dist", action="store_true",
                   help="also run the 2-process overlap experiment")
    p.add_argument("--bucket-kb", type=int, default=64)
    p.add_argument("--dist-worker", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--json", dest="json_path", default=None, metavar="PATH")
    args = p.parse_args(argv)
    from incubator_mxnet_tpu import config

    config.enable_compile_cache()

    if args.dist_worker:
        dist_worker(args.layers or 12, args.width or 256, args.batch or 32,
                    args.iters or 8, args.warmup or 3, args.bucket_kb)
        return None

    if args.smoke:
        defaults = dict(layers=6, width=32, batch=8, iters=3, warmup=2,
                        repeats=1)
    else:
        defaults = dict(layers=12, width=32, batch=8, iters=10, warmup=4,
                        repeats=args.repeats)
    for k in ("layers", "width", "batch", "iters", "warmup"):
        if getattr(args, k) is not None:
            defaults[k] = getattr(args, k)
        defaults.setdefault(k, None)

    if args.k_sweep is not None:
        ks = tuple(sorted(set(args.k_sweep))) or (
            (1, 4) if args.smoke else (1, 4, 16))
        result = run_k_sweep(ks=ks, **defaults)
        print(json.dumps(result))
        if args.json_path:
            with open(args.json_path, "w") as f:
                json.dump(result, f, indent=2)
                f.write("\n")
        rc = 0
        for k_str, ratio in result["dispatches_per_logical_step"].items():
            want = 1.0 / int(k_str)
            if abs(ratio - want) > 1e-9:
                print(f"FAIL: K={k_str}: {ratio} dispatches per logical "
                      f"step (want exactly {want:.6f})", file=sys.stderr)
                rc = 1
        if result["recompiles_steady_state"]:
            print(f"FAIL: {result['recompiles_steady_state']} steady-state "
                  "recompiles during the sweep", file=sys.stderr)
            rc = 1
        # smoke asserts the dispatch contract only — paired-median timing
        # on a 3-iter tiny config is noise, not signal
        if not args.smoke and len(ks) > 1 \
                and result["speedup_kmax_vs_k1"] < 1.3:
            print(f"FAIL: K={result['k_max']} only "
                  f"{result['speedup_kmax_vs_k1']}x the K={min(ks)} folded "
                  "step (acceptance floor 1.3x)", file=sys.stderr)
            rc = 1
        if rc:
            raise SystemExit(rc)
        return result

    result = run(**defaults)

    if args.dist:
        result["dist"] = run_dist(bucket_kb=args.bucket_kb)

    print(json.dumps(result))
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")

    rc = 0
    if result["folded_dispatches_per_step"] != 1:
        print(f"FAIL: {result['folded_dispatches_per_step']} dispatches "
              "per folded step (want exactly 1)", file=sys.stderr)
        rc = 1
    if result["recompiles_steady_state"]:
        print(f"FAIL: {result['recompiles_steady_state']} steady-state "
              "recompiles after warmup", file=sys.stderr)
        rc = 1
    if not args.smoke and result["speedup_folded_vs_eager"] < 2.0:
        print(f"FAIL: folded only {result['speedup_folded_vs_eager']}x "
              "eager (acceptance floor 2x)", file=sys.stderr)
        rc = 1
    if args.dist:
        d = result["dist"]
        if d.get("returncode"):
            print("FAIL: dist workers exited non-zero", file=sys.stderr)
            rc = 1
        if not d["convergence"]["parity"]:
            print("FAIL: overlap/sequential convergence parity",
                  file=sys.stderr)
            rc = 1
        if d["overlap_speedup"] <= 1.0:
            print(f"FAIL: overlap {d['overlap_speedup']}x sequential "
                  "(want > 1)", file=sys.stderr)
            rc = 1
    if rc:
        raise SystemExit(rc)
    return result


if __name__ == "__main__":
    main()

"""Pipeline-schedule microbenchmark: single-stage vs GPipe vs 1F1B.

Trains the SAME stage-split transformer encoder three ways through
``SPMDTrainer`` — unpipelined single program, GPipe schedule (paper
configuration: full rematerialization), 1F1B schedule (remat off; at most
P microbatches in flight) — and reports steps/sec plus each schedule's
measured bubble fraction.

Bubble measurement (docs/pipeline_parallelism.md): on the virtual CPU
mesh every "stage" runs on the same host serially, so a wall-clock bubble
would measure the box, not the schedule.  Instead the harness CALIBRATES
per-slot costs from real timed slot programs — a jitted single-stage
microbatch forward (tf) and forward+backward (tf+tb) — and feeds the
measured tf/tb into the deterministic schedule simulator
(``parallel.simulate_schedule``).  The reported fraction is exact for the
executed slot sequence under those measured costs; recompute slots count
as bubble (overhead the schedule demanded).

Measurement is PAIRED like the other opperf harnesses: each timing round
runs one step of every mode back-to-back, median round wins, GC paused.
The harness arms ``MXNET_COMPILE_GUARD=raise`` through the trainers'
auto-arm and exits non-zero if ANY mode recompiled after warmup.

Acceptance (ISSUE 13): on >=4 stages x >=8 microbatches, 1F1B's measured
bubble < GPipe's, and 1F1B within 1.5x of the analytic (P-1)/(M+P-1)
bound.  Evidence: docs/PIPELINE_EVIDENCE_r13.json.

    python benchmark/opperf/pipeline.py [--stages 4] [--microbatches 8]
        [--json PATH] [--smoke]
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def _build_net(n_layers, units, hidden, heads, seed):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon import nn

    mx.random.seed(seed)
    net = nn.HybridSequential()
    for _ in range(n_layers):
        net.add(nn.TransformerEncoderCell(units, hidden, heads))
    net.add(nn.Dense(8, flatten=False))
    net.initialize()
    net(mx.nd.zeros((2, 4, units)))
    return net


def _calibrate_slot_costs(units, hidden, heads, micro_batch, seq, iters=5):
    """Median wall of a jitted one-stage microbatch forward (tf) and
    forward+backward (tf+tb) — the per-slot costs the simulator scales."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    w1 = jnp.asarray(rng.randn(units, hidden).astype(np.float32) * 0.05)
    w2 = jnp.asarray(rng.randn(hidden, units).astype(np.float32) * 0.05)
    x = jnp.asarray(rng.randn(micro_batch, seq, units).astype(np.float32))

    def stage(w, h):
        # FFN-shaped stand-in with the microbatch's real GEMM volume
        return jnp.tanh(jnp.maximum(h @ w[0], 0.0) @ w[1]) + h

    fwd = jax.jit(stage)
    bwd = jax.jit(jax.value_and_grad(
        lambda w, h: jnp.sum(stage(w, h) ** 2)))
    fwd((w1, w2), x).block_until_ready()
    _, g = bwd((w1, w2), x)
    jax.block_until_ready(g)
    tfs, tbs = [], []
    for _ in range(iters):
        t0 = time.perf_counter()
        fwd((w1, w2), x).block_until_ready()
        tfs.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        _, g = bwd((w1, w2), x)
        jax.block_until_ready(g)
        tbs.append(time.perf_counter() - t0)
    tf = _median(tfs)
    tb = max(_median(tbs) - tf, 0.25 * tf)  # backward-only slot cost
    return tf, tb


@contextlib.contextmanager
def _armed_guard():
    """Arm the steady-state compile guard for the harness WITHOUT leaking
    process state: the CI smoke imports ``run()`` in-process, and a bare
    ``os.environ.setdefault`` here would leave the whole remaining test
    suite in raise mode (armed by whichever trainer stepped last)."""
    from incubator_mxnet_tpu import profiler

    unset = "MXNET_COMPILE_GUARD" not in os.environ
    if unset:
        os.environ["MXNET_COMPILE_GUARD"] = "raise"
    try:
        yield
    finally:
        if unset:
            os.environ.pop("MXNET_COMPILE_GUARD", None)
        profiler.disarm_compile_guard()


def _guarded(fn):
    def wrapper(*args, **kwargs):
        with _armed_guard():
            return fn(*args, **kwargs)
    wrapper.__doc__ = fn.__doc__
    wrapper.__name__ = fn.__name__
    return wrapper


@_guarded
def run(n_stages=4, layers_per_stage=1, n_microbatches=8, batch=16, seq=8,
        units=32, hidden=64, heads=4, iters=8, warmup=2, repeats=3):
    import gc

    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, profiler
    from incubator_mxnet_tpu.parallel import (
        SPMDTrainer, analytic_bubble_fraction, make_mesh, simulate_schedule)

    n_layers = n_stages * layers_per_stage
    rng = np.random.RandomState(1)
    x = rng.randn(batch, seq, units).astype(np.float32)
    y = rng.randint(0, 8, (batch,)).astype(np.float32)

    def loss_fn(out, label):
        return gluon.loss.SoftmaxCrossEntropyLoss()(out.mean(axis=1), label)

    def _merge(a, b):
        from incubator_mxnet_tpu.gluon import nn

        m = nn.HybridSequential()
        m.add(*list(a), *list(b))
        return m

    def make_trainer(mode):
        net = _build_net(n_layers, units, hidden, heads, seed=11)
        if mode == "single":
            return SPMDTrainer(net, loss_fn, "adam", {"learning_rate": 1e-3},
                               mesh=make_mesh())
        stages = net.split_stages([layers_per_stage] * n_stages + [1])
        # fold the classifier into the last stage
        merged = stages[:-2] + [_merge(stages[-2], stages[-1])]
        return SPMDTrainer(
            net, loss_fn, "adam", {"learning_rate": 1e-3},
            mesh=make_mesh(), stages=merged,
            pipeline={"schedule": mode, "n_microbatches": n_microbatches})

    modes = {}

    def one(mode):
        tr = modes[mode]
        t0 = time.perf_counter()
        loss = tr.step(mx.nd.array(x), mx.nd.array(y))
        loss.asnumpy()  # sync: time the whole compiled step
        return time.perf_counter() - t0

    # setup + warmup under a paused guard (the serving-warmup idiom):
    # each trainer's FIRST compile is expected; anything after this block
    # is a steady-state recompile and fails the run
    with profiler.compile_guard_paused():
        for mode in ("single", "gpipe", "1f1b"):
            modes[mode] = make_trainer(mode)
        for _ in range(max(1, warmup)):
            for m in modes:
                one(m)
    base_recompiles = profiler.counters()["recompile_steady_state"]

    rounds = max(1, iters * repeats)
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

    recompiles = profiler.counters()["recompile_steady_state"] - base_recompiles
    medians = {m: _median(ts) for m, ts in times.items()}
    steps_per_sec = {m: 1.0 / v for m, v in medians.items()}

    tf, tb = _calibrate_slot_costs(units, hidden, heads,
                                   batch // n_microbatches, seq)
    P = n_stages  # classifier folded into the last stage
    bubbles = {}
    for mode, remat in (("gpipe", True), ("1f1b", False)):
        sim = simulate_schedule(P, n_microbatches, mode,
                                tf=tf, tb=tb, remat=remat)
        bubbles[mode] = {
            "bubble_fraction": round(sim["bubble_fraction"], 4),
            "idle_fraction": round(sim["idle_fraction"], 4),
            "remat": remat,
        }
    analytic = analytic_bubble_fraction(P, n_microbatches)

    ok = (bubbles["1f1b"]["bubble_fraction"]
          < bubbles["gpipe"]["bubble_fraction"]
          and bubbles["1f1b"]["bubble_fraction"] <= 1.5 * analytic)
    from incubator_mxnet_tpu import config

    return {
        "bench": "pipeline",
        **config.device_record(),
        "stages": P,
        "layers_per_stage": layers_per_stage,
        "microbatches": n_microbatches,
        "batch": batch,
        "seq": seq,
        "units": units,
        "rounds": rounds,
        "steps_per_sec": {m: round(v, 2) for m, v in steps_per_sec.items()},
        "median_s": medians,
        "slot_costs_ms": {"tf": round(tf * 1e3, 4), "tb": round(tb * 1e3, 4)},
        "bubble": bubbles,
        "analytic_bound": round(analytic, 4),
        "bubble_acceptance": bool(ok),
        "post_warmup_recompiles": int(recompiles),
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--stages", type=int, default=4)
    p.add_argument("--layers-per-stage", type=int, default=1)
    p.add_argument("--microbatches", type=int, default=8)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--seq", type=int, default=8)
    p.add_argument("--units", type=int, default=32)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--iters", type=int, default=8)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--smoke", action="store_true",
                   help="tiny config + 1 round: the CI regression guard "
                        "(non-zero exit on post-warmup recompiles or a "
                        "bubble-acceptance failure)")
    p.add_argument("--json", dest="json_path", default=None, metavar="PATH")
    args = p.parse_args(argv)
    from incubator_mxnet_tpu import config

    config.enable_compile_cache()
    kw = dict(n_stages=args.stages, layers_per_stage=args.layers_per_stage,
              n_microbatches=args.microbatches, batch=args.batch,
              seq=args.seq, units=args.units, hidden=args.hidden,
              iters=args.iters, warmup=args.warmup, repeats=args.repeats)
    if args.smoke:
        kw.update(iters=1, repeats=1, warmup=1)
    line = run(**kw)
    print(json.dumps(line))
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(line, f, indent=2)
            f.write("\n")
    if line["post_warmup_recompiles"]:
        print(f"FAIL: {line['post_warmup_recompiles']} post-warmup "
              "recompile(s) in the scheduled step", file=sys.stderr)
        return 2
    if not line["bubble_acceptance"]:
        print("FAIL: bubble acceptance (1f1b < gpipe and within 1.5x "
              "analytic) not met", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    rc = main()
    sys.exit(rc if isinstance(rc, int) else 0)

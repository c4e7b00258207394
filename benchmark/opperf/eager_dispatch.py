"""Eager-dispatch microbenchmark: uncached vs cached-jit vs bulked.

Measures ops/sec on an N-op eager elementwise chain and an SGD-style
optimizer-update chain through ``ndarray.invoke`` under the three dispatch
regimes of docs/eager_dispatch.md:

* ``uncached``   — level-1 cache disabled (the pre-accelerator hot path:
                   raw Python tracing + per-primitive XLA dispatch per op)
* ``cached_jit`` — level-1 dispatch cache (ops/registry.py)
* ``bulked``     — level-2 op-bulking (engine.bulk): whole chain flushed
                   as one compiled program per iteration

Runs on any backend (CI smoke uses ``JAX_PLATFORMS=cpu``) and prints ONE
JSON line so CI and BENCH harvesting can grep it::

    python benchmark/opperf/eager_dispatch.py [--n-ops 64] [--iters 30]

Acceptance floor (ISSUE 2): cached_jit >= 2x uncached and
bulked >= cached_jit on the 64-op elementwise chain (CPU backend).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def _elemwise_chain(nd, x, n_ops):
    """n_ops elementwise ops (one ``invoke`` dispatch each), cycling
    scale / softsign / shift / hard_sigmoid — the small arithmetic +
    activation mix the motivation targets.  softsign (abs+add+div) and
    hard_sigmoid (mul+add+clip) lower to several XLA primitives, so the
    uncached path pays one dispatch per *primitive* while a cached entry
    replays one fused executable per *op* — exactly the gap the level-1
    cache exists to close.  Outputs stay in [0, 1]: numerically safe at
    any chain length."""
    steps = (lambda y: y * 1.0001,
             lambda y: nd.softsign(y),
             lambda y: y + 0.0001,
             lambda y: nd.hard_sigmoid(y))
    y = x
    for i in range(n_ops):
        y = steps[i % 4](y)
    return y


def _sgd_chain(nd, w, g, n_steps):
    """Manual SGD idiom (`w = w - lr * g` outside record): 2 dispatches
    per step, the optimizer/metric-update shape of eager traffic."""
    for _ in range(n_steps):
        w = w - (g * 0.01)
    return w


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def run(n_ops=64, iters=30, shape=(8, 8), warmup=5, repeats=5):
    """Returns the result dict (also usable from tests as a smoke check).

    Measurement is PAIRED: every timing round runs one iteration of each
    mode back-to-back and the per-mode score is the median round.  Dispatch
    overhead is tens of us/op — well inside the drift of a shared or
    virtualized CPU host over the seconds a blocked per-mode loop takes —
    and pairing at iteration granularity makes that drift hit all modes
    alike instead of whichever mode owned the slow window.  GC is paused
    during the timed rounds (standard microbenchmark hygiene: collection
    pauses land between rounds, not inside a random mode's timing).
    """
    import gc

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import engine
    from incubator_mxnet_tpu.ops import registry

    nd = mx.nd
    x = nd.ones(shape)
    g = nd.ones(shape)

    modes = ("uncached", "cached_jit", "bulked")
    results = {m: {} for m in modes}
    medians = {m: {} for m in modes}
    rounds = max(1, iters * repeats)
    prev = registry.set_dispatch_cache(enabled=True, warmup=0)
    try:
        out = {}

        def elem():
            out["y"] = _elemwise_chain(nd, x, n_ops)

        def sgd():
            out["y"] = _sgd_chain(nd, x, g, n_ops // 2)

        for name, body in (("elemwise", elem), ("sgd_update", sgd)):
            def bulked(_b=body):
                with engine.bulk(n_ops + 1):
                    _b()

            def one(mode, _body=body, _bulked=bulked):
                registry.set_dispatch_cache(enabled=(mode != "uncached"),
                                            warmup=0)
                t0 = time.perf_counter()
                (_bulked if mode == "bulked" else _body)()
                out["y"].wait_to_read()
                return time.perf_counter() - t0

            times = {m: [] for m in modes}
            for _ in range(max(1, warmup)):
                for m in modes:
                    one(m)
            gc.collect()
            gc_was_on = gc.isenabled()
            gc.disable()
            try:
                for r in range(rounds):
                    for m in modes:
                        times[m].append(one(m))
                    if r % 50 == 49:
                        gc.enable()
                        gc.collect()
                        gc.disable()
            finally:
                if gc_was_on:
                    gc.enable()
            for m in modes:
                med = _median(times[m])
                results[m][name] = n_ops / med
                medians[m][name] = med
    finally:
        registry.set_dispatch_cache(enabled=prev[0], max_entries=prev[1],
                                    warmup=prev[2])
        registry.clear_dispatch_cache()

    from incubator_mxnet_tpu import config

    line = {
        "bench": "eager_dispatch",
        **config.device_record(),
        "n_ops": n_ops,
        "iters": iters,
        "warmup": warmup,
        "repeats": repeats,
        "rounds": rounds,          # paired timing rounds behind each median
        "shape": list(shape),
        "ops_per_sec": results,
        "median_s": medians,       # raw per-mode median round, seconds
        "speedup_cached": round(
            results["cached_jit"]["elemwise"] / results["uncached"]["elemwise"], 2),
        "speedup_bulked": round(
            results["bulked"]["elemwise"] / results["uncached"]["elemwise"], 2),
    }
    return line


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--n-ops", type=int, default=64)
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--side", type=int, default=8,
                   help="square tensor side (small by design: the bench "
                        "isolates dispatch overhead, not kernel FLOPs)")
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--repeats", type=int, default=5,
                   help="multiplier on --iters for the number of paired "
                        "timing rounds (median round wins)")
    p.add_argument("--json", dest="json_path", default=None, metavar="PATH",
                   help="also write the result object to PATH — the "
                        "machine-readable record (medians, round counts, "
                        "config) bench trajectory harvesting reads instead "
                        "of hand-copied numbers")
    args = p.parse_args(argv)
    from incubator_mxnet_tpu import config

    config.enable_compile_cache()
    line = run(n_ops=args.n_ops, iters=args.iters,
               shape=(args.side, args.side), warmup=args.warmup,
               repeats=args.repeats)
    print(json.dumps(line))
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(line, f, indent=2)
            f.write("\n")
    return line


if __name__ == "__main__":
    main()

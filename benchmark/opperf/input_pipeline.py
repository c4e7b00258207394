"""Input-pipeline microbenchmark: async sharded infeed vs serial host loop.

Measures steps/sec of a **synthetic host-heavy training loop** — the
regime MLPerf-0.6-on-TPU-v3 (PAPERS.md) names the first wall at pod
scale: every batch pays real host-side input latency (modeled as a
``time.sleep`` I/O stall plus a numpy decode pass — disk/network wait
plus CPU work, the standard record-iterator shape) before a jitted
device step can run.

* ``off``      — the serial baseline: prep → ``device_put`` →  step,
  one batch at a time on the consumer thread (what any loop without the
  pipeline pays).
* ``pipeline`` — ``io.DataPipeline``: worker-pool prep + double-buffered
  async transfer deliver device-resident mesh-sharded batches while the
  previous step computes; depth autotunes from the stall/step feedback.

Both modes run the SAME prep work, transfer, and compiled step; the only
difference is overlap.  Per mode: fresh source, ``warmup`` steps, then
``steps`` timed steps, repeated ``trials`` times — the per-mode score is
the median trial (one continuous run per trial, NOT per-step pairs: an
epoch boundary would refill the buffer and bill phantom stalls).
Consumer stalls and the autotuned depth are sampled over the TIMED
window only, so ``stalls_after_warmup == 0`` is the steady-state
acceptance evidence (ISSUE 9: >= 1.5x steps/sec AND zero post-warmup
stalls at the autotuned depth, CPU backend).

Prints ONE JSON line so CI and BENCH harvesting can grep it::

    python benchmark/opperf/input_pipeline.py [--steps 40] [--host-ms 12]
        [--json PATH] [--smoke]

``--smoke`` shrinks the run and exits non-zero if the pipeline path
recorded a consumer stall after warmup — the CI ``io`` tier's
host-starvation regression guard.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def _make_source(n, batch, feat, host_ms, seed=0):
    """Raw record stream + the host-side decode it needs: ``prep`` sleeps
    ``host_ms`` (I/O wait) then runs a numpy normalize pass (CPU work)."""
    import numpy as np

    rng = np.random.RandomState(seed)
    raw = [rng.randn(batch, feat).astype(np.float32) for _ in range(n)]

    def source():
        return iter(raw)

    def prep(b):
        time.sleep(host_ms / 1e3)
        b = b - b.mean(axis=1, keepdims=True)
        return b / (b.std(axis=1, keepdims=True) + 1e-6)

    return source, prep


def _make_step(mesh, feat, layers, hidden, seed=1):
    """A jitted forward/backward-shaped compute: enough matmul to give
    the pipeline something to overlap with."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from incubator_mxnet_tpu.parallel import batch_pspec
    from jax.sharding import NamedSharding

    rng = np.random.RandomState(seed)
    ws = [jax.device_put(
        jnp.asarray(rng.randn(feat if i == 0 else hidden, hidden)
                    .astype(np.float32) / np.sqrt(feat)),
        NamedSharding(mesh, jax.sharding.PartitionSpec()))  # replicated
        for i in range(layers)]

    @jax.jit
    def step(x, *weights):
        h = x
        for w in weights:
            h = jnp.tanh(h @ w)
        return jnp.sum(h * h)

    sharding = NamedSharding(mesh, batch_pspec(2))
    return step, ws, sharding


def run(steps=40, warmup=8, trials=3, batch=256, feat=512, hidden=1024,
        layers=8, host_ms=12.0, num_workers=4, depth=2, max_depth=8):
    """Returns the result dict (also the tests' smoke check entry)."""
    import gc

    import jax

    from incubator_mxnet_tpu.io import DataPipeline
    from incubator_mxnet_tpu.parallel import make_mesh

    mesh = make_mesh()
    step, ws, sharding = _make_step(mesh, feat, layers, hidden)
    n_batches = (warmup + steps) * trials + 8

    def run_off():
        """Serial: prep -> device_put -> step on one thread per batch."""
        source, prep = _make_source(n_batches, batch, feat, host_ms)
        it = source()

        def one():
            b = prep(next(it))
            x = jax.device_put(b, sharding)
            return step(x, *ws)

        for _ in range(warmup):
            one().block_until_ready()
        t0 = time.perf_counter()
        for _ in range(steps):
            # per-step loss read (block) in BOTH modes: an async dispatch
            # loop would measure dispatch throughput, and its consumer
            # would drain the infeed at dispatch speed — billing phantom
            # stalls while the device is the actual bottleneck
            one().block_until_ready()
        return time.perf_counter() - t0, {}

    def run_pipe():
        source, prep = _make_source(n_batches, batch, feat, host_ms)
        pipe = DataPipeline(source, prep_fn=prep, mesh=mesh,
                            num_workers=num_workers, depth=depth,
                            max_depth=max_depth, num_parts=1, part_index=0,
                            name="io_bench")
        try:
            it = iter(pipe)
            for _ in range(warmup):
                step(next(it), *ws).block_until_ready()
            stalls0 = pipe.stats()["stalls"]
            t0 = time.perf_counter()
            for _ in range(steps):
                step(next(it), *ws).block_until_ready()
            dt = time.perf_counter() - t0
            st = pipe.stats()
            return dt, {"stalls_after_warmup": st["stalls"] - stalls0,
                        "autotuned_depth": st["depth"],
                        "depth_changes": st["depth_changes"]}
        finally:
            pipe.close()

    modes = {"off": run_off, "pipeline": run_pipe}
    times = {m: [] for m in modes}
    extras = {}
    gc.collect()
    for _ in range(trials):
        for m, fn in modes.items():
            dt, extra = fn()
            times[m].append(dt)
            if extra:
                extras = extra  # last trial's steady-state evidence
    medians = {m: _median(ts) for m, ts in times.items()}
    steps_per_sec = {m: steps / v for m, v in medians.items()}
    from incubator_mxnet_tpu import config

    return {
        "bench": "input_pipeline",
        **config.device_record(),
        "devices": len(jax.devices()),
        "steps": steps,
        "warmup": warmup,
        "trials": trials,
        "batch": batch,
        "feat": feat,
        "hidden": hidden,
        "layers": layers,
        "host_ms": host_ms,
        "num_workers": num_workers,
        "initial_depth": depth,
        "max_depth": max_depth,
        "steps_per_sec": {m: round(v, 2) for m, v in steps_per_sec.items()},
        "median_s": medians,
        "speedup_pipeline": round(
            steps_per_sec["pipeline"] / steps_per_sec["off"], 2),
        **extras,
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--warmup", type=int, default=8)
    p.add_argument("--trials", type=int, default=3,
                   help="independent runs per mode; the median trial wins")
    p.add_argument("--batch", type=int, default=256)
    p.add_argument("--feat", type=int, default=512)
    p.add_argument("--layers", type=int, default=8)
    p.add_argument("--host-ms", type=float, default=12.0,
                   help="per-batch host input latency the prep stage "
                        "models (I/O wait + decode)")
    p.add_argument("--workers", type=int, default=4,
                   help="prep worker threads; per-batch producer latency "
                        "is host_ms/workers, sized well under the device "
                        "step so steady state has zero consumer stalls")
    p.add_argument("--smoke", action="store_true",
                   help="tiny run; non-zero exit if the pipeline stalled "
                        "after warmup (CI regression guard)")
    p.add_argument("--json", dest="json_path", default=None, metavar="PATH",
                   help="also write the result object to PATH — the "
                        "machine-readable record evidence harvesting reads")
    args = p.parse_args(argv)
    from incubator_mxnet_tpu import config

    config.enable_compile_cache()
    kw = dict(steps=args.steps, warmup=args.warmup, trials=args.trials,
              batch=args.batch, feat=args.feat, layers=args.layers,
              host_ms=args.host_ms, num_workers=args.workers)
    if args.smoke:
        kw.update(steps=12, warmup=6, trials=1, batch=128, feat=512,
                  layers=6, host_ms=6.0, num_workers=4)
    line = run(**kw)
    print(json.dumps(line))
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(line, f, indent=2)
            f.write("\n")
    if args.smoke and line.get("stalls_after_warmup", 0) > 0:
        print("input_pipeline smoke: consumer stalled after warmup "
              f"({line['stalls_after_warmup']} stalls at depth "
              f"{line.get('autotuned_depth')})", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

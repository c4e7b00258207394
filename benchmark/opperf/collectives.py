"""Quantized-collectives microbenchmark: fp32 vs bf16 vs int8 gradient
exchange on BOTH cross-host paths (docs/gradient_compression.md).

* ``pushpull`` — ``kvstore.bucketed_pushpull`` against a dist store: the
  same gradient set allreduced under each codec tier, bytes-on-wire read
  back from the ``comms_bytes_raw``/``comms_bytes_wire`` counters (the
  acceptance evidence is counter-verified, not computed by the harness).
* ``spmd`` — one ``SPMDTrainer`` per tier on the virtual 8-device CPU
  mesh: the int8 tier's in-program quantize → integer psum → dequantize
  runs inside the same donated-buffer compiled step, so the comparison
  also guards the zero-steady-state-recompile contract
  (``MXNET_COMPILE_GUARD=raise`` armed after warmup; non-zero exit on
  any post-warmup compile).

Measurement is PAIRED like the other opperf harnesses: each timing round
runs one step of every tier back-to-back, median round wins, GC paused.

Acceptance (ISSUE 14): the int8 tier moves >= 3.5x fewer gradient bytes
than fp32 on BOTH paths (counters), with the opt-out groups still
travelling exact.  ``--algo ring|psum|both`` (ISSUE 19) A/Bs the SPMD
exchange algorithm over the SAME buckets — for the ring, the evidence is
per-HOP: every ppermute payload is one encoded chunk, and the harness
exits non-zero if the int8 per-hop byte ratio falls below 3.5x fp32 or
any tier recompiles after warmup.

    python benchmark/opperf/collectives.py [--json PATH] [--smoke]
                                           [--algo ring|psum|both]
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

# the SPMD half needs a multi-device dp axis; default to the suite's
# virtual 8-device CPU mesh when run bare (before any jax import)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

TIERS = ("fp32", "bf16", "int8")
# the SPMD half also runs the int4 packed tier: its nibble wire rides the
# ring hops / int4 psum grid, while the host bucket path rejects it (no
# linear sum for packed nibbles) — so it never joins the pushpull tiers
SPMD_TIERS = TIERS + ("int4",)


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


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])


def _policy(tier, algo=None):
    from incubator_mxnet_tpu import comm

    # "off", not None: None re-resolves MXNET_GRAD_COMPRESS downstream,
    # and an exported tier in the caller's env would silently compress
    # the fp32 BASELINE, making every ratio in the evidence meaningless
    if tier == "fp32":
        return "off"
    pol = comm.resolve_policy(tier)
    if algo is not None:
        # pin the exchange algorithm for the A/B regardless of the
        # caller's MXNET_GRAD_COMPRESS_ALGO
        pol = comm.CompressionPolicy(pol.codec,
                                     error_feedback=pol.error_feedback,
                                     algo=algo)
    return pol


def _counter_delta(fn):
    """Run ``fn`` and return (result, raw_bytes, wire_bytes) counted."""
    from incubator_mxnet_tpu import profiler

    c0 = profiler.counters()
    out = fn()
    c1 = profiler.counters()
    return (out, c1["comms_bytes_raw"] - c0["comms_bytes_raw"],
            c1["comms_bytes_wire"] - c0["comms_bytes_wire"])


def run_pushpull(n_params=64, shape=(64, 32), iters=10, warmup=2, repeats=3):
    """Paired bucketed-pushpull timing: one gradient set, three wire
    tiers, per-tier error feedback carried across rounds like a real
    training loop."""
    import gc

    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import comm, kvstore as kv_mod
    from incubator_mxnet_tpu.gluon import Parameter

    rs = np.random.RandomState(7)
    params = []
    for k in range(n_params):
        p = Parameter(f"c{k}_weight", shape=shape, dtype="float32")
        p.initialize()
        p.set_data(mx.nd.array(rs.randn(*shape).astype(np.float32)))
        params.append(p)
    grads = [rs.randn(*shape).astype(np.float32) for _ in params]
    kv = kv_mod.create("dist_sync")
    feedbacks = {t: comm.ErrorFeedback() for t in TIERS}

    def one(tier):
        for p, g in zip(params, grads):
            p.grad()[:] = mx.nd.array(g)
        items = [(i, p.grad()) for i, p in enumerate(params)]
        names = [p.name for p in params]
        pol = _policy(tier)
        t0 = time.perf_counter()
        kv_mod.bucketed_pushpull(kv, items, names=names, compression=pol,
                                 feedback=feedbacks[tier])
        mx.nd.waitall()
        return time.perf_counter() - t0

    byte_ratio = {}
    for tier in TIERS:
        for _ in range(max(1, warmup)):
            one(tier)
        _, raw, wire = _counter_delta(lambda: one(tier))
        byte_ratio[tier] = {"bytes_raw": raw, "bytes_wire": wire,
                            "ratio": round(raw / wire, 3) if wire else 0.0}
    rounds = max(1, iters * repeats)
    times = {t: [] for t in TIERS}
    gc.collect()
    gc_was_on = gc.isenabled()
    gc.disable()
    try:
        for _ in range(rounds):
            for t in TIERS:
                times[t].append(one(t))
    finally:
        if gc_was_on:
            gc.enable()
    medians = {t: _median(v) for t, v in times.items()}
    return {
        "rounds": rounds,
        "median_s": medians,
        "steps_per_sec": {t: round(1.0 / v, 2) for t, v in medians.items()},
        "bytes": byte_ratio,
    }


@_guarded
def run_spmd(batch=32, features=64, hidden=256, classes=8, iters=10,
             warmup=2, repeats=3, algo="psum"):
    """Paired SPMD-step timing, one trainer per tier, under the
    steady-state compile guard.  ``algo`` picks the exchange form for the
    compressed tiers: ``psum`` (quantize -> integer psum -> dequantize)
    or ``ring`` (explicit encoded ppermute hops, comm/ring.py) — same
    buckets either way, so the A/B isolates the algorithm."""
    import gc

    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, profiler
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.parallel import SPMDTrainer, make_mesh

    def build():
        mx.random.seed(11)
        net = nn.HybridSequential()
        net.add(nn.Dense(hidden, activation="relu"),
                nn.Dense(hidden, activation="relu"), nn.Dense(classes))
        net.initialize()
        net(mx.nd.zeros((2, features)))
        return net

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(1)
    x = rng.randn(batch, features).astype(np.float32)
    y = rng.randint(0, classes, (batch,)).astype(np.float32)

    trainers = {}

    def one(tier):
        tr = trainers[tier]
        t0 = time.perf_counter()
        loss = tr.step(mx.nd.array(x), mx.nd.array(y))
        loss.asnumpy()  # sync: time the whole compiled step
        return time.perf_counter() - t0

    with profiler.compile_guard_paused():
        for tier in SPMD_TIERS:
            trainers[tier] = SPMDTrainer(
                build(), loss_fn, "sgd", {"learning_rate": 0.05},
                mesh=make_mesh(),
                compression=_policy(tier, algo=algo))
        for _ in range(max(1, warmup)):
            for t in SPMD_TIERS:
                one(t)
    base_recompiles = profiler.counters()["recompile_steady_state"]

    byte_ratio = {}
    for tier in SPMD_TIERS:
        _, raw, wire = _counter_delta(lambda: one(tier))
        if tier == "fp32":
            # the fp32 trainer has no comm accounting: its dp exchange IS
            # the raw payload — derive it from the int8 trainer's layout
            continue
        entry = {"bytes_raw": raw, "bytes_wire": wire,
                 "ratio": round(raw / wire, 3) if wire else 0.0}
        cfg_t = trainers[tier]._comm_cfg
        if algo == "ring" and cfg_t["hops"]:
            # per-HOP wire accounting (the acceptance evidence is
            # hop-granular for the ring: every ppermute payload is the
            # encoded chunk, so the per-hop ratio IS the codec's)
            from incubator_mxnet_tpu.comm import ring as ring_mod

            chunk = ring_mod._ring_chunk(cfg_t["codec"], cfg_t["n"],
                                         cfg_t["shards"])
            entry.update(
                hops=cfg_t["hops"], bytes_per_hop=cfg_t["bytes_hop"],
                fp32_bytes_per_hop=4 * chunk,
                hop_ratio_vs_fp32=round(4 * chunk / cfg_t["bytes_hop"], 3)
                if cfg_t["bytes_hop"] else 0.0)
        byte_ratio[tier] = entry
    cfg = trainers["int8"]._comm_cfg
    byte_ratio["fp32"] = {"bytes_raw": cfg["bytes_raw"],
                          "bytes_wire": cfg["bytes_raw"], "ratio": 1.0}

    rounds = max(1, iters * repeats)
    times = {t: [] for t in SPMD_TIERS}
    gc.collect()
    gc_was_on = gc.isenabled()
    gc.disable()
    try:
        for _ in range(rounds):
            for t in SPMD_TIERS:
                times[t].append(one(t))
    finally:
        if gc_was_on:
            gc.enable()
    recompiles = profiler.counters()["recompile_steady_state"] - base_recompiles
    medians = {t: _median(v) for t, v in times.items()}
    return {
        "algo": algo,
        "rounds": rounds,
        "median_s": medians,
        "steps_per_sec": {t: round(1.0 / v, 2) for t, v in medians.items()},
        "bytes": byte_ratio,
        "post_warmup_recompiles": int(recompiles),
    }


def run(n_params=64, shape=(64, 32), batch=32, hidden=256, iters=10,
        warmup=2, repeats=3, algo="both"):
    pushpull = run_pushpull(n_params=n_params, shape=shape, iters=iters,
                            warmup=warmup, repeats=repeats)
    algos = ("psum", "ring") if algo == "both" else (algo,)
    spmd_ab = {}
    for a in algos:
        spmd_ab[a] = run_spmd(batch=batch, hidden=hidden, iters=iters,
                              warmup=warmup, repeats=repeats, algo=a)
    primary = "ring" if "ring" in spmd_ab else algos[0]
    spmd = spmd_ab[primary]
    ratios = {
        "pushpull_int8": pushpull["bytes"]["int8"]["ratio"],
        "spmd_int8": spmd["bytes"]["int8"]["ratio"],
    }
    ok = all(v >= 3.5 for v in ratios.values())
    if "ring" in spmd_ab:
        # hop-granular acceptance: the ring's per-ppermute payload must
        # be >= 3.5x narrower than the fp32 chunk it replaces (>= 6x for
        # the packed int4 nibbles)
        ratios["spmd_ring_int8_per_hop"] = \
            spmd_ab["ring"]["bytes"]["int8"]["hop_ratio_vs_fp32"]
        ratios["spmd_ring_int4_per_hop"] = \
            spmd_ab["ring"]["bytes"]["int4"]["hop_ratio_vs_fp32"]
        ok = (ok and ratios["spmd_ring_int8_per_hop"] >= 3.5
              and ratios["spmd_ring_int4_per_hop"] >= 6.0)
    recompiles = sum(r["post_warmup_recompiles"] for r in spmd_ab.values())
    from incubator_mxnet_tpu import config

    return {
        "bench": "collectives",
        **config.device_record(),
        "n_params": n_params,
        "shape": list(shape),
        "batch": batch,
        "hidden": hidden,
        "algo": algo,
        "pushpull": pushpull,
        "spmd": spmd,
        "spmd_ab": spmd_ab,
        "int8_byte_ratio": ratios,
        "bytes_acceptance": bool(ok),   # int8 >= 3.5x on BOTH paths
        "post_warmup_recompiles": int(recompiles),
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--n-params", type=int, default=64)
    p.add_argument("--side", type=int, default=64)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--warmup", type=int, default=2)
    p.add_argument("--repeats", type=int, default=3)
    p.add_argument("--algo", choices=("psum", "ring", "both"),
                   default="both",
                   help="gradient-exchange algorithm for the SPMD half: "
                        "the quantized psum sandwich, the explicit "
                        "encoded-ppermute ring, or an A/B of both over "
                        "the same buckets (default)")
    p.add_argument("--smoke", action="store_true",
                   help="tiny config + 1 round: the CI regression guard "
                        "(non-zero exit on post-warmup recompiles or an "
                        "int8 byte-ratio below 3.5x on either path, "
                        "per-hop for the ring)")
    p.add_argument("--json", dest="json_path", default=None, metavar="PATH")
    args = p.parse_args(argv)
    from incubator_mxnet_tpu import config

    config.enable_compile_cache()
    kw = dict(n_params=args.n_params, shape=(args.side, 32),
              batch=args.batch, hidden=args.hidden, iters=args.iters,
              warmup=args.warmup, repeats=args.repeats, algo=args.algo)
    if args.smoke:
        kw.update(n_params=16, iters=1, repeats=1, warmup=1, hidden=128)
    line = run(**kw)
    print(json.dumps(line))
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(line, f, indent=2)
            f.write("\n")
    if line["post_warmup_recompiles"]:
        print(f"FAIL: {line['post_warmup_recompiles']} post-warmup "
              "recompile(s) in the compressed SPMD step", file=sys.stderr)
        return 2
    if not line["bytes_acceptance"]:
        print(f"FAIL: int8 byte ratio below 3.5x: {line['int8_byte_ratio']}",
              file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    rc = main()
    sys.exit(rc if isinstance(rc, int) else 0)

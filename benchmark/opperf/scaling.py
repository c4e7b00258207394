"""Weak/strong scaling harness: samples/sec vs device (and process) count,
with every point's time attribution on one merged timeline (ISSUE 20).

The ROADMAP's MLPerf item demands that "every scaling claim ships with
its curve".  This harness produces the curve AND its evidence:

* sweeps device count on a CPU virtual mesh (each point is a fresh
  subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
  — device count is fixed per process) and/or process count (dist_sync
  ranks with the ``tools/launch_local.py`` DMLC environment).  The
  children are CPU-forced BY DESIGN: a chip belongs to one process at a
  time and one process drives all chips of a host, so a sweep of child
  processes can never be an on-chip curve — its samples/sec are a CPU
  structure check, not a device metric,
* **weak** scaling holds per-device batch fixed (ideal: samples/sec
  grows linearly with N); **strong** scaling holds the global batch
  fixed,
* supports ``dp`` / ``fsdp`` / ``pipeline`` SPMD configs,
* every point runs under ``MXNET_COMPILE_GUARD=raise`` after warmup —
  a post-warmup recompile fails the point, not just a gate,
* every point's per-rank traces are fused by ``tools/trace_merge.py``
  and the goodput ledger recomputed from the merged dump must match the
  live-reported one (the attribution is PROVEN against the trace, not
  asserted), with straggler attribution (slowest rank by median step
  wall) and bubble/comm bucket splits per point,
* ``--json`` writes the machine-readable evidence
  ``tools/perf_history.py`` ingests; acceptance gates (efficiency
  floor, zero post-warmup recompiles, attribution match) set the exit
  code.

Usage::

    python benchmark/opperf/scaling.py [--mode weak|strong]
        [--config dp|fsdp|pipeline] [--devices 1,2,4,8] [--procs 1]
        [--steps 20] [--warmup 5] [--per-device-batch 8]
        [--efficiency-floor 0.05] [--json OUT] [--out-dir DIR] [--smoke]

``--smoke`` is the CI tier entry: the 2- and 4-device dp weak-scaling
points with small step counts.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, _REPO)
sys.path.insert(0, os.path.join(_REPO, "tools"))

RESULT_MARK = "SCALING_RESULT "
_CHILD_TIMEOUT_S = 600


# ---------------------------------------------------------------------------
# Child: one curve point in its own process (fixed device count)
# ---------------------------------------------------------------------------


def _build_net(gluon, seed):
    """4 Dense stages — splittable for the pipeline config."""
    import incubator_mxnet_tpu as mx

    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(64, activation="relu"),
            gluon.nn.Dense(64, activation="relu"),
            gluon.nn.Dense(32, activation="relu"),
            gluon.nn.Dense(8))
    net.initialize()
    net(mx.nd.zeros((2, 32)))
    return net


def child_spmd(args):
    """Single-process point: SPMD over the N-device CPU mesh."""
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, profiler
    from incubator_mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss
    from incubator_mxnet_tpu.parallel import SPMDTrainer, make_mesh
    from incubator_mxnet_tpu.parallel.sharding import fsdp_rules

    import jax

    n = jax.device_count()
    assert n == args.devices, (n, args.devices)
    batch = (args.per_device_batch * n if args.mode == "weak"
             else args.global_batch)
    batch = max(n, batch - batch % n)  # global batch must shard over dp

    net = _build_net(gluon, seed=7)
    loss_fn = SoftmaxCrossEntropyLoss()
    kw = {}
    if args.config == "fsdp":
        kw["mesh"] = make_mesh(fsdp=n)
        kw["rules"] = fsdp_rules()
    elif args.config == "pipeline":
        kw["mesh"] = make_mesh()
        kw["stages"] = net.split_stages([1, 1, 1, 1])
        kw["pipeline"] = {"schedule": "1f1b",
                          "n_microbatches": max(2, min(4, batch))}
    else:
        kw["mesh"] = make_mesh()
    trainer = SPMDTrainer(net, loss_fn, "sgd", {"learning_rate": 0.01},
                          **kw)

    rng = np.random.RandomState(0)
    x = rng.randn(batch, 32).astype(np.float32)
    y = rng.randint(0, 8, size=(batch,)).astype(np.float32)

    profiler.set_config(filename=args.trace)
    profiler.start()
    for _ in range(args.warmup):
        trainer.step(x, y)
    mx.nd.waitall()
    # the ledger measures ONLY the timed window: compile/warmup stays out
    # of the curve the same way it stays out of samples/sec
    profiler.reset_goodput()
    t0 = time.perf_counter()
    for _ in range(args.steps):
        trainer.step(x, y)
    mx.nd.waitall()
    elapsed = time.perf_counter() - t0
    snap = profiler.goodput_snapshot()
    counters = profiler.counters()
    profiler.dump()  # embeds the ledger + counters into the trace
    print(RESULT_MARK + json.dumps({
        "devices": n, "procs": 1, "rank": 0, "config": args.config,
        "mode": args.mode, "batch_global": batch, "steps": args.steps,
        "elapsed_s": round(elapsed, 6),
        "samples_per_sec": round(args.steps * batch / elapsed, 3),
        "goodput": snap,
        "recompile_steady_state": counters["recompile_steady_state"],
        "comms_ring_hops": counters["comms_ring_hops"],
        "pipeline_bubble_ms": counters["pipeline_bubble_ms"],
        "trace": args.trace,
    }), flush=True)


def child_dist(args):
    """One rank of a multi-process dist_sync point (bucketed pushpull
    gradient exchange — the measured ``comm`` bucket)."""
    import numpy as np

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, gluon, profiler

    kv = mx.kv.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    batch = (args.per_device_batch if args.mode == "weak"
             else max(1, args.global_batch // nw))

    net = _build_net(gluon, seed=7)
    L = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01}, kvstore=kv)
    rng = np.random.RandomState(100 + rank)
    x = mx.nd.array(rng.randn(batch, 32).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 8, size=(batch,)).astype(np.float32))

    def one_step():
        with autograd.record():
            loss = L(net(x), y)
        loss.backward()
        trainer.step(batch)

    profiler.set_config(filename=args.trace)
    profiler.start()
    for _ in range(args.warmup):
        one_step()
    mx.nd.waitall()
    kv.barrier()
    profiler.reset_goodput()
    t0 = time.perf_counter()
    for _ in range(args.steps):
        one_step()
    mx.nd.waitall()
    kv.barrier()
    elapsed = time.perf_counter() - t0
    snap = profiler.goodput_snapshot()
    counters = profiler.counters()
    profiler.dump()
    if rank == 0:
        print(RESULT_MARK + json.dumps({
            "devices": 1, "procs": nw, "rank": 0, "config": "dist_sync",
            "mode": args.mode, "batch_global": batch * nw,
            "steps": args.steps, "elapsed_s": round(elapsed, 6),
            "samples_per_sec": round(args.steps * batch * nw / elapsed, 3),
            "goodput": snap,
            "recompile_steady_state": counters["recompile_steady_state"],
            "comms_ring_hops": counters["comms_ring_hops"],
            "pipeline_bubble_ms": counters["pipeline_bubble_ms"],
            "trace": args.trace,
        }), flush=True)


# ---------------------------------------------------------------------------
# Parent: sweep, merge, attribute, gate
# ---------------------------------------------------------------------------


def _reserve_port():
    s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", 0))
    return s, s.getsockname()[1]


def _child_env(devices, extra=None):
    env = dict(os.environ)
    env.pop("MXNET_FAULT_SPEC", None)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
        "MXNET_COMPILE_GUARD": "raise",
    })
    env.update(extra or {})
    return env


def _parse_result(stdout, what):
    for line in stdout.splitlines():
        if line.startswith(RESULT_MARK):
            return json.loads(line[len(RESULT_MARK):])
    raise RuntimeError(f"{what}: no {RESULT_MARK.strip()} line in output:\n"
                       + stdout[-2000:])


def run_point_spmd(args, devices, out_dir):
    trace = os.path.join(out_dir, f"d{devices}_rank0.json")
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--devices", str(devices), "--config", args.config,
           "--mode", args.mode, "--steps", str(args.steps),
           "--warmup", str(args.warmup),
           "--per-device-batch", str(args.per_device_batch),
           "--global-batch", str(args.global_batch), "--trace", trace]
    # the guard arms itself after the warmup steps; warmup runs inside the
    # child BEFORE the timed window, so any post-warmup compile raises
    env = _child_env(devices,
                     {"MXNET_COMPILE_WARMUP_STEPS": str(args.warmup)})
    res = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=_CHILD_TIMEOUT_S)
    if res.returncode != 0:
        raise RuntimeError(
            f"point devices={devices} failed (rc {res.returncode}):\n"
            + (res.stderr or res.stdout)[-2000:])
    return _parse_result(res.stdout, f"devices={devices}"), [trace]


def run_point_dist(args, procs, out_dir):
    holder, port = _reserve_port()
    traces = [os.path.join(out_dir, f"p{procs}_rank{r}.json")
              for r in range(procs)]
    children = []
    for r in range(procs):
        cmd = [sys.executable, os.path.abspath(__file__), "--child",
               "--dist", "--mode", args.mode, "--steps", str(args.steps),
               "--warmup", str(args.warmup),
               "--per-device-batch", str(args.per_device_batch),
               "--global-batch", str(args.global_batch),
               "--trace", traces[r]]
        env = _child_env(1, {
            "MXNET_COMPILE_WARMUP_STEPS": str(args.warmup),
            "DMLC_ROLE": "worker", "DMLC_PS_ROOT_URI": "127.0.0.1",
            "DMLC_PS_ROOT_PORT": str(port),
            "DMLC_NUM_WORKER": str(procs), "DMLC_NUM_SERVER": "0",
            "DMLC_WORKER_ID": str(r),
        })
        children.append(subprocess.Popen(
            cmd, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    holder.close()
    outs = []
    for r, p in enumerate(children):
        try:
            out, err = p.communicate(timeout=_CHILD_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            for q in children:
                q.kill()
            raise
        if p.returncode != 0:
            for q in children:
                q.kill()
            raise RuntimeError(
                f"point procs={procs} rank {r} failed "
                f"(rc {p.returncode}):\n" + (err or out)[-2000:])
        outs.append(out)
    return _parse_result(outs[0], f"procs={procs}"), traces


def attribute_point(result, traces, out_dir, tag):
    """Merge the point's per-rank traces and pull the attribution the
    curve ships with: the merged-ledger goodput (cross-checked against
    the live-reported one), bubble/comm splits, and the straggler rank."""
    import trace_merge

    merged = trace_merge.merge_traces(traces)
    merged_path = os.path.join(out_dir, f"merged_{tag}.json")
    with open(merged_path, "w") as f:
        json.dump(merged, f)
    summ = trace_merge.goodput_summary(merged)
    live = result["goodput"]
    match = False
    if summ is not None and live.get("wall_s"):
        # rank 0's live snapshot vs the same rank's ledger as embedded in
        # the merged dump: taken one dump() apart, so equal to tolerance
        rank0 = summ["per_rank"].get(result.get("rank", 0)) or {}
        w0, w1 = live["wall_s"], rank0.get("wall_s") or 0.0
        match = w1 > 0 and abs(w0 - w1) / max(w0, w1) < 0.10
    ranks = (merged.get("otherData") or {}).get("ranks") or {}
    med_walls = {}
    for rk, entry in ranks.items():
        steps = (entry or {}).get("steps") or []
        walls = sorted(s.get("wall_ms", 0.0) for s in steps)
        if walls:
            med_walls[int(rk)] = walls[len(walls) // 2]
    straggler = None
    if len(med_walls) > 1:
        worst = max(med_walls, key=med_walls.get)
        straggler = {"rank": worst,
                     "median_step_wall_ms": round(med_walls[worst], 3),
                     "ranks_compared": len(med_walls)}
    buckets = live.get("buckets_s") or {}
    return {
        "merged_trace": merged_path,
        "merged_goodput": None if summ is None else
            {"wall_s": summ["wall_s"], "goodput": summ["goodput"],
             "buckets_s": summ["buckets_s"], "worst": summ["worst"]},
        "attribution_match": match,
        "bubble_s": buckets.get("bubble", 0.0),
        "comm_s": buckets.get("comm", 0.0),
        "straggler": straggler,
    }


def run_sweep(args):
    out_dir = args.out_dir or tempfile.mkdtemp(prefix="mxnet_scaling_")
    os.makedirs(out_dir, exist_ok=True)
    points = []
    for devices in args.devices:
        result, traces = run_point_spmd(args, devices, out_dir)
        result.update(attribute_point(result, traces, out_dir,
                                      f"d{devices}"))
        points.append(result)
        print(f"[scaling] devices={devices}: "
              f"{result['samples_per_sec']:.1f} samples/s, goodput "
              f"{(result['goodput']['goodput'] or 0) * 100:.1f}%",
              file=sys.stderr, flush=True)
    for procs in args.procs_list:
        if procs < 2:
            continue
        result, traces = run_point_dist(args, procs, out_dir)
        result.update(attribute_point(result, traces, out_dir,
                                      f"p{procs}"))
        points.append(result)
        print(f"[scaling] procs={procs}: "
              f"{result['samples_per_sec']:.1f} samples/s",
              file=sys.stderr, flush=True)

    # per-point efficiency vs linear from the sweep's first point:
    # eff(N) = (T_N / T_base) / (N / base) — 1.0 is perfect scaling
    base = points[0]
    base_n = base["devices"] * base["procs"]
    base_t = base["samples_per_sec"]
    for pt in points:
        n = pt["devices"] * pt["procs"]
        ideal = base_t * n / base_n
        pt["efficiency"] = round(pt["samples_per_sec"] / ideal, 4)

    recomp_pass = all(pt["recompile_steady_state"] == 0 for pt in points)
    eff_pass = all(pt["efficiency"] >= args.efficiency_floor
                   for pt in points)
    attr_pass = all(pt["attribution_match"] for pt in points)
    evidence = {
        "schema": 1,
        "bench": "scaling",
        "mode": args.mode,
        "config": args.config,
        "per_device_batch": args.per_device_batch,
        "global_batch": args.global_batch,
        "steps": args.steps,
        "warmup": args.warmup,
        "points": points,
        "gates": {
            "efficiency_floor": args.efficiency_floor,
            "efficiency_pass": eff_pass,
            "recompile_pass": recomp_pass,
            "attribution_pass": attr_pass,
        },
        "pass": eff_pass and recomp_pass and attr_pass,
    }
    return evidence


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--mode", choices=("weak", "strong"), default="weak")
    ap.add_argument("--config", choices=("dp", "fsdp", "pipeline"),
                    default="dp")
    ap.add_argument("--devices", default="1,2,4,8",
                    help="comma-separated device counts (one subprocess "
                         "per point; CPU virtual mesh)")
    ap.add_argument("--procs", dest="procs_list", default="",
                    help="comma-separated dist_sync process counts to "
                         "sweep in addition to --devices")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--per-device-batch", type=int, default=8)
    ap.add_argument("--global-batch", type=int, default=64)
    ap.add_argument("--efficiency-floor", type=float, default=0.05,
                    help="minimum per-point efficiency-vs-linear "
                         "(CPU virtual meshes share one socket — the "
                         "floor proves the curve is a curve, not a wall)")
    ap.add_argument("--json", default=None,
                    help="write the evidence JSON here")
    ap.add_argument("--out-dir", default=None,
                    help="keep per-point traces/merges here")
    ap.add_argument("--smoke", action="store_true",
                    help="CI tier entry: 2- and 4-device dp weak points, "
                         "small step counts")
    # -- child-process plumbing (internal) --
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--dist", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--trace", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.smoke:
        args.devices = "2,4"
        args.steps = min(args.steps, 10)
        args.warmup = min(args.warmup, 3)

    args.devices = ([int(x) for x in str(args.devices).split(",") if x]
                    if not isinstance(args.devices, list) else args.devices)
    args.procs_list = [int(x) for x in str(args.procs_list).split(",") if x]

    if args.child:
        args.devices = args.devices[0] if args.devices else 1
        if args.dist:
            child_dist(args)
        else:
            child_spmd(args)
        return 0

    evidence = run_sweep(args)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(evidence, f, indent=1)
        print(f"[scaling] evidence -> {args.json}", file=sys.stderr)
    print(json.dumps({
        "bench": "scaling", "mode": evidence["mode"],
        "config": evidence["config"],
        "curve": [[pt["devices"] * pt["procs"], pt["samples_per_sec"],
                   pt["efficiency"]] for pt in evidence["points"]],
        "pass": evidence["pass"],
        "gates": evidence["gates"],
    }))
    return 0 if evidence["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())

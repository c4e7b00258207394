"""Serving-tier benchmark: throughput at a p99 latency SLO.

Open-loop harness in the Gemma-on-Cloud-TPU comparison shape (PAPERS.md):
requests arrive by a **Poisson process** (open loop — arrivals don't wait
for completions, so queueing delay is real) with **mixed lengths**, and
the headline metric is **throughput-at-SLO**: the highest sustained
arrival rate at which p99 end-to-end latency stays within ``--slo-ms``.

Two modes over the SAME workload:

* ``sequential`` — one request at a time through warmed single-request
  ``Predictor.forward`` (shape-bucketed, so it never recompiles either:
  the baseline isolates the BATCHING win, not compile overhead).
  Queueing is simulated exactly from measured service times (arrival
  order, M/D/1-style: start = max(arrival, previous completion)).
* ``served`` — through ``serving.InferenceServer`` (dynamic batching +
  (batch, length) bucketing), paced in real time by a feeder thread.

Acceptance (ISSUE 8): served throughput-at-SLO >= 3x sequential on CPU,
with ZERO recompiles after warmup — the harness exits non-zero if any
batch bound or compiled a new program once warmup finished (the CI
bucket-miss regression guard), so a bucketing regression cannot land
silently.

Prints ONE JSON line (like the other opperf harnesses)::

    python benchmark/opperf/serving.py [--smoke] [--json PATH]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as _np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

_perf = time.perf_counter


def build_model(layers=4, feat=64):
    """A padding-safe per-position MLP: ``layers`` blocks of
    FullyConnected(flatten=False) + tanh over (batch, length, feat).
    Parameter shapes are length-independent, so one weight copy serves
    every bucket."""
    import incubator_mxnet_tpu as mx
    import incubator_mxnet_tpu.symbol as S

    S.symbol._reset_naming()
    rng = _np.random.RandomState(0)
    x = S.var("data")
    params = {}
    for i in range(layers):
        name = f"fc{i}"
        x = S.FullyConnected(x, num_hidden=feat, flatten=False, name=name)
        x = S.Activation(x, act_type="tanh", name=f"act{i}")
        params[f"arg:{name}_weight"] = mx.nd.array(
            (rng.randn(feat, feat) / _np.sqrt(feat)).astype(_np.float32))
        params[f"arg:{name}_bias"] = mx.nd.array(
            _np.zeros(feat, _np.float32))
    return x, params


def make_workload(n, max_length, feat, seed):
    """(lengths, inputs): mixed request lengths uniform in
    [max_length//8, max_length] and the per-request sample arrays."""
    rng = _np.random.RandomState(seed)
    lo = max(1, max_length // 8)
    lengths = rng.randint(lo, max_length + 1, size=n)
    inputs = [rng.rand(int(L), feat).astype(_np.float32) for L in lengths]
    return lengths, inputs


def poisson_arrivals(n, rate, seed):
    rng = _np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / rate, size=n)
    return _np.cumsum(gaps)


def _pct(xs, q):
    from incubator_mxnet_tpu import profiler

    return float(profiler.percentile(xs, q))


def _trial_line(n, rate, elapsed, lats, slo_ms):
    p99 = _pct(lats, 0.99)
    return {
        "rate": float(rate),
        "throughput": float(n / elapsed) if elapsed > 0 else 0.0,
        "p50_ms": _pct(lats, 0.50),
        "p99_ms": p99,
        "ok": bool(p99 <= slo_ms),
    }


# ---------------------------------------------------------------------------
# sequential baseline
# ---------------------------------------------------------------------------

class SequentialBaseline:
    """Warmed single-request predictor over the same length buckets."""

    def __init__(self, sym, params, feat, bucketer):
        from incubator_mxnet_tpu.predictor import Predictor

        self.feat = feat
        self.bucketer = bucketer
        self.pred = Predictor(
            sym, params, {"data": (1, bucketer.buckets[0], feat)})
        for lb in bucketer.buckets:  # warm every bucket
            self.pred.reshape({"data": (1, lb, self.feat)})
            self.pred.forward()

    def serve_one(self, sample):
        lb = self.bucketer.bucket_for(sample.shape[0])
        buf = _np.zeros((1, lb, self.feat), _np.float32)
        buf[0, :sample.shape[0]] = sample
        t0 = _perf()
        self.pred.reshape({"data": buf.shape})
        self.pred.predict(data=buf)
        return _perf() - t0

    def trial(self, inputs, rate, seed, slo_ms):
        """Simulated open-loop queueing from REAL measured service times."""
        arrivals = poisson_arrivals(len(inputs), rate, seed)
        done_prev = 0.0
        lats = []
        for arr, sample in zip(arrivals, inputs):
            svc = self.serve_one(sample)
            start = max(arr, done_prev)
            done_prev = start + svc
            lats.append((done_prev - arr) * 1e3)
        elapsed = done_prev - arrivals[0]
        return _trial_line(len(inputs), rate, elapsed, lats, slo_ms)


class ServedMode:
    """Real-time open loop against an InferenceServer."""

    def __init__(self, server):
        self.server = server

    def trial(self, inputs, rate, seed, slo_ms):
        arrivals = poisson_arrivals(len(inputs), rate, seed)
        pendings = [None] * len(inputs)
        submit_lag = [0.0] * len(inputs)
        t_start = _perf()

        def feeder():
            for i, (arr, sample) in enumerate(zip(arrivals, inputs)):
                now = _perf() - t_start
                if arr > now:
                    time.sleep(arr - now)
                # open-loop honesty: latency is measured from the
                # SCHEDULED Poisson arrival, so any backlog the feeder
                # itself accumulates at high rates counts against the
                # request instead of silently shifting the clock — the
                # rate search must be able to find a failing rate
                submit_lag[i] = max(0.0, (_perf() - t_start) - arr)
                pendings[i] = self.server.submit({"data": sample})

        th = threading.Thread(target=feeder, daemon=True)
        th.start()
        th.join()
        for p in pendings:
            p.result(timeout=60.0)
        elapsed = (_perf() - t_start) - arrivals[0]
        lats = [p.latency_ms + lag * 1e3
                for p, lag in zip(pendings, submit_lag)]
        return _trial_line(len(inputs), rate, elapsed, lats, slo_ms)


def max_rate_at_slo(trial_fn, inputs, base_rate, slo_ms, seed,
                    max_doublings=10, bisect_steps=2):
    """Highest Poisson arrival rate whose p99 meets the SLO: double from
    ``base_rate`` until the first failure, then bisect the last bracket.
    Returns (best_passing_trial, trials_run)."""
    trials = []
    best, lo, hi = None, None, None
    rate = base_rate
    for _ in range(max_doublings):
        t = trial_fn(inputs, rate, seed, slo_ms)
        trials.append(t)
        if t["ok"]:
            best, lo = t, rate
            rate *= 2.0
        else:
            hi = rate
            break
    if best is None:
        return None, trials
    for _ in range(bisect_steps if hi is not None else 0):
        mid = (lo + hi) / 2.0
        t = trial_fn(inputs, mid, seed, slo_ms)
        trials.append(t)
        if t["ok"]:
            best, lo = t, mid
        else:
            hi = mid
    return best, trials


# ---------------------------------------------------------------------------


def run(n_requests=400, layers=4, feat=64, max_length=128, max_batch=16,
        slo_ms=50.0, seed=0, smoke=False):
    import incubator_mxnet_tpu  # noqa: F401 — path check
    from incubator_mxnet_tpu import profiler
    from incubator_mxnet_tpu.serving import InferenceServer, ShapeBucketer

    sym, params = build_model(layers=layers, feat=feat)
    _, inputs = make_workload(n_requests, max_length, feat, seed)
    bucketer = ShapeBucketer(max_length=max_length,
                             min_bucket=max(8, max_length // 8))

    # -- sequential baseline ------------------------------------------
    seq = SequentialBaseline(sym, params, feat, bucketer)
    seq_compile0 = seq.pred.compile_stats()
    # capacity estimate anchors the rate ladder
    svc = sorted(seq.serve_one(inputs[i % len(inputs)]) for i in range(9))[4]
    base_rate = max(1.0, 0.25 / svc)
    seq_best, seq_trials = max_rate_at_slo(
        seq.trial, inputs, base_rate, slo_ms, seed)
    seq_recompiled = seq.pred.compile_stats() != seq_compile0

    # -- served mode ---------------------------------------------------
    server = InferenceServer(
        sym, params, {"data": (None, feat)},
        max_batch_size=max_batch,
        max_queue_ms=slo_ms / 5.0,
        slo_ms=slo_ms,
        length_buckets=bucketer.buckets,
        name="serving_bench")
    srv_compile0 = server.compile_stats()
    served = ServedMode(server)
    served_best, served_trials = max_rate_at_slo(
        served.trial, inputs, base_rate, slo_ms, seed)
    stats = server.stats()
    srv_recompiled = (server.compile_stats() != srv_compile0
                      or stats["bucket_miss_after_warmup"] > 0)
    server.close()

    speedup = None
    if seq_best and served_best:
        speedup = round(served_best["throughput"] / seq_best["throughput"], 2)
    from incubator_mxnet_tpu import config

    line = {
        "bench": "serving",
        **config.device_record(),
        "smoke": smoke,
        "slo_ms": slo_ms,
        "n_requests": n_requests,
        "layers": layers,
        "feat": feat,
        "max_length": max_length,
        "max_batch": max_batch,
        "length_buckets": list(bucketer.buckets),
        "single_service_ms": round(svc * 1e3, 3),
        "sequential": seq_best,
        "served": served_best,
        "trials": {"sequential": len(seq_trials),
                   "served": len(served_trials)},
        "throughput_at_slo": {
            "sequential": seq_best["throughput"] if seq_best else None,
            "served": served_best["throughput"] if served_best else None,
        },
        "speedup_at_slo": speedup,
        "recompiles_after_warmup": {
            "sequential": bool(seq_recompiled),
            "served": bool(srv_recompiled),
            "bucket_miss_after_warmup": stats["bucket_miss_after_warmup"],
        },
        "serving_counters": {k: v for k, v in profiler.counters().items()
                             if k.startswith("serving_")},
    }
    return line


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--requests", type=int, default=400)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--feat", type=int, default=64)
    p.add_argument("--max-length", type=int, default=128)
    p.add_argument("--max-batch", type=int, default=16)
    p.add_argument("--slo-ms", type=float, default=50.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--smoke", action="store_true",
                   help="small fast configuration for the CI serving tier; "
                        "the zero-recompile guard still applies")
    p.add_argument("--json", dest="json_path", default=None, metavar="PATH",
                   help="also write the result object to PATH")
    args = p.parse_args(argv)
    from incubator_mxnet_tpu import config

    config.enable_compile_cache()
    if args.smoke:
        cfg = dict(n_requests=80, layers=2, feat=16, max_length=64,
                   max_batch=8, slo_ms=args.slo_ms, seed=args.seed,
                   smoke=True)
    else:
        cfg = dict(n_requests=args.requests, layers=args.layers,
                   feat=args.feat, max_length=args.max_length,
                   max_batch=args.max_batch, slo_ms=args.slo_ms,
                   seed=args.seed)
    line = run(**cfg)
    print(json.dumps(line))
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(line, f, indent=2)
            f.write("\n")
    rec = line["recompiles_after_warmup"]
    if rec["sequential"] or rec["served"]:
        print("FAIL: a batch recompiled after warmup "
              f"({rec})", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

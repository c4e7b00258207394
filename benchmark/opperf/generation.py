"""Generation-tier benchmark: tokens/sec at a TTFT + per-token SLO.

Open-loop harness in the Gemma-on-Cloud-TPU serving shape (PAPERS.md):
prompts arrive by a **Poisson process** (open loop — arrivals don't wait
for completions, so queueing delay is real) with **mixed prompt lengths
and mixed token budgets**, and the headline metric is **tokens/sec at
SLO**: generated-token throughput at the highest sustained arrival rate
whose p99 time-to-first-token AND p99 per-output-token latency both meet
their SLOs.

Two modes over the SAME workload and the SAME engine:

* ``static`` — drain-and-refill batching (``batching="static"``):
  admissions only into an EMPTY decode batch, so utilization drains as
  each wave finishes — the pre-continuous-batching baseline.
* ``continuous`` — iteration-level continuous batching: finished
  sequences leave and queued prefills join BETWEEN decode steps.

Acceptance (ISSUE 11): continuous beats static on tokens/sec-at-SLO,
with ZERO compiles after warmup under ``MXNET_COMPILE_GUARD=raise`` —
the harness arms raise mode itself and exits non-zero if any program
compiled once warmup finished (the CI regression guard for the
slot-cache discipline).

Prints ONE JSON line (like the other opperf harnesses)::

    python benchmark/opperf/generation.py [--smoke] [--json PATH]
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

VOCAB, BOS, EOS = 17, 1, 2


def build_model(units=24, layers=1, heads=2, seed=0):
    """Tiny pre-norm encoder-decoder transformer with materialized
    (seeded, untrained) weights — the harness measures the scheduler and
    the compiled decode loop, not model quality; request lifetimes vary
    through each request's sampled token budget."""
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import Transformer

    mx.random.seed(seed)
    net = Transformer(VOCAB, units=units, hidden_size=2 * units,
                      num_heads=heads, num_encoder_layers=layers,
                      num_decoder_layers=layers, dropout=0.0, max_length=256)
    net.initialize()
    net(mx.nd.array(_np.ones((1, 8), _np.int32), dtype="int32"),
        mx.nd.array(_np.ones((1, 1), _np.int32), dtype="int32"))
    return net


def make_workload(n, max_prompt, max_new, seed):
    rng = _np.random.RandomState(seed)
    prompts = [rng.randint(3, VOCAB, int(L)).astype(_np.int32)
               for L in rng.randint(2, max_prompt + 1, size=n)]
    budgets = rng.randint(2, max_new + 1, size=n).tolist()
    return prompts, budgets


def poisson_arrivals(n, rate, seed):
    rng = _np.random.RandomState(seed)
    return _np.cumsum(rng.exponential(1.0 / rate, size=n))


def _pct(xs, q):
    from incubator_mxnet_tpu import profiler

    return float(profiler.percentile(xs, q))


def run_trial(server, prompts, budgets, rate, seed, ttft_slo_ms,
              tpot_slo_ms):
    """One open-loop trial at ``rate`` req/s.  Latency is charged from
    the SCHEDULED Poisson arrival (feeder backlog counts against the
    request — the serving.py honesty rule), so the rate search can find
    the real SLO edge."""
    n = len(prompts)
    arrivals = poisson_arrivals(n, rate, seed)
    results = [None] * n
    lag = [0.0] * n
    t0 = _perf()

    def feeder():
        for i, (arr, p, b) in enumerate(zip(arrivals, prompts, budgets)):
            now = _perf() - t0
            if arr > now:
                time.sleep(arr - now)
            lag[i] = max(0.0, (_perf() - t0) - arr)
            results[i] = server.submit(p, max_new_tokens=int(b))

    th = threading.Thread(target=feeder, daemon=True)
    th.start()
    th.join()
    tokens = 0
    ttfts, tpots = [], []
    for r, lg in zip(results, lag):
        toks = r.result(timeout=300.0)
        tokens += len(toks)
        ttfts.append((r.ttft_ms or 0.0) + lg * 1e3)
        if r.tpot_ms is not None:
            tpots.append(r.tpot_ms)
    elapsed = (_perf() - t0) - float(arrivals[0])
    p99_ttft = _pct(ttfts, 0.99)
    p99_tpot = _pct(tpots, 0.99) if tpots else 0.0
    return {
        "rate": float(rate),
        "tokens": int(tokens),
        "tokens_per_s": float(tokens / elapsed) if elapsed > 0 else 0.0,
        "ttft_ms_p50": _pct(ttfts, 0.50),
        "ttft_ms_p99": p99_ttft,
        "tpot_ms_p50": _pct(tpots, 0.50) if tpots else 0.0,
        "tpot_ms_p99": p99_tpot,
        "ok": bool(p99_ttft <= ttft_slo_ms and p99_tpot <= tpot_slo_ms),
    }


def max_rate_at_slo(server, prompts, budgets, base_rate, seed, ttft_slo_ms,
                    tpot_slo_ms, max_doublings=8, bisect_steps=2):
    trials = []
    best, lo, hi = None, None, None
    rate = base_rate
    for _ in range(max_doublings):
        t = run_trial(server, prompts, budgets, rate, seed, ttft_slo_ms,
                      tpot_slo_ms)
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
        t = run_trial(server, prompts, budgets, mid, seed, ttft_slo_ms,
                      tpot_slo_ms)
        trials.append(t)
        if t["ok"]:
            best, lo = t, mid
        else:
            hi = mid
    return best, trials


def run(n_requests=120, units=24, layers=1, max_prompt=16, max_new=24,
        slots=4, ttft_slo_ms=250.0, tpot_slo_ms=50.0, seed=0, smoke=False):
    from incubator_mxnet_tpu import profiler
    from incubator_mxnet_tpu.serving import GenerationServer

    # the acceptance contract IS raise mode: one stray compile after
    # warmup fails every in-flight request, which fails the harness
    profiler.set_config(compile_guard="raise")
    net = build_model(units=units, layers=layers, seed=seed)
    prompts, budgets = make_workload(n_requests, max_prompt, max_new, seed)

    from incubator_mxnet_tpu import config

    line = {
        "bench": "generation",
        **config.device_record(),
        "smoke": smoke,
        "n_requests": n_requests,
        "units": units,
        "layers": layers,
        "max_prompt": max_prompt,
        "max_new": max_new,
        "slots_per_bucket": slots,
        "ttft_slo_ms": ttft_slo_ms,
        "tpot_slo_ms": tpot_slo_ms,
        "modes": {},
        "recompiles_after_warmup": {},
    }
    base_rate = None
    for mode in ("static", "continuous"):
        server = GenerationServer(
            net, bos=BOS, eos=EOS, max_prompt_length=max_prompt,
            max_new_tokens=max_new, slots_per_bucket=slots,
            tenants={"default": {"max_queue": 100000}},
            batching=mode, name=f"gen_bench_{mode}")
        try:
            if base_rate is None:
                # capacity anchor: one request alone, steady state
                t0 = _perf()
                toks = server.submit(prompts[0],
                                     max_new_tokens=int(budgets[0])) \
                    .result(120.0)
                svc = max(1e-4, _perf() - t0)
                base_rate = max(0.5, 0.25 * slots * len(toks)
                                / (svc * float(_np.mean(budgets))))
            steady0 = profiler.counters()["recompile_steady_state"]
            comp0 = server.compile_stats()["compiles"]
            best, trials = max_rate_at_slo(
                server, prompts, budgets, base_rate, seed, ttft_slo_ms,
                tpot_slo_ms)
            recompiled = (
                profiler.counters()["recompile_steady_state"] != steady0
                or server.compile_stats()["compiles"] != comp0)
            line["modes"][mode] = {"best": best, "trials": len(trials)}
            line["recompiles_after_warmup"][mode] = bool(recompiled)
        finally:
            server.close()
            profiler.disarm_compile_guard()
    cont = line["modes"]["continuous"]["best"]
    stat = line["modes"]["static"]["best"]
    line["tokens_per_s_at_slo"] = {
        "continuous": cont["tokens_per_s"] if cont else None,
        "static": stat["tokens_per_s"] if stat else None,
    }
    line["speedup_at_slo"] = (
        round(cont["tokens_per_s"] / stat["tokens_per_s"], 2)
        if cont and stat and stat["tokens_per_s"] > 0 else None)
    profiler.set_config(compile_guard=None)
    return line


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--requests", type=int, default=120)
    p.add_argument("--units", type=int, default=24)
    p.add_argument("--layers", type=int, default=1)
    p.add_argument("--max-prompt", type=int, default=16)
    p.add_argument("--max-new", type=int, default=24)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--ttft-slo-ms", type=float, default=250.0)
    p.add_argument("--tpot-slo-ms", type=float, default=50.0)
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
        cfg = dict(n_requests=40, units=16, layers=1, max_prompt=8,
                   max_new=12, slots=4, ttft_slo_ms=args.ttft_slo_ms,
                   tpot_slo_ms=args.tpot_slo_ms, seed=args.seed, smoke=True)
    else:
        cfg = dict(n_requests=args.requests, units=args.units,
                   layers=args.layers, max_prompt=args.max_prompt,
                   max_new=args.max_new, slots=args.slots,
                   ttft_slo_ms=args.ttft_slo_ms,
                   tpot_slo_ms=args.tpot_slo_ms, seed=args.seed)
    line = run(**cfg)
    print(json.dumps(line))
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(line, f, indent=2)
            f.write("\n")
    if any(line["recompiles_after_warmup"].values()):
        print(f"FAIL: a program compiled after warmup "
              f"({line['recompiles_after_warmup']})", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

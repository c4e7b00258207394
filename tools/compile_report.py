#!/usr/bin/env python
"""Offline report over the profiler's compile registry — "what compiled,
why, and what did it cost", by jit site.

Input is either a chrome-trace JSON written by ``profiler.dump()`` (the
registry rides under ``otherData.compiles``) or a bare registry dump
(``json.dump(profiler.compile_registry(), f)``); several inputs (per-rank
dumps) are merged.  ``.json.gz`` files are read transparently.

Usage::

    python tools/compile_report.py profile.json [--top 15] [--json]
                                   [--xplane DIR/mxtpu_profile]
    python tools/compile_report.py --analytic            # bench-config
                                   [--configs resnet50 ...]  # FLOPs table

Sections:

* **per-site totals** — compiles, wall ms, recompiles, steady-state
  violations, and (when XLA cost accounting was captured —
  ``MXNET_COMPILE_COST=1``) FLOPs / bytes-accessed / code-size totals;
* **top recompile culprits** — recompiles grouped by (site, offending
  argument, drift kind) with the attribution line, sorted by wall cost:
  the "why is this still compiling" answer;
* **individual compiles** — the top-N by wall time with program +
  signature summary;
* ``--xplane DIR`` — the device HLO-op table parsed from an xprof capture
  via the shared ``profiler.iter_xplane_ops`` reader (same stream
  ``tools/parse_xplane.py`` and ``dumps()`` present);
* ``--analytic`` — with no dump, the bench-config analytic FLOPs/MFU
  table that used to live in ``tools/flops_report.py`` (kept there as a
  deprecated shim); with a dump, the K-fold scan-body attribution table
  (whole-program cost / K iterations for ``gluon.step_fold_k`` /
  ``gluon.fold_eval`` compiles — see docs/step_fold.md).

Exit codes: 0 on success, 2 on an unreadable/empty registry.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the bench configs the --analytic mode counts FLOPs for.  Counts only: a
# utilisation needs a rate measured on a chip (the driver's ledger) and
# that chip's peak (tools/device_peaks.py) — none is assumed here.
ANALYTIC_CONFIGS = ("resnet50", "ssd512-resnet18", "ssd512-vgg16",
                    "yolo3-darknet53", "bert-base-mlm", "transformer-big")


def _open(path):
    if path.endswith(".gz"):
        return gzip.open(path, "rt")
    return open(path)


def load_registry(path):
    """Compile registry from a profiler.dump() trace or a bare
    compile_registry() dump."""
    if os.path.getsize(path) == 0:
        raise ValueError("empty file (0 bytes)")
    with _open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "records" in doc:
        return doc
    if isinstance(doc, dict):
        comp = (doc.get("otherData") or {}).get("compiles")
        if comp is not None:
            return comp
    raise ValueError("no compile registry found (neither a "
                     "compile_registry() dump nor a profiler.dump() trace "
                     "with otherData.compiles)")


def merge_registries(regs):
    sites = defaultdict(lambda: {"count": 0, "ms": 0.0, "recompiles": 0,
                                 "signatures": 0})
    records = []
    for reg in regs:
        for s, e in (reg.get("sites") or {}).items():
            d = sites[s]
            for k in ("count", "recompiles", "signatures"):
                d[k] += e.get(k, 0)
            d["ms"] += e.get("ms", 0.0)
        records.extend(reg.get("records") or [])
    records.sort(key=lambda r: r.get("time_unix", 0))
    return {"sites": dict(sites), "records": records}


def _sig_summary(sig, limit=4):
    parts = []
    for k in sorted(k for k in sig if k != "__program__"):
        v = sig[k]
        if isinstance(v, dict) and v.get("k") == "array":
            shape = "x".join(str(d) for d in v.get("shape", ()))
            parts.append(f"{k}={v.get('dtype', '?')}[{shape}]")
        else:
            val = v.get("value") if isinstance(v, dict) else v
            parts.append(f"{k}={val}")
    extra = f" (+{len(parts) - limit})" if len(parts) > limit else ""
    return ", ".join(parts[:limit]) + extra


def summarize(reg):
    """Machine-readable summary (--json; also what the report prints)."""
    sites = reg.get("sites") or {}
    records = reg.get("records") or []
    cost = defaultdict(lambda: {"flops": 0.0, "bytes_accessed": 0.0,
                                "code_bytes": 0, "with_cost": 0})
    steady = defaultdict(int)
    culprits = {}
    for r in records:
        site = r.get("site", "?")
        if r.get("steady_state"):
            steady[site] += 1
        c = r.get("cost") or {}
        if c:
            d = cost[site]
            d["flops"] += c.get("flops") or 0.0
            d["bytes_accessed"] += c.get("bytes_accessed") or 0.0
            d["code_bytes"] += c.get("code_bytes") or 0
            d["with_cost"] += 1
        if r.get("recompile"):
            f = (r.get("findings") or [{}])[0]
            key = (site, f.get("arg", "<none>"), f.get("kind", "<repeat>"))
            cu = culprits.setdefault(key, {"site": site,
                                           "arg": f.get("arg"),
                                           "kind": f.get("kind"),
                                           "count": 0, "ms": 0.0,
                                           "example": r.get("attribution")})
            cu["count"] += 1
            cu["ms"] += r.get("wall_ms", 0.0)
    return {
        "sites": sites,
        "steady_state_by_site": dict(steady),
        "cost_by_site": {k: dict(v) for k, v in cost.items()},
        "culprits": sorted(culprits.values(), key=lambda c: -c["ms"]),
        "total_compiles": sum(e.get("count", 0) for e in sites.values()),
        "total_ms": round(sum(e.get("ms", 0.0) for e in sites.values()), 3),
        "total_recompiles": sum(e.get("recompiles", 0)
                                for e in sites.values()),
        "total_steady_state": sum(steady.values()),
    }


def report(reg, top=15, out=sys.stdout):
    w = out.write
    summ = summarize(reg)
    records = reg.get("records") or []

    w(f"compile registry: {summ['total_compiles']} compiles, "
      f"{summ['total_ms']:.1f} ms total, {summ['total_recompiles']} "
      f"recompiles ({summ['total_steady_state']} in steady state)\n\n")

    w("Per-site totals:\n")
    w(f"{'site':<26}{'compiles':>9}{'wall(ms)':>11}{'recompile':>10}"
      f"{'steady':>8}{'GFLOP':>10}{'MB moved':>10}\n")
    for site, e in sorted(summ["sites"].items(), key=lambda kv: -kv[1]["ms"]):
        c = summ["cost_by_site"].get(site) or {}
        gflop = (f"{c['flops'] / 1e9:.2f}" if c.get("flops") else "-")
        mb = (f"{c['bytes_accessed'] / 1e6:.1f}"
              if c.get("bytes_accessed") else "-")
        w(f"{site:<26}{e['count']:>9}{e['ms']:>11.1f}{e['recompiles']:>10}"
          f"{summ['steady_state_by_site'].get(site, 0):>8}{gflop:>10}"
          f"{mb:>10}\n")

    # step-fold callout (docs/step_fold.md): the fold sites compile once
    # per (batch signature, optimizer-group-set[, K]); ANY steady-state
    # compile here means the single-program-per-(K-)step contract broke.
    # gluon.step_fold_k is the K-step scan program, gluon.fold_eval the
    # folded eval program — distinct program names per K are expected,
    # steady-state recompiles of an already-seen one are not.
    _FOLD_SITES = ("gluon.step_fold", "gluon.step_fold_k", "gluon.fold_eval")
    fold_records = [r for r in records if r.get("site") in _FOLD_SITES]
    if fold_records:
        progs = defaultdict(int)
        for r in fold_records:
            progs[str(r.get("program") or "step_fold")] += 1
        steady_fold = sum(summ["steady_state_by_site"].get(s, 0)
                          for s in _FOLD_SITES)
        w("\nStep fold (" + "/".join(
            s for s in _FOLD_SITES
            if any(r.get("site") == s for r in fold_records)) + "): "
          + ", ".join(f"{p} x{n}" for p, n in sorted(progs.items()))
          + (f" — {steady_fold} STEADY-STATE recompile(s): the one-"
             "dispatch-per-step contract broke" if steady_fold
             else " — zero steady-state recompiles") + "\n")

    if summ["culprits"]:
        w(f"\nTop recompile culprits (by wall cost):\n")
        w(f"{'site':<26}{'argument':<16}{'drift':<12}{'count':>6}"
          f"{'wall(ms)':>10}\n")
        for cu in summ["culprits"][:top]:
            w(f"{cu['site']:<26}{str(cu['arg']):<16}{str(cu['kind']):<12}"
              f"{cu['count']:>6}{cu['ms']:>10.1f}\n")
            if cu.get("example"):
                w(f"    e.g. {cu['example']}\n")

    if records:
        w(f"\nTop {top} compiles by wall time:\n")
        w(f"{'site':<26}{'program':<22}{'step':>6}{'wall(ms)':>10}"
          "  signature\n")
        for r in sorted(records, key=lambda r: -r.get("wall_ms", 0))[:top]:
            sig = r.get("signature") or {}
            prog = str(r.get("program") or "-")
            w(f"{r.get('site', '?'):<26}{prog[:22]:<22}"
              f"{r.get('step', '-'):>6}{r.get('wall_ms', 0):>10.1f}"
              f"  {_sig_summary(sig)}\n")


def xplane_report(trace_dir, top=20, out=sys.stdout):
    """Device HLO-op cost table via the shared xplane reader (the summary
    that used to require tools/parse_xplane.py alongside flops_report)."""
    from incubator_mxnet_tpu.profiler import collapse_hlo_name, iter_xplane_ops

    w = out.write
    by_inst = defaultdict(lambda: [0, 0])
    grand = 0
    for name, ps in iter_xplane_ops(trace_dir):
        inst, _ = collapse_hlo_name(name)
        by_inst[inst][0] += 1
        by_inst[inst][1] += ps
        grand += ps
    if not grand:
        w(f"(no device 'XLA Ops' events under {trace_dir})\n")
        return
    w(f"\nDevice HLO ops ({trace_dir}; total "
      f"{grand / 1e9:.3f} ms device time):\n")
    w(f"{'HLO op':<44}{'count':>8}{'total(ms)':>12}{'%':>7}\n")
    for inst, (cnt, ps) in sorted(by_inst.items(),
                                  key=lambda kv: -kv[1][1])[:top]:
        w(f"{inst[:44]:<44}{cnt:>8}{ps / 1e9:>12.3f}"
          f"{100 * ps / grand:>6.1f}%\n")


def fold_analytic_report(reg, out=sys.stdout):
    """Per-iteration cost attribution for K-step fold scan bodies.

    A ``gluon.step_fold_k`` compile covers K scan iterations in ONE
    program, so the XLA cost analysis captured under
    ``MXNET_COMPILE_COST=1`` reports K iterations' worth of flops and
    bytes.  The honest per-logical-step number is whole-program cost / K;
    K is parsed from the program name (``step_fold_k[4]``,
    ``fold_eval[8]``).  Comparing GFLOP/iter across K values is the quick
    check that the scan body really is the K=1 step and the fold is pure
    dispatch amortisation, not a different program."""
    import re
    rows = []
    for r in reg.get("records") or []:
        site = r.get("site")
        if site not in ("gluon.step_fold", "gluon.step_fold_k",
                        "gluon.fold_eval"):
            continue
        prog = str(r.get("program") or "step_fold")
        m = re.search(r"\[(\d+)\]", prog)
        k = int(m.group(1)) if m else 1
        c = r.get("cost") or {}
        rows.append((site, prog, k, c.get("flops"),
                     c.get("bytes_accessed"), r.get("wall_ms", 0.0)))
    w = out.write
    if not rows:
        w("\n(no step-fold compiles in the registry — nothing to "
          "attribute per scan iteration)\n")
        return
    w("\nK-fold scan-body attribution (whole-program cost / K iterations; "
      "needs MXNET_COMPILE_COST=1 for flops/bytes):\n")
    w(f"{'site':<22}{'program':<22}{'K':>4}{'GFLOP/iter':>12}"
      f"{'MB/iter':>10}{'compile(ms)':>13}\n")
    for site, prog, k, fl, by, ms in sorted(rows, key=lambda r: (r[0], r[2])):
        g = f"{fl / k / 1e9:.3f}" if fl else "-"
        mb = f"{by / k / 1e6:.2f}" if by else "-"
        w(f"{site:<22}{prog[:22]:<22}{k:>4}{g:>12}{mb:>10}{ms:>13.1f}\n")


# -- analytic bench-config FLOPs (absorbed from tools/flops_report.py) -------


def _fwd_flops_per_sample(net, *inputs):
    import jax

    fn, params = net.export_jittable()
    lowered = jax.jit(lambda p, *xs: fn(p, *xs)).lower(params, *inputs)
    cost = lowered.compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"]) / inputs[0].shape[0]


def _build(config):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import incubator_mxnet_tpu as mx

    cpu = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu):
        mx.random.seed(0)
        if config == "resnet50":
            from incubator_mxnet_tpu.gluon.model_zoo.vision import resnet50_v1
            net = resnet50_v1()
            x = jnp.zeros((1, 3, 224, 224), jnp.float32)
        elif config == "ssd512-resnet18":
            from incubator_mxnet_tpu.gluon.model_zoo.ssd import ssd_512_resnet18
            net = ssd_512_resnet18()
            x = jnp.zeros((1, 3, 512, 512), jnp.float32)
        elif config == "ssd512-vgg16":
            from incubator_mxnet_tpu.gluon.model_zoo.ssd import (
                ssd_512_vgg16_atrous)
            net = ssd_512_vgg16_atrous()
            x = jnp.zeros((1, 3, 512, 512), jnp.float32)
        elif config == "yolo3-darknet53":
            from incubator_mxnet_tpu.gluon.model_zoo.yolo import yolo3_darknet53
            net = yolo3_darknet53()
            x = jnp.zeros((1, 3, 416, 416), jnp.float32)
        elif config == "bert-base-mlm":
            from incubator_mxnet_tpu.gluon.model_zoo.bert import (
                BERTForPretrain, bert_base)
            net = BERTForPretrain(bert_base(vocab_size=30522, max_length=512,
                                            dropout=0.0), vocab_size=30522)
            S, Pn = 128, 20
            xs = (jnp.zeros((1, S), jnp.int32), jnp.zeros((1, S), jnp.int32),
                  jnp.zeros((1, Pn), jnp.int32))
        elif config == "transformer-big":
            from incubator_mxnet_tpu.gluon.model_zoo.transformer import (
                transformer_big)
            net = transformer_big(vocab_size=32768, max_length=512,
                                  dropout=0.0)
            S = 256
            xs = (jnp.zeros((1, S), jnp.int32), jnp.zeros((1, S), jnp.int32))
        else:
            raise ValueError(config)
        net.initialize()
        if config in ("bert-base-mlm", "transformer-big"):
            net(*[mx.nd.array(np.asarray(v)) for v in xs])
            return net, xs
        net(mx.nd.array(np.asarray(x)))  # materialize deferred shapes
        return net, (x,)


def analytic_report(configs=None, out=sys.stdout):
    """The bench-config analytic FLOP table: XLA's forward FLOP count per
    sample (training = 3x fwd by convention).  A count, valid from any
    backend; it is the numerator of an MFU, not an MFU."""
    rows = []
    for config in (configs or ANALYTIC_CONFIGS):
        net, xs = _build(config)
        gflops = _fwd_flops_per_sample(net, *xs) / 1e9
        rows.append((config, gflops))
        out.write(json.dumps({
            "metric": f"{config}_fwd_gflops_per_sample",
            "value": round(gflops, 2),
        }) + "\n")
        out.flush()

    out.write("\n| config | fwd GFLOP/sample | train GFLOP/sample (3x) |\n")
    out.write("|---|---|---|\n")
    for config, gflops in rows:
        out.write(f"| {config} | {gflops:.1f} | {3 * gflops:.1f} |\n")
    return 0


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("dump", nargs="*",
                   help="profiler.dump() trace(s) or compile_registry() "
                        "JSON dump(s); merged when several")
    p.add_argument("--top", type=int, default=15)
    p.add_argument("--json", action="store_true",
                   help="print the machine-readable summary instead")
    p.add_argument("--xplane", default=None,
                   help="xprof trace dir: append the device HLO-op table")
    p.add_argument("--analytic", action="store_true",
                   help="no dump: bench-config analytic FLOPs table (ex "
                        "tools/flops_report.py); with a dump: per-iteration "
                        "K-fold scan-body cost attribution")
    p.add_argument("--configs", nargs="*", default=None,
                   help="--analytic: subset of bench configs")
    args = p.parse_args(argv)

    if args.analytic and not args.dump:
        return analytic_report(args.configs)
    if not args.dump:
        p.error("give at least one dump file (or --analytic)")
    try:
        reg = merge_registries([load_registry(d) for d in args.dump])
    except (OSError, ValueError, KeyError, json.JSONDecodeError) as e:
        print(f"compile_report: invalid dump: {e}", file=sys.stderr)
        return 2
    if not (reg.get("records") or reg.get("sites")):
        print("compile_report: registry is empty — nothing ever compiled "
              "or the dump predates the compile registry", file=sys.stderr)
        return 2
    try:
        if args.json:
            json.dump(summarize(reg), sys.stdout, indent=2, default=str)
            sys.stdout.write("\n")
        else:
            report(reg, top=args.top)
        if args.analytic:
            # with a dump: per-iteration scan-body attribution instead of
            # (in addition to --configs would be ambiguous) the bench table
            fold_analytic_report(reg)
        if args.xplane:
            xplane_report(args.xplane, top=args.top)
    except BrokenPipeError:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run ONE cell of BENCHMARK.json once and print one JSON line.

    python3 chipbench/run.py --workload W --seed N --seconds S --trace 0|1

Everything that belongs to one configuration, one traffic mix or one per-layer
metric sits in a data file found by the name in ``BENCHMARK.json``:
``configs/<config>.json`` names its builder, ``traffic/<mix>.json`` its
driver, ``layer_metrics/<metric>.json`` its reducer.  See README.md.

The last line of standard output is the result; every other line starts with
``[chipbench]``.  Without a TPU the run fails (non-zero, no result line);
``--dry-run-cpu`` rehearses the control flow at tiny widths on the CPU and
prints ``"value": null`` for every metric.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import trace_read  # noqa: E402

TRACE_SECONDS = 3.0  # a traced window: long enough for ~70 train steps or ~90 scheduler iterations


PREFIX = ["[chipbench]"]


def say(message):
    print(f"{PREFIX[0]} {message}", flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def metrics_of(bench, section, cell):
    """The metrics of ``section`` that ``cell`` reports: those without a
    ``workloads`` key, and those that list the cell."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


class Span:
    """A stretch of the run under the ``chipbench.window`` annotation, in a
    profiler trace of its own if ``traced``.  The span named "window" is the
    measured one: entering it ends set-up."""

    def __init__(self, ctx, part, traced):
        self.ctx, self.part, self.traced = ctx, part, traced
        self.t0 = self.t1 = None

    def __enter__(self):
        import jax

        if self.traced:
            path = self.ctx.trace_path(self.part)
            shutil.rmtree(path, ignore_errors=True)  # a fixed path, emptied
            os.makedirs(path)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # the Python tracer slows the host
            options.host_tracer_level = 2
            jax.profiler.start_trace(path, profiler_options=options)
        self._span = jax.profiler.TraceAnnotation(trace_read.WINDOW_SPAN)
        self._span.__enter__()
        self.t0 = time.perf_counter()
        if self.part == "window":
            self.ctx.setup_s = self.t0 - T_PROCESS_START
        return self

    def __exit__(self, *exc):
        import jax

        self.t1 = time.perf_counter()
        self._span.__exit__(*exc)
        if self.traced:
            jax.profiler.stop_trace()
        return False


class Context:
    """What a builder, a driver and a reducer are handed."""

    def __init__(self, cell, config, traffic, seed, seconds, trace, dry_run, devices):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed = int(seed)
        self.seed31 = int(seed) & 0x7FFFFFFF  # for APIs that take 31 bits
        self.trace, self.dry_run, self.devices = bool(trace), dry_run, devices
        self.window_seconds = min(seconds, TRACE_SECONDS) if trace else seconds
        self.setup_s = None
        self.say = say

    def window(self):
        """The measured window; traced in a ``--trace 1`` run."""
        return Span(self, "window", self.trace)

    def traced_span(self, part):
        """A traced stretch of set-up, e.g. the identification pass."""
        return Span(self, part, True)

    def say_memory(self, when):
        return say_memory(self.devices, when)

    def trace_path(self, part):
        return os.path.join(ROOT, ".chipbench_trace", self.cell["name"], part)


def memory_peaks(devices):
    """The fullest chip's ``peak_bytes_in_use`` and ``peak_bytes_reserved``.
    On this runtime the first counts live arrays only and a running program's
    scratch is held as reserved (PERF.md, PR 24: reserved grows with the batch
    and equals the compiled step's temp size, while in-use does not move), so
    ``memory_peak_bytes`` is their sum.  Both are printed and reported apart
    so that the sum can be audited."""
    rows = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            rows.append({"peak_bytes_in_use": int(stats["peak_bytes_in_use"]),
                         "peak_bytes_reserved": int(stats.get("peak_bytes_reserved", 0)),
                         "bytes_limit": int(stats.get("bytes_limit", 0))})
    return max(rows, key=lambda r: r["peak_bytes_in_use"] + r["peak_bytes_reserved"],
               default=None)


def say_memory(devices, when):
    peaks = memory_peaks(devices)
    say(f"memory {when}: {peaks}")
    return peaks


def layer_metric_spec(name):
    """``layer_metrics/<name>.json``, or for ``<quantity>.<cells>`` the file
    of the quantity: ``device_idle_share.train`` and ``.backlog`` are one
    reader with two ``moves``."""
    for stem in (name, name.rsplit(".", 1)[0]):
        path = os.path.join(HERE, "layer_metrics", stem + ".json")
        if os.path.exists(path):
            return load_json(path)
    raise FileNotFoundError(f"no layer_metrics file for {name!r}")


def reduce_layer_metrics(bench, ctx, result, trace):
    """The cell's per-layer metrics, and the names of those whose reader
    found nothing to read."""
    out, missing = {}, []
    for m in metrics_of(bench, "per_layer", ctx.cell["name"]):
        spec = layer_metric_spec(m["name"])
        reducer = importlib.import_module(f"chipbench.reducers.{spec['reducer']}")
        value = reducer.reduce(spec.get("arguments", {}), ctx, result, trace)
        if value is None:
            missing.append(m["name"])
        else:
            out[m["name"]] = {"value": None if ctx.dry_run else float(value),
                              "unit": m["unit"]}
    return out, missing


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run-cpu", action="store_true")
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"unknown workload {args.workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[args.workload]
    config_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, config_entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    seconds = float(bench["run_seconds"] if args.seconds is None else args.seconds)
    if args.dry_run_cpu:
        config.update(config.get("dry_run", {}))
        traffic.update(traffic.get("dry_run", {}))
        seconds = min(seconds, 2.0)

    import jax

    if args.dry_run_cpu:
        jax.config.update("jax_platforms", "cpu")
        PREFIX[0] = "[chipbench CPU rehearsal: no figure below is a device figure]"
    import incubator_mxnet_tpu as mx

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.dry_run_cpu:
        raise SystemExit(f"chipbench: no TPU - jax.devices()[0].platform is "
                         f"{platform!r}; these are device metrics "
                         f"(--dry-run-cpu rehearses the path on the CPU)")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"chipbench: {cell['name']} needs {cell['chips']} "
                         f"chip(s), JAX finds {len(devices)}")
    devices = devices[:cell["chips"]]
    cache_dir = mx.config.enable_compile_cache()
    # cache every program, however quick its compile: set-up is the same
    # work in every run after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": None}
    say(f"{cell['name']} seed {args.seed} seconds {seconds} trace {args.trace} "
        f"on {json.dumps(device)}; jax {jax.__version__}; compile cache {cache_dir}; "
        f"imports and device start-up took {time.perf_counter() - T_PROCESS_START:.1f} s")

    ctx = Context(cell, config, traffic, args.seed, seconds, args.trace,
                  args.dry_run_cpu, devices)
    builder = importlib.import_module(f"chipbench.builders.{config['builder']}")
    driver = importlib.import_module(f"chipbench.drivers.{traffic['driver']}")
    with contextlib.ExitStack() as stack:
        system = builder.build(ctx)
        if "close" in system:
            stack.callback(system["close"])
        say(f"built in {time.perf_counter() - T_PROCESS_START:.1f} s since "
            f"process start")
        result = driver.run(system, ctx)
        # read before the harness's own late work (the serve reference check)
        peaks = say_memory(devices, "after the window")
        if peaks:
            device.update(peaks, memory_peak_bytes=peaks["peak_bytes_in_use"]
                          + peaks["peak_bytes_reserved"])
            del device["bytes_limit"]
        if "late_checks" in system:
            result["checks"].update(system["late_checks"]())
    say(f"set-up took {ctx.setup_s:.2f} s; checks {result['checks']}")

    out = {"correct": all(result["checks"].values()),
           "attempted": int(result["attempted"]), "failed": int(result["failed"])}
    if args.trace:
        t0 = time.perf_counter()
        trace = trace_read.load(ctx.trace_path("window"))
        out["metrics"], missing = reduce_layer_metrics(bench, ctx, result, trace)
        if missing:
            # on the chip a reader that finds nothing means a renamed program
            # or a lost trace plane: the yardstick is gone, so the run fails
            say(f"nothing to read for {missing}: left out"
                + ("" if args.dry_run_cpu else "; the run is not correct"))
            out["correct"] = out["correct"] and args.dry_run_cpu
        if trace.devices:
            device["busy_s"] = trace_read.device_busy_seconds(trace)
            device["window_s"] = trace.window_s
            out["breakdown"] = {
                "device_ops": trace_read.top_ops(trace),
                "idle_gaps": trace_read.attribute_gaps(
                    trace_read.idle_gaps(trace), trace.host)}
            for key, rows in out["breakdown"].items():
                for name, secs in rows:
                    say(f"{key}: {secs:.6f} s  {name}")
            say(f"trace reduced in {time.perf_counter() - t0:.1f} s: busy "
                f"{device['busy_s']:.4f} s of {device['window_s']:.4f} s")
    else:
        out["metrics"] = {}
        for m in metrics_of(bench, "end_to_end", cell["name"]):
            value = ctx.setup_s if m["name"] == "setup_s" else result["values"][m["name"]]
            if value is None or value != value or value in (float("inf"), float("-inf")):
                out["correct"], value = False, None
            out["metrics"][m["name"]] = {
                "value": None if args.dry_run_cpu else value, "unit": m["unit"]}
    out["device"] = device
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

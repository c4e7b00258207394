"""Reduce a JAX profiler trace (``*.xplane.pb``) to plain Python data.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a TPU trace
holds (seen on a v5e, jax 0.9.0): one plane ``/device:TPU:<n>`` per chip with
the lines ``XLA Modules`` (one event per program execution, named
``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event per operation), and
one plane ``/host:CPU`` with a line per host thread holding the runtime's
spans and every ``jax.profiler.TraceAnnotation``.  All times are nanoseconds
on one clock.

Everything here works on ``(start_ns, end_ns, name)`` tuples so that the
arithmetic can be tested without a trace file (``chipbench/tests``).
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
WINDOW_SPAN = "chipbench.window"


class Trace:
    """The events of one trace, clipped to the measured window."""

    def __init__(self, devices, host, window):
        self.devices = devices      # [{"modules": [...], "ops": [...]}] per chip
        self.host = host            # [(start, end, name)] over all host threads
        self.window = window        # (start_ns, end_ns)

    @property
    def window_s(self):
        return (self.window[1] - self.window[0]) / 1e9


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return files[-1]


def _events(line):
    out = []
    for ev in line.events:
        start = float(ev.start_ns)
        out.append((start, start + float(ev.duration_ns), ev.name))
    return out


def load(trace_dir):
    """Read the newest trace under ``trace_dir``.  The window is the
    ``chipbench.window`` annotation the harness wraps round the measurement;
    device events are clipped to it."""
    import jax

    data = jax.profiler.ProfileData.from_file(find_xplane(trace_dir))
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append({
                "modules": _events(lines[MODULE_LINE]) if MODULE_LINE in lines else [],
                "ops": _events(lines[OP_LINE]) if OP_LINE in lines else [],
            })
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                host.extend(_events(ln))
    spans = [e for e in host if e[2] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} annotation")
    window = (spans[-1][0], spans[-1][1])
    for dev in devices:
        dev["modules"] = clip(dev["modules"], window)
        dev["ops"] = clip(dev["ops"], window)
    return Trace(devices, host, window)


def clip(events, window):
    """Events that overlap ``window``, cut to it."""
    w0, w1 = window
    return [(max(s, w0), min(e, w1), n) for s, e, n in events
            if e > w0 and s < w1]


def union_intervals(events):
    """Merge overlapping ``(start, end, ...)`` into disjoint sorted intervals."""
    merged = []
    for s, e, *_ in sorted(events):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_seconds(events):
    """Seconds in which at least one of ``events`` ran: the union, so that
    overlapping operations are not counted twice."""
    return sum(e - s for s, e in union_intervals(events)) / 1e9


def overlap_seconds(a, b):
    """Seconds in which an interval of ``a`` and one of ``b`` are both open;
    each a list of disjoint sorted ``(start, end)`` as ``union_intervals``
    gives them."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total / 1e9


def device_busy_seconds(trace):
    """Busy seconds averaged over the chips (operations; a chip whose trace
    has no operation line falls back to its program executions)."""
    per_chip = [busy_seconds(d["ops"] or d["modules"]) for d in trace.devices]
    return sum(per_chip) / len(per_chip) if per_chip else 0.0


def program_times(trace):
    """``{program name: [seconds of each execution]}`` summed over nothing:
    one entry per execution on chip 0 (programs run in lockstep on a mesh)."""
    out = {}
    if trace.devices:
        for s, e, name in trace.devices[0]["modules"]:
            out.setdefault(name, []).append((e - s) / 1e9)
    return out


def top_ops(trace, n=10):
    """The ``n`` operations with most device time on chip 0, as
    ``[name, seconds]`` with the name cut to its HLO result name and kind."""
    raw = {}
    if trace.devices:
        for s, e, name in trace.devices[0]["ops"]:
            raw[name] = raw.get(name, 0.0) + (e - s) / 1e9
    total = {}
    for name, secs in raw.items():  # shorten each distinct name once
        key = short_op_name(name)
        total[key] = total.get(key, 0.0) + secs
    return sorted(([k, v] for k, v in total.items()),
                  key=lambda kv: -kv[1])[:n]


def short_op_name(name, limit=160):
    """``%fusion.7 = bf16[8,128]{1,0:T(8,128)} fusion(...)`` keeps its head:
    result name, result shape (layouts dropped) and the op, without operands."""
    name = re.sub(r"\{[^}]*\}", "", name)
    if " = " not in name:
        return name[:limit]
    result, rest = name.split(" = ", 1)
    if rest.startswith("("):  # a tuple result: the op follows its closing ")"
        close = rest.find(") ")
        shape, rest = rest[:close + 1], rest[close + 2:]
    else:
        shape, _, rest = rest.partition(" ")
    return f"{result} = {shape} {rest.split('(', 1)[0]}"[:limit]


def idle_gaps(trace, min_gap_ns=20_000.0):
    """Device-idle gaps of chip 0 inside the window, longest first, as
    ``(start, end)``; gaps shorter than ``min_gap_ns`` are launch latency
    between back-to-back programs and are left out."""
    if not trace.devices:
        return []
    busy = union_intervals(trace.devices[0]["ops"] or trace.devices[0]["modules"])
    edges = [trace.window[0]] + [t for iv in busy for t in iv] + [trace.window[1]]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= min_gap_ns]
    return sorted(gaps, key=lambda g: g[0] - g[1])


def attribute_gaps(gaps, host_events, n=10, ignore=(WINDOW_SPAN,),
                   max_gaps=2000):
    """Key each gap by the host span open at its midpoint (the shortest such
    span, i.e. the innermost) and sum the idle seconds by that name.
    Returns ``[[name, seconds], ...]``, the ``n`` largest; ``no-span`` is
    idle time during which no traced host span was open, and the gaps past
    the ``max_gaps`` longest are summed as ``shorter-gaps``."""
    import numpy as np

    spans = [(s, e, nm) for s, e, nm in host_events
             if nm not in ignore and e > s]
    starts = np.array([s for s, _, _ in spans], dtype=np.float64)
    ends = np.array([e for _, e, _ in spans], dtype=np.float64)
    durs = ends - starts
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    total = {}
    for g0, g1 in gaps[:max_gaps]:
        mid = (g0 + g1) / 2
        key = "no-span"
        if len(spans):
            open_ = np.flatnonzero((starts <= mid) & (ends >= mid))
            if open_.size:
                key = spans[int(open_[np.argmin(durs[open_])])][2]
        total[key] = total.get(key, 0.0) + (g1 - g0) / 1e9
    rest = sum(g1 - g0 for g0, g1 in gaps[max_gaps:]) / 1e9
    if rest:
        total["shorter-gaps"] = rest
    return sorted(([k, v] for k, v in total.items()),
                  key=lambda kv: -kv[1])[:n]

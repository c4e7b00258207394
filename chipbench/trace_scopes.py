"""Which phase of the program a device operation belongs to.

A TPU trace names an operation by its HLO text
(``%fusion.2078 = bf16[...] fusion(...), kind=kLoop, calls=...``) and carries
no ``op_name`` (seen on a v5e, jax 0.9.0: an ``XLA Ops`` event has the stats
``device_offset_ps``, ``device_duration_ps`` and ``Time Scale Multiplier``
and nothing else).  The scope therefore comes from a join on the HLO
instruction name, the head of the event's name, against the compiled
program's own optimized text, which the program serves on demand
(``incubator_mxnet_tpu.profiler.compiled_text(site)``); every instruction
there carries ``metadata={op_name="jit(pure_step)/jvp(spmd.forward)/
<model>/<layer>/dot_general" ...}``, the ``jax.named_scope`` stack it was
traced under.

Two rules, because an operation of the optimized program is not an operation
of the traced one:

* **a fusion counts under its root.**  XLA gives a fusion instruction the
  ``op_name`` of the instruction it was built round, and that one name stands
  for everything fused into it; a fusion that carries none takes the
  ``op_name`` of the ROOT of the computation it calls, and one that has
  neither is unscoped.
* **recomputed forward counts as backward.**  Under ``jax.checkpoint`` the
  forward operations that the backward pass runs again are traced inside
  ``transpose(jvp(...))``, and a selector that excludes ``transpose(`` leaves
  them to the backward pass, where their time is spent.

A program that serves no text (a parent commit, a server program) gives
``None`` and the metrics built on it are left out.  Everything below the
text works on ``(start_ns, end_ns, name)`` tuples.
"""
from __future__ import annotations

import bisect
import re
import statistics

from . import trace_read

_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")

_texts = {}  # site -> scopes of its program (one compile or cache load a run)


def hlo_scopes(text):
    """``{instruction name: op_name}`` for every instruction of an optimized
    HLO module's text, names without their ``%``; ``""`` where neither the
    instruction nor the root of the computation it calls has one."""
    own, calls, roots = {}, {}, {}
    computation = None
    for line in text.splitlines():
        started = _COMPUTATION.match(line)
        if started:
            computation = started.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(2)
        op_name = _OP_NAME.search(line)
        own[name] = op_name.group(1) if op_name else ""
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        if m.group(1) and computation:
            roots[computation] = name
    out = {}
    for name, scope in own.items():
        at, seen = name, set()
        while not scope and at in calls and at not in seen:
            seen.add(at)   # a fusion with no name of its own: its root's
            at = roots.get(calls[at], "")
            scope = own.get(at, "")
        out[name] = scope
    return out


def program_scopes(site):
    """``hlo_scopes`` of the program that ``site`` compiled last, or None
    where the program under test serves no text for it."""
    if site not in _texts:
        from incubator_mxnet_tpu import profiler

        serve = getattr(profiler, "compiled_text", None)
        text = serve(site) if serve else None
        _texts[site] = hlo_scopes(text) if text else None
    return _texts[site]


def instruction_name(event_name):
    """``%fusion.7 = f32[8] fusion(...)`` -> ``fusion.7``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def executions(trace, program_prefix):
    """``(start, end)`` of each execution on chip 0 of the programs whose
    traced name starts with ``program_prefix``, sorted."""
    if not trace.devices:
        return []
    return sorted((s, e) for s, e, name in trace.devices[0]["modules"]
                  if name.startswith(program_prefix))


def ops_of(trace, runs):
    """Chip 0's operations that start inside one of ``runs``."""
    starts = [s for s, _ in runs]
    out = []
    for ev in trace.devices[0]["ops"]:
        i = bisect.bisect_right(starts, ev[0]) - 1
        if i >= 0 and ev[0] < runs[i][1]:
            out.append(ev)
    return out


def selected(ops, scopes, scope, exclude):
    """The operations whose scope holds one of the strings in ``scope`` and
    none of those in ``exclude``; an operation the text does not know has the
    empty scope."""
    kept = {}
    for name in {ev[2] for ev in ops}:
        s = scopes.get(instruction_name(name), "")
        kept[name] = (any(part in s for part in scope)
                      and not any(part in s for part in exclude))
    return [ev for ev in ops if kept[ev[2]]]


def busy_ms_per_execution(ops, runs):
    """Device time in which one of ``ops`` ran (their union), per execution,
    in ms.  The window cuts the last execution, so the executions are counted
    as their total time over their median, as ``collective_time`` does."""
    lengths = [e - s for s, e in runs]
    count = sum(lengths) / statistics.median(lengths)
    return trace_read.busy_seconds(ops) / count * 1e3

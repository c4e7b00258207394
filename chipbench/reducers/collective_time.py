"""Device time per execution of a program, in ms, in which a collective
operation ran; with ``"exposed": true`` only the part in which nothing else
ran, which is what the collectives add to the program's length (the rest is
hidden behind other operations).  Chip 0; a collective is an operation whose
own HLO name, which XLA makes from the kind, starts with one
(``%all-reduce.157``, ``%all-reduce-start.3``, ``%all-gather-done.1``); a
fusion that consumes ``%all-reduce.5`` is none."""
import re
import statistics

from .. import trace_read

COLLECTIVE = re.compile(r"%?(all-reduce|all-gather|reduce-scatter|collective-permute"
                        r"|all-to-all)(-start|-done)?(\.\d+)* ")


def reduce(arguments, ctx, result, trace):
    if trace is None or not trace.devices:
        return None
    runs = [e - s for s, e, name in trace.devices[0]["modules"]
            if name.startswith(arguments["program_prefix"])]
    ops = trace.devices[0]["ops"]
    kinds = {name: bool(COLLECTIVE.match(name)) for name in {n for _, _, n in ops}}
    collective = [ev for ev in ops if kinds[ev[2]]]
    if not runs or not collective:
        return None
    union = trace_read.union_intervals(collective)
    secs = sum(e - s for s, e in union) / 1e9
    if arguments.get("exposed"):
        secs -= trace_read.overlap_seconds(union, trace_read.union_intervals(
            [ev for ev in ops if not kinds[ev[2]]]))
    executions = sum(runs) / statistics.median(runs)  # the window cuts the last one
    return secs / executions * 1e3

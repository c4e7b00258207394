"""Device time of one execution of a compiled program, in ms (median over
the window's executions on chip 0).

``{"program_prefix": "jit_pure_step("}`` picks programs by their traced name;
``{"label_prefix": "decode_"}`` picks them by the label the builder's
identification pass gave their fingerprint (the server's programs all trace
as ``jit_pure(<fingerprint>)``)."""
import statistics

from .. import trace_read


def executions(arguments, result, trace):
    """Seconds of each selected execution."""
    labels = result.get("programs", {})
    out = []
    for name, secs in trace_read.program_times(trace).items():
        if "program_prefix" in arguments:
            hit = name.startswith(arguments["program_prefix"])
        else:
            hit = labels.get(name, "").startswith(arguments["label_prefix"])
        if hit:
            out.extend(secs)
    return out


def reduce(arguments, ctx, result, trace):
    if trace is None:
        return None
    secs = executions(arguments, result, trace)
    return statistics.median(secs) * 1e3 if secs else None

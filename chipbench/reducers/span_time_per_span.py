"""Time under one host span for each span of another that closed in the
traced window, in ms: ``{"span": "io.wait", "per": "spmd.step", "witness":
"io.transfer"}`` is what the step's thread waited for data, a step.

The seconds of every ``"span"`` are clipped to the window and summed over
all host threads (thread-milliseconds: two workers busy at once count
twice), then divided by the ``"per"`` spans that closed in it.  A step that
never waited counts 0, which a median over the spans that did occur would
hide.  ``"witness"`` is a span the same instrumentation opens whenever it
runs at all: where the window holds none the program has no such spans and
the metric is left out; with a witness and no ``"span"`` it is 0.0."""
from .. import trace_read


def clipped_seconds(host, window, name):
    """Seconds of the ``name`` spans inside ``window``, summed span by span."""
    return sum(e - s for s, e, _ in trace_read.clip(
        [ev for ev in host if ev[2] == name], window)) / 1e9


def witnessed(host, window, name):
    """Whether a ``name`` span overlaps ``window``."""
    w0, w1 = window
    return any(nm == name and e > w0 and s < w1 for s, e, nm in host)


def reduce(arguments, ctx, result, trace):
    if trace is None or not witnessed(trace.host, trace.window,
                                      arguments["witness"]):
        return None
    w0, w1 = trace.window
    closed = sum(1 for _, e, name in trace.host
                 if name == arguments["per"] and w0 <= e <= w1)
    if not closed:
        return None
    return clipped_seconds(trace.host, trace.window,
                           arguments["span"]) / closed * 1e3

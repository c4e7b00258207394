"""Median length, in ms, of a host span of the given name inside the traced
window: a ``jax.profiler.TraceAnnotation`` of the benchmark's own, or a span
of the runtime."""
import statistics


def reduce(arguments, ctx, result, trace):
    if trace is None:
        return None
    w0, w1 = trace.window
    spans = [(e - s) / 1e6 for s, e, name in trace.host
             if name == arguments["span"] and s >= w0 and e <= w1]
    return statistics.median(spans) if spans else None

"""Of chip 0's idle time in the traced window, the share during which a host
span of one name was open on any thread: ``{"span": "io.wait", "witness":
"io.transfer"}`` is the device's idle time put down to the host waiting for
input.

Idle time is ``trace_read.idle_gaps``' (gaps of 20 us and more; shorter ones
are launch latency between back-to-back programs); the spans are merged
first, so two threads inside the span at once count once.  0.0 where the
device was never idle or the span never open; left out where the window
holds no ``"witness"`` span (``span_time_per_span``'s rule: the program has
no such instrumentation) or the trace no device."""
from .. import trace_read
from .span_time_per_span import witnessed


def idle_share_under(gaps, host, window, name):
    """``gaps``: disjoint ``(start, end)`` inside ``window``."""
    idle = sum(e - s for s, e in gaps) / 1e9
    if not idle:
        return 0.0
    spans = trace_read.union_intervals(trace_read.clip(
        [ev for ev in host if ev[2] == name], window))
    return trace_read.overlap_seconds(sorted(gaps), spans) / idle


def reduce(arguments, ctx, result, trace):
    if trace is None or not trace.devices or not witnessed(
            trace.host, trace.window, arguments["witness"]):
        return None
    return idle_share_under(trace_read.idle_gaps(trace), trace.host,
                            trace.window, arguments["span"])

"""Median self time, in ms, of a host span inside the traced window: its
length less the time of the spans named in ``"minus"`` that lie inside it.

``{"span": "spmd.step", "minus": ["spmd.step.enqueue"]}`` is the host's own
work per train step, without its wait for the device; with ``"minus": []``
it is the span's length.  Spans are the program's own
(``incubator_mxnet_tpu.profiler.span``), on the device trace's clock; a
program that opens none leaves the metric out."""
import bisect
import statistics


def self_times_ms(host, window, span, minus):
    """Self time of each ``span`` that lies wholly inside ``window``."""
    w0, w1 = window
    outer = [(s, e) for s, e, name in host if name == span and s >= w0 and e <= w1]
    inner = sorted((s, e) for s, e, name in host if name in minus)
    starts = [s for s, _ in inner]
    out = []
    for s, e in outer:
        taken = 0.0
        i = bisect.bisect_left(starts, s)
        while i < len(inner) and inner[i][0] < e:
            taken += min(inner[i][1], e) - inner[i][0]
            i += 1
        out.append((e - s - taken) / 1e6)
    return out


def reduce(arguments, ctx, result, trace):
    if trace is None:
        return None
    times = self_times_ms(trace.host, trace.window, arguments["span"],
                          set(arguments.get("minus", ())))
    return statistics.median(times) if times else None

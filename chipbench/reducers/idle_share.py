"""Share of the traced window, in %, in which no operation ran on the device:
1 - (union of the device's operation intervals) / window, averaged over the
chips."""
from .. import trace_read


def reduce(arguments, ctx, result, trace):
    if trace is None or not trace.devices:
        return None
    return 100.0 * (1.0 - trace_read.device_busy_seconds(trace) / trace.window_s)

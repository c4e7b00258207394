"""Program executions on chip 0 in the traced window over the spans of one
name that closed in it: with ``{"span": "spmd.step"}`` the programs the
device runs for each train step - the compiled step and the small ones the
host dispatches round it.  A program that opens no such span leaves the
metric out."""


def reduce(arguments, ctx, result, trace):
    if trace is None or not trace.devices:
        return None
    w0, w1 = trace.window
    closed = sum(1 for s, e, name in trace.host
                 if name == arguments["span"] and w0 <= e <= w1)
    programs = len(trace.devices[0]["modules"])
    return programs / closed if closed and programs else None

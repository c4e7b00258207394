"""One of the program's counters over another, read from its ``profiler``
registry after the window: ``{"numerator": "moe_rows_routed_here",
"denominator": "moe_step"}`` is the rows routed to this chip's experts a
step, over every step the program read back (warm-up included: the batch is
the same).  A program that has not both counters, or counted no step, gives
None and the metric is left out."""


def reduce(arguments, ctx, result, trace):
    from incubator_mxnet_tpu import profiler

    counts = profiler.counters()
    above = counts.get(arguments["numerator"])
    below = counts.get(arguments["denominator"])
    if above is None or not below:
        return None
    return above / below

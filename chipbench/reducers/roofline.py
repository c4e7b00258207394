"""A program's share of its roofline, in %: the least time the chip could
take for the operations and bytes the program NEEDS (``chipbench/flops.py``,
from the shapes; per chip) over the device time of one execution.  The bound
that binds is printed on a ``[chipbench]`` line."""
from .. import flops, peaks
from . import device_program_time


def reduce(arguments, ctx, result, trace):
    if trace is None:
        return None
    secs = device_program_time.reduce(arguments, ctx, result, trace)
    if secs is None:
        return None
    need = getattr(flops, arguments["needs"])(ctx.config, **result["shapes"])
    peak = peaks.peaks_for(ctx.devices[0].device_kind)
    chips = len(ctx.devices)
    t_flops = need["flops"] / chips / peak["bf16_flops_per_s"]
    t_bytes = need["bytes"] / peak["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    ctx.say(f"roofline of {arguments['needs']}: {need['flops'] / chips:.4g} FLOPs "
            f"({t_flops * 1e3:.3f} ms at peak), {need['bytes']:.4g} bytes "
            f"({t_bytes * 1e3:.3f} ms at peak): {bound}-bound; "
            f"device time {secs:.3f} ms")
    return 100.0 * max(t_flops, t_bytes) / (secs / 1e3)

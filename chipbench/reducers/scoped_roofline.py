"""The operations under a scope as a share of THEIR roofline, in %: the least
time the chip could take for the operations and bytes that part of the step
NEEDS (the function ``"needs"`` of the module ``"module"`` of ``chipbench/``,
from the shapes alone: it reads the same whatever implements the part, XLA
fusions or a kernel) over the device time per execution of the operations
traced under ``"scope"`` (``scoped_device_time``'s arguments and rules).  The
bound that binds is printed on a ``[chipbench]`` line.

``{"program_prefix": "jit_pure_step(", "site": "spmd.step", "scope":
["nemotron.mamba.scan"], "exclude": [], "module": "flops_nemotron", "needs":
"mamba2_scan"}``.  Where the program has no such scope the metric is left
out."""
import importlib

from .. import peaks
from . import scoped_device_time


def reduce(arguments, ctx, result, trace):
    ms = scoped_device_time.reduce(arguments, ctx, result, trace)
    if not ms:
        return None
    module = importlib.import_module(f"chipbench.{arguments['module']}")
    need = getattr(module, arguments["needs"])(ctx.config, **result["shapes"])
    peak = peaks.peaks_for(ctx.devices[0].device_kind)
    chips = len(ctx.devices)
    t_flops = need["flops"] / chips / peak["bf16_flops_per_s"]
    t_bytes = need["bytes"] / chips / peak["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    ctx.say(f"roofline of {arguments['needs']} under {arguments['scope']}: "
            f"{need['flops'] / chips:.4g} FLOPs ({t_flops * 1e3:.3f} ms at peak), "
            f"{need['bytes'] / chips:.4g} bytes ({t_bytes * 1e3:.3f} ms at peak): "
            f"{bound}-bound; device time {ms:.3f} ms")
    return 100.0 * max(t_flops, t_bytes) * 1e3 / ms

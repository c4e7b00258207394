"""A training step's share of the chip's peak, in %: the FLOPs the step NEEDS
(from its shapes, by the function ``"needs"`` of the module ``"module"`` of
``chipbench/`` that the metric's file names; nothing recomputed) over the
device time of one execution times the chips' bf16 peak.

``{"program_prefix": "jit_pure_step(", "module": "flops_xing4",
"needs": "xing4_clm_step"}``.  Unlike ``roofline`` it takes no byte bound:
it is the whole step's model-FLOPs utilisation."""
import importlib

from .. import peaks
from . import device_program_time


def reduce(arguments, ctx, result, trace):
    if trace is None:
        return None
    secs = device_program_time.reduce(arguments, ctx, result, trace)
    if secs is None:
        return None
    module = importlib.import_module(f"chipbench.{arguments['module']}")
    need = getattr(module, arguments["needs"])(ctx.config, **result["shapes"])
    peak = peaks.peaks_for(ctx.devices[0].device_kind)
    chips = len(ctx.devices)
    at_peak_ms = need["flops"] / chips / peak["bf16_flops_per_s"] * 1e3
    ctx.say(f"mfu of {arguments['needs']}: {need['flops'] / chips:.4g} FLOPs a chip "
            f"({at_peak_ms:.3f} ms at peak); device time {secs:.3f} ms")
    return 100.0 * at_peak_ms / secs

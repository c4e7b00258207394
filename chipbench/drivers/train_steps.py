"""Run training steps back to back for the length of the window.

The window opens after a warm-up that has run the step's one shape and
closes with a fence that ends in a D2H of the last loss and of an updated
parameter, so every step dispatched in the window has finished on the chip
before the clock is read.
"""
from __future__ import annotations

import math
import time

import numpy as np

_perf = time.perf_counter


def run(system, ctx):
    import jax

    step, fence = system["step"], system["fence"]
    warmup = int(ctx.traffic["warmup_steps"])
    t0 = _perf()
    first_loss = fence(step())
    ctx.say(f"step 0: loss {first_loss:.4f}, {_perf() - t0:.1f} s with its "
            f"compile or cache load")
    t0 = _perf()
    for _ in range(warmup):
        loss = step()
    fence(loss)
    ctx.say(f"warm-up: {(_perf() - t0) / warmup * 1e3:.2f} ms a step "
            f"(fenced, {warmup} steps)")

    recompiles_before = _recompiles()
    dispatch_s = []
    with ctx.window() as window:
        end = window.t0 + ctx.window_seconds
        while True:
            t = _perf()
            if t >= end:
                break
            with jax.profiler.TraceAnnotation("chipbench.train_step"):
                loss = step()
            dispatch_s.append(_perf() - t)
        with jax.profiler.TraceAnnotation("chipbench.fence"):
            last_loss = fence(loss)
    steps = len(dispatch_s)
    elapsed = window.t1 - window.t0
    recompiles = _recompiles() - recompiles_before
    chips = len(ctx.devices)
    rate = steps * system["tokens_per_step"] / elapsed / chips
    ctx.say(f"window: {steps} steps in {elapsed:.3f} s = "
            f"{elapsed / steps * 1e3:.3f} ms a step, {rate:.1f} tokens/s/chip; "
            f"loss {first_loss:.4f} -> {last_loss:.4f}; recompiles {recompiles}")
    checks = dict(system["checks"])
    checks.update({
        "loss_finite": math.isfinite(first_loss) and math.isfinite(last_loss),
        "loss_fell": last_loss < first_loss,
        "no_compile_in_window": recompiles == 0})
    return {"values": {"train_tokens_per_s_chip": rate},
            "counts": {"dispatch_ms_median": float(np.median(dispatch_s)) * 1e3,
                       "steps": steps},
            "shapes": system["shapes"],
            "checks": checks, "attempted": steps, "failed": 0}


def _recompiles():
    from incubator_mxnet_tpu import profiler

    return profiler.counters()["recompile_steady_state"]

#!/usr/bin/env python
"""A/B microbench: stock XLA conv→BN→ReLU(+residual) vs the Pallas fused
epilogue (``ops/pallas_bn.py``) on ResNet-50 stage shapes — the experiment
VERDICT r4 item 4b names.  Device timings: runs on a TPU only (through the
chip tool) and exits non-zero anywhere else:

    python tools/bench_fused_bn.py            # stage-3 shape, B=256
    MXNET_TPU_BN_STAGE=2 python tools/bench_fused_bn.py

Prints one JSON line per variant with ms/iter and the implied HBM
passes-per-feature-map (time · BW / bytes-per-map), feeding the
resnet_roofline.py pass-count assumption with a measurement.
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp
from jax import lax

# B=256 ResNet-50 v1 stage shapes (after the stage's stride-2 entry)
STAGE_SHAPES = {
    1: (256, 256, 56, 56),
    2: (256, 512, 28, 28),
    3: (256, 1024, 14, 14),
    4: (256, 2048, 7, 7),
}


def _fence(x):
    np.asarray(jax.device_get(x if not isinstance(x, tuple) else x[0]))


def _time(fn, *args, iters=30):
    out = fn(*args)
    _fence(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _fence(out)
    return (time.perf_counter() - t0) / iters * 1e3, out


def main():
    stage = int(os.environ.get("MXNET_TPU_BN_STAGE", "3"))
    N, C, H, W = STAGE_SHAPES[stage]
    from device_peaks import peaks_for
    from incubator_mxnet_tpu import config

    device = config.device_record()
    if device["platform"] != "tpu":
        raise SystemExit(f"bench_fused_bn.py times a Mosaic kernel: needs a "
                         f"TPU, found platform {device['platform']!r}")
    hbm_bytes_per_s = peaks_for(device["device_kind"])["hbm_bytes_per_s"]
    config.enable_compile_cache()
    mid = C // 4
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(N, mid, H, W).astype(np.float32)).astype(jnp.bfloat16)
    w = jnp.asarray(rng.randn(C, mid, 3, 3).astype(np.float32) * 0.05).astype(jnp.bfloat16)
    res = jnp.asarray(rng.rand(N, C, H, W).astype(np.float32)).astype(jnp.bfloat16)
    gamma = jnp.asarray(rng.rand(C).astype(np.float32) + 0.5)
    beta = jnp.asarray(rng.randn(C).astype(np.float32))

    def conv(xx):
        return lax.conv_general_dilated(
            xx, w, (1, 1), [(1, 1), (1, 1)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))

    @jax.jit
    def xla_path(xx, rr):
        h = conv(xx)
        h32 = h.astype(jnp.float32)
        mean = jnp.mean(h32, axis=(0, 2, 3))
        var = jnp.maximum(jnp.mean(jnp.square(h32), axis=(0, 2, 3))
                          - jnp.square(mean), 0.0)
        inv = lax.rsqrt(var + 1e-5) * gamma
        out = (h32 - mean[None, :, None, None]) * inv[None, :, None, None] \
            + beta[None, :, None, None]
        return jnp.maximum(out + rr.astype(jnp.float32), 0.0).astype(h.dtype)

    from incubator_mxnet_tpu.ops.pallas_bn import fused_bn_relu

    @jax.jit
    def pallas_path(xx, rr):
        h = conv(xx)
        out, _, _ = fused_bn_relu(h, gamma, beta, residual=rr)
        return out

    bytes_per_map = N * C * H * W * 2  # bf16
    results = {}
    for name, fn in (("xla", xla_path), ("pallas_epilogue", pallas_path)):
        ms, out = _time(fn, x, res)
        results[name] = (ms, out)
        passes = (ms / 1e3) * hbm_bytes_per_s / bytes_per_map
        print(json.dumps({
            "metric": f"conv_bn_relu_add_stage{stage}_{name}",
            "value": round(ms, 3), "unit": "ms/iter",
            "implied_hbm_passes_per_map": round(passes, 2), **device,
        }))
    a = np.asarray(jax.device_get(results["xla"][1]), np.float32)
    b = np.asarray(jax.device_get(results["pallas_epilogue"][1]), np.float32)
    print(json.dumps({"metric": "max_abs_diff", "value": float(np.abs(a - b).max())}))


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""Independent ResNet-50 control: an idiomatic raw-JAX train step with NO
framework code, same batch/chip/fence discipline as ``bench.py``'s
resnet50 config (VERDICT r4 item 4a).

Purpose: establish the CEILING the framework should be judged against.  If
this control lands within a few percent of the framework's img/s, the
framework adds no overhead and the remaining gap to 50% MFU is an XLA/
convolution property on this chip, not a framework defect.  If the control
is much faster, the framework has work to do.

Architecture matches ``gluon.model_zoo.vision.resnet50_v1`` (v1 bottleneck,
BN+ReLU, 224², 1000 classes) with the same bf16-AMP policy: bf16 conv/
matmul inputs, fp32 BN statistics/params, fp32 SGD-momentum.

Run on the chip, through the chip tool (exits non-zero with no
accelerator — the number is a device metric):
    python tools/resnet_control.py                 # B=256, 60 steps
    MXNET_TPU_BENCH_BATCH=128 python tools/resnet_control.py
Prints one JSON line: {"metric": "resnet50_control_img_per_sec", ...,
"platform": ..., "device_kind": ..., "n_devices": ...}.
"""
import functools
import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# model: functional ResNet-50 v1 (params as a pytree of dicts)
# ---------------------------------------------------------------------------

STAGES = (3, 4, 6, 3)
WIDTHS = (256, 512, 1024, 2048)


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    return jax.random.normal(key, (cout, cin, kh, kw), jnp.float32) * np.sqrt(2.0 / fan_in)


def init_params(key):
    params = {}
    k = iter(jax.random.split(key, 200))
    params["conv0"] = _conv_init(next(k), 7, 7, 3, 64)
    params["bn0"] = {"g": jnp.ones(64), "b": jnp.zeros(64)}
    cin = 64
    for si, (blocks, width) in enumerate(zip(STAGES, WIDTHS)):
        mid = width // 4
        for bi in range(blocks):
            p = {}
            p["c1"] = _conv_init(next(k), 1, 1, cin, mid)
            p["bn1"] = {"g": jnp.ones(mid), "b": jnp.zeros(mid)}
            p["c2"] = _conv_init(next(k), 3, 3, mid, mid)
            p["bn2"] = {"g": jnp.ones(mid), "b": jnp.zeros(mid)}
            p["c3"] = _conv_init(next(k), 1, 1, mid, width)
            p["bn3"] = {"g": jnp.ones(width), "b": jnp.zeros(width)}
            if bi == 0:
                p["proj"] = _conv_init(next(k), 1, 1, cin, width)
                p["bnp"] = {"g": jnp.ones(width), "b": jnp.zeros(width)}
            params[f"s{si}b{bi}"] = p
            cin = width
    params["fc_w"] = jax.random.normal(next(k), (2048, 1000), jnp.float32) * 0.01
    params["fc_b"] = jnp.zeros(1000)
    return params


def _conv(x, w, stride=1):
    # bf16 in, bf16 out (MXU accumulates fp32 internally; fp32
    # preferred_element_type breaks the conv gradient's dtype matching) —
    # BN immediately recomputes statistics in fp32 anyway
    return lax.conv_general_dilated(
        x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
        window_strides=(stride, stride),
        padding=[(w.shape[2] // 2, w.shape[2] // 2)] * 2,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))


def _bn_relu(x, bn, relu=True):
    # training-mode batch norm, fp32 statistics (one-pass E[x²]−E[x]²,
    # clamped: fp32 cancellation can drive the difference slightly negative)
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=(0, 2, 3))
    var = jnp.maximum(jnp.mean(jnp.square(x32), axis=(0, 2, 3))
                      - jnp.square(mean), 0.0)
    inv = lax.rsqrt(var + 1e-5) * bn["g"]
    out = (x32 - mean[None, :, None, None]) * inv[None, :, None, None] \
        + bn["b"][None, :, None, None]
    return jnp.maximum(out, 0.0) if relu else out


def _bottleneck(x, p, stride):
    # v1 bottleneck: stride on the FIRST 1x1, matching the framework's
    # BottleneckV1 (gluon/model_zoo/vision/resnet.py) — NOT v1.5's strided
    # 3x3; the control must be like-for-like or its ceiling is misstated
    h = _bn_relu(_conv(x, p["c1"], stride), p["bn1"])
    h = _bn_relu(_conv(h, p["c2"]), p["bn2"])
    h = _bn_relu(_conv(h, p["c3"]), p["bn3"], relu=False)
    if "proj" in p:
        x = _bn_relu(_conv(x, p["proj"], stride), p["bnp"], relu=False)
    return jnp.maximum(h + x, 0.0)


def forward(params, x):
    h = _conv(x, params["conv0"], stride=2)
    h = _bn_relu(h, params["bn0"])
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for si, blocks in enumerate(STAGES):
        for bi in range(blocks):
            stride = 2 if (bi == 0 and si > 0) else 1
            h = _bottleneck(h, params[f"s{si}b{bi}"], stride)
    h = jnp.mean(h, axis=(2, 3))  # global average pool
    return h.astype(jnp.bfloat16) @ params["fc_w"].astype(jnp.bfloat16) \
        + params["fc_b"]


def loss_fn(params, x, y):
    logits = forward(params, x).astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.mean(lse - jnp.take_along_axis(logits, y[:, None], 1)[:, 0])


@functools.partial(jax.jit, donate_argnums=(0, 1))
def train_step(params, momentum, x, y):
    loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
    lr, mom, wd = 0.1, 0.9, 1e-4

    def upd(p, m, g):
        g = g + wd * p
        m = mom * m - lr * g
        return p + m, m

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_m = jax.tree_util.tree_leaves(momentum)
    flat_g = jax.tree_util.tree_leaves(grads)
    out = [upd(p, m, g) for p, m, g in zip(flat_p, flat_m, flat_g)]
    params = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
    momentum = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
    return params, momentum, loss


def main():
    B = int(os.environ.get("MXNET_TPU_BENCH_BATCH", "256"))
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        raise SystemExit("resnet_control.py: no accelerator — "
                         "jax.devices()[0].platform is 'cpu'")
    warmup = 2
    steps = int(os.environ.get("MXNET_TPU_BENCH_STEPS", "60"))

    params = init_params(jax.random.PRNGKey(0))
    momentum = jax.tree_util.tree_map(jnp.zeros_like, params)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(B, 3, 224, 224).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 1000, (B,)).astype(np.int32))

    for _ in range(warmup):
        params, momentum, loss = train_step(params, momentum, x, y)
    # fence: concrete D2H of loss + one param (same discipline as
    # bench.py::_fence)
    float(np.asarray(loss))
    np.asarray(jax.tree_util.tree_leaves(params)[0])

    t0 = time.perf_counter()
    for _ in range(steps):
        params, momentum, loss = train_step(params, momentum, x, y)
    float(np.asarray(loss))
    np.asarray(jax.tree_util.tree_leaves(params)[0])
    dt = time.perf_counter() - t0

    print(json.dumps({
        "metric": "resnet50_control_img_per_sec",
        "value": round(B * steps / dt, 2),
        "unit": "img/sec/chip",
        "note": "raw-JAX control, no framework (VERDICT r4 item 4a)",
        "platform": dev.platform, "device_kind": dev.device_kind,
        "n_devices": len(jax.devices()),
    }))


if __name__ == "__main__":
    main()

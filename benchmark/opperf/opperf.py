#!/usr/bin/env python
"""opperf — per-operator micro-benchmarks.

Parity: [U:benchmark/opperf/] (the reference's per-op latency suite run
across contexts).  Times a curated slice of the op registry on the
default backend: forward eager, forward jitted, and backward (via
jax.grad) where the op is differentiable; prints a table and optionally
JSON.

Usage:
    python benchmark/opperf/opperf.py [--ops dot,softmax] [--runs 50]
        [--warmup 5] [--json out.json]

Runs on whatever device JAX holds and says which in its first line (and
in the ``--json`` file); only a run on the chip, through the chip tool,
gives device latencies.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
import numpy as np


def _cases(rng, large):
    """(op_name, args_factory, differentiable) — shapes follow the
    reference's default profiles (batched 2-D/4-D tensors)."""
    B = 32 if large else 8
    D = 512 if large else 64
    C, H, W = (64, 56, 56) if large else (8, 14, 14)
    f = np.float32

    def t(*shape):
        return rng.rand(*shape).astype(f)

    return [
        ("add", lambda: (t(B, D), t(B, D)), True, lambda a, b: a + b),
        ("mul", lambda: (t(B, D), t(B, D)), True, lambda a, b: a * b),
        ("dot", lambda: (t(D, D), t(D, D)), True, None),
        ("batch_dot", lambda: (t(B, D // 4, D // 4), t(B, D // 4, D // 4)), True, None),
        ("FullyConnected", lambda: (t(B, D), t(D, D), t(D)), True, None),
        ("Convolution", lambda: (t(B, C, H, W), t(C, C, 3, 3), t(C)), True, None),
        ("Pooling", lambda: (t(B, C, H, W),), True, None),
        ("BatchNorm", lambda: (t(B, C, H, W), t(C), t(C), t(C), t(C)), False, None),
        ("LayerNorm", lambda: (t(B, D), t(D), t(D)), True, None),
        ("softmax", lambda: (t(B, D),), True, None),
        ("log_softmax", lambda: (t(B, D),), True, None),
        ("relu", lambda: (t(B, D),), True, None),
        ("exp", lambda: (t(B, D),), True, None),
        ("sum", lambda: (t(B, D),), True, None),
        ("transpose", lambda: (t(B, D),), True, None),
        ("Embedding", lambda: (rng.randint(0, D, (B, 16)).astype(np.int32), t(D, 64)), False, None),
        ("Dropout", lambda: (t(B, D),), False, _dropout_fn),
        ("fused_attention", lambda: (t(B, 16, D), t(B, 16, D), t(B, 16, D)), True, None),
        # round-4 families
        ("linalg_potrf", lambda: (_gram(t(D // 4, D // 4)),), True, None),
        ("linalg_trsm", lambda: (np.tril(t(D // 4, D // 4)) + 2 * np.eye(D // 4, dtype=f), t(D // 4, D // 4)), True, None),
        ("CTCLoss", lambda: (t(16, B, 32), np.tile(np.arange(1, 6, dtype=f), (B, 1))), True, None),
        ("ROIPooling", lambda: (t(B, C, H, W), np.tile(np.array([0, 1, 1, H - 2, W - 2], f), (8, 1))), True, None),
        ("_contrib_ROIAlign", lambda: (t(B, C, H, W), np.tile(np.array([0, 1, 1, H - 2, W - 2], f), (8, 1))), True, None),
        ("_contrib_AdaptiveAvgPooling2D", lambda: (t(B, C, H, W),), True, None),
        ("im2col", lambda: (t(B, C, H, W),), True, None),
        ("masked_softmax", lambda: (t(B, D), rng.rand(B, D) > 0.2), True, None),
        ("_sample_normal", lambda: (t(B), t(B)), False, _sample_normal_fn),
        # round-5 families
        ("RNN", lambda: (t(16, B, 32), _rnn_params(rng, 32, 32)),
         True, None),
        ("_contrib_DeformableConvolution",
         lambda: (t(B, C, H, W), np.zeros((B, 18, H, W), f), t(C, C, 3, 3)),
         True, None),
        ("_contrib_DeformablePSROIPooling",
         lambda: (t(B, 2 * 4, H, W),
                  np.tile(np.array([0, 1, 1, H - 2, W - 2], f), (8, 1))),
         True, None),
        ("digamma", lambda: (t(B, D) + 0.5,), True, None),
        # round-5 tail
        ("Crop", lambda: (t(B, C, H, W),), True, None),
        ("quantize", lambda: (t(B, D), np.array([-1.0], f), np.array([1.0], f)),
         False, None),
        ("amp_multicast", lambda: (t(B, D).astype(np.float16), t(B, D)),
         False, None),
        ("choose_element_0index",
         lambda: (t(B, D), rng.randint(0, D, (B,)).astype(f)), True, None),
    ]


_KW = {"Convolution": {"kernel": (3, 3), "num_filter": 0, "pad": (1, 1)},
       "Pooling": {"kernel": (2, 2), "stride": (2, 2)},
       "fused_attention": {"num_heads": 4},
       "ROIPooling": {"pooled_size": (7, 7), "spatial_scale": 1.0},
       "_contrib_ROIAlign": {"pooled_size": (7, 7), "spatial_scale": 1.0,
                             "sample_ratio": 2},
       "_contrib_AdaptiveAvgPooling2D": {"output_size": (7, 7)},
       "im2col": {"kernel": (3, 3), "stride": (1, 1), "pad": (1, 1)},
       "RNN": {"mode": "lstm", "state_size": 32, "num_layers": 1},
       "_contrib_DeformableConvolution": {"kernel": (3, 3), "pad": (1, 1),
                                          "num_filter": 0, "no_bias": True},
       "_contrib_DeformablePSROIPooling": {"spatial_scale": 1.0,
                                           "output_dim": 2, "group_size": 2,
                                           "pooled_size": 7,
                                           "sample_per_part": 2,
                                           "no_trans": True},
       "Crop": {"h_w": (7, 7), "offset": (1, 1)},
       "quantize": {"out_type": "uint8"},
       "amp_multicast": {"num_outputs": 2}}


def _rnn_params(rng, C, H):
    from incubator_mxnet_tpu.ops.rnn_ops import rnn_param_size

    n = rnn_param_size("lstm", C, H)
    return rng.uniform(-0.1, 0.1, (n,)).astype(np.float32)


def _sample_normal_fn(mu, sigma):
    import jax

    from incubator_mxnet_tpu.ops.registry import get_op

    return get_op("_sample_normal").fn(mu, sigma, shape=(64,),
                                       key=jax.random.PRNGKey(0))


def _gram(x):
    """SPD input for the Cholesky benchmarks (A·Aᵀ + 4I)."""
    return (x @ x.T + 4 * np.eye(x.shape[0], dtype=x.dtype)).astype(x.dtype)


def _dropout_fn(x):
    import jax

    from incubator_mxnet_tpu.ops.registry import get_op

    # explicit key: the global key stack is for the framework's traced
    # paths, not plain jax.jit
    return get_op("Dropout").fn(x, training=True, key=jax.random.PRNGKey(0))


def bench_op(name, mk_args, diff, pyfn, runs, warmup):
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.registry import get_op

    kwargs = _KW.get(name, {})
    fn = pyfn or (lambda *a, _f=get_op(name).fn: _f(*a, **kwargs))
    args = tuple(jnp.asarray(a) for a in mk_args())

    def first(*a):
        out = fn(*a)
        return out[0] if isinstance(out, (list, tuple)) else out

    jfn = jax.jit(first)
    jax.block_until_ready(jfn(*args))

    def timed(g, n):
        t0 = time.perf_counter()
        for _ in range(n):
            out = g(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n * 1e3

    for _ in range(warmup):
        jax.block_until_ready(first(*args))
    eager_ms = timed(first, max(runs // 5, 3))
    jit_ms = timed(jfn, runs)

    bwd_ms = None
    if diff:
        gfn = jax.jit(jax.grad(lambda *a: first(*a).astype(jnp.float32).sum()))
        jax.block_until_ready(gfn(*args))
        bwd_ms = timed(gfn, runs)
    return eager_ms, jit_ms, bwd_ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default=None, help="comma-separated subset")
    ap.add_argument("--runs", type=int, default=50)
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--large", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    from incubator_mxnet_tpu import config

    config.enable_compile_cache()
    device = config.device_record()
    print(f"device: {device}")
    rng = np.random.RandomState(0)
    subset = set(args.ops.split(",")) if args.ops else None
    rows = []
    print(f"{'op':<22}{'eager(ms)':>12}{'jit(ms)':>12}{'bwd-jit(ms)':>14}")
    for name, mk, diff, pyfn in _cases(rng, args.large):
        if subset and name not in subset:
            continue
        try:
            eager, jit, bwd = bench_op(name, mk, diff, pyfn, args.runs, args.warmup)
        except Exception as e:  # keep going: the table is the product
            print(f"{name:<22}  FAILED: {type(e).__name__}: {str(e)[:60]}")
            continue
        print(f"{name:<22}{eager:>12.4f}{jit:>12.4f}"
              f"{(f'{bwd:.4f}' if bwd is not None else '-'):>14}")
        rows.append({"op": name, "eager_ms": eager, "jit_ms": jit, "bwd_ms": bwd})
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"device": device, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()

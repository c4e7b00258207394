#!/usr/bin/env python
"""Attention crossover benchmark: where the VMEM-resident Pallas kernels
beat the XLA path, by shape.

Times forward, backward and both together (``jax.grad``) of self-attention
read from a fused QKV projection ``[B, S, 3·H·Dh]`` — the head split, the
transposes a path needs and the merge back to ``[B, S, H·Dh]`` included, as
a transformer layer pays them — on every path ``ops/attention.py`` has, at
the shapes the benchmark's cells send (8,192 tokens of BERT-base heads at S
128–1024; ``latent``: the decoder cell's core, 32 heads at S 4096 with
192-wide queries and keys and 128-wide values, which no single projection
splits into: three ``[B, S, H, D]`` operands) and at long context.  The
crossover in ``ops/attention.py::_kernel_path`` is read off this table
(PERF.md §6).  Device timings: runs on a TPU only (through the chip tool)
and exits non-zero anywhere else:

    python tools/bench_longcontext.py [--shapes bert|long|latent|all] [--out FILE]
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (batch, sequence, heads, head width, causal[, the values' head width])
SHAPES = {
    "bert": [(64, 128, 12, 64, False), (32, 256, 12, 64, False),
             (21, 384, 12, 64, False), (16, 512, 12, 64, False),
             (16, 512, 12, 64, True), (8, 1024, 12, 64, False),
             (4, 2048, 12, 64, False)],
    "long": [(1, 4096, 8, 64, True), (1, 8192, 8, 64, True),
             (1, 16384, 8, 64, True), (1, 32768, 8, 64, True)],
    "latent": [(1, 4096, 32, 192, True, 128)],
}
SHAPES["all"] = SHAPES["bert"] + SHAPES["latent"] + SHAPES["long"]


def paths(att):
    """name -> (f(x, heads, causal) -> [B, S, H·Dv], fits(B, S, heads, head
    width, the values' head width)), each a differentiable path of
    ``ops/attention.py`` called below its dispatcher, so that the table
    does not move with the rule it is there to set.  ``x``: the fused
    projection ``[B, S, 3·H·Dh]``, or ``(q, k, v)``, each ``[B, S, H, D]``."""
    import jax.numpy as jnp

    def split(x, heads):
        if isinstance(x, tuple):
            return x
        b, s, d3 = x.shape
        x = x.reshape(b, s, 3, heads, d3 // 3 // heads)
        return x[:, :, 0], x[:, :, 1], x[:, :, 2]

    def xla(x, heads, causal):
        q, k, v = split(x, heads)
        out = att._flash_bshd(q, k, v, causal, q.shape[-1] ** -0.5)
        return out.reshape(out.shape[0], out.shape[1], -1)

    def blockwise(block, x, heads, causal):
        q, k, v = (x.transpose(0, 2, 1, 3) for x in split(x, heads))
        out = att._flash_kernels(q, k, v, causal, q.shape[-1] ** -0.5,
                                 att._Launch(False, (block, block)))
        out = out.transpose(0, 2, 1, 3)
        return out.reshape(out.shape[0], out.shape[1], -1)

    def in_place(qkv, heads, causal):
        return att._flash_qkv_tile(qkv, heads, causal,
                                   (qkv.shape[-1] // 3 // heads) ** -0.5,
                                   att._Launch(False))

    # the XLA path up to the score bytes the dispatcher leaves it: beyond, its
    # S×S temporaries do not fit the chip
    table = {"xla": (xla, lambda b, s, h, dh, dv:
                     4 * b * h * s * s < att._KERNEL_MIN_SCORE_BYTES)}
    for block in (512, 256, 128):
        table[f"blockwise{block}"] = (
            functools.partial(blockwise, block),
            lambda b, s, h, dh, dv, block=block: s % block == 0)
    table["tile_in_place"] = (
        in_place, lambda b, s, h, dh, dv:
        dv == dh and att._qkv_tile_fits(s, h, dh, jnp.bfloat16))
    return table


def time_ms(fn, *args, repeats=20):
    import jax

    jax.block_until_ready(fn(*args))  # compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shapes", choices=sorted(SHAPES), default="bert")
    ap.add_argument("--out", default="chiprun_out/attn_crossover.json")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu import config
    from incubator_mxnet_tpu.ops import attention as att

    device = config.device_record()
    if device["platform"] != "tpu":
        raise SystemExit(f"bench_longcontext.py times Mosaic kernels: needs "
                         f"a TPU, found platform {device['platform']!r}")
    config.enable_compile_cache()
    print(f"device: {device}", flush=True)
    rows = []
    print(f"{'B':>4}{'S':>7}{'H':>4}{'Dh/Dv':>8}{'causal':>7}  {'path':<16}"
          f"{'fwd ms':>10}{'bwd ms':>10}{'fwd+bwd ms':>12}"
          f"{'max |Δ| vs first':>18}", flush=True)
    for b, s, h, dh, causal, *dv in SHAPES[args.shapes]:
        dv, = dv or (dh,)
        ks = jax.random.split(jax.random.PRNGKey(s), 4)
        if dv == dh:
            qkv = jax.random.normal(ks[0], (b, s, 3 * h * dh), jnp.bfloat16)
        else:
            qkv = tuple(jax.random.normal(key, (b, s, h, width), jnp.bfloat16)
                        for key, width in zip(ks[:3], (dh, dh, dv)))
        w = jax.random.normal(ks[3], (b, s, h * dv), jnp.bfloat16)
        want = None  # of the first path that runs: XLA where it takes the shape
        for name, (fn, fits) in paths(att).items():
            row = {"B": b, "S": s, "H": h, "Dh": dh, "Dv": dv, "causal": causal,
                   "path": name}
            rows.append(row)
            label = (f"{b:>4}{s:>7}{h:>4}{f'{dh}/{dv}':>8}{str(causal):>7}  "
                     f"{name:<16}")
            if not fits(b, s, h, dh, dv):
                row["skipped"] = "the path does not take this shape"
                continue
            try:
                fwd = jax.jit(lambda x, fn=fn: fn(x, h, causal))
                # a loss whose gradient needs the output, as the layers
                # after attention do: under a linear one XLA drops the
                # forward of every path that rematerializes
                grad = jax.jit(jax.grad(
                    lambda x, fn=fn: jnp.sum(jnp.square(
                        (fn(x, h, causal) * w).astype(jnp.float32)))))
                # the backward alone: the residuals of one VJP forward, pulled
                # back again and again
                out, pull = jax.jit(lambda x, fn=fn: jax.vjp(
                    lambda x: fn(x, h, causal), x))(qkv)
                bwd = jax.jit(lambda pull, ct: pull(ct))
                got = [x.astype(jnp.float32) for x in
                       jax.tree_util.tree_leaves((fwd(qkv), grad(qkv)))]
                if want is None:
                    want = got
                row["max_abs_diff_vs_first"] = max(
                    float(jnp.abs(g - r).max()) for g, r in zip(got, want))
                row["fwd_ms"] = time_ms(fwd, qkv)
                row["bwd_ms"] = time_ms(bwd, pull, out)
                row["fwd_bwd_ms"] = time_ms(grad, qkv)
                print(f"{label}{row['fwd_ms']:>10.3f}{row['bwd_ms']:>10.3f}"
                      f"{row['fwd_bwd_ms']:>12.3f}"
                      f"{row['max_abs_diff_vs_first']:>18.4f}", flush=True)
            except Exception as e:  # a kernel the compiler refuses at this shape
                row["skipped"] = f"{type(e).__name__}: {str(e)[:200]}"
                print(f"{label}  {row['skipped'][:90]}", flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": device, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""Attention crossover benchmark: where the VMEM-resident Pallas kernels
beat the XLA path, by shape.

Times forward and forward+backward (``jax.grad``) of self-attention read
from a fused QKV projection ``[B, S, 3·H·Dh]`` — the head split, the
transposes a path needs and the merge back to ``[B, S, H·Dh]`` included, as
a transformer layer pays them — on every path ``ops/attention.py`` has, at
the shapes the benchmark's cells send (8,192 tokens of BERT-base heads at S
128–1024) and at long context.  The crossover in
``ops/attention.py::_kernel_path`` is read off this table (PERF.md §6).
Device timings: runs on a TPU only (through the chip tool) and exits
non-zero anywhere else:

    python tools/bench_longcontext.py [--shapes bert|long|all] [--out FILE]
"""
import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (batch, sequence, heads, head width, causal)
SHAPES = {
    "bert": [(64, 128, 12, 64, False), (32, 256, 12, 64, False),
             (21, 384, 12, 64, False), (16, 512, 12, 64, False),
             (16, 512, 12, 64, True), (8, 1024, 12, 64, False),
             (4, 2048, 12, 64, False)],
    "long": [(1, 4096, 8, 64, True), (1, 8192, 8, 64, True),
             (1, 16384, 8, 64, True), (1, 32768, 8, 64, True)],
}
SHAPES["all"] = SHAPES["bert"] + SHAPES["long"]


def paths(att):
    """name -> (f(qkv [B, S, 3·H·Dh], heads, causal) -> [B, S, H·Dh],
    fits(S, heads, head width)), each a differentiable path of
    ``ops/attention.py`` called below its dispatcher, so that the table
    does not move with the rule it is there to set."""
    import jax.numpy as jnp

    def split(qkv, heads):
        b, s, d3 = qkv.shape
        x = qkv.reshape(b, s, 3, heads, d3 // 3 // heads)
        return x[:, :, 0], x[:, :, 1], x[:, :, 2]

    def xla(qkv, heads, causal):
        q, k, v = split(qkv, heads)
        out = att._flash_bshd(q, k, v, causal, q.shape[-1] ** -0.5)
        return out.reshape(qkv.shape[0], qkv.shape[1], -1)

    def blockwise(block, qkv, heads, causal):
        q, k, v = (x.transpose(0, 2, 1, 3) for x in split(qkv, heads))
        out = att._flash_kernels(q, k, v, causal, q.shape[-1] ** -0.5,
                                 att._Launch(False, (block, block)))
        return out.transpose(0, 2, 1, 3).reshape(qkv.shape[0], qkv.shape[1], -1)

    def in_place(qkv, heads, causal):
        return att._flash_qkv_tile(qkv, heads, causal,
                                   (qkv.shape[-1] // 3 // heads) ** -0.5,
                                   att._Launch(False))

    table = {"xla": (xla, lambda s, h, dh: True)}
    for block in (512, 256, 128):
        table[f"blockwise{block}"] = (
            functools.partial(blockwise, block),
            lambda s, h, dh, block=block: s % block == 0)
    table["tile_in_place"] = (
        in_place, lambda s, h, dh: att._qkv_tile_fits(s, h, dh, jnp.bfloat16))
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
    print(f"{'B':>4}{'S':>7}{'H':>4}{'Dh':>4}{'causal':>7}  {'path':<16}"
          f"{'fwd ms':>10}{'fwd+bwd ms':>12}{'max |Δ| vs xla':>16}", flush=True)
    for b, s, h, dh, causal in SHAPES[args.shapes]:
        ks = jax.random.split(jax.random.PRNGKey(s), 2)
        qkv = jax.random.normal(ks[0], (b, s, 3 * h * dh), jnp.bfloat16)
        w = jax.random.normal(ks[1], (b, s, h * dh), jnp.bfloat16)
        want = None
        for name, (fn, fits) in paths(att).items():
            row = {"B": b, "S": s, "H": h, "Dh": dh, "causal": causal,
                   "path": name}
            rows.append(row)
            if not fits(s, h, dh):
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
                got = (fwd(qkv).astype(jnp.float32),
                       grad(qkv).astype(jnp.float32))
                if want is None:
                    want = got
                row["max_abs_diff_vs_xla"] = [
                    float(jnp.abs(g - r).max()) for g, r in zip(got, want)]
                row["fwd_ms"] = time_ms(fwd, qkv)
                row["fwd_bwd_ms"] = time_ms(grad, qkv)
                print(f"{b:>4}{s:>7}{h:>4}{dh:>4}{str(causal):>7}  {name:<16}"
                      f"{row['fwd_ms']:>10.3f}{row['fwd_bwd_ms']:>12.3f}"
                      f"{max(row['max_abs_diff_vs_xla']):>16.4f}", flush=True)
            except Exception as e:  # a kernel the compiler refuses at this shape
                row["skipped"] = f"{type(e).__name__}: {str(e)[:200]}"
                print(f"{b:>4}{s:>7}{h:>4}{dh:>4}{str(causal):>7}  {name:<16}"
                      f"  {row['skipped'][:90]}", flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": device, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()

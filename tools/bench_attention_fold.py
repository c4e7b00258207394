#!/usr/bin/env python
"""The causal blockwise attention kernels, folded and unfolded, at the
benchmark's decoder cells' shapes.

A causal call whose blocks tile a square score matrix with an even number n of
blocks takes the folded kernels (``ops/attention.py::_folds``): grid cell i
computes query blocks i and n-1-i (the backward: key blocks j and n-1-j) in
one loop of n + 1 steps whose trip count the compiler knows.  This times
each kernel both ways at each cell's shape, forward (with its log-sum-exp)
and one-pass backward, and reports ms a call and us a block (a causal call
computes n(n+1)/2 blocks a batch·head row):

    python tools/bench_attention_fold.py [--out FILE] [--cells a,b] [--rehearse]

Device timings: runs on a TPU only and exits non-zero anywhere else;
``--rehearse`` runs a 16th of every length in the Pallas interpreter on any
platform and writes nothing.
"""
import argparse
import contextlib
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# cell: batch, query heads, key/value heads, length, key width, value width, selected
CELLS = {
    "keye-vl-2.0-30b-a3b.clm-s8192": (1, 32, 4, 8192, 128, 128, True),
    "xing4.0-29b-a4b.clm-s4096": (1, 32, 32, 4096, 192, 128, False),
    "lfm2-24b-a2b.clm-b2-s8192": (2, 32, 8, 8192, 64, 64, False),
    "nemotron-3-nano-30b-a3b.clm-s8192": (1, 32, 2, 8192, 128, 128, False),
    "solar-open2-250b.clm-s4096": (1, 8, 1, 4096, 128, 128, False),
}
BLOCK = 512


@contextlib.contextmanager
def unfolded(attn):
    """Every call takes the unfolded kernels while this is open."""
    folds = attn._folds
    attn._folds = lambda *a: False
    try:
        yield
    finally:
        attn._folds = folds


def ms_a_call(fn, *args, calls=10, repeats=5):
    """Median over ``repeats`` of the mean time of ``calls`` calls issued back
    to back and waited for once."""
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeats):
        start = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - start) / calls * 1e3)
    return statistics.median(times)


def operands(cell, shrink, seed=0):
    import jax
    import jax.numpy as jnp

    b, h, h_kv, s, d, d_v, selected = CELLS[cell]
    s //= shrink
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b * h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b * h_kv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b * h_kv, s, d_v), jnp.bfloat16)
    do = jax.random.normal(ks[3], (b * h, s, d_v), jnp.bfloat16)
    select = None
    if selected:   # about the keye cell's share of live pairs, every query its own key
        chosen = jax.random.bernoulli(ks[4], 0.45, (b, s, s)) | jnp.eye(s, dtype=bool)
        select = chosen.astype(jnp.int8)
    return q, k, v, do, select, d ** -0.5


def time_cell(cell, shrink, interpret, calls):
    import jax

    from incubator_mxnet_tpu.ops import attention as attn

    q, k, v, do, select, scale = operands(cell, shrink)
    block = BLOCK // shrink
    n = q.shape[1] // block
    blocks = q.shape[0] * n * (n + 1) // 2
    row = {"cell": cell, "n": n, "blocks": blocks,
           "folds": attn._folds(True, q.shape[1], k.shape[1], (block, block), q.shape[2],
                                v.shape[2], q.dtype, q.shape[0] // k.shape[0], select is not None)}
    got = {}
    for form in ("unfolded", "folded"):
        with unfolded(attn) if form == "unfolded" else contextlib.nullcontext():
            # new functions a form: a trace is cached by its function
            f = jax.jit(lambda q, k, v, select: attn._flash_fwd_pallas(
                q, k, v, True, scale, interpret, block, block, with_lse=True, select=select))
            g = jax.jit(lambda q, k, v, do, o, lse, select: attn._flash_bwd_pallas(
                q, k, v, do, o, lse, True, scale, interpret, block, block, select=select))
            o, lse = f(q, k, v, select)
            lse = lse[:, :, 0]
            row[f"fwd_ms.{form}"] = ms_a_call(f, q, k, v, select, calls=calls)
            row[f"bwd_ms.{form}"] = ms_a_call(g, q, k, v, do, o, lse, select, calls=calls)
            got[form] = (o, g(q, k, v, do, o, lse, select))
    for part in ("fwd", "bwd"):
        for form in ("unfolded", "folded"):
            row[f"{part}_us_a_block.{form}"] = row[f"{part}_ms.{form}"] * 1e3 / blocks
    row["fwd_bits_equal"] = bool((got["folded"][0] == got["unfolded"][0]).all())
    row["bwd_max_abs_apart"] = [float(abs(a.astype("float32") - b.astype("float32")).max())
                                for a, b in zip(got["folded"][1], got["unfolded"][1])]
    return row


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default="chiprun_out/bench_attention_fold.json")
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--rehearse", action="store_true",
                    help="a 16th of every length in the interpreter, on any platform; writes nothing")
    args = ap.parse_args()
    import jax

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit("bench_attention_fold: no TPU here (device timings only); --rehearse runs anywhere")
    shrink, interpret, calls = (16, True, 1) if args.rehearse else (1, False, 10)
    rows = []
    for cell in args.cells.split(","):
        row = time_cell(cell, shrink, interpret, calls)
        rows.append(row)
        print(json.dumps(row), flush=True)
    if not args.rehearse:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device_kind": jax.devices()[0].device_kind, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()

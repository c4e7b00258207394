#!/usr/bin/env python
"""The held experts' row movements, both ways: where gathers through the
inverse of the expert sort beat scatter-adds, by shape.

A row bucket of ``moe_ffn_dropless`` moves its rows between token order and
sorted-row order three times a training step: the combine (rows summed into
their tokens with their gates), the gates' gradient and the input gradient
(the transpose of the row gather ``xt[token]``).  This times them at the
benchmark's decoder cells' shapes (tokens, experts a token, model width, every
row bucket ``dropless_row_buckets`` gives), each way:

* ``scatter``: ``zeros[T, d].at[token].add(f32(ys) · gate)`` and autodiff's
  transpose of ``xt[token]`` (what a bucket over ``GATHER_RATIO`` runs);
* ``gather``: ``ops/moe.py``'s ``_combine`` and ``_token_rows``, whose
  derivatives sum each token's held rows by a gather through ``inv``.

Rows: the combine alone, the input gradient alone, and the whole shell (row
gather, a stand-in for the products, combine; value and gradient of the
tokens and the gates).  Also the inverse itself, by a scatter of ``arange``
and by ``argsort``.  ``GATHER_RATIO`` in ``ops/moe.py`` is read off this table
(PERF.md §5).  Device timings: runs on a TPU only and
exits non-zero anywhere else; ``--rehearse`` runs a 64th of every shape on any
platform and writes nothing:

    python tools/bench_moe_combine.py [--out FILE] [--rehearse]
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# cell: tokens, experts a token, model width, experts held, experts
CELLS = {
    "xing4.0-29b-a4b.clm-s4096": (4096, 4, 3584, 8, 64),
    "lfm2-24b-a2b.clm-b2-s8192": (16384, 4, 2048, 8, 64),
    "keye-vl-2.0-30b-a3b.clm-s8192": (8192, 8, 2048, 16, 128),
    "nemotron-3-nano-30b-a3b.clm-s8192": (8192, 6, 2688, 8, 128),
    "solar-open2-250b.clm-s4096": (4096, 8, 4096, 8, 320),
}


def ms_a_call(fn, *args, calls=20, repeats=5):
    """Median over ``repeats`` of the mean time of ``calls`` calls issued back
    to back and waited for once: the device's time where it exceeds the
    host's dispatch."""
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


def layer(tokens, top_k, d, held, experts, rows, seed=0):
    """Operands of one bucket: pairs sorted by expert with the held ones
    first (as the router's sort leaves them), ``n_here`` the rows the
    expected share routes here, capped at the bucket."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    n_pairs = tokens * top_k
    key = np.minimum(rng.integers(0, experts, n_pairs), held)
    order = np.argsort(key, kind="stable").astype(np.int32)
    inv = np.argsort(order).astype(np.int32)
    n_here = min(rows, int((key < held).sum()))
    bf = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
    return dict(xt=bf(tokens, d), ys=bf(rows, d), da=bf(rows, d),
                gate=jnp.asarray(rng.uniform(size=n_pairs), jnp.float32),
                order=jnp.asarray(order), inv=jnp.asarray(inv), n_here=jnp.int32(n_here))


def forms(rows, top_k):
    """form -> (combine, input gradient, shell), each a jitted function."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops import moe

    def scatter_combine(ys, gate, order, inv, n_here):
        pair = order[:rows]
        y = ys.astype(jnp.float32) * gate[pair][:, None]
        return jnp.zeros((inv.shape[0] // top_k, ys.shape[1]), jnp.float32).at[
            pair // top_k].add(y)

    def gather_combine(ys, gate, order, inv, n_here):
        return moe._combine(ys, gate, order[:rows], inv, n_here, top_k)

    def scatter_grad(xt, da, order, inv, n_here):
        return jax.vjp(lambda x: x[order[:rows] // top_k], xt)[1](da)[0]

    def gather_grad(xt, da, order, inv, n_here):
        return jax.vjp(lambda x: moe._token_rows(x, order[:rows] // top_k, inv, n_here,
                                                 top_k), xt)[1](da)[0]

    def shell(take, combine):
        def loss(xt, gate, order, inv, n_here):
            ours = (jnp.arange(rows) < n_here)[:, None]
            a = jnp.where(ours, take(xt, order, inv, n_here), 0)
            ys = jnp.where(ours, a * jnp.bfloat16(1.5), 0)
            return combine(ys, gate, order, inv, n_here).sum()
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))

    scatter_take = lambda xt, order, inv, n_here: xt[order[:rows] // top_k]
    gather_take = lambda xt, order, inv, n_here: moe._token_rows(
        xt, order[:rows] // top_k, inv, n_here, top_k)
    return {"scatter": (jax.jit(scatter_combine), jax.jit(scatter_grad),
                        shell(scatter_take, scatter_combine)),
            "gather": (jax.jit(gather_combine), jax.jit(gather_grad),
                       shell(gather_take, gather_combine))}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default="chiprun_out/moe_combine.json")
    ap.add_argument("--rehearse", action="store_true",
                    help="a 64th of every shape, any platform, nothing written")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops import moe

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit("bench_moe_combine: device timings need a TPU, "
                         f"found {dev.platform!r}")
    table = []
    for cell, (tokens, top_k, d, held, experts) in CELLS.items():
        if args.rehearse:
            tokens //= 64
        n_pairs = tokens * top_k
        ops = layer(tokens, top_k, d, held, experts, n_pairs)
        inverse = {
            "scatter": jax.jit(lambda o: jnp.zeros_like(o).at[o].set(
                jnp.arange(o.shape[0], dtype=o.dtype), unique_indices=True)),
            "argsort": jax.jit(lambda o: jnp.argsort(o).astype(o.dtype))}
        row = {"cell": cell, "pairs": n_pairs, "d": d,
               "inverse_ms": {k: ms_a_call(f, ops["order"]) for k, f in inverse.items()},
               "buckets": []}
        for rows in moe.dropless_row_buckets(n_pairs, held, experts):
            ops = layer(tokens, top_k, d, held, experts, rows)
            at = (ops["order"], ops["inv"], ops["n_here"])
            bucket = {"rows": rows, "ratio": round(n_pairs / rows, 2)}
            for form, (combine, grad, shell) in forms(rows, top_k).items():
                bucket[form] = {
                    "combine_ms": ms_a_call(combine, ops["ys"], ops["gate"], *at),
                    "input_grad_ms": ms_a_call(grad, ops["xt"], ops["da"], *at),
                    "shell_ms": ms_a_call(shell, ops["xt"], ops["gate"], *at)}
            row["buckets"].append(bucket)
            print(cell, json.dumps(bucket), flush=True)
        print(cell, "inverse", json.dumps(row["inverse_ms"]), flush=True)
        table.append(row)
    if not args.rehearse:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device_kind": dev.device_kind, "cells": table}, f, indent=1)


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""Diagnostic: lower the BERT SPMD train step to optimized HLO (CPU, no chip
time) and report convert/transpose/fusion counts + biggest fp32 tensors.
Used to verify AMP/layout perf changes actually land in the compiled graph.

Usage: python tools/inspect_step.py [--layers N] [--dump FILE]
"""
import argparse
import collections
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if "--tpu" in sys.argv:
    sys.argv.remove("--tpu")
else:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--dump", default=None)
    args = ap.parse_args()

    import jax

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import amp
    from incubator_mxnet_tpu.gluon.model_zoo.bert import BERTModel, BERTForPretrain
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.parallel import make_mesh, SPMDTrainer

    B, S = args.batch, 128
    amp.init("bfloat16")
    mx.random.seed(0)
    bert = BERTModel(vocab_size=args.vocab, units=768, hidden_size=3072,
                     num_layers=args.layers, num_heads=12, max_length=512,
                     dropout=0.1)
    net = BERTForPretrain(bert, vocab_size=args.vocab)
    net.initialize()
    rng = np.random.RandomState(0)
    tok = mx.nd.array(rng.randint(0, args.vocab, (B, S)), dtype="int32")
    seg = mx.nd.zeros((B, S), dtype="int32")
    labels = mx.nd.array(rng.randint(0, args.vocab, (B, S)), dtype="int32")
    net(mx.nd.zeros((2, S), dtype="int32"), mx.nd.zeros((2, S), dtype="int32"))

    def mlm_loss(out, label):
        from incubator_mxnet_tpu.ops.nn import streaming_softmax_ce
        mlm_logits, _ = out
        return NDArray(streaming_softmax_ce(mlm_logits._data, label._data).mean(axis=-1))

    mesh = make_mesh()
    trainer = SPMDTrainer(net, mlm_loss, "adam", {"learning_rate": 1e-4}, mesh=mesh)
    arrays = trainer.shard_batch(tok, seg, labels)
    fn = trainer._build_step(arrays)
    lowered = fn.lower(
        *trainer._step_args(B), trainer._param_arrays, trainer._opt_states,
        *arrays,
    )
    hlo = lowered.compile().as_text()
    if args.dump:
        with open(args.dump, "w") as f:
            f.write(hlo)

    counts = collections.Counter()
    big_converts = collections.Counter()
    big_transposes = collections.Counter()
    # HLO line shape:  %name = f32[8,128,768]{2,1,0} convert(%arg)
    pat = re.compile(r"= *([a-z0-9]+)\[([0-9,]*)\][^ ]* +([\w\-]+)\(")
    for line in hlo.splitlines():
        m = pat.search(line)
        if not m:
            continue
        dt, dims, op = m.groups()
        counts[op] += 1
        if op in ("convert", "transpose", "copy"):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            if n >= B * S * 256:  # big tensors only
                tgt = big_converts if op == "convert" else big_transposes
                tgt[f"{op} {dt}[{dims}]"] += 1

    print("== op histogram (top 25) ==")
    for op, c in counts.most_common(25):
        print(f"  {op:22s} {c}")
    print("== big converts ==")
    for k, c in big_converts.most_common(20):
        print(f"  {c:3d}x {k}")
    print("== big transposes/copies ==")
    for k, c in big_transposes.most_common(20):
        print(f"  {c:3d}x {k}")


if __name__ == "__main__":
    main()

"""Build BERT pretraining through the program's normal entry points.

Re-states what ``bench.py::build_bert_pretrain`` / ``bench_bert`` set up
(AMP bf16, ``net.cast("bfloat16")``, Adam with fp32 masters, ``make_mesh``,
``SPMDTrainer``, ``shard_batch``, a fence that ends in a D2H) without
importing ``bench.py``.  Weights and the batch come from ``--seed``.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from .. import traffic_gen
from ..reference import bert as reference

# How far the system's MLM logits under bf16 AMP may lie from the float32
# reference, as shares of the logits' standard deviation.  Measured (PERF.md,
# PR 24): the system differs by 0.80 % rms and 4.2-4.6 % at the worst of its
# 4.9 million logits (bf16 matmul inputs; float32 accumulation, softmax and
# norms); the reference computed wholly in bf16 differs by 1.20 % rms and
# 6.7 % at worst.  The rms hardly moves with the seed, so its tolerance sits
# between the two and an all-bf16 computation fails it; the worst-case
# tolerance catches a fault confined to a few logits.
LOGIT_RMS_TOLERANCE_STD_SHARE = 0.01
LOGIT_MAX_TOLERANCE_STD_SHARE = 0.08
LOSS_TOLERANCE = 0.02
PROBE_SEQUENCES = 8


def build(ctx):
    import jax

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import amp
    from incubator_mxnet_tpu.gluon.model_zoo.bert import BERTForPretrain, BERTModel
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.ops.nn import streaming_softmax_ce
    from incubator_mxnet_tpu.parallel import SPMDTrainer, make_mesh

    c, t = ctx.config, ctx.traffic
    bf16 = c["dtype"] == "bfloat16_amp_fp32_master"
    if bf16:
        amp.init("bfloat16")
    t0 = time.perf_counter()
    # eager init on the host CPU, as the program's users do (net.initialize()
    # with no ctx); the trainer places the parameters on the mesh itself
    with jax.default_device(mx.cpu().jax_device()):
        mx.random.seed(ctx.seed31)
        bert = BERTModel(
            vocab_size=c["vocab_size"], units=c["hidden_size"],
            hidden_size=c["intermediate_size"],
            num_layers=c["num_hidden_layers"],
            num_heads=c["num_attention_heads"],
            max_length=c["max_position_embeddings"],
            type_vocab=c["type_vocab_size"],
            dropout=c["hidden_dropout_prob"])
        net = BERTForPretrain(bert, vocab_size=c["vocab_size"])
        net.initialize()
        s, p = t["seq_length"], t["masked_positions"]
        zeros = lambda n: mx.nd.zeros((2, n), dtype="int32")
        net(zeros(s), zeros(s), zeros(p))  # materialise deferred shapes
    if bf16:
        net.cast("bfloat16")
    ctx.say(f"model initialised on the host in {time.perf_counter() - t0:.1f} s")

    def mlm_loss(out, label):
        mlm_logits, _ = out
        return NDArray(streaming_softmax_ce(mlm_logits._data, label._data).mean(axis=-1))

    tok, seg, pos, labels = traffic_gen.train_batch(
        t, ctx.seed, c["vocab_size"], len(ctx.devices))
    # before the trainer exists, and released on return: the reference's
    # copies and scratch are not in the peak unless they exceed the trainer's
    checks = _reference_check(ctx, net, mlm_loss, tok, seg, pos, labels)
    ctx.say_memory("after the reference check, before the trainer is built")

    t0 = time.perf_counter()
    trainer = SPMDTrainer(
        net, mlm_loss, "adam",
        {"learning_rate": c["learning_rate"], "multi_precision": bf16},
        mesh=make_mesh(devices=ctx.devices))
    tok, seg, pos, labels = trainer.shard_batch(tok, seg, pos, labels)
    ctx.say(f"trainer built in {time.perf_counter() - t0:.1f} s: "
            f"{tok.shape[0] * tok.shape[1]} tokens a step on "
            f"{len(ctx.devices)} chip(s)")

    def fence(loss):
        """End a timed region in a real D2H of the last loss AND one updated
        parameter: the loss alone does not depend on the last update."""
        value = float(np.asarray(loss._data))
        leaf = jax.tree_util.tree_leaves(trainer._param_arrays)[0]
        np.asarray(leaf.addressable_data(0))
        return value

    return {"step": lambda: trainer.step((tok, seg, pos), labels),
            "fence": fence,
            "tokens_per_step": int(tok.shape[0] * tok.shape[1]),
            "checks": checks,
            "shapes": {"batch": int(tok.shape[0]), "seq": int(tok.shape[1]),
                       "masked": int(pos.shape[1])}}


def _reference_check(ctx, net, mlm_loss, tok, seg, pos, labels):
    """The system's forward (its own model code under AMP, inference mode,
    on the chip) against the float32 reference on the first sequences of the
    batch: MLM logits and per-sequence loss."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    c = ctx.config
    n = min(PROBE_SEQUENCES, tok.shape[0])
    t0 = time.perf_counter()
    dev = ctx.devices[0]
    batch = [jax.device_put(a[:n], dev) for a in (tok, seg, pos, labels)]
    fn, host_params = net.export_jittable()
    names = sorted(p.name for p in net.collect_params().values())
    params = jax.device_put(list(host_params), dev)
    sys_logits = jax.jit(fn)(params, *batch[:3])[0]
    sys_loss = mlm_loss((NDArray(sys_logits), None), NDArray(batch[3]))._data
    ref_fn = jax.jit(functools.partial(
        reference.forward, layers=c["num_hidden_layers"],
        heads=c["num_attention_heads"]))
    ref_logits = ref_fn(dict(zip(names, params)), *batch[:3])
    ref_loss = reference.mlm_loss_per_sequence(ref_logits, batch[3])
    delta = sys_logits.astype(jnp.float32) - ref_logits
    diff = float(jnp.max(jnp.abs(delta)))
    rms = float(jnp.sqrt(jnp.mean(delta ** 2)))
    std = float(jnp.std(ref_logits))
    loss_diff = float(jnp.max(jnp.abs(sys_loss.astype(jnp.float32) - ref_loss)))
    ctx.say(f"reference check on {n} sequences in {time.perf_counter() - t0:.1f} s: "
            f"logit difference rms {rms:.5f} ({100 * rms / std:.3f} % of the logit "
            f"std {std:.4f}), max {diff:.5f} ({100 * diff / std:.2f} %), max per-sequence loss difference "
            f"{loss_diff:.5f}; system loss "
            f"{np.round(np.asarray(sys_loss, np.float32), 4).tolist()}")
    return {"logits_match_reference": (rms <= LOGIT_RMS_TOLERANCE_STD_SHARE * std
                                       and diff <= LOGIT_MAX_TOLERANCE_STD_SHARE * std),
            "loss_matches_reference": loss_diff <= LOSS_TOLERANCE}

"""Build LFM2-MoE causal-LM training through the program's normal entry points.

The same set-up as ``solar_clm.py`` (AMP bf16, ``net.cast("bfloat16")``, Adam
with fp32 masters, ``make_mesh``, ``SPMDTrainer``, ``shard_batch``, a fence
that ends in a D2H; the selection bias balanced on the timed batch by the
model's own rule, by ``xing4_clm._balance_selection_bias``), for
``gluon.model_zoo.lfm2_moe.Lfm2ForCausalLM`` with the experts this chip holds.
Weights and the batch come from ``--seed``.

What decides ``correct`` reads the TIMED program: the trainer's first step
against the float32 reference's (``first_step.check``), by this cell's
``LIMITS``.  After the window the step's traced paths are checked: the
attention call on the blockwise kernels, none on XLA.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from .. import traffic_clm
from ..reference import lfm2 as reference
from . import first_step

# the reference's blocks of queries at the timed shapes: one block's float32
# scores, [2, 32, 256, 8192], are 537 MB
QUERY_BLOCK = 256

# How far the trainer's first step may lie from the float32 reference's, and
# the readings each limit lies between (a TPU v5e at the timed shapes; PERF.md
# §6 lists them by seed):
#
#                               system              reference      a step that
#                               (19 seeds)          wholly bf16    changed nothing
#                                                   (4 seeds)
#   loss apart                  0.00000-0.00029     0.00009-0.00033  -
#   rows routed apart           0.003-0.147 %       2.86-3.50 %      -
#   gradient, all leaves        0.0069-0.0109       -                1
#   gradient, worst leaf        0.085-0.223         -                1
#   weighted change, worst leaf 0.087-0.173 (8)     -                1
#
# The rows: between the system's and the whole-bf16 reference's, which it
# refuses on every seed.  The loss: the precision hardly moves it, so it
# keeps the limit of the harness's accepted cells, 0.02, far over three
# times the system's readings.  The gradient and the change: between the
# system's readings and 1, with the more room above the readings.  The
# change is weighted by ``|g| + |g_ref|`` (``first_step.leaf_sums``):
# unweighted it read 0.31-0.38 at the worst leaf on every seed, because
# Adam's first step is nearly ``lr · sign(g)`` and the elements whose
# gradient lies under the two gradients' difference flip their sign (1.9-2.2 %
# of all elements, 5.0-6.8 % of the worst leaf's; over the elements whose
# ``|g_ref|`` is above three times that difference the unweighted change
# reads 0.034-0.036).  Planted faults read: a loss over half the tokens
# 0.30-0.33 over all leaves; Adam without its bias correction 1.57-2.19 at
# every leaf, weighted.
LIMITS = first_step.Limits(loss=0.02, rows=0.005, rows_slack=4, gradient=0.1,
                           gradient_leaf=0.8, change_leaf=0.6)


def model_config(c):
    """The configuration as the model takes it: the router over ALL the
    published experts, of which ``experts_held`` are held here."""
    m = dict(c)
    m["num_experts"] = c["published"]["num_experts"]
    return m, tuple(c["experts_held"])


def build(ctx):
    import jax

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import amp
    from incubator_mxnet_tpu.gluon.model_zoo.lfm2_moe import Lfm2ForCausalLM
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.ops.nn import streaming_softmax_ce
    from incubator_mxnet_tpu.parallel import SPMDTrainer, make_mesh

    c, t = ctx.config, ctx.traffic
    bf16 = c["dtype"] == "bfloat16_amp_fp32_master"
    if bf16:
        amp.init("bfloat16")
    mc, held = model_config(c)
    t0 = time.perf_counter()
    # eager init on the host CPU, as the program's users do; every shape is
    # in the configuration, so no forward pass is needed to materialise them
    with jax.default_device(mx.cpu().jax_device()):
        mx.random.seed(ctx.seed31)
        net = Lfm2ForCausalLM(mc, experts_held=held, remat=c["remat"])
        net.initialize(mx.init.Normal(c["initializer_range"]))
        net.rescale_residual_writers(c["published"]["num_hidden_layers"])
    if bf16:
        net.cast("bfloat16")
    n_params = sum(int(np.prod(p.shape)) for p in net.collect_params().values())
    ctx.say(f"model initialised on the host in {time.perf_counter() - t0:.1f} s: "
            f"{n_params:,} parameters in layers {net.model.kinds} (the first "
            f"{mc['num_dense_layers']} dense), experts held {held} of {mc['num_experts']}")

    def clm_loss(logits, label):
        return NDArray(streaming_softmax_ce(logits._data, label._data).mean(axis=-1))

    tok, labels = traffic_clm.clm_batch(t, ctx.seed, c["vocab_size"], len(ctx.devices))
    # before the trainer exists, and released on return
    expected = first_step.set_up_readings(
        ctx, net, tok, labels, functools.partial(reference.forward, config=mc, experts_held=held,
                                                 query_block=QUERY_BLOCK),
        reference.loss_per_token)
    ctx.say_memory("after the reference's step, before the trainer is built")

    t0 = time.perf_counter()
    trainer = SPMDTrainer(
        net, clm_loss, "adam",
        {"learning_rate": c["learning_rate"], "multi_precision": bf16},
        mesh=make_mesh(devices=ctx.devices))
    tok, labels = trainer.shard_batch(tok, labels)
    ctx.say(f"trainer built in {time.perf_counter() - t0:.1f} s: "
            f"{tok.shape[0] * tok.shape[1]} tokens a step on "
            f"{len(ctx.devices)} chip(s)")
    checks = first_step.check(ctx, trainer, tok, labels, expected, LIMITS)
    del expected

    def fence(loss):
        """End a timed region in a real D2H of the last loss AND one updated
        parameter (the loss alone does not depend on the last update): the
        SMALLEST, since one program updates them all."""
        value = float(np.asarray(loss._data))
        leaf = min(jax.tree_util.tree_leaves(trainer._param_arrays), key=lambda a: a.size)
        np.asarray(leaf.addressable_data(0))
        return value

    def late_checks():
        from incubator_mxnet_tpu import profiler

        trainer._drain_moe_extras()   # the last step's routing metrics
        counts = profiler.counters()
        ctx.say(f"routing: {counts['moe_rows_routed_here']} rows routed here in "
                f"{counts['moe_step']} steps, {counts['moe_tokens_dropped']} dropped; "
                f"last step {trainer._moe_last}; traced call sites: short convolutions "
                f"{counts['short_conv_traced']}, attention on the kernels "
                f"{counts['attention_dispatch_pallas']} / on XLA "
                f"{counts['attention_dispatch_xla']}, grouped products on the kernels "
                f"{counts['moe_grouped_dispatch_pallas']} / on XLA "
                f"{counts['moe_grouped_dispatch_xla']}")
        late = {"no_tokens_dropped": counts["moe_tokens_dropped"] == 0
                and counts["moe_rows_routed_here"] > 0}
        if not ctx.dry_run:   # the CPU has no kernels to take
            late["attention_on_the_kernels"] = (counts["attention_dispatch_pallas"] > 0
                                                and counts["attention_dispatch_xla"] == 0)
        return late

    return {"step": lambda: trainer.step((tok,), labels),
            "fence": fence,
            "tokens_per_step": int(tok.shape[0] * tok.shape[1]),
            "checks": checks,
            "late_checks": late_checks,
            "shapes": {"batch": int(tok.shape[0]), "seq": int(tok.shape[1])}}

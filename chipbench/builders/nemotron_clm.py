"""Build Nemotron-H causal-LM training through the program's normal entry points.

The same set-up as ``xing4_clm.py`` (AMP bf16, ``net.cast("bfloat16")``, Adam
with fp32 masters, ``make_mesh``, ``SPMDTrainer``, ``shard_batch``, a fence
that ends in a D2H; the selection bias balanced on the timed batch by the
model's own rule, by that builder's ``_balance_selection_bias``), for
``gluon.model_zoo.nemotron_h.NemotronHForCausalLM`` with the experts this chip
holds.  Weights and the batch come from ``--seed``.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from .. import traffic_clm
from ..reference import nemotron_h as reference
from .xing4_clm import _balance_selection_bias, _compare

# How far the system's logits under bf16 AMP may lie from the float32
# reference on the timed batch, all 8,192 tokens, compared PER TOKEN as
# ``xing4_clm.py`` sets out (a token whose router score is a near-tie picks
# another expert under any rounding): each token's rms difference as a share
# of the logits' standard deviation; the MEDIAN token is held to one limit,
# the share of tokens over ``xing4_clm.FAR_TOKEN_STD_SHARE`` (10 %) to
# another.  Read on the chip (PERF.md, PR 32; ten seeds): the system's
# median token 1.167-1.186 % with 3.12-4.44 % of the tokens far off; the
# reference computed wholly in bf16, whose recurrence carries a bf16 state
# through 8,192 positions, 1.266 and 1.279 % with 7.29 and 8.62 %.  Each limit
# sits between its two readings; the loss hardly tells the two apart (0.0002
# to 0.0007 either way) and keeps the harness's accepted limit.
TOKEN_RMS_MEDIAN_TOLERANCE_STD_SHARE = 0.01225
FAR_TOKENS_TOLERANCE = 0.059
LOSS_TOLERANCE = 0.02


def model_config(c):
    """The configuration as the model takes it: the router over ALL the
    published experts, of which ``experts_held`` are held here."""
    m = dict(c)
    m["n_routed_experts"] = c["published"]["n_routed_experts"]
    return m, tuple(c["experts_held"])


def build(ctx):
    import jax

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import amp
    from incubator_mxnet_tpu.gluon.model_zoo.nemotron_h import NemotronHForCausalLM
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
        net = NemotronHForCausalLM(mc, experts_held=held, remat=c["remat"])
        net.initialize(mx.init.Normal(c["initializer_range"]))
        net.rescale_prenorm_residual(c["published"]["num_hidden_layers"])
    if bf16:
        net.cast("bfloat16")
    n_params = sum(int(np.prod(p.shape)) for p in net.collect_params().values())
    ctx.say(f"model initialised on the host in {time.perf_counter() - t0:.1f} s: "
            f"{n_params:,} parameters in layers {mc['hybrid_override_pattern']}, "
            f"experts held {held} of {mc['n_routed_experts']}")

    def clm_loss(logits, label):
        return NDArray(streaming_softmax_ce(logits._data, label._data).mean(axis=-1))

    tok, labels = traffic_clm.clm_batch(t, ctx.seed, c["vocab_size"], len(ctx.devices))
    # before the trainer exists, and released on return
    checks = _balance_and_check(ctx, net, mc, held, tok, labels)
    ctx.say_memory("after the reference check, before the trainer is built")

    t0 = time.perf_counter()
    trainer = SPMDTrainer(
        net, clm_loss, "adam",
        {"learning_rate": c["learning_rate"], "multi_precision": bf16},
        mesh=make_mesh(devices=ctx.devices))
    tok, labels = trainer.shard_batch(tok, labels)
    ctx.say(f"trainer built in {time.perf_counter() - t0:.1f} s: "
            f"{tok.shape[0] * tok.shape[1]} tokens a step on "
            f"{len(ctx.devices)} chip(s)")

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
                f"last step {trainer._moe_last}; scans traced {counts['ssm_scan_traced']}, "
                f"grouped-query attention calls traced {counts['attention_dispatch_grouped']}")
        return {"no_tokens_dropped": counts["moe_tokens_dropped"] == 0
                and counts["moe_rows_routed_here"] > 0}

    return {"step": lambda: trainer.step((tok,), labels),
            "fence": fence,
            "tokens_per_step": int(tok.shape[0] * tok.shape[1]),
            "checks": checks,
            "late_checks": late_checks,
            "shapes": {"batch": int(tok.shape[0]), "seq": int(tok.shape[1])}}


def _balance_and_check(ctx, net, mc, held, tok, labels):
    """The two passes of set-up that run the system's own forward (its model
    code under AMP, inference mode, on the chip) on the first sequence of the
    timed batch, with one copy of the parameters on the device."""
    import jax

    dev = ctx.devices[0]
    fn, host_params = net.export_jittable()
    names = sorted(p.name for p in net.collect_params().values())
    params = jax.device_put(list(host_params), dev)
    tok1, lab1 = (jax.device_put(a[:1], dev) for a in (tok, labels))
    _balance_selection_bias(ctx, net, fn, names, params, tok1)
    return _reference_check(ctx, fn, names, params, mc, held, tok1, lab1)


def _reference_check(ctx, fn, names, params, mc, held, tok1, lab1):
    """The system's logits against the float32 reference's, all the tokens of
    the sequence: per token, and the mean loss."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    sys_logits = jax.jit(fn)(params, tok1).astype(jnp.float32)
    ref_fn = jax.jit(functools.partial(reference.forward, config=mc, experts_held=held))
    ref_logits = ref_fn(dict(zip(names, params)), tok1)
    std = float(jnp.std(ref_logits))
    report = functools.partial(_compare, ctx, ref_logits, lab1, std)
    median, far, loss_diff = report("system", sys_logits)
    if ctx.config.get("probe", {}).get("bf16_reference"):
        # a measurement probe, never in a committed configuration: what the
        # tolerances have to refuse
        bf16_fn = jax.jit(functools.partial(reference.forward, config=mc, experts_held=held,
                                            dtype=jnp.bfloat16))
        report("reference wholly in bf16", bf16_fn(dict(zip(names, params)), tok1))
    ctx.say(f"reference check on {tok1.shape[1]} tokens took {time.perf_counter() - t0:.1f} s")
    return {"logits_match_reference": (median <= TOKEN_RMS_MEDIAN_TOLERANCE_STD_SHARE
                                       and far <= FAR_TOKENS_TOLERANCE),
            "loss_matches_reference": loss_diff <= LOSS_TOLERANCE}

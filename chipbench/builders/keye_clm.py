"""Build Keye-VL-2.0 causal-LM training through the program's normal entry points.

The same set-up as ``nemotron_clm.py`` (AMP bf16, ``net.cast("bfloat16")``,
Adam with fp32 masters, ``make_mesh``, ``SPMDTrainer``, ``shard_batch``, a
fence that ends in a D2H), for ``gluon.model_zoo.keye.KeyeForCausalLM`` with
the experts this chip holds.  Weights and the batch come from ``--seed``.
A softmax router has no selection bias for set-up to balance; what the model
has is its load-balance term, and set-up descends THAT in the routers'
weights on the timed batch (:func:`_balance_routers`) before the comparison
with the reference, as a trained model's routers arrive evened by it.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from .. import traffic_clm
from ..reference import keye as reference
from .xing4_clm import _compare

# How far the system under bf16 AMP may lie from the float32 reference on the
# timed batch, all 8,192 tokens.  Logits are compared PER TOKEN as
# ``xing4_clm.py`` sets out (a token whose router score is a near-tie picks
# another expert under any rounding, and here a query whose 2,048th index
# score is a near-tie selects another key): each token's rms difference as a
# share of the logits' standard deviation; the MEDIAN token is held to one
# limit, the share of tokens over ``xing4_clm.FAR_TOKEN_STD_SHARE`` (10 %) to
# another.  SELECTION AGREEMENT: over the rows that select (t + 1 > topk), the
# mean of |S_t ∩ S_t^ref| / topk, layer by layer; the LOWEST layer's reading is
# held to a floor.  ROWS ROUTED to the held experts: the difference from the
# reference's count, as a share of it (or ``ROWS_ROUTED_SLACK`` rows where
# that is more: the dry run routes 67 rows in all, and one near-tie is 1.5 %).
# Read on the chip (PERF.md §6, PR 34; eleven seeds for the system with the
# routers balanced and one without, two for the reference computed wholly in
# bf16):
#
#                            system            the reference wholly in bf16
#   median token             0.525 - 0.576 %   0.662, 0.695 %
#   tokens over 10 %         0.00 %            0.00 %
#   loss apart               0.00009 - 0.00053 0.00045, 0.00047
#   lowest layer's agreement 0.99044 - 0.99307 0.98847, 0.98826
#   rows routed apart        0.003 - 0.490 %   7.038, 6.399 %
#
# The median token, the agreement and the rows each sit between their two
# readings, and the bf16 reference is refused by all three (its router rounds
# too, and its index scores).  Far-off tokens read 0 on both sides (six layers
# do not carry one rerouted pair far) and the loss hardly tells the two apart:
# both keep the limits of the harness's accepted decoder cells.
TOKEN_RMS_MEDIAN_TOLERANCE_STD_SHARE = 0.0062
FAR_TOKENS_TOLERANCE = 0.072
LOSS_TOLERANCE = 0.02
SELECTION_AGREEMENT_FLOOR = 0.9890
ROWS_ROUTED_TOLERANCE = 0.02
ROWS_ROUTED_SLACK = 4


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
    from incubator_mxnet_tpu.gluon.model_zoo.keye import KeyeForCausalLM
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
        net = KeyeForCausalLM(mc, experts_held=held, remat=c["remat"])
        net.initialize(mx.init.Normal(c["initializer_range"]))
        net.rescale_residual_writers(c["published"]["num_hidden_layers"])
    if bf16:
        net.cast("bfloat16")
    n_params = sum(int(np.prod(p.shape)) for p in net.collect_params().values())
    ctx.say(f"model initialised on the host in {time.perf_counter() - t0:.1f} s: "
            f"{n_params:,} parameters in {mc['num_hidden_layers']} layers, "
            f"experts held {held} of {mc['num_experts']}")

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
                f"score tiles live {counts['sparse_attn_tiles_live']} of "
                f"{counts['sparse_attn_tiles_causal']} causal; last step {trainer._moe_last}; "
                f"sparse attention calls traced {counts['sparse_attention_traced']}, of the "
                f"kernels' calls {counts['attention_dispatch_masked']} given a selection")
        return {"no_tokens_dropped": counts["moe_tokens_dropped"] == 0
                and counts["moe_rows_routed_here"] > 0}

    return {"step": lambda: trainer.step((tok,), labels),
            "fence": fence,
            "tokens_per_step": int(tok.shape[0] * tok.shape[1]),
            "checks": checks,
            "late_checks": late_checks,
            "shapes": {"batch": int(tok.shape[0]), "seq": int(tok.shape[1])}}


# The set-up's balancing passes: sign steps down the balance term's gradient
# in each router's weights, of a size (a share of 1 / hidden_size: a logit
# moves by about that share a pass) that falls from the first value to the last.
BALANCE_PASSES = 48
BALANCE_STEPS = (0.5, 0.005)


def _balance_and_check(ctx, net, mc, held, tok, labels):
    """The passes of set-up that run the system's own forward (its model code
    under AMP, inference mode, on the chip) on the first sequence of the timed
    batch, with one copy of the parameters on the device: ONE compiled
    program serves them all (:func:`_system_pass`)."""
    import jax
    import jax.numpy as jnp

    dev = ctx.devices[0]
    fn, host_params = net.export_jittable()
    names = sorted(p.name for p in net.collect_params().values())
    params = jax.device_put(list(host_params), dev)
    tok1, lab1 = (jax.device_put(a[:1], dev) for a in (tok, labels))
    routers = [b.ffn.router_weight for b in net.model.blocks]
    where = [names.index(p.name) for p in routers]
    run = _system_pass(fn, where, mc)
    weights = _balance_routers(ctx, run, params, [params[at].astype(jnp.float32) for at in where],
                               tok1, mc, held)
    for p, at, w in zip(routers, where, weights):     # into the net, and into this copy
        params[at] = w.astype(params[at].dtype)
        p.set_data(_as_nd(params[at]))
    return _reference_check(ctx, run, names, params, weights, mc, held, tok1, lab1)


def _as_nd(array):
    import incubator_mxnet_tpu as mx

    return mx.nd.array(np.asarray(array), dtype=str(array.dtype))


def _system_pass(fn, where, mc):
    """The ONE program of set-up's passes through the system's forward:
    ``(params, router weights in float32, tokens, step) → {"logits", "selected"
    [layers, B, S, S] bool, "rows" routed to the held experts, "loads" [layers,
    E], "moved"}``, ``moved`` being the router weights after a sign step of
    size ``step`` down the balance term's gradient in them (``f_e`` detached,
    the layer's inputs held fixed).  The balancing passes read ``loads`` and
    ``moved``, the comparison with the reference the rest (at ``step`` 0): a
    second program for it would be 23 s of compile and 23 MB of cache a run."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.gluon.model_zoo import moe

    n_experts, d = mc["num_experts"], mc["hidden_size"]

    @jax.jit
    def run(params, weights, tok, step):
        params = list(params)
        for at, w in zip(where, weights):
            params[at] = w
        with moe.moe_loss_frame(taps=("selection", "router_input")) as frame:
            logits = fn(params, tok)
        loads = jnp.stack([m["expert_load_all"] for m in frame.metrics])   # [layers, E]
        moved = []
        for layer, w in enumerate(weights):
            h = frame.taps[layer]["router_input"].reshape(-1, d).astype(jnp.float32)
            share = loads[layer] / loads[layer].sum()

            def balance(w):
                return n_experts * jnp.sum(share * jax.nn.softmax(h @ w.T, axis=-1).mean(0))

            moved.append(w - step * jnp.sign(jax.grad(balance)(w)))
        return {"logits": logits.astype(jnp.float32), "loads": loads, "moved": moved,
                "selected": jnp.stack([tap["selection"] for tap in frame.taps]) != 0,
                "rows": moe.frame_metrics(frame)["rows_routed_here"]}

    return run


def _balance_routers(ctx, run, params, weights, tok1, mc, held):
    """A trained model of this family arrives with routers that its
    load-balance term (``router_aux_loss_coef · E · Σ_e f_e P̄_e``) has evened;
    random weights arrive with none of that, and route nearly every token of
    a layer to the same few experts (the hidden states of a random deep
    network are nearly parallel, and a tenth of a Zipf batch is ONE token):
    read on the chip, an expert of 8,187 of a layer's 8,192 tokens beside
    experts of none, so that whether this chip's 16 experts get a fifth of the
    pairs or a twentieth is the seed's luck, and the step's time with it.  A
    softmax router has no selection bias to move (the Xing and Nemotron
    set-ups run their ``noaux_tc`` rule); so the set-up descends the model's
    own balance term: forward pass by forward pass on the timed batch (a
    layer's routing moves the next layer's input), every router's weights
    take a sign step (what Adam's first steps are) down the term's gradient
    in them — the layer's inputs held fixed, ``f_e`` detached as the term has
    it — of a size that falls from ``BALANCE_STEPS[0] / hidden_size`` to
    ``BALANCE_STEPS[1] / hidden_size``.  At even loads the gradient is zero
    and the rule stops by itself.  The steps are taken in float32 (the last
    are below bf16's resolution); returned are the weights ROUNDED to the
    parameters' dtype, still as float32, which is what the net then holds.
    The training step goes on with the term at its own coefficient."""
    import jax.numpy as jnp

    t0 = time.perf_counter()
    first = None
    for i in range(BALANCE_PASSES):
        share = BALANCE_STEPS[0] * (BALANCE_STEPS[1] / BALANCE_STEPS[0]) ** (i / (BALANCE_PASSES - 1))
        out = run(params, weights, tok1, share / mc["hidden_size"])
        # read back every pass: an execution's results (1 GB of logits and
        # selections) are allocated when it is ENQUEUED, and the host would
        # run a dozen passes ahead (read: 15.9 GB in use for 4.8)
        loads = np.asarray(out["loads"])
        first = loads if first is None else first
        weights = out["moved"]
    weights = [w.astype(params[0].dtype).astype(jnp.float32) for w in weights]
    del out
    loads = np.asarray(run(params, weights, tok1, 0.0)["loads"])
    spread = lambda l: np.round(l.max(axis=1) / l.mean(axis=1), 3).tolist()
    here = lambda l: l[:, held[0]:held[0] + held[1]].sum(axis=1).astype(int).tolist()
    ctx.say(f"routers balanced in {BALANCE_PASSES} passes, {time.perf_counter() - t0:.1f} s: "
            f"greatest load over mean load by layer {spread(first)} -> {spread(loads)}; rows routed "
            f"here by layer {here(first)} -> {here(loads)}")
    return weights


def _reference_check(ctx, run, names, params, weights, mc, held, tok1, lab1):
    """The system's logits, the keys each layer selects and the rows routed
    to the held experts against the float32 reference's, all the tokens of
    the sequence."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    topk = min(mc["sa_config"]["topk"], tok1.shape[1])

    @jax.jit
    def agreement(ours, theirs):
        """By layer: over the rows that select, |S_t ∩ S_t^ref| / topk."""
        both = jnp.sum(ours[:, :, topk:] & theirs[:, :, topk:], axis=(1, 2, 3), dtype=jnp.float32)
        return both / (ours.shape[1] * max(ours.shape[2] - topk, 1) * topk)

    def reference_forward(dtype):
        run = jax.jit(functools.partial(reference.forward, config=mc, experts_held=held,
                                        with_terms=True, dtype=dtype))
        logits, terms = run(dict(zip(names, params)), tok1)
        return logits, terms["selections"], float(terms["rows_routed_here"])

    ours = run(params, weights, tok1, 0.0)
    ref_logits, ref_selected, ref_rows = reference_forward(jnp.float32)
    std = float(jnp.std(ref_logits))

    def report(who, logits, selected, rows):
        median, far, loss_diff = _compare(ctx, ref_logits, lab1, std, who, logits)
        agree = np.asarray(agreement(selected, ref_selected)).tolist()
        rows_diff = abs(float(rows) - ref_rows) / max(ref_rows, 1.0)
        ctx.say(f"{who}: selection agreement with the reference by layer "
                f"{[round(a, 5) for a in agree]} (lowest {min(agree):.5f}); rows routed here "
                f"{float(rows):.0f} against {ref_rows:.0f} (difference {100 * rows_diff:.3f} %)")
        return {"logits_match_reference": (median <= TOKEN_RMS_MEDIAN_TOLERANCE_STD_SHARE
                                           and far <= FAR_TOKENS_TOLERANCE),
                "loss_matches_reference": loss_diff <= LOSS_TOLERANCE,
                "selection_matches_reference": (tok1.shape[1] <= topk
                                                or min(agree) >= SELECTION_AGREEMENT_FLOOR),
                "rows_routed_match_reference": (
                    rows_diff * ref_rows <= max(ROWS_ROUTED_TOLERANCE * ref_rows, ROWS_ROUTED_SLACK))}

    checks = report("system", ours["logits"], ours["selected"], ours["rows"])
    if ctx.config.get("probe", {}).get("bf16_reference"):
        # a measurement probe, never in a committed configuration: what the
        # tolerances have to refuse
        refused = report("reference wholly in bf16", *reference_forward(jnp.bfloat16))
        ctx.say(f"the tolerances' verdict on the reference wholly in bf16: {refused}")
    ctx.say(f"reference check on {tok1.shape[1]} tokens took {time.perf_counter() - t0:.1f} s")
    return checks

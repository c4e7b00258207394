"""The check that decides ``correct`` in a causal-LM cell, read on the TIMED
program: set-up runs the trainer's compiled step once on the timed batch, and
holds its loss, the rows it routed here, the gradient it took and the change
it made to every parameter against the float32 reference's
``jax.value_and_grad`` on the same batch followed by the same Adam step.

A builder hands it its reference's ``forward(named, tok, *, dtype, with_rows,
remat)`` (logits and the rows routed to the experts held), its
``loss_per_token`` and its :class:`Limits`.  A configuration's ``"probe"``
(never in a committed file) reads what the limits lie between:
``bf16_reference`` puts the reference computed wholly in bfloat16 in the
trainer's place for the loss and the rows routed, so that the run's checks
come out false where a limit refuses the lower precision; ``planted`` reads
two planted faults beside the system's readings (a loss over half the
tokens; Adam without its bias correction).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time

import numpy as np

from ..reference.solar_open2 import adam_first_step
from .xing4_clm import _balance_selection_bias


@dataclasses.dataclass(frozen=True)
class Limits:
    """How far the trainer's first step may lie from the reference's."""
    loss: float          # the mean loss, apart
    rows: float          # the rows routed here, apart, as a share of the reference's ...
    rows_slack: int      # ... or this many rows, whichever is more
    gradient: float      # rms gradient difference ÷ the reference's rms, over all leaves
    gradient_leaf: float  # the same at the worst leaf
    change_leaf: float   # the weighted change difference (:func:`leaf_sums`) at the worst leaf


def set_up_readings(ctx, net, tok, labels, forward, loss_per_token):
    """The passes of set-up before the trainer exists, on the chip with one
    copy of the parameters: the balancing passes (the system's own forward)
    and the float32 reference's loss, rows routed and gradient on the timed
    batch, returned for :func:`check` (the gradient and the parameters on the
    host); and the probes."""
    import jax
    import jax.numpy as jnp

    dev = ctx.devices[0]
    fn, host_params = net.export_jittable()
    names = sorted(p.name for p in net.collect_params().values())
    params = jax.device_put(list(host_params), dev)
    tok, labels = (jax.device_put(a, dev) for a in (tok, labels))
    _balance_selection_bias(ctx, net, fn, names, params, tok)
    probe = ctx.config.get("probe", {})

    t0 = time.perf_counter()
    step = functools.partial(_reference_step, names, forward, loss_per_token)
    loss, rows, grads = step()(params, tok, labels)
    expected = {"loss": float(loss), "rows": float(rows),
                "grads": {n: np.asarray(g) for n, g in grads.items()},
                "weights": {n: np.asarray(a) for n, a in zip(names, params)}}
    del grads
    ctx.say(f"the float32 reference's step on {tok.size} tokens took "
            f"{time.perf_counter() - t0:.1f} s: loss {expected['loss']:.5f}, rows routed here "
            f"{expected['rows']:.0f}")
    if probe.get("planted"):
        grads = step(tokens=tok.shape[1] // 2)(params, tok, labels)[2]
        expected["grads_half"] = {n: np.asarray(g) for n, g in grads.items()}
        del grads
    if probe.get("bf16_reference"):
        logits, rows = jax.jit(functools.partial(forward, with_rows=True, dtype=jnp.bfloat16))(
            dict(zip(names, params)), tok)
        expected["control"] = {"loss": float(loss_per_token(logits, labels).mean()),
                               "rows": float(rows)}
    return expected


def _reference_step(names, forward, loss_per_token, tokens=None):
    """The float32 reference's loss, rows routed and gradient, jitted;
    ``tokens`` counts the loss over the first ``tokens`` tokens alone (a
    planted fault)."""
    import jax
    import jax.numpy as jnp

    def loss(named, tok, labels):
        logits, rows = forward(named, tok, with_rows=True, remat=True)
        upto = slice(None, tokens)
        return loss_per_token(logits[:, upto], labels[:, upto]).mean(), rows

    @jax.jit
    def step(params, tok, labels):
        named = {n: a.astype(jnp.float32) for n, a in zip(names, params)}
        (value, rows), grads = jax.value_and_grad(loss, has_aux=True)(named, tok, labels)
        return value, rows, grads

    return step


# the sums :func:`leaf_sums` returns, by name
SUMS = ("grad_diff", "grad_ref", "grad", "change_diff", "change_ref",
        "weighted_diff", "weighted_ref", "above_diff", "above_ref", "flipped", "moved",
        "half_diff", "half_ref", "unbiased_diff")


def leaf_sums(mean, w32, w0, g_ref, g_half, *, kept, adam, unbiased):
    """One leaf's sums of squares, by :data:`SUMS`.  The gradient ``g`` is
    read from Adam's first moment, ``m = kept · g`` after one step from zero;
    the change from the float32 master ``w32`` against ``adam(w0, g_ref)``.

    Adam's first step is nearly ``lr · sign(g)``, so an element whose gradient
    lies under the difference between the two gradients flips its sign and
    counts whole in the plain change difference (``change_diff``), however
    small it is.  The weighted form weighs each element by ``|g| + |g_ref|``:
    a step that changed nothing still reads 1 and Adam without its bias
    correction 2 and more.  ``above_*`` is the plain form over the elements
    whose ``|g_ref|`` exceeds three times the leaf's rms gradient difference,
    where nothing should flip; ``flipped``
    counts the elements whose change has the other sign than the
    reference's, of ``moved``.  ``g_half`` and ``unbiased`` (Adam's step
    without its bias correction, from ``g_ref``) are planted faults."""
    import jax.numpy as jnp

    f32 = jnp.float32
    g, w0 = mean.astype(f32) / kept, w0.astype(f32)
    change, want = w32.astype(f32) - w0, adam(w0, g_ref) - w0
    weight = jnp.abs(g) + jnp.abs(g_ref)
    above = jnp.abs(g_ref) > 3 * jnp.sqrt(jnp.mean(jnp.square(g - g_ref)))
    sq = lambda a, where=True: jnp.sum(jnp.where(where, jnp.square(a.astype(f32)), 0))
    return jnp.stack([
        sq(g - g_ref), sq(g_ref), sq(g), sq(change - want), sq(want),
        jnp.sum(weight * jnp.square(change - want)), jnp.sum(weight * jnp.square(want)),
        sq(change - want, above), sq(want, above),
        jnp.sum(change * want < 0).astype(f32), jnp.sum(want != 0).astype(f32),
        sq(g - g_half), sq(g_half), jnp.sum(weight * jnp.square(change - unbiased(g_ref)))])


def ratio(d, r):
    """``√(d / r)``: a difference's norm as a share of the reference's."""
    return math.sqrt(d / r) if r > 0 else (0.0 if d == 0 else math.inf)


def check(ctx, trainer, tok, labels, expected, limits):
    """The trainer's compiled step, run once on the timed batch as part of
    set-up (the driver's warm-up follows it), against ``expected``
    (:func:`set_up_readings`), by ``limits``: a name → verdict map."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu import profiler

    t0 = time.perf_counter()
    before = profiler.counters()["moe_rows_routed_here"]
    loss = float(np.asarray(trainer.step((tok,), labels)._data))
    trainer._drain_moe_extras()
    rows = profiler.counters()["moe_rows_routed_here"] - before
    ctx.say(f"the trainer's first step (compile included) took {time.perf_counter() - t0:.1f} s")

    opt, lr = trainer.optimizer, trainer.learning_rate()
    kept = np.float32(1) - np.float32(opt.beta1)
    adam = functools.partial(adam_first_step, learning_rate=lr, beta1=opt.beta1,
                             beta2=opt.beta2, epsilon=opt.epsilon)
    unbiased = lambda g: -lr * kept * g / (jnp.sqrt((1 - np.float32(opt.beta2)) * g * g)
                                           + opt.epsilon)
    sums = jax.jit(functools.partial(leaf_sums, kept=kept, adam=adam, unbiased=unbiased))
    planted = "grads_half" in expected

    t0 = time.perf_counter()
    by_leaf = {}
    for slot, j in enumerate(trainer._trainable_idx):
        name = trainer._params[j].name
        state = trainer._opt_states[slot]
        (mean, _), w32 = state if isinstance(state[0], tuple) else (state, trainer._param_arrays[j])
        g_ref = expected["grads"][name]
        by_leaf[name] = dict(zip(SUMS, np.asarray(
            sums(mean, w32, expected["weights"][name], g_ref,
                 expected["grads_half"][name] if planted else g_ref), np.float64)))
    total = {k: sum(v[k] for v in by_leaf.values()) for k in SUMS}
    share = lambda d, r: ({n: ratio(v[d], v[r]) for n, v in by_leaf.items()},
                          ratio(total[d], total[r]))
    grad, grad_all = share("grad_diff", "grad_ref")
    change, change_all = share("change_diff", "change_ref")
    weighted, weighted_all = share("weighted_diff", "weighted_ref")
    above, above_all = share("above_diff", "above_ref")
    flipped = {n: v["flipped"] / max(v["moved"], 1) for n, v in by_leaf.items()}
    worst = lambda m: ", ".join(f"{n} {m[n]:.4f}" for n in sorted(m, key=m.get, reverse=True)[:3])
    ctx.say(f"the first step against the reference ({len(by_leaf)} leaves, "
            f"{time.perf_counter() - t0:.1f} s): loss {loss:.5f} against {expected['loss']:.5f}; "
            f"rows routed here {rows} against {expected['rows']:.0f}; gradient norm "
            f"{math.sqrt(total['grad']):.6e} against {math.sqrt(total['grad_ref']):.6e}, difference "
            f"{grad_all:.4f} of it over all leaves, worst leaves {worst(grad)}; "
            f"change {change_all:.4f} over all leaves, worst leaves {worst(change)}; "
            f"of the elements with |g_ref| over 3 x the leaf's gradient difference {above_all:.4f}, "
            f"worst leaves {worst(above)}; signs flipped "
            f"{total['flipped'] / max(total['moved'], 1):.5f} of the elements, worst leaves "
            f"{worst(flipped)}; weighted change {weighted_all:.4f} over all leaves, worst leaves "
            f"{worst(weighted)}")
    if planted:
        half = {n: ratio(v["half_diff"], v["half_ref"]) for n, v in by_leaf.items()}
        biased = {n: ratio(v["unbiased_diff"], v["weighted_ref"]) for n, v in by_leaf.items()
                  if v["weighted_ref"] > 0}
        ctx.say(f"planted faults: the gradient against a loss over half the tokens "
                f"{ratio(total['half_diff'], total['half_ref']):.4f} over all leaves, worst leaf "
                f"{max(half.values()):.4f}, least {min(half.values()):.4f}; the weighted change "
                f"against Adam without bias correction, worst {max(biased.values()):.4f}, "
                f"least {min(biased.values()):.4f}")
    if "control" in expected:
        loss, rows = expected["control"]["loss"], expected["control"]["rows"]
        ctx.say(f"probe: the reference wholly in bf16 stands in for the trainer's loss and rows: "
                f"loss {abs(loss - expected['loss']):.5f} apart, rows routed here {rows:.0f} "
                f"({100 * abs(rows - expected['rows']) / expected['rows']:.3f} % apart)")
    rows_ref = expected["rows"]
    return {"loss_matches_reference": abs(loss - expected["loss"]) <= limits.loss,
            "rows_routed_match_reference": (
                abs(rows - rows_ref) <= max(limits.rows * rows_ref, limits.rows_slack)),
            "gradient_matches_reference": grad_all <= limits.gradient,
            "gradient_leaves_match_reference": max(grad.values()) <= limits.gradient_leaf,
            "update_matches_reference": max(weighted.values()) <= limits.change_leaf}

"""Mixture-of-Experts dispatch/combine kernels (the 'ep' mesh axis payload).

Two registered ops, one family:

:func:`moe_ffn` (the CAPACITY path; ``gluon.model_zoo.moe.MoEBlock`` takes
it) computes a full top-k-routed expert FFN layer: router logits → top-k
softmax gates → capacity-limited einsum dispatch → per-expert two-layer FFN
→ weighted combine.  The dispatch is the Mesh-TF/Switch formulation — dense
one-hot [tokens, experts, capacity] tensors instead of gather/scatter —
because it is pure MXU work, shards over 'ep' on the stacked expert dim with
zero custom collectives (XLA derives the all-to-alls from the shardings),
and its drop rule is exact and deterministic: slots are granted in (choice
rank, token position) order by a cumsum, so token t's first choice always
beats token t+1's first choice, which beats every second choice.  "Pure MXU
work" has a price that grows with T·E·C: the two dispatch/combine einsums
are 2 · 2·T·E·C·d FLOPs, which at T 4096, E 64, k 4, C 320 and d 3584 is
1.2 TFLOP a layer, 27 times the 0.045 TFLOP of the expert matmuls that one
chip holding 8 of the 64 experts needs — and overflow is DROPPED.  It is
the path for few experts and short batches.

:func:`moe_ffn_dropless` (``gluon.model_zoo.xing4`` takes it) drops nothing:
sigmoid scores with a selection bias, the (token, choice) pairs sorted by
expert, grouped matrix products over the experts this chip HOLDS (the Pallas
kernels of ``ops/grouped_matmul.py`` on a TPU, ``jax.lax.ragged_dot``
elsewhere: :func:`_grouped_path`), a weighted combine.  Its cost follows the
rows routed here.

Static knobs (``num_experts``/``top_k``/``capacity_factor``...) arrive as
kwargs → part of the dispatch-cache/compile signature; capacity derives
from the static token count, so a fixed batch shape never recompiles.

:func:`moe_ffn` returns ``(y, aux_loss, z_loss, tokens_dropped, load_min,
load_max)`` — losses raw (callers weight them), metrics ``stop_gradient``-ed
float32 so the tuple is vjp-safe end to end.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .registry import register

__all__ = ["moe_ffn", "moe_capacity", "moe_ffn_dropless", "dropless_row_buckets"]


def moe_capacity(n_tokens, num_experts, top_k, capacity_factor):
    """Per-expert slot budget: ``ceil(T·k/E · capacity_factor)``, clipped
    to [1, T].  Static — shapes and knobs only."""
    cap = math.ceil(n_tokens * top_k / num_experts * capacity_factor)
    return max(1, min(int(cap), int(n_tokens)))


@register("moe_ffn")
def moe_ffn(x, router_w, w1, b1, w2, b2, num_experts=1, top_k=1,
            capacity_factor=1.25, activation="relu"):
    """Top-k routed expert FFN over the last axis of ``x``.

    Shapes: ``x`` [..., d]; ``router_w`` [d, E]; ``w1`` [E, d, h];
    ``b1`` [E, h]; ``w2`` [E, h, d]; ``b2`` [E, d].  Router math runs in
    float32 regardless of ``x.dtype`` (gate ordering must not flip with
    an AMP cast); expert GEMMs run in ``x.dtype``.
    """
    E, k = int(num_experts), int(top_k)
    d = x.shape[-1]
    xt = x.reshape(-1, d)
    T = xt.shape[0]
    C = moe_capacity(T, E, k, capacity_factor)

    logits = xt.astype(jnp.float32) @ router_w.astype(jnp.float32)  # [T, E]
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, k)                        # [T, k]
    em = jax.nn.one_hot(idx, E, dtype=jnp.float32)                  # [T, k, E]

    # slot grant order: choice-rank major, token order minor — the cumsum
    # over the [k·T, E] layout IS the priority rule (deterministic drops)
    em_flat = em.transpose(1, 0, 2).reshape(k * T, E)
    pos_flat = jnp.cumsum(em_flat, axis=0) - em_flat
    pos = pos_flat.reshape(k, T, E).transpose(1, 0, 2)              # [T, k, E]
    pos_tk = jnp.sum(pos * em, axis=-1)                             # [T, k]
    kept = (pos_tk < C).astype(jnp.float32)                         # [T, k]

    gates = gate_vals.astype(jnp.float32) * kept
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    disp = em * kept[..., None]                                     # [T, k, E]
    oh_pos = jax.nn.one_hot(pos_tk.astype(jnp.int32), C,
                            dtype=jnp.float32) * kept[..., None]    # [T, k, C]
    dispatch = jnp.einsum("tke,tkc->tec", disp, oh_pos)             # [T, E, C]
    combine = jnp.einsum("tke,tkc,tk->tec", disp, oh_pos, gates)

    cdt = x.dtype
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(cdt), xt)
    h = jnp.einsum("ecd,edh->ech", expert_in, w1) + b1[:, None, :]
    if activation:
        from .nn import _ACTS

        h = _ACTS[activation](h)
    out_e = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None, :]
    y = jnp.einsum("tec,ecd->td", combine.astype(cdt), out_e)
    y = y.reshape(x.shape)

    # Switch-style load-balance loss: fraction routed × mean router prob,
    # summed over experts, scaled by E (uniform routing → 1.0)
    f = em.sum(axis=(0, 1)) / float(T * k)
    p_mean = probs.mean(axis=0)
    aux_loss = float(E) * jnp.sum(f * p_mean)

    sg = jax.lax.stop_gradient
    load = dispatch.sum(axis=(0, 2))                                # [E]
    tokens_dropped = sg(float(k * T) - kept.sum())
    return (y, aux_loss, z_loss, tokens_dropped,
            sg(load.min()), sg(load.max()))


# ---------------------------------------------------------------------------
# The dropless path
# ---------------------------------------------------------------------------


def dropless_row_buckets(n_pairs, count, num_experts):
    """The static row counts the dropless layer is compiled for.  Shapes are
    static and routing is not, so the work on the sorted rows (gather,
    grouped products, combine) is compiled at a few sizes and the step takes
    the smallest that holds the rows routed here: a thirty-second of the
    expected share T·k·count/E (hardly any row is ours), 1.5 × and 3 × the
    expected share, and every pair (the worst case: nothing is dropped).  A
    bucket of at least the grouped-product kernels' smallest row tile
    (``grouped_matmul.ROW_TILES``) is a whole number of those tiles, so that
    the kernels take it (320 experts at 8 a token over 4,096 tokens expect 819
    rows, 1.5 × is 1,229); a smaller one a whole number of 8."""
    from .grouped_matmul import ROW_TILES

    tile = min(ROW_TILES)
    expected = max(1, n_pairs * count // num_experts)
    whole = lambda r: -(-r // tile) * tile if r >= tile else max(8, -(-r // 8) * 8)
    sizes = {min(n_pairs, whole(r))
             for r in (expected // 32, expected * 3 // 2, expected * 3)}
    return sorted(sizes | {n_pairs})


def _grouped_path(x):
    """Which way the held experts' grouped products of one layer go, from
    what the call can observe: ``"pallas"`` (the kernels of
    ``ops/grouped_matmul.py``) on a TPU, ``"xla"`` (``jax.lax.ragged_dot``)
    off it, for a dtype Mosaic has not, and on a mesh that splits anything
    outside a ``shard_map`` — no compiler partitions a Mosaic kernel.  One
    count a traced call site, as ``attention_dispatch_*`` are."""
    from .. import profiler
    from ..parallel.mesh import current_mesh
    from ..util import resolve_platform
    from .attention import _axis_bound

    mesh = current_mesh()
    split = [] if mesh is None else [a for a in mesh.axis_names
                                     if mesh.shape[a] > 1]
    if (resolve_platform(x) == "tpu" and x.dtype in (jnp.bfloat16, jnp.float32)
            and all(_axis_bound(a) for a in split)):
        profiler.incr("moe_grouped_dispatch_pallas")
        return "pallas"
    profiler.incr("moe_grouped_dispatch_xla")
    return "xla"


def _count_combines(n_pairs, buckets, top_k):
    """One count a row bucket of a traced call site: ``moe_combine_gather``
    where the bucket moves its rows by gathers (:func:`_gathers`),
    ``moe_combine_scatter`` where by scatter-adds."""
    from .. import profiler

    for rows in buckets:
        profiler.incr("moe_combine_gather" if _gathers(n_pairs, rows, top_k)
                      else "moe_combine_scatter")


def _path_in(buckets, rows, path):
    """``path`` in the bucket of ``rows`` rows — but ``"xla"`` in the bucket of
    EVERY pair where that is beyond three times the expected share (all four
    of :func:`dropless_row_buckets`' sizes exist): the worst case the layer
    has to be compiled for and a routed deployment does not run.  A bucket's
    six kernels are traced and lowered at every start of the program, compile
    cache or not: ~1.3 s of set-up a bucket on the chip's host (PERF.md §6 PR
    33.5), spent where it buys steps."""
    return "xla" if len(buckets) == 4 and rows == buckets[-1] else path


# A bucket moves its rows between token order and sorted-row order by gathers
# through the inverse of the expert sort where the T·k pairs are at most
# GATHER_RATIO times its rows and a token has at most GATHER_TOP_K choices,
# and by scatter-adds of its rows otherwise: a gather reads every pair, one
# [T, d] pass a choice, a scatter-add serialises the bucket's rows.  On the
# v5e the gathers win alone at 5.3 pairs a row in every decoder cell and lose
# at 10.7 and above (the combine and the input gradient timed alone); in the
# training step they won at 4 choices a token and lost at 8 (PERF.md §5, §6).
GATHER_RATIO = 8
GATHER_TOP_K = 4


def _gathers(n_pairs, rows, top_k):
    """Whether a bucket of ``rows`` rows over ``n_pairs`` pairs, ``top_k`` a
    token, gathers."""
    return top_k <= GATHER_TOP_K and n_pairs <= GATHER_RATIO * rows


def _sum_of_pairs(values, inv, n_here, top_k, gate=None):
    """``y[t] = Σ_j [inv[p] < n_here] · f32(values[inv[p]]) · gate[p]``, ``p =
    t·k + j``: each token's held rows summed by gathers through the inverse of
    the expert sort, ``[T, d]`` float32.  A pair held elsewhere reads row 0 and
    is selected away, so whatever the rows past ``n_here`` hold stays out.  One
    ``[T, d]`` gather a choice ``j``: a ``[T·k, d]`` gather summed over ``k``
    lays ``k`` on the tile's sublanes, 3 × slower on the v5e at ``k`` 4.  The
    rows are selected before the cast: cast first, the v5e's compiler wrote
    each choice's float32 ``[T, d]`` out alone at the lfm2 cell's shape."""
    held = (inv < n_here).reshape(-1, top_k)
    at = jnp.where(held, inv.reshape(-1, top_k), 0)

    def choice(j):
        got = jnp.where(held[:, j, None], values[at[:, j]], 0).astype(jnp.float32)
        return got if gate is None else got * gate.reshape(-1, top_k)[:, j, None]
    return sum(map(choice, range(1, top_k)), choice(0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _token_rows(xt, token, inv, n_here, top_k):
    """``xt[token]``, whose transpose is :func:`_sum_of_pairs` (float32, cast
    to ``xt``'s dtype) and not autodiff's scatter-add."""
    return xt[token]


def _token_rows_fwd(xt, token, inv, n_here, top_k):
    return xt[token], (token, inv, n_here)


def _token_rows_bwd(top_k, res, da):
    from .nn import _zero_cotangent

    _, inv, n_here = res
    return ((_sum_of_pairs(da, inv, n_here, top_k).astype(da.dtype),)
            + tuple(_zero_cotangent(a) for a in res))


_token_rows.defvjp(_token_rows_fwd, _token_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _combine(ys, gate_flat, pair, inv, n_here, top_k):
    """The rows summed into their tokens with their gates,
    :func:`_sum_of_pairs`, the transpose of :func:`_token_rows`.  Its
    derivative gathers too: ``d ys[r] = ct[token[r]] · gate[pair[r]]``, ``d
    gate[p] = ⟨ct[t], f32(ys[inv[p]])⟩`` for the held pairs, the dot formed on
    the rows and gathered through ``inv``."""
    return _sum_of_pairs(ys, inv, n_here, top_k, gate_flat)


def _combine_fwd(ys, gate_flat, pair, inv, n_here, top_k):
    return _combine(ys, gate_flat, pair, inv, n_here, top_k), (ys, gate_flat, pair, inv,
                                                                n_here)


def _combine_bwd(top_k, res, ct):
    from .nn import _zero_cotangent

    ys, gate_flat, pair, inv, n_here = res
    ct_rows = ct[pair // top_k]                                   # [rows, d] float32
    d_ys = (ct_rows * gate_flat[pair][:, None]).astype(ys.dtype)
    dots = jnp.sum(ct_rows * ys.astype(jnp.float32), axis=-1)     # [rows]
    held = inv < n_here
    d_gate = jnp.where(held, dots[jnp.where(held, inv, 0)], 0)
    return (d_ys, d_gate) + tuple(_zero_cotangent(a) for a in (pair, inv, n_here))


_combine.defvjp(_combine_fwd, _combine_bwd)


def _experts_on_rows(rows, xt, order, gate_flat, group_sizes, n_here,
                     w_in, w_down, top_k, form, path="xla"):
    """The held experts' inner function (``form``: SwiGLU on a ``gate | up``
    weight, or the non-gated ``relu(·)²`` on an ``up`` weight) on the first
    ``rows`` sorted pairs, summed into their tokens: ``[T, d]`` float32.
    Pairs past ``n_here`` belong to experts held elsewhere: they add nothing
    (and neither grouped product defines their rows, so they are masked, not
    trusted).  Where :func:`_gathers` says so, the rows reach their tokens,
    and the tokens' gradient leaves the rows, by gathers through the inverse
    of the sort, ``inv[order[r]] = r`` (:func:`_combine`,
    :func:`_token_rows`), formed here so that a bucket that keeps the
    scatter-adds of its rows into ``[T, d]`` does not pay for it.  ``path``:
    :func:`_grouped_path`'s, or ``"interpret"`` (the kernels in the Pallas
    interpreter: the CPU tests ask for it here); a row count no kernel tile
    divides (the smallest bucket) stays on XLA."""
    from .grouped_matmul import grouped_dot, row_tile

    cdt = xt.dtype
    kernels = path != "xla" and row_tile(rows) is not None
    gathers = _gathers(order.shape[0], rows, top_k)
    pair = order[:rows]
    token = pair // top_k
    ours = (jnp.arange(rows) < n_here)[:, None]
    # named, not None: the package's global default is 'highest', which the
    # TPU's grouped-product kernel refuses for bf16 operands
    prec = (jax.lax.Precision.HIGHEST if cdt == jnp.float32
            else jax.lax.Precision.DEFAULT)

    def grouped(a, w):
        """The grouped product with the rows past the last group zero on both
        sides: on the TPU ``ragged_dot`` and the kernels leave them UNDEFINED,
        in the result and (through the transpose) in the cotangent of ``a``;
        masking the operand masks that cotangent before it goes back to real
        tokens, and masking the result keeps whatever lay there (NaN bit
        patterns included) out of everything downstream and its derivative."""
        a = jnp.where(ours, a, 0)
        if kernels:
            out = grouped_dot(a, w.astype(cdt), group_sizes, path == "interpret")
        else:
            out = jax.lax.ragged_dot(a, w.astype(cdt), group_sizes, precision=prec)
        return jnp.where(ours, out, 0)

    if gathers:
        inv = jnp.argsort(order).astype(order.dtype)
        a = _token_rows(xt, token, inv, n_here, top_k)
    else:
        a = xt[token]
    up = grouped(a, w_in)                            # [rows, 2h] or [rows, h]
    if form == "swiglu":
        h = w_down.shape[1]
        act = (jax.nn.silu(up[:, :h]) * up[:, h:]).astype(cdt)
    elif form == "relu2":
        act = jnp.square(jax.nn.relu(up.astype(jnp.float32))).astype(cdt)
    else:
        raise ValueError(f"expert_form {form!r}: 'swiglu' or 'relu2'")
    ys = grouped(act, w_down)                                  # [rows, d]
    if gathers:
        return _combine(ys, gate_flat, pair, inv, n_here, top_k)
    ys = ys.astype(jnp.float32) * gate_flat[pair][:, None]
    return jnp.zeros(xt.shape, jnp.float32).at[token].add(ys)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _experts_in_bucket(buckets, top_k, form, path, xt, gate_flat, w_in, w_down,
                       order, group_sizes, n_here):
    """:func:`_experts_on_rows` at the smallest of ``buckets`` that holds
    ``n_here`` rows, each bucket moving its rows by gathers or by
    scatter-adds as its static shape says (:func:`_gathers`).  The
    derivative is its own rule and not that of ``lax.switch``: branches of
    different row counts keep intermediates of
    different shapes, and differentiating the switch makes EVERY branch write
    (zeros for) every other branch's, the weights included — 1.9 ms a pass
    at 64 rows on the v5e.  So the forward keeps only what it was given, and
    the backward switches again and runs the chosen branch's forward and
    derivative together: one more forward of the held experts' products."""
    branches = [functools.partial(_experts_on_rows, rows, top_k=top_k, form=form,
                                  path=_path_in(buckets, rows, path))
                for rows in buckets]
    return jax.lax.switch(_bucket_of(buckets, n_here), branches, xt, order,
                          gate_flat, group_sizes, n_here, w_in, w_down)


def _bucket_of(buckets, n_here):
    return jnp.searchsorted(jnp.asarray(buckets, jnp.int32), n_here)


def _experts_in_bucket_fwd(buckets, top_k, form, path, *args):
    return _experts_in_bucket(buckets, top_k, form, path, *args), args


def _experts_in_bucket_bwd(buckets, top_k, form, path, args, ct):
    from .nn import _zero_cotangent

    xt, gate_flat, w_in, w_down, order, group_sizes, n_here = args

    def branch(rows):
        def run(xt, gate_flat, w_in, w_down, ct):
            _, pullback = jax.vjp(
                lambda xt, gate_flat, w_in, w_down: _experts_on_rows(
                    rows, xt, order, gate_flat, group_sizes, n_here,
                    w_in, w_down, top_k, form, _path_in(buckets, rows, path)),
                xt, gate_flat, w_in, w_down)
            return pullback(ct)
        return run

    grads = jax.lax.switch(_bucket_of(buckets, n_here),
                           [branch(rows) for rows in buckets],
                           xt, gate_flat, w_in, w_down, ct)
    return tuple(grads) + tuple(
        _zero_cotangent(a) for a in (order, group_sizes, n_here))


_experts_in_bucket.defvjp(_experts_in_bucket_fwd, _experts_in_bucket_bwd)


@register("moe_ffn_dropless")
def moe_ffn_dropless(x, router_w, select_bias, w_in, w_down,
                     num_experts=1, top_k=1, first_expert=0,
                     routed_scaling=1.0, norm_topk=True, scope="moe",
                     expert_form="swiglu", scoring="sigmoid"):
    """Dropless top-k routed experts over the last axis of ``x``: the part of
    the layer's sum that the experts HELD HERE give.

    Shapes: ``x`` [..., d]; ``router_w`` [E, d] over ALL ``num_experts``;
    ``select_bias`` [E] (the ``noaux_tc`` selection bias: it moves the
    choice, never the gate, and carries no gradient); for the experts
    ``first_expert .. first_expert + count - 1``, ``w_in`` [count, d, 2·h]
    (gate | up) under ``expert_form`` ``"swiglu"``, ``W_down (silu(W_gate x)
    ⊙ W_up x)``, or [count, d, h] under ``"relu2"``, the non-gated ``W_down
    relu(W_up x)²``; ``w_down`` [count, h, d].  The form is a static choice
    of the inner function only: router, selection bias, sort, row buckets,
    grouped products, masking and combine are one code.

    ``scoring`` is a static choice of the router's scores: ``"sigmoid"``,
    ``s = sigmoid(W_r x)`` in float32, the ``top_k`` largest ``s + bias``; or
    ``"softmax"``, ``s = softmax(W_r x)`` over ALL the experts, the ``top_k``
    largest ``s`` (no bias: ``select_bias`` is not read).  Either way the
    gates are ``routed_scaling · s_i / Σ_selected s_j`` (``norm_topk``) — over
    all selected experts, held here or not, so the shares of a layer add up
    to the whole layer.  Pairs routed to experts held elsewhere add nothing.
    No capacity, no token dropped.

    Returns ``(y, rows_routed_here, load_min, load_max, load_all)`` and,
    under ``"softmax"`` scoring, a sixth: the Switch load-balance term ``E ·
    Σ_e f_e P̄_e`` over ALL the experts (``f_e`` the share of the pairs routed
    to ``e``, no gradient; ``P̄_e`` the mean score; 1 under even routing),
    raw: the caller weights it.  The metrics are
    ``stop_gradient``-ed float32: three scalars (loads over the
    experts held) and the pairs routed to each of ALL the experts, ``[E]``,
    which is what the ``noaux_tc`` balancing rule moves the bias by.
    ``scope`` names the ``jax.named_scope``s ``<scope>.route`` (scores,
    choice, sort) and ``<scope>.experts`` (gather, grouped products, combine).
    """
    E, k, first = int(num_experts), int(top_k), int(first_expert)
    count, d = w_in.shape[0], x.shape[-1]
    xt = x.reshape(-1, d)
    T = xt.shape[0]
    sg = jax.lax.stop_gradient

    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"scoring {scoring!r}: 'sigmoid' or 'softmax'")
    with jax.named_scope(scope + ".route"):
        logits = jnp.einsum(
            "td,ed->te", xt.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)               # [T, E]
        if scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            _, idx = jax.lax.top_k(
                sg(scores + select_bias.astype(jnp.float32)), k)
        else:
            scores = jax.nn.softmax(logits, axis=-1)
            _, idx = jax.lax.top_k(sg(scores), k)
        chosen = jnp.take_along_axis(scores, idx, axis=-1)     # [T, k]
        gates = chosen * float(routed_scaling)
        if norm_topk:
            gates = gates / (chosen.sum(-1, keepdims=True) + 1e-20)
        # sort the (token, choice) pairs by expert, ours first
        local = idx.reshape(-1) - first
        key = jnp.where((local >= 0) & (local < count), local, count)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)  # [T·k]
        load_all = jnp.zeros((E,), jnp.int32).at[idx.reshape(-1)].add(1)
        group_sizes = load_all[first:first + count]
        n_here = group_sizes.sum()

    with jax.named_scope(scope + ".experts"):
        buckets = tuple(dropless_row_buckets(T * k, count, E))
        _count_combines(T * k, buckets, k)
        y = _experts_in_bucket(buckets, k, expert_form, _grouped_path(xt), xt,
                               gates.reshape(-1), w_in, w_down, order,
                               group_sizes, n_here)
        y = y.astype(x.dtype).reshape(x.shape)
    load = group_sizes.astype(jnp.float32)
    out = (y, sg(n_here.astype(jnp.float32)), sg(load.min()), sg(load.max()),
           sg(load_all.astype(jnp.float32)))
    if scoring == "softmax":
        with jax.named_scope(scope + ".route"):
            share = sg(load_all.astype(jnp.float32)) / float(T * k)
            out += (float(E) * jnp.sum(share * scores.mean(axis=0)),)
    return out

"""Mixture-of-Experts layers — the 'ep' mesh axis tier.

:class:`MoEBlock` is a drop-in replacement for a transformer FFN sublayer
(``nn.PositionwiseFFN``): a router picks ``top_k`` of ``num_experts``
expert FFNs per token, tokens are dispatched under a per-expert capacity
with deterministic overflow drops, and outputs combine gate-weighted.
The expert weights are SINGLE stacked parameters with a leading
``num_experts`` dim — :func:`moe_sharding_rules` shards exactly that dim
over 'ep', so expert parallelism is one ``ShardingRules`` entry and XLA
derives the token all-to-alls from the annotations (no bespoke comm
path, matching the repo's SPMD design).  The math lives in the
registered :func:`ops.moe.moe_ffn` kernel, so eager autograd, hybridize
and the SPMD trace all share one implementation.

Auxiliary losses (Switch-style load balancing + router z-loss) must
reach the training loss *inside* the compiled step.  The frame protocol
here does that: ``SPMDTrainer`` (and any custom step) opens a
:func:`moe_loss_frame` around the forward; every MoE layer registers its
weighted losses and routing metrics into the innermost frame, and the
trainer folds :func:`frame_loss` into the scalar it differentiates and
ships :func:`frame_metrics` out of the program for the
``moe_tokens_dropped`` counter / expert-load gauges.  With no frame open
(plain eager training) the layer stashes its last weighted loss on
``self`` — add ``block.aux_loss()`` to the loss before ``backward()``.
"""
from __future__ import annotations

import threading as _threading

from ..block import HybridBlock
from ...parallel.schedule import in_backward_trace

__all__ = [
    "MoEBlock",
    "moe_sharding_rules",
    "moe_loss_frame",
    "frame_loss",
    "frame_metrics",
    "frame_counters",
    "register_metrics",
    "register_side",
    "tapped",
]

_tls = _threading.local()


def _frames():
    st = getattr(_tls, "frames", None)
    if st is None:
        st = _tls.frames = []
    return st


class moe_loss_frame:
    """``with moe_loss_frame() as frame:`` — collect every MoE layer's
    weighted aux losses and routing metrics traced inside the scope.
    ``taps`` names intermediate arrays the caller wants to look at (a
    comparison with a reference: ``("selection",)`` is each sparse-attention
    layer's selected keys): a layer that has one hands it over only when it
    is asked for (:func:`tapped`), into ``frame.taps``, a dict a layer."""

    def __init__(self, taps=()):
        self.losses = []     # weighted scalar losses (traced values)
        self.metrics = []    # dicts of traced metric scalars
        self.counters = []   # dicts: profiler counter name → traced scalar
        self.wanted = frozenset(taps)
        self.taps = []       # dicts: tap name → traced array, a layer

    def __enter__(self):
        _frames().append(self)
        return self

    def __exit__(self, *exc):
        _frames().pop()
        return False


def _register(loss, metrics):
    if in_backward_trace():
        # a remat stage's backward slot re-traces the forward; its values
        # belong to the checkpoint primitive's inner scope — collecting
        # them would both double-count and leak inner tracers
        return False
    st = _frames()
    if not st:
        return False
    st[-1].losses.append(loss)
    st[-1].metrics.append(metrics)
    return True


def register_metrics(metrics):
    """Hand a layer's routing metrics (``tokens_dropped``,
    ``expert_load_min``, ``expert_load_max`` and, from a layer that holds a
    share of its experts, ``rows_routed_here``) to the innermost frame; a
    layer with no auxiliary loss registers nothing else.  Call it from the
    step's own trace: a value traced inside ``jax.checkpoint`` has to be
    returned out of it first."""
    st = _frames()
    if not st or in_backward_trace():
        return False
    st[-1].metrics.append(metrics)
    return True


def tapped(name):
    """Whether the innermost frame asked for the intermediate ``name``."""
    st = _frames()
    return bool(st) and name in st[-1].wanted


def register_side(loss=None, counters=None, taps=None):
    """Hand the innermost frame what a layer adds to the step beside its
    output: ``loss``, a weighted scalar that joins the differentiated loss (a
    router's balance term, an indexer's own loss), and ``counters``, a map
    from a DECLARED profiler counter's name to a traced scalar, which the
    trainer sums over the layers, ships out of the program and adds to that
    counter a step (``sparse_attn_tiles_live``); ``taps``, the intermediates
    the frame asked for (:func:`tapped`).  The same rule as
    :func:`register_metrics`: from the step's own trace."""
    st = _frames()
    if not st or in_backward_trace():
        return False
    if loss is not None:
        st[-1].losses.append(loss)
    if counters:
        st[-1].counters.append(dict(counters))
    if taps:
        st[-1].taps.append(dict(taps))
    return True


def frame_counters(frame):
    """The frame's counters summed by name over its layers: a name → traced
    scalar map (empty when no layer registered any)."""
    out = {}
    for layer in frame.counters:
        for name, value in layer.items():
            out[name] = out[name] + value if name in out else value
    return out


def frame_loss(frame):
    """Sum of the frame's weighted aux losses (None when no MoE ran)."""
    if not frame.losses:
        return None
    total = frame.losses[0]
    for l in frame.losses[1:]:
        total = total + l
    return total


def frame_metrics(frame):
    """Combined routing metrics across the frame's layers: summed drops
    and slots, min/max expert load over every layer.  Values are traced
    scalars — return them from the compiled step, then read on host."""
    if not frame.metrics:
        return None
    out = {
        "tokens_dropped": frame.metrics[0]["tokens_dropped"],
        "expert_load_min": frame.metrics[0]["expert_load_min"],
        "expert_load_max": frame.metrics[0]["expert_load_max"],
    }
    for m in frame.metrics[1:]:
        out["tokens_dropped"] = out["tokens_dropped"] + m["tokens_dropped"]
        mn, mx = m["expert_load_min"], m["expert_load_max"]
        out["expert_load_min"] = 0.5 * (
            out["expert_load_min"] + mn - abs(out["expert_load_min"] - mn))
        out["expert_load_max"] = 0.5 * (
            out["expert_load_max"] + mx + abs(out["expert_load_max"] - mx))
    rows = [m["rows_routed_here"] for m in frame.metrics
            if "rows_routed_here" in m]
    if rows:   # only layers that hold a share of their experts report it
        out["rows_routed_here"] = sum(rows[1:], rows[0])
    return out


def moe_sharding_rules(base=None):
    """Prepend expert-parallel placement to a rule table: the stacked
    expert dim (axis 0 of ``experts_*``) shards over 'ep', the router
    stays replicated.  ``base`` rules (tp/fsdp) apply to everything
    else."""
    from ...parallel.sharding import ShardingRules
    from jax.sharding import PartitionSpec as P

    rules = ShardingRules([
        (r"experts_.*weight$", P("ep", None, None)),
        (r"experts_.*bias$", P("ep", None)),
        (r"router_weight$", P(None, None)),
    ], default=base.default if base is not None else P())
    if base is not None:
        for pat, spec in base:   # ShardingRules is iterable; add()
            rules.add(pat, spec)  # accepts compiled patterns
    return rules


class MoEBlock(HybridBlock):
    """Top-k routed mixture-of-experts FFN: [..., units] → [..., units].

    Takes the CAPACITY path (``ops.moe.moe_ffn``): softmax gates, dense
    one-hot dispatch, overflow dropped and counted.  The dropless path
    (``ops.moe.moe_ffn_dropless``: sigmoid scores, sorted pairs, grouped
    products over the experts a chip holds) is ``model_zoo.xing4
    .SparseExperts``'.

    Parameters
    ----------
    units : int
        Token feature dim (input and output).
    hidden_size : int
        Per-expert FFN hidden dim.
    num_experts : int
    top_k : int, default 2
    capacity_factor : float, default 1.25
        Per-expert slots = ceil(T·k/E · capacity_factor); overflow tokens
        are dropped deterministically (choice-rank then token order) and
        counted.
    aux_loss_weight / z_loss_weight : float
        Weights on the load-balancing loss (Switch: E·Σ f·P̄) and router
        z-loss (mean logsumexp²); the WEIGHTED sum is what reaches the
        frame / ``aux_loss()``.
    """

    def __init__(self, units, hidden_size, num_experts, top_k=2,
                 capacity_factor=1.25, aux_loss_weight=1e-2,
                 z_loss_weight=1e-3, activation="relu", dtype="float32",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if top_k > num_experts:
            raise ValueError(f"top_k {top_k} > num_experts {num_experts}")
        self._units = int(units)
        self._hidden = int(hidden_size)
        self._num_experts = int(num_experts)
        self._top_k = int(top_k)
        self._capacity_factor = float(capacity_factor)
        self._aux_w = float(aux_loss_weight)
        self._z_w = float(z_loss_weight)
        self._activation = activation
        self._last_aux = None
        E, d, h = self._num_experts, self._units, self._hidden
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(d, E), dtype="float32")
            self.experts_mlp1_weight = self.params.get(
                "experts_mlp1_weight", shape=(E, d, h), dtype=dtype)
            self.experts_mlp1_bias = self.params.get(
                "experts_mlp1_bias", shape=(E, h), dtype=dtype, init="zeros")
            self.experts_mlp2_weight = self.params.get(
                "experts_mlp2_weight", shape=(E, h, d), dtype=dtype)
            self.experts_mlp2_bias = self.params.get(
                "experts_mlp2_bias", shape=(E, d), dtype=dtype, init="zeros")

    def hybrid_forward(self, F, x, router_weight, experts_mlp1_weight,
                       experts_mlp1_bias, experts_mlp2_weight,
                       experts_mlp2_bias):
        outs = F.contrib.moe_ffn(
            x, router_weight, experts_mlp1_weight, experts_mlp1_bias,
            experts_mlp2_weight, experts_mlp2_bias,
            num_experts=self._num_experts, top_k=self._top_k,
            capacity_factor=self._capacity_factor,
            activation=self._activation)
        y, aux, z, dropped, load_min, load_max = outs
        weighted = aux * self._aux_w + z * self._z_w

        def _raw(v):
            return v._data if hasattr(v, "_data") else v

        registered = _register(weighted, {
            "tokens_dropped": _raw(dropped),
            "expert_load_min": _raw(load_min),
            "expert_load_max": _raw(load_max),
        })
        if not registered and not in_backward_trace():
            import jax as _jax

            if not isinstance(_raw(weighted), _jax.core.Tracer):
                # eager path: stash for block.aux_loss().  A frameless
                # TRACED forward (hybridize's cached-graph build, a hand
                # jit) must not stash — the tracer would leak out of its
                # finished trace and poison a later aux_loss() use
                self._last_aux = weighted
        return y

    def aux_loss(self):
        """Last EAGER forward's weighted aux loss (add it to the task
        loss before ``backward()``).  Compiled paths don't stash: the
        SPMD step collects through :func:`moe_loss_frame`, and a
        hybridized block's cached graph never re-runs this Python — use
        the un-hybridized block (or the frame) when you need the loss."""
        if self._last_aux is None:
            raise RuntimeError(
                "MoEBlock.aux_loss(): no eager forward has run (compiled "
                "forwards — hybridize/SPMD — don't stash; collect via "
                "moe_loss_frame instead)")
        return self._last_aux

    def __repr__(self):
        return (f"MoEBlock({self._units} -> {self._num_experts}x"
                f"[{self._hidden}] top{self._top_k}, "
                f"cf={self._capacity_factor})")

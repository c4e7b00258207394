"""What the decoder models of the zoo share (``xing4``, ``nemotron_h``):
:class:`RMSNorm`, the two feed-forward forms (:class:`SwiGLU`,
:class:`Relu2FFN`), :class:`SparseExperts` (the dropless routed experts of
which a chip holds its share, with the ``noaux_tc`` selection bias),
:func:`run_layer` (one layer under ``jax.checkpoint``, which keeps the values
named in :data:`KEPT`; its routing statistics handed to the trainer's MoE
frame and to the bias rule) and
:class:`CausalLM` (a model and its untied output head).
"""
from __future__ import annotations

from ... import profiler
from ...ops import attention as _att
from ...ops import sparse_attention as _sparse
from ..block import HybridBlock, collect_aux_update
from ..nn import Dense
from . import moe as _moe

__all__ = ["RMSNorm", "SwiGLU", "Relu2FFN", "SparseExperts", "run_layer",
           "KEPT", "CausalLM"]

# The names (``jax.ad_checkpoint.checkpoint_name``) of what a layer's
# checkpoint keeps beside the layer's input: arrays that a kernel wrote, that
# are small next to the time it takes to write them again, and that the
# backward pass reads as they are: the attention core's output and its
# log-sum-exp (the blockwise kernels' forward rules set them), and the int8
# selection of an indexer (``select_topk``).  A name no layer sets costs
# nothing.
KEPT = (_att.KEEP_OUT, _att.KEEP_LSE, _sparse.KEEP_SELECT)


def _scope(name):
    import jax

    return jax.named_scope(name)


class RMSNorm(HybridBlock):
    def __init__(self, units, eps=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._eps = float(eps)
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(units,), init="ones")

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, eps=self._eps)


class SwiGLU(HybridBlock):
    """``W_down (silu(W_gate x) ⊙ W_up x)``, gate and up as one weight."""

    def __init__(self, units, hidden_size, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.gate_up_weight = self.params.get(
                "gate_up_weight", shape=(2 * hidden_size, units))
            self.down_weight = self.params.get(
                "down_weight", shape=(units, hidden_size))

    def hybrid_forward(self, F, x, gate_up_weight, down_weight):
        return F.contrib.swiglu_ffn(x, gate_up_weight, down_weight)


class Relu2FFN(HybridBlock):
    """The non-gated two-matrix feed-forward ``W_down relu(W_up x)²``."""

    def __init__(self, units, hidden_size, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.up_weight = self.params.get(
                "up_weight", shape=(hidden_size, units))
            self.down_weight = self.params.get(
                "down_weight", shape=(units, hidden_size))

    def hybrid_forward(self, F, x, up_weight, down_weight):
        return F.contrib.relu2_ffn(x, up_weight, down_weight)


# expert form → (the shared expert's block, the stacked routed weight's name
# and how many times the expert's width it is wide)
_EXPERT_FORMS = {"swiglu": (SwiGLU, "experts_gate_up_weight", 2),
                 "relu2": (Relu2FFN, "experts_up_weight", 1)}


class SparseExperts(HybridBlock):
    """Dropless routed experts + the shared expert, for the experts held.

    ``experts_held = (first, count)``: this chip holds the routed experts
    ``first .. first + count - 1`` of ``num_experts`` (default: all).  The
    router and its selection bias cover all ``num_experts``; a pair routed
    to an expert held elsewhere adds nothing here.

    ``expert_form`` is every expert's inner function, routed and shared
    alike: ``"swiglu"`` (``W_down (silu(W_gate x) ⊙ W_up x)``, stacked as
    ``experts_gate_up_weight`` [held, d, 2·width]) or ``"relu2"`` (``W_down
    relu(W_up x)²``, ``experts_up_weight`` [held, d, width]);
    ``shared_width`` is the shared expert's width (default ``expert_width ·
    n_shared_experts``).  ``scope`` names the ``jax.named_scope``s
    ``<scope>.route``, ``<scope>.experts`` and ``<scope>.shared``.

    The selection bias is the ``noaux_tc`` balancing buffer: no gradient
    reaches it; in training every step moves it by ``bias_update_speed``
    towards balance, ``b_e += γ · sign(mean load − load_e)`` over ALL the
    experts (:meth:`balanced_bias`; 0 freezes it).  It stays float32 under
    ``cast``: a step of 0.001 is below bf16's resolution at 0.5.

    ``forward`` returns ``(y, stats)``: ``stats`` is a float32 vector
    ``(rows routed here, least load, greatest load)`` over the experts held
    followed by the load of each of all the experts, which :func:`run_layer`
    hands to the trainer's MoE frame and to the rule.

    ``scoring`` is the router's: ``"sigmoid"`` with the selection bias (the
    above), or ``"softmax"`` over all the experts with NO selection bias (no
    such parameter exists, nothing is balanced outside the gradient) and the
    Switch load-balance term as a third result, ``(y, stats, balance)``,
    raw, for the block to weight into the step's loss.  ``n_shared_experts``
    0 builds no shared expert."""

    def __init__(self, units, expert_width, num_experts, top_k,
                 experts_held=None, n_shared_experts=1, routed_scaling=1.0,
                 norm_topk=True, bias_update_speed=0.001, scope="moe",
                 expert_form="swiglu", shared_width=None, scoring="sigmoid",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._biased = scoring == "sigmoid"
        self._bias_speed = float(bias_update_speed) if self._biased else 0.0
        self._jax_scope = str(scope)
        first, count = experts_held or (0, num_experts)
        if not (0 <= first and first + count <= num_experts and count > 0):
            raise ValueError(f"experts_held {experts_held} outside "
                             f"0..{num_experts}")
        if top_k > num_experts:
            raise ValueError(f"top_k {top_k} > num_experts {num_experts}")
        if expert_form not in _EXPERT_FORMS:
            raise ValueError(f"expert_form {expert_form!r}: one of "
                             f"{sorted(_EXPERT_FORMS)}")
        shared_block, in_name, in_mult = _EXPERT_FORMS[expert_form]
        self._kw = dict(num_experts=int(num_experts), top_k=int(top_k),
                        first_expert=int(first),
                        routed_scaling=float(routed_scaling),
                        norm_topk=bool(norm_topk), scope=self._jax_scope,
                        expert_form=expert_form)
        if not self._biased:      # the default stays out of the op's signature
            self._kw["scoring"] = scoring
        if shared_width is None:
            shared_width = expert_width * int(n_shared_experts)
        with self.name_scope():
            get = self.params.get
            self.router_weight = get("router_weight",
                                     shape=(num_experts, units))
            # the noaux_tc selection bias: a buffer that a balancing rule
            # outside the gradient would move; no gradient reaches it
            if self._biased:
                self.select_bias = get("select_bias", shape=(num_experts,),
                                       init="zeros", grad_req="null")
            self.experts_in_weight = get(
                in_name, shape=(count, units, in_mult * expert_width))
            self.experts_down_weight = get(
                "experts_down_weight", shape=(count, expert_width, units))
            self.shared_expert = (
                shared_block(units, int(shared_width), prefix="shared_")
                if n_shared_experts else None)

    def hybrid_forward(self, F, x, router_weight, experts_down_weight,
                       select_bias=None, **experts_in):
        (experts_in_weight,) = experts_in.values()   # named by the form
        if select_bias is None:      # softmax scoring reads none
            select_bias = F.zeros((router_weight.shape[0],))
        y, rows, load_min, load_max, load_all, *balance = F.contrib.moe_ffn_dropless(
            x, router_weight, select_bias, experts_in_weight,
            experts_down_weight, **self._kw)
        if self.shared_expert is not None:
            with _scope(self._jax_scope + ".shared"):
                y = y + self.shared_expert(x)
        stats = F.concat(F.stack(rows, load_min, load_max), load_all, dim=0)
        return (y, stats, *balance)

    def cast(self, dtype):
        super().cast(dtype)
        if self._biased:
            self.select_bias.cast("float32")
        return self

    def balanced_bias(self, bias, load_all):
        """One step of the ``noaux_tc`` rule on raw arrays: the bias of an
        expert with less than the mean load rises by ``bias_update_speed``,
        that of one with more falls."""
        import jax.numpy as jnp

        return bias + self._bias_speed * jnp.sign(load_all.mean() - load_all)


def _keeping_policy():
    """The layer checkpoint's policy: save the values named in :data:`KEPT`
    and nothing else.  Asked once an operation when a checkpoint is
    differentiated, so the bytes it says yes to are what the step's layers
    keep: the counter ``remat_kept_bytes``."""
    import jax

    named = jax.checkpoint_policies.save_only_these_names(*KEPT)

    def keeps(prim, *avals, **params):
        kept = named(prim, *avals, **params)
        if kept:
            profiler.incr("remat_kept_bytes", sum(a.size * a.dtype.itemsize for a in avals))
        return kept

    return keeps


def run_layer(body, x, remat, experts=None):
    """One decoder layer: ``body(x) → (out, stats)`` or ``(out, stats,
    side)``, ``stats`` the routing statistics of ``experts`` (a
    :class:`SparseExperts`) or None, ``side`` what the layer adds to the step
    beside its output: ``{"loss": a weighted scalar, "counters": {a profiler
    counter's name: a scalar}}`` (``model_zoo.moe.register_side``).  Under a
    jit trace with ``remat`` the body runs inside ``jax.checkpoint``: the
    layer keeps its input and the values named in :data:`KEPT`, and the
    backward pass runs the rest of its forward again (a kernel all of whose
    results were kept does not run: nothing reads it).  A layer of your own
    keeps a value the same way, ``checkpoint_name(value, one of KEPT)``:
    docs/observability.md says what it costs.
    Statistics and side are registered OUTSIDE the checkpoint (what the
    trainer's MoE frame and the aux collector keep must belong to the step's
    own trace), and in training the ``noaux_tc`` rule moves the selection
    bias."""
    import jax

    from ... import autograd
    from ...ndarray.ndarray import NDArray

    def raw(tree):
        return jax.tree_util.tree_map(
            lambda v: v._data if isinstance(v, NDArray) else v, tree,
            is_leaf=lambda v: isinstance(v, NDArray))

    traced = (isinstance(x._data, jax.core.Tracer)
              and not autograd.is_recording())
    if remat and traced:
        out, stats, *side = jax.checkpoint(
            lambda data: raw(body(NDArray(data))), policy=_keeping_policy())(x._data)
        out = NDArray(out)
    else:
        out, stats, *side = body(x)
        stats, side = raw(stats), raw(side)
    if side:
        _moe.register_side(**side[0])
    if stats is not None:
        _moe.register_metrics({
            "rows_routed_here": stats[0], "expert_load_min": stats[1],
            "expert_load_max": stats[2], "expert_load_all": stats[3:],
            "tokens_dropped": 0.0 * stats[0]})   # dropless
        if autograd.is_training() and experts._bias_speed:
            bias = experts.select_bias
            collect_aux_update(bias, NDArray(experts.balanced_bias(
                bias.data()._data, stats[3:])))
    return out


class CausalLM(HybridBlock):
    """A decoder model (token ids ``[B, S]`` → hidden states ``[B, S, d]``;
    ``build_model(prefix)`` makes it, inside this block's name scope) and the
    untied output head: logits ``[B, S, vocab]``, traced under ``head_scope``
    (``vocab_size`` may be this chip's slice)."""

    def __init__(self, build_model, vocab_size, hidden_size, head_scope,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._head_scope = head_scope
        with self.name_scope():
            self.model = build_model("model_")
            self.lm_head = Dense(vocab_size, use_bias=False, flatten=False,
                                 in_units=hidden_size, prefix="lm_head_")

    def forward(self, token_ids):
        hidden = self.model(token_ids)
        with _scope(self._head_scope):
            return self.lm_head(hidden)

"""What the decoder models of the zoo share (``xing4``, ``nemotron_h``,
``keye``, ``solar_open2``, ``lfm2_moe``):
:class:`RMSNorm`, the two feed-forward forms (:class:`SwiGLU`,
:class:`Relu2FFN`), :class:`GroupedQueryAttention` (with an optional output
gate, per-head q/k norms and rotary positions, for the query heads a chip
holds), :class:`SparseExperts` (the dropless
routed experts of which a chip holds its share, with the ``noaux_tc``
selection bias), the state-space initialisers (``_LogUniform``,
``_InverseSoftplusStep``),
:func:`run_layer` (one layer under ``jax.checkpoint``, which keeps the values
named in :data:`KEPT`; its routing statistics handed to the trainer's MoE
frame and to the bias rule) and
:class:`CausalLM` (a model and its output head, untied or tied).
"""
from __future__ import annotations

import numpy as _np

from ... import initializer as _init
from ... import profiler
from ... import random as _random
from ...ops import attention as _att
from ...ops import sparse_attention as _sparse
from ..block import HybridBlock, collect_aux_update
from ..nn import Dense
from . import moe as _moe

__all__ = ["RMSNorm", "SwiGLU", "Relu2FFN", "GroupedQueryAttention",
           "SparseExperts", "run_layer", "KEPT", "CausalLM"]

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


class _OwnInit(_init.Initializer):
    """An initializer that is its parameter's own whatever the name ends in
    (``initializer.py`` dispatches on the suffix: ``dt_bias`` would be zeroed
    as a bias)."""

    def __call__(self, desc, arr):
        self._init_weight(desc, arr)


class _LogUniform(_OwnInit):
    """``log U(low, high)``: Mamba-2's ``A_log`` (``A = −exp(A_log)`` then
    lies in ``−high .. −low``)."""

    def __init__(self, low=1.0, high=16.0):
        super().__init__(low=low, high=high)
        self._range = (float(low), float(high))

    def _init_weight(self, _, arr):
        arr[:] = _np.log(_random.uniform(*self._range, arr.shape,
                                         dtype="float32").asnumpy())


class _InverseSoftplusStep(_OwnInit):
    """``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in
    ``[dt_min, dt_max]`` (clamped below by ``dt_floor``): Mamba-2's."""

    def __init__(self, dt_min=0.001, dt_max=0.1, dt_floor=1e-4):
        super().__init__(dt_min=dt_min, dt_max=dt_max, dt_floor=dt_floor)
        self._args = (float(dt_min), float(dt_max), float(dt_floor))

    def _init_weight(self, _, arr):
        dt_min, dt_max, dt_floor = self._args
        u = _random.uniform(0.0, 1.0, arr.shape, dtype="float32").asnumpy()
        dt = _np.exp(u * (_np.log(dt_max) - _np.log(dt_min)) + _np.log(dt_min))
        dt = _np.clip(dt, dt_floor, dt_max)
        arr[:] = dt + _np.log(-_np.expm1(-dt))    # softplus⁻¹(dt)


class GroupedQueryAttention(HybridBlock):
    """Causal self-attention with ``num_heads`` query heads on ``kv_heads``
    key/value heads of ``head_dim``, scale ``head_dim^-½``, no bias:
    ``[B, S, d] → [B, S, d]``.  Query, key and value projections are one
    weight (q rows, then k, then v).  ``scope`` names the
    ``jax.named_scope`` of the layer and ``<scope>.core`` of its core.

    ``heads_held = (first, count)``: this chip holds query heads ``first ..
    first + count − 1`` of ``num_heads`` (default: all) and the key/value
    heads they read (query head ``i`` reads ``i // (num_heads / kv_heads)``),
    with the rows of ``W_o`` that read them; the output is those heads' part
    of the sum, as tensor parallelism by heads leaves it before its
    all-reduce.  ``gate`` adds an elementwise output gate, ``attn ⊙ σ(x
    W_z)`` before ``W_o`` (``gate_weight`` [count · head_dim, d]).

    ``qk_norm`` (an RMSNorm ε, or None) normalises each head of q and of k
    over its ``head_dim`` channels with one gain an element
    (``q_norm_gamma``, ``k_norm_gamma`` [head_dim]); ``rope_theta`` (or
    None) then turns q and k by rotary positions in the rotate-half form
    over every dim.  Both run outside ``<scope>.core``; with both off the
    layer has neither parameter nor operation of them."""

    def __init__(self, units, num_heads, kv_heads, head_dim, scope="attn",
                 gate=False, heads_held=None, qk_norm=None, rope_theta=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        heads, kv_heads = int(num_heads), int(kv_heads)
        if heads % kv_heads:
            raise ValueError(f"{heads} query heads do not divide into {kv_heads} key/value heads")
        group = heads // kv_heads
        first, count = heads_held or (0, heads)
        if not (0 <= first and first + count <= heads and count > 0):
            raise ValueError(f"heads_held {heads_held} outside 0..{heads}")
        kv_first, kv_last = first // group, (first + count - 1) // group
        if kv_first != kv_last and (first % group or count % group):
            raise ValueError(f"heads_held {heads_held} splits a group of {group} query "
                             f"heads that share a key/value head")
        self._heads, self._kv_heads = count, kv_last - kv_first + 1
        self._q, self._kv = self._heads * int(head_dim), self._kv_heads * int(head_dim)
        self._scale = float(head_dim) ** -0.5
        self._jax_scope = str(scope)
        self._qk_norm = None if qk_norm is None else float(qk_norm)
        self._rope_theta = None if rope_theta is None else float(rope_theta)
        with self.name_scope():
            self.qkv_weight = self.params.get(
                "qkv_weight", shape=(self._q + 2 * self._kv, units))
            self.gate_weight = (self.params.get("gate_weight", shape=(self._q, units))
                                if gate else None)
            self.o_weight = self.params.get("o_weight", shape=(units, self._q))
            if self._qk_norm is not None:
                self.q_norm_gamma = self.params.get(
                    "q_norm_gamma", shape=(int(head_dim),), init="ones")
                self.k_norm_gamma = self.params.get(
                    "k_norm_gamma", shape=(int(head_dim),), init="ones")

    def _heads_turned(self, F, a, heads, gamma):
        """``a`` [B, S, heads · head_dim] normed per head (with ``gamma``)
        and turned by rotary positions, as the options ask."""
        b, s, width = a.shape
        a = a.reshape((b, s, heads, width // heads))
        if gamma is not None:
            a = F.RMSNorm(a, gamma, eps=self._qk_norm)
        if self._rope_theta is not None:
            a = F.rotary_embedding(a, theta=self._rope_theta)
        return a.reshape((b, s, width))

    def hybrid_forward(self, F, x, qkv_weight, o_weight, gate_weight=None,
                       q_norm_gamma=None, k_norm_gamma=None):
        with _scope(self._jax_scope):
            qkv = F.FullyConnected(x, qkv_weight, no_bias=True, flatten=False,
                                   num_hidden=self._q + 2 * self._kv)
            q = F.slice_axis(qkv, axis=-1, begin=0, end=self._q)
            k = F.slice_axis(qkv, axis=-1, begin=self._q, end=self._q + self._kv)
            v = F.slice_axis(qkv, axis=-1, begin=self._q + self._kv,
                             end=self._q + 2 * self._kv)
            if self._qk_norm is not None or self._rope_theta is not None:
                q = self._heads_turned(F, q, self._heads, q_norm_gamma)
                k = self._heads_turned(F, k, self._kv_heads, k_norm_gamma)
            with _scope(self._jax_scope + ".core"):
                out = F.contrib.fused_attention(
                    q, k, v, num_heads=self._heads, kv_heads=self._kv_heads,
                    causal=True, scale=self._scale)
            if gate_weight is not None:
                out = out * F.sigmoid(F.FullyConnected(x, gate_weight, no_bias=True,
                                                       flatten=False, num_hidden=self._q))
            return F.FullyConnected(out, o_weight, no_bias=True, flatten=False,
                                    num_hidden=o_weight.shape[0])


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
    output head: logits ``[B, S, vocab]``, traced under ``head_scope``
    (``vocab_size`` may be this chip's slice).  The head is a weight of its
    own, or with ``tie_embedding`` the model's embedding (``model.embed``):
    the rows that embed the ids predict them."""

    def __init__(self, build_model, vocab_size, hidden_size, head_scope,
                 tie_embedding=False, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._head_scope = head_scope
        self._vocab = int(vocab_size)
        with self.name_scope():
            self.model = build_model("model_")
            self.lm_head = None if tie_embedding else Dense(
                vocab_size, use_bias=False, flatten=False, in_units=hidden_size,
                prefix="lm_head_")

    def forward(self, token_ids):
        from ... import ndarray as F

        hidden = self.model(token_ids)
        with _scope(self._head_scope):
            if self.lm_head is not None:
                return self.lm_head(hidden)
            return F.FullyConnected(hidden, self.model.embed.weight.data(), no_bias=True,
                                    flatten=False, num_hidden=self._vocab)

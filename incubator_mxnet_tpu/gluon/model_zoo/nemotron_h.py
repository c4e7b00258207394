"""Nemotron-H: a pre-norm decoder whose layers are ONE sublayer each, its
kind read from ``hybrid_override_pattern`` — ``M`` a Mamba-2 mixer, ``E``
dropless sigmoid-routed relu² experts, ``*`` grouped-query attention
(docs/nemotron_h.md has the equations).

Built from the published ``config.json`` keys (``model_type``
``nemotron_h``); trained by ``SPMDTrainer`` exactly as ``BERTForPretrain`` and
``Xing4ForCausalLM`` are.  What this file adds to the program:

* :class:`Mamba2Mixer` — input projection, causal depthwise convolution,
  the chunked state-space scan, the grouped gated RMSNorm, output projection
  (``ops.ssm.mamba2_mixer``).  ``A_log``, ``dt_bias`` and ``D`` stay float32
  under ``cast``.
* :class:`GroupedQueryAttention` — causal self-attention with fewer key/value
  heads than query heads through the attention dispatcher; NO rotary
  embedding (the state-space layers carry position).
* :class:`NemotronHBlock` / :class:`NemotronHModel` /
  :class:`NemotronHForCausalLM`.  The expert layer is ``decoder.py``'s
  :class:`SparseExperts` (``xing4`` shares it) with the relu² form: the
  router covers ALL ``n_routed_experts`` and this chip computes the part of
  the sum that the experts it HOLDS give (``experts_held = (first,
  count)``), plus the shared expert.

``remat=True`` wraps every layer in ``jax.checkpoint`` under a jit trace
(``SPMDTrainer``): a layer keeps its input ``[B, S, d]`` and, an attention
layer, the core's output and log-sum-exp (``decoder.KEPT``), and the backward
pass runs the rest of its forward again.
"""
from __future__ import annotations

import numpy as _np

from ... import initializer as _init
from ... import random as _random
from ..block import HybridBlock
from ..nn import Embedding
from .decoder import CausalLM, RMSNorm, SparseExperts, _scope, run_layer

__all__ = ["Mamba2Mixer", "GroupedQueryAttention", "NemotronHBlock",
           "NemotronHModel", "NemotronHForCausalLM", "LAYER_KINDS"]

# jax.named_scope names inside the compiled step (chipbench's per-layer
# metrics select device operations by them)
SCOPE_MAMBA = "nemotron.mamba"
SCOPE_ATTN = "nemotron.attn"
SCOPE_MOE = "nemotron.moe"
SCOPE_HEAD = "nemotron.head"

# a layer's character in ``hybrid_override_pattern`` → the block's attribute
# that holds its sublayer
LAYER_KINDS = {"M": "mamba", "E": "ffn", "*": "attn"}


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


class Mamba2Mixer(HybridBlock):
    """One Mamba-2 mixer, ``[B, S, d] → [B, S, d]``; the math is
    ``ops.ssm.mamba2_mixer``.  The inner width is ``num_heads · head_dim``
    (the ``nemotron_h`` convention; ``expand`` is not used for it)."""

    def __init__(self, units, num_heads, head_dim, n_groups, state_size,
                 conv_kernel=4, chunk_size=128, eps=1e-5, dt_min=0.001,
                 dt_max=0.1, dt_floor=1e-4, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        h, p, g, n = int(num_heads), int(head_dim), int(n_groups), int(state_size)
        if h % g:
            raise ValueError(f"{h} heads do not divide into {g} groups")
        inner, conv_dim = h * p, h * p + 2 * g * n
        self._kw = dict(num_heads=h, head_dim=p, n_groups=g, state_size=n,
                        chunk_size=int(chunk_size), dt_floor=float(dt_floor),
                        eps=float(eps), scope=SCOPE_MAMBA)
        with self.name_scope():
            get = self.params.get
            self.in_proj_weight = get("in_proj_weight",
                                      shape=(inner + conv_dim + h, units))
            self.conv_weight = get("conv_weight", shape=(conv_dim, int(conv_kernel)))
            self.conv_bias = get("conv_bias", shape=(conv_dim,), init="zeros")
            self.dt_bias = get("dt_bias", shape=(h,),
                               init=_InverseSoftplusStep(dt_min, dt_max, dt_floor))
            self.A_log = get("A_log", shape=(h,), init=_LogUniform(1.0, 16.0))
            self.D = get("D", shape=(h,), init=_init.Constant(1.0))
            self.norm_gamma = get("norm_gamma", shape=(inner,), init="ones")
            self.out_proj_weight = get("out_proj_weight", shape=(units, inner))

    def hybrid_forward(self, F, x, in_proj_weight, conv_weight, conv_bias,
                       dt_bias, A_log, D, norm_gamma, out_proj_weight):
        return F.contrib.mamba2_mixer(
            x, in_proj_weight, conv_weight, conv_bias, dt_bias, A_log, D,
            norm_gamma, out_proj_weight, **self._kw)

    def cast(self, dtype):
        # the decay's rate, the step's offset and the skip stay float32: a
        # bf16 A_log is 0.4 % of a decay rate that is then raised to the
        # power of up to 128 positions
        super().cast(dtype)
        for p in (self.dt_bias, self.A_log, self.D):
            p.cast("float32")
        return self


class GroupedQueryAttention(HybridBlock):
    """Causal self-attention with ``num_heads`` query heads on ``kv_heads``
    key/value heads of ``head_dim``, scale ``head_dim^-½``, no bias, no
    rotary embedding: ``[B, S, d] → [B, S, d]``.  Query, key and value
    projections are one weight (q rows, then k, then v)."""

    def __init__(self, units, num_heads, kv_heads, head_dim, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._heads, self._kv_heads = int(num_heads), int(kv_heads)
        self._q, self._kv = self._heads * int(head_dim), self._kv_heads * int(head_dim)
        self._scale = float(head_dim) ** -0.5
        with self.name_scope():
            self.qkv_weight = self.params.get(
                "qkv_weight", shape=(self._q + 2 * self._kv, units))
            self.o_weight = self.params.get("o_weight", shape=(units, self._q))

    def hybrid_forward(self, F, x, qkv_weight, o_weight):
        with _scope(SCOPE_ATTN):
            qkv = F.FullyConnected(x, qkv_weight, no_bias=True, flatten=False,
                                   num_hidden=self._q + 2 * self._kv)
            q = F.slice_axis(qkv, axis=-1, begin=0, end=self._q)
            k = F.slice_axis(qkv, axis=-1, begin=self._q, end=self._q + self._kv)
            v = F.slice_axis(qkv, axis=-1, begin=self._q + self._kv,
                             end=self._q + 2 * self._kv)
            with _scope(SCOPE_ATTN + ".core"):
                out = F.contrib.fused_attention(
                    q, k, v, num_heads=self._heads, kv_heads=self._kv_heads,
                    causal=True, scale=self._scale)
            return F.FullyConnected(out, o_weight, no_bias=True, flatten=False,
                                    num_hidden=o_weight.shape[0])


class NemotronHBlock(HybridBlock):
    """One layer, ``x + f(RMSNorm(x))`` with ``f`` of the given ``kind``
    (``"M"``, ``"E"`` or ``"*"``); the sublayer is ``self.mamba``,
    ``self.ffn`` or ``self.attn``."""

    def __init__(self, config, kind, experts_held=None, remat=False,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if kind not in LAYER_KINDS:
            raise ValueError(f"layer kind {kind!r}: one of {sorted(LAYER_KINDS)}")
        c = config
        d = c["hidden_size"]
        self._remat, self._sparse = bool(remat), kind == "E"
        self._sublayer = LAYER_KINDS[kind]
        with self.name_scope():
            self.norm = RMSNorm(d, c["layer_norm_epsilon"], prefix="norm_")
            if kind == "M":
                self.mamba = Mamba2Mixer(
                    d, c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
                    c["ssm_state_size"], c["conv_kernel"], c["chunk_size"],
                    c["layer_norm_epsilon"], c["time_step_min"],
                    c["time_step_max"], c["time_step_floor"], prefix="mamba_")
            elif kind == "*":
                self.attn = GroupedQueryAttention(
                    d, c["num_attention_heads"], c["num_key_value_heads"],
                    c["head_dim"], prefix="attn_")
            else:
                self.ffn = SparseExperts(
                    d, c["moe_intermediate_size"], c["n_routed_experts"],
                    c["num_experts_per_tok"], experts_held,
                    c["n_shared_experts"], c["routed_scaling_factor"],
                    c["norm_topk_prob"],
                    bias_update_speed=c.get("bias_update_speed", 0.001),
                    scope=SCOPE_MOE, expert_form="relu2",
                    shared_width=c["moe_shared_expert_intermediate_size"],
                    prefix="moe_")

    def _body(self, x):
        y = getattr(self, self._sublayer)(self.norm(x))
        stats = None
        if self._sparse:
            y, stats = y
        return x + y, stats

    def forward(self, x):
        return run_layer(self._body, x, self._remat,
                         self.ffn if self._sparse else None)


class NemotronHModel(HybridBlock):
    """Embedding → one block a character of ``hybrid_override_pattern`` →
    final RMSNorm: token ids ``[B, S]`` → hidden states ``[B, S, d]``."""

    def __init__(self, config, experts_held=None, remat=False, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        c = config
        pattern = c["hybrid_override_pattern"]
        if len(pattern) != c["num_hidden_layers"]:
            raise ValueError(f"hybrid_override_pattern has {len(pattern)} layers, "
                             f"num_hidden_layers is {c['num_hidden_layers']}")
        if c.get("n_group", 1) != 1 or c.get("topk_group", 1) != 1:
            raise ValueError("only n_group 1 (no group-limited routing) is built")
        if c.get("mlp_hidden_act", "relu2") != "relu2":
            raise ValueError(f"mlp_hidden_act {c['mlp_hidden_act']!r}: only relu2")
        with self.name_scope():
            self.embed = Embedding(c["vocab_size"], c["hidden_size"],
                                   prefix="embed_")
            self.blocks = []
            for i, kind in enumerate(pattern):
                block = NemotronHBlock(c, kind, experts_held=experts_held,
                                       remat=remat, prefix=f"layer{i}_")
                self.register_child(block, f"layer{i}")
                self.blocks.append(block)
            self.norm = RMSNorm(c["hidden_size"], c["layer_norm_epsilon"],
                                prefix="norm_")

    def forward(self, token_ids):
        x = self.embed(token_ids)
        for block in self.blocks:
            x = block(x)
        return self.norm(x)


class NemotronHForCausalLM(CausalLM):
    """:class:`NemotronHModel` and the untied output head: token ids
    ``[B, S]`` → logits ``[B, S, vocab]`` (``vocab_size`` may be this chip's
    slice).

    :meth:`rescale_prenorm_residual` is the family's initialisation rule
    (``rescale_prenorm_residual``): after ``initialize``, every projection
    that writes into the residual stream (``out_proj``, ``W_o``, the experts'
    ``W_down``) is divided by the square root of the PUBLISHED depth."""

    def __init__(self, config, experts_held=None, remat=False, prefix=None,
                 params=None):
        super().__init__(
            lambda prefix: NemotronHModel(config, experts_held, remat, prefix=prefix),
            config["vocab_size"], config["hidden_size"], SCOPE_HEAD,
            prefix=prefix, params=params)

    def rescale_prenorm_residual(self, num_layers):
        factor = float(num_layers) ** -0.5
        for name, p in self.collect_params().items():
            if name.endswith(("out_proj_weight", "o_weight", "down_weight")):
                p.set_data(p.data() * factor)
        return self

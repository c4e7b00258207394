"""LFM2-MoE: a pre-norm decoder whose token mixer is a gated short
convolution at the layers ``layer_types`` calls ``"conv"`` and grouped-query
attention with per-head q/k norms and rotary positions at those it calls
``"full_attention"``; its feed-forward is a dense SwiGLU in the first
``num_dense_layers`` layers and sigmoid-routed experts everywhere else
(docs/lfm2.md has the equations).

Built from the published ``config.json`` keys (``model_type`` ``lfm2_moe``);
trained by ``SPMDTrainer`` exactly as the other decoders of the zoo are.
What this file adds to the program:

* :class:`ShortConvMixer` — ``[B | C | x̃] = u W_in``, ``C ⊙ conv(B ⊙ x̃)``
  by a depthwise causal convolution of ``conv_L_cache`` taps with no bias
  and no activation (``ops.ssm.gated_short_conv``), ``W_out``.
* :class:`Lfm2Block` / :class:`Lfm2Model` / :class:`Lfm2ForCausalLM`.  The
  attention mixer is ``decoder.py``'s :class:`GroupedQueryAttention` with
  ``qk_norm`` and ``rope_theta``; the expert layer its
  :class:`SparseExperts` in the SwiGLU form with the selection bias and no
  shared expert; the head is tied to the embedding.

``remat=True`` wraps every layer in ``jax.checkpoint`` under a jit trace
(``SPMDTrainer``): a layer keeps its input ``[B, S, d]`` and, the attention
layer, the core's output and log-sum-exp (``decoder.KEPT``), and the
backward pass runs the rest of its forward again.
"""
from __future__ import annotations

from ..block import HybridBlock
from ..nn import Embedding
from .decoder import (CausalLM, GroupedQueryAttention, RMSNorm, SparseExperts, SwiGLU,
                      _scope, run_layer)

__all__ = ["ShortConvMixer", "Lfm2Block", "Lfm2Model", "Lfm2ForCausalLM", "LAYER_KINDS"]

# jax.named_scope names inside the compiled step (chipbench's per-layer
# metrics select device operations by them; a metric matches a SUBSTRING, so
# no name is part of another unless it is nested in it)
SCOPE_CONV = "lfm2.conv"
SCOPE_ATTN = "lfm2.attn"
SCOPE_FFN = "lfm2.ffn"
SCOPE_MOE = "lfm2.moe"
SCOPE_HEAD = "lfm2.head"

# a layer's entry in ``layer_types`` → the block's attribute that holds its mixer
LAYER_KINDS = {"conv": "conv", "full_attention": "attn"}


class ShortConvMixer(HybridBlock):
    """One gated short-convolution mixer, ``[B, S, d] → [B, S, d]``:
    ``W_in`` [3·d, d] (rows B, then C, then x̃), the depthwise weight [d,
    ``conv_kernel``], ``W_out`` [d, d]; no biases.  Traced under
    ``lfm2.conv`` with ``.proj``, ``.core`` and ``.out`` beneath it."""

    def __init__(self, units, conv_kernel=3, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = int(units)
        with self.name_scope():
            get = self.params.get
            self.in_proj_weight = get("in_proj_weight", shape=(3 * units, units))
            self.conv_weight = get("conv_weight", shape=(units, int(conv_kernel)))
            self.out_proj_weight = get("out_proj_weight", shape=(units, units))

    def hybrid_forward(self, F, x, in_proj_weight, conv_weight, out_proj_weight):
        with _scope(SCOPE_CONV):
            with _scope(SCOPE_CONV + ".proj"):
                bcx = F.FullyConnected(x, in_proj_weight, no_bias=True, flatten=False,
                                       num_hidden=3 * self._units)
            with _scope(SCOPE_CONV + ".core"):
                y = F.contrib.gated_short_conv(bcx, conv_weight)
            with _scope(SCOPE_CONV + ".out"):
                return F.FullyConnected(y, out_proj_weight, no_bias=True, flatten=False,
                                        num_hidden=self._units)


class Lfm2Block(HybridBlock):
    """One layer, ``h = x + mixer(RMSNorm(x))``, ``out = h + F(RMSNorm(h))``;
    the mixer is ``self.conv`` (``kind`` ``"conv"``) or ``self.attn``
    (``"full_attention"``), ``F`` the dense SwiGLU where ``dense``, else the
    routed experts (both ``self.ffn``)."""

    def __init__(self, config, kind, dense, experts_held=None, remat=False,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if kind not in LAYER_KINDS:
            raise ValueError(f"layer kind {kind!r}: one of {sorted(LAYER_KINDS)}")
        c = config
        d, eps = c["hidden_size"], c["norm_eps"]
        heads = c["num_attention_heads"]
        self._remat, self._sparse = bool(remat), not dense
        self._mixer = LAYER_KINDS[kind]
        with self.name_scope():
            self.operator_norm = RMSNorm(d, eps, prefix="operator_norm_")
            if kind == "conv":
                self.conv = ShortConvMixer(d, c["conv_L_cache"], prefix="conv_")
            else:
                self.attn = GroupedQueryAttention(
                    d, heads, c["num_key_value_heads"], d // heads, scope=SCOPE_ATTN,
                    qk_norm=eps, rope_theta=c["rope_parameters"]["rope_theta"],
                    prefix="attn_")
            self.ffn_norm = RMSNorm(d, eps, prefix="ffn_norm_")
            if dense:
                self.ffn = SwiGLU(d, c["intermediate_size"], prefix="ffn_")
            else:
                self.ffn = SparseExperts(
                    d, c["moe_intermediate_size"], c["num_experts"],
                    c["num_experts_per_tok"], experts_held, 0, c["routed_scaling_factor"],
                    c["norm_topk_prob"], bias_update_speed=c.get("bias_update_speed", 0.001),
                    scope=SCOPE_MOE, prefix="moe_")

    def _body(self, x):
        h = x + getattr(self, self._mixer)(self.operator_norm(x))
        u = self.ffn_norm(h)
        if self._sparse:
            y, stats = self.ffn(u)
        else:
            with _scope(SCOPE_FFN):
                y, stats = self.ffn(u), None
        return h + y, stats

    def forward(self, x):
        return run_layer(self._body, x, self._remat, self.ffn if self._sparse else None)


class Lfm2Model(HybridBlock):
    """Embedding → one block an entry of ``layer_types`` (the first
    ``num_dense_layers`` with the dense SwiGLU) → final RMSNorm: token ids
    ``[B, S]`` → hidden states ``[B, S, d]``."""

    def __init__(self, config, experts_held=None, remat=False, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        c = config
        kinds = c["layer_types"]
        if len(kinds) != c["num_hidden_layers"]:
            raise ValueError(f"layer_types has {len(kinds)} layers, num_hidden_layers is "
                             f"{c['num_hidden_layers']}")
        if c.get("conv_bias", False):
            raise ValueError("only the bias-free short convolution is built: conv_bias must "
                             "be false")
        if not c.get("use_expert_bias", True):
            raise ValueError("only the router with a selection bias is built: use_expert_bias "
                             "must be true")
        if c["hidden_size"] % c["num_attention_heads"]:
            raise ValueError(f"hidden_size {c['hidden_size']} does not split into "
                             f"{c['num_attention_heads']} heads")
        self.kinds = list(kinds)
        with self.name_scope():
            self.embed = Embedding(c["vocab_size"], c["hidden_size"], prefix="embed_")
            self.blocks = []
            for i, kind in enumerate(kinds):
                block = Lfm2Block(c, kind, i < c["num_dense_layers"], experts_held, remat,
                                  prefix=f"layer{i}_")
                self.register_child(block, f"layer{i}")
                self.blocks.append(block)
            self.norm = RMSNorm(c["hidden_size"], c["norm_eps"], prefix="norm_")

    def forward(self, token_ids):
        x = self.embed(token_ids)
        for block in self.blocks:
            x = block(x)
        return self.norm(x)


class Lfm2ForCausalLM(CausalLM):
    """:class:`Lfm2Model` and the head tied to its embedding: token ids
    ``[B, S]`` → logits ``[B, S, vocab]`` (``vocab_size`` may be this chip's
    slice: the same rows embed the ids and predict them).

    :meth:`rescale_residual_writers`: after ``initialize``, every projection
    that writes into the residual stream (the convolution's ``W_out``,
    attention's ``W_o``, the dense and routed experts' ``W_down``) is
    divided by the square root of twice the PUBLISHED depth."""

    def __init__(self, config, experts_held=None, remat=False, prefix=None, params=None):
        super().__init__(
            lambda prefix: Lfm2Model(config, experts_held, remat, prefix=prefix),
            config["vocab_size"], config["hidden_size"], SCOPE_HEAD, tie_embedding=True,
            prefix=prefix, params=params)

    def rescale_residual_writers(self, num_layers):
        factor = (2.0 * float(num_layers)) ** -0.5
        for name, p in self.collect_params().items():
            if name.endswith(("out_proj_weight", "o_weight", "down_weight")):
                p.set_data(p.data() * factor)
        return self

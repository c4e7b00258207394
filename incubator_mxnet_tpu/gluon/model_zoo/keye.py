"""Keye-VL-2.0's language model: a pre-norm decoder whose every layer is
grouped-query attention over the keys a learned indexer selects for each
query, then softmax-routed SwiGLU experts (docs/keye.md has the equations).

Built from the published ``config.json`` keys (``model_type`` ``KeyeVL2``;
the indexer's sizes under ``sa_config``); trained by ``SPMDTrainer`` exactly
as ``BERTForPretrain``, ``Xing4ForCausalLM`` and ``NemotronHForCausalLM`` are.
What this file adds to the program:

* :class:`SparseAttention` — the layer of ``ops.sparse_attention``: fused
  q | k | v projection, per-head RMSNorm of q and k, rotary positions in
  three streams (``mrope_section``), the lightning indexer, the per-query
  top-``topk`` selection, the core through the attention dispatcher under
  that selection, and the indexer's own loss.
* :class:`KeyeBlock` / :class:`KeyeModel` / :class:`KeyeForCausalLM`.  The
  expert layer is ``decoder.py``'s :class:`SparseExperts` with ``softmax``
  scoring and no shared expert: the router covers ALL ``num_experts`` and
  this chip computes the part of the sum that the experts it HOLDS give
  (``experts_held = (first, count)``).

A layer hands the step two loss terms beside its output (the router's
load-balance term times ``router_aux_loss_coef``, and the indexer's loss)
and two counters (``sparse_attn_tiles_live`` / ``_causal``), through
``run_layer`` and the trainer's MoE frame.

``remat=True`` wraps every layer in ``jax.checkpoint`` under a jit trace
(``SPMDTrainer``): a layer keeps its input ``[B, S, d]``, the attention
core's output and log-sum-exp and the int8 selection (``decoder.KEPT``), and
the backward pass runs the rest of its forward again: index scores,
projections, experts, not the core's kernel and not the bisection.

Not built: the vision tower (its widths are not in the language model's
config).  The model takes text; ``positions`` [3, B, S] are there for the
day patch embeddings are spliced into the sequence, and under text the three
streams are the token's index.
"""
from __future__ import annotations

from ..block import HybridBlock
from ..nn import Embedding
from . import moe as _moe
from .decoder import CausalLM, RMSNorm, SparseExperts, _scope, run_layer

__all__ = ["SparseAttention", "KeyeBlock", "KeyeModel", "KeyeForCausalLM"]

# jax.named_scope names inside the compiled step (chipbench's per-layer
# metrics select device operations by them)
SCOPE_ATTN = "keye.attn"
SCOPE_MOE = "keye.moe"
SCOPE_HEAD = "keye.head"


class SparseAttention(HybridBlock):
    """Causal grouped-query self-attention over the keys the indexer selects,
    ``[B, S, d] → ([B, S, d], index loss, live tiles, causal tiles, the
    selection)``; the math is ``ops.sparse_attention.sparse_attention``.  q, k
    and v are one weight (q rows, then k, then v), the indexer's ``q_I | k_I |
    w`` another."""

    def __init__(self, units, num_heads, kv_heads, head_dim, sa_config,
                 rms_eps=1e-6, rope_theta=10000.0, mrope_section=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        h, h_kv, dh = int(num_heads), int(kv_heads), int(head_dim)
        sa = dict(sa_config)
        hi, di = int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"])
        if sa.get("indexer_num_kv_heads", 1) != 1:
            raise ValueError("only ONE index key head is built "
                             f"(indexer_num_kv_heads {sa['indexer_num_kv_heads']})")
        self._kw = dict(
            num_heads=h, kv_heads=h_kv, head_dim=dh, index_heads=hi,
            index_dim=di, topk=int(sa["topk"]), q_chunk=int(sa["q_chunk_size"]),
            kv_chunk=int(sa["kv_chunk_size"]), eps=float(rms_eps),
            rope_theta=float(rope_theta),
            mrope_section=None if mrope_section is None else tuple(mrope_section),
            scope=SCOPE_ATTN)
        with self.name_scope():
            get = self.params.get
            self.qkv_weight = get("qkv_weight", shape=((h + 2 * h_kv) * dh, units))
            self.q_norm_gamma = get("q_norm_gamma", shape=(dh,), init="ones")
            self.k_norm_gamma = get("k_norm_gamma", shape=(dh,), init="ones")
            self.o_weight = get("o_weight", shape=(units, h * dh))
            self.index_weight = get("index_weight", shape=(hi * di + di + hi, units))
            self.index_norm_gamma = get("index_norm_gamma", shape=(di,), init="ones")
            self.index_norm_beta = get("index_norm_beta", shape=(di,), init="zeros")

    def hybrid_forward(self, F, x, positions=None, *, qkv_weight, q_norm_gamma,
                       k_norm_gamma, o_weight, index_weight, index_norm_gamma,
                       index_norm_beta):
        return F.contrib.sparse_attention(
            x, qkv_weight, q_norm_gamma, k_norm_gamma, o_weight, index_weight,
            index_norm_gamma, index_norm_beta, positions=positions, **self._kw)


class KeyeBlock(HybridBlock):
    """One layer: ``x ← x + Attn(RMSNorm(x))``, ``x ← x + Experts(RMSNorm(x))``."""

    def __init__(self, config, experts_held=None, remat=False, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        c = config
        d, eps = c["hidden_size"], c["rms_norm_eps"]
        self._remat = bool(remat)
        self._balance_coef = float(c.get("router_aux_loss_coef", 0.001))
        with self.name_scope():
            self.attn_norm = RMSNorm(d, eps, prefix="attn_norm_")
            self.attn = SparseAttention(
                d, c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"], c["sa_config"], eps, c["rope_theta"],
                (c.get("rope_scaling") or {}).get("mrope_section"),
                prefix="attn_")
            self.ffn_norm = RMSNorm(d, eps, prefix="ffn_norm_")
            self.ffn = SparseExperts(
                d, c["moe_intermediate_size"], c["num_experts"],
                c["num_experts_per_tok"], experts_held, n_shared_experts=0,
                norm_topk=c["norm_topk_prob"], scope=SCOPE_MOE,
                scoring="softmax", prefix="moe_")

    def _body(self, x, positions):
        a, index_loss, live, causal, selection = self.attn(self.attn_norm(x), positions)
        x = x + a
        h = self.ffn_norm(x)
        y, stats, balance = self.ffn(h)
        # the trainer differentiates the SUM of the batch's losses and divides
        # by the batch: the layer's terms are means, so they join it once a row
        side = {"loss": (index_loss + balance * self._balance_coef) * float(x.shape[0]),
                "counters": {"sparse_attn_tiles_live": live,
                             "sparse_attn_tiles_causal": causal}}
        # what a comparison with a reference, or a set-up that evens the
        # routers' loads, asks to look at
        taps = {name: value for name, value in (("selection", selection), ("router_input", h))
                if _moe.tapped(name)}
        if taps:
            side["taps"] = taps
        return x + y, stats, side

    def forward(self, x, positions=None):
        return run_layer(lambda x: self._body(x, positions), x, self._remat, self.ffn)


class KeyeModel(HybridBlock):
    """Embedding → ``num_hidden_layers`` blocks → final RMSNorm: token ids
    ``[B, S]`` (and, for other than text, positions ``[3, B, S]``) → hidden
    states ``[B, S, d]``."""

    def __init__(self, config, experts_held=None, remat=False, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        c = config
        if c.get("decoder_sparse_step", 1) != 1 or c.get("mlp_only_layers"):
            raise ValueError("only a stack whose every layer has experts is built "
                             "(decoder_sparse_step 1, mlp_only_layers [])")
        if c.get("hidden_act", "silu") != "silu":
            raise ValueError(f"hidden_act {c['hidden_act']!r}: only silu")
        with self.name_scope():
            self.embed = Embedding(c["vocab_size"], c["hidden_size"], prefix="embed_")
            self.blocks = []
            for i in range(c["num_hidden_layers"]):
                block = KeyeBlock(c, experts_held=experts_held, remat=remat,
                                  prefix=f"layer{i}_")
                self.register_child(block, f"layer{i}")
                self.blocks.append(block)
            self.norm = RMSNorm(c["hidden_size"], c["rms_norm_eps"], prefix="norm_")

    def forward(self, token_ids, positions=None):
        x = self.embed(token_ids)
        for block in self.blocks:
            x = block(x, positions)
        return self.norm(x)


class KeyeForCausalLM(CausalLM):
    """:class:`KeyeModel` and the untied output head: token ids ``[B, S]`` →
    logits ``[B, S, vocab]`` (``vocab_size`` may be this chip's slice).

    :meth:`rescale_residual_writers` is the family's initialisation rule:
    after ``initialize``, every projection that writes into the residual
    stream (``W_o``, the experts' ``W_down``) is divided by the square root
    of twice the PUBLISHED depth."""

    def __init__(self, config, experts_held=None, remat=False, prefix=None,
                 params=None):
        super().__init__(
            lambda prefix: KeyeModel(config, experts_held, remat, prefix=prefix),
            config["vocab_size"], config["hidden_size"], SCOPE_HEAD,
            prefix=prefix, params=params)

    def forward(self, token_ids, positions=None):
        hidden = self.model(token_ids, positions)
        with _scope(self._head_scope):
            return self.lm_head(hidden)

    def rescale_residual_writers(self, num_layers):
        factor = (2.0 * float(num_layers)) ** -0.5
        for name, p in self.collect_params().items():
            if name.endswith(("o_weight", "down_weight")):
                p.set_data(p.data() * factor)
        return self

"""Xing4.0: a pre-norm decoder whose residual path is ``hc_mult`` streams
mixed by manifold-constrained hyper-connections, with multi-head latent
attention and dropless sigmoid-routed experts (docs/xing4.md has the
equations).

Built from the published ``config.json`` keys; trained by ``SPMDTrainer``
exactly as ``BERTForPretrain`` is.  What this file adds to the program:

* :class:`HyperConnection` — the residual mix, used twice a block (before the
  attention and before the feed-forward sublayer).
* :class:`LatentAttention` — the low-rank query and key/value projections
  with an RMSNorm between, rotary (YaRN) on the shared rope part, the core
  through the attention dispatcher with 192-wide queries and keys and
  128-wide values.
* :class:`SparseExperts` (``decoder.py``: ``nemotron_h`` shares it) —
  routes over ALL ``n_routed_experts`` and computes the part of the sum given
  by the experts this chip HOLDS (``experts_held = (first, count)``), plus
  the shared expert, which every chip computes alike.  Nothing stands in for
  the absent chips: on one chip the layer runs without its exchange, and the
  partial sum goes on.
* :class:`Xing4Block` / :class:`Xing4Model` / :class:`Xing4ForCausalLM`.

``remat=True`` wraps every block in ``jax.checkpoint`` under a jit trace
(``SPMDTrainer``): a block keeps its input state, ``[n, B, S, d]``, and the
attention core's output and log-sum-exp (``decoder.KEPT``), and the backward
pass runs the rest of its forward again.

Not built: the multi-token-prediction module (``num_nextn_predict_layers``
must be 0).  How its input is formed from several residual streams is not
in the published config, and nothing in this program could use it.
"""
from __future__ import annotations

import math

import numpy as _np

from ... import initializer as _init
from ..block import HybridBlock
from ..nn import Embedding
from .decoder import CausalLM, RMSNorm, SparseExperts, SwiGLU, run_layer

__all__ = ["HyperConnection", "LatentAttention", "SwiGLU", "SparseExperts",
           "Xing4Block", "Xing4Model", "Xing4ForCausalLM",
           "mhc_offset_init"]

# jax.named_scope names inside the compiled step (chipbench's per-layer
# metrics select device operations by them)
SCOPE_ATTN = "xing.attn"
SCOPE_MOE = "xing.moe"
SCOPE_MIX = "xing.mix"
SCOPE_HEAD = "xing.head"


def mhc_offset_init(n, off_diagonal=-8.0):
    """``b_pre | b_post | b_res`` for which, at ``α·m = 0``, ``H_pre`` is
    ``1/n`` on every stream (the sublayer reads the streams' mean),
    ``H_post`` is 1 (its output is added to every stream) and ``H_res`` is
    close to the identity (a dominant diagonal): the block starts as a plain
    pre-norm residual block on each stream."""
    b_pre = _np.full((n,), -math.log(n - 1.0) if n > 1 else 30.0)
    b_post = _np.zeros((n,))
    b_res = _np.full((n, n), off_diagonal) * (1.0 - _np.eye(n))
    return _np.concatenate([b_pre, b_post, b_res.reshape(-1)]).astype("float32")


class HyperConnection(HybridBlock):
    """The residual mix of one sublayer.  ``mix(state)`` gives ``(u, H_post,
    H_res)``: the sublayer's input and the coefficients that
    :meth:`merge` writes its output back with."""

    def __init__(self, units, hc_mult=4, sinkhorn_iters=20, rms_eps=1e-6,
                 hc_eps=1e-6, clamp=(-30.0, 30.0), alpha_init=0.01,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        n = int(hc_mult)
        self._kw = dict(sinkhorn_iters=int(sinkhorn_iters), eps=float(rms_eps),
                        hc_eps=float(hc_eps), clamp_min=float(clamp[0]),
                        clamp_max=float(clamp[1]), scope=SCOPE_MIX)
        with self.name_scope():
            self.proj_weight = self.params.get(
                "proj_weight", shape=(n * n + 2 * n, n * units))
            # names without a weight/bias suffix: their initializers are
            # theirs, not the net's (initializer.py dispatches on the suffix)
            self.alpha = self.params.get(
                "alpha", shape=(3,), init=_init.Constant(alpha_init))
            self.offset = self.params.get(
                "offset", shape=(n * n + 2 * n,),
                init=_init.Constant(mhc_offset_init(n)))

    def hybrid_forward(self, F, state, proj_weight, alpha, offset):
        return F.contrib.mhc_pre(state, proj_weight, alpha, offset, **self._kw)

    def merge(self, state, y, h_post, h_res):
        from ... import ndarray as F

        return F.contrib.mhc_post(state, y, h_post, h_res, scope=SCOPE_MIX)


class LatentAttention(HybridBlock):
    """Multi-head latent self-attention (causal), ``[B, S, d] → [B, S, d]``;
    the math is ``ops.attention.latent_attention``."""

    def __init__(self, units, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 rms_eps=1e-6, rope_theta=10000.0, rope_scaling=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        h, dn, dr, dv = (int(num_heads), int(qk_nope_head_dim),
                         int(qk_rope_head_dim), int(v_head_dim))
        rs = dict(rope_scaling or {})
        if rs and rs.get("type", "yarn") != "yarn":
            raise ValueError(f"rope_scaling type {rs['type']!r}: only yarn")
        self._kw = dict(
            num_heads=h, qk_nope_dim=dn, qk_rope_dim=dr, v_dim=dv,
            eps=float(rms_eps), causal=True, rope_theta=float(rope_theta),
            yarn_factor=float(rs.get("factor", 1.0)),
            yarn_original=int(rs.get("original_max_position_embeddings", 4096)),
            yarn_beta_fast=float(rs.get("beta_fast", 32)),
            yarn_beta_slow=float(rs.get("beta_slow", 1)),
            yarn_mscale_=float(rs.get("mscale", 1.0)),
            yarn_mscale_all_dim=float(rs.get("mscale_all_dim", 0.0)),
            scope=SCOPE_ATTN)
        with self.name_scope():
            get = self.params.get
            self.q_a_weight = get("q_a_weight", shape=(q_lora_rank, units))
            self.q_a_norm_gamma = get("q_a_norm_gamma", shape=(q_lora_rank,),
                                      init="ones")
            self.q_b_weight = get("q_b_weight",
                                  shape=(h * (dn + dr), q_lora_rank))
            self.kv_a_weight = get("kv_a_weight",
                                   shape=(kv_lora_rank + dr, units))
            self.kv_a_norm_gamma = get("kv_a_norm_gamma",
                                       shape=(kv_lora_rank,), init="ones")
            self.kv_b_weight = get("kv_b_weight",
                                   shape=(h * (dn + dv), kv_lora_rank))
            self.o_weight = get("o_weight", shape=(units, h * dv))

    def hybrid_forward(self, F, x, q_a_weight, q_a_norm_gamma, q_b_weight,
                       kv_a_weight, kv_a_norm_gamma, kv_b_weight, o_weight):
        return F.contrib.latent_attention(
            x, q_a_weight, q_a_norm_gamma, q_b_weight, kv_a_weight,
            kv_a_norm_gamma, kv_b_weight, o_weight, **self._kw)


class Xing4Block(HybridBlock):
    """One decoder layer on the residual state ``[n, B, S, d]``: latent
    attention, then a SwiGLU (the leading dense layers) or
    :class:`SparseExperts`, each behind its own mix and RMSNorm."""

    def __init__(self, config, dense, experts_held=None, remat=False,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        c = config
        d, eps = c["hidden_size"], c["rms_norm_eps"]
        self._remat = bool(remat)

        def mix(prefix):
            return HyperConnection(
                d, c["hc_mult"], c["hc_sinkhorn_iters"], eps, c["hc_eps"],
                (c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"]),
                prefix=prefix)

        with self.name_scope():
            self.attn_mix = mix("attn_mix_")
            self.attn_norm = RMSNorm(d, eps, prefix="attn_norm_")
            self.attn = LatentAttention(
                d, c["num_attention_heads"], c["q_lora_rank"],
                c["kv_lora_rank"], c["qk_nope_head_dim"],
                c["qk_rope_head_dim"], c["v_head_dim"], eps,
                c["rope_theta"], c.get("rope_scaling"), prefix="attn_")
            self.ffn_mix = mix("ffn_mix_")
            self.ffn_norm = RMSNorm(d, eps, prefix="ffn_norm_")
            if dense:
                self.ffn = SwiGLU(d, c["intermediate_size"], prefix="mlp_")
            else:
                self.ffn = SparseExperts(
                    d, c["moe_intermediate_size"], c["n_routed_experts"],
                    c["num_experts_per_tok"], experts_held,
                    c["n_shared_experts"], c["routed_scaling_factor"],
                    c["norm_topk_prob"],
                    bias_update_speed=c.get("bias_update_speed", 0.001),
                    scope=SCOPE_MOE, prefix="moe_")
        self._sparse = not dense

    def _body(self, state):
        u, h_post, h_res = self.attn_mix(state)
        state = self.attn_mix.merge(
            state, self.attn(self.attn_norm(u)), h_post, h_res)
        u, h_post, h_res = self.ffn_mix(state)
        y = self.ffn(self.ffn_norm(u))
        stats = None
        if self._sparse:
            y, stats = y
        return self.ffn_mix.merge(state, y, h_post, h_res), stats

    def forward(self, state):
        return run_layer(self._body, state, self._remat,
                         self.ffn if self._sparse else None)


class Xing4Model(HybridBlock):
    """Embedding → ``first_k_dense_replace`` dense blocks → expert blocks →
    final RMSNorm: token ids ``[B, S]`` → hidden states ``[B, S, d]``.  The
    embedding is copied into all ``hc_mult`` streams; the streams are summed
    before the final norm."""

    def __init__(self, config, experts_held=None, remat=False, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        c = config
        if c.get("num_nextn_predict_layers", 0):
            raise ValueError("the multi-token-prediction module is not built: "
                             "num_nextn_predict_layers must be 0")
        if c.get("n_group", 1) != 1 or c.get("scoring_func") != "sigmoid":
            raise ValueError("only sigmoid scoring with n_group 1 is built")
        self._streams = int(c["hc_mult"])
        with self.name_scope():
            self.embed = Embedding(c["vocab_size"], c["hidden_size"],
                                   prefix="embed_")
            self.blocks = []
            for i in range(c["num_hidden_layers"]):
                block = Xing4Block(
                    c, dense=i < c["first_k_dense_replace"],
                    experts_held=experts_held, remat=remat,
                    prefix=f"layer{i}_")
                self.register_child(block, f"layer{i}")
                self.blocks.append(block)
            self.norm = RMSNorm(c["hidden_size"], c["rms_norm_eps"],
                                prefix="norm_")

    def forward(self, token_ids):
        from ... import ndarray as F

        x = self.embed(token_ids)                                # [B, S, d]
        # the state is stream-major, [n, B, S, d]: stream i is state[i]
        # (ops/hyper_connections.py says why)
        state = F.stack(*([x] * self._streams), axis=0)
        for block in self.blocks:
            state = block(state)
        return self.norm(F.sum(state, axis=0))


class Xing4ForCausalLM(CausalLM):
    """:class:`Xing4Model` and the untied output head: token ids ``[B, S]``
    → logits ``[B, S, vocab]`` (``vocab_size`` may be this chip's slice)."""

    def __init__(self, config, experts_held=None, remat=False, prefix=None,
                 params=None):
        super().__init__(
            lambda prefix: Xing4Model(config, experts_held, remat, prefix=prefix),
            config["vocab_size"], config["hidden_size"], SCOPE_HEAD,
            prefix=prefix, params=params)

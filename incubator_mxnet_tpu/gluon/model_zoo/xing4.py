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
* :class:`SparseExperts` — routes over ALL ``n_routed_experts`` and computes
  the part of the sum given by the experts this chip HOLDS
  (``experts_held = (first, count)``), plus the shared expert, which every
  chip computes alike.  Nothing stands in for the absent chips: on one chip
  the layer runs without its exchange, and the partial sum goes on.
* :class:`Xing4Block` / :class:`Xing4Model` / :class:`Xing4ForCausalLM`.

``remat=True`` wraps every block in ``jax.checkpoint`` under a jit trace
(``SPMDTrainer``): a block keeps only its input state, ``[n, B, S, d]``, and
the backward pass runs its forward again.

Not built: the multi-token-prediction module (``num_nextn_predict_layers``
must be 0).  How its input is formed from several residual streams is not
in the published config, and nothing in this program could use it.
"""
from __future__ import annotations

import math

import numpy as _np

from ... import initializer as _init
from ..block import HybridBlock, collect_aux_update
from ..nn import Dense, Embedding
from . import moe as _moe

__all__ = ["HyperConnection", "LatentAttention", "SwiGLU", "SparseExperts",
           "Xing4Block", "Xing4Model", "Xing4ForCausalLM",
           "mhc_offset_init"]

# jax.named_scope names inside the compiled step (chipbench's per-layer
# metrics select device operations by them)
SCOPE_ATTN = "xing.attn"
SCOPE_MOE = "xing.moe"
SCOPE_MIX = "xing.mix"
SCOPE_HEAD = "xing.head"


def _scope(name):
    import jax

    return jax.named_scope(name)


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


class RMSNorm(HybridBlock):
    def __init__(self, units, eps=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._eps = float(eps)
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(units,), init="ones")

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, eps=self._eps)


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


class SparseExperts(HybridBlock):
    """Dropless routed experts + the shared expert, for the experts held.

    ``experts_held = (first, count)``: this chip holds the routed experts
    ``first .. first + count - 1`` of ``num_experts`` (default: all).  The
    router and its selection bias cover all ``num_experts``; a pair routed
    to an expert held elsewhere adds nothing here.

    The selection bias is the ``noaux_tc`` balancing buffer: no gradient
    reaches it; in training every step moves it by ``bias_update_speed``
    towards balance, ``b_e += γ · sign(mean load − load_e)`` over ALL the
    experts (:meth:`balanced_bias`; 0 freezes it).  It stays float32 under
    ``cast``: a step of 0.001 is below bf16's resolution at 0.5.

    ``forward`` returns ``(y, stats)``: ``stats`` is a float32 vector
    ``(rows routed here, least load, greatest load)`` over the experts held
    followed by the load of each of all the experts, which
    :class:`Xing4Block` hands to the trainer's MoE frame and to the rule."""

    def __init__(self, units, expert_width, num_experts, top_k,
                 experts_held=None, n_shared_experts=1, routed_scaling=1.0,
                 norm_topk=True, bias_update_speed=0.001,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._bias_speed = float(bias_update_speed)
        first, count = experts_held or (0, num_experts)
        if not (0 <= first and first + count <= num_experts and count > 0):
            raise ValueError(f"experts_held {experts_held} outside "
                             f"0..{num_experts}")
        if top_k > num_experts:
            raise ValueError(f"top_k {top_k} > num_experts {num_experts}")
        self._kw = dict(num_experts=int(num_experts), top_k=int(top_k),
                        first_expert=int(first),
                        routed_scaling=float(routed_scaling),
                        norm_topk=bool(norm_topk))
        with self.name_scope():
            get = self.params.get
            self.router_weight = get("router_weight",
                                     shape=(num_experts, units))
            # the noaux_tc selection bias: a buffer that a balancing rule
            # outside the gradient would move; no gradient reaches it
            self.select_bias = get("select_bias", shape=(num_experts,),
                                   init="zeros", grad_req="null")
            self.experts_gate_up_weight = get(
                "experts_gate_up_weight",
                shape=(count, units, 2 * expert_width))
            self.experts_down_weight = get(
                "experts_down_weight", shape=(count, expert_width, units))
            self.shared_expert = (
                SwiGLU(units, expert_width * int(n_shared_experts),
                       prefix="shared_")
                if n_shared_experts else None)

    def hybrid_forward(self, F, x, router_weight, select_bias,
                       experts_gate_up_weight, experts_down_weight):
        y, rows, load_min, load_max, load_all = F.contrib.moe_ffn_dropless(
            x, router_weight, select_bias, experts_gate_up_weight,
            experts_down_weight, scope=SCOPE_MOE, **self._kw)
        if self.shared_expert is not None:
            with _scope(SCOPE_MOE + ".shared"):
                y = y + self.shared_expert(x)
        return y, F.concat(F.stack(rows, load_min, load_max), load_all, dim=0)

    def cast(self, dtype):
        super().cast(dtype)
        self.select_bias.cast("float32")
        return self

    def balanced_bias(self, bias, load_all):
        """One step of the ``noaux_tc`` rule on raw arrays: the bias of an
        expert with less than the mean load rises by ``bias_update_speed``,
        that of one with more falls."""
        import jax.numpy as jnp

        return bias + self._bias_speed * jnp.sign(load_all.mean() - load_all)


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
                    prefix="moe_")
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
        import jax

        from ... import autograd
        from ...ndarray.ndarray import NDArray

        traced = (isinstance(state._data, jax.core.Tracer)
                  and not autograd.is_recording())
        if self._remat and traced:
            def body(data):
                out, stats = self._body(NDArray(data))
                return out._data, None if stats is None else stats._data

            out, stats = jax.checkpoint(body)(state._data)
            out = NDArray(out)
        else:
            out, stats = self._body(state)
            stats = None if stats is None else stats._data
        if stats is not None:
            # outside the checkpoint: what the frame and the aux collector
            # keep must belong to the step's own trace
            _moe.register_metrics({
                "rows_routed_here": stats[0], "expert_load_min": stats[1],
                "expert_load_max": stats[2], "expert_load_all": stats[3:],
                "tokens_dropped": 0.0 * stats[0]})   # dropless
            if autograd.is_training() and self.ffn._bias_speed:
                bias = self.ffn.select_bias
                collect_aux_update(bias, NDArray(self.ffn.balanced_bias(
                    bias.data()._data, stats[3:])))
        return out


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


class Xing4ForCausalLM(HybridBlock):
    """:class:`Xing4Model` and the untied output head: token ids ``[B, S]``
    → logits ``[B, S, vocab]`` (``vocab_size`` may be this chip's slice)."""

    def __init__(self, config, experts_held=None, remat=False, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.model = Xing4Model(config, experts_held, remat,
                                    prefix="model_")
            self.lm_head = Dense(config["vocab_size"], use_bias=False,
                                 flatten=False,
                                 in_units=config["hidden_size"],
                                 prefix="lm_head_")

    def forward(self, token_ids):
        hidden = self.model(token_ids)
        with _scope(SCOPE_HEAD):
            return self.lm_head(hidden)

"""Plain reference of Keye-VL-2.0's language model: forward pass, the three
terms of its training loss and (by ``jax.grad`` of :func:`step_loss`) its
gradients.

Straightforward ``jax.numpy`` in float32 with matmul precision "highest": no
kernels, no tiles, no bisection, no sorting of routed pairs, no recomputation,
no AMP.  Index scores are a dense einsum, the selection is ``jax.lax.top_k``
on the causal-masked row, attention is a dense soft-max under the selection
mask with its keys and values repeated over the group, experts are a loop
over the experts held, each applied to every token and weighted by a gate
that is zero where the token did not choose it.  Queries are taken in blocks
(``query_block``, by ``jax.lax.map``) only so that no ``[heads, S, S]`` array
exists at S 8192; every row is computed whole.  Imports nothing of the program's model code; it
reads the program's parameters by name.

Equations (``x`` [S, d]; a layer is ``x ← x + Attn(RMSNorm(x))``, ``x ← x +
Experts(RMSNorm(x))``; 32 query heads on 4 key/value heads of 128):

attention:  ``[q | k | v] = x W_qkv``; RMSNorm over each head's 128 dims of q
    and of k (a gain [128] each); rotary on all 128 dims, rotate-half pairs
    ``(i, i + 64)``, θ 1e7, frequency pair ``i`` reading position stream 0 /
    1 / 2 by ``mrope_section`` [16, 24, 24] (text: the streams are equal);
    ``o[t, h] = Σ_{s ∈ S_t} softmax_s(q[t, h] · k[s, h // 8] · 128^-½) v[s, h // 8]``;
    ``out = concat_h(o) W_o``.
indexer (``x̄ = stop_gradient(x)``):  ``[q_I | k_I | w] = x̄ W_index`` (16 heads
    of 64, ONE key of 64, 16 weights); ``k_I ← LayerNorm(k_I)``; the same
    rotary on the 64 dims of q_I and k_I (the sections halved);
    ``I[t, s] = Σ_j 16^-½ w[t, j] · relu(q_I[t, j] · k_I[s] · 64^-½)``, ``s ≤ t``;
    ``S_t`` = the ``topk`` visible keys of largest ``I[t, s]`` (all while
    ``t + 1 ≤ topk``); no gradient passes through the selection.
indexer's loss:  ``p[t, s] = (1/32) Σ_h`` the head's probability, detached;
    ``L_I = mean_t KL(p[t, ·] ‖ softmax_{s ∈ S_t} I[t, s])``, summed over layers.
experts:  ``P = softmax(W_r x)`` over ALL the experts; the 8 largest; gates
    ``P_i / Σ_selected P_j``; ``y = Σ_held g_i W_down,i (silu(W_gate,i x) ⊙
    W_up,i x)``; balance term ``E · Σ_e f_e P̄_e`` (``f_e`` the share of pairs
    routed to ``e``, detached), summed over layers.
step loss:  ``L_LM + router_aux_loss_coef · L_balance + L_I``.

Departures from the published model, each also under ``assumed`` in
``chipbench/configs/keye-vl-2.0-30b-a3b.json``: the per-head q/k RMSNorm, the
indexer's LayerNorm, rotary and two scalings, the contiguous split of the
frequency pairs over the streams and the indexer's loss are the family's and
DeepSeek-V3.2's conventions (the config is silent); q, k, v are stored as one
weight, the indexer's three projections as another, the routed experts' as
``[count, d, 2·width]`` (gate | up) and ``[count, width, d]``; experts held
elsewhere add nothing (``experts_held``); the vision tower is absent.
"""
import jax
import jax.numpy as jnp
import numpy as np

from .common import by_suffix, layer_norm


def rms_norm(x, gamma, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def rotary_angles(positions, dim, theta, sections):
    """Angles [B, S, dim/2] from position streams [3, B, S]: frequency pair
    ``i`` turns by ``positions[stream(i)] · theta^(-2i/dim)``, the streams
    split contiguously over the pairs in the ratio of ``sections``."""
    half = dim // 2
    inv_freq = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / dim)
    share = np.asarray(sections, np.int64) * half // int(np.sum(sections))
    stream = np.repeat(np.arange(len(share)), share)                      # [half]
    return jnp.moveaxis(positions, 0, -1)[..., stream] * inv_freq


def rotate_half(x, angles):
    """``x`` [B, S, H, dim]: the pairs ``(x[i], x[i + dim/2])`` turned by
    ``angles`` [B, S, dim/2]."""
    a, b = jnp.split(x, 2, axis=-1)
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1).astype(x.dtype)


def attention(p, pre, x, positions, c, query_block):
    """The attention sublayer on normed ``x`` [B, S, d]: ``(out, the
    indexer's loss, the selection [B, S, S] bool)``."""
    bsz, s, _ = x.shape
    h, h_kv, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    sa, eps, theta = c["sa_config"], c["rms_norm_eps"], float(c["rope_theta"])
    hi, di, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], min(sa["topk"], s)
    sections = c["rope_scaling"]["mrope_section"]

    q, k, v = jnp.split(x @ p[pre + "qkv_weight"].T, [h * hd, (h + h_kv) * hd], axis=-1)
    angles = rotary_angles(positions, hd, theta, sections)
    q = rotate_half(rms_norm(q.reshape(bsz, s, h, hd), p[pre + "q_norm_gamma"], eps), angles)
    k = rotate_half(rms_norm(k.reshape(bsz, s, h_kv, hd), p[pre + "k_norm_gamma"], eps), angles)
    k = jnp.repeat(k, h // h_kv, axis=2)
    v = jnp.repeat(v.reshape(bsz, s, h_kv, hd), h // h_kv, axis=2)

    idx = jax.lax.stop_gradient(x) @ p[pre + "index_weight"].T
    q_i, k_i, w_i = jnp.split(idx, [hi * di, (hi + 1) * di], axis=-1)
    angles_i = rotary_angles(positions, di, theta, sections)
    q_i = rotate_half(q_i.reshape(bsz, s, hi, di), angles_i)
    k_i = layer_norm(k_i, p[pre + "index_norm_gamma"], p[pre + "index_norm_beta"], eps)
    k_i = rotate_half(k_i[:, :, None], angles_i)[:, :, 0]

    key_pos = jnp.arange(s)
    block = query_block if s % query_block == 0 else s
    blocks = lambda a: jnp.moveaxis(a.reshape((bsz, s // block, block) + a.shape[2:]), 1, 0)

    def rows(args):   # whole rows, a block of queries at a time
        q0, q_b, qi_b, wi_b = args
        seen = key_pos[None, :] <= (q0 + jnp.arange(block))[:, None]
        index = jnp.einsum("bqhd,bkd->bqhk", qi_b, k_i) * di ** -0.5
        index = jnp.sum(jax.nn.relu(index) * wi_b[..., None], axis=2) * hi ** -0.5
        index = jnp.where(seen, index, -jnp.inf)                         # [B, q, S]
        _, best = jax.lax.top_k(index, topk)
        chosen = jnp.zeros(index.shape, bool).at[
            jnp.arange(bsz)[:, None, None], jnp.arange(block)[None, :, None], best
        ].set(True) & seen
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_b, k) * hd ** -0.5
        prob = jax.nn.softmax(jnp.where(chosen[:, None], scores, -jnp.inf), axis=-1)
        target = jax.lax.stop_gradient(prob.mean(axis=1))                # [B, q, S]
        log_index = jax.nn.log_softmax(jnp.where(chosen, index, -jnp.inf), axis=-1)
        held = chosen & (target > 0)
        kl = jnp.where(held, target * (jnp.log(jnp.where(held, target, 1.0))
                                       - jnp.where(held, log_index, 0.0)), 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", prob, v), kl.sum(-1), chosen

    outs, losses, selections = jax.lax.map(
        rows, (jnp.arange(0, s, block), blocks(q), blocks(q_i), blocks(w_i)))
    unblock = lambda a: jnp.moveaxis(a, 0, 1).reshape((bsz, s) + a.shape[3:])
    out = unblock(outs).reshape(bsz, s, h * hd) @ p[pre + "o_weight"].T
    return out, losses.mean(), unblock(selections)


def experts(p, pre, x, c, experts_held):
    """The share of the expert layer that ``experts_held = (first, count)``
    gives: ``(y, the balance term, rows routed to the experts held)``."""
    first, count = experts_held
    top_k, width = c["num_experts_per_tok"], c["moe_intermediate_size"]
    prob = jax.nn.softmax(x @ p[pre + "router_weight"].T, axis=-1)       # [..., E]
    chosen, idx = jax.lax.top_k(prob, top_k)
    gates = chosen / chosen.sum(-1, keepdims=True) if c["norm_topk_prob"] else chosen
    y = jnp.zeros_like(x)
    for j in range(count):
        gate = jnp.sum(jnp.where(idx == first + j, gates, 0.0), -1)      # 0: not chosen
        up = x @ p[pre + "experts_gate_up_weight"][j]
        y = y + gate[..., None] * ((jax.nn.silu(up[..., :width]) * up[..., width:])
                                   @ p[pre + "experts_down_weight"][j])
    n_experts = prob.shape[-1]
    routed = jax.nn.one_hot(idx, n_experts).sum(-2).reshape(-1, n_experts)   # [T, E]
    share = jax.lax.stop_gradient(routed.sum(0) / routed.sum())
    balance = n_experts * jnp.sum(share * prob.reshape(-1, n_experts).mean(0))
    return y, balance, routed[:, first:first + count].sum()


def forward(named_params, tok, *, config, experts_held, positions=None, query_block=256,
            dtype=jnp.float32, with_terms=False):
    """Logits [B, S, V] for token ids ``tok`` [B, S] (``positions`` [3, B, S];
    None is text: every stream the token's index).  ``with_terms`` also
    returns ``{"index_loss", "balance", "selections" [layers, B, S, S] bool,
    "rows_routed_here"}``, the loss terms summed over layers.  ``dtype``
    float32 is the reference; bfloat16 computes everything in bf16 at default
    precision (the reading that the comparison's tolerances must refuse)."""
    c = config
    precision = "highest" if dtype == jnp.float32 else "default"
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tok.shape[1])[None, None], (3,) + tok.shape)
    positions = positions.astype(jnp.float32)
    with jax.default_matmul_precision(precision):
        p = {k: v.astype(dtype) for k, v in by_suffix(named_params).items()}
        x = p["model_embed_weight"][tok]
        eps = c["rms_norm_eps"]
        index_loss = balance = rows = 0.0
        selections = []
        for l in range(c["num_hidden_layers"]):
            pre = f"model_layer{l}_"
            a, loss_l, chosen = attention(p, pre + "attn_", rms_norm(x, p[pre + "attn_norm_gamma"], eps),
                                          positions, c, query_block)
            x = x + a.astype(dtype)
            y, balance_l, rows_l = experts(p, pre + "moe_", rms_norm(x, p[pre + "ffn_norm_gamma"], eps),
                                           c, experts_held)
            x = x + y
            index_loss, balance, rows = index_loss + loss_l, balance + balance_l, rows + rows_l
            selections.append(chosen)
        hidden = rms_norm(x, p["model_norm_gamma"], eps)
        logits = (hidden @ p["lm_head_weight"].T).astype(jnp.float32)
    if with_terms:
        return logits, {"index_loss": jnp.asarray(index_loss, jnp.float32),
                        "balance": jnp.asarray(balance, jnp.float32),
                        "selections": jnp.stack(selections), "rows_routed_here": rows}
    return logits


def loss_per_token(logits, labels):
    """Cross-entropy of each position against its label (the next token), [B, S]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def loss_terms(named_params, tok, labels, **kwargs):
    """``(L_LM, L_balance, L_I)``: the mean causal-LM loss, the routers'
    balance terms and the indexers' losses, each summed over layers."""
    logits, terms = forward(named_params, tok, with_terms=True, **kwargs)
    return loss_per_token(logits, labels).mean(), terms["balance"], terms["index_loss"]


def step_loss(named_params, tok, labels, *, balance_coef=0.001, **kwargs):
    """What a training step differentiates: ``L_LM + balance_coef · L_balance
    + L_I``."""
    lm, balance, index = loss_terms(named_params, tok, labels, **kwargs)
    return lm + balance_coef * balance + index

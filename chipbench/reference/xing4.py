"""Plain reference of Xing4.0's forward pass and causal-LM loss.

Straightforward ``jax.numpy`` in float32 with matmul precision "highest": no
kernels, no sorting of routed pairs, no recomputation, no AMP.  Experts are a
loop over the experts held, each applied to every token and weighted by a
gate that is zero where the token did not choose it.  Imports nothing of the
program's model code; it reads the program's parameters by name.

Equations (per token; ``X`` [n, d] is the residual state, n = ``hc_mult``):

    x̃ = vec(X);  m = (W_hc x̃) / sqrt(mean(x̃²) + rms_norm_eps)
    H_pre = σ(α_pre m[0:n] + b_pre);  H_post = 2σ(α_post m[n:2n] + b_post)
    H_res = SK(clip(α_res mat(m[2n:]) + b_res, clamp_min, clamp_max))
    SK(A): M = exp(A); hc_sinkhorn_iters times: M /= M·1 + hc_eps (rows),
           then M /= 1ᵀ·M + hc_eps (columns)
    u = H_preᵀ X;  y = F(RMSNorm(u));  X' = H_res X + H_post ⊗ y

Latent attention: ``c_q = RMSNorm(W_qa u)``; ``[q_nope | q_rope] = W_qb c_q``;
``[c_kv | k_rope] = W_kva u``; ``[k_nope | v] = W_kvb RMSNorm(c_kv)``; rotary
on the rope parts; causal softmax of ``(q·k)·(d_nope + d_rope)^-0.5·mscale²``.
Experts: ``s = σ(W_r u)``; the ``k`` largest ``s + b_sel``; gates
``scaling · s_i / Σ_selected s_j``; ``y = Σ_held g_i E_i(u) + E_shared(u)``.

Departures from the published model, each also under ``assumed`` in
``chipbench/configs/xing4.0-29b-a4b.json``:

* the ends of the residual path are not in ``config.json``: the embedding is
  copied into all n streams and the streams are summed before the final
  RMSNorm (the hyper-connections paper's convention);
* rotary and YaRN as the DeepSeek-V3 modeling file applies them (interleaved
  pairs; blended frequencies; ``mscale²`` on the softmax scale when
  ``mscale_all_dim`` is set); with ``mscale == mscale_all_dim`` the tables
  themselves are not scaled;
* gate and up projections are stored as one weight (gate rows first), the
  routed experts' as ``[count, d, 2·width]`` and ``[count, width, d]``;
* experts held elsewhere add nothing (``experts_held``): the partial sum is
  what goes on to the next layer, as it does in the program;
* the multi-token-prediction module is not built.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import by_suffix


def rms_norm(x, gamma, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def sinkhorn(a, iters, eps):
    m = jnp.exp(a)
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def mix(p, pre, state, c):
    """``(u, H_post, H_res)`` of the mix whose parameters start with ``pre``."""
    n = state.shape[-2]
    flat = state.reshape(state.shape[:-2] + (-1,))
    m = (flat @ p[pre + "proj_weight"].T
         / jnp.sqrt(jnp.mean(flat * flat, -1, keepdims=True) + c["rms_norm_eps"]))
    alpha, b = p[pre + "alpha"], p[pre + "offset"]
    h_pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + b[:n])
    h_post = 2 * jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + b[n:2 * n])
    logits = (alpha[2] * m[..., 2 * n:] + b[2 * n:]).reshape(m.shape[:-1] + (n, n))
    h_res = sinkhorn(jnp.clip(logits, c["mhc_h_res_clamp_min"], c["mhc_h_res_clamp_max"]),
                     c["hc_sinkhorn_iters"], c["hc_eps"])
    return jnp.einsum("...n,...nd->...d", h_pre, state), h_post, h_res


def merge(state, y, h_post, h_res):
    return (jnp.einsum("...nm,...md->...nd", h_res, state)
            + h_post[..., :, None] * y[..., None, :])


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary_angles(seq, dim, c):
    """Angles [seq, dim/2] and the factor on cos/sin (YaRN, DeepSeek-V3)."""
    theta, rs = float(c["rope_theta"]), c.get("rope_scaling") or {}
    half = dim // 2
    inv = 1.0 / theta ** (np.arange(half) * 2.0 / dim)
    factor, scale = float(rs.get("factor", 1.0)), 1.0
    if factor > 1:
        original = rs["original_max_position_embeddings"]

        def correction(rotations):
            return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

        low = max(math.floor(correction(rs["beta_fast"])), 0)
        high = min(math.ceil(correction(rs["beta_slow"])), dim - 1)
        ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
        inv = inv / factor * ramp + inv * (1 - ramp)
        scale = (yarn_mscale(factor, rs.get("mscale", 1.0))
                 / yarn_mscale(factor, rs.get("mscale_all_dim", 0.0)))
    return np.arange(seq)[:, None] * inv[None, :], scale


def rotate(x, angles, scale):
    """Interleaved pairs of ``x`` [B, S, H, dim] rotated; laid out as the
    modeling file leaves them (first halves, then second halves)."""
    cos = jnp.asarray(np.cos(angles) * scale, x.dtype)[None, :, None, :]
    sin = jnp.asarray(np.sin(angles) * scale, x.dtype)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def latent_attention(p, pre, x, c, head_block):
    b, s, _ = x.shape
    h, dn, dr, dv = (c["num_attention_heads"], c["qk_nope_head_dim"],
                     c["qk_rope_head_dim"], c["v_head_dim"])
    eps, rank = c["rms_norm_eps"], c["kv_lora_rank"]
    q = rms_norm(x @ p[pre + "q_a_weight"].T, p[pre + "q_a_norm_gamma"], eps)
    q = (q @ p[pre + "q_b_weight"].T).reshape(b, s, h, dn + dr)
    kva = x @ p[pre + "kv_a_weight"].T
    kv = rms_norm(kva[..., :rank], p[pre + "kv_a_norm_gamma"], eps)
    kv = (kv @ p[pre + "kv_b_weight"].T).reshape(b, s, h, dn + dv)
    angles, cs = rotary_angles(s, dr, c)
    q_rope = rotate(q[..., dn:], angles, cs)
    k_rope = rotate(kva[..., None, rank:], angles, cs)            # [B, S, 1, dr]
    rs = c.get("rope_scaling") or {}
    scale = ((dn + dr) ** -0.5
             * yarn_mscale(float(rs.get("factor", 1.0)), rs.get("mscale_all_dim", 0.0)) ** 2)
    causal = jnp.tril(jnp.ones((s, s), bool))
    outs = []
    for h0 in range(0, h, head_block):    # in blocks of heads, so that S x S fits
        hs = slice(h0, h0 + head_block)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", q[:, :, hs, :dn], kv[:, :, hs, :dn])
                  + jnp.einsum("bqhd,bkd->bhqk", q_rope[:, :, hs], k_rope[:, :, 0]))
        prob = jax.nn.softmax(jnp.where(causal, scores * scale, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", prob, kv[:, :, hs, dn:]))
    out = jnp.concatenate(outs, axis=2).reshape(b, s, h * dv)
    return out @ p[pre + "o_weight"].T


def swiglu(x, w_gate_up, w_down):
    """Weights ``[out, in]``: gate rows first, then up rows."""
    width = w_down.shape[1]
    gu = x @ w_gate_up.T
    return (jax.nn.silu(gu[..., :width]) * gu[..., width:]) @ w_down.T


def experts(p, pre, x, c, experts_held, shared=True):
    """The share of the expert layer that ``experts_held = (first, count)``
    gives, and with ``shared`` the shared expert."""
    first, count = experts_held
    k = c["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ p[pre + "router_weight"].T)                   # [..., E]
    _, idx = jax.lax.top_k(s + p[pre + "select_bias"], k)
    chosen = jnp.take_along_axis(s, idx, -1)
    gates = chosen * c["routed_scaling_factor"]
    if c["norm_topk_prob"]:
        gates = gates / chosen.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    width = p[pre + "experts_down_weight"].shape[1]
    for j in range(count):
        gate = jnp.sum(jnp.where(idx == first + j, gates, 0.0), -1)      # 0: not chosen
        gu = x @ p[pre + "experts_gate_up_weight"][j]
        out = (jax.nn.silu(gu[..., :width]) * gu[..., width:]) @ p[pre + "experts_down_weight"][j]
        y = y + gate[..., None] * out
    if shared:
        y = y + swiglu(x, p[pre + "shared_gate_up_weight"], p[pre + "shared_down_weight"])
    return y


def forward(named_params, tok, *, config, experts_held, head_block=8, dtype=jnp.float32):
    """Logits [B, S, V] for token ids ``tok`` [B, S].  ``dtype`` float32 is
    the reference; bfloat16 computes everything in bf16 at default precision
    (the reading that the comparison's tolerances must refuse)."""
    c = config
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        p = {k: v.astype(dtype) for k, v in by_suffix(named_params).items()}
        x = p["model_embed_weight"][tok]
        state = jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (c["hc_mult"], x.shape[-1]))
        eps = c["rms_norm_eps"]
        for l in range(c["num_hidden_layers"]):
            pre = f"model_layer{l}_"
            u, h_post, h_res = mix(p, pre + "attn_mix_", state, c)
            y = latent_attention(p, pre + "attn_", rms_norm(u, p[pre + "attn_norm_gamma"], eps),
                                 c, head_block)
            state = merge(state, y, h_post, h_res)
            u, h_post, h_res = mix(p, pre + "ffn_mix_", state, c)
            u = rms_norm(u, p[pre + "ffn_norm_gamma"], eps)
            if l < c["first_k_dense_replace"]:
                y = swiglu(u, p[pre + "mlp_gate_up_weight"], p[pre + "mlp_down_weight"])
            else:
                y = experts(p, pre + "moe_", u, c, experts_held)
            state = merge(state, y, h_post, h_res)
        hidden = rms_norm(state.sum(-2), p["model_norm_gamma"], eps)
        return (hidden @ p["lm_head_weight"].T).astype(jnp.float32)


def loss_per_token(logits, labels):
    """Cross-entropy of each position against its label (the next token), [B, S]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def loss(named_params, tok, labels, **kwargs):
    """Mean causal-LM loss: what ``jax.grad`` differentiates in the tests."""
    return loss_per_token(forward(named_params, tok, **kwargs), labels).mean()

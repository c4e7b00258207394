"""Plain reference of LFM2-MoE's forward pass and causal-LM loss.

Straightforward ``jax.numpy`` in float32 with matmul precision "highest": no
kernels, no sorting of routed pairs, no AMP (``remat`` recomputes a layer's
forward in its backward pass, which changes no number).  The short
convolution is the literal padded depthwise convolution
(``lax.conv_general_dilated`` with a group a channel, ``K − 1`` zeros on each
side and the first S outputs kept, as a ``Conv1d(groups=C, padding=K−1)``
followed by ``[..., :S]``), attention a plain softmax in blocks of queries
with the key/value head repeated over its group, experts a loop over the
experts held, each applied to every token and weighted by a gate that is
zero where the token did not choose it.  Imports nothing of the program's
model code; it reads the program's parameters by name.

Equations (per token ``x`` [d]; every layer is ``h = x + M(RMSNorm(x))``,
``out = h + F(RMSNorm(h))``; RMSNorm ε ``norm_eps``, one gain an element):

conv (``layer_types[i] == "conv"``):
    [B | C | x̃] = u W_in;   z = B ⊙ x̃
    c_t = Σ_{k=0..K−1} w[:, k] ⊙ z_{t−K+1+k}      (zeros before the start; K = conv_L_cache)
    M(u) = (C ⊙ c) W_out
full_attention: q, k, v = u W_q, u W_k, u W_v (heads of d / num_attention_heads)
    q, k ← RMSNorm_head(q), RMSNorm_head(k); then rotary, rotate-half pairs
    (i, i + D/2) turned by ``t · θ^(−2i/D)``
    M(u) = W_o softmax_causal(q kᵀ · D^-½) v
F: the first ``num_dense_layers`` layers ``W_2 (silu(W_1 u) ⊙ W_3 u)``; the
    others s = σ(u W_r) over all the published experts, the top k of s + b,
    gates ``scaling · s_i / Σ_selected s_j``, ``y = Σ_held g_i E_i(u)``.
logits = RMSNorm(x) Eᵀ, E the embedding (tied).

Departures from the published model, each also under ``assumed`` in
``chipbench/configs/lfm2-24b-a2b.json``: what ``config.json`` does not state
(the sigmoid scores, the order B, C, x̃, the head width, the dense width as
given, the tied head); experts held elsewhere add nothing (``experts_held``):
the partial sum is what goes on to the next layer, as it does in the
program.
"""
import functools

import jax
import jax.numpy as jnp

from .common import by_suffix
from .nemotron_h import loss_per_token, rms_norm
from .solar_open2 import experts
from .xing4 import swiglu


def short_conv(p, pre, x):
    """One gated short-convolution mixer on the normed input ``x``."""
    s = x.shape[1]
    b, c, xx = jnp.split(x @ p[pre + "in_proj_weight"].T, 3, axis=-1)
    w = p[pre + "conv_weight"]                                    # [C, K]
    taps = w.shape[1]
    conv = jax.lax.conv_general_dilated(
        b * xx, w.T[:, None, :], window_strides=(1,), padding=[(taps - 1, taps - 1)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=w.shape[0])[:, :s]
    return (c * conv) @ p[pre + "out_proj_weight"].T


def rotary(x, theta):
    """Rotate-half rotary positions 0..S−1 on ``x`` [B, S, H, D]."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq    # [S, D/2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, None, :].astype(x.dtype)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(p, pre, x, c, query_block):
    """Grouped-query attention with per-head q/k norms and rotary positions.
    The queries run in blocks of ``query_block`` (where that divides S; else
    one block), one block at a time and each recomputed in the backward pass
    (``lax.map`` of a ``jax.checkpoint``), so that no more than one block's
    scores [B, H, block, S] exist at once; which changes no number."""
    bsz, s, d = x.shape
    h = c["num_attention_heads"]
    hd = d // h
    h_kv = c["num_key_value_heads"]
    eps, theta = c["norm_eps"], c["rope_parameters"]["rope_theta"]
    q, k, v = jnp.split(x @ p[pre + "qkv_weight"].T, [h * hd, (h + h_kv) * hd], axis=-1)
    q = rotary(rms_norm(q.reshape(bsz, s, h, hd), p[pre + "q_norm_gamma"], eps), theta)
    k = rotary(rms_norm(k.reshape(bsz, s, h_kv, hd), p[pre + "k_norm_gamma"], eps), theta)
    k = jnp.repeat(k, h // h_kv, axis=2)
    v = jnp.repeat(v.reshape(bsz, s, h_kv, hd), h // h_kv, axis=2)
    block = query_block if s % query_block == 0 else s
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        i, q_blk = args
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) * hd ** -0.5
        seen = key_pos[None, :] <= (i * block + jnp.arange(block))[:, None]
        prob = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", prob, v)

    blocks = q.reshape(bsz, s // block, block, h, hd).swapaxes(0, 1)
    out = jax.lax.map(one, (jnp.arange(s // block), blocks))
    out = out.swapaxes(0, 1).reshape(bsz, s, h * hd)
    return out @ p[pre + "o_weight"].T


def _layer(p, x, layer, c, experts_held, query_block):
    """Layer ``layer``: ``x`` → ``(x + mixer + feed-forward, pairs routed to
    the experts held)``."""
    pre, eps = f"model_layer{layer}_", c["norm_eps"]
    u = rms_norm(x, p[pre + "operator_norm_gamma"], eps)
    if c["layer_types"][layer] == "conv":
        x = x + short_conv(p, pre + "conv_", u)
    else:
        x = x + attention(p, pre + "attn_", u, c, query_block)
    u = rms_norm(x, p[pre + "ffn_norm_gamma"], eps)
    if layer < c["num_dense_layers"]:
        return x + swiglu(u, p[pre + "ffn_gate_up_weight"], p[pre + "ffn_down_weight"]), 0
    y, rows = experts(p, pre + "moe_", u, c, experts_held, shared=False)
    return x + y, rows


def forward(named_params, tok, *, config, experts_held, query_block=512, dtype=jnp.float32,
            with_rows=False, remat=False):
    """Logits [B, S, V] for token ids ``tok`` [B, S] (and with ``with_rows``
    the pairs routed to the experts held, over the layers).  ``dtype``
    float32 is the reference; bfloat16 computes everything in bf16 at
    default precision (the reading that the comparison's tolerances are set
    against).  ``remat`` recomputes each layer's forward in the backward pass
    (``jax.checkpoint``): the same numbers, with one layer's residuals alive
    at a time, so that the gradient at the timed shapes fits on the chip."""
    c = config
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        p = {k: v.astype(dtype) for k, v in by_suffix(named_params).items()}
        x = p["model_embed_weight"][tok]
        rows = 0
        for layer in range(c["num_hidden_layers"]):
            run = functools.partial(_layer, layer=layer, c=c, experts_held=experts_held,
                                    query_block=query_block)
            if remat:
                run = jax.checkpoint(run)
            x, here = run(p, x)
            rows = rows + here
        hidden = rms_norm(x, p["model_norm_gamma"], c["norm_eps"])
        logits = (hidden @ p["model_embed_weight"].T).astype(jnp.float32)
    return (logits, rows) if with_rows else logits


def loss(named_params, tok, labels, **kwargs):
    """Mean causal-LM loss: what ``jax.grad`` differentiates in the tests."""
    return loss_per_token(forward(named_params, tok, **kwargs), labels).mean()

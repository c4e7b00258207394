"""Plain reference of the encoder-decoder Transformer's forward pass.

Straightforward ``jax.numpy`` in float32 with matmul precision "highest": the
whole target prefix at once under a causal mask, no KV cache, no buckets, no
padding, no batching.  Follows Vaswani et al. 2017 with the tensor2tensor
pre-norm layout (``layer_preprocess_sequence="n"``).  Departures, which are
the program's model and so the configuration's ``assumed``: no final
LayerNorm after either stack, source, target and output embeddings are one
tied [vocab, d_model] matrix, sinusoids interleave sin and cos by column, and
LayerNorm epsilon is 1e-5.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

from .common import attention, by_suffix, dense, layer_norm


def sinusoid(n, d):
    pos = np.arange(n)[:, None]
    dim = np.arange((d + 1) // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * dim / d)
    table = np.zeros((n, d), np.float32)
    table[:, 0::2] = np.sin(angle)
    table[:, 1::2] = np.cos(angle[:, : d // 2])
    return jnp.asarray(table)


def _ln(x, p, name):
    return layer_norm(x, p[name + "_gamma"], p[name + "_beta"])


def _ffn(x, p, pre):
    return dense(jax.nn.relu(dense(x, p, pre + "ffn_ffn1")), p, pre + "ffn_ffn2")


def logits(named_params, src, tgt, *, enc_layers, dec_layers, heads):
    """``src`` [S] and ``tgt`` [T] int token ids of ONE request -> logits
    [T, V]: row ``t`` scores the token that follows ``tgt[:t + 1]``."""
    with jax.default_matmul_precision("highest"):
        p = by_suffix(named_params)
        emb = p["embed_weight"]
        d = emb.shape[1]
        x = (emb[src] * math.sqrt(d) + sinusoid(len(src), d))[None]
        for l in range(enc_layers):
            pre = f"enc_layer{l}_"
            q, k, v = jnp.split(dense(_ln(x, p, pre + "ln1"), p, pre + "attn_qkv"), 3, -1)
            x = x + dense(attention(q, k, v, heads), p, pre + "attn_out")
            x = x + _ffn(_ln(x, p, pre + "ln2"), p, pre)
        memory = x
        y = (emb[tgt] * math.sqrt(d) + sinusoid(len(tgt), d))[None]
        for l in range(dec_layers):
            pre = f"dec_layer{l}_"
            q, k, v = jnp.split(dense(_ln(y, p, pre + "ln1"), p, pre + "selfattn_qkv"), 3, -1)
            y = y + dense(attention(q, k, v, heads, causal=True), p, pre + "selfattn_out")
            q = dense(_ln(y, p, pre + "ln2"), p, pre + "crossattn_q")
            k, v = jnp.split(dense(memory, p, pre + "crossattn_kv"), 2, -1)
            y = y + dense(attention(q, k, v, heads), p, pre + "crossattn_out")
            y = y + _ffn(_ln(y, p, pre + "ln3"), p, pre)
        return (y @ emb.T)[0]

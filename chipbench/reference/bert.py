"""Plain reference of BERT pretraining's forward pass and MLM loss.

Straightforward ``jax.numpy`` in float32 with matmul precision "highest": no
AMP, no fused attention, no streaming cross-entropy.  Follows
google-research/bert ``modeling.py`` (post-LN encoder, learned positions,
erf GELU, MLM transform + LayerNorm + decoder).  Departures, which are the
program's model and so the configuration's ``assumed``: the MLM decoder has
its own [vocab, hidden] weight (not tied to the embedding), LayerNorm epsilon
is 1e-5, and the fused QKV projection's output columns are laid out
[3, heads, head_dim].
"""
import jax
import jax.numpy as jnp

from .common import attention, by_suffix, dense, layer_norm


def forward(named_params, tok, seg, pos, *, layers, heads):
    """MLM logits [B, P, V] at the masked positions ``pos`` [B, P]."""
    with jax.default_matmul_precision("highest"):
        p = by_suffix(named_params)
        s = tok.shape[1]
        x = (p["word_embed_weight"][tok] + p["type_embed_weight"][seg]
             + p["pos_embed_pos_weight"][:s][None])
        x = layer_norm(x, p["embed_ln_gamma"], p["embed_ln_beta"])
        for l in range(layers):
            pre = f"enc_layer{l}_"
            q, k, v = jnp.split(dense(x, p, pre + "attn_qkv"), 3, axis=-1)
            a = dense(attention(q, k, v, heads), p, pre + "attn_out")
            x = layer_norm(x + a, p[pre + "ln1_gamma"], p[pre + "ln1_beta"])
            f = jax.nn.gelu(dense(x, p, pre + "ffn_ffn1"), approximate=False)
            f = dense(f, p, pre + "ffn_ffn2")
            x = layer_norm(x + f, p[pre + "ln2_gamma"], p[pre + "ln2_beta"])
        h = jnp.take_along_axis(x, pos[:, :, None], axis=1)
        h = jax.nn.gelu(dense(h, p, "mlm_dense"), approximate=False)
        h = layer_norm(h, p["mlm_ln_gamma"], p["mlm_ln_beta"])
        return dense(h, p, "mlm_decoder")


def mlm_loss_per_sequence(logits, labels):
    """Mean cross-entropy of each sequence's masked positions, [B]."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, :, None], axis=-1)[..., 0].mean(-1)

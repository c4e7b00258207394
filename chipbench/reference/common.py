"""What the two references share: parameters by name, layer norm, attention."""
import math

import jax
import jax.numpy as jnp


def by_suffix(named_arrays):
    """``{full parameter name: array}`` -> ``{name without the block's own
    prefix: float32 array}``: ``bertmodel0_enc_layer0_ln1_gamma`` becomes
    ``enc_layer0_ln1_gamma``, whatever counter the block's prefix carries."""
    return {name.split("_", 1)[1]: jnp.asarray(a, jnp.float32)
            for name, a in named_arrays.items()}


def layer_norm(x, gamma, beta, eps=1e-5):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * gamma + beta


def dense(x, p, name):
    """Gluon ``Dense``: weight is [out, in]."""
    return x @ p[name + "_weight"].T + p[name + "_bias"]


def attention(q, k, v, heads, causal=False):
    """q [B, Sq, D], k/v [B, Sk, D] -> [B, Sq, D]; softmax(q k^T / sqrt(dh)) v."""
    b, sq, d = q.shape
    sk, dh = k.shape[1], d // heads
    q = q.reshape(b, sq, heads, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, sk, heads, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, sk, heads, dh).transpose(0, 2, 1, 3)
    s = q @ k.transpose(0, 1, 3, 2) / math.sqrt(dh)
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool)), s, -jnp.inf)
    out = jax.nn.softmax(s, axis=-1) @ v
    return out.transpose(0, 2, 1, 3).reshape(b, sq, d)

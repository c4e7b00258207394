"""The COMPILED causal blockwise kernels, folded and unfolded, side by side on
the chip at the keye cell's shape (32 query heads on 4 key/value heads of 128
at S 8192 under an int8 selection shared by the heads) and the xing cell's (32
heads, 192-wide keys, 128-wide values, S 4096), 512 x 512 blocks, bf16: the
folded forward gives the unfolded kernel's output and log-sum-exp bit for
bit, and the gradients fall within a float32 sum's reordering of each other
(only dq's sums change order); both sets of gradients are as far from a
float32 reference on two heads as each other.

    MXNET_TEST_CTX=tpu python -m pytest tpu_tests/test_attention_fold_tpu.py -q
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu.ops import attention as attn

# cell: batch, query heads, key/value heads, length, key width, value width, selected
SHAPES = {
    "keye": (1, 32, 4, 8192, 128, 128, True),
    "xing": (1, 32, 32, 4096, 192, 128, False),
}
LAUNCH = attn._Launch(False, (512, 512))


def operands(shape, seed):
    b, h, h_kv, s, d, d_v, selected = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, h, s, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, h_kv, s, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, h_kv, s, d_v), jnp.bfloat16)
    w = jax.random.normal(ks[3], (b, h, s, d_v), jnp.float32)
    select = None
    if selected:
        select = (jax.random.bernoulli(ks[4], 0.45, (b, s, s))
                  | jnp.eye(s, dtype=bool)).astype(jnp.int8)
    return q, k, v, w, select


def value_and_grads(q, k, v, w, select, fold, monkeypatch):
    if not fold:
        monkeypatch.setattr(attn, "_folds", lambda *a: False)
    scale = q.shape[-1] ** -0.5

    def loss(q, k, v):
        out, lse = attn._flash_kernels_lse(q, k, v, True, scale, LAUNCH, select)
        return jnp.sum(out.astype(jnp.float32) * w), (out, lse)

    (_, (out, lse)), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(
        q, k, v)
    monkeypatch.undo()
    return out, lse, grads


def apart(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2)) / np.sqrt(np.mean(want ** 2)))


@pytest.mark.parametrize("cell", SHAPES)
def test_folded_kernels_match_the_unfolded_ones_on_the_chip(cell, monkeypatch):
    _, h, h_kv, s, d, d_v, selected = SHAPES[cell]
    assert attn._folds(True, s, s, (512, 512), d, d_v, jnp.bfloat16, h // h_kv, selected)
    q, k, v, w, select = operands(SHAPES[cell], seed=45)
    folded = value_and_grads(q, k, v, w, select, True, monkeypatch)
    unfolded = value_and_grads(q, k, v, w, select, False, monkeypatch)
    np.testing.assert_array_equal(np.asarray(folded[0], np.float32),
                                  np.asarray(unfolded[0], np.float32))
    np.testing.assert_array_equal(np.asarray(folded[1]), np.asarray(unfolded[1]))
    for got, want in zip(folded[2], unfolded[2]):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert apart(got, want) < 2e-3

    # dq of two query heads against float32: the folded as near as the unfolded
    heads = jnp.arange(2)
    two = lambda x: x[:, heads].astype(jnp.float32)

    def reference(q):
        kv = [x[:, heads // (h // h_kv)].astype(jnp.float32) for x in (k, v)]
        out = attn.attention_reference(q, *kv, True, q.shape[-1] ** -0.5, select)
        return jnp.sum(out * two(w))

    dq_ref = jax.jit(jax.grad(reference))(two(q))
    assert (apart(folded[2][0][:, heads], dq_ref)
            <= 1.05 * apart(unfolded[2][0][:, heads], dq_ref) + 1e-4)

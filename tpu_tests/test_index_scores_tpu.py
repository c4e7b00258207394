"""The COMPILED index-score kernels (``ops/sparse_attention.py``) on the chip at
the keye cell's shape — 16 index heads of 64 on one index key at S 8192,
512 x 512 tiles — against a float32 dense loop at ``Precision.HIGHEST`` on the
same chip, value and the three gradients; and ``index_scores`` taking them on
the TPU.

    MXNET_TEST_CTX=tpu python -m pytest tpu_tests/test_index_scores_tpu.py -q
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu.ops import sparse_attention as sa

S, HEADS, DIM, TOPK, ROWS = 8192, 16, 64, 2048, 512


def operands(seed=0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((1, S, HEADS, DIM), dtype=np.float32)
    k = rng.standard_normal((1, S, DIM), dtype=np.float32)
    w = rng.standard_normal((1, S, HEADS), dtype=np.float32) * 0.3
    # what the indexer's loss sends back: zero off ~TOPK visible keys a row
    ct = rng.standard_normal((1, S, S), dtype=np.float32) / TOPK
    ct *= rng.random((1, S, S), dtype=np.float32) < 0.25
    return tuple(jnp.asarray(a) for a in (q, k, w, np.tril(ct[0])[None]))


@jax.jit
def dense_loop(q, k, w, ct):
    """ROWS queries at a time against every key, float32 at ``HIGHEST``:
    the scores, the cotangent that was used, and the gradients of
    ``sum(scores · cotangent)`` written out.  The cotangent is ``ct`` less
    the pairs where a head's product is within rounding of relu's kink: there
    two float32 sums may fall on either side, and the gradient jumps by a
    whole term."""
    c = (HEADS * DIM) ** -0.5

    def rows(dk, chunk):
        qc, wc, ctc, first = chunk                       # [R, H, D], [R, H], [R, S]
        prod = jnp.einsum("qhd,kd->qhk", qc, k[0], precision=jax.lax.Precision.HIGHEST)
        val = jnp.sum(jax.nn.relu(prod) * wc[..., None], axis=1) * c
        t = first + jnp.arange(ROWS)[:, None]
        val = jnp.where(t >= jnp.arange(S)[None], val, -jnp.inf)
        ctc = jnp.where(jnp.min(jnp.abs(prod), axis=1) > 1e-3, ctc, 0.0)
        g = ctc[:, None, :] * wc[..., None] * (prod > 0)
        dq = jnp.einsum("qhk,kd->qhd", g, k[0], precision=jax.lax.Precision.HIGHEST) * c
        dk = dk + jnp.einsum("qhk,qhd->kd", g, qc, precision=jax.lax.Precision.HIGHEST) * c
        dw = jnp.sum(ctc[:, None, :] * jax.nn.relu(prod), axis=-1) * c
        return dk, (val, ctc, dq, dw)

    split = lambda a: a[0].reshape((S // ROWS, ROWS) + a.shape[2:])
    dk, (val, ct, dq, dw) = jax.lax.scan(
        rows, jnp.zeros((S, DIM), jnp.float32),
        (split(q), split(w), split(ct), jnp.arange(0, S, ROWS)))
    whole = lambda a: a.reshape((1, S) + a.shape[2:])
    return whole(val), whole(ct), whole(dq), dk[None], whole(dw)


def close(got, want, what, rms=2e-6, worst=2e-5):
    """Both are sums of six bfloat16 partial products in float32, in another
    order: they part by float32's rounding of sums of up to 8,192 terms (a
    bfloat16 operand in place of a float32 one would part them by 4e-3, a
    partial product left out by 1e-5 and more)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.sqrt(np.mean(want ** 2))
    apart = np.sqrt(np.mean((got - want) ** 2)) / scale, np.abs(got - want).max() / np.abs(want).max()
    assert apart[0] < rms and apart[1] < worst, (what, apart)


@pytest.mark.parametrize("seed", [0, 1])
def test_compiled_kernels_match_a_dense_loop_at_the_cells_shape(seed):
    q, k, w, ct = operands(seed)
    before = profiler.counters()
    got, pull = jax.vjp(lambda *a: sa.index_scores(*a, 512, 512), q, k, w)
    after = profiler.counters()
    assert after["index_scores_dispatch_pallas"] - before["index_scores_dispatch_pallas"] == 1
    assert after["index_scores_dispatch_xla"] == before["index_scores_dispatch_xla"]
    val, used, want_dq, want_dk, want_dw = dense_loop(q, k, w, ct)
    assert 0.99 < float(jnp.sum(used != 0) / jnp.sum(ct != 0)) < 1.0
    dq, dk, dw = pull(used)
    past = np.isneginf(np.asarray(val))
    assert np.array_equal(np.isneginf(np.asarray(got)), past)
    assert np.array_equal(past[0], ~np.tril(np.ones((S, S), bool)))
    close(np.where(past, 0, got), np.where(past, 0, val), "scores")
    for name, grad, want in (("dq", dq, want_dq), ("dk", dk, want_dk), ("dw", dw, want_dw)):
        close(grad, want, name, rms=5e-6, worst=5e-5)


def test_the_kernels_and_the_tiles_select_nearly_the_same_keys():
    """The sixteen heads are summed in another order, so a near-tie at the
    2,048th score may fall the other way: a handful of keys in ten thousand."""
    q, k, w, _ = operands(2)
    kernels = sa.select_topk(sa.index_scores(q, k, w, 512, 512), TOPK)
    tiles = sa.select_topk(jax.jit(lambda *a: sa._index_scores_tiles(*a, 512, 512))(q, k, w), TOPK)
    both = jnp.sum((kernels != 0) & (tiles != 0), dtype=jnp.float32)
    assert float(both / jnp.sum(tiles != 0, dtype=jnp.float32)) > 0.9995

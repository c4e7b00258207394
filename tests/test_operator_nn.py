"""NN-operator depth tests (the [U:tests/python/unittest/test_operator.py]
normalization/conv/pool sections): every check against an independent
numpy reference, gradients by finite differences where cheap.
"""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd
from incubator_mxnet_tpu.utils.test_utils import (
    assert_almost_equal,
    check_numeric_gradient,
)

from common import with_seed


def _nd(x, dtype="float32"):
    return mx.nd.array(np.asarray(x, dtype=dtype))


class TestNormalizationOps:
    @with_seed()
    def test_batchnorm_training_stats(self):
        x = np.random.randn(4, 3, 5, 5).astype(np.float32) * 2 + 1
        gamma = np.random.rand(3).astype(np.float32) + 0.5
        beta = np.random.randn(3).astype(np.float32)
        mean = np.zeros(3, np.float32)
        var = np.ones(3, np.float32)
        # the op computes batch statistics (returns out, batch_mean,
        # batch_var; the gluon layer owns running-stat mutation)
        out, bmean, bvar = mx.nd.BatchNorm(_nd(x), _nd(gamma), _nd(beta),
                                           _nd(mean), _nd(var),
                                           fix_gamma=False)
        bm = x.mean(axis=(0, 2, 3))
        bv = x.var(axis=(0, 2, 3))
        assert_almost_equal(bmean.asnumpy(), bm, rtol=1e-4, atol=1e-4)
        assert_almost_equal(bvar.asnumpy(), bv, rtol=1e-3, atol=1e-4)
        expect = ((x - bm[None, :, None, None])
                  / np.sqrt(bv[None, :, None, None] + 1e-5)
                  * gamma[None, :, None, None] + beta[None, :, None, None])
        assert_almost_equal(out.asnumpy(), expect, rtol=1e-3, atol=1e-3)

    @with_seed()
    def test_batchnorm_inference_uses_running(self):
        x = np.random.randn(2, 3, 4, 4).astype(np.float32)
        gamma = np.ones(3, np.float32)
        beta = np.zeros(3, np.float32)
        mean = np.array([0.5, -0.5, 1.0], np.float32)
        var = np.array([2.0, 1.0, 0.5], np.float32)
        out = mx.nd.BatchNorm(_nd(x), _nd(gamma), _nd(beta), _nd(mean),
                              _nd(var), fix_gamma=False,
                              use_global_stats=True)[0]
        expect = (x - mean[None, :, None, None]) / np.sqrt(
            var[None, :, None, None] + 1e-5)
        assert_almost_equal(out.asnumpy(), expect, rtol=1e-3, atol=1e-3)

    @with_seed()
    def test_layernorm_vs_numpy(self):
        x = np.random.randn(3, 7).astype(np.float32)
        gamma = np.random.rand(7).astype(np.float32) + 0.5
        beta = np.random.randn(7).astype(np.float32)
        out = mx.nd.LayerNorm(_nd(x), _nd(gamma), _nd(beta), eps=1e-5)
        mu = x.mean(-1, keepdims=True)
        sd = np.sqrt(x.var(-1, keepdims=True) + 1e-5)
        assert_almost_equal(out.asnumpy(), (x - mu) / sd * gamma + beta,
                            rtol=1e-4, atol=1e-4)

    @with_seed()
    def test_layernorm_grad(self):
        x = np.random.randn(2, 5).astype(np.float32)
        g = np.random.rand(5).astype(np.float32) + 0.5
        b = np.random.randn(5).astype(np.float32)
        check_numeric_gradient(
            lambda a, gg, bb: mx.nd.LayerNorm(a, gg, bb) ** 2, [x, g, b],
            rtol=2e-2, atol=2e-3)

    @with_seed()
    def test_groupnorm_instancenorm_rmsnorm(self):
        x = np.random.randn(2, 4, 3, 3).astype(np.float32)
        g = np.ones(4, np.float32)
        b = np.zeros(4, np.float32)
        # InstanceNorm: per-sample per-channel normalization
        out = mx.nd.InstanceNorm(_nd(x), _nd(g), _nd(b), eps=1e-5).asnumpy()
        mu = x.mean(axis=(2, 3), keepdims=True)
        sd = np.sqrt(x.var(axis=(2, 3), keepdims=True) + 1e-5)
        assert_almost_equal(out, (x - mu) / sd, rtol=1e-3, atol=1e-3)
        # GroupNorm with 2 groups
        out = mx.nd.GroupNorm(_nd(x), _nd(g), _nd(b), num_groups=2,
                              eps=1e-5).asnumpy()
        xr = x.reshape(2, 2, 2, 3, 3)
        mu = xr.mean(axis=(2, 3, 4), keepdims=True)
        sd = np.sqrt(xr.var(axis=(2, 3, 4), keepdims=True) + 1e-5)
        expect = ((xr - mu) / sd).reshape(x.shape)
        assert_almost_equal(out, expect, rtol=1e-3, atol=1e-3)
        # RMSNorm over the last axis
        xr2 = np.random.randn(3, 6).astype(np.float32)
        gw = np.random.rand(6).astype(np.float32) + 0.5
        out = mx.nd.RMSNorm(_nd(xr2), _nd(gw), eps=1e-6).asnumpy()
        rms = np.sqrt((xr2 ** 2).mean(-1, keepdims=True) + 1e-6)
        assert_almost_equal(out, xr2 / rms * gw, rtol=1e-4, atol=1e-4)

    @with_seed()
    def test_l2_normalization(self):
        x = np.random.randn(3, 5).astype(np.float32)
        out = mx.nd.L2Normalization(_nd(x), mode="instance").asnumpy()
        expect = x / np.sqrt((x ** 2).sum(-1, keepdims=True) + 1e-10)
        assert_almost_equal(out, expect, rtol=1e-4, atol=1e-5)
        x4 = np.random.randn(2, 3, 4, 4).astype(np.float32)
        out = mx.nd.L2Normalization(_nd(x4), mode="channel").asnumpy()
        expect = x4 / np.sqrt((x4 ** 2).sum(1, keepdims=True) + 1e-10)
        assert_almost_equal(out, expect, rtol=1e-4, atol=1e-5)


class TestConvPoolOps:
    @with_seed()
    def test_convolution_vs_numpy(self):
        x = np.random.randn(2, 2, 5, 5).astype(np.float32)
        w = np.random.randn(3, 2, 3, 3).astype(np.float32)
        b = np.random.randn(3).astype(np.float32)
        out = mx.nd.Convolution(_nd(x), _nd(w), _nd(b), kernel=(3, 3),
                                num_filter=3, pad=(1, 1)).asnumpy()
        xp = np.pad(x, [(0, 0), (0, 0), (1, 1), (1, 1)])
        expect = np.zeros((2, 3, 5, 5), np.float32)
        for n in range(2):
            for f in range(3):
                for i in range(5):
                    for j in range(5):
                        expect[n, f, i, j] = (
                            xp[n, :, i:i + 3, j:j + 3] * w[f]).sum() + b[f]
        assert_almost_equal(out, expect, rtol=1e-3, atol=1e-3)

    @with_seed()
    def test_convolution_stride_dilate_group(self):
        x = np.random.randn(1, 4, 8, 8).astype(np.float32)
        w = np.random.randn(4, 2, 3, 3).astype(np.float32)
        out = mx.nd.Convolution(_nd(x), _nd(w), kernel=(3, 3), num_filter=4,
                                stride=(2, 2), num_group=2, no_bias=True)
        assert out.shape == (1, 4, 3, 3)
        # grouped: filter f sees only its group's input channels
        g0 = out.asnumpy()[0, 0]
        xp = x[0, 0:2]
        expect = np.zeros((3, 3), np.float32)
        for i in range(3):
            for j in range(3):
                expect[i, j] = (xp[:, 2 * i:2 * i + 3, 2 * j:2 * j + 3] * w[0]).sum()
        assert_almost_equal(g0, expect, rtol=1e-3, atol=1e-3)
        # dilation
        out = mx.nd.Convolution(_nd(x), _nd(w[:, :, :, :]), kernel=(3, 3),
                                num_filter=4, dilate=(2, 2), num_group=2,
                                no_bias=True)
        assert out.shape == (1, 4, 4, 4)

    @with_seed()
    def test_conv_grad(self):
        x = np.random.randn(1, 1, 4, 4).astype(np.float32)
        w = np.random.randn(2, 1, 3, 3).astype(np.float32)
        check_numeric_gradient(
            lambda a, ww: mx.nd.Convolution(a, ww, kernel=(3, 3), num_filter=2,
                                            pad=(1, 1), no_bias=True),
            [x, w], rtol=2e-2, atol=2e-3)

    @with_seed()
    def test_deconvolution_shapes_and_values(self):
        x = np.random.randn(1, 2, 3, 3).astype(np.float32)
        w = np.random.randn(2, 3, 2, 2).astype(np.float32)
        out = mx.nd.Deconvolution(_nd(x), _nd(w), kernel=(2, 2), num_filter=3,
                                  stride=(2, 2), no_bias=True)
        assert out.shape == (1, 3, 6, 6)
        # each input pixel stamps w scaled by its value (stride=kernel → no overlap)
        expect = np.zeros((1, 3, 6, 6), np.float32)
        for c_in in range(2):
            for i in range(3):
                for j in range(3):
                    expect[0, :, 2 * i:2 * i + 2, 2 * j:2 * j + 2] += (
                        x[0, c_in, i, j] * w[c_in])
        assert_almost_equal(out.asnumpy(), expect, rtol=1e-3, atol=1e-3)

    @with_seed()
    def test_pooling_modes(self):
        x = np.random.randn(1, 2, 4, 4).astype(np.float32)
        out = mx.nd.Pooling(_nd(x), kernel=(2, 2), stride=(2, 2),
                            pool_type="max").asnumpy()
        expect = x.reshape(1, 2, 2, 2, 2, 2).max(axis=(3, 5))
        assert_almost_equal(out, expect, rtol=0, atol=0)
        out = mx.nd.Pooling(_nd(x), kernel=(2, 2), stride=(2, 2),
                            pool_type="avg").asnumpy()
        expect = x.reshape(1, 2, 2, 2, 2, 2).mean(axis=(3, 5))
        assert_almost_equal(out, expect, rtol=1e-5, atol=1e-6)
        out = mx.nd.Pooling(_nd(x), global_pool=True, pool_type="avg",
                            kernel=(1, 1)).asnumpy()
        assert_almost_equal(out[..., 0, 0], x.mean(axis=(2, 3)),
                            rtol=1e-5, atol=1e-6)

    @with_seed()
    def test_maxpool_grad_routes_to_argmax(self):
        x = np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4)
        xa = _nd(x)
        xa.attach_grad()
        with autograd.record():
            y = mx.nd.Pooling(xa, kernel=(2, 2), stride=(2, 2), pool_type="max")
        y.backward()
        g = xa.grad.asnumpy()[0, 0]
        expect = np.zeros((4, 4), np.float32)
        expect[1::2, 1::2] = 1  # max of each 2x2 block is bottom-right
        assert_almost_equal(g, expect, rtol=0, atol=0)

    @with_seed()
    def test_upsampling_nearest(self):
        x = np.array([[[[1.0, 2.0], [3.0, 4.0]]]], np.float32)
        out = mx.nd.UpSampling(_nd(x), scale=2, sample_type="nearest").asnumpy()
        expect = np.repeat(np.repeat(x, 2, axis=2), 2, axis=3)
        assert_almost_equal(out, expect, rtol=0, atol=0)


class TestEmbeddingAndHeads:
    @with_seed()
    def test_embedding_grad_accumulates(self):
        w = np.random.randn(10, 4).astype(np.float32)
        idx = np.array([1, 3, 1], np.float32)  # repeated row 1
        wa = _nd(w)
        wa.attach_grad()
        with autograd.record():
            out = mx.nd.Embedding(_nd(idx, dtype="int32"), wa,
                                  input_dim=10, output_dim=4)
        out.backward()
        g = wa.grad.asnumpy()
        assert (g[1] == 2).all()  # row 1 hit twice
        assert (g[3] == 1).all()
        assert g[[0, 2, 4, 5, 6, 7, 8, 9]].sum() == 0

    @with_seed()
    def test_fullyconnected_flatten_semantics(self):
        x = np.random.randn(2, 3, 4).astype(np.float32)
        w = np.random.randn(5, 12).astype(np.float32)
        b = np.zeros(5, np.float32)
        out = mx.nd.FullyConnected(_nd(x), _nd(w), _nd(b), num_hidden=5)
        assert out.shape == (2, 5)
        assert_almost_equal(out.asnumpy(), x.reshape(2, 12) @ w.T,
                            rtol=1e-4, atol=1e-4)
        w2 = np.random.randn(5, 4).astype(np.float32)
        out = mx.nd.FullyConnected(_nd(x), _nd(w2), _nd(b), num_hidden=5,
                                   flatten=False)
        assert out.shape == (2, 3, 5)
        assert_almost_equal(out.asnumpy(), x @ w2.T, rtol=1e-4, atol=1e-4)

    @with_seed()
    def test_dropout_statistics_and_determinism(self):
        x = np.ones((400, 100), np.float32)
        with autograd.record(train_mode=True):
            out = mx.nd.Dropout(_nd(x), p=0.3)
        o = out.asnumpy()
        keep_rate = (o != 0).mean()
        assert abs(keep_rate - 0.7) < 0.02
        # kept values rescaled by 1/keep
        kept = o[o != 0]
        assert abs(kept.mean() - 1.0 / 0.7) < 0.05
        # eval mode: identity
        out = mx.nd.Dropout(_nd(x), p=0.3)
        assert_almost_equal(out.asnumpy(), x, rtol=0, atol=0)

    @with_seed()
    def test_slice_channel(self):
        x = np.random.randn(2, 6, 3).astype(np.float32)
        parts = mx.nd.SliceChannel(_nd(x), num_outputs=3, axis=1)
        assert len(parts) == 3
        for k in range(3):
            assert_almost_equal(parts[k].asnumpy(), x[:, 2 * k:2 * k + 2],
                                rtol=0, atol=0)
        sq = mx.nd.SliceChannel(_nd(x[:, :3]), num_outputs=3, axis=1,
                                squeeze_axis=True)
        assert sq[0].shape == (2, 3)


class TestSoftmaxOutputNormalization:
    """Backward normalization modes of the legacy SoftmaxOutput head
    ([U:src/operator/softmax_output-inl.h]): 'valid' divides by the valid
    count — equal to the TOTAL label count when use_ignore is off (it is
    NOT a no-op there)."""

    def _grad(self, **kwargs):
        x = np.arange(12, dtype=np.float32).reshape(4, 3) * 0.1
        lab = np.array([0, 1, 2, 1], np.float32)
        xa = _nd(x)
        xa.attach_grad()
        with autograd.record():
            out = mx.nd.SoftmaxOutput(xa, _nd(lab), **kwargs)
        out.backward()
        return xa.grad.asnumpy(), out.asnumpy()

    @with_seed()
    def test_valid_without_ignore_divides_by_count(self):
        g_null, p = self._grad()
        g_valid, _ = self._grad(normalization="valid")
        g_batch, _ = self._grad(normalization="batch")
        assert_almost_equal(g_valid, g_null / 4.0, rtol=1e-5, atol=1e-7)
        assert_almost_equal(g_batch, g_null / 4.0, rtol=1e-5, atol=1e-7)
        oh = np.eye(3, dtype=np.float32)[[0, 1, 2, 1]]
        assert_almost_equal(g_null, p - oh, rtol=1e-5, atol=1e-6)

    @with_seed()
    def test_valid_with_ignore_divides_by_valid_count(self):
        g, p = self._grad(normalization="valid", use_ignore=True,
                          ignore_label=1)
        oh = np.eye(3, dtype=np.float32)[[0, 1, 2, 1]]
        want = (p - oh)
        want[[1, 3]] = 0.0  # ignored rows contribute nothing
        assert_almost_equal(g, want / 2.0, rtol=1e-5, atol=1e-7)


def _layer_norm_f64(x, g, b, eps=1e-5):
    """LayerNorm over the last axis and the gradients of
    ``sum(sin(out))`` in NumPy float64, written from the closed form
    (independent of the op and of autodiff)."""
    mean = x.mean(-1, keepdims=True)
    inv = 1.0 / np.sqrt(x.var(-1, keepdims=True) + eps)
    xhat = (x - mean) * inv
    out = xhat * g + b
    dy = np.cos(out)
    dyg = dy * g
    dx = inv * (dyg - dyg.mean(-1, keepdims=True)
                - xhat * (dyg * xhat).mean(-1, keepdims=True))
    batch = tuple(range(x.ndim - 1))
    return out, dx, (dy * xhat).sum(batch), dy.sum(batch)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 3e-2)])
def test_layer_norm_grads_keep_primal_dtypes(dtype, tol):
    """Value and the three gradients against the float64 closed form, and
    the dtype contract under AMP: ``dx`` in the activation's dtype,
    ``dgamma`` / ``dbeta`` in the parameters' own."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.nn import layer_norm

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 6, 16).astype(np.float32)).astype(dtype)
    g = jnp.asarray(rng.rand(16).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(16).astype(np.float32))

    def loss(x, g, b):
        out = layer_norm(x, g, b)
        return jnp.sum(jnp.sin(out.astype(jnp.float32))), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(x, g, b)
    want = _layer_norm_f64(*(np.asarray(a, np.float64) for a in (x, g, b)))
    assert out.dtype == x.dtype
    for got, ref in zip((out, *grads), want):
        np.testing.assert_allclose(np.asarray(got, np.float64), ref,
                                   rtol=tol, atol=tol)
    assert grads[0].dtype == x.dtype
    assert grads[1].dtype == g.dtype and grads[2].dtype == b.dtype


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 4e-2)])
@pytest.mark.parametrize("causal", [False, True])
def test_bshd_attention_vjp_matches_autodiff(causal, dtype, tol):
    """``_flash_bshd``'s value and its hand-written backward (the path the
    S 128 cells take) against autodiff of ``attention_reference_bshd``."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops import attention as att

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(2, 16, 4, 8).astype(np.float32))
               .astype(dtype) for _ in range(3))
    weight = jnp.arange(8, dtype=jnp.float32)

    def loss(attend):
        def f(q, k, v):
            out = attend(q, k, v, causal, 0.35)
            return (out.astype(jnp.float32) * weight).sum()
        return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)

    val, grads = loss(att._flash_bshd)
    want_val, want_grads = loss(att.attention_reference_bshd)
    np.testing.assert_allclose(float(val), float(want_val), rtol=1e-5)
    for got, ref, operand in zip(grads, want_grads, (q, k, v)):
        assert got.dtype == operand.dtype
        ref = np.asarray(ref, np.float32)
        np.testing.assert_allclose(np.asarray(got, np.float32), ref,
                                   rtol=tol, atol=tol * np.abs(ref).max())

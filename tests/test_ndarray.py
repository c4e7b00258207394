"""NDArray core semantics (parity model: [U:tests/python/unittest/test_ndarray.py])."""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.utils.test_utils import assert_almost_equal

from common import with_seed


def test_creation():
    a = mx.nd.zeros((3, 4))
    assert a.shape == (3, 4)
    assert a.dtype == np.float32
    assert_almost_equal(a, np.zeros((3, 4)))
    b = mx.nd.ones((2,), dtype="int32")
    assert b.dtype == np.int32
    c = mx.nd.full((2, 2), 7.0)
    assert_almost_equal(c, np.full((2, 2), 7.0))
    d = mx.nd.array([[1, 2], [3, 4]])
    assert d.dtype == np.float32
    assert_almost_equal(d, np.array([[1, 2], [3, 4]], dtype="float32"))
    e = mx.nd.arange(0, 10, 2)
    assert_almost_equal(e, np.arange(0, 10, 2, dtype="float32"))


def test_basic_math():
    a = mx.nd.array([[1.0, 2.0], [3.0, 4.0]])
    b = mx.nd.array([[5.0, 6.0], [7.0, 8.0]])
    assert_almost_equal(a + b, np.array([[6, 8], [10, 12]]))
    assert_almost_equal(a - b, np.array([[-4, -4], [-4, -4]]))
    assert_almost_equal(a * b, np.array([[5, 12], [21, 32]]))
    assert_almost_equal(b / a, np.array([[5, 3], [7 / 3, 2]]))
    assert_almost_equal(a + 1, np.array([[2, 3], [4, 5]]))
    assert_almost_equal(2 * a, np.array([[2, 4], [6, 8]]))
    assert_almost_equal(1.0 / a, 1.0 / a.asnumpy())
    assert_almost_equal(a ** 2, a.asnumpy() ** 2)
    assert_almost_equal(-a, -a.asnumpy())


def test_inplace_and_setitem():
    a = mx.nd.zeros((3, 3))
    a[:] = 5.0
    assert_almost_equal(a, np.full((3, 3), 5.0))
    a += 1
    assert_almost_equal(a, np.full((3, 3), 6.0))
    a[0, 0] = 0.0
    assert a.asnumpy()[0, 0] == 0.0
    a[1] = np.array([9.0, 9.0, 9.0])
    assert_almost_equal(a.asnumpy()[1], np.full((3,), 9.0))
    v0 = a._version
    a *= 2
    assert a._version > v0


def test_indexing():
    x = np.arange(24, dtype="float32").reshape(2, 3, 4)
    a = mx.nd.array(x)
    assert_almost_equal(a[0], x[0])
    assert_almost_equal(a[1, 2], x[1, 2])
    assert_almost_equal(a[:, 1], x[:, 1])
    assert_almost_equal(a[0, 1:3], x[0, 1:3])
    assert_almost_equal(a[:, :, -1], x[:, :, -1])
    idx = mx.nd.array([1, 0], dtype="int32")
    assert_almost_equal(a[idx], x[[1, 0]])


def test_reshape_magic():
    x = np.arange(24, dtype="float32").reshape(2, 3, 4)
    a = mx.nd.array(x)
    assert a.reshape((-1,)).shape == (24,)
    assert a.reshape((0, -1)).shape == (2, 12)
    assert a.reshape((-2,)).shape == (2, 3, 4)
    assert a.reshape((-3, 4)).shape == (6, 4)
    assert a.reshape((0, -3)).shape == (2, 12)
    assert a.reshape((-4, 1, 2, -2)).shape == (1, 2, 3, 4)
    assert a.reshape((2, -4, -1, 3, 4)).shape == (2, 1, 3, 4)
    assert_almost_equal(a.reshape((6, 4)), x.reshape(6, 4))


def test_shape_ops():
    x = np.arange(24, dtype="float32").reshape(2, 3, 4)
    a = mx.nd.array(x)
    assert_almost_equal(a.T, x.T)
    assert_almost_equal(a.transpose((1, 0, 2)), x.transpose(1, 0, 2))
    assert_almost_equal(a.swapaxes(0, 2), x.swapaxes(0, 2))
    assert_almost_equal(a.expand_dims(1), np.expand_dims(x, 1))
    assert_almost_equal(a.flatten(), x.reshape(2, -1))
    assert_almost_equal(mx.nd.flip(a, axis=1), np.flip(x, 1))
    assert_almost_equal(a.tile((2, 1, 1)), np.tile(x, (2, 1, 1)))
    assert_almost_equal(a.repeat(2, axis=1), np.repeat(x, 2, 1))
    parts = a.split(2, axis=2)
    assert len(parts) == 2 and parts[0].shape == (2, 3, 2)
    assert_almost_equal(mx.nd.concat(parts[0], parts[1], dim=2), x)
    assert_almost_equal(mx.nd.stack(a, a, axis=0), np.stack([x, x]))


def test_reductions():
    x = np.random.uniform(-1, 1, (3, 4, 5)).astype("float32")
    a = mx.nd.array(x)
    assert_almost_equal(a.sum(), x.sum())
    assert_almost_equal(a.sum(axis=1), x.sum(1))
    assert_almost_equal(a.mean(axis=(0, 2)), x.mean((0, 2)))
    assert_almost_equal(a.max(axis=0), x.max(0))
    assert_almost_equal(a.min(axis=-1, keepdims=True), x.min(-1, keepdims=True))
    assert_almost_equal(mx.nd.sum(a, axis=1, exclude=True), x.sum((0, 2)))
    assert int(a.argmax(axis=1).asnumpy()[0, 0]) == int(x.argmax(1)[0, 0])
    assert_almost_equal(a.norm(), np.sqrt((x ** 2).sum()), rtol=1e-4, atol=1e-5)


def test_dot():
    a = np.random.uniform(size=(3, 4)).astype("float32")
    b = np.random.uniform(size=(4, 5)).astype("float32")
    assert_almost_equal(mx.nd.dot(mx.nd.array(a), mx.nd.array(b)), a @ b, rtol=1e-4, atol=1e-5)
    assert_almost_equal(
        mx.nd.dot(mx.nd.array(a), mx.nd.array(b.T), transpose_b=True), a @ b, rtol=1e-4, atol=1e-5
    )
    assert_almost_equal(
        mx.nd.dot(mx.nd.array(a.T), mx.nd.array(b), transpose_a=True), a @ b, rtol=1e-4, atol=1e-5
    )
    # batched
    x = np.random.uniform(size=(2, 3, 4)).astype("float32")
    y = np.random.uniform(size=(2, 4, 5)).astype("float32")
    assert_almost_equal(mx.nd.batch_dot(mx.nd.array(x), mx.nd.array(y)), x @ y, rtol=1e-4, atol=1e-5)


def test_comparison_and_where():
    a = mx.nd.array([1.0, 2.0, 3.0])
    b = mx.nd.array([3.0, 2.0, 1.0])
    assert_almost_equal(a > b, np.array([0.0, 0.0, 1.0]))
    assert_almost_equal(a == b, np.array([0.0, 1.0, 0.0]))
    assert_almost_equal(mx.nd.where(a > b, a, b), np.array([3.0, 2.0, 3.0]))
    assert_almost_equal(mx.nd.maximum(a, b), np.array([3.0, 2.0, 3.0]))


def test_astype_copy_context():
    a = mx.nd.array([1.5, 2.5])
    b = a.astype("int32")
    assert b.dtype == np.int32
    c = a.copy()
    c[:] = 0
    assert_almost_equal(a, np.array([1.5, 2.5]))
    d = a.as_in_context(mx.cpu())
    assert d.context == mx.cpu()
    # a requested accelerator is never substituted by the host CPU
    with pytest.raises(mx.MXNetError, match=r"tpu\(0\) requested"):
        mx.nd.zeros((2,), ctx=mx.tpu())
    # copyto
    f = mx.nd.zeros((2,))
    a.copyto(f)
    assert_almost_equal(f, np.array([1.5, 2.5]))


def test_scalar_conversion():
    a = mx.nd.array([3.5])
    assert a.asscalar() == 3.5
    assert float(a) == 3.5
    assert int(mx.nd.array([2])) == 2
    with pytest.raises(ValueError):
        mx.nd.zeros((2,)).asscalar()


def test_wait_and_version():
    a = mx.nd.ones((10, 10))
    b = (a * 2).wait_to_read()
    assert_almost_equal(b, np.full((10, 10), 2.0))
    mx.nd.waitall()


def test_save_load(tmp_path):
    fname = str(tmp_path / "arrays.params")
    d = {"w": mx.nd.array([1.0, 2.0]), "b": mx.nd.array([[3.0]])}
    mx.nd.save(fname, d)
    loaded = mx.nd.load(fname)
    assert set(loaded) == {"w", "b"}
    assert_almost_equal(loaded["w"], d["w"])
    lst = [mx.nd.array([1.0]), mx.nd.array([2.0, 3.0])]
    mx.nd.save(fname, lst)
    loaded = mx.nd.load(fname)
    assert isinstance(loaded, list) and len(loaded) == 2
    assert_almost_equal(loaded[1], lst[1])


@with_seed()
def test_random_basic():
    a = mx.nd.random.uniform(0, 1, (100, 100))
    assert 0.4 < float(a.mean().asscalar()) < 0.6
    b = mx.nd.random.normal(0, 1, (100, 100))
    assert abs(float(b.mean().asscalar())) < 0.1
    mx.random.seed(42)
    x1 = mx.nd.random.uniform(shape=(5,)).asnumpy()
    mx.random.seed(42)
    x2 = mx.nd.random.uniform(shape=(5,)).asnumpy()
    assert np.array_equal(x1, x2)
    c = mx.nd.random.randint(0, 10, (50,))
    cn = c.asnumpy()
    assert cn.min() >= 0 and cn.max() < 10


def test_take_pick_onehot():
    x = np.arange(12, dtype="float32").reshape(3, 4)
    a = mx.nd.array(x)
    idx = mx.nd.array([2, 0], dtype="int32")
    assert_almost_equal(mx.nd.take(a, idx), x[[2, 0]])
    p = mx.nd.pick(a, mx.nd.array([1, 2, 3]), axis=1)
    assert_almost_equal(p, np.array([x[0, 1], x[1, 2], x[2, 3]]))
    oh = mx.nd.one_hot(mx.nd.array([0, 2], dtype="int32"), 3)
    assert_almost_equal(oh, np.eye(3, dtype="float32")[[0, 2]])


def test_topk_sort():
    x = np.array([[3.0, 1.0, 2.0], [0.0, 5.0, 4.0]], dtype="float32")
    a = mx.nd.array(x)
    idx = mx.nd.topk(a, k=2)
    assert idx.shape == (2, 2)
    assert set(idx.asnumpy()[0].astype(int).tolist()) == {0, 2}
    vals = mx.nd.topk(a, k=1, ret_typ="value")
    assert_almost_equal(vals, np.array([[3.0], [5.0]]))
    assert_almost_equal(mx.nd.sort(a, axis=1), np.sort(x, 1))


def test_mx_np_namespace():
    a = mx.np.array([[1.0, 2.0], [3.0, 4.0]])
    b = mx.np.exp(a)
    assert isinstance(b, mx.NDArray)
    assert_almost_equal(b, np.exp(a.asnumpy()))
    c = mx.np.concatenate([a, a], axis=0)
    assert c.shape == (4, 2)
    assert float(mx.np.trace(a).asscalar()) == 5.0


def test_gamma_is_gamma_function():
    assert abs(float(mx.nd.gamma(mx.nd.array([3.0])).asscalar()) - 2.0) < 1e-4
    assert abs(float(mx.nd.gammaln(mx.nd.array([3.0])).asscalar()) - np.log(2.0)) < 1e-4


def test_method_tail_pad_round_floor_ceil_diag():
    """Round-5 NDArray method tail mirrors the reference's fluent set."""
    a = mx.nd.array(np.array([[1.5, -2.5], [0.4, 3.6]], np.float32))
    np.testing.assert_allclose(a.round().asnumpy(),
                               [[2.0, -3.0], [0.0, 4.0]])  # half away from 0
    np.testing.assert_allclose(a.floor().asnumpy(), np.floor(a.asnumpy()))
    np.testing.assert_allclose(a.ceil().asnumpy(), np.ceil(a.asnumpy()))
    p = a.pad(pad_width=(0, 0, 1, 1), constant_value=9.0)
    assert p.shape == (2, 4) and p.asnumpy()[0, 0] == 9.0
    d = mx.nd.array(np.array([1.0, 2.0])).diag()
    np.testing.assert_allclose(d.asnumpy(), np.diag([1.0, 2.0]))


def test_contrib_boolean_mask():
    """[U:src/operator/contrib/boolean_mask.cc]: eager data-dependent
    selection, differentiable through the kept rows; traced masks raise."""
    from incubator_mxnet_tpu import autograd

    a = mx.nd.array(np.arange(12.0).reshape(4, 3))
    m = mx.nd.array(np.array([1, 0, 1, 0], np.float32))
    out = mx.nd.contrib.boolean_mask(a, m)
    np.testing.assert_allclose(out.asnumpy(), a.asnumpy()[[0, 2]])
    # axis=1
    mc = mx.nd.array(np.array([0, 1, 1], np.float32))
    out = mx.nd.contrib.boolean_mask(a, mc, axis=1)
    np.testing.assert_allclose(out.asnumpy(), a.asnumpy()[:, 1:])
    # gradient scatters into the kept rows only
    a.attach_grad()
    with autograd.record():
        y = mx.nd.contrib.boolean_mask(a, m).sum()
    y.backward()
    np.testing.assert_allclose(a.grad.asnumpy().sum(1), [3.0, 0.0, 3.0, 0.0])
    # traced mask -> actionable error
    import jax
    import pytest as _pytest
    with _pytest.raises(NotImplementedError, match="CONCRETE mask"):
        jax.jit(lambda d, mm: mx.nd.contrib.boolean_mask(
            mx.nd.NDArray(d), mx.nd.NDArray(mm))._data)(a._data, m._data)
    # explicit bool-dtype mask indexing on NDArray also works eagerly
    mask = (a > 6).astype("bool")
    assert a[mask].shape == (5,)


def test_sym_contrib_namespace():
    import incubator_mxnet_tpu.symbol as S

    S.symbol._reset_naming()
    x = S.var("x")
    y = S.contrib.AdaptiveAvgPooling2D(x, output_size=(2, 2))
    _, outs, _ = y.infer_shape(x=(1, 3, 8, 8))
    assert outs == [(1, 3, 2, 2)]

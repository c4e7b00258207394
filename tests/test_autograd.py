"""Autograd tape semantics (parity model: [U:tests/python/unittest/test_autograd.py])."""
import numpy as np

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd
from incubator_mxnet_tpu.utils.test_utils import assert_almost_equal, check_numeric_gradient

from common import with_seed


def test_record_backward_simple():
    x = mx.nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = (x * x).sum()
    y.backward()
    assert_almost_equal(x.grad, 2 * x.asnumpy())


def test_chain_rule():
    x = mx.nd.array([[0.5, -0.5], [1.0, 2.0]])
    x.attach_grad()
    with autograd.record():
        y = mx.nd.exp(x) * 2
        z = (y + x).sum()
    z.backward()
    assert_almost_equal(x.grad, 2 * np.exp(x.asnumpy()) + 1)


def test_head_grad():
    x = mx.nd.array([1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 3
    y.backward(mx.nd.array([10.0, 20.0]))
    assert_almost_equal(x.grad, np.array([30.0, 60.0]))


def test_grad_req_add_and_zero():
    x = mx.nd.array([1.0, 1.0])
    x.attach_grad(grad_req="add")
    for _ in range(3):
        with autograd.record():
            y = (x * x).sum()
        y.backward()
    assert_almost_equal(x.grad, np.array([6.0, 6.0]))
    x.zero_grad()
    assert_almost_equal(x.grad, np.array([0.0, 0.0]))


def test_write_overwrites():
    x = mx.nd.array([2.0])
    x.attach_grad()
    for _ in range(2):
        with autograd.record():
            y = (x * x).sum()
        y.backward()
    assert_almost_equal(x.grad, np.array([4.0]))


def test_multi_input_multi_use():
    a = mx.nd.array([3.0])
    b = mx.nd.array([4.0])
    a.attach_grad()
    b.attach_grad()
    with autograd.record():
        c = a * b + a  # dc/da = b + 1, dc/db = a
    c.backward()
    assert_almost_equal(a.grad, np.array([5.0]))
    assert_almost_equal(b.grad, np.array([3.0]))


def test_is_recording_training():
    assert not autograd.is_recording()
    with autograd.record():
        assert autograd.is_recording()
        assert autograd.is_training()
        with autograd.pause():
            assert not autograd.is_recording()
        with autograd.predict_mode():
            assert not autograd.is_training()
    with autograd.train_mode():
        assert autograd.is_training()


def test_detach():
    x = mx.nd.array([2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 3
        z = (y.detach() * x).sum()
    z.backward()
    assert_almost_equal(x.grad, np.array([6.0]))  # y treated as constant


def test_autograd_grad_api():
    x = mx.nd.array([2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = (x * x * x).sum()
    (g,) = autograd.grad([y], [x])
    assert_almost_equal(g, 3 * x.asnumpy() ** 2)
    # .grad buffer untouched by autograd.grad
    assert_almost_equal(x.grad, np.zeros(2))


def test_retain_graph():
    x = mx.nd.array([2.0])
    x.attach_grad()
    with autograd.record():
        y = (x * x).sum()
    y.backward(retain_graph=True)
    assert_almost_equal(x.grad, np.array([4.0]))
    y.backward()
    assert_almost_equal(x.grad, np.array([4.0]))


def test_custom_function():
    class Sigmoid(autograd.Function):
        def forward(self, x):
            y = mx.nd.sigmoid(x)
            self.save_for_backward(y)
            return y

        def backward(self, dy):
            (y,) = self.saved_tensors
            return dy * y * (1 - y)

    f = Sigmoid()
    x = mx.nd.array([0.5, -0.5])
    x.attach_grad()
    with autograd.record():
        y = f(x)
        z = y.sum()
    z.backward()
    s = 1 / (1 + np.exp(-x.asnumpy()))
    assert_almost_equal(x.grad, s * (1 - s), rtol=1e-5, atol=1e-6)


def test_mark_variables():
    x = mx.nd.array([1.0, 2.0])
    g = mx.nd.zeros((2,))
    autograd.mark_variables([x], [g])
    with autograd.record():
        y = (x * 4).sum()
    y.backward()
    assert_almost_equal(x.grad, np.array([4.0, 4.0]))


@with_seed()
def test_numeric_gradient_matmul():
    a = np.random.uniform(-1, 1, (3, 4)).astype("float32")
    b = np.random.uniform(-1, 1, (4, 2)).astype("float32")
    check_numeric_gradient(lambda x, y: mx.nd.dot(x, y), [a, b])


@with_seed()
def test_numeric_gradient_elemwise():
    x = np.random.uniform(0.5, 2.0, (5, 5)).astype("float32")
    check_numeric_gradient(lambda a: mx.nd.log(a) * mx.nd.sqrt(a), [x])


def test_getitem_grad():
    x = mx.nd.array([[1.0, 2.0], [3.0, 4.0]])
    x.attach_grad()
    with autograd.record():
        y = (x[0] * 2).sum()
    y.backward()
    assert_almost_equal(x.grad, np.array([[2.0, 2.0], [0.0, 0.0]]))


def test_multi_output_op_grad():
    x = mx.nd.array([[1.0, 2.0, 3.0, 4.0]])
    x.attach_grad()
    with autograd.record():
        parts = mx.nd.split(x, 2, axis=1)
        z = (parts[0] * 2 + parts[1] * 3).sum()
    z.backward()
    assert_almost_equal(x.grad, np.array([[2.0, 2.0, 3.0, 3.0]]))


def test_stop_gradient_blocks():
    x = mx.nd.array([2.0])
    x.attach_grad()
    with autograd.record():
        z = (3 * mx.nd.stop_gradient(x * x)).sum()
    z.backward()
    assert float(x.grad.asscalar()) == 0.0


def test_function_grad_alignment_with_constant_input():
    """Custom Function must pair grads positionally even when an earlier
    input is not attached (regression for provenance filtering bug)."""

    class F(autograd.Function):
        def forward(self, a, b):
            return a * b

        def backward(self, dy):
            return dy * 0 + 111, dy * 0 + 222

    c = mx.nd.array([1.0])
    v = mx.nd.array([1.0])
    v.attach_grad()
    with autograd.record():
        out = F()(c, v).sum()
    out.backward()
    assert float(v.grad.asscalar()) == 222.0


def test_grad_rejects_unmarked_intermediate():
    import pytest

    x = mx.nd.array([1.0])
    x.attach_grad()
    with autograd.record():
        y = x * 2
        z = (y * 3).sum()
    with pytest.raises(ValueError):
        autograd.grad([z], [y])


def test_dropout_eval_identity_train_random():
    d = mx.nd.Dropout(mx.nd.ones((4, 4)), p=0.5)
    assert float(d.sum().asscalar()) == 16.0
    with autograd.record():
        d2 = mx.nd.Dropout(mx.nd.ones((200,)), p=0.5)
    # sum()!=200 is a bad oracle: it trips whenever exactly half the mask
    # survives (~5.6% of seeds).  Dropped-count > 0 fails with p = 2^-200.
    assert int((d2.asnumpy() == 0).sum()) > 0


def test_dropout_fast_path_unbiased():
    """The uint8-bits fast path rescales by its own quantized keep-prob, so
    surviving values are exactly data/keep_q and the empirical drop rate
    tracks p to the 1/256 quantization."""
    mx.random.seed(7)
    n = 200_000
    with autograd.record():
        out = mx.nd.Dropout(mx.nd.ones((n,)), p=0.1).asnumpy()
    kept = out[out != 0]
    thresh = round(0.9 * 256)
    np.testing.assert_allclose(kept, 256.0 / thresh, rtol=1e-6)
    drop_rate = 1.0 - len(kept) / n
    assert abs(drop_rate - (1 - thresh / 256.0)) < 0.01


def test_second_order_grad_basic():
    """grad(create_graph=True): d/dx of ||grad sum(x^3)||^2 == 36 x^3
    (parity: tests/python/unittest/test_higher_order_grad.py idiom)."""
    x = mx.nd.array(np.array([1.0, 2.0, -0.5], np.float32))
    x.attach_grad()
    with autograd.record():
        y = (x ** 3).sum()
        g = autograd.grad(y, x, create_graph=True)
        z = (g * g).sum()
    z.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), 36 * x.asnumpy() ** 3,
                               rtol=1e-5)


def test_second_order_grad_matches_jax_oracle():
    """Gradient penalty d/dx and d/dw of ||∂f/∂x||² vs functional jax —
    the cross-term through the replayed forward must be exact."""
    import jax
    import jax.numpy as jnp

    xv = np.array([0.3, -1.2, 0.8], np.float32)
    wv = np.array([0.5, 2.0, -1.0], np.float32)

    def f(x, w):
        return jnp.sum(jnp.tanh(x * w))

    def pen(x, w):
        return jnp.sum(jax.grad(f, argnums=0)(x, w) ** 2)

    want_x = np.asarray(jax.grad(pen, argnums=0)(jnp.array(xv), jnp.array(wv)))
    want_w = np.asarray(jax.grad(pen, argnums=1)(jnp.array(xv), jnp.array(wv)))

    x = mx.nd.array(xv)
    w = mx.nd.array(wv)
    x.attach_grad()
    w.attach_grad()
    with autograd.record():
        y = mx.nd.tanh(x * w).sum()
        gx = autograd.grad(y, x, create_graph=True)
        p = (gx * gx).sum()
    p.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), want_x, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(w.grad.asnumpy(), want_w, rtol=1e-4, atol=1e-6)


def test_third_order_grad():
    """create_graph composes: d³/dx³ of x⁴ (summed) is 24x."""
    x = mx.nd.array(np.array([1.5, -2.0], np.float32))
    x.attach_grad()
    with autograd.record():
        y = (x ** 4).sum()
        g1 = autograd.grad(y, x, create_graph=True)   # 4x³
        g2 = autograd.grad(g1.sum(), x, create_graph=True)  # 12x²
        z3 = g2.sum()
    z3.backward()                                     # 24x
    np.testing.assert_allclose(x.grad.asnumpy(), 24 * x.asnumpy(), rtol=1e-5)


def test_second_order_grad_wrt_intermediate():
    """create_graph also returns grads w.r.t. intermediates (not only
    marked leaves)."""
    x = mx.nd.array(np.array([2.0], np.float32))
    x.attach_grad()
    with autograd.record():
        h = x * x           # intermediate
        y = (h * h).sum()   # x^4
        gh = autograd.grad(y, h, create_graph=True)  # 2h = 2x²
        z = (gh * gh).sum()                          # 4x⁴
    z.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), 16 * x.asnumpy() ** 3,
                               rtol=1e-5)


def test_create_graph_immune_to_inplace_mutation():
    """Second-order replay uses record-time snapshots: mutating x after
    the forward must not corrupt the gradient."""
    x = mx.nd.array(np.array([3.0], np.float32))
    x.attach_grad()
    with autograd.record():
        y = (x * x).sum()
        x[:] = 0.0  # in-place mutation after the recorded op
        g = autograd.grad(y, x, create_graph=True)
    np.testing.assert_allclose(g.asnumpy(), [6.0])  # 2*x at record time


def test_create_graph_with_numpy_calling_function():
    """A custom Function whose backward uses asnumpy() must not break an
    unrelated create_graph pass (it runs eagerly, grads are constants)."""

    class NumpyBackward(autograd.Function):
        def forward(self, a):
            return a * 2.0

        def backward(self, dy):
            scale = float(dy.sum().asnumpy())  # eager-only operation
            return dy * (2.0 if scale == scale else 0.0)

    w = mx.nd.array(np.array([1.0, 2.0], np.float32))
    w.attach_grad()
    a = mx.nd.array(np.array([0.5, 0.5], np.float32))
    a.attach_grad()
    with autograd.record():
        y = (w ** 2).sum() + NumpyBackward()(a).sum()
        g = autograd.grad(y, w, create_graph=True)
        z = (g * g).sum()
    z.backward()
    np.testing.assert_allclose(w.grad.asnumpy(), 8 * w.asnumpy(), rtol=1e-5)


def test_backward_frees_replay_state():
    """Plain first-order backward must release the replay snapshot along
    with the vjp residuals (peak-memory contract)."""
    x = mx.nd.array(np.array([2.0], np.float32))
    x.attach_grad()
    with autograd.record():
        y = (x * x).sum()
    node = y._prov[0]
    assert node._replay_raw is not None
    y.backward()
    assert node.vjp_fn is None
    assert node._replay_fn is None and node._replay_raw is None


@with_seed()
def test_leaf_survives_inplace_update():
    """`w -= lr * w.grad` outside record() — the reference's manual-SGD
    idiom — must keep the attach_grad leaf on the tape (round-4 fix:
    _inplace used to wipe the leaf provenance)."""
    w = mx.nd.array(np.array([4.0, -3.0], np.float32))
    w.attach_grad()
    losses = []
    for _ in range(25):
        with autograd.record():
            loss = (w * w).sum()
        loss.backward()
        w -= 0.1 * w.grad
        losses.append(float(loss.asnumpy()))
    assert losses[-1] < 1e-2 * losses[0], losses[-1]


def test_second_order_grad_through_rnn_megaop():
    """Gradient-penalty (||d loss/d data||²) through the fused RNN scan vs
    the functional jax oracle — create_graph must compose with lax.scan."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.rnn_ops import rnn_mega, rnn_param_size

    T, B, C, H = 3, 2, 2, 3
    rng = np.random.RandomState(0)
    xv = rng.uniform(-1, 1, (T, B, C)).astype(np.float32)
    pv = rng.uniform(-0.3, 0.3, (rnn_param_size("gru", C, H),)).astype(np.float32)

    def f(x):
        return jnp.sum(rnn_mega(x, jnp.asarray(pv), mode="gru", state_size=H))

    def pen(x):
        return jnp.sum(jax.grad(f)(x) ** 2)

    want = np.asarray(jax.grad(pen)(jnp.asarray(xv)))

    x = mx.nd.array(xv)
    x.attach_grad()
    with autograd.record():
        y = mx.nd.RNN(x, mx.nd.array(pv), mode="gru", state_size=H).sum()
        g = autograd.grad(y, x, create_graph=True)
        p = (g * g).sum()
    p.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), want, rtol=1e-3, atol=1e-5)


def test_second_order_grad_through_deformable_conv():
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops.spatial import deformable_convolution

    rng = np.random.RandomState(1)
    xv = rng.randn(1, 2, 5, 5).astype(np.float32)
    wv = rng.randn(2, 2, 3, 3).astype(np.float32)
    off = np.full((1, 18, 5, 5), 0.37, np.float32)

    def f(x):
        return jnp.sum(deformable_convolution(
            x, jnp.asarray(off), jnp.asarray(wv), kernel=(3, 3), pad=(1, 1),
            num_filter=2, no_bias=True))

    def pen(x):
        return jnp.sum(jax.grad(f)(x) ** 2)

    want = np.asarray(jax.grad(pen)(jnp.asarray(xv)))

    x = mx.nd.array(xv)
    x.attach_grad()
    with autograd.record():
        y = mx.nd._contrib_DeformableConvolution(
            x, mx.nd.array(off), mx.nd.array(wv), kernel=(3, 3), pad=(1, 1),
            num_filter=2, no_bias=True).sum()
        g = autograd.grad(y, x, create_graph=True)
        p = (g * g).sum()
    p.backward()
    np.testing.assert_allclose(x.grad.asnumpy(), want, rtol=1e-3, atol=1e-4)

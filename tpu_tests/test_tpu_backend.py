"""cpu-vs-tpu correctness for the core op surface + on-hardware Pallas
flash attention + AMP bf16 numerics + a small train-to-accuracy.

Parity: [U:tests/python/gpu/test_operator_gpu.py]'s rerun-under-ctx
pattern, with ``check_consistency`` (utils/test_utils.py) as the oracle —
jax-CPU is the reference backend, the TPU the device under test.

Tolerances: TPU fp32 matmuls run through the MXU with fp32 accumulate but
bf16-precision multiplies unless precision=HIGHEST; the package pins
highest by default, so most ops compare at tight tolerance.  Ops with
reductions get a slightly looser bound.
"""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.utils.test_utils import check_consistency

RNG = np.random.RandomState(7)


def _r(*shape):
    return RNG.randn(*shape).astype(np.float32)


def _p(*shape):
    return np.abs(RNG.randn(*shape)).astype(np.float32) + 0.5


# ---------------------------------------------------------------------------
# ~30 core ops, forward + gradient, cpu-vs-tpu
# ---------------------------------------------------------------------------

ELEMWISE_CASES = [
    ("add", lambda a, b: a + b, [_r(4, 5), _r(4, 5)], None),
    ("sub", lambda a, b: a - b, [_r(4, 5), _r(4, 5)], None),
    ("mul", lambda a, b: a * b, [_r(4, 5), _r(4, 5)], None),
    ("div", lambda a, b: a / b, [_r(4, 5), _p(4, 5)], None),
    ("exp", lambda a: mx.nd.exp(a), [_r(3, 4)], None),
    # TPU transcendental units round differently from the CPU libm path:
    # log/log_softmax observed at ~1.6e-4 rel — still fp32-faithful
    ("log", lambda a: mx.nd.log(a), [_p(3, 4)], "loose"),
    ("sqrt", lambda a: mx.nd.sqrt(a), [_p(3, 4)], None),
    ("square", lambda a: mx.nd.square(a), [_r(3, 4)], None),
    ("tanh", lambda a: mx.nd.tanh(a), [_r(3, 4)], None),
    ("sigmoid", lambda a: mx.nd.sigmoid(a), [_r(3, 4)], None),
    ("relu", lambda a: mx.nd.relu(a), [_r(3, 4)], None),
    ("leaky_relu", lambda a: mx.nd.LeakyReLU(a, act_type="leaky"), [_r(3, 4)], None),
    ("gelu", lambda a: mx.nd.LeakyReLU(a, act_type="gelu"), [_r(3, 4)], None),
    ("clip", lambda a: mx.nd.clip(a, -0.5, 0.5), [_r(3, 4)], None),
    ("maximum", lambda a, b: mx.nd.maximum(a, b), [_r(3, 4), _r(3, 4)], None),
    ("where", lambda c, a, b: mx.nd.where(c > 0, a, b), [_r(3, 4), _r(3, 4), _r(3, 4)], None),
    ("sum", lambda a: mx.nd.sum(a, axis=1), [_r(4, 6)], None),
    ("mean", lambda a: mx.nd.mean(a, axis=0), [_r(4, 6)], None),
    ("max", lambda a: mx.nd.max(a, axis=1), [_r(4, 6)], None),
    ("argmax-fwd", lambda a: mx.nd.argmax(a, axis=1), [_r(4, 6)], "nograd"),
    ("transpose", lambda a: mx.nd.transpose(a, axes=(1, 0, 2)), [_r(2, 3, 4)], None),
    ("reshape", lambda a: a.reshape((6, 4)), [_r(2, 3, 4)], None),
    ("concat", lambda a, b: mx.nd.concat(a, b, dim=1), [_r(3, 2), _r(3, 5)], None),
    ("slice", lambda a: mx.nd.slice_axis(a, axis=1, begin=1, end=3), [_r(4, 5)], None),
    ("softmax", lambda a: mx.nd.softmax(a), [_r(4, 7)], None),
    ("log_softmax", lambda a: mx.nd.log_softmax(a), [_r(4, 7)], "loose"),
    ("dot", lambda a, b: mx.nd.dot(a, b), [_r(4, 6), _r(6, 5)], None),
    ("batch_dot", lambda a, b: mx.nd.batch_dot(a, b), [_r(2, 3, 4), _r(2, 4, 5)], None),
    ("broadcast_add", lambda a, b: mx.nd.broadcast_add(a, b), [_r(4, 5), _r(1, 5)], None),
    ("norm", lambda a: mx.nd.norm(a), [_r(4, 5)], None),
]


@pytest.mark.parametrize("name,fn,inputs,mode", ELEMWISE_CASES,
                         ids=[c[0] for c in ELEMWISE_CASES])
def test_core_op_cpu_vs_tpu(name, fn, inputs, mode):
    tol = 1e-3 if mode == "loose" else 2e-5
    check_consistency(fn, inputs, rtol=tol, atol=tol, grad=(mode != "nograd"))


def test_fully_connected_cpu_vs_tpu():
    w, b = _r(8, 12), _r(8)
    check_consistency(
        lambda x, w, b: mx.nd.FullyConnected(x, w, b, num_hidden=8),
        [_r(4, 12), w, b], rtol=1e-4, atol=1e-4)


def test_convolution_cpu_vs_tpu():
    check_consistency(
        lambda x, w, b: mx.nd.Convolution(x, w, b, kernel=(3, 3), num_filter=6, pad=(1, 1)),
        [_r(2, 3, 8, 8), _r(6, 3, 3, 3), _r(6)], rtol=1e-4, atol=1e-4)


def test_pooling_cpu_vs_tpu():
    check_consistency(
        lambda x: mx.nd.Pooling(x, kernel=(2, 2), stride=(2, 2), pool_type="max"),
        [_r(2, 3, 8, 8)], rtol=1e-5, atol=1e-5)
    check_consistency(
        lambda x: mx.nd.Pooling(x, kernel=(2, 2), stride=(2, 2), pool_type="avg"),
        [_r(2, 3, 8, 8)], rtol=1e-5, atol=1e-5)


def test_batchnorm_layernorm_cpu_vs_tpu():
    c = 5
    check_consistency(
        lambda x, g, b, mm, mv: mx.nd.BatchNorm(x, g, b, mm, mv, fix_gamma=False),
        [_r(4, c, 3, 3), _p(c), _r(c), _r(c), _p(c)], rtol=1e-4, atol=1e-4)
    check_consistency(
        lambda x, g, b: mx.nd.LayerNorm(x, g, b),
        [_r(4, 8), _p(8), _r(8)], rtol=1e-4, atol=1e-4)


def test_embedding_take_cpu_vs_tpu():
    from incubator_mxnet_tpu import autograd

    idx = np.array([[1, 3], [0, 2]], dtype=np.float32)
    check_consistency(
        lambda w: mx.nd.Embedding(mx.nd.array(idx, dtype="int32", ctx=w.context), w,
                                  input_dim=5, output_dim=4),
        [_r(5, 4)], rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Pallas flash attention ON HARDWARE (the only place the Mosaic kernel
# actually runs; tests/ exercises it in interpret mode only)
# ---------------------------------------------------------------------------


class TestFlashOnChip:
    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_fwd_matches_xla_on_tpu(self, causal, monkeypatch):
        import jax.numpy as jnp
        from incubator_mxnet_tpu.ops import attention as att

        q = jnp.asarray(_r(1, 2, 1024, 64)).astype(jnp.bfloat16)
        k = jnp.asarray(_r(1, 2, 1024, 64)).astype(jnp.bfloat16)
        v = jnp.asarray(_r(1, 2, 1024, 64)).astype(jnp.bfloat16)
        monkeypatch.setenv("MXNET_TPU_FLASH", "on")   # force the kernel
        out = att.flash_attention(q, k, v, causal=causal)
        monkeypatch.setenv("MXNET_TPU_FLASH", "off")  # force XLA reference
        ref = att.attention_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out, dtype=np.float32), np.asarray(ref, dtype=np.float32),
            rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("heads,s,d_qk,d_v", [(1, 512, 64, 64), (4, 4096, 192, 128)],
                             ids=["s512", "xing-cell-4-heads"])
    def test_pallas_bwd_matches_xla_on_tpu(self, heads, s, d_qk, d_v):
        """The blockwise kernels by the dispatcher's own rule, forward and the
        one-pass backward (two Mosaic calls in the gradient's lowering), at S
        512 and at the decoder cell's shape cut in heads only: 192-wide
        queries and keys, 128-wide values, S 4096, causal, bf16."""
        import jax
        import jax.numpy as jnp
        from incubator_mxnet_tpu.ops import attention as att

        q, k = (jnp.asarray(_r(1, heads, s, d_qk)).astype(jnp.bfloat16) for _ in range(2))
        v = jnp.asarray(_r(1, heads, s, d_v)).astype(jnp.bfloat16)
        assert att._kernel_path(q, k) == ("blockwise", att._Launch(False, (512, 512)))

        def loss(attend):
            return lambda q, k, v: (attend(q, k, v, causal=True) ** 2).sum().astype(jnp.float32)

        grad = jax.jit(jax.grad(loss(att.flash_attention), argnums=(0, 1, 2)))
        assert grad.lower(q, k, v).as_text().count("tpu_custom_call") == 2
        g_ref = jax.grad(loss(att.attention_reference), argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(grad(q, k, v), g_ref):
            np.testing.assert_allclose(
                np.asarray(got, dtype=np.float32), np.asarray(want, dtype=np.float32),
                rtol=5e-2, atol=5e-2)

    @pytest.mark.parametrize("causal", [False, True])
    def test_one_tile_kernels_match_float32_reference_on_tpu(self, causal):
        """BERT-base's heads at S 512 through ``fused_qkv_attention``: the
        dispatcher takes the one-tile kernels by itself (two Mosaic calls in
        the gradient's lowering: forward, backward); forward and gradient
        against the float32 reference at bf16 tolerances."""
        import jax
        import jax.numpy as jnp
        from incubator_mxnet_tpu.ops import attention as att

        b, s, h, dh = 2, 512, 12, 64
        qkv32 = jnp.asarray(_r(b, s, 3 * h * dh))
        weights = jnp.asarray(_r(b, s, h * dh))
        qkv = qkv32.astype(jnp.bfloat16)

        def system(x):
            return att.fused_qkv_attention(x, num_heads=h, causal=causal)

        def plain(x):
            x = x.astype(jnp.float32).reshape(b, s, 3, h, dh).transpose(2, 0, 3, 1, 4)
            out = att.attention_reference(x[0], x[1], x[2], causal=causal)
            return out.transpose(0, 2, 1, 3).reshape(b, s, h * dh)

        def loss(fn):
            return lambda x: (fn(x).astype(jnp.float32) * weights).sum()

        grad = jax.jit(jax.grad(loss(system)))
        assert grad.lower(qkv).as_text().count("tpu_custom_call") == 2
        np.testing.assert_allclose(
            np.asarray(jax.jit(system)(qkv), dtype=np.float32),
            np.asarray(plain(qkv)), rtol=2e-2, atol=2e-2)
        np.testing.assert_allclose(
            np.asarray(grad(qkv), dtype=np.float32),
            np.asarray(jax.grad(loss(plain))(qkv), dtype=np.float32),
            rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# AMP bf16 numerics on the chip
# ---------------------------------------------------------------------------


def test_amp_bf16_matmul_on_tpu():
    from incubator_mxnet_tpu import amp

    x, w = _r(8, 16), _r(4, 16)
    fp32 = mx.nd.FullyConnected(
        mx.nd.array(x, ctx=mx.tpu()), mx.nd.array(w, ctx=mx.tpu()), None,
        num_hidden=4, no_bias=True).asnumpy()
    amp.init("bfloat16")
    try:
        out = mx.nd.FullyConnected(
            mx.nd.array(x, ctx=mx.tpu()), mx.nd.array(w, ctx=mx.tpu()), None,
            num_hidden=4, no_bias=True)
        assert str(out.dtype) == "bfloat16"
        np.testing.assert_allclose(out.asnumpy().astype(np.float32), fp32,
                                   rtol=3e-2, atol=3e-2)
    finally:
        amp.disable()


# ---------------------------------------------------------------------------
# Small train-to-accuracy on the chip (fused SPMD step)
# ---------------------------------------------------------------------------


def test_train_mlp_on_tpu():
    import jax
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray
    from incubator_mxnet_tpu.ops.nn import streaming_softmax_ce
    from incubator_mxnet_tpu.parallel import make_mesh, SPMDTrainer

    rng = np.random.RandomState(0)
    n, d = 256, 8
    centers = rng.randn(4, d) * 3
    yb = rng.randint(0, 4, n)
    xb = centers[yb] + rng.randn(n, d) * 0.5

    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(32, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    net(mx.nd.zeros((2, d)))

    def loss_fn(out, label):
        logits = out._data if hasattr(out, "_data") else out[0]._data
        return NDArray(streaming_softmax_ce(logits, label._data))

    accel = [dev for dev in jax.local_devices() if dev.platform != "cpu"]
    mesh = make_mesh(devices=accel[:1])
    trainer = SPMDTrainer(net, loss_fn, "adam", {"learning_rate": 1e-2}, mesh=mesh)
    xs, ys = trainer.shard_batch(xb.astype(np.float32), yb.astype(np.int32))
    for _ in range(60):
        loss = trainer.step(xs, ys)
    final = float(np.asarray(loss._data))
    trainer.sync_to_block()
    pred = net(mx.nd.array(xb.astype(np.float32))).asnumpy().argmax(axis=1)
    acc = (pred == yb).mean()
    assert acc > 0.9, (acc, final)


# ---------------------------------------------------------------------------
# Round-3 op families on the chip
# ---------------------------------------------------------------------------


def test_quantized_fc_on_tpu():
    """int8 MXU matmul path executes on hardware within int8 tolerance."""
    x = _r(8, 32)
    w = _r(16, 32)
    ctx = mx.tpu()
    xq, xmn, xmx = mx.nd.quantize_v2(mx.nd.array(x, ctx=ctx))
    wq, wmn, wmx = mx.nd.quantize_v2(mx.nd.array(w, ctx=ctx))
    out = mx.nd.quantized_fully_connected(
        xq, wq, None, xmn, xmx, wmn, wmx, num_hidden=16, no_bias=True)
    ref = x @ w.T
    np.testing.assert_allclose(out.asnumpy(), ref, atol=np.abs(ref).max() * 0.05)


def test_control_flow_foreach_on_tpu():
    ctx = mx.tpu()
    data = mx.nd.array(_r(6, 4), ctx=ctx)
    init = mx.nd.array(np.zeros(4, np.float32), ctx=ctx)
    outs, final = mx.nd.contrib.foreach(lambda x, s: (s + x, s + x), data, init)
    np.testing.assert_allclose(final.asnumpy(), data.asnumpy().sum(axis=0),
                               rtol=1e-5, atol=1e-5)


def test_gather_positions_on_tpu():
    ctx = mx.tpu()
    seq = mx.nd.array(_r(2, 8, 4), ctx=ctx)
    pos_np = np.array([[1, 5], [0, 7]], np.int32)
    pos = mx.nd.array(pos_np, ctx=ctx)
    out = mx.nd.gather_positions(seq, pos)
    ref = np.take_along_axis(seq.asnumpy(), pos_np[..., None], axis=1)
    np.testing.assert_allclose(out.asnumpy(), ref, rtol=1e-6)


def test_rtc_pallas_kernel_on_tpu():
    """mx.rtc kernels compile through Mosaic and run on the chip; values
    match the CPU interpret path."""
    import numpy as np

    import incubator_mxnet_tpu as mx

    mod = mx.rtc.PallasModule('''
def scale_add(x_ref, y_ref, o_ref):
    o_ref[...] = 2.0 * x_ref[...] + y_ref[...]
''')
    k = mod.get_kernel("scale_add", out_shapes=[(128, 256)])
    x = np.random.RandomState(0).rand(128, 256).astype(np.float32)
    y = np.random.RandomState(1).rand(128, 256).astype(np.float32)
    z = k.launch([mx.nd.array(x), mx.nd.array(y)])
    np.testing.assert_allclose(z.asnumpy(), 2 * x + y, rtol=1e-6)


# ---------------------------------------------------------------------------
# round-4 op families on the chip (same check_consistency oracle)
# ---------------------------------------------------------------------------


def test_linalg_family_cpu_vs_tpu():
    spd = np.einsum("ij,kj->ik", *(2 * [np.random.RandomState(0).randn(4, 4).astype(np.float32)])) + 4 * np.eye(4, dtype=np.float32)
    check_consistency(lambda a: mx.nd.linalg_potrf(a), [spd], rtol=1e-3, atol=1e-3, grad=False)
    check_consistency(lambda a: mx.nd.linalg_sumlogdiag(
        mx.nd.linalg_potrf(a)), [spd], rtol=1e-3, atol=1e-3, grad=False)
    tri = np.tril(np.random.RandomState(1).randn(4, 4)).astype(np.float32)
    np.fill_diagonal(tri, np.abs(np.diag(tri)) + 2)
    b = np.random.RandomState(2).randn(4, 3).astype(np.float32)
    check_consistency(lambda a, bb: mx.nd.linalg_trsm(a, bb), [tri, b],
                      rtol=1e-3, atol=1e-3)
    check_consistency(lambda a: mx.nd.linalg_extractdiag(a), [tri],
                      rtol=0, atol=0)


def test_ctc_loss_cpu_vs_tpu():
    logits = np.random.RandomState(3).randn(6, 2, 5).astype(np.float32)
    labels = np.array([[1, 2], [3, 0]], np.float32)
    check_consistency(lambda d: mx.nd.CTCLoss(d, mx.nd.array(labels)),
                      [logits], rtol=1e-3, atol=1e-3)


def test_spatial_family_cpu_vs_tpu():
    x = np.random.RandomState(4).rand(1, 2, 8, 8).astype(np.float32)
    rois = np.array([[0, 1, 1, 6, 6]], np.float32)
    check_consistency(
        lambda d: mx.nd.ROIPooling(d, mx.nd.array(rois), pooled_size=(2, 2),
                                   spatial_scale=1.0), [x],
        rtol=1e-3, atol=1e-4)
    check_consistency(
        lambda d: mx.nd._contrib_ROIAlign(d, mx.nd.array(rois),
                                          pooled_size=(2, 2),
                                          spatial_scale=1.0, sample_ratio=2),
        [x], rtol=1e-3, atol=1e-4)
    theta = np.array([[1, 0, 0.2, 0, 1, -0.1]], np.float32)
    check_consistency(
        lambda d, t: mx.nd.SpatialTransformer(d, t, target_shape=(8, 8)),
        [x, theta], rtol=1e-3, atol=1e-4)
    check_consistency(
        lambda d: mx.nd._contrib_AdaptiveAvgPooling2D(d, output_size=(3, 3)),
        [x], rtol=1e-3, atol=1e-4)


def test_new_optimizer_kernels_on_tpu():
    """nadam/ftml/adamax fused kernels on the chip vs the same kernels on
    CPU — the file's cpu-vs-tpu oracle, applied at the kernel level."""
    import jax
    import jax.numpy as jnp

    from incubator_mxnet_tpu.ops import optimizer_ops as K

    w = np.random.RandomState(5).randn(8, 4).astype(np.float32)
    g = np.random.RandomState(6).randn(8, 4).astype(np.float32)
    z = np.zeros_like(w)
    cpu = jax.local_devices(backend="cpu")[0]
    hyper = [jnp.float32(v) for v in (0.01, 0.0, 1.0, np.inf)]

    def run(kernel, arrays, extra):
        tpu_out = kernel(*[jnp.asarray(a) for a in arrays], *hyper, *extra)
        with jax.default_device(cpu):
            cpu_out = kernel(*[jnp.asarray(a) for a in arrays], *hyper, *extra)
        for t, c in zip(tpu_out, cpu_out):
            np.testing.assert_allclose(np.asarray(t), np.asarray(c),
                                       rtol=2e-3, atol=2e-4)

    run(K.nadam_update, [w, g, z, z, np.ones((), np.float32)],
        [jnp.float32(0.9), jnp.float32(0.999), jnp.float32(1e-8),
         jnp.float32(1), jnp.float32(0.004)])
    run(K.ftml_update, [w, g, z, z, z],
        [jnp.float32(0.6), jnp.float32(0.999), jnp.float32(1e-8),
         jnp.float32(1)])
    run(K.adamax_update, [w, g, z, z],
        [jnp.float32(0.9), jnp.float32(0.999)])


# ---------------------------------------------------------------------------
# round-5 op families on the chip (same check_consistency oracle)
# ---------------------------------------------------------------------------


def test_rnn_megaop_cpu_vs_tpu():
    from incubator_mxnet_tpu.ops.rnn_ops import rnn_param_size

    T, B, C, H = 5, 2, 3, 4
    rng = np.random.RandomState(7)
    x = rng.uniform(-1, 1, (T, B, C)).astype(np.float32)
    for mode, bidir in (("lstm", True), ("gru", False)):
        n = rnn_param_size(mode, C, H, 2, bidir)
        p = rng.uniform(-0.3, 0.3, (n,)).astype(np.float32)
        check_consistency(
            lambda d, pp, _m=mode, _b=bidir: mx.nd.RNN(
                d, pp, mode=_m, state_size=H, num_layers=2, bidirectional=_b),
            [x, p], rtol=1e-3, atol=1e-4)


def test_deformable_ops_cpu_vs_tpu():
    rng = np.random.RandomState(8)
    x = rng.randn(1, 4, 8, 8).astype(np.float32)
    w = rng.randn(6, 4, 3, 3).astype(np.float32)
    off = np.full((1, 18, 8, 8), 0.37, np.float32)
    check_consistency(
        lambda d, ww: mx.nd._contrib_DeformableConvolution(
            d, mx.nd.array(off), ww, kernel=(3, 3), pad=(1, 1), num_filter=6,
            no_bias=True), [x, w], rtol=1e-3, atol=1e-3)
    C = 2 * 2 * 2
    score = rng.randn(1, C, 8, 8).astype(np.float32)
    rois = np.array([[0, 1, 1, 11, 13]], np.float32)
    check_consistency(
        lambda d: mx.nd._contrib_DeformablePSROIPooling(
            d, mx.nd.array(rois), spatial_scale=0.5, output_dim=2,
            group_size=2, pooled_size=2, sample_per_part=2, no_trans=True),
        [score], rtol=1e-3, atol=1e-4)


def test_scalar_special_cpu_vs_tpu():
    x = np.random.RandomState(9).uniform(0.5, 4.0, (16,)).astype(np.float32)
    check_consistency(lambda d: mx.nd.digamma(d), [x], rtol=1e-3, atol=1e-4)
    check_consistency(lambda d: mx.nd.polygamma(d, n=1), [x],
                      rtol=1e-3, atol=1e-3, grad=False)


def test_pallas_fused_bn_on_tpu():
    """The fused BN epilogue COMPILED on the chip (interpret-mode tests
    cover CPU) vs the stock batch_norm op on the same device."""
    import jax
    import jax.numpy as jnp

    if jax.default_backend() == "cpu":
        pytest.skip("needs an accelerator backend")
    from incubator_mxnet_tpu.ops.pallas_bn import fused_bn_relu
    from incubator_mxnet_tpu.ops.nn import batch_norm

    rng = np.random.RandomState(10)
    x = jnp.asarray(rng.randn(2, 8, 14, 14).astype(np.float32))
    g = jnp.asarray(rng.rand(8).astype(np.float32) + 0.5)
    b = jnp.asarray(rng.randn(8).astype(np.float32))
    got, m, v = fused_bn_relu(x, g, b, relu=False, interpret=False)
    want, wm, wv = batch_norm(x, g, b, jnp.zeros(8), jnp.ones(8),
                              fix_gamma=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(m), np.asarray(wm), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(v), np.asarray(wv), rtol=1e-4,
                               atol=1e-4)


def test_round5_tail_ops_cpu_vs_tpu():
    """Round-5 tail: Crop, legacy quantize, amp casts, element_0index trio
    — cpu-as-oracle rows for the chip tier."""
    rng = np.random.RandomState(11)
    img = rng.randn(2, 3, 8, 8).astype(np.float32)
    check_consistency(
        lambda d: mx.nd.Crop(d, h_w=(4, 4), offset=(1, 2)), [img])
    check_consistency(
        lambda d: mx.nd.Crop(d, mx.nd.zeros((2, 3, 5, 5)), center_crop=True),
        [img])

    x = rng.randn(3, 4).astype(np.float32)
    idx = np.array([0, 2, 3], np.float32)
    check_consistency(
        lambda d: mx.nd.choose_element_0index(d, mx.nd.array(idx)), [x])
    check_consistency(
        lambda d: mx.nd.fill_element_0index(
            d, mx.nd.array([9.0, 8.0, 7.0]), mx.nd.array(idx)), [x])

    check_consistency(lambda d: mx.nd.amp_cast(d, dtype="float16"), [x],
                      rtol=1e-3, atol=1e-3, grad=False)

    q = rng.rand(2, 8).astype(np.float32) * 2 - 1
    check_consistency(
        lambda d: mx.nd.quantize(d, mx.nd.array([-1.0]), mx.nd.array([1.0]),
                                 out_type="uint8")[0], [q], grad=False)


def test_onnx_breadth3_roundtrip_on_tpu():
    """The breadth-3 ONNX roundtrip executed with the TPU as the bind
    target (export/import themselves are host-side)."""
    import tempfile

    import incubator_mxnet_tpu.symbol as S
    from incubator_mxnet_tpu.contrib import onnx as onnx_mxnet

    S.symbol._reset_naming()
    data = S.var("data")
    x = S.clip(data, a_min=-0.8, a_max=0.8)
    x = S.expand_dims(S.sum(x, axis=1), axis=1)
    out_sym = S.log_softmax(S.tile(x, reps=(1, 4)), axis=-1)
    xv = np.random.RandomState(12).rand(3, 5).astype(np.float32) - 0.5

    exe = out_sym.simple_bind(data=xv.shape)
    exe.arg_dict["data"][:] = xv
    ref = exe.forward(is_train=False)[0].asnumpy()

    with tempfile.TemporaryDirectory() as td:
        f = td + "/b3.onnx"
        onnx_mxnet.export_model(out_sym, {}, input_shape=xv.shape,
                                onnx_file_path=f)
        sym2, arg2, aux2 = onnx_mxnet.import_model(f)
    exe2 = sym2.simple_bind(data=xv.shape)
    exe2.arg_dict["data"][:] = xv
    out = exe2.forward(is_train=False)[0].asnumpy()
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)

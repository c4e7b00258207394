"""Transformer layers, flash attention (pallas-interpret + reference), BERT."""
import os

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.gluon.model_zoo import bert as bert_zoo
from incubator_mxnet_tpu.ops import attention as att

import jax
import jax.numpy as jnp


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_interpret_matches_reference(self, causal, monkeypatch):
        """Flash kernel (interpret mode on CPU) vs plain XLA attention."""
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(3, 2, 128, 32).astype(np.float32))
        k = jnp.asarray(rng.randn(3, 2, 128, 32).astype(np.float32))
        v = jnp.asarray(rng.randn(3, 2, 128, 32).astype(np.float32))
        ref = att.attention_reference(q, k, v, causal=causal)
        monkeypatch.setenv("MXNET_TPU_FLASH", "interpret")
        out = att.flash_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    def test_gradients_match_reference(self):
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(2, 2, 64, 16).astype(np.float32))
        k = jnp.asarray(rng.randn(2, 2, 64, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(2, 2, 64, 16).astype(np.float32))

        def f_flash(q, k, v):
            return att.flash_attention(q, k, v, causal=True).sum()

        def f_ref(q, k, v):
            return att.attention_reference(q, k, v, causal=True).sum()

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)

    def test_nd_contrib_namespace(self):
        x = mx.nd.random.normal(shape=(2, 16, 32))
        out = mx.nd.contrib.fused_attention(x, x, x, num_heads=4)
        assert out.shape == (2, 16, 32)

    def test_bf16_supported(self):
        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randn(1, 2, 64, 16)).astype(jnp.bfloat16)
        out = att.flash_attention(q, q, q)
        assert out.dtype == jnp.bfloat16

    @pytest.mark.parametrize("causal", [False, True])
    def test_fused_qkv_matches_split_path(self, causal):
        """[B,S,H,Dh]-layout self-attention (fused_qkv_attention) must equal
        the split-heads bhsd path, values AND gradients."""
        rng = np.random.RandomState(3)
        b, s, h, dh = 2, 32, 4, 16
        d = h * dh
        qkv = jnp.asarray(rng.randn(b, s, 3 * d).astype(np.float32))

        from incubator_mxnet_tpu.ops.attention import fused_qkv_attention

        def split_path(qkv):
            q, k, v = jnp.split(qkv, 3, axis=-1)

            def sp(x):
                return x.reshape(b, s, h, dh).transpose(0, 2, 1, 3)

            out = att.attention_reference(sp(q), sp(k), sp(v), causal=causal)
            return out.transpose(0, 2, 1, 3).reshape(b, s, d)

        out = fused_qkv_attention(qkv, num_heads=h, causal=causal)
        ref = split_path(qkv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

        g1 = jax.grad(lambda x: (fused_qkv_attention(x, num_heads=h, causal=causal) ** 2).sum())(qkv)
        g2 = jax.grad(lambda x: (split_path(x) ** 2).sum())(qkv)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=2e-4, atol=2e-5)

    def test_fused_kv_cross_attention(self):
        rng = np.random.RandomState(4)
        b, sq, sk, h, dh = 2, 8, 16, 2, 8
        d = h * dh
        q = jnp.asarray(rng.randn(b, sq, d).astype(np.float32))
        kv = jnp.asarray(rng.randn(b, sk, 2 * d).astype(np.float32))

        from incubator_mxnet_tpu.ops.attention import fused_kv_attention

        k, v = jnp.split(kv, 2, axis=-1)

        def sp(x, s):
            return x.reshape(b, s, h, dh).transpose(0, 2, 1, 3)

        ref = att.attention_reference(sp(q, sq), sp(k, sk), sp(v, sk))
        ref = ref.transpose(0, 2, 1, 3).reshape(b, sq, d)
        out = fused_kv_attention(q, kv, num_heads=h)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def _spec(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _self(heads, causal=False):
    return lambda qkv: att.fused_qkv_attention(qkv, num_heads=heads, causal=causal)


# what one attention call traces to: (Pallas kernels in the primal, in the VJP
# forward, in the backward)
XLA, TILE, BLOCKWISE = (0, 0, 0), (1, 1, 1), (1, 1, 1)

DISPATCH_CASES = {
    # BERT-base heads from a fused QKV projection: the rule of PERF.md §6, PR 29
    "s128": (_self(12), [_spec(2, 128, 2304)], XLA),
    "s256": (_self(12), [_spec(2, 256, 2304)], TILE),
    "s384": (_self(12), [_spec(2, 384, 2304)], TILE),
    "s512": (_self(12), [_spec(2, 512, 2304)], TILE),
    "s512-causal": (_self(12, True), [_spec(2, 512, 2304)], TILE),
    "s512-float32": (_self(12), [_spec(2, 512, 2304, dtype=jnp.float32)], TILE),
    "s1024": (_self(12), [_spec(2, 1024, 2304)], BLOCKWISE),
    # three 64-wide heads do not fill 128-lane columns: transposes + blockwise,
    # from S 512 and where a block of 256 or more divides the length
    "s512-odd-heads": (_self(3), [_spec(2, 512, 576)], BLOCKWISE),
    "s768-odd-heads": (_self(3), [_spec(2, 768, 576)], BLOCKWISE),
    "s256-odd-heads": (_self(3), [_spec(2, 256, 576)], XLA),
    "s640-odd-heads-block-128": (_self(3), [_spec(2, 640, 576)], XLA),
    "s512-float16": (_self(12), [_spec(2, 512, 2304, dtype=jnp.float16)], XLA),
    # 500 = 4 · 125: no block divides it; 264 = 8 · 33: only an 8-wide one
    "s500-no-block": (_self(12), [_spec(2, 500, 2304)], XLA),
    "s264-narrow-block": (_self(12), [_spec(2, 264, 2304)], XLA),
    # S 128 whose float32 scores are 1.03 GiB: the kernels, for the memory
    "s128-gigabyte-of-scores": (_self(12), [_spec(1400, 128, 2304)], TILE),
    "cross-256x512": (lambda q, kv: att.fused_kv_attention(q, kv, num_heads=2),
                      [_spec(2, 256, 128), _spec(2, 512, 256)], BLOCKWISE),
    "cross-one-query": (lambda q, kv: att.fused_kv_attention(q, kv, num_heads=2),
                        [_spec(2, 1, 128), _spec(2, 512, 256)], XLA),
    # latent attention's widths: 192-wide queries and keys, 128-wide values
    "values-narrower-than-keys": (
        lambda q, k, v: att.fused_attention(q, k, v, num_heads=2, causal=True),
        [_spec(1, 512, 384), _spec(1, 512, 384), _spec(1, 512, 256)], BLOCKWISE),
    "bhsd-s128": (lambda q: att.flash_attention(q, q, q), [_spec(2, 4, 128, 64)], XLA),
    "bhsd-s2048": (lambda q: att.flash_attention(q, q, q, causal=True),
                   [_spec(1, 4, 2048, 64)], BLOCKWISE),
}


@pytest.mark.parametrize("case", sorted(DISPATCH_CASES))
def test_attention_dispatch_by_shape(case, monkeypatch):
    """``_kernel_path`` by shape: the primal, the VJP forward and the backward
    of a call take the same path, and the counters say which.  Traced with
    the kernels ``on`` (nothing is lowered, so the CPU can)."""
    from incubator_mxnet_tpu import profiler

    fn, specs, (primal, vjp_forward, backward) = DISPATCH_CASES[case]
    monkeypatch.setenv("MXNET_TPU_FLASH", "on")

    def kernels(f):
        return str(jax.make_jaxpr(f)(*specs)).count("pallas_call")

    before = profiler.counters()
    assert kernels(fn) == primal
    after = profiler.counters()
    counted = {name: after[name] - before[name]
               for name in ("attention_dispatch_pallas", "attention_dispatch_xla")}
    assert counted == {"attention_dispatch_pallas": int(primal > 0),
                       "attention_dispatch_xla": int(primal == 0)}
    assert kernels(lambda *a: jax.vjp(fn, *a)[0]) == vjp_forward
    loss = lambda *a: fn(*a).astype(jnp.float32).sum()
    assert kernels(jax.grad(loss, argnums=tuple(range(len(specs))))) \
        == vjp_forward + backward


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 4e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_one_tile_kernels_at_s512_match_reference(causal, dtype, tol, monkeypatch):
    """S 512, two 64-wide heads in one 128-lane column of the fused QKV
    projection, through ``fused_qkv_attention`` (interpreter): forward and
    gradient against ``attention_reference`` in float32."""
    monkeypatch.setenv("MXNET_TPU_FLASH", "interpret")
    b, s, h, dh = 1, 512, 2, 64
    assert att._qkv_tile_fits(s, h, dh, dtype)
    keys = jax.random.split(jax.random.PRNGKey(29), 2)
    qkv = jax.random.normal(keys[0], (b, s, 3 * h * dh), dtype)
    weights = jax.random.normal(keys[1], (b, s, h * dh), jnp.float32)

    def plain(x):
        x = x.astype(jnp.float32).reshape(b, s, 3, h, dh).transpose(2, 0, 3, 1, 4)
        out = att.attention_reference(x[0], x[1], x[2], causal=causal)
        return out.transpose(0, 2, 1, 3).reshape(b, s, h * dh)

    system = lambda x: att.fused_qkv_attention(x, num_heads=h, causal=causal)
    out = system(qkv)
    assert out.dtype == dtype and out.shape == (b, s, h * dh)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(plain(qkv)),
                               atol=tol)
    got = jax.grad(lambda x: (system(x).astype(jnp.float32) * weights).sum())(qkv)
    want = jax.grad(lambda x: (plain(x) * weights).sum())(qkv)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("axes,kernels", [({"dp": 4}, True), ({"dp": 2, "fsdp": 2}, True),
                                          ({"dp": 2, "tp": 2}, False), ({"dp": 3}, False)],
                         ids=["dp4", "dp2-fsdp2", "dp2-tp2", "dp3-of-batch-4"])
@pytest.mark.parametrize("path,shape", [("tile", (4, 128, 3 * 2 * 64)),
                                        ("blockwise", (4, 64, 3 * 2 * 16))])
def test_kernels_are_placed_on_the_mesh_of_the_trace(path, shape, axes, kernels, monkeypatch):
    """No compiler partitions a Mosaic kernel, so under ``mesh_scope`` (which
    ``SPMDTrainer`` opens round a step's trace) the dispatcher launches the
    kernels in a ``shard_map`` over the batch axes — the gradient equals the
    one-device one, stays split by rows, and no chip gathers another's — and
    leaves a mesh that splits the model, or a batch it does not divide, to
    the XLA path."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from incubator_mxnet_tpu.parallel import make_mesh, mesh_scope

    n = int(np.prod(list(axes.values())))
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    monkeypatch.setenv("MXNET_TPU_FLASH", "interpret")
    mesh = make_mesh(devices=jax.devices()[:n], **axes)
    x = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)

    def attend(x):
        return att.fused_qkv_attention(x, num_heads=2, causal=True)

    def loss(x):
        return jnp.sum(jnp.square(attend(x)))

    want = jax.grad(loss)(x)
    rows = NamedSharding(mesh, P(("dp", "fsdp")) if kernels else P())

    def on_mesh(fn):
        def scoped(x):
            with mesh_scope(mesh):
                return fn(x)
        return jax.jit(scoped, out_shardings=rows)

    q = x.reshape(shape[0], shape[1], 3, 2, -1)[:, :, 0]
    with mesh_scope(mesh):
        assert att._kernel_path(q, q, seq_axis=1, qkv_heads=2)[0] == (path if kernels else "xla")
    split = jax.device_put(x, rows)
    traced = str(jax.make_jaxpr(on_mesh(attend))(split))
    assert traced.count("shard_map") == traced.count("pallas_call") == int(kernels)
    got = on_mesh(jax.grad(loss))(split)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5)
    if kernels:
        assert "all-gather" not in on_mesh(jax.grad(loss)).lower(split).compile().as_text()


class TestTransformerLayers:
    def test_encoder_cell_shapes_and_grad(self):
        mx.random.seed(0)
        cell = nn.TransformerEncoderCell(units=64, hidden_size=128, num_heads=4)
        cell.initialize()
        x = mx.nd.random.normal(shape=(2, 16, 64))
        with mx.autograd.record():
            y = cell(x)
            loss = (y * y).sum()
        loss.backward()
        assert y.shape == (2, 16, 64)
        g = cell.collect_params()[f"{cell.prefix}attn_qkv_weight"].grad()
        assert float((g.asnumpy() ** 2).sum()) > 0

    def test_encoder_hybridize_consistency(self):
        mx.random.seed(1)
        enc = nn.TransformerEncoder(num_layers=2, units=32, hidden_size=64, num_heads=2)
        enc.initialize()
        x = mx.nd.random.normal(shape=(2, 8, 32))
        eager = enc(x).asnumpy()
        enc.hybridize()
        jitted = enc(x).asnumpy()
        np.testing.assert_allclose(eager, jitted, rtol=2e-5, atol=2e-5)

    def test_decoder_cross_attention(self):
        mx.random.seed(2)
        dec = nn.TransformerDecoder(num_layers=1, units=32, hidden_size=64, num_heads=2)
        dec.initialize()
        tgt = mx.nd.random.normal(shape=(2, 6, 32))
        mem = mx.nd.random.normal(shape=(2, 10, 32))
        out = dec(tgt, mem)
        assert out.shape == (2, 6, 32)

    def test_causal_masking_in_mha(self):
        """Causal MHA output at position t must not depend on inputs > t."""
        mx.random.seed(3)
        mha = nn.MultiHeadAttention(units=16, num_heads=2, causal=True)
        mha.initialize()
        x1 = mx.nd.random.normal(shape=(1, 8, 16))
        y1 = mha(x1).asnumpy()
        x2 = x1.asnumpy().copy()
        x2[0, -1] = 99.0  # perturb the last position
        y2 = mha(mx.nd.array(x2)).asnumpy()
        np.testing.assert_allclose(y1[0, :-1], y2[0, :-1], rtol=1e-5, atol=1e-5)
        assert not np.allclose(y1[0, -1], y2[0, -1])

    def test_sinusoidal_positions(self):
        enc = nn.SinusoidalPositionalEncoding(units=32)
        x = mx.nd.zeros((1, 10, 32))
        out = enc(x).asnumpy()
        assert not np.allclose(out[0, 1], out[0, 2])

    def test_sinusoidal_odd_units(self):
        enc = nn.SinusoidalPositionalEncoding(units=31)
        out = enc(mx.nd.zeros((1, 4, 31))).asnumpy()
        assert out.shape == (1, 4, 31)

    def test_flash_unaligned_seq_falls_back(self, monkeypatch):
        """Non-power-of-two sequence lengths must not crash the pallas path
        (falls back to smaller blocks or the XLA reference)."""
        monkeypatch.setenv("MXNET_TPU_FLASH", "interpret")
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(1, 2, 200, 16).astype(np.float32))
        out = att.flash_attention(q, q, q, causal=True)
        ref = att.attention_reference(q, q, q, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


class TestBERT:
    def _tiny_bert(self, seed=0):
        mx.random.seed(seed)
        net = bert_zoo.BERTModel(
            vocab_size=128, units=32, hidden_size=64, num_layers=2,
            num_heads=2, max_length=64, dropout=0.0,
        )
        net.initialize()
        return net

    def test_forward_shapes(self):
        net = self._tiny_bert()
        ids = mx.nd.array(np.random.RandomState(0).randint(0, 128, (4, 16)), dtype="int32")
        types = mx.nd.zeros((4, 16), dtype="int32")
        seq, pooled = net(ids, types)
        assert seq.shape == (4, 16, 32)
        assert pooled.shape == (4, 32)

    def test_pretrain_heads_and_training_step(self):
        mx.random.seed(1)
        base = bert_zoo.BERTModel(vocab_size=64, units=32, hidden_size=64,
                                  num_layers=1, num_heads=2, max_length=32, dropout=0.0)
        model = bert_zoo.BERTForPretrain(base, vocab_size=64)
        model.initialize()
        rng = np.random.RandomState(0)
        ids = mx.nd.array(rng.randint(0, 64, (2, 8)), dtype="int32")
        labels = mx.nd.array(rng.randint(0, 64, (2, 8)), dtype="float32")
        trainer = gluon.Trainer(model.collect_params(), "adam", {"learning_rate": 1e-3})
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        with mx.autograd.record():
            mlm, nsp = model(ids)
            loss = loss_fn(mlm.reshape((-1, 64)), labels.reshape((-1,)))
        loss.backward()
        trainer.step(ids.shape[0])
        assert mlm.shape == (2, 8, 64)
        assert nsp.shape == (2, 2)

    def test_bert_spmd_tp_training(self):
        """BERT with Megatron-style tp=2 sharding trains and matches the
        replicated result (XLA-inserted collectives)."""
        from incubator_mxnet_tpu.parallel import make_mesh, SPMDTrainer

        def make(seed):
            mx.random.seed(seed)
            base = bert_zoo.BERTModel(vocab_size=64, units=32, hidden_size=64,
                                      num_layers=1, num_heads=2, max_length=32,
                                      dropout=0.0)
            model = bert_zoo.BERTForPretrain(base, vocab_size=64)
            model.initialize()
            model(mx.nd.zeros((2, 8), dtype="int32"))  # materialize deferred shapes
            return model

        rng = np.random.RandomState(0)
        ids = mx.nd.array(rng.randint(0, 64, (8, 8)), dtype="int32")
        labels = rng.randint(0, 64, (8, 8)).astype(np.float32)

        def loss_fn(out, label):
            mlm, nsp = out
            return gluon.loss.SoftmaxCrossEntropyLoss()(
                mlm.reshape((-1, 64)), label.reshape((-1,))
            )

        m_rep = make(7)
        m_tp = make(7)
        a = SPMDTrainer(m_rep, loss_fn, "adam", {"learning_rate": 1e-3},
                        mesh=make_mesh(dp=8))
        b = SPMDTrainer(m_tp, loss_fn, "adam", {"learning_rate": 1e-3},
                        mesh=make_mesh(dp=4, tp=2),
                        rules=bert_zoo.bert_sharding_rules())
        la = lb = None
        for _ in range(2):
            la = a.step(ids, mx.nd.array(labels))
            lb = b.step(ids, mx.nd.array(labels))
        np.testing.assert_allclose(
            la.asnumpy(), lb.asnumpy(), rtol=2e-4, atol=2e-5
        )

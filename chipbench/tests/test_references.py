"""Each plain reference against the system's own model code, tiny, float32."""
import numpy as np

import jax


def named(net):
    return {p.name: p._data._data for p in net.collect_params().values()}


def test_bert_reference_matches_the_models_forward():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo.bert import BERTForPretrain, BERTModel

    from chipbench.reference import bert as reference

    mx.random.seed(3)
    bert = BERTModel(vocab_size=97, units=32, hidden_size=64, num_layers=2,
                     num_heads=4, max_length=24, dropout=0.1)
    net = BERTForPretrain(bert, vocab_size=97)
    net.initialize(mx.init.Normal(0.2))
    rng = np.random.RandomState(0)
    tok = rng.randint(0, 97, (3, 16)).astype(np.int32)
    seg = rng.randint(0, 2, (3, 16)).astype(np.int32)
    pos = np.sort(rng.randint(0, 16, (3, 4)), axis=1).astype(np.int32)
    labels = rng.randint(0, 97, (3, 4)).astype(np.int32)
    nd = lambda a: mx.nd.array(a, dtype="int32")
    want = np.asarray(net(nd(tok), nd(seg), nd(pos))[0]._data)
    got = reference.forward(named(net), tok, seg, pos, layers=2, heads=4)
    # float32 both sides: rounding order only
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)
    loss = reference.mlm_loss_per_sequence(got, labels)
    assert loss.shape == (3,) and np.all(np.isfinite(np.asarray(loss)))


def test_transformer_reference_matches_the_models_forward():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo.transformer import Transformer

    from chipbench.reference import transformer as reference

    mx.random.seed(4)
    net = Transformer(89, units=32, hidden_size=64, num_heads=4,
                      num_encoder_layers=2, num_decoder_layers=2, dropout=0.0,
                      max_length=32)
    net.initialize(mx.init.Normal(0.3))
    rng = np.random.RandomState(1)
    src = rng.randint(3, 89, 11).astype(np.int32)
    tgt = rng.randint(3, 89, 7).astype(np.int32)
    nd = lambda a: mx.nd.array(a[None], dtype="int32")
    want = np.asarray(net(nd(src), nd(tgt))._data)[0]
    got = np.asarray(reference.logits(named(net), src, tgt, enc_layers=2,
                                      dec_layers=2, heads=4))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got / scale, want / scale, atol=2e-5)

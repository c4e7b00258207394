"""What a decoder layer's checkpoint keeps (``model_zoo.decoder.run_layer``):
the blockwise attention core's output and log-sum-exp and the indexer's
selection, by name, so that the backward pass's second forward runs neither
the attention kernel nor the bisection again, and no number changes.  On the
CPU, the kernels in the Pallas interpreter, tiny shapes, over the three paths
that set the names: the plain blockwise call (``_flash_kernels``), the call
under a selection that also returns its log-sum-exp (``_flash_kernels_lse``,
through ``sparse_attention``) and ``latent_attention``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu.gluon.model_zoo import decoder, moe
from incubator_mxnet_tpu.ndarray.ndarray import NDArray
from incubator_mxnet_tpu.ops import attention as att
from incubator_mxnet_tpu.ops import sparse_attention as sa

B, S, D, H, DH = 2, 32, 16, 2, 8
BLOCK = 16           # two blocks a length: the kernels loop
TOPK = 12            # S ≤ 4 · topk: the selection is named
LAYERS = 2
PATHS = ["blockwise", "selected", "latent"]


@pytest.fixture(autouse=True)
def interpreted_kernels(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_FLASH", "interpret")
    monkeypatch.setattr(att, "_PALLAS_BLOCK_Q", BLOCK)
    monkeypatch.setattr(att, "_PALLAS_BLOCK_K", BLOCK)


def weights(path, seed):
    rng = np.random.RandomState(seed)
    w = lambda *shape: jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)
    if path == "blockwise":
        return {"qkv": w(3 * D, D), "o": w(D, D)}
    if path == "selected":
        return {"qkv": w((H + 2) * DH, D), "q_gamma": 1 + w(DH), "k_gamma": 1 + w(DH),
                "o": w(D, H * DH), "index": w(2 * 4 + 4 + 2, D), "index_gamma": 1 + w(4),
                "index_beta": w(4)}
    return {"qa": w(8, D), "g_q": 1 + w(8), "qb": w(H * (DH + 4), 8), "kva": w(8 + 4, D),
            "g_kv": 1 + w(8), "kvb": w(H * (DH + DH), 8), "o": w(D, H * DH)}


def layer(path, p, topk=TOPK):
    """``body(x)`` as ``run_layer`` takes it: NDArray in, (NDArray, None[,
    side]) out."""
    def body(x):
        x = x._data
        if path == "blockwise":
            q, k, v = jnp.split(x @ p["qkv"].T, 3, -1)
            y = att.fused_attention(q, k, v, num_heads=H, causal=True) @ p["o"].T
            return NDArray(x + y), None
        if path == "selected":
            y, index_loss, *_ = sa.sparse_attention(
                x, p["qkv"], p["q_gamma"], p["k_gamma"], p["o"], p["index"],
                p["index_gamma"], p["index_beta"], num_heads=H, kv_heads=1, head_dim=DH,
                index_heads=2, index_dim=4, topk=topk, q_chunk=BLOCK, kv_chunk=BLOCK)
            return NDArray(x + y), None, {"loss": index_loss}
        y = att.latent_attention(x, p["qa"], p["g_q"], p["qb"], p["kva"], p["g_kv"], p["kvb"],
                                 p["o"], num_heads=H, qk_nope_dim=DH, qk_rope_dim=4, v_dim=DH)
        return NDArray(x + y), None
    return body


def stack_loss(path, remat, topk=TOPK):
    def loss(params, x):
        with moe.moe_loss_frame() as frame:
            h = NDArray(x)
            for p in params:
                h = decoder.run_layer(layer(path, p, topk), h, remat)
        side = moe.frame_loss(frame)
        return jnp.sum(jnp.sin(h._data)) + (0.0 if side is None else side)
    return loss


def inputs(path):
    params = [weights(path, seed) for seed in range(LAYERS)]
    x = jnp.asarray(np.random.RandomState(9).normal(0, 1, (B, S, D)), jnp.float32)
    return params, x


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from equations(inner)


def count(jaxpr, what):
    """The forward attention kernels (two results, the second the
    lane-replicated log-sum-exp), all Pallas kernels, the 32-pass bisections
    and the named values of a jaxpr."""
    found = {"forward": 0, "pallas": 0, "bisect": 0, "named": 0}
    for eqn in equations(jaxpr.jaxpr):
        name = eqn.primitive.name
        if name == "pallas_call":
            found["pallas"] += 1
            outs = [v.aval for v in eqn.outvars]
            found["forward"] += len(outs) == 2 and outs[1].shape[-1] == att._LANE
        found["bisect"] += name == "scan" and eqn.params["length"] == 32
        found["named"] += name == "name"
    return found[what]


def grad_jaxpr(path, remat, topk=TOPK):
    params, x = inputs(path)
    # a new function a trace: make_jaxpr caches by the function's identity
    return jax.make_jaxpr(jax.grad(stack_loss(path, remat, topk)))(params, x)


# Pallas kernels a layer with and without the checkpoint's second forward: the
# forward and backward attention kernels; under a selection also the index
# scores' forward and backward and the head-averaged probabilities.  The
# second forward reruns the index scores (the indexer's loss differentiates
# them) and the probabilities, never the attention kernel.
KERNELS = {"blockwise": (2, 2), "selected": (5, 7), "latent": (2, 2)}


@pytest.mark.parametrize("path", PATHS)
def test_the_second_forward_runs_no_attention_kernel_and_no_bisection(monkeypatch, path):
    kept = grad_jaxpr(path, True)
    assert count(kept, "forward") == LAYERS
    assert count(kept, "pallas") == LAYERS * KERNELS[path][1]
    monkeypatch.setattr(decoder, "KEPT", ())         # a checkpoint that names nothing
    bare = grad_jaxpr(path, True)
    assert count(bare, "forward") == 2 * LAYERS
    assert count(bare, "pallas") == LAYERS * (KERNELS[path][1] + 1)
    if path == "selected":
        assert count(kept, "bisect") == LAYERS and count(bare, "bisect") == 2 * LAYERS


@pytest.mark.parametrize("path", PATHS)
def test_keeping_changes_no_bit_of_the_loss_or_of_a_gradient(monkeypatch, path):
    params, x = inputs(path)
    run = lambda remat: jax.jit(jax.value_and_grad(stack_loss(path, remat), argnums=(0, 1)))(
        params, x)
    kept, plain = run(True), run(False)
    monkeypatch.setattr(decoder, "KEPT", ())
    bare = run(True)
    for other in (bare, plain):
        for a, b in zip(jax.tree_util.tree_leaves(kept), jax.tree_util.tree_leaves(other)):
            assert np.isfinite(np.asarray(a)).all()
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("path", PATHS)
def test_without_a_checkpoint_the_program_has_the_kernels_it_had(path):
    plain = grad_jaxpr(path, False)
    assert count(plain, "pallas") == LAYERS * KERNELS[path][0]
    assert count(plain, "forward") == LAYERS


def kept_bytes(path):
    core = 4 * B * H * S * DH + 4 * B * H * S          # float32 output, log-sum-exp
    return LAYERS * (core + (B * S * S if path == "selected" else 0))


@pytest.mark.parametrize("path", PATHS)
def test_the_counter_reads_the_bytes_the_layers_keep(path):
    before = profiler.counters()["remat_kept_bytes"]
    grad_jaxpr(path, True)
    after = profiler.counters()["remat_kept_bytes"]
    assert after - before == kept_bytes(path)
    grad_jaxpr(path, False)                            # no checkpoint keeps nothing
    assert profiler.counters()["remat_kept_bytes"] == after


def test_a_bert_block_keeps_nothing():
    """No checkpoint round a BERT layer: the blockwise kernels run (the
    interpreter is asked for by name) and their names are the identity."""
    from incubator_mxnet_tpu.gluon.model_zoo.bert import BERTModel

    net = BERTModel(vocab_size=50, units=D, hidden_size=2 * D, num_layers=LAYERS, num_heads=H,
                    max_length=S, dropout=0.0)
    net.initialize(mx.init.Normal(0.1))
    tokens = np.random.RandomState(3).randint(0, 50, (B, S)).astype(np.int32)
    net(mx.nd.array(tokens))
    fn, params = net.export_jittable()
    before = profiler.counters()
    jaxpr = jax.make_jaxpr(jax.grad(lambda ps: jnp.sum(fn(ps, tokens)[0])))(list(params))
    after = profiler.counters()
    assert count(jaxpr, "pallas") == 2 * LAYERS
    assert after["remat_kept_bytes"] == before["remat_kept_bytes"]


@pytest.mark.parametrize("topk,named", [(TOPK, 1), (S // 4, 1), (S // 4 - 1, 0), (S, 0)],
                         ids=["within", "at-4-topk", "past-4-topk", "selects-all"])
def test_the_selection_is_named_only_while_a_row_is_no_longer_than_four_topk(topk, named):
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool)),
                       jnp.asarray(np.random.RandomState(1).normal(0, 1, (1, S, S)), jnp.float32),
                       -jnp.inf)
    jaxpr = jax.make_jaxpr(lambda s: sa.select_topk(s, topk, BLOCK))(scores)
    assert count(jaxpr, "named") == named
    if not named and topk < S:
        assert count(grad_jaxpr("selected", True, topk), "bisect") == 2 * LAYERS

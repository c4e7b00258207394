"""Keye-VL-2.0's language model (grouped-query attention over the keys a
learned indexer selects for each query, softmax-routed experts of which a
chip holds its share) against the plain float32 reference in
``chipbench/reference/keye.py``, at the configuration's ``dry_run`` sizes.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import amp, profiler
from incubator_mxnet_tpu.gluon.model_zoo import decoder, keye, moe
from incubator_mxnet_tpu.ndarray.ndarray import NDArray
from incubator_mxnet_tpu.ops import attention as attn_ops
from incubator_mxnet_tpu.ops import moe as moe_ops
from incubator_mxnet_tpu.ops import sparse_attention as sa
from incubator_mxnet_tpu.ops.nn import streaming_softmax_ce
from incubator_mxnet_tpu.parallel import SPMDTrainer, make_mesh

from chipbench.reference import keye as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_config(**over):
    c = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                    "keye-vl-2.0-30b-a3b.json")))
    c.update(c["dry_run"])
    c["num_experts"] = c["published"]["num_experts"]      # the router's width: 8
    c.update(over)
    return c


HELD = (2, 4)   # experts 2..5 of 8
TOPK = 12       # the dry run's: below the test lengths, so queries select


def build(c, held=HELD, remat=False, seed=5, sigma=0.3):
    mx.random.seed(seed)
    net = keye.KeyeForCausalLM(c, experts_held=held, remat=remat)
    net.initialize(mx.init.Normal(sigma))
    return net


def named(net):
    return {p.name: p._data._data for p in net.collect_params().values()}


def batch(c, b=2, s=40, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, c["vocab_size"], (b, s + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def causal(s):
    return np.tril(np.ones((s, s), bool))


# ---------------------------------------------------------------------------
# ops/sparse_attention.py against naive forms
# ---------------------------------------------------------------------------


def dense_index_scores(q, k, w):
    heads, dim = q.shape[2:]
    prod = jnp.einsum("bqhd,bkd->bqhk", q, k) * dim ** -0.5
    scores = (jax.nn.relu(prod) * w[..., None]).sum(2) * heads ** -0.5
    return jnp.where(causal(q.shape[1])[None], scores, -jnp.inf)


def indexer_inputs(s, b=2, heads=4, dim=8, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, s, heads, dim), jnp.float32),
            jnp.asarray(rng.randn(b, s, dim), jnp.float32),
            jnp.asarray(rng.randn(b, s, heads), jnp.float32))


# a length the tiles divide, one they do not, one shorter than a tile
TILINGS = pytest.mark.parametrize("s,cq,ck", [(64, 16, 16), (50, 16, 8), (40, 64, 64)])


@TILINGS
def test_index_scores_in_tiles_match_the_dense_form(s, cq, ck):
    q, k, w = indexer_inputs(s)
    got, want = sa.index_scores(q, k, w, cq, ck), dense_index_scores(q, k, w)
    assert np.array_equal(np.isneginf(got), ~np.broadcast_to(causal(s), got.shape))
    np.testing.assert_allclose(np.where(causal(s), got, 0), np.where(causal(s), want, 0),
                               atol=2e-6)


@TILINGS
def test_index_scores_gradients_match_the_dense_form(s, cq, ck):
    q, k, w = indexer_inputs(s, seed=1)
    ct = jnp.asarray(np.random.RandomState(2).randn(2, s, s) * causal(s), jnp.float32)
    loss = lambda f: lambda *a: jnp.sum(jnp.where(causal(s), f(*a), 0.0) * ct)
    got = jax.grad(loss(lambda *a: sa.index_scores(*a, cq, ck)), (0, 1, 2))(q, k, w)
    want = jax.grad(loss(dense_index_scores), (0, 1, 2))(q, k, w)
    for g, r in zip(got, want):
        np.testing.assert_allclose(g, r, atol=1e-5)


# the index scores' Pallas kernels, in the interpreter: (S, q_chunk, kv_chunk, H, D, batch) — small
# heads, the cell's 16 heads of 64 in square and in oblong blocks, one block a side, a batch of one
KERNEL_CASES = pytest.mark.parametrize("s,cq,ck,heads,dim,b", [
    (64, 16, 16, 4, 8, 2), (256, 128, 128, 16, 64, 2), (256, 64, 128, 16, 64, 1),
    (128, 128, 32, 16, 64, 2), (96, 96, 96, 3, 16, 2)],
    ids=["s64-16x16-h4-d8", "s256-128x128-h16-d64", "s256-64x128-h16-d64-b1",
         "s128-128x32-h16-d64", "s96-one-block-h3-d16"])


def float64_index_scores(q, k, w, ct=None):
    """The dense form in float64 (NumPy): the scores, and with a cotangent
    ``ct`` [B, S, S] (zero where it does not count) the three gradients."""
    q, k, w = (np.asarray(a, np.float64) for a in (q, k, w))
    heads, dim = q.shape[2:]
    c = (heads * dim) ** -0.5
    prod = np.einsum("bqhd,bkd->bqhk", q, k)
    scores = (np.maximum(prod, 0) * w[..., None]).sum(2) * c
    if ct is None:
        return scores
    ct = np.asarray(ct, np.float64)[:, :, None, :]
    g = ct * w[..., None] * (prod > 0)
    return (np.einsum("bqhk,bkd->bqhd", g, k) * c, np.einsum("bqhk,bqhd->bkd", g, q) * c,
            (ct * np.maximum(prod, 0)).sum(-1) * c)


def selected_cotangent(b, s, seed=2, share=0.3):
    """What the indexer's loss sends back: zero off a selection of visible keys."""
    rng = np.random.RandomState(seed)
    chosen = (rng.rand(b, s, s) < share) & causal(s)
    return jnp.asarray(rng.randn(b, s, s) * chosen, jnp.float32)


def dispatched(before):
    after = profiler.counters()
    return tuple(after[n] - before[n] for n in ("index_scores_dispatch_pallas",
                                                "index_scores_dispatch_xla"))


@KERNEL_CASES
def test_index_scores_kernel_matches_the_dense_form(monkeypatch, s, cq, ck, heads, dim, b):
    monkeypatch.setenv("MXNET_TPU_FLASH", "interpret")
    q, k, w = indexer_inputs(s, b, heads, dim)
    before = profiler.counters()
    got, want = sa.index_scores(q, k, w, cq, ck), dense_index_scores(q, k, w)
    assert dispatched(before) == (1, 0)
    assert got.dtype == jnp.float32 and got.shape == (b, s, s)
    assert np.array_equal(np.isneginf(got), ~np.broadcast_to(causal(s), got.shape))
    np.testing.assert_allclose(np.where(causal(s), got, 0), np.where(causal(s), want, 0),
                               atol=4e-6)


@KERNEL_CASES
def test_index_scores_kernel_gradients_match_the_dense_form(monkeypatch, s, cq, ck, heads, dim, b):
    """All three, under a cotangent that is zero off a selection; what stands
    past the diagonal of the cotangent is ignored, as the tiles ignore it."""
    monkeypatch.setenv("MXNET_TPU_FLASH", "interpret")
    q, k, w = indexer_inputs(s, b, heads, dim, seed=1)
    ct = selected_cotangent(b, s)
    junk = ct + jnp.asarray(~causal(s), jnp.float32)       # ones past the diagonal
    run = lambda *a: sa.index_scores(*a, cq, ck)
    before = profiler.counters()
    got = jax.vjp(run, q, k, w)[1](junk)
    assert dispatched(before) == (1, 0)
    loss = lambda *a: jnp.sum(jnp.where(causal(s), dense_index_scores(*a), 0.0) * ct)
    for g, r in zip(got, jax.grad(loss, (0, 1, 2))(q, k, w)):
        assert g.dtype == jnp.float32 and g.shape == r.shape
        np.testing.assert_allclose(g, r, atol=1e-5)


def test_index_scores_kernel_is_as_exact_as_the_highest_einsum(monkeypatch):
    """Value and gradients against a float64 dense form, at the cell's 16
    heads of 64: the kernels' six bfloat16 partial products, two to an MXU
    pass, err no more than the float32 einsum at ``Precision.HIGHEST`` — and
    a product of bfloat16 operands alone, or one partial product left out,
    would err a thousand times more."""
    monkeypatch.setenv("MXNET_TPU_FLASH", "interpret")
    b, s = 2, 256
    q, k, w = indexer_inputs(s, b, 16, 64, seed=7)
    ct = selected_cotangent(b, s, seed=8)
    visible = causal(s)[None]
    masked = lambda f: lambda *a: jnp.where(visible, f(*a), 0.0)

    def einsum_highest(q, k, w):
        prod = jnp.einsum("bqhd,bkd->bqhk", q, k, precision=jax.lax.Precision.HIGHEST) * 64 ** -0.5
        return (jax.nn.relu(prod) * w[..., None]).sum(2) * 16 ** -0.5

    kernel = masked(lambda *a: sa.index_scores(*a, 128, 128))
    want = (np.where(visible, float64_index_scores(q, k, w), 0.0),) + float64_index_scores(q, k, w, ct)
    worst = {}
    for name, f in (("kernel", kernel), ("einsum", masked(einsum_highest))):
        value, vjp = jax.vjp(f, q, k, w)
        worst[name] = [np.abs(np.asarray(a) - r).max() / np.abs(r).max()
                       for a, r in zip((value,) + vjp(ct), want)]
    for got, ref in zip(worst["kernel"], worst["einsum"]):
        assert got <= max(ref, 2e-7) * 1.25, worst
    assert max(worst["kernel"]) < 1e-6, worst


@pytest.mark.parametrize("flash,s,chunks,path", [
    ("interpret", 64, (16, 16), "pallas"), ("interpret", 40, (64, 64), "pallas"),
    ("interpret", 50, (16, 8), "xla"), ("interpret", 64, (16, 24), "xla"),
    ("auto", 64, (16, 16), "xla"), ("off", 64, (16, 16), "xla")],
    ids=["chunks-divide", "shorter-than-a-chunk", "no-chunk-divides", "key-chunk-does-not-divide",
         "on-the-cpu", "kernels-off"])
def test_index_scores_dispatch_is_counted_at_trace_time(monkeypatch, flash, s, chunks, path):
    """One count a traced call site, on the path the predicate chose: the
    kernels where the platform runs them and the chunks divide the length,
    the XLA tiles for everything else; a compiled program is not traced, and
    not counted, again."""
    monkeypatch.setenv("MXNET_TPU_FLASH", flash)
    q, k, w = indexer_inputs(s, seed=5)
    launch = sa._index_path(q, min(chunks[0], s), min(chunks[1], s))
    assert (launch is not None) == (path == "pallas")
    run = jax.jit(lambda *a: sa.index_scores(*a, *chunks))
    before = profiler.counters()
    got = run(q, k, w)
    assert dispatched(before) == ((1, 0) if path == "pallas" else (0, 1))
    run(q, k, w)
    assert dispatched(before) == ((1, 0) if path == "pallas" else (0, 1))
    traced = str(jax.make_jaxpr(lambda *a: sa.index_scores(*a, *chunks))(q, k, w))
    assert traced.count("pallas_call") == (path == "pallas")
    want = dense_index_scores(q, k, w)
    np.testing.assert_allclose(np.where(causal(s), got, 0), np.where(causal(s), want, 0), atol=4e-6)


def test_compiled_index_kernels_are_asked_only_for_blocks_mosaic_tiles(monkeypatch):
    """Off the interpreter the predicate also wants 128-row and 128-column
    tiles (the tile is turned inside the kernels), a head width whose pair
    fills whole 128-lane columns, and blocks that fit VMEM."""
    monkeypatch.setenv("MXNET_TPU_FLASH", "on")
    like = lambda s, h, d: jax.ShapeDtypeStruct((1, s, h, d), jnp.float32)
    assert sa._index_path(like(8192, 16, 64), 512, 512).blocks == (512, 512)
    assert sa._index_path(like(1024, 16, 128), 256, 128).blocks == (256, 128)
    assert sa._index_path(like(8192, 16, 64), 512, 64) is None       # a 64-column tile
    assert sa._index_path(like(8192, 16, 48), 512, 512) is None      # 96 lanes a pair
    assert sa._index_path(like(8192, 16, 64), 512, 500) is None      # divides nothing
    assert sa._index_path(like(8192, 128, 64), 2048, 2048) is None   # VMEM
    assert sa._index_path(like(1 << 18, 16, 64), 512, 512) is None   # dk's [S, 2D] resident


@pytest.mark.parametrize("axes,kernels", [({"dp": 2}, True), ({"dp": 2, "tp": 2}, False)],
                         ids=["dp2", "dp2-tp2"])
def test_index_kernels_are_placed_on_the_mesh_of_the_trace(monkeypatch, axes, kernels):
    """Under a mesh that splits the batch alone the kernels are launched in a
    ``shard_map`` over its rows; a mesh that splits the model takes the XLA
    tiles: no compiler partitions a Mosaic kernel."""
    from incubator_mxnet_tpu.parallel import mesh_scope

    n = int(np.prod(list(axes.values())))
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} devices")
    monkeypatch.setenv("MXNET_TPU_FLASH", "interpret")
    mesh = make_mesh(devices=jax.devices()[:n], **axes)
    q, k, w = indexer_inputs(64, seed=6)
    ct = selected_cotangent(2, 64)
    loss = lambda *a: jnp.sum(jnp.where(causal(64), sa.index_scores(*a, 16, 16), 0.0) * ct)
    want = jax.grad(loss, (0, 1, 2))(q, k, w)

    def scoped(*a):
        with mesh_scope(mesh):
            return jax.grad(loss, (0, 1, 2))(*a)

    traced = str(jax.make_jaxpr(scoped)(q, k, w))
    assert traced.count("pallas_call") == (2 if kernels else 0)
    assert traced.count("shard_map") == (2 if kernels else 0)
    for g, r in zip(jax.jit(scoped)(q, k, w), want):
        np.testing.assert_allclose(g, r, atol=1e-5)


@TILINGS
@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
def test_selection_is_exactly_the_top_k_visible_keys(s, cq, ck, ties):
    """Exactly ``min(t + 1, k)`` keys a row, all causal, the set ``lax.top_k``
    gives; with scores rounded to halves, so that many are equal, too."""
    scores = sa.index_scores(*indexer_inputs(s, seed=3), cq, ck)
    if ties:
        scores = jnp.where(jnp.isneginf(scores), scores, jnp.round(scores * 2) / 2)
    got = np.asarray(sa.select_topk(scores, TOPK, cq))
    assert got.dtype == np.int8 and set(np.unique(got)) <= {0, 1}
    assert (got.sum(-1) == np.minimum(np.arange(s) + 1, TOPK)[None]).all()
    assert not (got.astype(bool) & ~causal(s)[None]).any()
    _, best = jax.lax.top_k(scores, TOPK)
    want = np.zeros(got.shape, bool)
    np.put_along_axis(want, np.asarray(best), True, -1)
    want[:, :TOPK] = causal(s)[:TOPK]
    assert np.array_equal(got.astype(bool), want & causal(s)[None])


def test_a_query_that_sees_no_more_than_topk_keys_selects_them_all():
    scores = sa.index_scores(*indexer_inputs(24, seed=4), 8, 8)
    assert np.array_equal(np.asarray(sa.select_topk(scores, 24)),
                          np.broadcast_to(causal(24), (2, 24, 24)))
    assert np.array_equal(np.asarray(sa.select_topk(scores, 100)),
                          np.broadcast_to(causal(24), (2, 24, 24)))


def test_live_tiles_counts_the_tiles_that_hold_a_selected_pair():
    select = np.zeros((1, 48, 48), np.int8)
    select[0, np.arange(48), np.arange(48)] = 1        # the diagonal: 3 tiles of 16
    select[0, 40, 3] = 1                               # and one far below it
    live, below = sa.live_tiles(jnp.asarray(select), 16, 16)
    assert (float(live), float(below)) == (4.0, 6.0)
    live, below = sa.live_tiles(jnp.asarray(np.tile(causal(40)[None], (2, 1, 1)).astype(np.int8)), 16, 16)
    assert (float(live), float(below)) == (12.0, 12.0)   # a length no tile divides


# ---------------------------------------------------------------------------
# ops/attention.py: the dispatcher under a selection
# ---------------------------------------------------------------------------


def attention_inputs(group, s=64, b=2, h_kv=2, d=16, seed=0):
    rng = np.random.RandomState(seed)
    arr = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    q, k, v = arr(b, group * h_kv, s, d), arr(b, h_kv, s, d), arr(b, h_kv, s, d)
    select = (rng.rand(b, s, s) < 0.3) | np.eye(s, dtype=bool)[None]
    select &= causal(s)[None]
    select[:, 40:, :32] = False          # rows whose first key blocks hold no live key
    return q, k, v, jnp.asarray(select)


def dense_selected_attention(q, k, v, select):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(select[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("group", [1, 4, 8])
@pytest.mark.parametrize("flash", ["off", "interpret"], ids=["xla", "kernels"])
def test_selected_attention_through_the_dispatcher(monkeypatch, group, flash):
    """Values and all three gradients against the dense masked soft-max."""
    monkeypatch.setenv("MXNET_TPU_FLASH", flash)
    q, k, v, select = attention_inputs(group)
    before = profiler.counters()
    run = lambda q, k, v: attn_ops.flash_attention(q, k, v, causal=True, select=select)
    want = dense_selected_attention(q, k, v, select)
    np.testing.assert_allclose(run(q, k, v), want, atol=2e-6)
    after = profiler.counters()
    assert (after["attention_dispatch_masked"] - before["attention_dispatch_masked"]
            == (flash == "interpret"))
    ct = jnp.asarray(np.random.RandomState(1).randn(*want.shape), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(run(*a) * ct), (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: jnp.sum(dense_selected_attention(*a, select) * ct), (0, 1, 2))(q, k, v)
    for g, r in zip(got, ref):
        np.testing.assert_allclose(g, r, atol=5e-6)


@pytest.mark.parametrize("flash", ["off", "interpret"], ids=["xla", "kernels"])
def test_bshd_dispatcher_returns_the_kernels_log_sum_exp(monkeypatch, flash):
    monkeypatch.setenv("MXNET_TPU_FLASH", flash)
    q, k, v, select = attention_inputs(4)
    t = lambda x: x.transpose(0, 2, 1, 3)
    out, lse, launch = attn_ops._attend_bshd(t(q), t(k), t(v), True, 0.25, select=select,
                                             with_lse=True)
    np.testing.assert_allclose(t(out), dense_selected_attention(q, k, v, select), atol=2e-6)
    if flash == "off":
        assert lse is None and launch is None
        return
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 4, 1)) * 0.25
    want = jax.nn.logsumexp(jnp.where(select[:, None], s, -jnp.inf), axis=-1)
    np.testing.assert_allclose(lse, want, atol=2e-6)


@pytest.mark.parametrize("group", [1, 4])
def test_without_a_selection_the_kernels_are_bit_equal_to_before(monkeypatch, group):
    """No selection given: the same kernels, the same bits, forward and
    backward, as a call that never heard of one (select=None is the default
    and the all-visible selection is NOT the same program)."""
    monkeypatch.setenv("MXNET_TPU_FLASH", "interpret")
    q, k, v, _ = attention_inputs(group)
    launch = attn_ops._Launch(True, (16, 16))
    plain = lambda q, k, v: attn_ops._flash_kernels(q, k, v, True, 0.25, launch)
    explicit = lambda q, k, v: attn_ops._flash_kernels(q, k, v, True, 0.25, launch, None)
    assert np.array_equal(plain(q, k, v), explicit(q, k, v))
    jaxpr = str(jax.make_jaxpr(jax.grad(lambda *a: plain(*a).sum(), (0, 1, 2)))(q, k, v))
    assert jaxpr.count("pallas_call") == 2 and "i8[" not in jaxpr
    everything = jnp.ones((2, 64, 64), jnp.int8)
    masked = attn_ops._flash_kernels(q, k, v, True, 0.25, launch, everything)
    np.testing.assert_allclose(masked, plain(q, k, v), atol=1e-6)
    g_plain = jax.grad(lambda *a: plain(*a).sum(), (0, 1, 2))(q, k, v)
    g_explicit = jax.grad(lambda *a: explicit(*a).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(g_plain, g_explicit):
        assert np.array_equal(a, b)


def test_head_mean_probs_kernel_matches_the_plain_expression(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_FLASH", "interpret")
    q, k, v, select = attention_inputs(4)
    select = select.astype(jnp.int8)
    t = lambda x: x.transpose(0, 2, 1, 3)
    _, lse, launch = attn_ops._attend_bshd(t(q), t(k), t(v), True, 0.25, select=select,
                                           with_lse=True)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, jnp.repeat(k, 4, 1)) * 0.25
    want = jax.nn.softmax(jnp.where(select[:, None] != 0, s, -jnp.inf), axis=-1).mean(1)
    np.testing.assert_allclose(sa.head_mean_probs(t(q), t(k), select, 0.25, lse, launch),
                               want, atol=1e-6)
    np.testing.assert_allclose(sa.head_mean_probs(t(q), t(k), select, 0.25, q_chunk=24),
                               want, atol=1e-6)


def test_indexer_kl_loss_and_its_gradient():
    rng = np.random.RandomState(0)
    scores = jnp.where(causal(40)[None], jnp.asarray(rng.randn(2, 40, 40), jnp.float32), -jnp.inf)
    select = sa.select_topk(scores, TOPK, 16)
    target = jax.nn.softmax(jnp.where(select != 0, jnp.asarray(rng.randn(2, 40, 40)), -jnp.inf), -1)
    log_q = jax.nn.log_softmax(jnp.where(select != 0, scores, -jnp.inf), -1)
    want = jnp.where(select != 0, target * (jnp.log(jnp.where(select != 0, target, 1.0))
                                            - jnp.where(select != 0, log_q, 0.0)), 0.0).sum(-1).mean()
    np.testing.assert_allclose(sa.indexer_kl_loss(scores, select, target), want, rtol=1e-5)
    grad = jax.grad(sa.indexer_kl_loss)(scores, select, target)
    assert np.isfinite(grad).all()
    np.testing.assert_allclose(grad, (jnp.exp(log_q) - target) / 80.0, atol=1e-7)


# ---------------------------------------------------------------------------
# rotary positions in three streams
# ---------------------------------------------------------------------------


def test_three_stream_rotary_matches_a_naive_form():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 9, 3, 16), jnp.float32)
    positions = jnp.asarray(rng.randint(0, 50, (3, 2, 9)))
    cos, sin = attn_ops.multi_stream_rotary_tables(positions, 16, 1e4, [2, 4, 2])
    got = np.asarray(attn_ops.apply_rotary(x, cos, sin, "half"))
    stream = [0, 0, 1, 1, 1, 1, 2, 2]
    want = np.array(x)
    for b in range(2):
        for t in range(9):
            for i in range(8):
                angle = float(positions[stream[i], b, t]) * 1e4 ** (-2.0 * i / 16)
                lo, hi = np.asarray(x[b, t, :, i]), np.asarray(x[b, t, :, i + 8])
                want[b, t, :, i] = lo * np.cos(angle) - hi * np.sin(angle)
                want[b, t, :, i + 8] = hi * np.cos(angle) + lo * np.sin(angle)
    np.testing.assert_allclose(got, want, atol=2e-5)
    with pytest.raises(ValueError, match="do not add up"):
        attn_ops.multi_stream_rotary_tables(positions, 16, 1e4, [2, 4, 4])


@pytest.mark.parametrize("pairing", ["interleaved", "half"])
def test_equal_streams_are_one_stream(pairing):
    x = jnp.asarray(np.random.RandomState(1).randn(2, 11, 3, 16), jnp.float32)
    one = attn_ops.yarn_rotary_tables(11, 16, 1e7)
    positions = jnp.broadcast_to(jnp.arange(11)[None, None], (3, 2, 11))
    three = attn_ops.multi_stream_rotary_tables(positions, 16, 1e7, [2, 4, 2])
    np.testing.assert_allclose(attn_ops.apply_rotary(x, *three, pairing),
                               attn_ops.apply_rotary(x, *one, pairing), atol=1e-6)


def test_one_stream_interleaved_rotary_is_what_it_was():
    """The Xing block's call, bit for bit: pairs (2i, 2i + 1), halves out."""
    x = jnp.asarray(np.random.RandomState(2).randn(1, 7, 2, 8), jnp.float32)
    cos, sin = attn_ops.yarn_rotary_tables(7, 8)
    a, b = x[..., 0::2], x[..., 1::2]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    want = jnp.concatenate([a * c - b * s, b * c + a * s], -1)
    assert np.array_equal(attn_ops.apply_rotary(x, cos, sin), want)


# ---------------------------------------------------------------------------
# ops/moe.py: the router's scoring
# ---------------------------------------------------------------------------


def routed_inputs(seed=0, tokens=48, d=16, width=8, experts=8, count=4):
    rng = np.random.RandomState(seed)
    arr = lambda *shape: jnp.asarray(rng.randn(*shape) * 0.5, jnp.float32)
    return (arr(2, tokens // 2, d), arr(experts, d), jnp.zeros((experts,), jnp.float32),
            arr(count, d, 2 * width), arr(count, width, d))


def test_softmax_scoring_matches_a_dense_loop_and_its_balance_term():
    x, w_r, bias, w_in, w_down = routed_inputs()
    kw = dict(num_experts=8, top_k=2, first_expert=2, norm_topk=True)
    y, rows, _, _, load_all, balance = moe_ops.moe_ffn_dropless(
        x, w_r, bias, w_in, w_down, scoring="softmax", **kw)
    prob = jax.nn.softmax(x.reshape(-1, 16) @ w_r.T, axis=-1)
    chosen, idx = jax.lax.top_k(prob, 2)
    gates = chosen / chosen.sum(-1, keepdims=True)
    want = jnp.zeros((48, 16))
    for j in range(4):
        gate = jnp.where(idx == 2 + j, gates, 0.0).sum(-1)
        up = x.reshape(-1, 16) @ w_in[j]
        want += gate[:, None] * ((jax.nn.silu(up[:, :8]) * up[:, 8:]) @ w_down[j])
    np.testing.assert_allclose(y.reshape(-1, 16), want, atol=1e-5)
    load = np.bincount(np.asarray(idx).ravel(), minlength=8)
    assert np.array_equal(np.asarray(load_all), load) and float(rows) == load[2:6].sum()
    # E · Σ_e f_e · P̄_e, f_e the share of the 96 pairs routed to e
    np.testing.assert_allclose(balance, 8 * np.sum(load / 96.0 * np.asarray(prob.mean(0))), rtol=1e-5)
    # its gradient reaches the router through P̄ only (f is detached)
    grad = jax.grad(lambda w: moe_ops.moe_ffn_dropless(
        x, w, bias, w_in, w_down, scoring="softmax", **kw)[5])(w_r)
    want_grad = jax.grad(lambda w: 8 * jnp.sum(
        load / 96.0 * jax.nn.softmax(x.reshape(-1, 16) @ w.T, axis=-1).mean(0)))(w_r)
    np.testing.assert_allclose(grad, want_grad, atol=1e-6)


def test_softmax_scoring_reads_no_selection_bias():
    x, w_r, _, w_in, w_down = routed_inputs(1)
    kw = dict(num_experts=8, top_k=2, first_expert=2, scoring="softmax")
    plain = moe_ops.moe_ffn_dropless(x, w_r, jnp.zeros((8,)), w_in, w_down, **kw)
    biased = moe_ops.moe_ffn_dropless(x, w_r, jnp.full((8,), 5.0).at[0].set(-5.0), w_in, w_down, **kw)
    for a, b in zip(plain, biased):
        assert np.array_equal(a, b)


def test_sigmoid_scoring_is_unchanged_and_the_default():
    x, w_r, _, w_in, w_down = routed_inputs(2)
    bias = jnp.asarray(np.random.RandomState(3).randn(8) * 0.1, jnp.float32)
    kw = dict(num_experts=8, top_k=2, first_expert=2, routed_scaling=2.5)
    default = moe_ops.moe_ffn_dropless(x, w_r, bias, w_in, w_down, **kw)
    named_ = moe_ops.moe_ffn_dropless(x, w_r, bias, w_in, w_down, scoring="sigmoid", **kw)
    assert len(default) == len(named_) == 5          # no balance term
    for a, b in zip(default, named_):
        assert np.array_equal(a, b)
    s = jax.nn.sigmoid(x.reshape(-1, 16) @ w_r.T)
    _, idx = jax.lax.top_k(s + bias, 2)
    chosen = jnp.take_along_axis(s, idx, -1)
    gates = 2.5 * chosen / chosen.sum(-1, keepdims=True)
    want = jnp.zeros((48, 16))
    for j in range(4):
        up = x.reshape(-1, 16) @ w_in[j]
        want += jnp.where(idx == 2 + j, gates, 0.0).sum(-1)[:, None] * (
            (jax.nn.silu(up[:, :8]) * up[:, 8:]) @ w_down[j])
    np.testing.assert_allclose(default[0].reshape(-1, 16), want, atol=1e-5)
    with pytest.raises(ValueError, match="scoring"):
        moe_ops.moe_ffn_dropless(x, w_r, bias, w_in, w_down, scoring="tanh", **kw)


def test_softmax_scored_experts_have_no_bias_parameter_and_no_shared_expert():
    layer = decoder.SparseExperts(16, 8, 8, 2, (2, 4), n_shared_experts=0, scoring="softmax",
                                  prefix="moe_")
    names = sorted(n.split("moe_")[-1] for n in layer.collect_params())
    assert names == ["experts_down_weight", "experts_gate_up_weight", "router_weight"]
    assert layer.shared_expert is None and layer._bias_speed == 0.0
    layer.initialize(mx.init.Normal(0.3))
    layer.cast("bfloat16")                       # no float32 bias to keep
    sigmoid = decoder.SparseExperts(16, 8, 8, 2, (2, 4), prefix="moe_")
    assert any(n.endswith("select_bias") for n in sigmoid.collect_params())
    assert "scoring" not in sigmoid._kw          # the default stays out of the op's signature


def _expert_layer(c, held):
    mx.random.seed(11)
    layer = decoder.SparseExperts(
        c["hidden_size"], c["moe_intermediate_size"], c["num_experts"],
        c["num_experts_per_tok"], held, n_shared_experts=0,
        norm_topk=c["norm_topk_prob"], scope="keye.moe", scoring="softmax", prefix="moe_")
    layer.initialize(mx.init.Normal(0.3))
    return layer


def test_the_shares_of_the_expert_layer_add_up_to_the_whole_layer():
    """The guide's share test: 4 chips hold 2 of 8 experts each; their routed
    parts (there is no shared expert to count once) are the uncut
    reference's layer, and every chip reads the same balance term."""
    c = tiny_config()
    whole = _expert_layer(c, (0, 8))
    p = {k.split("_", 1)[1] if not k.startswith("moe_") else k: jnp.asarray(v)
         for k, v in named(whole).items()}
    x = np.random.RandomState(2).randn(2, 24, c["hidden_size"]).astype(np.float32)
    want, want_balance, want_rows = reference.experts(p, "moe_", jnp.asarray(x), c, (0, 8))
    total, rows, balances = 0.0, 0, []
    for chip in range(4):
        first = 2 * chip
        share = _expert_layer(c, (first, 2))
        for name, param in share.collect_params().items():
            full = whole.collect_params()[name].data().asnumpy()
            param.set_data(mx.nd.array(full[first:first + 2] if "experts_" in name else full))
        y, stats, balance = share(mx.nd.array(x))
        total = total + y.asnumpy()
        rows += int(stats.asnumpy()[0])
        balances.append(float(balance.asnumpy()))
    assert rows == int(want_rows) == 2 * 24 * c["num_experts_per_tok"]
    scale = np.abs(np.asarray(want)).max()
    np.testing.assert_allclose(total / scale, np.asarray(want) / scale, atol=5e-6)
    np.testing.assert_allclose(balances, float(want_balance), rtol=1e-5)


# ---------------------------------------------------------------------------
# the whole model
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def system():
    """The tiny model in float32, its jittable forward and a batch whose
    length no tile divides (40 over tiles of 16; 28 of 40 queries select)."""
    c = tiny_config()
    net = build(c)
    fn, params = net.export_jittable()
    names = sorted(p.name for p in net.collect_params().values())
    tok, labels = batch(c)
    return {"c": c, "net": net, "fn": fn, "params": list(params), "names": names,
            "tok": tok, "labels": labels}


def system_terms(s, params, tok=None, labels=None, taps=()):
    """The program's three loss terms the way a training step forms them:
    ``(L_LM, the layers' side loss a batch row, the frame)``."""
    tok = s["tok"] if tok is None else tok
    labels = s["labels"] if labels is None else labels
    with moe.moe_loss_frame(taps=taps) as frame:
        logits = s["fn"](params, tok)
    lm = streaming_softmax_ce(logits, jnp.asarray(labels)).mean()
    return lm, moe.frame_loss(frame) / tok.shape[0], frame


def test_the_model_is_built_from_the_configs_keys(system):
    net, c = system["net"], system["c"]
    assert len(net.model.blocks) == c["num_hidden_layers"] == 2
    attn = net.model.blocks[0].attn
    assert attn._kw["topk"] == TOPK and attn._kw["mrope_section"] == (2, 4, 2)
    assert attn.index_weight.shape == (4 * 8 + 8 + 4, 64)
    assert attn.qkv_weight.shape == ((8 + 2 * 2) * 16, 64)
    assert not any(n.endswith("select_bias") for n in system["names"])
    with pytest.raises(ValueError, match="every layer has experts"):
        keye.KeyeModel(dict(c, mlp_only_layers=[0]))
    with pytest.raises(ValueError, match="ONE index key head"):
        keye.KeyeModel(dict(c, sa_config=dict(c["sa_config"], indexer_num_kv_heads=2)))


def test_logits_and_the_three_loss_terms_match_the_reference_in_float32(system):
    s = system
    got = np.asarray(jax.jit(s["fn"])(s["params"], s["tok"]))
    want, terms = reference.forward(named(s["net"]), s["tok"], config=s["c"],
                                    experts_held=HELD, query_block=8, with_terms=True)
    # float32 on both sides, highest precision: rounding order only
    scale = np.abs(want).max()
    np.testing.assert_allclose(got / scale, np.asarray(want) / scale, atol=1e-4)
    lm, side, frame = system_terms(s, s["params"])
    ref_lm, ref_balance, ref_index = reference.loss_terms(
        named(s["net"]), s["tok"], s["labels"], config=s["c"], experts_held=HELD, query_block=8)
    assert abs(float(lm) - float(ref_lm)) <= 1e-4 * float(ref_lm)
    assert float(ref_index) > 0.1 and float(ref_balance) > 1.0       # both terms are there
    np.testing.assert_allclose(float(side), float(ref_index) + 0.001 * float(ref_balance), rtol=1e-4)
    assert float(moe.frame_metrics(frame)["rows_routed_here"]) == float(terms["rows_routed_here"])


def test_every_layer_selects_the_keys_the_reference_selects(system):
    s = system
    _, _, frame = system_terms(s, s["params"], taps=("selection",))
    _, terms = reference.forward(named(s["net"]), s["tok"], config=s["c"], experts_held=HELD,
                                 with_terms=True)
    got = np.stack([np.asarray(tap["selection"]) for tap in frame.taps]).astype(bool)
    want = np.asarray(terms["selections"])
    assert got.shape == want.shape == (2, 2, 40, 40)
    assert (got.sum(-1) == np.minimum(np.arange(40) + 1, TOPK)).all()
    # float32 both sides: a near-tie at the 12th score may still fall the
    # other way once in a few thousand rows; here none does
    assert np.array_equal(got, want)
    # without a tap asked for, nothing is handed over; the routers' inputs
    # (what the benchmark's set-up evens the loads with) are another tap
    assert system_terms(s, s["params"])[2].taps == []
    taps = system_terms(s, s["params"], taps=("router_input",))[2].taps
    assert [sorted(t) for t in taps] == [["router_input"]] * 2
    assert taps[0]["router_input"].shape == (2, 40, s["c"]["hidden_size"])


def test_every_parameters_gradient_matches_the_reference(system):
    s = system

    def sys_loss(params):
        lm, side, _ = system_terms(s, params)
        return lm + side

    got = jax.jit(jax.grad(sys_loss))(s["params"])
    want = jax.jit(jax.grad(lambda p: reference.step_loss(
        p, s["tok"], s["labels"], config=s["c"], experts_held=HELD, query_block=8)))(named(s["net"]))
    for name, g in zip(s["names"], got):
        w, g = np.asarray(want[name]), np.asarray(g)
        scale = max(np.abs(w).max(), 1e-8)
        assert np.abs(w).max() > 0, f"{name}: the reference's gradient is zero"
        # float32 both sides; tiles, the kernels' order of sums and the
        # sorted rows differ from the dense forms through two layers
        np.testing.assert_allclose(g / scale, w / scale, atol=5e-4, err_msg=name)


def test_the_indexers_loss_moves_the_indexer_only_and_the_lm_loss_never_it(system):
    """§1's two halves: ``x̄`` and the target are detached, so ``L_I`` reaches
    ``W_index`` and the LayerNorm alone; the selection carries no gradient,
    so ``L_LM`` (and the balance term) reach everything else and never them."""
    s = system
    indexer = [i for i, n in enumerate(s["names"]) if "_index_" in n]
    assert len(indexer) == 3 * 2                      # weight, gain, offset a layer

    lm_grads = jax.jit(jax.grad(lambda p: system_terms(s, p)[0]))(s["params"])
    side_grads = jax.jit(jax.grad(lambda p: system_terms(s, p)[1]))(s["params"])
    routers = [i for i, n in enumerate(s["names"]) if n.endswith("router_weight")]
    for i, name in enumerate(s["names"]):
        lm_g, side_g = float(jnp.abs(lm_grads[i]).max()), float(jnp.abs(side_grads[i]).max())
        if i in indexer:
            assert lm_g == 0.0 and side_g > 0.0, name
        else:
            assert lm_g > 0.0, name
    # the side loss is L_I + 0.001 L_balance: beyond the indexer it reaches
    # the routers, and through them what feeds the LATER router — never the
    # last layer's experts or the head, which only L_LM reaches
    last = [i for i, n in enumerate(s["names"])
            if "layer1_moe_experts" in n or "lm_head" in n or n.endswith("model_norm_gamma")]
    assert last and all(float(jnp.abs(side_grads[i]).max()) == 0.0 for i in last)
    assert all(float(jnp.abs(side_grads[i]).max()) > 0.0 for i in routers)
    # with the balance term switched off the side loss is the indexer's alone
    c0 = dict(s["c"], router_aux_loss_coef=0.0)
    net0 = build(c0)
    fn0, params0 = net0.export_jittable()

    def only_indexer_loss(params):
        with moe.moe_loss_frame() as frame:
            fn0(params, s["tok"])
        return moe.frame_loss(frame)

    grads0 = jax.jit(jax.grad(only_indexer_loss))(list(params0))
    for i, name in enumerate(s["names"]):
        moved = float(jnp.abs(grads0[i]).max()) > 0.0
        assert moved == (i in indexer), name


def test_three_position_streams_reach_the_model(system):
    s = system
    text = jnp.broadcast_to(jnp.arange(40)[None, None], (3, 2, 40))
    run = lambda positions: np.asarray(s["net"](mx.nd.array(s["tok"], dtype="int32"),
                                                NDArray(positions))._data)
    plain = np.asarray(s["net"](mx.nd.array(s["tok"], dtype="int32"))._data)
    # equal streams are text (float32 angles against the float64 tables)
    np.testing.assert_allclose(run(text), plain, atol=2e-3 * np.abs(plain).max())
    grid = text.at[1].set(text[1] // 5).at[2].set(text[2] % 5)
    want = reference.forward(named(s["net"]), s["tok"], config=s["c"], experts_held=HELD,
                             positions=grid)
    assert np.abs(run(grid) - plain).max() > 1e-2 * np.abs(plain).max()
    np.testing.assert_allclose(run(grid), want, atol=1e-3 * np.abs(plain).max())


def test_remat_changes_no_number(system):
    s = system
    grads = []
    for remat in (False, True):
        fn, params = build(s["c"], remat=remat).export_jittable()

        def loss(ps):
            with moe.moe_loss_frame() as frame:
                logits = fn(ps, s["tok"])
            return (streaming_softmax_ce(logits, jnp.asarray(s["labels"])).mean()
                    + moe.frame_loss(frame))

        grads.append(jax.jit(jax.grad(loss))(list(params)))
    for name, a, b in zip(s["names"], *grads):
        scale = max(float(jnp.abs(b).max()), 1e-8)
        np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(b) / scale, atol=1e-5,
                                   err_msg=name)


def test_logits_under_bf16_amp_stay_near_the_reference():
    """bf16 AMP as the benchmark sets it up (bf16 parameters; float32 index
    scores, selection, router, soft-maxes and norms), compared as the
    benchmark compares: per token, because a near-tie in a router score or at
    the 12th index score sends a token elsewhere under any rounding."""
    c = tiny_config()
    amp.init("bfloat16")
    try:
        net = build(c, sigma=0.05)
        net.cast("bfloat16")
        fn, params = net.export_jittable()
        tok, _ = batch(c, s=48)
        got = np.asarray(jax.jit(fn)(list(params), tok).astype(jnp.float32))
        want = np.asarray(reference.forward(named(net), tok, config=c, experts_held=HELD))
    finally:
        amp.disable()
    per_token = np.sqrt(np.mean((got - want) ** 2, axis=-1)).ravel() / want.std()
    assert np.median(per_token) <= 0.02
    assert np.mean(per_token > 0.10) < 0.10


def test_rescale_divides_what_writes_into_the_residual_stream():
    net = build(tiny_config(), sigma=0.1)
    before = {n: p.data().asnumpy() for n, p in net.collect_params().items()}
    net.rescale_residual_writers(48)
    scaled = 0
    for n, p in net.collect_params().items():
        writes = n.endswith(("o_weight", "down_weight"))
        ratio = 96 ** -0.5 if writes else 1.0
        np.testing.assert_allclose(p.data().asnumpy(), before[n] * ratio, rtol=1e-6, err_msg=n)
        scaled += writes
    assert scaled == 2 * 2                 # W_o and the experts' W_down, a layer


@pytest.mark.parametrize("flash", ["off", "interpret"], ids=["xla", "kernels"])
def test_spmd_trainer_step_lowers_the_loss_and_compiles_once(monkeypatch, flash):
    """A few ``SPMDTrainer`` steps under bf16 AMP with remat, on the XLA path
    and on the interpreted kernels: the loss finite and falling, no token
    dropped, one program a step, the four counters counted and the scopes in
    the compiled step's text."""
    monkeypatch.setenv("MXNET_TPU_FLASH", flash)
    c = tiny_config()
    amp.init("bfloat16")
    try:
        net = build(c, remat=True, sigma=0.05)
        net.cast("bfloat16")
        tok, labels = batch(c, b=2, s=32)

        def loss_fn(out, label):
            return NDArray(streaming_softmax_ce(out._data, label._data).mean(axis=-1))

        trainer = SPMDTrainer(net, loss_fn, "adam",
                              {"learning_rate": 3e-3, "multi_precision": True},
                              mesh=make_mesh(devices=jax.devices()[:1]))
        tok, labels = trainer.shard_batch(tok, labels)
        step = lambda: float(np.asarray(trainer.step((tok,), labels)._data))
        counted = profiler.counters()
        losses = [step()]            # the one compile
        trainer._drain_moe_extras()
        before = profiler.counters()
        losses += [step() for _ in range(5)]
        trainer._drain_moe_extras()
        after = profiler.counters()
        programs = len(trainer._step_cache)
        text = profiler.compiled_text("spmd.step")
        last = dict(trainer._moe_last)
    finally:
        amp.disable()          # clears the jit caches with it
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    assert programs == 1
    assert after["recompile_steady_state"] == before["recompile_steady_state"]
    assert after["moe_tokens_dropped"] == before["moe_tokens_dropped"]
    assert after["moe_step"] - before["moe_step"] == 5
    rows = after["moe_rows_routed_here"] - before["moe_rows_routed_here"]
    assert 0 < rows <= 5 * 2 * 64 * c["num_experts_per_tok"]     # 2 expert layers
    # from the program, through the frame's extras: 2 layers x 2 rows x the 3
    # causal tiles of 16 x 16 at S 32, all of them live at this length
    causal_tiles = after["sparse_attn_tiles_causal"] - before["sparse_attn_tiles_causal"]
    live_tiles = after["sparse_attn_tiles_live"] - before["sparse_attn_tiles_live"]
    assert causal_tiles == 5 * 2 * 2 * 3 and 0 < live_tiles <= causal_tiles
    assert last["sparse_attn_tiles_causal"] == 12 and "moe_rows_routed_here" in last
    # counted at trace time, a call site: each layer's attention is traced for
    # the forward and again inside its checkpoint's backward
    assert before["sparse_attention_traced"] - counted["sparse_attention_traced"] >= 2
    masked = before["attention_dispatch_masked"] - counted["attention_dispatch_masked"]
    assert (masked >= 2) if flash == "interpret" else (masked == 0)
    scored = [before[n] - counted[n] for n in ("index_scores_dispatch_pallas",
                                               "index_scores_dispatch_xla")]
    taken, other = scored if flash == "interpret" else scored[::-1]
    assert taken == before["sparse_attention_traced"] - counted["sparse_attention_traced"]
    assert other == 0
    assert after["sparse_attention_traced"] == before["sparse_attention_traced"]   # no retrace
    # what the two layers' checkpoints keep by name (B 2, S 32): the int8
    # selection, and on the kernels' path the core's bf16 output (8 heads of
    # 16) and its float32 log-sum-exp
    selection, core = 2 * 32 * 32, 2 * 8 * 32 * (16 * 2 + 4)
    assert (before["remat_kept_bytes"] - counted["remat_kept_bytes"]
            == 2 * (selection + (core if flash == "interpret" else 0)))
    assert after["remat_kept_bytes"] == before["remat_kept_bytes"]
    for scope in ("keye.attn/", "keye.attn.proj", "keye.attn.index/", "keye.attn.select",
                  "keye.attn.core", "keye.attn.index_loss", "keye.attn.out",
                  "keye.moe.route", "keye.moe.experts", "keye.head"):
        assert scope in text, scope
    assert "xing." not in text and "nemotron." not in text and "keye.moe.shared" not in text

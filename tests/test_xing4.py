"""Xing4.0's block (latent attention, the mHC residual mix, dropless experts
of which a chip holds its share) against the plain float32 reference in
``chipbench/reference/xing4.py``, at the configuration's ``dry_run`` sizes.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import amp, profiler
from incubator_mxnet_tpu.gluon.model_zoo import xing4
from incubator_mxnet_tpu.ndarray.ndarray import NDArray
from incubator_mxnet_tpu.ops import attention as attn_ops
from incubator_mxnet_tpu.ops import hyper_connections as hc
from incubator_mxnet_tpu.ops import moe as moe_ops
from incubator_mxnet_tpu.ops.nn import streaming_softmax_ce
from incubator_mxnet_tpu.parallel import SPMDTrainer, make_mesh

from chipbench.reference import xing4 as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_config(**over):
    c = json.load(open(os.path.join(ROOT, "chipbench", "configs", "xing4.0-29b-a4b.json")))
    c.update(c["dry_run"])
    c["n_routed_experts"] = c["published"]["n_routed_experts"]   # the router's width: 8
    c.update(over)
    return c


HELD = (2, 2)   # experts 2 and 3 of 8


def build(c, held=HELD, remat=False, seed=5, sigma=0.3):
    mx.random.seed(seed)
    net = xing4.Xing4ForCausalLM(c, experts_held=held, remat=remat)
    net.initialize(mx.init.Normal(sigma))
    return net


def named(net):
    return {p.name: p._data._data for p in net.collect_params().values()}


def batch(c, b=2, s=16, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, c["vocab_size"], (b, s + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


@pytest.fixture(scope="module")
def system():
    """The tiny model in float32 (one dense and one expert block: every
    kind of parameter), its jittable forward and a batch."""
    c = tiny_config(num_hidden_layers=2)
    net = build(c)
    fn, params = net.export_jittable()
    names = sorted(p.name for p in net.collect_params().values())
    tok, labels = batch(c)
    return {"c": c, "net": net, "fn": fn, "params": list(params), "names": names,
            "tok": tok, "labels": labels}


def test_logits_and_loss_match_the_reference_in_float32(system):
    s = system
    got = np.asarray(jax.jit(s["fn"])(s["params"], s["tok"]))
    want = np.asarray(reference.forward(named(s["net"]), s["tok"], config=s["c"],
                                        experts_held=HELD))
    # float32 on both sides, highest precision: rounding order only
    scale = np.abs(want).max()
    np.testing.assert_allclose(got / scale, want / scale, atol=1e-4)
    sys_loss = float(streaming_softmax_ce(jnp.asarray(got), jnp.asarray(s["labels"])).mean())
    ref_loss = float(reference.loss_per_token(jnp.asarray(want), s["labels"]).mean())
    assert abs(sys_loss - ref_loss) <= 1e-4 * abs(ref_loss)


def test_every_parameters_gradient_matches_the_reference(system):
    s = system
    tok, labels = s["tok"], s["labels"]

    def sys_loss(params):
        return streaming_softmax_ce(s["fn"](params, tok), jnp.asarray(labels)).mean()

    got = jax.jit(jax.grad(sys_loss))(s["params"])
    want = jax.jit(jax.grad(lambda p: reference.loss(
        p, tok, labels, config=s["c"], experts_held=HELD)))(named(s["net"]))
    trained = {p.name for p in s["net"].collect_params().values() if p.grad_req != "null"}
    assert len(trained) == len(s["names"]) - 1          # the selection bias
    for name, g in zip(s["names"], got):
        if name not in trained:
            continue
        w, g = np.asarray(want[name]), np.asarray(g)
        scale = max(np.abs(w).max(), 1e-8)
        assert np.abs(w).max() > 0, f"{name}: the reference's gradient is zero"
        np.testing.assert_allclose(g / scale, w / scale, atol=2e-4, err_msg=name)


def test_remat_changes_no_number(system):
    s = system
    c = s["c"]
    grads = []
    for remat in (False, True):
        fn, params = build(c, remat=remat).export_jittable()
        loss = lambda ps: streaming_softmax_ce(fn(ps, s["tok"]), jnp.asarray(s["labels"])).mean()
        grads.append(jax.jit(jax.grad(loss))(list(params)))
    names = sorted(p.name for p in build(c).collect_params().values())
    for name, a, b in zip(names, *grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7,
                                   err_msg=name)


def test_logits_under_bf16_amp_stay_near_the_reference():
    """bf16 AMP as the benchmark sets it up (bf16 parameters; float32 router,
    coefficients, softmax and norms), compared as the benchmark compares:
    per token, because a near-tie in a router score sends a token to another
    expert under any rounding and that token is then far off in a computation
    that is right.  8 mantissa bits are 0.4 % a rounding and a logit sums a
    few hundred of them through three blocks: the median token within 2 % of
    the logits' std, and fewer than a tenth of the tokens over 10 %.  A
    dropped expert or a bf16 softmax moves the median past that."""
    c = tiny_config(num_hidden_layers=2)
    amp.init("bfloat16")
    try:
        net = build(c, sigma=0.05)
        net.cast("bfloat16")
        fn, params = net.export_jittable()
        tok, _ = batch(c)
        got = np.asarray(jax.jit(fn)(list(params), tok).astype(jnp.float32))
        want = np.asarray(reference.forward(named(net), tok, config=c, experts_held=HELD))
    finally:
        amp.disable()
    per_token = np.sqrt(np.mean((got - want) ** 2, axis=-1)).ravel() / want.std()
    assert np.median(per_token) <= 0.02
    assert np.mean(per_token > 0.10) < 0.10


def _expert_layer(c, held, seed=11):
    mx.random.seed(seed)
    layer = xing4.SparseExperts(
        c["hidden_size"], c["moe_intermediate_size"], c["n_routed_experts"],
        c["num_experts_per_tok"], held, c["n_shared_experts"],
        c["routed_scaling_factor"], c["norm_topk_prob"], prefix="moe_")
    layer.initialize(mx.init.Normal(0.3))
    return layer


def test_the_shares_of_the_expert_layer_add_up_to_the_whole_layer():
    """The guide's share test: 4 chips hold 2 of 8 experts each; their parts,
    with the shared expert (which every chip computes alike) counted once,
    are the uncut reference's layer."""
    c = tiny_config()
    whole = _expert_layer(c, (0, 8))
    p = {k: jnp.asarray(v) for k, v in named(whole).items()}
    x = np.random.RandomState(2).randn(2, 24, c["hidden_size"]).astype(np.float32)
    want = np.asarray(reference.experts(p, "moe_", jnp.asarray(x), c, (0, 8)))
    shared = np.asarray(reference.swiglu(jnp.asarray(x), p["moe_shared_gate_up_weight"],
                                         p["moe_shared_down_weight"]))
    total, rows = -3 * shared, 0            # four chips computed it; it counts once
    for chip in range(4):
        first = 2 * chip
        share = _expert_layer(c, (first, 2))
        for name, param in share.collect_params().items():
            full = whole.collect_params()[name].data().asnumpy()
            if "experts_" in name:
                full = full[first:first + 2]
            param.set_data(mx.nd.array(full))
        y, stats = share(mx.nd.array(x))
        total = total + y.asnumpy()
        rows += int(stats.asnumpy()[0])
    assert rows == 2 * 24 * c["num_experts_per_tok"]      # every pair lands on one chip
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("target,rows_here", [(2, 2 * 24 * 2), (5, 0)])
def test_routing_drops_nothing_when_every_token_picks_the_same_experts(target, rows_here):
    """A selection bias sends every token to experts ``target, target+1``: all
    pairs land on the two experts held (every row is ours, the largest
    bucket), or on none (the smallest); the layer equals the reference."""
    c = tiny_config()
    layer = _expert_layer(c, HELD)
    bias = np.zeros((8,), np.float32)
    bias[target:target + 2] = 10.0
    layer.select_bias.set_data(mx.nd.array(bias))
    x = np.random.RandomState(3).randn(2, 24, c["hidden_size"]).astype(np.float32)
    y, stats = layer(mx.nd.array(x))
    p = {k: jnp.asarray(v) for k, v in named(layer).items()}
    want = np.asarray(reference.experts(p, "moe_", jnp.asarray(x), c, HELD))
    np.testing.assert_allclose(y.asnumpy(), want, rtol=1e-4, atol=1e-5)
    rows, load_min, load_max = stats.asnumpy()[:3]
    assert rows == rows_here and load_max == rows_here / 2 and load_min == rows_here / 2
    assert stats.asnumpy()[3:].tolist() == [48.0 if target <= e < target + 2 else 0.0
                                            for e in range(8)]


def test_row_buckets_hold_the_expected_share_and_the_worst_case():
    buckets = moe_ops.dropless_row_buckets(4096 * 4, 8, 64)
    assert buckets == [64, 3072, 6144, 16384]
    assert moe_ops.dropless_row_buckets(48, 2, 8)[-1] == 48


@pytest.mark.parametrize("clamp", [-30.0, 30.0])
def test_sinkhorn_is_doubly_stochastic_at_both_clamps(clamp):
    """Every entry at one clamp; the diagonal at this clamp and the rest at
    the other; and logits as training sees them (order 1) moved to the clamp
    and cut there: 20 iterations leave rows and columns summing to 1."""
    rng = np.random.RandomState(4)
    logits = np.clip(rng.randn(6, 4, 4) + clamp, -30, 30).astype(np.float32)
    logits[0] = clamp
    logits[1] = np.where(np.eye(4) > 0, clamp, -clamp)
    m = np.asarray(hc.sinkhorn(jnp.asarray(logits), 20, 1e-6))
    assert np.all(np.isfinite(m)) and np.all(m >= 0)
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-3)
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-3)
    want = np.asarray(reference.sinkhorn(jnp.asarray(logits), 20, 1e-6))
    np.testing.assert_allclose(m, want, atol=1e-6)


def test_mix_starts_as_a_plain_residual_block():
    """H_pre = 1/n, H_post = 1 and H_res near the identity at α·m = 0."""
    n, d = 4, 16
    state = jnp.asarray(np.random.RandomState(5).randn(n, 3, d), jnp.float32)
    u, h_post, h_res = hc.mhc_pre(state, jnp.zeros((n * n + 2 * n, n * d)),
                                  jnp.full((3,), 0.01), jnp.asarray(xing4.mhc_offset_init(n)))
    np.testing.assert_allclose(np.asarray(u), np.asarray(state.mean(0)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_post), 1.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_res), np.eye(n)[:, :, None] * np.ones(3), atol=2e-3)
    merged = hc.mhc_post(state, u, h_post, h_res)
    np.testing.assert_allclose(np.asarray(merged), np.asarray(state + u[None]), atol=2e-2)


def _qkv(b=1, h=2, s=256, d_qk=24, d_v=16, dtype=jnp.float32, seed=6):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, d_qk), dtype)
    k = jax.random.normal(ks[1], (b, s, h, d_qk), dtype)
    v = jax.random.normal(ks[2], (b, s, h, d_v), dtype)
    return q, k, v


def _plain_attention(q, k, v, scale):
    t = lambda x: x.transpose(0, 2, 1, 3)
    return t(attn_ops.attention_reference(t(q), t(k), t(v), True, scale))


@pytest.mark.parametrize("path", ["xla", "pallas-one-block", "pallas-2x2-blocks"])
def test_attention_dispatcher_takes_values_narrower_than_keys(path, monkeypatch):
    """d_qk 24, d_v 16 through ``_attend_bshd``: the XLA path, and the Pallas
    forward and backward (interpreter) with the 256 rows in one block and in
    2 x 2 blocks of 128, against ``attention_reference``, forward and backward."""
    monkeypatch.setenv("MXNET_TPU_FLASH", "off" if path == "xla" else "interpret")
    if path == "pallas-2x2-blocks":
        monkeypatch.setattr(attn_ops, "_PALLAS_BLOCK_Q", 128)
        monkeypatch.setattr(attn_ops, "_PALLAS_BLOCK_K", 128)
    q, k, v = _qkv()
    scale = 0.2
    weights = jax.random.normal(jax.random.PRNGKey(7), (1, 256, 2, 16))

    def through(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * weights)

    system = lambda q, k, v: attn_ops._attend_bshd(q, k, v, True, scale)
    plain = lambda q, k, v: _plain_attention(q, k, v, scale)
    kernels = str(jax.make_jaxpr(jax.grad(through(system), argnums=(0, 1, 2)))(q, k, v)
                  ).count("pallas_call")
    assert kernels == (0 if path == "xla" else 2)  # forward, one-pass backward
    out = system(q, k, v)
    assert out.shape == (1, 256, 2, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain(q, k, v)), atol=2e-5)
    got = jax.grad(through(system), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(through(plain), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 4e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("sq,sk,blocks", [(128, 128, (32, 32)), (64, 128, (32, 64)),
                                          (128, 64, (64, 32))],
                         ids=["four-blocks", "fewer-queries", "fewer-keys"])
@pytest.mark.parametrize("d_qk,d_v", [(192, 128), (64, 64)], ids=["192-128", "64-64"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_one_pass_backward_matches_the_xla_backward(causal, d_qk, d_v, sq, sk, blocks,
                                                    dtype, tol):
    """The blockwise kernels' backward (interpreter) is ONE ``pallas_call``
    whose dq, dk and dv equal ``_flash_bwd_xla``'s on the same operands:
    query blocks on and past the diagonal, blocks of unlike lengths,
    ``sq != sk`` (the causal mask stays aligned at position 0), keys wider
    than values."""
    ks = jax.random.split(jax.random.PRNGKey(sq + d_qk), 4)
    q = jax.random.normal(ks[0], (1, 2, sq, d_qk), dtype)
    k = jax.random.normal(ks[1], (1, 2, sk, d_qk), dtype)
    v = jax.random.normal(ks[2], (1, 2, sk, d_v), dtype)
    do = jax.random.normal(ks[3], (1, 2, sq, d_v), dtype)
    scale = d_qk ** -0.5

    def kernels(q, k, v):
        return attn_ops._flash_kernels(q, k, v, causal, scale,
                                       attn_ops._Launch(True, blocks))

    out, pull = jax.vjp(kernels, q, k, v)
    assert str(jax.make_jaxpr(pull)(do)).count("pallas_call") == 1
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(attn_ops.attention_reference(q, k, v, causal, scale), np.float32),
        atol=2 * tol)
    for got, want in zip(pull(do), attn_ops._flash_bwd_xla(causal, scale, (q, k, v), do)):
        assert got.shape == want.shape and got.dtype == dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32), atol=tol)


def _fwd_kernel_of_pr30(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k, causal, scale):
    """The blockwise forward kernel as PR 30 left it, kept here as what the
    forward's bits are held to: the causal mask and
    ``online_softmax_update``'s guards on EVERY block."""
    pl = attn_ops._pl
    i = pl.program_id(1)
    block_q = q_ref.shape[1]
    nk = k_ref.shape[1] // block_k
    prec = (jax.lax.Precision.HIGHEST if q_ref.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    q = q_ref[0]
    m0 = jnp.full((block_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, v_ref.shape[2]), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
            precision=prec) * scale
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        acc_new, m_new, l_new = attn_ops.online_softmax_update(
            acc, m, l, s, v,
            lambda p, v: jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=prec))
        return m_new, l_new, acc_new

    nk_bound = (jnp.minimum(nk, ((i + 1) * block_q + block_k - 1) // block_k)
                if causal else nk)
    m, l, acc = jax.lax.fori_loop(0, nk_bound, body, (m0, l0, acc0))
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    m_safe = jnp.where(jnp.isneginf(m), 0.0, m)
    lse_ref[0] = jnp.broadcast_to(m_safe + jnp.log(l), lse_ref.shape[1:])


class _EagerRef:
    """A kernel's ``Ref`` outside a ``pallas_call``: under ``disable_jit`` the
    body then runs operation by operation, so two bodies that do the same
    operations give the same bits (inside one jitted program, the
    interpreter's included, the CPU compiler's fusion decides the last one)."""

    def __init__(self, value):
        self.value = value

    shape = property(lambda self: self.value.shape)
    dtype = property(lambda self: self.value.dtype)

    @staticmethod
    def _index(index):
        index = index if isinstance(index, tuple) else (index,)
        return tuple(slice(int(i.start), int(i.start) + i.size)
                     if isinstance(i, attn_ops._pl.Slice) else i for i in index)

    def __getitem__(self, index):
        return self.value[self._index(index)]

    def __setitem__(self, index, value):
        self.value = self.value.at[self._index(index)].set(value)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("blocks", [(32, 32), (64, 32), (32, 64)], ids=str)
def test_forward_without_the_dead_row_guards_keeps_every_bit(blocks, dtype, monkeypatch):
    """Causal, 128 rows in several blocks, 192-wide keys and 128-wide values:
    the forward kernel, whose online-softmax update has dropped the guards
    for rows with no live key (``_live_softmax_update``: under the causal
    mask every row sees key 0 in its first block), gives PR 30's output and
    log-sum-exp bit for bit, grid cell by grid cell; and through the
    interpreter it equals ``attention_reference``."""
    s, scale = 128, 192 ** -0.5
    block_q, block_k = blocks
    ks = jax.random.split(jax.random.PRNGKey(31), 3)
    q = jax.random.normal(ks[0], (1, s, 192), dtype)
    k = jax.random.normal(ks[1], (1, s, 192), dtype)
    v = jax.random.normal(ks[2], (1, s, 128), dtype)

    def cell(kernel, i):
        monkeypatch.setattr(attn_ops._pl, "program_id", lambda axis: (0, i)[axis])
        o = _EagerRef(jnp.zeros((1, block_q, 128), dtype))
        lse = _EagerRef(jnp.zeros((1, block_q, attn_ops._LANE), jnp.float32))
        with jax.disable_jit():
            kernel(_EagerRef(q[:, i * block_q:(i + 1) * block_q]), _EagerRef(k),
                   _EagerRef(v), o, lse, block_k=block_k, causal=True, scale=scale)
        return np.asarray(o.value, np.float32), np.asarray(lse.value)

    rows = []
    for i in range(s // block_q):
        got, want = cell(attn_ops._fwd_kernel, i), cell(_fwd_kernel_of_pr30, i)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
        rows.append(got[0])
    monkeypatch.undo()
    out = attn_ops._flash_fwd_pallas(q, k, v, True, scale, True, *blocks)
    want = attn_ops.attention_reference(q, k, v, True, scale)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32),
                               atol=tol)
    np.testing.assert_allclose(np.concatenate(rows, 1), np.asarray(out, np.float32), atol=tol)


def test_large_scores_take_the_blockwise_kernels(monkeypatch):
    """Above ``_KERNEL_MIN_SCORE_BYTES`` of float32 scores the kernels are
    taken, forward and backward, whatever the length: S 128 here, which the
    crossover alone leaves on the XLA path."""
    monkeypatch.setenv("MXNET_TPU_FLASH", "on")  # traced only, nothing lowered
    q, k, v = _qkv(s=128)

    def kernels():  # a new function each time: a trace is cached by its function
        grad = jax.grad(lambda q, k, v: attn_ops._attend_bshd(q, k, v, True, 0.2).sum(),
                        argnums=(0, 1, 2))
        return str(jax.make_jaxpr(grad)(q, k, v)).count("pallas_call")

    assert attn_ops._kernel_path(q, k, seq_axis=1) == ("xla", None)
    assert kernels() == 0
    monkeypatch.setattr(attn_ops, "_KERNEL_MIN_SCORE_BYTES", 4 * 2 * 128 * 128)
    assert attn_ops._kernel_path(q, k, seq_axis=1) == (
        "blockwise", attn_ops._Launch(False, (128, 128)))
    assert kernels() == 2  # forward, one-pass backward


def test_fused_attention_merges_heads_at_the_values_width():
    q, k, v = _qkv(s=32)
    out = attn_ops.fused_attention(q.reshape(1, 32, 48), k.reshape(1, 32, 48),
                                   v.reshape(1, 32, 32), num_heads=2, causal=True, scale=0.2)
    want = _plain_attention(q, k, v, 0.2).reshape(1, 32, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_yarn_tables_keep_fast_frequencies_and_stretch_slow_ones():
    plain = attn_ops.yarn_rotary_tables(8, 64, 10000.0)
    yarn = attn_ops.yarn_rotary_tables(8, 64, 10000.0, factor=64.0, original=4096,
                                       beta_fast=32, beta_slow=1, mscale=1.0,
                                       mscale_all_dim=1.0)
    # the fastest pair keeps its angle, the slowest turns 64 times less
    np.testing.assert_allclose(yarn[1][:, 0], plain[1][:, 0], rtol=1e-6)
    np.testing.assert_allclose(np.arcsin(yarn[1][1, -1]) * 64, np.arcsin(plain[1][1, -1]),
                               rtol=1e-4)
    angles, scale = reference.rotary_angles(8, 64, tiny_config())
    np.testing.assert_allclose(yarn[0], np.cos(angles) * scale, atol=1e-6)


def test_spmd_trainer_step_lowers_the_loss_and_compiles_once():
    c = tiny_config()
    net = build(c, remat=True, sigma=0.05)
    tok, labels = batch(c, b=2, s=32)

    def loss_fn(out, label):
        return NDArray(streaming_softmax_ce(out._data, label._data).mean(axis=-1))

    trainer = SPMDTrainer(net, loss_fn, "adam", {"learning_rate": 3e-3},
                          mesh=make_mesh(devices=jax.devices()[:1]))
    tok, labels = trainer.shard_batch(tok, labels)
    step = lambda: float(np.asarray(trainer.step((tok,), labels)._data))
    losses = [step()]            # the one compile (an earlier test may have armed the guard)
    trainer._drain_moe_extras()
    before = profiler.counters()
    losses += [step() for _ in range(5)]
    trainer._drain_moe_extras()
    after = profiler.counters()
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    assert len(trainer._step_cache) == 1
    assert after["recompile_steady_state"] == before["recompile_steady_state"]
    assert after["moe_tokens_dropped"] == before["moe_tokens_dropped"]
    assert after["moe_step"] - before["moe_step"] == 5
    rows = after["moe_rows_routed_here"] - before["moe_rows_routed_here"]
    assert 0 < rows <= 5 * 2 * 64 * c["num_experts_per_tok"]     # 2 expert layers
    assert trainer._moe_last["moe_expert_load_max"] >= trainer._moe_last["moe_expert_load_min"]
    # the noaux_tc rule ran inside each step: six moves of 0.001 at most
    trainer.sync_to_block()
    for block in net.model.blocks[1:]:
        bias = block.ffn.select_bias.data().asnumpy()
        assert bias.dtype == np.float32 and 0 < np.abs(bias).max() <= 6 * 0.001 + 1e-7


def test_the_balancing_rule_moves_the_bias_towards_even_loads():
    c = tiny_config()
    layer = _expert_layer(c, HELD)
    load = jnp.asarray([40.0, 0, 8, 8, 8, 8, 12, 12])          # mean 12
    bias = np.asarray(layer.balanced_bias(jnp.zeros((8,)), load))
    np.testing.assert_allclose(bias, [-0.001, 0.001, 0.001, 0.001, 0.001, 0.001, 0, 0])
    layer.cast("bfloat16")
    assert str(layer.select_bias.dtype) == "float32"           # 0.001 is under bf16's step
    assert str(layer.router_weight.dtype) == "bfloat16"


def test_the_mtp_module_is_refused_not_guessed():
    with pytest.raises(ValueError, match="multi-token-prediction"):
        xing4.Xing4Model(tiny_config(num_nextn_predict_layers=1))

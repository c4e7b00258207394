"""Nemotron-H's hybrid stack (Mamba-2 layers by a chunked scan, grouped-query
attention, relu² experts of which a chip holds its share) against the plain
float32 reference in ``chipbench/reference/nemotron_h.py``, at the
configuration's ``dry_run`` sizes.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import amp, profiler
from incubator_mxnet_tpu.gluon.model_zoo import decoder, nemotron_h, xing4
from incubator_mxnet_tpu.ndarray.ndarray import NDArray
from incubator_mxnet_tpu.ops import attention as attn_ops
from incubator_mxnet_tpu.ops import moe as moe_ops
from incubator_mxnet_tpu.ops import ssm
from incubator_mxnet_tpu.ops.nn import streaming_softmax_ce
from incubator_mxnet_tpu.parallel import SPMDTrainer, make_mesh

from chipbench.reference import nemotron_h as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_config(**over):
    c = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                    "nemotron-3-nano-30b-a3b.json")))
    c.update(c["dry_run"])
    c["n_routed_experts"] = c["published"]["n_routed_experts"]   # the router's width: 8
    c.update(over)
    return c


HELD = (2, 2)   # experts 2 and 3 of 8


def build(c, held=HELD, remat=False, seed=5, sigma=0.3):
    mx.random.seed(seed)
    net = nemotron_h.NemotronHForCausalLM(c, experts_held=held, remat=remat)
    net.initialize(mx.init.Normal(sigma))
    return net


def named(net):
    return {p.name: p._data._data for p in net.collect_params().values()}


def batch(c, b=2, s=20, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, c["vocab_size"], (b, s + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


# ---------------------------------------------------------------------------
# ops/ssm.py against naive forms
# ---------------------------------------------------------------------------


def _scan_operands(s, g, seed=0, b=2, h=4, p=8, n=16, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed + s), 8)
    return dict(
        x=jax.random.normal(k[0], (b, s, h, p), dtype),
        dt=jax.random.normal(k[1], (b, s, h), dtype),
        a_log=jnp.log(jax.random.uniform(k[2], (h,), minval=1.0, maxval=16.0)),
        b=jax.random.normal(k[3], (b, s, g, n), dtype),
        c=jax.random.normal(k[4], (b, s, g, n), dtype),
        d_skip=jax.random.normal(k[5], (h,)),
        dt_bias=jax.random.normal(k[6], (h,)) - 2.0,
        weights=jax.random.normal(k[7], (b, s, h, p)))


def _naive_scan(x, dt, a_log, b, c, d_skip, dt_bias, floor):
    """The recurrence, step by step (the reference's), plus the skip."""
    h, g = x.shape[2], b.shape[2]
    delta = jnp.maximum(jax.nn.softplus(dt + dt_bias), floor)
    per_head = lambda m: jnp.repeat(m, h // g, axis=2)
    y = reference.recurrence(x, delta, -jnp.exp(a_log), per_head(b), per_head(c))
    return y + d_skip[:, None] * x


SCAN_CASES = [(64, 16, 2), (50, 16, 1), (32, 32, 4), (40, 128, 2), (17, 4, 2)]


@pytest.mark.parametrize("s,chunk,groups", SCAN_CASES, ids=str)
def test_chunked_scan_matches_the_recurrence(s, chunk, groups):
    """Values of ``ssm_scan`` against the step-by-step recurrence: several
    chunks, one chunk, a chunk longer than the sequence, and lengths no chunk
    divides (50 and 17: padded inside the op)."""
    o = _scan_operands(s, groups)
    w = o.pop("weights")
    got = ssm.ssm_scan(**o, chunk_size=chunk, dt_floor=1e-4)
    want = _naive_scan(**o, floor=1e-4)
    assert got.shape == want.shape == w.shape
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got) / scale, np.asarray(want) / scale, atol=5e-6)


@pytest.mark.parametrize("s,chunk,groups", SCAN_CASES, ids=str)
def test_chunked_scan_gradients_match_the_recurrences(s, chunk, groups):
    """All seven operands' gradients by autodiff through the chunked form."""
    o = _scan_operands(s, groups)
    w = o.pop("weights")
    names = list(o)
    got = jax.grad(lambda *a: jnp.sum(ssm.ssm_scan(*a, chunk_size=chunk, dt_floor=1e-4) * w),
                   argnums=tuple(range(7)))(*o.values())
    want = jax.grad(lambda *a: jnp.sum(_naive_scan(*a, floor=1e-4) * w),
                    argnums=tuple(range(7)))(*o.values())
    for name, g, r in zip(names, got, want):
        scale = float(jnp.abs(r).max())
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(g) / scale, np.asarray(r) / scale, atol=2e-4,
                                   err_msg=name)


def test_scan_keeps_decays_and_state_in_float32_under_bf16_operands():
    """bf16 operands: the products ride in bf16, the result is bf16 and stays
    within bf16's rounding of the float32 recurrence (8 mantissa bits, a few
    dozen terms a sum: 3 % of the largest value); a decay or a cumulative sum
    computed in bf16 would be off by tens of percent after 64 positions."""
    o = _scan_operands(64, 2)
    o.pop("weights")
    want = _naive_scan(**o, floor=1e-4)
    low = {k: (v.astype(jnp.bfloat16) if k in ("x", "dt", "b", "c") else v) for k, v in o.items()}
    got = ssm.ssm_scan(**low, chunk_size=16, dt_floor=1e-4)
    assert got.dtype == jnp.bfloat16
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) / scale < 0.03


def test_step_is_clamped_below_by_the_floor():
    o = _scan_operands(16, 2)
    o.pop("weights")
    o["dt_bias"] = jnp.full_like(o["dt_bias"], -30.0)          # softplus → 1e-13
    got = ssm.ssm_scan(**o, chunk_size=8, dt_floor=0.05)
    want = _naive_scan(**o, floor=0.05)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert float(jnp.abs(got - o["d_skip"][:, None] * o["x"]).max()) > 1e-3   # the state moved


@pytest.mark.parametrize("taps", [4, 2])
def test_causal_convolution_matches_shifted_adds(taps):
    rng = np.random.RandomState(taps)
    x, w, b = rng.randn(2, 11, 6), rng.randn(6, taps), rng.randn(6)
    want = np.zeros_like(x)
    for t in range(11):
        for k in range(taps):
            src = t - (taps - 1) + k
            if src >= 0:
                want[:, t] += w[:, k] * x[:, src]
    want += b
    args = [jnp.asarray(a, jnp.float32) for a in (x, w, b)]
    np.testing.assert_allclose(np.asarray(ssm.causal_conv1d(*args)), want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ssm.causal_conv1d(*args, activation="silu")),
                               want / (1 + np.exp(-want)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(reference.causal_conv(*args)), want, atol=1e-5)
    # causal: position 3's output does not see position 4
    moved = args[0].at[:, 4:].add(1.0)
    np.testing.assert_array_equal(np.asarray(ssm.causal_conv1d(moved, *args[1:]))[:, :4],
                                  np.asarray(ssm.causal_conv1d(*args))[:, :4])


@pytest.mark.parametrize("groups", [1, 2, 4])
def test_grouped_gated_norm_matches_the_naive_form(groups):
    rng = np.random.RandomState(groups)
    y, z, gamma = rng.randn(2, 5, 16), rng.randn(2, 5, 16), rng.rand(16) + 0.5
    gated = y * z / (1 + np.exp(-z))
    blocks = gated.reshape(2, 5, groups, 16 // groups)
    want = (blocks / np.sqrt((blocks ** 2).mean(-1, keepdims=True) + 1e-5)).reshape(2, 5, 16) * gamma
    got = ssm.gated_rms_norm(*(jnp.asarray(a, jnp.float32) for a in (y, z, gamma)),
                             num_groups=groups, eps=1e-5)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


def test_mixer_matches_the_references_layer():
    c = tiny_config()
    mx.random.seed(4)
    layer = nemotron_h.Mamba2Mixer(
        c["hidden_size"], c["mamba_num_heads"], c["mamba_head_dim"], c["n_groups"],
        c["ssm_state_size"], c["conv_kernel"], c["chunk_size"], c["layer_norm_epsilon"],
        c["time_step_min"], c["time_step_max"], c["time_step_floor"], prefix="mamba_")
    layer.initialize(mx.init.Normal(0.3))
    layer.conv_bias.set_data(mx.nd.array(np.random.RandomState(1).randn(*layer.conv_bias.shape)))
    x = np.random.RandomState(2).randn(2, 21, c["hidden_size"]).astype(np.float32)
    p = {k: jnp.asarray(v) for k, v in named(layer).items()}
    want = np.asarray(reference.mamba(p, "mamba_", jnp.asarray(x), c))
    np.testing.assert_allclose(layer(mx.nd.array(x)).asnumpy(), want, rtol=1e-4, atol=1e-5)
    # the published initialisation: A in -16..-1, the step log-uniform in [min, max], D = 1
    a = -np.exp(layer.A_log.data().asnumpy())
    step = np.log1p(np.exp(layer.dt_bias.data().asnumpy()))
    assert (-16 <= a).all() and (a <= -1).all()
    assert (c["time_step_min"] * 0.999 <= step).all() and (step <= c["time_step_max"] * 1.001).all()
    assert (layer.D.data().asnumpy() == 1).all()
    layer.cast("bfloat16")
    assert {str(p.dtype) for p in (layer.A_log, layer.dt_bias, layer.D)} == {"float32"}
    assert str(layer.in_proj_weight.dtype) == "bfloat16"


# ---------------------------------------------------------------------------
# grouped-query heads through the attention dispatcher
# ---------------------------------------------------------------------------


def _gqa_operands(h=8, h_kv=2, s=128, d=32, b=2):
    k = jax.random.split(jax.random.PRNGKey(h_kv), 4)
    return (jax.random.normal(k[0], (b, s, h, d)), jax.random.normal(k[1], (b, s, h_kv, d)),
            jax.random.normal(k[2], (b, s, h_kv, d)), jax.random.normal(k[3], (b, s, h, d)))


def _repeated_heads(q, k, v, scale=None):
    group = q.shape[2] // k.shape[2]
    return attn_ops.attention_reference_bshd(
        q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2), True, scale)


@pytest.mark.parametrize("h_kv", [1, 2, 4])
@pytest.mark.parametrize("path", ["xla", "pallas-one-block", "pallas-2x2-blocks"])
def test_grouped_query_attention_matches_repeated_heads(path, h_kv, monkeypatch):
    """8 query heads on 1, 2 or 4 key/value heads through ``_attend_bshd``:
    the XLA path, and the blockwise kernels (interpreter) with the 128 rows in
    one block and in 2 x 2 blocks of 64; values and all three gradients against
    plain attention on keys and values repeated over the group."""
    monkeypatch.setenv("MXNET_TPU_FLASH", "off" if path == "xla" else "interpret")
    if path == "pallas-2x2-blocks":
        monkeypatch.setattr(attn_ops, "_PALLAS_BLOCK_Q", 64)
        monkeypatch.setattr(attn_ops, "_PALLAS_BLOCK_K", 64)
    q, k, v, w = _gqa_operands(h_kv=h_kv)
    system = lambda q, k, v: attn_ops._attend_bshd(q, k, v, True, None)
    through = lambda fn: (lambda q, k, v: jnp.sum(fn(q, k, v) * w))
    before = profiler.counters()["attention_dispatch_grouped"]
    kernels = str(jax.make_jaxpr(jax.grad(through(system), argnums=(0, 1, 2)))(q, k, v)
                  ).count("pallas_call")
    assert kernels == (0 if path == "xla" else 2)
    assert profiler.counters()["attention_dispatch_grouped"] == before + 1
    out = system(q, k, v)
    assert out.shape == q.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(_repeated_heads(q, k, v)), atol=2e-5)
    got = jax.grad(through(system), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(through(_repeated_heads), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-4)


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_equal_heads_keep_every_bit_and_count_no_group(path, monkeypatch):
    """With as many key/value heads as query heads the dispatcher is what it
    was: the kernels' index maps are the identity and nothing is repeated, so
    the result equals the launchers' called as before grouped heads existed
    (``_flash_kernels`` / ``_flash_bshd`` directly), bit for bit, forward and
    backward, and ``attention_dispatch_grouped`` does not move."""
    monkeypatch.setenv("MXNET_TPU_FLASH", "off" if path == "xla" else "interpret")
    q, k, v, w = _gqa_operands(h_kv=8)
    before = profiler.counters()["attention_dispatch_grouped"]
    loss = lambda fn: (lambda q, k, v: jnp.sum(fn(q, k, v) * w))
    got = jax.value_and_grad(loss(lambda q, k, v: attn_ops._attend_bshd(q, k, v, True, 0.2)),
                             argnums=(0, 1, 2))(q, k, v)
    assert profiler.counters()["attention_dispatch_grouped"] == before
    if path == "xla":
        direct = lambda q, k, v: attn_ops._flash_bshd(q, k, v, True, 0.2)
    else:
        t = lambda x: x.transpose(0, 2, 1, 3)
        launch = attn_ops._Launch(True, (128, 128))
        direct = lambda q, k, v: t(attn_ops._flash_kernels(t(q), t(k), t(v), True, 0.2, launch))
    want = jax.value_and_grad(loss(direct), argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


def test_fused_attention_takes_fewer_key_value_heads():
    q, k, v, _ = _gqa_operands(h=4, h_kv=2, s=24, d=16)
    out = attn_ops.fused_attention(q.reshape(2, 24, 64), k.reshape(2, 24, 32),
                                   v.reshape(2, 24, 32), num_heads=4, kv_heads=2, causal=True)
    want = _repeated_heads(q, k, v).reshape(2, 24, 64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    with pytest.raises(ValueError, match="kv_heads"):
        attn_ops.fused_attention(q.reshape(2, 24, 64), k.reshape(2, 24, 32),
                                 v.reshape(2, 24, 32), num_heads=4, kv_heads=3)
    with pytest.raises(ValueError, match="do not divide"):
        attn_ops.flash_attention(jnp.zeros((1, 4, 8, 8)), jnp.zeros((1, 3, 8, 8)),
                                 jnp.zeros((1, 3, 8, 8)))


def test_grouped_query_block_matches_the_references_layer():
    c = tiny_config()
    mx.random.seed(6)
    layer = nemotron_h.GroupedQueryAttention(
        c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
        prefix="attn_")
    layer.initialize(mx.init.Normal(0.3))
    x = np.random.RandomState(2).randn(2, 40, c["hidden_size"]).astype(np.float32)
    p = {k: jnp.asarray(v) for k, v in named(layer).items()}
    for block in (16, 64):            # the reference's query blocks change nothing
        want = np.asarray(reference.attention(p, "attn_", jnp.asarray(x), c, block))
        np.testing.assert_allclose(layer(mx.nd.array(x)).asnumpy(), want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# relu² experts
# ---------------------------------------------------------------------------


def _expert_layer(c, held, seed=11):
    mx.random.seed(seed)
    layer = decoder.SparseExperts(
        c["hidden_size"], c["moe_intermediate_size"], c["n_routed_experts"],
        c["num_experts_per_tok"], held, c["n_shared_experts"],
        c["routed_scaling_factor"], c["norm_topk_prob"], scope="nemotron.moe",
        expert_form="relu2", shared_width=c["moe_shared_expert_intermediate_size"],
        prefix="moe_")
    layer.initialize(mx.init.Normal(0.3))
    return layer


@pytest.mark.parametrize("held", [(2, 2), (0, 8), (6, 2)], ids=str)
def test_relu2_experts_match_a_dense_loop(held):
    c = tiny_config()
    layer = _expert_layer(c, held)
    x = np.random.RandomState(3).randn(2, 24, c["hidden_size"]).astype(np.float32)
    y, stats = layer(mx.nd.array(x))
    p = {k: jnp.asarray(v) for k, v in named(layer).items()}
    want = np.asarray(reference.experts(p, "moe_", jnp.asarray(x), c, held))
    np.testing.assert_allclose(y.asnumpy(), want, rtol=1e-4, atol=1e-5)
    assert stats.asnumpy()[3:].sum() == 2 * 24 * c["num_experts_per_tok"]
    assert layer.shared_expert.up_weight.shape == (c["moe_shared_expert_intermediate_size"],
                                                   c["hidden_size"])


def test_relu2_experts_gradients_match_a_dense_loop():
    c = tiny_config()
    x = jnp.asarray(np.random.RandomState(3).randn(2, 24, c["hidden_size"]), jnp.float32)
    rng = np.random.RandomState(5)
    d, h, e = c["hidden_size"], c["moe_intermediate_size"], c["n_routed_experts"]
    router, bias = jnp.asarray(rng.randn(e, d), jnp.float32), jnp.zeros((e,))
    w_up = jnp.asarray(rng.randn(2, d, h) * 0.3, jnp.float32)
    w_down = jnp.asarray(rng.randn(2, h, d) * 0.3, jnp.float32)
    p = lambda router, w_up, w_down: {"router_weight": router, "select_bias": bias,
                                      "experts_up_weight": w_up, "experts_down_weight": w_down}

    def system(x, router, w_up, w_down):
        return moe_ops.moe_ffn_dropless(
            x, router, bias, w_up, w_down, num_experts=e, top_k=2, first_expert=2,
            routed_scaling=2.5, norm_topk=True, expert_form="relu2")[0].sum()

    def plain(x, router, w_up, w_down):
        return reference.experts(p(router, w_up, w_down), "", x, c, HELD, shared=False).sum()

    got = jax.grad(system, argnums=(0, 1, 2, 3))(x, router, w_up, w_down)
    want = jax.grad(plain, argnums=(0, 1, 2, 3))(x, router, w_up, w_down)
    for g, r in zip(got, want):
        scale = float(jnp.abs(r).max())
        np.testing.assert_allclose(np.asarray(g) / scale, np.asarray(r) / scale, atol=2e-4)


def test_swiglu_experts_are_unchanged_and_the_form_is_checked():
    """The default form is SwiGLU on a ``gate | up`` weight, as before the
    form was a choice: the layer Xing builds equals its own reference."""
    from chipbench.reference import xing4 as xing_reference

    mx.random.seed(11)
    layer = xing4.SparseExperts(32, 16, 8, 2, HELD, 1, 2.0, True, prefix="moe_")
    assert layer is not None and xing4.SparseExperts is decoder.SparseExperts
    layer.initialize(mx.init.Normal(0.3))
    assert sorted(n.split("moe_")[1] for n in layer.collect_params()) == [
        "experts_down_weight", "experts_gate_up_weight", "router_weight", "select_bias",
        "shared_down_weight", "shared_gate_up_weight"]
    x = np.random.RandomState(3).randn(2, 24, 32).astype(np.float32)
    cfg = {"num_experts_per_tok": 2, "routed_scaling_factor": 2.0, "norm_topk_prob": True}
    p = {k: jnp.asarray(v) for k, v in named(layer).items()}
    want = np.asarray(xing_reference.experts(p, "moe_", jnp.asarray(x), cfg, HELD))
    np.testing.assert_allclose(layer(mx.nd.array(x))[0].asnumpy(), want, rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="expert_form"):
        decoder.SparseExperts(32, 16, 8, 2, expert_form="gelu")
    with pytest.raises(ValueError, match="expert_form"):
        moe_ops.moe_ffn_dropless(jnp.zeros((4, 8)), jnp.zeros((2, 8)), jnp.zeros((2,)),
                                 jnp.zeros((2, 8, 4)), jnp.zeros((2, 4, 8)), num_experts=2,
                                 expert_form="gelu")


def test_the_shares_of_the_expert_layer_add_up_to_the_whole_layer():
    """The guide's share test: 4 chips hold 2 of 8 experts each; their routed
    parts, with the shared expert (which every chip computes alike) counted
    once, are the uncut reference's layer."""
    c = tiny_config()
    whole = _expert_layer(c, (0, 8))
    p = {k: jnp.asarray(v) for k, v in named(whole).items()}
    x = np.random.RandomState(2).randn(2, 24, c["hidden_size"]).astype(np.float32)
    want = np.asarray(reference.experts(p, "moe_", jnp.asarray(x), c, (0, 8)))
    shared = np.asarray(reference.relu2(jnp.asarray(x), p["moe_shared_up_weight"],
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
    # squares of sums over 64 inputs reach 70; four shares less three shared
    # experts cancel to the whole in float32's rounding of that scale
    scale = np.abs(want).max()
    np.testing.assert_allclose(total / scale, want / scale, atol=5e-6)


# ---------------------------------------------------------------------------
# the whole model
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def system():
    """The tiny model in float32 (every kind of layer), its jittable forward
    and a batch whose length no chunk divides."""
    c = tiny_config()
    net = build(c)
    fn, params = net.export_jittable()
    names = sorted(p.name for p in net.collect_params().values())
    tok, labels = batch(c)
    return {"c": c, "net": net, "fn": fn, "params": list(params), "names": names,
            "tok": tok, "labels": labels}


def test_the_model_is_built_from_the_pattern(system):
    net, c = system["net"], system["c"]
    kinds = [b._sublayer for b in net.model.blocks]
    assert kinds == ["mamba", "ffn", "mamba", "attn", "ffn"]          # MEM*E
    assert [b._sparse for b in net.model.blocks] == [False, True, False, False, True]
    with pytest.raises(ValueError, match="hybrid_override_pattern has 5 layers"):
        nemotron_h.NemotronHModel(dict(c, num_hidden_layers=4))
    with pytest.raises(ValueError, match="layer kind"):
        nemotron_h.NemotronHModel(dict(c, hybrid_override_pattern="MEM-E"))
    with pytest.raises(ValueError, match="n_group 1"):
        nemotron_h.NemotronHModel(dict(c, n_group=2))


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
    assert len(trained) == len(s["names"]) - 2          # the two selection biases
    for name, g in zip(s["names"], got):
        if name not in trained:
            continue
        w, g = np.asarray(want[name]), np.asarray(g)
        scale = max(np.abs(w).max(), 1e-8)
        assert np.abs(w).max() > 0, f"{name}: the reference's gradient is zero"
        # float32 both sides; the chunked scan and the recurrence sum in
        # different orders through five layers
        np.testing.assert_allclose(g / scale, w / scale, atol=5e-4, err_msg=name)


def test_remat_changes_no_number(system):
    s = system
    grads = []
    for remat in (False, True):
        fn, params = build(s["c"], remat=remat).export_jittable()
        loss = lambda ps: streaming_softmax_ce(fn(ps, s["tok"]), jnp.asarray(s["labels"])).mean()
        grads.append(jax.jit(jax.grad(loss))(list(params)))
    for name, a, b in zip(s["names"], *grads):
        scale = max(float(jnp.abs(b).max()), 1e-8)
        np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(b) / scale, atol=1e-5,
                                   err_msg=name)


def test_logits_under_bf16_amp_stay_near_the_reference():
    """bf16 AMP as the benchmark sets it up (bf16 parameters; float32 decays,
    states, router, softmax and norms), compared as the benchmark compares:
    per token, because a near-tie in a router score sends a token to another
    expert under any rounding.  8 mantissa bits are 0.4 % a rounding and a
    logit sums a few hundred of them through five layers: the median token
    within 2 % of the logits' std, and fewer than a tenth of the tokens over
    10 %.  A dropped expert, a bf16 decay or a bf16 softmax moves the median
    past that."""
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
    c = tiny_config()
    net = build(c, sigma=0.1)
    before = {n: p.data().asnumpy() for n, p in net.collect_params().items()}
    net.rescale_prenorm_residual(52)
    scaled = 0
    for n, p in net.collect_params().items():
        writes = n.endswith(("out_proj_weight", "o_weight", "down_weight"))
        ratio = 52 ** -0.5 if writes else 1.0
        np.testing.assert_allclose(p.data().asnumpy(), before[n] * ratio, rtol=1e-6, err_msg=n)
        scaled += writes
    assert scaled == 2 + 1 + 2 * 2        # mixers, attention, routed and shared experts


def test_spmd_trainer_step_lowers_the_loss_and_compiles_once():
    """A few ``SPMDTrainer`` steps under bf16 AMP with remat: the loss finite
    and falling, the bias rule moving, no token dropped, one program a step;
    the scopes in the compiled step's text and the counters counted."""
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
        trainer.sync_to_block()
    finally:
        amp.disable()          # clears the jit caches with it
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    assert programs == 1
    assert after["recompile_steady_state"] == before["recompile_steady_state"]
    assert after["moe_tokens_dropped"] == before["moe_tokens_dropped"]
    assert after["moe_step"] - before["moe_step"] == 5
    rows = after["moe_rows_routed_here"] - before["moe_rows_routed_here"]
    assert 0 < rows <= 5 * 2 * 64 * c["num_experts_per_tok"]     # 2 expert layers
    # counted at trace time, a call site: 2 mixers and 1 attention layer,
    # each traced for the forward and again inside its checkpoint's backward
    assert before["ssm_scan_traced"] - counted["ssm_scan_traced"] >= 2
    assert before["attention_dispatch_grouped"] - counted["attention_dispatch_grouped"] >= 1
    assert after["ssm_scan_traced"] == before["ssm_scan_traced"]  # no retrace in steady state
    for scope in ("nemotron.mamba/", "nemotron.mamba.in_proj", "nemotron.mamba.conv",
                  "nemotron.mamba.scan", "nemotron.mamba.gate_norm",
                  "nemotron.mamba.out_proj", "nemotron.attn/", "nemotron.attn.core",
                  "nemotron.moe.route", "nemotron.moe.experts", "nemotron.moe.shared",
                  "nemotron.head"):
        assert scope in text, scope
    assert "xing." not in text
    # the noaux_tc rule ran inside each step: six moves of 0.001 at most;
    # the mixers' float32 parameters stayed float32 through the update
    for block in net.model.blocks:
        if block._sparse:
            bias = block.ffn.select_bias.data().asnumpy()
            assert bias.dtype == np.float32 and 0 < np.abs(bias).max() <= 6 * 0.001 + 1e-7
        if block._sublayer == "mamba":
            assert block.mamba.A_log.data().asnumpy().dtype == np.float32

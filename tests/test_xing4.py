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
    assert kernels == (0 if path == "xla" else 3)  # forward, dq pass, dk/dv pass
    out = system(q, k, v)
    assert out.shape == (1, 256, 2, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain(q, k, v)), atol=2e-5)
    got = jax.grad(through(system), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(through(plain), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5)


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
    assert kernels() == 3  # forward, dq pass, dk/dv pass


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

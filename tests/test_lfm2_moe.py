"""LFM2-MoE's stack (gated short-convolution mixers, a grouped-query layer with
per-head q/k norms and rotary positions, a leading dense SwiGLU, sigmoid-routed
experts of which a chip holds its share, a tied head) against the plain float32
reference in ``chipbench/reference/lfm2.py``, at the configuration's
``dry_run`` sizes.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import amp, profiler
from incubator_mxnet_tpu.gluon.model_zoo import decoder, lfm2_moe, nemotron_h
from incubator_mxnet_tpu.ndarray.ndarray import NDArray
from incubator_mxnet_tpu.ops import attention as attn_ops
from incubator_mxnet_tpu.ops import ssm
from incubator_mxnet_tpu.ops.nn import streaming_softmax_ce
from incubator_mxnet_tpu.parallel import SPMDTrainer, make_mesh

from chipbench.reference import lfm2 as reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXPERTS = (2, 2)     # experts 2 and 3 of 8


def tiny_config(**over):
    c = json.load(open(os.path.join(ROOT, "chipbench", "configs", "lfm2-24b-a2b.json")))
    c.update(c["dry_run"])
    c["num_experts"] = c["published"]["num_experts"]   # the router's width: 8
    c.update(over)
    return c


def build(c, experts=EXPERTS, remat=False, seed=5, sigma=0.3):
    mx.random.seed(seed)
    net = lfm2_moe.Lfm2ForCausalLM(c, experts_held=experts, remat=remat)
    net.initialize(mx.init.Normal(sigma))
    return net


def named(net):
    return {p.name: p._data._data for p in net.collect_params().values()}


def batch(c, b=2, s=27, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, c["vocab_size"], (b, s + 1)).astype(np.int32)
    return ids[:, :-1], ids[:, 1:]


def _x(c, s=27, seed=2):
    return np.random.RandomState(seed).randn(2, s, c["hidden_size"]).astype(np.float32)


def loss_fn(out, label):
    return NDArray(streaming_softmax_ce(out._data, label._data).mean(axis=-1))


# ---------------------------------------------------------------------------
# the operator: ops/ssm.py::gated_short_conv
# ---------------------------------------------------------------------------


def _literal_conv1d(z, w):
    """``Conv1d(C, C, K, groups=C, padding=K−1, bias=False)(z)[..., :S]`` on
    ``z`` [B, S, C], ``w`` [C, K]: the depthwise convolution padded by K − 1
    zeros on BOTH sides, its first S outputs kept."""
    s, taps = z.shape[1], w.shape[1]
    out = jax.lax.conv_general_dilated(
        z, w.T[:, None, :], (1,), [(taps - 1, taps - 1)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=w.shape[0],
        precision=jax.lax.Precision.HIGHEST)
    return out[:, :s]


@pytest.mark.parametrize("taps", [3, 4, 1])
def test_gated_short_conv_is_the_literal_padded_conv1d_with_its_gradient(taps):
    """``C ⊙ conv(B ⊙ x̃)`` against the literal form, float32, and the
    gradients of both operands; both sum three products a channel, so they
    agree to float32 rounding (1e-6 of the largest magnitude)."""
    rng = np.random.RandomState(taps)
    bcx = jnp.asarray(rng.randn(2, 11, 3 * 6), jnp.float32)
    w = jnp.asarray(rng.randn(6, taps), jnp.float32)
    weights = jnp.asarray(rng.randn(2, 11, 6), jnp.float32)

    def literal(bcx, w):
        b, c, x = jnp.split(bcx, 3, axis=-1)
        return c * _literal_conv1d(b * x, w)

    got, want = ssm.gated_short_conv(bcx, w), literal(bcx, w)
    assert got.shape == (2, 11, 6) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)
    grads = [jax.grad(lambda *a: jnp.sum(f(*a) * weights), argnums=(0, 1))(bcx, w)
             for f in (ssm.gated_short_conv, literal)]
    for a, b in zip(*grads):
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-6 * scale


def test_gated_short_conv_counts_its_call_sites_keeps_the_dtype_and_is_causal():
    rng = np.random.RandomState(0)
    bcx = jnp.asarray(rng.randn(1, 9, 12), jnp.bfloat16)
    w = jnp.asarray(rng.randn(4, 3), jnp.bfloat16)
    before = profiler.counters()["short_conv_traced"]
    out = ssm.gated_short_conv(bcx, w)
    assert out.dtype == jnp.bfloat16
    assert profiler.counters()["short_conv_traced"] == before + 1
    # a change at position 5 moves positions 5..7 (the three taps) and no earlier one
    moved = ssm.gated_short_conv(bcx.at[:, 5].add(1.0), w)
    diff = np.asarray(jnp.abs(moved.astype(jnp.float32) - out.astype(jnp.float32)).max(-1))[0]
    assert np.all(diff[:5] == 0) and np.all(diff[5:8] > 0) and np.all(diff[8:] == 0)


def test_short_conv_mixer_matches_the_references_layer():
    c = tiny_config()
    mx.random.seed(3)
    layer = lfm2_moe.ShortConvMixer(c["hidden_size"], c["conv_L_cache"], prefix="conv_")
    layer.initialize(mx.init.Normal(0.3))
    x = _x(c)
    p = {k: jnp.asarray(v) for k, v in named(layer).items()}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(reference.short_conv(p, "conv_", jnp.asarray(x)))
    np.testing.assert_allclose(layer(mx.nd.array(x)).asnumpy(), want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# decoder.GroupedQueryAttention's q/k norms and rotary; its defaults unchanged
# ---------------------------------------------------------------------------


def _gqa_layer(c, seed=8, **kw):
    mx.random.seed(seed)
    heads = c["num_attention_heads"]
    layer = decoder.GroupedQueryAttention(
        c["hidden_size"], heads, c["num_key_value_heads"], c["hidden_size"] // heads,
        scope="lfm2.attn", prefix="attn_", **kw)
    layer.initialize(mx.init.Normal(0.3))
    return layer


def test_rotary_embedding_is_the_rotate_half_form():
    x = jnp.asarray(np.random.RandomState(1).randn(2, 7, 3, 8), jnp.float32)
    got = attn_ops.rotary_embedding(x, theta=1e6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(reference.rotary(x, 1e6)),
                               rtol=1e-5, atol=1e-6)
    # a rotation: each pair's norm kept, position 0 untouched
    pairs = lambda a: np.hypot(*np.split(np.asarray(a), 2, axis=-1))
    np.testing.assert_allclose(pairs(got), pairs(x), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[:, 0]), np.asarray(x[:, 0]))


def test_normed_rotary_grouped_query_layer_matches_the_references_layer():
    c = tiny_config()
    layer = _gqa_layer(c, qk_norm=c["norm_eps"], rope_theta=1e6)
    assert layer.q_norm_gamma.shape == layer.k_norm_gamma.shape == (16,)
    mx.random.seed(9)      # gains away from 1, so that a gain on the wrong side shows
    for gamma in (layer.q_norm_gamma, layer.k_norm_gamma):
        gamma.set_data(mx.nd.random.uniform(0.5, 1.5, shape=(16,)))
    x = _x(c, s=40)
    p = {k: jnp.asarray(v) for k, v in named(layer).items()}
    with jax.default_matmul_precision("highest"):
        for block in (8, 40):        # the reference's query blocks change nothing
            want = np.asarray(reference.attention(p, "attn_", jnp.asarray(x), c, block))
            np.testing.assert_allclose(layer(mx.nd.array(x)).asnumpy(), want, rtol=1e-4,
                                       atol=1e-5)


@pytest.mark.parametrize("gate", [False, True], ids=["nemotron", "solar"])
def test_with_the_options_off_the_layer_is_the_one_nemotron_and_solar_train(gate):
    """Neither parameter nor operation of the new options: the parameter names
    and shapes are those of the layer the two cells build, and its traced
    program is the one of a layer that never had the options."""
    c = tiny_config()
    plain = _gqa_layer(c, gate=gate)
    off = _gqa_layer(c, gate=gate, qk_norm=None, rope_theta=None)
    shapes = lambda l: {n.split("attn_", 1)[1]: tuple(v.shape) for n, v in named(l).items()}
    want = {"qkv_weight": (128, 64), "o_weight": (64, 64)}
    if gate:
        want["gate_weight"] = (64, 64)
    assert shapes(plain) == shapes(off) == want
    x = jnp.asarray(_x(c, s=16))
    fn_plain, params = plain.export_jittable()
    fn_off, _ = off.export_jittable()
    assert str(jax.make_jaxpr(fn_plain)(params, x)) == str(jax.make_jaxpr(fn_off)(params, x))
    np.testing.assert_array_equal(np.asarray(fn_plain(params, x)), np.asarray(fn_off(params, x)))
    assert "rotary" not in str(jax.make_jaxpr(fn_off)(params, x))
    # nemotron's own layer is this class, with no option of its own set
    assert nemotron_h.GroupedQueryAttention is decoder.GroupedQueryAttention


# ---------------------------------------------------------------------------
# the whole model against the reference
# ---------------------------------------------------------------------------


def test_the_layer_kinds_follow_layer_types_and_num_dense_layers():
    c = tiny_config()
    net = build(c)
    blocks = net.model.blocks
    assert [b._mixer for b in blocks] == ["conv", "attn", "conv", "conv", "conv"]
    assert [b._sparse for b in blocks] == [False, True, True, True, True]
    assert isinstance(blocks[0].ffn, decoder.SwiGLU)
    assert all(b.ffn.shared_expert is None for b in blocks[1:])
    c2 = tiny_config(num_dense_layers=2, layer_types=["conv", "conv", "full_attention"],
                     num_hidden_layers=3)
    assert [b._sparse for b in build(c2).model.blocks] == [False, False, True]
    for over in ({"conv_bias": True}, {"use_expert_bias": False}, {"num_hidden_layers": 4}):
        with pytest.raises(ValueError):
            lfm2_moe.Lfm2Model(tiny_config(**over))


def test_the_head_is_the_embedding():
    """Tied: no weight of the head's own; the logits are the final hidden
    state against the embedding's rows; an untied CausalLM keeps its head."""
    c = tiny_config()
    net = build(c)
    names = sorted(named(net))
    assert not any("lm_head" in n for n in names) and net.lm_head is None
    tok, _ = batch(c)
    hidden = net.model(mx.nd.array(tok, dtype="int32")).asnumpy()
    embed = net.model.embed.weight.data().asnumpy()
    np.testing.assert_allclose(net(mx.nd.array(tok, dtype="int32")).asnumpy(),
                               hidden @ embed.T, rtol=1e-5, atol=1e-5)
    untied = decoder.CausalLM(lambda prefix: lfm2_moe.Lfm2Model(c, EXPERTS, prefix=prefix),
                              c["vocab_size"], c["hidden_size"], "lfm2.head")
    assert untied.lm_head is not None


def test_the_model_matches_the_reference_logits_loss_and_gradients():
    c = tiny_config()
    net = build(c)
    tok, labels = batch(c)
    want = np.asarray(net(mx.nd.array(tok, dtype="int32"))._data)
    p = named(net)
    got, rows = reference.forward(p, tok, config=c, experts_held=EXPERTS, query_block=9,
                                  with_rows=True)
    scale = np.abs(want).max()
    np.testing.assert_allclose(np.asarray(got) / scale, want / scale, atol=1e-4)
    assert 0 < int(rows) <= 4 * tok.size * c["num_experts_per_tok"]

    fn, params = net.export_jittable()
    names = sorted(p)

    def ours(params):
        return reference.loss_per_token(fn(params, jnp.asarray(tok)), labels).mean()

    def theirs(params):
        return reference.loss(dict(zip(names, params)), tok, labels, config=c,
                              experts_held=EXPERTS, query_block=9)

    with jax.default_matmul_precision("highest"):
        l_ours, g_ours = jax.value_and_grad(ours)(params)
        l_theirs, g_theirs = jax.value_and_grad(theirs)(params)
    assert abs(float(l_ours) - float(l_theirs)) < 1e-5
    # five layers of float32 sums in another order: 1e-4 of a parameter's
    # largest gradient leaves room for the order and none for a wrong term
    assert len(names) == len(g_ours) == 46
    for name, a, b in zip(names, g_ours, g_theirs):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * scale, name


# Under bf16 AMP (bf16 weights and matmuls; norms, router, gates, the
# convolution's sums, rotary and soft-maxes in float32) against the float32
# reference on the same bf16 weights: the median over tokens of a token's
# rms logit difference, as a share of the logits' std.  Read on the four
# seeds below: the system 0.0068-0.0075, the reference computed wholly in
# bf16 0.0085-0.0094; 0.008 lies between (a router's near tie flips a pair
# under either rounding, so the median and not the worst token).
AMP_MEDIAN_TOKEN_TOLERANCE = 0.008


def _median_token(got, want):
    per = jnp.sqrt(jnp.mean((got - want) ** 2, -1)) / jnp.std(want)
    return float(jnp.median(per))


@pytest.mark.parametrize("seed", range(4))
def test_bf16_amp_against_the_reference_within_a_limit_a_bf16_reference_exceeds(seed):
    c = tiny_config()
    mx.random.seed(seed)
    net = lfm2_moe.Lfm2ForCausalLM(c, experts_held=EXPERTS)
    net.initialize(mx.init.Normal(0.05))
    tok = np.random.RandomState(seed).randint(0, c["vocab_size"], (2, 32)).astype(np.int32)
    amp.init("bfloat16")
    try:
        net.cast("bfloat16")
        fn, params = net.export_jittable()
        got = jax.jit(fn)(params, jnp.asarray(tok)).astype(jnp.float32)
    finally:
        amp.disable()
    p = named(net)
    want = reference.forward(p, tok, config=c, experts_held=EXPERTS, query_block=8)
    bf16 = reference.forward(p, tok, config=c, experts_held=EXPERTS, query_block=8,
                             dtype=jnp.bfloat16)
    assert _median_token(got, want) <= AMP_MEDIAN_TOKEN_TOLERANCE
    assert _median_token(bf16, want) > AMP_MEDIAN_TOKEN_TOLERANCE


def test_the_lint_finds_the_counter_documented_and_the_scopes_apart():
    """``tools/lint_counters.py``: ``short_conv_traced`` declared and in the
    doc's table; the ``lfm2.*`` scopes inside no other scope and named in
    docs/observability.md."""
    import subprocess
    import sys

    done = subprocess.run([sys.executable, os.path.join(ROOT, "tools", "lint_counters.py")],
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr
    assert "model scopes apart and named" in done.stdout
    assert "`short_conv_traced`" in open(os.path.join(ROOT, "docs", "observability.md")).read()


def test_residual_writers_are_scaled_by_twice_the_published_depth():
    c = tiny_config()
    net = build(c, sigma=0.1)
    before = {n: p.data().asnumpy() for n, p in net.collect_params().items()}
    net.rescale_residual_writers(40)
    scaled = 0
    for n, p in net.collect_params().items():
        writes = n.endswith(("out_proj_weight", "o_weight", "down_weight"))
        np.testing.assert_allclose(p.data().asnumpy(), before[n] * (80 ** -0.5 if writes else 1.0),
                                   rtol=1e-6, err_msg=n)
        scaled += writes
    assert scaled == 5 + 5       # five mixers; the dense W_down and four experts' W_down


# ---------------------------------------------------------------------------
# the share test: the chips' shares of an expert layer add up to the uncut layer
# ---------------------------------------------------------------------------


def test_eight_expert_shares_add_up_to_the_uncut_references_layer():
    """8-way expert parallelism at the router's width 8: each share holds one
    expert; the routed parts the eight compute add up to what the uncut
    reference gives for the whole layer (there is no shared expert), and each
    share is the reference's share."""
    c = tiny_config()
    x = jnp.asarray(_x(c, s=24))

    def layer(held, seed=11):
        mx.random.seed(seed)
        block = decoder.SparseExperts(
            c["hidden_size"], c["moe_intermediate_size"], 8, c["num_experts_per_tok"], held,
            0, c["routed_scaling_factor"], c["norm_topk_prob"], scope="lfm2.moe", prefix="moe_")
        block.initialize(mx.init.Normal(0.3))
        return block

    whole = layer((0, 8))
    full = named(whole)
    full["moe_select_bias"] = jnp.asarray(np.random.RandomState(3).randn(8) * 0.1, jnp.float32)
    p = {n.split("moe_", 1)[1]: v for n, v in full.items()}
    with jax.default_matmul_precision("highest"):
        want, _ = reference.experts({"moe_" + k: v for k, v in p.items()}, "moe_", x, c, (0, 8),
                                     shared=False)
    total = 0
    for first in range(8):
        share = layer((first, 1))
        for n, prm in share.collect_params().items():
            cut = full[n][first:first + 1] if n.endswith(
                ("experts_gate_up_weight", "experts_down_weight")) else full[n]
            prm.set_data(mx.nd.array(np.asarray(cut)))
        y, _ = share(mx.nd.array(np.asarray(x)))
        with jax.default_matmul_precision("highest"):
            mine, _ = reference.experts(named(share), "moe_", x, c, (first, 1), shared=False)
        np.testing.assert_allclose(y.asnumpy(), np.asarray(mine), rtol=1e-4, atol=1e-5)
        total = total + y.asnumpy()
    np.testing.assert_allclose(total, np.asarray(want), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# through the trainer
# ---------------------------------------------------------------------------


def test_spmd_trainer_steps_lower_the_loss_and_compile_once():
    """A few ``SPMDTrainer`` steps under bf16 AMP with remat: the loss finite
    and falling, one program a step, the four convolutions counted at trace
    time, the scopes in the compiled step's text."""
    c = tiny_config()
    amp.init("bfloat16")
    try:
        net = build(c, remat=True, sigma=0.05)
        net.cast("bfloat16")
        tok, labels = batch(c, b=2, s=32)
        trainer = SPMDTrainer(net, loss_fn, "adam", {"learning_rate": 3e-3, "multi_precision": True},
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
    finally:
        amp.disable()          # clears the jit caches with it
    assert losses[-1] < losses[0] and all(np.isfinite(losses))
    assert programs == 1
    assert after["recompile_steady_state"] == before["recompile_steady_state"]
    assert after["moe_step"] - before["moe_step"] == 5
    assert after["moe_tokens_dropped"] == before["moe_tokens_dropped"]
    # four conv layers, each traced once into the step (its checkpoint's
    # backward replays the traced jaxpr); none in steady state
    assert before["short_conv_traced"] - counted["short_conv_traced"] == 4
    assert after["short_conv_traced"] == before["short_conv_traced"]
    for scope in ("lfm2.conv/", "lfm2.conv.proj", "lfm2.conv.core", "lfm2.conv.out",
                  "lfm2.attn/", "lfm2.attn.core", "lfm2.ffn", "lfm2.moe.route",
                  "lfm2.moe.experts", "lfm2.head"):
        assert scope in text, scope
    assert "lfm2.moe.shared" not in text and "nemotron." not in text


def test_one_trainer_step_holds_the_references_gradient_and_adam_step():
    """Float32 end to end: one ``SPMDTrainer`` step from zero moments leaves
    ``(1 − β1) · g`` in Adam's first moment and the reference's Adam change in
    every parameter, ``g`` the reference's ``jax.grad`` (with ``remat``, which
    changes no number): 1e-4 of a leaf's largest gradient, 5 % of the change's
    norm (Adam's first step is nearly ``lr · sign(g)``, so a gradient near
    zero counts whole); a wrong term reads of the order of 1."""
    from chipbench.reference.solar_open2 import adam_first_step

    c = tiny_config()
    net = build(c, remat=True, sigma=0.05)
    tok, labels = batch(c, b=2, s=32)
    before = {n: np.asarray(a) for n, a in named(net).items()}
    trainer = SPMDTrainer(net, loss_fn, "adam", {"learning_rate": 1e-3},
                          mesh=make_mesh(devices=jax.devices()[:1]))
    loss = float(np.asarray(trainer.step((trainer.shard_batch(tok)[0],),
                                         trainer.shard_batch(labels)[0])._data))
    with jax.default_matmul_precision("highest"):
        want, grads = jax.value_and_grad(reference.loss)(
            {n: jnp.asarray(a) for n, a in before.items()}, tok, labels, config=c,
            experts_held=EXPERTS, query_block=8, remat=True)
    assert abs(loss - float(want)) < 1e-5
    kept = np.float32(1) - np.float32(trainer.optimizer.beta1)
    for slot, j in enumerate(trainer._trainable_idx):
        name = trainer._params[j].name
        mean, _ = trainer._opt_states[slot]
        g, ref = np.asarray(mean) / kept, np.asarray(grads[name])
        scale = float(np.abs(ref).max()) or 1.0
        assert np.abs(g - ref).max() <= 1e-4 * scale, name
        change = np.asarray(trainer._param_arrays[j]) - before[name]
        ref_change = np.asarray(adam_first_step(before[name], ref, learning_rate=1e-3)) - before[name]
        assert np.linalg.norm(change - ref_change) <= 0.05 * np.linalg.norm(ref_change), name

"""Quantized collectives — the gradient-compression subsystem (ISSUE 14).

Covers the acceptance surface: exact bf16/int8 encode-decode round-trip
contracts, error-feedback residual carry, the per-parameter-group
opt-out (mixed buckets stay exact for opted-out groups), bucket keys
namespaced by codec id with the dist store's loud wire-agreement check,
the async-PS ``push_enc`` envelope (server accumulates decoded fp32),
SPMDTrainer's in-program quantized dp-allreduce (parity with the fp32
build, convergence of int8 + error feedback to fp32 final loss, zero
steady-state recompiles under ``MXNET_COMPILE_GUARD=raise``, residual
persistence through ``save_states``/``load_states``), the comms byte counters + ``comm``
metrics provider, and a CI smoke of ``benchmark/opperf/collectives.py``.

ISSUE 19 adds the quantized ring collectives: the int4 packed codec
(wire bytes + host-path rejection), the explicit-hop ring allreduce
(numerics, the aggregate error-feedback invariant, D=1 bit-exactness
with the psum sandwich, zero steady-state recompiles), the fsdp-sharded
quantized reduce-scatter/all-gather build (convergence parity), and the
async-PS encoded pull leg (versioned envelope, loud codec-id/version
mismatch).
"""
import os
import socket

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import comm, gluon, profiler
from incubator_mxnet_tpu import kvstore as kv_mod
from incubator_mxnet_tpu.comm import compression as comp_mod
from incubator_mxnet_tpu.gluon import Parameter, nn
from incubator_mxnet_tpu.parallel import SPMDTrainer, make_mesh

nd = mx.nd


@pytest.fixture(autouse=True)
def fresh_counters():
    profiler.reset_counters()
    yield
    profiler.reset_counters()


def _c():
    return profiler.counters()


# ---------------------------------------------------------------------------
# codec round-trips
# ---------------------------------------------------------------------------


def test_bf16_roundtrip_matches_astype():
    import jax.numpy as jnp

    x = jnp.asarray(np.random.RandomState(0).randn(301).astype(np.float32))
    codec = comm.Bf16Codec()
    payload, resid = codec.encode(x)
    dec = codec.decode(payload, 301)
    ref = np.asarray(x).astype(jnp.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(dec), ref)
    # residual is exactly the truncation error
    np.testing.assert_allclose(np.asarray(resid),
                               np.asarray(x) - ref, rtol=0, atol=0)


def test_int8_roundtrip_error_bounded_and_grid_exact():
    import jax.numpy as jnp

    rs = np.random.RandomState(1)
    x = rs.randn(1000).astype(np.float32) * 3.0
    codec = comm.Int8BlockCodec(block=128)
    payload, resid = codec.encode(jnp.asarray(x))
    dec = np.asarray(codec.decode(payload, 1000))
    scales = np.asarray(payload["scales"])
    # per-block error bound: half a quantization step
    bound = np.repeat(np.where(scales > 0, scales, 1.0), 128)[:1000]
    assert (np.abs(dec - x) <= bound / 2 + 1e-7).all()
    # residual == what the codec dropped (small fp reassociation slack:
    # the residual is computed inside the fused encode program)
    np.testing.assert_allclose(np.asarray(resid), x - dec,
                               rtol=1e-4, atol=1e-5)
    # values already on the quantization grid decode EXACTLY: pin the
    # block scale with a +/-127*s entry, put everything else on k*s
    s = 0.03125  # power of two: k*s is exact in fp32
    on_grid = (rs.randint(-127, 128, 256) * s).astype(np.float32)
    on_grid[0] = 127 * s
    big = comm.Int8BlockCodec(block=256)
    payload2, resid2 = big.encode(jnp.asarray(on_grid))
    np.testing.assert_array_equal(np.asarray(big.decode(payload2, 256)),
                                  on_grid)
    np.testing.assert_array_equal(np.asarray(resid2), np.zeros(256))


def test_int8_zero_block_safe():
    import jax.numpy as jnp

    codec = comm.Int8BlockCodec(block=4)
    x = jnp.zeros((8,), jnp.float32)
    payload, resid = codec.encode(x)
    np.testing.assert_array_equal(np.asarray(codec.decode(payload, 8)),
                                  np.zeros(8))
    np.testing.assert_array_equal(np.asarray(resid), np.zeros(8))


def test_codec_ids_roundtrip():
    assert comm.codec_from_id("bf16").id == "bf16"
    assert comm.codec_from_id("int8b512").block == 512
    assert comm.Int8BlockCodec(64).id == "int8b64"
    with pytest.raises(ValueError):
        comm.codec_from_id("int7")


def test_decode_np_matches_device_decode():
    import jax.numpy as jnp

    x = np.random.RandomState(2).randn(130).astype(np.float32)
    codec = comm.Int8BlockCodec(block=32)
    payload, _ = codec.encode(jnp.asarray(x))
    np_payload = {k: np.asarray(v) for k, v in payload.items()}
    np.testing.assert_allclose(
        comm.decode_np(codec.id, np_payload, 130),
        np.asarray(codec.decode(payload, 130)), atol=1e-6)
    bf = comm.Bf16Codec()
    payload, _ = bf.encode(jnp.asarray(x))
    np.testing.assert_array_equal(
        comm.decode_np("bf16", {"enc": np.asarray(payload["enc"])}, 130),
        np.asarray(bf.decode(payload, 130)))


# ---------------------------------------------------------------------------
# error feedback
# ---------------------------------------------------------------------------


def test_error_feedback_residual_carry():
    """k compensated pushes of the same gradient sum to ~k*g: the running
    error stays bounded by ONE quantization step instead of growing."""
    import jax.numpy as jnp

    g = np.random.RandomState(3).randn(256).astype(np.float32)
    codec = comm.Int8BlockCodec(block=64)
    fb = comm.ErrorFeedback()
    total = np.zeros_like(g)
    for _ in range(5):
        flat = fb.compensate("k", jnp.asarray(g))
        payload, resid = codec.encode(flat)
        fb.update("k", resid)
        total += np.asarray(codec.decode(payload, 256))
    scales = np.asarray(codec.local_scales(jnp.asarray(g)))
    bound = np.repeat(np.where(scales > 0, scales, 1.0), 64)[:256]
    assert (np.abs(total - 5 * g) <= bound + 1e-6).all()


def test_error_feedback_retain_and_shape_guard():
    import jax.numpy as jnp

    fb = comm.ErrorFeedback()
    fb.update("__grad_bucket__:0:int8b256:float32:0", jnp.zeros(4))
    fb.update("__grad_bucket__:1:int8b256:float32:0", jnp.zeros(4))
    fb.update("__grad_bucket__:0:bf16:float32:0", jnp.zeros(4))
    fb.retain("__grad_bucket__:1:int8b256:")
    assert list(fb.state_dict()) == ["__grad_bucket__:1:int8b256:float32:0"]
    # layout change under a reused key: residual dropped, not misapplied
    assert fb.get("__grad_bucket__:1:int8b256:float32:0",
                  jnp.zeros(8)) is None
    assert len(fb) == 0


def test_error_feedback_state_dict_roundtrip():
    import jax.numpy as jnp

    fb = comm.ErrorFeedback()
    fb.update("a", jnp.asarray(np.arange(4, dtype=np.float32)))
    fb2 = comm.ErrorFeedback()
    fb2.load_state_dict(fb.state_dict())
    out = fb2.compensate("a", jnp.zeros(4))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.arange(4, dtype=np.float32))


# ---------------------------------------------------------------------------
# policy / opt-out resolution
# ---------------------------------------------------------------------------


def test_policy_env_resolution(monkeypatch):
    monkeypatch.delenv("MXNET_GRAD_COMPRESS", raising=False)
    assert comm.resolve_policy() is None
    monkeypatch.setenv("MXNET_GRAD_COMPRESS", "off")
    assert comm.resolve_policy() is None
    monkeypatch.setenv("MXNET_GRAD_COMPRESS", "bf16")
    pol = comm.resolve_policy()
    assert pol.id == "bf16" and pol.error_feedback is False
    monkeypatch.setenv("MXNET_GRAD_COMPRESS", "int8")
    monkeypatch.setenv("MXNET_GRAD_COMPRESS_BLOCK", "128")
    pol = comm.resolve_policy()
    assert pol.id == "int8b128" and pol.error_feedback is True
    monkeypatch.setenv("MXNET_GRAD_COMPRESS_EF", "0")
    assert comm.resolve_policy().error_feedback is False
    monkeypatch.setenv("MXNET_GRAD_COMPRESS", "int4")
    monkeypatch.delenv("MXNET_GRAD_COMPRESS_EF", raising=False)
    pol = comm.resolve_policy()
    assert pol.id == "int4b128" and pol.error_feedback is True
    assert isinstance(pol.codec, comm.Int4PackedCodec)
    # the exchange algorithm rides its own knob (default psum)
    assert pol.algo == "psum"
    monkeypatch.setenv("MXNET_GRAD_COMPRESS_ALGO", "ring")
    assert comm.resolve_policy().algo == "ring"
    monkeypatch.setenv("MXNET_GRAD_COMPRESS_ALGO", "butterfly")
    with pytest.raises(ValueError, match="butterfly"):
        comm.resolve_policy()
    monkeypatch.delenv("MXNET_GRAD_COMPRESS_ALGO", raising=False)
    with pytest.raises(ValueError, match="tree"):
        comm.CompressionPolicy(comm.Int8BlockCodec(), algo="tree")


def test_quantization_sensitive_groups(monkeypatch):
    from incubator_mxnet_tpu.optimizer.fused import quantization_sensitive

    for name in ("bn0_gamma", "bn0_beta", "dense1_bias", "ln_norm_weight",
                 "tok_embedding_weight", "batchnorm2_moving_mean"):
        assert quantization_sensitive(name)
    assert not quantization_sensitive("dense1_weight")
    pol = comm.CompressionPolicy(comm.Int8BlockCodec())
    assert pol.codec_for("dense1_weight") is not None
    assert pol.codec_for("dense1_bias") is None
    assert pol.codec_for(None) is not None   # no name info -> compress
    # env regex replaces the builtin classification
    monkeypatch.setenv("MXNET_GRAD_COMPRESS", "int8")
    monkeypatch.setenv("MXNET_GRAD_COMPRESS_SKIP", "dense1_")
    pol = comm.resolve_policy()
    assert pol.codec_for("dense1_weight") is None
    assert pol.codec_for("bn0_gamma") is not None


# ---------------------------------------------------------------------------
# bucketed pushpull wire
# ---------------------------------------------------------------------------


def _make_params(n, seed, shape=(16, 8)):
    rs = np.random.RandomState(seed)
    params = []
    for k in range(n):
        p = Parameter(f"p{k}_weight", shape=shape, dtype="float32")
        p.initialize()
        p.set_data(nd.array(rs.randn(*shape).astype(np.float32)))
        params.append(p)
    return params


def test_bucketed_pushpull_mixed_groups_exact_optout():
    params = _make_params(4, 0)
    pb = Parameter("p_bias", shape=(8,), dtype="float32")
    pb.initialize()
    pb.set_data(nd.array(np.random.RandomState(9).randn(8).astype(np.float32)))
    params.append(pb)
    kv = kv_mod.create("dist_sync")
    gvals = [np.random.RandomState(10 + i).randn(*p.shape).astype(np.float32)
             for i, p in enumerate(params)]
    for p, g in zip(params, gvals):
        p.grad()[:] = nd.array(g)
    pol = comm.CompressionPolicy(comm.Int8BlockCodec(block=64))
    fb = comm.ErrorFeedback()
    kv_mod.bucketed_pushpull(kv, [(i, p.grad()) for i, p in enumerate(params)],
                             names=[p.name for p in params],
                             compression=pol, feedback=fb)
    # opted-out group (bias) is BIT-exact; compressed groups are bounded
    np.testing.assert_array_equal(params[-1].grad().asnumpy(), gvals[-1])
    for p, g in zip(params[:-1], gvals[:-1]):
        assert np.abs(p.grad().asnumpy() - g).max() <= np.abs(g).max() / 100
    # two wire formats -> two buckets; bytes counted raw > wire
    assert _c()["allreduce_bucket"] == 2
    assert _c()["allreduce_bucket_params"] == 5
    assert _c()["comms_bytes_raw"] > _c()["comms_bytes_wire"] > 0
    # residual keyed by the full codec-namespaced bucket key (satellite:
    # codec id beside the membership epoch)
    (key,) = fb.state_dict().keys()
    assert key == "__grad_bucket__:0:int8b64:float32:0"


def test_bucketed_pushpull_codec_toggle_prunes_residuals():
    params = _make_params(2, 4)
    kv = kv_mod.create("dist_sync")
    fb = comm.ErrorFeedback()
    for codec in (comm.Int8BlockCodec(64), comm.Int8BlockCodec(32)):
        for p in params:
            p.grad()[:] = nd.array(np.ones(p.shape, np.float32))
        pol = comm.CompressionPolicy(codec)
        kv_mod.bucketed_pushpull(
            kv, [(i, p.grad()) for i, p in enumerate(params)],
            names=[p.name for p in params], compression=pol, feedback=fb)
    # only the CURRENT codec's residuals survive a toggle
    keys = list(fb.state_dict())
    assert keys and all(":int8b32:" in k for k in keys)


def test_bucketed_pushpull_fp32_counts_bytes_equal():
    params = _make_params(3, 5)
    kv = kv_mod.create("dist_sync")
    for p in params:
        p.grad()[:] = nd.array(np.ones(p.shape, np.float32))
    kv_mod.bucketed_pushpull(kv, [(i, p.grad()) for i, p in enumerate(params)])
    assert _c()["comms_bytes_raw"] == _c()["comms_bytes_wire"] > 0


def test_wire_agreement_check_raises_on_divergence(monkeypatch):
    kv = kv_mod.create("dist_sync")
    # single process: a no-op by contract
    kv.check_wire_agreement("__grad_bucket__:0:int8b256:float32:0")
    # simulate 2 processes whose key hashes disagree
    import jax

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(
        kv, "_allreduce",
        lambda arr, op="sum": np.asarray([int(arr[0]) + 7, int(arr[1])]))
    with pytest.raises(RuntimeError, match="wire-format mismatch"):
        kv.check_wire_agreement("__grad_bucket__:0:bf16:float32:0")
    # agreement passes — and is NOT cached: the check must re-run every
    # bucket so a peer that never changed its key still participates in
    # (and raises from) a toggling worker's mismatch
    calls = []

    def agree(arr, op="sum"):
        calls.append(op)
        return np.asarray(arr)

    monkeypatch.setattr(kv, "_allreduce", agree)
    kv.check_wire_agreement("__grad_bucket__:0:fp32:float32:1")
    kv.check_wire_agreement("__grad_bucket__:0:fp32:float32:1")
    assert len(calls) == 2


def test_trainer_dist_sync_env_policy_and_feedback_persistence(
        monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_GRAD_COMPRESS", "int8")
    params = _make_params(3, 6)
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                       kvstore="dist_sync")
    for p in params:
        p.grad()[:] = nd.array(np.random.RandomState(1).randn(*p.shape)
                               .astype(np.float32))
    tr.allreduce_grads()
    assert tr._grad_feedback is not None and len(tr._grad_feedback)
    f = str(tmp_path / "states")
    tr.save_states(f)
    tr2 = gluon.Trainer(_make_params(3, 6), "sgd", {"learning_rate": 0.1},
                        kvstore="dist_sync")
    tr2.load_states(f)
    assert (tr2._grad_feedback.state_dict().keys()
            == tr._grad_feedback.state_dict().keys())
    # a snapshot with NO residuals clears live ones on restore — keeping
    # them would compensate the restored step with another trajectory's
    # quantization error
    tr3 = gluon.Trainer(_make_params(3, 6), "sgd", {"learning_rate": 0.1},
                        kvstore="dist_sync")
    f2 = str(tmp_path / "fresh_states")
    tr3.save_states(f2)   # never stepped: no grad_feedback in the payload
    tr2.load_states(f2)
    assert len(tr2._grad_feedback) == 0


# ---------------------------------------------------------------------------
# per-key compressed pushpull (non-bucketed dist path)
# ---------------------------------------------------------------------------


def test_dist_per_key_codec_compression():
    kv = kv_mod.create("dist_sync")
    kv.set_gradient_compression({"type": "int8", "block": 8})
    g = nd.array(np.random.RandomState(2).randn(4, 8).astype(np.float32))
    out = nd.zeros((4, 8))
    kv.pushpull("w", g, out=out)
    ref = g.asnumpy()
    assert np.abs(out.asnumpy() - ref).max() <= np.abs(ref).max() / 60
    assert kv._last_wire_dtype == "int8"
    assert not kv.supports_grad_bucketing()  # per-key residual semantics


# ---------------------------------------------------------------------------
# async PS: codec envelope, server accumulates decoded fp32
# ---------------------------------------------------------------------------


@pytest.fixture()
def async_store(monkeypatch):
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    monkeypatch.setenv("MXNET_ASYNC_PS_PORT", str(port))
    monkeypatch.setenv("DMLC_WORKER_ID", "0")
    monkeypatch.setenv("DMLC_NUM_WORKER", "1")
    from incubator_mxnet_tpu.kvstore import async_ps

    monkeypatch.setattr(async_ps, "_SERVER", None)
    kv = mx.kv.create("dist_async")
    yield kv
    kv.close()   # stops the heartbeat thread — leaked, it trips the
    # thread-leak teardown of every later test in the run
    kv._server.stop()


def test_async_push_enc_int8_with_error_feedback(async_store):
    kv = async_store
    kv.set_gradient_compression({"type": "int8", "block": 4})
    kv.init("w", nd.zeros((6,)))
    g = np.array([0.7, -0.9, 0.2, 0.0, 3.0, -0.1], np.float32)
    for k in range(1, 4):
        kv.push("w", nd.array(g))
        out = nd.zeros((6,))
        kv.pull("w", out=out)
        # server accumulates DECODED fp32; with error feedback the
        # running sum stays within one quantization step of k*g — plus
        # one more step for the encoded pull leg (the server's fp32
        # master re-quantizes per read, never accumulated)
        scale = 3.0 / 127.0  # the largest block's grid
        assert np.abs(out.asnumpy() - k * g).max() <= 2 * scale + 1e-6
    assert kv._last_wire_dtype == "int8"
    assert _c()["comms_bytes_raw"] > _c()["comms_bytes_wire"] > 0


def test_async_push_enc_bf16(async_store):
    kv = async_store
    kv.set_gradient_compression({"type": "bf16"})
    kv.init("w", nd.zeros((4,)))
    x = np.array([1.0, 2.5, -3.25, 0.001], np.float32)
    kv.push("w", nd.array(x))
    out = nd.zeros((4,))
    kv.pull("w", out=out)
    import jax.numpy as jnp

    ref = x.astype(jnp.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(out.asnumpy(), ref)
    assert kv._last_wire_dtype == "bfloat16"


def test_async_int8_training_converges_to_fp32(async_store):
    """Async-PS convergence parity: server-side SGD driven by int8+EF
    pushes reaches the fp32 run's weights within quantization tolerance
    on a deterministic least-squares problem."""
    kv = async_store
    rs = np.random.RandomState(0)
    w_true = rs.randn(8).astype(np.float32)
    X = rs.randn(64, 8).astype(np.float32)
    y = X @ w_true

    def train(compressed):
        key = "w_c" if compressed else "w_f"
        if compressed:
            kv.set_gradient_compression({"type": "int8", "block": 8})
        else:
            kv._compression = None
        kv.init(key, nd.zeros((8,)))
        w = np.zeros(8, np.float32)
        for _ in range(60):
            grad = 2.0 / len(X) * X.T @ (X @ w - y)
            kv.push(key, nd.array(0.1 * grad))
            out = nd.zeros((8,))
            kv.pull(key, out=out)
            w = -out.asnumpy()  # accumulated (lr * grad) sum
        return w

    w_f = train(False)
    w_c = train(True)
    # both runs reach the same neighborhood of w_true
    assert np.abs(w_c - w_f).max() < 0.05
    assert np.linalg.norm(w_c - w_true) < 1.5 * np.linalg.norm(w_f - w_true) + 0.05


# ---------------------------------------------------------------------------
# SPMD quantized dp-allreduce
# ---------------------------------------------------------------------------


def _build_net(seed, features=16, hidden=32, classes=8):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(hidden, activation="relu"), nn.Dense(classes))
    net.initialize()
    net(nd.zeros((2, features)))
    return net


_LOSS = gluon.loss.SoftmaxCrossEntropyLoss()


def _spmd_pair(compression, seed=3, lr=0.1):
    ref = SPMDTrainer(_build_net(seed), _LOSS, "sgd", {"learning_rate": lr},
                      mesh=make_mesh())
    cmp_tr = SPMDTrainer(_build_net(seed), _LOSS, "sgd",
                         {"learning_rate": lr}, mesh=make_mesh(),
                         compression=compression)
    return ref, cmp_tr


def _batch(seed=0, batch=16, features=16, classes=8):
    rng = np.random.RandomState(seed)
    return (rng.randn(batch, features).astype(np.float32),
            rng.randint(0, classes, (batch,)).astype(np.float32))


@pytest.mark.parametrize("tier", ["bf16", "int8"])
def test_spmd_compressed_matches_fp32_losses(tier):
    ref, cmp_tr = _spmd_pair(tier)
    assert cmp_tr._comm_cfg is not None
    x, y = _batch()
    for _ in range(5):
        l0 = float(ref.step(nd.array(x), nd.array(y)).asnumpy())
        l1 = float(cmp_tr.step(nd.array(x), nd.array(y)).asnumpy())
        assert abs(l0 - l1) < 2e-3 * max(1.0, abs(l0))
    assert _c()["comms_bytes_raw"] > _c()["comms_bytes_wire"] > 0


# the largest error a step's UPDATE may carry, as a share of the largest
# update of that parameter: bf16 rounds each shard's gradient to 8 bits of
# mantissa; int8 rounds it to half a step of max/127, on each of 8 shards
_FIRST_STEP_TOL = {"bf16": 2.0 ** -7, "int8": 8 * 0.5 / 127}


@pytest.mark.parametrize("tier", ["bf16", "int8"])
def test_spmd_compressed_first_step_matches_fp32_parameters(tier):
    """One step of the compressed and the plain trainer move every
    parameter alike, to the codec's tolerance: a gradient summed twice
    over the shards shows here at once and not as a drifting loss."""
    ref, cmp_tr = _spmd_pair(tier)
    assert cmp_tr._comm_cfg is not None and cmp_tr._comm_cfg["shards"] == 8
    before = [np.asarray(a) for a in ref._param_arrays]
    x, y = _batch()
    ref.step(nd.array(x), nd.array(y))
    cmp_tr.step(nd.array(x), nd.array(y))
    exact = {cmp_tr._trainable_idx[s] for s in cmp_tr._comm_cfg["exact_slots"]}
    for j, (b, r, c) in enumerate(zip(before, ref._param_arrays,
                                      cmp_tr._param_arrays)):
        want, got = np.asarray(r) - b, np.asarray(c) - b
        assert np.abs(want).max() > 0
        tol = 1e-5 if j in exact else _FIRST_STEP_TOL[tier]
        np.testing.assert_allclose(got, want, rtol=0,
                                   atol=tol * np.abs(want).max(),
                                   err_msg=ref._params[j].name)


def test_spmd_int8_convergence_parity():
    """dist_sync-tier convergence: int8 + error feedback over the dp=8
    quantized psum reaches the fp32 final loss within tolerance."""
    ref, cmp_tr = _spmd_pair("int8", lr=0.2)
    x, y = _batch(1)
    l0 = None
    for _ in range(40):
        lf = float(ref.step(nd.array(x), nd.array(y)).asnumpy())
        lc = float(cmp_tr.step(nd.array(x), nd.array(y)).asnumpy())
        l0 = lf if l0 is None else l0
    assert lc < 0.5 * l0       # actually trained
    assert abs(lc - lf) < 0.05 * max(lf, 0.1) + 0.02


def test_spmd_optout_slots_resolved():
    _, cmp_tr = _spmd_pair("int8")
    cfg = cmp_tr._comm_cfg
    names = [cmp_tr._params[cmp_tr._trainable_idx[s]].name
             for s in cfg["exact_slots"]]
    assert names and all("bias" in n for n in names)
    names_c = [cmp_tr._params[cmp_tr._trainable_idx[s]].name
               for s in cfg["comp_slots"]]
    assert names_c and all("weight" in n for n in names_c)


def test_spmd_all_optout_falls_back_to_plain_build():
    pol = comm.CompressionPolicy(comm.Int8BlockCodec(),
                                 skip=lambda name: True)
    tr = SPMDTrainer(_build_net(3), _LOSS, "sgd", {"learning_rate": 0.1},
                     mesh=make_mesh(), compression=pol)
    assert tr._comm_cfg is None and tr._comm_state is None


def test_spmd_unsupported_builds_warn_and_fall_back():
    # tp > 1 is still outside the compressed build's supported shape
    with pytest.warns(UserWarning, match="running uncompressed"):
        tr = SPMDTrainer(_build_net(3), _LOSS, "sgd", {"learning_rate": 0.1},
                         mesh=make_mesh(dp=4, tp=2), compression="int8")
    assert tr._comm_cfg is None
    x, y = _batch()
    tr.step(nd.array(x), nd.array(y))  # the fallback build still trains


def test_spmd_fsdp_sharded_builds_compressed():
    """fsdp-sharded parameters now COMPRESS (quantized reduce-scatter of
    grads + quantized all-gather of updated shards) instead of falling
    back — the PR 14 refusal is lifted for axis-0 'fsdp' shards."""
    from incubator_mxnet_tpu.parallel import fsdp_rules

    tr = SPMDTrainer(_build_net(3), _LOSS, "sgd", {"learning_rate": 0.1},
                     mesh=make_mesh(fsdp=2), rules=fsdp_rules(),
                     compression="int8")
    cfg = tr._comm_cfg
    assert cfg is not None and cfg["sharded"] and cfg["shard_ax"] == "fsdp"
    assert cfg["F"] == 2 and cfg["n"] == cfg["S"] * cfg["F"]
    assert cfg["comp_slots"] and cfg["hops"] > 0
    assert cfg["bytes_wire"] < cfg["bytes_raw"]


def test_spmd_zero_steady_state_recompiles(monkeypatch):
    monkeypatch.setenv("MXNET_COMPILE_GUARD", "raise")
    # fresh registry + disarmed guard: another test's trainer may have
    # armed the module-global guard against the same (site, signature)
    profiler.reset_compiles()
    profiler.disarm_compile_guard()
    try:
        _, cmp_tr = _spmd_pair("int8")
        x, y = _batch(2)
        cmp_tr.step(nd.array(x), nd.array(y))   # compile + arm
        base = _c()["recompile_steady_state"]
        for _ in range(3):
            cmp_tr.step(nd.array(x), nd.array(y))  # raise mode: any
            # steady-state recompile would throw CompileGuardError here
        assert _c()["recompile_steady_state"] == base
    finally:
        profiler.disarm_compile_guard()
        profiler.reset_compiles()


def test_spmd_residual_persists_through_save_load(tmp_path):
    _, tr = _spmd_pair("int8")
    x, y = _batch(4)
    tr.step(nd.array(x), nd.array(y))
    resid = np.asarray(tr._comm_state)
    assert np.abs(resid).max() > 0
    f = str(tmp_path / "spmd_states")
    tr.save_states(f)
    _, tr2 = _spmd_pair("int8")
    tr2.load_states(f)
    np.testing.assert_array_equal(np.asarray(tr2._comm_state), resid)
    # layout mismatch: loud warning + fresh zeros, never a misapplied carry
    tr3 = SPMDTrainer(_build_net(3), _LOSS, "sgd", {"learning_rate": 0.1},
                      mesh=make_mesh(),
                      compression=comm.CompressionPolicy(
                          comm.Int8BlockCodec(block=32)))
    with pytest.warns(UserWarning, match="residuals"):
        tr3.load_states(f)
    assert np.abs(np.asarray(tr3._comm_state)).max() == 0
    # a residual-FREE snapshot (uncompressed trainer) also resets live
    # residuals: a restore must not carry another trajectory's error
    ref, tr4 = _spmd_pair("int8")
    tr4.step(nd.array(x), nd.array(y))
    assert np.abs(np.asarray(tr4._comm_state)).max() > 0
    f2 = str(tmp_path / "plain_states")
    ref.save_states(f2)
    tr4.load_states(f2)
    assert np.abs(np.asarray(tr4._comm_state)).max() == 0


def test_comm_metrics_provider_surfaces_bytes():
    _, cmp_tr = _spmd_pair("int8")
    x, y = _batch()
    cmp_tr.step(nd.array(x), nd.array(y))
    snap = profiler.metrics_snapshot()
    fields = snap["providers"]["comm"]
    assert fields["bytes_raw"] > fields["bytes_wire"] > 0
    assert fields["compression_ratio"] > 3.0
    text = profiler.render_prometheus()
    assert "mxnet_comm_bytes_wire" in text


def test_spmd_span_carries_payload_args(tmp_path):
    import json

    _, cmp_tr = _spmd_pair("int8")
    x, y = _batch()
    profiler.set_config(filename=str(tmp_path / "trace.json"))
    profiler.start()
    try:
        cmp_tr.step(nd.array(x), nd.array(y))
        path = profiler.dump()
    finally:
        profiler.set_config(filename="profile.json")
    with open(path) as f:
        doc = json.load(f)
    spans = [e for e in doc["traceEvents"]
             if isinstance(e, dict) and e.get("ph") == "B"
             and e.get("name") == "spmd.step"]
    assert spans
    args = spans[-1]["args"]
    assert args["bytes_raw"] > args["bytes_wire"] > 0
    assert args["codec"].startswith("int8b")


# ---------------------------------------------------------------------------
# quantized ring collectives + the int4 tier (ISSUE 19)
# ---------------------------------------------------------------------------


def test_int4_roundtrip_wire_and_host_rejection():
    import jax.numpy as jnp

    x = np.random.RandomState(7).randn(500).astype(np.float32) * 2.0
    codec = comm.Int4PackedCodec(block=64)
    assert codec.id == "int4b64"
    assert comm.codec_from_id("int4b64").block == 64
    payload, resid = codec.encode(jnp.asarray(x))
    assert np.asarray(payload["packed"]).dtype == np.uint8
    dec = np.asarray(codec.decode(payload, 500))
    # 4-bit grid: error bounded by half a step of the DECODED block scale
    scodes = np.asarray(payload["scodes"]).reshape(-1)
    scales = scodes.astype(np.float32) / 255.0 * float(payload["tmax"])
    bound = np.repeat(np.where(scales > 0, scales, 1.0), 64)[:500]
    assert (np.abs(dec - x) <= bound / 2 + 1e-6).all()
    np.testing.assert_allclose(np.asarray(resid), x - dec,
                               rtol=1e-4, atol=1e-5)
    # numpy decode (the async-PS server) matches the jitted decode
    np_payload = {k: np.asarray(v) for k, v in payload.items()}
    np.testing.assert_allclose(comm.decode_np("int4b64", np_payload, 500),
                               dec, atol=1e-6)
    # wire accounting: packed nibbles + uint8 scale codes + one fp32 tmax
    nb = -(-500 // 64)
    assert codec.wire_nbytes(500) == nb * 32 + nb + 4
    assert 4 * 500 / codec.wire_nbytes(500) > 6.0
    # the host bucket wire has no linear sum for packed nibbles: rejected
    with pytest.raises(TypeError, match="no wire protocol"):
        comm.bucket_allreduce(codec, jnp.asarray(x), lambda a, op: a)


@pytest.mark.parametrize("tier", ["int8b64", "int4b64"])
def test_ring_allreduce_numerics_and_ef_invariant(tier):
    """The explicit-hop ring allreduce sums the per-device buckets, and
    the per-device residuals sum EXACTLY to the dropped error
    (exact − delivered) — the aggregate EF invariant."""
    from incubator_mxnet_tpu.comm import ring

    codec = comm.codec_from_id(tier)
    n = 640
    x = np.random.RandomState(11).randn(n).astype(np.float32)
    out, resid = ring.ring_allreduce_sharded(
        codec, np.asarray(x), make_mesh(), axis_names=("dp",), algo="ring")
    out, resid = np.asarray(out), np.asarray(resid)
    exact = 8.0 * x  # replicated input: every device contributes x
    step = 127.0 if tier.startswith("int8") else 7.0
    assert np.abs(out - exact).max() <= 16 * np.abs(exact).max() / step
    np.testing.assert_allclose(resid.reshape(8, n).sum(axis=0),
                               exact - out, rtol=2e-4, atol=2e-4)
    # static plan matches what the trace layers report: 2(D-1) hops of
    # one encoded chunk each
    hops, bytes_hop = ring.hop_plan(codec, n, 8)
    assert hops == 14
    assert bytes_hop == codec.wire_nbytes(ring._ring_chunk(codec, n, 8))


def test_ring_psum_bitexact_at_world_one():
    """D=1 degenerate form: the ring is a local encode/decode roundtrip,
    bit-exact with the psum sandwich (same grid helpers at both ends)."""
    import jax

    from incubator_mxnet_tpu.comm import ring

    mesh1 = make_mesh(devices=jax.devices()[:1])
    x = np.random.RandomState(13).randn(300).astype(np.float32)
    for codec in (comm.Int8BlockCodec(64), comm.Int4PackedCodec(64)):
        a, ra = ring.ring_allreduce_sharded(codec, np.asarray(x), mesh1,
                                            axis_names=("dp",), algo="ring")
        b, rb = ring.ring_allreduce_sharded(codec, np.asarray(x), mesh1,
                                            axis_names=("dp",), algo="psum")
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(ra), np.asarray(rb))
    assert ring.hop_plan(comm.Int8BlockCodec(64), 300, 1) == (0, 0)


def test_ring_rs_ag_roundtrip():
    """Sharded-group exchange: quantized reduce-scatter then quantized
    all-gather of the reduced shards recovers the cross-device sum within
    the codec grid."""
    from incubator_mxnet_tpu.comm import ring

    codec = comm.Int8BlockCodec(32)
    n = 512  # divisible by the axis size
    x = np.random.RandomState(17).randn(n).astype(np.float32)
    gathered, resid = ring.ring_rs_ag_sharded(
        codec, np.asarray(x), make_mesh(fsdp=8), axis_name="fsdp")
    gathered = np.asarray(gathered)
    exact = 8.0 * x
    assert np.abs(gathered - exact).max() <= np.abs(exact).max() / 10
    assert np.asarray(resid).shape == (8 * n,)
    hops, bytes_hop = ring.rs_ag_hop_plan(codec, n, 8)
    assert hops == 14 and bytes_hop == codec.wire_nbytes(n // 8)


def test_spmd_ring_matches_fp32_losses_and_counts_hops():
    pol = comm.CompressionPolicy(comm.Int8BlockCodec(), algo="ring")
    ref, cmp_tr = _spmd_pair(pol)
    cfg = cmp_tr._comm_cfg
    assert cfg["algo"] == "ring" and cfg["hops"] == 14 > 0
    assert cfg["bytes_hop"] > 0
    x, y = _batch()
    for _ in range(5):
        l0 = float(ref.step(nd.array(x), nd.array(y)).asnumpy())
        l1 = float(cmp_tr.step(nd.array(x), nd.array(y)).asnumpy())
        assert abs(l0 - l1) < 5e-3 * max(1.0, abs(l0))
    # 2(D-1) encoded ppermute hops per step ride the counter
    assert _c()["comms_ring_hops"] == 5 * cfg["hops"]
    assert _c()["comms_bytes_raw"] > _c()["comms_bytes_wire"] > 0


def test_spmd_ring_zero_steady_state_recompiles(monkeypatch):
    monkeypatch.setenv("MXNET_COMPILE_GUARD", "raise")
    profiler.reset_compiles()
    profiler.disarm_compile_guard()
    try:
        pol = comm.CompressionPolicy(comm.Int8BlockCodec(), algo="ring")
        _, cmp_tr = _spmd_pair(pol)
        x, y = _batch(2)
        cmp_tr.step(nd.array(x), nd.array(y))   # compile + arm
        base = _c()["recompile_steady_state"]
        for _ in range(3):
            cmp_tr.step(nd.array(x), nd.array(y))
        assert _c()["recompile_steady_state"] == base
    finally:
        profiler.disarm_compile_guard()
        profiler.reset_compiles()


def test_spmd_fsdp_int8_convergence_parity():
    """The sharded compressed build (quantized RS of grads + quantized AG
    of updated shards, int8 + error feedback) converges to the fp32
    fsdp run's loss within the PR 14 tolerance."""
    from incubator_mxnet_tpu.parallel import fsdp_rules

    def mk(compression):
        return SPMDTrainer(_build_net(3), _LOSS, "sgd",
                           {"learning_rate": 0.2}, mesh=make_mesh(fsdp=2),
                           rules=fsdp_rules(), compression=compression)

    ref, cmp_tr = mk(None), mk("int8")
    assert cmp_tr._comm_cfg["sharded"]
    x, y = _batch(1)
    l0 = None
    for _ in range(40):
        lf = float(ref.step(nd.array(x), nd.array(y)).asnumpy())
        lc = float(cmp_tr.step(nd.array(x), nd.array(y)).asnumpy())
        l0 = lf if l0 is None else l0
    assert lc < 0.5 * l0       # actually trained
    assert abs(lc - lf) < 0.05 * max(lf, 0.1) + 0.02
    assert _c()["comms_ring_hops"] > 0
    assert _c()["comms_bytes_raw"] > _c()["comms_bytes_wire"] > 0


def test_async_pull_enc_int4(async_store):
    kv = async_store
    kv.set_gradient_compression({"type": "int4", "block": 4})
    kv.init("w", nd.zeros((6,)))
    g = np.array([0.7, -0.9, 0.2, 0.0, 3.0, -0.1], np.float32)
    kv.push("w", nd.array(g))
    out = nd.zeros((6,))
    kv.pull("w", out=out)
    # one 4-bit push quantization + one 4-bit pull quantization
    assert np.abs(out.asnumpy() - g).max() <= 2 * 3.0 / 7 + 1e-5
    assert kv._last_wire_dtype == "uint8"  # packed nibbles on the wire


def test_async_pull_enc_mismatches_fail_loudly(async_store):
    from incubator_mxnet_tpu.kvstore.async_ps import PSProtocolError

    kv = async_store
    kv.set_gradient_compression({"type": "int8", "block": 4})
    kv.init("w", nd.zeros((4,)))
    kv.push("w", nd.array(np.ones(4, np.float32)))
    # codec id the server cannot encode: named protocol error, not a
    # silent mis-decode (mixed old-server/new-client deployment)
    with pytest.raises(PSProtocolError, match="codec-id mismatch"):
        kv._client.request("pull_enc", "w", "nosuchcodec99", 1)
    # envelope version drift: the versioned pull leg rejects loudly too
    with pytest.raises(PSProtocolError, match="v99"):
        kv._client.request("pull_enc", "w", "int8b4",
                           comp_mod.PULL_ENC_WIRE_VERSION + 98)
    # the store itself still works after the rejected probes
    out = nd.zeros((4,))
    kv.pull("w", out=out)
    assert np.abs(out.asnumpy() - 1.0).max() <= 2 * 1.0 / 127 + 1e-6


# ---------------------------------------------------------------------------
# benchmark smoke
# ---------------------------------------------------------------------------


def test_collectives_benchmark_smoke():
    """Tier-1-adjacent smoke of benchmark/opperf/collectives.py: tiny
    sizes, proves the harness runs end-to-end and meets the >=3.5x int8
    byte acceptance on both paths (the timing numbers come from the full
    run, not here)."""
    import importlib.util

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "opperf", "collectives.py")
    spec = importlib.util.spec_from_file_location("opperf_collectives", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    line = mod.run(n_params=8, shape=(32, 16), batch=16, hidden=64,
                   iters=1, warmup=1, repeats=1)
    assert line["bytes_acceptance"]
    assert line["post_warmup_recompiles"] == 0
    assert line["int8_byte_ratio"]["pushpull_int8"] >= 3.5
    assert line["int8_byte_ratio"]["spmd_int8"] >= 3.5

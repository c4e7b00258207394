"""Pipeline-schedule + MoE tier tests (ISSUE 13): the 1F1B/GPipe
training scheduler (parallel/schedule.py), its SPMDTrainer integration
(stages= / pipeline=), and the expert-parallel MoE layer."""
import json
import os

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import gluon, profiler
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.gluon.model_zoo.moe import (
    MoEBlock, moe_loss_frame, frame_loss, frame_metrics)
from incubator_mxnet_tpu.ops.moe import moe_capacity, moe_ffn
from incubator_mxnet_tpu.parallel import (
    SPMDTrainer,
    analytic_bubble_fraction,
    build_schedule,
    make_mesh,
    pipeline_value_and_grad,
    simulate_schedule,
)

import jax
import jax.numpy as jnp


class TestScheduleBuilder:
    @pytest.mark.parametrize("kind", ["gpipe", "1f1b"])
    @pytest.mark.parametrize("P,M", [(2, 2), (4, 8), (4, 3), (8, 16), (1, 4)])
    def test_every_slot_once_and_runnable(self, kind, P, M):
        orders = build_schedule(P, M, kind)
        assert len(orders) == P
        for s in range(P):
            assert sorted(orders[s]) == sorted(
                [("F", m) for m in range(M)] + [("B", m) for m in range(M)])
        # the simulator raises on any dependency deadlock
        sim = simulate_schedule(P, M, kind)
        assert len(sim["timeline"]) == 2 * P * M

    def test_1f1b_in_flight_bound(self):
        """At most P−s microbatches are in flight per stage under 1F1B —
        the activation-memory property the schedule exists for."""
        P, M = 4, 12
        orders = build_schedule(P, M, "1f1b")
        for s, slots in enumerate(orders):
            live = 0
            peak = 0
            for op, _m in slots:
                live += 1 if op == "F" else -1
                peak = max(peak, live)
            assert peak <= P - s, f"stage {s} holds {peak} stashes"

    def test_bubble_fractions(self):
        P, M = 4, 8
        bound = analytic_bubble_fraction(P, M)
        f1 = simulate_schedule(P, M, "1f1b", tf=1.0, tb=2.0, remat=False)
        gp = simulate_schedule(P, M, "gpipe", tf=1.0, tb=2.0, remat=True)
        # 1F1B without remat sits exactly on the fill/drain bound
        assert abs(f1["bubble_fraction"] - bound) < 1e-9
        assert f1["bubble_fraction"] <= 1.5 * bound
        # GPipe in its paper configuration (full remat) pays recompute
        assert gp["bubble_fraction"] > f1["bubble_fraction"]
        # idle fraction (ignoring recompute overhead) matches the classic
        # result: both schedules are work-conserving
        assert abs(gp["idle_fraction"] - bound) < 1e-9

    def test_validation(self):
        with pytest.raises(ValueError):
            build_schedule(2, 4, "zigzag")
        with pytest.raises(ValueError):
            build_schedule(0, 4)
        with pytest.raises(ValueError):
            simulate_schedule(3, 4, remat=[True])  # wrong per-stage length


def _stage_setup(P=4, D=6, B=16):
    rng = np.random.RandomState(0)
    params = [{"w": jnp.asarray(rng.randn(D, D).astype(np.float32) * 0.4),
               "b": jnp.asarray(rng.randn(D).astype(np.float32) * 0.1)}
              for _ in range(P)]
    x = jnp.asarray(rng.randn(B, D).astype(np.float32))
    y = jnp.asarray(rng.randn(B, D).astype(np.float32))
    stage_fn = lambda p, h: jnp.tanh(h @ p["w"] + p["b"])
    loss_fn = lambda out, lab: jnp.sum((out - lab) ** 2)
    return params, x, y, stage_fn, loss_fn


class TestPipelineEngine:
    @pytest.mark.parametrize("kind", ["gpipe", "1f1b"])
    @pytest.mark.parametrize("remat", [False, True])
    def test_matches_sequential(self, kind, remat):
        P = 4
        params, x, y, stage_fn, loss_fn = _stage_setup(P=P)

        def seq(ps, xx, yy):
            h = xx
            for p in ps:
                h = stage_fn(p, h)
            return jnp.sum((h - yy) ** 2)

        ref_l, ref_g = jax.value_and_grad(seq)(params, x, y)
        task, side, grads, _ = jax.jit(
            lambda ps, xx, yy: pipeline_value_and_grad(
                [stage_fn] * P, loss_fn, ps, xx, yy, 8,
                schedule=kind, remat=remat))(params, x, y)
        np.testing.assert_allclose(float(task), float(ref_l), rtol=1e-5)
        assert float(side) == 0.0
        for s in range(P):
            for k in ("w", "b"):
                np.testing.assert_allclose(
                    np.asarray(grads[s][k]), np.asarray(ref_g[s][k]),
                    rtol=1e-4, atol=1e-5, err_msg=f"stage {s} {k}")

    def test_rich_side_losses_and_metrics(self):
        """Side losses get cotangent 1 through their own slot's vjp —
        including rematerialized stages, where the recompute must
        reproduce them — and metrics arrive per (stage, microbatch)."""
        P, M = 3, 4
        params, x, y, _, loss_fn = _stage_setup(P=P)

        def rich(p, h):
            h2 = jnp.tanh(h @ p["w"] + p["b"])
            return h2, 0.01 * jnp.sum(p["w"] ** 2), {
                "mean": jax.lax.stop_gradient(h2.mean())}

        def seq(ps, xx, yy):
            h = xx
            side = 0.0
            for p in ps:
                h = jnp.tanh(h @ p["w"] + p["b"])
                side = side + M * 0.01 * jnp.sum(p["w"] ** 2)
            return jnp.sum((h - yy) ** 2) + side

        ref_l, ref_g = jax.value_and_grad(seq)(params, x, y)
        for remat in (False, True):
            task, side, grads, mets = jax.jit(
                lambda ps, xx, yy: pipeline_value_and_grad(
                    [rich] * P, loss_fn, ps, xx, yy, M, schedule="1f1b",
                    remat=remat, stage_outputs="rich"))(params, x, y)
            np.testing.assert_allclose(
                float(task) + float(side), float(ref_l), rtol=1e-5)
            for s in range(P):
                np.testing.assert_allclose(
                    np.asarray(grads[s]["w"]), np.asarray(ref_g[s]["w"]),
                    rtol=1e-4, atol=1e-5)
            assert len(mets) == P and all(len(row) == M for row in mets)


def _mlp4(seed, in_dim=12):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(32, activation="relu"),
            nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize()
    net(mx.nd.zeros((2, in_dim)))
    return net


def _params_of(net):
    return {k: p.data().asnumpy()
            for k, p in net._collect_params_with_prefix().items()}


def _assert_params_close(a, b, **kw):
    kw.setdefault("rtol", 2e-4)
    kw.setdefault("atol", 2e-5)
    pa, pb = _params_of(a), _params_of(b)
    assert set(pa) == set(pb)
    for k in pa:
        np.testing.assert_allclose(pa[k], pb[k], err_msg=k, **kw)


def _data(n=16, d=12, seed=3):
    rng = np.random.RandomState(seed)
    return (rng.randn(n, d).astype(np.float32),
            rng.randint(0, 4, (n,)).astype(np.float32))


class TestSPMDPipelineTrainer:
    @pytest.mark.parametrize("kind,remat", [
        ("gpipe", True), ("gpipe", False), ("1f1b", False), ("1f1b", True)])
    def test_matches_unpipelined(self, kind, remat):
        """The acceptance equivalence: pipelined (both schedules, with and
        without remat) params after 3 steps match the unpipelined
        single-program step on the same params within tolerance."""
        x, y = _data()
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        net_a = _mlp4(seed=7)
        tr_a = SPMDTrainer(net_a, loss_fn, "sgd",
                           {"learning_rate": 0.1, "momentum": 0.9},
                           mesh=make_mesh())
        for _ in range(3):
            tr_a.step(mx.nd.array(x), mx.nd.array(y))
        tr_a.sync_to_block()

        net_b = _mlp4(seed=7)
        tr_b = SPMDTrainer(
            net_b, loss_fn, "sgd", {"learning_rate": 0.1, "momentum": 0.9},
            mesh=make_mesh(), stages=net_b.split_stages([1, 1, 1, 1]),
            pipeline={"schedule": kind, "n_microbatches": 8, "remat": remat})
        for _ in range(3):
            tr_b.step(mx.nd.array(x), mx.nd.array(y))
        tr_b.sync_to_block()
        _assert_params_close(net_a, net_b)

    def test_vector_loss_mean_parity(self):
        """A loss_fn returning per-ELEMENT losses (e.g. [B, T] token CE):
        the pipelined step must report the same mean as the unpipelined
        jnp.mean — sum/B would be off by a factor of T."""
        rng = np.random.RandomState(0)
        B, T, D = 8, 5, 6
        x = rng.randn(B, T, D).astype(np.float32)
        y = rng.randn(B, T, 4).astype(np.float32)

        def build():
            mx.random.seed(3)
            net = nn.HybridSequential()
            net.add(nn.Dense(16, flatten=False), nn.Dense(4, flatten=False))
            net.initialize()
            net(mx.nd.zeros((2, T, D)))
            return net

        def loss_fn(out, label):
            return (out - label) ** 2   # [B, T, 4] per-element loss

        net_a = build()
        la = SPMDTrainer(net_a, loss_fn, "sgd", {"learning_rate": 0.0},
                         mesh=make_mesh()).step(mx.nd.array(x), mx.nd.array(y))
        net_b = build()
        lb = SPMDTrainer(net_b, loss_fn, "sgd", {"learning_rate": 0.0},
                         mesh=make_mesh(), stages=net_b.split_stages([1, 1]),
                         pipeline={"schedule": "1f1b", "n_microbatches": 4}
                         ).step(mx.nd.array(x), mx.nd.array(y))
        np.testing.assert_allclose(float(la.asnumpy()), float(lb.asnumpy()),
                                   rtol=1e-5)

    def test_engine_pins_slot_for_keys(self):
        """The scheduler pins (stage, microbatch) around every slot trace
        — forward AND remat recompute — which is what lets the trainer
        fold a distinct PRNG key per microbatch (dropout masks must not
        repeat across microbatches) while a remat backward reproduces its
        forward's key exactly."""
        from incubator_mxnet_tpu.parallel.schedule import (
            current_slot, in_backward_trace)

        P, M = 2, 3
        seen = []

        def stage(p, h):
            seen.append((current_slot(), in_backward_trace()))
            return jnp.tanh(h * p)

        params = [jnp.float32(1.1), jnp.float32(0.9)]
        x = jnp.ones((6, 2), jnp.float32)
        loss_fn = lambda out, lab: jnp.sum((out - lab) ** 2)
        pipeline_value_and_grad([stage] * P, loss_fn, params, x,
                                jnp.zeros_like(x), M, schedule="1f1b",
                                remat=True)
        fwd = [slot for slot, bwd in seen if not bwd]
        # every (s, m) traced exactly once forward, slot always pinned —
        # in particular NOT one shared trace reused for every microbatch
        # (jax.checkpoint caches by function identity, so the engine must
        # wrap a fresh callable per slot; a cached reuse here would bake
        # microbatch 0's key fold into every microbatch)
        assert sorted(fwd) == [(s, m) for s in range(P) for m in range(M)]
        assert None not in fwd
        # modern jax.checkpoint replays the saved jaxpr in the backward
        # (no Python re-trace), so the forward trace above is the ONLY
        # place slot-dependent values enter — and they entered correctly

    def test_batchnorm_aux_through_pipeline(self):
        mx.random.seed(3)
        net = nn.HybridSequential()
        net.add(nn.Dense(16), nn.BatchNorm(), nn.Dense(4))
        net.initialize()
        net(mx.nd.zeros((2, 8)))
        x, y = _data(n=16, d=8)
        tr = SPMDTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1}, mesh=make_mesh(),
            stages=net.split_stages([2, 1]),
            pipeline={"schedule": "1f1b", "n_microbatches": 4})
        params = net.collect_params()
        rm = [k for k in params if "running_mean" in k][0]
        before = params[rm].data().asnumpy().copy()
        tr.step(mx.nd.array(x), mx.nd.array(y))
        tr.sync_to_block()
        assert not np.allclose(before, params[rm].data().asnumpy())

    def test_zero_steady_state_recompiles_guard_raise(self, monkeypatch):
        """Acceptance: the whole scheduled step dispatches as one compiled
        program with zero steady-state recompiles under the raise-mode
        guard (auto-armed after the first step)."""
        monkeypatch.setenv("MXNET_COMPILE_GUARD", "raise")
        profiler.disarm_compile_guard()
        try:
            x, y = _data()
            net = _mlp4(seed=9)
            tr = SPMDTrainer(
                net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
                {"learning_rate": 0.1}, mesh=make_mesh(),
                stages=net.split_stages([2, 2]),
                pipeline={"schedule": "1f1b", "n_microbatches": 4})
            base = profiler.counters()["recompile_steady_state"]
            for _ in range(5):   # guard armed after step 1; raise = failure
                tr.step(mx.nd.array(x), mx.nd.array(y))
            assert profiler.counters()["recompile_steady_state"] == base
        finally:
            profiler.disarm_compile_guard()

    def test_counters_spans_provider(self, tmp_path):
        x, y = _data()
        net = _mlp4(seed=13)
        tr = SPMDTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1}, mesh=make_mesh(),
            stages=net.split_stages([1, 3]),
            pipeline={"schedule": "gpipe", "n_microbatches": 4})
        c0 = profiler.counters()
        out = str(tmp_path / "trace.json")
        profiler.set_config(filename=out)
        profiler.start()
        try:
            for _ in range(2):
                tr.step(mx.nd.array(x), mx.nd.array(y))
            out = profiler.dump()
        finally:
            profiler.stop()
        c1 = profiler.counters()
        assert c1["pipeline_step"] - c0["pipeline_step"] == 2
        assert c1["pipeline_microbatch"] - c0["pipeline_microbatch"] == 8
        assert c1["pipeline_bubble_ms"] >= c0["pipeline_bubble_ms"]
        snap = profiler.metrics_snapshot()
        prov = [v for k, v in snap["providers"].items()
                if k.startswith("pipeline")]
        assert prov and any(p.get("stages") == 2 for p in prov)
        with open(out) as f:
            events = json.load(f)["traceEvents"]
        names = {e.get("name") for e in events}
        assert "pipeline.step" in names
        assert "pipeline.stage" in names
        stage_args = [e["args"] for e in events
                      if e.get("name") == "pipeline.stage"
                      and e.get("ph") == "B"]
        assert {a["stage"] for a in stage_args} == {0, 1}

    def test_slow_step_annotator_scoped_to_own_steps(self, caplog):
        """The pipeline annotator names its busiest stage on the
        trainer's OWN slow steps and stays silent on anyone else's (a
        stale not-yet-collected trainer must not annotate an unrelated
        loop — the detector's exactly-once contract is per subsystem)."""
        import logging
        import time

        x, y = _data()
        net = _mlp4(seed=17)
        tr = SPMDTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1}, mesh=make_mesh(),
            stages=net.split_stages([2, 2]),
            pipeline={"schedule": "1f1b", "n_microbatches": 4})
        tr.step(mx.nd.array(x), mx.nd.array(y))  # compile outside timing
        profiler.set_config(slow_step_ms=0.001)  # every step is "slow"
        profiler.start()
        try:
            with caplog.at_level(logging.WARNING,
                                 logger="incubator_mxnet_tpu.profiler"):
                tr.step(mx.nd.array(x), mx.nd.array(y))
                tr.step(mx.nd.array(x), mx.nd.array(y))
                main = [r for r in caplog.records
                        if "host-dispatch" in r.getMessage()]
                own = [r for r in caplog.records
                       if "modeled busy" in r.getMessage()]
                # exactly ONE annotator line per slow step, no more
                assert main and len(own) == len(main)
                assert "stage" in own[0].getMessage()
                caplog.clear()
                time.sleep(0.002)
                profiler.step_boundary()   # unrelated slow step
                stale = [r for r in caplog.records
                         if "modeled busy" in r.getMessage()]
                assert not stale
                assert any("slow step" in r.getMessage()
                           for r in caplog.records)
        finally:
            profiler.set_config(slow_step_ms=None)
            profiler.stop()

    def test_validation(self):
        x, y = _data()
        net = _mlp4(seed=2)
        with pytest.raises(ValueError):
            net.split_stages([1, 1])        # sizes don't cover
        with pytest.raises(ValueError):
            net.split_stages([0, 4])        # empty stage
        stages = net.split_stages([2, 2])
        loss = gluon.loss.SoftmaxCrossEntropyLoss()
        with pytest.raises(ValueError):     # missing n_microbatches
            SPMDTrainer(net, loss, "sgd", {}, stages=stages, pipeline={})
        with pytest.raises(ValueError):     # overlapping stage params
            SPMDTrainer(net, loss, "sgd", {},
                        stages=[stages[0], stages[0], stages[1]],
                        pipeline={"n_microbatches": 2})
        with pytest.raises(ValueError):     # pipeline config without stages
            SPMDTrainer(net, loss, "sgd", {},
                        pipeline={"n_microbatches": 2})


class TestMoE:
    def test_capacity_rule(self):
        assert moe_capacity(64, 4, 1, 1.0) == 16
        assert moe_capacity(64, 4, 2, 1.0) == 32
        assert moe_capacity(64, 4, 2, 1.25) == 40
        assert moe_capacity(4, 64, 1, 1.0) == 1    # floor
        assert moe_capacity(8, 2, 2, 100.0) == 8   # ceil at T

    def test_overflow_drop_exact_and_deterministic(self):
        """Force every token onto expert 0 (k=1): dropped must equal
        exactly T − capacity, twice in a row, under a fixed seed."""
        T, E, d = 24, 4, 8
        rng = np.random.RandomState(1)
        x = jnp.asarray(np.abs(rng.randn(T, d)).astype(np.float32) + 0.5)
        rw = np.zeros((d, E), np.float32)
        rw[:, 0] = 1.0
        args = (x, jnp.asarray(rw),
                jnp.asarray(rng.randn(E, d, 16).astype(np.float32) * 0.1),
                jnp.zeros((E, 16), jnp.float32),
                jnp.asarray(rng.randn(E, 16, d).astype(np.float32) * 0.1),
                jnp.zeros((E, d), jnp.float32))
        kw = dict(num_experts=E, top_k=1, capacity_factor=1.0)
        C = moe_capacity(T, E, 1, 1.0)
        o1 = moe_ffn(*args, **kw)
        o2 = moe_ffn(*args, **kw)
        assert float(o1[3]) == T - C == 18
        assert float(o1[4]) == 0.0 and float(o1[5]) == C
        np.testing.assert_array_equal(np.asarray(o1[0]), np.asarray(o2[0]))
        for i in range(1, 6):
            assert float(o1[i]) == float(o2[i])

    def test_dense_equivalence_at_full_capacity(self):
        """With capacity >= T and k = E, the MoE output must equal the
        dense mixture Σ_e gate_e · FFN_e(x) — routing is then a no-op."""
        T, E, d, h = 6, 3, 4, 5
        rng = np.random.RandomState(0)
        x = rng.randn(T, d).astype(np.float32)
        rw = rng.randn(d, E).astype(np.float32) * 0.3
        w1 = rng.randn(E, d, h).astype(np.float32) * 0.5
        b1 = rng.randn(E, h).astype(np.float32) * 0.1
        w2 = rng.randn(E, h, d).astype(np.float32) * 0.5
        b2 = rng.randn(E, d).astype(np.float32) * 0.1
        y, aux, z, dropped, _, _ = moe_ffn(
            jnp.asarray(x), jnp.asarray(rw), jnp.asarray(w1),
            jnp.asarray(b1), jnp.asarray(w2), jnp.asarray(b2),
            num_experts=E, top_k=E, capacity_factor=float(E))
        assert float(dropped) == 0.0
        logits = x @ rw
        probs = np.exp(logits - logits.max(-1, keepdims=True))
        probs = probs / probs.sum(-1, keepdims=True)
        ref = np.zeros_like(x)
        for e in range(E):
            he = np.maximum(x @ w1[e] + b1[e], 0.0)
            ref += probs[:, e:e + 1] * (he @ w2[e] + b2[e])
        np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-4, atol=1e-5)
        # Switch aux at uniform-ish routing ~ 1; z finite
        assert np.isfinite(float(aux)) and np.isfinite(float(z))

    def test_frame_and_eager_aux(self):
        mx.random.seed(0)
        blk = MoEBlock(units=8, hidden_size=16, num_experts=4, top_k=2)
        blk.initialize()
        x = mx.nd.array(np.random.RandomState(0).randn(4, 6, 8)
                        .astype(np.float32))
        with moe_loss_frame() as fr:
            y = blk(x)
        assert y.shape == (4, 6, 8)
        assert frame_loss(fr) is not None
        mets = frame_metrics(fr)
        assert set(mets) == {"tokens_dropped", "expert_load_min",
                             "expert_load_max"}
        y2 = blk(x)   # no frame: stashes for the eager path
        np.testing.assert_allclose(y.asnumpy(), y2.asnumpy(), rtol=1e-6)
        assert float(np.asarray(blk.aux_loss()._data
                                if hasattr(blk.aux_loss(), "_data")
                                else blk.aux_loss())) >= 0.0

    def test_hybridize_does_not_stash_tracer(self):
        """A hybridized MoE forward runs inside the cached-graph trace:
        it must NOT stash that trace's tracer for aux_loss() (which would
        leak out of the finished trace) — and the hybridized output must
        still match eager."""
        mx.random.seed(8)
        blk = MoEBlock(units=8, hidden_size=16, num_experts=4, top_k=2)
        blk.initialize()
        x = mx.nd.array(np.random.RandomState(2).randn(4, 6, 8)
                        .astype(np.float32))
        eager = blk(x).asnumpy()          # eager: stashes a concrete value
        concrete = blk.aux_loss()
        blk.hybridize()
        hybrid = blk(x).asnumpy()
        np.testing.assert_allclose(hybrid, eager, rtol=1e-5, atol=1e-6)
        assert blk.aux_loss() is concrete   # tracer never replaced it
        mx.random.seed(8)
        fresh = MoEBlock(units=8, hidden_size=16, num_experts=4, top_k=2)
        fresh.initialize()
        fresh.hybridize()
        fresh(x)
        with pytest.raises(RuntimeError, match="moe_loss_frame"):
            fresh.aux_loss()

    def test_moe_trains_through_pipeline_acceptance(self, monkeypatch):
        """The ISSUE acceptance: an MoE block trains through the 1F1B
        pipeline on a dp×ep mesh — loss decreases, zero steady-state
        recompiles under the raise guard, drop/load metrics visible in
        metrics_snapshot(), expert weights genuinely ep-sharded."""
        monkeypatch.setenv("MXNET_COMPILE_GUARD", "raise")
        profiler.disarm_compile_guard()
        try:
            from incubator_mxnet_tpu.gluon.model_zoo.moe import (
                moe_sharding_rules)

            mx.random.seed(5)
            net = nn.HybridSequential()
            net.add(nn.Dense(16, activation="relu", flatten=False),
                    MoEBlock(units=16, hidden_size=32, num_experts=4,
                             top_k=2, capacity_factor=1.1),
                    nn.Dense(4, flatten=False))
            net.initialize()
            net(mx.nd.zeros((2, 6, 12)))
            rng = np.random.RandomState(0)
            x = rng.randn(16, 6, 12).astype(np.float32)
            y = rng.randint(0, 4, (16,)).astype(np.float32)

            def loss_fn(out, label):
                return gluon.loss.SoftmaxCrossEntropyLoss()(
                    out.mean(axis=1), label)

            tr = SPMDTrainer(
                net, loss_fn, "adam", {"learning_rate": 1e-2},
                mesh=make_mesh(dp=2, ep=4), rules=moe_sharding_rules(),
                stages=net.split_stages([2, 1]),
                pipeline={"schedule": "1f1b", "n_microbatches": 8})
            base = profiler.counters()
            losses = [float(tr.step(mx.nd.array(x), mx.nd.array(y))
                            .asnumpy()) for _ in range(6)]
            assert losses[-1] < losses[0]
            c = profiler.counters()
            assert c["recompile_steady_state"] == base[
                "recompile_steady_state"]
            assert c["moe_tokens_dropped"] > base["moe_tokens_dropped"]
            snap = profiler.metrics_snapshot()
            prov = [v for k, v in snap["providers"].items()
                    if k.startswith("pipeline")
                    and "moe_expert_load_max" in v]
            assert prov
            assert prov[-1]["moe_expert_load_max"] >= prov[-1][
                "moe_expert_load_min"] >= 0
            j = [i for i, p in enumerate(tr._params)
                 if "experts_mlp1_weight" in p.name][0]
            assert tr._param_arrays[j].sharding.spec[0] == "ep"
        finally:
            profiler.disarm_compile_guard()

    def test_moe_unpipelined_step_counts_drops(self):
        mx.random.seed(4)
        net = nn.HybridSequential()
        net.add(MoEBlock(units=8, hidden_size=16, num_experts=4, top_k=1,
                         capacity_factor=0.5),
                nn.Dense(4, flatten=False))
        net.initialize()
        net(mx.nd.zeros((2, 4, 8)))
        rng = np.random.RandomState(1)
        x = rng.randn(8, 4, 8).astype(np.float32)
        y = rng.randint(0, 4, (8,)).astype(np.float32)

        def loss_fn(out, label):
            return gluon.loss.SoftmaxCrossEntropyLoss()(
                out.mean(axis=1), label)

        tr = SPMDTrainer(net, loss_fn, "sgd", {"learning_rate": 0.05},
                         mesh=make_mesh())
        base = profiler.counters()["moe_tokens_dropped"]
        first = float(tr.step(mx.nd.array(x), mx.nd.array(y)).asnumpy())
        for _ in range(5):
            last = float(tr.step(mx.nd.array(x), mx.nd.array(y)).asnumpy())
        # capacity_factor 0.5 guarantees overflow: T·k·(1−cf) slots drop
        assert profiler.counters()["moe_tokens_dropped"] > base
        assert np.isfinite(last) and last < first + 1.0


@pytest.mark.slow
def test_pipeline_bench_smoke(monkeypatch, tmp_path):
    """The opperf harness in smoke mode: acceptance flags set, zero
    post-warmup recompiles, evidence JSON well-formed."""
    monkeypatch.delenv("MXNET_COMPILE_GUARD", raising=False)
    profiler.disarm_compile_guard()
    try:
        from benchmark.opperf import pipeline as bench

        line = bench.run(n_stages=4, layers_per_stage=1, n_microbatches=8,
                         batch=16, seq=4, units=16, hidden=32, heads=2,
                         iters=1, warmup=1, repeats=1)
        assert line["post_warmup_recompiles"] == 0
        assert line["bubble_acceptance"] is True
        assert line["bubble"]["1f1b"]["bubble_fraction"] < line[
            "bubble"]["gpipe"]["bubble_fraction"]
        assert line["bubble"]["1f1b"]["bubble_fraction"] <= (
            1.5 * line["analytic_bound"])
        assert set(line["steps_per_sec"]) == {"single", "gpipe", "1f1b"}
    finally:
        monkeypatch.delenv("MXNET_COMPILE_GUARD", raising=False)
        profiler.disarm_compile_guard()

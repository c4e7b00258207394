"""Generation tier (ISSUE 11): iteration-level continuous batching over a
device-resident slot KV cache.

The acceptance contracts:

* **equivalence** — tokens from a request decoded inside a churning
  mixed batch (requests joining and leaving around it) exactly match the
  same request decoded alone (greedy);
* **cancellation** — mid-stream ``cancel()`` frees the slot and a queued
  request takes it over;
* **zero steady-state recompiles** — a mixed-length join/leave workload
  completes with ``MXNET_COMPILE_GUARD=raise`` armed post-warmup;
* **admission control** — queue-depth load shedding raises
  ``AdmissionError`` at ``submit()``; per-tenant accounting is exported
  through the metrics provider.
"""
import threading
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu.gluon.model_zoo.transformer import (Transformer,
                                                             greedy_search)
from incubator_mxnet_tpu.ndarray.ndarray import NDArray
from incubator_mxnet_tpu.ops.nn import streaming_softmax_ce
from incubator_mxnet_tpu.parallel import make_mesh, SPMDTrainer
from incubator_mxnet_tpu.predictor import StatefulExecutor
from incubator_mxnet_tpu.serving import (AdmissionError, GenerationServer,
                                         KVCacheLadder, ShapeBucketer,
                                         SlotKVCache)

VOCAB, BOS, EOS = 17, 1, 2


@pytest.fixture(autouse=True)
def _clean_guard():
    """Server start() arms the module-global compile guard; a leftover
    armed guard would tag every later test's compiles as steady-state
    violations."""
    profiler.disarm_compile_guard()
    profiler.set_config(compile_guard=None)
    yield
    profiler.disarm_compile_guard()
    profiler.set_config(compile_guard=None)


def _materialize(net, S=8):
    net(mx.nd.array(np.ones((1, S), np.int32), dtype="int32"),
        mx.nd.array(np.ones((1, 1), np.int32), dtype="int32"))
    return net


@pytest.fixture(scope="module")
def tiny_net():
    """Untrained (but materialized) 2+2-layer transformer."""
    mx.random.seed(0)
    net = Transformer(VOCAB, units=24, hidden_size=48, num_heads=2,
                      num_encoder_layers=2, num_decoder_layers=2,
                      dropout=0.0, max_length=64)
    net.initialize()
    return _materialize(net)


@pytest.fixture(scope="module")
def trained_net():
    """Copy-with-EOS task: greedy decode of a length-8 prompt copies its
    first 7 tokens then emits EOS — diverse tokens and a REAL eos path,
    so equivalence failures can't hide behind degenerate outputs."""
    mx.random.seed(0)
    net = Transformer(VOCAB, units=24, hidden_size=48, num_heads=2,
                      num_encoder_layers=1, num_decoder_layers=1,
                      dropout=0.0, max_length=64)
    net.initialize()

    def batch(B, S, seed):
        rng = np.random.RandomState(seed)
        src = rng.randint(3, VOCAB, (B, S)).astype(np.int32)
        tgt_out = np.concatenate(
            [src[:, :-1], np.full((B, 1), EOS, np.int32)], axis=1)
        tgt_in = np.concatenate(
            [np.full((B, 1), BOS, np.int32), tgt_out[:, :-1]], axis=1)
        return src, tgt_in, tgt_out

    def loss_fn(out, label):
        return NDArray(
            streaming_softmax_ce(out._data, label._data).mean(axis=-1))

    B, S = 16, 8
    s0, t0, _ = batch(B, S, 0)
    net(mx.nd.array(s0, dtype="int32"), mx.nd.array(t0, dtype="int32"))
    trainer = SPMDTrainer(net, loss_fn, "adam", {"learning_rate": 5e-3},
                          mesh=make_mesh())
    for i in range(150):
        src, tgt_in, tgt_out = batch(B, S, i)
        trainer.step((mx.nd.array(src, dtype="int32"),
                      mx.nd.array(tgt_in, dtype="int32")),
                     mx.nd.array(tgt_out, dtype="int32"))
    trainer.sync_to_block()
    return net


def _server(net, **kw):
    kw.setdefault("bos", BOS)
    kw.setdefault("eos", EOS)
    kw.setdefault("max_prompt_length", 16)
    kw.setdefault("max_new_tokens", 24)
    kw.setdefault("decode_buckets", [24])
    kw.setdefault("slots_per_bucket", 4)
    kw.setdefault("name", "gen_test")
    return GenerationServer(net, **kw)


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(3, VOCAB, n).astype(np.int32)


# ---------------------------------------------------------------------------
# units: bucketer ceiling, slot cache, stateful executor
# ---------------------------------------------------------------------------


class TestShapeBucketerCeiling:
    def test_explicit_buckets_with_ceiling(self):
        b = ShapeBucketer(buckets=[8, 16, 32], max_length=20)
        assert b.max_length == 20
        assert b.bucket_for(17) == 32       # bucket above the ceiling is
        with pytest.raises(ValueError) as e:  # fine for lengths under it
            b.bucket_for(21)
        assert "max_length" in str(e.value)

    def test_ceiling_above_top_bucket_rejected(self):
        with pytest.raises(ValueError) as e:
            ShapeBucketer(buckets=[8, 16], max_length=64)
        assert "top bucket" in str(e.value)

    def test_default_ceiling_is_top_bucket(self):
        b = ShapeBucketer(buckets=[8, 16])
        assert b.max_length == 16
        b2 = ShapeBucketer(max_length=100, min_bucket=8)
        assert b2.max_length == 100


class TestSlotKVCache:
    def test_alloc_free_cycle(self):
        c = SlotKVCache(layers=2, slots=2, bucket=8, mem_width=8,
                        heads=2, head_dim=4)
        s0 = c.alloc("a", mem_len=3, first_token=BOS)
        s1 = c.alloc("b", mem_len=5, first_token=BOS)
        assert {s0, s1} == {0, 1} and c.n_active == 2
        assert c.alloc("c", 1, BOS) is None          # exhausted
        c.free(s0)
        assert c.n_free == 1 and c.owners[s0] is None
        assert c.mem_len[s0] == 1                    # NaN guard floor
        with pytest.raises(ValueError):
            c.free(s0)                               # double free is loud
        s2 = c.alloc("c", 2, BOS)
        assert s2 == s0 and c.joins == 3 and c.leaves == 1

    def test_ladder_walks_up_when_tight_pool_full(self):
        lad = KVCacheLadder(layers=1, heads=2, head_dim=4, mem_width=8,
                            buckets=[8, 16], slots_per_bucket=1)
        p0, _ = lad.try_alloc(6, "a", 1, BOS)
        assert p0.bucket == 8
        p1, _ = lad.try_alloc(6, "b", 1, BOS)        # 8-pool full -> 16
        assert p1.bucket == 16
        assert lad.try_alloc(6, "c", 1, BOS) is None
        with pytest.raises(ValueError):
            lad.bucket_for(17)


class TestStatefulExecutor:
    def test_state_advances_and_warms(self):
        import jax.numpy as jnp

        exe = StatefulExecutor({"x": jnp.zeros(4)}, name="t",
                               compile_site="test.stateful")

        def step(state, inputs):
            x = state["x"] + inputs["d"]
            return x.sum(), {"x": x}

        exe.add_program("step", step)
        assert not exe.is_warm("step")
        s1 = exe.run("step", d=np.float32(1.0))
        assert float(s1) == 4.0 and exe.is_warm("step")
        s2 = exe.run("step", d=np.float32(1.0))
        assert float(s2) == 8.0                      # state carried over
        st = exe.compile_stats()
        assert st["calls"]["step"] == 2 and st["entries"] >= 1

    def test_dropped_state_key_is_loud(self):
        import jax.numpy as jnp

        exe = StatefulExecutor({"x": jnp.zeros(2), "y": jnp.zeros(2)})
        exe.add_program("bad", lambda s, i: (s["x"], {"x": s["x"]}))
        with pytest.raises(RuntimeError) as e:
            exe.run("bad")
        assert "y" in str(e.value)


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------


class TestGenerationBasics:
    def test_single_request_matches_greedy_oracle(self, trained_net):
        srv = _server(trained_net)
        try:
            p = _prompt(8, 3)
            toks = srv.submit(p, max_new_tokens=12).result(timeout=60.0)
            gt, gl = greedy_search(trained_net,
                                   mx.nd.array(p[None], dtype="int32"),
                                   bos=BOS, eos=EOS, max_length=13)
            # greedy_search tokens include the BOS prime at position 0
            want = gt[0, 1:int(gl[0])]
            np.testing.assert_array_equal(toks, want)
            assert toks[-1] == EOS
        finally:
            srv.close()

    def test_finish_reasons_and_latency_fields(self, trained_net):
        srv = _server(trained_net)
        try:
            res_eos = srv.submit(_prompt(8, 4))
            res_len = srv.submit(_prompt(8, 5), max_new_tokens=3)
            assert res_eos.result(60.0)[-1] == EOS
            assert res_eos.finish_reason == "eos"
            assert len(res_len.result(60.0)) == 3
            assert res_len.finish_reason == "length"
            assert res_eos.ttft_ms is not None and res_eos.ttft_ms > 0
            assert res_eos.tpot_ms is not None
        finally:
            srv.close()

    def test_submit_rejects_oversized_at_the_door(self, tiny_net):
        srv = _server(tiny_net)
        try:
            with pytest.raises(ValueError) as e:
                srv.submit(_prompt(17, 0))           # prompt ceiling is 16
            assert "max_prompt_length" in str(e.value)
            with pytest.raises(ValueError) as e:
                srv.submit(_prompt(4, 0), max_new_tokens=25)
            assert "decode ladder" in str(e.value)
            with pytest.raises(ValueError):
                srv.submit(np.zeros(0, np.int32))
            with pytest.raises(ValueError):
                srv.submit(_prompt(4, 0), tenant="nope")
        finally:
            srv.close()

    def test_streaming_yields_before_done(self, tiny_net):
        srv = _server(tiny_net)
        try:
            res = srv.submit(_prompt(6, 7), max_new_tokens=24)
            seen = []
            for tok in res.stream(timeout=30.0):
                if not seen:
                    assert not res.done()            # mid-stream, not a
                seen.append(tok)                     # batch done+replay
            assert len(seen) >= 1
            np.testing.assert_array_equal(seen, res.result(1.0))
        finally:
            srv.close()

    def test_on_token_callback(self, tiny_net):
        srv = _server(tiny_net)
        try:
            got = []
            res = srv.submit(_prompt(6, 8), max_new_tokens=5,
                             on_token=lambda r, t: got.append(t))
            toks = res.result(60.0)
            np.testing.assert_array_equal(got, toks)
        finally:
            srv.close()


class TestContinuousBatchingEquivalence:
    def test_churning_mixed_batch_matches_alone(self, trained_net):
        """THE acceptance contract: the target request's tokens must be
        bit-identical whether it decodes alone or inside a batch with
        requests of other lengths joining and leaving around it."""
        srv = _server(trained_net, slots_per_bucket=3,
                      max_prefills_per_iter=2)
        try:
            target = _prompt(8, 42)
            alone = srv.submit(target, max_new_tokens=20).result(60.0)

            # churn: 3 slots, 9 live requests with staggered lifetimes
            # (mixed prompt lengths AND mixed max_new), target in the
            # middle of the wave — joins and leaves happen around it
            others = [srv.submit(_prompt(3 + (i % 9), 100 + i),
                                 max_new_tokens=3 + 2 * i)
                      for i in range(4)]
            res_t = srv.submit(target, max_new_tokens=20)
            others += [srv.submit(_prompt(3 + (i % 9), 200 + i),
                                  max_new_tokens=3 + 2 * i)
                       for i in range(4)]
            churned = res_t.result(120.0)
            for r in others:
                r.result(120.0)
            np.testing.assert_array_equal(churned, alone)
            st = srv.stats()
            assert st["completed"] == 10
            # the batch genuinely churned: more joins than slots
            joins = sum(p["joins"] for p in st["pools"].values())
            assert joins == 10 > srv._ladder.n_slots
        finally:
            srv.close()

    def test_static_mode_also_correct(self, trained_net):
        """Drain-and-refill (the benchmark baseline) produces the same
        tokens — it is slower, not different."""
        srv = _server(trained_net, batching="static", slots_per_bucket=2)
        try:
            target = _prompt(8, 42)
            alone = srv.submit(target, max_new_tokens=20).result(60.0)
            rs = [srv.submit(_prompt(5, 300 + i), max_new_tokens=6)
                  for i in range(3)]
            res = srv.submit(target, max_new_tokens=20)
            np.testing.assert_array_equal(res.result(120.0), alone)
            for r in rs:
                r.result(120.0)
        finally:
            srv.close()


class TestCancellation:
    def test_cancel_frees_slot_for_queued_request(self, tiny_net):
        """Mid-stream cancellation: the slot comes back and the queued
        request takes it over (the disconnected-client contract)."""
        srv = _server(tiny_net, slots_per_bucket=1, decode_buckets=[24])
        try:
            a = srv.submit(_prompt(6, 1), max_new_tokens=24)
            b = srv.submit(_prompt(6, 2), max_new_tokens=4)  # queued: 1 slot
            it = a.stream(timeout=30.0)
            next(it)
            next(it)
            a.cancel()
            b_toks = b.result(60.0)                  # b got the slot
            assert len(b_toks) == 4
            with pytest.raises(StopIteration):       # a's stream ended
                while True:
                    next(it)
            assert a.finish_reason == "cancelled"
            assert a.cancelled() and len(a.tokens_so_far()) < 24
            st = srv.stats()
            assert st["active_slots"] == 0
            assert st["tenants"]["default"]["cancelled"] == 1
        finally:
            srv.close()


class TestAdmissionControl:
    def test_queue_depth_load_shedding(self, tiny_net):
        srv = _server(tiny_net,
                      tenants={"capped": dict(max_queue=2, max_slots=0)})
        try:
            c0 = profiler.counters()["generation_shed"]
            srv.submit(_prompt(4, 0), tenant="capped")
            srv.submit(_prompt(4, 1), tenant="capped")
            with pytest.raises(AdmissionError):
                srv.submit(_prompt(4, 2), tenant="capped")
            assert profiler.counters()["generation_shed"] == c0 + 1
            st = srv.stats()["tenants"]["capped"]
            assert st["shed"] == 1 and st["submitted"] == 2
            # default tenant is unaffected by the capped tenant's backlog
            assert len(srv.submit(_prompt(4, 3), max_new_tokens=2)
                       .result(60.0)) == 2
        finally:
            srv.close(drain=False)

    def test_tenant_slot_cap_respected(self, tiny_net):
        srv = _server(tiny_net, slots_per_bucket=4,
                      tenants={"small": dict(max_slots=1)})
        try:
            peak = {"v": 0}

            def watch(r, t):
                peak["v"] = max(peak["v"],
                                srv.stats()["tenants"]["small"]
                                ["active_slots"])

            rs = [srv.submit(_prompt(4, i), tenant="small",
                             max_new_tokens=6, on_token=watch)
                  for i in range(3)]
            for r in rs:
                r.result(60.0)
            assert peak["v"] == 1
        finally:
            srv.close()

    def test_per_tenant_slo_accounting(self, tiny_net):
        # an SLO of 0 ms is violated by construction — the accounting,
        # not the latency, is under test
        srv = _server(tiny_net,
                      tenants={"strict": dict(slo_ttft_ms=0.0,
                                              slo_tpot_ms=0.0)})
        try:
            c0 = profiler.counters()["generation_slo_violation"]
            srv.submit(_prompt(4, 0), tenant="strict",
                       max_new_tokens=3).result(60.0)
            srv.submit(_prompt(4, 1), max_new_tokens=3).result(60.0)
            assert profiler.counters()["generation_slo_violation"] == c0 + 1
            assert srv.stats()["tenants"]["strict"]["slo_violations"] == 1
            assert srv.stats()["tenants"]["default"]["slo_violations"] == 0
        finally:
            srv.close()


class TestSteadyStateCompileGuard:
    def test_churn_workload_zero_recompiles_guard_raise(self, trained_net):
        """The tentpole acceptance: with the PR 9 guard armed in raise
        mode post-warmup, a mixed-length workload with requests joining
        and leaving the decode batch completes without a single compile
        — slot join/leave is pure buffer indexing."""
        profiler.set_config(compile_guard="raise")
        srv = _server(trained_net, slots_per_bucket=2)
        try:
            c0 = profiler.counters()["recompile_steady_state"]
            comp0 = srv.compile_stats()["compiles"]
            rng = np.random.RandomState(0)
            rs = []
            for i in range(12):                      # mixed, staggered
                rs.append(srv.submit(
                    _prompt(int(rng.randint(2, 16)), 1000 + i),
                    max_new_tokens=int(rng.randint(2, 24))))
                if i % 3 == 0:
                    time.sleep(0.01)                 # joins mid-decode
            for r in rs:
                r.result(120.0)                      # raise mode: a compile
            assert profiler.counters()["recompile_steady_state"] == c0
            assert srv.compile_stats()["compiles"] == comp0
            assert profiler.compile_guard_state()["armed"]
        finally:
            srv.close()

    def test_warmup_compiles_are_declared(self, tiny_net):
        profiler.reset_compiles()
        srv = _server(tiny_net, decode_buckets=[8, 24])
        try:
            reg = profiler.compile_registry()["sites"]
            assert "generation.warmup" in reg
            # 2 prompt buckets (8,16) + 2 pools x (decode+insert)
            assert reg["generation.warmup"]["count"] == 6
            assert "generation.decode" not in reg    # nothing outside warmup
        finally:
            srv.close()


class TestObservability:
    def test_metrics_provider_and_counters(self, tiny_net):
        c0 = dict(profiler.counters())
        srv = _server(tiny_net, name="gen_metrics")
        try:
            srv.submit(_prompt(5, 0), max_new_tokens=4).result(60.0)
            snap = profiler.metrics_snapshot()
            prov = snap["providers"]["gen_metrics"]
            assert prov["tenant_default_completed"] == 1
            assert prov["tenant_default_tokens"] == 4
            assert prov["active_slots"] == 0
            c = profiler.counters()
            assert c["generation_request"] == c0["generation_request"] + 1
            assert c["generation_token"] >= c0["generation_token"] + 4
            assert c["generation_slot_join"] == c0["generation_slot_join"] + 1
            assert (c["generation_slot_leave"]
                    == c0["generation_slot_leave"] + 1)
        finally:
            srv.close()
        assert "gen_metrics" not in profiler.metrics_snapshot()["providers"]

    def test_generation_spans_in_trace(self, tiny_net, tmp_path):
        srv = _server(tiny_net, name="gen_spans")
        try:
            profiler.set_config(filename=str(tmp_path / "gen_trace.json"))
            profiler.start()
            srv.submit(_prompt(5, 0), max_new_tokens=3).result(60.0)
            profiler.stop()
        finally:
            srv.close()
        import json

        with open(profiler.dump()) as f:
            names = {e.get("name") for e in json.load(f)["traceEvents"]}
        for want in ("generation.enqueue", "generation.prefill",
                     "generation.step", "generation.complete"):
            assert want in names, names


    def test_spans_nest_and_share_the_request_id(self, tiny_net, tmp_path):
        """ISSUE 25: the scheduler's spans are in the xprof trace of whoever
        traces, nested as the code nests, and ``request=`` joins a request's
        enqueue, prefill and completion."""
        from common import host_spans, span_inside

        srv = _server(tiny_net, name="gen_nest")
        try:
            profiler.set_config(filename=str(tmp_path / "gen_nest.json"))
            profiler.start()
            res = srv.submit(_prompt(5, 0), max_new_tokens=3, request_id=77)
            res.result(60.0)
            trace_dir = profiler._state["dir"]
            profiler.stop()
        finally:
            srv.close()
        spans = host_spans(trace_dir, "generation.")
        by = {}
        for s in spans:
            by.setdefault(s[3], []).append(s)
        (prefill,), (done,) = by["generation.prefill"], by["generation.complete"]
        (enq,) = by["generation.enqueue"]
        assert {enq[4]["request"], prefill[4]["request"],
                done[4]["request"]} == {"77"}
        assert float(prefill[4]["queue_wait_ms"]) >= 0.0
        assert enq[0] != prefill[0]        # submit thread, scheduler thread
        assert sum(span_inside(prefill, a) for a in by["generation.admit"]) == 1
        assert len(by["generation.step"]) == 3 == len(by["generation.decode.emit"])
        for d2h in by["generation.decode.d2h"]:
            assert sum(span_inside(d2h, st) for st in by["generation.step"]) == 1
        for emit in by["generation.decode.emit"]:
            assert not any(span_inside(emit, st) for st in by["generation.step"])
        assert sum(span_inside(done, e) for e in by["generation.decode.emit"]) == 1
        assert done[4]["reason"] == "length" and done[4]["tokens"] == "3"

    @pytest.mark.parametrize("slots,waits", [(4, False), (1, True)])
    def test_queue_wait_is_admission_minus_submit(self, tiny_net, slots, waits):
        """``queue_wait_ms`` p50/p95 in ``stats()``: near zero while slots
        are free; with ONE slot the later requests wait out the earlier
        ones' decodes."""
        srv = _server(tiny_net, name=f"gen_wait{slots}", slots_per_bucket=slots)
        try:
            assert srv.stats()["queue_wait_ms_p50"] is None
            rs = [srv.submit(_prompt(4, i), max_new_tokens=6) for i in range(3)]
            for r in rs:
                r.result(60.0)
            st = srv.stats()
        finally:
            srv.close()
        assert 0.0 <= st["queue_wait_ms_p50"] <= st["queue_wait_ms_p95"]
        ttft = max(r.ttft_ms for r in rs)
        assert st["queue_wait_ms_p95"] <= ttft
        if waits:   # the third request sat through two whole decodes
            assert st["queue_wait_ms_p95"] > 0.5 * ttft


class TestLifecycle:
    def test_close_drains(self, tiny_net):
        srv = _server(tiny_net, slots_per_bucket=1)
        rs = [srv.submit(_prompt(4, i), max_new_tokens=3) for i in range(4)]
        srv.close(drain=True)
        for r in rs:
            assert len(r.result(1.0)) == 3
        with pytest.raises(RuntimeError):
            srv.submit(_prompt(4, 9))

    def test_drain_close_with_unadmittable_queue_returns(self, tiny_net):
        """A zero-slot tenant's queued request can never run: the
        scheduler must idle-wait (not busy-spin) on it, and
        close(drain=True) must fail it and return promptly instead of
        hanging until the join timeout."""
        srv = _server(tiny_net, tenants={"frozen": dict(max_slots=0)})
        res = srv.submit(_prompt(4, 0), tenant="frozen")
        time.sleep(0.2)              # scheduler parks instead of spinning
        assert srv.stats()["iterations"] <= 2
        t0 = time.perf_counter()
        srv.close(drain=True, timeout=30.0)
        assert time.perf_counter() - t0 < 10.0
        with pytest.raises(RuntimeError) as e:
            res.result(1.0)
        assert "slot-capped" in str(e.value)

    def test_close_no_drain_fails_queued(self, tiny_net):
        srv = _server(tiny_net, slots_per_bucket=1)
        rs = [srv.submit(_prompt(4, i), max_new_tokens=24)
              for i in range(4)]
        srv.close(drain=False)
        outcomes = []
        for r in rs:
            try:
                r.result(5.0)
                outcomes.append(r.finish_reason)
            except RuntimeError:
                outcomes.append("error")
        assert all(o in ("error", "cancelled", "eos", "length")
                   for o in outcomes)
        assert "error" in outcomes                  # the queued tail failed

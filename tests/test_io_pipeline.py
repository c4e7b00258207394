"""Async sharded input pipeline tests (io/pipeline.py + io.py satellites).

Covers the ISSUE-9 acceptance surface: ordered delivery under
multi-worker prep, exact sharded-union equivalence, device
placement/sharding of delivered batches, autotune (host-bound raise +
memory-cap backoff), exact stall counters, lifecycle (close() drains and
joins every thread), and the SPMDTrainer integration contract — batches
arrive device-resident with the mesh data-axis NamedSharding so the step
dispatch does zero per-step host→device work (no ``spmd.shard_batch``
span on the consumer thread).
"""
import gc
import threading
import time

import numpy as np
import pytest

import jax
from jax.sharding import NamedSharding

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu import gluon
from incubator_mxnet_tpu.gluon import nn
from incubator_mxnet_tpu.io import DataPipeline, NDArrayIter, PrefetchingIter
from incubator_mxnet_tpu.parallel import batch_pspec, make_mesh, mesh_scope


def _pipeline_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("mxtpu-") and t.is_alive()]


def _wait_until(cond, timeout=10.0, msg="condition"):
    deadline = time.perf_counter() + timeout
    while time.perf_counter() < deadline:
        if cond():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


@pytest.fixture
def clean_profiler(tmp_path):
    profiler.stop()
    profiler.set_config(filename=str(tmp_path / "trace.json"),
                        ring_size=65536, slow_step_ms=None)
    profiler.reset_counters()
    yield tmp_path
    profiler.stop()
    profiler.set_config(slow_step_ms=None, slow_step_auto=True)
    profiler.reset_counters()


@pytest.fixture(autouse=True)
def no_thread_leak():
    """Every test must leave zero pipeline threads behind — the leak the
    PrefetchingIter lifecycle fix exists for, enforced suite-wide."""
    yield
    gc.collect()
    _wait_until(lambda: not _pipeline_threads(), timeout=5.0,
                msg="pipeline threads to exit")


class TestDelivery:
    def test_plain_iterable_order_values_and_sharding(self):
        mesh = make_mesh()
        src = [np.full((8, 4), i, np.float32) for i in range(12)]
        with DataPipeline(src, mesh=mesh, num_workers=3) as pipe:
            got = list(pipe)
            assert len(got) == 12
            want = NamedSharding(mesh, batch_pspec(2))
            for i, a in enumerate(got):
                assert isinstance(a, jax.Array)
                assert a.sharding == want
                np.testing.assert_array_equal(np.asarray(a), src[i])

    def test_multiworker_prep_preserves_order(self):
        """Workers finish out of order (seeded random sleep); delivery
        must still be exactly source order, with prep applied."""
        rng = np.random.RandomState(0)
        delays = rng.uniform(0.0, 0.01, size=32)

        def prep(b):
            time.sleep(delays[int(b[0, 0])])
            return b * 2.0

        src = [np.full((4, 2), i, np.float32) for i in range(32)]
        with DataPipeline(src, mesh=make_mesh(), prep_fn=prep,
                          num_workers=4) as pipe:
            got = [np.asarray(a) for a in pipe]
        assert [int(a[0, 0]) for a in got] == [2 * i for i in range(32)]

    def test_databatch_source_wraps_ndarray_and_keeps_bookkeeping(self):
        mesh = make_mesh()
        it = NDArrayIter(np.arange(80, dtype=np.float32).reshape(20, 4),
                         np.arange(20, dtype=np.float32), batch_size=8)
        with DataPipeline(it, mesh=mesh) as pipe:
            batches = list(pipe)
        assert len(batches) == 3
        want = NamedSharding(mesh, batch_pspec(2))
        for b in batches:
            assert isinstance(b.data[0], mx.nd.NDArray)
            assert b.data[0]._data.sharding == want
            assert isinstance(b.label[0], mx.nd.NDArray)
        assert batches[-1].pad == 4  # 20 % 8 — pad bookkeeping survives

    def test_multi_epoch_reiteration_and_reset(self):
        src = [np.full((4, 2), i, np.float32) for i in range(6)]
        pipe = DataPipeline(src, mesh=make_mesh(), num_workers=2)
        try:
            e1 = [int(np.asarray(a)[0, 0]) for a in pipe]
            e2 = [int(np.asarray(a)[0, 0]) for a in pipe]  # auto re-open
            assert e1 == e2 == list(range(6))
            # mid-epoch reset: no stale pre-reset batch may survive
            it = iter(pipe)
            next(it)
            pipe.reset()
            e3 = [int(np.asarray(a)[0, 0]) for a in pipe]
            assert e3 == list(range(6))
        finally:
            pipe.close()

    def test_source_error_propagates_in_order(self):
        def gen():
            for i in range(3):
                yield np.full((2, 2), i, np.float32)
            raise ValueError("decode failed")

        pipe = DataPipeline(gen, mesh=make_mesh(), num_workers=2)
        try:
            got = []
            with pytest.raises(ValueError, match="decode failed"):
                for a in pipe:
                    got.append(int(np.asarray(a)[0, 0]))
            assert got == [0, 1, 2]  # every good batch delivered first
        finally:
            pipe.close()


class TestSharding:
    def test_sharded_union_equals_unsharded_stream(self):
        """Exact equivalence: the union of all parts' delivered samples is
        the unsharded stream's sample set, and parts are disjoint."""
        full = np.arange(24, dtype=np.float32).reshape(24, 1)
        unsharded = NDArrayIter(full, batch_size=4, shuffle=True, seed=7)
        ref = []
        for b in unsharded:
            ref.extend(int(v) for v in b.data[0].asnumpy().ravel())

        parts = []
        for pi in range(3):
            it = NDArrayIter(full, batch_size=4, shuffle=True, seed=7,
                             num_parts=3, part_index=pi)
            got = []
            for b in it:
                got.extend(int(v) for v in b.data[0].asnumpy().ravel())
            assert len(got) == 8  # equal share per host
            parts.append(got)
        flat = [v for p in parts for v in p]
        assert sorted(flat) == sorted(ref) == list(range(24))
        assert len(set(flat)) == 24  # disjoint

    def test_shuffle_is_epoch_aware_and_host_agreeing(self):
        full = np.arange(16, dtype=np.float32).reshape(16, 1)

        def epoch(it):
            out = []
            for b in it:
                out.extend(int(v) for v in b.data[0].asnumpy().ravel())
            return out

        a = NDArrayIter(full, batch_size=4, shuffle=True, seed=3,
                        num_parts=2, part_index=0)
        b = NDArrayIter(full, batch_size=4, shuffle=True, seed=3,
                        num_parts=2, part_index=1)
        a1, b1 = epoch(a), epoch(b)
        a.reset(), b.reset()
        a2, b2 = epoch(a), epoch(b)
        # per-epoch: hosts split the full set disjointly
        assert sorted(a1 + b1) == list(range(16))
        assert sorted(a2 + b2) == list(range(16))
        # epochs reshuffle (the RNG stream advances identically everywhere)
        assert a1 != a2

    def test_uneven_shard_raises_unless_allow_pad(self):
        full = np.arange(25, dtype=np.float32).reshape(25, 1)
        with pytest.raises(ValueError, match="allow_pad"):
            NDArrayIter(full, batch_size=4, num_parts=3, part_index=0)
        seen = []
        for pi in range(3):
            it = NDArrayIter(full, batch_size=3, num_parts=3, part_index=pi,
                             allow_pad=True)
            assert it.num_data == 9  # every host sees the same count
            for b in it:
                seen.extend(int(v) for v in b.data[0].asnumpy().ravel())
        assert set(seen) == set(range(25))  # wrap covers every sample

    def test_pipeline_rejects_mismatched_source_sharding(self):
        full = np.arange(16, dtype=np.float32).reshape(16, 1)
        it = NDArrayIter(full, batch_size=4, num_parts=2, part_index=0)
        with pytest.raises(ValueError, match="sharded"):
            DataPipeline(it, mesh=make_mesh(), num_parts=4, part_index=1)

    def test_pipeline_strides_plain_iterable(self):
        src = [np.full((2, 2), i, np.float32) for i in range(10)]
        got = {}
        for pi in range(2):
            with DataPipeline(src, mesh=make_mesh(), num_parts=2,
                              part_index=pi, name=f"io_part{pi}") as pipe:
                got[pi] = [int(np.asarray(a)[0, 0]) for a in pipe]
        assert got[0] == [0, 2, 4, 6, 8]
        assert got[1] == [1, 3, 5, 7, 9]


class TestAutotune:
    def test_depth_rises_while_host_bound(self, monkeypatch):
        monkeypatch.setenv("MXNET_IO_TUNE_INTERVAL", "1")
        hostbound = [{"wall_ms": 10.0, "host_ms": 9.0, "comms_ms": 0.0,
                      "device_ms": 1.0}] * 8

        def slow_gen():
            for i in range(64):
                yield np.full((4, 2), i, np.float32)

        pipe = DataPipeline(slow_gen, mesh=make_mesh(), depth=2, max_depth=6,
                            _step_stats_fn=lambda: hostbound,
                            _device_pressure_fn=lambda frac: False)
        try:
            it = iter(pipe)
            for _ in range(4):
                next(it)
            _wait_until(lambda: pipe.depth == 6, msg="depth to reach cap")
            assert pipe.stats()["depth_changes"] >= 4
        finally:
            pipe.close()

    def test_memory_budget_caps_depth(self, monkeypatch):
        monkeypatch.setenv("MXNET_IO_TUNE_INTERVAL", "1")
        hostbound = [{"wall_ms": 10.0, "host_ms": 9.0, "comms_ms": 0.0,
                      "device_ms": 1.0}] * 8
        batch_bytes = 4 * 2 * 4  # (4, 2) float32
        budget_mb = (3 * batch_bytes) / (1 << 20)  # room for exactly 3

        def gen():
            for i in range(64):
                yield np.full((4, 2), i, np.float32)

        pipe = DataPipeline(gen, mesh=make_mesh(), depth=2, max_depth=8,
                            memory_budget_mb=budget_mb,
                            _step_stats_fn=lambda: hostbound,
                            _device_pressure_fn=lambda frac: False)
        try:
            it = iter(pipe)
            for _ in range(16):
                next(it)
            _wait_until(lambda: pipe.depth == 3, msg="depth to settle at 3")
            for _ in range(16):
                next(it)
            assert pipe.depth == 3  # never raised past the budget
        finally:
            pipe.close()

    def test_device_pressure_backs_off(self, monkeypatch):
        monkeypatch.setenv("MXNET_IO_TUNE_INTERVAL", "1")

        def gen():
            for i in range(64):
                yield np.full((4, 2), i, np.float32)

        pipe = DataPipeline(gen, mesh=make_mesh(), depth=4, max_depth=8,
                            _step_stats_fn=lambda: [],
                            _device_pressure_fn=lambda frac: True)
        try:
            it = iter(pipe)
            for _ in range(16):
                next(it)
            _wait_until(lambda: pipe.depth == 2,
                        msg="depth to back off to the floor")
        finally:
            pipe.close()

    def test_epoch_boundary_stalls_do_not_ratchet_depth(self, monkeypatch):
        """The consumer's unavoidable arrival at a refilling epoch-start
        buffer is NOT an autotune signal: a healthy producer over many
        epochs must keep the double-buffer depth, not creep to the cap."""
        monkeypatch.setenv("MXNET_IO_TUNE_INTERVAL", "1")
        src = [np.full((4, 2), i, np.float32) for i in range(8)]
        pipe = DataPipeline(src, mesh=make_mesh(), depth=2, max_depth=8,
                            _step_stats_fn=lambda: [],
                            _device_pressure_fn=lambda frac: False)
        try:
            for _ in range(5):  # 5 epochs, each restarts with an empty buffer
                it, delivered = iter(pipe), 0
                while True:
                    if delivered >= pipe.depth:
                        # the buffer has filled once this epoch: from here
                        # the consumer is the slower side, whatever else
                        # the machine runs — it asks when a batch is there
                        _wait_until(
                            lambda: pipe.stats()["buffer_occupancy"] > 0,
                            msg="the producer's next batch")
                    try:
                        next(it)
                    except StopIteration:
                        break
                    delivered += 1
                assert delivered == len(src)
            assert pipe.depth == 2
            # phantom (epoch-refill) stalls are race-dependent; the
            # contract is that whatever occurred never fed the tuner
            assert pipe.stats()["stalls_warm"] == 0
        finally:
            pipe.close()

    def test_fixed_depth_when_autotune_off(self, monkeypatch):
        monkeypatch.setenv("MXNET_IO_TUNE_INTERVAL", "1")
        src = [np.full((4, 2), i, np.float32) for i in range(32)]
        with DataPipeline(src, mesh=make_mesh(), depth=3,
                          autotune=False) as pipe:
            list(pipe)
            assert pipe.depth == 3
            assert pipe.stats()["depth_changes"] == 0


class TestObservability:
    def test_stall_counters_exact(self, clean_profiler):
        """Each consumer arrival at an empty buffer is EXACTLY one stall:
        the producer is gated per-batch, and next() is always issued
        before the gate opens."""
        gate = threading.Semaphore(0)

        def prep(b):
            gate.acquire()
            return b

        src = [np.full((2, 2), i, np.float32) for i in range(4)]
        before = profiler.counters()["io_pipeline_stalls"]
        pipe = DataPipeline(src, mesh=make_mesh(), prep_fn=prep,
                            num_workers=1, autotune=False)
        try:
            it = iter(pipe)
            for _ in range(4):
                t = threading.Timer(0.05, gate.release)
                t.start()
                next(it)  # issued while the gate is shut -> one stall each
                t.join()
        finally:
            gate.release()  # let the epoch finish so close() is quick
            pipe.close()
        assert profiler.counters()["io_pipeline_stalls"] - before == 4
        st = pipe.stats()
        assert st["stalls"] == 4
        assert st["stall_ms_p50"] is not None
        assert st["stall_ms_p99"] >= st["stall_ms_p50"]

    def test_counters_spans_and_bytes(self, clean_profiler):
        profiler.start()
        src = [np.zeros((8, 4), np.float32) for _ in range(5)]
        with DataPipeline(src, mesh=make_mesh(),
                          prep_fn=lambda b: b + 1.0) as pipe:
            list(pipe)
        c = profiler.counters()
        assert c["io_pipeline_batches"] == 5
        assert c["io_pipeline_bytes"] == 5 * 8 * 4 * 4
        names = {e.get("name") for e in profiler._trace_events()
                 if e.get("ph") == "B"}
        assert "io.prep" in names
        assert "io.transfer" in names
        profiler.stop()

    def test_metrics_provider_lifecycle(self, clean_profiler):
        src = [np.zeros((4, 2), np.float32) for _ in range(3)]
        pipe = DataPipeline(src, mesh=make_mesh(), name="io_test_pipe")
        try:
            list(pipe)
            snap = profiler.metrics_snapshot()
            prov = snap["providers"]["io_test_pipe"]
            assert prov["batches"] == 3
            assert prov["depth"] >= 2
            assert "stall_ms_p99" in prov
        finally:
            pipe.close()
        assert "io_test_pipe" not in profiler.metrics_snapshot()["providers"]

    def test_spans_reach_a_jax_trace_with_the_recorder_off(self, clean_profiler):
        """Every stage is a ``profiler.span``: under a bare
        ``jax.profiler.start_trace`` the host plane of the ``.xplane.pb``
        holds ``io.read``, ``io.prep``, ``io.transfer`` and (slow source)
        ``io.wait``, the spans of one batch sharing a ``seq``, while the
        ring recorder is OFF (``record_span`` wrote nothing then)."""
        from common import host_spans

        assert not profiler.recording_enabled()
        ring_rows = profiler.recorder_stats()["spans"]
        n = 5

        def slow_source():
            for i in range(n):
                time.sleep(0.03)  # the consumer arrives first: it waits
                yield np.full((8, 4), i, np.float32)

        trace_dir = clean_profiler / "xplane"
        jax.profiler.start_trace(str(trace_dir))
        try:
            with DataPipeline(slow_source, mesh=make_mesh(), autotune=False,
                              prep_fn=lambda b: b + 1.0) as pipe:
                got = [np.asarray(a) for a in pipe]
        finally:
            jax.profiler.stop_trace()
        assert len(got) == n
        assert profiler.recorder_stats()["spans"] == ring_rows  # the ring stayed off
        spans = host_spans(trace_dir, "io.")
        by_name = {}
        for line, s, e, name, stats in spans:
            by_name.setdefault(name, []).append((line, s, e, stats))
        assert {"io.read", "io.prep", "io.transfer", "io.wait"} <= set(by_name)
        seq_of = lambda name: sorted(int(st["seq"]) for _, _, _, st in by_name[name])
        # one read a batch and the one that found the source exhausted,
        # which carries the end marker's seq
        assert seq_of("io.read") == list(range(n + 1))
        assert seq_of("io.prep") == list(range(n))
        assert seq_of("io.transfer") == list(range(n))
        assert set(seq_of("io.wait")) <= set(range(n + 1)) and seq_of("io.wait")
        for _, _, _, st in by_name["io.transfer"]:
            assert int(st["bytes"]) == 8 * 4 * 4
        # a batch is read before it is staged, each stage on a thread of its own
        reads = {int(st["seq"]): (line, e) for line, _, e, st in by_name["io.read"]}
        for line, s, _, st in by_name["io.transfer"]:
            read_line, read_end = reads[int(st["seq"])]
            assert read_line != line and read_end <= s
        assert len({line for line, *_ in by_name["io.read"]}) == 1
        assert len({line for line, *_ in by_name["io.transfer"]}) == 1

    def test_strided_and_skipped_reads_carry_no_seq(self, clean_profiler):
        """A read the stride drops or the resume cursor skips delivers
        nothing: its ``io.read`` carries ``seq=-1``, and the survivors are
        numbered by delivery position."""
        from common import host_spans

        src = [np.full((2, 2), i, np.float32) for i in range(8)]
        trace_dir = clean_profiler / "xplane"
        jax.profiler.start_trace(str(trace_dir))
        try:
            pipe = DataPipeline(src, mesh=make_mesh(), num_parts=2,
                                part_index=1, autostart=False)
            pipe.load_state_dict({"kind": "DataPipeline", "epoch": 0,
                                  "delivered": 1})
            with pipe:
                got = [float(np.asarray(a)[0, 0]) for a in pipe]
        finally:
            jax.profiler.stop_trace()
        assert got == [3.0, 5.0, 7.0]  # part 1 of 2, its first batch skipped
        reads = sorted((s, int(st["seq"])) for _, s, _, name, st
                       in host_spans(trace_dir, "io.read"))
        assert [seq for _, seq in reads] == [-1, -1, -1, 0, -1, 1, -1, 2, -1]

    @pytest.mark.parametrize("slow", [True, False], ids=["slow-source", "fast-source"])
    def test_wait_counter_is_the_time_the_consumer_waited(self, clean_profiler, slow):
        """``io_pipeline_wait_us`` grows by at least the slept time behind a
        slow source and by nothing behind one that runs ahead."""
        n, nap = 4, 0.05

        def source():
            for i in range(n):
                if slow:
                    time.sleep(nap)
                yield np.full((2, 2), i, np.float32)

        before = profiler.counters()
        with DataPipeline(source, mesh=make_mesh(), autotune=False,
                          depth=n + 1) as pipe:
            it = iter(pipe)
            if not slow:  # let the whole epoch reach the buffer first
                _wait_until(lambda: pipe.stats()["buffer_occupancy"] == n + 1,
                            msg="the buffer to fill")
            got = [next(it) for _ in range(n)]
        assert len(got) == n
        after = profiler.counters()
        waited = after["io_pipeline_wait_us"] - before["io_pipeline_wait_us"]
        stalls = after["io_pipeline_stalls"] - before["io_pipeline_stalls"]
        if slow:
            assert stalls >= 1 and waited >= (n * nap - 0.01) * 1e6 * 0.9
            assert waited == pytest.approx(
                sum(pipe._eng._stall_ms) * 1e3, abs=len(pipe._eng._stall_ms))
        else:
            assert stalls == 0 and waited == 0

    def test_wait_still_bills_the_step_and_the_goodput_ledger(self, clean_profiler):
        """The ring's billing of ``io.wait`` reads as it did under
        ``record_span``: a ring row, the step's host bucket and the run's
        ``data_wait``, from the step-owning thread."""
        profiler.start()
        profiler.reset_goodput()
        profiler.step_boundary()  # pin this thread as the step's

        def source():
            for i in range(3):
                time.sleep(0.04)
                yield np.full((2, 2), i, np.float32)

        with DataPipeline(source, mesh=make_mesh(), autotune=False) as pipe:
            list(pipe)
        waited = profiler.counters()["io_pipeline_wait_us"] / 1e6
        assert waited >= 0.1
        assert profiler.goodput_snapshot()["buckets_s"]["data_wait"] == \
            pytest.approx(waited, abs=0.01)
        rows = [e for e in profiler._trace_events()
                if e.get("ph") == "B" and e.get("name") == "io.wait"]
        assert rows and all("seq" in e.get("args", {}) for e in rows)
        names = {e.get("name") for e in profiler._trace_events() if e.get("ph") == "B"}
        assert {"io.read", "io.transfer"} <= names
        profiler.stop()

    def test_read_ahead_is_bounded_on_an_endless_source(self):
        """An endless source (a generator that repeats its loader) is held
        back by the consumer: no stage buffers without bound."""
        made = []

        def endless():
            i = 0
            while True:
                made.append(i)
                yield np.full((2, 2), i, np.float32)
                i += 1

        with DataPipeline(endless, mesh=make_mesh(), autotune=False) as pipe:
            it = iter(pipe)
            first = [float(np.asarray(next(it))[0, 0]) for _ in range(3)]
            time.sleep(0.3)  # unbounded, the reader makes thousands in this time
            eng = pipe._eng
            # prep queue + seq table (2 x workers each) + a batch in each
            # stage's hands + the device buffer
            bound = 4 * eng._num_workers + eng._num_workers + 2 + eng.depth
            assert len(made) - 3 <= bound, len(made)
            assert len(eng._ready) <= 2 * eng._num_workers
            nxt = [float(np.asarray(next(it))[0, 0]) for _ in range(20)]
        assert first + nxt == [float(i) for i in range(23)]

    def test_bounded_table_keeps_order_under_many_workers(self):
        """Stress for the bound the workers share with the transfer stage:
        more workers than cores, a short switch interval, preps that finish
        out of order — delivery stays in source order, the seq table never
        passes its bound, and nothing deadlocks."""
        import os
        import random
        import sys

        workers = min(32, 2 * (os.cpu_count() or 4))
        n = 300
        rng = random.Random(7)
        naps = [rng.random() * 1e-3 for _ in range(n)]

        def prep(b):
            time.sleep(naps[int(b[0, 0])])
            return b

        src = [np.full((2, 2), i, np.float32) for i in range(n)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            deadline = time.perf_counter() + 60.0
            with DataPipeline(src, mesh=make_mesh(), prep_fn=prep,
                              num_workers=workers, autotune=False) as pipe:
                got, most = [], 0
                for a in pipe:
                    got.append(float(np.asarray(a)[0, 0]))
                    most = max(most, len(pipe._eng._ready))
                    assert time.perf_counter() < deadline, "pipeline stuck"
        finally:
            sys.setswitchinterval(interval)
        assert got == [float(i) for i in range(n)]
        assert most <= 2 * workers, most

class TestLifecycle:
    def test_close_drains_and_joins_all_threads(self):
        src = [np.zeros((4, 2), np.float32) for _ in range(100)]
        pipe = DataPipeline(src, mesh=make_mesh(), num_workers=3,
                            prep_fn=lambda b: b)
        it = iter(pipe)
        next(it)  # mid-epoch abandon: buffer full, workers busy
        assert _pipeline_threads()
        pipe.close()
        assert not _pipeline_threads()
        with pytest.raises(RuntimeError):
            next(it)

    def test_abandoned_pipeline_is_collected_without_leaking(self):
        src = [np.zeros((4, 2), np.float32) for _ in range(50)]
        pipe = DataPipeline(src, mesh=make_mesh(), num_workers=2)
        next(iter(pipe))
        del pipe
        gc.collect()
        _wait_until(lambda: not _pipeline_threads(), timeout=5.0,
                    msg="GC'd pipeline threads to exit")

    def test_prefetching_iter_close_and_context_manager(self):
        it = NDArrayIter(np.zeros((64, 4), np.float32), batch_size=4)
        pf = PrefetchingIter(it)
        pf.next()  # abandon mid-epoch: the worker holds queued batches
        worker = pf._thread
        assert worker.is_alive()
        pf.close()
        assert pf._thread is None and not worker.is_alive()
        pf.close()  # idempotent
        with pytest.raises(RuntimeError, match="closed"):
            pf.next()  # must error loudly, not hang on the drained queue
        with PrefetchingIter(NDArrayIter(np.zeros((8, 4), np.float32),
                                         batch_size=4)) as pf2:
            assert pf2.next() is not None
        assert pf2._thread is None

    def test_prefetching_iter_depth_env(self, monkeypatch):
        monkeypatch.setenv("MXNET_IO_PREFETCH_DEPTH", "5")
        pf = PrefetchingIter(NDArrayIter(np.zeros((8, 4), np.float32),
                                         batch_size=4))
        assert pf._queue.maxsize == 5
        pf.close()

    def test_prefetching_iter_reset_still_works(self):
        it = NDArrayIter(np.arange(16, dtype=np.float32).reshape(16, 1),
                         batch_size=4, last_batch_handle="discard")
        pf = PrefetchingIter(it)
        e1 = [b.data[0].asnumpy().ravel().tolist() for b in pf]
        pf.reset()
        e2 = [b.data[0].asnumpy().ravel().tolist() for b in pf]
        assert e1 == e2 and len(e1) == 4
        pf.close()


class TestTrainerIntegration:
    def test_spmd_batches_device_resident_no_per_step_transfer(
            self, clean_profiler):
        """The acceptance contract: pipeline batches carry the mesh
        data-axis NamedSharding BEFORE step dispatch, and the step does
        zero per-step host→device work on the consumer thread (no
        ``spmd.shard_batch`` span) — while the same loop fed numpy
        transfers every step."""
        from incubator_mxnet_tpu.parallel import SPMDTrainer

        mesh = make_mesh()
        mx.random.seed(11)
        net = nn.HybridSequential()
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
        net.initialize()
        net(mx.nd.zeros((2, 8)))
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        spmd = SPMDTrainer(net, loss_fn, "sgd", {"learning_rate": 0.1},
                           mesh=mesh)

        rng = np.random.RandomState(0)
        x = rng.randn(32, 8).astype(np.float32)
        y = rng.randint(0, 4, size=(32,)).astype(np.float32)
        it = NDArrayIter(x, y, batch_size=8)

        def shard_batch_spans():
            return [e for e in profiler._trace_events()
                    if e.get("ph") == "B"
                    and e.get("name") == "spmd.shard_batch"]

        want = NamedSharding(mesh, batch_pspec(2))
        with mesh_scope(mesh):
            pipe = DataPipeline(it, sp_axis=None)
        try:
            profiler.start()
            losses = []
            for b in pipe:
                xb, yb = b.data[0], b.label[0]
                assert xb._data.sharding == want  # placed BEFORE dispatch
                losses.append(float(spmd.step(xb, yb).asnumpy()))
            assert all(np.isfinite(l) for l in losses) and len(losses) == 4
            assert shard_batch_spans() == []  # zero per-step device_put

            # control: numpy feeding pays the per-step transfer
            spmd.step(x[:8], y[:8])
            assert len(shard_batch_spans()) == 2  # data + label
            profiler.stop()
        finally:
            pipe.close()

    def test_pipeline_without_mesh_feeds_gluon_eagerly(self):
        """No mesh (eager/gluon path): leaves land on the default device
        unsharded and train a gluon Trainer step end to end."""
        mx.random.seed(5)
        net = nn.Dense(2)
        net.initialize()
        net(mx.nd.zeros((2, 4)))
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})
        loss_fn = gluon.loss.L2Loss()
        x = np.random.RandomState(1).randn(16, 4).astype(np.float32)
        y = np.zeros((16, 2), np.float32)
        it = NDArrayIter(x, y, batch_size=4)
        with DataPipeline(it, mesh=None, num_parts=1, part_index=0) as pipe:
            for b in pipe:
                with mx.autograd.record():
                    loss = loss_fn(net(b.data[0]), b.label[0])
                loss.backward()
                trainer.step(4)
        assert np.isfinite(float(loss.asnumpy().sum()))


@pytest.mark.slow
def test_bench_smoke():
    """The benchmark harness runs end to end in smoke mode and reports a
    sane result dict (the CI io tier runs this same path)."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "input_pipeline_bench",
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark", "opperf", "input_pipeline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    res = mod.run(steps=6, warmup=2, trials=1, host_ms=2.0, feat=32,
                  batch=8, layers=1)
    assert res["steps_per_sec"]["pipeline"] > 0
    assert res["steps_per_sec"]["off"] > 0
    assert "stalls_after_warmup" in res

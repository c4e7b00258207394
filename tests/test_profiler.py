"""Profiler bridge + engine fence (parity: [U:tests/python/unittest/
test_profiler.py] control-surface checks, the round-3 device-op aggregate
table and multi-device waitall, plus the ISSUE-5 tracing subsystem: span
recorder / chrome-trace round trip, per-step telemetry, slow-step
detector, strict counters, and the trace_report CLI)."""
import json
import logging
import os
import subprocess
import sys
import threading
import time
from collections import defaultdict

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, engine, profiler
from incubator_mxnet_tpu.gluon import Trainer, nn

import jax

from common import host_spans, span_inside

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def clean_profiler(tmp_path):
    """Arm-safe profiler state: fresh filename, stopped recorder, zeroed
    counters and an empty peer-metrics registry before AND after
    (profiler state is module-global; a leftover peer snapshot would make
    every slow step also log a straggler line)."""
    profiler.stop()
    profiler.stop_metrics()
    profiler.set_config(filename=str(tmp_path / "trace.json"),
                        ring_size=65536, slow_step_ms=None)
    profiler.reset_counters()
    with profiler._counter_lock:
        profiler._peer_metrics.clear()
    yield tmp_path
    profiler.stop()
    profiler.stop_metrics()
    profiler.set_config(slow_step_ms=None, ring_size=65536,
                        slow_step_auto=True, memory_sampling=True)
    profiler.reset_counters()
    with profiler._counter_lock:
        profiler._peer_metrics.clear()


def _paired_spans(events):
    """Pair B/E events per (pid, tid); returns the B events (with their
    matching E verified) and asserts nothing is unpaired."""
    stacks = defaultdict(list)
    spans = []
    for e in sorted((e for e in events if e.get("ph") in ("B", "E")),
                    key=lambda e: e["ts"]):
        k = (e["pid"], e["tid"])
        if e["ph"] == "B":
            stacks[k].append(e)
        else:
            assert stacks[k], f"E without open B at ts={e['ts']}"
            b = stacks[k].pop()
            assert e["ts"] >= b["ts"]
            b["_end"] = e["ts"]
            spans.append(b)
    assert not any(stacks.values()), "B events left unclosed"
    return spans


class TestProfiler:
    def test_scope_and_dumps(self):
        with profiler.scope("unit_region"):
            (mx.nd.ones((8, 8)) * 2).asnumpy()
        s = profiler.dumps()
        assert "Profile Statistics" in s
        assert "unit_region" in s

    def test_device_op_stats_parses_synthetic_xplane(self, tmp_path):
        from tensorflow.tsl.profiler.protobuf import xplane_pb2

        xs = xplane_pb2.XSpace()
        plane = xs.planes.add()
        plane.name = "/device:TPU:0"
        md = plane.event_metadata[1]
        md.id = 1
        md.name = "%fusion.42 = f32[8,8]{1,0} fusion(%p0), kind=kLoop"
        line = plane.lines.add()
        line.name = "XLA Ops"
        for _ in range(3):
            ev = line.events.add()
            ev.metadata_id = 1
            ev.duration_ps = int(2e9)  # 2 ms each
        d = tmp_path / "t"
        d.mkdir()
        with open(d / "host.xplane.pb", "wb") as f:
            f.write(xs.SerializeToString())
        rows = profiler._device_op_stats(str(d))
        assert len(rows) == 1
        name, count, total_s = rows[0]
        assert (name, count) == ("fusion", 3)
        # 3 events × 2e9 ps = 6e9 ps = 6 ms
        np.testing.assert_allclose(total_s, 6e-3, rtol=1e-9)

    def test_dumps_mentions_device_section_after_start_stop(self, tmp_path):
        profiler.set_config(filename=str(tmp_path / "prof.json"))
        profiler.start()
        (mx.nd.ones((16, 16)) @ mx.nd.ones((16, 16))).asnumpy()
        profiler.stop()
        s = profiler.dumps()
        assert "Profile Statistics" in s  # device rows depend on backend


def test_waitall_covers_all_devices():
    # dispatch work on every device of the 8-device mesh, then fence
    outs = []
    for d in jax.local_devices():
        x = jax.device_put(np.arange(1024.0), d)
        outs.append(x * 2 + 1)
    mx.nd.waitall()
    for o in outs:
        # after waitall every per-device queue has drained; reads are instant
        assert np.isfinite(np.asarray(o)).all()


# ---------------------------------------------------------------------------
# ISSUE 5: span recorder + chrome-trace round trip
# ---------------------------------------------------------------------------


class TestChromeTrace:
    def test_train_trace_roundtrip(self, clean_profiler):
        """The acceptance loop: start(); 3 train steps; dump() -> a
        chrome://tracing-valid JSON with spans from the dispatch-cache,
        bulk-flush, fused-step, and kvstore categories, each tagged with
        the correct (monotone) step id."""
        net = nn.Dense(8)
        net.initialize()
        trainer = Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.1, "momentum": 0.9},
                          kvstore="device")
        x = mx.nd.ones((4, 16))

        profiler.start()
        first_step = profiler.current_step()
        for _ in range(3):
            with autograd.record():
                loss = (net(x) ** 2).sum()
            loss.backward()
            with engine.bulk(8):  # eager metric chain -> bulk spans
                m = loss + 0.0
                for _ in range(4):
                    m = m * 1.0
            m.asnumpy()
            trainer.step(4)
        path = profiler.dump()

        with open(path) as f:
            doc = json.load(f)
        assert isinstance(doc["traceEvents"], list)
        spans = _paired_spans(doc["traceEvents"])
        cats = {s["cat"] for s in spans}
        assert {"dispatch", "bulk", "optimizer", "comms", "step",
                "trainer"} <= cats

        # step ids: monotone per thread in timestamp order
        per_tid = defaultdict(list)
        for s in sorted(spans, key=lambda s: s["ts"]):
            per_tid[s["tid"]].append(s["args"]["step"])
        for ids in per_tid.values():
            assert ids == sorted(ids)

        # step ids: CORRECT — every span inside a step span's [B, E] range
        # carries that step's id (asserted for the synchronous train-loop
        # categories; the three steps are first_step..first_step+2)
        step_spans = sorted((s for s in spans if s["cat"] == "step"),
                            key=lambda s: s["ts"])
        assert [s["args"]["step"] for s in step_spans] == [
            first_step, first_step + 1, first_step + 2]
        for s in spans:
            if s["cat"] not in ("optimizer", "comms", "trainer"):
                continue
            owner = [st for st in step_spans
                     if st["ts"] <= s["ts"] and s["_end"] <= st["_end"]]
            assert owner, f"span {s['name']} outside every step"
            assert s["args"]["step"] == owner[0]["args"]["step"]

        # at least one span of each acceptance name family
        names = {s["name"] for s in spans}
        assert "fused.group_apply" in names
        assert "bulk.flush" in names
        assert "kvstore.pushpull" in names
        assert names & {"dispatch.cache_hit", "dispatch.jit_compile"}

        # telemetry rode along: 3 closed steps with bucket splits
        steps = profiler.step_stats()[-3:]
        assert [s["step"] for s in steps] == [first_step, first_step + 1,
                                              first_step + 2]
        for s in steps:
            assert s["wall_ms"] >= s["host_ms"] >= 0
            assert s["device_ms"] >= 0

    def test_dump_finished_false_keeps_recording(self, clean_profiler):
        profiler.start()
        with profiler.span("before", "user"):
            pass
        path = profiler.dump(finished=False)
        assert profiler.state() == "running"
        assert profiler.recording_enabled()
        with profiler.span("after", "user"):
            pass
        path = profiler.dump()  # default finishes
        assert profiler.state() == "stopped"
        assert not profiler.recording_enabled()
        names = {s["name"] for s in
                 _paired_spans(json.load(open(path))["traceEvents"])}
        assert {"before", "after"} <= names

    def test_multithreaded_span_counts(self, clean_profiler):
        """Exact per-thread span counts under concurrency: the per-thread
        rings may not drop or duplicate spans."""
        n_threads, n_spans = 4, 250
        profiler.start()
        barrier = threading.Barrier(n_threads)

        def work():
            barrier.wait()
            for i in range(n_spans):
                t0 = time.perf_counter()
                profiler.record_span(f"mt_{i % 7}", "user", t0)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        profiler.stop()
        spans = _paired_spans(profiler._trace_events())
        per_tid = defaultdict(int)
        for s in spans:
            if s["name"].startswith("mt_"):
                per_tid[s["tid"]] += 1
        assert len(per_tid) == n_threads
        assert all(c == n_spans for c in per_tid.values())

    def test_ring_buffer_bounds_memory(self, clean_profiler):
        """Recording more spans than the ring capacity must not grow
        memory: the oldest spans are evicted and counted as dropped."""
        profiler.set_config(ring_size=64)
        profiler.start()
        for i in range(200):
            t0 = time.perf_counter()
            profiler.record_span(f"ring_{i}", "user", t0)
        stats = profiler.recorder_stats()
        profiler.stop()
        assert stats["spans"] == 64
        assert stats["dropped"] == 200 - 64
        spans = _paired_spans(profiler._trace_events())
        kept = sorted(int(s["name"].split("_")[1]) for s in spans
                      if s["name"].startswith("ring_"))
        assert kept == list(range(136, 200))  # oldest evicted, newest kept

    def test_ring_registry_bounded_under_thread_churn(self, clean_profiler):
        """Short-lived threads (a fresh prefetch worker per epoch) must not
        grow the retained-rings list without bound: dead threads' rings are
        evicted once the cap is exceeded."""
        profiler.set_config(ring_size=8)
        profiler.start()
        for i in range(profiler._MAX_RINGS + 20):
            t = threading.Thread(
                target=lambda: profiler.record_span("churn", "user",
                                                    time.perf_counter()))
            t.start()
            t.join()
        n_rings = profiler.recorder_stats()["threads"]
        profiler.stop()
        # cap + the handful of genuinely-alive threads at eviction time
        assert n_rings <= profiler._MAX_RINGS + 1


# ---------------------------------------------------------------------------
# ISSUE 5: per-step telemetry + slow-step detector
# ---------------------------------------------------------------------------


class TestStepTelemetry:
    def test_slow_step_detector_fires_exactly_once(self, clean_profiler,
                                                   caplog):
        profiler.set_config(slow_step_ms=50.0)
        profiler.start()
        with caplog.at_level(logging.WARNING,
                             logger="incubator_mxnet_tpu.profiler"):
            for _ in range(4):      # normal steps: well under 50 ms
                profiler.step_boundary()
            time.sleep(0.08)        # injected stall
            profiler.step_boundary()
            for _ in range(4):      # back to normal
                profiler.step_boundary()
        profiler.stop()
        slow_lines = [r for r in caplog.records if "slow step" in r.message]
        assert len(slow_lines) == 1
        msg = slow_lines[0].getMessage()
        assert "host-dispatch" in msg and "comms" in msg
        assert profiler.counters()["slow_step_detected"] == 1

    def test_slow_step_auto_percentile_mode(self, clean_profiler, caplog):
        """No explicit threshold: a step > mult x the rolling median is
        flagged once the window has enough history."""
        profiler.set_config(slow_step_ms=None, slow_step_auto=True,
                            slow_step_auto_mult=4.0)
        profiler.start()
        with caplog.at_level(logging.WARNING,
                             logger="incubator_mxnet_tpu.profiler"):
            for _ in range(20):
                time.sleep(0.01)
                profiler.step_boundary()
            time.sleep(0.3)         # >> 4x the ~10 ms median
            profiler.step_boundary()
        profiler.stop()
        auto = [r for r in caplog.records if "auto:" in r.message]
        assert len(auto) == 1

    def test_step_buckets_accumulate(self, clean_profiler):
        profiler.start()
        sid = profiler.current_step()
        t0 = time.perf_counter()
        profiler.record_span("kvstore.pushpull", "comms", t0, t0 + 0.010)
        profiler.record_span("dispatch.cache_hit", "dispatch", t0, t0 + 0.005)
        profiler.record_span("bulk.trace", "bulk", t0, t0 + 0.003)  # nested:
        profiler.step_boundary()                    # excluded from buckets
        profiler.stop()
        s = [s for s in profiler.step_stats() if s["step"] == sid][-1]
        assert s["comms_ms"] == pytest.approx(10.0, rel=0.3)
        assert s["host_ms"] == pytest.approx(5.0, rel=0.3)

    def test_memory_watermark_surface(self, clean_profiler):
        # CPU devices may expose no memory_stats: the sampler must stay
        # silent/empty, never raise
        profiler.start()
        profiler.step_boundary()
        profiler.step_boundary()
        profiler.stop()
        wm = profiler.memory_watermark()
        assert isinstance(wm, dict)
        assert all(isinstance(v, int) and v >= 0 for v in wm.values())


# ---------------------------------------------------------------------------
# ISSUE 5 satellites: strict counters, locked _tally, trace-error surfacing
# ---------------------------------------------------------------------------


class TestCounters:
    def test_incr_unknown_name_raises(self):
        typo = "dispatch_cache_hti"  # built dynamically elsewhere this
        with pytest.raises(KeyError):  # would silently report zeros forever
            profiler.incr(typo)

    def test_declare_counter_extension_path(self):
        profiler.declare_counter("test_custom_counter")
        profiler.incr("test_custom_counter", 3)
        assert profiler.counters()["test_custom_counter"] == 3
        profiler.reset_counters()
        assert profiler.counters()["test_custom_counter"] == 0

    def test_incr_exact_under_concurrency(self):
        profiler.reset_counters()
        n_threads, n_incr = 8, 500

        def work():
            for _ in range(n_incr):
                profiler.incr("dispatch_cache_hit")

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert profiler.counters()["dispatch_cache_hit"] == n_threads * n_incr
        profiler.reset_counters()

    def test_tally_exact_under_concurrency(self):
        """Satellite 1: concurrent scopes must not drop _agg tallies (the
        old unlocked read-modify-write did) and dumps() must iterate a
        stable snapshot."""
        name = "tally_race_probe"
        with profiler._counter_lock:
            profiler._agg.pop(name, None)
        n_threads, n_tallies = 8, 400
        stop = threading.Event()

        def dump_loop():  # concurrent reader: would blow up on a mutating
            while not stop.is_set():  # dict pre-fix
                profiler.dumps()

        reader = threading.Thread(target=dump_loop)
        reader.start()

        def work():
            for _ in range(n_tallies):
                profiler._tally(name, 0.001)

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        reader.join()
        cnt, tot = profiler._agg[name]
        assert cnt == n_threads * n_tallies
        assert tot == pytest.approx(cnt * 0.001)
        with profiler._counter_lock:
            profiler._agg.pop(name, None)

    def test_trace_error_warns_once_and_counts(self, clean_profiler,
                                               monkeypatch):
        """Satellite 3: a broken xprof install is diagnosable — RuntimeWarning
        (once) + profiler_trace_error counter, and the span recorder still
        arms."""
        def boom(*a, **k):
            raise RuntimeError("no xprof here")

        monkeypatch.setattr(jax.profiler, "start_trace", boom)
        monkeypatch.setattr(profiler, "_trace_warned", False)
        with pytest.warns(RuntimeWarning, match="profiler_trace_error"):
            profiler.start()
        assert profiler.recording_enabled()  # python spans still captured
        assert profiler.counters()["profiler_trace_error"] == 1
        profiler.stop()  # must not call stop_trace (xprof never started)
        assert profiler.counters()["profiler_trace_error"] == 1


# ---------------------------------------------------------------------------
# ISSUE 5: disabled-recorder overhead + trace_report CLI
# ---------------------------------------------------------------------------


def test_disabled_recorder_overhead_smoke():
    """The eager-dispatch chain runs with the recorder OFF: no spans may be
    recorded and the benchmark harness must be unperturbed (the <3% number
    is measured by the full paired-median run, not asserted here)."""
    import importlib.util

    profiler.stop()
    assert not profiler.recording_enabled()
    before = profiler.recorder_stats()["spans"]
    path = os.path.join(_REPO, "benchmark", "opperf", "eager_dispatch.py")
    spec = importlib.util.spec_from_file_location("eager_dispatch_bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    line = mod.run(n_ops=6, iters=2, shape=(4, 4), warmup=1)
    for mode in ("uncached", "cached_jit", "bulked"):
        assert line["ops_per_sec"][mode]["elemwise"] > 0
    assert profiler.recorder_stats()["spans"] == before


class TestTraceReport:
    def _synthetic_trace(self, path):
        evs = []
        t = 1000.0
        for step in (1, 2, 3):
            evs.append({"ph": "B", "name": "step", "cat": "step", "ts": t,
                        "pid": 1, "tid": 7, "args": {"step": step}})
            evs.append({"ph": "B", "name": "fused.group_apply",
                        "cat": "optimizer", "ts": t + 10, "pid": 1,
                        "tid": 7, "args": {"step": step}})
            evs.append({"ph": "E", "name": "fused.group_apply",
                        "cat": "optimizer", "ts": t + 60, "pid": 1, "tid": 7})
            evs.append({"ph": "E", "name": "step", "cat": "step",
                        "ts": t + 100, "pid": 1, "tid": 7})
            t += 200
        doc = {"traceEvents": evs, "displayTimeUnit": "ms",
               "otherData": {"steps": [
                   {"step": s, "wall_ms": 0.1, "host_ms": 0.05,
                    "comms_ms": 0.0, "device_ms": 0.05} for s in (1, 2, 3)]}}
        with open(path, "w") as f:
            json.dump(doc, f)
        return path

    def test_report_on_synthetic_trace(self, tmp_path):
        trace = self._synthetic_trace(str(tmp_path / "synth.json"))
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "trace_report.py"),
             trace, "--top", "5"],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert "Per-category totals" in out.stdout
        assert "optimizer" in out.stdout
        assert "Step-time histogram" in out.stdout
        assert "fused.group_apply" in out.stdout

    def test_report_rejects_invalid_trace(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "trace_report.py"),
             str(bad)],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 2

    def test_report_on_real_dump(self, clean_profiler, tmp_path):
        profiler.start()
        with profiler.span("real_work", "user"):
            (mx.nd.ones((8, 8)) * 3).asnumpy()
        profiler.step_boundary()
        path = profiler.dump()
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "trace_report.py"),
             path], capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert "real_work" in out.stdout


# ---------------------------------------------------------------------------
# ISSUE 7: pause/resume vs the telemetry window + metrics snapshots
# ---------------------------------------------------------------------------

sys.path.insert(0, os.path.join(_REPO, "tools"))
import trace_merge  # noqa: E402


def _peer_snap(rank, host="peer-host", seq=1, wall=900.0, comms=700.0,
               step=77):
    return {"schema": 1, "rank": rank, "host": host, "pid": 10000 + rank,
            "seq": seq, "time_unix": time.time(),
            "counters": {"bulk_flush": 1},
            "last_step": {"step": step, "wall_ms": wall, "host_ms": 50.0,
                          "comms_ms": comms,
                          "device_ms": wall - 50.0 - comms},
            "window": {"n": 1, "wall_ms_median": wall, "wall_ms_max": wall},
            "memory_watermark_bytes": {}}


class TestPauseResumeWindow:
    def test_pause_gap_not_billed_to_window(self, clean_profiler):
        """A pause()d interval must not pollute step_stats(): the first
        post-resume boundary anchors at resume time, so the gap never
        appears as a giant step wall."""
        profiler.start()
        profiler.step_boundary()                    # ~0 ms step
        time.sleep(0.01)
        profiler.step_boundary()                    # ~10 ms step
        profiler.pause()
        time.sleep(0.25)                            # the paused gap
        profiler.resume()
        time.sleep(0.01)
        profiler.step_boundary()                    # measured from resume
        profiler.stop()
        steps = profiler.step_stats()
        assert len(steps) == 3                      # window survived pause
        assert all(s["wall_ms"] < 200.0 for s in steps), steps

    def test_dump_unfinished_keeps_window_accumulating(self, clean_profiler):
        profiler.start()
        time.sleep(0.002)
        profiler.step_boundary()
        profiler.dump(finished=False)
        assert profiler.recording_enabled()
        time.sleep(0.002)
        profiler.step_boundary()
        profiler.dump()
        assert len(profiler.step_stats()) == 2

    def test_metrics_snapshot_monotone_across_session_events(
            self, clean_profiler):
        """Snapshot monotonicity: seq/time/counters/window size never go
        backwards across boundaries, mid-run dumps, and pause/resume."""
        profiler.start()
        profiler.step_boundary()
        s1 = profiler.metrics_snapshot()
        time.sleep(0.005)
        profiler.step_boundary()
        profiler.dump(finished=False)
        s2 = profiler.metrics_snapshot()
        profiler.pause()
        profiler.resume()
        s3 = profiler.metrics_snapshot()
        profiler.stop()
        for a, b in ((s1, s2), (s2, s3)):
            assert b["seq"] > a["seq"]
            assert b["time_unix"] >= a["time_unix"]
            assert b["window"]["n"] >= a["window"]["n"]
            for k, v in a["counters"].items():
                assert b["counters"][k] >= v, k
        assert s2["window"]["n"] == 2
        assert s2["last_step"]["wall_ms"] >= 4.0


# ---------------------------------------------------------------------------
# ISSUE 7: live metrics export (registry, Prometheus endpoint, JSONL)
# ---------------------------------------------------------------------------


class TestMetricsExport:
    def test_render_prometheus_includes_local_and_peers(self, clean_profiler):
        profiler.start()
        time.sleep(0.003)
        profiler.step_boundary()
        time.sleep(0.003)
        profiler.step_boundary()
        profiler.publish_peer_metrics(_peer_snap(9))
        txt = profiler.render_prometheus()
        profiler.stop()
        me = profiler.process_info()["rank"]
        assert f'mxnet_profiler_counter_total{{counter="bulk_flush",rank="9"' \
            in txt
        assert f'rank="{me}"' in txt
        assert 'mxnet_step_last_wall_ms{' in txt
        assert 'mxnet_step_last_comms_ms{rank="9"' in txt
        assert "# TYPE mxnet_profiler_counter_total counter" in txt
        assert "# TYPE mxnet_step_last_wall_ms gauge" in txt

    def test_peer_registry_replaces_by_seq_and_pid(self, clean_profiler):
        profiler.publish_peer_metrics(_peer_snap(4, seq=5, wall=100.0))
        profiler.publish_peer_metrics(_peer_snap(4, seq=3, wall=999.0))
        assert profiler.peer_metrics()[4]["last_step"]["wall_ms"] == 100.0
        restarted = _peer_snap(4, seq=1, wall=50.0)
        restarted["pid"] = 4242                     # a restarted peer wins
        profiler.publish_peer_metrics(restarted)
        assert profiler.peer_metrics()[4]["last_step"]["wall_ms"] == 50.0

    def test_http_endpoint_serves_cluster(self, clean_profiler):
        import urllib.request

        profiler.start()
        time.sleep(0.003)
        profiler.step_boundary()
        time.sleep(0.003)
        profiler.step_boundary()
        profiler.publish_peer_metrics(_peer_snap(9))
        port = profiler.start_metrics(port=0)       # explicit 0 = ephemeral
        try:
            assert port and port == profiler.metrics_server_port()
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
            assert 'mxnet_profiler_counter_total' in body
            assert 'rank="9"' in body               # the peer is on the scrape
            assert 'mxnet_step_last_wall_ms' in body
            doc = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics.json", timeout=10).read())
            assert "9" in doc["peers"]
            assert doc["local"]["rank"] == profiler.process_info()["rank"]
            assert profiler.counters()["metrics_scrape"] >= 2
        finally:
            profiler.stop_metrics()
        assert profiler.metrics_server_port() is None

    def test_jsonl_exporter_writes_monotone_snapshots(self, clean_profiler,
                                                     tmp_path):
        path = tmp_path / "metrics.jsonl"
        profiler.start()
        profiler.step_boundary()
        profiler.start_metrics(port=None, jsonl=str(path), interval_s=0.05)
        time.sleep(0.35)
        profiler.stop_metrics()
        profiler.stop()
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        assert len(lines) >= 2
        assert [l["seq"] for l in lines] == sorted(l["seq"] for l in lines)
        assert all(l["schema"] == 1 for l in lines)
        assert all(l["rank"] == lines[0]["rank"] for l in lines)


# ---------------------------------------------------------------------------
# ISSUE 7: cross-rank straggler attribution
# ---------------------------------------------------------------------------


class TestStragglerAttribution:
    def test_straggler_named_exactly_once_per_anomalous_step(
            self, clean_profiler, caplog):
        profiler.set_config(slow_step_ms=30.0)
        profiler.start()
        profiler.publish_peer_metrics(_peer_snap(5, host="worker-h5",
                                                 wall=900.0, comms=700.0))
        with caplog.at_level(logging.WARNING,
                             logger="incubator_mxnet_tpu.profiler"):
            profiler.step_boundary()            # fast step
            time.sleep(0.05)
            profiler.step_boundary()            # THE anomalous step
            profiler.step_boundary()            # fast again
        profiler.stop()
        lines = [r for r in caplog.records if "straggler" in r.message]
        assert len(lines) == 1
        msg = lines[0].getMessage()
        assert "rank 5" in msg and "worker-h5" in msg
        assert "host-dispatch" in msg and "comms" in msg \
            and "device/other" in msg
        assert "700.3" not in msg               # numbers come from the snap
        assert "900.0 ms" in msg and "700.0 ms" in msg
        assert profiler.counters()["straggler_detected"] == 1

    def test_no_straggler_line_without_peer_data(self, clean_profiler,
                                                 caplog):
        profiler.set_config(slow_step_ms=30.0)
        profiler.start()
        with caplog.at_level(logging.WARNING,
                             logger="incubator_mxnet_tpu.profiler"):
            profiler.step_boundary()
            time.sleep(0.05)
            profiler.step_boundary()
        profiler.stop()
        assert [r for r in caplog.records if "slow step" in r.message]
        assert not [r for r in caplog.records if "straggler" in r.message]
        assert profiler.counters()["straggler_detected"] == 0

    def test_straggler_report_compares_local_and_peers(self, clean_profiler):
        profiler.start()
        time.sleep(0.003)
        profiler.step_boundary()
        time.sleep(0.003)
        profiler.step_boundary()
        assert profiler.straggler_report() is None  # one rank: nothing to
        profiler.publish_peer_metrics(_peer_snap(2, wall=5000.0))  # compare
        rep = profiler.straggler_report()
        profiler.stop()
        assert rep["rank"] == 2 and rep["wall_ms"] == 5000.0
        assert rep["ranks_compared"] == 2
        assert rep["step"] == 77


# ---------------------------------------------------------------------------
# ISSUE 7: multi-rank trace merge + gz round trip
# ---------------------------------------------------------------------------


class TestTraceMerge:
    def _rank_doc(self, rank, epoch_unix, clock_offset_s, host="hostX"):
        evs = [{"ph": "M", "pid": 1234, "name": "process_name",
                "args": {"name": "local"}}]
        t = 100.0
        for step in (1, 2):
            evs += [{"ph": "B", "name": "step", "cat": "step", "ts": t,
                     "pid": 1234, "tid": 7, "args": {"step": step}},
                    {"ph": "E", "name": "step", "cat": "step", "ts": t + 50,
                     "pid": 1234, "tid": 7}]
            t += 60
        return {"traceEvents": evs, "displayTimeUnit": "ms",
                "otherData": {"process": {
                    "rank": rank, "host": host, "pid": 1234,
                    "epoch_unix": epoch_unix,
                    "clock_offset_s": clock_offset_s, "clock_rtt_s": 0.001},
                    "counters": {}, "steps": []}}

    def test_merge_offset_corrects_and_labels_ranks(self, tmp_path):
        # rank 1's wall clock runs 1 s AHEAD (offset +1.0) and its process
        # started 3 s after rank 0: corrected shift = 3 - 1 = 2 s
        p0, p1 = str(tmp_path / "r0.json"), str(tmp_path / "r1.json")
        json.dump(self._rank_doc(0, 1000.0, 0.0, "hostA"), open(p0, "w"))
        json.dump(self._rank_doc(1, 1003.0, 1.0, "hostB"), open(p1, "w"))
        merged = trace_merge.merge_traces([p0, p1])
        names = {e["pid"]: e["args"]["name"] for e in merged["traceEvents"]
                 if e.get("ph") == "M" and e.get("name") == "process_name"}
        assert names == {0: "rank 0 (hostA)", 1: "rank 1 (hostB)"}
        ts = {pid: [e["ts"] for e in merged["traceEvents"]
                    if e.get("ph") == "B" and e["pid"] == pid]
              for pid in (0, 1)}
        assert ts[0] == [100.0, 160.0]
        assert ts[1] == [100.0 + 2e6, 160.0 + 2e6]
        summary = trace_merge.check_merged(merged, expect_ranks=2)
        assert summary["steps_per_rank"] == {0: 2, 1: 2}
        assert merged["otherData"]["ranks"]["1"]["shift_us"] == 2e6

    def test_merge_rejects_duplicate_ranks(self, tmp_path):
        p0, p1 = str(tmp_path / "a.json"), str(tmp_path / "b.json")
        json.dump(self._rank_doc(0, 1000.0, 0.0), open(p0, "w"))
        json.dump(self._rank_doc(0, 1001.0, 0.0), open(p1, "w"))
        with pytest.raises(ValueError, match="duplicate rank"):
            trace_merge.merge_traces([p0, p1])

    def test_check_catches_non_monotone_steps(self, tmp_path):
        doc = self._rank_doc(0, 1000.0, 0.0)
        for e in doc["traceEvents"]:
            if e.get("args", {}).get("step") == 2:
                e["args"]["step"] = 1               # duplicate id
        p = str(tmp_path / "bad.json")
        json.dump(doc, open(p, "w"))
        merged = trace_merge.merge_traces([p])
        with pytest.raises(ValueError, match="monotone"):
            trace_merge.check_merged(merged)

    def test_real_dump_gz_roundtrip_through_report(self, clean_profiler,
                                                   tmp_path, monkeypatch):
        """dump() honors MXNET_PROFILER_TRACE_GZ=1 and the gz file flows
        through trace_report unchanged."""
        monkeypatch.setenv("MXNET_PROFILER_TRACE_GZ", "1")
        profiler.start()
        with profiler.span("gz_work", "user"):
            time.sleep(0.002)
        profiler.step_boundary()
        path = profiler.dump()
        assert path.endswith(".json.gz") and os.path.exists(path)
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "trace_report.py"),
             path], capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert "gz_work" in out.stdout

    def test_report_merge_mode_and_empty_diagnosis(self, tmp_path):
        p0, p1 = str(tmp_path / "r0.json"), str(tmp_path / "r1.json")
        json.dump(self._rank_doc(0, 1000.0, 0.0), open(p0, "w"))
        json.dump(self._rank_doc(1, 1000.5, 0.0), open(p1, "w"))
        merged = str(tmp_path / "merged.json")
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "trace_report.py"),
             p0, p1, "--merge", merged],
            capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert "Per-rank attribution" in out.stdout
        assert "hostX" in out.stdout
        assert os.path.exists(merged)
        empty = tmp_path / "empty.json"
        empty.write_text("")
        out = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "trace_report.py"),
             str(empty)], capture_output=True, text=True, timeout=120)
        assert out.returncode == 2
        assert "empty trace file" in out.stderr
        assert "Traceback" not in out.stderr

    def test_dump_carries_process_metadata(self, clean_profiler):
        profiler.start()
        profiler.step_boundary()
        path = profiler.dump()
        proc = json.load(open(path))["otherData"]["process"]
        assert proc["rank"] == profiler.process_info()["rank"]
        assert proc["host"] and proc["pid"] == os.getpid()
        assert proc["epoch_unix"] > 0


@pytest.mark.slow
def test_dist_trace_smoke_two_workers():
    """The CI acceptance path end to end: 2 dist_async workers -> per-rank
    traces -> offset-corrected merge with one process row per rank; rank
    0's /metrics scrape aggregates both ranks; straggler attribution fires
    exactly once (tools/dist_trace_smoke.py, also run by ci.sh profiler)."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "dist_trace_smoke.py")],
        env=env, capture_output=True, text=True, timeout=280)
    sys.stdout.write(out.stdout[-2000:])
    sys.stderr.write(out.stderr[-2000:])
    assert out.returncode == 0
    assert "dist trace smoke OK" in out.stdout


class TestStragglerRegistryHygiene:
    def test_schema_light_peer_snapshot_cannot_break_step_boundary(
            self, clean_profiler, caplog):
        """A peer on an older build may heartbeat a snapshot whose
        last_step lacks bucket fields; the straggler comparison must
        degrade, never raise out of the training hot path."""
        profiler.set_config(slow_step_ms=30.0)
        profiler.start()
        profiler.publish_peer_metrics(
            {"rank": 8, "pid": 1, "seq": 1, "time_unix": time.time(),
             "last_step": {"step": 3, "wall_ms": 5000.0}})   # no buckets
        with caplog.at_level(logging.WARNING,
                             logger="incubator_mxnet_tpu.profiler"):
            profiler.step_boundary()
            time.sleep(0.05)
            profiler.step_boundary()                 # must not raise
        profiler.stop()
        lines = [r for r in caplog.records if "straggler" in r.message]
        assert len(lines) == 1 and "rank 8" in lines[0].getMessage()
        # and a last_step that is not even a dict is skipped outright
        profiler.publish_peer_metrics(
            {"rank": 9, "pid": 1, "seq": 1, "last_step": "garbage"})
        rep = profiler.straggler_report()
        assert rep is None or rep["rank"] != 9

    def test_stale_peer_snapshot_aged_out_of_comparison(self,
                                                       clean_profiler):
        profiler.start()
        time.sleep(0.003)
        profiler.step_boundary()
        time.sleep(0.003)
        profiler.step_boundary()
        old = _peer_snap(6, wall=9000.0)
        old["time_unix"] = time.time() - 3600.0      # an hour-dead rank
        profiler.publish_peer_metrics(old)
        assert profiler.straggler_report() is None   # nothing fresh to
        profiler.publish_peer_metrics(_peer_snap(7, wall=8000.0))  # compare
        rep = profiler.straggler_report()
        profiler.stop()
        assert rep["rank"] == 7                      # ghost never wins

    def test_forget_peer_metrics_on_deregister_and_eviction(self):
        """The PS purges a departed rank's telemetry from its table AND
        the co-located peer registry — clean leave and lease eviction."""
        from incubator_mxnet_tpu.kvstore.async_ps import (AsyncClient,
                                                          ParameterServer)

        ps = ParameterServer(num_workers=2, port=0, lease_s=0.4)
        try:
            c = AsyncClient(*ps.address)
            snap = {"rank": 1, "pid": 1, "seq": 1, "time_unix": time.time(),
                    "last_step": {"step": 1, "wall_ms": 1.0, "host_ms": 0.0,
                                  "comms_ms": 0.0, "device_ms": 1.0}}
            c.request("register", 1)
            c.request("heartbeat", 1, snap)
            assert 1 in c.request("metrics")
            assert 1 in profiler.peer_metrics()
            c.request("deregister", 1)
            assert 1 not in c.request("metrics")
            assert 1 not in profiler.peer_metrics()
            # eviction path: register + one beat, then let the lease lapse
            c.request("register", 2)
            c.request("heartbeat", 2, dict(snap, rank=2))
            assert 2 in c.request("metrics")
            deadline = time.monotonic() + 10.0
            while 2 in c.request("metrics"):
                assert time.monotonic() < deadline, "reaper never purged"
                time.sleep(0.1)
            assert 2 not in profiler.peer_metrics()
        finally:
            ps.stop()
            with profiler._counter_lock:
                profiler._peer_metrics.clear()


# ---------------------------------------------------------------------------
# ISSUE 25: one span, two sinks — the device trace and the ring
# ---------------------------------------------------------------------------


def _ring_names(prefix):
    with profiler._counter_lock:
        rings = list(profiler._rings)
    return [ev[0] for r in rings for ev in r.snapshot()
            if ev[0].startswith(prefix)]


@pytest.mark.parametrize("session", ["jax_start_trace", "mx_profiler_start"])
def test_span_reaches_the_device_trace_whoever_started_it(clean_profiler,
                                                          session):
    """A span is found on the /host:CPU plane of the session's xplane with
    its args, nested inside its parent; the ring holds the same names only
    when ``mx.profiler.start()`` armed it."""
    tmp = clean_profiler
    before = profiler.recorder_stats()["spans"]
    if session == "jax_start_trace":
        jax.profiler.start_trace(str(tmp / "xp"))
    else:
        profiler.start()
    try:
        with profiler.span("t25.parent", "user", {"step": 7}):
            with profiler.span("t25.child", "user",
                               {"request": 3, "pool": 128}):
                time.sleep(0.002)
        with profiler.scope("t25.scope"):
            pass
    finally:
        if session == "jax_start_trace":
            jax.profiler.stop_trace()
            trace_dir = tmp / "xp"
            in_ring = _ring_names("t25.")
        else:
            in_ring = _ring_names("t25.")
            trace_dir = profiler._state["dir"]
            profiler.stop()
    spans = {s[3]: s for s in host_spans(trace_dir, "t25.")}
    assert set(spans) == {"t25.parent", "t25.child", "t25.scope"}
    assert spans["t25.parent"][4]["step"] == "7"
    assert spans["t25.child"][4] == {"request": "3", "pool": "128"}
    assert span_inside(spans["t25.child"], spans["t25.parent"])
    assert not span_inside(spans["t25.scope"], spans["t25.parent"])
    if session == "jax_start_trace":
        assert in_ring == []
        assert profiler.recorder_stats()["spans"] == before
    else:
        assert sorted(in_ring) == ["t25.child", "t25.parent", "t25.scope"]


def test_span_with_both_sinks_off_changes_nothing(clean_profiler):
    stats = profiler.recorder_stats()
    agg = dict(profiler._agg)
    for i in range(1000):
        with profiler.span("t25off.x", "trainer", {"step": i}):
            pass
    assert profiler.recorder_stats() == stats   # an older session's rings stay
    assert _ring_names("t25off.") == [] and dict(profiler._agg) == agg


def test_span_keeps_the_step_id_of_its_entry(clean_profiler):
    """A span that contains its step boundary stays in the step it opened
    in (``spmd.step`` closes after ``step_boundary``)."""
    profiler.start()
    profiler.step_boundary()
    sid = profiler.current_step()
    with profiler.span("t25.straddle", "trainer"):
        profiler.step_boundary()
    assert profiler.current_step() == sid + 1
    with profiler._counter_lock:
        rings = list(profiler._rings)
    got = [ev for r in rings for ev in r.snapshot()
           if ev[0] == "t25.straddle"]
    profiler.stop()
    assert len(got) == 1 and got[0][4] == sid


def _tiny_spmd(builder):
    """A two-layer SPMDTrainer through each of the four ``pure_step``
    builders, with a batch it accepts."""
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.parallel import (SPMDTrainer, fsdp_rules,
                                              make_mesh)

    mx.random.seed(11)
    net = nn.HybridSequential()
    net.add(nn.Dense(32, activation="relu"), nn.Dense(32, activation="relu"),
            nn.Dense(8))
    net.initialize()
    net(mx.nd.zeros((2, 16)))
    kw = {"plain": dict(mesh=make_mesh()),
          "compressed": dict(mesh=make_mesh(), compression="int8"),
          "compressed_sharded": dict(mesh=make_mesh(fsdp=2),
                                     rules=fsdp_rules(), compression="int8"),
          "pipeline": dict(mesh=make_mesh(),
                           stages=net.split_stages([2, 1]),
                           pipeline={"schedule": "1f1b",
                                     "n_microbatches": 2})}[builder]
    tr = SPMDTrainer(net, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
                     {"learning_rate": 0.01}, **kw)
    rng = np.random.RandomState(0)
    x = rng.randn(16, 16).astype(np.float32)
    y = rng.randint(0, 8, (16,)).astype(np.float32)
    return net, tr, x, y


@pytest.mark.parametrize("builder", ["plain", "compressed",
                                     "compressed_sharded", "pipeline"])
def test_compiled_step_carries_phase_and_block_names(builder):
    net, tr, x, y = _tiny_spmd(builder)
    assert (tr._stages is not None) == (builder == "pipeline")
    assert (tr._comm_cfg is not None) == builder.startswith("compressed")
    if tr._comm_cfg is not None:
        assert bool(tr._comm_cfg["sharded"]) == (builder
                                                 == "compressed_sharded")
    arrays = tr.shard_batch(x, y)
    fn = tr._build_step(arrays)
    assert fn.__name__ == "pure_step"   # the trace reads jit_pure_step(
    comm = () if tr._comm_state is None else (tr._comm_state,)
    text = fn.lower(tr._step_key(), np.int32(1), np.float32(0.01),
                    np.float32(1.0), tr._param_arrays, tr._opt_states,
                    *comm, *arrays).as_text(debug_info=True)
    dense = net[0].name
    for needle in ("spmd.forward", "spmd.loss", "spmd.optimizer",
                   "transpose(jvp(spmd.forward", f"/{dense}/"):
        assert needle in text, needle
    assert ("spmd.grad_sync" in text) == builder.startswith("compressed")
    assert "jit(pure_step)" in text


def test_block_names_are_entered_only_while_tracing():
    from incubator_mxnet_tpu.gluon.block import trace_scope

    net = nn.Dense(4)
    net.initialize()
    net(mx.nd.zeros((2, 3)))
    params = list(net.collect_params().values())

    def traced(w, b, x):
        with trace_scope(params, [w, b], jax.random.PRNGKey(0), False):
            return net(mx.nd.NDArray(x))._data

    def eager(x):
        return net(mx.nd.NDArray(x))._data

    arrs = [p.data()._data for p in params]
    x = np.zeros((2, 3), np.float32)
    assert f"[{net.name}]" in str(jax.make_jaxpr(traced)(*arrs, x).pretty_print(
        name_stack=True))
    assert net.name not in str(jax.make_jaxpr(eager)(x).pretty_print(
        name_stack=True))


def test_step_spans_nest_once_per_call_in_both_sinks(clean_profiler):
    """Per ``trainer.step`` call: one root span with ``step=``, and
    ``spmd.step.args`` / ``.enqueue`` / ``.obs`` once each inside it, on the
    xplane's host plane AND in the ring; ``spmd.shard_batch`` only where a
    host batch is transferred."""
    _, tr, x, y = _tiny_spmd("plain")
    root = "spmd.step"
    host, staged = (x, y), tr.shard_batch(x, y)

    def call(host_batch):
        return tr.step(*(host if host_batch else staged))

    call(True)                       # compile outside the session
    profiler.start()
    try:
        first = tr._t + 1
        call(False)
        call(True)
        float(call(False).asnumpy())
        ring = _ring_names("spmd.")
        trace_dir = profiler._state["dir"]
    finally:
        profiler.stop()
    spans = host_spans(trace_dir, "spmd.")
    roots = sorted((s for s in spans if s[3] == root), key=lambda s: s[1])
    assert len(roots) == 3
    assert [int(r[4]["step"]) for r in roots] == [first, first + 1,
                                                  first + 2]
    for child in ("spmd.step.args", "spmd.step.enqueue", "spmd.step.obs"):
        inner = [s for s in spans if s[3] == child]
        assert len(inner) == 3, child
        for r in roots:
            assert sum(span_inside(s, r) for s in inner) == 1, child
    transfers = [s for s in spans if s[3] == "spmd.shard_batch"]
    assert len(transfers) == 2 and all(span_inside(t, roots[1])
                                       for t in transfers)
    assert all(int(t[4]["bytes"]) > 0 for t in transfers)
    assert sorted(ring) == sorted(s[3] for s in spans)

"""Goodput ledger (ISSUE 20): every second of a run accounted, exclusively.

Unit coverage of the run-scoped wall-clock decomposition in
``profiler`` — bucket exclusivity (the buckets sum to wall by
construction), downtime attribution, pause/resume wall semantics,
cluster aggregation naming the worst rank, and the metrics-provider /
Prometheus / trace-dump export surfaces — plus THE acceptance: a
supervised 2-proc dist_sync run with one injected SIGKILL restart and
one injected data stall, where the restart gap and the stall land in
their own buckets and the buckets sum to wall within 5%.
"""
import json
import os
import signal
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUPERVISE = os.path.join(ROOT, "tools", "supervise.py")

from incubator_mxnet_tpu import profiler


@pytest.fixture
def prof(tmp_path):
    """Armed profiler with a FRESH goodput ledger; restores + re-zeroes
    on exit so the run-scoped ledger never leaks across tests."""
    profiler.stop()
    profiler.set_config(filename=str(tmp_path / "trace.json"))
    profiler.start()
    profiler.reset_goodput()
    yield profiler
    profiler.stop()
    profiler.reset_goodput()


def _span(name, cat, dur):
    """Record a completed span of ``dur`` seconds ending now (the span
    recorder clamps t0 to the arm time, so keep durations < the armed
    window)."""
    now = time.perf_counter()
    profiler.record_span(name, cat, now - dur, now)


class TestLedgerExclusivity:
    def test_buckets_sum_to_wall_and_land_exclusively(self, prof):
        time.sleep(0.12)
        _span("io.wait", "io", 0.05)            # -> data_wait
        _span("kvstore.pushpull", "comms", 0.03)  # -> comm
        _span("elastic.snapshot", "elastic", 0.02)  # -> checkpoint
        snap = profiler.goodput_snapshot()
        b = snap["buckets_s"]
        # exclusivity invariant: compute is the clamped residual, so the
        # buckets sum to wall (per-bucket 6dp rounding leaves ~1e-5)
        assert sum(b.values()) == pytest.approx(snap["wall_s"], abs=1e-4)
        assert b["data_wait"] == pytest.approx(0.05, abs=0.02)
        assert b["comm"] == pytest.approx(0.03, abs=0.02)
        assert b["checkpoint"] == pytest.approx(0.02, abs=0.02)
        assert b["compute"] > 0
        assert 0 < snap["goodput"] <= 1
        assert snap["overhead_s"] == pytest.approx(
            snap["wall_s"] - b["compute"], abs=1e-4)

    def test_off_thread_spans_do_not_bill(self, prof):
        import threading

        def off_thread():
            _span("io.wait", "io", 0.05)

        t = threading.Thread(target=off_thread)
        t.start()
        t.join()
        assert profiler.goodput_snapshot()["buckets_s"]["data_wait"] == 0

    def test_parent_pushpull_is_not_double_billed(self, prof):
        # kvstore.bucketed_pushpull is the PARENT of per-bucket pushpull
        # legs — only the leaves bill, or comm would double-count
        _span("kvstore.bucketed_pushpull", "comms", 0.5)
        assert profiler.goodput_snapshot()["buckets_s"]["comm"] == 0


class TestDowntime:
    def test_downtime_lands_in_bucket_and_grows_wall(self, prof):
        w0 = profiler.goodput_snapshot()["wall_s"]
        profiler.record_downtime(0.25, "elastic_restart")
        snap = profiler.goodput_snapshot()
        assert snap["buckets_s"]["downtime"] == pytest.approx(0.25)
        # downtime happened while the process did not exist: wall grows
        # by the same amount (the buckets-sum-to-wall invariant)
        assert snap["wall_s"] >= w0 + 0.25
        assert snap["downtime_detail"]["elastic_restart"] == pytest.approx(0.25)
        assert ["downtime", 0.25] in snap["top_overhead"]
        assert sum(snap["buckets_s"].values()) == pytest.approx(
            snap["wall_s"], abs=1e-4)

    def test_nonpositive_downtime_is_a_noop(self, prof):
        before = profiler.counters()["goodput_downtime_ms"]
        profiler.record_downtime(0.0)
        profiler.record_downtime(-5.0)
        assert profiler.goodput_snapshot()["buckets_s"]["downtime"] == 0
        assert profiler.counters()["goodput_downtime_ms"] == before

    def test_downtime_counter_tracks_ms(self, prof):
        before = profiler.counters()["goodput_downtime_ms"]
        profiler.record_downtime(0.125, "elastic_restart")
        assert profiler.counters()["goodput_downtime_ms"] == before + 125


class TestPauseResume:
    def test_wall_is_monotone_and_freezes_while_paused(self, prof):
        time.sleep(0.02)
        w1 = profiler.goodput_snapshot()["wall_s"]
        profiler.pause()
        w2 = profiler.goodput_snapshot()["wall_s"]
        time.sleep(0.06)
        w3 = profiler.goodput_snapshot()["wall_s"]
        assert w1 <= w2  # monotone
        # frozen: the pause gap must NOT be billed (it would otherwise
        # inflate compute — nothing observed the process meanwhile)
        assert w3 == pytest.approx(w2, abs=5e-3)
        profiler.resume()
        time.sleep(0.02)
        w4 = profiler.goodput_snapshot()["wall_s"]
        assert w4 > w3
        assert w4 - w3 < 0.06  # the paused 60 ms never entered the wall

    def test_start_does_not_reset_the_run_ledger(self, prof, tmp_path):
        profiler.record_downtime(0.2, "elastic_restart")
        profiler.stop()
        profiler.set_config(filename=str(tmp_path / "trace2.json"))
        profiler.start()   # fresh SPAN session — same RUN ledger
        snap = profiler.goodput_snapshot()
        assert snap["buckets_s"]["downtime"] == pytest.approx(0.2)


class TestClusterAggregation:
    def _peer(self, rank, wall, compute, **buckets):
        g = {"wall_s": wall, "goodput": compute / wall,
             "compute_s": compute}
        g.update({f"{k}_s": v for k, v in buckets.items()})
        return {"schema": 1, "rank": rank, "pid": 990000 + rank, "seq": 1,
                "host": f"peer{rank}", "providers": {"goodput": g}}

    def test_worst_rank_and_its_bucket_are_named(self, prof):
        time.sleep(0.05)
        try:
            profiler.publish_peer_metrics(
                self._peer(1, 10.0, 9.0, comm=1.0))
            profiler.publish_peer_metrics(
                self._peer(2, 10.0, 2.0, comm=1.0, downtime=7.0))
            agg = profiler.cluster_goodput()
            assert agg["ranks"] == 3   # local + two peers
            assert agg["worst"]["rank"] == 2
            assert agg["worst"]["bucket"] == "downtime"
            assert agg["worst"]["bucket_s"] == pytest.approx(7.0)
            # job goodput is wall-weighted, so the straggler drags it
            assert agg["goodput"] < 0.75
        finally:
            profiler.forget_peer_metrics(1)
            profiler.forget_peer_metrics(2)

    def test_none_when_no_rank_has_wall(self):
        profiler.stop()
        profiler.reset_goodput()
        assert profiler.cluster_goodput() is None


class TestExportSurfaces:
    def test_provider_rides_metrics_snapshot_and_prometheus(self, prof):
        time.sleep(0.02)
        snap = profiler.metrics_snapshot()
        g = snap["providers"]["goodput"]
        for key in ("wall_s", "goodput", "compute_s", "data_wait_s",
                    "downtime_s"):
            assert key in g, key
        assert g["wall_s"] > 0
        text = profiler.render_prometheus()
        assert "mxnet_goodput_wall_s" in text
        assert "mxnet_goodput_compute_s" in text

    def test_snapshot_roundtrips_json_and_rides_dump(self, prof, tmp_path):
        _span("io.wait", "io", 0.01)
        snap = json.loads(json.dumps(profiler.goodput_snapshot()))
        assert snap["schema"] == 1
        assert set(snap["buckets_s"]) == set(profiler._GOODPUT_BUCKETS)
        profiler.stop()
        profiler.dump()
        with open(str(tmp_path / "trace.json")) as f:
            doc = json.load(f)
        gp = doc["otherData"]["goodput"]
        assert gp["schema"] == 1 and gp["buckets_s"]["data_wait"] > 0

    def test_snapshot_counter_counts_captures(self, prof):
        before = profiler.counters()["goodput_snapshot"]
        profiler.goodput_snapshot()
        profiler.goodput_snapshot()
        assert profiler.counters()["goodput_snapshot"] == before + 2


# ---------------------------------------------------------------------------
# THE acceptance: supervised 2-proc run, one SIGKILL restart + one data
# stall — every second lands in its bucket
# ---------------------------------------------------------------------------


def _subproc_env(**extra):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_FAULT_SPEC", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.update({k: str(v) for k, v in extra.items()})
    return env


@pytest.mark.slow
def test_goodput_elastic_acceptance(tmp_path):
    """A 2-proc dist_sync folded run is SIGKILL'd on rank 1 at step 3
    (one supervisor restart) and rank 0 stalls 0.4 s on data at step 5.
    Each relaunched rank's ledger must (a) sum its buckets to wall
    within 5%, (b) carry the supervisor-measured restart gap in the
    ``downtime`` bucket under the ``elastic_restart`` reason, matching
    the run manifest, and (c) show the stall in ``data_wait`` on the
    stalled rank ONLY."""
    stall_s = 0.4
    manifest_path = str(tmp_path / "manifest.json")
    prefix = str(tmp_path / "run" / "ck")
    os.makedirs(os.path.dirname(prefix), exist_ok=True)
    env = _subproc_env(
        MXNET_COMPILE_WARMUP_STEPS="3", MXNET_COMPILE_GUARD="raise",
        MXNET_ELASTIC_BACKOFF_S="0.2", MXNET_FAULT_SEED="0",
        MXNET_FAULT_SPEC="proc.kill_rank:n=1:rank=1:at=3:gen=0",
        MXNET_TEST_STALL_S=str(stall_s), MXNET_TEST_STALL_AT="5",
        MXNET_TEST_STALL_RANK="0",
    )
    proc = subprocess.run(
        [sys.executable, SUPERVISE, "-n", "2", "--manifest", manifest_path,
         sys.executable, os.path.join(ROOT, "tests", "goodput_worker.py"),
         prefix],
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-3000:]
    restarts = [l for l in proc.stderr.splitlines()
                if l.startswith("ELASTIC_RESTART ")]
    assert len(restarts) == 1, proc.stderr[-3000:]
    rep = json.loads(restarts[0].split(" ", 1)[1])
    assert rep["reason"] == "rank_exit" and rep["rank"] == 1
    assert rep["exit_code"] == -signal.SIGKILL

    # the machine-readable run manifest tells the same story
    with open(manifest_path) as f:
        manifest = json.load(f)
    assert manifest["schema"] == 1 and manifest["final"] == "complete"
    assert manifest["restarts"] == 1
    assert len(manifest["generations"]) == 2
    assert manifest["generations"][0]["exit_cause"]["reason"] == "rank_exit"
    assert manifest["generations"][1]["exit_cause"]["reason"] == "clean"
    assert manifest["total_downtime_s"] >= 0.2   # at least the backoff

    # final-generation ledgers, one per rank
    snaps = {}
    for line in proc.stdout.splitlines():
        if line.startswith("GOODPUT_SNAPSHOT "):
            _, _, rank, payload = line.split(" ", 3)
            snaps[int(rank)] = json.loads(payload)
    assert sorted(snaps) == [0, 1], proc.stdout[-3000:]

    for rank, snap in snaps.items():
        b = snap["buckets_s"]
        # (a) every second accounted: buckets sum to wall within 5%
        assert sum(b.values()) == pytest.approx(
            snap["wall_s"], rel=0.05, abs=1e-4), (rank, snap)
        # (b) the restart gap landed in downtime, reason elastic_restart,
        # and equals what the supervisor measured into the manifest
        assert b["downtime"] == pytest.approx(
            manifest["total_downtime_s"], abs=0.05), (rank, snap)
        assert snap["downtime_detail"]["elastic_restart"] == pytest.approx(
            manifest["total_downtime_s"], abs=0.05)
    # (c) the stall is attributed to data_wait on the stalled rank only
    assert snaps[0]["buckets_s"]["data_wait"] >= stall_s * 0.9, snaps[0]
    assert snaps[1]["buckets_s"]["data_wait"] < 0.1, snaps[1]

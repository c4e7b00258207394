"""Worker body for the multi-host-SHAPED tier: N processes × 4 virtual CPU
devices each, one GLOBAL mesh spanning all of them through
``parallel.init_distributed`` (jax.distributed) — the topology a real
multi-host TPU pod presents, where the mesh's outer axis crosses the DCN
boundary and collectives span processes.

Covers what tests/dist_worker.py (1 device/process, kvstore transport)
cannot: ``make_array_from_process_local_data`` batch staging, cross-process
psum inside one jitted SPMD step, and a full SPMDTrainer step whose dp axis
spans hosts.  Exact-value assertions throughout.

Invoked by tests/test_dist.py via tools/launch_local.py.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=4"

import numpy as np


def main():
    import jax

    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import parallel

    # DMLC_* env (set by launch_local.py) → jax.distributed.initialize
    parallel.init_distributed()
    rank = jax.process_index()
    nproc = jax.process_count()
    assert nproc == int(os.environ["DMLC_NUM_WORKER"]), (
        nproc, os.environ["DMLC_NUM_WORKER"])
    assert len(jax.local_devices()) == 4
    n_global = len(jax.devices())
    assert n_global == 4 * nproc, f"global devices {n_global} != {4 * nproc}"

    # --- global dp×tp mesh with dp crossing the process boundary --------
    mesh = parallel.make_mesh(tp=2)  # dp = n_global // 2 spans hosts
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    # cross-process psum inside one jitted program: every process
    # contributes its rank+1 per local device slot
    local = np.full((4, 8), float(rank + 1), np.float32)
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), local)  # dp-sharded over axis 0

    @jax.jit
    def global_sum(x):
        return jnp.sum(x)

    total = float(global_sum(arr))
    # each process contributes 4*8*(rank+1) but the dp axis has
    # n_global//2 shards of 2 rows... simpler invariant: the GLOBAL array
    # concatenates the per-process local blocks over dp — total is the sum
    # over processes of 4*8*(rank+1)
    expect = sum(4 * 8 * (r + 1) for r in range(nproc))
    assert total == expect, (total, expect)

    # --- SPMDTrainer step with dp spanning hosts ------------------------
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.gluon import nn
    from incubator_mxnet_tpu.ndarray.ndarray import NDArray

    mx.random.seed(0)  # identical params on every process
    net = nn.Dense(4)
    net.initialize()
    net(mx.nd.zeros((2, 8)))

    def loss_fn(out, label):
        return (out - label) * (out - label)

    trainer = parallel.SPMDTrainer(net, loss_fn, "sgd",
                                   {"learning_rate": 0.1}, mesh=mesh)
    B_local = 8  # global batch = 8 * nproc, dp-sharded
    rng = np.random.RandomState(100 + rank)  # different data per host
    x_local = rng.rand(B_local, 8).astype(np.float32)
    y_local = rng.rand(B_local, 4).astype(np.float32)
    loss = trainer.step(NDArray(jnp.asarray(x_local)), NDArray(jnp.asarray(y_local)))
    val = float(np.asarray(loss._data))
    assert np.isfinite(val)
    # the updated parameters must be IDENTICAL on all processes (grad psum
    # across the dp axis, which spans hosts): gather each process's local
    # checksum onto a dp-sharded array and assert zero spread globally
    p0 = trainer._param_arrays[0]
    local_c = float(np.asarray(p0.addressable_data(0), dtype=np.float64).sum())
    dp_mesh = parallel.make_mesh()  # pure-dp over all global devices
    cs = jax.make_array_from_process_local_data(
        NamedSharding(dp_mesh, P("dp")),
        np.full((4, 1), local_c, np.float32))  # one row per local device

    @jax.jit
    def spread(x):
        return jnp.max(x) - jnp.min(x)

    s = float(spread(cs))
    assert s == 0.0, f"params diverged across hosts: spread={s}"
    print(f"rank {rank}/{nproc}: multihost assertions passed "
          f"(global_sum={total}, loss={val:.5f}, checksum={local_c:.3f})")


if __name__ == "__main__":
    main()

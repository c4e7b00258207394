"""Worker body for the multi-process distributed tier — the port of the
reference's [U:tests/nightly/dist_sync_kvstore.py] assertions, run at
``process_count == N`` (2 in CI; any N via DMLC_NUM_WORKER) on the CPU
backend via ``tools/launch_local.py``.  All expected values are exact
functions of the worker count.

Every check asserts EXACT aggregated values (deterministic inputs), the
reference suite's discipline.  Invoked by tests/test_dist.py; exits
non-zero on any failure.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np


def main():
    import incubator_mxnet_tpu as mx

    kv = mx.kv.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    expected = int(os.environ.get("DMLC_NUM_WORKER", "2"))
    assert nw == expected, f"worker count mismatch: {nw} != {expected}"

    # --- exact aggregated push/pull (int and string keys) ---------------
    kv.init(3, mx.nd.ones((4, 5)))
    kv.push(3, mx.nd.ones((4, 5)) * (rank + 1))  # sum over ranks of (r+1)
    out = mx.nd.zeros((4, 5))
    kv.pull(3, out=out)
    np.testing.assert_allclose(out.asnumpy(),
                               (nw * (nw + 1) / 2) * np.ones((4, 5)))

    kv.init("weight0", mx.nd.zeros((3,)))
    kv.push("weight0", mx.nd.array([float(rank), 1.0, -1.0]))
    out = mx.nd.zeros((3,))
    kv.pull("weight0", out=out)
    np.testing.assert_allclose(
        out.asnumpy(), np.array([nw * (nw - 1) / 2, float(nw), -float(nw)]))

    # list-of-values aggregation first, then cross-worker reduce
    kv.push(3, [mx.nd.ones((4, 5)), mx.nd.ones((4, 5))])  # each worker: 2
    out2 = mx.nd.zeros((4, 5))
    kv.pull(3, out=out2)
    np.testing.assert_allclose(out2.asnumpy(), 2.0 * nw * np.ones((4, 5)))

    # --- updater on the store (optimizer-on-kvstore parity) -------------
    kvu = mx.kv.create("dist_sync")
    kvu.init(11, mx.nd.ones((2, 2)))

    def updater(key, grad, weight):
        weight += -0.1 * grad

    kvu._set_updater(updater)
    kvu.push(11, mx.nd.ones((2, 2)))  # agg grad = nw
    out = mx.nd.zeros((2, 2))
    kvu.pull(11, out=out)
    np.testing.assert_allclose(out.asnumpy(),
                               (1.0 - 0.1 * nw) * np.ones((2, 2)))

    # --- 2-bit gradient compression: wire dtype + exact quantized values
    kvc = mx.kv.create("dist_sync")
    kvc.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kvc.init(7, mx.nd.zeros((8,)))
    g = np.array([0.6, -0.7, 0.1, 0.0, 1.2, -0.2, 0.49, -0.51], np.float32)
    kvc.push(7, mx.nd.array(g))
    out = mx.nd.zeros((8,))
    kvc.pull(7, out=out)
    codes = np.array([1, -1, 0, 0, 1, 0, 0, -1], np.float32)
    # every worker pushes the same g → summed codes = nw·codes, times t
    np.testing.assert_allclose(out.asnumpy(), codes * nw * 0.5)
    assert kvc._last_wire_dtype == "int8", kvc._last_wire_dtype

    # error feedback: residual carries the quantization error into the next
    # push (residual[4] = 1.2 - 0.5 = 0.7 > t → fires on a zero gradient)
    kvc.push(7, mx.nd.zeros((8,)))
    kvc.pull(7, out=out)
    expect = np.zeros(8, np.float32)
    expect[4] = nw * 0.5
    np.testing.assert_allclose(out.asnumpy(), expect)

    # pushpull must take the same compressed wire path as push
    kvp = mx.kv.create("dist_sync")
    kvp.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kvp.init(9, mx.nd.zeros((4,)))
    outp = mx.nd.zeros((4,))
    kvp.pushpull(9, mx.nd.array([0.6, -0.7, 0.1, 0.0]), out=outp)
    np.testing.assert_allclose(outp.asnumpy(),
                               np.array([1, -1, 0, 0]) * nw * 0.5)
    assert kvp._last_wire_dtype == "int8", kvp._last_wire_dtype

    # --- barrier + SPMDTrainer.shard_batch over the N-process mesh ------
    kv.barrier()
    from incubator_mxnet_tpu.parallel import make_mesh, SPMDTrainer
    from incubator_mxnet_tpu import gluon

    mx.random.seed(0)
    net = gluon.nn.Dense(4, flatten=False)
    net.initialize()
    net(mx.nd.zeros((2, 8)))  # materialize shapes

    def loss_fn(out, label):
        return ((out - label) ** 2).mean(axis=-1)

    mesh = make_mesh()  # pure dp over one device per process
    assert mesh.devices.size == nw
    trainer = SPMDTrainer(net, loss_fn, "sgd", {"learning_rate": 0.1}, mesh=mesh)
    # each process feeds its LOCAL 1/nw shard of the global batch
    rng = np.random.RandomState(42 + rank)
    x = mx.nd.array(rng.rand(4, 8).astype(np.float32))
    y = mx.nd.array(rng.rand(4, 4).astype(np.float32))
    l0 = float(trainer.step(x, y).asscalar())
    for _ in range(20):
        loss = trainer.step(x, y)
    l1 = float(loss.asscalar())
    assert np.isfinite(l0) and l1 < l0, (l0, l1)

    print(f"dist_worker rank {rank}/{nw}: all assertions passed", flush=True)


if __name__ == "__main__":
    main()

"""Worker body for the dist_async straggler-tolerance tier (the port of
the reference's [U:tests/nightly/dist_async_kvstore.py] discipline, plus
an explicit straggler-independence assertion the sync tier cannot make).

Run via tools/launch_local.py at DMLC_NUM_WORKER=N.  The LAST rank is a
deliberate straggler (sleeps before pushing); every other rank must
complete its pushes and pulls in far less than the straggler's sleep —
push/pull are barrier-free against the worker-0 parameter server.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

STRAGGLE_S = 3.0
PUSHES = 4


def main():
    import incubator_mxnet_tpu as mx

    kv = mx.kv.create("dist_async")
    assert kv.type == "dist_async"
    rank, nw = kv.rank, kv.num_workers
    expected = int(os.environ.get("DMLC_NUM_WORKER", "2"))
    assert nw == expected, f"worker count mismatch: {nw} != {expected}"
    straggler = nw - 1

    # --- async accumulation with a straggler ----------------------------
    kv.init("acc", mx.nd.zeros((4,)))
    kv.barrier()  # everyone sees the initialized key

    t0 = time.monotonic()
    if rank == straggler:
        time.sleep(STRAGGLE_S)
    for _ in range(PUSHES):
        kv.push("acc", mx.nd.ones((4,)) * (rank + 1))
    out = mx.nd.zeros((4,))
    kv.pull("acc", out=out)
    elapsed = time.monotonic() - t0

    if rank != straggler:
        # THE async property: fast workers finish all pushes+pull while the
        # straggler is still asleep — no barrier in push/pull
        assert elapsed < STRAGGLE_S / 2, (
            f"rank {rank} blocked {elapsed:.1f}s behind the straggler")
        # and the pulled value reflects only what has arrived so far: it
        # must be a valid partial sum (monotonicity, not the full total)
        total = float(out.asnumpy()[0])
        full = PUSHES * nw * (nw + 1) / 2
        assert 0 < total <= full, total

    kv.barrier()  # straggler done too
    kv.pull("acc", out=out)
    full = PUSHES * nw * (nw + 1) / 2  # sum over ranks of PUSHES*(r+1)
    np.testing.assert_allclose(out.asnumpy(), np.full((4,), full))
    counts = kv.push_counts()
    assert counts == [PUSHES] * nw, counts

    # --- server-side optimizer (the async contract) ---------------------
    kv2 = mx.kv.create("dist_async")
    kv2.init("w", mx.nd.ones((3,)))
    opt = mx.optimizer.create("sgd", learning_rate=0.1)
    kv2.set_optimizer(opt)  # includes a barrier
    kv2.push("w", mx.nd.ones((3,)))  # each push: w -= 0.1*1
    kv2.barrier()
    got = mx.nd.zeros((3,))
    kv2.pull("w", out=got)
    np.testing.assert_allclose(got.asnumpy(), np.full((3,), 1.0 - 0.1 * nw),
                               rtol=1e-6)

    # --- Module routes its update through the kvstore for dist_* --------
    import incubator_mxnet_tpu.symbol as S

    S.symbol._reset_naming()
    data = S.var("data")
    fc = S.FullyConnected(data, num_hidden=1, no_bias=True, name="fc")
    out_sym = S.LinearRegressionOutput(fc, S.var("lin_label"), name="lin")
    mod = mx.mod.Module(out_sym, data_names=("data",), label_names=("lin_label",))
    from incubator_mxnet_tpu.io import NDArrayIter

    x = np.linspace(-1, 1, 16).reshape(16, 1).astype(np.float32)
    y = 3.0 * x
    it = NDArrayIter(data=x, label=y, batch_size=8)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.initializer.Zero())
    mod.init_optimizer(kvstore="dist_async",
                       optimizer_params=(("learning_rate", 0.05),))
    for _ in range(60):
        it.reset()
        for batch in it:
            mod.forward(batch, is_train=True)
            mod.backward()
            mod.update()
    kv.barrier()
    w = mod._exec.arg_dict["fc_weight"].asnumpy()
    assert abs(float(w[0, 0]) - 3.0) < 0.25, w

    print(f"rank {rank}: async assertions passed")


if __name__ == "__main__":
    main()

"""Worker body for the elastic chaos acceptance (tests/test_elastic.py).

A 2-process dist_sync FOLDED training run (one compiled program per step,
in-fold gradient exchange) that snapshots a :class:`RunCheckpoint` after
every step with ``kv.barrier`` as the two-phase ack.  On relaunch it
restores the newest COMMITTED snapshot and continues — under
``tools/supervise.py`` with a ``proc.kill_rank`` fault injected the run
loses a worker mid-run, the supervisor re-forms the job, and the resumed
trajectory must land on the fault-free final loss exactly (same seeds,
exact data-cursor/RNG/trainer resume).

Prints one ``ELASTIC_FINAL rank <r> <loss>`` marker per rank on success;
``ELASTIC_RESUMED rank <r> step <s>`` when a generation resumed.  Runs
with the compile guard armed (MXNET_COMPILE_WARMUP_STEPS small,
MXNET_COMPILE_GUARD=raise in the test env): a steady-state recompile
after resume fails the run.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("MXNET_KVSTORE_BUCKET_BYTES", "2048")

import numpy as np

TOTAL = 8


def main():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, profiler
    from incubator_mxnet_tpu.io.io import NDArrayIter
    from incubator_mxnet_tpu.parallel import elastic
    from incubator_mxnet_tpu.utils import faultinject as fi

    prefix = sys.argv[1]
    L2 = gluon.loss.L2Loss()
    kv = mx.kv.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    assert nw == 2, nw

    elastic.init()  # heartbeat lease + collective watchdog (no-op w/o env)

    mx.random.seed(7)
    np.random.seed(7)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    net.hybridize()
    net(mx.nd.zeros((2, 6)))
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05}, kvstore=kv)

    # per-rank shard, shuffled — exercises the data-cursor resume
    rs = np.random.RandomState(100 + rank)
    x = rs.rand(32, 6).astype(np.float32)
    y = rs.rand(32, 4).astype(np.float32)
    it = NDArrayIter(x, y, batch_size=8, shuffle=True, seed=13 + rank)

    ck = elastic.RunCheckpoint(prefix, net=net, trainer=tr,
                               rank=rank, world=nw)
    start = 0
    payload = ck.restore(data=it)
    if payload is not None:
        start = payload["step"]
        print(f"ELASTIC_RESUMED rank {rank} step {start}", flush=True)

    program = tr.fold_step(lambda a, b: L2(net(a), b), block=net)
    loss = None
    for step in range(start, TOTAL):
        fi.step_faults(step, rank)   # proc.kill_rank / slow_rank gate here
        if not it.iter_next():
            it.reset()
            it.iter_next()
        a, b = it.getdata()[0], it.getlabel()[0]
        # reduce the local loss shard in numpy: an eager mean over the
        # fold's mesh-sharded output would compile AFTER the guard arms
        loss = float(np.asarray(program(a, b).asnumpy()).mean())
        ck.save(step + 1, data=it, barrier=kv.barrier)
    assert program.folded, program.fallback_reason
    c = profiler.counters()
    assert c["recompile_steady_state"] == 0, c["recompile_steady_state"]

    kv.barrier()
    print(f"ELASTIC_FINAL rank {rank} {loss:.6f}", flush=True)


if __name__ == "__main__":
    main()

"""Worker body for the goodput-ledger acceptance (tests/test_goodput.py).

The elastic chaos worker (tests/elastic_worker.py) with the profiler
armed: a 2-process dist_sync folded run, RunCheckpoint after every step,
under ``tools/supervise.py`` with a ``proc.kill_rank`` fault — plus one
injected DATA STALL on rank 0 (a sleep reported exactly the way
``io.DataPipeline`` reports consumer stalls: one ``io.wait`` span).  At
the end each rank prints its run ledger::

    GOODPUT_SNAPSHOT rank <r> <goodput_snapshot() json>

The acceptance asserts the buckets sum to wall, the supervisor's restart
gap (ridden in on ``MXNET_ELASTIC_DOWNTIME_S``) lands in ``downtime``
with the ``elastic_restart`` reason, and the stall lands in
``data_wait`` — on the stalled rank only.

Knobs: ``MXNET_TEST_STALL_S`` (default 0.4), ``MXNET_TEST_STALL_AT``
(step, default 5), ``MXNET_TEST_STALL_RANK`` (default 0).
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("MXNET_KVSTORE_BUCKET_BYTES", "2048")

import json

import numpy as np

TOTAL = 8


def main():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon, profiler
    from incubator_mxnet_tpu.io.io import NDArrayIter
    from incubator_mxnet_tpu.parallel import elastic
    from incubator_mxnet_tpu.utils import faultinject as fi

    prefix = sys.argv[1]
    stall_s = float(os.environ.get("MXNET_TEST_STALL_S", "0.4"))
    stall_at = int(os.environ.get("MXNET_TEST_STALL_AT", "5"))
    stall_rank = int(os.environ.get("MXNET_TEST_STALL_RANK", "0"))

    L2 = gluon.loss.L2Loss()
    kv = mx.kv.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    assert nw == 2, nw

    # arm the profiler FIRST: the ledger's wall window opens here, and
    # elastic.init() below folds the supervisor's restart gap into it
    profiler.set_config(filename=f"{prefix}_trace_rank{rank}.json")
    profiler.start()
    elastic.init()

    mx.random.seed(7)
    np.random.seed(7)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize()
    net.hybridize()
    net(mx.nd.zeros((2, 6)))
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05}, kvstore=kv)

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
    for step in range(start, TOTAL):
        fi.step_faults(step, rank)   # proc.kill_rank gates here
        if step == stall_at and rank == stall_rank:
            # the data stall: producer starves the consumer for stall_s —
            # reported the same way DataPipeline reports a consumer stall
            # (one io.wait span covering the blocked wait)
            t0 = time.perf_counter()
            time.sleep(stall_s)
            profiler.record_span("io.wait", "io", t0)
        if not it.iter_next():
            it.reset()
            it.iter_next()
        a, b = it.getdata()[0], it.getlabel()[0]
        float(np.asarray(program(a, b).asnumpy()).mean())
        ck.save(step + 1, data=it, barrier=kv.barrier)
    assert program.folded, program.fallback_reason
    c = profiler.counters()
    assert c["recompile_steady_state"] == 0, c["recompile_steady_state"]

    kv.barrier()
    snap = profiler.goodput_snapshot()
    print(f"GOODPUT_SNAPSHOT rank {rank} {json.dumps(snap)}", flush=True)


if __name__ == "__main__":
    main()

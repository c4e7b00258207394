"""Worker for the subprocess chaos tier (tests/test_chaos.py): pushes a
fixed workload against a STANDALONE parameter server
(``python -m incubator_mxnet_tpu.kvstore.async_ps``) that the test
SIGKILLs and restarts mid-run.

Resume discipline (the idempotent-retry contract end to end): the worker
treats the SERVER's applied-push count as the source of truth — each
iteration re-reads ``counts[rank]`` and pushes only while it is below the
target.  A server crash that rolls back to an older snapshot (losing
acked-but-unsnapshotted pushes) is therefore repaired by re-pushing, and a
push can never be applied twice (the dedup window absorbs replays), so the
run ends with counts == TOTAL exactly and the accumulated value exact.

Env (set by the test): MXNET_ASYNC_PS_EXTERNAL=1, MXNET_ASYNC_PS_PORT,
DMLC_WORKER_ID, DMLC_NUM_WORKER, short MXNET_KVSTORE_REQUEST_TIMEOUT so
the kill window is crossed quickly.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

TOTAL = 30


def main():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.kvstore import PSKeyError

    kv = mx.kv.create("dist_async")
    assert kv._server is None, "worker must NOT self-host (external PS mode)"
    rank, nw = kv.rank, kv.num_workers

    if rank == 0:
        kv.init("acc", mx.nd.zeros((4,)))
    else:
        # no barrier: under elastic membership a counting barrier is the
        # wrong sync primitive across a server restart — poll for the key
        deadline = time.monotonic() + 60
        while True:
            try:
                kv.pull("acc", out=mx.nd.zeros((4,)))
                break
            except PSKeyError:
                assert time.monotonic() < deadline, "init never appeared"
                time.sleep(0.1)

    # push until the SERVER says TOTAL of ours were applied: survives the
    # mid-run SIGKILL+restart (rollback to the last snapshot) without ever
    # over- or under-pushing
    deadline = time.monotonic() + 120
    while True:
        applied = kv.push_counts()[rank]
        if applied >= TOTAL:
            break
        assert time.monotonic() < deadline, f"rank {rank} stuck at {applied}"
        kv.push("acc", mx.nd.ones((4,)))
        time.sleep(0.04)

    # wait for every peer to finish (counts are server-authoritative)
    deadline = time.monotonic() + 120
    while True:
        counts = kv.push_counts()
        if all(c >= TOTAL for c in counts[:nw]):
            break
        assert time.monotonic() < deadline, f"peers stuck: {counts}"
        time.sleep(0.2)

    assert counts[:nw] == [TOTAL] * nw, counts
    out = mx.nd.zeros((4,))
    kv.pull("acc", out=out)
    np.testing.assert_allclose(out.asnumpy(), np.full((4,), float(TOTAL * nw)))
    kv.close()
    print(f"CHAOS_OK rank {rank} counts {counts[:nw]}", flush=True)


if __name__ == "__main__":
    main()

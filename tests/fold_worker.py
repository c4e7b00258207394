"""Worker body for the 2-process step-fold tier: the IN-FOLD gradient
exchange (forward/backward per worker shard inside one shard_map over the
dist_sync worker mesh, per-bucket psum/codec allreduce nodes scheduled by
XLA against the remaining backward) must train to the same trajectory as
the out-of-fold path (eager backward + bucketed pushpull + fused update).

Run at process_count == 2 via tools/launch_local.py (tests/test_step_fold
launches it like tests/test_dist.py does its workers).  Exits non-zero on
any failure; prints the marker line once per rank on success.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("MXNET_KVSTORE_BUCKET_BYTES", "2048")

import numpy as np


def main():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import autograd, gluon, profiler

    L2 = gluon.loss.L2Loss()
    kv = mx.kv.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    assert nw == 2, nw

    def build(seed):
        mx.random.seed(seed)
        np.random.seed(seed)
        net = gluon.nn.HybridSequential()
        net.add(gluon.nn.Dense(32, activation="relu"),
                gluon.nn.Dense(32, activation="relu"),
                gluon.nn.Dense(4))
        net.initialize()
        net.hybridize()
        # per-rank local batch shard (different data per worker — the
        # exchange has to actually carry information)
        rs = np.random.RandomState(100 + rank)
        x = mx.nd.array(rs.rand(8, 6).astype(np.float32))
        y = mx.nd.array(rs.rand(8, 4).astype(np.float32))
        net(mx.nd.zeros((2, 6)))
        return net, x, y

    # --- out-of-fold reference: eager backward + bucketed pushpull ------
    net1, x, y = build(5)
    tr1 = gluon.Trainer(net1.collect_params(), "sgd",
                        {"learning_rate": 0.05}, kvstore=kv)
    losses1 = []
    for _ in range(6):
        with autograd.record():
            loss = L2(net1(x), y)
        loss.backward()
        tr1.step(8)
        losses1.append(float(loss.mean().asscalar()))

    # --- in-fold: ONE compiled program incl. per-bucket allreduce -------
    kv2 = mx.kv.create("dist_sync")
    net2, x2, y2 = build(5)
    tr2 = gluon.Trainer(net2.collect_params(), "sgd",
                        {"learning_rate": 0.05}, kvstore=kv2)
    program = tr2.fold_step(lambda a, b: L2(net2(a), b), block=net2)
    c0 = profiler.counters()
    losses2 = []
    for _ in range(6):
        losses2.append(float(program(x2, y2).mean().asscalar()))
    c1 = profiler.counters()
    assert program.folded, program.fallback_reason
    assert c1["step_fold_call"] - c0["step_fold_call"] == 6
    assert c1["recompile_steady_state"] == c0["recompile_steady_state"], \
        "in-fold dist step recompiled in steady state"

    # local loss parity (this rank's shard, step for step) and global
    # param parity: grads crossed the wire inside the program.  The dist
    # fold holds params in donated global registers — sync them into the
    # live Parameters before reading.
    program.sync()
    np.testing.assert_allclose(losses1, losses2, rtol=1e-4, atol=1e-6)
    for pa, pb in zip(sorted(net1.collect_params().values(),
                             key=lambda p: p.name),
                      sorted(net2.collect_params().values(),
                             key=lambda p: p.name)):
        np.testing.assert_allclose(pa.data().asnumpy(),
                                   pb.data().asnumpy(),
                                   rtol=2e-4, atol=2e-6, err_msg=pa.name)

    # save/load through the dist fold's global registers
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        fname = os.path.join(d, f"states_{rank}")
        tr2.save_states(fname)   # syncs fold registers first
        tr2.load_states(fname)   # invalidates → next call re-stages
    losses3 = [float(program(x2, y2).mean().asscalar()) for _ in range(2)]
    assert all(np.isfinite(v) for v in losses3)

    # --- K-step window through the dist fold (ISSUE 17) -----------------
    # k=2 windows with the int8-codec bucket nodes inside EACH scan
    # iteration: BIT-exact trajectory vs the same codec run per-step, in
    # half the dispatches (EF residuals ride the loop carry).
    # The IN-FOLD codec rides the env policy (MXNET_GRAD_COMPRESS), not
    # per-key store compression — that path keeps one key per param and
    # refuses bucketing.
    os.environ["MXNET_GRAD_COMPRESS"] = "int8"

    def codec_pair(k):
        kvn = mx.kv.create("dist_sync")
        netn, xn, yn = build(5)
        trn = gluon.Trainer(netn.collect_params(), "sgd",
                            {"learning_rate": 0.05}, kvstore=kvn)
        fold = trn.fold_steps(lambda a, b, n=netn: L2(n(a), b), k=k,
                              block=netn)
        return netn, fold, xn, yn

    net5, ref5, x5, y5 = codec_pair(1)
    mx.random.seed(9)
    losses5 = [float(ref5(x5, y5).mean().asscalar()) for _ in range(4)]
    assert ref5.folded, ref5.fallback_reason

    net6, fold6, x6, y6 = codec_pair(2)
    xw = mx.nd.array(np.repeat(x6.asnumpy()[None], 2, axis=0))
    yw = mx.nd.array(np.repeat(y6.asnumpy()[None], 2, axis=0))
    c0 = profiler.counters()
    mx.random.seed(9)
    losses6 = []
    for _ in range(2):                       # 2 windows == 4 logical steps
        out = np.asarray(fold6(xw, yw).asnumpy(), np.float64)
        losses6.extend(out.reshape(out.shape[0], -1).mean(axis=1))
    c1 = profiler.counters()
    assert fold6.folded, fold6.fallback_reason
    assert fold6.logical_steps == 4
    assert c1["step_fold_call"] - c0["step_fold_call"] == 2, \
        "k=2 window must be ONE dispatch per 2 logical steps"
    np.testing.assert_allclose(losses5, losses6, rtol=1e-6, atol=1e-8)
    ref5.sync()
    fold6.sync()
    # pair positionally: by this phase the gluon auto-name counters are
    # past dense9, and lexical name sort ("dense10" < "dense9") scrambles
    # cross-net pairing; collect_params() insertion order is stable
    for pa, pb in zip(list(net5.collect_params().values()),
                      list(net6.collect_params().values())):
        assert np.array_equal(pa.data().asnumpy(), pb.data().asnumpy()), \
            f"{pa.name} vs {pb.name} diverged"

    # --- ring algorithm through the dist fold (ISSUE 19) ----------------
    # same int8 codec, MXNET_GRAD_COMPRESS_ALGO=ring: the in-fold bucket
    # exchange becomes explicit encoded ppermute hops.  Pin that the fold
    # still builds, trains, recompiles nothing in steady state, and that
    # the hop/byte accounting lands in the counters (the per-hop evidence
    # for the K-fold dist leg).
    os.environ["MXNET_GRAD_COMPRESS_ALGO"] = "ring"
    net7, fold7, x7, y7 = codec_pair(2)
    mx.random.seed(9)
    losses7 = []

    def window():
        out = np.asarray(fold7(xw, yw).asnumpy(), np.float64)
        losses7.extend(out.reshape(out.shape[0], -1).mean(axis=1))

    window()                       # first window compiles the ring program
    c0 = profiler.counters()
    window()                       # second window must be steady state
    c1 = profiler.counters()
    assert fold7.folded, fold7.fallback_reason
    assert all(np.isfinite(v) for v in losses7)
    # ring int8 tracks the psum int8 trajectory within quantization slack
    np.testing.assert_allclose(losses6, losses7, rtol=5e-2, atol=5e-3)
    assert c1["recompile_steady_state"] == c0["recompile_steady_state"], \
        "ring dist fold recompiled in steady state"
    hops = c1["comms_ring_hops"] - c0["comms_ring_hops"]
    raw = c1["comms_bytes_raw"] - c0["comms_bytes_raw"]
    wire = c1["comms_bytes_wire"] - c0["comms_bytes_wire"]
    assert hops > 0 and hops % 4 == 0, hops  # 2(nw-1) per bucket * k=2
    # total wire ratio includes the exact fp32 opt-out buckets (biases),
    # which dominate at this toy scale — the tier acceptance bar is the
    # PER-HOP ratio of the compressed buckets, from the fold's hop plan
    assert raw / max(wire, 1) >= 3.0, (raw, wire)
    ca = next(e["comm_args"] for e in fold7._cache.values()
              if e.get("comm_args"))
    hop_ratio = ca["bytes_hop_fp32"] / max(ca["bytes_hop"], 1)
    assert hop_ratio >= 3.5, ca
    if rank == 0:
        import json

        print("fold_worker ring evidence: " + json.dumps(
            {"hops": int(hops), "bytes_raw": int(raw),
             "bytes_wire": int(wire),
             "byte_ratio": round(raw / max(wire, 1), 3),
             "bytes_per_hop": ca["bytes_hop"],
             "fp32_bytes_per_hop": ca["bytes_hop_fp32"],
             "hop_ratio_vs_fp32": round(hop_ratio, 3),
             "k": 2, "windows": 1, "workers": nw}), flush=True)
    os.environ.pop("MXNET_GRAD_COMPRESS_ALGO", None)

    kv.barrier()
    print(f"fold_worker rank {rank}/{nw}: all assertions passed",
          flush=True)


if __name__ == "__main__":
    main()

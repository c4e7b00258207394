#!/usr/bin/env python
"""Model-parallel LSTM — the [U:example/model-parallel/] analog.

The reference places each LSTM layer on a different GPU by hand
(``group2ctx`` in ``Symbol.bind``).  The TPU-native equivalent is
strictly more capable: declare a ``ShardingRules`` table mapping
parameter names to ``PartitionSpec``s over a named mesh axis and jit the
whole step — XLA splits every matmul across the ``tp`` axis and inserts
the collectives the hand-placed version needed explicit device-to-device
copies for.

This example runs on the 8-device virtual CPU mesh (dp=4 × tp=2),
trains a 2-layer LSTM regression model twice — tensor-parallel and
fully replicated — and checks the two learn identical parameters, then
prints the per-device shard shapes to show the weights really are
split.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python example/model_parallel_lstm.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# self-provision the 8-device virtual CPU mesh (same discipline as
# tests/conftest.py)
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np


def build(hidden, layers, seed):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon

    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    net.add(gluon.rnn.LSTM(hidden, num_layers=layers, layout="NTC"),
            gluon.nn.Dense(1, flatten=False))
    net.initialize()
    net(mx.nd.zeros((2, 8, 16)))  # materialize deferred shapes
    return net


def train(net, rules, steps=12, seed=0):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import gluon
    from incubator_mxnet_tpu.parallel import SPMDTrainer, make_mesh

    mesh = make_mesh(tp=2)  # dp fills the rest: 4×2 on 8 devices
    loss = gluon.loss.L2Loss()
    trainer = SPMDTrainer(net, loss, "adam", {"learning_rate": 3e-3},
                          mesh=mesh, rules=rules)
    rng = np.random.RandomState(seed)
    last = None
    for _ in range(steps):
        x = rng.rand(32, 8, 16).astype(np.float32)
        y = x.sum(axis=2, keepdims=True).astype(np.float32)
        last = trainer.step(x, y)
    return trainer, float(last)


def main():
    from jax.sharding import PartitionSpec as P

    from incubator_mxnet_tpu.parallel import ShardingRules
    from incubator_mxnet_tpu.parallel.sharding import default_rules

    # Megatron-style row split of the stacked-gate matrices over 'tp'.
    # (The 4h gate rows interleave across devices; XLA keeps the math
    # correct by inserting the collectives — that's the point.)
    tp_rules = ShardingRules([
        (r"(i2h|h2h)_weight", P("tp", None)),
        (r"(i2h|h2h)_bias", P("tp")),
        (r"dense.*weight", P(None, "tp")),
    ])

    net_tp = build(64, 2, seed=7)
    net_rep = build(64, 2, seed=7)  # identical init

    tr_tp, loss_tp = train(net_tp, tp_rules)
    tr_rep, loss_rep = train(net_rep, default_rules())

    # same training trajectory regardless of placement
    for (p_tp, a_tp), (p_rep, a_rep) in zip(
            zip(tr_tp._params, tr_tp._param_arrays),
            zip(tr_rep._params, tr_rep._param_arrays)):
        np.testing.assert_allclose(np.asarray(a_tp), np.asarray(a_rep),
                                   rtol=2e-4, atol=2e-4, err_msg=p_tp.name)

    # show the split: an LSTM weight's per-device shard is half the rows
    w = next(a for p, a in zip(tr_tp._params, tr_tp._param_arrays)
             if "h2h_weight" in p.name)
    shard_shapes = {str(s.data.shape) for s in w.addressable_shards}
    print(f"h2h_weight global {w.shape}, per-device shards {sorted(shard_shapes)}")
    print(f"tp loss {loss_tp:.5f} == replicated loss {loss_rep:.5f}")
    print("model-parallel == replicated: OK")


if __name__ == "__main__":
    main()

"""What PR 28 added to the benchmark: the Xing4 reference against the
model's own forward, the needed-FLOPs count, the causal-LM batch, and the two
new readers on hand-made inputs."""
import json
import os
import types

import numpy as np

from chipbench import flops_xing4, traffic_clm
from chipbench.builders import xing4_clm
from chipbench.reducers import counter_ratio, mfu
from conftest import ROOT


def config(dry_run=False):
    c = json.load(open(os.path.join(ROOT, "chipbench", "configs", "xing4.0-29b-a4b.json")))
    if dry_run:
        c.update(c["dry_run"])
    return c


def test_xing4_reference_matches_the_models_forward():
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo.xing4 import Xing4ForCausalLM

    from chipbench.reference import xing4 as reference

    mc, held = xing4_clm.model_config(config(dry_run=True))
    mx.random.seed(3)
    net = Xing4ForCausalLM(mc, experts_held=held)
    net.initialize(mx.init.Normal(0.2))
    tok, _ = traffic_clm.clm_batch({"ids": {"dist": "zipf", "exponent": 1.0},
                                    "per_chip_batch": 2, "seq_length": 24}, 7, mc["vocab_size"], 1)
    want = np.asarray(net(mx.nd.array(tok, dtype="int32"))._data)
    named = {p.name: p._data._data for p in net.collect_params().values()}
    got = np.asarray(reference.forward(named, tok, config=mc, experts_held=held))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got / scale, want / scale, atol=1e-4)


def test_needed_flops_are_the_issues_arithmetic():
    c = config()
    macs = flops_xing4.macs_per_token(c)
    assert flops_xing4.attention_params(c) == 28_409_856
    assert flops_xing4.expert_params(c) == 11_010_048
    assert macs["attention_projections"] == 142_049_280
    assert macs["dense_mlp"] == 99_090_432 and macs["head"] == 58_720_256
    assert macs["routed_experts"] == 4 * 4 * 8 / 64 * 11_010_048      # the expected share
    assert round(sum(macs.values()) / 1e6, 1) == 370.3
    assert round(flops_xing4.param_count(c) / 1e6, 1) == 759.3
    need = flops_xing4.xing4_clm_step(c, batch=1, seq=4096)
    assert round(need["flops"] / 1e12, 2) == 11.68
    # the count of parameters is the model's own
    tiny = config(dry_run=True)
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo.xing4 import Xing4ForCausalLM

    mc, held = xing4_clm.model_config(tiny)
    net = Xing4ForCausalLM(mc, experts_held=held)
    net.initialize(mx.init.Zero())
    held_params = sum(int(np.prod(p.shape)) for p in net.collect_params().values())
    assert flops_xing4.param_count(tiny) == held_params


def test_clm_batch_is_seeded_zipf_and_labels_are_the_next_token():
    traffic = json.load(open(os.path.join(ROOT, "chipbench", "traffic", "clm-s4096.json")))
    tok, labels = traffic_clm.clm_batch(traffic, 3000000011, 16384, 1)
    again, _ = traffic_clm.clm_batch(traffic, 3000000011, 16384, 1)
    other, _ = traffic_clm.clm_batch(traffic, 12, 16384, 1)
    assert tok.shape == labels.shape == (1, 4096) and tok.dtype == np.int32
    np.testing.assert_array_equal(tok, again)
    assert (tok != other).any()
    np.testing.assert_array_equal(tok[:, 1:], labels[:, :-1])
    assert 0 <= tok.min() and tok.max() < 16384
    # Zipf(1.0): id 0 takes 1 / H(16384) = 9.7 % of the draws, the first ten 28 %
    assert 0.07 < np.mean(tok == 0) < 0.13 and 0.24 < np.mean(tok < 10) < 0.33


def test_mfu_reader_divides_needed_flops_by_device_time_times_peak():
    said = []
    ctx = types.SimpleNamespace(config=config(), say=said.append,
                                devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    modules = [(i * 0.3e9, i * 0.3e9 + 0.2e9, "jit_pure_step(123)") for i in range(5)]
    trace = types.SimpleNamespace(devices=[{"modules": modules, "ops": []}])
    args = {"program_prefix": "jit_pure_step(", "module": "flops_xing4", "needs": "xing4_clm_step"}
    value = mfu.reduce(args, ctx, {"shapes": {"batch": 1, "seq": 4096}}, trace)
    assert abs(value - 100 * 11.676942336e12 / 197e12 / 0.2) < 1e-6     # 29.6 %
    assert mfu.reduce(args, ctx, {"shapes": {"batch": 1, "seq": 4096}}, None) is None
    trace.devices[0]["modules"] = []
    assert mfu.reduce(args, ctx, {"shapes": {"batch": 1, "seq": 4096}}, trace) is None


def test_counter_ratio_reads_the_programs_counters_or_nothing():
    from incubator_mxnet_tpu import profiler

    args = {"numerator": "moe_rows_routed_here", "denominator": "moe_step"}
    before = profiler.counters()
    profiler.incr("moe_rows_routed_here", 700)
    profiler.incr("moe_step", 2)
    after = profiler.counters()
    want = after["moe_rows_routed_here"] / after["moe_step"]
    assert counter_ratio.reduce(args, None, {}, None) == want
    assert after["moe_step"] - before["moe_step"] == 2
    assert counter_ratio.reduce({"numerator": "no_such_counter", "denominator": "moe_step"},
                                None, {}, None) is None

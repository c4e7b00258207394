"""What the LFM2-24B-A2B cell added to the benchmark: the configuration's file
against the published widths and its cut's arithmetic, the needed-FLOP and byte
counts against hand arithmetic, the reference against the model's own
forward, the seven metric files, and the dry run."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import flops_lfm2, traffic_clm
from chipbench.builders import lfm2_clm
from conftest import ROOT

CELL = "lfm2-24b-a2b.clm-b2-s8192"
PUBLISHED_WIDTHS = {
    "hidden_size": 2048, "intermediate_size": 11776, "moe_intermediate_size": 1536,
    "num_attention_heads": 32, "num_key_value_heads": 8, "num_experts_per_tok": 4,
    "conv_L_cache": 3, "routed_scaling_factor": 1, "norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
NEW_METRICS = ("train_step_mfu.lfm2", "short_conv_device_ms.train",
               "short_conv_core_device_ms.train", "short_conv_core_roofline",
               "gqa_attention_device_ms.lfm2", "experts_device_ms.lfm2", "dense_ffn_device_ms.lfm2")


def config(dry_run=False):
    c = json.load(open(os.path.join(ROOT, "chipbench", "configs", "lfm2-24b-a2b.json")))
    if dry_run:
        c.update(c["dry_run"])
    return c


def built(c):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo.lfm2_moe import Lfm2ForCausalLM

    mc, held = lfm2_clm.model_config(c)
    mx.random.seed(3)
    net = Lfm2ForCausalLM(mc, experts_held=held)
    net.initialize(mx.init.Normal(0.2))
    return net, mc, held


def test_lfm2_reference_matches_the_models_forward():
    import incubator_mxnet_tpu as mx

    from chipbench.reference import lfm2 as reference

    net, mc, held = built(config(dry_run=True))
    tok, _ = traffic_clm.clm_batch({"ids": {"dist": "zipf", "exponent": 1.0},
                                    "per_chip_batch": 2, "seq_length": 27}, 7, mc["vocab_size"], 1)
    want = np.asarray(net(mx.nd.array(tok, dtype="int32"))._data)
    named = {p.name: p._data._data for p in net.collect_params().values()}
    got = np.asarray(reference.forward(named, tok, config=mc, experts_held=held, query_block=9))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got / scale, want / scale, atol=1e-4)


def test_the_file_keeps_every_published_width_and_states_the_cut():
    c = config()
    for key, value in PUBLISHED_WIDTHS.items():
        assert c[key] == value, key
    assert c["published"] == {"num_hidden_layers": 40, "num_dense_layers": 2,
                              "num_experts": 64, "vocab_size": 65536}
    assert (c["num_hidden_layers"], c["num_dense_layers"], c["num_experts"],
            c["vocab_size"]) == (5, 1, 8, 8192)
    assert c["experts_held"] == [0, 8]
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    assert set(c["reduced"]) == set(c["cut"]) - {"parameters"}
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(e for e in bench["configs"] if e["name"] == "lfm2-24b-a2b")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    # the cut's layers are config.json's layer_types entries 1-5: one dense
    # layer, then one whole period beginning at an attention layer
    published = ["full_attention" if i % 4 == 2 else "conv" for i in range(40)]
    assert c["layer_types"] == published[1:6]
    assert published.count("conv") == 30


def test_needed_flops_and_parameters_are_the_hand_arithmetic():
    c = config()
    assert sum(flops_lfm2.conv_params(c).values()) == 16_783_360
    assert sum(flops_lfm2.attention_params(c).values()) == 10_485_888
    assert flops_lfm2.dense_params(c) == 72_351_744
    assert flops_lfm2.moe_params(c) == 75_628_608
    assert flops_lfm2.layer_counts(c) == {"conv": 4, "full_attention": 1, "dense": 1, "moe": 4}
    assert flops_lfm2.param_count(c) == 469_285_248          # 6.57 GB at 14 bytes
    macs = flops_lfm2.macs_per_token(c)
    assert macs["routed_experts"] == 4 * 4 * 8 / 64 * 9_437_184   # the expected share
    # per token, forward, MFLOP: each conv mixer 33.55, attention at S 8192
    # 54.53, the dense FFN 144.70, each expert layer 9.70, the head 33.55
    per_token = flops_lfm2.lfm2_clm_step(c, batch=1, seq=8192)["flops"] / 3 / 8192
    assert round(per_token / 1e6, 1) == 405.8
    need = flops_lfm2.lfm2_clm_step(c, batch=2, seq=8192)
    assert round(need["flops"] / 1e12, 2) == 19.95         # 101.2 ms at 197 TFLOP/s
    assert need["bytes"] == 469_285_248 * 32
    core = flops_lfm2.short_conv_core(c, batch=2, seq=8192)
    # B, C, x read and y written at 2 bytes, four conv layers, forward x 3: 3.22 GB
    assert core["bytes"] == 3 * 4 * 16384 * 2048 * 4 * 2
    assert core["flops"] == 3 * 4 * 16384 * 2048 * (2 + 2 * 3)
    # two periods: 832,652,032 (11.66 GB at 14 B)
    two = dict(c, num_hidden_layers=9, layer_types=["conv"] + ["full_attention", "conv",
                                                               "conv", "conv"] * 2)
    assert flops_lfm2.param_count(two) == 832_652_032
    # the count of parameters is the model's own
    tiny = config(dry_run=True)
    net, _, _ = built(tiny)
    held_params = sum(int(np.prod(p.shape)) for p in net.collect_params().values())
    assert flops_lfm2.param_count(tiny) == held_params


def test_the_weighted_change_reads_sign_flips_small_and_the_faults_large():
    """Adam's first step is nearly ``lr · sign(g)``: a gradient that differs
    from the reference's by 1 % of its rms flips the sign of the elements
    near zero, which the plain change difference counts whole and the form
    weighted by ``|g| + |g_ref|`` does not; a step that changed nothing reads
    1 in that form and Adam without its bias correction over 2."""
    import jax.numpy as jnp

    from chipbench.builders import first_step
    from chipbench.reference.solar_open2 import adam_first_step

    rng = np.random.default_rng(5)
    g_ref = rng.standard_normal(200_000).astype(np.float32)
    g = g_ref + 0.01 * rng.standard_normal(g_ref.shape).astype(np.float32)
    w0 = rng.standard_normal(g_ref.shape).astype(np.float32)
    kept, lr = np.float32(0.1), 1e-3
    adam = lambda w, grad: adam_first_step(w, grad, learning_rate=lr)
    unbiased = lambda grad: -lr * kept * grad / (jnp.sqrt(np.float32(0.001) * grad * grad) + 1e-8)

    def read(w32):
        s = dict(zip(first_step.SUMS, np.asarray(first_step.leaf_sums(
            kept * g, w32, w0, g_ref, g_ref, kept=kept, adam=adam, unbiased=unbiased),
            np.float64)))
        return ({k: first_step.ratio(s[k + "_diff"], s[k + "_ref"])
                 for k in ("grad", "change", "weighted", "above")},
                s["flipped"] / s["moved"], first_step.ratio(s["unbiased_diff"], s["weighted_ref"]))

    sound, flipped, biased = read(np.asarray(adam(w0, g)))
    assert 0.009 < sound["grad"] < 0.011
    assert 0.0005 < flipped < 0.01 and sound["change"] > 0.05
    # the flips lie under the gradients' difference: above three times it
    # nothing flips, and weighted they read a tenth of the plain form
    assert sound["above"] < 1e-3 and sound["weighted"] < 0.1 * sound["change"]
    assert biased > 2
    unchanged, _, _ = read(w0)
    assert unchanged["weighted"] == pytest.approx(1, rel=1e-3)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_the_new_metrics_read_this_cell_only(name):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["workloads"] == [CELL] and entry["moves"] == "train_tokens_per_s_chip"
    spec = json.load(open(os.path.join(ROOT, "chipbench", "layer_metrics", name + ".json")))
    scopes = spec["arguments"].get("scope", [])
    assert all(s.startswith("lfm2.") for s in scopes)


@pytest.mark.parametrize("trace", [0, 1])
def test_dry_run_reports_the_cells_metrics(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", CELL, "--seed",
         "3000000011", "--seconds", "2", "--trace", str(trace), "--dry-run-cpu"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    if trace:
        # every metric whose reader needs no device plane; the CPU's trace has none
        assert {"dispatch_ms.train", "expert_rows_per_step.train"} <= set(out["metrics"])
    else:
        assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}

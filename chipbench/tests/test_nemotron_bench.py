"""What PR 32 added to the benchmark: the Nemotron-H reference against the
model's own forward, the needed-FLOPs counts, the configuration's file against
the catalog's row, the dry run, and the scoped roofline reader on hand-made
inputs."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from chipbench import flops_nemotron, traffic_clm
from chipbench.builders import nemotron_clm
from chipbench.reducers import scoped_roofline
from conftest import ROOT

CELL = "nemotron-3-nano-30b-a3b.clm-s8192"
PUBLISHED_WIDTHS = {
    "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64, "n_groups": 8,
    "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128, "num_attention_heads": 32,
    "num_key_value_heads": 2, "head_dim": 128, "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "num_experts_per_tok": 6,
    "routed_scaling_factor": 2.5}


def config(dry_run=False):
    c = json.load(open(os.path.join(ROOT, "chipbench", "configs", "nemotron-3-nano-30b-a3b.json")))
    if dry_run:
        c.update(c["dry_run"])
    return c


def built(c):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo.nemotron_h import NemotronHForCausalLM

    mc, held = nemotron_clm.model_config(c)
    mx.random.seed(3)
    net = NemotronHForCausalLM(mc, experts_held=held)
    net.initialize(mx.init.Normal(0.2))
    return net, mc, held


def test_nemotron_reference_matches_the_models_forward():
    import incubator_mxnet_tpu as mx

    from chipbench.reference import nemotron_h as reference

    net, mc, held = built(config(dry_run=True))
    tok, _ = traffic_clm.clm_batch({"ids": {"dist": "zipf", "exponent": 1.0},
                                    "per_chip_batch": 2, "seq_length": 27}, 7, mc["vocab_size"], 1)
    want = np.asarray(net(mx.nd.array(tok, dtype="int32"))._data)
    named = {p.name: p._data._data for p in net.collect_params().values()}
    got = np.asarray(reference.forward(named, tok, config=mc, experts_held=held, query_block=8))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got / scale, want / scale, atol=1e-4)


def test_the_file_keeps_every_published_width_and_states_the_cut():
    c = config()
    for key, value in PUBLISHED_WIDTHS.items():
        assert c[key] == value, key
    assert c["published"] == {"num_hidden_layers": 52, "n_routed_experts": 128, "vocab_size": 131072,
                              "hybrid_override_pattern": c["published"]["hybrid_override_pattern"]}
    published = c["published"]["hybrid_override_pattern"]
    assert len(published) == 52 and [published.count(k) for k in "ME*"] == [23, 23, 6]
    assert c["hybrid_override_pattern"] == published[:9] == "MEMEM*EME"
    assert (c["num_hidden_layers"], c["n_routed_experts"], c["vocab_size"]) == (9, 8, 16384)
    assert c["experts_held"] == [0, 8]
    assert set(c["reduced"]) == set(c["published"]) == set(c["cut"]) - {"parameters"}
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(e for e in bench["configs"] if e["name"] == "nemotron-3-nano-30b-a3b")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):      # every key of the catalog's row, unchanged unless reduced
        row = next(r for r in map(json.loads, open(catalog)) if r["source_url"] == c["source"])
        for key, value in row["config"].items():
            assert key in c, key
            assert c[key] == value or key in c["reduced"], key


def test_needed_flops_are_the_issues_arithmetic():
    c = config()
    mamba = flops_nemotron.mamba_params(c)
    assert mamba["in_proj"] == 2688 * 10304 and sum(mamba.values()) == 38_742_208
    assert flops_nemotron.attention_params(c) == 23_396_352
    assert flops_nemotron.expert_params(c) == 9_977_856
    assert flops_nemotron.shared_expert_params(c) == 19_955_712
    macs = flops_nemotron.macs_per_token(c)
    assert macs["mamba_projections"] == 4 * (27_697_152 + 11_010_048)         # 155 M of ...
    assert macs["routed_experts"] == 4 * 6 * 8 / 128 * 9_977_856              # the expected share
    assert round(sum(macs.values()) / 1e6) == 318                             # ... 318 M a token
    assert flops_nemotron.param_count(c) == 666_963_456
    need = flops_nemotron.nemotron_clm_step(c, batch=1, seq=8192)
    assert round(need["flops"] / 1e12, 1) == 17.5
    scan = flops_nemotron.mamba2_scan(c, batch=1, seq=8192)
    assert scan["flops"] == 3 * 4 * 4 * 8192 * 64 * 64 * 128
    assert scan["bytes"] == 3 * 4 * 8192 * (2 * 4096 + 2 * 1024 + 64) * 2
    # the count of parameters is the model's own
    tiny = config(dry_run=True)
    net, _, _ = built(tiny)
    held_params = sum(int(np.prod(p.shape)) for p in net.collect_params().values())
    assert flops_nemotron.param_count(tiny) == held_params


def test_clm_s8192_is_one_unpadded_sequence_over_the_slice():
    traffic = json.load(open(os.path.join(ROOT, "chipbench", "traffic", "clm-s8192.json")))
    tok, labels = traffic_clm.clm_batch(traffic, 3000000011, 16384, 1)
    assert tok.shape == labels.shape == (1, 8192) and tok.dtype == np.int32
    np.testing.assert_array_equal(tok[:, 1:], labels[:, :-1])
    assert 0 <= tok.min() and tok.max() < 16384
    assert (traffic["per_chip_batch"], traffic["warmup_steps"], traffic["driver"]) == (1, 4, "train_steps")


def _scoped_trace(scan_ms):
    """Five executions of a step, each with one operation that the program's
    text puts under the scan's scope and one that it does not."""
    modules, ops = [], []
    for i in range(5):
        t0 = i * 0.4e9
        modules.append((t0, t0 + 0.3e9, "jit_pure_step(123)"))
        ops.append((t0, t0 + scan_ms * 1e6, "%fusion.1 = f32[8] fusion(...)"))
        ops.append((t0 + 0.2e9, t0 + 0.25e9, "%fusion.2 = f32[8] fusion(...)"))
    return types.SimpleNamespace(devices=[{"modules": modules, "ops": ops}])


def test_scoped_roofline_divides_the_needed_time_by_the_scoped_device_time(monkeypatch):
    from chipbench import trace_scopes

    scopes = {"fusion.1": "jit(pure_step)/nemotron.mamba/nemotron.mamba.scan/dot_general",
              "fusion.2": "jit(pure_step)/nemotron.mamba/nemotron.mamba.conv/add"}
    monkeypatch.setattr(trace_scopes, "program_scopes", lambda site: scopes)
    said = []
    ctx = types.SimpleNamespace(config=config(), say=said.append,
                                devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    args = json.load(open(os.path.join(ROOT, "chipbench", "layer_metrics",
                                       "ssm_scan_roofline.json")))["arguments"]
    result = {"shapes": {"batch": 1, "seq": 8192}}
    value = scoped_roofline.reduce(args, ctx, result, _scoped_trace(20.0))
    need = flops_nemotron.mamba2_scan(config(), 1, 8192)
    at_peak_ms = max(need["flops"] / 197e12, need["bytes"] / 819e9) * 1e3
    assert abs(value - 100 * at_peak_ms / 20.0) < 1e-6 and "memory-bound" in said[-1]
    # a program without the scope (the parent commit's), no text, no trace: left out
    monkeypatch.setattr(trace_scopes, "program_scopes", lambda site: {"fusion.2": scopes["fusion.2"]})
    assert scoped_roofline.reduce(args, ctx, result, _scoped_trace(20.0)) is None
    monkeypatch.setattr(trace_scopes, "program_scopes", lambda site: None)
    assert scoped_roofline.reduce(args, ctx, result, _scoped_trace(20.0)) is None
    assert scoped_roofline.reduce(args, ctx, result, None) is None


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

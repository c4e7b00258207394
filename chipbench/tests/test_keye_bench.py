"""What PR 34 added to the benchmark: the Keye reference against the model's
own forward, the needed-FLOPs counts, the configuration's file against the
catalog's row, the dry run, and the new metric files on hand-made inputs."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from chipbench import flops_keye, traffic_clm
from chipbench.builders import keye_clm
from chipbench.reducers import counter_ratio, scoped_roofline
from conftest import ROOT

CELL = "keye-vl-2.0-30b-a3b.clm-s8192"
PUBLISHED_WIDTHS = {
    "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
    "moe_intermediate_size": 768, "intermediate_size": 6144, "num_experts_per_tok": 8,
    "rope_theta": 10000000, "rms_norm_eps": 1e-06, "norm_topk_prob": True,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048}}


def config(dry_run=False):
    c = json.load(open(os.path.join(ROOT, "chipbench", "configs", "keye-vl-2.0-30b-a3b.json")))
    if dry_run:
        c.update(c["dry_run"])
    return c


def built(c):
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.gluon.model_zoo.keye import KeyeForCausalLM

    mc, held = keye_clm.model_config(c)
    mx.random.seed(3)
    net = KeyeForCausalLM(mc, experts_held=held)
    net.initialize(mx.init.Normal(0.2))
    return net, mc, held


def test_keye_reference_matches_the_models_forward():
    import incubator_mxnet_tpu as mx

    from chipbench.reference import keye as reference

    net, mc, held = built(config(dry_run=True))
    tok, _ = traffic_clm.clm_batch({"ids": {"dist": "zipf", "exponent": 1.0},
                                    "per_chip_batch": 2, "seq_length": 27}, 7, mc["vocab_size"], 1)
    want = np.asarray(net(mx.nd.array(tok, dtype="int32"))._data)
    named = {p.name: p._data._data for p in net.collect_params().values()}
    got, terms = reference.forward(named, tok, config=mc, experts_held=held, query_block=9,
                                   with_terms=True)
    scale = np.abs(want).max()
    np.testing.assert_allclose(np.asarray(got) / scale, want / scale, atol=1e-4)
    # the dry run's topk 12 is below its length: 15 of 27 queries select
    chosen = np.asarray(terms["selections"])
    assert chosen.shape == (2, 2, 27, 27)
    assert (chosen.sum(-1) == np.minimum(np.arange(27) + 1, 12)).all()
    assert float(terms["index_loss"]) > 0 and float(terms["balance"]) > 0


def test_the_file_keeps_every_published_width_and_states_the_cut():
    c = config()
    for key, value in PUBLISHED_WIDTHS.items():
        assert c[key] == value, key
    assert c["rope_scaling"]["mrope_section"] == [16, 24, 24]
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                              "num_local_experts": 128, "vocab_size": 151936}
    assert (c["num_hidden_layers"], c["num_experts"], c["num_local_experts"],
            c["vocab_size"]) == (6, 16, 16, 18992)
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"] and c["experts_held"] == [0, 16]
    assert set(c["reduced"]) == set(c["published"]) == set(c["cut"]) - {"parameters"}
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(e for e in bench["configs"] if e["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):      # every key of the catalog's row, unchanged unless reduced
        row = next(r for r in map(json.loads, open(catalog)) if r["source_url"] == c["source"])
        for key, value in row["config"].items():
            assert key in c, key
            assert c[key] == value or key in c["reduced"], key
    # the dry run selects: its topk is below its length
    traffic = json.load(open(os.path.join(ROOT, "chipbench", "traffic", "clm-s8192.json")))
    assert c["dry_run"]["sa_config"]["topk"] < traffic["dry_run"]["seq_length"]


def test_needed_flops_are_the_issues_arithmetic():
    c = config()
    assert flops_keye.attention_params(c) == 2 * 8_388_608 + 2 * 1_048_576
    assert flops_keye.indexer_params(c) == 2048 * (1024 + 64 + 16)
    assert flops_keye.expert_params(c) == 4_718_592
    macs = flops_keye.macs_per_token(c)
    assert macs["routed_experts"] == 6 * 8 * 16 / 128 * 4_718_592             # the expected share
    assert macs["router"] == 6 * 128 * 2048 and macs["head"] == 18992 * 2048
    assert flops_keye.param_count(c) == 659_190_016
    assert flops_keye.selected_pairs(c, 8192) == 14_681_088                   # of 33,558,528 causal
    need = flops_keye.keye_clm_step(c, batch=1, seq=8192)
    assert round(need["flops"] / 1e12, 2) == 15.18
    core = flops_keye.sparse_attention_core(c, batch=1, seq=8192)
    assert core["flops"] == 3 * 6 * 4 * 128 * 32 * 14_681_088
    assert core["bytes"] == 3 * 6 * 8192 * 128 * 2 * (32 + 4) * 2
    index = flops_keye.indexer_select(c, batch=1, seq=8192)
    assert index["flops"] == 3 * 6 * 2 * 64 * 16 * (8192 * 8193 // 2)
    assert index["bytes"] == 6 * (3 * 8192 * (1024 + 64 + 16) * 4 + 8192 * 2048 * 4)
    # a sequence no longer than topk selects every causal pair
    assert flops_keye.selected_pairs(c, 1024) == 1024 * 1025 // 2
    # the count of parameters is the model's own
    tiny = config(dry_run=True)
    net, _, _ = built(tiny)
    held_params = sum(int(np.prod(p.shape)) for p in net.collect_params().values())
    assert flops_keye.param_count(tiny) == held_params


def test_the_cell_takes_the_clm_s8192_traffic_that_is_there():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("keye-vl-2.0-30b-a3b", "clm-s8192", 1)
    traffic = json.load(open(os.path.join(ROOT, "chipbench", "traffic", "clm-s8192.json")))
    tok, labels = traffic_clm.clm_batch(traffic, 3000000011, config()["vocab_size"], 1)
    assert tok.shape == labels.shape == (1, 8192) and 0 <= tok.min() and tok.max() < 18992
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert listed == {"dispatch_ms.train", "device_step_ms.train", "device_idle_share.train",
                      "programs_per_step.train", "expert_rows_per_step.train",
                      "train_step_mfu.keye", "sparse_attention_device_ms.train",
                      "indexer_select_device_ms.train", "index_loss_device_ms.train",
                      "sparse_attention_core_roofline", "indexer_select_roofline",
                      "experts_device_ms.keye", "sparse_tiles_live_share.train"}


def _scoped_trace(index_ms):
    modules, ops = [], []
    for i in range(5):
        t0 = i * 0.4e9
        modules.append((t0, t0 + 0.3e9, "jit_pure_step(123)"))
        ops.append((t0, t0 + index_ms * 1e6, "%fusion.1 = f32[8] fusion(...)"))
        ops.append((t0 + 0.2e9, t0 + 0.25e9, "%fusion.2 = f32[8] fusion(...)"))
    return types.SimpleNamespace(devices=[{"modules": modules, "ops": ops}])


@pytest.mark.parametrize("metric,needs,scope", [
    ("sparse_attention_core_roofline", "sparse_attention_core", "keye.attn.core"),
    ("indexer_select_roofline", "indexer_select", "keye.attn.select")])
def test_the_two_rooflines_read_their_scopes_and_stay_silent_without_them(monkeypatch, metric,
                                                                         needs, scope):
    from chipbench import trace_scopes

    scopes = {"fusion.1": f"jit(pure_step)/keye.attn/{scope}/dot_general",
              "fusion.2": "jit(pure_step)/keye.attn/keye.attn.proj/add"}
    monkeypatch.setattr(trace_scopes, "program_scopes", lambda site: scopes)
    said = []
    ctx = types.SimpleNamespace(config=config(), say=said.append,
                                devices=[types.SimpleNamespace(device_kind="TPU v5 lite")])
    args = json.load(open(os.path.join(ROOT, "chipbench", "layer_metrics", metric + ".json")))["arguments"]
    result = {"shapes": {"batch": 1, "seq": 8192}}
    value = scoped_roofline.reduce(args, ctx, result, _scoped_trace(40.0))
    need = getattr(flops_keye, needs)(config(), 1, 8192)
    at_peak_ms = max(need["flops"] / 197e12, need["bytes"] / 819e9) * 1e3
    assert abs(value - 100 * at_peak_ms / 40.0) < 1e-6 and "compute-bound" in said[-1]
    assert value < 100
    # a program without the scope (the parent commit's): left out, nothing raised
    monkeypatch.setattr(trace_scopes, "program_scopes", lambda site: {"fusion.2": scopes["fusion.2"]})
    assert scoped_roofline.reduce(args, ctx, result, _scoped_trace(40.0)) is None


def test_live_tile_share_is_the_two_counters_ratio():
    from incubator_mxnet_tpu import profiler

    args = json.load(open(os.path.join(ROOT, "chipbench", "layer_metrics",
                                       "sparse_tiles_live_share.train.json")))["arguments"]
    before = profiler.counters()
    profiler.incr("sparse_attn_tiles_live", 500)
    profiler.incr("sparse_attn_tiles_causal", 816)
    after = profiler.counters()
    want = after["sparse_attn_tiles_live"] / after["sparse_attn_tiles_causal"]
    assert counter_ratio.reduce(args, None, None, None) == want       # a plain ratio: the unit is "ratio"
    assert after["sparse_attn_tiles_causal"] - before["sparse_attn_tiles_causal"] == 816
    # a program that has not the counters (the parent commit's): left out
    assert counter_ratio.reduce({"numerator": "no_such", "denominator": "moe_step"},
                                None, None, None) is None


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
        assert {"dispatch_ms.train", "expert_rows_per_step.train",
                "sparse_tiles_live_share.train"} <= set(out["metrics"])
    else:
        assert set(out["metrics"]) == {"train_tokens_per_s_chip", "setup_s"}

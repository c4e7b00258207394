"""BENCHMARK.json against the contract's limits, and against the files."""
import importlib
import json
import os
import re

import pytest

from chipbench import run
from conftest import ROOT, load_bench

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = load_bench()
BOTH = pytest.mark.parametrize("bench", [BENCH, load_bench(with_shelved=True)],
                               ids=["BENCHMARK.json", "with-shelved-cells"])
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}


def reported_by(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


@BOTH
def test_keys_names_units_and_lengths(bench):
    BENCH = bench
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for section, keys in KEYS.items():
        names = [e["name"] for e in BENCH[section]]
        assert len(names) == len(set(names)), section
        for e in BENCH[section]:
            assert set(e) - {"workloads"} == keys, e
            assert NAME.match(e["name"]), e["name"]
            for field in ("why", "source", "layer"):
                if field in e:
                    assert 1 <= len(e[field]) <= 200 and "\n" not in e[field] and "\t" not in e[field]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@BOTH
def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric(bench):
    BENCH = bench
    for w in BENCH["workloads"]:
        e2e = [m["name"] for m in BENCH["end_to_end"] if reported_by(m, w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert any(reported_by(m, w["name"]) for m in BENCH["per_layer"]), w["name"]


@BOTH
def test_every_layer_metric_moves_a_metric_its_cells_report(bench):
    BENCH = bench
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        for w in BENCH["workloads"]:
            if reported_by(m, w["name"]):
                assert reported_by(e2e[m["moves"]], w["name"]), (m["name"], w["name"])


@BOTH
def test_every_name_resolves_to_a_file_and_a_module(bench):
    BENCH = bench
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in configs.values():
        assert c["file"].startswith("chipbench/")
        spec = json.load(open(os.path.join(ROOT, c["file"])))
        importlib.import_module(f"chipbench.builders.{spec['builder']}")
        assert c["reduced"] == spec["reduced"]
    for w in BENCH["workloads"]:
        assert w["config"] in configs
        spec = json.load(open(os.path.join(ROOT, "chipbench", "traffic", w["traffic"] + ".json")))
        importlib.import_module(f"chipbench.drivers.{spec['driver']}")
    for m in BENCH["per_layer"]:
        spec = run.layer_metric_spec(m["name"])
        assert hasattr(importlib.import_module(f"chipbench.reducers.{spec['reducer']}"), "reduce")
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == set(configs)


def test_no_traffic_or_config_file_sets_a_scheduling_knob():
    for sub in ("configs", "traffic"):
        folder = os.path.join(ROOT, "chipbench", sub)
        for name in os.listdir(folder):
            text = open(os.path.join(folder, name)).read()
            spec = json.loads(text)
            assert "MXNET_" not in json.dumps({k: v for k, v in spec.items()
                                               if k not in ("assumed", "why")})
            assert "max_prefills_per_iter" not in spec.get("server", {})
            assert "batching" not in spec.get("server", {})

"""The fed BERT cell's yardstick (PR 36): the two reducers that read the
input path's spans, on hand-made ``(start, end, name)`` tuples; the cell's
entries in BENCHMARK.json; the feeding driver's data checks; and the cell
end to end on the CPU (``--dry-run-cpu``)."""
import importlib
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from chipbench import run, trace_read
from chipbench.drivers import train_feed
from chipbench.reducers import idle_under_span, span_time_per_span

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "bert-base.pretrain-s128-fed"
NEW_METRICS = {"input_wait_ms.train": "span_time_per_span",
               "input_read_ms.train": "span_time_per_span",
               "input_transfer_ms.train": "span_time_per_span",
               "input_stall_share.train": "counter_ratio",
               "idle_in_input_wait_share.train": "idle_under_span"}
MS = 1e6  # ns
WINDOW = (100 * MS, 200 * MS)
STEPS = [(100 * MS + i * 20 * MS, 119 * MS + i * 20 * MS, "spmd.step")
         for i in range(5)]  # five steps close in the window
WITNESS = [(150 * MS, 151 * MS, "io.transfer")]


def trace(host, ops=None):
    devices = [] if ops is None else [{"modules": [], "ops": trace_read.clip(ops, WINDOW)}]
    return trace_read.Trace(devices, host, WINDOW)


WAIT_PER_STEP = {"span": "io.wait", "per": "spmd.step", "witness": "io.transfer"}


@pytest.mark.parametrize("host, want", [
    # the instrumentation ran and nothing waited: 0, never "nothing to read"
    (STEPS + WITNESS, 0.0),
    # no witness: the program has no such spans
    (STEPS, None),
    (STEPS + [(110 * MS, 113 * MS, "io.wait")], None),
    # a witness outside the window is none
    (STEPS + [(10 * MS, 20 * MS, "io.transfer")], None),
    # no step closed in the window: nothing to divide by
    (WITNESS + [(110 * MS, 113 * MS, "io.wait")], None),
    # 3 ms + 2 ms over five steps
    (STEPS + WITNESS + [(110 * MS, 113 * MS, "io.wait"),
                        (160 * MS, 162 * MS, "io.wait")], 1.0),
    # two threads inside the span at once count twice: thread-ms
    (STEPS + WITNESS + [(110 * MS, 115 * MS, "io.wait"),
                        (112 * MS, 117 * MS, "io.wait")], 2.0),
    # a span that straddles either edge is clipped to the window
    (STEPS + WITNESS + [(90 * MS, 105 * MS, "io.wait"),
                        (195 * MS, 230 * MS, "io.wait")], 2.0),
    # a step that straddles the window's end has not closed in it
    (STEPS[:4] + [(180 * MS, 201 * MS, "spmd.step")] + WITNESS
     + [(110 * MS, 114 * MS, "io.wait")], 1.0),
], ids=["no-span-with-witness", "no-witness", "span-without-witness",
        "witness-outside", "no-step", "sum-over-steps", "two-threads",
        "clipped-at-both-edges", "open-step-not-counted"])
def test_span_time_per_span(host, want):
    got = span_time_per_span.reduce(WAIT_PER_STEP, None, {}, trace(host))
    assert got == (want if want is None else pytest.approx(want))


def test_span_time_per_span_without_a_trace():
    assert span_time_per_span.reduce(WAIT_PER_STEP, None, {}, None) is None


IDLE_IN_WAIT = {"span": "io.wait", "witness": "io.transfer"}
# busy 100-140, idle 140-150, busy 150-190, idle 190-200: 20 ms idle
OPS = [(100 * MS, 140 * MS, "%fusion.1"), (150 * MS, 190 * MS, "%fusion.2")]


@pytest.mark.parametrize("host, ops, want", [
    # the device never idle: 0, whatever the host did
    (WITNESS + [(120 * MS, 130 * MS, "io.wait")],
     [(90 * MS, 210 * MS, "%fusion.0")], 0.0),
    # idle, and the span never open
    (WITNESS, OPS, 0.0),
    # no witness, or no device plane: left out
    ([(142 * MS, 148 * MS, "io.wait")], OPS, None),
    (WITNESS + [(142 * MS, 148 * MS, "io.wait")], None, None),
    # 6 of the 20 idle ms under the span; the part under busy time is not idle
    (WITNESS + [(130 * MS, 146 * MS, "io.wait")], OPS, 0.3),
    # two threads overlapping in the span count once: 141-149 = 8 of 20
    (WITNESS + [(141 * MS, 147 * MS, "io.wait"),
                (143 * MS, 149 * MS, "io.wait")], OPS, 0.4),
    # a span straddling the window's end is clipped: 195-200 = 5 of 20
    (WITNESS + [(195 * MS, 260 * MS, "io.wait")], OPS, 0.25),
    # a gap under 20 us is launch latency, not idle time
    (WITNESS + [(100 * MS, 200 * MS, "io.wait")],
     [(100 * MS, 150 * MS, "%a"), (150 * MS + 10_000, 200 * MS, "%b")], 0.0),
], ids=["no-idle-time", "span-never-open", "no-witness", "no-device",
        "idle-under-span", "two-threads-once", "clipped", "launch-latency"])
def test_idle_under_span(host, ops, want):
    got = idle_under_span.reduce(IDLE_IN_WAIT, None, {}, trace(host, ops))
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_new_metric_files_resolve_to_their_reducers(metric):
    spec = run.layer_metric_spec(metric)
    assert spec["reducer"] == NEW_METRICS[metric] and spec["what"]
    reducer = importlib.import_module(f"chipbench.reducers.{spec['reducer']}")
    if spec["reducer"] != "counter_ratio":  # its counters are the process's
        # a program without the spans (the parent): nothing to read, no raise
        assert reducer.reduce(spec["arguments"], None, {}, trace(STEPS, OPS)) is None


def test_the_fed_cell_reports_setup_throughput_and_ten_layer_metrics():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "bert-base", "pretrain-s128-fed", 1)
    assert [m["name"] for m in run.metrics_of(bench, "end_to_end", CELL)] == [
        "train_tokens_per_s_chip", "setup_s"]
    layer = {m["name"]: m for m in run.metrics_of(bench, "per_layer", CELL)}
    assert set(layer) == set(NEW_METRICS) | {
        "dispatch_ms.train", "device_step_ms.train", "train_step_roofline",
        "device_idle_share.train", "programs_per_step.train"}
    for name in NEW_METRICS:
        m = layer[name]
        assert (m["layer"], m["moves"], m["workloads"], m["better"]) == (
            "input path", "train_tokens_per_s_chip", [CELL], "lower")
    # the mix sets no scheduling knob and keeps pretrain-s128's shape
    fed = run.load_json(ROOT, "chipbench", "traffic", "pretrain-s128-fed.json")
    resident = run.load_json(ROOT, "chipbench", "traffic", "pretrain-s128.json")
    assert fed["driver"] == "train_feed" and fed["records"] == 8192
    for key in ("per_chip_batch", "seq_length", "masked_positions", "warmup_steps"):
        assert fed[key] == resident[key]
    assert not {"depth", "max_depth", "num_workers", "memory_budget_mb"} & set(fed)


def _records(n=24, widths=(6, 6, 2, 2), seed=5):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 1000, (n, w)).astype(np.int32) for w in widths]


def _batches(arrays, order, size):
    return [[a[order[i:i + size]] for a in arrays]
            for i in range(0, len(order) - size + 1, size)]


def test_record_file_round_trip_by_plain_numpy(tmp_path):
    from incubator_mxnet_tpu.gluon.data import RecordFileDataset

    arrays = _records()
    path = str(tmp_path / "instances.rec")
    train_feed.write_records(path, arrays)
    table = train_feed.read_records_plain(path, 24, 16)
    np.testing.assert_array_equal(table, np.concatenate(arrays, axis=1))
    # the program's reader and the driver's decoder give the same records
    data = RecordFileDataset(path).transform(train_feed.decoder([6, 6, 2, 2]))
    assert len(data) == 24
    for got, want in zip(data[7], arrays):
        np.testing.assert_array_equal(got, want[7])
    with pytest.raises(RuntimeError):
        train_feed.read_records_plain(path, 23, 16)


@pytest.mark.parametrize("fault, want", [
    (None, True), ("label", False), ("unknown-row", False),
    ("twice-in-an-epoch", False), ("epoch-boundary-elsewhere", False)])
def test_feed_matches_file(fault, want):
    arrays = _records()
    table = np.concatenate(arrays, axis=1)
    rng = np.random.RandomState(1)
    # the stream: epochs of 6 batches of 4, each holding every record once
    # (so a record comes again, rightly, in the next epoch); the window
    # opens 3 batches in
    stream = sum((_batches(arrays, rng.permutation(24), 4) for _ in range(3)), [])
    window = [[a.copy() for a in b] for b in stream[3:15]]
    first = 3
    if fault == "label":
        window[4][3][2, 1] += 1
    elif fault == "unknown-row":
        window[4][0][2, 0] += 1
    elif fault == "twice-in-an-epoch":
        window[5] = window[4]  # stream batches 7 and 8: both of epoch 1
    elif fault == "epoch-boundary-elsewhere":
        first = 4  # epoch 0's last batch is then judged with epoch 1's
    assert train_feed.feed_matches_file(window, table, 6, first, 6) is want


def test_seeded_shuffle_draws_a_new_permutation_each_pass():
    a, b = train_feed.SeededShuffle(50, 2 ** 31 + 7), train_feed.SeededShuffle(50, 2 ** 31 + 7)
    first, second = list(a), list(a)
    assert sorted(first) == sorted(second) == list(range(50)) and first != second
    assert [list(b), list(b)] == [first, second] and len(a) == 50


def test_the_driver_takes_trainer_and_batch_from_the_builders_closure():
    trainer, tok, seg, pos, labels = object(), 1, 2, 3, 4
    step = lambda: trainer.step((tok, seg, pos), labels)  # the builders' form
    assert train_feed.trainer_and_batch(step) == (trainer, [1, 2, 3, 4])
    other = types.SimpleNamespace(step=lambda *a: None)
    with pytest.raises(RuntimeError, match="trainer"):
        train_feed.trainer_and_batch(lambda: other.step((tok, seg, pos), labels))


def test_the_fed_cell_rehearses_on_the_cpu_traced(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"), "--workload", CELL,
         "--seed", "3000000011", "--seconds", "2", "--trace", "1", "--dry-run-cpu"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 16
    checks = next(ln for ln in lines if "set-up took" in ln)
    for name in ("feed_matches_file", "a_fresh_batch_every_step", "loss_finite",
                 "no_compile_in_window", "logits_match_reference"):
        assert f"'{name}': True" in checks, checks
    # every metric a host plane alone can give (the CPU has no device plane:
    # the five device readings are left out here, as in every cell)
    assert set(out["metrics"]) == {
        "dispatch_ms.train", "input_wait_ms.train", "input_read_ms.train",
        "input_transfer_ms.train", "input_stall_share.train"}
    assert all(m["value"] is None for m in out["metrics"].values())
    # the record file's directory went with the run
    assert not [d for d in os.listdir(tmp_path) if d.startswith("chipbench-feed-")]

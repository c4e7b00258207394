"""The trace reduction on synthetic events: (start_ns, end_ns, name)."""
import pytest

from chipbench import trace_read
from chipbench.reducers import (collective_time, device_program_time,
                                host_span, idle_share)


def make_trace():
    # window 0..1000; ops busy 100-300 (two overlapping), 400-500, 900-1000
    ops = [(100.0, 250.0, "%fusion.1 = f32[8] fusion(f32[8] %a), kind=kLoop"),
           (200.0, 300.0, "%copy.2 = f32[8] copy(f32[8] %b)"),
           (400.0, 500.0, "%fusion.1 = f32[8] fusion(f32[8] %a), kind=kLoop"),
           (900.0, 1100.0, "%copy.2 = f32[8] copy(f32[8] %b)")]
    modules = [(100.0, 300.0, "jit_step(1)"), (400.0, 500.0, "jit_pure(7)"),
               (900.0, 1100.0, "jit_pure(7)")]
    host = [(0.0, 1000.0, trace_read.WINDOW_SPAN),
            (0.0, 90.0, "dispatch"), (300.0, 400.0, "d2h"),
            (310.0, 390.0, "inner"), (480.0, 600.0, "dispatch")]
    window = (0.0, 1000.0)
    dev = {"modules": trace_read.clip(modules, window),
           "ops": trace_read.clip(ops, window)}
    return trace_read.Trace([dev], host, window)


def test_union_counts_overlap_once_and_clips_to_the_window():
    trace = make_trace()
    # 100-300 + 400-500 + 900-1000 (cut at the window's end) = 400 ns
    assert trace_read.device_busy_seconds(trace) == pytest.approx(400e-9)
    assert idle_share.reduce({}, None, {}, trace) == pytest.approx(60.0)


def test_per_program_time_by_name_and_by_label():
    trace = make_trace()
    times = trace_read.program_times(trace)
    assert times["jit_pure(7)"] == pytest.approx([100e-9, 100e-9])
    by_name = device_program_time.reduce({"program_prefix": "jit_step("}, None, {}, trace)
    assert by_name == pytest.approx(200e-6)
    labelled = {"programs": {"jit_pure(7)": "decode_8"}}
    by_label = device_program_time.reduce({"label_prefix": "decode_"}, None, labelled, trace)
    assert by_label == pytest.approx(100e-6)
    assert device_program_time.reduce({"label_prefix": "prefill_"}, None, labelled, trace) is None


def test_gaps_are_keyed_by_the_innermost_host_span_open_in_them():
    trace = make_trace()
    gaps = trace_read.idle_gaps(trace, min_gap_ns=50.0)
    assert gaps[0] == (500.0, 900.0)           # longest first
    assert sorted(gaps) == [(0.0, 100.0), (300.0, 400.0), (500.0, 900.0)]
    rows = dict(trace_read.attribute_gaps(gaps, trace.host))
    assert rows["no-span"] == pytest.approx(400e-9)   # midpoint 700: nothing open
    assert rows["inner"] == pytest.approx(100e-9)     # inside "d2h", shorter wins
    assert rows["dispatch"] == pytest.approx(100e-9)  # midpoint 50


def test_top_ops_and_short_names():
    trace = make_trace()
    top = trace_read.top_ops(trace, n=1)
    assert top[0][0] == "%fusion.1 = f32[8] fusion" and top[0][1] == pytest.approx(250e-9)
    long = ("%fusion.9 = (bf16[4,8]{1,0:T(8,128)(2,1)}, f32[4]{0}) "
            "fusion(bf16[4,8]{1,0} %x, f32[] %y), kind=kLoop")
    assert trace_read.short_op_name(long) == "%fusion.9 = (bf16[4,8], f32[4]) fusion"
    copy = "%copy.1 = f32[6,64]{1,0:T(8,128)} copy(f32[6,64]{0,1:T(8,128)} %b)"
    assert trace_read.short_op_name(copy) == "%copy.1 = f32[6,64] copy"


def test_host_span_median_inside_the_window_only():
    trace = make_trace()
    assert host_span.reduce({"span": "dispatch"}, None, {}, trace) == pytest.approx(105e-6)
    assert host_span.reduce({"span": "absent"}, None, {}, trace) is None


def test_only_the_part_of_a_collective_that_nothing_overlaps_is_exposed():
    # two steps of 500; all-reduce 100-300 with a fusion over 100-250, and a
    # start/done pair of 20 each in the second step; a fusion that CONSUMES
    # %all-reduce.5 is no collective
    ops = [(100.0, 300.0, "%all-reduce.5 = (f32[8]{0}, f32[4]{0}) all-reduce(f32[8]{0} %g, f32[4]{0} %h), replica_groups={}"),
           (100.0, 250.0, "%fusion.1 = f32[8] fusion(f32[8] %all-reduce.5), kind=kLoop"),
           (600.0, 620.0, "%all-reduce-start.7 = (f32[8], f32[8]) all-reduce-start(f32[8] %g)"),
           (620.0, 900.0, "%fusion.2 = f32[8] fusion(f32[8] %a), kind=kLoop"),
           (900.0, 920.0, "%all-reduce-done.7 = f32[8] all-reduce-done((f32[8], f32[8]) %all-reduce-start.7)")]
    modules = [(0.0, 500.0, "jit_pure_step(9)"), (500.0, 1000.0, "jit_pure_step(9)")]
    trace = trace_read.Trace([{"modules": modules, "ops": ops}], [], (0.0, 1000.0))
    args = {"program_prefix": "jit_pure_step("}
    # open: 100-300, 600-620, 900-920 = 240 ns over two steps
    assert collective_time.reduce(args, None, {}, trace) == pytest.approx(120e-6)
    # exposed: 250-300, 600-620, 900-920 = 90 ns
    exposed = dict(args, exposed=True)
    assert collective_time.reduce(exposed, None, {}, trace) == pytest.approx(45e-6)
    one_chip = trace_read.Trace([{"modules": modules, "ops": ops[1:2]}], [], (0.0, 1000.0))
    assert collective_time.reduce(exposed, None, {}, one_chip) is None


def test_a_reader_that_finds_nothing_is_named():
    from chipbench import run

    bench = {"per_layer": [{"name": "device_idle_share.train", "unit": "%"},
                           {"name": "dispatch_ms.train", "unit": "ms"}]}
    ctx = type("Ctx", (), {"cell": {"name": "c"}, "dry_run": False})()
    trace = make_trace()
    trace.host.append((10.0, 30.0, "chipbench.train_step"))
    out, missing = run.reduce_layer_metrics(bench, ctx, {}, trace)
    assert missing == [] and out["device_idle_share.train"]["value"] == pytest.approx(60.0)
    out, missing = run.reduce_layer_metrics(bench, ctx, {}, None)
    assert out == {} and missing == ["device_idle_share.train", "dispatch_ms.train"]

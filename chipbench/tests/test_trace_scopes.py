"""The phase split, span self time and programs per step: on synthetic
``(start_ns, end_ns, name)`` tuples with a scope for each name, and on one
small trace recorded on a v5e (``data/tiny_step_trace.json``)."""
import json
import os

import pytest

from chipbench import trace_read, trace_scopes
from chipbench.reducers import (programs_per_span, scoped_device_time,
                                span_self_time)

HERE = os.path.dirname(os.path.abspath(__file__))
STEP = {"program_prefix": "jit_pure_step(", "site": "spmd.step"}
FORWARD = dict(STEP, scope=["spmd.forward", "spmd.loss"], exclude=["transpose("])
BACKWARD = dict(STEP, scope=["transpose(jvp(spmd.forward", "transpose(jvp(spmd.loss"],
                exclude=[])
OPTIMIZER = dict(STEP, scope=["spmd.optimizer"], exclude=[])
UNSCOPED = dict(STEP, scope=[""],
                exclude=["spmd.forward", "spmd.loss", "spmd.optimizer"])

HLO = """HloModule jit_pure_step, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %mul.3 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(pure_step)/spmd.optimizer/jit(adam_update)/mul"}
  ROOT %add.4 = f32[8]{0} add(%mul.3, %p), metadata={op_name="jit(pure_step)/transpose(jvp(spmd.forward))/net0/dense0/dot_general"}
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(pure_step)/jvp(spmd.forward)/net0/dense0/dot_general" source_file="x.py"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(pure_step)/spmd.optimizer/jit(adam_update)/sub"}
  %copy-start.5 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%a)
  ROOT %copy-done.5 = f32[8]{0} copy-done(%copy-start.5)
}
"""


def op(name):
    return f"%{name} = f32[8] fusion(f32[8] %a), kind=kLoop"


def make_trace():
    # window 0..2000.  Two whole steps (100-600, 700-1200) and a third that
    # the window cuts (1800-2300 -> 1800-2000); a small program between them
    # whose one operation has a name the step's text also knows.
    def step(t):
        return [(t + 0, t + 100, op("fusion.2")),       # forward
                (t + 50, t + 150, op("fusion.2")),      # forward, overlapping
                (t + 150, t + 350, op("fusion.1")),     # backward, by its root
                (t + 350, t + 400, op("fusion.3")),     # optimizer
                (t + 400, t + 500, op("copy-done.5"))]  # no op_name anywhere
    ops = step(100) + step(700) + step(1800) + [(640.0, 660.0, op("fusion.3"))]
    modules = [(100.0, 600.0, "jit_pure_step(11)"), (640.0, 660.0, "jit_convert(3)"),
               (700.0, 1200.0, "jit_pure_step(11)"), (1800.0, 2300.0, "jit_pure_step(11)")]
    host = [(0.0, 2000.0, trace_read.WINDOW_SPAN),
            (80.0, 620.0, "spmd.step"), (90.0, 120.0, "spmd.step.args"),
            (120.0, 600.0, "spmd.step.enqueue"), (600.0, 615.0, "spmd.step.obs"),
            (680.0, 1260.0, "spmd.step"), (700.0, 1200.0, "spmd.step.enqueue"),
            (1780.0, 2400.0, "spmd.step"), (1800.0, 2300.0, "spmd.step.enqueue")]
    window = (0.0, 2000.0)
    dev = {"modules": trace_read.clip(modules, window), "ops": trace_read.clip(ops, window)}
    return trace_read.Trace([dev], host, window)


@pytest.fixture
def served(monkeypatch):
    """The step's text as the program would serve it."""
    monkeypatch.setitem(trace_scopes._texts, "spmd.step", trace_scopes.hlo_scopes(HLO))


def test_a_fusion_counts_under_its_own_op_name_else_under_its_root():
    scopes = trace_scopes.hlo_scopes(HLO)
    assert "jvp(spmd.forward)" in scopes["fusion.2"]          # its own
    assert "transpose(jvp(spmd.forward))" in scopes["fusion.1"]  # its root's
    assert scopes["copy-done.5"] == "" and "main.9" not in scopes
    assert scopes["mul.3"].endswith("adam_update)/mul")       # fused instructions too
    assert trace_scopes.instruction_name(op("fusion.2")) == "fusion.2"


@pytest.mark.parametrize("arguments,whole,cut", [
    (FORWARD, 150.0, 150.0),    # 100-200 and 150-250: the union, once
    (BACKWARD, 200.0, 50.0),    # under transpose(jvp(spmd.forward)): not forward
    (OPTIMIZER, 50.0, 0.0),     # the other program's %fusion.3 is not the step's
    (UNSCOPED, 100.0, 0.0),
], ids=["forward", "backward", "optimizer", "unscoped"])
def test_phase_time_per_execution_with_a_step_cut_by_the_window(served, arguments, whole, cut):
    # 2.4 executions: 500 + 500 + the 200 ns the window leaves of the third,
    # which holds 150 ns of forward and 50 of backward
    got = scoped_device_time.reduce(arguments, None, {}, make_trace())
    assert got == pytest.approx((2 * whole + cut) / 2.4 / 1e6)


def test_the_four_phases_leave_nothing_of_the_steps_busy_time_out(served):
    trace = make_trace()
    parts = [scoped_device_time.reduce(a, None, {}, trace)
             for a in (FORWARD, BACKWARD, OPTIMIZER, UNSCOPED)]
    runs = trace_scopes.executions(trace, STEP["program_prefix"])
    busy = trace_scopes.busy_ms_per_execution(trace_scopes.ops_of(trace, runs), runs)
    assert sum(parts) == pytest.approx(busy)


def test_no_text_no_metric(monkeypatch):
    # a parent commit's program serves none; another program's text joins nothing
    monkeypatch.setitem(trace_scopes._texts, "spmd.step", None)
    assert scoped_device_time.reduce(FORWARD, None, {}, make_trace()) is None
    monkeypatch.setitem(trace_scopes._texts, "spmd.step", {"fusion.99": "spmd.forward"})
    assert scoped_device_time.reduce(UNSCOPED, None, {}, make_trace()) is None
    assert scoped_device_time.reduce(FORWARD, None, {}, trace_read.Trace([], [], (0.0, 1.0))) is None
    monkeypatch.delitem(trace_scopes._texts, "spmd.step")
    from incubator_mxnet_tpu import profiler
    monkeypatch.delattr(profiler, "compiled_text", raising=False)
    assert trace_scopes.program_scopes("spmd.step") is None


def test_span_self_time_and_programs_per_span():
    trace = make_trace()
    # spmd.step 80-620 less enqueue 120-600 = 60; 680-1260 less 500 = 80;
    # the third step's span ends after the window and is left out
    work = span_self_time.reduce({"span": "spmd.step", "minus": ["spmd.step.enqueue"]},
                                 None, {}, trace)
    assert work == pytest.approx(70e-6)
    assert span_self_time.reduce({"span": "spmd.step.enqueue", "minus": []},
                                 None, {}, trace) == pytest.approx(490e-6)
    assert span_self_time.reduce({"span": "generation.step"}, None, {}, trace) is None
    # four program executions in the window, two spmd.step spans closed in it
    assert programs_per_span.reduce({"span": "spmd.step"}, None, {}, trace) == pytest.approx(2.0)
    assert programs_per_span.reduce({"span": "nothing"}, None, {}, trace) is None


@pytest.fixture(scope="module")
def recorded():
    rec = json.load(open(os.path.join(HERE, "data", "tiny_step_trace.json")))
    window = tuple(rec["window"])
    dev = {"modules": trace_read.clip([tuple(e) for e in rec["modules"]], window),
           "ops": trace_read.clip([tuple(e) for e in rec["ops"]], window)}
    text = open(os.path.join(HERE, "data", "tiny_step_hlo.txt")).read()
    return trace_read.Trace([dev], [tuple(e) for e in rec["host"]], window), text


def test_on_a_recorded_trace(recorded, monkeypatch):
    trace, text = recorded
    scopes = trace_scopes.hlo_scopes(text)
    monkeypatch.setitem(trace_scopes._texts, "spmd.step", scopes)
    runs = trace_scopes.executions(trace, STEP["program_prefix"])
    ops = trace_scopes.ops_of(trace, runs)
    assert len(runs) == 6 and len(ops) == 6 * 88
    # every operation of the step is an instruction of its text
    assert all(trace_scopes.instruction_name(n) in scopes for _, _, n in ops)
    fwd, bwd, opt, rest = (scoped_device_time.reduce(a, None, {}, trace)
                           for a in (FORWARD, BACKWARD, OPTIMIZER, UNSCOPED))
    busy = trace_scopes.busy_ms_per_execution(ops, runs)
    assert fwd + bwd + opt + rest == pytest.approx(busy, rel=0.02)
    assert 0 < opt < fwd < bwd and rest > 0   # XLA fused Adam into the backward matmuls
    assert programs_per_span.reduce({"span": "spmd.step"}, None, {}, trace) == pytest.approx(9.0)
    work = span_self_time.reduce({"span": "spmd.step", "minus": ["spmd.step.enqueue"]},
                                 None, {}, trace)
    wait = span_self_time.reduce({"span": "spmd.step.enqueue", "minus": []}, None, {}, trace)
    assert 3.5 < work < 5.0 and 0.3 < wait < 0.6    # ms, as recorded

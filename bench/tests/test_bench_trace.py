"""The trace reduction, on a synthetic TPU trace and a recorded CPU one."""
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import trace_reduce  # noqa: E402

# one TPU with a window of 100 us: the fitness kernel 10..30 and 25..40
# (overlapping), another op 50..60, an all-gather 70..75; two executions of
# the fault simulator's program; host spans for the window and an artifact
# write over 40..100
_EV = 'events {{ metadata_id: {m} offset_ps: {o} duration_ps: {d} {s}}}'
XSPACE = """
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {ops} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    {mods} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fitness_errors.7 = f32[8,128]{{1,0}} custom-call(f32[8,640]{{1,0}} %pad.1), custom_call_target=\\"tpu_custom_call\\"" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%fusion.12 = f32[8]{{0}} fusion(f32[8,128]{{1,0}} %fitness_errors.7), kind=kLoop" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%all-gather.3 = f32[16]{{0}} all-gather(f32[4]{{0}} %p)" }} }}
  event_metadata {{ key: 4 value {{ id: 4 name: "jit__sim_one(4)" }} }}
}}
planes {{
  id: 2
  name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {spans} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench:window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench:write_pareto_artifact" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "PjitFunction(chunk)" }} }}
}}
"""
US = 1_000_000  # ps


def _xspace() -> str:
    ops = " ".join([
        _EV.format(m=1, o=10 * US, d=20 * US, s=""),
        _EV.format(m=1, o=25 * US, d=15 * US, s=""),
        _EV.format(m=2, o=50 * US, d=10 * US, s=""),
        _EV.format(m=3, o=70 * US, d=5 * US, s=""),
    ])
    mods = " ".join(_EV.format(m=4, o=o * US, d=US, s="") for o in (80, 90))
    spans = " ".join([
        _EV.format(m=1, o=0, d=100 * US, s=""),
        _EV.format(m=2, o=40 * US, d=60 * US, s=""),
        _EV.format(m=3, o=0, d=10 * US, s=""),
    ])
    return XSPACE.format(ops=ops, mods=mods, spans=spans)


@pytest.fixture(scope="module")
def trace():
    import jax

    return trace_reduce.from_profile(
        jax.profiler.ProfileData.from_text_proto(_xspace()))


def test_planes_and_spans(trace):
    assert sorted(trace.devices) == [0]
    assert [o.name for o in trace.devices[0]] == [
        "fitness_errors.7", "fitness_errors.7", "fusion.12", "all-gather.3"]
    assert [o.kind for o in trace.devices[0]] == [
        "fitness_errors", "fitness_errors", "fusion", "all-gather"]
    assert [s.name for s in trace.spans] == ["window",
                                            "write_pareto_artifact"]
    assert trace.window("window") == (0.0, 100_000.0)
    assert len(trace.modules[0]) == 2


def test_busy_union_and_idle_share(trace):
    lo, hi = trace.window("window")
    # 10..40 (overlap merged) + 50..60 + 70..75 = 45 us of 100
    assert trace_reduce.busy_seconds(trace.devices[0], lo, hi) == \
        pytest.approx(45e-6)
    # clipping to a sub-window cuts ops at its edges
    assert trace_reduce.busy_seconds(trace.devices[0], 20_000, 55_000) == \
        pytest.approx(25e-6)


def test_kernel_time_by_name(trace):
    # the fusion that reads the kernel's output names it as an operand only
    fit = trace_reduce.of_kind(trace.devices[0], "fitness_errors")
    assert len(fit) == 2
    assert sum(o.seconds for o in fit) == pytest.approx(35e-6)
    lo, hi = trace.window("window")
    assert trace_reduce.busy_seconds(fit, lo, hi) == pytest.approx(30e-6)


def test_op_totals_leave_out_loops():
    ops = [trace_reduce.Op("while.3", 0, 100), trace_reduce.Op("fusion.1", 0, 40),
           trace_reduce.Op("fusion.2", 50, 60)]
    assert trace_reduce.op_totals(ops) == [("fusion", pytest.approx(50e-9))]


def test_idle_gaps_are_put_down_to_the_innermost_span(trace):
    lo, hi = trace.window("window")
    gaps = dict(trace_reduce.idle_gaps(trace.devices[0], trace.spans, lo, hi))
    # 0..10 lies in the window only; 40..50, 60..70, 75..100 in the writer
    assert gaps == pytest.approx({"window": 10e-6,
                                  "write_pareto_artifact": 45e-6})


def test_readers_on_the_synthetic_trace(trace):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    import spec

    lo, hi = trace.window("window")
    red = types.SimpleNamespace(
        trace=trace, lo=lo, hi=hi, ops={0: trace.devices[0]},
        busy_s=trace_reduce.busy_seconds(trace.devices[0], lo, hi),
        window_s=(hi - lo) * 1e-9)
    faults = types.SimpleNamespace(reduced=red, devices=[0],
                                   counters={"kind": "faults", "lanes": 6})
    assert spec.reader("fault_lanes_per_dispatch")(faults) == 3.0
    assert spec.reader("idle_share.faults")(faults) == pytest.approx(55.0)
    assert spec.reader("idle_share.search")(faults) is None
    search = types.SimpleNamespace(reduced=red, devices=[0], counters={
        "kind": "search", "generations": 5, "save_calls": 2})
    # (45 us busy - 30 us kernel) / 5 generations = 3 us = 0.003 ms
    assert spec.reader("nsga2_ms_per_gen")(search) == pytest.approx(0.003)
    # no save span: no stall; the artifact span holds 45 us of idle device
    assert spec.reader("checkpoint_stall_ms")(search) == 0.0


def test_recorded_trace_has_the_benchmark_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x.T)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + "window"):
        with jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + "step"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace_reduce.load(str(tmp_path))
    names = [s.name for s in tr.spans]
    assert names == ["window", "step"]
    lo, hi = tr.window("window")
    step = tr.spans[1]
    assert lo <= step.start_ns <= step.end_ns <= hi

"""The reader `fault_dispatches_per_fetch`: the fault simulator's dispatches
for each copy of predictions back to the host, on synthetic totals and on a
real fault slice."""
import contextlib
import sys
import types
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import spec  # noqa: E402

NAME = "fault_dispatches_per_fetch"


def _span(calls, seconds):
    return {"calls": calls, "seconds": seconds, "self_seconds": seconds}


# a fault window of 10 slices of 32 dispatches (256 lanes a slice), one copy
# back a slice
TOTALS = {
    "faults.run": _span(10, 0.8),
    "faults.fetch": _span(10, 0.48),
    "faults.dispatches": 320, "faults.lanes": 2560,
}


@pytest.fixture
def totals(monkeypatch):
    from repro.runtime import spans

    t = dict(TOTALS)
    monkeypatch.setattr(spans, "totals", lambda: t)
    return t


def _run(kind):
    return types.SimpleNamespace(counters={"kind": kind})


def test_reads_dispatches_over_fetches_by_hand(totals):
    assert spec.reader(NAME)(_run("faults")) == 32.0


def test_stays_silent_in_a_search_cell(totals):
    assert spec.reader(NAME)(_run("search")) is None


@pytest.mark.parametrize("missing", ["faults.fetch", "faults.dispatches"])
def test_stays_silent_without_its_counters(totals, missing):
    del totals[missing]
    assert spec.reader(NAME)(_run("faults")) is None


def test_stays_silent_on_a_program_without_spans(monkeypatch):
    """A program that predates the recorder: the import fails, no value."""
    import repro.runtime

    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    monkeypatch.delattr(repro.runtime, "spans")
    assert spec.reader(NAME)(_run("faults")) is None


def test_stays_silent_on_an_empty_profile(monkeypatch):
    from repro.runtime import spans

    monkeypatch.setattr(spans, "totals", dict)
    assert spec.reader(NAME)(_run("faults")) is None


@pytest.fixture(scope="module")
def seeds_simulator():
    from repro.core import faults, netlist, quant
    from repro.core.train import train_tree
    from repro.core.tree import to_parallel
    from repro.datasets import load_dataset, quantize_u8

    ds = load_dataset("seeds")
    pt = to_parallel(train_tree(ds.x_train, ds.y_train, ds.n_classes))
    bits = np.full(pt.n_comparators, 8)
    t_int = np.asarray(quant.threshold_to_int(pt.threshold, bits))
    circuit = netlist.build_circuit([pt], bits, t_int, ds.n_classes)
    return faults.FaultSimulator(circuit), quantize_u8(ds.x_test)[:16]


@pytest.mark.parametrize("traced", [True, False], ids=["traced", "untraced"])
def test_reads_a_real_slice(seeds_simulator, tmp_path, traced):
    """A 256-lane slice at chunk 8 is 32 dispatches behind one copy back;
    without a profile the program records nothing and the reader is
    silent."""
    import jax
    from repro.core import faults
    from repro.runtime import spans

    sim, x8 = seeds_simulator
    gates, values = faults.single_fault_lanes(sim.circuit)
    lanes = np.arange(256) % len(gates)
    spans.reset()
    try:
        with (jax.profiler.trace(str(tmp_path)) if traced
              else contextlib.nullcontext()):
            sim.run_sites(x8, gates[lanes], values[lanes], chunk=8)
        got = spec.reader(NAME)(_run("faults"))
    finally:
        spans.reset()
    assert got == (32.0 if traced else None)

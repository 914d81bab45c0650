"""The readers of the program's own spans and counters
(`repro.runtime.spans`), on synthetic totals."""
import sys
import types
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import spec  # noqa: E402

SEARCH = ("artifact_decode_s_per_campaign", "artifact_netlist_s_per_campaign",
          "retrace_s_per_campaign", "checkpoint_write_ms")
FAULTS = ("fault_host_ms_per_dispatch", "fault_fetch_ms_per_dispatch")


def _span(calls, seconds, self_seconds=None):
    return {"calls": calls, "seconds": seconds,
            "self_seconds": seconds if self_seconds is None else self_seconds}


# four campaigns with 50 saves each; a fault window of 10 slices of 32
# dispatches (256 lanes a slice)
TOTALS = {
    "search.run": _span(4, 28.0, 2.0),
    "artifact.decode": _span(800, 6.0),
    "artifact.netlist": _span(800, 10.0),
    "checkpoint.write": _span(200, 3.0, 2.5),
    "artifact.points": 800, "artifact.distinct_points": 200,
    "search.run/jit.trace_s": 1.5, "search.run/jit.lower_s": 0.9,
    "artifact.decode/jit.trace_s": 0.1, "jit.lower_s": 0.02,
    "search.run/jit.compiles": 3,
    "faults.run": _span(10, 0.8, 0.32),
    "faults.fetch": _span(320, 0.48),
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


def test_search_readers_by_hand(totals):
    run = _run("search")
    assert spec.reader("artifact_decode_s_per_campaign")(run) == 1.5
    assert spec.reader("artifact_netlist_s_per_campaign")(run) == 2.5
    assert spec.reader("retrace_s_per_campaign")(run) == pytest.approx(
        (1.5 + 0.9 + 0.1 + 0.02) / 4)
    assert spec.reader("checkpoint_write_ms")(run) == pytest.approx(12.5)


def test_fault_readers_by_hand(totals):
    run = _run("faults")
    assert spec.reader("fault_host_ms_per_dispatch")(run) == pytest.approx(1.0)
    assert spec.reader("fault_fetch_ms_per_dispatch")(run) == pytest.approx(
        1.5)


@pytest.mark.parametrize("name", SEARCH + FAULTS)
def test_readers_stay_silent_in_the_other_cell(totals, name):
    other = "faults" if name in SEARCH else "search"
    assert spec.reader(name)(_run(other)) is None


@pytest.mark.parametrize("name,missing", [
    ("artifact_decode_s_per_campaign", "artifact.decode"),
    ("artifact_decode_s_per_campaign", "search.run"),
    ("artifact_netlist_s_per_campaign", "artifact.netlist"),
    ("artifact_netlist_s_per_campaign", "search.run"),
    ("retrace_s_per_campaign", "search.run"),
    ("checkpoint_write_ms", "checkpoint.write"),
    ("fault_host_ms_per_dispatch", "faults.run"),
    ("fault_host_ms_per_dispatch", "faults.dispatches"),
    ("fault_fetch_ms_per_dispatch", "faults.fetch"),
    ("fault_fetch_ms_per_dispatch", "faults.dispatches"),
])
def test_readers_stay_silent_without_their_counters(totals, name, missing):
    del totals[missing]
    kind = "search" if name in SEARCH else "faults"
    assert spec.reader(name)(_run(kind)) is None


@pytest.mark.parametrize("name", SEARCH + FAULTS)
def test_readers_stay_silent_on_a_program_without_spans(monkeypatch, name):
    """A program that predates the recorder: the import fails, no value."""
    import repro.runtime

    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    monkeypatch.delattr(repro.runtime, "spans")
    kind = "search" if name in SEARCH else "faults"
    assert spec.reader(name)(_run(kind)) is None


@pytest.mark.parametrize("name", SEARCH + FAULTS)
def test_readers_stay_silent_on_an_empty_profile(monkeypatch, name):
    from repro.runtime import spans

    monkeypatch.setattr(spans, "totals", dict)
    kind = "search" if name in SEARCH else "faults"
    assert spec.reader(name)(_run(kind)) is None

"""The program's host spans and counters (`repro.runtime.spans`): silent
outside a profile; inside one, exact counts at the search driver, the
checkpoint and artifact writers and the fault simulator, self times, and
``repro:`` events in the profile itself."""
import glob
import json
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro import search
from repro.core import faults, netlist, quant
from repro.core.train import train_tree
from repro.core.tree import to_parallel
from repro.datasets import load_dataset, quantize_u8
from repro.runtime import checkpoint, spans


@pytest.fixture(autouse=True)
def _fresh_totals():
    spans.reset()
    yield
    spans.reset()


@pytest.fixture(scope="module")
def problem():
    ds = load_dataset("vertebral")
    tree = train_tree(ds.x_train, ds.y_train, ds.n_classes)
    return search.build_tree_problem(to_parallel(tree), ds.x_test, ds.y_test)


@pytest.fixture(scope="module")
def fault_case():
    ds = load_dataset("seeds")
    pt = to_parallel(train_tree(ds.x_train, ds.y_train, ds.n_classes))
    bits = np.full(pt.n_comparators, 6)
    t_int = np.asarray(quant.threshold_to_int(pt.threshold, bits))
    circuit = netlist.build_circuit([pt], bits, t_int, ds.n_classes)
    return faults.FaultSimulator(circuit), quantize_u8(ds.x_test)[:16]


@pytest.fixture(scope="module")
def traced_search(problem, tmp_path_factory):
    """One small campaign with checkpoints and a written front, profiled:
    (totals, pareto.json, saves, profile directory)."""
    root = tmp_path_factory.mktemp("traced_search")
    out, log_dir = str(root / "run"), str(root / "profile")
    saves = []
    save = checkpoint.save

    def counting_save(*a, **kw):
        saves.append(a[1])
        return save(*a, **kw)

    spans.reset()
    checkpoint.save = counting_save
    try:
        with jax.profiler.trace(log_dir):
            search.run_search(problem, backend="reference", pop_size=8,
                              n_generations=5, checkpoint_every=2,
                              out_dir=out)
    finally:
        checkpoint.save = save
    totals = spans.totals()
    with open(os.path.join(out, "pareto.json")) as f:
        payload = json.load(f)
    return totals, payload, saves, log_dir


def test_nothing_accumulates_outside_a_profile(problem, fault_case, tmp_path):
    assert not spans.recording()
    with spans.span("outer", call=1):
        spans.count("n", 3)
    sim, x8 = fault_case
    sim.run_sites(x8, np.array([2, 3, 4]), np.array([0, 1, 0]), chunk=2)
    search.run_search(problem, backend="reference", pop_size=8,
                      n_generations=2, checkpoint_every=1,
                      out_dir=str(tmp_path / "run"))
    assert spans.totals() == {}


def test_search_campaign_counts_exactly(traced_search):
    totals, payload, saves, _ = traced_search
    n_points = len(payload["pareto"])
    assert totals["search.run"]["calls"] == 1
    assert saves == [2, 4, 5]
    assert totals["checkpoint.write"]["calls"] == len(saves)
    assert totals["artifact.points"] == n_points
    # the whole front decodes in one call, padded to the population (8)
    assert totals["artifact.decode"]["calls"] == 1
    assert totals["artifact.decode_rows"] == 8
    assert totals["artifact.netlist"]["calls"] == n_points
    genes = np.array([p["genes"] for p in payload["pareto"]])
    assert totals["artifact.distinct_points"] == len(np.unique(genes, axis=0))
    # the campaign's fresh jitted chunks trace under its span
    assert totals["search.run/jit.trace_s"] > 0
    assert totals["search.run/jit.lower_s"] > 0
    run = totals["search.run"]
    children = sum(totals[k]["seconds"] for k in
                   ("checkpoint.write", "artifact.decode", "artifact.netlist"))
    assert run["self_seconds"] == pytest.approx(run["seconds"] - children)


@pytest.mark.parametrize("n_lanes,chunk", [(20, 8), (16, 8), (5, 16)])
def test_fault_lanes_and_dispatches(fault_case, tmp_path, n_lanes, chunk):
    sim, x8 = fault_case
    gates, values = faults.single_fault_lanes(sim.circuit)
    with jax.profiler.trace(str(tmp_path)):
        preds = sim.run_sites(x8, gates[:n_lanes], values[:n_lanes],
                              chunk=chunk)
    totals = spans.totals()
    n_dispatches = -(-n_lanes // min(chunk, n_lanes))
    assert preds.shape == (n_lanes, x8.shape[0])
    assert totals["faults.lanes"] == n_lanes
    assert totals["faults.dispatches"] == n_dispatches
    assert totals["faults.run"]["calls"] == 1
    # every dispatch is in flight before the call's one copy back
    assert totals["faults.fetch"]["calls"] == 1


def test_self_time_is_duration_less_children(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with spans.span("outer"):
            time.sleep(0.01)
            for _ in range(2):
                with spans.span("inner"):
                    with spans.span("leaf"):
                        time.sleep(0.005)
    t = spans.totals()
    assert t["outer"]["calls"] == 1 and t["inner"]["calls"] == 2
    assert t["outer"]["self_seconds"] == pytest.approx(
        t["outer"]["seconds"] - t["inner"]["seconds"])
    assert t["inner"]["self_seconds"] == pytest.approx(
        t["inner"]["seconds"] - t["leaf"]["seconds"])
    assert t["leaf"]["self_seconds"] == t["leaf"]["seconds"]
    assert t["outer"]["self_seconds"] >= 0.01
    spans.reset()
    assert spans.totals() == {}


def test_threads_nest_their_own_spans_and_lose_no_count(tmp_path):
    n_threads, n_iter = (os.cpu_count() or 4) + 2, 200
    switch = sys.getswitchinterval()

    def work(i):
        for _ in range(n_iter):
            with spans.span(f"outer{i}"):
                with spans.span(f"inner{i}"):
                    spans.count("n")
                spans.count("n", 2)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    sys.setswitchinterval(1e-6)
    try:
        with jax.profiler.trace(str(tmp_path)):
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    t = spans.totals()
    assert t["n"] == 3 * n_threads * n_iter
    for i in range(n_threads):
        outer, inner = t[f"outer{i}"], t[f"inner{i}"]
        assert outer["calls"] == inner["calls"] == n_iter
        # a thread's inner spans are children of its own outer spans only
        assert outer["self_seconds"] == pytest.approx(
            outer["seconds"] - inner["seconds"])
        assert inner["self_seconds"] == inner["seconds"]


def test_profile_holds_program_events_with_the_campaign(traced_search):
    _, payload, _, log_dir = traced_search
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    host, = [p for p in profile.planes if p.name == "/host:CPU"]
    events = [(e.name, dict(e.stats)) for line in host.lines
              for e in line.events if e.name.startswith(spans.PREFIX)]
    names = {n for n, _ in events}
    assert {"repro:search.run", "repro:checkpoint.write",
            "repro:artifact.decode", "repro:artifact.netlist"} <= names
    campaign = {s["campaign"] for n, s in events if n == "repro:search.run"}
    assert len(campaign) == 1
    # every span under the campaign carries its id
    assert all(s.get("campaign") in campaign for _, s in events)
    assert sum(n == "repro:artifact.decode" for n, _ in events) == 1
    assert sum(n == "repro:artifact.netlist" for n, _ in events) == len(
        payload["pareto"])

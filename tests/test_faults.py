"""Stuck-at fault injection suite (DESIGN.md §17).

Pins `core.faults` to its two oracles and the campaign layer to its schema:

  - the zero-fault (empty mask) lane is bit-identical to
    `core.netlist.simulate` on a tree, a K>1 forest under BOTH vote-adder
    modes, and an MLP MAC circuit;
  - the exhaustive single stuck-at campaign matches the serial per-gate
    Python oracle array-for-array on the same circuit zoo;
  - Monte-Carlo campaigns reproduce bit-for-bit under a fixed seed and
    move under a different one;
  - `fault_report.json` round-trips its validator; missing/unknown keys at
    every nesting level raise named `ValueError`s (never bare `KeyError`);
  - the `faults` and `serve` CLIs exit 2 with a one-line error on
    missing/truncated artifacts.
"""
from __future__ import annotations

import copy
import json

import numpy as np
import pytest

from repro import search
from repro.core import faults, netlist, quant
from repro.core.forest import train_forest
from repro.core.train import train_tree
from repro.core.tree import to_parallel
from repro.datasets import load_dataset, quantize_u8
from repro.search import robustness
from repro.search.__main__ import faults_main, serve_main

N_VECTORS = 32   # test-split slice driving the bit-exactness differentials


def _tree_circuit(dataset="seeds", n_trees=1, vote_adder="exact"):
    ds = load_dataset(dataset)
    if n_trees <= 1:
        ptrees = [to_parallel(train_tree(ds.x_train, ds.y_train,
                                         ds.n_classes))]
    else:
        ptrees = train_forest(ds.x_train, ds.y_train, ds.n_classes,
                              n_trees=n_trees).ptrees
    n = sum(p.n_comparators for p in ptrees)
    rng = np.random.default_rng(hash((dataset, n_trees)) % 2**32)
    bits = rng.integers(quant.MIN_BITS, quant.MAX_BITS + 1, n)
    thr = np.concatenate([p.threshold for p in ptrees])
    t_int = np.asarray(quant.threshold_to_int(thr, bits))
    circuit = netlist.build_circuit(ptrees, bits, t_int, ds.n_classes,
                                    vote_adder=vote_adder)
    x8 = quantize_u8(ds.x_test)[:N_VECTORS]
    y = np.asarray(ds.y_test[:N_VECTORS], np.int64)
    return circuit, x8, y


def _mlp_circuit():
    """A tiny integer MLP netlist — small enough for the serial oracle."""
    rng = np.random.default_rng(3)
    f, h, c = 3, 4, 3
    w1 = rng.integers(-3, 4, (f, h))
    w2 = rng.integers(-3, 4, (h, c))
    circuit = netlist.build_mlp_circuit(w1, w2, 4, c)
    x8 = quantize_u8(rng.uniform(0, 1, (N_VECTORS, f)).astype(np.float32))
    y = rng.integers(0, c, N_VECTORS).astype(np.int64)
    return circuit, x8, y


CIRCUITS = {
    "tree": lambda: _tree_circuit("seeds", 1),
    "forest_exact": lambda: _tree_circuit("vertebral", 3, "exact"),
    "forest_approx": lambda: _tree_circuit("vertebral", 3, "approx"),
    "mlp": _mlp_circuit,
}


@pytest.fixture(scope="module", params=sorted(CIRCUITS))
def circuit_case(request):
    circuit, x8, y = CIRCUITS[request.param]()
    return request.param, circuit, x8, y


# --- site enumeration ------------------------------------------------------

def test_sites_cover_every_non_const_gate(circuit_case):
    """Sites = all INPUT + logic gates, in gate-id order, constants never."""
    _, circuit, _, _ = circuit_case
    sites = faults.enumerate_fault_sites(circuit)
    op = np.asarray(circuit.op)
    expect = np.flatnonzero(op >= netlist.INPUT)
    assert [s.gate for s in sites] == expect.tolist()
    for s in sites:
        assert s.kind == ("input" if op[s.gate] == netlist.INPUT else "gate")
        if s.kind == "input":
            assert s.label == f"input[f{s.feature}.b{s.bit}]"
            assert (s.feature, s.bit) == (int(circuit.a[s.gate]),
                                          int(circuit.b[s.gate]))
        else:
            assert s.label.endswith(f"@{s.gate}")


def test_single_fault_lanes_pair_every_site():
    circuit, _, _ = _tree_circuit()
    gates, values = faults.single_fault_lanes(circuit)
    sites = faults.enumerate_fault_sites(circuit)
    assert len(gates) == 2 * len(sites)
    assert gates[::2].tolist() == gates[1::2].tolist()
    assert values[::2].tolist() == [0] * len(sites)
    assert values[1::2].tolist() == [1] * len(sites)


# --- the two oracle pins ---------------------------------------------------

def test_zero_fault_bit_identical_to_simulate(circuit_case):
    """Acceptance: the empty-mask lane IS `netlist.simulate`, bit for bit,
    on trees, forests (both vote adders), and MLP MAC circuits."""
    _, circuit, x8, _ = circuit_case
    sim = faults.FaultSimulator(circuit)
    np.testing.assert_array_equal(
        sim.run_zero_fault(x8), np.asarray(netlist.simulate(circuit, x8)))


def test_exhaustive_single_stuck_at_matches_serial_oracle(circuit_case):
    """Acceptance: every (site, polarity) lane of the vmapped campaign
    equals the serial per-gate Python oracle, array for array."""
    _, circuit, x8, _ = circuit_case
    sim = faults.FaultSimulator(circuit)
    gates, values = faults.single_fault_lanes(circuit)
    preds = sim.run_sites(x8, gates, values, chunk=17)  # pad-and-crop path
    assert preds.shape == (len(gates), x8.shape[0])
    for i in range(len(gates)):
        serial = faults.simulate_faulty_serial(
            circuit, x8, [(gates[i], values[i])])
        np.testing.assert_array_equal(preds[i], serial, err_msg=(
            f"lane {i}: gate {gates[i]} stuck-at-{values[i]}"))


def test_serial_oracle_zero_fault_matches_simulate(circuit_case):
    _, circuit, x8, _ = circuit_case
    np.testing.assert_array_equal(
        faults.simulate_faulty_serial(circuit, x8),
        np.asarray(netlist.simulate(circuit, x8)))


def test_multi_fault_mask_matches_serial_oracle():
    """Multi-hot masks (the Monte-Carlo shape) agree with the serial oracle
    applying the same fault set."""
    circuit, x8, _ = _tree_circuit()
    sites = faults.enumerate_fault_sites(circuit)
    rng = np.random.default_rng(7)
    sim = faults.FaultSimulator(circuit)
    for trial in range(4):
        chosen = rng.choice(len(sites), size=3, replace=False)
        vals = rng.integers(0, 2, 3)
        mask = np.zeros((1, circuit.n_gates), bool)
        val = np.zeros((1, circuit.n_gates), bool)
        pairs = []
        for s, v in zip(chosen, vals):
            mask[0, sites[s].gate] = True
            val[0, sites[s].gate] = bool(v)
            pairs.append((sites[s].gate, int(v)))
        np.testing.assert_array_equal(
            sim.run_masks(x8, mask, val)[0],
            faults.simulate_faulty_serial(circuit, x8, pairs))


def test_run_masks_shape_validation():
    circuit, x8, _ = _tree_circuit()
    sim = faults.FaultSimulator(circuit)
    bad = np.zeros((2, circuit.n_gates + 1), bool)
    with pytest.raises(ValueError, match="stuck masks must be"):
        sim.run_masks(x8, bad, bad)
    good = np.zeros((2, circuit.n_gates), bool)
    with pytest.raises(ValueError, match="do not match"):
        sim.run_masks(x8, good, np.zeros((3, circuit.n_gates), bool))


def _multi_hot_lanes(circuit, n_lanes, seed=5):
    """(n_lanes, G) Monte-Carlo-shaped masks: 1–4 random sites a lane."""
    sites = np.array([s.gate for s in faults.enumerate_fault_sites(circuit)])
    rng = np.random.default_rng(seed)
    mask = np.zeros((n_lanes, circuit.n_gates), bool)
    for lane in range(n_lanes):
        mask[lane, rng.choice(sites, size=rng.integers(1, 5),
                              replace=False)] = True
    return mask, mask & rng.integers(0, 2, mask.shape).astype(bool)


@pytest.mark.parametrize("shape", ["single_hot", "multi_hot"])
def test_chunking_is_invisible(shape):
    """Any chunk size — 1, prime, larger than the lane count — returns the
    identical campaign (padding lanes are cropped, never leaked), with all
    of a call's chunks in flight before its one copy back."""
    circuit, x8, _ = _tree_circuit()
    sim = faults.FaultSimulator(circuit)
    if shape == "single_hot":
        mask, val = faults.site_masks(
            circuit.n_gates, *faults.single_fault_lanes(circuit))
    else:
        mask, val = _multi_hot_lanes(circuit, 60)
    ref = sim.run_masks(x8, mask, val, chunk=len(mask))
    for chunk in (1, 13, len(mask) + 100):
        np.testing.assert_array_equal(
            ref, sim.run_masks(x8, mask, val, chunk=chunk))


def test_ragged_tail_is_cropped_and_matches_serial_oracle():
    """37 lanes at chunk 8: five chunks, the last padded with three
    zero-fault lanes that never reach the result; every lane is the serial
    oracle's."""
    circuit, x8, _ = _tree_circuit()
    sim = faults.FaultSimulator(circuit)
    mask, val = _multi_hot_lanes(circuit, 37, seed=9)
    preds = sim.run_masks(x8, mask, val, chunk=8)
    assert preds.shape == (37, x8.shape[0])
    for i in range(37):
        stuck = np.flatnonzero(mask[i])
        np.testing.assert_array_equal(
            preds[i], faults.simulate_faulty_serial(
                circuit, x8, zip(stuck, val[i, stuck])),
            err_msg=f"lane {i}")


# --- campaign metrics ------------------------------------------------------

def test_monte_carlo_reproducible_under_fixed_seed():
    circuit, x8, y = _tree_circuit()
    sim = faults.FaultSimulator(circuit)
    a = robustness.monte_carlo(sim, x8, y, n_trials=8, seed=11)
    b = robustness.monte_carlo(sim, x8, y, n_trials=8, seed=11)
    np.testing.assert_array_equal(a.pop("_accuracies"), b.pop("_accuracies"))
    assert a == b
    c = robustness.monte_carlo(sim, x8, y, n_trials=8, seed=12)
    assert not np.array_equal(b and 0, c.pop("_accuracies"))  # different draw


def test_critical_gates_ranked_by_drop():
    circuit, x8, y = _tree_circuit()
    sim = faults.FaultSimulator(circuit)
    sites, accs = robustness.single_stuck_at(sim, x8, y)
    baseline = float((sim.run_zero_fault(x8) == y).mean())
    ranked = robustness.critical_gates(sites, accs, baseline, top_k=5)
    drops = [r["drop"] for r in ranked]
    assert drops == sorted(drops, reverse=True)
    assert len(ranked) == 5
    worst = ranked[0]
    per_site = baseline - np.asarray(accs).reshape(-1, 2).min(axis=1)
    assert worst["drop"] == pytest.approx(per_site.max())
    assert worst["stuck_value"] in (0, 1)


def test_point_robustness_invariants():
    circuit, x8, y = _tree_circuit()
    row = robustness.point_robustness(circuit, x8, y, n_trials=4)
    assert row["zero_fault_matches_simulate"] is True
    assert row["n_faults"] == 2 * row["n_sites"]
    sf = row["single_fault"]
    assert sf["worst_accuracy"] <= sf["mean_accuracy"]
    assert sf["worst_drop"] == pytest.approx(
        row["baseline_accuracy"] - sf["worst_accuracy"])


# --- fault_report.json schema discipline -----------------------------------

@pytest.fixture(scope="module")
def tree_report(tmp_path_factory):
    """A real campaign payload from a tiny seeds search (any family path
    would do — the schema is family-agnostic)."""
    ds = load_dataset("seeds")
    pt = to_parallel(train_tree(ds.x_train, ds.y_train, ds.n_classes))
    problem = search.build_tree_problem(pt, ds.x_test, ds.y_test)
    out = str(tmp_path_factory.mktemp("faults") / "run")
    cfg = search.SearchConfig(pop_size=8, n_generations=2, seed=0,
                              dataset="seeds", out_dir=out)
    search.run_search(problem, cfg)
    artifact = search.load_pareto_artifact(out + "/pareto.json")
    x8 = quantize_u8(ds.x_test)[:N_VECTORS]
    y = np.asarray(ds.y_test[:N_VECTORS])
    payload = robustness.run_campaign(artifact, x8, y, source="pareto.json",
                                      point="all", n_trials=4)
    return payload, out


def test_fault_report_roundtrip(tree_report, tmp_path):
    """Acceptance: write -> load -> identical payload, validated twice."""
    payload, _ = tree_report
    path = str(tmp_path / "fault_report.json")
    robustness.write_fault_report(payload, path)
    assert robustness.load_fault_report(path) == json.loads(
        json.dumps(payload))


def test_fault_report_rejects_missing_and_unknown_keys(tree_report):
    payload, _ = tree_report

    bad = copy.deepcopy(payload)
    del bad["defect_rate"]
    with pytest.raises(ValueError, match=r"missing keys.*defect_rate"):
        robustness.validate_fault_report(bad)

    bad = copy.deepcopy(payload)
    bad["surprise"] = 1
    with pytest.raises(ValueError, match=r"unknown keys.*surprise"):
        robustness.validate_fault_report(bad)

    bad = copy.deepcopy(payload)
    del bad["points"][0]["single_fault"]["worst_drop"]
    with pytest.raises(ValueError,
                       match=r"single_fault.*missing keys.*worst_drop"):
        robustness.validate_fault_report(bad)

    bad = copy.deepcopy(payload)
    bad["points"][0]["monte_carlo"]["extra"] = 0
    with pytest.raises(ValueError, match=r"monte_carlo.*unknown keys"):
        robustness.validate_fault_report(bad)

    bad = copy.deepcopy(payload)
    bad["points"][0]["critical_gates"][0].pop("drop")
    with pytest.raises(ValueError,
                       match=r"critical_gates\[0\].*missing keys"):
        robustness.validate_fault_report(bad)

    bad = copy.deepcopy(payload)
    bad["points"][0]["n_faults"] += 1
    with pytest.raises(ValueError, match=r"not 2 \* n_sites"):
        robustness.validate_fault_report(bad)

    bad = copy.deepcopy(payload)
    bad["points"][0]["zero_fault_matches_simulate"] = False
    with pytest.raises(ValueError, match="diverged"):
        robustness.validate_fault_report(bad)


def test_select_points():
    class FakeArtifact:
        points = [{"acc_loss": 0.0, "norm_area": 0.9},
                  {"acc_loss": 0.005, "norm_area": 0.5},
                  {"acc_loss": 0.2, "norm_area": 0.1}]

        def best_under_loss(self, max_loss=0.01):
            ok = [i for i, p in enumerate(self.points)
                  if p["acc_loss"] <= max_loss]
            return min(ok, key=lambda i: self.points[i]["norm_area"]) \
                if ok else None

    art = FakeArtifact()
    assert robustness.select_points(art, "all") == [0, 1, 2]
    assert robustness.select_points(art, "best") == [1]
    assert robustness.select_points(art, "2") == [2]
    with pytest.raises(ValueError, match="out of range"):
        robustness.select_points(art, "7")
    art.points = [{"acc_loss": 0.5, "norm_area": 0.5}]
    with pytest.raises(ValueError, match="no pareto point"):
        robustness.select_points(art, "best")


# --- CLI: campaign end-to-end + hardening ----------------------------------

def test_faults_cli_end_to_end(tree_report, tmp_path, capsys):
    _, out = tree_report
    report_path = str(tmp_path / "fault_report.json")
    faults_main(["--pareto", out + "/pareto.json", "--point", "best",
                 "--trials", "4", "--out", report_path])
    report = robustness.load_fault_report(report_path)
    assert report["dataset"] == "seeds"
    assert len(report["points"]) == 1
    assert "report:" in capsys.readouterr().out


@pytest.mark.parametrize("cli", [faults_main, serve_main],
                         ids=["faults", "serve"])
def test_cli_exits_cleanly_on_missing_artifact(cli, tmp_path, capsys):
    """Missing pareto.json: exit code 2 + a one-line named error on stderr,
    never a traceback."""
    missing = str(tmp_path / "nope" / "pareto.json")
    with pytest.raises(SystemExit) as exc:
        cli(["--pareto", missing])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert f"error: pareto artifact {missing}" in err
    assert "FileNotFoundError" in err
    assert "Traceback" not in err


@pytest.mark.parametrize("cli", [faults_main, serve_main],
                         ids=["faults", "serve"])
def test_cli_exits_cleanly_on_truncated_artifact(cli, tmp_path, capsys):
    """Truncated JSON (simulated torn write): same clean exit contract."""
    path = str(tmp_path / "pareto.json")
    with open(path, "w") as f:
        f.write('{"backend": "reference", "pareto": [{"acc_l')
    with pytest.raises(SystemExit) as exc:
        cli(["--pareto", path])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert f"error: pareto artifact {path}" in err
    assert "JSONDecodeError" in err
    assert "Traceback" not in err


def test_cli_exits_cleanly_on_schema_violation(tmp_path, capsys):
    """Valid JSON, invalid schema: the named ValueError surfaces as the
    one-line error, not a stack dump."""
    path = str(tmp_path / "pareto.json")
    with open(path, "w") as f:
        json.dump({"backend": "reference"}, f)
    with pytest.raises(SystemExit) as exc:
        faults_main(["--pareto", path])
    assert exc.value.code == 2
    err = capsys.readouterr().err
    assert "ValueError" in err and "missing keys" in err

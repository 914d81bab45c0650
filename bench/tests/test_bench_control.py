"""The comparison that decides ``correct`` fails its control and each fault
of the timed path, and passes the program as it is.

A whole run (`run.run_cell`, everything but the look for a chip) of a small
tree configuration on the CPU, with the timed path broken underneath it.
"""
import dataclasses
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import spec  # noqa: E402

SEED = 2**31 + 4321  # more than 32 signed bits hold
SEARCH = {"kind": "search_campaigns", "backend": "kernel", "pop_size": 8,
          "n_generations": 6, "checkpoint_every": 2, "mesh": None,
          "campaign_seeds": [5, 6]}
FAULTS = {"kind": "fault_slices", "slice_lanes": 16, "check_lanes": 16}
CELLS = {"search_campaigns": "har_tree.search_p128_g500",
         "fault_slices": "har_tree.faults_exact"}


@pytest.fixture(scope="module")
def config():
    from repro.datasets import load_dataset

    ds = load_dataset("seeds")
    # the seeds tree grown to pure leaves has 7 splits; cut to 5
    return {"name": "seeds_tree", "dataset": "seeds", "n_trees": 1,
            "max_depth": 64, "n_features": ds.x_test.shape[1],
            "n_classes": ds.n_classes, "n_comparators": 5, "n_leaves": 6,
            "n_test": int(ds.y_test.shape[0]), "n_genes": 16}


def run(config, traffic, tmp_path):
    import jax

    import run as bench_run

    bench = spec.benchmark()
    cell = spec.cell(bench, CELLS[traffic["kind"]])
    return bench_run.run_cell(bench, cell, config, traffic, SEED, 0.0, False,
                              jax.devices()[:1], str(tmp_path / "out"))


def failing(result):
    return sorted(k for k, c in result["checks"].items()
                  if not c["value"] <= c["limit"])


@pytest.mark.parametrize("traffic", [SEARCH, FAULTS],
                         ids=["search", "faults"])
def test_sound_program_is_correct(config, traffic, tmp_path):
    result = run(config, traffic, tmp_path)
    assert result["correct"], result["checks"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0


def test_search_control_in_bfloat16_fails(config, tmp_path):
    import ml_dtypes

    from workloads import SearchCampaigns

    runner = SearchCampaigns(config, SEARCH, SEED, str(tmp_path / "out"))
    runner.setup()
    runner.step(0)
    produced = runner.collect()
    limits = spec.limits()
    sound = dict(runner.check(produced))
    control = dict(runner.check(produced, control=ml_dtypes.bfloat16))
    assert all(v <= limits[k] for k, v in sound.items()), sound
    assert control["acc_gap_samples"] > limits["acc_gap_samples"] or \
        control["area_gap_rel"] > limits["area_gap_rel"], control


def test_fault_control_with_swapped_polarity_fails(config, tmp_path):
    from workloads import FaultSlices

    runner = FaultSlices(config, FAULTS, SEED, str(tmp_path / "out"))
    runner.setup()
    runner.step(0)
    control = dict(runner.check(runner.collect(), control=True))
    assert control["lane_mismatches"] > 0


def _unchanged_state(monkeypatch):
    from repro.core import nsga2

    monkeypatch.setattr(nsga2, "make_chunk",
                        lambda fitness, cfg, n: (lambda state: state))


def _half_batch(monkeypatch):
    from repro.search import backends

    make = backends.make_kernel_fitness

    def half(problem, **kw):
        b = problem.y.shape[0] // 2
        return make(dataclasses.replace(problem, x8=problem.x8[:b],
                                        x_sel=problem.x_sel[:b],
                                        y=problem.y[:b]), **kw)

    monkeypatch.setattr(backends, "make_kernel_fitness", half)


def _altered_answer(monkeypatch):
    from repro.search import backends

    make = backends.make_kernel_fitness

    def altered(problem, **kw):
        fit = make(problem, **kw)
        return lambda pop: fit(pop).at[:, 0].add(1.0 / problem.y.shape[0])

    monkeypatch.setattr(backends, "make_kernel_fitness", altered)


def _parents_returned(monkeypatch):
    from repro.core import nsga2

    monkeypatch.setattr(nsga2, "_sbx", lambda key, a, b, *cfg: (a, b))
    monkeypatch.setattr(nsga2, "_poly_mutation",
                        lambda key, genes, *cfg: genes)


def _worst_kept(monkeypatch):
    from repro.core import nsga2

    sort = nsga2.non_dominated_sort
    monkeypatch.setattr(nsga2, "non_dominated_sort",
                        lambda objs, dom=None: -sort(objs, dom))


def _altered_rank(monkeypatch):
    from repro.core import nsga2

    sort = nsga2.non_dominated_sort
    monkeypatch.setattr(nsga2, "non_dominated_sort",
                        lambda objs, dom=None: sort(objs, dom) + 1)


def _dropped_front_point(monkeypatch):
    from repro.search import engine

    write = engine.write_pareto_artifact

    def dropped(problem, result, out_dir, **kw):
        result = dataclasses.replace(result,
                                     pareto_objs=result.pareto_objs[:-1],
                                     pareto_genes=result.pareto_genes[:-1])
        return write(problem, result, out_dir, **kw)

    monkeypatch.setattr(engine, "write_pareto_artifact", dropped)


@pytest.mark.parametrize("fault, fails", [
    (_unchanged_state, "generation_gap"),
    (_parents_returned, "rows_carried_share"),
    (_worst_kept, "front_regressions"),
    (_half_batch, "acc_gap_samples"),
    (_altered_answer, "acc_gap_samples"),
    (_altered_rank, "rank_mismatches"),
    (_dropped_front_point, "front_mismatches"),
], ids=["unchanged_state", "parents_returned", "worst_kept",
        "half_batch", "altered_answer", "altered_rank", "dropped_front_point"])
def test_search_faults_are_not_correct(config, fault, fails, monkeypatch,
                                       tmp_path):
    fault(monkeypatch)
    result = run(config, SEARCH, tmp_path)
    assert not result["correct"]
    assert fails in failing(result), result["checks"]


def _fault_free_lanes(monkeypatch):
    from repro.core import faults

    def unchanged(self, x8, gates, values, chunk=None):
        g = self.circuit.n_gates
        empty = np.zeros((len(gates), g), bool)
        return self.run_masks(x8, empty, empty, chunk=chunk)

    monkeypatch.setattr(faults.FaultSimulator, "run_sites", unchanged)


def _fault_half_batch(monkeypatch):
    from repro.core import faults

    sites = faults.FaultSimulator.run_sites
    monkeypatch.setattr(
        faults.FaultSimulator, "run_sites",
        lambda self, x8, g, v, chunk=None: sites(
            self, x8[:x8.shape[0] // 2], g, v, chunk))


def _fault_altered_answer(monkeypatch):
    from repro.core import faults

    sites = faults.FaultSimulator.run_sites

    def altered(self, x8, g, v, chunk=None):
        out = sites(self, x8, g, v, chunk).copy()
        out[:, 0] = (out[:, 0] + 1) % self.circuit.n_classes
        return out

    monkeypatch.setattr(faults.FaultSimulator, "run_sites", altered)


def _wrong_circuit(monkeypatch):
    from repro.core import netlist

    build = netlist.build_circuit

    def swapped(*a, **kw):
        c = build(*a, **kw)
        return dataclasses.replace(c, out_bits=c.out_bits[::-1])

    monkeypatch.setattr(netlist, "build_circuit", swapped)


@pytest.mark.parametrize("fault, fails", [
    (_fault_free_lanes, "lane_mismatches"),
    (_fault_half_batch, "lane_mismatches"),
    (_fault_altered_answer, "lane_mismatches"),
    (_wrong_circuit, "circuit_mismatches"),
], ids=["unchanged_state", "half_batch", "altered_answer", "wrong_circuit"])
def test_fault_campaign_faults_are_not_correct(config, fault, fails,
                                               monkeypatch, tmp_path):
    fault(monkeypatch)
    result = run(config, FAULTS, tmp_path)
    assert not result["correct"]
    assert fails in failing(result), result["checks"]

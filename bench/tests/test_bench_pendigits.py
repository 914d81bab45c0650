"""The pendigits_tree configuration, the Pallas domination route its pool
takes, and the two readers of that kernel, on the CPU.

The cell's pool of 8,192 rows sorts through `kernels.domination`; at pop
256 the pool of 512 rows is the smallest that takes the same route, so a
small campaign here forces it (the kernel in interpret mode) and holds it to
the plain reference and to the jnp relation.
"""
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import domination_cost  # noqa: E402
import grow  # noqa: E402
import spec  # noqa: E402
from trace_reduce import Op  # noqa: E402

SEED = 2**31 + 1717
SMALL = {"kind": "search_campaigns", "backend": "kernel", "pop_size": 256,
         "n_generations": 4, "checkpoint_every": 2, "mesh": None,
         "campaign_seeds": [3]}


@pytest.fixture(scope="module")
def config():
    return spec.config("pendigits_tree")


@pytest.fixture(scope="module")
def built(config):
    from workloads import build_tree

    return build_tree(config)


def test_config_builds_at_its_stated_sizes(config, built):
    problem, ref, _ = built
    assert (problem.n_comparators, problem.n_leaves, problem.n_genes,
            int(problem.y.shape[0])) == (225, 226, 676, 3298)
    assert (ref.n_comparators, ref.n_leaves, ref.n_samples) == (225, 226,
                                                                3298)
    assert config["reduced"] == ["n_comparators"]
    assert config["published"]["n_comparators"] == 243


def test_table_one_size_cannot_be_grown_from_the_stand_in(config):
    """Why ``n_comparators`` is reduced: the grown tree has 225 splits."""
    from repro.core.train import train_tree
    from repro.datasets import load_dataset

    ds = load_dataset(config["dataset"])
    grown = train_tree(ds.x_train, ds.y_train, ds.n_classes, max_depth=60)
    nodes = {k: getattr(grown, k) for k in ("feature", "threshold", "left",
                                            "right")}
    assert int((np.asarray(grown.feature) >= 0).sum()) == 225
    with pytest.raises(ValueError, match="fewer than 243 splits"):
        grow.best_first(nodes, ds.x_train, ds.y_train, ds.n_classes,
                        config["published"]["n_comparators"])


def _campaign(runner, monkeypatch, kernel: bool):
    """One campaign of the runner, its pool sorted by the Pallas relation
    (``kernel``) or by the jnp one; what it produced, and how many sorts
    traced the kernel route."""
    from repro.core import nsga2
    from repro.kernels import ops

    traced = []

    def counted(objs, **kw):
        traced.append(objs.shape[0])
        return ops.domination_matrix(objs, **kw) > 0.5

    monkeypatch.setattr(nsga2, "_kernel_domination_available",
                        lambda: kernel)
    monkeypatch.setattr(ops, "domination_matrix_bool", counted)
    runner.reseed(SEED)
    runner.step(0)
    return runner.collect(), traced


def test_small_campaign_on_the_kernel_route_matches_the_reference(
        config, monkeypatch, tmp_path):
    from workloads import SearchCampaigns

    runner = SearchCampaigns(config, SMALL, SEED, str(tmp_path / "out"))
    runner.setup()
    on_kernel, traced = _campaign(runner, monkeypatch, kernel=True)
    assert traced and set(traced) == {2 * SMALL["pop_size"]}
    checks = dict(runner.check(on_kernel))
    limits = spec.limits()
    for name in ("acc_gap_samples", "area_gap_rel", "rank_mismatches",
                 "front_mismatches", "generation_gap", "front_regressions"):
        assert checks[name] <= limits[name], (name, checks)
    assert checks["rank_mismatches"] == checks["front_mismatches"] == 0

    on_jnp, traced = _campaign(runner, monkeypatch, kernel=False)
    assert not traced
    for key in ("genes", "objs", "rank", "first_genes"):
        np.testing.assert_array_equal(on_kernel[0][key], on_jnp[0][key])
    assert on_kernel[0]["points"] == on_jnp[0]["points"]


def test_domination_bytes_by_hand():
    # 3 rows of 2 objectives: 9 one-byte pairs and 3 * 2 float32 reads
    assert domination_cost.relation_bytes(3) == 9 + 24
    # one campaign of pop 3 over 2 generations: its initial sort, then two
    # sorts of the 6-row pool (36 pairs + 12 float32 each)
    assert domination_cost.search_bytes(3, 1, 2) == 33 + 2 * (36 + 48)
    # the cell's pool: 8,192 rows
    assert domination_cost.relation_bytes(8192) == 8192**2 + 2 * 8192 * 4


def _run(dom_us, pop=3, **counters):
    ops = [Op("fusion.3", 0.0, 2e3)]
    t = 10e3
    for us in dom_us:
        ops.append(Op("domination_block.7", t, t + us * 1e3))
        t += us * 1e3 + 1e3
    c = {"kind": "search", "campaigns": 1, "generations": 2,
         "fitness_calls": 3, "evaluations": 3 * pop, "pop_per_device": pop}
    c.update(counters)
    return types.SimpleNamespace(
        reduced=types.SimpleNamespace(ops={0: ops}, lo=0.0, hi=t),
        devices=[0], counters=c, peak={"hbm_bytes_per_s": 1e9})


def test_domination_readers_by_hand():
    # three sorts of 3, 5 and 4 us over 2 generations: 6 us a generation
    run = _run([3.0, 5.0, 4.0])
    assert spec.reader("domination_ms_per_gen")(run) == pytest.approx(0.006)
    # least 201 bytes at 1 GB/s = 0.201 us, over 12 us of kernel time
    assert spec.reader("domination_roofline")(run) == pytest.approx(
        100 * 0.201 / 12.0)


@pytest.mark.parametrize("name", ["domination_ms_per_gen",
                                  "domination_roofline"])
def test_domination_readers_stay_silent(name):
    read = spec.reader(name)
    assert read(_run([])) is None                    # no kernel: jnp route
    assert read(_run([3.0], kind="faults")) is None
    assert read(_run([3.0], generations=0, fitness_calls=0)) is None


def test_domination_roofline_stays_silent_on_a_sharded_population():
    # a pop of 6 over two chips: 3 rows a chip, 6 per evaluation call
    run = _run([3.0], evaluations=3 * 6)
    assert spec.reader("domination_roofline")(run) is None

"""The Pareto artifact writer decodes a whole front in one compiled call
(`engine.decode_front`, padded to the population's row count): field for
field and file for file the same as decoding each point on its own with
eager ops, and one compiled program for every front of one population
shape."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import search
from repro.core import forest as forest_mod, quant
from repro.core.train import train_tree
from repro.core.tree import to_parallel
from repro.datasets import load_dataset
from repro.runtime import spans
from repro.search import engine

POP = 16


def _per_point_decode(threshold, genes):
    """The oracle: each row decoded on its own in eager ops, as the writer
    did before the front was decoded in one call."""
    rows = []
    for g in genes:
        bits, margin, trunc, vote = quant.decode_tree_genes(jnp.asarray(g))
        t_sub = quant.substitute(quant.threshold_to_int(threshold, bits),
                                 margin, bits)
        rows.append((np.asarray(bits), np.asarray(margin), np.asarray(t_sub),
                     np.asarray(trunc), int(vote)))
    return tuple(np.stack(c).astype(np.int32) for c in zip(*rows))


def _campaign(problem, out_dir=None, **kw):
    return search.run_search(problem, backend="reference", pop_size=POP,
                             n_generations=2, out_dir=out_dir, **kw)


@pytest.fixture(scope="module")
def tree_case():
    ds = load_dataset("vertebral")
    tree = train_tree(ds.x_train, ds.y_train, ds.n_classes)
    problem = search.build_tree_problem(to_parallel(tree), ds.x_test,
                                        ds.y_test)
    return problem, _campaign(problem)


@pytest.fixture(scope="module")
def forest_case():
    """K = 3 trees: the vote gene selects the approximate vote adder."""
    ds = load_dataset("seeds")
    fr = forest_mod.train_forest(ds.x_train, ds.y_train, ds.n_classes,
                                 n_trees=3)
    problem = search.build_forest_problem(fr, ds.x_test, ds.y_test)
    return problem, _campaign(problem)


def _front(problem, n_rows, seed):
    """``n_rows`` genes: random rows, then rows that put 0.0, the largest
    float32 below 1.0, or 1.0 in every slot, and rows that mix the three
    slot by slot; random objectives."""
    rng = np.random.default_rng(seed)
    edges = np.array([0.0, np.nextafter(np.float32(1), np.float32(0)), 1.0],
                     np.float32)
    g = problem.n_genes
    rows = [np.full(g, e, np.float32) for e in edges]
    rows += [rng.choice(edges, g).astype(np.float32) for _ in range(3)]
    rows = np.concatenate([rng.uniform(0, 1, (POP, g)).astype(np.float32),
                           np.stack(rows)])
    genes = rows[rng.permutation(len(rows))[:n_rows]]
    if n_rows >= 3:     # keep every all-edge row in a short front
        genes[:3] = rows[POP:POP + 3]
    objs = rng.uniform(0, 1, (n_rows, 2)).astype(np.float32)
    return objs, genes


def _write(problem, result, out_dir, **kw):
    path = engine.write_pareto_artifact(problem, result, out_dir, **kw)
    files = {}
    for root, _, names in os.walk(out_dir):
        for name in names:
            with open(os.path.join(root, name)) as f:
                files[os.path.relpath(os.path.join(root, name),
                                      out_dir)] = f.read()
    assert "pareto.json" in files and path.endswith("pareto.json")
    return files


@pytest.mark.parametrize("case,n_rows,rtl", [
    ("tree", 1, False),         # one point, the rest of the block padding
    ("tree", 7, True),          # some padding
    ("tree", POP, False),       # a front as large as the population
    ("forest", 9, True),        # truncation and the approximate vote adder
])
def test_batched_decode_equals_per_point(case, n_rows, rtl, request,
                                         tmp_path, monkeypatch):
    problem, result = request.getfixturevalue(f"{case}_case")
    objs, genes = _front(problem, n_rows, seed=n_rows)
    front = dataclasses.replace(result, pareto_objs=objs, pareto_genes=genes)

    block = np.zeros((POP, problem.n_genes), np.float32)
    block[:n_rows] = genes
    got = jax.device_get(engine.decode_front(problem.threshold, block))
    want = _per_point_decode(problem.threshold, genes)
    for name, a, b in zip(("bits", "margin", "t_int", "trunc", "vote"),
                          got, want):
        assert a.dtype == np.int32, name
        np.testing.assert_array_equal(a[:n_rows], b, err_msg=name)
    bits, trunc, vote = want[0], want[3], want[4]
    if n_rows >= 3:     # the all-edge rows reach both ends of every range
        assert (bits[0] == quant.MIN_BITS).all() and (trunc[0] == 0).all()
        assert (bits[1:3] == quant.MAX_BITS).all()
        assert (trunc[1:3] == quant.MAX_TRUNC).all()
    if case == "forest":
        assert trunc.any() and vote.any() and not vote.all()

    kw = dict(emit_rtl=rtl, verify_rtl=rtl, dataset=case)
    batched = _write(problem, front, str(tmp_path / "batched"), **kw)
    monkeypatch.setattr(engine, "decode_front", _per_point_decode)
    per_point = _write(problem, front, str(tmp_path / "per_point"), **kw)
    assert batched == per_point
    assert len(batched) == 1 + (n_rows if rtl else 0)


def test_one_compile_per_population_shape(tree_case, tmp_path):
    """Fronts of different sizes from campaigns of one population share one
    compiled decode: only the first write traces it."""
    problem, _ = tree_case
    engine.decode_front.clear_cache()
    jit_keys = lambda t: {k for k in t if "/jit." in k}
    with jax.profiler.trace(str(tmp_path / "profile")):
        spans.reset()
        _campaign(problem, str(tmp_path / "a"))
        first = spans.totals()
        spans.reset()
        second = _campaign(problem, str(tmp_path / "b"), seed=1)
        second_totals = spans.totals()
        spans.reset()
        short = dataclasses.replace(second,
                                    pareto_objs=second.pareto_objs[:1],
                                    pareto_genes=second.pareto_genes[:1])
        engine.write_pareto_artifact(problem, short, str(tmp_path / "c"))
        third = spans.totals()
        spans.reset()
    assert engine.decode_front._cache_size() == 1
    assert first["artifact.decode/jit.trace_s"] > 0
    # the second campaign re-traces its own chunks, and nothing of the writer
    assert second_totals["search.run/jit.trace_s"] > 0
    assert {k.split("/")[0] for k in jit_keys(second_totals)} == {"search.run"}
    assert second_totals["artifact.decode"]["calls"] == 1
    assert second_totals["artifact.decode_rows"] == POP
    assert third["artifact.decode_rows"] == POP
    assert third["artifact.points"] == 1
    assert not jit_keys(third)

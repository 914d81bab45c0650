"""Serving runtime differential suite (DESIGN.md §14).

Pins `runtime.classify.ClassifyServer` bit-exactly to the two independent
oracles — the tensor dataflow (`search.predict_votes`) and the gate-level
netlist simulator (`core.netlist.simulate`) — across every pareto point of
tiny searches on >= 3 datasets, tree AND forest designs, both serving
backends.  Also covers:

  - hypothesis-generated ragged request sizes (batch=1, batch=bucket_max,
    chunk-spanning, out-of-grid integer codes where the mask semantics
    `codes & 0xFF` must match the netlist's bits-0..7 reads);
  - bucket invariance: padding rows and >= 3 consecutive ping-pong steps
    never change real-row predictions;
  - steady state: once a bucket's two slots are warm, steps allocate no
    new device arrays and compile nothing new;
  - `pareto.json` loader round-trips: re-serving a point reproduces its
    recorded accuracy; missing/unknown keys raise `ValueError` (never a
    bare `KeyError`);
  - the `runtime.serve` -> `runtime.lm_serve` deprecation shim.
"""
from __future__ import annotations

import copy
import gc
import importlib
import json
import sys

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import search
from repro.core.forest import train_forest
from repro.core.netlist import build_circuit, simulate
from repro.core.train import train_tree
from repro.core.tree import to_parallel
from repro.datasets import load_dataset
from repro.datasets.synthetic import quantize_u8
from repro.runtime.classify import BACKENDS, ClassifyServer
from repro.search.artifact import (
    OPTIONAL_POINT_KEYS,
    OPTIONAL_TOP_KEYS,
    REQUIRED_POINT_KEYS,
    REQUIRED_TOP_KEYS,
    from_payload,
    load_pareto_artifact,
)

# (dataset, n_trees): three datasets, single tree AND voted forest
CASES = (("seeds", 1), ("vertebral", 1), ("balance", 1), ("seeds", 3))


def _build_problem(dataset: str, n_trees: int):
    ds = load_dataset(dataset)
    if n_trees <= 1:
        pt = to_parallel(train_tree(ds.x_train, ds.y_train, ds.n_classes))
        problem = search.build_tree_problem(pt, ds.x_test, ds.y_test)
    else:
        forest = train_forest(ds.x_train, ds.y_train, ds.n_classes,
                              n_trees=n_trees)
        problem = search.build_forest_problem(forest, ds.x_test, ds.y_test)
    return ds, problem


@pytest.fixture(scope="module")
def searched(tmp_path_factory):
    """(dataset, n_trees) -> (pareto.json path, artifact, problem, ds)."""
    out = {}
    root = tmp_path_factory.mktemp("serve")
    for dataset, n_trees in CASES:
        ds, problem = _build_problem(dataset, n_trees)
        out_dir = str(root / f"{dataset}_{n_trees}")
        cfg = search.SearchConfig(pop_size=8, n_generations=2, seed=0,
                                  dataset=dataset, out_dir=out_dir)
        search.run_search(problem, cfg)
        path = out_dir + "/pareto.json"
        out[(dataset, n_trees)] = (path, load_pareto_artifact(path),
                                   problem, ds)
    return out


def _netlist_predict(artifact, point_idx: int, codes) -> np.ndarray:
    """The gate-level oracle, rebuilt from the artifact alone."""
    bits, t_int, trunc, vote_adder = artifact.point_design(point_idx)
    circuit = build_circuit(artifact.ptrees(), bits, t_int,
                            artifact.n_classes, trunc=trunc,
                            vote_adder=vote_adder)
    return np.asarray(simulate(circuit, np.asarray(codes)))


# --- the oracle triangle: served == predict_votes == netlist ---------------

@pytest.mark.parametrize("case", CASES, ids=[f"{d}x{k}" for d, k in CASES])
def test_every_pareto_point_bit_exact(searched, case):
    """served == tensor predict_votes == netlist sim, every pareto point."""
    _, artifact, problem, ds = searched[case]
    x = np.asarray(ds.x_test)[:64]          # one 64-bucket per server
    assert len(artifact.points) >= 1
    for i in range(len(artifact.points)):
        bits, t_int, trunc, vote_adder = artifact.point_design(i)
        # tensor oracle evaluates the EFFECTIVE design (§16 folding)
        cap = np.float32(1.0 if vote_adder == "approx" else np.inf)
        votes = np.asarray(search.predict_votes(
            problem, bits - trunc, t_int >> trunc, cap))[: x.shape[0]]
        gates = _netlist_predict(artifact, i, quantize_u8(x))
        for backend in BACKENDS:
            server = ClassifyServer.from_artifact(artifact, point=i,
                                                  backend=backend)
            served = server.classify(x)
            np.testing.assert_array_equal(
                served, votes,
                err_msg=f"{case} point {i} {backend}: served != votes")
            np.testing.assert_array_equal(
                served, gates,
                err_msg=f"{case} point {i} {backend}: served != netlist")


@pytest.mark.parametrize("case", CASES[:1] + CASES[-1:],
                         ids=["seedsx1", "seedsx3"])
@settings(max_examples=8, deadline=None)
@given(n=st.integers(min_value=1, max_value=40),
       wild=st.booleans(), seed=st.integers(min_value=0, max_value=2**31))
def test_ragged_requests_match_netlist(searched, case, n, wild, seed):
    """Hypothesis-sized requests (incl. out-of-grid ints) track the netlist.

    Wild integer codes are NOT clipped to the 8-bit grid: the netlist reads
    input bits 0..7, so any int wraps mod 256 — the server's `& 0xFF` mask
    must agree bit-for-bit (including negatives via two's complement).
    """
    _, artifact, problem, ds = searched[case]
    idx = artifact.best_under_loss(1.0)
    server = ClassifyServer.from_artifact(artifact, point=idx, max_batch=64)
    rng = np.random.default_rng(seed)
    if wild:
        codes = rng.integers(-300, 900,
                             size=(n, ds.x_test.shape[1])).astype(np.int32)
    else:
        rows = rng.integers(0, ds.x_test.shape[0], size=n)
        codes = server.featurize(np.asarray(ds.x_test)[rows]).astype(np.int32)
    served = server.classify(codes)
    gates = _netlist_predict(artifact, idx, codes)
    np.testing.assert_array_equal(served, gates)
    assert served.shape == (n,)


def test_batch_one_and_bucket_max_and_chunking(searched):
    """The edge sizes: n=1, n == bucket_max, and n > max_batch (chunking)."""
    _, artifact, problem, ds = searched[("seeds", 1)]
    idx = artifact.best_under_loss(1.0)
    server = ClassifyServer.from_artifact(artifact, point=idx, max_batch=16)
    codes = server.featurize(np.asarray(ds.x_test)).astype(np.int32)
    gates = _netlist_predict(artifact, idx, codes)

    np.testing.assert_array_equal(server.classify(codes[:1]), gates[:1])
    assert server.bucket_for(1) == 8

    np.testing.assert_array_equal(server.classify(codes[:16]), gates[:16])
    assert server.bucket_for(16) == 16 == server.max_batch

    # 40 rows through max_batch=16 -> chunks of 16/16/8, reassembled in order
    np.testing.assert_array_equal(server.classify(codes[:40]), gates[:40])
    assert server.compiled_buckets() == [8, 16]

    # empty request: legal, empty answer, no step consumed
    steps = server.stats.n_steps
    assert server.classify(codes[:0]).shape == (0,)
    assert server.stats.n_steps == steps


def test_float_and_code_paths_agree(searched):
    _, artifact, _, ds = searched[("vertebral", 1)]
    server = ClassifyServer.from_artifact(artifact, point=0)
    x = np.asarray(ds.x_test)[:20]
    np.testing.assert_array_equal(
        server.classify(x),
        server.classify_codes(server.featurize(x)))


@settings(max_examples=16, deadline=None)
@given(n=st.integers(min_value=1, max_value=12),
       row=st.integers(min_value=0, max_value=2**31),
       col=st.integers(min_value=0, max_value=2**31),
       bad=st.sampled_from(("nan", "+inf", "-inf")),
       everywhere=st.booleans())
def test_classify_rejects_non_finite_features(searched, n, row, col, bad,
                                              everywhere):
    """Satellite contract: NaN/±inf feature vectors raise a named
    ValueError BEFORE the float->int quantization cast (whose behavior on
    non-finite values is undefined) — one poisoned entry or a whole batch
    alike, while the same batch without the poison still serves."""
    _, artifact, _, ds = searched[("seeds", 1)]
    server = ClassifyServer.from_artifact(artifact, point=0)
    x = np.asarray(ds.x_test[:n], np.float64).copy()
    poison = {"nan": np.nan, "+inf": np.inf, "-inf": -np.inf}[bad]
    if everywhere:
        x[:] = poison
    else:
        x[row % x.shape[0], col % x.shape[1]] = poison
    with pytest.raises(ValueError, match="non-finite"):
        server.classify(x)
    clean = np.asarray(ds.x_test[:n], np.float64)
    assert server.classify(clean).shape == (n,)


# --- bucket invariance + ping-pong steadiness ------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_bucket_and_pingpong_invariance(searched, backend):
    """Real rows never change when padded into a larger bucket, nor across
    >= 3 consecutive ping-pong steps (both slots exercised), both backends."""
    _, artifact, problem, ds = searched[("seeds", 3)]
    idx = artifact.best_under_loss(1.0)
    server = ClassifyServer.from_artifact(artifact, point=idx,
                                          backend=backend)
    codes = server.featurize(np.asarray(ds.x_test)).astype(np.int32)
    alone = server.classify(codes[:5])          # bucket 8

    # same 5 rows leading a 33-row request -> padded into the 64 bucket
    wider = server.classify(codes[:33])
    np.testing.assert_array_equal(wider[:5], alone)
    assert server.bucket_for(33) == 64

    # >= 3 consecutive steps through the same bucket: the ping-pong slots
    # alternate (donation recycles buffers) but answers never drift
    compiles = server.compile_count()
    for _ in range(4):
        np.testing.assert_array_equal(server.classify(codes[:5]), alone)
    assert server.compile_count() == compiles   # no steady-state retrace
    assert server.stats.steps_per_bucket[8] >= 5


@pytest.mark.parametrize("backend", BACKENDS)
def test_steady_state_serving_allocates_nothing(searched, backend):
    """Once both ping-pong slots of a bucket are warm, further steps
    recycle the resident buffers: the live device-array count stays flat."""
    _, artifact, _, ds = searched[("seeds", 3)]
    server = ClassifyServer.from_artifact(
        artifact, point=artifact.best_under_loss(1.0), backend=backend)
    codes = server.featurize(np.asarray(ds.x_test)[:10]).astype(np.int32)
    padded = [server.batch(codes[:5])[0][0], server.batch(codes[5:])[0][0]]
    for i in range(4):                       # warm both slots of bucket 8
        np.asarray(server.step(padded[i % 2]))
    gc.collect()
    live = len(jax.live_arrays())
    for i in range(8):
        np.asarray(server.step(padded[i % 2]))
    gc.collect()
    assert len(jax.live_arrays()) == live


def test_manual_padding_is_inert(searched):
    """`batch()` zero-padding == hand-padding with arbitrary junk rows."""
    _, artifact, _, ds = searched[("seeds", 1)]
    server = ClassifyServer.from_artifact(artifact, point=0)
    codes = server.featurize(np.asarray(ds.x_test)[:6]).astype(np.int32)
    alone = server.classify(codes)
    junk = np.vstack([codes, np.full((2, codes.shape[1]), 255, np.int32)])
    np.testing.assert_array_equal(server.classify(junk)[:6], alone)


@settings(max_examples=20, deadline=None)
@given(n=st.integers(min_value=1, max_value=5000))
def test_bucket_for_properties(n):
    bucket = search.round_up_pow2(n, 8)
    assert bucket >= max(n, 8)
    assert (bucket & (bucket - 1)) == 0          # a power of two
    assert search.round_up_pow2(bucket, 8) == bucket  # idempotent


# --- pareto.json loader: round-trip + validation ---------------------------

@pytest.mark.parametrize("case", CASES, ids=[f"{d}x{k}" for d, k in CASES])
def test_artifact_accuracy_roundtrip(searched, case):
    """Re-serving each point reproduces its recorded accuracy (1e-6)."""
    _, artifact, problem, ds = searched[case]
    assert artifact.dataset == case[0]
    y = np.asarray(ds.y_test)
    for i in range(len(artifact.points)):
        server = ClassifyServer.from_artifact(artifact, point=i,
                                              backend="reference")
        served = server.classify(np.asarray(ds.x_test))
        acc = float(np.mean(served == y))
        assert abs(acc - artifact.point_accuracy(i)) <= 1e-6, (
            f"{case} point {i}: served acc {acc} vs recorded "
            f"{artifact.point_accuracy(i)}")


def test_loader_file_roundtrip(searched):
    path, artifact, problem, _ = searched[("seeds", 1)]
    again = load_pareto_artifact(path)
    np.testing.assert_array_equal(again.path, artifact.path)
    assert again.tree_comparators == artifact.tree_comparators
    # the artifact alone rebuilds the problem's layout arrays
    pts = again.ptrees()
    assert len(pts) == problem.n_trees
    assert sum(int(p.feature.shape[0]) for p in pts) == problem.n_comparators


def test_loader_rejects_missing_and_unknown_keys(searched):
    path, *_ = searched[("seeds", 1)]
    with open(path) as f:
        good = json.load(f)

    bad = copy.deepcopy(good)
    del bad["threshold"]
    with pytest.raises(ValueError, match=r"missing keys \['threshold'\]"):
        from_payload(bad)

    bad = copy.deepcopy(good)
    bad["surprise"] = 1
    with pytest.raises(ValueError, match=r"unknown keys \['surprise'\]"):
        from_payload(bad)

    bad = copy.deepcopy(good)
    del bad["pareto"][0]["t_int"]
    with pytest.raises(ValueError, match=r"pareto\[0\].*missing keys"):
        from_payload(bad)

    bad = copy.deepcopy(good)
    bad["pareto"][0]["extra"] = []
    with pytest.raises(ValueError, match=r"unknown keys \['extra'\]"):
        from_payload(bad)

    bad = copy.deepcopy(good)
    bad["pareto"][0]["bits"] = bad["pareto"][0]["bits"][:-1]
    with pytest.raises(ValueError, match="bits"):
        from_payload(bad)

    # §16 approximation config gets the same named-ValueError treatment
    bad = copy.deepcopy(good)
    del bad["pareto"][0]["vote_adder"]
    with pytest.raises(ValueError, match=r"pareto\[0\].*missing keys"):
        from_payload(bad)

    bad = copy.deepcopy(good)
    bad["pareto"][0]["trunc"] = bad["pareto"][0]["trunc"][:-1]
    with pytest.raises(ValueError, match="trunc"):
        from_payload(bad)

    bad = copy.deepcopy(good)
    bad["pareto"][0]["trunc"] = [9] * len(bad["pareto"][0]["trunc"])
    with pytest.raises(ValueError, match="trunc"):
        from_payload(bad)

    bad = copy.deepcopy(good)
    bad["pareto"][0]["vote_adder"] = "fuzzy"
    with pytest.raises(ValueError, match="vote_adder"):
        from_payload(bad)

    with pytest.raises(ValueError, match="JSON object"):
        from_payload([1, 2, 3])

    # schema constants stay two-way consistent with the writer's output
    assert REQUIRED_TOP_KEYS <= set(good)
    assert set(good) <= REQUIRED_TOP_KEYS | OPTIONAL_TOP_KEYS
    assert REQUIRED_POINT_KEYS <= set(good["pareto"][0])
    assert set(good["pareto"][0]) <= REQUIRED_POINT_KEYS | OPTIONAL_POINT_KEYS


def test_server_constructor_validation(searched):
    _, artifact, _, _ = searched[("seeds", 1)]
    bits, t_int, _, _ = artifact.point_design(0)
    with pytest.raises(ValueError, match="unknown serving backend"):
        ClassifyServer(artifact.ptrees(), bits, t_int, artifact.n_classes,
                       backend="verilog")
    with pytest.raises(ValueError, match="do not match"):
        ClassifyServer(artifact.ptrees(), bits[:-1], t_int,
                       artifact.n_classes)
    with pytest.raises(ValueError, match="out of range"):
        ClassifyServer.from_artifact(artifact, point=99)
    with pytest.raises(ValueError, match="no pareto point within"):
        ClassifyServer.from_artifact(artifact, point="best", max_loss=-0.5)
    server = ClassifyServer.from_artifact(artifact)
    with pytest.raises(ValueError, match="features"):
        server.classify(np.zeros((4, 1), np.int32))


# --- runtime.serve -> runtime.lm_serve deprecation shim --------------------

def test_lm_serve_rename_shim():
    from repro.runtime import lm_serve

    sys.modules.pop("repro.runtime.serve", None)
    with pytest.warns(DeprecationWarning, match="lm_serve"):
        shim = importlib.import_module("repro.runtime.serve")
    assert shim.generate is lm_serve.generate
    assert shim.make_prefill_step is lm_serve.make_prefill_step
    assert shim.make_serve_step is lm_serve.make_serve_step

    # lazy attribute on the package resolves to the shim too
    import repro.runtime as runtime
    assert runtime.serve.generate is lm_serve.generate

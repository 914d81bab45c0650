"""The hardware loop (DESIGN.md §10): gate-level netlist IR, batched
simulation oracle, forest RTL emission, and the verified pareto artifact.

Edge cases the RTL layer must survive: constant-false comparators
(t' = 2^p - 1), single-leaf trees, non-power-of-two class counts; plus
hypothesis-driven gene draws against the sequential descent oracle and the
acceptance round-trip — every pareto point of a seeds tree and a
vertebral 4-tree forest bit-exact across netlist sim / predict_votes /
kernel backend, re-materializable from pareto.json alone.
"""
import json
import os

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import area, forest as forest_mod, netlist, quant, rtl
from repro.core.train import train_tree
from repro.core.tree import (ParallelTree, concatenate_ptrees,
                             predict_descent_quantized, to_parallel)
from repro.datasets import load_dataset, quantize_u8
from repro import search
from repro.search.problem import decode_chromosome, predict_votes


@pytest.fixture(scope="module")
def seeds_tree():
    ds = load_dataset("seeds")
    tree = train_tree(ds.x_train, ds.y_train, ds.n_classes)
    pt = to_parallel(tree)
    # compile the eager gene decode for this tree's shapes once, here, so a
    # hypothesis deadline times each example and not jax's first compile
    _decode(pt.threshold,
            _legacy_genes(np.random.default_rng(0), pt.n_comparators))
    return ds, tree, pt


def _legacy_genes(rng, n_comparators: int) -> np.ndarray:
    """Random chromosome in the pre-§16 subspace: precision/margin genes
    free, truncation and vote-adder genes zeroed (the oracles below predate
    approximate cells)."""
    g = rng.uniform(0, 1, 3 * n_comparators + 1).astype(np.float32)
    g[2::3] = 0.0
    g[-1] = 0.0
    return g


def _decode(pt_threshold, genes):
    bits, marg, _, _ = quant.decode_tree_genes(jnp.asarray(genes))
    t_sub = quant.substitute(
        quant.threshold_to_int(jnp.asarray(pt_threshold), bits), marg, bits)
    return np.asarray(bits), np.asarray(t_sub)


# ---------------------------------------------------------------------------
# builder-level invariants
# ---------------------------------------------------------------------------

def test_comparator_gates_match_area_model_exhaustively():
    """The netlist comparator lowering IS the construction the area LUT
    prices: AND/OR counts agree for every (t, p)."""
    for p in range(quant.MIN_BITS, quant.MAX_BITS + 1):
        for t in range(1 << p):
            nb = netlist.NetlistBuilder()
            nb.comparator(0, t, p)
            ops = np.asarray(nb.op)
            got = (int((ops == netlist.AND).sum()),
                   int((ops == netlist.OR).sum()))
            assert got == area.comparator_gate_counts(t, p), (t, p)


def test_truncated_comparator_gates_match_area_model_exhaustively():
    """EVERY truncated-cell variant — p in [MIN_BITS, MAX_BITS], k in
    [0, MAX_TRUNC], all 2^p thresholds — lowered through the real
    `build_tree_cells` path: gate counts equal
    `core.area.trunc_comparator_gate_counts` (DESIGN.md §16), so the GA's
    area quanta and the emitted hardware cannot drift apart."""
    one_comp = ParallelTree(
        feature=np.zeros(1, np.int32), threshold=np.zeros(1, np.float32),
        path=np.zeros((0, 1), np.int8), path_len=np.zeros(0, np.int32),
        n_neg=np.zeros(0, np.int32), leaf_class=np.zeros(0, np.int32),
        n_classes=2)
    for p in range(quant.MIN_BITS, quant.MAX_BITS + 1):
        for k in range(quant.MAX_TRUNC + 1):
            for t in range(1 << p):
                nb = netlist.NetlistBuilder()
                cells = netlist.build_tree_cells(
                    nb, one_comp, np.array([p]), np.array([t]), 2,
                    trunc=np.array([k]))
                ops = np.asarray(nb.op)
                got = (int((ops == netlist.AND).sum()),
                       int((ops == netlist.OR).sum()))
                assert got == area.trunc_comparator_gate_counts(t, p, k), \
                    (t, p, k)
                assert cells.comparators[0].trunc == k
    # fully-truncated minimum-width cells degenerate to constant false
    assert area.trunc_comparator_gate_counts(1, 2, 2) == (0, 0)


@settings(deadline=None, max_examples=80)
@given(p=st.integers(quant.MIN_BITS, quant.MAX_BITS),
       k=st.integers(0, quant.MAX_TRUNC),
       t_raw=st.integers(0, (1 << quant.MAX_BITS) - 1))
def test_truncation_flips_only_within_threshold_block(p, k, t_raw):
    """k-LSB truncation can only flip decisions for codes in the same
    2^k-aligned block as the threshold (equivalently: within the bottom
    2^k codes above it) — and every flip is True -> False, never the
    reverse. This is the §16 bound on how far a truncated cell can stray
    from the exact comparator."""
    t = t_raw % (1 << p)
    x = np.arange(1 << p)
    exact = x > t
    truncated = (x >> k) > (t >> k)
    flips = np.flatnonzero(exact != truncated)
    assert np.all((flips >> k) == (t >> k))        # same 2^k block as t
    assert np.all((flips - t) < (1 << k))          # within 2^k codes of t
    assert flips.size <= (1 << k) - 1
    assert np.all(exact[flips])                    # only True -> False


def test_vote_adder_pricing_matches_isolated_lowering():
    """`area.vote_adder_units` prices exactly the gate inventory of the
    isolated vote-stage harness; the approximate OR-tree is never costlier
    than the exact popcount adder, and K = 1 designs have no adder at all."""
    for n_trees in (2, 3, 5):
        for n_classes in (2, 5):
            for approx in (False, True):
                counts = netlist.vote_adder_gate_counts(n_trees, n_classes,
                                                        approx=approx)
                units = area.vote_adder_units(n_trees, n_classes, approx)
                want = area.gate_area_mm2(*counts) / area.AREA_QUANTUM_MM2
                assert units == round(want)
                assert units > 0
            assert (area.vote_adder_units(n_trees, n_classes, True)
                    <= area.vote_adder_units(n_trees, n_classes, False))
    assert area.vote_adder_units(1, 5, False) == 0
    assert area.vote_adder_units(1, 5, True) == 0


def test_constant_false_comparator_folds_away(seeds_tree):
    """t' = 2^p - 1 comparators fold to constant false — in the netlist, in
    the emitted Verilog, and in the simulated predictions."""
    _, tree, pt = seeds_tree
    bits = np.full(pt.n_comparators, 3, np.int64)
    t_sub = np.full(pt.n_comparators, (1 << 3) - 1, np.int64)  # all const
    nb = netlist.NetlistBuilder()
    cells = netlist.build_tree_cells(nb, pt, bits, t_sub, pt.n_classes)
    assert all(c.wire == nb.zero for c in cells.comparators)

    v = rtl.emit_verilog(pt, bits, t_sub)
    assert v.count("= 1'b0;") >= pt.n_comparators

    # every decision is False -> descent always goes left; sim must agree
    x8 = np.arange(256, dtype=np.int32)[:, None].repeat(
        int(pt.feature.max()) + 1, axis=1)
    circ = netlist.build_circuit(pt, bits, t_sub, pt.n_classes)
    internal = np.flatnonzero(tree.feature >= 0)
    bf = np.zeros(tree.n_nodes, np.int64)
    bf[internal] = bits
    # saturating margin clips t' to 2^3 - 1 = 7 everywhere in the oracle too
    want = predict_descent_quantized(x8, tree, bf,
                                     np.full(tree.n_nodes, 7, np.int64))
    got = np.asarray(netlist.simulate(circ, x8))
    np.testing.assert_array_equal(got, want)
    assert len(set(got.tolist())) == 1  # constant circuit


def test_single_leaf_tree():
    """A tree with zero comparators is a constant circuit and a legal,
    input-less Verilog module."""
    pt = ParallelTree(
        feature=np.zeros(0, np.int32), threshold=np.zeros(0, np.float32),
        path=np.zeros((1, 1), np.int8), path_len=np.zeros(1, np.int32),
        n_neg=np.zeros(1, np.int32), leaf_class=np.array([2], np.int32),
        n_classes=4)
    circ = netlist.build_circuit(pt, np.zeros(0), np.zeros(0), 4)
    x8 = np.zeros((5, 3), np.int32)
    np.testing.assert_array_equal(np.asarray(netlist.simulate(circ, x8)),
                                  np.full(5, 2))
    v = rtl.emit_verilog(pt, np.zeros(0), np.zeros(0))
    assert "wire leaf0 = 1'b1;" in v and "input" not in v
    assert "assign class_out[1] = leaf0;" in v  # class 2 = 0b10


def test_forest_with_non_power_of_two_classes():
    """C = 5 classes: vote counts, argmax chain and tie-breaking must match
    the looped forest oracle (ties -> lowest class index)."""
    rng = np.random.default_rng(2)
    x = rng.uniform(0, 1, (300, 4)).astype(np.float32)
    y = np.clip((x[:, 0] * 5).astype(np.int64)
                + (rng.uniform(size=300) < 0.2), 0, 4)
    fr = forest_mod.train_forest(x, y, 5, n_trees=3, seed=1)
    x8 = quantize_u8(rng.uniform(0, 1, (96, 4)).astype(np.float32))
    x8 = x8.astype(np.int32)
    thresholds = np.concatenate([p.threshold for p in fr.ptrees])
    for trial in range(3):
        genes = _legacy_genes(rng, fr.n_comparators)
        bits, t_sub = _decode(thresholds, genes)
        bits_j, marg_j, _, _ = quant.decode_tree_genes(jnp.asarray(genes))
        circ = netlist.build_circuit(fr.ptrees, bits, t_sub, 5)
        got = np.asarray(netlist.simulate(circ, x8))
        want = np.asarray(forest_mod.forest_predict(
            fr, jnp.asarray(x8), bits_j, marg_j))
        np.testing.assert_array_equal(got, want)
    # the Verilog carries the 3-bit class encoding and the full argmax chain
    v = rtl.emit_forest_verilog(fr.ptrees, bits, t_sub, 5)
    assert "wire [2:0] idx0 = 3'd0;" in v
    assert "assign class_out = idx4;" in v


@settings(max_examples=10)
@given(st.integers(0, 2**31 - 1))
def test_netlist_sim_matches_descent_oracle(seeds_tree, draw_seed):
    """Hypothesis-driven gene draws: the gate-level simulation of the emitted
    circuit equals the sequential quantized descent, bit for bit."""
    ds, tree, pt = seeds_tree
    rng = np.random.default_rng(draw_seed)
    genes = _legacy_genes(rng, pt.n_comparators)
    bits, t_sub = _decode(pt.threshold, genes)
    _, marg, _, _ = quant.decode_tree_genes(jnp.asarray(genes))
    circ = netlist.build_circuit(pt, bits, t_sub, pt.n_classes)
    x8 = quantize_u8(ds.x_test).astype(np.int32)
    internal = np.flatnonzero(tree.feature >= 0)
    bf = np.zeros(tree.n_nodes, np.int64)
    mf = np.zeros(tree.n_nodes, np.int64)
    bf[internal] = bits
    mf[internal] = np.asarray(marg)
    want = predict_descent_quantized(x8, tree, bf, mf)
    np.testing.assert_array_equal(np.asarray(netlist.simulate(circ, x8)),
                                  want)


def test_cross_tree_cse_shares_comparators():
    """Two identical trees: hash-consing shares every comparator/leaf gate,
    so the forest netlist costs vote logic only — the sharing gap the
    additive LUT estimate cannot see."""
    rng = np.random.default_rng(0)
    x = rng.uniform(0, 1, (200, 3)).astype(np.float32)
    y = (x[:, 0] > 0.5).astype(np.int64)
    pt = to_parallel(train_tree(x, y, 2))
    bits = np.full(pt.n_comparators, 8, np.int64)
    t_sub = np.clip(np.floor(pt.threshold * 256).astype(np.int64), 0, 255)
    one = netlist.build_circuit(pt, bits, t_sub, 2)
    two = netlist.build_circuit([pt, pt], np.tile(bits, 2),
                                np.tile(t_sub, 2), 2)
    c1, c2 = netlist.gate_counts(one), netlist.gate_counts(two)
    # tree logic counted once; only popcount/argmax gates are new
    assert c2["and"] + c2["or"] < 2 * (c1["and"] + c1["or"]) + 20


def test_problem_ptrees_roundtrip():
    """problem_ptrees inverts the block-diagonal concatenation exactly."""
    ds = load_dataset("vertebral")
    fr = forest_mod.train_forest(ds.x_train, ds.y_train, ds.n_classes,
                                 n_trees=3)
    prob = search.build_forest_problem(fr, ds.x_test, ds.y_test)
    back = search.problem_ptrees(prob)
    want = concatenate_ptrees(fr.ptrees)
    got = concatenate_ptrees(back)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])


# ---------------------------------------------------------------------------
# the acceptance round-trip: verified pareto artifacts, tree AND forest
# ---------------------------------------------------------------------------

def _roundtrip_t_int(artifact):
    """Re-materialize every point's t_int from the artifact alone."""
    thr = np.asarray(artifact["threshold"], np.float32)
    for p in artifact["pareto"]:
        bits = np.asarray(p["bits"], np.int64)
        marg = np.asarray(p["margin"], np.int64)
        t = np.clip(np.floor(thr.astype(np.float64) * (2.0 ** bits)),
                    0, (1 << bits) - 1).astype(np.int64)
        t_sub = np.clip(t + marg, 0, (1 << bits) - 1)
        np.testing.assert_array_equal(t_sub, np.asarray(p["t_int"]))


def _check_verified_artifact(prob, out):
    with open(os.path.join(out, "pareto.json")) as f:
        artifact = json.load(f)
    assert artifact["rtl_verified"] is True
    assert len(artifact["threshold"]) == prob.n_comparators
    for i, p in enumerate(artifact["pareto"]):
        assert p["verified"] is True
        assert len(p["t_int"]) == prob.n_comparators
        assert p["area_netlist_mm2"] > 0
        assert os.path.exists(os.path.join(out, p["rtl"]))
    _roundtrip_t_int(artifact)
    return artifact


def test_pareto_points_verified_seeds_tree(tmp_path):
    """Acceptance: every pareto point of a seeds tree — netlist sim ==
    predict_votes == kernel backend over the full test set (the engine
    raises otherwise), artifact self-contained."""
    ds = load_dataset("seeds")
    pt = to_parallel(train_tree(ds.x_train, ds.y_train, ds.n_classes))
    prob = search.build_tree_problem(pt, ds.x_test, ds.y_test)
    out = str(tmp_path / "tree")
    search.run_search(prob, pop_size=8, n_generations=2, out_dir=out,
                      emit_rtl=True, verify_rtl=True)
    artifact = _check_verified_artifact(prob, out)
    assert artifact["n_trees"] == 1


def test_pareto_points_verified_vertebral_forest(tmp_path):
    """Acceptance: same, for a vertebral 4-tree forest — the emitted design
    includes the majority-vote adder tree."""
    ds = load_dataset("vertebral")
    fr = forest_mod.train_forest(ds.x_train, ds.y_train, ds.n_classes,
                                 n_trees=4)
    prob = search.build_forest_problem(fr, ds.x_test, ds.y_test)
    out = str(tmp_path / "forest")
    search.run_search(prob, pop_size=8, n_generations=2, out_dir=out,
                      emit_rtl=True, verify_rtl=True)
    artifact = _check_verified_artifact(prob, out)
    assert artifact["n_trees"] == 4
    with open(os.path.join(out, artifact["pareto"][0]["rtl"])) as f:
        v = f.read()
    assert "majority-vote adder tree" in v
    assert v.count("endmodule") == 5  # 4 tree modules + top

    # explicit three-way re-check of one point, independent of the engine.
    # decode_chromosome returns the EFFECTIVE design (§16 truncation already
    # folded into bits/t_sub), so the netlist lowers it with trunc unset.
    g = jnp.asarray(artifact["pareto"][0]["genes"], jnp.float32)
    bits, t_sub, vote_cap = decode_chromosome(prob, g)
    vote_adder = "approx" if np.isfinite(float(vote_cap)) else "exact"
    circ = netlist.build_circuit(search.problem_ptrees(prob),
                                 np.asarray(bits), np.asarray(t_sub),
                                 prob.n_classes, vote_adder=vote_adder)
    sim = np.asarray(netlist.simulate(circ, prob.x8))
    np.testing.assert_array_equal(
        sim, np.asarray(predict_votes(prob, bits, t_sub, vote_cap)))


def test_rtl_flags_require_out_dir(seeds_tree):
    ds, _, pt = seeds_tree
    prob = search.build_tree_problem(pt, ds.x_test, ds.y_test)
    with pytest.raises(ValueError, match="out_dir"):
        search.run_search(prob, pop_size=8, n_generations=1, verify_rtl=True)

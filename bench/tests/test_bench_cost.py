"""Operation and byte counts, the readers built on them, and the plain
references, against hand counts on toy problems."""
import sys
import types
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import kernel_cost  # noqa: E402
import ref_gates  # noqa: E402
import grow  # noqa: E402
import ref_trees  # noqa: E402
import spec  # noqa: E402


def test_fitness_ops_and_bytes_by_hand():
    # P=2 chromosomes, B=3 samples, N=2 comparators, L=3 leaves, C=2
    # classes: per (chromosome, sample) 2*3 path + 3*2 vote multiply-adds
    assert kernel_cost.fitness_ops(2, 3, 2, 3, 2) == 2 * 6 * (6 + 6)
    # codes 3*2, widths+thresholds 2*2*2, path 2*3, targets 3, classes 3*2,
    # labels 3, vote flags 2, output 4 bytes per chromosome
    assert kernel_cost.fitness_bytes(2, 3, 2, 3, 2) == \
        6 + 8 + 6 + 3 + 6 + 3 + 2 + 8


def test_least_seconds_names_its_bound():
    peak = {"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert kernel_cost.least_seconds(200, 10, peak) == (2.0, "ops")
    assert kernel_cost.least_seconds(100, 30, peak) == (3.0, "bytes")


def test_peaks_table_has_its_source():
    for kind, peak in spec.peaks().items():
        assert peak["source"]
        assert peak["int8_ops_per_s"] > peak["bf16_flops_per_s"] > 0
        assert peak["hbm_bytes_per_s"] > 0


def _search_run(kernel_s):
    from trace_reduce import Op

    peak = {"int8_ops_per_s": 1e12, "hbm_bytes_per_s": 1e12}
    ops = [Op("fitness_errors.1", 0, kernel_s * 1e9)]
    return types.SimpleNamespace(
        peak=peak, devices=[0], window_s=2.0,
        reduced=types.SimpleNamespace(ops={0: ops}),
        counters={"kind": "search", "dims": (100, 10, 11, 3),
                  "pop_per_device": 8, "fitness_calls": 5,
                  "evaluations": 40})


def test_fitness_roofline_and_mfu_by_hand():
    ops = 2 * 8 * 100 * (10 * 11 + 11 * 3) * 5      # 1,144,000
    run = _search_run(kernel_s=ops / 1e12 * 4)       # four times the least
    assert spec.reader("fitness_roofline")(run) == pytest.approx(25.0)
    mfu = 2 * 40 * 100 * (10 * 11 + 11 * 3) / 2.0 / 1e12
    assert spec.reader("search_mfu")(run) == pytest.approx(100 * mfu)


def test_readers_stay_silent_outside_their_cells():
    run = _search_run(kernel_s=1.0)
    run.counters = {"kind": "faults", "lanes": 4}
    for name in ("fitness_roofline", "search_mfu", "nsga2_ms_per_gen",
                 "artifact_s_per_campaign", "checkpoint_stall_ms",
                 "idle_share.search"):
        assert spec.reader(name)(run) is None


def test_comparator_quanta_by_hand():
    table = ref_trees._QUANTA
    # X > 2 at 3 bits: X >= 0b011, one AND (bit 1) and one OR (bit 2)
    assert table[3, 2] == 55 + 57
    # X > 3 at 3 bits is the wire X2; X > 7 is constant false
    assert table[3, 3] == 0 and table[3, 7] == 0
    # X > 0 at 8 bits: X >= 1, seven ORs
    assert table[8, 0] == 7 * 57


def _toy_trees():
    # root: feature 0 > 127 ? (node 2: feature 1 > 63 ? class 2 : class 1)
    #                        : class 0
    tree = {"feature": np.array([0, -1, 1, -1, -1]),
            "threshold": np.array([127.5, 0, 63.5, 0, 0]) / 256,
            "left": np.array([1, -1, 3, -1, -1]),
            "right": np.array([2, -1, 4, -1, -1]),
            "leaf_class": np.array([-1, 0, -1, 1, 2])}
    x = np.array([[0.1, 0.9], [0.9, 0.1], [0.9, 0.9], [0.5, 0.5]])
    return ref_trees.Tree(tree, x, np.array([0, 1, 2, 0]), 3)


def test_reference_tree_by_hand():
    ref = _toy_trees()
    assert ref.x8.tolist() == [[25, 230], [230, 25], [230, 230],
                               [128, 128]]
    assert ref.exact_correct == 3          # the last sample goes right twice
    exact = ref.exact_genes()[None, :]
    assert ref.predict(*ref.decode(exact)).tolist() == [[0, 1, 2, 2]]
    # root at 2 bits, margin -1, 1 bit truncated: t = floor(0.498 * 4) = 1,
    # t' = 0, width 1: 25 >> 7 = 0 goes left, 230 and 128 >> 7 = 1 right
    g = exact.copy()
    g[0, 0], g[0, 1], g[0, 2] = 0.0, 4.5 / 11, 0.5
    width, thr = ref.decode(g)
    assert width[0].tolist() == [1, 8] and thr[0].tolist() == [0, 63]
    assert ref.predict(width, thr).tolist() == [[0, 1, 2, 2]]
    # area: X > 0 at width 1 is the wire X0, X > 63 at 8 bits has one OR
    # per clear bit above bit 6 of 64 (bit 7): 57 quanta
    assert ref.area_mm2(width, thr)[0] == pytest.approx(
        0.57 + 2 * 0.02 + 3 * 0.04)


def test_best_first_cut_by_hand():
    # root: x0 > 0.5 splits 6 samples 2 | 4; left x1 > 0.5 separates its
    # two (Gini mass 1 -> 0), right x1 > 0.5 separates 3 of class 1 from
    # one of class 2 (1.5 -> 0): the right split goes first
    tree = {"feature": np.array([0, 1, 1, -1, -1, -1, -1]),
            "threshold": np.array([128.5, 128.5, 128.5, 0, 0, 0, 0]) / 256,
            "left": np.array([1, 3, 5, -1, -1, -1, -1]),
            "right": np.array([2, 4, 6, -1, -1, -1, -1])}
    x = np.array([[0.1, 0.1], [0.1, 0.9], [0.9, 0.1], [0.9, 0.1],
                  [0.9, 0.1], [0.9, 0.9]])
    y = np.array([0, 3, 1, 1, 1, 2])
    cut = grow.best_first(tree, x, y, 4, 2)
    assert cut["feature"].tolist() == [0, -1, 1, -1, -1]
    assert cut["left"].tolist() == [1, -1, 3, -1, -1]
    assert cut["right"].tolist() == [2, -1, 4, -1, -1]
    # the unsplit left node keeps its first majority class (0 before 3)
    assert cut["leaf_class"].tolist() == [-1, 0, -1, 1, 2]
    with pytest.raises(ValueError):
        grow.best_first(tree, x, y, 4, 4)


def test_pareto_ranks_by_hand():
    keys = np.array([[0, 5], [1, 4], [1, 5], [2, 6], [0, 5]])
    assert ref_trees.pareto_ranks(keys).tolist() == [0, 0, 1, 2, 0]


def test_gate_reference_by_hand():
    # out = (x0.b0 AND x0.b1) OR NOT x1.b0, one output bit
    op = [0, 1, 2, 2, 2, 4, 3, 5]
    a = [0, 0, 0, 0, 1, 2, 4, 5]
    b = [0, 0, 0, 1, 0, 3, 0, 6]
    x8 = np.array([[3, 1], [1, 1], [0, 0]])
    free = ref_gates.simulate(op, a, b, (7,), x8)
    assert free.tolist() == [[1, 0, 1]]
    faulty = ref_gates.simulate(op, a, b, (7,), x8,
                                np.array([5, 6, 3]), np.array([1, 0, 1]))
    assert faulty.tolist() == [[1, 1, 1], [1, 0, 0], [1, 1, 1]]

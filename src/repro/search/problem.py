"""`SearchProblem`: one evaluation context for tree *and* forest GA search.

The paper's design-space search is always the same shape — NSGA-II over
per-comparator (precision, margin) genes, each chromosome scored as
(accuracy loss, normalized area) against an exact bespoke reference — but the
seed repo grew three hand-rolled copies of it (single tree in `core.approx`,
forest in `core.forest`, islands in `core.dist`). This module collapses the
*data* side of all three into one immutable problem object (DESIGN.md §7):

  - the comparator axis is the concatenation of every tree's comparators
    (a single tree is the K=1 case), so one chromosome of 3*N_total + 1
    genes — per-comparator (precision, margin, truncation) plus the
    forest-wide vote-adder gene (DESIGN.md §16) — covers the whole ensemble
    exactly like `core.forest`'s joint search;
  - the leaf axis concatenates every tree's leaves and `path` is the
    block-diagonal "super-tree" path matrix, so leaf decode + the class-vote
    matmul evaluate every tree in one fused tensor program — the same
    operands the Pallas kernel consumes (`repro.kernels.tree_infer`);
  - area bookkeeping (LUT, offsets, overheads, exact-design reference) is
    computed once here instead of per-pipeline.

Fitness *backends* over this object live in `repro.search.backends`; the
driver loop in `repro.search.engine`.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import area as area_mod
from repro.core import quant
from repro.core.tree import ParallelTree, concatenate_ptrees
from repro.datasets.synthetic import quantize_u8


@dataclasses.dataclass
class SearchProblem:
    """Immutable evaluation context for one (tree-ensemble, dataset) pair.

    All comparator/leaf arrays are concatenated across the K trees of the
    ensemble (K = 1 for a single tree); `path` is block-diagonal.
    """

    feature: jnp.ndarray      # (N,) int32   concatenated comparator features
    threshold: jnp.ndarray    # (N,) float32 trained float thresholds
    path: jnp.ndarray         # (L, N) int8  block-diagonal super-tree paths
    path_len: jnp.ndarray     # (L,) int32
    n_neg: jnp.ndarray        # (L,) int32
    leaf_class: jnp.ndarray   # (L,) int32
    leaf_tree: jnp.ndarray    # (L,) int32   owning tree per leaf
    x8: jnp.ndarray           # (B, F) int32 master codes (test set)
    x_sel: jnp.ndarray        # (B, N) int32 hoisted x8[:, feature] — the
                              #   chromosome-invariant feature gather,
                              #   computed once per problem (DESIGN.md §12)
    y: jnp.ndarray            # (B,) int32
    area_lut_units: jnp.ndarray  # flat LUT, integer AREA_QUANTUM_MM2 quanta
                                 #   stored as f32 (exact, order-free sums)
    lut_offsets: jnp.ndarray  # (MAX_BITS+1,) int32
    overhead_mm2: float
    exact_area_mm2: float
    exact_accuracy: float
    n_classes: int
    n_features: int
    n_trees: int
    tree_comparators: tuple   # per-tree comparator counts (static)
    tree_leaves: tuple        # per-tree leaf counts (static)
    vote_units_exact: int = 0     # vote-stage area quanta per adder mode —
    vote_units_approx: int = 0    # priced from the netlist harness (§16)

    @property
    def n_comparators(self) -> int:
        return int(self.feature.shape[0])

    @property
    def n_leaves(self) -> int:
        return int(self.leaf_class.shape[0])

    @property
    def n_genes(self) -> int:
        """Cross-layer chromosome length (DESIGN.md §16): three genes per
        comparator (precision, margin, truncation) + the vote-adder gene."""
        return 3 * self.n_comparators + 1

    def exact_genes(self) -> np.ndarray:
        """Chromosome of the exact (8-bit, zero-margin, un-truncated,
        exact-vote) reference design."""
        return quant.exact_tree_genes(self.n_comparators)


jax.tree_util.register_pytree_node(
    SearchProblem,
    lambda p: (
        (p.feature, p.threshold, p.path, p.path_len, p.n_neg, p.leaf_class,
         p.leaf_tree, p.x8, p.x_sel, p.y, p.area_lut_units, p.lut_offsets),
        (p.overhead_mm2, p.exact_area_mm2, p.exact_accuracy, p.n_classes,
         p.n_features, p.n_trees, p.tree_comparators, p.tree_leaves,
         p.vote_units_exact, p.vote_units_approx),
    ),
    lambda aux, children: SearchProblem(*children, *aux),
)


# ---------------------------------------------------------------------------
# reference (pure-jnp) evaluation primitives shared by backends
# ---------------------------------------------------------------------------

def decode_chromosome(problem: SearchProblem, genes):
    """genes (..., 3N+1) -> (bits, t_sub, vote_cap): the EFFECTIVE design.

    Decodes the cross-layer chromosome (DESIGN.md §16) and folds LSB
    truncation into the returned pair — `bits` is the effective comparator
    width p - k and `t_sub` the substituted threshold shifted down by k —
    because a k-truncated comparator IS the exact comparator at that
    width/threshold. `vote_cap` is the f32 saturation the vote counts are
    clipped to before argmax: 1.0 under the approximate OR-tree adder,
    +inf (an exact f32 no-op) under the exact popcount adder.
    """
    bits, margin, trunc, vote = quant.decode_tree_genes(genes)
    t_int = quant.threshold_to_int(problem.threshold, bits)
    t_sub = quant.substitute(t_int, margin, bits)
    vote_cap = jnp.where(vote > 0, jnp.float32(1.0), jnp.float32(jnp.inf))
    return bits - trunc, jnp.right_shift(t_sub, trunc), vote_cap


def area_mm2(problem: SearchProblem, units, vote_cap):
    """Design area from its summed comparator LUT quanta ``units`` plus the
    vote-adder cell the decoded cap selects (0 when K = 1).

    Every term before the final scaling is an integer number of quanta, so
    the comparator sum is exact in f32 under any reduction order or batch
    shape, and the reference and kernel backends agree on it bit for bit
    on any device (the sweep's padded evaluation sums the same quanta,
    DESIGN.md §11).
    """
    units = units + jnp.where(jnp.isfinite(vote_cap),
                              jnp.float32(problem.vote_units_approx),
                              jnp.float32(problem.vote_units_exact))
    return units * area_mod.AREA_QUANTUM_MM2 + problem.overhead_mm2


def exact_matmul(a, b):
    """f32 matmul of small integers, exact on every backend.

    At the default precision XLA may narrow the operands (bf16 on a TPU;
    it turned the 0/1 and ±1 matrices here into a pred x int8
    convolution). On a TPU v5e such programs returned wrong accuracies for
    a 256-chromosome pendigits forest[4] population and for the HAR tree
    in 18-chromosome blocks; at HIGHEST both were right.
    """
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def predict_votes(problem: SearchProblem, bits, t_sub, vote_cap=None):
    """(B,) voted class per sample — the block-diagonal super-tree dataflow.

    Exactly one leaf per tree satisfies its path, so `sat @ CLS1H` counts one
    vote per tree per class; for K=1 the votes are the predicted class's
    one-hot and this reduces bit-exactly to single-tree leaf decode.

    The feature gather is hoisted: `problem.x_sel` is the chromosome-
    invariant `x8[:, feature]`, computed once at problem build, so the
    per-chromosome work starts at the precision shift + broadcast compare
    (DESIGN.md §12).
    """
    x_p = quant.inputs_at_precision(problem.x_sel, bits)
    d = (x_p > t_sub[None, :]).astype(jnp.float32)
    score = exact_matmul(d, problem.path.T.astype(jnp.float32))   # (B, L)
    target = (problem.path_len - problem.n_neg).astype(jnp.float32)
    sat = (score == target[None, :]).astype(jnp.float32)
    cls1h = jax.nn.one_hot(problem.leaf_class, problem.n_classes)
    votes = exact_matmul(sat, cls1h)                              # (B, C)
    if vote_cap is not None:
        # saturating (approximate) vote adder; +inf cap = exact no-op
        votes = jnp.minimum(votes, vote_cap)
    return jnp.argmax(votes, axis=1)


def n_correct(problem: SearchProblem, pred):
    """Exact f32 count of test samples ``pred`` classifies correctly."""
    return jnp.sum((pred == problem.y).astype(jnp.float32), axis=-1)


def accuracy(problem: SearchProblem, correct):
    """Test accuracy from an exact correct count.

    A multiplication by the constant reciprocal, not a division: XLA made
    that multiplication of the division in the programs inspected, and
    written out the result no longer depends on the rewrite, which can
    differ from a true division in the last bit.
    """
    return correct * (1.0 / problem.y.shape[0])


def objective_pair(problem: SearchProblem, correct, units, vote_cap):
    """(accuracy loss vs exact, normalized area) from a design's exact
    counts: correct test predictions and summed comparator area quanta.

    Every tree backend ends in this one formula, which only adds and
    multiplies by constants, so backends that agree on the counts agree
    on the objectives bit for bit, on any device (see `accuracy`).
    """
    area = area_mm2(problem, units, vote_cap)
    return (problem.exact_accuracy - accuracy(problem, correct),
            area * (1.0 / problem.exact_area_mm2))


def chromosome_accuracy(problem: SearchProblem, genes):
    bits, t_sub, vote_cap = decode_chromosome(problem, genes)
    pred = predict_votes(problem, bits, t_sub, vote_cap)
    return accuracy(problem, n_correct(problem, pred))


def chromosome_area_mm2(problem: SearchProblem, genes):
    """Additive LUT area (the paper's GA estimator) + per-node overheads +
    the vote-adder cell of the decoded mode (DESIGN.md §16)."""
    bits, t_sub, vote_cap = decode_chromosome(problem, genes)
    idx = problem.lut_offsets[bits] + t_sub
    return area_mm2(problem, problem.area_lut_units[idx].sum(), vote_cap)


def objectives(problem: SearchProblem, genes):
    """(accuracy_loss vs exact, normalized area) — both minimized.

    ONE shared gene decode feeds both objectives (DESIGN.md §12): the
    accuracy term consumes the effective (bits, t_sub, vote_cap) for the
    comparator/vote eval, the area term reuses the same triple as the LUT
    index + vote-adder cell — historically each objective decoded the
    chromosome independently, doubling the decode work per eval.
    """
    bits, t_sub, vote_cap = decode_chromosome(problem, genes)
    pred = predict_votes(problem, bits, t_sub, vote_cap)
    idx = problem.lut_offsets[bits] + t_sub
    return jnp.stack(objective_pair(
        problem, n_correct(problem, pred),
        problem.area_lut_units[idx].sum(), vote_cap))


# ---------------------------------------------------------------------------
# builders
# ---------------------------------------------------------------------------

def build_problem(ptrees, x_test: np.ndarray, y_test: np.ndarray,
                  n_classes: int | None = None) -> SearchProblem:
    """Build a SearchProblem from one or more `ParallelTree`s.

    `ptrees` may be a single tree or a list (forest, joint chromosome).
    """
    if isinstance(ptrees, ParallelTree):
        ptrees = [ptrees]
    if n_classes is None:
        n_classes = max(pt.n_classes for pt in ptrees)
    n_features = int(x_test.shape[1])

    arrays = concatenate_ptrees(ptrees)
    feature, threshold, path = (arrays["feature"], arrays["threshold"],
                                arrays["path"])
    path_len, n_neg = arrays["path_len"], arrays["n_neg"]
    leaf_class, leaf_tree = arrays["leaf_class"], arrays["leaf_tree"]
    n_total = feature.shape[0]
    l_total = leaf_class.shape[0]

    lut_units, offsets = area_mod.build_area_unit_lut()
    x8 = quantize_u8(x_test).astype(np.int32)
    overhead = area_mod.tree_overhead_mm2(n_total, l_total)
    # vote-adder cells, priced from the isolated netlist harness (§16);
    # both zero for K = 1 (no vote stage exists — the gene is inert)
    vote_exact = area_mod.vote_adder_units(len(ptrees), int(n_classes),
                                           approx=False)
    vote_approx = area_mod.vote_adder_units(len(ptrees), int(n_classes),
                                            approx=True)

    # exact design: 8-bit, zero margin, exact vote adder
    t8 = np.clip(np.floor(threshold.astype(np.float64) * 256.0), 0, 255)
    t8 = t8.astype(np.int64)
    exact_bits = np.full(n_total, quant.MAX_BITS, dtype=np.int64)
    exact_units = int(lut_units[offsets[exact_bits] + t8].sum()) + vote_exact
    exact_area = exact_units * area_mod.AREA_QUANTUM_MM2 + overhead

    problem = SearchProblem(
        feature=jnp.asarray(feature),
        threshold=jnp.asarray(threshold),
        path=jnp.asarray(path),
        path_len=jnp.asarray(path_len),
        n_neg=jnp.asarray(n_neg),
        leaf_class=jnp.asarray(leaf_class),
        leaf_tree=jnp.asarray(leaf_tree),
        x8=jnp.asarray(x8),
        x_sel=jnp.asarray(x8[:, feature]),
        y=jnp.asarray(y_test.astype(np.int32)),
        area_lut_units=jnp.asarray(lut_units),
        lut_offsets=jnp.asarray(offsets),
        overhead_mm2=float(overhead),
        exact_area_mm2=exact_area,
        exact_accuracy=0.0,  # filled below
        n_classes=int(n_classes),
        n_features=n_features,
        n_trees=len(ptrees),
        tree_comparators=tuple(pt.n_comparators for pt in ptrees),
        tree_leaves=tuple(pt.n_leaves for pt in ptrees),
        vote_units_exact=vote_exact,
        vote_units_approx=vote_approx,
    )
    exact_acc = float(chromosome_accuracy(
        problem, jnp.asarray(quant.exact_tree_genes(n_total))))
    return dataclasses.replace(problem, exact_accuracy=exact_acc)


def problem_ptrees(problem: SearchProblem) -> list:
    """Recover the per-tree `ParallelTree`s from the concatenated layout.

    The block-diagonal super-tree is sliced back apart using the static
    per-tree comparator/leaf counts, so the hardware pipeline (netlist
    build, RTL emission, DESIGN.md §10) needs only the `SearchProblem` —
    the original trees don't have to be threaded through the engine.
    """
    feature = np.asarray(problem.feature)
    threshold = np.asarray(problem.threshold)
    path = np.asarray(problem.path)
    path_len = np.asarray(problem.path_len)
    n_neg = np.asarray(problem.n_neg)
    leaf_class = np.asarray(problem.leaf_class)
    ptrees, n_off, l_off = [], 0, 0
    for n_k, l_k in zip(problem.tree_comparators, problem.tree_leaves):
        block = path[l_off:l_off + l_k, n_off:n_off + n_k]
        if n_k == 0:  # single-leaf tree: ParallelTree keeps one dummy column
            block = np.zeros((l_k, 1), np.int8)
        ptrees.append(ParallelTree(
            feature=feature[n_off:n_off + n_k],
            threshold=threshold[n_off:n_off + n_k],
            path=np.ascontiguousarray(block),
            path_len=path_len[l_off:l_off + l_k],
            n_neg=n_neg[l_off:l_off + l_k],
            leaf_class=leaf_class[l_off:l_off + l_k],
            n_classes=problem.n_classes,
        ))
        n_off += n_k
        l_off += l_k
    return ptrees


def build_tree_problem(ptree: ParallelTree, x_test, y_test) -> SearchProblem:
    return build_problem(ptree, x_test, y_test)


def build_forest_problem(forest, x_test, y_test) -> SearchProblem:
    """`forest` is a `repro.core.forest.Forest`."""
    return build_problem(list(forest.ptrees), x_test, y_test,
                         n_classes=forest.n_classes)

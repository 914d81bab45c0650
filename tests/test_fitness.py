"""Fused fitness pipeline (DESIGN.md §12): the population-tiled Pallas
`fitness_errors` kernel vs the reference backend and the materializing
`tree_infer_scores` oracle — bit-exact on trees AND forests, including
ragged tile edges and the sweep's inert-padded genes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.datasets import load_dataset
from repro.core import forest as forest_mod, quant
from repro.core.train import train_tree
from repro.core.tree import to_parallel
from repro.kernels import ops, ref
from repro import search
from repro.search import sweep as sweep_mod


@pytest.fixture(scope="module")
def tree_problem():
    ds = load_dataset("vertebral")
    pt = to_parallel(train_tree(ds.x_train, ds.y_train, ds.n_classes))
    return search.build_tree_problem(pt, ds.x_test, ds.y_test)


@pytest.fixture(scope="module")
def forest_problem():
    ds = load_dataset("seeds")
    fr = forest_mod.train_forest(ds.x_train, ds.y_train, ds.n_classes,
                                 n_trees=4)
    return search.build_forest_problem(fr, ds.x_test, ds.y_test)


@pytest.fixture(scope="module")
def wide_forest_problem():
    """pendigits forest[2]: N = L = 512 after padding, wide enough that the
    derived leaf tile (128) is below the leaf axis."""
    ds = load_dataset("pendigits")
    fr = forest_mod.train_forest(ds.x_train, ds.y_train, ds.n_classes,
                                 n_trees=2)
    return search.build_forest_problem(fr, ds.x_test, ds.y_test)


def _fit_operands(problem):
    return ops.prepare_fitness_operands(
        problem.x_sel, problem.y, problem.path, problem.path_len,
        problem.n_neg, problem.leaf_class, problem.n_classes)


# ---------------------------------------------------------------------------
# objectives: fused kernel backend == reference backend, array-for-array
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=8)
@given(seed=st.integers(0, 2**31 - 1), pop=st.integers(1, 21),
       block_p=st.sampled_from([1, 3, 8, 16]))
def test_fused_objectives_bitexact_tree(tree_problem, seed, pop, block_p):
    """Tree problem, ragged population edges (P not a block_p multiple)."""
    f_ref = search.make_fitness(tree_problem, "reference")
    f_ker = search.make_fitness(tree_problem, "kernel", interpret=True,
                                block_p=block_p)
    genes = jax.random.uniform(jax.random.PRNGKey(seed),
                               (pop, tree_problem.n_genes))
    np.testing.assert_array_equal(np.asarray(f_ref(genes)),
                                  np.asarray(f_ker(genes)))


@settings(deadline=None, max_examples=6)
@given(seed=st.integers(0, 2**31 - 1), block_p=st.sampled_from([2, 8]),
       block_b=st.sampled_from([128, 256]),
       block_l=st.sampled_from([None, 128]))
def test_fused_objectives_bitexact_forest(forest_problem, seed, block_p,
                                          block_b, block_l):
    """Forest problem: block-diagonal super-tree with leaf-axis tiling and
    a batch size that is not a block_b multiple."""
    f_ref = search.make_fitness(forest_problem, "reference")
    f_ker = search.make_fitness(forest_problem, "kernel", interpret=True,
                                block_p=block_p, block_b=block_b,
                                block_l=block_l)
    genes = jax.random.uniform(jax.random.PRNGKey(seed),
                               (11, forest_problem.n_genes))
    np.testing.assert_array_equal(np.asarray(f_ref(genes)),
                                  np.asarray(f_ker(genes)))


def test_fused_exact_genes_zero_loss(tree_problem):
    """The exact 8-bit zero-margin chromosome scores (to f32 rounding of the
    stored reference point) zero loss and unit area through the fused path,
    bit-identical to the reference backend."""
    g = jnp.asarray(tree_problem.exact_genes())[None]
    f_ref = search.make_fitness(tree_problem, "reference")
    f_ker = search.make_fitness(tree_problem, "kernel", interpret=True)
    objs = np.asarray(f_ker(g))
    np.testing.assert_array_equal(objs, np.asarray(f_ref(g)))
    assert abs(objs[0, 0]) < 1e-6
    assert np.isclose(objs[0, 1], 1.0)


# ---------------------------------------------------------------------------
# error counts: fused kernel == argmax(tree_infer_scores) == jnp oracle
# ---------------------------------------------------------------------------

def test_fitness_errors_matches_tree_infer_scores_oracle(forest_problem):
    """The materializing kernel stays the bit-exact oracle of the fused one:
    errors == count(argmax(tree_infer_scores) != y), chromosome by
    chromosome."""
    prob = forest_problem
    fit_ops = _fit_operands(prob)
    ti_ops = ops.prepare_operands(
        prob.feature, prob.path, prob.path_len, prob.n_neg, prob.leaf_class,
        prob.n_classes, prob.n_features)
    genes = jax.random.uniform(jax.random.PRNGKey(7), (9, prob.n_genes))
    scale, thr, vote_cap = ops.decode_population(prob.threshold, genes)
    errors = np.asarray(ops.fitness_errors(fit_ops, scale, thr, vote_cap,
                                           interpret=True))
    preds = np.asarray(ops.tree_infer_predict(prob.x8, ti_ops, scale, thr,
                                              vote_cap, interpret=True))
    want = (preds != np.asarray(prob.y)[None, :]).sum(axis=1)
    np.testing.assert_array_equal(errors, want.astype(np.float32))


@settings(deadline=None, max_examples=8)
@given(seed=st.integers(0, 2**31 - 1), block_p=st.sampled_from([1, 2, 8]),
       block_b=st.sampled_from([128, 256]))
def test_raw_kernel_matches_ref_oracle_padded_ops(tree_problem, seed,
                                                  block_p, block_b):
    """Raw kernel vs kernels.ref on identical padded operands: the
    lane-replicated accumulator holds the same correct count in every lane."""
    from repro.kernels.fitness import fitness_errors as raw_kernel
    prob = tree_problem
    x_sel, path_t, target, cls1h, y_row = _fit_operands(prob)
    rng = np.random.default_rng(seed)
    n = x_sel.shape[1]
    p = 8
    bits = rng.integers(2, 9, (p, n))
    scale = jnp.asarray(np.exp2(-(8 - bits)).astype(np.float32))
    thr = jnp.asarray(rng.integers(0, 256, (p, n)).astype(np.float32))
    # mixed exact/approx vote caps (lane-replicated for the kernel operand)
    cap = jnp.asarray(np.where(rng.integers(0, 2, p) > 0, 1.0,
                               np.inf).astype(np.float32))
    from repro.kernels.fitness import LANES
    vcap = jnp.broadcast_to(cap[:, None], (p, LANES))
    x_pad = ops._pad_to(x_sel, block_b, 0)
    y_pad = ops._pad_to(y_row, block_b, 1, value=-1.0)
    got = np.asarray(raw_kernel(x_pad, scale, thr, path_t, target, cls1h,
                                y_pad, vcap, block_p=block_p, block_b=block_b,
                                interpret=True))
    want = np.asarray(ref.fitness_correct_counts(
        x_pad, scale, thr, path_t, target, cls1h, y_pad, cap))
    for lane in (0, 1, 127):
        np.testing.assert_array_equal(got[:, lane], want)


# ---------------------------------------------------------------------------
# the sweep's inert-padded genes ride the fused path unchanged
# ---------------------------------------------------------------------------

def test_fused_errors_on_sweep_padded_problem(tree_problem, forest_problem):
    """Run the fused kernel on a sweep-padded problem (§11 inert padding):
    pad-gene columns never change the error counts, and the counts match
    the real problem's reference predictions."""
    problems = {"tree": tree_problem, "forest": forest_problem}
    (bucket,) = sweep_mod.plan_buckets(problems, max_buckets=1)
    rng = np.random.default_rng(3)
    for name, problem in problems.items():
        pp = sweep_mod.pad_problem(problem, bucket.dims)
        leaf_class = np.asarray(jnp.argmax(pp.leaf_onehot, axis=1))
        fit_ops = ops.prepare_fitness_operands(
            pp.x_sel, pp.y, pp.path, pp.path_len, pp.n_neg,
            leaf_class, int(pp.leaf_onehot.shape[1]))

        g_real = rng.uniform(0, 1, problem.n_genes).astype(np.float32)
        a = rng.uniform(0, 1, (1, pp.n_genes)).astype(np.float32)
        b = rng.uniform(0, 1, (1, pp.n_genes)).astype(np.float32)
        # §16 layout: real comparator genes are a prefix, but the trailing
        # vote gene lives in the LAST padded column (TreeFamily.unpad_genes)
        n_comp_genes = problem.n_genes - 1
        for g in (a, b):
            g[0, :n_comp_genes] = g_real[:n_comp_genes]
            g[0, -1] = g_real[-1]

        errs = []
        for g in (a, b):
            scale, thr, vote_cap = ops.decode_population(pp.threshold,
                                                         jnp.asarray(g))
            errs.append(np.asarray(ops.fitness_errors(
                fit_ops, scale, thr, vote_cap, interpret=True)))
        np.testing.assert_array_equal(errs[0], errs[1], err_msg=name)

        bits, t_sub, vote_cap = search.decode_chromosome(problem,
                                                         jnp.asarray(g_real))
        pred = np.asarray(search.predict_votes(problem, bits, t_sub,
                                               vote_cap))
        want = float((pred != np.asarray(problem.y)).sum())
        assert errs[0][0] == want, name


# ---------------------------------------------------------------------------
# hoisted prep + shared decode plumbing
# ---------------------------------------------------------------------------

def test_problem_x_sel_is_hoisted_gather(tree_problem, forest_problem):
    for prob in (tree_problem, forest_problem):
        want = np.asarray(prob.x8)[:, np.asarray(prob.feature)]
        np.testing.assert_array_equal(np.asarray(prob.x_sel), want)


def test_decode_population_full_consistent(tree_problem):
    """The shared decode returns exactly what the two historical decodes
    produced — (scale, thr) for the kernel, (bits, t_sub) for the area LUT —
    with DESIGN.md §16 truncation folded into the EFFECTIVE operands."""
    genes = jax.random.uniform(jax.random.PRNGKey(11),
                               (6, tree_problem.n_genes))
    scale, t_sub, bits, vote_cap = ops.decode_population_full(
        tree_problem.threshold, genes)
    scale2, thr2, vote_cap2 = ops.decode_population(tree_problem.threshold,
                                                    genes)
    np.testing.assert_array_equal(np.asarray(scale), np.asarray(scale2))
    np.testing.assert_array_equal(np.asarray(t_sub, np.float32),
                                  np.asarray(thr2))
    np.testing.assert_array_equal(np.asarray(vote_cap), np.asarray(vote_cap2))
    bits_w, margin, trunc_w, vote_w = quant.decode_tree_genes(genes)
    t_sub_w = quant.substitute(
        quant.threshold_to_int(tree_problem.threshold[None, :], bits_w),
        margin, bits_w)
    np.testing.assert_array_equal(np.asarray(bits),
                                  np.asarray(bits_w - trunc_w))
    np.testing.assert_array_equal(
        np.asarray(t_sub), np.asarray(jnp.right_shift(t_sub_w, trunc_w)))
    np.testing.assert_array_equal(
        np.asarray(vote_cap),
        np.where(np.asarray(vote_w) > 0, np.float32(1.0), np.float32(np.inf)))


def test_shift_scale_floor_equals_integer_shift():
    """The kernels' ``floor(x8 * scale)`` is the reference's ``x8 >> s`` for
    every master code and every shift the decode can produce: the scale is
    the exact power of two, multiples of 2^s included."""
    shifts = jnp.arange(quant.MASTER_BITS + 1, dtype=jnp.int32)
    scale = np.asarray(jax.jit(quant.shift_scale)(shifts))
    np.testing.assert_array_equal(
        scale, 2.0 ** -np.arange(quant.MASTER_BITS + 1, dtype=np.float32))
    x8 = np.arange(256, dtype=np.float32)[:, None]
    np.testing.assert_array_equal(
        np.floor(x8 * scale[None, :]).astype(np.int64),
        np.arange(256)[:, None] >> np.arange(quant.MASTER_BITS + 1)[None, :])


def test_fitness_errors_rejects_bad_blocking(tree_problem):
    from repro.kernels.fitness import fitness_errors as raw_kernel
    x_sel, path_t, target, cls1h, y_row = _fit_operands(tree_problem)
    x_pad = ops._pad_to(x_sel, 256, 0)
    y_pad = ops._pad_to(y_row, 256, 1, value=-1.0)
    n = x_sel.shape[1]
    scale = jnp.ones((6, n), jnp.float32)
    from repro.kernels.fitness import LANES
    vcap = jnp.full((6, LANES), jnp.inf, jnp.float32)
    with pytest.raises(ValueError, match="block_p"):
        raw_kernel(x_pad, scale, scale, path_t, target, cls1h, y_pad, vcap,
                   block_p=4, block_b=256, interpret=True)


# ---------------------------------------------------------------------------
# kernel repairs for the TPU compiler: int32 class iota, derived leaf tile
# ---------------------------------------------------------------------------

def test_fitness_kernel_argmax_first_max_on_ties():
    """The in-kernel argmax (int32 iota cast to f32 + masked min) keeps
    jnp.argmax's first-max rule: on tied votes the lowest class wins, with
    and without the saturating vote cap."""
    from repro.kernels.fitness import LANES, fitness_errors as raw_kernel

    n = l = c = 128
    n_b, n_pop, n_cls = 256, 8, 6
    rng = np.random.default_rng(5)
    leaf_class = np.arange(l) % n_cls
    # leaf j fires iff comparator j fires; comparator j fires iff thr < 0
    path_t = np.eye(n, l, dtype=np.float32)
    target = np.ones((1, l), np.float32)
    cls1h = np.zeros((l, c), np.float32)
    cls1h[np.arange(l), leaf_class] = 1.0
    # chromosome p fires 2 leaves of class a_p and 2 of class b_p (a tie),
    # plus one leaf of a third class
    fire = np.zeros((n_pop, n), bool)
    for p in range(n_pop):
        a, b, third = rng.choice(n_cls, 3, replace=False)
        for cls, k in ((a, 2), (b, 2), (third, 1)):
            fire[p, np.flatnonzero(leaf_class == cls)[:k]] = True
    thr = np.where(fire, -1.0, 256.0).astype(np.float32)
    cap = np.where(np.arange(n_pop) % 2, 1.0, np.inf).astype(np.float32)
    votes = np.minimum(fire.astype(np.float32) @ cls1h[:n],
                       cap[:, None])                       # (P, C)
    pred = votes.argmax(axis=1)                            # first max
    assert (np.sort(votes, axis=1)[:, -1] == np.sort(votes, axis=1)[:, -2]).all()
    # class k labels k * 10 + 5 rows, so each count names one class
    y = np.repeat(np.arange(n_cls), np.arange(n_cls) * 10 + 5)
    y = np.concatenate([y, np.full(n_b - y.size, -1)]).astype(np.float32)
    args = (jnp.zeros((n_b, n), jnp.float32), jnp.ones((n_pop, n)),
            jnp.asarray(thr), jnp.asarray(path_t), jnp.asarray(target),
            jnp.asarray(cls1h), jnp.asarray(y[None]),
            jnp.broadcast_to(jnp.asarray(cap)[:, None], (n_pop, LANES)))
    got = np.asarray(raw_kernel(*args, interpret=True))[:, 0]
    np.testing.assert_array_equal(got, pred * 10.0 + 5.0)
    want = ref.fitness_correct_counts(*args[:7], jnp.asarray(cap))
    np.testing.assert_array_equal(got, np.asarray(want))


def test_derived_block_l_below_leaf_axis_matches_whole_axis(
        wide_forest_problem):
    """``block_l=None`` derives a 128 leaf tile for a 512-leaf forest; the
    tiled vote accumulation equals the whole-axis run, and the kernel
    backend's objectives equal the reference backend's exactly."""
    from repro.kernels import fitness as fit_mod

    prob = wide_forest_problem
    fit_ops = _fit_operands(prob)
    _, path_t, _, cls1h, _ = fit_ops
    n, l = path_t.shape
    c = cls1h.shape[1]
    assert l == 512
    derived = fit_mod.pick_block_l(
        l, lambda bl: fit_mod.vmem_bytes(n, bl, c, 256, 8))
    assert derived == 128 < l
    genes = jax.random.uniform(jax.random.PRNGKey(2), (8, prob.n_genes))
    scale, thr, vote_cap = ops.decode_population(prob.threshold, genes)
    tiled = np.asarray(ops.fitness_errors(fit_ops, scale, thr, vote_cap,
                                          interpret=True))
    whole = np.asarray(ops.fitness_errors(fit_ops, scale, thr, vote_cap,
                                          block_l=l, interpret=True))
    np.testing.assert_array_equal(tiled, whole)
    f_ref = search.make_fitness(prob, "reference")
    f_ker = search.make_fitness(prob, "kernel", interpret=True)
    np.testing.assert_array_equal(np.asarray(f_ker(genes)),
                                  np.asarray(f_ref(genes)))

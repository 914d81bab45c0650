"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU), shape sweeps."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.datasets import load_dataset, quantize_u8
from repro.core.train import train_tree
from repro.core.tree import to_parallel, ptree_to_jnp, predict_quantized
from repro.core import nsga2, quant
from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# tree_infer
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tree_setup():
    ds = load_dataset("vertebral")
    tree = train_tree(ds.x_train, ds.y_train, ds.n_classes)
    pt = to_parallel(tree)
    x8 = quantize_u8(ds.x_test).astype(np.int32)
    return ds, pt, x8


def test_tree_infer_matches_core_reference(tree_setup):
    """Kernel == the core.tree quantized predictor for a random population."""
    ds, pt, x8 = tree_setup
    operands = ops.prepare_tree_operands(pt, ds.n_features)
    rng = np.random.default_rng(0)
    genes = jnp.asarray(
        rng.uniform(0, 1, (9, 3 * pt.n_comparators + 1)).astype(np.float32))
    # the core.tree oracle predates §16 approximation genes: zero them
    genes = genes.at[:, 2::3].set(0.0).at[:, -1].set(0.0)
    scale, thr, vote_cap = ops.decode_population(jnp.asarray(pt.threshold),
                                                 genes)
    preds = ops.tree_infer_predict(jnp.asarray(x8), operands, scale, thr,
                                   vote_cap, interpret=True)
    pj = ptree_to_jnp(pt)
    for i in range(genes.shape[0]):
        bits, marg, _, _ = quant.decode_tree_genes(genes[i])
        want = predict_quantized(jnp.asarray(x8), pj, bits, marg)
        np.testing.assert_array_equal(np.asarray(preds[i]), np.asarray(want))


def test_tree_infer_exact_genes_match_float_tree(tree_setup):
    ds, pt, x8 = tree_setup
    operands = ops.prepare_tree_operands(pt, ds.n_features)
    genes = jnp.asarray(quant.exact_tree_genes(pt.n_comparators))[None]
    scale, thr, vote_cap = ops.decode_population(jnp.asarray(pt.threshold),
                                                 genes)
    preds = ops.tree_infer_predict(jnp.asarray(x8), operands, scale, thr,
                                   vote_cap, interpret=True)
    pj = ptree_to_jnp(pt)
    bits = jnp.full(pt.n_comparators, 8, jnp.int32)
    marg = jnp.zeros(pt.n_comparators, jnp.int32)
    want = predict_quantized(jnp.asarray(x8), pj, bits, marg)
    np.testing.assert_array_equal(np.asarray(preds[0]), np.asarray(want))


def test_tree_infer_kernel_vs_ref_oracle_padded_ops(tree_setup):
    """Raw kernel vs ref.py on identical padded operands (several blockings)."""
    ds, pt, x8 = tree_setup
    operands = ops.prepare_tree_operands(pt, ds.n_features)
    sel, path_t, target, cls1h = operands
    rng = np.random.default_rng(1)
    n = sel.shape[1]
    p = 4
    bits = rng.integers(2, 9, (p, n))
    scale = np.exp2(-(8 - bits)).astype(np.float32)
    thr = rng.integers(0, 256, (p, n)).astype(np.float32)
    b = 512
    x8f = rng.integers(0, 256, (b, sel.shape[0])).astype(np.float32)
    want = ref.tree_infer_scores(jnp.asarray(x8f), sel, jnp.asarray(scale),
                                 jnp.asarray(thr), path_t, target, cls1h)
    from repro.kernels.tree_infer import tree_infer_scores
    for block_b in (128, 256, 512):
        got = tree_infer_scores(jnp.asarray(x8f), sel, jnp.asarray(scale),
                                jnp.asarray(thr), path_t, target, cls1h,
                                block_b=block_b, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=0)


def test_tree_infer_population_rows_match_single_rows(tree_setup):
    """P > 1 population operands travel as (P, 1, N) with the leading axis
    squeezed out of the block (the layout the TPU compiler accepts): every
    row equals its own P = 1 launch and the jnp oracle, leaf tiling too."""
    from repro.kernels.tree_infer import tree_infer_scores

    ds, pt, x8 = tree_setup
    sel, path_t, target, cls1h = ops.prepare_forest_operands(
        [pt] * 5, ds.n_features)
    assert path_t.shape[1] > 128   # block_l=128 really tiles the leaves
    rng = np.random.default_rng(4)
    n, p = sel.shape[1], 5
    scale = jnp.asarray(np.exp2(-(8 - rng.integers(2, 9, (p, n))))
                        .astype(np.float32))
    thr = jnp.asarray(rng.integers(0, 256, (p, n)).astype(np.float32))
    x8f = jnp.asarray(rng.integers(0, 256, (256, sel.shape[0]))
                      .astype(np.float32))
    want = ref.tree_infer_scores(x8f, sel, scale, thr, path_t, target, cls1h)
    for block_l in (None, 128):
        got = tree_infer_scores(x8f, sel, scale, thr, path_t, target, cls1h,
                                block_b=128, block_l=block_l, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        for i in range(p):
            row = tree_infer_scores(x8f, sel, scale[i:i + 1], thr[i:i + 1],
                                    path_t, target, cls1h, block_b=128,
                                    block_l=block_l, interpret=True)
            np.testing.assert_array_equal(np.asarray(row[0]),
                                          np.asarray(got[i]))


# ---------------------------------------------------------------------------
# domination
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=10)
@given(seed=st.integers(0, 2**31 - 1), p=st.integers(2, 300),
       m=st.integers(1, 4))
def test_domination_kernel_matches_oracle(seed, p, m):
    rng = np.random.default_rng(seed)
    objs = jnp.asarray(rng.integers(0, 5, (p, m)).astype(np.float32))
    got = ops.domination_matrix(objs, interpret=True)
    want = ref.domination_matrix(objs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_domination_kernel_plugs_into_nsga2():
    rng = np.random.default_rng(3)
    objs = jnp.asarray(rng.uniform(0, 1, (64, 2)).astype(np.float32))
    rank_kernel = nsga2.non_dominated_sort(
        objs, ops.domination_matrix_bool(objs, interpret=True))
    rank_ref = nsga2.non_dominated_sort(objs)
    np.testing.assert_array_equal(np.asarray(rank_kernel), np.asarray(rank_ref))


@pytest.mark.parametrize("pi,pj,m", [
    (8, 16, 2), (130, 64, 3), (5, 300, 2), (64, 64, 4),
])
def test_domination_block_rectangular_matches_oracle(pi, pj, m):
    """The sharded-sort entry point (DESIGN.md §13): a (Pi, Pj) row block of
    the domination matrix, rows and columns from DIFFERENT populations, must
    equal the rectangular jnp oracle exactly (incl. internal +inf padding)."""
    rng = np.random.default_rng(pi * 1000 + pj)
    a = jnp.asarray(rng.integers(0, 5, (pi, m)).astype(np.float32))
    b = jnp.asarray(rng.integers(0, 5, (pj, m)).astype(np.float32))
    got = ops.domination_block_bool(a, b, interpret=True)
    want = ref.domination_matrix(a, b)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_domination_block_rejects_mismatched_objectives():
    a = jnp.zeros((8, 2), dtype=jnp.float32)
    b = jnp.zeros((8, 3), dtype=jnp.float32)
    with pytest.raises(ValueError):
        ops.domination_block(a, b, interpret=True)


# ---------------------------------------------------------------------------
# qmatmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [
    (128, 512, 256), (256, 1024, 512), (100, 300, 77), (1, 512, 640),
    (257, 129, 385),
])
def test_qmatmul_matches_oracle_shapes(m, k, n):
    rng = np.random.default_rng(m * 7 + k * 3 + n)
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    w = jnp.asarray(rng.integers(-8, 8, (k, n)).astype(np.int8))
    s = jnp.asarray(rng.uniform(0.01, 0.1, (n,)).astype(np.float32))
    got = ops.qmatmul(x, w, s, interpret=True)
    want = ref.qmatmul(x, w, s.reshape(1, -1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qmatmul_dtypes(dtype):
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(64, 256)).astype(np.float32)).astype(dtype)
    w = jnp.asarray(rng.integers(-2, 3, (256, 128)).astype(np.int8))
    s = jnp.asarray(np.full((128,), 0.5, np.float32))
    got = ops.qmatmul(x, w, s, interpret=True)
    want = ref.qmatmul(x.astype(jnp.float32), w, s.reshape(1, -1))
    tol = 1e-5 if dtype == jnp.float32 else 0.15
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


def test_qmatmul_blocking_sweep():
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(256, 1024)).astype(np.float32))
    w = jnp.asarray(rng.integers(-128, 128, (1024, 256)).astype(np.int8))
    s = jnp.asarray(rng.uniform(0.001, 0.01, (256,)).astype(np.float32))
    want = ref.qmatmul(x, w, s.reshape(1, -1))
    for bm, bn, bk in [(128, 128, 128), (256, 128, 512), (128, 256, 1024)]:
        got = ops.qmatmul(x, w, s, block_m=bm, block_n=bn, block_k=bk,
                          interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-3)


def test_kernel_fitness_equals_reference_fitness(tree_setup):
    """The kernel-backed GA fitness is bit-identical to the vmap reference."""
    from repro.core import approx
    ds, pt, x8 = tree_setup
    prob = approx.build_problem(pt, ds.x_test, ds.y_test)
    f_ref = approx.make_fitness_fn(prob)
    f_ker = approx.make_fitness_fn_kernel(prob, pt, ds.n_features, interpret=True)
    g = jax.random.uniform(jax.random.PRNGKey(7), (24, prob.n_genes))
    np.testing.assert_allclose(np.asarray(f_ref(g)), np.asarray(f_ker(g)),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,skv,hd,group", [
    (256, 256, 64, 1), (512, 512, 128, 4), (256, 512, 64, 2),
])
def test_flash_attention_matches_oracle(sq, skv, hd, group):
    from repro.kernels.flash_attn import flash_attention
    rng = np.random.default_rng(sq + skv + hd)
    hkv = 4
    h = hkv * group
    q = jnp.asarray(rng.normal(size=(h, sq, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(hkv, skv, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(hkv, skv, hd)).astype(np.float32))
    got = flash_attention(q, k, v, group=group, block_q=128, block_k=128,
                          interpret=True)
    want = ref.flash_attention(q, k, v, group=group)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16_and_softcap():
    from repro.kernels.flash_attn import flash_attention
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(2, 256, 64))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 256, 64))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 256, 64))).astype(jnp.bfloat16)
    got = flash_attention(q, k, v, group=1, softcap=30.0, block_q=128,
                          block_k=128, interpret=True)
    want = ref.flash_attention(q, k, v, group=1, softcap=30.0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=0.05, atol=0.05)


def test_flash_attention_blocking_sweep():
    from repro.kernels.flash_attn import flash_attention
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(4, 512, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(4, 512, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(4, 512, 64)).astype(np.float32))
    want = ref.flash_attention(q, k, v)
    for bq, bk in [(128, 256), (256, 128), (512, 512)]:
        got = flash_attention(q, k, v, block_q=bq, block_k=bk, interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

"""Jitted public wrappers around the Pallas kernels.

Handle padding to MXU-aligned tiles, operand preparation (one-hot selector /
path matrices, per-chromosome threshold decode) and CPU fallback: on a CPU
backend the kernels execute with ``interpret=True`` (the Pallas interpreter
runs the kernel body in Python), on TPU they compile to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.tree import ParallelTree, concatenate_ptrees
from repro.kernels import domination as _dom
from repro.kernels import fitness as _fit
from repro.kernels import qmatmul as _qmm
from repro.kernels import tree_infer as _ti


def _auto_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pad_to(x, mult, axis, value=0.0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ---------------------------------------------------------------------------
# tree_infer
# ---------------------------------------------------------------------------

def prepare_operands(feature, path, path_len, n_neg, leaf_class,
                     n_classes: int, n_features: int):
    """Padded kernel operands from (concatenated) comparator/leaf arrays.

    `path` (L, N) may be a single tree's path matrix or the block-diagonal
    super-tree of a forest (e.g. `SearchProblem.path`) — the kernel dataflow
    is identical either way (DESIGN.md §7).

    Padding is correctness-preserving:
      - SEL extra columns are all-zero -> x_sel = 0, thr pad = 2^8 so the
        padded comparator always outputs 0;
      - PATH pad rows/cols are zero; target pad = -1 is unsatisfiable, so
        padded leaves never fire; padded classes never win argmax.
    """
    feature = np.asarray(feature)
    path = np.asarray(path)
    path_len = np.asarray(path_len)
    n_neg = np.asarray(n_neg)
    leaf_class = np.asarray(leaf_class)
    l, n = path.shape
    sel = np.zeros((n_features, n), np.float32)
    sel[feature, np.arange(n)] = 1.0
    path_t = path.T.astype(np.float32)                          # (N, L)
    target = (path_len - n_neg).astype(np.float32)[None]        # (1, L)
    cls1h = np.zeros((l, n_classes), np.float32)
    cls1h[np.arange(l), leaf_class] = 1.0

    sel = _pad_to(_pad_to(jnp.asarray(sel), 128, 0), 128, 1)
    path_t = _pad_to(_pad_to(jnp.asarray(path_t), 128, 0), 128, 1)
    target = _pad_to(jnp.asarray(target), 128, 1, value=-1.0)
    cls1h = _pad_to(_pad_to(jnp.asarray(cls1h), 128, 0), 128, 1)
    return sel, path_t, target, cls1h


def prepare_forest_operands(ptrees, n_features: int):
    """Static operands for fused multi-tree inference (DESIGN.md §7).

    The forest is laid out as one block-diagonal "super-tree": the comparator
    axis concatenates every tree's comparators, the leaf axis every tree's
    leaves, and PATH^T is block-diagonal so each leaf row only sees its own
    tree's comparators. Exactly one leaf per tree satisfies its path, so the
    vote matmul (sat @ CLS1H) accumulates one vote per tree per class — the
    kernel's argmax IS the majority vote, with no per-tree Python loop.

    A single ``ParallelTree`` is the K=1 special case (`prepare_tree_operands`).
    """
    arrays = concatenate_ptrees(ptrees)
    return prepare_operands(
        arrays["feature"], arrays["path"], arrays["path_len"],
        arrays["n_neg"], arrays["leaf_class"],
        max(pt.n_classes for pt in ptrees), n_features,
    )


def prepare_tree_operands(pt: ParallelTree, n_features: int):
    """Single-tree operands: the K=1 case of `prepare_forest_operands`."""
    return prepare_forest_operands([pt], n_features)


def decode_population_full(threshold, genes):
    """ONE gene decode shared by the accuracy and area terms (DESIGN.md §12).

    threshold (N,) float; genes (P, 3N+1) in the cross-layer layout
    (DESIGN.md §16). Returns (scale, t_sub, bits, vote_cap): scale/t_sub/
    bits are (P, N) EFFECTIVE comparator operands with LSB truncation
    already folded in — width p - k, threshold t' >> k, shift scale
    2^-(8-p+k) — because a k-truncated comparator IS the exact comparator
    at that width, so the kernel compare needs no new op. `t_sub` (int32)
    indexes the area LUT directly (cast to f32 for the kernel); `vote_cap`
    (P,) f32 is the vote saturation (1.0 approx adder, +inf exact — an
    exact f32 no-op). Historically the kernel fitness decoded twice — once
    for scale/thr, once more for the area LUT index — doubling the
    per-chromosome decode work.
    """
    bits, margin, trunc, vote = quant.decode_tree_genes(genes)  # (P, N) each
    t_int = quant.threshold_to_int(threshold[None, :], bits)
    t_sub = quant.substitute(t_int, margin, bits)
    bits_eff = bits - trunc
    t_eff = jnp.right_shift(t_sub, trunc)
    scale = quant.shift_scale(quant.MASTER_BITS - bits_eff)
    vote_cap = jnp.where(vote > 0, jnp.float32(1.0), jnp.float32(jnp.inf))
    return scale, t_eff, bits_eff, vote_cap


def decode_population(threshold, genes):
    """Per-chromosome kernel operands from real-coded genes.

    threshold (N,) float; genes (P, 3N+1). Returns scale (P, N), thr (P, N)
    f32 (effective, truncation folded in) and vote_cap (P,) f32.
    """
    scale, t_sub, _, vote_cap = decode_population_full(threshold, genes)
    return scale, t_sub.astype(jnp.float32), vote_cap


@functools.partial(jax.jit, static_argnames=("block_b", "block_l", "interpret"))
def tree_infer_predict(x8, pt_operands, scale, thr, vote_cap=None, *,
                       block_b=256, block_l=None, interpret=None):
    """(P, B) predicted classes for a population of approximate trees/forests.

    x8 (B, F) int; pt_operands from prepare_tree_operands /
    prepare_forest_operands (already padded); scale/thr (P, N_padded-able);
    vote_cap (P,) f32 optional vote saturation (DESIGN.md §16) — the
    materialized class scores are clipped to it before argmax, modeling the
    approximate OR-tree vote adder (+inf rows are an exact no-op).
    For forest operands the returned class is the (possibly saturated)
    majority vote over trees (ties -> lowest class index, matching
    `forest_predict`). ``block_l`` tiles the concatenated leaf axis for
    large forests.
    """
    interpret = _auto_interpret() if interpret is None else interpret
    sel, path_t, target, cls1h = pt_operands
    x8f = _pad_to(_pad_to(x8.astype(jnp.float32), block_b, 0), 128, 1)
    x8f = x8f[:, : sel.shape[0]]
    n = sel.shape[1]
    scale = _pad_to(scale, n, 1)[:, :n]
    # padded comparators must never fire: thr pad = 256 > any x_p
    thr = _pad_to(thr, n, 1, value=256.0)[:, :n]
    if block_l is not None:
        block_l = _fit_block_l(path_t.shape[1], block_l)
    scores = _ti.tree_infer_scores(
        x8f, sel, scale, thr, path_t, target, cls1h,
        block_b=block_b, block_l=block_l, interpret=interpret,
    )
    scores = scores[:, : x8.shape[0], :]
    if vote_cap is not None:
        scores = jnp.minimum(scores, vote_cap[:, None, None])
    return jnp.argmax(scores, axis=-1)


def _fit_block_l(l_pad: int, block_l: int) -> int:
    """Round ``block_l`` down to a 128-multiple that divides the padded leaf
    axis, so one configured tile size works for any forest size (128 always
    divides the padded L)."""
    block_l = max(128, (min(block_l, l_pad) // 128) * 128)
    while l_pad % block_l:
        block_l -= 128
    return block_l


# ---------------------------------------------------------------------------
# serving (DESIGN.md §14)
# ---------------------------------------------------------------------------

def prepare_design(bits, t_int, trunc=None, vote_adder: str = "exact"):
    """Fixed-design kernel operands from a decoded pareto point.

    ``bits``/``t_int`` are one design's per-comparator precisions and
    substituted integer thresholds (e.g. a `pareto.json` point's `bits` /
    `t_int` arrays) — the already-decoded form, so serving never re-rounds
    genes. ``trunc``/``vote_adder`` select the point's approximate cells
    (DESIGN.md §16); truncation is folded into the effective scale/thr
    exactly as `decode_population_full` does. Returns (scale, thr,
    vote_cap): scale/thr (1, N) f32, vote_cap (1,) f32 — the P=1 row the
    population kernels consume.
    """
    if vote_adder not in ("exact", "approx"):
        raise ValueError(f"unknown vote_adder {vote_adder!r}")
    bits = jnp.asarray(bits, jnp.int32)
    t_int = jnp.asarray(t_int, jnp.int32)
    if trunc is not None:
        k = jnp.asarray(trunc, jnp.int32)
        bits = bits - k
        t_int = jnp.right_shift(t_int, k)
    scale = quant.shift_scale(quant.MASTER_BITS - bits)[None, :]
    thr = t_int.astype(jnp.float32)[None, :]
    cap = jnp.full((1,), 1.0 if vote_adder == "approx" else jnp.inf,
                   jnp.float32)
    return scale, thr, cap


def classify(x8, pt_operands, design, *, block_b=256, block_l=None,
             interpret=None):
    """(B,) predicted classes for ONE fixed tree/forest design.

    The batch-1..bucket serving entry (DESIGN.md §14): the P=1 row of
    `tree_infer_predict` over the same prepared operands, so a served
    prediction runs the exact tensor program the search scored — and the
    netlist simulator stays its bit-exact oracle. ``design`` comes from
    `prepare_design` (including the point's truncation/vote-adder
    approximation config); ``x8`` is (B, F) int master codes with B at any
    bucket size (the kernel pads the batch axis to ``block_b`` internally).
    """
    scale, thr, vote_cap = design
    return tree_infer_predict(x8, pt_operands, scale, thr, vote_cap,
                              block_b=block_b, block_l=block_l,
                              interpret=interpret)[0]


# ---------------------------------------------------------------------------
# fitness (fused fitness pipeline, DESIGN.md §12)
# ---------------------------------------------------------------------------

def prepare_fitness_operands(x_sel, y, path, path_len, n_neg,
                             leaf_class, n_classes: int):
    """Hoisted, padded operands for the fused fitness kernel.

    ``x_sel`` is the chromosome-invariant gather ``x8[:, feature]`` already
    hoisted onto the problem (`SearchProblem.x_sel` / `PaddedProblem.x_sel`,
    DESIGN.md §12) — it replaces the one-hot ``X8 @ SEL`` matmul that
    `tree_infer_scores` re-runs in every grid cell, and the per-chromosome
    comparator eval becomes a pure broadcast compare. Padding is
    correctness-preserving exactly as in `prepare_operands`: padded
    comparator columns are neutralized by the thr = 256 row padding applied
    in `fitness_errors`, padded leaves carry the unsatisfiable target -1,
    padded classes receive no votes.

    Returns ``(x_sel, path_t, target, cls1h, y_row)`` — `x_sel` (B, N) f32,
    `y_row` (1, B) f32 — with N/L/C padded to 128 multiples; the batch axis
    is padded at call time (it depends on ``block_b``).
    """
    path = np.asarray(path)
    path_len = np.asarray(path_len)
    n_neg = np.asarray(n_neg)
    leaf_class = np.asarray(leaf_class)
    l, n = path.shape
    x_sel = np.asarray(x_sel).astype(np.float32)
    path_t = path.T.astype(np.float32)                          # (N, L)
    target = (path_len - n_neg).astype(np.float32)[None]        # (1, L)
    cls1h = np.zeros((l, n_classes), np.float32)
    cls1h[np.arange(l), leaf_class] = 1.0

    x_sel = _pad_to(jnp.asarray(x_sel), 128, 1)
    path_t = _pad_to(_pad_to(jnp.asarray(path_t), 128, 0), 128, 1)
    target = _pad_to(jnp.asarray(target), 128, 1, value=-1.0)
    cls1h = _pad_to(_pad_to(jnp.asarray(cls1h), 128, 0), 128, 1)
    y_row = jnp.asarray(np.asarray(y).astype(np.float32))[None]  # (1, B)
    return x_sel, path_t, target, cls1h, y_row


@functools.partial(
    jax.jit, static_argnames=("block_p", "block_b", "block_l", "interpret")
)
def fitness_errors(fit_operands, scale, thr, vote_cap=None, *, block_p=8,
                   block_b=256, block_l=None, interpret=None):
    """(P,) misclassified-sample counts for a population of trees/forests.

    `fit_operands` from `prepare_fitness_operands` (N/L/C already padded);
    scale/thr (P, N-padded-able) f32; vote_cap (P,) f32 optional vote
    saturation (DESIGN.md §16) — the kernel clips the accumulated class
    votes to it before the on-chip argmax (+inf rows are an exact no-op,
    so omitting it IS the exact adder). Handles ragged edges internally:
    the batch axis pads to ``block_b`` with label -1 rows (never counted
    correct), the population axis pads to ``block_p`` with inert rows that
    are cropped from the result. One kernel launch computes the whole
    population x test-set x forest product and writes only the O(P)
    accumulator to HBM — `argmax(tree_infer_scores) != y` is the bit-exact
    materializing oracle (DESIGN.md §12).
    """
    interpret = _auto_interpret() if interpret is None else interpret
    x_sel, path_t, target, cls1h, y_row = fit_operands
    n_pop = scale.shape[0]
    n = x_sel.shape[1]
    x_sel_p = _pad_to(x_sel, block_b, 0)
    y_p = _pad_to(y_row, block_b, 1, value=-1.0)
    scale_p = _pad_to(_pad_to(scale, n, 1)[:, :n], block_p, 0)
    # padded comparators / chromosomes must never fire: thr pad = 256 > x_p
    thr_p = _pad_to(_pad_to(thr, n, 1, value=256.0)[:, :n],
                    block_p, 0, value=256.0)
    if vote_cap is None:
        vote_cap = jnp.full((n_pop,), jnp.inf, jnp.float32)
    # lane-replicated (P, LANES) tile; pad rows get the exact +inf cap
    vcap_p = _pad_to(jnp.broadcast_to(vote_cap[:, None].astype(jnp.float32),
                                      (n_pop, _fit.LANES)),
                     block_p, 0, value=jnp.inf)
    if block_l is not None:
        block_l = _fit_block_l(path_t.shape[1], block_l)
    counts = _fit.fitness_errors(
        x_sel_p, scale_p, thr_p, path_t, target, cls1h, y_p, vcap_p,
        block_p=block_p, block_b=block_b, block_l=block_l,
        interpret=interpret,
    )
    n_valid = jnp.sum((y_row >= 0).astype(jnp.float32))
    return n_valid - counts[:n_pop, 0]


# ---------------------------------------------------------------------------
# domination
# ---------------------------------------------------------------------------

def _dom_block_size(p, block):
    return min(block, max(128, 1 << (p - 1).bit_length()))


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def domination_block(objs_i, objs_j, *, block=256, interpret=None):
    """(Pi, Pj) f32 rectangular domination slab; accepts any Pi/Pj (pads
    internally).

    The sharded-sort entry point (DESIGN.md §13): ``objs_i`` is a shard's
    local population slab (rows), ``objs_j`` the all-gathered pool (columns).
    Padding rows/columns are +inf objectives — pad rows never dominate
    anything real, and pad columns (which real rows trivially dominate) are
    cropped before return.
    """
    interpret = _auto_interpret() if interpret is None else interpret
    pi, pj = objs_i.shape[0], objs_j.shape[0]
    bi, bj = _dom_block_size(pi, block), _dom_block_size(pj, block)
    oi = _pad_to(objs_i.astype(jnp.float32), bi, 0, value=jnp.inf)
    oj = _pad_to(objs_j.astype(jnp.float32), bj, 0, value=jnp.inf)
    dom = _dom.domination_block(oi, oj, block_i=bi, block_j=bj,
                                interpret=interpret)
    return dom[:pi, :pj]


def domination_block_bool(objs_i, objs_j, *, interpret=None):
    """Adapter with the core.nsga2 rectangular signature (bool output)."""
    return domination_block(objs_i, objs_j, interpret=interpret) > 0.5


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def domination_matrix(objs, *, block=256, interpret=None):
    """(P, P) f32 domination matrix; accepts any P (pads internally).

    Padding rows are +inf objectives: they never dominate anything real and
    the returned matrix is cropped back to (P, P).
    """
    return domination_block(objs, objs, block=block, interpret=interpret)


def domination_matrix_bool(objs, *, interpret=None):
    """Adapter with the core.nsga2 signature (bool output)."""
    return domination_matrix(objs, interpret=interpret) > 0.5


# ---------------------------------------------------------------------------
# qmatmul
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def qmatmul(x, w_q, scale, *, block_m=256, block_n=256, block_k=512,
            interpret=None):
    """Mixed-precision matmul with padding to MXU tiles.

    x (M, K) f32/bf16; w_q (K, N) int8 codes; scale (N,) or (1, N) f32.
    Returns (M, N) f32.
    """
    interpret = _auto_interpret() if interpret is None else interpret
    m, k = x.shape
    _, n = w_q.shape
    scale = scale.reshape(1, -1)
    bm, bn, bk = (min(block_m, _ceil_mult(m)), min(block_n, _ceil_mult(n)),
                  min(block_k, _ceil_mult(k)))
    xp = _pad_to(_pad_to(x, bm, 0), bk, 1)
    wp = _pad_to(_pad_to(w_q, bk, 0), bn, 1)
    sp = _pad_to(scale, bn, 1)
    out = _qmm.qmatmul(xp, wp, sp, block_m=bm, block_n=bn, block_k=bk,
                       interpret=interpret)
    return out[:m, :n]


def _ceil_mult(size, base=128):
    """Smallest multiple of `base` >= min(size_rounded, base*8)."""
    r = ((size + base - 1) // base) * base
    return max(base, min(r, base * 8))

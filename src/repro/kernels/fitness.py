"""Pallas TPU kernel: population-tiled fused fitness (DESIGN.md §12).

`tree_infer_scores` (kernels.tree_infer) evaluates the GA's
`population x test_set` product but materializes a full (P, B, C) vote
tensor to HBM, re-runs the chromosome-invariant `X8 @ SEL` feature-gather
matmul in every grid cell, and streams each chromosome's operands as (1, N)
tiles that leave 7 of 8 VPU sublanes idle. This kernel is the fused-fitness
replacement: the argmax + label compare + batch reduction happen *inside*
the kernel, so the only HBM write is the per-chromosome correct-count
accumulator — O(P) instead of O(P·B·C) — and the feature gather is hoisted
out entirely (the caller passes the precomputed `x_sel (B, N)` once per
problem, see `search.problem`/`kernels.ops.prepare_fitness_operands`).

Per grid cell, a `(block_p, N)` slab of chromosomes meets a `(block_b, N)`
batch tile of hoisted codes:

    x_p    = floor(x_sel * 2^-(8-p))      broadcast over block_p      (VPU)
    d      = x_p > t'                     (block_p, block_b, N)       (VPU)
    score  = d @ PATH^T                   batched path matmul         (MXU)
    sat    = (score == target)            leaf decode                 (VPU)
    votes  = sat @ CLS1H                  batched vote matmul         (MXU)
    [accumulate votes over leaf blocks in VMEM scratch]
    pred   = first-max argmax over C      iota + masked min           (VPU)
    out   += sum_b (pred == y)            per-chromosome correct count

Grid = (pop_blocks, batch_blocks, leaf_blocks); the leaf axis is innermost
so partial vote matmuls accumulate into the VMEM scratch, and the batch
axis is sequential so the (block_p, LANES) output block — lane-replicated
so the accumulator stays a native f32 tile — is revisited, not re-written.

All integer quantities are exact in f32 (< 2^24) and every reduction adds
small exact integers, so the errors computed here match
`argmax(tree_infer_scores) != y` bit-for-bit; `tree_infer_scores` stays the
materializing oracle (tests assert equality, see tests/test_fitness.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The correct-count accumulator is replicated across one full lane tile so
# the output block is a native (block_p, 128) f32 tile; callers read lane 0.
LANES = 128

# Scoped VMEM the TPU compiler grants one kernel by default (16 MiB on v5e).
# A leaf tile derived for ``block_l=None`` keeps the estimated footprint
# under it; past it the compile fails with RESOURCE_EXHAUSTED.
VMEM_BUDGET_BYTES = 16 * 2**20


def pick_block_l(l: int, footprint) -> int:
    """Largest 128-multiple dividing the padded leaf axis ``l`` whose
    ``footprint(block_l)`` (bytes) fits `VMEM_BUDGET_BYTES`.

    One lane tile (128) is the floor: at that width the compiler does not
    keep the kernel's (rows, N) comparator slabs resident, and every real
    problem compiles there (tests/test_tpu_compile.py holds HAR forest[4],
    N = L = 1,920, to it).
    """
    for block_l in range(l, 128, -128):
        if l % block_l == 0 and footprint(block_l) <= VMEM_BUDGET_BYTES:
            return block_l
    return 128


def vmem_bytes(n: int, block_l: int, c: int, block_b: int,
               block_p: int) -> int:
    """Estimated VMEM bytes of one `fitness_errors` grid cell.

    Double-buffered operand/output blocks (a 1-row block occupies an
    8-sublane tile), the vote scratch, and the f32 (block_p, block_b, .)
    intermediates: four over N (scaled codes, their floor, the compare and
    its cast) and two over ``block_l`` (path scores, leaf hits). Checked
    against the v5e compiler's own sizes, which it never undercounts.
    """
    blocks = (block_b * n + 2 * block_p * n + n * block_l + 8 * block_l
              + block_l * c + 8 * block_b + 2 * block_p * LANES)
    slabs = block_p * block_b * (4 * n + 2 * block_l + 2 * c)
    return 4 * (2 * blocks + slabs)


def _kernel(xsel_ref, scale_ref, thr_ref, path_ref, target_ref, cls1h_ref,
            y_ref, vcap_ref, out_ref, votes_ref):
    # xsel_ref:   (block_b, N)           f32  hoisted gathered master codes
    # scale_ref:  (block_p, N)           f32  2^-(8-p) per comparator
    # thr_ref:    (block_p, N)           f32  substituted integer threshold t'
    # path_ref:   (N, block_l)           f32  path matrix transpose
    # target_ref: (1, block_l)           f32  path_len - n_neg
    # cls1h_ref:  (block_l, C)           f32  leaf -> class one-hot
    # y_ref:      (1, block_b)           f32  labels (-1 on padded rows)
    # vcap_ref:   (block_p, LANES)       f32  lane-replicated vote caps
    #                                         (1.0 approx adder, +inf exact)
    # out_ref:    (block_p, LANES)       f32  lane-replicated correct counts
    # votes_ref:  (block_p, block_b, C)  f32  VMEM vote accumulator
    x = xsel_ref[...]
    x_p = jnp.floor(x[None, :, :] * scale_ref[...][:, None, :])
    d = (x_p > thr_ref[...][:, None, :]).astype(jnp.float32)
    score = jax.lax.dot_general(
        d, path_ref[...], dimension_numbers=(((2,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)
    sat = (score == target_ref[...][None, :, :]).astype(jnp.float32)
    votes = jax.lax.dot_general(
        sat, cls1h_ref[...], dimension_numbers=(((2,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)

    b_idx = pl.program_id(1)
    l_idx = pl.program_id(2)

    @pl.when(l_idx == 0)
    def _init_votes():
        votes_ref[...] = votes

    @pl.when(l_idx != 0)
    def _accum_votes():
        votes_ref[...] += votes

    @pl.when((b_idx == 0) & (l_idx == 0))
    def _init_out():
        out_ref[...] = jnp.zeros_like(out_ref)

    # last leaf block: votes are complete for this (pop, batch) tile —
    # reduce to correct counts on-chip instead of spilling (P, B, C) to HBM
    @pl.when(l_idx == pl.num_programs(2) - 1)
    def _reduce():
        v = votes_ref[...]                                 # (bp, bb, C)
        # saturating (approximate) vote adder, DESIGN.md §16: clip the
        # accumulated counts to the per-chromosome cap (+inf = exact no-op)
        v = jnp.minimum(v, vcap_ref[...][:, :1][:, :, None])
        n_cls = v.shape[-1]
        vmax = jnp.max(v, axis=-1, keepdims=True)
        # Mosaic builds integer iotas only; class ids < 2^24 are exact in f32
        cls = jax.lax.broadcasted_iota(jnp.int32, v.shape, 2).astype(jnp.float32)
        # first-max argmax as iota + masked min (jnp.argmax tie semantics)
        pred = jnp.min(jnp.where(v == vmax, cls, jnp.float32(n_cls)), axis=-1)
        correct = (pred == y_ref[...]).astype(jnp.float32)  # (bp, bb)
        out_ref[...] += jnp.sum(correct, axis=1)[:, None]


@functools.partial(
    jax.jit, static_argnames=("block_p", "block_b", "block_l", "interpret")
)
def fitness_errors(
    x_sel,    # (B, N)  f32 hoisted gathered codes (padded: B % block_b == 0,
              #             N % 128 == 0)
    scale,    # (P, N)  f32 per-chromosome shift scales (P % block_p == 0)
    thr,      # (P, N)  f32 per-chromosome substituted thresholds
    path_t,   # (N, L)  f32
    target,   # (1, L)  f32
    cls1h,    # (L, C)  f32
    y,        # (1, B)  f32 labels, -1 on padded batch rows
    vote_cap,  # (P, LANES) f32 lane-replicated vote caps (+inf = exact)
    *,
    block_p: int = 8,
    block_b: int = 256,
    block_l: int | None = None,
    interpret: bool = False,
):
    """Lane-replicated per-chromosome correct counts, shape (P, LANES).

    ``out[p, 0]`` is the number of test samples chromosome ``p`` classifies
    correctly (padded rows carry label -1 and never match); errors are
    ``n_valid - out[:, 0]``. ``block_p`` tiles the population axis,
    ``block_l`` the (concatenated) leaf axis — both must divide the padded
    extents. ``block_l=None`` derives the leaf tile from the padded shapes
    (`pick_block_l` over `vmem_bytes`): the whole axis where it fits.
    """
    n_pop = scale.shape[0]
    b, n = x_sel.shape
    l, c = cls1h.shape
    if block_l is None:
        block_l = pick_block_l(
            l, lambda bl: vmem_bytes(n, bl, c, block_b, block_p))
    if n_pop % block_p != 0:
        raise ValueError(f"block_p={block_p} must divide padded P={n_pop}")
    if b % block_b != 0:
        raise ValueError(f"block_b={block_b} must divide padded B={b}")
    if l % block_l != 0:
        raise ValueError(f"block_l={block_l} must divide padded L={l}")
    grid = (n_pop // block_p, b // block_b, l // block_l)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, n), lambda p, i, j: (i, 0)),
            pl.BlockSpec((block_p, n), lambda p, i, j: (p, 0)),
            pl.BlockSpec((block_p, n), lambda p, i, j: (p, 0)),
            pl.BlockSpec((n, block_l), lambda p, i, j: (0, j)),
            pl.BlockSpec((1, block_l), lambda p, i, j: (0, j)),
            pl.BlockSpec((block_l, c), lambda p, i, j: (j, 0)),
            pl.BlockSpec((1, block_b), lambda p, i, j: (0, i)),
            pl.BlockSpec((block_p, LANES), lambda p, i, j: (p, 0)),
        ],
        out_specs=pl.BlockSpec((block_p, LANES), lambda p, i, j: (p, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pop, LANES), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_p, block_b, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(x_sel, scale, thr, path_t, target, cls1h, y, vote_cap)

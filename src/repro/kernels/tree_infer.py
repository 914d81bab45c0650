"""Pallas TPU kernel: fused approximate-DT/forest inference (the paper's hot loop).

The GA evaluates `population x test_set` predictions every generation. This
kernel computes one (chromosome, batch-block) cell of that product with the
*parallel bespoke circuit* dataflow (DESIGN.md §2), fully gather-free so every
step lands on the MXU / VPU:

    x_sel   = X8 @ SEL            feature gather as one-hot matmul  (MXU)
    x_p     = floor(x_sel * 2^-(8-p))   per-comparator precision    (VPU)
    d       = x_p > t'                   comparator array           (VPU)
    score   = d @ PATH^T                 path matmul                (MXU)
    sat     = (score == target)          leaf decode                (VPU)
    votes   = sat @ CLS1H                vote matmul                (MXU)

For a single tree exactly one leaf satisfies its path, so `votes` is the
one-hot of the predicted class. For a *forest* the same program evaluates all
trees at once (DESIGN.md §7): the comparator axis is the concatenation of all
trees' comparators, PATH is block-diagonal (leaf rows only see their own
tree's comparators), and one leaf per tree fires — `votes` then accumulates
one vote per tree per class, i.e. the vote matmul IS the majority-vote adder
tree of the bespoke RF circuit. argmax over classes = voted prediction.

Block layout (VMEM): the tree tensors (SEL: F x N, PATH: N x L, CLS1H: L x C)
stay resident per grid cell; the batch is tiled by `block_b` rows and the leaf
axis may additionally be tiled by `block_l` (forests concatenate many trees'
leaves, so L can outgrow a single VMEM-resident block). Grid =
(population, batch_blocks, leaf_blocks): each chromosome's per-comparator
(shift_scale, threshold) vector is a [1, N] VMEM tile indexed by the
population coordinate; the leaf axis is the innermost (sequential) grid
dimension so partial vote matmuls accumulate into the same revisited output
block. The (P, N) population operands travel as (P, 1, N) with the leading
axis squeezed out of the block: Mosaic tiles the last two block dimensions
by (8, 128) unless they span the whole array, so a (1, N) row block of a
(P, N) array compiles only for P = 1.

All integer quantities are exact in f32 (values < 2^24) and vote accumulation
adds small exact integers, so MXU execution is bit-exact vs the integer
reference in `repro.kernels.ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fitness import pick_block_l


def vmem_bytes(f: int, n: int, block_l: int, c: int, block_b: int) -> int:
    """Estimated VMEM bytes of one `tree_infer_scores` grid cell:
    double-buffered blocks (a 1-row block occupies an 8-sublane tile) and
    the f32 intermediates, three (block_b, N) for the gather, scaled codes
    and compare, two (block_b, block_l) for path scores and leaf hits."""
    blocks = (block_b * f + f * n + 2 * 8 * n + n * block_l + 8 * block_l
              + block_l * c + block_b * c)
    slabs = block_b * (3 * n + 2 * block_l + c)
    return 4 * (2 * blocks + slabs)


def _kernel(x_ref, sel_ref, scale_ref, thr_ref, path_ref, target_ref,
            cls1h_ref, out_ref):
    # x_ref:      (block_b, F)    f32   master 8-bit codes
    # sel_ref:    (F, N)          f32   one-hot feature selector
    # scale_ref:  (1, N)          f32   2^-(8-p) per comparator (this chromosome)
    # thr_ref:    (1, N)          f32   substituted integer threshold t'
    # path_ref:   (N, block_l)    f32   path matrix transpose, entries {-1,0,1}
    # target_ref: (1, block_l)    f32   path_len - n_neg
    # cls1h_ref:  (block_l, C)    f32   leaf -> class one-hot
    # out_ref:    (1, block_b, C) f32   per-class vote counts (accumulated
    #                                   over the leaf-block grid dimension)
    x = x_ref[...]
    x_sel = jax.lax.dot(x, sel_ref[...], precision=jax.lax.Precision.HIGHEST)
    x_p = jnp.floor(x_sel * scale_ref[...])
    d = (x_p > thr_ref[...]).astype(jnp.float32)
    score = jax.lax.dot(d, path_ref[...], precision=jax.lax.Precision.HIGHEST)
    sat = (score == target_ref[...]).astype(jnp.float32)
    votes = jax.lax.dot(sat, cls1h_ref[...],
                        precision=jax.lax.Precision.HIGHEST)

    l_idx = pl.program_id(2)

    @pl.when(l_idx == 0)
    def _init():
        out_ref[0, :, :] = votes

    @pl.when(l_idx != 0)
    def _accum():
        out_ref[0, :, :] += votes


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_l", "interpret")
)
def tree_infer_scores(
    x8f,      # (B, F)  f32 master codes (padded: B % block_b == 0, F % 128 == 0)
    sel,      # (F, N)  f32
    scale,    # (P, N)  f32 per-chromosome shift scales
    thr,      # (P, N)  f32 per-chromosome substituted thresholds
    path_t,   # (N, L)  f32
    target,   # (1, L)  f32
    cls1h,    # (L, C)  f32
    *,
    block_b: int = 256,
    block_l: int | None = None,
    interpret: bool = False,
):
    """Returns per-class vote counts (P, B, C); argmax over C = prediction.

    ``block_l`` tiles the leaf axis (must divide L); ``None`` derives it
    from the padded shapes under the VMEM budget (`fitness.pick_block_l`),
    keeping the whole leaf axis resident where it fits.
    """
    n_pop = scale.shape[0]
    b, f = x8f.shape
    n = sel.shape[1]
    l, c = cls1h.shape
    if block_l is None:
        block_l = pick_block_l(
            l, lambda bl: vmem_bytes(f, n, bl, c, block_b))
    if l % block_l != 0:
        raise ValueError(f"block_l={block_l} must divide padded L={l}")
    grid = (n_pop, b // block_b, l // block_l)
    # one (1, N) row per chromosome: see the module docstring
    scale = scale.reshape(n_pop, 1, n)
    thr = thr.reshape(n_pop, 1, n)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, f), lambda p, i, j: (i, 0)),
            pl.BlockSpec((f, n), lambda p, i, j: (0, 0)),
            pl.BlockSpec((None, 1, n), lambda p, i, j: (p, 0, 0)),
            pl.BlockSpec((None, 1, n), lambda p, i, j: (p, 0, 0)),
            pl.BlockSpec((n, block_l), lambda p, i, j: (0, j)),
            pl.BlockSpec((1, block_l), lambda p, i, j: (0, j)),
            pl.BlockSpec((block_l, c), lambda p, i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_b, c), lambda p, i, j: (p, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pop, b, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x8f, sel, scale, thr, path_t, target, cls1h)

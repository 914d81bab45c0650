"""Threshold precision-conversion module (paper Fig. 3b; DESIGN.md §3).

Semantics (all integer, derived from the [0,1]-normalized reals):

  master code     x8 = floor(x * 2^8)            in [0, 255]
  input @ p bits  x_p = x8 >> (8 - p)            (truncation)
  thr float  T in (0,1)
  thr fixed  t_p = floor(T * 2^p)                in [0, 2^p - 1]
  substitution    t'_p = clip(t_p + m, 0, 2^p-1) with margin m in [-5, 5]
  comparator      decision = (x_p > t'_p)        -> go right

At p = 8 and m = 0 this reproduces the exact (non-approximate) tree bit-for-
bit, because training thresholds are stored as (t8 + 0.5)/256 (core.train).

The fixed-point value used for accuracy evaluation and the integer used to
index the area LUT are the same code scaled by 2^-p — exactly the paper's
"flexible threshold conversion" between the two representations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MASTER_BITS = 8
MIN_BITS = 2
MAX_BITS = 8
MARGIN = 5  # paper §IV: threshold substitution margin m in [-5, +5]
# Cross-layer co-search (DESIGN.md §16): per-comparator LSB truncation depth
# k in [0, MAX_TRUNC]. A k-truncated p-bit comparator ignores its k lowest
# threshold/input bit stages, which is exactly an exact comparator of width
# p - k compared against t' >> k.
MAX_TRUNC = 2
VOTE_ADDER_MODES = ("exact", "approx")


def shift_scale(shift):
    """Exact f32 ``2^-shift`` for integer ``shift`` in [0, 126].

    Built from the exponent bits: the kernels' ``floor(x8 * scale)`` equals
    ``x8 >> shift`` only for the exact power of two, and ``jnp.exp2``
    lowers to ``exp(x * ln 2)``, exact at these points only as far as the
    backend's ``exp`` happens to be.
    """
    e = 127 - jnp.asarray(shift, jnp.int32)
    return jax.lax.bitcast_convert_type(jnp.left_shift(e, 23), jnp.float32)


def threshold_to_int(threshold, bits):
    """float T in (0,1) -> fixed-point integer code at ``bits`` precision."""
    b = jnp.asarray(bits, jnp.int32)
    t = jnp.floor(threshold * jnp.exp2(b.astype(jnp.float32))).astype(jnp.int32)
    return jnp.clip(t, 0, jnp.left_shift(1, b) - 1)


def substitute(t_int, margin, bits):
    """Area-driven substitution: move the integer threshold by ``margin``."""
    hi = jnp.left_shift(1, jnp.asarray(bits, jnp.int32)) - 1
    return jnp.clip(t_int + margin, 0, hi)


def inputs_at_precision(x8, bits):
    """Right-shift the master 8-bit code down to per-node precision.

    x8: (..., N) int32 master codes gathered per comparator.
    bits: (N,) int32 per-comparator precision.
    """
    shift = (MASTER_BITS - bits).astype(jnp.int32)
    return jnp.right_shift(x8.astype(jnp.int32), shift)


def decode_genes(genes):
    """Real-coded genes in [0,1]^(2N) -> (bits[N], margin[N]) int32.

    Gene layout follows paper Fig. 3a: per comparator, gene 2k is the
    precision, gene 2k+1 the substitution margin.
    """
    g = jnp.asarray(genes)
    gp, gm = g[..., 0::2], g[..., 1::2]
    span_p = MAX_BITS - MIN_BITS + 1
    bits = MIN_BITS + jnp.clip(jnp.floor(gp * span_p), 0, span_p - 1)
    margin = -MARGIN + jnp.clip(jnp.floor(gm * (2 * MARGIN + 1)), 0, 2 * MARGIN)
    return bits.astype(jnp.int32), margin.astype(jnp.int32)


def exact_genes(n_comparators: int) -> np.ndarray:
    """Chromosome encoding the exact 8-bit, zero-margin design.

    Historical 2-genes-per-comparator layout (paper Fig. 3a). The tree
    search space now also carries approximation genes — use
    `exact_tree_genes` / `decode_tree_genes` for the engine's layout
    (DESIGN.md §16); this pair remains the precision/margin primitive the
    MLP family mirrors at its own ranges.
    """
    g = np.zeros(2 * n_comparators, dtype=np.float32)
    g[0::2] = 0.999  # precision -> 8 bits
    g[1::2] = 0.5    # margin -> 0  (floor(0.5 * 11) = 5 -> m = 0)
    return g


def decode_tree_genes(genes):
    """Cross-layer tree genes [0,1]^(3N+1) -> (bits, margin, trunc, vote).

    Gene layout (DESIGN.md §16): per comparator k, gene 3k is the precision,
    gene 3k+1 the substitution margin (both decoded exactly as
    `decode_genes`), and gene 3k+2 the LSB-truncation depth in
    [0, MAX_TRUNC]. The final gene toggles the forest's vote adder:
    floor(g*2) = 0 selects the exact popcount adder, 1 the approximate
    saturating OR-tree. Returns int32 arrays (bits[N], margin[N], trunc[N])
    and the int32 vote flag (shape = leading batch dims).
    """
    g = jnp.asarray(genes)
    comp = g[..., :-1]
    gp, gm, gt = comp[..., 0::3], comp[..., 1::3], comp[..., 2::3]
    span_p = MAX_BITS - MIN_BITS + 1
    bits = MIN_BITS + jnp.clip(jnp.floor(gp * span_p), 0, span_p - 1)
    margin = -MARGIN + jnp.clip(jnp.floor(gm * (2 * MARGIN + 1)), 0, 2 * MARGIN)
    span_t = MAX_TRUNC + 1
    trunc = jnp.clip(jnp.floor(gt * span_t), 0, span_t - 1)
    vote = jnp.clip(jnp.floor(g[..., -1] * 2), 0, 1)
    return (bits.astype(jnp.int32), margin.astype(jnp.int32),
            trunc.astype(jnp.int32), vote.astype(jnp.int32))


def exact_tree_genes(n_comparators: int) -> np.ndarray:
    """Chromosome for the exact design in the cross-layer layout (§16):
    8 bits, zero margin, zero truncation, exact vote adder."""
    g = np.zeros(3 * n_comparators + 1, dtype=np.float32)
    g[0:-1:3] = 0.999  # precision -> 8 bits
    g[1:-1:3] = 0.5    # margin -> 0
    # trunc genes (2::3) and the vote gene (last) stay 0.0 -> exact cells
    return g

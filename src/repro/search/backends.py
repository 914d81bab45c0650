"""Pluggable fitness backends over a `SearchProblem` (DESIGN.md §7, §12).

Every backend maps a population of real-coded genes (P, n_genes) — for
trees the cross-layer (P, 3N+1) layout of DESIGN.md §16 — to objectives
(P, 2) = (accuracy loss vs exact design, normalized area), bit-compatible
with each other:

  reference — pure-jnp vmap of the block-diagonal super-tree dataflow; the
              portable oracle (and what `core.approx.make_fitness_fn`
              historically computed for K=1). Rides the hoisted fitness
              pipeline (§12): the chromosome-invariant feature gather is
              precomputed on the problem (`SearchProblem.x_sel`) and ONE
              gene decode feeds both objectives.
  kernel    — the fused Pallas *fitness* kernel (`kernels.fitness`): the
              whole population x test-set x forest evaluation is ONE launch
              (grid = pop-blocks x batch-blocks x leaf-blocks, `block_p`
              chromosomes per cell), votes -> argmax -> label-compare happen
              inside the kernel, and only the O(P) per-chromosome error
              counts reach HBM — the (P, B, C) vote tensor the historical
              `tree_infer_scores` path materialized stays on-chip. That
              scores path remains the bit-exact materializing oracle
              (`kernels.ops.tree_infer_predict`, asserted in tests and used
              by the §10 RTL verification triangle).
  islands   — not a fitness function but a *driver* strategy (per-device
              NSGA-II islands with ring migration, `core.dist`); it reuses
              the reference fitness per island, is selected through
              `repro.search.engine.run_search`, and shares the engine's
              chunked-scan checkpoint/resume machinery (DESIGN.md §9).

`reference` and `kernel` agree bit-exactly: every integer quantity is
exact in f32 (< 2^24), the kernel's on-chip reductions add small exact
integers (see `repro.kernels.fitness`), the area term sums integer LUT
quanta, exact in any order, and both end in `problem.objective_pair` on
the same exact counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.search.problem import SearchProblem, objective_pair, objectives

BACKENDS = ("reference", "kernel", "islands")


def make_reference_fitness(problem: SearchProblem):
    """Population fitness: (P, n_genes) genes -> (P, 2) objectives, jitted."""

    @jax.jit
    def fitness(pop):
        return jax.vmap(functools.partial(objectives, problem))(pop)

    return fitness


def make_kernel_fitness(problem: SearchProblem, *, block_p: int = 8,
                        block_b: int = 256, block_l: int | None = None,
                        interpret: bool | None = None):
    """Kernel-backed fitness: accuracy via ONE fused Pallas launch for the
    entire (population x test-set x forest) product, area via the LUT gather.
    Same objectives as `make_reference_fitness` — asserted equal in tests.

    `block_p` tiles the population axis (DESIGN.md §12): each grid cell
    evaluates a (block_p, N) slab of chromosomes against a (block_b, N)
    batch tile, amortizing the static operands over the slab and keeping
    the VPU sublanes dense.
    """
    from repro.kernels import ops as kops  # local import: kernels are optional

    # problem.path is already the block-diagonal super-tree layout;
    # problem.x_sel is the feature gather, hoisted once at problem build —
    # the kernel never re-runs it per grid cell (§12).
    fit_operands = kops.prepare_fitness_operands(
        problem.x_sel, problem.y, problem.path, problem.path_len,
        problem.n_neg, problem.leaf_class, problem.n_classes)
    threshold = problem.threshold
    n_samples = jnp.float32(problem.y.shape[0])

    @jax.jit
    def fitness(pop):
        # ONE decode feeds the kernel operands AND the area LUT index
        # (historically this decoded twice per eval). Truncation is already
        # folded into the effective (scale, t_sub, bits) and the vote cap
        # rides into the kernel's on-chip argmax (DESIGN.md §16).
        scale, t_sub, bits, vote_cap = kops.decode_population_full(
            threshold, pop)
        errors = kops.fitness_errors(
            fit_operands, scale, t_sub.astype(jnp.float32), vote_cap,
            block_p=block_p, block_b=block_b, block_l=block_l,
            interpret=interpret)
        units = problem.area_lut_units[problem.lut_offsets[bits] + t_sub]
        return jnp.stack(objective_pair(problem, n_samples - errors,
                                        units.sum(axis=1), vote_cap), axis=1)

    return fitness


def make_fitness(problem, backend: str = "reference", **kw):
    """Factory: backend name -> population fitness function.

    Family-agnostic: `SearchProblem`s take the tree routes above; any other
    registered family's problem dispatches to that family's own
    `make_fitness` (DESIGN.md §15) so `engine.run_search` stays generic.
    """
    if backend not in ("reference", "kernel"):
        raise ValueError(
            f"unknown fitness backend {backend!r}; islands is driver-level "
            f"(use repro.search.engine.run_search), options: {BACKENDS}")
    if isinstance(problem, SearchProblem):
        if backend == "reference":
            return make_reference_fitness(problem)
        return make_kernel_fitness(problem, **kw)
    from repro.families import family_of
    return family_of(problem).make_fitness(problem, backend, **kw)

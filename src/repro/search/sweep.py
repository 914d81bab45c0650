"""Batched full-suite campaign engine (DESIGN.md §11).

The paper's evaluation is one uniform campaign over ten UCI datasets
(Tables I/II, Figs. 4-5), but `run_search` drives one `SearchProblem` at a
time — ten sequential GA runs, ten times the dispatch overhead, and a CI
that only ever exercised two of the ten scenarios. This module runs the
whole campaign as a handful of vmapped programs:

  1. **Pad** every problem's operands up to bucket-boundary shapes
     (`pad_problem`): comparator/leaf/class/feature/sample axes are rounded
     up to powers of two and filled with *inert* genes — padded comparators
     carry zero path entries, padded leaves an unreachable satisfaction
     target, padded samples the impossible label -1 — so the padded
     objectives reproduce the unpadded semantics (predictions bit-exact,
     objectives equal to float rounding; the inertness itself is exact:
     changing pad genes never changes an objective bit).
  2. **Bucket** problems sharing a padded shape (`plan_buckets`), greedily
     merging the cheapest pairs until at most `max_buckets` remain, so the
     whole 10-dataset suite compiles a handful of programs instead of ten.
  3. **Stack & vmap**: each bucket's operands stack on a leading problem
     axis and `nsga2.make_batched_init` / `make_batched_chunk` (§9's
     chunked scan, vmapped) advance every member with ONE dispatch per
     stage — `SweepResult.n_dispatches` is 2 per bucket vs 2 per dataset
     for the serial loop.

The per-problem serial loop (`vmapped=False`) is kept as the bit-exact
oracle: it runs the SAME padded problems through the un-vmapped
`nsga2.make_chunk`, and tests assert the final populations are
bit-identical array-for-array. Exactness under vmap holds because every
cross-lane reduction is integer-valued in f32: accuracy sums 0/1 matches,
and area sums the integer-quanta LUT (`area.build_area_unit_lut`), scaling
to mm^2 only at the end.

Per-dataset artifacts reuse the single-run pipeline unchanged: each
problem's final population is unpadded (real gene columns sliced back out)
and handed to `engine.write_pareto_artifact`, so `pareto.json`, `--emit-rtl`
and `--verify-rtl` behave exactly as in `run_search`. `write_sweep_report`
then scores every dataset against the paper's published Tables I/II
(`repro.datasets.paper_refs`).

CLI: ``python -m repro.search sweep --datasets all --report``.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import area as area_mod
from repro.core import nsga2, quant
from repro.search import engine as _engine
from repro.search.problem import SearchProblem, exact_matmul

GRANULE = 8            # minimum padded extent per axis
DEFAULT_MAX_BUCKETS = 6


# ---------------------------------------------------------------------------
# padding
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PaddedProblem:
    """One `SearchProblem` padded to bucket-boundary shapes with inert genes.

    The padding is masked so a padded evaluation means the same thing as the
    unpadded one:

      - padded **comparators** gather feature 0 and carry all-zero `path`
        columns, so their decisions never reach a leaf score; their rows are
        masked out of the area sum (`comp_valid`);
      - padded **leaves** carry `path_len = 1` over an all-zero path row —
        a satisfaction target of 1 that a zero score can never meet — so
        they never vote;
      - padded **classes** receive votes from no leaf, and first-max argmax
        cannot select them because every real sample collects >= 1 real
        vote (exactly one leaf per real tree fires);
      - padded **samples** carry label -1, which no prediction (>= 0) can
        match; accuracy divides by the real sample count `n_valid`;
      - padded **features** are zero columns no real comparator gathers.

    `area_lut_units` holds the integer-quanta LUT: the masked population
    area sum stays integer-valued in f32, hence bit-identical under any
    vmap tiling (DESIGN.md §11); `AREA_QUANTUM_MM2` scales once at the end.
    """

    feature: jnp.ndarray         # (Np,) int32
    threshold: jnp.ndarray       # (Np,) float32
    path: jnp.ndarray            # (Lp, Np) int8
    path_len: jnp.ndarray        # (Lp,) int32
    n_neg: jnp.ndarray           # (Lp,) int32
    leaf_onehot: jnp.ndarray     # (Lp, Cp) float32
    x8: jnp.ndarray              # (Bp, Fp) int32
    x_sel: jnp.ndarray           # (Bp, Np) int32 hoisted x8[:, feature]
                                 #   (chromosome-invariant, DESIGN.md §12)
    y: jnp.ndarray               # (Bp,) int32 (-1 on padded rows)
    comp_valid: jnp.ndarray      # (Np,) bool
    n_valid: jnp.ndarray         # () float32 — real test-sample count
    area_lut_units: jnp.ndarray  # integer-quanta area LUT (f32-exact)
    lut_offsets: jnp.ndarray     # (MAX_BITS+1,) int32
    overhead_mm2: jnp.ndarray    # () float32
    exact_area_mm2: jnp.ndarray  # () float32
    exact_accuracy: jnp.ndarray  # () float32
    # integer vote-adder quanta (DESIGN.md §16): exact popcount tree vs
    # saturating OR-tree. Integer-valued f32 like the LUT rows, so the
    # area sum stays vmap-order invariant. Both 0 for single trees.
    vote_units_exact: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.float32(0.0))
    vote_units_approx: jnp.ndarray = dataclasses.field(
        default_factory=lambda: jnp.float32(0.0))

    @property
    def n_genes(self) -> int:
        # cross-layer layout (DESIGN.md §16): 3 genes per comparator slot
        # plus the trailing forest-level vote-adder gene
        return 3 * int(self.feature.shape[0]) + 1


jax.tree_util.register_pytree_node(
    PaddedProblem,
    lambda p: (tuple(getattr(p, f.name)
                     for f in dataclasses.fields(PaddedProblem)), None),
    lambda _, children: PaddedProblem(*children),
)


def round_up_pow2(n: int, granule: int = GRANULE) -> int:
    """Next power of two >= max(n, granule): the bucket boundary per axis.

    Shared by the sweep's shape buckets and the serving runtime's request
    micro-batching (`runtime.classify`, DESIGN.md §14) — one rounding rule
    means a served batch and a sweep problem land on the same grid of
    compiled shapes.
    """
    n = max(int(n), int(granule))
    p = 1
    while p < n:
        p <<= 1
    return p


_round_up_pow2 = round_up_pow2


def problem_dims(problem: SearchProblem) -> tuple[int, int, int, int, int]:
    """Real (unpadded) operand extents: (N, L, C, F, B)."""
    return (problem.n_comparators, problem.n_leaves, problem.n_classes,
            problem.n_features, int(problem.x8.shape[0]))


def pad_problem(problem: SearchProblem,
                dims: tuple[int, int, int, int, int]) -> PaddedProblem:
    """Pad a `SearchProblem` to `dims` = (Np, Lp, Cp, Fp, Bp) (see class doc)."""
    np_, lp, cp, fp, bp = dims
    n, l, c, f, b = problem_dims(problem)
    if not (np_ >= n and lp >= l and cp >= c and fp >= f and bp >= b):
        raise ValueError(f"padded dims {dims} smaller than problem dims "
                         f"{(n, l, c, f, b)}")

    feature = np.zeros(np_, np.int32)
    feature[:n] = np.asarray(problem.feature)
    threshold = np.full(np_, 0.5, np.float32)
    threshold[:n] = np.asarray(problem.threshold)
    path = np.zeros((lp, np_), np.int8)
    path[:l, :n] = np.asarray(problem.path)
    path_len = np.ones(lp, np.int32)              # unreachable target for pads
    path_len[:l] = np.asarray(problem.path_len)
    n_neg = np.zeros(lp, np.int32)
    n_neg[:l] = np.asarray(problem.n_neg)
    leaf_onehot = np.zeros((lp, cp), np.float32)  # padded leaves never vote
    leaf_onehot[np.arange(l), np.asarray(problem.leaf_class)] = 1.0
    x8 = np.zeros((bp, fp), np.int32)
    x8[:b, :f] = np.asarray(problem.x8)
    y = np.full(bp, -1, np.int32)
    y[:b] = np.asarray(problem.y)
    comp_valid = np.zeros(np_, bool)
    comp_valid[:n] = True
    lut_units, offsets = area_mod.build_area_unit_lut()

    return PaddedProblem(
        feature=jnp.asarray(feature),
        threshold=jnp.asarray(threshold),
        path=jnp.asarray(path),
        path_len=jnp.asarray(path_len),
        n_neg=jnp.asarray(n_neg),
        leaf_onehot=jnp.asarray(leaf_onehot),
        x8=jnp.asarray(x8),
        x_sel=jnp.asarray(x8[:, feature]),
        y=jnp.asarray(y),
        comp_valid=jnp.asarray(comp_valid),
        n_valid=jnp.float32(b),
        area_lut_units=jnp.asarray(lut_units),
        lut_offsets=jnp.asarray(offsets),
        overhead_mm2=jnp.float32(problem.overhead_mm2),
        exact_area_mm2=jnp.float32(problem.exact_area_mm2),
        exact_accuracy=jnp.float32(problem.exact_accuracy),
        vote_units_exact=jnp.float32(area_mod.vote_adder_units(
            problem.n_trees, problem.n_classes, approx=False)),
        vote_units_approx=jnp.float32(area_mod.vote_adder_units(
            problem.n_trees, problem.n_classes, approx=True)),
    )


# ---------------------------------------------------------------------------
# padded evaluation (mirrors search.problem's reference primitives)
# ---------------------------------------------------------------------------

def _padded_decode(pp: PaddedProblem, genes):
    """ONE gene decode shared by predictions and the area term (§12).

    Returns the EFFECTIVE (bits, t_sub, vote_cap): comparator truncation is
    folded into the operands exactly as in `search.decode_chromosome`
    (DESIGN.md §16), so the padded dataflow prices and evaluates the same
    approximate cells the netlist lowers."""
    bits, margin, trunc, vote = quant.decode_tree_genes(genes)
    t_int = quant.threshold_to_int(pp.threshold, bits)
    t_sub = quant.substitute(t_int, margin, bits)
    vote_cap = jnp.where(vote > 0, jnp.float32(1.0), jnp.float32(jnp.inf))
    return bits - trunc, jnp.right_shift(t_sub, trunc), vote_cap


def _padded_predict_decoded(pp: PaddedProblem, bits, t_sub, vote_cap):
    """(Bp,) voted class from an already-decoded chromosome."""
    x_p = quant.inputs_at_precision(pp.x_sel, bits)
    d = (x_p > t_sub[None, :]).astype(jnp.float32)
    score = exact_matmul(d, pp.path.T.astype(jnp.float32))
    target = (pp.path_len - pp.n_neg).astype(jnp.float32)
    sat = (score == target[None, :]).astype(jnp.float32)
    votes = exact_matmul(sat, pp.leaf_onehot)
    # saturating (approximate) vote adder: +inf cap = exact f32 no-op
    votes = jnp.minimum(votes, vote_cap)
    return jnp.argmax(votes, axis=1)


def padded_predict(pp: PaddedProblem, genes):
    """(Bp,) voted class per sample — §2's dataflow on padded operands.

    On the real sample rows this is bit-exact vs `problem.predict_votes`
    with the real gene slice (tests pin it): every padded contribution is
    structurally zero, and all reductions are integer-valued in f32. The
    feature gather is hoisted onto the context (`pp.x_sel`, §12), so the
    per-chromosome work starts at the precision shift.
    """
    bits, t_sub, vote_cap = _padded_decode(pp, genes)
    return _padded_predict_decoded(pp, bits, t_sub, vote_cap)


def padded_objectives(pp: PaddedProblem, genes):
    """(accuracy loss, normalized area) for one padded chromosome (3*Np+1,).

    Matches `search.objectives` on the real slice up to float rounding
    (both sum integer area quanta — that is what buys vmap-order
    invariance); the *inertness* of pad genes is exact.
    One shared decode feeds both objectives (§12). The vote-adder term
    selects between the two integer unit counts (DESIGN.md §16), so the
    sum stays integer-valued in f32.
    """
    bits, t_sub, vote_cap = _padded_decode(pp, genes)
    pred = _padded_predict_decoded(pp, bits, t_sub, vote_cap)
    acc = jnp.sum((pred == pp.y).astype(jnp.float32)) / pp.n_valid

    idx = pp.lut_offsets[bits] + t_sub
    units = jnp.where(pp.comp_valid, pp.area_lut_units[idx], 0.0).sum()
    units = units + jnp.where(jnp.isfinite(vote_cap),
                              pp.vote_units_approx, pp.vote_units_exact)
    area = units * area_mod.AREA_QUANTUM_MM2 + pp.overhead_mm2
    return jnp.stack([pp.exact_accuracy - acc, area / pp.exact_area_mm2])


def population_objectives(pp: PaddedProblem, pop):
    """(P, 3*Np+1) genes -> (P, 2) objectives — the `fitness_from_ctx` handed
    to `nsga2.make_batched_init` / `make_batched_chunk`."""
    return jax.vmap(lambda g: padded_objectives(pp, g))(pop)


# ---------------------------------------------------------------------------
# bucketing
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Bucket:
    """A set of problems of ONE family sharing a padded operand shape.

    Tree buckets carry dims (N, L, C, F, B); MLP buckets (H, C, F, B).
    Problems of different families never share a bucket: their padded
    pytrees are different types and cannot stack (DESIGN.md §15).
    """
    names: tuple[str, ...]
    dims: tuple[int, ...]
    family: str = "tree"

    def dims_dict(self) -> dict:
        keys = (("n_comparators", "n_leaves", "n_classes", "n_features",
                 "n_samples") if self.family == "tree"
                else ("n_hidden", "n_classes", "n_features", "n_samples"))
        return dict(zip(keys, self.dims))


def _eval_cost(dims: tuple[int, ...]) -> float:
    """Dominant per-chromosome FLOP terms of §2's dataflow at padded shapes."""
    np_, lp, cp, fp, bp = dims
    return float(bp) * (np_ + np_ * lp + lp * cp)


def plan_buckets(problems: dict, *,
                 granule: int = GRANULE,
                 max_buckets: int = DEFAULT_MAX_BUCKETS) -> list[Bucket]:
    """Group problems by (family, power-of-two-rounded operand shape), then
    greedily merge the SAME-FAMILY pair costing the least extra padded
    compute until at most `max_buckets` buckets remain (a mixed-family
    campaign may exceed `max_buckets` when no intra-family merge is left —
    cross-family stacks cannot exist). Deterministic given the problem dict
    (iteration is name-sorted); merged dims are elementwise maxima, so they
    stay powers of two."""
    from repro.families import family_of, get_family

    if max_buckets < 1:
        raise ValueError(f"max_buckets must be >= 1, got {max_buckets}")
    groups: dict[tuple, list[str]] = {}
    for name in sorted(problems):
        fam = family_of(problems[name])
        dims = tuple(_round_up_pow2(d, granule)
                     for d in fam.problem_dims(problems[name]))
        groups.setdefault((fam.name, dims), []).append(name)
    buckets = [Bucket(names=tuple(v), dims=k[1], family=k[0])
               for k, v in sorted(groups.items())]

    while len(buckets) > max_buckets:
        best = None
        for i in range(len(buckets)):
            for j in range(i + 1, len(buckets)):
                bi, bj = buckets[i], buckets[j]
                if bi.family != bj.family:
                    continue
                cost = get_family(bi.family).eval_cost
                merged = tuple(max(a, b) for a, b in zip(bi.dims, bj.dims))
                extra = (cost(merged) * (len(bi.names) + len(bj.names))
                         - cost(bi.dims) * len(bi.names)
                         - cost(bj.dims) * len(bj.names))
                if best is None or extra < best[0]:
                    best = (extra, i, j, merged)
        if best is None:  # only cross-family pairs left: cannot merge further
            break
        _, i, j, merged = best
        buckets[i] = Bucket(names=tuple(sorted(buckets[i].names
                                               + buckets[j].names)),
                            dims=merged, family=buckets[i].family)
        del buckets[j]
    return sorted(buckets, key=lambda b: b.names)


def stack_padded(padded: list[PaddedProblem]) -> PaddedProblem:
    """Stack same-shape PaddedProblems on a leading problem axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *padded)


# ---------------------------------------------------------------------------
# the campaign driver
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SweepConfig:
    pop_size: int = 64
    n_generations: int = 40
    seed: int = 0
    vmapped: bool = True            # False = the serial bit-exact oracle
    granule: int = GRANULE
    max_buckets: int = DEFAULT_MAX_BUCKETS
    # mesh spec for `launch.mesh.make_search_mesh(axes=("bucket", "pop"))`
    # (DESIGN.md §13): "2x4" spreads each bucket's problem stack over 2
    # bucket shards and every population over 4 shards; "4"/"auto" put all
    # devices on the population axis. None = the single-device vmapped path.
    # Requires vmapped=True (the serial loop is the mesh-free oracle).
    mesh: str | None = None
    out_dir: str | None = None      # per-dataset artifacts under OUT/<name>/
    emit_rtl: bool = False
    verify_rtl: bool = False


@dataclasses.dataclass
class BucketRun:
    bucket: Bucket
    n_dispatches: int
    wall_s: float


@dataclasses.dataclass
class SweepResult:
    results: dict[str, "_engine.SearchResult"]
    bucket_runs: list[BucketRun]
    wall_s: float

    @property
    def n_dispatches(self) -> int:
        """Generation-loop dispatches summed over buckets — the acceptance
        number: 2 per bucket (init + one chunk) vs 2 per dataset serially."""
        return sum(r.n_dispatches for r in self.bucket_runs)

    def serial_baseline_dispatches(self) -> int:
        """What the same campaign costs as per-dataset `run_search` calls."""
        return 2 * len(self.results)


def _problem_keys(names_sorted: list[str], seed: int):
    """Per-problem PRNG keys: dataset i of the name-sorted campaign always
    folds in i, so the key never depends on the bucket plan. (The padded
    chromosome length IS part of the plan — GA draws are shape-dependent —
    so results are reproducible per (seed, plan), and vmapped-vs-serial
    equality holds at equal plan.)"""
    base = jax.random.PRNGKey(seed)
    return {name: jax.random.fold_in(base, i)
            for i, name in enumerate(names_sorted)}


def run_sweep(problems: dict[str, SearchProblem],
              cfg: SweepConfig | None = None, **overrides) -> SweepResult:
    """Run the NSGA-II campaign over every problem in `problems`.

    Returns per-dataset `SearchResult`s (pareto genes already unpadded back
    to each problem's real 3N+1 columns) plus bucket-level dispatch/wall
    accounting. With `out_dir`, each dataset writes the standard
    `pareto.json` artifact (and RTL, per `emit_rtl`/`verify_rtl`) under
    `out_dir/<dataset>/` through the single-run pipeline.
    """
    cfg = dataclasses.replace(cfg or SweepConfig(), **overrides)
    if not problems:
        raise ValueError("run_sweep needs at least one problem")
    if (cfg.emit_rtl or cfg.verify_rtl) and not cfg.out_dir:
        raise ValueError("emit_rtl/verify_rtl require out_dir")
    mesh = None
    if cfg.mesh:
        from repro.launch.mesh import make_search_mesh

        if not cfg.vmapped:
            raise ValueError("mesh sharding requires the vmapped path "
                             "(the serial loop is the mesh-free oracle)")
        mesh = make_search_mesh(cfg.mesh, axes=("bucket", "pop"))
        if mesh is not None and cfg.pop_size % mesh.shape["pop"]:
            raise ValueError(
                f"pop_size={cfg.pop_size} not divisible by the mesh's pop "
                f"axis ({mesh.shape['pop']})")

    from repro.families import get_family

    names_sorted = sorted(problems)
    keys = _problem_keys(names_sorted, cfg.seed)
    buckets = plan_buckets(problems, granule=cfg.granule,
                           max_buckets=cfg.max_buckets)
    nsga_cfg = nsga2.NSGA2Config(pop_size=cfg.pop_size,
                                 n_generations=cfg.n_generations)

    t0 = time.time()
    results: dict[str, _engine.SearchResult] = {}
    bucket_runs: list[BucketRun] = []
    for bucket in buckets:
        t_b = time.time()
        fam = get_family(bucket.family)
        fam_objectives = fam.population_objectives
        padded = [fam.pad_problem(problems[n], bucket.dims)
                  for n in bucket.names]
        bucket_keys = jnp.stack([keys[n] for n in bucket.names])
        n_genes = fam.padded_n_genes(bucket.dims)
        seed_genes = fam.padded_exact_genes(bucket.dims)

        if cfg.vmapped:
            n_real = len(padded)
            if mesh is not None:
                # the stacked problem axis shards over the bucket mesh axis:
                # pad the stack by repeating the last problem (extra lanes
                # are pure compute waste, dropped below) so it divides
                kb = mesh.shape["bucket"]
                pad_k = (-n_real) % kb
                padded = padded + [padded[-1]] * pad_k
                if pad_k:
                    bucket_keys = jnp.concatenate(
                        [bucket_keys, jnp.tile(bucket_keys[-1:], (pad_k, 1))])
            stacked = stack_padded(padded)
            init = jax.jit(nsga2.make_batched_init(
                fam_objectives, n_genes, nsga_cfg,
                seed_genes=seed_genes))
            states = init(bucket_keys, stacked)
            if mesh is None:
                chunk = jax.jit(nsga2.make_batched_chunk(
                    fam_objectives, nsga_cfg, cfg.n_generations))
                states = chunk(states, stacked)
            else:
                # lay the stack over the (bucket, pop) mesh and advance the
                # whole bucket with the sharded generation (DESIGN.md §13) —
                # bit-identical lanes, so unpadding below is unchanged
                from jax.sharding import NamedSharding, PartitionSpec as P
                from repro.core import dist
                from repro.sharding import search as _sspec

                states = jax.tree.map(jax.device_put, states,
                                      _sspec.batched_state_sharding(mesh))
                ctx_shard = NamedSharding(mesh, P("bucket"))
                stacked = jax.tree.map(
                    lambda a: jax.device_put(a, ctx_shard), stacked)
                chunk = dist.make_sharded_batched_chunk(
                    fam_objectives, mesh, nsga_cfg,
                    cfg.n_generations)
                states = chunk(states, stacked)
            states = jax.device_get(states)
            per_problem = [
                jax.tree_util.tree_map(lambda a, i=i: a[i], states)
                for i in range(n_real)]
            n_dispatches = 2
        else:
            # serial oracle: the SAME padded problems through the un-vmapped
            # chunked scan, one at a time. Like the vmapped path, both
            # stages are jitted AND take the padded problem as an argument
            # (closed-over operands would constant-fold and round
            # differently; eager evaluation likewise) — that symmetry is
            # what the bit-exactness contract rests on.
            init_fn = jax.jit(lambda key, pp: nsga2.init_state(
                key, lambda pop: fam_objectives(pp, pop),
                n_genes, nsga_cfg, seed_genes=seed_genes))
            chunk_fn = jax.jit(lambda state, pp: nsga2.make_chunk(
                lambda pop: fam_objectives(pp, pop),
                nsga_cfg, cfg.n_generations)(state))
            per_problem = []
            n_dispatches = 0
            for pp, key in zip(padded, bucket_keys):
                state = init_fn(key, pp)
                state = chunk_fn(state, pp)
                per_problem.append(jax.device_get(state))
                n_dispatches += 2
        wall_b = time.time() - t_b
        bucket_runs.append(BucketRun(bucket, n_dispatches, wall_b))

        for name, state in zip(bucket.names, per_problem):
            problem = problems[name]
            genes = fam.unpad_genes(problem, np.asarray(state.genes),
                                    bucket.dims)
            objs = np.asarray(state.objs)
            p_objs, p_genes = nsga2.pareto_front(objs, genes)
            result = _engine.SearchResult(
                state=state,
                pareto_objs=np.asarray(p_objs),
                pareto_genes=np.asarray(p_genes),
                backend="sweep" if cfg.vmapped else "sweep-serial",
                wall_s=wall_b,
                n_evaluations=cfg.pop_size * (1 + cfg.n_generations),
                n_dispatches=n_dispatches,  # shared across the bucket
            )
            results[name] = result
            if cfg.out_dir:
                fam.write_artifact(
                    problem, result, os.path.join(cfg.out_dir, name),
                    emit_rtl=cfg.emit_rtl, verify_rtl=cfg.verify_rtl,
                    dataset=name)

    return SweepResult(results=results, bucket_runs=bucket_runs,
                       wall_s=time.time() - t0)


# ---------------------------------------------------------------------------
# campaign construction + paper scoring
# ---------------------------------------------------------------------------

def build_problems(datasets, n_trees: int = 1,
                   verbose: bool = False, *, mlp_datasets=(),
                   n_hidden: int = 16) -> dict:
    """Train the exact design per dataset: bespoke trees (or forests,
    `n_trees > 1`) for `datasets`, printed MLPs for `mlp_datasets`
    (campaign keys suffixed `_mlp` so one dataset can run in both
    families). A mixed campaign flows through the same `run_sweep`; the
    bucket planner keeps the families apart (DESIGN.md §15)."""
    from repro.core.forest import train_forest
    from repro.core.train import train_tree
    from repro.core.tree import to_parallel
    from repro.datasets import load_dataset
    from repro.search.problem import build_forest_problem, build_tree_problem

    out = {}
    for name in datasets:
        t0 = time.time()
        ds = load_dataset(name)
        if n_trees <= 1:
            tree = train_tree(ds.x_train, ds.y_train, ds.n_classes)
            problem = build_tree_problem(to_parallel(tree), ds.x_test,
                                         ds.y_test)
        else:
            forest = train_forest(ds.x_train, ds.y_train, ds.n_classes,
                                  n_trees=n_trees)
            problem = build_forest_problem(forest, ds.x_test, ds.y_test)
        out[name] = problem
        if verbose:
            print(f"  {name}: comparators={problem.n_comparators} "
                  f"leaves={problem.n_leaves} "
                  f"exact_acc={problem.exact_accuracy:.3f} "
                  f"({time.time() - t0:.1f}s)")
    for name in mlp_datasets:
        from repro.families import get_family

        t0 = time.time()
        problem = get_family("mlp").build_problem(name, n_hidden=n_hidden)
        out[f"{name}_mlp"] = problem
        if verbose:
            print(f"  {name}_mlp: hidden={problem.n_hidden} "
                  f"shift={problem.shift} "
                  f"exact_acc={problem.exact_accuracy:.3f} "
                  f"({time.time() - t0:.1f}s)")
    return out


def _netlist_ratios(pareto_path: str) -> dict | None:
    """Estimated-vs-netlist area spread from a written pareto.json."""
    if not os.path.exists(pareto_path):
        return None
    with open(pareto_path) as f:
        artifact = json.load(f)
    ratios = _engine.netlist_area_ratios(artifact["pareto"])
    if not ratios:
        return None
    return {"min": round(min(ratios), 4),
            "mean": round(sum(ratios) / len(ratios), 4),
            "max": round(max(ratios), 4),
            "n_points": len(ratios)}


def _robustness_summary(report_path: str) -> dict | None:
    """Condensed robustness metrics from a written fault_report.json.

    Loose by design (like `_netlist_ratios`): a dataset without a fault
    campaign — or with an invalid report — simply contributes no
    robustness row rather than failing the sweep report.
    """
    from repro.search import robustness

    if not os.path.exists(report_path):
        return None
    try:
        report = robustness.load_fault_report(report_path)
    except (OSError, ValueError):
        return None
    if not report["points"]:
        return None
    pt = report["points"][0]    # --fault-report runs the best point
    return {
        "point": pt["point"],
        "norm_area": round(pt["norm_area"], 4),
        "n_sites": pt["n_sites"],
        "baseline_accuracy": round(pt["baseline_accuracy"], 4),
        "single_fault_mean_accuracy":
            round(pt["single_fault"]["mean_accuracy"], 4),
        "single_fault_worst_accuracy":
            round(pt["single_fault"]["worst_accuracy"], 4),
        "mc_expected_accuracy":
            round(pt["monte_carlo"]["expected_accuracy"], 4),
        "defect_rate": report["defect_rate"],
    }


def write_sweep_report(sweep: SweepResult,
                       problems: dict[str, SearchProblem],
                       out_dir: str, *, meta: dict | None = None,
                       max_loss: float = 0.01) -> tuple[str, str]:
    """Score the campaign against the paper and write the report artifacts.

    Emits `out_dir/sweep_report.json` (machine-readable: per-dataset
    accuracy deltas vs Table I, normalized area at the loss budget vs
    Table II, estimated-vs-netlist spreads from each dataset's pareto.json,
    bucket/dispatch accounting) and `out_dir/REPORT.md` (the same as one
    human-readable table). Returns (json_path, md_path).
    """
    from repro.datasets.paper_refs import (
        PAPER_MEAN_AREA_REDUCTION_1PCT,
        PAPER_TABLE1,
        PAPER_TABLE2_NORM,
    )

    os.makedirs(out_dir, exist_ok=True)
    rows: dict[str, dict] = {}
    reductions = []
    acc_deltas = []
    for name in sorted(sweep.results):
        result = sweep.results[name]
        problem = problems[name]
        paper1 = PAPER_TABLE1.get(name)
        paper2 = PAPER_TABLE2_NORM.get(name)
        if hasattr(problem, "n_comparators"):   # tree row (schema unchanged)
            row: dict = {
                "exact_accuracy": round(problem.exact_accuracy, 4),
                "n_comparators": problem.n_comparators,
                "n_trees": problem.n_trees,
                "exact_area_mm2": round(problem.exact_area_mm2, 2),
                "n_pareto_points": int(len(result.pareto_objs)),
                "wall_s": round(result.wall_s, 2),
            }
        else:                                   # printed-MLP row
            row = {
                "family": "mlp",
                "exact_accuracy": round(problem.exact_accuracy, 4),
                "n_hidden": problem.n_hidden,
                "exact_area_mm2": round(problem.exact_area_mm2, 2),
                "n_pareto_points": int(len(result.pareto_objs)),
                "wall_s": round(result.wall_s, 2),
            }
            paper1 = paper2 = None  # paper tables are tree-family numbers
        if paper1:
            row["paper_accuracy"] = paper1[0]
            row["accuracy_delta"] = round(problem.exact_accuracy - paper1[0], 4)
            row["paper_n_comparators"] = paper1[1]
            row["paper_area_mm2"] = paper1[3]
            acc_deltas.append(abs(row["accuracy_delta"]))
        best = result.best_under_loss(max_loss)
        if best is not None:
            objs, _ = best
            norm_area = float(objs[1])
            area_mm2 = norm_area * problem.exact_area_mm2
            row["at_budget"] = {
                "max_loss": max_loss,
                "acc_loss": round(float(objs[0]), 4),
                "norm_area": round(norm_area, 4),
                "area_mm2": round(area_mm2, 2),
                "power_mw": round(area_mod.power_mw(area_mm2), 3),
            }
            if norm_area > 0:
                reductions.append(1.0 / norm_area)
            if paper2:
                row["at_budget"]["paper_norm_area"] = paper2[0]
                row["at_budget"]["norm_area_delta"] = round(
                    norm_area - paper2[0], 4)
        else:
            row["at_budget"] = None
        ratios = _netlist_ratios(os.path.join(out_dir, name, "pareto.json"))
        if ratios:
            row["netlist_vs_estimated_area"] = ratios
        robust = _robustness_summary(
            os.path.join(out_dir, name, "fault_report.json"))
        if robust:
            row["robustness"] = robust
        rows[name] = row

    payload = {
        "meta": meta or {},
        "buckets": [{
            "datasets": list(r.bucket.names),
            "family": r.bucket.family,
            "dims": r.bucket.dims_dict(),
            "n_dispatches": r.n_dispatches,
            "wall_s": round(r.wall_s, 2),
        } for r in sweep.bucket_runs],
        "n_dispatches": sweep.n_dispatches,
        "serial_baseline_dispatches": sweep.serial_baseline_dispatches(),
        "wall_s": round(sweep.wall_s, 2),
        "datasets": rows,
        "summary": {
            "n_datasets": len(rows),
            "n_at_budget": len(reductions),
            "mean_area_reduction_at_budget":
                round(float(np.mean(reductions)), 3) if reductions else None,
            "paper_mean_area_reduction_1pct": PAPER_MEAN_AREA_REDUCTION_1PCT,
            "mean_abs_accuracy_delta_vs_paper":
                round(float(np.mean(acc_deltas)), 4) if acc_deltas else None,
        },
    }
    json_path = os.path.join(out_dir, "sweep_report.json")
    tmp = json_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, json_path)

    md_path = os.path.join(out_dir, "REPORT.md")
    with open(md_path + ".tmp", "w") as f:
        f.write(_report_markdown(payload, max_loss))
    os.replace(md_path + ".tmp", md_path)
    return json_path, md_path


def _report_markdown(payload: dict, max_loss: float) -> str:
    lines = ["# Full-suite sweep report", ""]
    meta = payload.get("meta") or {}
    if meta:
        opts = " ".join(f"{k}={v}" for k, v in sorted(meta.items()))
        lines += [f"Campaign: {opts}", ""]
    lines += [
        f"Dispatches: **{payload['n_dispatches']}** over "
        f"{len(payload['buckets'])} buckets (serial per-dataset baseline: "
        f"{payload['serial_baseline_dispatches']}); "
        f"wall {payload['wall_s']}s.",
        "",
        "| bucket | family | datasets | padded dims | dispatches |",
        "|---|---|---|---|---|",
    ]
    for i, b in enumerate(payload["buckets"]):
        d = b["dims"]
        dims = "(" + ", ".join(str(v) for v in d.values()) + ")"
        lines.append(f"| {i} | {b.get('family', 'tree')} "
                     f"| {', '.join(b['datasets'])} | {dims} "
                     f"| {b['n_dispatches']} |")
    lines += [
        "",
        f"Per dataset, scored against paper Tables I/II "
        f"(budget: {max_loss:.0%} accuracy loss):",
        "",
        "| dataset | acc (paper) | Δacc | comparators (paper) "
        "| norm area @budget (paper) | netlist/LUT mean |",
        "|---|---|---|---|---|---|",
    ]
    for name, row in payload["datasets"].items():
        pacc = row.get("paper_accuracy")
        acc = (f"{row['exact_accuracy']:.3f} ({pacc:.3f})"
               if pacc is not None else f"{row['exact_accuracy']:.3f} (—)")
        dacc = (f"{row['accuracy_delta']:+.3f}"
                if "accuracy_delta" in row else "—")
        if "n_comparators" in row:
            ncmp = (f"{row['n_comparators']} ({row['paper_n_comparators']})"
                    if "paper_n_comparators" in row
                    else f"{row['n_comparators']} (—)")
        else:
            ncmp = f"mlp h={row['n_hidden']}"
        at = row.get("at_budget")
        if at:
            pna = at.get("paper_norm_area")
            na = (f"{at['norm_area']:.3f} ({pna:.3f})"
                  if pna is not None else f"{at['norm_area']:.3f} (—)")
        else:
            na = "none under budget"
        ratios = row.get("netlist_vs_estimated_area")
        ratio = f"{ratios['mean']:.2f}" if ratios else "—"
        lines.append(f"| {name} | {acc} | {dacc} | {ncmp} | {na} | {ratio} |")
    s = payload["summary"]
    lines += [
        "",
        f"Mean area reduction at budget: "
        f"**{s['mean_area_reduction_at_budget']}x** over "
        f"{s['n_at_budget']}/{s['n_datasets']} datasets "
        f"(paper: {s['paper_mean_area_reduction_1pct']}x at 1%). "
        f"Mean |Δaccuracy| vs Table I: "
        f"{s['mean_abs_accuracy_delta_vs_paper']}.",
        "",
    ]
    robust = {name: row["robustness"]
              for name, row in payload["datasets"].items()
              if row.get("robustness")}
    if robust:
        rate = next(iter(robust.values()))["defect_rate"]
        lines += [
            "## Robustness vs area (stuck-at campaign, DESIGN.md §17)",
            "",
            f"Best-under-budget point per dataset: exhaustive single "
            f"stuck-at over every fault site + Monte-Carlo expected "
            f"accuracy at a {rate:.0%} iid defect rate.",
            "",
            "| dataset | point | norm area | sites | baseline acc "
            "| 1-fault mean | 1-fault worst | MC expected |",
            "|---|---|---|---|---|---|---|---|",
        ]
        for name, r in robust.items():
            lines.append(
                f"| {name} | {r['point']} | {r['norm_area']:.3f} "
                f"| {r['n_sites']} | {r['baseline_accuracy']:.3f} "
                f"| {r['single_fault_mean_accuracy']:.3f} "
                f"| {r['single_fault_worst_accuracy']:.3f} "
                f"| {r['mc_expected_accuracy']:.3f} |")
        lines.append("")
    return "\n".join(lines)

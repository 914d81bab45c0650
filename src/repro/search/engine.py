"""`run_search`: the one NSGA-II driver behind tree, forest and island search.

Collapses the three hand-rolled GA loops (core.approx quickstart path,
core.forest fitness, core.dist islands) into a single entry point
(DESIGN.md §7):

    problem = search.build_tree_problem(ptree, x_test, y_test)
    result  = search.run_search(problem, SearchConfig(backend="kernel"))

Features over the old loops:
  - backend selection: `reference` (pure jnp), `kernel` (fused Pallas,
    one launch per generation for the whole population x test-set x forest
    product), `islands` (per-device NSGA-II + ring migration via core.dist);
  - device-resident generation loop (DESIGN.md §9): generations run as
    lax.scan chunks of `checkpoint_every` (or the whole run when
    checkpointing is off), so a checkpoint interval costs exactly one host
    dispatch and one device->host transfer — `SearchResult.n_dispatches`
    reports the count;
  - checkpointable state: `checkpoint_every` saves the full NSGA2State
    through `repro.runtime.checkpoint` (atomic, retained-K) and
    `resume=True` continues from the latest checkpoint — for the islands
    backend too, whose gathered state round-trips through the same path;
  - pareto-front artifacts: `out_dir` receives pareto.json (objectives,
    genes, decoded per-comparator designs) for downstream RTL emission.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import nsga2, quant
from repro.search import backends as _backends
from repro.search.problem import SearchProblem

# the `campaign=` id of the profiler spans of one `run_search` call
_CAMPAIGNS = itertools.count()


@dataclasses.dataclass
class SearchConfig:
    backend: str = "reference"      # reference | kernel | islands
    pop_size: int = 64
    n_generations: int = 40
    seed: int = 0
    seed_exact: bool = True         # inject the exact design into the init pop
    # mesh sharding (DESIGN.md §13): a `launch.mesh.make_search_mesh` spec
    # (None = single-device oracle; "auto"/"4" shard the population axis;
    # islands interpret it as the ring size). Orthogonal to `backend`: the
    # reference and kernel fitness paths both run per-shard unmodified, and
    # checkpoints stay mesh-agnostic ("single" family) so a run can resume
    # onto a different mesh — or none — bit-exactly.
    mesh: str | None = None
    # kernel backend
    block_p: int = 8                # population-axis tile (DESIGN.md §12)
    block_b: int = 256
    block_l: int | None = None
    interpret: bool | None = None   # None = auto (interpret off TPU)
    # islands backend (generations round UP to whole migration rounds;
    # checkpoints land on round boundaries)
    migrate_every: int = 5
    n_migrate: int = 4
    # artifacts / checkpointing
    dataset: str | None = None      # dataset label recorded in pareto.json so
                                    # `python -m repro.search serve` can find
                                    # the matching test split by itself
    out_dir: str | None = None
    checkpoint_every: int = 0       # generations between saves; 0 = off
    resume: bool = False
    # hardware loop (DESIGN.md §10) — both need out_dir
    emit_rtl: bool = False          # write per-pareto-point Verilog (OUT/rtl/)
    verify_rtl: bool = False        # netlist-simulate every pareto point and
                                    # assert bit-exactness vs predict_votes
                                    # and the kernel backend


@dataclasses.dataclass
class SearchResult:
    state: nsga2.NSGA2State
    pareto_objs: np.ndarray    # (K, 2) accuracy-loss / normalized-area
    pareto_genes: np.ndarray   # (K, 3N+1) — DESIGN.md §16 gene layout
    backend: str
    wall_s: float
    n_evaluations: int
    n_dispatches: int = 0      # generation-loop device dispatches this call

    def best_under_loss(self, max_loss: float = 0.01):
        """Smallest-area pareto point within an accuracy-loss budget."""
        ok = self.pareto_objs[:, 0] <= max_loss + 1e-9
        if not ok.any():
            return None
        idx = np.flatnonzero(ok)
        best = idx[np.argmin(self.pareto_objs[idx, 1])]
        return self.pareto_objs[best], self.pareto_genes[best]


def _ckpt_dir(cfg: SearchConfig) -> str | None:
    return os.path.join(cfg.out_dir, "ckpt") if cfg.out_dir else None


def _seed_genes(problem: SearchProblem, cfg: SearchConfig):
    return problem.exact_genes() if cfg.seed_exact else None


def _chunk_schedule(start: int, stop: int, every: int) -> list[int]:
    """Chunk lengths covering [start, stop) with boundaries at multiples of
    `every` (every=0 -> one chunk for the whole remaining run). A resume from
    an off-boundary final save realigns at the next multiple, so checkpoints
    always land on the same cadence regardless of interruptions."""
    if every < 0:
        raise ValueError(f"checkpoint_every must be >= 0, got {every}")
    if start >= stop:
        return []
    if not every:
        return [stop - start]
    out = []
    g = start
    while g < stop:
        nxt = min(stop, (g // every + 1) * every)
        out.append(nxt - g)
        g = nxt
    return out


def _drive_chunks(state, start: int, stop: int, every: int, make_chunk_fn,
                  save_fn=None):
    """The chunked-scan driver shared by the single and islands families.

    Runs positions [start, stop) as lax.scan chunks with boundaries at
    multiples of `every`, compiling one chunk program per distinct length
    (at most three: realignment after an off-boundary resume, the
    steady-state `every`-long chunk, and a shorter tail). `save_fn`
    is called at every boundary and — unless that position was just saved —
    once at the end, so a partial run always leaves its final state on disk
    without ever mislabeling a step. Returns (state, position, n_chunks)."""
    chunk_fns = {}
    cur = start
    last_saved = start if start else -1
    n_chunks = 0
    for length in _chunk_schedule(start, stop, every):
        fn = chunk_fns.get(length)
        if fn is None:
            fn = chunk_fns[length] = make_chunk_fn(length)
        state = fn(state)
        cur += length
        n_chunks += 1
        if save_fn and every and cur % every == 0:
            save_fn(cur, state)
            last_saved = cur
    if save_fn and last_saved != cur:
        save_fn(cur, state)
    return state, cur, n_chunks


def _validate_resume_meta(ckpt_dir: str, step: int, family: str,
                          cfg: SearchConfig) -> dict:
    """Refuse to restore a state whose layout can't match this run.

    Returns the manifest's meta dict ({} for pre-meta checkpoints, which
    fall through to checkpoint.restore's shape asserts)."""
    from repro.runtime import checkpoint

    meta = checkpoint.read_manifest(ckpt_dir, step).get("meta", {})
    if not meta:
        return meta
    saved = meta.get("family")
    if saved != family:
        raise ValueError(
            f"checkpoint at {ckpt_dir} step {step} was written by the "
            f"{saved!r} driver; cannot resume it with backend={cfg.backend!r} "
            f"({family!r} state layout)")
    if meta.get("pop_size", cfg.pop_size) != cfg.pop_size:
        raise ValueError(
            f"checkpoint at {ckpt_dir} step {step} was written with "
            f"pop_size={meta['pop_size']}; cannot resume with "
            f"pop_size={cfg.pop_size}")
    return meta


def _restore_template(problem: SearchProblem, cfg: SearchConfig):
    """NSGA2State skeleton for checkpoint.restore — shapes/dtypes only, no
    fitness evaluation (init_state would run a full population eval just to
    be overwritten by the restored arrays)."""
    p = cfg.pop_size
    return nsga2.NSGA2State(
        genes=jnp.zeros((p, problem.n_genes), jnp.float32),
        objs=jnp.zeros((p, 2), jnp.float32),
        rank=jnp.zeros((p,), jnp.int32),
        crowd=jnp.zeros((p,), jnp.float32),
        key=jax.random.PRNGKey(0),
        generation=jnp.int32(0),
    )


def _run_single(problem: SearchProblem, cfg: SearchConfig, fitness,
                mesh=None):
    """reference/kernel driver: chunked-scan generations + checkpoint/resume.

    Returns (state, n_evaluations, n_dispatches) for THIS call. Generations
    execute as `nsga2.make_chunk` programs of `checkpoint_every` length
    (falling back to the full run), so the host dispatches once per
    checkpoint interval — bit-exact vs the historical per-generation loop.

    With a mesh the SAME schedule runs through `dist.make_sharded_chunk`
    (population axis sharded, hierarchical domination, DESIGN.md §13) —
    bit-identical arrays, so the checkpoint family stays "single" and a run
    may freely resume onto a different mesh or none (elastic restore)."""
    from repro.runtime import checkpoint

    nsga_cfg = nsga2.NSGA2Config(pop_size=cfg.pop_size,
                                 n_generations=cfg.n_generations)
    if mesh is not None:
        from repro.core import dist
        n_shards = mesh.shape["pop"]
        if cfg.pop_size % n_shards:
            raise ValueError(
                f"pop_size={cfg.pop_size} not divisible by the mesh's "
                f"pop axis ({n_shards})")
    key = jax.random.PRNGKey(cfg.seed)
    state = None
    start_gen = 0
    n_evals = 0
    n_dispatches = 0
    ckpt_dir = _ckpt_dir(cfg)
    meta = {"family": "single", "backend": cfg.backend,
            "pop_size": cfg.pop_size}
    if cfg.resume and ckpt_dir:
        step = checkpoint.latest_step(ckpt_dir)
        if step is not None:
            _validate_resume_meta(ckpt_dir, step, "single", cfg)
            state, start_gen = checkpoint.restore(
                ckpt_dir, step, _restore_template(problem, cfg),
                shardings=(dist.sharded_state_sharding(mesh)
                           if mesh is not None else None))

    if state is None:
        if mesh is not None:
            state = dist.init_sharded(key, fitness, problem.n_genes, mesh,
                                      nsga_cfg,
                                      seed_genes=_seed_genes(problem, cfg))
        else:
            state = nsga2.init_state(key, fitness, problem.n_genes, nsga_cfg,
                                     seed_genes=_seed_genes(problem, cfg))
        n_evals += cfg.pop_size
        n_dispatches += 1

    if mesh is not None:
        make_chunk_fn = lambda n: dist.make_sharded_chunk(
            fitness, mesh, nsga_cfg, n)
    else:
        make_chunk_fn = lambda n: jax.jit(nsga2.make_chunk(
            fitness, nsga_cfg, n))
    # no out_dir -> nothing to save, so don't let checkpoint_every shrink
    # the chunks (the whole run stays one dispatch)
    saving = bool(ckpt_dir and cfg.checkpoint_every)
    state, cur_gen, n_chunks = _drive_chunks(
        state, start_gen, cfg.n_generations,
        cfg.checkpoint_every if saving else 0,
        make_chunk_fn,
        (lambda gen, s: checkpoint.save(ckpt_dir, gen, s, meta=meta))
        if saving else None)
    n_evals += cfg.pop_size * (cur_gen - start_gen)
    n_dispatches += n_chunks
    return state, n_evals, n_dispatches


def _islands_template(problem: SearchProblem, n_islands: int, local_pop: int):
    """Island NSGA2State skeleton (key axis = islands) for checkpoint.restore."""
    p = n_islands * local_pop
    return nsga2.NSGA2State(
        genes=jnp.zeros((p, problem.n_genes), jnp.float32),
        objs=jnp.zeros((p, 2), jnp.float32),
        rank=jnp.zeros((p,), jnp.int32),
        crowd=jnp.zeros((p,), jnp.float32),
        key=jnp.zeros((n_islands, 2), jnp.uint32),
        generation=jnp.int32(0),
    )


def _run_islands(problem: SearchProblem, cfg: SearchConfig):
    """Island driver: one NSGA-II island per device, ring migration.

    Generations are rounded UP to whole migration rounds (migrate_every
    each), so the islands backend may run slightly more generations than
    configured; `n_evaluations` reports what actually ran. Rounds execute as
    `dist.make_island_chunk` scans sized to the checkpoint cadence
    (DESIGN.md §9): checkpoints land on round boundaries, every
    ceil(checkpoint_every / migrate_every) rounds, labeled in generations;
    `resume=True` restores the gathered island state through
    `runtime.checkpoint` and re-shards it onto the current mesh."""
    from repro.core import dist
    from repro.launch.mesh import make_search_mesh
    from repro.runtime import checkpoint

    from repro.families import family_of

    fitness = family_of(problem).make_fitness(problem, "reference")
    # one mesh constructor for every driver (DESIGN.md §13); islands default
    # to a ring over all host devices when --mesh is unset
    mesh = make_search_mesh(cfg.mesh or "auto", axes=("data",))
    n_islands = mesh.shape["data"]
    local_pop = max(8, cfg.pop_size // max(n_islands, 1))
    island_cfg = dist.IslandConfig(
        local_pop=local_pop,
        migrate_every=cfg.migrate_every,
        n_migrate=min(cfg.n_migrate, local_pop // 2),
        nsga=nsga2.NSGA2Config(pop_size=local_pop,
                               n_generations=cfg.n_generations),
    )
    n_rounds = max(1, -(-cfg.n_generations // cfg.migrate_every))
    ckpt_rounds = (max(1, -(-cfg.checkpoint_every // cfg.migrate_every))
                   if cfg.checkpoint_every else 0)

    state = None
    start_round = 0
    n_evals = 0
    n_dispatches = 0
    ckpt_dir = _ckpt_dir(cfg)
    meta = {"family": "islands", "backend": cfg.backend,
            "pop_size": cfg.pop_size, "local_pop": local_pop,
            "n_islands": n_islands, "migrate_every": cfg.migrate_every}
    if cfg.resume and ckpt_dir:
        step = checkpoint.latest_step(ckpt_dir)
        if step is not None:
            saved_meta = _validate_resume_meta(ckpt_dir, step, "islands", cfg)
            if saved_meta.get("migrate_every", cfg.migrate_every) != cfg.migrate_every:
                raise ValueError(
                    f"islands checkpoint at step {step} was written with "
                    f"migrate_every={saved_meta['migrate_every']}; resuming "
                    f"with migrate_every={cfg.migrate_every} would shift the "
                    f"round grid")
            if saved_meta.get("n_islands", n_islands) != n_islands:
                raise ValueError(
                    f"islands checkpoint at step {step} was written on "
                    f"{saved_meta['n_islands']} islands; this host has "
                    f"{n_islands} devices (per-island populations would not "
                    f"line up)")
            state, gens_done = checkpoint.restore(
                ckpt_dir, step, _islands_template(problem, n_islands, local_pop),
                shardings=dist.island_state_sharding(mesh))
            start_round = gens_done // cfg.migrate_every

    if state is None:
        state = dist.init_islands(jax.random.PRNGKey(cfg.seed), fitness,
                                  problem.n_genes, mesh, island_cfg,
                                  seed_genes=_seed_genes(problem, cfg))
        n_evals += n_islands * local_pop
        n_dispatches += 1

    saving = bool(ckpt_dir and ckpt_rounds)
    state, cur_round, n_chunks = _drive_chunks(
        state, start_round, n_rounds, ckpt_rounds if saving else 0,
        lambda n: dist.make_island_chunk(fitness, mesh, island_cfg, n),
        (lambda rnd, s: checkpoint.save(
            ckpt_dir, rnd * cfg.migrate_every, s, meta=meta))
        if saving else None)
    n_evals += (n_islands * local_pop
                * (cur_round - start_round) * cfg.migrate_every)
    n_dispatches += n_chunks
    return state, n_evals, n_dispatches


def run_search(problem: SearchProblem, cfg: SearchConfig | None = None,
               **overrides) -> SearchResult:
    """One entry point for every search scenario.

    `overrides` are applied on top of `cfg` (or a default SearchConfig), so
    `run_search(problem, backend="kernel", pop_size=128)` works without
    building a config first.
    """
    cfg = dataclasses.replace(cfg or SearchConfig(), **overrides)
    if cfg.backend not in _backends.BACKENDS:
        raise ValueError(
            f"unknown backend {cfg.backend!r}; options: {_backends.BACKENDS}")
    if cfg.checkpoint_every < 0:
        raise ValueError(
            f"checkpoint_every must be >= 0, got {cfg.checkpoint_every}")
    if (cfg.emit_rtl or cfg.verify_rtl) and not cfg.out_dir:
        raise ValueError("emit_rtl/verify_rtl require out_dir")

    from repro.runtime import spans

    with spans.span("search.run", campaign=next(_CAMPAIGNS)):
        return _search(problem, cfg)


def _search(problem: SearchProblem, cfg: SearchConfig) -> SearchResult:
    t0 = time.perf_counter()
    if cfg.backend == "islands":
        state, n_evals, n_dispatches = _run_islands(problem, cfg)
    else:
        from repro.launch.mesh import make_search_mesh

        kw = {}
        if cfg.backend == "kernel":
            kw = dict(block_p=cfg.block_p, block_b=cfg.block_b,
                      block_l=cfg.block_l, interpret=cfg.interpret)
        fitness = _backends.make_fitness(problem, cfg.backend, **kw)
        mesh = make_search_mesh(cfg.mesh, axes=("pop",))
        state, n_evals, n_dispatches = _run_single(problem, cfg, fitness,
                                                   mesh=mesh)
    wall_s = time.perf_counter() - t0

    objs, genes = nsga2.pareto_front(jax.device_get(state.objs),
                                     jax.device_get(state.genes))
    result = SearchResult(
        state=state,
        pareto_objs=np.asarray(objs),
        pareto_genes=np.asarray(genes),
        backend=cfg.backend,
        wall_s=wall_s,
        n_evaluations=n_evals,
        n_dispatches=n_dispatches,
    )
    if cfg.out_dir:
        from repro.families import family_of

        family_of(problem).write_artifact(
            problem, result, cfg.out_dir, emit_rtl=cfg.emit_rtl,
            verify_rtl=cfg.verify_rtl, dataset=cfg.dataset)
    return result


def _make_kernel_predict(problem: SearchProblem):
    """Single-chromosome (3N+1,) -> (B,) predictions through the Pallas path —
    the third leg of the RTL verification triangle (DESIGN.md §10). The
    decode folds comparator truncation into the effective operands and the
    vote cap models the approximate vote adder (DESIGN.md §16)."""
    from repro.kernels import ops as kops

    operands = kops.prepare_operands(
        problem.feature, problem.path, problem.path_len, problem.n_neg,
        problem.leaf_class, problem.n_classes, problem.n_features)

    def predict(genes):
        scale, thr, vote_cap = kops.decode_population(
            problem.threshold, genes[None, :])
        return kops.tree_infer_predict(problem.x8, operands, scale, thr,
                                       vote_cap)[0]

    return predict


def netlist_area_ratios(points) -> list[float]:
    """Per-point netlist/LUT area ratio from `pareto.json` points — the
    paper's Fig. 5 estimated-vs-actual gap (DESIGN.md §10). Points whose
    LUT estimate is zero (degenerate constant-false designs) are skipped."""
    return [p["area_netlist_mm2"] / p["area_mm2"] for p in points
            if p["area_mm2"] > 0]


@jax.jit
def decode_front(threshold, genes):
    """(R, 3N+1) genes -> int32 (bits, margin, t_sub, trunc, vote) of every
    row: the per-comparator design of DESIGN.md §16, pre-truncation, with
    the substituted integer thresholds — the arithmetic of
    `kops.decode_population_full` before truncation is folded in.
    `threshold` (N,) is an argument, so the compile is keyed on shapes."""
    bits, margin, trunc, vote = quant.decode_tree_genes(genes)
    t_sub = quant.substitute(quant.threshold_to_int(threshold, bits), margin,
                             bits)
    return bits, margin, t_sub, trunc, vote


def write_pareto_artifact(problem: SearchProblem, result: SearchResult,
                          out_dir: str, *, emit_rtl: bool = False,
                          verify_rtl: bool = False,
                          dataset: str | None = None) -> str:
    """pareto.json: objectives + genes + decoded designs + hardware artifact.

    Every point records the decoded `bits`/`margin` AND the substituted
    integer thresholds `t_int` — both PRE-truncation — plus the per-comparator
    `trunc` LSB-drop counts and the `vote_adder` mode (DESIGN.md §16), the
    top-level trained float `threshold` array AND the full super-tree leaf
    layout (`path`, `path_len`, `n_neg`, `leaf_class`), so a design
    re-materializes into RTL or a serving runtime from the artifact alone
    (`search.load_pareto_artifact`, DESIGN.md §14);
    the additive-LUT `area_mm2` estimate is paired with the
    synthesized-netlist `area_netlist_mm2` (gate counts after CSE/constant
    propagation) — the paper's Fig. 5 estimated-vs-actual gap as a measured
    artifact. The payload round-trips through the shared
    `search.artifact` schema validation, so writer and loader cannot drift.

    emit_rtl: write each point's Verilog (tree or forest) under OUT/rtl/.
    verify_rtl: simulate each point's netlist over the full test set and
    assert bit-exactness against `predict_votes` and the kernel backend.
    dataset: optional dataset label recorded for the serving CLI.
    """
    from repro.core import netlist, rtl
    from repro.runtime import spans
    from repro.search import artifact as _artifact
    from repro.search.problem import predict_votes, problem_ptrees

    os.makedirs(out_dir, exist_ok=True)
    ptrees = problem_ptrees(problem)
    if emit_rtl:
        os.makedirs(os.path.join(out_dir, "rtl"), exist_ok=True)
    kernel_predict = _make_kernel_predict(problem) if verify_rtl else None
    spans.count("artifact.points", len(result.pareto_genes))
    if spans.recording():   # a front keeps every copy of a rank-0 row
        spans.count("artifact.distinct_points",
                    len(np.unique(result.pareto_genes, axis=0)))

    # one compiled decode of the whole front, padded to the population's
    # row count so every campaign of one problem shares one program
    genes = np.asarray(result.pareto_genes, np.float32)
    n_rows = max(len(genes), result.state.genes.shape[0])
    block = np.zeros((n_rows, genes.shape[1]), np.float32)
    block[:len(genes)] = genes
    spans.count("artifact.decode_rows", n_rows)
    with spans.span("artifact.decode"):
        front = jax.device_get(decode_front(problem.threshold, block))
    points = []
    for i, (o, g) in enumerate(zip(result.pareto_objs, result.pareto_genes)):
        bits, margin, t_sub, trunc, vote = (a[i] for a in front)
        vote_adder = "approx" if vote else "exact"
        with spans.span("artifact.netlist"):
            circuit = netlist.build_circuit(ptrees, bits, t_sub,
                                            problem.n_classes, trunc=trunc,
                                            vote_adder=vote_adder)
            area_netlist = round(netlist.netlist_area_mm2(circuit), 4)
            gates = netlist.gate_counts(circuit)
        point = {
            "acc_loss": float(o[0]),
            "norm_area": float(o[1]),
            "area_mm2": float(o[1] * problem.exact_area_mm2),
            "area_netlist_mm2": area_netlist,
            "netlist_gates": gates,
            "bits": bits.tolist(),
            "margin": margin.tolist(),
            "t_int": t_sub.tolist(),
            "trunc": trunc.tolist(),
            "vote_adder": vote_adder,
            "genes": np.asarray(g, np.float64).round(6).tolist(),
        }
        if emit_rtl:
            verilog = rtl.emit_design(ptrees, bits, t_sub, problem.n_classes,
                                      trunc=trunc, vote_adder=vote_adder)
            rel = os.path.join("rtl", f"point_{i:02d}.v")
            with open(os.path.join(out_dir, rel), "w") as f:
                f.write(verilog)
            point["rtl"] = rel
        if verify_rtl:
            vote_cap = jnp.float32(1.0 if vote > 0 else jnp.inf)
            sim = np.asarray(netlist.simulate(circuit, problem.x8))
            ref = np.asarray(predict_votes(
                problem, jnp.asarray(bits - trunc),
                jnp.asarray(t_sub >> trunc), vote_cap))
            ker = np.asarray(kernel_predict(jnp.asarray(g)))
            if not (np.array_equal(sim, ref) and np.array_equal(sim, ker)):
                n_ref = int((sim != ref).sum())
                n_ker = int((sim != ker).sum())
                raise AssertionError(
                    f"pareto point {i}: netlist simulation diverges from "
                    f"predict_votes on {n_ref} and from the kernel backend "
                    f"on {n_ker} of {sim.shape[0]} test samples")
            point["verified"] = True
        points.append(point)

    payload = {
        "family": "tree",
        "backend": result.backend,
        "wall_s": round(result.wall_s, 3),
        "n_evaluations": result.n_evaluations,
        "n_dispatches": result.n_dispatches,
        "n_trees": problem.n_trees,
        "n_comparators": problem.n_comparators,
        "n_classes": problem.n_classes,
        "tree_comparators": list(problem.tree_comparators),
        "tree_leaves": list(problem.tree_leaves),
        "feature": np.asarray(problem.feature).tolist(),
        "threshold": np.asarray(problem.threshold, np.float64)
                       .round(8).tolist(),
        "path": np.asarray(problem.path).tolist(),
        "path_len": np.asarray(problem.path_len).tolist(),
        "n_neg": np.asarray(problem.n_neg).tolist(),
        "leaf_class": np.asarray(problem.leaf_class).tolist(),
        "exact_accuracy": problem.exact_accuracy,
        "exact_area_mm2": problem.exact_area_mm2,
        "rtl_verified": bool(verify_rtl),
        "pareto": points,
    }
    if dataset is not None:
        payload["dataset"] = dataset
    _artifact.validate_payload(payload, where="write_pareto_artifact")
    path = os.path.join(out_dir, "pareto.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(tmp, path)
    return path

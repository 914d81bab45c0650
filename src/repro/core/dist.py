"""Distributed NSGA-II: population sharding + island model across the mesh.

Three levels, matching DESIGN.md §6 and §13:

1. `sharded_fitness` — data-parallel fitness: the population tensor is sharded
   over mesh axes; each device evaluates its slice against the (replicated)
   dataset. The GA bookkeeping (P×P domination, selection) happens on the
   gathered objectives — tiny (P×2).

2. `make_sharded_chunk` / `make_sharded_batched_chunk` — ONE global NSGA-II
   population with its axis sharded over the mesh (DESIGN.md §13). Fitness —
   the dominant cost — runs on per-shard population slabs (the fused fitness
   kernel unmodified per shard), and the O(P²) domination relation is
   *hierarchical*: each shard computes only its (P/S, P) row block against
   the all-gathered objectives, then the front-peel merges per-shard
   dominator-count partials with `psum`s — O(P) integer vectors on the wire
   per peel, never the O(P²) matrix. Integer sums partition exactly over
   shards, and every remaining reduction is replicated bookkeeping on tiny
   (P, 2) gathers, so the sharded search is bit-identical to the
   single-device `nsga2.make_chunk` oracle (tests pin array-for-array
   equality). The batched variant vmaps the same generation body over a
   second mesh axis of sweep buckets, spreading the 10-dataset campaign over
   a 2-D mesh.

3. `island_step` / `run_islands` — one NSGA-II *island* per mesh group (pods
   at production scale). Islands evolve independently (zero cross-pod traffic
   in the inner loop) and exchange elites via a `ppermute` ring every
   `migrate_every` generations. A dead pod costs search breadth, not
   correctness — the fault-tolerance story for the GA workload.

Rounds are device-resident: the chunk makers scan whole checkpoint intervals
in one dispatch (DESIGN.md §9), and `island_state_sharding` /
`sharded_state_sharding` give the sharding pytrees
`runtime.checkpoint.restore` needs to re-shard a saved state onto the
current mesh.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import nsga2


def sharded_fitness(fitness_fn, mesh: Mesh, axis: str = "data"):
    """Wrap a (P, G) -> (P, M) fitness so the population axis is sharded."""
    pspec = P(axis)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(pspec,),
        out_specs=pspec,
        check_vma=False,
    )
    def _eval(genes):
        return fitness_fn(genes)

    @jax.jit
    def eval_sharded(genes):
        return _eval(genes)

    return eval_sharded


# ---------------------------------------------------------------------------
# Mesh-sharded global NSGA-II (DESIGN.md §13)
# ---------------------------------------------------------------------------

def _hierarchical_ranks(objs_local, objs_full, axis: str):
    """Global NSGA-II ranks from a per-shard row block (inside shard_map).

    ``objs_local`` (P_local, M) is this shard's contiguous slab of the
    ``objs_full`` (P, M) pool — slab i covers rows [i*P_local, (i+1)*P_local)
    in mesh-axis order (what a tiled all_gather produces). Each shard
    computes only its rows of the domination relation — O(P²/S) pairwise
    work, routed through `nsga2._dispatch_domination` on the LOCAL row count
    (the §13 routing fix) — and the shared front-peel merges the per-shard
    dominator-count partials with `psum`s. Integer sums partition exactly
    over shards, so the (replicated) result equals the monolithic sort's
    bit-for-bit.
    """
    p_local = objs_local.shape[0]
    start = jax.lax.axis_index(axis) * p_local
    dom_rows = nsga2._dispatch_domination(objs_local, objs_full)
    n_dominators = jax.lax.psum(
        dom_rows.sum(axis=0).astype(jnp.int32), axis)

    def dec(current):
        cur_rows = jax.lax.dynamic_slice_in_dim(current, start, p_local)
        part = (dom_rows & cur_rows[:, None]).sum(axis=0).astype(jnp.int32)
        return jax.lax.psum(part, axis)

    return nsga2._peel_fronts(n_dominators, dec)


def sharded_non_dominated_sort(objs, mesh: Mesh, axis: str = "pop"):
    """`nsga2.non_dominated_sort` with the population axis sharded over
    ``axis``: per-shard (P/S, P) domination rows merged hierarchically.

    ``objs`` (P, M) with P divisible by the mesh axis size. Returns the (P,)
    global ranks (sharded like the input), bit-identical to the monolithic
    sort."""
    _check_divisible(objs.shape[0], mesh, axis, "population")

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axis),), out_specs=P(axis),
             check_vma=False)
    def _sort(objs_local):
        full = jax.lax.all_gather(objs_local, axis, tiled=True)
        ranks = _hierarchical_ranks(objs_local, full, axis)
        start = jax.lax.axis_index(axis) * objs_local.shape[0]
        return jax.lax.dynamic_slice_in_dim(ranks, start,
                                            objs_local.shape[0])

    return jax.jit(_sort)(objs)


def sharded_crowding_distance(objs, rank, mesh: Mesh, axis: str = "pop"):
    """`nsga2.crowding_distance` over a sharded population.

    Crowding is global — every distance depends on the whole front's sort
    order — and its f32 per-axis contributions are added SEQUENTIALLY in
    axis order; psum-merging per-shard partial sums would reassociate those
    adds and drift by an ulp per generation (DESIGN.md §13). So each shard
    gathers the (tiny, (P, M)) objectives and replicates the exact oracle
    arithmetic, returning its slab of the identical result."""
    _check_divisible(objs.shape[0], mesh, axis, "population")

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(axis), P(axis)),
             out_specs=P(axis), check_vma=False)
    def _crowd(objs_local, rank_local):
        full = jax.lax.all_gather(objs_local, axis, tiled=True)
        rank_full = jax.lax.all_gather(rank_local, axis, tiled=True)
        crowd = nsga2.crowding_distance(full, rank_full)
        start = jax.lax.axis_index(axis) * objs_local.shape[0]
        return jax.lax.dynamic_slice_in_dim(crowd, start,
                                            objs_local.shape[0])

    return jax.jit(_crowd)(objs, rank)


def _sharded_gen_body(state: nsga2.NSGA2State, fitness_fn,
                      cfg: nsga2.NSGA2Config, axis: str) -> nsga2.NSGA2State:
    """One (mu+lambda) generation on a population sharded over ``axis``.

    Runs inside shard_map (optionally under a bucket-axis vmap). The state's
    population arrays are this shard's slab; ``state.key`` is replicated, so
    every shard draws identical randomness and the cheap O(P·G) selection /
    variation bookkeeping is replicated rather than communicated. Only the
    two expensive pieces are actually distributed: fitness (each shard
    evaluates its contiguous child slab; per-chromosome results are
    row-independent, so the gather reassembles exactly the monolithic
    array) and domination (hierarchical row blocks, `_hierarchical_ranks`).
    Crowding and truncation run on the replicated gathered pool with the
    exact oracle arithmetic — see `sharded_crowding_distance` for why the
    f32 adds must not be psum-reassociated. Net: bit-identical to
    `nsga2.make_step` on the gathered state (tests pin it)."""
    p_local, g = state.genes.shape
    idx0 = jax.lax.axis_index(axis)
    genes = jax.lax.all_gather(state.genes, axis, tiled=True)    # (P, G)
    objs = jax.lax.all_gather(state.objs, axis, tiled=True)      # (P, M)
    rank = jax.lax.all_gather(state.rank, axis, tiled=True)
    crowd = jax.lax.all_gather(state.crowd, axis, tiled=True)
    p = genes.shape[0]
    p_mut = cfg.p_mutation if cfg.p_mutation is not None else 1.0 / g
    key, ksel, kx, km = jax.random.split(state.key, 4)

    idx = nsga2._tournament(ksel, rank, crowd, p)
    pa, pb = genes[idx[0::2]], genes[idx[1::2]]
    o1, o2 = nsga2._sbx(kx, pa, pb, cfg.eta_crossover, cfg.p_crossover)
    children = jnp.concatenate([o1, o2], axis=0)[:p]
    children = nsga2._poly_mutation(km, children, cfg.eta_mutation, p_mut)
    # sharded fitness: each shard evaluates only its contiguous child slab
    c_local = jax.lax.dynamic_slice_in_dim(children, idx0 * p_local, p_local)
    c_objs = jax.lax.all_gather(fitness_fn(c_local), axis, tiled=True)

    pool_genes = jnp.concatenate([genes, children], axis=0)      # (2P, G)
    pool_objs = jnp.concatenate([objs, c_objs], axis=0)          # (2P, M)
    rows = 2 * p_local
    pool_local = jax.lax.dynamic_slice_in_dim(pool_objs, idx0 * rows, rows)
    pool_rank = _hierarchical_ranks(pool_local, pool_objs, axis)
    pool_crowd = nsga2.crowding_distance(pool_objs, pool_rank)
    # elitist truncation: (rank asc, crowding desc) — replicated argsort
    order = jnp.argsort(pool_rank.astype(jnp.float32) * nsga2._BIG
                        - jnp.minimum(pool_crowd, nsga2._BIG / 2))
    keep = order[:p]

    def slab(a):
        return jax.lax.dynamic_slice_in_dim(a, idx0 * p_local, p_local)

    return nsga2.NSGA2State(
        slab(pool_genes[keep]), slab(pool_objs[keep]), slab(pool_rank[keep]),
        slab(pool_crowd[keep]), key, state.generation + 1,
    )


def _check_divisible(p: int, mesh: Mesh, axis: str, what: str) -> None:
    n = mesh.shape[axis]
    if p % n:
        raise ValueError(
            f"{what} size {p} not divisible by mesh axis {axis!r} ({n})")


def _make_sharded_gen(fitness_fn, mesh: Mesh, cfg: nsga2.NSGA2Config,
                      axis: str = "pop"):
    from repro.sharding import search as _specs

    specs = _specs.search_state_specs(axis)

    @partial(jax.shard_map, mesh=mesh, in_specs=(specs,), out_specs=specs,
             check_vma=False)
    def _gen(state: nsga2.NSGA2State) -> nsga2.NSGA2State:
        return _sharded_gen_body(state, fitness_fn, cfg, axis)

    return _gen


def make_sharded_step(fitness_fn, mesh: Mesh, cfg: nsga2.NSGA2Config,
                      axis: str = "pop"):
    """One sharded generation as a jitted program (see `_sharded_gen_body`)."""
    return jax.jit(_make_sharded_gen(fitness_fn, mesh, cfg, axis))


def make_sharded_chunk(fitness_fn, mesh: Mesh, cfg: nsga2.NSGA2Config,
                       chunk_len: int, axis: str = "pop"):
    """`nsga2.make_chunk` with the population axis sharded over ``axis``.

    One dispatch advances the whole sharded population by ``chunk_len``
    generations (the §9 device-resident loop, scanned over the shard_map'd
    generation); bit-identical to the single-device chunk on the gathered
    state."""
    if chunk_len < 1:
        raise ValueError(f"chunk_len must be >= 1, got {chunk_len}")
    gen = _make_sharded_gen(fitness_fn, mesh, cfg, axis)

    @jax.jit
    def chunk(state: nsga2.NSGA2State) -> nsga2.NSGA2State:
        return jax.lax.scan(lambda s, _: (gen(s), None), state, None,
                            length=chunk_len)[0]

    return chunk


def make_sharded_batched_chunk(fitness_from_ctx, mesh: Mesh,
                               cfg: nsga2.NSGA2Config, chunk_len: int,
                               bucket_axis: str = "bucket",
                               axis: str = "pop"):
    """`nsga2.make_batched_chunk` spread over a 2-D (bucket, pop) mesh.

    The sweep's stacked problem axis is sharded over ``bucket_axis`` and
    every problem's population over ``axis``, so one dispatch advances the
    whole campaign using the full mesh (DESIGN.md §13). The per-problem body
    is exactly `_sharded_gen_body` vmapped over the local problem slab —
    named-axis collectives batch transparently under vmap — so each lane is
    bit-identical to its `nsga2.make_chunk` serial oracle. The stacked
    problem count must divide the bucket axis (pad the stack by repeating a
    problem and drop the extra lanes — compute waste, not wrong results)."""
    if chunk_len < 1:
        raise ValueError(f"chunk_len must be >= 1, got {chunk_len}")
    from repro.sharding import search as _specs

    specs = _specs.batched_state_specs(bucket_axis, axis)

    @jax.jit
    def chunk(states: nsga2.NSGA2State, ctxs) -> nsga2.NSGA2State:
        ctx_specs = jax.tree.map(lambda _: P(bucket_axis), ctxs)

        @partial(jax.shard_map, mesh=mesh, in_specs=(specs, ctx_specs),
                 out_specs=specs, check_vma=False)
        def _chunk(states, ctxs):
            def one(state, ctx):
                fit = lambda pop: fitness_from_ctx(ctx, pop)

                def step(s, _):
                    return _sharded_gen_body(s, fit, cfg, axis), None

                return jax.lax.scan(step, state, None, length=chunk_len)[0]

            return jax.vmap(one)(states, ctxs)

        return _chunk(states, ctxs)

    return chunk


def sharded_state_sharding(mesh: Mesh, axis: str = "pop") -> nsga2.NSGA2State:
    """Sharding pytree for a mesh-sharded global NSGA2State.

    Population arrays shard over ``axis``; the key and generation counter are
    replicated (every shard draws identical randomness — the bit-exactness
    anchor of `_sharded_gen_body`). Also what `runtime.checkpoint.restore`
    needs to re-shard a saved single-device search state onto a mesh."""
    from repro.sharding import search as _specs

    return _specs.search_state_sharding(mesh, axis)


def init_sharded(key, fitness_fn, n_genes: int, mesh: Mesh,
                 cfg: nsga2.NSGA2Config, axis: str = "pop",
                 seed_genes=None) -> nsga2.NSGA2State:
    """`nsga2.init_state` laid out sharded over ``axis``.

    Init is a one-off, so it runs the monolithic oracle and lays the result
    out over the mesh — trivially bit-identical, and the same path a
    checkpoint restore takes (`sharded_state_sharding`)."""
    _check_divisible(cfg.pop_size, mesh, axis, "population")
    state = nsga2.init_state(key, fitness_fn, n_genes, cfg,
                             seed_genes=seed_genes)
    return jax.tree.map(jax.device_put, state,
                        sharded_state_sharding(mesh, axis))


# ---------------------------------------------------------------------------
# Island model
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class IslandConfig:
    local_pop: int = 32          # per-island population
    migrate_every: int = 5       # generations between migrations
    n_migrate: int = 4           # elites sent around the ring
    nsga: nsga2.NSGA2Config = dataclasses.field(default_factory=nsga2.NSGA2Config)


def _local_evolve(state: nsga2.NSGA2State, fitness_fn, cfg: nsga2.NSGA2Config,
                  n_gens: int) -> nsga2.NSGA2State:
    step = nsga2.make_step(fitness_fn, cfg)
    return jax.lax.fori_loop(0, n_gens, lambda _, s: step(s), state)


def _migrate(state: nsga2.NSGA2State, axis: str, n_migrate: int,
             n_islands: int) -> nsga2.NSGA2State:
    """Ring migration of the n_migrate best; they replace the worst."""
    order = jnp.argsort(
        state.rank.astype(jnp.float32) * 1e9 - jnp.minimum(state.crowd, 5e8)
    )
    best, worst = order[:n_migrate], order[-n_migrate:]
    perm = [(i, (i + 1) % n_islands) for i in range(n_islands)]
    mig_genes = jax.lax.ppermute(state.genes[best], axis, perm)
    mig_objs = jax.lax.ppermute(state.objs[best], axis, perm)
    genes = state.genes.at[worst].set(mig_genes)
    objs = state.objs.at[worst].set(mig_objs)
    rank = nsga2.non_dominated_sort(objs)
    crowd = nsga2.crowding_distance(objs, rank)
    return nsga2.NSGA2State(genes, objs, rank, crowd, state.key, state.generation)


def _make_round(fitness_fn, mesh: Mesh, cfg: IslandConfig, axis: str = "data"):
    """Unjitted one-round body shared by make_island_step / make_island_chunk."""
    pspec = P(axis)
    state_specs = nsga2.NSGA2State(
        genes=pspec, objs=pspec, rank=pspec, crowd=pspec, key=pspec,
        generation=P(),
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(state_specs,),
        out_specs=state_specs,
        check_vma=False,
    )
    def _round(state: nsga2.NSGA2State) -> nsga2.NSGA2State:
        local = nsga2.NSGA2State(
            state.genes, state.objs, state.rank, state.crowd,
            state.key[0], state.generation,
        )
        local = _local_evolve(local, fitness_fn, cfg.nsga, cfg.migrate_every)
        local = _migrate(local, axis, cfg.n_migrate, mesh.shape[axis])
        return nsga2.NSGA2State(
            local.genes, local.objs, local.rank, local.crowd,
            local.key[None], local.generation,
        )

    return _round


def make_island_step(fitness_fn, mesh: Mesh, cfg: IslandConfig, axis: str = "data"):
    """One migration round: `migrate_every` local generations + ring exchange.

    State arrays are sharded over `axis`: genes (n_islands*local_pop, G).
    """
    return jax.jit(_make_round(fitness_fn, mesh, cfg, axis))


def make_island_chunk(fitness_fn, mesh: Mesh, cfg: IslandConfig, n_rounds: int,
                      axis: str = "data"):
    """`n_rounds` migration rounds as ONE dispatch: lax.scan over the round.

    The island analogue of `nsga2.make_chunk` (DESIGN.md §9): the host
    dispatches once per checkpoint interval instead of once per round; the
    scan body is exactly the `make_island_step` round, so chunked and
    per-round execution are bit-identical."""
    if n_rounds < 1:
        raise ValueError(f"n_rounds must be >= 1, got {n_rounds}")
    round_fn = _make_round(fitness_fn, mesh, cfg, axis)

    @jax.jit
    def chunk(state: nsga2.NSGA2State) -> nsga2.NSGA2State:
        return jax.lax.scan(lambda s, _: (round_fn(s), None), state, None,
                            length=n_rounds)[0]

    return chunk


def init_islands(key, fitness_fn, n_genes: int, mesh: Mesh, cfg: IslandConfig,
                 axis: str = "data", seed_genes=None) -> nsga2.NSGA2State:
    """Initialize per-island states, already laid out sharded over `axis`.

    seed_genes: optional known-good designs injected into every island's
    initial population (see nsga2.init_state)."""
    n_islands = mesh.shape[axis]
    keys = jax.random.split(key, n_islands)
    local_cfg = dataclasses.replace(cfg.nsga, pop_size=cfg.local_pop)

    def one(k):
        return nsga2.init_state(k, fitness_fn, n_genes, local_cfg,
                                seed_genes=seed_genes)

    states = [one(k) for k in keys]
    genes = jnp.concatenate([s.genes for s in states])
    objs = jnp.concatenate([s.objs for s in states])
    rank = jnp.concatenate([s.rank for s in states])
    crowd = jnp.concatenate([s.crowd for s in states])
    key_arr = jnp.stack([s.key for s in states])
    state = nsga2.NSGA2State(genes, objs, rank, crowd, key_arr, jnp.int32(0))

    shard = NamedSharding(mesh, P(axis))
    return nsga2.NSGA2State(
        jax.device_put(state.genes, shard),
        jax.device_put(state.objs, shard),
        jax.device_put(state.rank, shard),
        jax.device_put(state.crowd, shard),
        jax.device_put(state.key, shard),
        state.generation,
    )


def island_state_sharding(mesh: Mesh, axis: str = "data") -> nsga2.NSGA2State:
    """Sharding pytree matching an island NSGA2State (elastic restore)."""
    shard = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())
    return nsga2.NSGA2State(genes=shard, objs=shard, rank=shard, crowd=shard,
                            key=shard, generation=rep)


def run_islands(key, fitness_fn, n_genes: int, mesh: Mesh, cfg: IslandConfig,
                n_rounds: int, axis: str = "data",
                state: nsga2.NSGA2State | None = None,
                seed_genes=None) -> nsga2.NSGA2State:
    """All `n_rounds` rounds in one device dispatch (chunked scan)."""
    if state is None:
        state = init_islands(key, fitness_fn, n_genes, mesh, cfg, axis,
                             seed_genes)
    if n_rounds > 0:
        state = make_island_chunk(fitness_fn, mesh, cfg, n_rounds, axis)(state)
    return state


def gathered_pareto(state: nsga2.NSGA2State):
    """Global pareto front across all islands."""
    return nsga2.pareto_front(jax.device_get(state.objs), jax.device_get(state.genes))

"""Vectorized NSGA-II (Deb et al. 2002), the paper's design-space explorer.

Faithful to the paper's configuration: elitist (mu+lambda), binary tournament
selection on (rank, crowding), simulated binary crossover, polynomial
mutation, fast non-dominated sort, crowding-distance truncation.

Everything is fixed-shape jnp so a whole generation is ONE compiled program —
and `make_chunk` scans that program over a generation chunk so a whole
checkpoint interval is one dispatch (DESIGN.md §9). Fitness is a vmapped
batch; the domination matrix is a dense (P, P) block, auto-routed to the
Pallas kernel in repro.kernels.domination above DOMINATION_KERNEL_MIN_POP;
fronts are peeled with a while_loop; crowding uses masked sorts. Population
parallelism maps onto the mesh in repro.core.dist.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

INF = jnp.inf
_BIG = 1e9

# Number of *rows* of the domination relation at which `non_dominated_sort`
# routes through the blocked Pallas kernel (repro.kernels.domination) instead
# of the pure-jnp broadcast. The row count is the LOCAL population slab: the
# monolithic sort hands the full pool (rows == columns == pool P, and inside
# the GA step the pool is the combined parent+offspring set 2P, so the kernel
# engages from pop_size >= DOMINATION_KERNEL_MIN_POP / 2); the mesh-sharded
# hierarchical sort hands each shard's (P_local, P_global) row block, so a
# population sharded 8 ways routes on P/8 — small shards skip the Pallas
# launch overhead even when the global pool is huge (DESIGN.md §13). The jnp
# path stays the bit-exact oracle (the matrix is boolean, so "bit-exact" is
# plain equality) — see DESIGN.md §9.
DOMINATION_KERNEL_MIN_POP = 512


def domination_matrix(objs: jnp.ndarray,
                      against: jnp.ndarray | None = None) -> jnp.ndarray:
    """objs (Pi, M), minimized. out[i, j] = True iff objs[i] dominates
    against[j] (default ``against = objs`` — the square pool-vs-pool case)."""
    a = objs[:, None, :]  # i
    b = (objs if against is None else against)[None, :, :]  # j
    return jnp.all(a <= b, axis=-1) & jnp.any(a < b, axis=-1)


def _kernel_domination_available() -> bool:
    """Auto-routing engages only on a real TPU. Off-TPU the kernel runs in
    the Pallas interpreter — a bit-exact correctness fallback for explicit
    use (cfg.domination_fn), never a win to route to automatically."""
    return jax.default_backend() == "tpu"


def _dispatch_domination(objs: jnp.ndarray,
                         against: jnp.ndarray | None = None) -> jnp.ndarray:
    """Pure-jnp domination below DOMINATION_KERNEL_MIN_POP rows, Pallas above.

    Routing is on ``objs.shape[0]`` — the local (post-shard) row count, not
    the global pool size — so a small per-shard slab of a large sharded pool
    never pays the kernel's launch overhead. Shapes are static under jit, so
    the routing resolves at trace time — no runtime branching inside the
    compiled program."""
    if (objs.shape[0] >= DOMINATION_KERNEL_MIN_POP
            and _kernel_domination_available()):
        from repro.kernels import ops as _kops

        if against is None:
            return _kops.domination_matrix_bool(objs)
        return _kops.domination_block_bool(objs, against)
    return domination_matrix(objs, against)


def _peel_fronts(n_dominators: jnp.ndarray, dec_fn) -> jnp.ndarray:
    """Front-peeling while_loop shared by the monolithic and sharded sorts.

    ``n_dominators`` (P,) int32 — how many pool members dominate each j;
    ``dec_fn(current)`` — given the (P,) bool mask of the front being peeled,
    return the (P,) int32 count of dominators each j loses. The monolithic
    sort reduces its full (P, P) matrix; the sharded sort reduces its local
    (P_local, P) row block and merges with a psum — integer sums partition
    exactly over shards, so both produce identical ranks (DESIGN.md §13).
    """
    p = n_dominators.shape[0]

    def body(state):
        rank, counts, r = state
        current = (counts == 0) & (rank < 0)
        rank = jnp.where(current, r, rank)
        # removing `current` decrements the dominator count of their dominatees
        counts = jnp.where(rank < 0, counts - dec_fn(current), -1)
        return rank, counts, r + 1

    def cond(state):
        rank, _, _ = state
        return jnp.any(rank < 0)

    rank0 = jnp.full((p,), -1, dtype=jnp.int32)
    counts0 = jnp.where(rank0 < 0, n_dominators, -1)
    rank, _, _ = jax.lax.while_loop(cond, body, (rank0, counts0, jnp.int32(0)))
    return rank


def non_dominated_sort(objs: jnp.ndarray, dom: jnp.ndarray | None = None) -> jnp.ndarray:
    """Returns integer rank per individual (0 = first/pareto front)."""
    if dom is None:
        dom = _dispatch_domination(objs)
    n_dominators = dom.sum(axis=0).astype(jnp.int32)  # how many dominate j

    def dec(current):
        return (dom & current[:, None]).sum(axis=0).astype(jnp.int32)

    return _peel_fronts(n_dominators, dec)


def crowding_distance(objs: jnp.ndarray, rank: jnp.ndarray) -> jnp.ndarray:
    """Crowding distance computed per-front with masked sorts (fixed shape).

    The per-objective pass is vmapped over the objective axis instead of a
    Python loop of M sequential sort programs, so all objectives sort at
    once. Bit-identical to the historical loop (tests pin it against an
    independent loop oracle): per-axis contributions are non-negative, the
    scatter indices are a permutation, and the contributions are added
    sequentially in axis order — a tree-shaped `sum` would reassociate the
    f32 adds and drift by an ulp from generation to generation.
    """
    p, m = objs.shape

    def one_axis(v):
        # sort within fronts: composite key pushes other fronts far away
        key = rank.astype(jnp.float32) * _BIG + v
        order = jnp.argsort(key)
        v_s = v[order]
        r_s = rank[order]
        # neighbours within the same front
        prev_ok = jnp.concatenate([jnp.array([False]), r_s[1:] == r_s[:-1]])
        next_ok = jnp.concatenate([r_s[:-1] == r_s[1:], jnp.array([False])])
        v_prev = jnp.concatenate([v_s[:1], v_s[:-1]])
        v_next = jnp.concatenate([v_s[1:], v_s[-1:]])
        # per-front objective range for normalization
        fmin = jnp.full((p,), jnp.inf).at[r_s].min(v_s)
        fmax = jnp.full((p,), -jnp.inf).at[r_s].max(v_s)
        span = jnp.maximum((fmax - fmin)[r_s], 1e-12)
        d = jnp.where(prev_ok & next_ok, (v_next - v_prev) / span, jnp.inf)
        return jnp.zeros((p,), jnp.float32).at[order].add(
            jnp.where(jnp.isinf(d), _BIG, d))

    contribs = jax.vmap(one_axis, in_axes=1)(objs)  # (M, P)
    dist = contribs[0]
    for k in range(1, m):
        dist = dist + contribs[k]
    return dist


def _tournament(key, rank, crowd, n_out):
    p = rank.shape[0]
    k1, k2 = jax.random.split(key)
    a = jax.random.randint(k1, (n_out,), 0, p)
    b = jax.random.randint(k2, (n_out,), 0, p)
    # lower rank wins; tie -> higher crowding wins; tie -> a
    a_wins = (rank[a] < rank[b]) | ((rank[a] == rank[b]) & (crowd[a] >= crowd[b]))
    return jnp.where(a_wins, a, b)


def _sbx(key, parents_a, parents_b, eta_c, p_cross):
    """Simulated binary crossover on [0,1] genes."""
    ku, kc, kv = jax.random.split(key, 3)
    u = jax.random.uniform(ku, parents_a.shape)
    beta = jnp.where(
        u <= 0.5,
        (2.0 * u) ** (1.0 / (eta_c + 1.0)),
        (1.0 / (2.0 * (1.0 - u))) ** (1.0 / (eta_c + 1.0)),
    )
    c1 = 0.5 * ((1 + beta) * parents_a + (1 - beta) * parents_b)
    c2 = 0.5 * ((1 - beta) * parents_a + (1 + beta) * parents_b)
    do = jax.random.uniform(kc, parents_a.shape[:1]) < p_cross
    c1 = jnp.where(do[:, None], c1, parents_a)
    c2 = jnp.where(do[:, None], c2, parents_b)
    swap = jax.random.uniform(kv, parents_a.shape) < 0.5
    o1 = jnp.where(swap, c1, c2)
    o2 = jnp.where(swap, c2, c1)
    return jnp.clip(o1, 0.0, 1.0), jnp.clip(o2, 0.0, 1.0)


def _poly_mutation(key, genes, eta_m, p_mut):
    km, ku = jax.random.split(key)
    u = jax.random.uniform(ku, genes.shape)
    delta = jnp.where(
        u < 0.5,
        (2.0 * u) ** (1.0 / (eta_m + 1.0)) - 1.0,
        1.0 - (2.0 * (1.0 - u)) ** (1.0 / (eta_m + 1.0)),
    )
    mask = jax.random.uniform(km, genes.shape) < p_mut
    return jnp.clip(genes + jnp.where(mask, delta, 0.0), 0.0, 1.0)


@dataclasses.dataclass
class NSGA2Config:
    pop_size: int = 64
    n_generations: int = 40
    eta_crossover: float = 20.0
    eta_mutation: float = 20.0
    p_crossover: float = 0.9
    p_mutation: float | None = None  # default 1/n_genes
    domination_fn: Callable | None = None  # e.g. Pallas kernel; default jnp


@dataclasses.dataclass
class NSGA2State:
    genes: jnp.ndarray   # (P, G)
    objs: jnp.ndarray    # (P, M)
    rank: jnp.ndarray    # (P,)
    crowd: jnp.ndarray   # (P,)
    key: jnp.ndarray
    generation: jnp.ndarray


jax.tree_util.register_pytree_node(
    NSGA2State,
    lambda s: ((s.genes, s.objs, s.rank, s.crowd, s.key, s.generation), None),
    lambda _, c: NSGA2State(*c),
)


def init_state(key, fitness_fn, n_genes: int, cfg: NSGA2Config,
               seed_genes=None) -> NSGA2State:
    """seed_genes (K, n_genes): known-good designs injected into the initial
    population (e.g. the exact bespoke design + jittered copies). Beyond-paper
    improvement: for high-gene-count trees (HAR: 1000+ genes) random init
    never recovers the near-exact region within realistic budgets."""
    kinit, kloop, kjit = jax.random.split(key, 3)
    genes = jax.random.uniform(kinit, (cfg.pop_size, n_genes))
    if seed_genes is not None:
        seed_genes = jnp.atleast_2d(jnp.asarray(seed_genes))
        k = seed_genes.shape[0]
        n_seed = min(cfg.pop_size // 2, max(k, cfg.pop_size // 8))
        reps = jnp.tile(seed_genes, ((n_seed + k - 1) // k, 1))[:n_seed]
        jitter = jax.random.normal(kjit, reps.shape) * 0.03
        jitter = jitter.at[:k].set(0.0)  # keep pristine seeds
        genes = genes.at[:n_seed].set(jnp.clip(reps + jitter, 0.0, 1.0))
    objs = fitness_fn(genes)
    dom_fn = cfg.domination_fn or _dispatch_domination
    rank = non_dominated_sort(objs, dom_fn(objs))
    crowd = crowding_distance(objs, rank)
    return NSGA2State(genes, objs, rank, crowd, kloop, jnp.int32(0))


def make_step(fitness_fn, cfg: NSGA2Config):
    """One (mu+lambda) generation, jittable."""
    dom_fn = cfg.domination_fn or _dispatch_domination

    def step(state: NSGA2State) -> NSGA2State:
        p, g = state.genes.shape
        p_mut = cfg.p_mutation if cfg.p_mutation is not None else 1.0 / g
        key, ksel, kx, km = jax.random.split(state.key, 4)

        idx = _tournament(ksel, state.rank, state.crowd, p)
        pa, pb = state.genes[idx[0::2]], state.genes[idx[1::2]]
        o1, o2 = _sbx(kx, pa, pb, cfg.eta_crossover, cfg.p_crossover)
        children = jnp.concatenate([o1, o2], axis=0)[:p]
        children = _poly_mutation(km, children, cfg.eta_mutation, p_mut)
        c_objs = fitness_fn(children)

        pool_genes = jnp.concatenate([state.genes, children], axis=0)
        pool_objs = jnp.concatenate([state.objs, c_objs], axis=0)
        rank = non_dominated_sort(pool_objs, dom_fn(pool_objs))
        crowd = crowding_distance(pool_objs, rank)
        # elitist truncation: (rank asc, crowding desc)
        order = jnp.argsort(rank.astype(jnp.float32) * _BIG - jnp.minimum(crowd, _BIG / 2))
        keep = order[:p]
        return NSGA2State(
            pool_genes[keep], pool_objs[keep], rank[keep], crowd[keep],
            key, state.generation + 1,
        )

    return step


def make_chunk(fitness_fn, cfg: NSGA2Config, chunk_len: int):
    """`chunk_len` generations as ONE device program: lax.scan over make_step.

    The device-resident generation loop (DESIGN.md §9): instead of the host
    dispatching one jitted step per generation (a host round-trip each), a
    whole chunk — typically one checkpoint interval — is a single dispatch
    and a single device->host transfer. The scan body is exactly `make_step`,
    so a chunked run is bit-identical to the per-generation loop (tests
    enforce this)."""
    if chunk_len < 1:
        raise ValueError(f"chunk_len must be >= 1, got {chunk_len}")
    step = make_step(fitness_fn, cfg)

    def chunk(state: NSGA2State) -> NSGA2State:
        return jax.lax.scan(lambda s, _: (step(s), None), state, None,
                            length=chunk_len)[0]

    return chunk


def make_batched_init(fitness_from_ctx, n_genes: int, cfg: NSGA2Config,
                      seed_genes=None):
    """`init_state` vmapped over a leading problem axis (DESIGN.md §11).

    `fitness_from_ctx(ctx, pop)` evaluates one problem's population given its
    per-problem context pytree (e.g. a padded `sweep.PaddedProblem`); the
    returned function maps stacked `(keys, ctxs)` — both with a leading
    problem axis — to a stacked `NSGA2State`, initializing every problem in
    ONE dispatch (jit the result). `seed_genes` is shared across problems
    (the sweep pads every bucket member to the same chromosome length, and
    the exact design is the same inert-padded encoding for all)."""

    def init_one(key, ctx):
        return init_state(key, lambda pop: fitness_from_ctx(ctx, pop),
                          n_genes, cfg, seed_genes=seed_genes)

    return jax.vmap(init_one)


def make_batched_chunk(fitness_from_ctx, cfg: NSGA2Config, chunk_len: int):
    """`make_chunk` vmapped over a leading problem axis (DESIGN.md §11).

    One dispatch of the returned function advances EVERY problem in the
    batch by `chunk_len` generations: the scanned generation program (§9) is
    vmapped over stacked per-problem contexts, so the whole bucket of
    campaigns costs one host round-trip. Per-problem arithmetic is
    bit-identical to running `make_chunk` problem-by-problem (the sweep's
    serial oracle; tests pin it) — every cross-lane reduction the GA step
    performs is either integer-valued in f32 or elementwise."""

    def chunk_one(state, ctx):
        return make_chunk(lambda pop: fitness_from_ctx(ctx, pop),
                          cfg, chunk_len)(state)

    return jax.vmap(chunk_one)


def run(key, fitness_fn, n_genes: int, cfg: NSGA2Config,
        state: NSGA2State | None = None, jit: bool = True,
        seed_genes=None) -> NSGA2State:
    """Run the GA; `state` allows checkpoint/restart continuation.

    jit=False runs the generation eagerly so `fitness_fn` may be a host
    (numpy) function — used by the LM mixed-precision search where fitness
    re-quantizes weight tensors on the host."""
    if state is None:
        state = init_state(key, fitness_fn, n_genes, cfg, seed_genes)
    step = make_step(fitness_fn, cfg)
    if jit:
        step = jax.jit(step)
    for _ in range(cfg.n_generations):
        state = step(state)
    return state


def pareto_front(objs: jnp.ndarray, genes: jnp.ndarray):
    """Extract the non-dominated set, sorted by the first objective."""
    rank = non_dominated_sort(objs)
    mask = rank == 0
    import numpy as np
    objs_np = np.asarray(objs)[np.asarray(mask)]
    genes_np = np.asarray(genes)[np.asarray(mask)]
    order = np.argsort(objs_np[:, 0])
    return objs_np[order], genes_np[order]

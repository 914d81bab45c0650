"""Multi-device behaviours (8 host devices via subprocess: XLA_FLAGS must be
set before jax init, so these run in a fresh interpreter).

Covers: island-model GA with ring migration, sharded population fitness,
int8 compressed cross-group psum, elastic checkpoint restore onto a
different mesh, and the sharded LM train step (the production train path in
miniature). A second suite covers the mesh-sharded NSGA-II (DESIGN.md §13):
hierarchical domination vs the monolithic oracle, per-shard kernel routing
on LOCAL rows, sharded crowding vs the sequential-loop oracle, sharded
chunks bit-exact vs `nsga2.make_chunk` on tree / forest / inert-padded
sweep problems above and below DOMINATION_KERNEL_MIN_POP, and an island
checkpoint resumed onto a mesh of entirely different devices."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
assert len(jax.devices()) == 8

# --- island GA + sharded fitness -------------------------------------------
from repro.datasets import load_dataset
from repro.core.train import train_tree
from repro.core.tree import to_parallel
from repro.core import approx, dist, nsga2

ds = load_dataset("seeds")
tree = train_tree(ds.x_train, ds.y_train, ds.n_classes)
pt = to_parallel(tree)
prob = approx.build_problem(pt, ds.x_test, ds.y_test)
fit_vm = lambda g: jax.vmap(lambda x: approx.objectives(prob, x))(g)

mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
sf = dist.sharded_fitness(fit_vm, mesh)
g = jax.random.uniform(jax.random.PRNGKey(0), (64, prob.n_genes))
g = jax.device_put(g, NamedSharding(mesh, P("data")))
o_sharded = np.asarray(sf(g))
o_ref = np.asarray(fit_vm(g))
assert np.allclose(o_sharded, o_ref, atol=1e-6), "sharded fitness != local"

cfg = dist.IslandConfig(local_pop=16, migrate_every=2, n_migrate=2)
st = dist.run_islands(jax.random.PRNGKey(1), fit_vm, prob.n_genes, mesh, cfg,
                      n_rounds=3)
objs, genes = dist.gathered_pareto(st)
assert (objs[:, 1] < 1.0).any(), "islands found no area reduction"
print("ISLANDS_OK", len(objs))

# --- compressed cross-group psum --------------------------------------------
from repro.optim import compress
from functools import partial

mesh2 = Mesh(np.array(jax.devices()).reshape(2, 4), ("pod", "data"))
x = jnp.arange(32.0).reshape(2, 16) / 7.0

@partial(jax.shard_map, mesh=mesh2, in_specs=(P("pod", None),), out_specs=P("pod", None),
         check_vma=False)
def mean_pods(g):
    return compress.compressed_psum({"g": g}, "pod")["g"]

got = np.asarray(mean_pods(x))
want = np.broadcast_to(x.mean(axis=0, keepdims=True), x.shape)
err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
assert err < 0.02, f"compressed psum err {err}"
print("COMPRESS_OK", err)

# --- elastic checkpoint restore ---------------------------------------------
from repro.runtime import checkpoint
import tempfile
tree8 = {"w": jax.device_put(jnp.arange(64.0).reshape(8, 8),
                             NamedSharding(mesh, P("data", None)))}
with tempfile.TemporaryDirectory() as td:
    checkpoint.save(td, 3, tree8)
    mesh4 = Mesh(np.array(jax.devices()[:4]).reshape(4), ("data",))
    shard4 = {"w": NamedSharding(mesh4, P(None, "data"))}
    restored, step = checkpoint.restore(td, 3, tree8, shardings=shard4)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(64.0).reshape(8, 8))
    assert restored["w"].sharding.mesh.shape["data"] == 4
print("ELASTIC_OK")

# --- sharded LM train step (production path in miniature) -------------------
import dataclasses
from repro.configs import get_config, reduced_config
from repro.models import transformer
from repro.runtime import train as train_rt
from repro.optim import get_optimizer
from repro.sharding import params as sp
from repro.sharding.rules import MeshRules

mesh3 = Mesh(np.array(jax.devices()).reshape(2, 2, 2), ("pod", "data", "model"))
rules = MeshRules(tp=2, batch=("pod", "data"), expert=("pod", "data"),
                  ff_wide=("pod", "data", "model"))
cfg = reduced_config(get_config("minitron-8b"), n_heads=4, n_kv_heads=2,
                     d_model=64, d_ff=128)
params = transformer.init_params(jax.random.PRNGKey(0), cfg)
pspecs = sp.param_specs(cfg, rules, mesh3)
params = jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh3, s)),
                      params, pspecs)
opt = get_optimizer(cfg)
state = train_rt.init_train_state(params, opt)
step_fn = jax.jit(train_rt.make_train_step(cfg, rules=rules, optimizer=opt))
tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
batch = {"tokens": jax.device_put(tok, NamedSharding(mesh3, P(("pod", "data"), None)))}
with mesh3:  # with_sharding_constraint(PartitionSpec) needs an ambient mesh
    state, metrics = step_fn(state, batch)
    loss1 = float(metrics["loss"])
    state, metrics = step_fn(state, batch)
assert np.isfinite(loss1) and float(metrics["loss"]) < loss1 + 1.0
print("SHARDED_TRAIN_OK", loss1, float(metrics["loss"]))
print("ALL_MULTIDEVICE_OK")
"""


SCRIPT_SHARDED = r"""
import os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
assert len(jax.devices()) == 8

from repro.datasets import load_dataset
from repro.core import dist, forest as forest_mod, nsga2
from repro.core.train import train_tree
from repro.core.tree import to_parallel
from repro.kernels import ops as kops
from repro.launch.mesh import make_search_mesh
from repro.runtime import checkpoint
from repro import search
from repro.search import sweep as sweep_mod

mesh4 = make_search_mesh("4", axes=("pop",))
key = jax.random.PRNGKey(0)

# --- hierarchical domination sort == monolithic oracle (jnp routing) --------
for p, m in ((64, 2), (128, 3), (256, 2)):
    objs = jax.random.uniform(jax.random.fold_in(key, p), (p, m))
    np.testing.assert_array_equal(
        np.asarray(dist.sharded_non_dominated_sort(objs, mesh4)),
        np.asarray(nsga2.non_dominated_sort(objs)),
        err_msg=f"hier sort p={p}")
print("HIER_SORT_OK")

# --- sharded crowding == the sequential-loop oracle (bit-exact) -------------
def loop_crowding(objs, rank):
    p, m = objs.shape
    out = jnp.zeros((p,), dtype=jnp.float32)
    for k in range(m):
        v = objs[:, k]
        order = jnp.argsort(rank.astype(jnp.float32) * nsga2._BIG + v)
        v_s, r_s = v[order], rank[order]
        prev_ok = jnp.concatenate([jnp.array([False]), r_s[1:] == r_s[:-1]])
        next_ok = jnp.concatenate([r_s[:-1] == r_s[1:], jnp.array([False])])
        v_prev = jnp.concatenate([v_s[:1], v_s[:-1]])
        v_next = jnp.concatenate([v_s[1:], v_s[-1:]])
        fmin = jnp.full((p,), jnp.inf).at[r_s].min(v_s)
        fmax = jnp.full((p,), -jnp.inf).at[r_s].max(v_s)
        span = jnp.maximum((fmax - fmin)[r_s], 1e-12)
        d = jnp.where(prev_ok & next_ok, (v_next - v_prev) / span, jnp.inf)
        out = out.at[order].add(jnp.where(jnp.isinf(d), nsga2._BIG, d))
    return out

objs = jax.random.uniform(jax.random.fold_in(key, 99), (128, 2))
rank = nsga2.non_dominated_sort(objs)
np.testing.assert_array_equal(
    np.asarray(dist.sharded_crowding_distance(objs, rank, mesh4)),
    np.asarray(loop_crowding(objs, rank)))
print("CROWD_OK")

# --- kernel routing decides on LOCAL (post-shard) rows ----------------------
# Oracle ranks first (default jnp routing), then force the kernel available
# (interpret mode off-TPU) with a lowered threshold: p=128 shards to 32 local
# rows (stays jnp), p=256 shards to 64 (engages the kernel) — both bit-exact.
oracle = {}
for p in (128, 256):
    o = jax.random.uniform(jax.random.fold_in(key, 1000 + p), (p, 2))
    oracle[p] = (o, np.asarray(nsga2.non_dominated_sort(o)))
orig_min = nsga2.DOMINATION_KERNEL_MIN_POP
orig_avail = nsga2._kernel_domination_available
real_block = kops.domination_block_bool
nsga2.DOMINATION_KERNEL_MIN_POP = 64
nsga2._kernel_domination_available = lambda: True
calls = []
kops.domination_block_bool = (
    lambda a, b, **kw: calls.append((a.shape[0], b.shape[0]))
    or real_block(a, b, **kw))
jax.clear_caches()
for p in (128, 256):
    o, want = oracle[p]
    np.testing.assert_array_equal(
        np.asarray(dist.sharded_non_dominated_sort(o, mesh4)), want,
        err_msg=f"kernel-routed sort p={p}")
assert (32, 128) not in calls, f"32-row shard must stay jnp: {calls}"
assert (64, 256) in calls, f"64-row shard must engage the kernel: {calls}"
print("ROUTING_OK", sorted(set(calls)))

# --- sharded chunk == nsga2.make_chunk, tree/forest, above+below min-pop ----
ds = load_dataset("seeds")
pt = to_parallel(train_tree(ds.x_train, ds.y_train, ds.n_classes))
prob_tree = search.build_tree_problem(pt, ds.x_test, ds.y_test)
forest = forest_mod.train_forest(ds.x_train, ds.y_train, ds.n_classes,
                                 n_trees=2)
prob_forest = search.build_forest_problem(forest, ds.x_test, ds.y_test)

def check_chunk(prob, pop, gens, tag):
    fit = search.make_fitness(prob, "reference")
    cfg = nsga2.NSGA2Config(pop_size=pop, n_generations=gens)
    st0 = nsga2.init_state(jax.random.PRNGKey(7), fit, prob.n_genes, cfg)
    want = jax.jit(nsga2.make_chunk(fit, cfg, gens))(st0)
    st = jax.tree.map(jax.device_put, st0, dist.sharded_state_sharding(mesh4))
    got = dist.make_sharded_chunk(fit, mesh4, cfg, gens)(st)
    for f in ("genes", "objs", "rank", "crowd", "key", "generation"):
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)),
                                      err_msg=f"{tag}.{f}")

# threshold still patched to 64: the pool's 128 local rows run the kernel
check_chunk(prob_tree, 256, 2, "tree-kernel-routed")
kops.domination_block_bool = real_block
nsga2.DOMINATION_KERNEL_MIN_POP = orig_min
nsga2._kernel_domination_available = orig_avail
jax.clear_caches()
check_chunk(prob_tree, 64, 3, "tree-below-minpop")
check_chunk(prob_tree, 1024, 2, "tree-above-minpop")  # pool 2048 > 512
check_chunk(prob_forest, 64, 2, "forest")
print("CHUNK_OK")

# --- inert-padded sweep bucket on a 2x4 (bucket, pop) mesh ------------------
ds2 = load_dataset("balance")
pt2 = to_parallel(train_tree(ds2.x_train, ds2.y_train, ds2.n_classes))
problems = {"seeds": prob_tree,
            "balance": search.build_tree_problem(pt2, ds2.x_test, ds2.y_test)}
scfg = dict(pop_size=16, n_generations=4, seed=0, max_buckets=1)
s_ref = sweep_mod.run_sweep(problems, sweep_mod.SweepConfig(**scfg))
s_mesh = sweep_mod.run_sweep(problems, sweep_mod.SweepConfig(mesh="2x4",
                                                             **scfg))
for name in problems:
    a, b = s_ref.results[name], s_mesh.results[name]
    np.testing.assert_array_equal(np.asarray(a.state.genes),
                                  np.asarray(b.state.genes), err_msg=name)
    np.testing.assert_array_equal(a.pareto_objs, b.pareto_objs, err_msg=name)
print("SWEEP_MESH_OK")

# --- engine e2e: --mesh run == single-device oracle run ---------------------
rcfg = dict(pop_size=32, n_generations=6, seed=3)
r_ref = search.run_search(prob_tree, search.SearchConfig(**rcfg))
r_mesh = search.run_search(prob_tree, search.SearchConfig(mesh="4", **rcfg))
for name in ("genes", "objs", "rank", "crowd"):
    np.testing.assert_array_equal(np.asarray(getattr(r_ref.state, name)),
                                  np.asarray(getattr(r_mesh.state, name)),
                                  err_msg=f"engine {name}")
np.testing.assert_array_equal(r_ref.pareto_objs, r_mesh.pareto_objs)
print("ENGINE_MESH_OK", r_mesh.n_dispatches)

# --- island checkpoint resumed onto a mesh of different devices -------------
fit = search.make_fitness(prob_tree, "reference")
icfg = dist.IslandConfig(local_pop=16, migrate_every=2, n_migrate=2)
devs = jax.devices()
mesh_a = Mesh(np.array(devs[:4]).reshape(4), ("data",))
mesh_b = Mesh(np.array(devs[4:]).reshape(4), ("data",))
st0 = dist.init_islands(jax.random.PRNGKey(5), fit, prob_tree.n_genes,
                        mesh_a, icfg)
chunk_a = dist.make_island_chunk(fit, mesh_a, icfg, 2)
mid = chunk_a(st0)
want = chunk_a(mid)  # uninterrupted continuation on mesh A
with tempfile.TemporaryDirectory() as td:
    checkpoint.save(td, 2, mid)
    restored, step = checkpoint.restore(
        td, 2, jax.device_get(mid),
        shardings=dist.island_state_sharding(mesh_b))
assert step == 2
got = dist.make_island_chunk(fit, mesh_b, icfg, 2)(restored)
used = {d for a in jax.tree.leaves(got) for d in a.devices()}
assert used <= set(devs[4:]), f"resumed run not on the new mesh: {used}"
for f in ("genes", "objs", "rank", "crowd", "key", "generation"):
    np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                  np.asarray(getattr(want, f)),
                                  err_msg=f"resharded islands {f}")
print("RESHARD_OK")
print("ALL_SHARDED_OK")
"""


def _run_subprocess_suite(script, sentinel):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-3000:]
    assert sentinel in res.stdout, res.stdout[-3000:]


@pytest.mark.slow
def test_multidevice_suite():
    _run_subprocess_suite(SCRIPT, "ALL_MULTIDEVICE_OK")


@pytest.mark.slow
def test_sharded_search_suite():
    _run_subprocess_suite(SCRIPT_SHARDED, "ALL_SHARDED_OK")

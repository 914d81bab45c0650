#!/usr/bin/env python3
"""Bring-up check: the search, serving and fault path, compiled on a TPU.

    python3 chip_smoke.py               # one chip: search, serving, faults
    python3 chip_smoke.py --four-chips  # mesh-sharded search on four chips

One process drives every phase, through the entry points a user calls:

1. search — `run_search` with the fused Pallas fitness kernel on the HAR
   tree (561 features, 6 classes, 588 comparators, 1,765 genes, 3,090 test
   samples) at ``pop_size=256``, so the 512-row NSGA-II pool takes the
   Pallas domination kernel; then the pendigits 4-tree forest (872
   comparators), whose leaf axis is tiled under the VMEM budget. Each
   kernel run must equal a ``backend="reference"`` run array for array.
2. serving — `ClassifyServer.from_artifact` on the HAR run's best
   ``pareto.json`` point serves the test split in requests of 1, 64 and
   1,000 rows; every request must equal `core.netlist.simulate` on the same
   design, and the served accuracy the recorded one.
3. faults — `run_campaign` on that point (exhaustive single stuck-at plus a
   small Monte-Carlo draw); the zero-fault lane must equal `simulate`.

``--four-chips`` runs only a ``make_search_mesh("4")`` population-sharded
kernel search at ``pop_size=1024`` (512 pool rows per shard, so the
hierarchical domination kernel engages) and the single-device run it must
equal bit for bit.

There is no CPU fallback: off a TPU the script exits non-zero with a
one-line error before any work. Every failed check raises. Earlier lines
print the jax version, the device, and each phase's wall and compile time;
the last line is the JSON verdict read by automation.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "runs" / "chip_smoke"
SEED = 0
GENERATIONS = 10
CHECKPOINT_EVERY = 5
REQUEST_SIZES = (1, 64, 1000)
MC_TRIALS = 8


def _fail(msg: str) -> None:
    print(f"chip_smoke: error: {msg}", file=sys.stderr)
    raise SystemExit(2)


class CompileClock:
    """Sums jax's trace, lowering and backend-compile durations."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, monitoring):
        self.total = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.total += duration


class Phase:
    """Prints one phase's wall and compile seconds when it ends cleanly."""

    def __init__(self, name: str, clock: CompileClock):
        self.name, self.clock = name, clock

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.total
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            print(f"[{self.name}] wall {time.perf_counter() - self.t0:.2f}s, "
                  f"compile {self.clock.total - self.c0:.2f}s", flush=True)
        return False


def _assert_equal(name: str, got, want) -> None:
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or not np.array_equal(got, want):
        n_bad = (int((got != want).sum()) if got.shape == want.shape
                 else "shape")
        raise AssertionError(f"{name}: arrays differ ({n_bad})")


def _assert_kernel_lowered(name: str, problem, pop_size: int) -> None:
    """The kernel backend's fitness step lowers to a Mosaic kernel."""
    import jax
    import jax.numpy as jnp
    from repro import search

    fitness = search.make_fitness(problem, "kernel", interpret=False)
    pop = jax.ShapeDtypeStruct((pop_size, problem.n_genes), jnp.float32)
    if "tpu_custom_call" not in fitness.lower(pop).as_text():
        raise AssertionError(f"{name}: lowered fitness step has no Pallas "
                             f"kernel (tpu_custom_call)")


def _search_pair(name: str, problem, clock, **cfg):
    """Kernel run vs reference run of one problem, same seed."""
    import jax
    from repro import search

    _assert_kernel_lowered(name, problem, cfg["pop_size"])
    runs = {}
    for backend in ("kernel", "reference"):
        with Phase(f"search {name} {backend}", clock):
            res = search.run_search(
                problem, search.SearchConfig(
                    backend=backend, seed=SEED, n_generations=GENERATIONS,
                    checkpoint_every=CHECKPOINT_EVERY, interpret=False,
                    out_dir=str(OUT / f"{name}_{backend}"),
                    dataset=name.split("_")[0], **cfg))
            jax.block_until_ready(res.state.objs)
        runs[backend] = res
        print(f"  {backend}: {len(res.pareto_objs)} pareto points, "
              f"{res.n_dispatches} dispatches", flush=True)
    k, r = runs["kernel"].state, runs["reference"].state
    for field in ("objs", "genes", "rank", "crowd"):
        _assert_equal(f"{name} kernel vs reference {field}",
                      getattr(k, field), getattr(r, field))
    print(f"  {name}: kernel == reference (objs, genes, rank, crowd) over "
          f"{GENERATIONS} generations", flush=True)
    return runs["kernel"]


def phase_search(clock):
    from repro.families import get_family

    fam = get_family("tree")
    with Phase("build har tree", clock):
        har = fam.build_problem("har")
    print(f"  har: {fam.describe(har)}, {har.n_genes} genes, "
          f"{har.y.shape[0]} test samples", flush=True)
    _search_pair("har_tree", har, clock, pop_size=256)
    with Phase("build pendigits forest[4]", clock):
        pen = fam.build_problem("pendigits", n_trees=4)
    print(f"  pendigits: {fam.describe(pen)}", flush=True)
    _search_pair("pendigits_forest4", pen, clock, pop_size=256)
    return har


def phase_serving(har, clock):
    import numpy as np
    from repro import search
    from repro.core import netlist
    from repro.families import get_family
    from repro.runtime.classify import ClassifyServer

    artifact = search.load_pareto_artifact(
        str(OUT / "har_tree_kernel" / "pareto.json"))
    with Phase("serve har", clock):
        server = ClassifyServer.from_artifact(
            artifact, point="best", backend="kernel", interpret=False)
        idx = server.point_index
        circuit = get_family("tree").build_point_circuit(artifact, idx)
        codes = np.asarray(har.x8)
        y = np.asarray(har.y)
        preds = np.zeros(codes.shape[0], np.int64)
        lo = n_req = 0
        while lo < codes.shape[0]:
            size = REQUEST_SIZES[n_req % len(REQUEST_SIZES)]
            chunk = codes[lo:lo + size]
            out = server.classify_codes(chunk)
            _assert_equal(f"request {n_req} rows [{lo}, {lo + len(chunk)})",
                          out, netlist.simulate(circuit, chunk))
            preds[lo:lo + len(chunk)] = out
            lo += len(chunk)
            n_req += 1
    acc = float((preds == y).mean())
    recorded = artifact.point_accuracy(idx)
    if abs(acc - recorded) > 1e-6:
        raise AssertionError(f"served accuracy {acc:.6f} != recorded "
                             f"{recorded:.6f}")
    print(f"  point {idx}: {n_req} requests (sizes {REQUEST_SIZES}), every "
          f"one == netlist.simulate; buckets {server.compiled_buckets()}; "
          f"accuracy {acc:.4f} == recorded {recorded:.4f}", flush=True)
    return artifact


def phase_faults(har, artifact, clock):
    import numpy as np
    from repro.search import robustness

    with Phase("faults har", clock):
        payload = robustness.run_campaign(
            artifact, np.asarray(har.x8), np.asarray(har.y),
            source=str(OUT / "har_tree_kernel" / "pareto.json"),
            dataset="har", point="best", n_trials=MC_TRIALS)
    row = payload["points"][0]
    if not row["zero_fault_matches_simulate"]:
        raise AssertionError("zero-fault lane != netlist.simulate")
    sf = row["single_fault"]
    print(f"  point {row['point']}: {row['n_gates']} gates, "
          f"{row['n_faults']} single faults, zero-fault lane == "
          f"netlist.simulate; baseline {row['baseline_accuracy']:.4f}, "
          f"1-fault worst {sf['worst_accuracy']:.4f}, MC({MC_TRIALS}) "
          f"{row['monte_carlo']['expected_accuracy']:.4f}", flush=True)


def phase_four_chips(clock):
    import jax
    from repro import search
    from repro.families import get_family

    if len(jax.devices()) < 4:
        _fail(f"--four-chips needs 4 devices, found {len(jax.devices())}")
    fam = get_family("tree")
    with Phase("build pendigits forest[4]", clock):
        problem = fam.build_problem("pendigits", n_trees=4)
    runs = {}
    for mesh in ("4", None):
        with Phase(f"search mesh={mesh}", clock):
            res = search.run_search(
                problem, search.SearchConfig(
                    backend="kernel", pop_size=1024, seed=SEED,
                    n_generations=GENERATIONS,
                    checkpoint_every=CHECKPOINT_EVERY, interpret=False,
                    mesh=mesh, out_dir=str(OUT / f"mesh_{mesh}"),
                    dataset="pendigits"))
            jax.block_until_ready(res.state.objs)
        runs[mesh] = res
    sharded = runs["4"].state
    shards = sharded.genes.addressable_shards
    devices = {s.device for s in shards}
    rows = sorted({s.data.shape[0] for s in shards})
    if len(devices) != 4 or rows != [1024 // 4]:
        raise AssertionError(f"sharded state is not spread over 4 devices: "
                             f"{len(devices)} devices, shard rows {rows}")
    for field in ("objs", "genes", "rank", "crowd"):
        _assert_equal(f"mesh=4 vs single-device {field}",
                      getattr(sharded, field),
                      getattr(runs[None].state, field))
    print(f"  mesh=4 ({len(devices)} devices x {rows[0]} rows) == "
          f"single device (objs, genes, rank, crowd) over {GENERATIONS} "
          f"generations", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh-sharded search on 4 chips and "
                         "its single-device comparison")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"no repro package under {ROOT / 'src'}; run from a checkout")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        _fail(f"no TPU: jax found {dev.platform} devices; this check does "
              f"not fall back to the CPU")
    from repro.runtime import compile_cache

    cache_dir = compile_cache.configure()
    print(f"jax {jax.__version__}; device {dev.device_kind} x "
          f"{len(jax.devices())}; compile cache {cache_dir}", flush=True)
    clock = CompileClock(jax.monitoring)
    shutil.rmtree(OUT, ignore_errors=True)

    if args.four_chips:
        phase_four_chips(clock)
    else:
        har = phase_search(clock)
        artifact = phase_serving(har, clock)
        phase_faults(har, artifact, clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

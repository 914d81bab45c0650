"""CLI for the unified search engine.

    PYTHONPATH=src python -m repro.search --dataset seeds
    PYTHONPATH=src python -m repro.search --dataset seeds --trees 4 \
        --backend kernel --pop 64 --gens 40 --out runs/seeds_forest
    PYTHONPATH=src python -m repro.search sweep --datasets all --report
    PYTHONPATH=src python -m repro.search serve --pareto OUT/pareto.json
    PYTHONPATH=src python -m repro.search faults --pareto OUT/pareto.json

The `serve` subcommand loads a searched design back out of `pareto.json`
and serves feature-vector queries through `runtime.classify.ClassifyServer`
(power-of-two batch buckets + donated ping-pong buffers, DESIGN.md §14),
asserting the served accuracy reproduces the artifact's recorded point and
— with `--verify-netlist` — that every prediction is bit-exact against the
gate-level netlist simulator.

The `sweep` subcommand runs the paper's whole multi-dataset campaign as a
handful of vmapped programs (DESIGN.md §11): problems are padded to bucket
boundaries, stacked, and advanced with one device dispatch per bucket per
stage; per-dataset `pareto.json` artifacts land under `OUT/<dataset>/` and
`--report` scores every dataset against the paper's Tables I/II
(`OUT/sweep_report.json` + `OUT/REPORT.md`).

Trains the exact bespoke tree (or a bootstrap forest with --trees K), runs
the NSGA-II dual-approximation search on the selected backend, prints the
pareto front and the best design under the 1% accuracy-loss budget, and —
with --out — writes pareto.json plus the bespoke Verilog of the selected
design (trees AND forests: per-tree modules + the majority-vote adder tree,
DESIGN.md §10). `--emit-rtl` additionally writes every pareto point's
Verilog under OUT/rtl/; `--verify-rtl` simulates each point's gate-level
netlist over the full test set and asserts bit-exactness against the tensor
program and the kernel backend. `--checkpoint-every N --resume` gives
kill-safe long runs on every backend (islands included); see the README's
CLI reference for the flag-by-flag walkthrough.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.core import area
from repro.datasets import DATASET_SPECS, load_dataset
from repro import search
from repro.runtime import compile_cache


def _load_artifact_or_exit(path: str):
    """Load a pareto.json for a CLI, or exit(2) with a one-line error.

    A missing, truncated, or schema-violating artifact is an operator
    mistake, not a bug — so the CLIs report the named error on stderr and
    exit non-zero instead of dumping a traceback.
    """
    import sys

    try:
        return search.load_pareto_artifact(path)
    except (OSError, ValueError) as e:
        msg = str(e).strip() or type(e).__name__
        print(f"error: pareto artifact {path}: {type(e).__name__}: {msg}",
              file=sys.stderr)
        raise SystemExit(2)


def sweep_main(argv=None) -> None:
    """`python -m repro.search sweep`: the batched full-suite campaign."""
    from repro.search import sweep as sweep_mod

    ap = argparse.ArgumentParser(prog="python -m repro.search sweep")
    ap.add_argument("--datasets", default="all",
                    help="comma-separated dataset names, or 'all' for the "
                         "paper's full 10-dataset suite")
    ap.add_argument("--trees", type=int, default=1,
                    help="1 = single bespoke DT per dataset; K>1 = bootstrap "
                         "forest per dataset (joint chromosome)")
    ap.add_argument("--mlp-datasets", default="",
                    help="comma-separated datasets to ALSO search as printed "
                         "MLPs (campaign keys suffixed _mlp); the bucket "
                         "planner keeps families in separate buckets")
    ap.add_argument("--hidden", type=int, default=16,
                    help="printed-MLP hidden-layer width for --mlp-datasets")
    ap.add_argument("--pop", type=int, default=64)
    ap.add_argument("--gens", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="runs/sweep",
                    help="artifact root: per-dataset pareto.json under "
                         "OUT/<dataset>/, report at OUT/sweep_report.json")
    ap.add_argument("--max-buckets", type=int,
                    default=sweep_mod.DEFAULT_MAX_BUCKETS,
                    help="merge shape buckets down to at most this many "
                         "vmapped programs")
    ap.add_argument("--serial", action="store_true",
                    help="run the per-problem serial loop (the bit-exact "
                         "oracle the vmapped path is tested against)")
    ap.add_argument("--mesh", default=None,
                    help="device mesh spec (DESIGN.md §13): 'KxN' = K-way "
                         "bucket axis x N-way population axis, 'N'/'auto' = "
                         "population axis only; default: single device")
    ap.add_argument("--compilation-cache", default=None, metavar="DIR",
                    help="persistent XLA compilation cache directory "
                         "(default: $JAX_COMPILATION_CACHE_DIR, else "
                         "<checkout>/.jax_cache): re-runs skip recompiling "
                         "every bucket shape")
    ap.add_argument("--emit-rtl", action="store_true",
                    help="write every pareto point's Verilog under "
                         "OUT/<dataset>/rtl/")
    ap.add_argument("--verify-rtl", action="store_true",
                    help="netlist-simulate every pareto point of every "
                         "dataset and assert bit-exactness vs the tensor "
                         "program and the kernel backend")
    ap.add_argument("--report", action="store_true",
                    help="score the campaign against paper Tables I/II "
                         "(OUT/sweep_report.json + OUT/REPORT.md)")
    ap.add_argument("--fault-report", action="store_true",
                    help="run the stuck-at robustness campaign on every "
                         "dataset's best-under-loss point (DESIGN.md §17): "
                         "OUT/<dataset>/fault_report.json + a robustness-"
                         "vs-area section in REPORT.md")
    ap.add_argument("--max-loss", type=float, default=0.01)
    args = ap.parse_args(argv)

    names = (sorted(DATASET_SPECS) if args.datasets == "all"
             else [n.strip() for n in args.datasets.split(",") if n.strip()])
    mlp_names = [n.strip() for n in args.mlp_datasets.split(",") if n.strip()]
    unknown = [n for n in names + mlp_names if n not in DATASET_SPECS]
    if unknown:
        ap.error(f"unknown datasets: {unknown}; options: "
                 f"{sorted(DATASET_SPECS)}")
    compile_cache.configure(args.compilation_cache)

    kind = "tree" if args.trees <= 1 else f"forest[{args.trees}]"
    extra = (f" + {len(mlp_names)} printed-MLP datasets" if mlp_names else "")
    print(f"== sweep: {len(names)} datasets, {kind} per dataset{extra}, "
          f"pop={args.pop} gens={args.gens} ==")
    problems = sweep_mod.build_problems(names, n_trees=args.trees,
                                        verbose=True,
                                        mlp_datasets=mlp_names,
                                        n_hidden=args.hidden)

    cfg = sweep_mod.SweepConfig(
        pop_size=args.pop, n_generations=args.gens, seed=args.seed,
        vmapped=not args.serial, max_buckets=args.max_buckets,
        mesh=args.mesh, out_dir=args.out, emit_rtl=args.emit_rtl,
        verify_rtl=args.verify_rtl)
    sweep = sweep_mod.run_sweep(problems, cfg)

    for i, run in enumerate(sweep.bucket_runs):
        d = run.bucket.dims
        if run.bucket.family == "tree":
            dims_s = f"(N={d[0]}, L={d[1]}, C={d[2]}, F={d[3]}, B={d[4]})"
        else:
            dims_s = f"(H={d[0]}, C={d[1]}, F={d[2]}, B={d[3]})"
        print(f"bucket {i}: [{run.bucket.family}] "
              f"{', '.join(run.bucket.names)} -> padded {dims_s}, "
              f"{run.n_dispatches} dispatches, {run.wall_s:.1f}s")
    print(f"campaign: {sweep.n_dispatches} dispatches over "
          f"{len(sweep.bucket_runs)} buckets (serial per-dataset baseline: "
          f"{sweep.serial_baseline_dispatches()}), wall {sweep.wall_s:.1f}s")

    for name in sorted(sweep.results):
        result = sweep.results[name]
        problem = problems[name]
        best = result.best_under_loss(args.max_loss)
        if best is None:
            line = f"no design within {args.max_loss:.0%} loss"
        else:
            o, _ = best
            a_mm2 = float(o[1]) * problem.exact_area_mm2
            line = (f"@<={args.max_loss:.0%} loss: {1 / max(float(o[1]), 1e-9):.2f}x "
                    f"smaller, {a_mm2:.1f}mm^2, "
                    f"{area.power_mw(a_mm2):.2f}mW")
        print(f"  {name}: exact_acc={problem.exact_accuracy:.3f} "
              f"pareto={len(result.pareto_objs)} pts; {line}")
    if args.verify_rtl:
        n_pts = sum(len(r.pareto_objs) for r in sweep.results.values())
        print(f"RTL verified: {n_pts} pareto points across {len(problems)} "
              f"problems (netlist sim == tensor predict == kernel route)")

    if args.fault_report:
        import os

        from repro.search import robustness

        print(f"== fault campaign: best point per dataset, defect_rate="
              f"{robustness.DEFAULT_DEFECT_RATE:.0%}, "
              f"{robustness.DEFAULT_TRIALS} MC trials ==")
        for name in sorted(sweep.results):
            pareto_path = os.path.join(args.out, name, "pareto.json")
            if not os.path.exists(pareto_path):
                continue
            artifact = search.load_pareto_artifact(pareto_path)
            problem = problems[name]
            x8 = np.asarray(problem.x8)
            y = np.asarray(problem.y)
            try:
                payload = robustness.run_campaign(
                    artifact, x8, y, source=pareto_path,
                    dataset=name, point="best", max_loss=args.max_loss)
            except ValueError as e:   # e.g. no point within the budget
                print(f"  {name}: skipped ({e})")
                continue
            out_path = robustness.write_fault_report(
                payload, os.path.join(args.out, name, "fault_report.json"))
            row = payload["points"][0]
            print(f"  {name}: point {row['point']} "
                  f"({row['n_sites']} sites) baseline "
                  f"{row['baseline_accuracy']:.4f} -> 1-fault worst "
                  f"{row['single_fault']['worst_accuracy']:.4f}, "
                  f"MC {row['monte_carlo']['expected_accuracy']:.4f} "
                  f"-> {out_path}")

    if args.report:
        meta = {"datasets": args.datasets, "trees": args.trees,
                "pop": args.pop, "gens": args.gens, "seed": args.seed,
                "mode": "serial" if args.serial else "vmapped"}
        if mlp_names:
            meta["mlp_datasets"] = args.mlp_datasets
            meta["hidden"] = args.hidden
        json_path, md_path = sweep_mod.write_sweep_report(
            sweep, problems, args.out, meta=meta, max_loss=args.max_loss)
        print(f"report: {json_path} + {md_path}")
    print(f"artifacts: {args.out}/<dataset>/pareto.json")


def serve_main(argv=None) -> None:
    """`python -m repro.search serve`: serve a pareto.json design under load.

    Loads a `pareto.json` point (the artifact is self-contained —
    DESIGN.md §14), stands up `runtime.classify.ClassifyServer`, and
    serves the recorded dataset's test split in request batches: reports
    throughput and the served accuracy, asserts it matches the artifact's
    recorded per-point accuracy, and with `--verify-netlist` additionally
    asserts every served prediction bit-exact against the gate-level
    netlist simulator (the serving oracle triangle).
    """
    import sys
    import time

    from repro.core import netlist
    from repro.runtime.classify import BACKENDS as SERVE_BACKENDS
    from repro.runtime.classify import ClassifyServer

    ap = argparse.ArgumentParser(prog="python -m repro.search serve")
    ap.add_argument("--pareto", required=True,
                    help="path to a pareto.json written by run_search/sweep")
    ap.add_argument("--point", default="best",
                    help="pareto point index, or 'best' = smallest area "
                         "within --max-loss")
    ap.add_argument("--max-loss", type=float, default=0.01)
    ap.add_argument("--dataset", default=None,
                    help="dataset whose test split to serve (default: the "
                         "artifact's recorded dataset)")
    ap.add_argument("--backend", default="kernel", choices=SERVE_BACKENDS,
                    help="kernel = fused Pallas inference; reference = "
                         "pure-jnp predict_votes dataflow")
    ap.add_argument("--batch", type=int, default=64,
                    help="request size: the test split is served in batches "
                         "of this many feature vectors")
    ap.add_argument("--max-batch", type=int, default=1024,
                    help="largest power-of-two batch bucket")
    ap.add_argument("--repeats", type=int, default=1,
                    help="serve the test split this many times (throughput "
                         "measurement)")
    ap.add_argument("--verify-netlist", action="store_true",
                    help="simulate the served design's gate-level netlist "
                         "over every served batch and assert bit-exactness")
    ap.add_argument("--compilation-cache", default=None, metavar="DIR",
                    help="persistent XLA compilation cache directory "
                         "(default: $JAX_COMPILATION_CACHE_DIR, else "
                         "<checkout>/.jax_cache)")
    args = ap.parse_args(argv)
    compile_cache.configure(args.compilation_cache)

    artifact = _load_artifact_or_exit(args.pareto)
    point = args.point if args.point == "best" else int(args.point)
    server = ClassifyServer.from_artifact(
        artifact, point=point, max_loss=args.max_loss,
        backend=args.backend, max_batch=args.max_batch)
    idx = server.point_index
    pt = artifact.points[idx]
    family = getattr(artifact, "family", "tree")
    if family == "mlp":
        design = (f"printed MLP {artifact.n_features}-"
                  f"{artifact.n_hidden}-{artifact.n_classes}")
    else:
        design = (f"{artifact.n_trees} tree(s), "
                  f"{artifact.n_comparators} comparators")
    print(f"== serving {args.pareto} point {idx}: {design}, "
          f"acc_loss={pt['acc_loss']:+.4f} "
          f"norm_area={pt['norm_area']:.3f} backend={args.backend} ==")

    dataset = args.dataset or artifact.dataset
    if dataset is None:
        ap.error("--dataset required: this artifact predates the recorded "
                 "'dataset' label")
    ds = load_dataset(dataset)
    codes = server.featurize(ds.x_test)
    y = ds.y_test.astype(np.int64)

    circuit = None
    if args.verify_netlist:
        from repro.families import get_family
        circuit = get_family(family).build_point_circuit(artifact, idx)

    n = codes.shape[0]
    preds = np.zeros(n, np.int64)
    n_requests = 0
    n_verified = 0
    t0 = time.perf_counter()
    for _ in range(max(1, args.repeats)):
        for lo in range(0, n, args.batch):
            chunk = codes[lo:lo + args.batch]
            out = server.classify_codes(chunk)
            preds[lo:lo + args.batch] = out
            n_requests += 1
            if circuit is not None:
                sim = np.asarray(netlist.simulate(circuit, chunk))
                if not np.array_equal(sim, out):
                    print(f"FAIL: request at rows [{lo}, {lo + len(out)}) "
                          f"diverges from the netlist oracle on "
                          f"{int((sim != out).sum())} rows")
                    sys.exit(1)
                n_verified += len(out)
    wall = time.perf_counter() - t0

    acc = float((preds == y).mean())
    recorded = artifact.point_accuracy(idx)
    total = n * max(1, args.repeats)
    print(f"served {total} samples in {n_requests} requests "
          f"({wall:.3f}s, {total / max(wall, 1e-9):,.0f} samples/s, "
          f"{n_requests / max(wall, 1e-9):,.0f} requests/s)")
    print(f"buckets compiled: {server.compiled_buckets()} "
          f"(steps per bucket: {server.stats.steps_per_bucket})")
    print(f"served accuracy on {dataset} test split: {acc:.4f} "
          f"(artifact recorded {recorded:.4f})")
    if abs(acc - recorded) > 1e-6:
        print(f"FAIL: served accuracy {acc:.6f} != recorded "
              f"{recorded:.6f} — the loaded design does not reproduce "
              f"the searched point")
        sys.exit(1)
    if circuit is not None:
        print(f"netlist oracle: {n_verified} served predictions bit-exact "
              f"vs the gate-level simulation")


def faults_main(argv=None) -> None:
    """`python -m repro.search faults`: stuck-at robustness campaign.

    Loads a `pareto.json`, rebuilds the selected point(s)' gate-level
    circuits through the family registry, and runs the DESIGN.md §17
    campaign — exhaustive single stuck-at over every fault site,
    Monte-Carlo defect draws under fixed PRNG keys, and the critical-gate
    ranking — writing a validated `fault_report.json` next to the artifact
    (or to --out).
    """
    import os

    from repro.datasets import quantize_u8
    from repro.search import robustness

    ap = argparse.ArgumentParser(prog="python -m repro.search faults")
    ap.add_argument("--pareto", required=True,
                    help="path to a pareto.json written by run_search/sweep")
    ap.add_argument("--point", default="all",
                    help="pareto point index, 'best' = smallest area within "
                         "--max-loss, or 'all' (default)")
    ap.add_argument("--max-loss", type=float, default=0.01)
    ap.add_argument("--dataset", default=None,
                    help="dataset whose test split drives the campaign "
                         "(default: the artifact's recorded dataset)")
    ap.add_argument("--defect-rate", type=float,
                    default=robustness.DEFAULT_DEFECT_RATE,
                    help="Monte-Carlo iid per-site defect probability")
    ap.add_argument("--trials", type=int, default=robustness.DEFAULT_TRIALS,
                    help="Monte-Carlo defect draws per point")
    ap.add_argument("--mc-seed", type=int,
                    default=robustness.DEFAULT_MC_SEED,
                    help="PRNG seed for the Monte-Carlo masks (fixed seed "
                         "-> bit-reproducible report)")
    ap.add_argument("--top-k", type=int, default=robustness.DEFAULT_TOP_K,
                    help="critical gates reported per point")
    ap.add_argument("--chunk", type=int, default=None,
                    help="fault lanes per vmapped dispatch (default: "
                         "auto-sized to the memory budget)")
    ap.add_argument("--out", default=None,
                    help="fault_report.json path (default: next to --pareto)")
    args = ap.parse_args(argv)
    compile_cache.configure()

    artifact = _load_artifact_or_exit(args.pareto)
    dataset = args.dataset or artifact.dataset
    if dataset is None:
        ap.error("--dataset required: this artifact predates the recorded "
                 "'dataset' label")
    ds_name = dataset.removesuffix("_mlp")
    if ds_name not in DATASET_SPECS:
        ap.error(f"unknown dataset {ds_name!r}; options: "
                 f"{sorted(DATASET_SPECS)}")
    ds = load_dataset(ds_name)
    x8 = quantize_u8(ds.x_test)
    y = np.asarray(ds.y_test, np.int64)

    family = getattr(artifact, "family", "tree")
    print(f"== fault campaign: {args.pareto} [{family}] on {ds_name} "
          f"({x8.shape[0]} test vectors), point={args.point}, "
          f"defect_rate={args.defect_rate:.2%}, {args.trials} MC trials, "
          f"seed={args.mc_seed} ==")
    try:
        payload = robustness.run_campaign(
            artifact, x8, y, source=args.pareto, dataset=dataset,
            point=args.point, max_loss=args.max_loss,
            defect_rate=args.defect_rate, n_trials=args.trials,
            seed=args.mc_seed, top_k=args.top_k, chunk=args.chunk,
            verbose=True)
    except ValueError as e:
        import sys

        print(f"error: fault campaign: {e}", file=sys.stderr)
        raise SystemExit(2)
    out = args.out or os.path.join(
        os.path.dirname(args.pareto) or ".", "fault_report.json")
    robustness.write_fault_report(payload, out)
    worst = min(p["single_fault"]["worst_accuracy"]
                for p in payload["points"])
    print(f"campaign: {len(payload['points'])} point(s), worst single-fault "
          f"accuracy {worst:.4f}; report: {out}")


def main(argv=None) -> None:
    import sys

    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "sweep":
        return sweep_main(argv[1:])
    if argv and argv[0] == "serve":
        return serve_main(argv[1:])
    if argv and argv[0] == "faults":
        return faults_main(argv[1:])
    ap = argparse.ArgumentParser(prog="python -m repro.search")
    ap.add_argument("--dataset", default="seeds",
                    choices=sorted(DATASET_SPECS))
    ap.add_argument("--family", default="tree", choices=("tree", "mlp"),
                    help="classifier family to search (DESIGN.md §15): "
                         "bespoke decision trees/forests, or integer-weight "
                         "printed MLPs")
    ap.add_argument("--trees", type=int, default=1,
                    help="tree family: 1 = single bespoke DT; K>1 = "
                         "bootstrap forest with a joint 3*sum(N_k)+1-gene "
                         "chromosome (DESIGN.md §16)")
    ap.add_argument("--hidden", type=int, default=16,
                    help="mlp family: hidden-layer width")
    ap.add_argument("--backend", default="reference",
                    choices=list(search.BACKENDS))
    ap.add_argument("--mesh", default=None,
                    help="device mesh spec (DESIGN.md §13): 'N' or 'auto' "
                         "shards the population axis over N / all devices "
                         "(islands: the ring size); default: single device")
    ap.add_argument("--compilation-cache", default=None, metavar="DIR",
                    help="persistent XLA compilation cache directory "
                         "(default: $JAX_COMPILATION_CACHE_DIR, else "
                         "<checkout>/.jax_cache)")
    ap.add_argument("--block-p", type=int, default=8,
                    help="kernel backend: chromosomes per fused-fitness grid "
                         "cell (population-axis tile, DESIGN.md §12)")
    ap.add_argument("--pop", type=int, default=64)
    ap.add_argument("--gens", type=int, default=40)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="artifact directory")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="generations between checkpoint saves (0 = off); "
                         "also the lax.scan chunk length, so one interval = "
                         "one device dispatch")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest checkpoint under "
                         "OUT/ckpt (all backends, islands included)")
    ap.add_argument("--migrate-every", type=int, default=5,
                    help="islands backend: generations between ring "
                         "migrations (checkpoints land on round boundaries)")
    ap.add_argument("--n-migrate", type=int, default=4,
                    help="islands backend: elites migrated per round")
    ap.add_argument("--max-loss", type=float, default=0.01)
    ap.add_argument("--emit-rtl", action="store_true",
                    help="write every pareto point's Verilog under OUT/rtl/ "
                         "(single trees and forests alike)")
    ap.add_argument("--verify-rtl", action="store_true",
                    help="netlist-simulate every pareto point over the full "
                         "test set and assert bit-exactness vs the tensor "
                         "program and the kernel backend")
    args = ap.parse_args(argv)
    if (args.emit_rtl or args.verify_rtl) and not args.out:
        ap.error("--emit-rtl/--verify-rtl require --out")
    compile_cache.configure(args.compilation_cache)

    from repro.families import get_family

    fam = get_family(args.family)
    if args.family == "mlp":
        problem = fam.build_problem(args.dataset, n_hidden=args.hidden)
        kind = f"mlp[h={args.hidden}]"
    else:
        problem = fam.build_problem(args.dataset, n_trees=args.trees)
        kind = "tree" if args.trees <= 1 else f"forest[{args.trees}]"

    print(f"== {args.dataset} {fam.describe(problem)} "
          f"exact_area={problem.exact_area_mm2:.1f}mm^2 "
          f"power={area.power_mw(problem.exact_area_mm2):.2f}mW ==")

    cfg = search.SearchConfig(
        backend=args.backend, block_p=args.block_p, pop_size=args.pop,
        n_generations=args.gens, seed=args.seed, mesh=args.mesh,
        dataset=args.dataset, out_dir=args.out,
        checkpoint_every=args.checkpoint_every, resume=args.resume,
        migrate_every=args.migrate_every, n_migrate=args.n_migrate,
        emit_rtl=args.emit_rtl, verify_rtl=args.verify_rtl,
    )
    print(f"== run_search backend={cfg.backend} pop={cfg.pop_size} "
          f"gens={cfg.n_generations} ==")
    result = search.run_search(problem, cfg)

    print(f"search wall time: {result.wall_s:.1f}s "
          f"({result.n_evaluations} chromosome evaluations, "
          f"{result.n_dispatches} device dispatches)")
    print("pareto front (acc_loss, normalized area):")
    for o in result.pareto_objs:
        print(f"  {o[0]:+.4f}  {o[1]:.3f}  ({1 / max(o[1], 1e-9):.2f}x smaller)")

    best = result.best_under_loss(args.max_loss)
    if best is None:
        print(f"no design within {args.max_loss:.0%} accuracy loss")
    else:
        o, genes = best
        a_mm2 = float(o[1]) * problem.exact_area_mm2
        print(f"\nselected @<={args.max_loss:.0%} loss: area={a_mm2:.1f}mm^2 "
              f"({1 / o[1]:.2f}x), power={area.power_mw(a_mm2):.2f}mW "
              f"{'< 3mW: printed-battery OK' if area.power_mw(a_mm2) < 3 else ''}")

    if args.out:
        import json
        import os

        import jax.numpy as jnp
        from repro.core import rtl
        if best is not None:
            if args.family == "mlp":
                from repro.core import netlist
                from repro.families import printed_mlp as pm_mod

                bits_a, margin_a = pm_mod.decode_design(np.asarray(genes))
                h = problem.n_hidden
                w1 = pm_mod.effective_weights(problem.w1_master,
                                              bits_a[:h], margin_a[:h])
                w2 = pm_mod.effective_weights(problem.w2_master,
                                              bits_a[h:], margin_a[h:])
                circuit = netlist.build_mlp_circuit(
                    w1, w2, problem.shift, problem.n_classes)
                verilog = rtl.emit_circuit_verilog(
                    circuit, module_name=f"printed_mlp_{args.dataset}")
            else:
                # effective (post-truncation) design: lowering it with
                # trunc=None is identical to lowering the pre-truncation
                # design with its trunc vector (DESIGN.md §16)
                bits, t_int, vote_cap = search.decode_chromosome(
                    problem, jnp.asarray(genes))
                vote_adder = ("approx" if np.isfinite(float(vote_cap))
                              else "exact")
                verilog = rtl.emit_design(search.problem_ptrees(problem),
                                          np.asarray(bits),
                                          np.asarray(t_int),
                                          problem.n_classes,
                                          vote_adder=vote_adder)
            path = os.path.join(args.out, f"bespoke_{args.dataset}.v")
            with open(path, "w") as f:
                f.write(verilog)
            print(f"bespoke {kind} RTL written to {path} "
                  f"({len(verilog.splitlines())} lines)")

        with open(os.path.join(args.out, "pareto.json")) as f:
            artifact = json.load(f)
        pts = artifact["pareto"]
        if args.emit_rtl:
            print(f"per-pareto-point RTL: {args.out}/rtl/ "
                  f"({len(pts)} designs: "
                  f"{', '.join(p['rtl'] for p in pts[:3])}"
                  f"{', ...' if len(pts) > 3 else ''})")
        if args.verify_rtl:
            oracle = ("tensor predict" if args.family == "mlp"
                      else "predict_votes")
            print(f"RTL verified: {len(pts)}/{len(pts)} pareto points "
                  f"bit-exact over {problem.x8.shape[0]} test samples "
                  f"(netlist sim == {oracle} == kernel backend)")
        gaps = search.netlist_area_ratios(pts)
        if gaps:
            print(f"estimated-vs-netlist area: netlist/LUT ratio "
                  f"min {min(gaps):.2f} / mean {sum(gaps) / len(gaps):.2f} / "
                  f"max {max(gaps):.2f} across {len(gaps)} points")
        print(f"pareto artifact: {args.out}/pareto.json")


if __name__ == "__main__":
    main()

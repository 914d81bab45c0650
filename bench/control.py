#!/usr/bin/env python3
"""Readings of the correctness comparison: the program's and the control's.

    python3 bench/control.py --workload har_tree.search_p128_g500 \\
        --seeds 101,102,103 --steps 2

Sets the cell up once, then for each seed runs ``--steps`` units of the
cell's own work (whole campaigns, or fault-lane slices) through the timed
path and compares what they produced with the plain reference, twice: as
produced, and with the control in the program's place. The control of a
search cell is the reference's own objectives computed in bfloat16, the
precision below the float32 the configuration states; that of a fault
cell swaps each lane's stuck polarity, breaking the campaign's guarantee
that lane 2k is site k stuck at 0. One JSON line per seed:
``{"seed", "program": {name: reading}, "control": {name: reading}}``.
A search cell then runs its first three seeds again with a fault planted
in the program, variation that hands back its parents, and prints
``{"seed", "fault": {name: reading}}`` for each. The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import spec
from run import chips, use_compile_cache
from workloads import RUNNERS


def readings(runner, seeds, steps: int):
    import ml_dtypes

    control = (ml_dtypes.bfloat16 if runner.rate_metric == "evals_per_s"
               else True)
    for seed in seeds:
        runner.reseed(seed)
        for k in range(steps):
            runner.step(k)
        produced = runner.collect()
        yield {"seed": seed,
               "program": dict(runner.check(produced)),
               "control": dict(runner.check(produced, control=control))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one short window each")
    ap.add_argument("--steps", type=int, default=1)
    args = ap.parse_args(argv)
    cell = spec.cell(spec.benchmark(), args.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    use_compile_cache()
    chips(cell["chips"])
    seeds = [int(s) for s in args.seeds.split(",")]
    runner = RUNNERS[spec.traffic(cell["traffic"])["kind"]](
        spec.config(cell["config"]), spec.traffic(cell["traffic"]), seeds[0],
        str(spec.ROOT / "runs" / "bench" / (args.workload + "_control")))
    runner.setup()
    for r in readings(runner, seeds, args.steps):
        print(json.dumps(r), flush=True)
    if runner.rate_metric == "evals_per_s":
        from repro.core import nsga2

        nsga2._sbx = lambda key, a, b, *cfg: (a, b)
        nsga2._poly_mutation = lambda key, genes, *cfg: genes
        for seed in seeds[:3]:
            runner.reseed(seed)
            for k in range(args.steps):
                runner.step(k)
            print(json.dumps({"seed": seed, "fault": dict(
                runner.check(runner.collect()))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

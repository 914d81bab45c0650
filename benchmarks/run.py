"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Heavy GA searches are cached
under benchmarks/results/paper/. The chip benchmark is ``bench/run.py``.
"""
from __future__ import annotations

import argparse


def _row(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller GA budgets (smoke use)")
    ap.add_argument("--pop", type=int, default=None)
    ap.add_argument("--gens", type=int, default=None)
    args = ap.parse_args()
    pop = args.pop or (32 if args.quick else 64)
    gens = args.gens or (12 if args.quick else 40)

    from benchmarks import paper_tables

    print("name,us_per_call,derived")

    # ---- Table I: exact bespoke DTs --------------------------------------
    t1 = paper_tables.table1()
    for name, r in t1.items():
        _row(f"table1.{name}", 0.0,
             f"acc={r['accuracy']:.3f};comps={r['n_comparators']};"
             f"area_mm2={r['area_mm2']:.1f};power_mw={r['power_mw']:.2f};"
             f"paper_area={r['paper']['area_mm2']}")

    # ---- Fig. 4: comparator area LUT -------------------------------------
    f4 = paper_tables.fig4()
    import numpy as np
    for p, vals in f4.items():
        _row(f"fig4.p{p}", 0.0,
             f"mean_mm2={np.mean(vals):.3f};zero_area_frac="
             f"{np.mean(np.array(vals) == 0):.3f}")

    # ---- Fig. 5 + Table II: NSGA-II pareto fronts ------------------------
    f5 = paper_tables.fig5_and_table2(pop=pop, gens=gens)
    for name, r in f5.items():
        a1 = r["at_1pct"]
        derived = (f"pareto_n={len(r['pareto'])};search_s={r['search_s']}")
        if a1:
            derived += (f";area_red_1pct={1/a1['norm_area']:.2f}x"
                        f";power_mw={a1['power_mw']:.2f}"
                        f";paper_norm_area={r['paper_at_1pct']['norm_area']}")
        _row(f"fig5.{name}", r["search_s"] * 1e6, derived)
    summary = paper_tables.summarize(f5)
    _row("table2.summary", 0.0,
         f"mean_area_red={summary['mean_area_reduction_1pct']:.2f}x"
         f";mean_power_red={summary['mean_power_reduction_1pct']:.2f}x"
         f";paper=3.2x/3.4x")


if __name__ == "__main__":
    main()

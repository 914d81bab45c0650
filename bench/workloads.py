"""The general generator: one runner per traffic ``kind``, driven by data.

A traffic file (``bench/traffic/<name>.json``) names its ``kind`` and the
parameters of the work; a configuration file (``bench/configs/<name>.json``)
names the dataset, the classifier and its published sizes. A runner builds
the configuration, warms up every shape its traffic uses, then does one
unit of work per `step` (a whole search campaign, or one slice of fault
lanes) until the window closes, and afterwards `check`s what the window
produced against the plain reference (`ref_trees`, `ref_gates`).

Everything a window does is decided by ``--seed``: a search run cycles
through the traffic's fixed campaign seeds from a position drawn from the
seed; a fault run starts at a lane offset drawn from the seed. Every seed
gets the same work in another order: a campaign's length follows its
Pareto front, which its search seed decides.
"""
from __future__ import annotations

import os
import shutil
import time

import numpy as np

import grow
import ref_gates
import ref_trees


def build_tree(config: dict):
    """(problem, reference, tree) for a tree configuration.

    The repository's CART trainer grows the tree to the configuration's
    ``max_depth``; `grow.best_first` cuts it to the published
    ``n_comparators``. The configuration's data (the synthetic test split)
    and the cut tree come to the reference as plain arrays.
    """
    from repro.core.train import TreeArrays, train_tree
    from repro.core.tree import to_parallel
    from repro.datasets import load_dataset
    from repro.search.problem import build_tree_problem

    ds = load_dataset(config["dataset"])
    grown = train_tree(ds.x_train, ds.y_train, ds.n_classes,
                       max_depth=config["max_depth"])
    nodes = grow.best_first(
        {k: getattr(grown, k) for k in ("feature", "threshold", "left",
                                         "right")},
        ds.x_train, ds.y_train, ds.n_classes, config["n_comparators"])
    tree = TreeArrays(**nodes, n_classes=ds.n_classes)
    problem = build_tree_problem(to_parallel(tree), ds.x_test, ds.y_test)
    sizes = {"n_features": problem.n_features,
             "n_classes": problem.n_classes,
             "n_trees": problem.n_trees,
             "n_comparators": problem.n_comparators,
             "n_leaves": problem.n_leaves,
             "n_test": int(problem.y.shape[0]),
             "n_genes": problem.n_genes}
    wrong = {k: (v, config[k]) for k, v in sizes.items() if config[k] != v}
    if wrong:
        raise RuntimeError(f"{config['name']}: built sizes differ from the "
                           f"configuration (built, stated): {wrong}")
    ref = ref_trees.Tree(nodes, ds.x_test, ds.y_test, ds.n_classes)
    return problem, ref, tree


class SearchCampaigns:
    """Back-to-back `run_search` campaigns, each to a written Pareto front.

    Traffic keys: ``backend``, ``pop_size``, ``n_generations``,
    ``checkpoint_every``, ``mesh`` (a `make_search_mesh` spec or null),
    ``campaign_seeds`` (the search seeds the window cycles through).
    """

    rate_metric = "evals_per_s"

    def __init__(self, config: dict, traffic: dict, seed: int, out_dir: str):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.out_dir = out_dir
        self.results = []       # (k, campaign dir, SearchResult, first genes)
        self.spans = {}         # benchmark-side host seconds per program call
        self._first = None      # genes of the running campaign's first save

    def _cfg(self, search_seed: int, out_dir: str, n_generations: int):
        from repro import search

        t = self.traffic
        return search.SearchConfig(
            backend=t["backend"], pop_size=t["pop_size"],
            n_generations=n_generations, seed=search_seed,
            checkpoint_every=t["checkpoint_every"], mesh=t["mesh"],
            interpret=None, out_dir=out_dir, dataset=self.config["dataset"])

    def setup(self) -> None:
        from repro import search
        from repro.runtime import checkpoint

        shutil.rmtree(self.out_dir, ignore_errors=True)
        self.problem, self.ref, _ = build_tree(self.config)
        checkpoint.save = self._keeping_first(
            getattr(checkpoint.save, "__wrapped__", checkpoint.save))
        # one chunk compiles every program a campaign runs: init, the
        # checkpoint-interval scan, the front and the artifact writer
        warm = os.path.join(self.out_dir, "warm")
        search.run_search(self.problem, self._cfg(
            self.traffic["campaign_seeds"][0], warm,
            self.traffic["checkpoint_every"]))
        shutil.rmtree(warm, ignore_errors=True)
        self.reseed(self.seed)

    def reseed(self, seed: int) -> None:
        """Forget the window's work; the next steps cycle through the
        campaign seeds from a position drawn from ``seed``."""
        self.seed, self.results = seed, []
        self.start = int(np.random.default_rng(seed).integers(
            len(self.traffic["campaign_seeds"])))
        shutil.rmtree(self.out_dir, ignore_errors=True)

    def step(self, k: int) -> int:
        from repro import search

        seeds = self.traffic["campaign_seeds"]
        d = os.path.join(self.out_dir, f"c{k}")
        self._first = None
        res = search.run_search(self.problem, self._cfg(
            seeds[(self.start + k) % len(seeds)], d,
            self.traffic["n_generations"]))
        self.results.append((k, d, res, self._first))
        return res.n_evaluations

    def _keeping_first(self, save):
        """``save`` that also keeps the genes of a campaign's first
        checkpoint, which the program deletes later (it keeps the last
        three). The save itself copies the state to the host."""
        import functools

        import jax

        @functools.wraps(save)
        def wrapper(ckpt_dir, step, tree, *a, **kw):
            if self._first is None:
                self._first = np.asarray(jax.device_get(tree.genes))
            return save(ckpt_dir, step, tree, *a, **kw)
        return wrapper

    def counters(self) -> dict:
        t = self.traffic
        n_shards = 1 if not t["mesh"] else int(str(t["mesh"]).split("x")[-1])
        p = self.problem
        return {
            "kind": "search",
            "campaigns": len(self.results),
            "evaluations": sum(r[2].n_evaluations for r in self.results),
            "generations": len(self.results) * t["n_generations"],
            "fitness_calls": len(self.results) * (t["n_generations"] + 1),
            "pop_per_device": t["pop_size"] // n_shards,
            "dims": (int(p.y.shape[0]), p.n_comparators, p.n_leaves,
                     p.n_classes),
            **{f"{k}_s": v[0] for k, v in self.spans.items()},
            **{f"{k}_calls": v[1] for k, v in self.spans.items()},
        }

    def instrument(self, annotate) -> None:
        """Host spans around the program's checkpoint save and artifact
        writer (traced runs only): the program has none of its own."""
        from repro.runtime import checkpoint
        from repro.search import engine

        for mod, name in ((checkpoint, "save"),
                          (engine, "write_pareto_artifact")):
            setattr(mod, name, self._spanned(getattr(mod, name), name,
                                             annotate))

    def _spanned(self, fn, name, annotate):
        def wrapper(*a, **kw):
            t0 = time.perf_counter()
            with annotate(name):
                out = fn(*a, **kw)
            s, n = self.spans.get(name, (0.0, 0))
            self.spans[name] = (s + time.perf_counter() - t0, n + 1)
            return out
        return wrapper

    def collect(self) -> list:
        """Host copies of what each campaign produced, for `check`."""
        import json

        import jax

        out = []
        for k, d, res, first in self.results:
            st = jax.device_get(res.state)
            with open(os.path.join(d, "pareto.json")) as f:
                points = json.load(f)["pareto"]
            out.append({
                "first_genes": first,
                "genes": np.asarray(st.genes), "objs": np.asarray(st.objs),
                "rank": np.asarray(st.rank),
                "generation": int(st.generation),
                "points": [{k: pt[k] for k in ("acc_loss", "norm_area",
                                                "bits", "t_int", "trunc",
                                                "vote_adder")}
                           for pt in points]})
        return out

    def check(self, produced: list, control=None) -> list:
        """[(name, reading)] comparing ``produced`` (from `collect`) with the
        reference. ``control`` (a dtype) replaces the program's objectives
        by the reference's own computed in that precision.

        Besides the objectives, ranks and written front, two numbers tie
        each campaign's end to its progress from its first checkpoint:
        the share of final rows already in that checkpoint (a search whose
        variation hands back its parents makes no new row), and the points of the
        checkpoint's front that no point of the final front weakly
        dominates (elitism keeps or betters every one)."""
        ref = self.ref
        b, n = ref.n_samples, ref.n_comparators
        acc_gap = area_gap = 0.0
        rank_bad = front_bad = gen_gap = carried = regressed = 0
        for c in produced:
            ev = ref.evaluate_genes(c["genes"])
            want = ref.objectives(ev)
            got = (ref.objectives(ev, control) if control is not None
                   else c["objs"])
            keys = ref.keys(ev)
            rank = ref_trees.pareto_ranks(keys)
            pts = c["points"]
            width = np.array([np.asarray(p["bits"]) - np.asarray(p["trunc"])
                              for p in pts], np.int64).reshape(len(pts), n)
            thr = np.array([np.asarray(p["t_int"]) >> np.asarray(p["trunc"])
                            for p in pts], np.int64).reshape(len(pts), n)
            pev = ref.evaluate(width, thr) if pts else None
            pwant = ref.objectives(pev) if pts else np.zeros((0, 2))
            pgot = np.array([[p["acc_loss"], p["norm_area"]] for p in pts],
                            np.float64).reshape(-1, 2)
            if control is not None and pts:
                pgot = ref.objectives(pev, control)
            got = np.asarray(got, np.float64)
            if (got.shape != want.shape or c["rank"].shape != rank.shape
                    or c["first_genes"] is None
                    or c["first_genes"].shape != c["genes"].shape):
                return [("shape_mismatches", float("inf"))]
            for g, w in ((got, want), (pgot, pwant)):
                if len(w):
                    acc_gap = max(acc_gap, float(np.abs(g[:, 0] - w[:, 0])
                                                 .max()) * b)
                    area_gap = max(area_gap, float(
                        (np.abs(g[:, 1] - w[:, 1]) / w[:, 1]).max()))
            rank_bad += int((c["rank"] != rank).sum())
            front_bad += abs(len(pts) - int((rank == 0).sum()))
            gen_gap = max(gen_gap, abs(c["generation"]
                                       - self.traffic["n_generations"]))
            seen = {r.tobytes() for r in c["first_genes"]}
            carried = max(carried, sum(r.tobytes() in seen
                                       for r in c["genes"]) / len(c["genes"]))
            fkeys = ref.keys(ref.evaluate_genes(c["first_genes"]))
            was = fkeys[ref_trees.pareto_ranks(fkeys) == 0]
            now = keys[rank == 0]
            covered = (now[None, :, :] <= was[:, None, :]).all(-1).any(1)
            regressed += int((~covered).sum())
        return [("acc_gap_samples", acc_gap),
                ("area_gap_rel", area_gap),
                ("rank_mismatches", rank_bad),
                ("front_mismatches", front_bad),
                ("generation_gap", gen_gap),
                ("rows_carried_share", carried),
                ("front_regressions", regressed)]


class FaultSlices:
    """Single stuck-at campaign over consecutive slices of fault lanes.

    Traffic keys: ``slice_lanes`` (lanes per `run_sites` call), ``check_lanes``
    (lanes the reference re-simulates after the window), ``trace_seconds``
    (the longest traced window: a second of it is some 85,000 device
    operations, which take the profiler about 3 s to write). The design is the
    configuration's exact tree (8-bit comparators, no margin, no truncation,
    exact vote); the lane list is every site stuck at 0 and at 1.
    """

    rate_metric = "faults_per_s"

    def __init__(self, config: dict, traffic: dict, seed: int, out_dir: str):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.lanes, self.preds = [], []

    def setup(self) -> None:
        import jax.numpy as jnp
        from repro.core import faults, netlist
        from repro.core.tree import to_parallel

        problem, self.ref, tree = build_tree(self.config)
        n = problem.n_comparators
        t8 = np.clip(np.floor(np.asarray(problem.threshold, np.float64)
                              * 256.0), 0, 255).astype(np.int64)
        self.circuit = netlist.build_circuit(
            [to_parallel(tree)], np.full(n, 8), t8,
            problem.n_classes, trunc=np.zeros(n, np.int64),
            vote_adder="exact")
        self.sim = faults.FaultSimulator(self.circuit)
        self.gates, self.values = faults.single_fault_lanes(self.circuit)
        self.x8 = jnp.asarray(problem.x8)
        self._run(np.arange(self.traffic["slice_lanes"]))  # warm-up
        self.reseed(self.seed)

    def reseed(self, seed: int) -> None:
        """Forget the window's work; the next slices start at an offset
        drawn from ``seed``."""
        self.seed, self.lanes, self.preds = seed, [], []
        self.offset = int(np.random.default_rng(seed).integers(
            len(self.gates)))

    def _run(self, idx):
        return self.sim.run_sites(self.x8, self.gates[idx], self.values[idx])

    def step(self, k: int) -> int:
        s = self.traffic["slice_lanes"]
        idx = (self.offset + k * s + np.arange(s)) % len(self.gates)
        self.preds.append(self._run(idx).astype(np.int8))
        self.lanes.append(idx)
        return s

    def counters(self) -> dict:
        return {"kind": "faults", "lanes": sum(len(i) for i in self.lanes),
                "gates": self.circuit.n_gates}

    def instrument(self, annotate) -> None:
        pass

    def collect(self):
        if not self.lanes:
            return np.zeros(0, np.int64), np.zeros((0, self.ref.n_samples))
        return np.concatenate(self.lanes), np.concatenate(self.preds)

    def check(self, produced, control=None) -> list:
        """[(name, reading)]: sampled lanes against the gate-level reference,
        and the fault-free circuit against the tree it implements.
        ``control`` swaps each lane's stuck polarity in place of the
        program."""
        lanes, preds = produced
        c = self.circuit
        args = (c.op, c.a, c.b, c.out_bits, self.ref.x8)
        rng = np.random.default_rng([self.seed, 1])
        n = min(self.traffic["check_lanes"], len(lanes))
        pick = np.sort(rng.choice(len(lanes), size=n, replace=False))
        gates, values = self.gates[lanes[pick]], self.values[lanes[pick]]
        want = ref_gates.simulate(*args, gates, values)
        got = (ref_gates.simulate(*args, gates, 1 - values)
               if control is not None else np.asarray(preds)[pick])
        lane_bad = (int((got != want).sum()) if got.shape == want.shape
                    else float("inf"))
        free = ref_gates.simulate(*args)[0]
        exact = self.ref.exact_genes()[None, :]
        want_free = self.ref.predict(*self.ref.decode(exact))[0]
        return [("lane_mismatches", lane_bad),
                ("circuit_mismatches", int((free != want_free).sum()))]


RUNNERS = {"search_campaigns": SearchCampaigns, "fault_slices": FaultSlices}

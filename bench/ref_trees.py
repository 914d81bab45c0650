"""Plain reference for the tree configurations.

Written from the published description of the design space (arXiv
2203.08011, Fig. 3; the cross-layer genes of the repository's DESIGN.md
§16), not from the program's code, and importing nothing of it. Its inputs
are the configuration's data: the test split as floats in [0, 1], its
labels, and the trained tree as plain node arrays (feature, threshold,
left, right, leaf class). It builds no path matrix, no lookup table and no
kernel operand: each chromosome is decoded here, every test sample descends
the tree node by node, and the area is counted gate by gate.

Semantics, all in integers:

- master code ``x8 = clip(floor(x * 256), 0, 255)``;
- per comparator genes (precision, margin, truncation), one trailing vote
  gene: ``bits = 2 + min(floor(g * 7), 6)``, ``margin = -5 + min(floor(g
  * 11), 10)``, ``trunc = min(floor(g * 3), 2)``, each product rounded to
  float32 as the genes are stored;
- threshold ``t = clip(floor(T * 2^bits), 0, 2^bits - 1)``, substituted
  ``t' = clip(t + margin, 0, 2^bits - 1)``; a k-truncated comparator is the
  comparator of width ``bits - k`` against ``t' >> k``;
- a node sends a sample right iff ``(x8 >> (8 - width)) > threshold``;
  the sample takes its leaf's class. One tree casts one vote, so the vote
  gene (exact or saturating adder) changes neither the class nor the area;
- a comparator ``X > t`` of width p costs ``p - 1 - tz(t + 1)`` two-input
  gates (none when ``t + 1 == 2^p``): one AND for every set bit of ``t + 1``
  above its lowest set bit, one OR for every clear one; AND 0.55 mm², OR
  0.57 mm², 0.02 mm² per comparator and 0.04 mm² per leaf.

A forest's vote stage is not counted here: a forest configuration needs it
counted gate by gate first.
"""
from __future__ import annotations

import numpy as np

AND_MM2 = 0.55
OR_MM2 = 0.57
NODE_MM2 = 0.02
LEAF_MM2 = 0.04
QUANTUM_MM2 = 0.01


def _gate_quanta(t: int, p: int) -> int:
    u = t + 1
    if u >= (1 << p):
        return 0
    tz = (u & -u).bit_length() - 1
    n_and = bin(u >> (tz + 1)).count("1")
    n_or = (p - 1 - tz) - n_and
    return (n_and * round(AND_MM2 / QUANTUM_MM2)
            + n_or * round(OR_MM2 / QUANTUM_MM2))


# [width, threshold] -> quanta, widths 0..8; thresholds past 2^width - 1
# never occur
_QUANTA = np.array([[_gate_quanta(t, p) if t < (1 << p) else 0
                     for t in range(256)] for p in range(9)], np.int64)


class Tree:
    """One tree, as plain node arrays, with the test split."""

    def __init__(self, tree, x_test, y_test, n_classes: int):
        self.tree = {k: np.asarray(v) for k, v in tree.items()}
        self.x8 = np.clip(np.floor(np.asarray(x_test, np.float64) * 256.0),
                          0, 255).astype(np.int64)
        self.y = np.asarray(y_test, np.int64)
        self.n_classes = int(n_classes)
        # comparator order: internal nodes by node id -- the chromosome layout
        self.internal = np.flatnonzero(self.tree["feature"] >= 0)
        self.n_comparators = int(len(self.internal))
        self.n_leaves = int((self.tree["feature"] < 0).sum())
        exact = self.evaluate_genes(self.exact_genes()[None, :])
        self.exact_correct = int(exact["correct"][0])
        self.exact_area_mm2 = float(exact["area_mm2"][0])

    @property
    def n_samples(self) -> int:
        return int(self.y.shape[0])

    def exact_genes(self) -> np.ndarray:
        g = np.zeros(3 * self.n_comparators + 1, np.float32)
        g[0:-1:3] = 0.999
        g[1:-1:3] = 0.5
        return g

    # -- decode -------------------------------------------------------------
    def decode(self, genes):
        """(P, 3N+1) genes -> effective (width, threshold) per comparator,
        both (P, N) int64; the trailing vote gene has no effect on one
        tree."""
        g = np.asarray(genes, np.float32)
        comp = g[:, :-1]

        def level(col, n):
            v = np.floor(col * np.float32(n)).astype(np.int64)
            return np.clip(v, 0, n - 1)

        bits = 2 + level(comp[:, 0::3], 7)
        margin = -5 + level(comp[:, 1::3], 11)
        trunc = level(comp[:, 2::3], 3)
        thr = self.tree["threshold"][self.internal].astype(np.float32)
        top = (1 << bits) - 1
        t = np.floor(thr[None, :].astype(np.float64)
                     * np.exp2(bits)).astype(np.int64)
        t = np.clip(np.clip(t, 0, top) + margin, 0, top)
        return bits - trunc, t >> trunc

    # -- accuracy -----------------------------------------------------------
    def predict(self, width, thr) -> np.ndarray:
        """(P, B) predicted class of every test sample under every design
        given as effective comparator widths and thresholds (P, N)."""
        width = np.asarray(width, np.int64)
        thr = np.asarray(thr, np.int64)
        tree = self.tree
        p, b = width.shape[0], self.n_samples
        rows = np.arange(p)[:, None]
        cols = np.arange(b)[None, :]
        comp_of_node = np.full(tree["feature"].shape[0], -1, np.int64)
        comp_of_node[self.internal] = np.arange(self.n_comparators)
        node = np.zeros((p, b), np.int64)
        while True:
            feat = tree["feature"][node]
            inner = feat >= 0
            if not inner.any():
                break
            c = np.maximum(comp_of_node[node], 0)
            w = width[rows, c]
            x = self.x8[cols, np.maximum(feat, 0)] >> (8 - w)
            right = x > thr[rows, c]
            nxt = np.where(right, tree["right"][node], tree["left"][node])
            node = np.where(inner, nxt, node)
        return tree["leaf_class"][node]

    def correct_counts(self, width, thr) -> np.ndarray:
        return (self.predict(width, thr) == self.y[None, :]).sum(axis=1)

    # -- area ---------------------------------------------------------------
    def area_mm2(self, width, thr) -> np.ndarray:
        quanta = _QUANTA[np.asarray(width, np.int64),
                         np.asarray(thr, np.int64)].sum(axis=1)
        return (quanta * QUANTUM_MM2 + self.n_comparators * NODE_MM2
                + self.n_leaves * LEAF_MM2)

    # -- objectives ---------------------------------------------------------
    def evaluate(self, width, thr) -> dict:
        """Exact counts of designs given in effective form (see `predict`)."""
        return {"correct": self.correct_counts(width, thr),
                "area_mm2": self.area_mm2(width, thr)}

    def evaluate_genes(self, genes) -> dict:
        return self.evaluate(*self.decode(genes))

    def objectives(self, ev: dict, dtype=np.float64) -> np.ndarray:
        """(P, 2) objectives (accuracy loss vs the exact design, area over
        the exact design's), computed in ``dtype``."""
        one = np.asarray(1.0, dtype)
        b = np.asarray(self.n_samples, dtype)
        loss = (np.asarray(self.exact_correct, dtype) / b
                - np.asarray(ev["correct"]).astype(dtype) / b)
        area = (np.asarray(ev["area_mm2"]).astype(dtype)
                * (one / np.asarray(self.exact_area_mm2, dtype)))
        return np.stack([loss.astype(dtype), area.astype(dtype)], axis=1)

    def keys(self, ev: dict) -> np.ndarray:
        """Integer keys ordered as the objectives: (-correct, area quanta)."""
        return np.stack([-np.asarray(ev["correct"], np.int64),
                         np.round(np.asarray(ev["area_mm2"]) / QUANTUM_MM2)
                         .astype(np.int64)], axis=1)


def pareto_ranks(keys) -> np.ndarray:
    """Front index of every row (0 = non-dominated), both keys minimised."""
    keys = np.asarray(keys)
    le = (keys[:, None, :] <= keys[None, :, :]).all(axis=-1)
    lt = (keys[:, None, :] < keys[None, :, :]).any(axis=-1)
    dom = le & lt  # dom[i, j]: i dominates j
    rank = np.full(keys.shape[0], -1, np.int64)
    r = 0
    while (rank < 0).any():
        alive = rank < 0
        dominated = (dom & alive[:, None]).any(axis=0)
        front = alive & ~dominated
        rank[front] = r
        r += 1
    return rank

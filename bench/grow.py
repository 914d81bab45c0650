"""A grown CART tree cut to a published comparator count, best first.

The paper's trees come from scikit-learn, and Table I gives each one's
comparator count. scikit-learn reaches a given count with
``max_leaf_nodes``: it grows the tree best first, always splitting next the
node whose split lowers the training set's weighted Gini impurity most.
`best_first` applies that rule to a tree grown further by the repository's
CART trainer (whose split at each node is the one CART chose there): it keeps
the first ``n`` splits in that order, and every other node it reaches becomes
a leaf of its training majority, the first class with the most samples.
Plain numpy on plain node arrays, so the reference can take the result as is.
"""
from __future__ import annotations

import heapq

import numpy as np


def _gini_mass(y: np.ndarray, n_classes: int) -> float:
    """Samples times Gini impurity."""
    if not len(y):
        return 0.0
    c = np.bincount(y, minlength=n_classes)
    return float(len(y) - np.square(c).sum() / len(y))


def best_first(tree: dict, x_train, y_train, n_classes: int, n: int) -> dict:
    """The first ``n`` splits of ``tree`` (node arrays ``feature``,
    ``threshold``, ``left``, ``right``), best first, as node arrays with
    ``leaf_class``; node ids in the order the nodes were made."""
    x8 = np.clip(np.floor(np.asarray(x_train, np.float64) * 256.0),
                 0, 255).astype(np.int64)
    y = np.asarray(y_train, np.int64)
    feat, thr = np.asarray(tree["feature"]), np.asarray(tree["threshold"])
    out = {"feature": [], "threshold": [], "left": [], "right": [],
           "leaf_class": []}
    reached, heap = [], []

    def sides(old, idx):
        right = x8[idx, feat[old]] > int(np.floor(float(thr[old]) * 256.0))
        return idx[~right], idx[right]

    def add(old, idx) -> int:
        new = len(out["feature"])
        for k, v in (("feature", -1), ("threshold", 0.0), ("left", -1),
                     ("right", -1)):
            out[k].append(v)
        out["leaf_class"].append(
            int(np.bincount(y[idx], minlength=n_classes).argmax()))
        reached.append((old, idx))
        if feat[old] >= 0:
            lo, hi = sides(old, idx)
            gain = (_gini_mass(y[idx], n_classes) - _gini_mass(y[lo], n_classes)
                    - _gini_mass(y[hi], n_classes))
            heapq.heappush(heap, (-gain, new))
        return new

    add(0, np.arange(len(y)))
    for _ in range(n):
        if not heap:
            raise ValueError(f"the tree has fewer than {n} splits")
        _, new = heapq.heappop(heap)
        old, idx = reached[new]
        lo, hi = sides(old, idx)
        out["feature"][new] = int(feat[old])
        out["threshold"][new] = float(thr[old])
        out["leaf_class"][new] = -1
        out["left"][new] = add(int(tree["left"][old]), lo)
        out["right"][new] = add(int(tree["right"][old]), hi)
    return {"feature": np.asarray(out["feature"], np.int32),
            "threshold": np.asarray(out["threshold"], np.float32),
            "left": np.asarray(out["left"], np.int32),
            "right": np.asarray(out["right"], np.int32),
            "leaf_class": np.asarray(out["leaf_class"], np.int32)}

"""Bytes the NSGA-II domination relation needs, from unpadded shapes.

A sort over R rows of M objectives needs the (R, R) relation ``dom[i, j]``
(row i dominates row j) once. At its least the relation is written once at
one byte a pair and the objectives are read once as float32; nothing is
read back in between. The work is a few VPU compares a pair, and the peak
table has no VPU figure, so the least time is the bytes over HBM bandwidth:
a bytes roofline, which padding, a wider output dtype or a second pass over
the relation show as lost share.

One campaign of population P sorts P rows once (`nsga2.init_state`) and
the 2P-row pool of parents and offspring every generation.
"""
from __future__ import annotations

N_OBJECTIVES = 2   # accuracy loss and area


def relation_bytes(rows: int) -> int:
    return rows * rows + 4 * rows * N_OBJECTIVES


def search_bytes(pop: int, campaigns: int, generations: int) -> int:
    """Relations of ``campaigns`` campaigns of population ``pop`` that run
    ``generations`` generations between them."""
    return (campaigns * relation_bytes(pop)
            + generations * relation_bytes(2 * pop))

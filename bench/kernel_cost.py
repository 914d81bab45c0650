"""Operations and bytes the fitness evaluation needs, from unpadded shapes.

These count what the algorithm requires, not what a kernel's padding or
multi-pass precision does, so padding and extra passes show as lost share
and the count stays the same whatever implements the kernel.

For P chromosomes over B test samples of a design with N comparators, L
leaves and C classes, the comparator decisions meet the path matrix (B·N·L
multiply-adds per chromosome) and the satisfied leaves meet the class
one-hot (B·L·C): 2·P·B·(N·L + L·C) operations. Every operand is an integer
of 8 bits or fewer (codes, thresholds, widths, 0/1 path entries, labels),
so the work is exact in int8 and each operand is read once at one byte
per element; the output is one 4-byte count per chromosome.
"""
from __future__ import annotations


def fitness_ops(p: int, b: int, n: int, l: int, c: int) -> int:
    return 2 * p * b * (n * l + l * c)


def fitness_bytes(p: int, b: int, n: int, l: int, c: int) -> int:
    operands = (b * n        # gathered master codes
                + 2 * p * n  # per-chromosome widths and thresholds
                + n * l      # path matrix
                + l          # leaf targets
                + l * c      # leaf classes
                + b          # labels
                + p)         # vote-adder flags
    return operands + 4 * p


def least_seconds(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """(least time, what bounds it) on a chip with ``peak`` (peaks.json)."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")

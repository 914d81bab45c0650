"""search_mfu (%): the whole search's share of the chips' int8 peak.

The operations that every fitness evaluation of the window needs
(`kernel_cost`, unpadded shapes) over the window and the peak of all the
cell's chips. Decode, ranking, variation, checkpoints and the artifact
writer add time and no operations, so this bounds what a faster kernel
alone can gain.
"""
import kernel_cost


def read(run):
    c = run.counters
    if c.get("kind") != "search":
        return None
    b, n, l, k = c["dims"]
    ops = kernel_cost.fitness_ops(c["evaluations"], b, n, l, k)
    peak = run.peak["int8_ops_per_s"] * len(run.devices)
    return 100.0 * ops / run.window_s / peak

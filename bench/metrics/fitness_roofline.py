"""fitness_roofline (%): the fitness kernel's share of its roofline.

The least time the chip could take for the kernel calls of the window --
the larger of their operations over the int8 peak and their bytes over HBM
bandwidth (`kernel_cost`, unpadded shapes) -- over the summed device time
of the ``fitness_errors`` kernel's instructions, per chip,
averaged over the chips.
"""
import kernel_cost
import trace_reduce


def read(run):
    c = run.counters
    if c.get("kind") != "search":
        return None
    b, n, l, k = c["dims"]
    p, calls = c["pop_per_device"], c["fitness_calls"]
    least, _ = kernel_cost.least_seconds(
        kernel_cost.fitness_ops(p, b, n, l, k) * calls,
        kernel_cost.fitness_bytes(p, b, n, l, k) * calls, run.peak)
    shares = []
    for i in run.devices:
        ops = trace_reduce.of_kind(run.reduced.ops[i], "fitness_errors")
        t = sum(o.seconds for o in ops)
        if t <= 0:
            return None
        shares.append(least / t)
    return 100.0 * sum(shares) / len(shares)

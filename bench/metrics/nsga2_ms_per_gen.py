"""nsga2_ms_per_gen (ms): device busy time outside the fitness kernel per
generation -- gene decode, area lookup, domination, front peeling,
crowding, selection and variation, and the front and artifact work of
each campaign -- averaged over the chips.
"""
import trace_reduce


def read(run):
    c = run.counters
    if c.get("kind") != "search" or not c["generations"]:
        return None
    red = run.reduced
    per_chip = []
    for i in run.devices:
        fit = trace_reduce.of_kind(red.ops[i], "fitness_errors")
        if not fit:
            return None
        other = (trace_reduce.busy_seconds(red.ops[i], red.lo, red.hi)
                 - trace_reduce.busy_seconds(fit, red.lo, red.hi))
        per_chip.append(other / c["generations"])
    return 1e3 * sum(per_chip) / len(per_chip)

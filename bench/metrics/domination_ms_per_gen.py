"""domination_ms_per_gen (ms): device time of the NSGA-II domination
kernel per generation -- the summed time of the ``domination_block``
instructions (the Pallas relation, `kernels/domination.py`, which the sort
takes from `nsga2.DOMINATION_KERNEL_MIN_POP` rows) over the window's
generations, each campaign's initial sort included, averaged over the
chips. None where the trace has no such instruction: a pool under the
kernel's threshold takes the jnp relation, inside the fused step ops.
"""
import trace_reduce


def read(run):
    c = run.counters
    if c.get("kind") != "search" or not c["generations"]:
        return None
    per_chip = []
    for i in run.devices:
        ops = trace_reduce.of_kind(run.reduced.ops[i], "domination_block")
        if not ops:
            return None
        per_chip.append(sum(o.seconds for o in ops) / c["generations"])
    return 1e3 * sum(per_chip) / len(per_chip)

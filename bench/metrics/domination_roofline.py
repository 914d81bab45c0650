"""domination_roofline (%): the domination kernel's share of its bytes
roofline.

The least time the chip could take for the window's domination relations
-- each campaign's initial sort of P rows and every generation's sort of
the 2P-row pool, written once at one byte a pair with the float32
objectives read once (`domination_cost`, unpadded shapes), over HBM
bandwidth -- over the summed device time of the ``domination_block``
instructions, per chip, averaged over the chips. A bytes roofline: the
kernel's work is VPU compares, for which the peak table has no figure.
None without such instructions, and for a population sharded over chips,
whose relation is split between them.
"""
import domination_cost
import trace_reduce


def read(run):
    c = run.counters
    if c.get("kind") != "search" or not c["fitness_calls"]:
        return None
    pop = c["pop_per_device"]
    if pop * c["fitness_calls"] != c["evaluations"]:
        return None
    least = (domination_cost.search_bytes(pop, c["campaigns"],
                                          c["generations"])
             / run.peak["hbm_bytes_per_s"])
    shares = []
    for i in run.devices:
        ops = trace_reduce.of_kind(run.reduced.ops[i], "domination_block")
        t = sum(o.seconds for o in ops)
        if t <= 0:
            return None
        shares.append(least / t)
    return 100.0 * sum(shares) / len(shares)

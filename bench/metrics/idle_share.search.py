"""idle_share.search (%): share of the window in which no operation ran on
the chip, averaged over the chips, in a search cell."""


def read(run):
    if run.counters.get("kind") != "search":
        return None
    return 100.0 * (1.0 - run.reduced.busy_s / run.reduced.window_s)

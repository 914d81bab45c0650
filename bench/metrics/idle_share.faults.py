"""idle_share.faults (%): share of the window in which no operation ran on
the chip, averaged over the chips, in a fault-campaign cell."""


def read(run):
    if run.counters.get("kind") != "faults":
        return None
    return 100.0 * (1.0 - run.reduced.busy_s / run.reduced.window_s)

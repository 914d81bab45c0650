"""fault_dispatches_per_fetch (dispatches): dispatches of the fault
simulator's vmapped program for each time the host waits for the device to
copy predictions back: the program's counter ``faults.dispatches`` over the
calls of its span ``faults.fetch``, from `repro.runtime.spans` in a traced
run. None where the program records no spans."""


def read(run):
    if run.counters.get("kind") != "faults":
        return None
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    t = spans.totals()
    fetch = t.get("faults.fetch")
    if not fetch or not t.get("faults.dispatches"):
        return None
    return t["faults.dispatches"] / fetch["calls"]

"""fault_host_ms_per_dispatch (ms): host milliseconds the fault simulator
spends per dispatch of its vmapped program outside the wait for the result:
the self time of the program's span ``faults.run`` (slicing and padding the
lane masks, copying them to the device, dispatching) over its counter
``faults.dispatches``, from `repro.runtime.spans` in a traced run. None where
the program records no spans."""


def read(run):
    if run.counters.get("kind") != "faults":
        return None
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    t = spans.totals()
    if "faults.run" not in t or not t.get("faults.dispatches"):
        return None
    return 1e3 * t["faults.run"]["self_seconds"] / t["faults.dispatches"]

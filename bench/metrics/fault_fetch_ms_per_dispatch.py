"""fault_fetch_ms_per_dispatch (ms): host milliseconds per dispatch of the
fault simulator's vmapped program spent blocked on the copy of its
predictions to the host, which waits for the device to finish: the program's
span ``faults.fetch`` over its counter ``faults.dispatches``, from
`repro.runtime.spans` in a traced run. None where the program records no
spans."""


def read(run):
    if run.counters.get("kind") != "faults":
        return None
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    t = spans.totals()
    if "faults.fetch" not in t or not t.get("faults.dispatches"):
        return None
    return 1e3 * t["faults.fetch"]["seconds"] / t["faults.dispatches"]

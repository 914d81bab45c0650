"""checkpoint_write_ms (ms): host milliseconds a checkpoint save spends
writing once the state has reached the host (archive, manifest, atomic
publish, pruning of old saves): the program's span ``checkpoint.write``,
self time over its calls, from `repro.runtime.spans` in a traced run. None
where the program records no spans."""


def read(run):
    if run.counters.get("kind") != "search":
        return None
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    w = spans.totals().get("checkpoint.write")
    if not w or not w["calls"]:
        return None
    return 1e3 * w["self_seconds"] / w["calls"]

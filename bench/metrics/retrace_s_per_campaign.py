"""retrace_s_per_campaign (s): host seconds of jaxpr tracing and lowering
per search campaign: the program's counters ``<span>/jit.trace_s`` and
``<span>/jit.lower_s`` (booked under whichever span was open) over its
``search.run`` calls, from `repro.runtime.spans` in a traced run. None where
the program records no spans."""


def read(run):
    if run.counters.get("kind") != "search":
        return None
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    t = spans.totals()
    calls = t.get("search.run", {}).get("calls")
    if not calls:
        return None
    return sum(v for k, v in t.items()
               if k.rsplit("/", 1)[-1] in ("jit.trace_s", "jit.lower_s")
               ) / calls

"""artifact_netlist_s_per_campaign (s): host seconds the program's Pareto
artifact writer spends building and costing each front point's netlist per
search campaign: the program's span ``artifact.netlist`` (`build_circuit`,
`netlist_area_mm2`, `gate_counts`) over its ``search.run`` calls, from
`repro.runtime.spans` in a traced run. None where the program records no
spans."""


def read(run):
    if run.counters.get("kind") != "search":
        return None
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    t = spans.totals()
    if "artifact.netlist" not in t or not t.get("search.run", {}).get("calls"):
        return None
    return t["artifact.netlist"]["seconds"] / t["search.run"]["calls"]

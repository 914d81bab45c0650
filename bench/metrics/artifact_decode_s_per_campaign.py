"""artifact_decode_s_per_campaign (s): host seconds the program's Pareto
artifact writer spends decoding front points per search campaign: the
program's span ``artifact.decode`` (gene decode, threshold substitution,
copies of each point's arrays to the host) over its ``search.run`` calls,
from `repro.runtime.spans` in a traced run. None where the program records
no spans."""


def read(run):
    if run.counters.get("kind") != "search":
        return None
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    t = spans.totals()
    if "artifact.decode" not in t or not t.get("search.run", {}).get("calls"):
        return None
    return t["artifact.decode"]["seconds"] / t["search.run"]["calls"]

"""artifact_s_per_campaign (s): host seconds in the program's Pareto
artifact writer (`write_pareto_artifact`: one netlist per front point) per
campaign, timed by the benchmark around the call in a traced run."""


def read(run):
    c = run.counters
    if c.get("kind") != "search" or not c.get("write_pareto_artifact_calls"):
        return None
    return c["write_pareto_artifact_s"] / c["write_pareto_artifact_calls"]

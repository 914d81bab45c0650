"""fault_lanes_per_dispatch (lanes): fault lanes the window simulated over
the executions of the fault simulator's program (``jit__sim_one``) that the
device trace shows on the first chip."""
import trace_reduce


def read(run):
    c = run.counters
    if c.get("kind") != "faults":
        return None
    red = run.reduced
    execs = [m for m in red.trace.modules.get(run.devices[0], [])
             if "_sim_one" in m.name and red.lo <= m.start_ns <= red.hi]
    return c["lanes"] / len(execs) if execs else None

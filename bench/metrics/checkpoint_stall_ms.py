"""checkpoint_stall_ms (ms): device idle time inside the program's
checkpoint saves (`runtime.checkpoint.save`, spanned by the benchmark in a
traced run) per save, averaged over the chips. A save first waits for the
checkpoint interval's scan to finish; only the idle time after that is the
stall."""
import trace_reduce


def read(run):
    c = run.counters
    if c.get("kind") != "search" or "save_calls" not in c:
        return None
    red = run.reduced
    idle = [dict(trace_reduce.idle_gaps(red.ops[i], red.trace.spans, red.lo,
                                        red.hi)).get("save", 0.0)
            for i in run.devices]
    return 1e3 * sum(idle) / len(idle) / c["save_calls"]

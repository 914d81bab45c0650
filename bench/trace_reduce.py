"""From a profiler trace to busy time, kernel time and idle gaps.

The JAX profiler writes an ``.xplane.pb`` under ``<dir>/plugins/profile/
<time>/``. Each TPU is a plane named ``/device:TPU:<i>``. Its ``XLA Ops``
line holds one event per executed operation, named by the operation's HLO
text (``%fitness_errors.6 = f32[256,128] custom-call(...)``: a Pallas
kernel's instruction carries the name of the jitted function that calls
it); a loop's instruction (``%while.14``) spans the operations of its
body. Its ``XLA Modules`` line holds one event per program execution
(``jit__sim_one(<hash>)``). The benchmark's own host spans are
``jax.profiler.TraceAnnotation`` events named ``bench:<what>`` on the
``/host:CPU`` plane; both clocks are the host's.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    name: str      # the instruction's name: "fitness_errors.6", "while.14"
    start_ns: float
    end_ns: float

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    @property
    def kind(self) -> str:
        """The name without its numeric suffix: "fitness_errors"."""
        head, _, tail = self.name.rpartition(".")
        return head if head and tail.isdigit() else self.name


def instruction_name(event_name: str) -> str:
    """``%fitness_errors.6 = f32[...] custom-call(...)`` -> ``fitness_errors.6``
    (a module execution's name is kept whole)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclasses.dataclass
class Trace:
    devices: dict      # device id -> [Op] in start order
    modules: dict      # device id -> [Op], one per program execution
    spans: list        # [Span] of the benchmark's own annotations

    def window(self, name: str) -> tuple[float, float]:
        """(start, end) of the first span called ``name``."""
        for s in self.spans:
            if s.name == name:
                return s.start_ns, s.end_ns
        raise ValueError(f"trace has no span {name!r}")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def from_profile(profile) -> Trace:
    """Reduce a `jax.profiler.ProfileData` to device ops and host spans."""
    devices, modules, spans = {}, {}, []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {OPS_LINE: [], MODULES_LINE: []}
            for line in plane.lines:
                if line.name not in lines:
                    continue
                for e in line.events:
                    lines[line.name].append(Op(instruction_name(e.name),
                                               e.start_ns,
                                               e.start_ns + e.duration_ns))
            for ops in lines.values():
                ops.sort(key=lambda o: o.start_ns)
            devices[int(m.group(1))] = lines[OPS_LINE]
            modules[int(m.group(1))] = lines[MODULES_LINE]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Span(e.name[len(SPAN_PREFIX):],
                                          e.start_ns,
                                          e.start_ns + e.duration_ns))
    spans.sort(key=lambda s: s.start_ns)
    return Trace(devices, modules, spans)


def load(log_dir: str) -> Trace:
    import jax

    return from_profile(jax.profiler.ProfileData.from_file(
        find_xplane(log_dir)))


def clip(ops, lo: float, hi: float) -> list:
    """Ops overlapping [lo, hi], cut to it."""
    out = []
    for o in ops:
        s, e = max(o.start_ns, lo), min(o.end_ns, hi)
        if e > s:
            out.append(Op(o.name, s, e))
    return out


def union(ops) -> list:
    """Merged busy intervals [(start, end)] of ``ops``."""
    merged = []
    for o in sorted(ops, key=lambda o: o.start_ns):
        if merged and o.start_ns <= merged[-1][1]:
            if o.end_ns > merged[-1][1]:
                merged[-1][1] = o.end_ns
        else:
            merged.append([o.start_ns, o.end_ns])
    return [(s, e) for s, e in merged]


def busy_seconds(ops, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(clip(ops, lo, hi))) * 1e-9


def of_kind(ops, kind: str) -> list:
    """Ops whose instruction is named ``kind`` (any numeric suffix)."""
    return [o for o in ops if o.kind == kind]


def op_totals(ops) -> list:
    """[(instruction kind, seconds)], longest first. Loops and conditionals
    are left out: their instructions span the operations they run."""
    totals = {}
    for o in ops:
        if o.kind not in CONTAINERS:
            totals[o.kind] = totals.get(o.kind, 0.0) + o.seconds
    return sorted(totals.items(), key=lambda kv: -kv[1])


def idle_gaps(ops, spans, lo: float, hi: float) -> list:
    """[(what the host was doing, idle seconds)], longest first.

    A gap is a stretch of [lo, hi] with no device op; it is put down to the
    shortest benchmark span that holds its midpoint ("no span" if none).
    """
    busy = union(clip(ops, lo, hi))
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    # label each stretch between consecutive span boundaries once, then
    # find each gap's stretch by bisection
    cuts = sorted({t for sp in spans for t in (sp.start_ns, sp.end_ns)})
    labels = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        inside = [sp for sp in spans if sp.start_ns <= mid <= sp.end_ns]
        labels.append(min(inside, key=lambda sp: sp.end_ns - sp.start_ns).name
                      if inside else "no span")
    totals = {}
    for s, e in gaps:
        i = bisect.bisect_right(cuts, (s + e) / 2) - 1
        label = labels[i] if 0 <= i < len(labels) else "no span"
        totals[label] = totals.get(label, 0.0) + (e - s) * 1e-9
    return sorted(totals.items(), key=lambda kv: -kv[1])

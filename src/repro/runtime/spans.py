"""Host spans and counters of the program, recorded while a profile is taken.

The search driver, the checkpoint writer, the Pareto artifact writer and the
fault simulator open named spans and add to counters at their boundaries
(API.md, "Profiling the program"). They record only while a `jax.profiler`
trace is being taken in this process; otherwise `span` hands back one shared
no-op context and `count` returns at once, so an unprofiled run pays one
enabled-check a call and nothing else.

While a trace is taken, each span also opens a
``jax.profiler.TraceAnnotation("repro:<name>", **ids)``: the program's spans
land on the profiler's own clock, in the same ``.xplane.pb`` as the device
operations. A span's ids are handed down to the spans opened inside it, so
every event of one unit of work (``campaign=<n>`` for one `run_search` call,
``call=<n>`` for one fault call) carries the same id.

    jax.profiler.start_trace(log_dir)
    ...                                    # run the program
    jax.profiler.stop_trace()
    spans.totals()    # {"search.run": {"calls", "seconds", "self_seconds"},
                      #  "artifact.points": 412, ...}

Spans nest per thread; a span's self time is its duration less the time its
child spans cover. A `jax.monitoring` listener books the jaxpr trace and
lowering seconds and the backend compiles the persistent cache did not serve
as counters under the innermost open span (``<span>/jit.trace_s``,
``<span>/jit.lower_s``, ``<span>/jit.compiles``; no prefix outside every
span), which says which step traced again.
"""
from __future__ import annotations

import contextlib
import threading
import time

import jax

PREFIX = "repro:"

_recording = jax.profiler.TraceAnnotation.is_enabled
_OFF = contextlib.nullcontext()
_lock = threading.Lock()
_spans: dict[str, list] = {}        # name -> [calls, seconds, self seconds]
_counters: dict[str, float] = {}
_local = threading.local()          # .stack: open spans; .cache_hit


def recording() -> bool:
    """Whether spans and counters are being recorded (a profile is taken)."""
    return _recording()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class _Span:
    __slots__ = ("name", "ids", "annotation", "t0", "child_s")

    def __init__(self, name: str, ids: dict):
        self.name, self.ids, self.child_s = name, ids, 0.0

    def __enter__(self):
        stack = _stack()
        if stack:
            self.ids = {**stack[-1].ids, **self.ids}
        self.annotation = jax.profiler.TraceAnnotation(PREFIX + self.name,
                                                       **self.ids)
        self.annotation.__enter__()
        stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        stack = _stack()
        stack.pop()
        if stack:
            stack[-1].child_s += dur
        self.annotation.__exit__(*exc)
        with _lock:
            rec = _spans.setdefault(self.name, [0, 0.0, 0.0])
            rec[0] += 1
            rec[1] += dur
            rec[2] += dur - self.child_s
        return False


def span(name: str, **ids):
    """A context manager timing ``name``; ``ids`` tag its profiler event and
    those of the spans opened inside it."""
    if not _recording():
        return _OFF
    return _Span(name, ids)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    if not _recording():
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def totals() -> dict:
    """``{span: {"calls", "seconds", "self_seconds"}}`` and ``{counter:
    value}`` recorded since the last `reset`."""
    with _lock:
        out = {k: {"calls": c, "seconds": s, "self_seconds": ss}
               for k, (c, s, ss) in _spans.items()}
        out.update(_counters)
    return out


def reset() -> None:
    """Forget every span and counter recorded so far."""
    with _lock:
        _spans.clear()
        _counters.clear()


_JIT_SECONDS = {"/jax/core/compile/jaxpr_trace_duration": "jit.trace_s",
                "/jax/core/compile/jaxpr_to_mlir_module_duration":
                    "jit.lower_s"}
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


def _under_span(key: str) -> str:
    stack = _stack()
    return f"{stack[-1].name}/{key}" if stack else key


def _on_duration(event: str, duration: float, **_) -> None:
    key = _JIT_SECONDS.get(event)
    if (key is None and event != _BACKEND_COMPILE) or not _recording():
        return
    if key is not None:
        count(_under_span(key), duration)
    elif getattr(_local, "cache_hit", False):
        _local.cache_hit = False    # served by the persistent cache
    else:
        count(_under_span("jit.compiles"))


def _on_event(event: str, **_) -> None:
    if event == _CACHE_HIT and _recording():
        _local.cache_hit = True


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)

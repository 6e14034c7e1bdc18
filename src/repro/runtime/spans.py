"""Named host-side spans and counters of the program's set-up.

    from repro.runtime import spans

    with spans.span("load"):       # or @spans.span("engine.init") on a function
        ...                        # host code only, never inside a jit
    spans.spans()                  # [Span(name, start, end, parent, fun_name)]
    spans.counters()               # {"compile.cache_miss": 1, ...}
    spans.seconds("compile.backend")

A span opens a `jax.profiler.TraceAnnotation` of its name, so a profiled
run shows it in the host plane on the device trace's clock, and appends
one `Span` to an in-memory list on `time.perf_counter`, the clock of a
benchmark's `setup_s`.  `parent` is the name of the span that was open
around it.

On import the module listens to JAX's compile events (`jax.monitoring`)
and records them as spans that end when JAX reports them:

    compile.trace    /jax/core/compile/jaxpr_trace_duration
    compile.lower    /jax/core/compile/jaxpr_to_mlir_module_duration
    compile.backend  /jax/core/compile/backend_compile_duration
                     (the XLA compile, or the load from the persistent
                     compilation cache)

each with the compiled function's name in `fun_name`, and counts the
persistent cache's answers as `compile.cache_hit` and `compile.cache_miss`.
Device work is named by `jax.named_scope` in the traced code instead
(docs/tracing.md lists both).
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import Counter
from typing import Dict, Iterator, List, NamedTuple, Optional

import jax


class Span(NamedTuple):
    """One span: `start` and `end` in `time.perf_counter` seconds, `parent`
    the name of the span open around it, `fun_name` the compiled function
    (compile.* spans only)."""

    name: str
    start: float
    end: float
    parent: Optional[str]
    fun_name: Optional[str] = None


COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hit",
    "/jax/compilation_cache/cache_misses": "compile.cache_miss",
}

# JAX's listeners are process-wide, so the record is too; each thread keeps
# its own stack of open spans.
_lock = threading.Lock()
_spans: List[Span] = []
_counters: Counter = Counter()


class _Open(threading.local):
    def __init__(self):
        self.stack: List[str] = []


_open = _Open()


def _parent() -> Optional[str]:
    return _open.stack[-1] if _open.stack else None


def _record(s: Span) -> None:
    with _lock:
        _spans.append(s)


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Record the enclosed host code as the span `name`."""
    parent = _parent()
    _open.stack.append(name)
    start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        _open.stack.pop()
        _record(Span(name, start, time.perf_counter(), parent))


def count(name: str, k: int = 1) -> None:
    with _lock:
        _counters[name] += k


def spans() -> List[Span]:
    """Every span recorded so far, in the order they ended."""
    with _lock:
        return list(_spans)


def counters() -> Dict[str, int]:
    with _lock:
        return dict(_counters)


def reset() -> None:
    """Forget the recorded spans and counters (open spans still record)."""
    with _lock:
        _spans.clear()
        _counters.clear()


def seconds(*names: str) -> Optional[float]:
    """Seconds covered by the recorded spans of these names, a nested or
    overlapping span counted once (JAX traces an inner jitted function
    inside its caller's trace); None when none was recorded."""
    total, end = 0.0, float("-inf")
    intervals = sorted((s.start, s.end) for s in spans() if s.name in names)
    for a, b in intervals:
        if b > end:
            total += b - max(a, end)
            end = b
    return total if intervals else None


def _on_duration(event: str, duration: float, **kwargs) -> None:
    name = COMPILE_EVENTS.get(event)
    if name is not None:
        end = time.perf_counter()
        _record(Span(name, end - duration, end, _parent(), kwargs.get("fun_name")))


def _on_event(event: str, **kwargs) -> None:
    name = CACHE_EVENTS.get(event)
    if name is not None:
        count(name)


# A module body runs once per process, so each listener is registered once.
jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)

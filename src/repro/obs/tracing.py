"""Structured spans with device-accurate timing, causal ids and compiles.

JAX dispatch is asynchronous: ``fn(x)`` returns as soon as the computation
is *enqueued*, so a naive ``perf_counter`` pair around a jitted call times
the Python dispatch, not the device execution.  :class:`Tracer` records
spans that say what the program was doing, and when:

* a span can carry a **sync target** (``sp.sync(out)``): at span exit the
  tracer calls ``jax.block_until_ready`` on it *before* taking the end
  timestamp, so the recorded duration covers actual device execution;
* every span records its own id, its **parent**'s id and its **root**
  span's id (``span_id``, ``parent_id``, ``root_id`` in the Chrome event's
  ``args``), taken from a per-thread stack of open spans: the spans of one
  ``PTMTEngine.discover`` share the root ``engine.mine``, those of one
  streaming ``ingest`` call the root ``stream.ingest`` (unless a caller's
  span is open around them, which is then the root);
* every span is **mirrored into the profiler**: it opens a
  ``jax.profiler.TraceAnnotation`` named ``span:<name>``, so a
  ``jax.profiler`` trace holds the program's spans on its own clock, next
  to the device's operations (the prefix keeps a span from colliding with
  an annotation of the same bare name);
* **compilations** are recorded as they happen: JAX reports each backend
  compilation (``/jax/core/compile/backend_compile_duration``, which
  includes a load from the persistent compilation cache); the tracer
  records it as a ``jax.compile`` event, with the jitted function's name
  and its duration, under the span open on the compiling thread, and
  counts ``repro_jax_compiles_total{span=...}`` in its metrics registry.
  One process-wide listener serves every live tracer; a compilation with
  no span of a tracer open on its thread is not that tracer's.

:meth:`Tracer.to_chrome_trace` emits the Chrome tracing / Perfetto JSON
format — load the ``--trace-out`` file at ``chrome://tracing`` or
https://ui.perfetto.dev directly.

:data:`NULL_TRACER` is the disabled-mode singleton: ``span()`` returns one
shared no-op context manager, so an instrumented hot path costs a single
dict-free method call when tracing is off; it opens no annotation and
listens to no compilation.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
import weakref

from .metrics import NULL_REGISTRY

__all__ = ["Span", "Tracer", "NULL_TRACER", "NullTracer"]

#: JAX's monitoring event for one backend compilation (or cache load)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: prefix of a span's profiler annotation
ANNOTATION_PREFIX = "span:"

_live_tracers: "weakref.WeakSet[Tracer]" = weakref.WeakSet()
_live_lock = threading.Lock()
_listening = False


def _on_duration_event(event: str, duration: float, **kwargs) -> None:
    if event != COMPILE_EVENT:
        return
    with _live_lock:
        tracers = list(_live_tracers)
    for tracer in tracers:
        tracer._note_compile(str(kwargs.get("fun_name", "?")), duration)


def _track(tracer: "Tracer") -> None:
    """Add ``tracer`` to the compile listener's set, registering the one
    process-wide listener the first time."""
    global _listening
    with _live_lock:
        _live_tracers.add(tracer)
        if _listening:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            _on_duration_event)
        _listening = True


class Span:
    """One in-flight span; use as a context manager (``with tracer.span(...)
    as sp``).  Mutate via :meth:`set` (attach attributes) and :meth:`sync`
    (block on a jax value before the end timestamp)."""

    __slots__ = ("name", "args", "id", "parent_id", "root_id", "_tracer",
                 "_sync", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, args: dict):
        self.name = name
        self.args = args
        self.id = self.parent_id = self.root_id = None
        self._tracer = tracer
        self._sync = None
        self._t0 = 0.0
        self._annotation = None

    def set(self, **attrs) -> "Span":
        self.args.update(attrs)
        return self

    def sync(self, value) -> "Span":
        """Block on ``value`` (any jax pytree) at span exit, before the end
        timestamp — makes the duration device-accurate."""
        self._sync = value
        return self

    def __enter__(self) -> "Span":
        self._tracer._push(self)
        self._annotation = self._tracer._annotate(ANNOTATION_PREFIX
                                                  + self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        try:
            if self._sync is not None:
                import jax

                jax.block_until_ready(self._sync)
        finally:
            t1 = time.perf_counter()
            self._annotation.__exit__(exc_type, exc, tb)
            if exc_type is not None:
                self.args["error"] = exc_type.__name__
            self._tracer._finish(self, self._t0, t1)
        return False


class Tracer:
    """Collects finished spans and compilations; exports Chrome-trace JSON.

    Thread-safe: spans may open/close concurrently on any thread (each
    event records its thread id, and per-thread stacks keep parents
    local).  The event buffer is bounded (``max_events``) so a runaway loop
    cannot exhaust memory — overflow increments :attr:`dropped` instead.
    ``metrics`` is the registry that counts compilations (none by default).
    """

    enabled = True

    def __init__(self, max_events: int = 200_000, *, metrics=None):
        from jax.profiler import TraceAnnotation

        self.max_events = int(max_events)
        self.dropped = 0
        self.metrics = metrics if metrics is not None else NULL_REGISTRY
        self._annotate = TraceAnnotation
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._local = threading.local()
        self._origin = time.perf_counter()
        _track(self)

    def span(self, name: str, **args) -> Span:
        return Span(self, name, args)

    # -- span plumbing ------------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _push(self, span: Span) -> None:
        stack = self._stack()
        span.id = next(self._ids)
        span.parent_id = stack[-1].id if stack else None
        span.root_id = stack[0].id if stack else span.id
        stack.append(span)

    def _finish(self, span: Span, t0: float, t1: float) -> None:
        stack = self._stack()
        if span in stack:
            stack.remove(span)
        args = span.args
        args.update(span_id=span.id, parent_id=span.parent_id,
                    root_id=span.root_id)
        self._record(span.name, t0, t1, args)

    def _record(self, name: str, t0: float, t1: float, args: dict) -> None:
        event = {
            "name": name,
            "cat": "repro",
            "ph": "X",
            "ts": (t0 - self._origin) * 1e6,
            "dur": (t1 - t0) * 1e6,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "args": args,
        }
        with self._lock:
            if len(self._events) < self.max_events:
                self._events.append(event)
            else:
                self.dropped += 1

    def _note_compile(self, fun_name: str, duration_s: float) -> None:
        """Record a compilation that just ended on this thread under the
        innermost open span (nothing when no span is open here)."""
        stack = getattr(self._local, "stack", None)
        if not stack:
            return
        parent = stack[-1]
        t1 = time.perf_counter()
        self._record("jax.compile", t1 - duration_s, t1, {
            "fun_name": fun_name, "span": parent.name,
            "span_id": next(self._ids), "parent_id": parent.id,
            "root_id": stack[0].id})
        self.metrics.counter("repro_jax_compiles_total",
                             span=parent.name).inc()

    # -- introspection / export --------------------------------------------

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def span_names(self) -> set[str]:
        with self._lock:
            return {e["name"] for e in self._events}

    def to_chrome_trace(self) -> dict:
        """Chrome tracing JSON object format (Perfetto-loadable)."""
        events = self.events()
        meta = [{
            "name": "process_name",
            "ph": "M",
            "pid": os.getpid(),
            "args": {"name": "repro-ptmt"},
        }]
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": self.dropped},
        }

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f, indent=1)


class _NullSpan:
    __slots__ = ()
    name, args = "", {}

    def set(self, **attrs):
        return self

    def sync(self, value):
        return self

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled-mode tracer: every ``span()`` is the same shared no-op."""

    enabled = False
    dropped = 0

    def span(self, name, **args):
        return _NULL_SPAN

    def events(self):
        return []

    def span_names(self):
        return set()

    def to_chrome_trace(self):
        return {"traceEvents": [], "displayTimeUnit": "ms", "otherData": {}}

    def write(self, path):
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)


NULL_TRACER = NullTracer()

"""Deterministic tracer: nested spans, instant events, counter tracks.

A ``Tracer`` records what happened and WHEN — but "when" is read from an
injectable clock callable, never the wall clock: the serving engine hands
its iteration clock, the fleet controller its tick counter, wall-clock
replay tests a ``ManualClock``.  Two identical runs therefore record
identical event streams, and the Chrome-trace export (``obs.export``) is
byte-identical — the property the trace-determinism tests pin.

Events carry a ``track`` (Perfetto process row: one per replica, one for
the controller, one per engine) and a ``lane`` (thread row within the
track: per-request lanes like ``req:3``, an ``engine`` lane for step
spans, a ``membership`` lane for kill/join).  Spans that stay open across
engine iterations (queue-wait, a request's whole decode residency) are
keyed: ``begin(..., key=...)`` then ``end(key)`` from a later step.

``NullTracer`` is the default everywhere except the serving engine: every
hook in a hot loop costs exactly one no-op method call and allocates
nothing — the engine's dispatch count with tracing on equals the count
with it off (tested), because hooks only read host-side state the loop
already owns.

``ProfilerTracer`` (the serving engine's default) takes the same hooks
onto the profiler's clock: each becomes a ``jax.profiler.TraceAnnotation``
named ``serve.<name>`` with the hook's arguments as its stats, so a device
trace taken with ``jax.profiler`` shows the engine's host work on the same
timeline as the device programs.  Without a profiler session each hook is
one inactive TraceMe.
"""

from __future__ import annotations

import contextlib
from typing import Any, Callable, Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Tracer", "NullTracer", "ProfilerTracer"]


def _jsonable(v: Any) -> Any:
    """Coerce numpy scalars/arrays so exports are plain JSON."""
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if hasattr(v, "item") and getattr(v, "ndim", 1) == 0:
        return v.item()
    if hasattr(v, "tolist"):
        return v.tolist()
    return str(v)


class Tracer:
    """Append-only event recorder against an injectable clock."""

    enabled = True

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self.clock = clock
        self.events: List[Dict[str, Any]] = []
        self._open: Dict[Any, Dict[str, Any]] = {}
        self._auto = 0

    # -- clock ----------------------------------------------------------
    def use_clock(self, fn: Callable[[], float]) -> None:
        """Adopt ``fn`` as the timeline.  The outermost timeline owner
        wins (a fleet controller overrides the engines' step clocks so
        the whole fleet renders on one tick axis)."""
        self.clock = fn

    def now(self) -> float:
        return float(self.clock()) if self.clock is not None else 0.0

    # -- recording ------------------------------------------------------
    def _emit(self, ph: str, name: str, track: str, lane: str,
              args: Dict[str, Any]) -> Dict[str, Any]:
        ev = {"ph": ph, "name": name, "ts": self.now(), "track": track,
              "lane": lane,
              "args": {k: _jsonable(v) for k, v in args.items()}}
        self.events.append(ev)
        return ev

    def event(self, name: str, *, track: str = "main",
              lane: str = "events", **args) -> None:
        """Instant event (Perfetto arrow tick)."""
        self._emit("i", name, track, lane, args)

    def begin(self, name: str, *, track: str = "main",
              lane: str = "events", key: Any = None, **args) -> Any:
        """Open a span; ``key`` lets a later call close it (idempotent
        keys: re-beginning an open key first closes the stale span so a
        crashed path cannot leak an unbounded open set)."""
        if key is None:
            self._auto += 1
            key = ("__auto__", self._auto)
        if key in self._open:
            self.end(key)
        self._open[key] = self._emit("B", name, track, lane, args)
        return key

    def end(self, key: Any, **args) -> None:
        """Close the span opened under ``key`` (no-op for unknown keys:
        failure paths may kill a request whose span someone else already
        closed)."""
        b = self._open.pop(key, None)
        if b is None:
            return
        self._emit("E", b["name"], b["track"], b["lane"], args)

    @contextlib.contextmanager
    def span(self, name: str, *, track: str = "main",
             lane: str = "events", **args):
        key = self.begin(name, track=track, lane=lane, **args)
        try:
            yield self
        finally:
            self.end(key)

    def counter(self, name: str, value: float, *,
                track: str = "main") -> None:
        """Counter sample (Perfetto renders a stacked area track)."""
        self._emit("C", name, track, name, {"value": _jsonable(value)})

    # -- introspection --------------------------------------------------
    def open_spans(self) -> List[str]:
        return [ev["name"] for ev in self._open.values()]

    def __len__(self) -> int:
        return len(self.events)


class _NullSpan:
    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer: the default.  Every hook is one no-op call."""

    enabled = False
    events: List[Dict[str, Any]] = []   # always empty, shared sentinel

    def use_clock(self, fn) -> None:
        pass

    def now(self) -> float:
        return 0.0

    def event(self, name, **kw) -> None:
        pass

    def begin(self, name, **kw) -> Any:
        return None

    def end(self, key, **kw) -> None:
        pass

    def span(self, name, **kw):
        return _NULL_SPAN

    def counter(self, name, value, **kw) -> None:
        pass

    def open_spans(self) -> List[str]:
        return []

    def __len__(self) -> int:
        return 0


class ProfilerTracer:
    """The tracer hooks as ``jax.profiler`` host annotations.

    A span is a ``TraceAnnotation`` named ``serve.<name>`` whose keyword
    arguments become the event's stats; a keyed span holds its annotation
    open from ``begin`` until ``end`` (which may add stats); events and
    counters are zero-length annotations (a counter's value is its
    ``value`` stat).  ``track`` and ``lane`` only lay out the
    deterministic export, and are dropped.  There is no timeline of its
    own: the profiler's clock is the timeline.
    """

    enabled = True
    clock = None
    prefix = "serve."

    def __init__(self):
        self._open: Dict[Any, Any] = {}
        self._auto = 0

    def use_clock(self, fn) -> None:
        pass

    def event(self, name: str, *, track=None, lane=None, **args) -> None:
        with TraceAnnotation(self.prefix + name, **args):
            pass

    def begin(self, name: str, *, track=None, lane=None, key: Any = None,
              **args) -> Any:
        if key is None:
            self._auto += 1
            key = ("__auto__", self._auto)
        if key in self._open:
            self.end(key)
        span = TraceAnnotation(self.prefix + name, **args)
        span.__enter__()
        self._open[key] = (name, span)
        return key

    def end(self, key: Any, **args) -> None:
        opened = self._open.pop(key, None)
        if opened is None:
            return
        span = opened[1]
        if args:
            span.set_metadata(**args)
        span.__exit__(None, None, None)

    def span(self, name: str, *, track=None, lane=None, **args):
        return TraceAnnotation(self.prefix + name, **args)

    def counter(self, name: str, value: float, *, track=None) -> None:
        with TraceAnnotation(self.prefix + name, value=value):
            pass

    def open_spans(self) -> List[str]:
        return [name for name, _ in self._open.values()]

"""The program's tracer: scoped spans, request spans and counters, off by
default.

One module-level :data:`TRACER` serves every layer, so that the engine,
the scheduler, the JAX backend and the AGFT tuner record without a tracer
being passed through their constructors. Only code turns it on
(``enable``); there is no flag or environment variable for it.

Off, a call site pays one call that reads ``on`` and returns a shared
no-op context (``span``) or returns at once (``begin``, ``end``, ``add``):
nothing is allocated and ``jax`` is not imported. On:

- ``span(name)`` enters ``jax.profiler.TraceAnnotation(name)``, so the
  span lands in a profile's host plane on the device's timeline, and
  appends ``(name, start_ns, end_ns, parent)`` on ``time.perf_counter_ns``
  to ``spans``; ``parent`` is the index of the enclosing span, or None;
- ``begin(name, key)`` / ``end(name, key)`` record a span across calls,
  such as one request's wait, in ``requests`` as ``(name, key, start_ns,
  end_ns)``; an ``end`` with no ``begin`` since the last ``reset`` is
  dropped;
- ``add(name, n)`` adds to an integer counter.

Records stay in memory until ``reset``; ``records()`` is their snapshot.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional, Tuple

_OFF = contextlib.nullcontext()


class _Span:
    __slots__ = ("tracer", "name", "index", "annotation")

    def __init__(self, tracer: "Tracer", name: str):
        self.tracer = tracer
        self.name = name

    def __enter__(self):
        t = self.tracer
        self.annotation = t._annotation(self.name)
        self.annotation.__enter__()
        self.index = len(t.spans)
        t.spans.append([self.name, time.perf_counter_ns(), 0,
                        t._stack[-1] if t._stack else None])
        t._stack.append(self.index)
        return self

    def __exit__(self, *exc):
        t = self.tracer
        t.spans[self.index][2] = time.perf_counter_ns()
        t._stack.pop()
        return self.annotation.__exit__(*exc)


class Tracer:
    """Spans, request spans and counters of one process (see the module's
    docstring). ``reset`` only between spans, never inside one."""

    def __init__(self):
        self.on = False
        self._annotation = None
        self.reset()

    def reset(self) -> None:
        self.spans: List[list] = []
        self.requests: List[Tuple[str, object, int, int]] = []
        self.counters: Dict[str, int] = {}
        self._open: Dict[tuple, int] = {}
        self._stack: List[int] = []

    def enable(self) -> None:
        if self._annotation is None:
            from jax.profiler import TraceAnnotation
            self._annotation = TraceAnnotation
        self.on = True

    def disable(self) -> None:
        self.on = False

    def span(self, name: str):
        return _Span(self, name) if self.on else _OFF

    def begin(self, name: str, key) -> None:
        if self.on:
            self._open[name, key] = time.perf_counter_ns()

    def end(self, name: str, key) -> None:
        if self.on:
            start: Optional[int] = self._open.pop((name, key), None)
            if start is not None:
                self.requests.append((name, key, start,
                                      time.perf_counter_ns()))

    def add(self, name: str, n: int) -> None:
        if self.on:
            self.counters[name] = self.counters.get(name, 0) + n

    def records(self) -> dict:
        """``spans`` as tuples, ``requests`` and ``counters``."""
        return {"spans": [tuple(s) for s in self.spans],
                "requests": list(self.requests),
                "counters": dict(self.counters)}


#: the program's one tracer
TRACER = Tracer()
enable, disable, reset = TRACER.enable, TRACER.disable, TRACER.reset
span, begin, end, add = TRACER.span, TRACER.begin, TRACER.end, TRACER.add
records = TRACER.records

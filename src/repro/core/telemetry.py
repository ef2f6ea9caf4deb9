"""In-process spans and counters: where a request's and a round's time goes.

One recorder per process, always on (like `serving.ServeStats`): no config
field, no environment variable. It holds

- **spans** in one bounded in-memory ring of ``RING_SPANS`` (oldest
  dropped). A `Span` is a name, start and end in ``time.perf_counter_ns``
  nanoseconds, the ``id`` of the request, batch or round it belongs to (a
  compile: the compiled function's name), the ``seq`` of its parent span,
  its own ``seq`` (unique in the process) and, for a batch, the ``ids`` of
  the requests it carries;
- **counters** by name (`count`, `counters`).

`span` is a context manager for work that starts and ends in one thread;
nested spans take the enclosing one as parent. It also enters
``jax.profiler.TraceAnnotation(name)``, so inside a profiler session with
host events the span lands in the trace beside the device ops; outside one
the annotation costs about a microsecond. `add` records a span that starts
in one thread and ends in another (a request's queue wait): its stamps come
from `now_ns`, and `reserve` hands out a ``seq`` early so that children can
name a parent recorded after them.

The profiler stamps its events with the wall clock, and a trace read with
``jax.profiler.ProfileData`` gives them relative to the session's
``profile_start_time``: a span's stamp plus `profiler_offset_ns` is on the
profiler's clock.

A ``jax.monitoring`` listener records every backend compile as a
``jax.compile`` span (``id``: the function's name; parent: the span open in
the compiling thread) and counts ``jax.compiles``, ``jax.compile_ns``,
``jax.cache_hits`` and ``jax.cache_misses``; a recompile inside a measured
window then shows by name.

The spans the program records (``serve.*`` in `core/serving.py`, ``fl.*``
in `core/server.py`), their ids and what reads each: DESIGN.md §18.
"""
from __future__ import annotations

import collections
import itertools
import statistics
import threading
import time
from typing import Iterable, NamedTuple

import jax

RING_SPANS = 1 << 16
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "jax.cache_hits",
                "/jax/compilation_cache/cache_misses": "jax.cache_misses"}

now_ns = time.perf_counter_ns


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    id: int | str | None = None  # request, batch or round; a compile: the function's name
    parent: int | None = None  # seq of the enclosing span
    seq: int = 0  # unique in the process
    ids: tuple = ()  # a batch: the ids of the requests it carries

    @property
    def ns(self) -> int:
        return self.end_ns - self.start_ns


_ring: collections.deque[Span] = collections.deque(maxlen=RING_SPANS)
_seq = itertools.count(1)
_counters: dict[str, int] = {}
_lock = threading.Lock()
_local = threading.local()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def reserve() -> int:
    """A fresh span number, for a span recorded later with `add`."""
    return next(_seq)


def add(name: str, start_ns: int, end_ns: int, *, id=None, parent: int | None = None,
        ids: Iterable = (), seq: int | None = None) -> int:
    """Record a finished span; returns its ``seq``."""
    seq = next(_seq) if seq is None else seq
    _ring.append(Span(name, start_ns, end_ns, id, parent, seq, tuple(ids)))
    return seq


class span:
    """``with span(name, id=...) as s:`` records ``name`` around the block.
    ``s.seq`` names it as a parent; ``s.ids`` may be set inside the block."""

    __slots__ = ("name", "id", "ids", "parent", "seq", "start_ns", "end_ns", "_ann")

    def __init__(self, name: str, *, id=None, parent: int | None = None):
        self.name, self.id, self.parent, self.ids = name, id, parent, ()

    def __enter__(self) -> "span":
        stack = _stack()
        if self.parent is None and stack:
            self.parent = stack[-1].seq
        self.seq = next(_seq)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        stack.append(self)
        self.start_ns = now_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = now_ns()
        _stack().pop()
        self._ann.__exit__(*exc)
        _ring.append(Span(self.name, self.start_ns, self.end_ns, self.id, self.parent, self.seq,
                          tuple(self.ids)))


def count(name: str, n: int = 1) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def counters() -> dict[str, int]:
    with _lock:
        return dict(_counters)


def spans(name: str | None = None) -> list[Span]:
    """The ring's spans, oldest first (only those called ``name``, if given)."""
    snap = list(_ring)
    return snap if name is None else [s for s in snap if s.name == name]


def last(name: str, n) -> list[Span] | None:
    """The last ``n`` spans called ``name``, or None when the ring holds fewer
    (or ``n`` is not a positive count)."""
    found = spans(name)
    if not n or n < 1 or len(found) < n:
        return None
    return found[-n:]


def children(of: Iterable[Span] | None = None) -> dict[int, list[Span]]:
    """Parent seq -> its child spans, over ``of`` (the whole ring by default)."""
    out: dict[int, list[Span]] = {}
    for s in spans() if of is None else of:
        if s.parent is not None:
            out.setdefault(s.parent, []).append(s)
    return out


def self_ns(s: Span, kids: dict[int, list[Span]], names: Iterable[str] | None = None) -> int:
    """``s``'s duration less that of its children (only those called one of
    ``names``, if given); ``kids`` from `children`."""
    names = None if names is None else set(names)
    return s.ns - sum(c.ns for c in kids.get(s.seq, ()) if names is None or c.name in names)


def median_ms(values_ns: Iterable[int]) -> float | None:
    values = list(values_ns)
    return statistics.median(values) / 1e6 if values else None


def profiler_offset_ns() -> int:
    """Nanoseconds to add to a span's stamps to put them on the profiler's
    (wall) clock; the tightest of a few paired readings."""
    best = None
    for _ in range(5):
        a = time.perf_counter_ns()
        wall = time.time_ns()
        b = time.perf_counter_ns()
        if best is None or b - a < best[0]:
            best = (b - a, wall - (a + b) // 2)
    return best[1]


def reset() -> None:
    """Empty the ring and the counters."""
    _ring.clear()
    with _lock:
        _counters.clear()


def _on_duration(event: str, duration: float, fun_name: str = "?", **_) -> None:
    if event == COMPILE_EVENT:
        end, dur = now_ns(), int(duration * 1e9)
        stack = _stack()
        add("jax.compile", end - dur, end, id=fun_name, parent=stack[-1].seq if stack else None)
        count("jax.compiles")
        count("jax.compile_ns", dur)


def _on_event(event: str, **_) -> None:
    if event in CACHE_EVENTS:
        count(CACHE_EVENTS[event])


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)

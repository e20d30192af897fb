"""Spans at the layer boundaries of a recommend, on the profiler's clock.

`span(name)` marks one stretch of the program's own work, and
`traced(name)` marks every call of a function::

    with tracing.span("advisor.cost"):
        ...

    @tracing.traced("estimate.plan")
    def plan(...):
        ...

Tracing is off by default.  Then `span` checks one module flag and
returns a shared no-op, and a traced function checks the same flag and
calls straight through: neither records anything or opens an
annotation.
After `enable()` each span records a `Span` (name, start, end, its own
id, the parent span's id, the request id) in an in-process list, and
opens `jax.profiler.TraceAnnotation("repro." + name)`, so that the same
interval lands in the profiler's host plane, the plane the device's
events are aligned to.  `drain()` hands the recorded spans over and
clears the list; `disable()` turns recording off again.

Clock: the profiler stamps host events with the host's real-time clock
(`time.time_ns()`); an `.xplane.pb` read through
`jax.profiler.ProfileData` gives them as nanoseconds after the trace's
`profile_start_time`.  Spans read the same clock, the start just after
the annotation opens and the end just before it closes, so a span and
its trace event differ only by what runs between the two clock reads at
each end: a return from the profiler's call, or a pause of the
interpreter that falls there.

Nesting: a span's parent is the innermost span open when it started
(the advisor opens spans from one thread).  A span opened with
`request=True` (`advisor.recommend`) starts a request: it and every
span inside it carry its id as their request id.
"""
from __future__ import annotations

import functools
import itertools
import time
from typing import List, NamedTuple, Optional


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    span_id: int
    parent_id: Optional[int]
    request_id: Optional[int]


class _NoSpan:
    """What `span` returns while tracing is off."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return None


NO_SPAN = _NoSpan()

_enabled = False
_annotation = None           # jax.profiler.TraceAnnotation, bound by enable()
_spans: List[Span] = []
_ids = itertools.count(1)
_open: List["_OpenSpan"] = []  # the spans open now, innermost last
_now = time.time_ns          # the profiler's host clock (module docstring)


class _OpenSpan:
    __slots__ = ("name", "request", "span_id", "parent_id", "request_id",
                 "start_ns", "_note")

    def __init__(self, name: str, request: bool):
        self.name = name
        self.request = request

    def __enter__(self):
        parent = _open[-1] if _open else None
        self.span_id = next(_ids)
        self.parent_id = parent.span_id if parent is not None else None
        if self.request:
            self.request_id = self.span_id
        else:
            self.request_id = parent.request_id if parent is not None \
                else None
        _open.append(self)
        self._note = _annotation("repro." + self.name)
        self._note.__enter__()
        self.start_ns = _now()
        return self

    def __exit__(self, *exc):
        close = self._note.__exit__
        end_ns = _now()
        close(*exc)
        _open.pop()
        _spans.append(Span(self.name, self.start_ns, end_ns, self.span_id,
                           self.parent_id, self.request_id))
        return None


def span(name: str, request: bool = False):
    """A context manager around one stretch of work named `name`; with
    `request`, the stretch is one request (see the module docstring)."""
    if not _enabled:
        return NO_SPAN
    return _OpenSpan(name, request)


def traced(name: str, request: bool = False):
    """Decorator: each call of the function runs in `span(name, request)`."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            if not _enabled:
                return fn(*args, **kwargs)
            with _OpenSpan(name, request):
                return fn(*args, **kwargs)
        return call
    return wrap


def enable() -> None:
    """Record spans from now on."""
    global _enabled, _annotation
    import jax.profiler
    _annotation = jax.profiler.TraceAnnotation
    _enabled = True


def disable() -> None:
    """Record no more spans; those recorded stay until `drain()`."""
    global _enabled
    _enabled = False


def drain() -> List[Span]:
    """The spans recorded since the last drain, in the order they closed;
    the list is cleared."""
    out = _spans[:]
    del _spans[:len(out)]
    return out

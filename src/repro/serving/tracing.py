"""Spans, events, counters and gauges on the serve path, recorded in memory.

The recorder is off by default. Off, every call checks one module flag
and returns at once; ``span`` hands back one shared null context, so the
serve path pays a function call per site and keeps nothing. ``start()``
turns the recorder on and ``stop()`` turns it off and returns what it
recorded. While on, each span also enters
``jax.profiler.TraceAnnotation("repro." + name)``, so a profiler trace
taken meanwhile shows the span on the host timeline beside the device ops.

Timestamps are ``time.perf_counter_ns()``. A profiler trace counts its
own nanoseconds from the start of its session; ``Records.spans`` and the
trace's ``repro.*`` annotations pair up one to one, so one offset maps
either clock onto the other.

Nothing here runs inside a jitted function: the compiled programs are the
same with the recorder on or off.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

PREFIX = "repro."


class Span:
    """One timed region: ``parent`` is the index in ``Records.spans`` of
    the span open around it (-1 at the top). A span holds no reference
    to its ``Records``, so dropped records are freed at once, not left to
    the cycle collector."""
    __slots__ = ("name", "start", "end", "parent", "_open", "_ann")

    def __init__(self, name: str):
        self.name = name
        self.start = self.end = 0
        self.parent = -1

    def __enter__(self) -> Span:
        rec = _rec
        self._ann = _annotation(PREFIX + self.name)
        self._ann.__enter__()
        self._open = rec.open
        self.parent = rec.open[-1] if rec.open else -1
        rec.open.append(len(rec.spans))
        rec.spans.append(self)
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter_ns()
        self._open.pop()
        ann, self._ann = self._ann, None
        ann.__exit__(*exc)


class _Null:
    """What ``span`` returns while the recorder is off: falsy, so a call
    site can skip computing a counter's value with ``if sp:``."""
    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def __enter__(self) -> _Null:
        return self

    def __exit__(self, *exc) -> None:
        pass


NULL = _Null()


@dataclass
class Records:
    """What one recording holds; times in ``perf_counter_ns``."""
    spans: list = field(default_factory=list)     # Span, in start order
    events: list = field(default_factory=list)    # (name, t, rid)
    counts: list = field(default_factory=list)    # (name, t, n)
    gauges: list = field(default_factory=list)    # (name, t, value)
    open: list = field(default_factory=list)      # indices of open spans

    def total(self, name: str, until: int | None = None) -> int:
        """Sum of ``count(name, n)`` up to ``until``."""
        return sum(n for k, t, n in self.counts
                   if k == name and (until is None or t <= until))


_rec: Records | None = None
_annotation = None    # jax.profiler.TraceAnnotation, imported by start()


def start() -> None:
    """Turn the recorder on with empty records."""
    global _rec, _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
    _rec = Records()


def stop() -> Records | None:
    """Turn the recorder off; returns what it recorded (None if it was
    off)."""
    global _rec
    rec, _rec = _rec, None
    return rec


def span(name: str):
    """Context manager timing a region; ``as sp`` gives the ``Span``, or
    the falsy null context while the recorder is off."""
    if _rec is None:
        return NULL
    return Span(name)


def event(name: str, rid: str) -> None:
    """An instant in the life of request ``rid``."""
    if _rec is None:
        return
    _rec.events.append((name, time.perf_counter_ns(), rid))


def count(name: str, n: int) -> None:
    """Add ``n`` to a counter."""
    if _rec is None:
        return
    _rec.counts.append((name, time.perf_counter_ns(), n))


def gauge(name: str, value: float) -> None:
    """Record a sampled value."""
    if _rec is None:
        return
    _rec.gauges.append((name, time.perf_counter_ns(), value))

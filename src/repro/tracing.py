"""Spans, counters and samples of the program, always recorded.

``span(name, **attrs)`` times a block on ``time.perf_counter_ns()`` into a
bounded in-memory ring, with the innermost span open on the same thread
as its parent; while a profiler trace is taken it is also a
``TraceAnnotation`` on the host plane, on the device events' clock.
``count(name, n)`` adds to a monotonic counter and stamps the change into
the ring; ``observe(name, value, **attrs)`` stamps a sample whose start
lies in the past (a request's queue wait). Each program compiled or
fetched from the persistent cache counts under ``compiles:<innermost open
span>`` (``compiles:none`` outside any span). Readers take a window
``[t0_ns, t1_ns)`` on the same clock; one that reaches back past the
oldest entry still held, once the ring has dropped any, reads None.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import NamedTuple, Optional

from jax import monitoring, profiler

RING = 1 << 17
COMPILES = "compiles:"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_clock = time.perf_counter_ns
_tracing = profiler.TraceAnnotation.is_enabled


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: int        # index of the enclosing span, -1 at a root
    attrs: dict
    index: int
    child_ns: int      # what the span's direct children cover


class Count(NamedTuple):
    name: str
    n: int
    ns: int


class Sample(NamedTuple):
    name: str
    value: float
    ns: int
    attrs: dict


_KINDS = {"span": Span, "count": Count, "sample": Sample}


class Recorder:
    """The ring, the counters and each thread's open spans. Entries are
    plain tuples, ``(kind, *fields)`` with the stamp (a span's end) at
    index 3, made into ``Span``/``Count``/``Sample`` only when read."""

    def __init__(self, maxlen: int = RING):
        self.ring: deque = deque(maxlen=maxlen)
        self.appended = 0
        self._ids = itertools.count()
        threads = self._threads = []          # each thread's totals

        class _Local(threading.local):
            def __init__(self):
                self.stack = []               # open spans, innermost last
                self.totals = {}
                threads.append(self.totals)

        self._local = _Local()

    @property
    def dropped(self) -> int:
        """Entries the ring has let go."""
        return max(0, self.appended - self.ring.maxlen)

    def span(self, name: str, **attrs) -> "_SpanScope":
        return _SpanScope(self, name, attrs)

    def count(self, name: str, n: int = 1) -> None:
        n = int(n)
        totals = self._local.totals            # one writer per dict
        totals[name] = totals.get(name, 0) + n
        self.ring.append(("count", name, n, _clock()))
        self.appended += 1

    def observe(self, name: str, value: float, **attrs) -> None:
        self.ring.append(("sample", name, float(value), _clock(), attrs))
        self.appended += 1

    def counters(self) -> dict:
        out: dict = {}
        for totals in list(self._threads):
            for name, n in dict(totals).items():
                out[name] = out.get(name, 0) + n
        return out

    def between(self, t0_ns: int, t1_ns: int, kind: str) -> Optional[list]:
        """Entries of ``kind`` stamped in [t0_ns, t1_ns) (spans: that start
        and end inside), or None where the ring no longer holds it all."""
        ring = list(self.ring)
        if self.dropped and ring and ring[0][3] >= t0_ns:
            return None
        make = _KINDS[kind]._make
        out = [make(e[1:]) for e in ring if e[0] == kind
               and t0_ns <= e[3] < t1_ns]
        return [s for s in out if s.start_ns >= t0_ns] \
            if kind == "span" else out


class _SpanScope:
    __slots__ = ("rec", "name", "attrs")

    def __init__(self, rec: Recorder, name: str, attrs: dict):
        self.rec, self.name, self.attrs = rec, name, attrs

    def __enter__(self):
        rec, ann = self.rec, None
        if _tracing():
            ann = profiler.TraceAnnotation(self.name)
            ann.__enter__()
        # [index, name, start, child_ns, annotation, attrs]
        rec._local.stack.append([next(rec._ids), self.name, _clock(), 0,
                                 ann, self.attrs])
        return self

    def __exit__(self, *exc) -> bool:
        end = _clock()
        rec = self.rec
        stack = rec._local.stack
        index, name, start, child, ann, attrs = stack.pop()
        if ann is not None:
            ann.__exit__(None, None, None)
        parent = -1
        if stack:
            stack[-1][3] += end - start
            parent = stack[-1][0]
        rec.ring.append(("span", name, start, end, parent, attrs, index,
                         child))
        rec.appended += 1
        return False


RECORDER = Recorder()
span, count, observe = RECORDER.span, RECORDER.count, RECORDER.observe
counters = RECORDER.counters


def spans_between(t0_ns: int, t1_ns: int) -> Optional[list]:
    return RECORDER.between(t0_ns, t1_ns, "span")


def samples_between(t0_ns: int, t1_ns: int) -> Optional[list]:
    return RECORDER.between(t0_ns, t1_ns, "sample")


def counts_between(t0_ns: int, t1_ns: int) -> Optional[dict]:
    """Each counter's change inside the window."""
    got = RECORDER.between(t0_ns, t1_ns, "count")
    if got is None:
        return None
    out: dict = {}
    for c in got:
        out[c.name] = out.get(c.name, 0) + c.n
    return out


def self_ns(s: Span) -> int:
    """The span's duration less what its direct children cover."""
    return s.end_ns - s.start_ns - s.child_ns


def _on_compile(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        stack = RECORDER._local.stack
        count(COMPILES + (stack[-1][1] if stack else "none"))


monitoring.register_event_duration_secs_listener(_on_compile)

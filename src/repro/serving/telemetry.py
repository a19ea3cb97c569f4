"""Host spans of the serving engine: what each tick spent its time on.

One recorder serves the whole process and is always on.  ``span(name)``
times a block of host code with ``time.monotonic()`` (the clock of the
engine's ``arrival_s`` / ``first_token_s``) and keeps the record in a
bounded ring, the oldest dropping first.  The same call opens a
``jax.profiler.TraceAnnotation``, so while a profile is being taken the span
also lands on the host plane of the device trace, on the trace's own clock;
with no profile running that costs one check in C++.

Each record names its enclosing span (``parent``), so a span's self time is
its duration less what its children cover.  The engine is single-threaded,
so the open spans form one plain stack.

    from repro.serving import telemetry

    with telemetry.span("engine.decode", n=live_rows):
        ...
    records = telemetry.spans()
"""

from __future__ import annotations

import collections
import time
from typing import Callable, List, NamedTuple

from jax.profiler import TraceAnnotation

__all__ = ["Span", "Recorder", "RECORDER", "span", "spans", "clear"]

CAPACITY = 65536


class Span(NamedTuple):
    """One closed span.  ``id`` counts spans in the order they opened;
    ``parent`` is the ``id`` of the span open around it (-1 at the root);
    ``rid`` the request it served (-1 for none) and ``n`` the work it
    carried (rows, tokens, or the tick number)."""
    id: int
    name: str
    start: float
    end: float
    parent: int
    rid: int
    n: int


class _Open:
    """A span while it is open: ``rid`` and ``n`` may still be set on it,
    and are recorded when it closes."""
    __slots__ = ("rec", "name", "rid", "n", "id", "parent", "start", "ann")

    def __init__(self, rec: "Recorder", name: str, rid: int, n: int):
        self.rec, self.name, self.rid, self.n = rec, name, rid, n

    def __enter__(self) -> "_Open":
        rec = self.rec
        stack = rec._stack
        self.id = sid = rec._next_id
        rec._next_id = sid + 1
        self.parent = stack[-1] if stack else -1
        stack.append(sid)
        self.ann = ann = TraceAnnotation(self.name)
        ann.__enter__()
        self.start = rec._clock()
        return self

    def __exit__(self, *exc) -> None:
        rec = self.rec
        end = rec._clock()
        self.ann.__exit__(*exc)
        rec._stack.pop()
        # a plain tuple here: ``spans()`` makes the ``Span``s off the hot path
        rec._ring.append((self.id, self.name, self.start, end, self.parent,
                          self.rid, self.n))


class Recorder:
    """A bounded ring of closed spans.  ``clock`` is the time source
    (``time.monotonic``; a test may hand in its own)."""

    def __init__(self, capacity: int = CAPACITY,
                 clock: Callable[[], float] = time.monotonic):
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._stack: List[int] = []
        self._next_id = 0
        self._clock = clock

    def span(self, name: str, *, rid: int = -1, n: int = 0) -> _Open:
        """Context manager timing the block under ``name``."""
        return _Open(self, name, rid, n)

    def spans(self) -> List[Span]:
        """A snapshot of the ring, in the order the spans closed."""
        return [Span._make(r) for r in self._ring]

    def clear(self) -> None:
        self._ring.clear()


RECORDER = Recorder()


def span(name: str, *, rid: int = -1, n: int = 0) -> _Open:
    """A span on the process recorder."""
    return RECORDER.span(name, rid=rid, n=n)


def spans() -> List[Span]:
    return RECORDER.spans()


def clear() -> None:
    RECORDER.clear()

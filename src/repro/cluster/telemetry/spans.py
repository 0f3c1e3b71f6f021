"""Spans: where one item's time goes, layer by layer, in one process.

A span is one timed stretch of a layer's work, recorded as the tuple
``(name, start_ns, end_ns, span_id, parent_id, thread, ids)``:

* ``start_ns``/``end_ns`` come from :func:`time.time_ns`, epoch
  nanoseconds, so the spans of the host and of every node-loader on one
  machine share a clock with each other and with the JAX profiler's
  trace (its ``profile_start_time`` is epoch nanoseconds too);
* ``parent_id`` is the span open on the same thread when this one opened,
  and the child inherits the parent's ``ids``: a node worker opens
  ``node.work`` with the item's ``job``/``item`` and the work function's
  own spans carry them without taking them as arguments;
* a stretch that starts on one thread and ends on another (an item
  waiting in a queue) is recorded whole with :func:`record`, without a
  parent.

Records go into a bounded ring; a record that pushes out the oldest one
is counted as dropped (as :class:`~repro.cluster.telemetry.Telemetry`'s
event ring counts its own).  Recording is off until :func:`enable`; while
it is off :func:`span` costs one flag test and returns a shared no-op, and
:func:`stamp` returns ``None`` without reading the clock.  :func:`drain`
hands back what was recorded and the drop count, and empties the ring:
spans are kept in memory and written out by whoever drains them.

The span names the cluster records, and the metric or breakdown each
feeds, are listed in ARCHITECTURE.md "Observability".
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any

__all__ = ["Recorder", "SPAN_RING_SLOTS", "disable", "drain", "enable",
           "record", "self_times", "span", "stamp"]

# About 30 s of the Mandelbrot farm at 800 bands/s and nine spans a band;
# a record costs about 200 B, so a full ring holds about 26 MB.
SPAN_RING_SLOTS = 1 << 17


class _NoSpan:
    """What :func:`span` returns while recording is off."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NO_SPAN = _NoSpan()


class _Span:
    __slots__ = ("_rec", "_state", "name", "ids", "span_id", "parent_id",
                 "start")

    def __init__(self, rec: "Recorder", name: str, ids: dict):
        self._rec = rec
        self.name = name
        self.ids = ids

    def __enter__(self) -> "_Span":
        rec = self._rec
        state = self._state = rec._thread_state()
        stack = state.stack
        if stack:
            parent = stack[-1]
            self.parent_id = parent.span_id
            if parent.ids:
                self.ids = {**parent.ids, **self.ids}
        else:
            self.parent_id = None
        self.span_id = next(rec._ids)
        stack.append(self)
        self.start = time.time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        end = time.time_ns()
        state = self._state
        state.stack.pop()
        rec = self._rec
        if rec.on:  # a span still open at disable() is not kept
            rec._append((self.name, self.start, end, self.span_id,
                         self.parent_id, state.thread, self.ids))
        return False


class Recorder:
    """One process's span ring.  The module-level functions act on the
    process's own :data:`RECORDER`; tests make their own."""

    def __init__(self, slots: int = SPAN_RING_SLOTS):
        self.on = False
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=slots)
        self._dropped = 0
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _thread_state(self):
        local = self._local
        try:
            return local.state
        except AttributeError:
            state = local.state = _ThreadState()
            return state

    def _append(self, rec: tuple) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self._dropped += 1
            self._ring.append(rec)

    def span(self, name: str, **ids: Any):
        """A context manager that records ``name`` around its body."""
        if not self.on:
            return _NO_SPAN
        return _Span(self, name, ids)

    def stamp(self) -> int | None:
        """Now, in epoch nanoseconds, while recording is on; else None."""
        return time.time_ns() if self.on else None

    def record(self, name: str, start_ns: int, end_ns: int,
               **ids: Any) -> None:
        """Record a span whose two ends were read on different threads."""
        if self.on:
            self._append((name, start_ns, end_ns, next(self._ids), None,
                          self._thread_state().thread, ids))

    def enable(self) -> None:
        self.on = True

    def disable(self) -> None:
        self.on = False

    def drain(self) -> tuple[list[tuple], int]:
        """The records kept so far, oldest first, and how many were
        dropped; the ring starts empty again."""
        with self._lock:
            records = list(self._ring)
            self._ring.clear()
            dropped, self._dropped = self._dropped, 0
        return records, dropped


class _ThreadState:
    __slots__ = ("stack", "thread")

    def __init__(self):
        self.stack: list[_Span] = []
        self.thread = threading.current_thread().name


RECORDER = Recorder()


span = RECORDER.span
stamp = RECORDER.stamp
record = RECORDER.record


# These three take one argument and ignore it, so that each can be the work
# function of a one-item job: that is how a node-loader's spans are turned
# on, off and collected (ARCHITECTURE.md "Observability").

def enable(_item: Any = None) -> None:
    """Start recording spans in this process."""
    RECORDER.enable()


def disable(_item: Any = None) -> None:
    """Stop recording; spans still open are not kept."""
    RECORDER.disable()


def drain(_item: Any = None) -> tuple[list[tuple], int]:
    """This process's records so far and its drop count (see
    :meth:`Recorder.drain`)."""
    return RECORDER.drain()


def self_times(records: list[tuple]) -> dict[str, tuple[int, int, int]]:
    """Per span name: ``(count, total_ns, self_ns)``.  A span's self time
    is its duration less the part of it that its child spans cover."""
    children: dict[Any, list[tuple[int, int]]] = collections.defaultdict(
        list)
    for r in records:
        if r[4] is not None:
            children[r[4]].append((r[1], r[2]))
    out: dict[str, list[int]] = {}
    for name, start, end, span_id, *_ in records:
        covered, t = 0, start
        for s, e in sorted(children.get(span_id, ())):
            s, e = max(s, t), min(e, end)
            if e > s:
                covered += e - s
                t = e
        acc = out.setdefault(name, [0, 0, 0])
        acc[0] += 1
        acc[1] += end - start
        acc[2] += end - start - covered
    return {name: tuple(v) for name, v in out.items()}

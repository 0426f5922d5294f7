"""The program's span recorder: where the host's time goes, on the same
clock as the profiler's trace.

    with span("khaos.ckpt.commit", step=12, parent=save_id) as sp:
        ...
        sp.add(bytes_written=n)

A span keeps its name, id, parent id, thread, start and end on
``time.perf_counter()``, the step it belongs to and integer counts (bytes
read or written, tokens), so counters need no mechanism of their own.  The
parent is the innermost span open on the same thread; work handed to
another thread (the commit thread, the io and transfer pools) names its
parent explicitly with the id ``current()`` gave where the work was
queued.  Each span also enters ``jax.profiler.TraceAnnotation`` under its
name, so it shows on the profiler's host lines whenever a profile runs.

Closed spans go to a bounded in-memory buffer that drops the oldest when
full and counts the drops; ``spans(t0, t1)`` reads it back.  Nothing is
written anywhere.
"""
from __future__ import annotations

import itertools
import math
import threading
import time
from collections import deque
from typing import Optional

import jax

CAPACITY = 1 << 16


class Span:
    """One timed phase; enter it with ``with``."""

    __slots__ = ("name", "id", "parent", "thread", "start", "end", "step",
                 "counts", "_rec", "_ann")

    def __init__(self, rec: "Recorder", name: str, step: Optional[int],
                 parent: Optional[int], counts: dict):
        self._rec = rec
        self.name = name
        self.id = next(rec._ids)
        self.parent = parent
        self.step = step
        self.counts = counts
        self.thread = 0
        self.start = self.end = math.nan

    def add(self, **counts: int) -> None:
        """Add to the span's counts (a count not there yet starts at 0)."""
        for k, v in counts.items():
            self.counts[k] = self.counts.get(k, 0) + int(v)

    @property
    def duration(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "Span":
        stack = self._rec._stack()
        if self.parent is None and stack:
            self.parent = stack[-1].id
        self.thread = threading.get_ident()
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._ann.__exit__(*exc)
        self._ann = None
        stack = self._rec._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._rec._keep(self)


class Recorder:
    """A bounded buffer of closed spans, and the per-thread stacks of open
    ones."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self._buf: deque = deque()
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = threading.local()
        self.dropped = 0
        self._dropped_end = -math.inf

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _keep(self, sp: Span) -> None:
        with self._lock:
            if len(self._buf) >= self.capacity:
                old = self._buf.popleft()
                self.dropped += 1
                self._dropped_end = max(self._dropped_end, old.end)
            self._buf.append(sp)

    def span(self, name: str, *, step: Optional[int] = None,
             parent: Optional[int] = None, **counts: int) -> Span:
        """A span named ``name``; ``parent`` (an id) overrides the
        innermost span open on this thread."""
        return Span(self, name, step, parent, dict(counts))

    def current(self) -> Optional[int]:
        """The id of the innermost span open on this thread, if any."""
        stack = self._stack()
        return stack[-1].id if stack else None

    def spans(self, t0: float, t1: float) -> tuple[list, int]:
        """The closed spans that start in [t0, t1], oldest first, and how
        many spans the buffer dropped: 0 when every dropped span ended
        before ``t0``, else all the drops so far."""
        with self._lock:
            out = [s for s in self._buf if t0 <= s.start <= t1]
            dropped = self.dropped if self._dropped_end >= t0 else 0
        return sorted(out, key=lambda s: s.start), dropped


RECORDER = Recorder()
span = RECORDER.span
current = RECORDER.current
spans = RECORDER.spans

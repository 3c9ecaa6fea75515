"""Structured tracing spans: a context-manager API over a bounded
in-memory ring buffer (see package docstring).

Design constraints, in order:

1. **The disabled path is the hot path.**  ``span()`` always measures
   wall-time — result accounting (``MappingResult.construction_seconds``
   etc.) reads ``span.dur`` whether or not tracing is on — but the span
   is only appended to the ring buffer when the tracer is enabled, so
   serving traffic pays one ``perf_counter`` pair per span, exactly what
   the ad-hoc timing it replaced cost, and never touches ``jax``.
2. **Bounded memory.**  The buffer is a ``deque(maxlen=capacity)``;
   long-lived services drop the *oldest* spans (``dropped`` counts them)
   instead of growing without bound.
3. **Thread-safe.**  Spans record the emitting thread; nesting (depth and
   the open parent span) is tracked per-thread, so a service worker's
   spans interleave cleanly with client-thread spans in the exported
   trace.
4. **One clock with the device.**  While enabled, every live span also
   enters a ``jax.profiler.TraceAnnotation`` of its name, so a profiler
   trace taken meanwhile holds the program's spans on its host plane, on
   the same clock as the device operations.

Spans of one request share an identifier: :meth:`Tracer.request` sets a
per-thread request context (the service's tickets) that every span
opened inside records as ``req``.

One process-global tracer (``get_tracer()``) is shared by every layer so
a single ``enable()`` captures lower/construct/refine/execute/tick spans
end to end; independent ``Tracer`` instances remain available for tests
and embedded use.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field

__all__ = ["Span", "Tracer", "get_tracer"]

_IDS = itertools.count(1)       # span ids, unique per process


@dataclass
class Span:
    """One recorded operation: name, category, wall-clock window
    (``t0``/``dur`` in ``perf_counter`` seconds), emitting thread,
    per-thread nesting depth, its ``id``, the ``id`` of the span open on
    the same thread when it started (``parent``, ``None`` at the top),
    the request tickets it serves (``req``), and free-form
    attributes."""
    name: str
    cat: str = "viem"
    t0: float = 0.0
    dur: float = 0.0
    tid: int = 0
    depth: int = 0
    attrs: dict = field(default_factory=dict)
    id: int = 0
    parent: int | None = None
    req: tuple | None = None

    def to_dict(self) -> dict:
        from .export import sanitize_attrs
        return {"name": self.name, "cat": self.cat, "t0": self.t0,
                "dur": self.dur, "tid": self.tid, "depth": self.depth,
                "id": self.id, "parent": self.parent,
                "req": None if self.req is None else list(self.req),
                "attrs": sanitize_attrs(self.attrs)}


class Tracer:
    """Span recorder with a bounded ring buffer (see module docstring).

    ``span(name, **attrs)`` is a context manager yielding the live
    :class:`Span` — callers may add attributes inside the block and read
    ``span.dur`` after it.
    """

    def __init__(self, capacity: int = 65536, enabled: bool = False):
        self.capacity = int(capacity)
        self.enabled = bool(enabled)
        self.dropped = 0
        self._buf: "deque[Span]" = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._local = threading.local()

    # ------------------------------------------------------------ control
    def enable(self, capacity: int | None = None) -> "Tracer":
        """Start recording (optionally resizing the ring buffer)."""
        if capacity is not None:
            # compare-and-resize under one lock scope: the bare-read
            # check raced a concurrent enable() resizing the buffer
            with self._lock:
                if int(capacity) != self.capacity:
                    self.capacity = int(capacity)
                    self._buf = deque(self._buf, maxlen=self.capacity)
        self.enabled = True
        return self

    def disable(self) -> "Tracer":
        self.enabled = False
        return self

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self.dropped = 0

    # ------------------------------------------------------------ record
    def _open(self) -> list:
        """This thread's stack of open span ids."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _append(self, sp: Span) -> None:
        with self._lock:
            if len(self._buf) == self._buf.maxlen:
                self.dropped += 1
            self._buf.append(sp)

    @contextmanager
    def request(self, tickets):
        """Spans opened on this thread inside the block record
        ``tickets`` as their ``req``."""
        prev = getattr(self._local, "req", None)
        self._local.req = tuple(tickets)
        try:
            yield
        finally:
            self._local.req = prev

    @contextmanager
    def span(self, name: str, cat: str = "viem", **attrs):
        stack = self._open()
        sp = Span(name=name, cat=cat, t0=time.perf_counter(),
                  tid=threading.get_ident(), depth=len(stack),
                  attrs=attrs, id=next(_IDS),
                  parent=stack[-1] if stack else None,
                  req=getattr(self._local, "req", None))
        stack.append(sp.id)
        note = None
        if self.enabled:
            import jax
            note = jax.profiler.TraceAnnotation(name)
            note.__enter__()
        try:
            yield sp
        finally:
            if note is not None:
                note.__exit__(None, None, None)
            stack.pop()
            sp.dur = time.perf_counter() - sp.t0
            if self.enabled:
                self._append(sp)

    def record(self, name: str, dur: float, cat: str = "viem",
               t0: float | None = None, req: tuple | None = None,
               **attrs) -> Span:
        """Record an already-measured interval (for code that cannot
        wrap the work in a ``with`` block).  Its parent is the span open
        on this thread now; ``req`` defaults to the request context.
        Such a span is never mirrored into the profiler trace: the
        interval is over before it is known."""
        stack = self._open()
        sp = Span(name=name, cat=cat, dur=float(dur),
                  t0=time.perf_counter() - float(dur) if t0 is None
                  else float(t0),
                  tid=threading.get_ident(), depth=len(stack),
                  attrs=attrs, id=next(_IDS),
                  parent=stack[-1] if stack else None,
                  req=(getattr(self._local, "req", None) if req is None
                       else tuple(req)))
        if self.enabled:
            self._append(sp)
        return sp

    # ------------------------------------------------------------ inspect
    def spans(self) -> "list[Span]":
        """Snapshot of the ring buffer (oldest first)."""
        with self._lock:
            return list(self._buf)

    def drain(self) -> "list[Span]":
        """Snapshot and clear in one atomic step."""
        with self._lock:
            out = list(self._buf)
            self._buf.clear()
            return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)


_GLOBAL = Tracer(enabled=False)


def get_tracer() -> Tracer:
    """The process-global tracer every pipeline layer records into.  It
    is a stable singleton — hold the reference; ``enable()``/``disable``
    toggle recording without invalidating it."""
    return _GLOBAL

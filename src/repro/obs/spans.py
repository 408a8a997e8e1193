"""In-memory spans on the host's `perf_counter`, for the serving path.

A span is one stage of one request or one batch: its name, start and
end (`time.perf_counter()` seconds), its own id, its parent's id (the
span open on the same thread when it began) and the request and batch
it belongs to. Spans go into a bounded buffer in memory; nothing is
written to a sink or to disk while serving. A reader drains them after
the run and maps `perf_counter` onto whatever clock it compares with
(the benchmark maps it onto the device trace's clock).

One process-wide recorder, `RECORDER`, because the spans sit in code
that holds no handle of the service (the detector's dispatch, the
result's device-to-host fetch). The service switches it on at `start()`
when `MetricsConfig.spans` is above 0 (the buffer's capacity) and off
at `stop()`; what was recorded stays until drained.

Off, `span(name)` is one flag check and returns a shared no-op
context: it allocates nothing, takes no lock and never touches the
device. On, it also records two kinds of span that explain stalls:
`host.gc` (a `gc.callbacks` hook) and `jax.compile` (a `jax.monitoring`
duration listener: end = now, start = now - duration). Both hooks are
installed only while the recorder is on.

This module imports without jax (`repro.obs` loads on the pre-jax-init
config path); jax is imported when the recorder is switched on.
"""
from __future__ import annotations

import gc
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

#: the XLA compile event the `jax.compile` span is made from
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Span(NamedTuple):
    """One finished span; times in `perf_counter` seconds."""

    name: str
    start: float
    end: float
    id: int
    parent: Optional[int]
    request: Optional[int]
    batch: Optional[int]


class _Off:
    """The context `span()` returns while the recorder is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Open:
    """A span being timed; pushed on its thread's stack while open."""

    __slots__ = ("_rec", "name", "start", "id", "parent", "request",
                 "batch")

    def __init__(self, rec: "SpanRecorder", name: str,
                 request: Optional[int], batch: bool):
        self._rec = rec
        self.name = name
        self.id = next(rec._ids)
        stack = rec._stack()
        top = stack[-1] if stack else None
        self.parent = top.id if top is not None else None
        self.request = request if request is not None else (
            top.request if top is not None else None)
        self.batch = self.id if batch else (
            top.batch if top is not None else None)
        self.start = 0.0

    def __enter__(self):
        self._rec._stack().append(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        stack = self._rec._stack()
        if stack and stack[-1] is self:
            stack.pop()
        self._rec._keep(Span(self.name, self.start, end, self.id,
                             self.parent, self.request, self.batch))
        return False


class SpanRecorder:
    """Bounded in-memory span buffer with a count of dropped spans."""

    def __init__(self):
        self.on = False
        self.capacity = 0
        self.dropped = 0
        self._buf: List[Span] = []
        # reentrant: a gc.callbacks hook may record while this thread
        # holds the lock (an allocation under it can start a collection)
        self._lock = threading.RLock()
        self._ids = itertools.count(1)
        self._local = threading.local()

    # ------------------------------------------------------------ switch
    def start(self, capacity: int) -> None:
        """Record up to `capacity` spans (0 leaves the recorder off)."""
        if capacity <= 0 or self.on:
            return
        import jax
        self.capacity = int(capacity)
        gc.callbacks.append(self._on_gc)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_compile)
        self.on = True

    def stop(self) -> None:
        """Stop recording; the buffer is kept for `drain()`."""
        if not self.on:
            return
        import jax
        self.on = False
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        jax.monitoring.unregister_event_duration_listener(self._on_compile)

    # ---------------------------------------------------------- recording
    def span(self, name: str, request: Optional[int] = None,
             batch: bool = False):
        """A context timing one stage. `batch=True` opens a new batch
        (its id is the span's own); otherwise the span takes the request
        and batch of the span open on this thread, if any."""
        if not self.on:
            return _OFF
        return _Open(self, name, request, batch)

    def new_id(self) -> int:
        """A fresh id, e.g. for a request whose span starts and ends on
        different threads (`add`)."""
        return next(self._ids)

    def current_batch(self) -> Optional[int]:
        """The batch of the span open on this thread."""
        stack = self._stack()
        return stack[-1].batch if stack else None

    def add(self, name: str, start: float, end: float,
            request: Optional[int] = None, batch: Optional[int] = None,
            parent: Optional[int] = None) -> None:
        """Record a span timed elsewhere."""
        if self.on:
            self._keep(Span(name, start, end, self.new_id(), parent,
                            request, batch))

    def _stack(self) -> list:
        try:
            return self._local.stack
        except AttributeError:
            self._local.stack = []
            return self._local.stack

    def _keep(self, s: Span) -> None:
        with self._lock:
            if len(self._buf) < self.capacity:
                self._buf.append(s)
            else:
                self.dropped += 1

    def _child_of_current(self, name: str, start: float, end: float) -> None:
        stack = self._stack()
        top = stack[-1] if stack else None
        self.add(name, start, end,
                 request=top.request if top is not None else None,
                 batch=top.batch if top is not None else None,
                 parent=top.id if top is not None else None)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._local.gc_start = time.perf_counter()
        elif phase == "stop":
            t0 = getattr(self._local, "gc_start", None)
            if t0 is not None:
                self._child_of_current("host.gc", t0, time.perf_counter())
                self._local.gc_start = None

    def _on_compile(self, event: str, duration: float, **_) -> None:
        if event == _COMPILE_EVENT:
            now = time.perf_counter()
            self._child_of_current("jax.compile", now - duration, now)

    # ------------------------------------------------------------ reading
    def spans(self) -> List[Span]:
        """The spans recorded so far, in the order they ended."""
        with self._lock:
            return list(self._buf)

    def drain(self) -> Tuple[List[Span], int]:
        """The recorded spans and the count dropped; empties the buffer."""
        with self._lock:
            out, dropped = self._buf, self.dropped
            self._buf, self.dropped = [], 0
        return out, dropped


def totals(spans: List[Span]) -> Dict[str, Dict[str, float]]:
    """Per span name: count, total ms and self ms (the span's time less
    what its children cover)."""
    child_ms: Dict[int, float] = {}
    for s in spans:
        if s.parent is not None:
            child_ms[s.parent] = child_ms.get(s.parent, 0.0) \
                + 1e3 * (s.end - s.start)
    out: Dict[str, Dict[str, float]] = {}
    for s in spans:
        t = out.setdefault(s.name, {"count": 0, "total_ms": 0.0,
                                    "self_ms": 0.0})
        ms = 1e3 * (s.end - s.start)
        t["count"] += 1
        t["total_ms"] += ms
        t["self_ms"] += ms - child_ms.get(s.id, 0.0)
    return out


#: the process's recorder (see the module docstring)
RECORDER = SpanRecorder()
span = RECORDER.span

"""Always-on span tracing into a bounded ring buffer, mirrored into the
JAX profiler.

Each span is one complete interval — name, track, start/end on the
shared :func:`time.perf_counter` clock, small ``args`` dict, and the
name of the span it was opened under — appended to a
``deque(maxlen=...)`` so memory stays bounded no matter how long a
server runs; old spans fall off the back.

Two recording styles:

* ``with tracer.span("engine.wait", morsel=3) as sp:`` — timed by the
  context manager.  The span records its parent (the innermost span
  still open on this thread) and inherits the parent's ``morsel``
  sequence number.  While the tracer is enabled and a profiler hook is
  set (:func:`set_profiler_hook`), the span also opens the hook's
  annotation under the span's name for its duration, so it lands on
  the profiler's host plane on the same clock as the device's ops.
  ``sp.seconds`` is the span's duration after the block, measured even
  when the tracer is disabled: stage timers read it, so the span and
  the stage seconds are one measurement.
* ``tracer.add_span("plan", t0, t1, track="plans", ...)`` —
  explicitly timed, for intervals only known afterwards (a plan's
  first dispatch to last collect).  These stay in the ring buffer:
  only ``with``-spans reach the profiler.

Tracks are logical timelines ("host", "plans", "shards"), not OS
threads.  The Chrome exporter maps each track to a tid with a
thread_name metadata event.

``tracer.enabled = False`` stops recording and mirroring: ``span``
still times its block, ``add_span`` returns early — the same
kill-switch discipline as the metrics registry.

:mod:`repro.obs` imports only the standard library, so the profiler
hook is a factory ``name -> context manager`` that a module importing
JAX installs once (``repro.core.inference`` installs
``jax.profiler.TraceAnnotation``).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, ContextManager, Dict, List, Optional

#: Default ring capacity — at 8 spans per morsel this holds ~4k
#: morsels of history, a few hundred bytes each.
DEFAULT_CAPACITY = 32768

#: The ``with``-spans of the served path, each opened where its work
#: happens (DESIGN.md §Tracing lists their parents).  The benchmark
#: keeps these names from the profiler's host plane.
PROFILER_SPANS = (
    "serve.merge",      # LookupServer: concatenate requests .. np.unique
    "serve.scatter",    # LookupServer: per-column concat + gather to callers
    "exec.key_source",  # executor: a plan's key stream (index walk or cache)
    "collect",          # executor: one morsel's host half
    "engine.dispatch",  # InferenceEngine.dispatch: featurize, upload, launch
    "engine.wait",      # InferenceEngine.collect: blocked on device outputs
    "store.exist",      # host BitVector.test fallback, only when it runs
    "aux.get",          # AuxTable.get
    "aux.decompress",   # inside aux.get: one wave of pool misses, or a view build
    "store.filter",     # predicate filter on aux-corrected codes
    "store.decode",     # decode of the selected columns
    "exec.agg",         # code-space group-by fold of one chunk
)

_profiler_hook: Optional[Callable[[str], ContextManager]] = None
_open = threading.local()  # .stack: the spans open on this thread


def set_profiler_hook(
    factory: Optional[Callable[[str], ContextManager]],
) -> Optional[Callable[[str], ContextManager]]:
    """Install the annotation factory every enabled tracer mirrors its
    ``with``-spans into (None turns mirroring off); returns the
    previous one."""
    global _profiler_hook
    prev, _profiler_hook = _profiler_hook, factory
    return prev


def _stack() -> list:
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


@dataclass
class Span:
    """One completed interval on a logical track."""

    name: str
    track: str
    start: float  # perf_counter seconds
    end: float
    args: Dict[str, object] = field(default_factory=dict)
    #: name of the span open on the same thread when this one opened
    parent: str = ""

    @property
    def duration(self) -> float:
        return self.end - self.start


class _SpanContext:
    """Context manager handed out by :meth:`Tracer.span`; records the
    span on exit (even when the body raises, so traces show the work
    that was attempted).  With ``record`` False it only times."""

    __slots__ = ("_tracer", "name", "track", "args", "start", "end",
                 "parent", "_record", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, track: str, args: Dict,
                 record: bool):
        self._tracer = tracer
        self.name = name
        self.track = track
        self.args = args
        self.start = self.end = 0.0
        self.parent = ""
        self._record = record
        self._annotation = None

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def __enter__(self) -> "_SpanContext":
        if self._record:
            stack = _stack()
            if stack:
                outer = stack[-1]
                self.parent = outer.name
                if "morsel" in outer.args:
                    self.args.setdefault("morsel", outer.args["morsel"])
            stack.append(self)
            hook = _profiler_hook
            if hook is not None:
                self._annotation = hook(self.name)
                self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        if not self._record:
            return
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            stack.remove(self)
        self._tracer._append(Span(self.name, self.track, self.start, self.end,
                                  self.args, self.parent))


class Tracer:
    """Bounded span recorder (thread-safe append, snapshot reads)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY, enabled: bool = True):
        self.enabled = enabled
        self.capacity = capacity
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=capacity)  # guarded-by: _lock

    def span(self, name: str, track: str = "host", **args) -> _SpanContext:
        """Context manager timing one span; ``args`` become trace-event
        args (keep them small and low-cardinality)."""
        return _SpanContext(self, name, track, args, self.enabled)

    def add_span(
        self, name: str, start: float, end: float, track: str = "host", **args
    ) -> None:
        """Record an explicitly-timed span (perf_counter endpoints);
        ring buffer only, never mirrored to the profiler."""
        if not self.enabled:
            return
        # clamp negative durations (clock skew between explicit endpoints)
        self._append(Span(name=name, track=track, start=start,
                          end=max(start, end), args=args))

    def _append(self, span: Span) -> None:
        with self._lock:
            self._spans.append(span)

    def spans(self, name: Optional[str] = None, track: Optional[str] = None) -> List[Span]:
        """Snapshot of recorded spans, oldest first, optionally
        filtered by exact name and/or track."""
        with self._lock:
            out = list(self._spans)
        if name is not None:
            out = [s for s in out if s.name == name]
        if track is not None:
            out = [s for s in out if s.track == track]
        return out

    def clear(self) -> None:
        """Drop all recorded spans (capacity unchanged)."""
        with self._lock:
            self._spans.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)


# ------------------------------------------------------------ default tracer
_default_tracer = Tracer()
_default_lock = threading.Lock()


def tracer() -> Tracer:
    """The process-global default tracer (resolved at call time)."""
    return _default_tracer


def set_tracer(t: Tracer) -> Tracer:
    """Install ``t`` as the process default; returns the previous one."""
    global _default_tracer
    with _default_lock:
        prev = _default_tracer
        _default_tracer = t
    return prev

"""Phase spans for the mega simulator: one recorder per ``run_mega`` call.

``run_mega`` opens a :class:`Recorder` for the length of the call
(``recording``) and marks its phases on it; the compiled backend marks
each compiled call with ``span``.  A span records its name, its parent
and its start and end on ``time.perf_counter``.  When ``jax`` is already
imported, the same block also enters a ``jax.profiler.TraceAnnotation``
of the same name, so a profiled run shows every span on the device
trace's clock.  Spans mark phases only, about a dozen a simulated day:
nothing here runs per event.

The open recorder lives in a ``contextvars.ContextVar``, so concurrent
runs (the planner's thread pool) and successive runs (a sweep) never
share one, and a span opened with no recorder open does nothing.

Compiles: one ``jax.monitoring`` duration listener per process, acting
only while a recorder is open in the calling context, adds each
lowering (``/jax/core/compile/jaxpr_to_mlir_module_duration``) and each
backend compile (``/jax/core/compile/backend_compile_duration``, which
on a persistent-cache hit is the load) to ``compile_s`` and to the
innermost open span, and counts each lowering under
``compiles.<innermost span>``.  JAX 0.9 marks a persistent-cache hit
with the plain event ``/jax/compilation_cache/cache_hits`` and, right
after it, the duration ``/jax/compilation_cache/cache_retrieval_time_sec``;
the listener counts the latter as ``cache_loads``.  Tracing
(``jaxpr_trace_duration``) is left out: JAX records it once for every
nested function it traces, so its durations overlap.
"""
from __future__ import annotations

import contextvars
import sys
import threading
import time
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

_current: contextvars.ContextVar = contextvars.ContextVar(
    "repro_mega_recorder", default=None)
_listening = False
_listen_lock = threading.Lock()


class Span:
    """One closed or open span; ``parent`` indexes ``Recorder.spans``
    (-1 for a root)."""
    __slots__ = ("name", "parent", "start", "end", "compile_s")

    def __init__(self, name: str, parent: int, start: float):
        self.name = name
        self.parent = parent
        self.start = start
        self.end = start
        self.compile_s = 0.0       # lowering + backend compile inside it,
        #                            not counting its children's

    @property
    def wall(self) -> float:
        return self.end - self.start


class Recorder:
    """The spans and compile counts of one ``run_mega`` call."""

    def __init__(self):
        self.spans: List[Span] = []
        self.counters: Dict[str, int] = {}
        self.compile_s = 0.0
        self._open: List[tuple] = []       # (span index, annotation)
        self._phase: Optional[int] = None

    def open(self, name: str) -> None:
        parent = self._open[-1][0] if self._open else -1
        jax = sys.modules.get("jax")
        ann = None
        if jax is not None:
            _listen(jax)
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        self.spans.append(Span(name, parent, time.perf_counter()))
        self._open.append((len(self.spans) - 1, ann))

    def close(self) -> None:
        """Close the innermost open span."""
        i, ann = self._open.pop()
        self.spans[i].end = time.perf_counter()
        if ann is not None:
            ann.__exit__(None, None, None)

    def close_all(self) -> None:
        while self._open:
            self.close()
        self._phase = None

    def phase(self, name: Optional[str]) -> None:
        """End the current phase (with any span still open inside it)
        and, unless ``name`` is None, start the next one under the
        innermost span then open."""
        if self._phase is not None:
            while self._open and self._open[-1][0] >= self._phase:
                self.close()
            self._phase = None
        if name is not None:
            self.open(name)
            self._phase = self._open[-1][0]

    def wall(self, name: str) -> float:
        """Summed wall of every span called ``name``."""
        return sum((s.wall for s in self.spans if s.name == name), 0.0)

    def _compiled(self, event: str, duration: float) -> None:
        if event == CACHE_LOAD:
            self._count("cache_loads")
            return
        self.compile_s += duration
        if self._open:
            sp = self.spans[self._open[-1][0]]
            sp.compile_s += duration
            if event == LOWERING:
                self._count(f"compiles.{sp.name}")

    def _count(self, key: str) -> None:
        self.counters[key] = self.counters.get(key, 0) + 1


@contextmanager
def recording(root: str) -> Iterator[Recorder]:
    """Open a recorder for the calling context, with ``root`` its first
    span; every span still open is closed on the way out, error or
    not."""
    rec = Recorder()
    token = _current.set(rec)
    try:
        rec.open(root)
        yield rec
    finally:
        rec.close_all()
        _current.reset(token)


@contextmanager
def span(name: str) -> Iterator[None]:
    """Mark ``name`` on the recorder open in the calling context, if
    any."""
    rec = _current.get()
    if rec is None:
        yield
        return
    rec.open(name)
    depth = len(rec._open)
    try:
        yield
    finally:
        while len(rec._open) >= depth:
            rec.close()


def _on_duration(event: str, duration: float, **kw) -> None:
    if event not in (LOWERING, BACKEND_COMPILE, CACHE_LOAD):
        return
    rec = _current.get()
    if rec is not None:
        rec._compiled(event, duration)


def _listen(jax) -> None:
    global _listening
    if _listening:
        return
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True

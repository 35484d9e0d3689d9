"""The one span API — named intervals of the program on the profiler's clock.

The reference DeepSpeed times things with ad-hoc ``SynchronizedWallClockTimer``
instances and NVTX ranges; here one process-local tracer owns every timed
region. ``tracer.span(name, **counts)`` (reached as ``obs.span(...)``) is the
one call; spans nest (context manager / decorator / explicit begin-end for
non-lexical regions like ``start_profile``..``stop_profile``).

A span is *recording* when the tracer is enabled by config or while a
``jax.profiler`` capture is open (``TraceAnnotation.is_enabled()``), so
"tracing on" needs no switch of its own. A recording span

* opens a ``jax.profiler.TraceAnnotation(name, **counts)`` while a capture is
  open: it lies in the ``.xplane.pb`` beside the device lines, on their clock,
  with its counts as the event's ``stats`` — the NVTX-range analog;
* is kept in memory when it closes (``snapshot()``; bounded by ``max_spans``)
  with name, start and end in ``time.perf_counter`` seconds, thread name, its
  counts, an id and its parent's id — what the benchmark's reducers read;
* is appended to the JSONL (``jsonl_path``, enabled tracers only) as it
  closes, so a killed run keeps its tail. The ``report`` CLI
  (``python -m deepspeed_tpu.observability report``) summarizes it.

When not recording the call costs one ``is_enabled()`` check and returns the
shared ``NOOP_SPAN``: no object is built and no clock is read. A tracer that
records only because a capture is open empties its record when the next
capture opens, so two captures in one process never read each other's spans.

TPU honesty rule: a jitted call returns before the device finishes (async
dispatch), so a naive wall-clock around it times the *enqueue*, not the work.
Spans therefore carry ``sync=``: a syncing span of an ENABLED tracer drains
the dispatch queue at entry and exit (the ``cudaEventSynchronize`` analog),
making its duration a true device-inclusive measurement. Non-syncing spans are
free and honest about what they are — their records carry ``"synced": false``.
A span that records only because a capture is open never syncs: the capture
is there to see the program as it runs.

Rank-awareness: by default only process 0 is enabled (the reference's rank-0
logging convention); ``all_ranks=True`` enables everywhere, with the process
index in every record's ``pid``.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

_capture_open = TraceAnnotation.is_enabled


def write_chrome_trace(events: List[Dict[str, Any]], path: str) -> str:
    """Write pre-built Chrome trace events as a loadable trace file (the
    request tracer's timelines, ``reqtrace.py``; spans reach a timeline
    through the profiler's own capture)."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as fh:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, fh)
    return path


def _drain_dispatch_queue() -> None:
    """Block until previously dispatched device work completes. Enqueues a
    trivial computation and drains it — XLA executes per-device programs in
    dispatch order, so this returns only after everything before it."""
    try:
        import jax
        import jax.numpy as jnp

        (jnp.zeros(()) + 0).block_until_ready()
    except Exception:
        pass


class Span:
    """One open (then closed) recording span. Returned by ``SpanTracer.span``
    while recording; ``duration_s`` is valid after the context exits (or
    after ``end()``)."""

    __slots__ = ("name", "category", "attrs", "sync", "depth", "id",
                 "parent_id", "start_ns", "end_ns", "_tracer", "_annotation")
    recording = True

    def __init__(self, name: str, category: str, sync: bool,
                 attrs: Dict[str, Any], tracer: "SpanTracer", capture: bool):
        self.name = name
        self.category = category
        self.attrs = attrs
        self.sync = sync
        self.depth = 0
        self.id = next(tracer._ids)
        self.parent_id: Optional[int] = None
        self.start_ns = 0
        self.end_ns = 0
        self._tracer = tracer
        # made here, entered in begin(): a capture that closes in between
        # leaves a harmless annotation that records nothing
        self._annotation = TraceAnnotation(name, **attrs) if capture else None

    @property
    def duration_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def annotate(self, **attrs: Any) -> "Span":
        """Counts known only once the span is open (rows found ready, tokens
        emitted): into the record and, before ``end()``, the trace event."""
        self.attrs.update(attrs)
        if self._annotation is not None:
            self._annotation.set_metadata(**attrs)
        return self

    # -- lifecycle --------------------------------------------------------
    def begin(self) -> "Span":
        if self.sync:
            _drain_dispatch_queue()
        t = self._tracer
        stack = t._stack()
        # tpusync: disable=unguarded-shared-write — here and below: a Span is
        # built, begun and ended by ONE thread (the open-span stack is
        # thread-local); only its closed record is shared, under the
        # tracer's lock
        self.depth = len(stack)
        if stack:
            # tpusync: disable=unguarded-shared-write
            self.parent_id = stack[-1].id
        stack.append(self)
        if self._annotation is not None:
            self._annotation.__enter__()
        # tpusync: disable=unguarded-shared-write
        self.start_ns = time.perf_counter_ns()
        if t.on_event is not None:
            t.on_event("begin", self)
        return self

    def end(self) -> "Span":
        if self.sync:
            _drain_dispatch_queue()
        # tpusync: disable=unguarded-shared-write
        self.end_ns = time.perf_counter_ns()
        self._close_annotation()
        t = self._tracer
        stack = t._stack()
        # pop through any unclosed children (non-lexical misuse, or an
        # exception between a child's begin() and end()) so the stack cannot
        # leak depth nor the trace an open annotation
        while stack and stack[-1] is not self:
            stack.pop()._close_annotation()
        if stack:
            stack.pop()
        t._record(self)
        if t.on_event is not None:
            t.on_event("end", self)
        return self

    def _close_annotation(self) -> None:
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            # tpusync: disable=unguarded-shared-write
            self._annotation = None

    def __enter__(self) -> "Span":
        return self.begin()

    def __exit__(self, *exc) -> None:
        self.end()

    def to_record(self) -> Dict[str, Any]:
        rec: Dict[str, Any] = {
            "type": "span",
            "name": self.name,
            "cat": self.category,
            "id": self.id,
            "start_s": self.start_ns / 1e9,
            "end_s": self.end_ns / 1e9,
            "dur_us": (self.end_ns - self.start_ns) / 1e3,
            "depth": self.depth,
            "synced": self.sync,
        }
        if self.parent_id is not None:
            rec["parent_id"] = self.parent_id
        if self.attrs:
            rec["attrs"] = self.attrs
        return rec


class _NoopSpan:
    """What ``span()`` hands back when nothing records: one shared object,
    every method a no-op, no clock read. ``duration_s`` is 0.0 — a caller
    that needs a time when tracing is off reads the clock itself."""

    __slots__ = ()
    recording = False
    sync = False
    duration_s = 0.0

    def annotate(self, **attrs: Any) -> "_NoopSpan":
        return self

    def begin(self) -> "_NoopSpan":
        return self

    def end(self) -> "_NoopSpan":
        return self

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


NOOP_SPAN = _NoopSpan()


class SpanTracer:
    """Process-local span recorder. Thread-safe: each thread has its own open-
    span stack; the closed-span list and the JSONL handle are lock-guarded."""

    def __init__(self, enabled: bool = True, jsonl_path: Optional[str] = None,
                 all_ranks: bool = False, max_spans: int = 100_000,
                 process_index: Optional[int] = None):
        if process_index is None:
            try:
                import jax

                process_index = jax.process_index()
            except Exception:
                process_index = 0
        self.process_index = process_index
        self.enabled = enabled and (all_ranks or process_index == 0)
        self.jsonl_path = jsonl_path if self.enabled else None
        self.max_spans = max_spans
        self.dropped = 0
        # optional ("begin"|"end", span) callback — the observability session
        # wires the flight recorder / hang watchdog / goodput accountant
        # through this single hook; None (the default) costs one attribute
        # check per span boundary
        self.on_event: Optional[Any] = None
        self._spans: List[Dict[str, Any]] = []
        self._ids = itertools.count(1)
        self._capture_seen = False      # a profiler capture was open at the
        #   last span() call (guarded by _lock on change)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._fh = None
        if self.jsonl_path:
            os.makedirs(os.path.dirname(os.path.abspath(self.jsonl_path)),
                        exist_ok=True)
            self._fh = open(self.jsonl_path, "a", buffering=1)

    # -- internals --------------------------------------------------------
    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
            self._local.tid = threading.get_ident() & 0xFFFF
            self._local.thread = threading.current_thread().name
        return stack

    def _record(self, span: Span) -> None:
        rec = span.to_record()
        rec["pid"] = self.process_index
        rec["tid"] = self._local.tid
        rec["thread"] = self._local.thread
        with self._lock:
            if len(self._spans) < self.max_spans:
                self._spans.append(rec)
            else:
                self.dropped += 1
            if self._fh is not None:
                self._fh.write(json.dumps(rec) + "\n")

    def _capture_edge(self, capture: bool) -> None:
        """A capture opened or closed since the last span() call. A tracer
        that records only under a capture starts each one with an empty
        record (an enabled tracer keeps its whole run). The edge is seen at a
        span() call: two captures with no span between them share a record."""
        with self._lock:
            if capture is self._capture_seen:
                return
            self._capture_seen = capture
            if capture and not self.enabled:
                self._spans.clear()
                self.dropped = 0

    # -- public API -------------------------------------------------------
    def span(self, name: str, category: str = "span", sync: bool = False,
             **attrs: Any):
        """Open a span as a context manager (``with tracer.span("fwd"): ...``)
        or drive it manually via ``begin()``/``end()``. ``attrs`` are the
        span's counts (numbers or short strings). Returns ``NOOP_SPAN`` when
        neither the tracer is enabled nor a profiler capture is open."""
        capture = _capture_open()
        if capture is not self._capture_seen:
            self._capture_edge(capture)
        if self.enabled or capture:
            return Span(name, category, sync and self.enabled, attrs, self,
                        capture)
        return NOOP_SPAN

    def trace(self, name: Optional[str] = None, category: str = "span",
              sync: bool = False):
        """Decorator form: ``@tracer.trace("checkpoint/save")``."""

        def deco(fn):
            import functools

            label = name or fn.__qualname__

            @functools.wraps(fn)
            def wrapper(*args, **kwargs):
                with self.span(label, category=category, sync=sync):
                    return fn(*args, **kwargs)

            return wrapper

        return deco

    def current_name(self) -> Optional[str]:
        """Name of the innermost open span on this thread that is not a
        ``category="phase"`` subdivision of its parent (recompile watchdog
        attribution hook: a compile under ``serving/decode/dispatch`` is
        ``serving/decode``'s, the name the program is registered under)."""
        for span in reversed(getattr(self._local, "stack", ())):
            if span.category != "phase":
                return span.name
        return None

    def snapshot(self) -> List[Dict[str, Any]]:
        """The closed spans kept in memory, in closing order."""
        with self._lock:
            return list(self._spans)

    def clear(self) -> None:
        """Empty the in-memory record (``reset_session``: tests, end of run)."""
        with self._lock:
            self._spans.clear()
            self.dropped = 0
            self._capture_seen = False

    def flush(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.flush()

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


_NOOP_TRACER: Optional[SpanTracer] = None


def noop_tracer() -> SpanTracer:
    """Shared disabled tracer — what ``get_tracer()`` hands out before any
    session is configured, so call sites never need a None check."""
    global _NOOP_TRACER
    if _NOOP_TRACER is None:
        _NOOP_TRACER = SpanTracer(enabled=False, process_index=0)
    return _NOOP_TRACER

"""The one span API — named intervals of the program on the profiler's clock.

The reference DeepSpeed times things with ad-hoc ``SynchronizedWallClockTimer``
instances and NVTX ranges; here one process-local tracer owns every timed
region. ``tracer.span(name, **counts)`` (reached as ``obs.span(...)``) is the
one call; spans nest (context manager, or explicit begin-end for non-lexical
regions like ``start_profile``..``stop_profile``).

A span is *recording* when the tracer is enabled by config or while a
``jax.profiler`` capture is open (``TraceAnnotation.is_enabled()``), so
"tracing on" needs no switch of its own. A recording span

* opens a ``jax.profiler.TraceAnnotation(name)`` while a capture is open: it
  lies in the ``.xplane.pb`` beside the device lines, on their clock, with
  its counts as the event's ``stats`` (set in one call as the span closes) —
  the NVTX-range analog;
* is kept in memory when it closes (``snapshot()``; bounded by ``max_spans``)
  with name, start and end in ``time.perf_counter`` seconds, thread name, its
  counts, an id and its parent's id — what the benchmark's reducers read;
* where its call site asks (``span(name, cpu=True)``), says how long its
  thread RAN inside it: ``cpu_us``, one of its counts, from
  ``time.thread_time_ns`` read beside the wall clock at both ends. A span's
  off-CPU self time is (its duration less its children's) less (its
  ``cpu_us`` less its children's), children of the same thread only: the
  wait for the interpreter, for a lock, for the device. It is asked for and
  not read by every span because the thread's clock is a system call: 0.3 us
  on a plain Linux host, 6 us on the chip's (``PERF.md`` section 3), where
  it also advances in steps, so that only sums over many spans say much;
* is appended to the JSONL (``jsonl_path``, enabled tracers only) as it
  closes, so a killed run keeps its tail. The ``report`` CLI
  (``python -m deepspeed_tpu.observability report``) summarizes it.

While a tracer records, the collector's pauses are spans too
(``runtime/gc``, ``_gc_callback``): a ``gc.callbacks`` hook that is installed
when recording starts and taken out when it ends.

When not recording the call costs one ``is_enabled()`` check and returns the
shared ``NOOP_SPAN``: no object is built, no clock is read and nothing is
hooked. A tracer that records only because a capture is open empties its
record when the next capture opens, so two captures in one process never read
each other's spans.

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

import gc
import itertools
import json
import os
import threading
import time
import weakref
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

_capture_open = TraceAnnotation.is_enabled

# a collection that lasted this long, or any of generation 2, is recorded as
# a ``runtime/gc`` span; shorter ones are counted (``SpanTracer.gc_counts``)
GC_RECORD_US = 100.0


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


class _Stack(list):
    """One thread's open spans, innermost last, with the thread's identity
    as a record carries it."""

    __slots__ = ("tid", "thread")


class Span:
    """One open (then closed) recording span. Returned by ``SpanTracer.span``
    while recording; ``duration_s`` is valid after the context exits (or
    after ``end()``), and so is ``cpu_us`` among its counts where the span
    was opened with ``cpu``. A span's two boundaries are on the thread that is
    being timed: they build no record and take no lock, and the trace event
    gets its counts in one call."""

    __slots__ = ("name", "category", "attrs", "sync", "depth", "id",
                 "parent_id", "start_ns", "end_ns", "_cpu_ns", "_stack",
                 "_tracer", "_annotation")
    recording = True

    def __init__(self, name: str, category: str, sync: bool,
                 attrs: Dict[str, Any], tracer: "SpanTracer", capture: bool,
                 cpu: bool = False):
        self.name = name
        self.category = category
        self.attrs = attrs
        self.sync = sync
        self.id = next(tracer._ids)
        self.start_ns = self.end_ns = 0
        # None: the thread's clock is not read for this span
        self._cpu_ns: Optional[int] = 0 if cpu else None
        self._tracer = tracer
        # made here, entered in begin(): a capture that closes in between
        # leaves a harmless annotation that records nothing. Its counts are
        # set as it closes, all at once
        self._annotation = TraceAnnotation(name) if capture else None

    @property
    def duration_s(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def annotate(self, **attrs: Any) -> "Span":
        """Counts known only once the span is open (rows found ready, tokens
        emitted), or taken before it opens so that taking them is not timed:
        into the record and, when the span ends, the trace event."""
        self.attrs.update(attrs)
        return self

    # -- lifecycle --------------------------------------------------------
    def begin(self) -> "Span":
        if self.sync:
            _drain_dispatch_queue()
        t = self._tracer
        # tpusync: disable=unguarded-shared-write — here and below: a Span is
        # built, begun and ended by ONE thread (the open-span stack is
        # thread-local); only the closed span is shared, in the tracer's
        # record
        try:
            # tpusync: disable=unguarded-shared-write
            stack = self._stack = t._local.stack
        except AttributeError:      # the thread's first span
            # tpusync: disable=unguarded-shared-write
            stack = self._stack = t._new_stack()
        if stack:
            # tpusync: disable=unguarded-shared-write
            self.depth = len(stack)
            # tpusync: disable=unguarded-shared-write
            self.parent_id = stack[-1].id
        else:
            # tpusync: disable=unguarded-shared-write
            self.depth = 0
            # tpusync: disable=unguarded-shared-write
            self.parent_id = None
        stack.append(self)
        if self._annotation is not None:
            self._annotation.__enter__()
        # tpusync: disable=unguarded-shared-write
        self.start_ns = time.perf_counter_ns()
        if self._cpu_ns is not None:
            # the thread's clock inside the wall clock's interval, at both
            # ends: ``cpu_us`` is never above the duration
            # tpusync: disable=unguarded-shared-write
            self._cpu_ns = time.thread_time_ns()
        if t.on_event is not None:
            t.on_event("begin", self)
        return self

    __enter__ = begin

    def __exit__(self, *exc) -> None:
        if self.sync:
            _drain_dispatch_queue()
        if self._cpu_ns is not None:
            # whole microseconds: the trace event takes a whole number faster
            self.attrs["cpu_us"] = (time.thread_time_ns()
                                    - self._cpu_ns) // 1000
        # tpusync: disable=unguarded-shared-write
        self.end_ns = time.perf_counter_ns()
        if self._annotation is not None:
            self._close_annotation()
        stack = self._stack
        if stack[-1] is self:
            stack.pop()
        else:
            # pop through any unclosed children (non-lexical misuse, or an
            # exception between a child's begin() and end()) so the stack
            # cannot leak depth nor the trace an open annotation
            while stack:
                child = stack.pop()
                if child is self:
                    break
                if child._annotation is not None:
                    child._close_annotation()
        t = self._tracer
        if t._fh is None and len(t._spans) < t.max_spans:
            t._spans.append(self)   # ``_record``'s first case, uncalled
        else:
            t._record(self)
        if t.on_event is not None:
            t.on_event("end", self)

    def end(self) -> "Span":
        self.__exit__()
        return self

    def _close_annotation(self) -> None:
        if self.attrs:
            self._annotation.set_metadata(**self.attrs)
        self._annotation.__exit__(None, None, None)
        # tpusync: disable=unguarded-shared-write
        self._annotation = None

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
        rec["pid"] = self._tracer.process_index
        rec["tid"] = self._stack.tid
        rec["thread"] = self._stack.thread
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


# -- the collector's pauses ---------------------------------------------------
# ONE ``gc.callbacks`` hook for the process, there only while some tracer
# records (``_gc_watch``). The collector runs on whichever thread's allocation
# tripped it, with the interpreter lock held, and never inside itself: one
# open collection at a time. The callback takes no lock (a collection can
# start under any lock this module holds).
_gc_tracers: "weakref.WeakSet[SpanTracer]" = weakref.WeakSet()
_gc_hook_lock = threading.Lock()
_gc_open: Optional[tuple] = None    # (start_ns, cpu_ns, annotation)


def _gc_callback(phase: str, info: Dict[str, int]) -> None:
    global _gc_open
    if phase == "start":
        capture = _capture_open()
        if not capture and not any(t.enabled for t in _gc_tracers):
            return      # the capture closed and no span() call has seen it
        annotation = None
        if capture:
            # how long it will last is not known yet, so every collection
            # is an event of the capture; the record keeps the long ones
            annotation = TraceAnnotation("runtime/gc",
                                         generation=info["generation"])
            annotation.__enter__()
        _gc_open = (time.perf_counter_ns(), time.thread_time_ns(),
                    annotation)
        return
    opened, _gc_open = _gc_open, None
    if opened is None:
        return          # hooked while this collection ran
    start_ns, cpu_ns, annotation = opened
    cpu_us = (time.thread_time_ns() - cpu_ns) // 1000
    end_ns = time.perf_counter_ns()
    counts = {"generation": info["generation"],
              "collected": info["collected"],
              "pause_us": (end_ns - start_ns) / 1e3, "cpu_us": cpu_us}
    if annotation is not None:
        annotation.set_metadata(**counts)
        annotation.__exit__(None, None, None)
    keep = counts["generation"] == 2 or counts["pause_us"] >= GC_RECORD_US
    for tracer in list(_gc_tracers):
        if tracer.enabled or annotation is not None:
            tracer._note_gc(start_ns, end_ns, dict(counts) if keep else None)


def _gc_watch(tracer: "SpanTracer", on: bool) -> None:
    """``tracer`` starts or stops recording: the hook is in ``gc.callbacks``
    exactly while some tracer records."""
    with _gc_hook_lock:
        if on:
            _gc_tracers.add(tracer)
            if _gc_callback not in gc.callbacks:
                gc.callbacks.append(_gc_callback)
        else:
            _gc_tracers.discard(tracer)
            if not _gc_tracers and _gc_callback in gc.callbacks:
                gc.callbacks.remove(_gc_callback)


class SpanTracer:
    """Process-local span recorder. Thread-safe: each thread has its own open-
    span stack; a closed span is appended to the record with no lock (a list
    append is atomic), and the lock guards the JSONL handle, the bound and
    the record's emptying."""

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
        self._spans: List[Span] = []
        self._ids = itertools.count(1)
        self._capture_seen = False      # a profiler capture was open at the
        #   last span() call (guarded by _lock on change)
        self._lock = threading.Lock()
        self._local = threading.local()
        # what the collector's hook leaves here, lock-free: the closed
        # ``runtime/gc`` spans until the next ``_record`` / ``snapshot``
        # takes them into the record, and the count and pause of ALL
        # collections since ``gc_counts`` was last asked
        self._gc_pending: List[Span] = []
        self._gc_n = 0
        self._gc_pause_ns = 0
        self._fh = None
        if self.jsonl_path:
            os.makedirs(os.path.dirname(os.path.abspath(self.jsonl_path)),
                        exist_ok=True)
            self._fh = open(self.jsonl_path, "a", buffering=1)
        if self.enabled:
            _gc_watch(self, True)

    # -- internals --------------------------------------------------------
    def _new_stack(self) -> _Stack:
        stack = self._local.stack = _Stack()
        stack.tid = threading.get_ident() & 0xFFFF
        stack.thread = threading.current_thread().name
        return stack

    def _record(self, span: Span) -> None:
        """A closed span into the record, on the thread that is being
        timed: no dictionary is built (``snapshot`` builds them) and no lock
        taken, unless a JSONL file is open or the bound is reached."""
        if self._gc_pending and span.name != "runtime/gc":
            # (a collector's span is recorded by the loop that takes them:
            # taking from inside it recursed once a pending span, and a
            # thousand pending ones, which a process that builds many
            # engines between two spans collects, ended the run)
            self._take_gc_spans()
        if self._fh is None and len(self._spans) < self.max_spans:
            # tpusync: disable=unguarded-shared-write — a list's append is
            # atomic under the interpreter lock, and two threads at the
            # bound overshoot it by one span at most
            self._spans.append(span)
            return
        with self._lock:
            if len(self._spans) < self.max_spans:
                self._spans.append(span)
            else:
                self.dropped += 1
            if self._fh is not None:
                self._fh.write(json.dumps(span.to_record()) + "\n")

    def _note_gc(self, start_ns: int, end_ns: int,
                 counts: Optional[Dict[str, Any]]) -> None:
        """From the collector's hook, on the thread the collection ran on:
        count it, and where it is one to record (``counts``) close it as a
        ``runtime/gc`` span, child of that thread's innermost open span. No
        lock, no file, no ``on_event``."""
        # tpusync: disable=unguarded-shared-write — here and below: the
        # collector runs with the interpreter lock held and never inside
        # itself, and a lock could be one the thread it interrupted holds
        self._gc_n += 1
        # tpusync: disable=unguarded-shared-write
        self._gc_pause_ns += end_ns - start_ns
        if counts is None:
            return
        span = Span("runtime/gc", "runtime", False, counts, self, False)
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._new_stack()
        span._stack = stack
        span.depth = len(stack)
        span.parent_id = stack[-1].id if stack else None
        span.start_ns, span.end_ns = start_ns, end_ns
        if len(self._gc_pending) < self.max_spans:
            # tpusync: disable=unguarded-shared-write
            self._gc_pending.append(span)
        else:
            # tpusync: disable=unguarded-shared-write
            self.dropped += 1

    def _take_gc_spans(self) -> None:
        while self._gc_pending:
            try:
                # tpusync: disable=unguarded-shared-write — a list's pop is
                # atomic: each pending span is taken once
                span = self._gc_pending.pop(0)
            except IndexError:      # another thread took the last
                return
            self._record(span)

    def _capture_edge(self, capture: bool) -> None:
        """A capture opened or closed since the last span() call. A tracer
        that records only under a capture starts each one with an empty
        record (an enabled tracer keeps its whole run) and watches the
        collector for as long as the capture is open. The edge is seen at a
        span() call: two captures with no span between them share a record."""
        with self._lock:
            if capture is self._capture_seen:
                return
            self._capture_seen = capture
            if self.enabled:
                return
            if capture:
                self._spans.clear()
                self.dropped = 0
                self._gc_n = self._gc_pause_ns = 0
        _gc_watch(self, capture)

    # -- public API -------------------------------------------------------
    def span(self, name: str, category: str = "span", sync: bool = False,
             cpu: bool = False, **attrs: Any):
        """Open a span as a context manager (``with tracer.span("fwd"): ...``)
        or drive it manually via ``begin()``/``end()``. ``attrs`` are the
        span's counts (numbers or short strings); with ``cpu`` the span also
        says how long its thread ran inside it (``cpu_us``: two reads of the
        thread's clock, a system call each). Returns ``NOOP_SPAN`` when
        neither the tracer is enabled nor a profiler capture is open."""
        capture = _capture_open()
        if capture is not self._capture_seen:
            self._capture_edge(capture)
        if self.enabled or capture:
            return Span(name, category, sync and self.enabled, attrs, self,
                        capture, cpu)
        return NOOP_SPAN

    def current_name(self) -> Optional[str]:
        """Name of the innermost open span on this thread that is not a
        ``category="phase"`` subdivision of its parent (recompile watchdog
        attribution hook: a compile under ``serving/decode/dispatch`` is
        ``serving/decode``'s, the name the program is registered under)."""
        for span in reversed(getattr(self._local, "stack", ())):
            if span.category != "phase":
                return span.name
        return None

    def gc_counts(self) -> Dict[str, int]:
        """The collections this tracer saw, of any length, and their pause,
        since this was last asked (``serving/iteration`` carries them); both
        0 while nothing is hooked."""
        n, ns = self._gc_n, self._gc_pause_ns
        # less what was read, not zeroed: a collection between the two
        # lines is the next call's
        # tpusync: disable=unguarded-shared-write
        self._gc_n -= n
        # tpusync: disable=unguarded-shared-write
        self._gc_pause_ns -= ns
        return {"gc_collections": n, "gc_pause_us": ns // 1000}

    def snapshot(self) -> List[Dict[str, Any]]:
        """The closed spans kept in memory, in closing order, as records
        (``Span.to_record``). Reading them after a capture has closed is
        also where a tracer that recorded under it sees that edge."""
        if self._capture_seen and not _capture_open():
            self._capture_edge(False)
        self._take_gc_spans()
        with self._lock:
            spans = list(self._spans)
        return [span.to_record() for span in spans]

    def clear(self) -> None:
        """Empty the in-memory record (``reset_session``: tests, end of run)."""
        with self._lock:
            self._spans.clear()
            del self._gc_pending[:]
            self.dropped = 0
            self._capture_seen = False
        if not self.enabled:
            _gc_watch(self, False)

    def flush(self) -> None:
        self._take_gc_spans()
        with self._lock:
            if self._fh is not None:
                self._fh.flush()

    def close(self) -> None:
        """The JSONL file closed and the collector no longer watched: the
        record stays readable."""
        _gc_watch(self, False)
        self._take_gc_spans()
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

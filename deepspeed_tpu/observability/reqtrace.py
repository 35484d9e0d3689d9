"""Request-scoped distributed tracing — a causal timeline for every token.

The serving stack (router, chunked prefill, KV handoffs, speculation, COW
forks, death-resubmission) has been observable only through aggregate
histograms: a p99 TTFT or a ``deadline_exceeded`` in the metrics JSONL
cannot answer *which* request, *which* replica, *which* phase. This module
is the per-request answer: a ``trace_id`` minted at ``submit`` follows the
request through

* the routing decision (policy + reason + replica),
* queue wait and admission (row + replica),
* every prefill chunk (tokens, chunk start, replica),
* KV handoff export → transfer → import — the trace context rides the
  ``KVHandoff`` seam, so the handoff's stages carry BOTH replicas,
* decode / verify iteration participation (sampled every
  ``trace_decode_sample`` iterations, never per-token — aggregates are
  exact, events are bounded),
* preemption / recompute, death-resubmission (same ``trace_id``, a new
  ``attempt`` index), fork lineage (``submit(n=)`` / ``fork(n)`` parent
  and child links),
* XLA compiles attributed to the open trace (the recompile-watchdog feed),
* and the terminal state.

**Head sampling + tail retention**: every trace accumulates events (bounded
per trace — a host append, never a device interaction); at the terminal
event a trace is *retained* — written to the append-only ``reqtrace.jsonl``
and kept in a bounded ring for Chrome-trace export — when it was
head-sampled (``trace_sample_rate``, decided deterministically at mint) OR
it is an outlier: ``deadline_exceeded``, ``shed``, preempted, resubmitted,
or TTFT past ``trace_ttft_slo_ms``. Outliers always survive, whatever the
sample rate — the tail is the point.

Export: one JSONL record per retained trace (the ``report`` CLI's
``== request traces ==`` input) plus Chrome trace-event rendering
(:func:`write_chrome_trace`: one row per trace, pid = replica of first
service; spans reach a timeline through the profiler's own capture).

Everything is gated off by default (``ObservabilityConfig.request_tracing``);
the disabled path wires nothing — no fields on requests, no events, zero
extra dispatches or compiles (watchdog-asserted in the tests).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ..utils.logging import logger

__all__ = ["ReqTrace", "RequestTracer", "write_chrome_trace"]

# terminal states a trace can finish in (mirrors the scheduler's states plus
# the router-level "shed")
TERMINAL_STATES = ("finished", "cancelled", "deadline_exceeded", "shed")


def write_chrome_trace(events: List[Dict[str, Any]], path: str) -> str:
    """Write pre-built Chrome trace events as a loadable trace file."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as fh:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, fh)
    return path


_ACTIVE = threading.local()   # .trace — the trace whose dispatch is open on
#   this thread (compile attribution; see RequestTracer.active)


class ReqTrace:
    """One request's causal timeline. Mutable and engine-agnostic: the same
    object rides the request across replicas (resubmission rebinding, KV
    handoff adoption) so the trace_id — and the event list — survive every
    engine the request touches."""

    __slots__ = ("trace_id", "seq", "sampled", "tenant", "attempt",
                 "created_s", "finish_s", "queued_at", "state", "events",
                 "phases", "replicas", "preemptions", "resubmits", "handoffs",
                 "decode_iters", "verify_iters", "tokens", "ttft_s",
                 "fork_of", "forks", "compile_s", "dropped_events", "attrs")

    def __init__(self, trace_id: str, seq: int, sampled: bool, tenant: str,
                 t: float, fork_of: Optional[str] = None,
                 attrs: Optional[Dict[str, Any]] = None):
        self.trace_id = trace_id
        self.seq = seq
        self.sampled = sampled
        self.tenant = tenant
        self.attempt = 1
        self.created_s = t
        self.finish_s: Optional[float] = None
        self.queued_at = t            # start of the current queue wait
        self.state: Optional[str] = None   # terminal state once finished
        self.events: List[Dict[str, Any]] = []
        self.phases: Dict[str, float] = {}
        self.replicas: List[str] = []      # replicas visited, in order
        self.preemptions = 0
        self.resubmits = 0
        self.handoffs = 0
        self.decode_iters = 0
        self.verify_iters = 0
        self.tokens = 0
        self.ttft_s: Optional[float] = None
        self.fork_of = fork_of
        self.forks: List[str] = []
        self.compile_s = 0.0
        self.dropped_events = 0
        self.attrs = dict(attrs) if attrs else {}

    @property
    def done(self) -> bool:
        return self.state is not None

    def note_replica(self, replica: Any) -> None:
        replica = str(replica)
        if not self.replicas or self.replicas[-1] != replica:
            self.replicas.append(replica)

    def to_record(self) -> Dict[str, Any]:
        rec: Dict[str, Any] = {
            "type": "reqtrace",
            "trace_id": self.trace_id,
            "state": self.state or "in_flight",
            "tenant": self.tenant,
            "sampled": self.sampled,
            "attempt": self.attempt,
            "start_s": round(self.created_s, 6),
            "phases": {k: round(v, 6) for k, v in self.phases.items()},
            "replicas": list(self.replicas),
            "preemptions": self.preemptions,
            "resubmits": self.resubmits,
            "handoffs": self.handoffs,
            "decode_iters": self.decode_iters,
            "verify_iters": self.verify_iters,
            "tokens": self.tokens,
            "events": list(self.events),
        }
        if self.finish_s is not None:
            rec["finish_s"] = round(self.finish_s, 6)
            rec["wall_s"] = round(self.finish_s - self.created_s, 6)
        if self.ttft_s is not None:
            rec["ttft_ms"] = round(self.ttft_s * 1e3, 3)
        if self.fork_of is not None:
            rec["fork_of"] = self.fork_of
        if self.forks:
            rec["forks"] = list(self.forks)
        if self.compile_s:
            rec["compile_s"] = round(self.compile_s, 6)
        if self.dropped_events:
            rec["dropped_events"] = self.dropped_events
        if self.attrs:
            rec["attrs"] = self.attrs
        return rec


class RequestTracer:
    """Process-local request-trace collector (one per enabled observability
    session with ``request_tracing`` on). Thread-safe; every recording call
    is a bounded host append."""

    def __init__(self, sample_rate: float = 1.0,
                 jsonl_path: Optional[str] = None, keep: int = 1024,
                 max_events: int = 256, decode_sample: int = 16,
                 ttft_slo_ms: float = 0.0,
                 clock: Callable[[], float] = time.monotonic):
        self.sample_rate = float(sample_rate)
        self.max_events = int(max_events)
        self.decode_sample = max(int(decode_sample), 1)
        self.ttft_slo_ms = float(ttft_slo_ms)
        self._clock = clock
        self._lock = threading.RLock()
        self._seq = 0
        # trace_id -> open trace (removed at finish): the crash-dump tail
        self._open: Dict[str, ReqTrace] = {}
        import collections

        # retained terminal records (Chrome export / bench top-k)
        self._retained: "collections.deque" = collections.deque(
            maxlen=max(int(keep), 1))
        self.started = 0
        self.retained = 0
        self.dropped = 0              # finished traces NOT retained
        self._fh = None
        self.jsonl_path = jsonl_path
        if jsonl_path:
            os.makedirs(os.path.dirname(os.path.abspath(jsonl_path)),
                        exist_ok=True)
            self._fh = open(jsonl_path, "a", buffering=1)

    # -- minting -----------------------------------------------------------
    def start(self, tenant: str = "default", t: Optional[float] = None,
              fork_of: Optional[str] = None,
              attrs: Optional[Dict[str, Any]] = None) -> ReqTrace:
        """Mint a trace. The head-sampling decision is made HERE,
        deterministically from the mint sequence number (no RNG — traces
        are reproducible under the injectable clocks), but retention is
        decided at ``finish``: an unsampled trace that turns out to be an
        outlier is retained anyway (tail retention)."""
        if t is None:
            t = self._clock()
        with self._lock:
            self._seq += 1
            seq = self._seq
            self.started += 1
        # Knuth multiplicative hash of the sequence number -> [0, 1)
        u = ((seq * 2654435761) & 0xFFFFFFFF) / 2 ** 32
        sampled = u < self.sample_rate
        trace = ReqTrace(f"req-{seq}", seq, sampled, tenant, t,
                         fork_of=fork_of, attrs=attrs)
        with self._lock:
            self._open[trace.trace_id] = trace
        self.event(trace, "submitted", t=t, tenant=tenant)
        return trace

    def link_fork(self, parent: ReqTrace, child: ReqTrace) -> None:
        parent.forks.append(child.trace_id)
        self.event(parent, "fork", child=child.trace_id)
        self.event(child, "forked_from", parent=parent.trace_id)

    # -- recording ---------------------------------------------------------
    def event(self, trace: ReqTrace, kind: str, t: Optional[float] = None,
              **attrs: Any) -> None:
        if t is None:
            t = self._clock()
        with self._lock:
            if len(trace.events) >= self.max_events:
                trace.dropped_events += 1
                return
            ev = {"t": round(t, 6), "kind": kind}
            if attrs:
                ev.update(attrs)
            trace.events.append(ev)

    def interval(self, trace: ReqTrace, phase: str, t0: float, t1: float,
                 kind: Optional[str] = None, **attrs: Any) -> None:
        """A timed phase interval: accumulates ``phases[phase]`` (exact)
        and records one event with ``dur_s`` (bounded). A ``replica``
        attr also joins the trace's visited-replicas path."""
        dur = max(t1 - t0, 0.0)
        with self._lock:
            trace.phases[phase] = trace.phases.get(phase, 0.0) + dur
            if attrs.get("replica") is not None:
                trace.note_replica(attrs["replica"])
        self.event(trace, kind or phase, t=t0, dur_s=round(dur, 6), **attrs)

    def admitted(self, trace: ReqTrace, t: float, replica: Any,
                 row: Optional[int] = None) -> None:
        """Admission onto a decode row closes the current queue wait."""
        with self._lock:
            wait = max(t - trace.queued_at, 0.0)
            trace.phases["queue_wait"] = \
                trace.phases.get("queue_wait", 0.0) + wait
            trace.note_replica(replica)
        self.event(trace, "admitted", t=t, queue_wait_s=round(wait, 6),
                   replica=str(replica), row=row)

    def note_decode(self, trace: ReqTrace, t0: float, t1: float,
                    kind: str = "decode", replica: Any = None,
                    batch: int = 0) -> None:
        """One decode/verify iteration this request participated in. The
        phase accumulation is exact (the iteration's device-inclusive wall,
        shared by every participating row — documented semantics); the
        EVENT is sampled every ``trace_decode_sample`` participations so a
        4096-token stream does not write 4096 events."""
        with self._lock:
            trace.phases[kind] = trace.phases.get(kind, 0.0) + (t1 - t0)
            if kind == "verify":
                trace.verify_iters += 1
                n = trace.verify_iters
            else:
                trace.decode_iters += 1
                n = trace.decode_iters
        if n == 1 or n % self.decode_sample == 0:
            self.event(trace, kind, t=t0, dur_s=round(t1 - t0, 6),
                       iter=n, batch=batch,
                       replica=str(replica) if replica is not None else None)

    def preempted(self, trace: ReqTrace, t: float, replica: Any) -> None:
        with self._lock:
            trace.preemptions += 1
            trace.queued_at = t     # the recompute wait is queue time
        self.event(trace, "preempted", t=t, replica=str(replica))

    def resubmitted(self, trace: ReqTrace, t: float, replica: Any,
                    reason: str = "replica_death") -> None:
        """Death-resubmission: the SAME trace_id continues on another
        replica at attempt + 1."""
        with self._lock:
            trace.resubmits += 1
            trace.attempt += 1
            trace.queued_at = t
        self.event(trace, "resubmitted", t=t, replica=str(replica),
                   attempt=trace.attempt, reason=reason)

    def handoff_adopted(self, trace: ReqTrace, t: float, src: Any,
                        dst: Any) -> None:
        """The KV handoff committed: the trace's next events come from the
        destination replica."""
        with self._lock:
            trace.handoffs += 1
            trace.queued_at = t     # waits for a decode row on dst
        self.event(trace, "handoff_adopted", t=t, src=str(src),
                   dst=str(dst))

    # -- compile attribution (recompile-watchdog feed) ---------------------
    def active(self, trace: Optional[ReqTrace]):
        """Context manager marking ``trace`` as the one whose dispatch is
        open on this thread — a compile firing inside attributes to it."""
        return _ActiveTrace(trace)

    def note_compile(self, secs: float, where: str) -> None:
        trace = getattr(_ACTIVE, "trace", None)
        if trace is None or trace.done:
            return
        with self._lock:
            trace.compile_s += secs
        self.event(trace, "compile", secs=round(secs, 4), where=where)

    # -- terminal ----------------------------------------------------------
    def outlier_reasons(self, trace: ReqTrace, state: str) -> List[str]:
        reasons = []
        if state in ("deadline_exceeded", "shed"):
            reasons.append(state)
        if trace.preemptions:
            reasons.append("preempted")
        if trace.resubmits:
            reasons.append("resubmitted")
        if (self.ttft_slo_ms > 0 and trace.ttft_s is not None
                and trace.ttft_s * 1e3 > self.ttft_slo_ms):
            reasons.append("ttft_slo")
        return reasons

    def finish(self, trace: ReqTrace, state: str, t: Optional[float] = None,
               ttft_s: Optional[float] = None, tokens: Optional[int] = None,
               replica: Any = None, **attrs: Any) -> bool:
        """Terminal event + the retention decision. Idempotent: the first
        terminal state wins (a router-level ``shed`` recorded before the
        engine-level cancel keeps ``shed``). Returns whether the trace was
        retained."""
        with self._lock:
            if trace.done:
                return False
            if t is None:
                t = self._clock()
            trace.state = state
            trace.finish_s = t
            if ttft_s is not None:
                trace.ttft_s = ttft_s
            if tokens is not None:
                trace.tokens = tokens
            if replica is not None:
                trace.note_replica(replica)
            self._open.pop(trace.trace_id, None)
            # the terminal event bypasses the per-trace cap: a trace whose
            # event budget filled up must still end with its state (the
            # causal chain's last link), and finish runs exactly once
            ev = {"t": round(t, 6), "kind": state}
            if attrs:
                ev.update(attrs)
            trace.events.append(ev)
        reasons = self.outlier_reasons(trace, state)
        retain = trace.sampled or bool(reasons)
        rec = trace.to_record()
        if reasons:
            rec["outlier"] = reasons
        with self._lock:
            if retain:
                self.retained += 1
                self._retained.append(rec)
                if self._fh is not None:
                    try:
                        self._fh.write(json.dumps(rec) + "\n")
                    except Exception:   # tracing must never take serving down
                        logger.warning("reqtrace JSONL write failed",
                                       exc_info=True)
            else:
                self.dropped += 1
        return retain

    # -- inspection / export ----------------------------------------------
    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._retained)

    def get(self, trace_id: str) -> Optional[Dict[str, Any]]:
        with self._lock:
            for rec in self._retained:
                if rec["trace_id"] == trace_id:
                    return rec
        return None

    def inflight_summary(self, limit: int = 64) -> List[Dict[str, Any]]:
        """What every stuck request was doing — the crash-bundle tail a
        serving hang gets stapled to its MANIFEST."""
        now = self._clock()
        out = []
        with self._lock:
            open_traces = list(self._open.values())[:limit]
        for tr in open_traces:
            last = tr.events[-1] if tr.events else None
            out.append({
                "trace_id": tr.trace_id,
                "tenant": tr.tenant,
                "attempt": tr.attempt,
                "age_s": round(now - tr.created_s, 3),
                "replicas": list(tr.replicas),
                "phases": {k: round(v, 4) for k, v in tr.phases.items()},
                "tokens": tr.tokens,
                "preemptions": tr.preemptions,
                "resubmits": tr.resubmits,
                "handoffs": tr.handoffs,
                "last_event": last,
            })
        return out

    def chrome_events(self, records: Optional[List[Dict[str, Any]]] = None
                      ) -> List[Dict[str, Any]]:
        """Retained traces as Chrome trace events: one row (tid) per trace,
        pid = the replica that first served it, phase intervals as complete
        events, instants (preempted/resubmitted/terminal) as instant
        events, plus a thread-name metadata row naming the trace_id."""
        if records is None:
            records = self.snapshot()
        events: List[Dict[str, Any]] = []
        for rec in records:
            tid = int(rec["trace_id"].rsplit("-", 1)[-1])
            reps = rec.get("replicas") or ["0"]
            try:
                pid = int(reps[0])
            except (TypeError, ValueError):
                pid = 0
            name = rec["trace_id"]
            if rec.get("outlier"):
                name += " [" + ",".join(rec["outlier"]) + "]"
            events.append({"name": "thread_name", "ph": "M", "pid": pid,
                           "tid": tid, "args": {"name": name}})
            for ev in rec.get("events", []):
                ts = ev.get("t", 0.0) * 1e6
                args = {k: v for k, v in ev.items()
                        if k not in ("t", "kind", "dur_s") and v is not None}
                args["trace_id"] = rec["trace_id"]
                if "dur_s" in ev:
                    events.append({"name": ev["kind"], "cat": "reqtrace",
                                   "ph": "X", "ts": ts,
                                   "dur": ev["dur_s"] * 1e6,
                                   "pid": pid, "tid": tid, "args": args})
                else:
                    events.append({"name": ev["kind"], "cat": "reqtrace",
                                   "ph": "i", "s": "t", "ts": ts,
                                   "pid": pid, "tid": tid, "args": args})
        return events

    def export_chrome_trace(self, path: str,
                            records: Optional[List[Dict[str, Any]]] = None
                            ) -> str:
        return write_chrome_trace(self.chrome_events(records), path)

    def export_chrome_top(self, path: str, k: int = 3,
                          key: str = "ttft_ms") -> List[str]:
        """Chrome-export the top-``k`` retained traces by ``key`` (default:
        worst TTFT — the bench's outlier dump). Returns their trace ids."""
        recs = sorted(self.snapshot(),
                      key=lambda r: -(r.get(key) or 0.0))[:max(k, 0)]
        if recs:
            self.export_chrome_trace(path, records=recs)
        return [r["trace_id"] for r in recs]

    def flush(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.flush()

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None


class _ActiveTrace:
    __slots__ = ("_trace", "_prev")

    def __init__(self, trace: Optional[ReqTrace]):
        self._trace = trace
        self._prev = None

    def __enter__(self):
        # tpusync: disable=unguarded-shared-write — one thread builds and
        # uses an _ActiveTrace, inside one with-statement
        self._prev = getattr(_ACTIVE, "trace", None)
        _ACTIVE.trace = self._trace
        return self._trace

    def __exit__(self, *exc) -> None:
        _ACTIVE.trace = self._prev

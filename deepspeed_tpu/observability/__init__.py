"""``deepspeed_tpu.observability`` — the one substrate the whole stack
publishes telemetry into.

The reference DeepSpeed ships telemetry as disconnected islands
(``utils/timer.py``, ``monitor/``, ``utils/comms_logging.py``, the flops
profiler); this package unifies them behind two process-local primitives plus
two TPU-specific watchers:

* :mod:`.spans`   — the one span API (context manager / decorator, rank-0
  aware, sync-honest): spans reach the ``jax.profiler`` capture beside the
  device lines, an in-memory record (``recorded_spans()``) and an
  append-only JSONL;
* :mod:`.metrics` — ``MetricsRegistry`` of labeled counters / gauges /
  histograms; the ``monitor/`` CSV/TB/WandB writers are *exporters* of this
  registry, not a parallel event path;
* :mod:`.recompile` — XLA recompilation watchdog on ``jax.monitoring``
  listeners: compile counts + seconds attributed to the active span, warning
  when a steady-state step recompiles;
* :mod:`.memory`  — device HBM gauges via ``device.memory_stats()`` (no-op
  guarded on stat-less backends) + host RSS;
* :mod:`.flightrecorder` — always-cheap bounded ring of recent events with a
  crash-bundle ``dump()`` (ring + per-thread stacks + open spans + device
  memory + tpuaudit fingerprints) on unhandled exception, SIGUSR1, or
  hang-watchdog fire;
* :mod:`.hangdetect` — heartbeat watchdog: span boundaries heartbeat, and a
  silent run past ``max(k × median step, floor)`` dumps a flight record
  naming the stalled span (optionally aborting with a distinct exit code);
* :mod:`.goodput` — wall-time buckets (compute/recompile/checkpoint/
  input-wait/stall) + ``goodput_fraction`` / ``mfu`` / ``tokens_per_sec``
  gauges;
* :mod:`.fleethealth` — cross-rank health aggregation at a step cadence
  (fleet min/median/max/skew of step time / loss / grad norm / HBM /
  recompiles), straggler detection (``fleet/straggler_rank``), and the
  replica-divergence/SDC sentinel (loss/grad-norm agreement + optional
  per-replica param checksums) dumping a bundle that names the culprit
  rank;
* :mod:`.numerics` — in-program numerics sentinel: a fused isfinite /
  loss-spike flag threaded through the jitted train step (no extra host
  sync on the happy path) with configurable ``warn | skip_step | abort``;
* :mod:`.faultinject` — deterministic chaos harness: rank kills, synthetic
  stragglers, NaN-poisoned params, and checkpoint truncation pinned to
  (step, rank, incarnation), so the whole failure → detect → remediate →
  resume loop is CI-testable on a CPU mesh (docs/resilience.md).

Everything is **off by default** (``ObservabilityConfig.enabled``); a
disabled session writes no files and records nothing — except spans while a
``jax.profiler`` capture is open, which is what the capture is for.
``python -m deepspeed_tpu.observability report <jsonl...>`` summarizes runs.
"""

from __future__ import annotations

import os
from typing import Any, Optional

from .faultinject import Fault, FaultInjector
from .fleethealth import FleetHealthMonitor, build_replica_checksum_probe
from .flightrecorder import (FlightRecorder, find_latest_bundle,
                             install_sigusr1, uninstall_sigusr1)
from .goodput import GoodputAccountant
from .goodput import STEP_SPANS as _STEP_SPANS
from .hangdetect import HangWatchdog
from .memory import record_memory
from .metrics import Counter, Gauge, Histogram, MetricsRegistry, get_registry
from .numerics import NumericsSentinel, NumericsState, NumericsTrip
from .profiler import (DeepProfiler, install_sigusr2, parse_trace_dir,
                       uninstall_sigusr2)
from .recompile import RecompileWatchdog, get_watchdog
from .recompile import install as install_watchdog
from .recompile import uninstall as uninstall_watchdog
from .reqtrace import ReqTrace, RequestTracer, write_chrome_trace
from .servegoodput import ServeGoodput
from .servegoodput import note_compile_current as _sg_note_compile
from .spans import NOOP_SPAN, Span, SpanTracer, noop_tracer
from .timeseries import TimeSeriesStore

__all__ = [
    "Observability", "configure_observability", "get_session", "reset_session",
    "recorded_spans", "SpanTracer", "Span", "NOOP_SPAN", "noop_tracer",
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "get_registry",
    "RecompileWatchdog", "install_watchdog", "uninstall_watchdog",
    "get_watchdog", "record_memory",
    "FlightRecorder", "find_latest_bundle", "install_sigusr1",
    "uninstall_sigusr1", "HangWatchdog", "GoodputAccountant",
    "FleetHealthMonitor", "build_replica_checksum_probe",
    "NumericsSentinel", "NumericsState", "NumericsTrip",
    "Fault", "FaultInjector",
    "ReqTrace", "RequestTracer", "ServeGoodput", "write_chrome_trace",
    "TimeSeriesStore",
    "DeepProfiler", "parse_trace_dir", "install_sigusr2",
    "uninstall_sigusr2",
]


class Observability:
    """One configured observability session: tracer + registry + watchdog +
    output paths. The engine owns one; the *current* session (module global)
    is what free-function call sites (``comm``, inference) publish through."""

    def __init__(self, config: Optional[Any] = None,
                 process_index: Optional[int] = None):
        if config is None:
            from ..config.config import ObservabilityConfig

            config = ObservabilityConfig()
        self.config = config
        self.enabled = bool(config.enabled)
        self.output_dir = (config.output_dir or "./dstpu_obs") \
            if self.enabled else ""
        self.registry = get_registry()
        jsonl = (os.path.join(self.output_dir, config.trace_file)
                 if self.enabled else None)
        self.tracer = SpanTracer(enabled=self.enabled, jsonl_path=jsonl,
                                 all_ranks=config.all_ranks,
                                 max_spans=config.max_spans,
                                 process_index=process_index)
        # the one span call, ``obs.span(name, **counts)``: records while
        # this session is enabled or a profiler capture is open, else hands
        # back the shared ``NOOP_SPAN``. The tracer's own method, bound
        # here: a delegate would repack the counts at every boundary
        self.span = self.tracer.span
        self.watchdog: Optional[RecompileWatchdog] = None
        if self.enabled and config.recompile_watchdog:
            self.watchdog = install_watchdog(
                registry=self.registry, tracer=self.tracer,
                steady_state_step=config.steady_state_step)
        # flight recorder / hang watchdog / goodput accountant ride the span
        # stream through ONE dispatcher on the tracer — a disabled session
        # (or all three gates off) leaves tracer.on_event None, so the
        # default path costs a single attribute check per span boundary
        self.recorder: Optional[FlightRecorder] = None
        self.hang: Optional[HangWatchdog] = None
        self.goodput: Optional[GoodputAccountant] = None
        if self.enabled and config.flight_recorder:
            self.recorder = FlightRecorder(
                capacity=config.flight_ring_size,
                dump_dir=(config.flight_dump_dir
                          or os.path.join(self.output_dir, "crash")))
            self.recorder.attach_logging()
        if self.enabled and config.hang_watchdog:
            self.hang = HangWatchdog(
                recorder=self.recorder, registry=self.registry,
                timeout_factor=config.hang_timeout_factor,
                timeout_floor_s=config.hang_timeout_floor_s,
                poll_interval_s=config.hang_poll_interval_s,
                abort=config.hang_abort, exit_code=config.hang_exit_code,
                on_fire=self._on_hang_fire)
            self.hang.start()
        if self.enabled and config.goodput:
            self.goodput = GoodputAccountant(self.registry)
        # fleet health + numerics sentinel: off unless their gates are on;
        # the disabled path wires nothing (no hooks, no state)
        self.fleet: Optional[FleetHealthMonitor] = None
        if self.enabled and getattr(config, "fleet_health", False):
            self.fleet = FleetHealthMonitor(
                registry=self.registry, recorder=self.recorder,
                cadence_steps=config.fleet_cadence_steps,
                straggler_factor=config.fleet_straggler_factor,
                divergence_tolerance=config.fleet_divergence_tolerance,
                window=config.fleet_window)
            self.fleet.heartbeat = self.heartbeat
        self.numerics: Optional[NumericsSentinel] = None
        if self.enabled and getattr(config, "numerics_sentinel", False):
            self.numerics = NumericsSentinel(
                action=config.numerics_action,
                check_steps=config.numerics_check_steps,
                spike_factor=config.numerics_spike_factor,
                spike_warmup=config.numerics_spike_warmup_steps,
                registry=self.registry, recorder=self.recorder)
        # request tracing (observability/reqtrace.py): off unless its gate
        # is on — the serving layer consults ``session.reqtrace`` at submit
        # time, so the disabled path wires nothing request-side
        self.reqtrace: Optional[RequestTracer] = None
        if self.enabled and getattr(config, "request_tracing", False):
            self.reqtrace = RequestTracer(
                sample_rate=config.trace_sample_rate,
                jsonl_path=os.path.join(self.output_dir,
                                        config.reqtrace_file),
                keep=config.trace_keep,
                max_events=config.trace_max_events,
                decode_sample=config.trace_decode_sample,
                ttft_slo_ms=config.trace_ttft_slo_ms)
            if self.recorder is not None:
                # a serving hang's crash bundle names what every stuck
                # request was doing (the in-flight trace tail)
                self.recorder.context_providers["request_traces"] = \
                    self.reqtrace.inflight_summary
        # metric time-series store (observability/timeseries.py): rolling
        # per-series history over the registry's publish stream — the
        # measurement half of the closed tune loop. Gated by
        # ``config.tune.enabled``; the disabled path allocates nothing.
        self.timeseries: Optional[TimeSeriesStore] = None
        tune_cfg = getattr(config, "tune", None)
        if isinstance(tune_cfg, dict):
            # direct-constructor convenience: a dict reaches here only when
            # nobody called config.validate() (which coerces); a silently
            # ignored tune gate would be a store that never materializes
            from ..config.config import TuneConfig

            tune_cfg = config.tune = TuneConfig.from_dict(tune_cfg)
            tune_cfg.validate()
        if self.enabled and tune_cfg is not None \
                and getattr(tune_cfg, "enabled", False):
            self.timeseries = TimeSeriesStore(
                capacity=tune_cfg.store_capacity,
                max_series=tune_cfg.store_max_series,
                ewma_alpha=tune_cfg.store_ewma_alpha)
            if self.recorder is not None:
                # a crash bundle carries every series' recent trajectory
                self.recorder.context_providers["timeseries"] = \
                    self.timeseries.summary
        # triggered deep profiling (observability/profiler.py): capture
        # windows + measured-vs-predicted attribution. Gated by
        # ``config.profiling.enabled``; the disabled path wires nothing —
        # no engine tick, no SIGUSR2, no hang pre-fire hook.
        self.profiler: Optional[DeepProfiler] = None
        prof_cfg = getattr(config, "profiling", None)
        if isinstance(prof_cfg, dict):
            from ..config.config import ProfilingConfig

            prof_cfg = config.profiling = ProfilingConfig.from_dict(prof_cfg)
            prof_cfg.validate()
        if self.enabled and prof_cfg is not None \
                and getattr(prof_cfg, "enabled", False):
            self.profiler = DeepProfiler(
                prof_cfg, registry=self.registry,
                timeseries=self.timeseries, recorder=self.recorder,
                output_dir=self.output_dir)
            if self.recorder is not None:
                # crash bundles carry the latest measured-vs-predicted
                # summary; a hang-prefire window still open at dump time is
                # closed first so its trace flushes into the bundle
                self.recorder.context_providers["profile_summary"] = \
                    self.profiler.bundle_context
            if self.hang is not None and prof_cfg.trigger_hang:
                self.hang.prefire_fraction = prof_cfg.hang_prefire_fraction
                self.hang.on_prefire = self._on_hang_prefire
        if self.recorder is not None or self.hang is not None \
                or self.goodput is not None or self.fleet is not None:
            self.tracer.on_event = self._span_event
        if self.hang is not None and self.fleet is not None:
            # a hang dump taken while blocked in the fleet gather should
            # name the rank that never arrived
            self.hang.context_fn = self.fleet.hang_context
        if self.watchdog is not None:
            self.watchdog.on_compile = self._on_compile
        self._mem_has_device_stats = None
        self._closed = False
        if self.enabled:
            # nothing in the engine API marks "the run is over", so the final
            # metrics export rides process exit; close() is idempotent,
            # so sessions torn down earlier (tests, bench) no-op here
            import atexit

            atexit.register(self.close)

    def _activate_process_hooks(self) -> None:
        """Grab the PROCESS-global channels — the singleton registry's
        publish hook and the SIGUSR1 recorder pointer. Only the CURRENT
        session may own these: a side session built with
        ``make_current=False`` must not steal the live session's crash
        evidence, so this runs from ``configure_observability``, not from
        construction."""
        if self.recorder is not None or self.timeseries is not None:
            self.registry.on_publish = self._on_publish
        if self.recorder is not None and self.config.flight_sigusr1:
            install_sigusr1(self.recorder)
        if self.profiler is not None and self.config.profiling.sigusr2:
            install_sigusr2(self.profiler)

    # -- event dispatch (span stream -> recorder/hang/goodput) ------------
    def _span_event(self, phase: str, span: Span) -> None:
        if self.recorder is not None:
            self.recorder.record_span(phase, span)
        if self.hang is not None:
            self.hang.heartbeat(span.name)
        if self.goodput is not None or self.hang is not None \
                or self.fleet is not None:
            if phase == "end":
                dur = span.duration_s
                t = span.end_ns / 1e9
                if span.name in _STEP_SPANS:
                    if self.hang is not None:
                        self.hang.note_step_time(dur)
                    if self.fleet is not None:
                        self.fleet.note_step_time(dur)
            else:
                dur = 0.0
                t = span.start_ns / 1e9
            if self.goodput is not None:
                self.goodput.on_span(phase, span.name, t, dur_s=dur)

    def _on_publish(self, step: int, events) -> None:
        if self.timeseries is not None:
            self.timeseries.ingest(step, events)
            # the store's own health is itself a series next publish
            self.timeseries.publish_self(self.registry)
        if self.recorder is not None:
            self.recorder.record("metric_publish", step=step,
                                 events=len(events))

    def _on_compile(self, secs: float, where: str, steady: bool) -> None:
        if self.recorder is not None:
            self.recorder.record("compile", seconds=round(secs, 4),
                                 where=where, steady=steady)
        if self.goodput is not None:
            self.goodput.on_compile(secs, where=where)
        if self.reqtrace is not None:
            # attribute the compile to the trace whose dispatch is open on
            # this thread (serving compiles name their victim request)
            self.reqtrace.note_compile(secs, where)
        # serving goodput: routed to whichever replica accountant is
        # mid-iteration on this thread (a threadlocal read when none is)
        _sg_note_compile(secs)
        if self.profiler is not None:
            # steady-state recompile => capture trigger (pending; opened at
            # the next engine tick)
            self.profiler.on_compile(secs, where, steady)

    def _on_hang_prefire(self, stalled_span: str, waited: float,
                         deadline: float) -> None:
        if self.profiler is not None:
            self.profiler.on_hang_prefire(stalled_span, waited, deadline)

    def _on_hang_fire(self, stalled_span: str, waited: float,
                      deadline: float, bundle: str) -> None:
        if self.goodput is not None:
            self.goodput.on_stall(waited, where=stalled_span)
            self.goodput.publish()

    # -- thin delegates (the API integration sites use) -------------------
    def heartbeat(self, name: str) -> None:
        """Non-span liveness signal (comm census, pipeline census) for the
        hang watchdog."""
        if self.hang is not None:
            self.hang.heartbeat(name)

    def flight_event(self, kind: str, **fields: Any) -> None:
        """Drop one event into the flight-recorder ring (no-op without a
        recorder). The serving layer records request-terminal incidents
        (shed, deadline_exceeded, resubmit, handoff_fail) through this so
        crash bundles from fleet incidents carry the victim requests' ids
        even with request tracing disabled."""
        if self.recorder is not None:
            self.recorder.record(kind, **fields)

    def crash_dump(self, reason: str, exc: Optional[BaseException] = None,
                   **extra: Any) -> Optional[str]:
        """Dump a flight-record bundle; never raises, returns the bundle dir
        (None when no recorder is active). The engines call this from their
        unhandled-exception paths."""
        if self.recorder is None:
            return None
        return self.recorder.dump(reason=reason, exc=exc,
                                  extra=extra or None) or None

    def note_step(self, global_step: int) -> None:
        # NO profiler tick here: the serving engine calls note_step while
        # holding its lock, and the profiler tick may dispatch
        # (start_trace). Engines tick the profiler explicitly, outside
        # their locks — ServingEngine.step and TpuEngine's step sites.
        if self.watchdog is not None:
            self.watchdog.note_step(global_step)
        if self.goodput is not None:
            self.goodput.publish()

    def maybe_record_memory(self, step: int) -> None:
        """Poll memory gauges at ``memory_poll_steps`` cadence; the first
        reported step always polls, so short (smoke) runs still carry memory
        telemetry."""
        if not self.enabled:
            return
        every = max(int(self.config.memory_poll_steps), 1)
        if self._mem_has_device_stats is None or step % every == 0:
            self._mem_has_device_stats = record_memory(self.registry)

    # -- output -----------------------------------------------------------
    def metrics_path(self) -> Optional[str]:
        if not self.enabled:
            return None
        return os.path.join(self.output_dir, self.config.metrics_file)

    def dump_metrics(self, path: Optional[str] = None, **extra: Any) -> Optional[str]:
        """Write the registry snapshot (+ recompile report) as JSONL. Honors
        the same rank gate as the tracer (``all_ranks=False`` => rank 0
        only), so N processes sharing an output dir don't interleave appends
        into one file."""
        path = path or self.metrics_path()
        if path is None or not self.tracer.enabled:
            return None
        if self.watchdog is not None:
            extra.setdefault("recompile_report", self.watchdog.report())
        return self.registry.dump_jsonl(path, extra=extra or None)

    def flush(self) -> None:
        self.tracer.flush()

    def close(self, export: bool = True) -> None:
        if self._closed:
            return
        self._closed = True
        if self.hang is not None:
            self.hang.disarm()
            self.hang.stop()
        if self.numerics is not None:
            # final-window flush: a trip after the last cadence check must
            # not exit silently (never raises; abort downgrades to log)
            self.numerics.flush()
        if self.profiler is not None:
            try:
                # before dump_metrics: a window still open flushes, and its
                # summary gauges make the final JSONL snapshot
                self.profiler.close()
            except Exception:
                from ..utils.logging import logger

                logger.warning("profiler close failed", exc_info=True)
        if self.enabled and export:
            try:
                if self.goodput is not None:
                    self.goodput.publish()   # final bucket snapshot
                self.dump_metrics()
                if self.reqtrace is not None and self.reqtrace.retained:
                    self.reqtrace.export_chrome_trace(os.path.join(
                        self.output_dir, self.config.reqtrace_chrome_file))
                if self.timeseries is not None:
                    self.timeseries.export_jsonl(os.path.join(
                        self.output_dir, self.config.tune.timeseries_file))
            except Exception:  # telemetry must never take the job down
                from ..utils.logging import logger

                logger.warning("observability export failed on close",
                               exc_info=True)
        self.tracer.on_event = None
        self.tracer.close()
        if self.reqtrace is not None:
            self.reqtrace.close()
        # the registry is a process singleton: only clear the publish hook
        # if it is still OURS — a replacement session installed its own
        # before closing us (configure_observability ordering). Outside the
        # recorder branch: a store-only session owns the hook too.
        if self.registry.on_publish == self._on_publish:
            self.registry.on_publish = None
        if self.recorder is not None:
            self.recorder.detach_logging()
            from .flightrecorder import _ACTIVE_RECORDER

            if _ACTIVE_RECORDER is self.recorder:
                uninstall_sigusr1()
        if self.profiler is not None:
            from .profiler import _ACTIVE_PROFILER

            if _ACTIVE_PROFILER is self.profiler:
                uninstall_sigusr2()
        if self.watchdog is not None and get_watchdog() is self.watchdog:
            uninstall_watchdog()


_SESSION: Optional[Observability] = None
_DISABLED: Optional[Observability] = None


def _disabled_session() -> Observability:
    global _DISABLED
    if _DISABLED is None:
        _DISABLED = Observability(config=None, process_index=0)
    return _DISABLED


def configure_observability(config: Optional[Any] = None,
                            process_index: Optional[int] = None,
                            make_current: bool = True) -> Observability:
    """Build a session from an ``ObservabilityConfig``. An enabled session
    becomes the *current* one (what ``get_session()`` returns — the hook the
    comm layer and inference engine publish through); a disabled config
    returns the shared no-op session and leaves any current session alone,
    so constructing a telemetry-free engine never tears down a live trace."""
    global _SESSION
    if config is None or not getattr(config, "enabled", False):
        return _disabled_session()
    session = Observability(config, process_index=process_index)
    if make_current:
        if _SESSION is not None and _SESSION is not session:
            if (session.timeseries is not None
                    and _SESSION.timeseries is not None):
                # engine rebuilds (training soft-restart remediation,
                # fleet revival) reconfigure the session — the rolling
                # windows must carry over, or the tuner/fleet-health
                # medians re-warm from zero after every recovery
                session.timeseries.adopt(_SESSION.timeseries)
            # close (without exporting) the session being replaced: left
            # open, its LIFO atexit hook would run LAST and overwrite the
            # live run's exports with stale data, and its JSONL handle
            # would leak until exit
            _SESSION.close(export=False)
        session._activate_process_hooks()
        _SESSION = session
    return session


def get_session() -> Observability:
    """The current session; a shared disabled one when nothing is configured
    (callers never need a None check — test ``.enabled``)."""
    return _SESSION if _SESSION is not None else _disabled_session()


def recorded_spans() -> list:
    """The spans this process holds in memory, closed ones in closing order
    (``spans.Span.to_record`` dicts): the current session's — with no session
    configured, what the shared disabled one recorded while a profiler
    capture was open. The benchmark's span reducers read this."""
    return get_session().tracer.snapshot()


def reset_session(close: bool = True) -> None:
    """Tear down the current session (tests / end of run), and empty what
    the shared disabled one recorded under profiler captures."""
    global _SESSION
    if _SESSION is not None and close:
        _SESSION.close(export=False)
    _SESSION = None
    if _DISABLED is not None:
        _DISABLED.tracer.clear()
    uninstall_watchdog()

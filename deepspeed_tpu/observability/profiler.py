"""Triggered deep profiling — on-device capture windows whose parsed device
time closes the measured-vs-predicted loop.

A tpucost number is a *prediction*; this module is the measurement half. It
opens
bounded ``jax.profiler.start_trace``/``stop_trace`` windows — on demand
(SIGUSR2, ``TrainEngine.start_profile``), on a step schedule
(``profile_every_steps``), or **triggered by telemetry the session already
collects**: TTFT/TPOT SLO burn over a ceiling and goodput-EWMA slope
collapse (time-series store), a steady-state recompile (recompile
watchdog), and the hang watchdog's pre-fire (a window opened at a fraction
of the deadline, so the trace shows the stall forming, not the corpse).

Discipline, because a flapping trigger must never fill a disk or stack
overlapping captures: one window open at a time, a per-trigger cooldown, a
global ``capture_budget`` per session, and keep-last-K pruning of capture
directories.

Attribution: the captured trace-events JSON (``plugins/profile/<ts>/
*.trace.json[.gz]``) is parsed with the stdlib into per-program device and
host seconds — XLA executor events carry ``args.hlo_module`` (the lowered
program name, ``jit_<fn>``) and ``args.hlo_op``; ``PjitFunction(<fn>)``
events on the caller thread give host dispatch time. Programs key back to
tpuaudit registry entries through the ``program`` tag recorded at
registration (``serving/decode`` → ``jit_decode``, ``train/step`` →
``jit_train_step``, ...). The ``.xplane.pb`` artifact is read by a
tolerant protobuf wire walker (names only, no schema) purely as a
fallback census — on CPU the device planes are thin and the JSON carries
everything; on TPU a future session gets program names even if the JSON
layout shifts.

Pairing: every closed window writes ``profile_summary.json`` joining
measured device seconds per entry against the tpucost roofline vector
(measured vs predicted step time, measured MFU vs ceiling, binding pipe),
publishes ``profile/*`` metrics, and staples the latest summary into
flight-recorder crash bundles via the ``context_providers`` seam.

All injectable for tests: the clock, the start/stop trace hooks, the
trigger sources. The disabled path (``ObservabilityConfig.profiling``)
constructs nothing.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import shutil
import signal
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..utils.logging import logger

__all__ = ["DeepProfiler", "Capture", "parse_trace_dir",
           "entry_program_map", "summarize_capture", "PROFILE_FORMAT",
           "install_sigusr2", "uninstall_sigusr2"]

PROFILE_FORMAT = 1

# triggers that bypass the global budget: both are explicit operator
# actions, not telemetry that can flap
_UNBUDGETED = ("manual", "sigusr2")


@dataclasses.dataclass
class Capture:
    """One capture window's ledger entry (the ``== profiling ==`` table)."""

    seq: int
    trigger: str
    dir: str
    opened_iteration: int
    opened_wall: float
    window_iterations: int
    closed_wall: float = 0.0
    status: str = "open"          # open | parsed | empty | failed
    programs_matched: int = 0
    entries_matched: int = 0

    @property
    def wall_s(self) -> float:
        if not self.closed_wall:
            return 0.0
        return self.closed_wall - self.opened_wall

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["wall_s"] = round(self.wall_s, 4)
        return d


# ---------------------------------------------------------------------------
# trace parsing (pure functions — the offline CLI path uses these too)


def _iter_trace_files(path: str) -> List[str]:
    """Every trace-events artifact under a capture dir. jax writes
    ``<dir>/plugins/profile/<timestamp>/<host>.trace.json.gz``; committed
    test fixtures may be plain ``.trace.json``."""
    out: List[str] = []
    for pat in ("**/*.trace.json.gz", "**/*.trace.json"):
        out.extend(glob.glob(os.path.join(path, pat), recursive=True))
    return sorted(set(out))


def _read_trace_events(path: str) -> List[Dict[str, Any]]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:  # type: ignore[operator]
        doc = json.load(fh)
    ev = doc.get("traceEvents", []) if isinstance(doc, dict) else []
    return [e for e in ev if isinstance(e, dict)]


def parse_trace_dir(path: str) -> Dict[str, Any]:
    """Parse every trace artifact under ``path`` into per-program seconds.

    Returns ``{"programs": {name: {"device_s", "host_s", "invocations",
    "ops": {op: seconds}}}, "trace_files": n, "events": n}`` where
    ``name`` is the lowered program name (``jit_<fn>``). Durations come
    from ``ph == "X"`` events (microseconds): events with ``args.hlo_op``
    are summed as device-side op time; module-level events (``hlo_module``
    without ``hlo_op``) are kept separately and used only for programs
    with no op slices, so nothing double counts. ``PjitFunction(<fn>)``
    events give host dispatch seconds and the invocation count. Compile-
    flood host events (``$``-prefixed Python names) are ignored."""
    programs: Dict[str, Dict[str, Any]] = {}

    def prog(name: str) -> Dict[str, Any]:
        return programs.setdefault(name, {
            "device_s": 0.0, "host_s": 0.0, "invocations": 0,
            "ops": {}, "_module_s": 0.0})

    files = _iter_trace_files(path)
    n_events = 0
    for f in files:
        try:
            events = _read_trace_events(f)
        except Exception:   # a torn half-written trace must not take
            logger.warning("unparseable trace artifact %s", f,
                           exc_info=True)
            continue        # the report down with it
        for e in events:
            if e.get("ph") != "X":
                continue
            n_events += 1
            name = str(e.get("name", ""))
            if name.startswith("$"):
                continue    # Python host-event flood (compile windows)
            dur_s = float(e.get("dur", 0.0)) / 1e6
            args = e.get("args") or {}
            hm = args.get("hlo_module")
            if hm:
                p = prog(str(hm))
                op = args.get("hlo_op")
                if op:
                    p["device_s"] += dur_s
                    p["ops"][str(op)] = p["ops"].get(str(op), 0.0) + dur_s
                else:
                    p["_module_s"] += dur_s
            elif name.startswith("PjitFunction(") and name.endswith(")"):
                fn = name[len("PjitFunction("):-1]
                p = prog("jit_" + fn)
                p["host_s"] += dur_s
                p["invocations"] += 1
    for p in programs.values():
        if p["device_s"] == 0.0 and p["_module_s"] > 0.0:
            # no per-op slices in this trace — module-level events are the
            # only device evidence (thin-plane backends)
            p["device_s"] = p["_module_s"]
        del p["_module_s"]
    # xplane fallback census: programs the planes mention that the JSON
    # missed still get a (zero-duration) row, so the summary names them
    for xp in glob.glob(os.path.join(path, "**/*.xplane.pb"),
                        recursive=True):
        for name in _xplane_program_names(xp):
            prog(name)
    return {"programs": programs, "trace_files": len(files),
            "events": n_events}


_NAME_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_./:-]{2,120}$")


def _xplane_program_names(path: str, max_bytes: int = 16 << 20) -> set:
    """Tolerant protobuf wire-format walk of an XSpace artifact: collect
    strings that look like lowered program names (``jit_*``). No schema,
    no proto dependency — any malformed byte just ends that branch. Used
    only as a fallback census (the trace-events JSON carries durations)."""
    names: set = set()
    try:
        with open(path, "rb") as fh:
            data = fh.read(max_bytes)
    except OSError:
        return names

    def varint(buf: bytes, i: int) -> Tuple[int, int]:
        val, shift = 0, 0
        while True:
            if i >= len(buf) or shift > 63:
                raise ValueError("truncated varint")
            b = buf[i]
            i += 1
            val |= (b & 0x7F) << shift
            if not b & 0x80:
                return val, i
            shift += 7

    def walk(buf: bytes, depth: int) -> None:
        i = 0
        while i < len(buf):
            try:
                key, i = varint(buf, i)
            except ValueError:
                return
            wire = key & 7
            if wire == 0:
                try:
                    _, i = varint(buf, i)
                except ValueError:
                    return
            elif wire == 1:
                i += 8
            elif wire == 5:
                i += 4
            elif wire == 2:
                try:
                    n, i = varint(buf, i)
                except ValueError:
                    return
                if n < 0 or i + n > len(buf):
                    return
                chunk = buf[i:i + n]
                i += n
                try:
                    text = chunk.decode("utf-8")
                    if _NAME_RE.match(text):
                        if text.startswith("jit_"):
                            names.add(text)
                        continue
                except UnicodeDecodeError:
                    pass
                if depth < 8 and n > 1:
                    walk(chunk, depth + 1)
            else:
                return   # groups/unknown: stop rather than misparse

    try:
        walk(data, 0)
    except Exception:       # tolerant by contract
        pass
    return names


def entry_program_map() -> Dict[str, List[str]]:
    """Lowered program name (``jit_<fn>``) → registry entry names, from the
    ``program`` tag recorded at registration. Draft-model entries sort
    after their target twins (the drafter's decode lowers to the same
    ``jit_decode`` module name), so attribution prefers the target and
    marks the row shared."""
    try:
        from tools.tpuaudit.registry import get_entry_points
    except ImportError:
        return {}
    out: Dict[str, List[str]] = {}
    drafts: Dict[str, List[str]] = {}
    for ep in get_entry_points():
        prog = (ep.tags or {}).get("program")
        if not prog:
            continue
        prog = str(prog)
        if not prog.startswith("jit_"):
            prog = "jit_" + prog
        bucket = drafts if (ep.tags or {}).get("draft_model") else out
        bucket.setdefault(prog, []).append(ep.name)
    for prog, entries in drafts.items():
        out.setdefault(prog, []).extend(entries)
    return out


def summarize_capture(parsed: Dict[str, Any], top_k: int = 5,
                      cost_join: Optional[Callable[[str, float],
                                                   Optional[dict]]] = None
                      ) -> Dict[str, Any]:
    """Join parsed per-program seconds to registry entries (+ the tpucost
    roofline when a join fn is given): the ``entries`` half of
    ``profile_summary.json``. Programs no entry claims land in
    ``unmatched_programs`` — silence would read as full coverage."""
    emap = entry_program_map()
    entries: Dict[str, Any] = {}
    unmatched: List[str] = []
    for prog, stats in sorted(parsed.get("programs", {}).items()):
        owners = emap.get(prog)
        if not owners:
            unmatched.append(prog)
            continue
        primary = owners[0]
        inv = int(stats.get("invocations", 0))
        device_s = float(stats.get("device_s", 0.0))
        per_inv = device_s / inv if inv else None
        hotspots = sorted(stats.get("ops", {}).items(),
                          key=lambda kv: -kv[1])[:top_k]
        row: Dict[str, Any] = {
            "program": prog,
            "device_s": round(device_s, 6),
            "host_s": round(float(stats.get("host_s", 0.0)), 6),
            "invocations": inv,
            "measured_step_ms": (round(per_inv * 1e3, 4)
                                 if per_inv is not None else None),
            "hlo_hotspots": [{"op": op, "seconds": round(s, 6)}
                             for op, s in hotspots],
        }
        if len(owners) > 1:
            row["shared_with"] = owners[1:]
        if cost_join is not None and per_inv:
            try:
                joined = cost_join(primary, per_inv)
            except Exception:   # a cost trace failure is a missing column,
                joined = None   # never a missing summary
            if joined:
                row.update(joined)
        entries[primary] = row
    return {"entries": entries, "unmatched_programs": unmatched,
            "trace_files": parsed.get("trace_files", 0),
            "events": parsed.get("events", 0)}


def _tpucost_join(entry: str, measured_step_s: float) -> Optional[dict]:
    try:
        from tools.tpucost.core import measured_join
    except ImportError:
        return None
    return measured_join(entry, measured_step_s)


# ---------------------------------------------------------------------------
# the profiler


class DeepProfiler:
    """One session's capture-window state machine + attribution pipeline.

    Engine hook points call :meth:`on_iteration` (serving) /
    :meth:`on_step` (training) outside their locks; the compile watchdog
    feeds :meth:`on_compile`; the hang watchdog feeds
    :meth:`on_hang_prefire` from its own thread. Everything mutating
    window state holds ``_lock`` — tpusync's guarded-by discipline."""

    def __init__(self, config: Any, registry: Optional[Any] = None,
                 timeseries: Optional[Any] = None,
                 recorder: Optional[Any] = None,
                 output_dir: str = "",
                 clock: Callable[[], float] = time.monotonic,
                 start_trace: Optional[Callable[[str], None]] = None,
                 stop_trace: Optional[Callable[[], None]] = None):
        self.config = config
        self.registry = registry
        self.timeseries = timeseries
        self.recorder = recorder
        self.trace_dir = config.trace_dir or os.path.join(
            output_dir or ".", "profile")
        self.clock = clock
        self._start_trace = start_trace or self._jax_start
        self._stop_trace = stop_trace or self._jax_stop
        self._lock = threading.Lock()
        self._open: Optional[Capture] = None
        self._seq = 0
        self._budget = int(config.capture_budget)
        self._cooldown_until: Dict[str, int] = {}
        self._pending: Optional[str] = None
        self._last_iteration = 0
        self._summarizing = False
        self.captures: List[Capture] = []
        self.latest_summary: Optional[Dict[str, Any]] = None
        self.summary_path = os.path.join(self.trace_dir,
                                         config.summary_file)

    @staticmethod
    def _jax_start(path: str) -> None:
        import jax

        jax.profiler.start_trace(path)

    @staticmethod
    def _jax_stop() -> None:
        import jax

        jax.profiler.stop_trace()

    # -- trigger feeds -----------------------------------------------------
    def on_iteration(self, iteration: int) -> None:
        """The per-iteration tick (serving engine, outside its lock). O(1)
        attribute checks unless a window boundary or trigger-poll cadence
        lands on this iteration."""
        # tpusync: disable=unguarded-shared-write — monotonic iteration
        # hint only (open_window's fallback when the hang-prefire path has
        # no iteration); an atomic int store, and the O(1) fast path must
        # not take the lock every engine iteration
        self._last_iteration = iteration
        cap = self._open
        if cap is not None:
            if (iteration - cap.opened_iteration >= cap.window_iterations
                    or self.clock() - cap.opened_wall
                    >= self.config.window_wall_s):
                self.close_window()
            return
        trig = self._poll_trigger(iteration)
        if trig is not None:
            self.open_window(trig, iteration=iteration)

    def on_step(self, step: int) -> None:
        """Training cadence (``Observability.note_step``)."""
        self.on_iteration(step)

    def on_compile(self, secs: float, where: str, steady: bool) -> None:
        if not steady or not self.config.trigger_recompile:
            return
        with self._lock:
            # compiles fired by our own summary-time cost traces must not
            # re-trigger a capture of the capture
            if self._summarizing or self._open is not None:
                return
            if self._pending is None:
                self._pending = "recompile"

    def on_hang_prefire(self, stalled_span: str, waited: float,
                        deadline: float) -> None:
        """Hang-watchdog pre-fire (watchdog thread): open the window NOW —
        by the time the deadline expires the engine thread may never tick
        again. The window is closed by the bundle context provider at dump
        time (the trace flushes before the crash bundle reads it), by
        ``close()``, or by the next iteration if the stall resolves."""
        if not self.config.trigger_hang:
            return
        cap = self.open_window("hang_prefire")
        if cap is not None and self.recorder is not None:
            self.recorder.record("profile_hang_prefire",
                                 stalled_span=stalled_span,
                                 waited_s=round(waited, 3),
                                 deadline_s=round(deadline, 3))

    def request_capture(self, trigger: str = "manual") -> None:
        """On-demand window (SIGUSR2 handler / CLI): opened at the next
        engine tick, not here — ``start_trace`` is not signal-safe.
        Deliberately lock-free: the SIGUSR2 handler may interrupt a frame
        that already holds the (non-reentrant) profiler lock, so this is a
        single atomic attribute store — the worst race overwrites one
        pending trigger with another, and the tick consumes it under the
        lock either way."""
        if self._open is None and self._pending is None:
            # tpusync: disable=unguarded-shared-write — signal-safety
            # requires NOT taking the lock here (see docstring); a plain
            # reference store is atomic under the GIL
            self._pending = trigger

    # -- trigger evaluation ------------------------------------------------
    def _poll_trigger(self, iteration: int) -> Optional[str]:
        cfg = self.config
        with self._lock:
            pending, self._pending = self._pending, None
        if pending is not None and self._admissible(pending, iteration):
            return pending
        if cfg.profile_every_steps and iteration > 0 \
                and iteration % cfg.profile_every_steps == 0 \
                and self._admissible("schedule", iteration):
            return "schedule"
        if iteration % cfg.check_interval_iterations != 0:
            return None
        trig = self._telemetry_trigger()
        if trig is not None and self._admissible(trig, iteration):
            return trig
        return None

    def _admissible(self, trigger: str, iteration: int) -> bool:
        with self._lock:
            if self._open is not None:
                return False
            if trigger not in _UNBUDGETED and self._budget <= 0:
                return False
            return iteration >= self._cooldown_until.get(trigger, 0)

    def _telemetry_trigger(self) -> Optional[str]:
        ts = self.timeseries
        if ts is None:
            return None
        cfg = self.config
        try:
            if cfg.trigger_burn:
                stats = ts.stats_matching("serve_goodput/*slo_burn_rate*",
                                          window=32)
                for st in stats.values():
                    if st.get("n", 0) >= 4 \
                            and st.get("ewma", 0.0) > cfg.burn_ceiling:
                        return "burn"
            if cfg.trigger_goodput_slope:
                stats = ts.stats_matching("*goodput_fraction*", window=32)
                for st in stats.values():
                    if st.get("n", 0) >= 8 \
                            and st.get("slope", 0.0) < cfg.slope_floor:
                        return "goodput_slope"
        except Exception:   # a store hiccup must not take the step loop
            logger.warning("profiler trigger evaluation failed",
                           exc_info=True)
        return None

    # -- window lifecycle --------------------------------------------------
    def open_window(self, trigger: str,
                    iteration: Optional[int] = None) -> Optional[Capture]:
        it = self._last_iteration if iteration is None else iteration
        safe = re.sub(r"[^A-Za-z0-9_-]", "_", trigger)
        with self._lock:
            if self._open is not None:
                return None
            if trigger not in _UNBUDGETED:
                if self._budget <= 0 \
                        or it < self._cooldown_until.get(trigger, 0):
                    return None
            self._seq += 1
            d = os.path.join(self.trace_dir,
                             f"capture-{self._seq:03d}-{safe}")
            cap = Capture(seq=self._seq, trigger=trigger, dir=d,
                          opened_iteration=it, opened_wall=self.clock(),
                          window_iterations=self.config.window_iterations)
            try:
                # tpusync: disable=blocking-under-lock — admission and
                # trace start must be atomic (a concurrent hang-prefire
                # open must see _open before it starts a second trace);
                # this path runs at most capture_budget times per process
                # and the mkdir is a local dirent
                os.makedirs(d, exist_ok=True)
                self._start_trace(d)
            except Exception:
                logger.warning("profiler start_trace failed", exc_info=True)
                return None
            self._open = cap
            if trigger not in _UNBUDGETED:
                self._budget -= 1
            # cooldown runs from open: a trigger that stays hot re-fires
            # only after the window AND the cooldown have both passed
            self._cooldown_until[trigger] = \
                it + self.config.cooldown_iterations
            self.captures.append(cap)
            budget = self._budget
        logger.info("profiler: capture window opened (trigger=%s, dir=%s)",
                    trigger, d)
        if self.registry is not None:
            self.registry.counter(
                "profile/captures",
                help="profiler capture windows opened, by trigger").inc(
                    trigger=trigger)
            self.registry.gauge(
                "profile/budget_remaining",
                help="capture-budget headroom left this session").set(budget)
        if self.recorder is not None:
            self.recorder.record("profile_capture_open", trigger=trigger,
                                 dir=d, iteration=it)
        self._prune()
        return cap

    def close_window(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            cap = self._open
            if cap is None:
                return None
            self._open = None
            self._summarizing = True
        try:
            try:
                self._stop_trace()
            except Exception:
                logger.warning("profiler stop_trace failed", exc_info=True)
                cap.status = "failed"
            cap.closed_wall = self.clock()
            summary = None
            if cap.status != "failed":
                summary = self._summarize(cap)
            if self.registry is not None:
                self.registry.histogram(
                    "profile/capture_wall_seconds",
                    help="wall cost of one capture window").observe(
                        cap.wall_s)
            if self.recorder is not None:
                self.recorder.record(
                    "profile_capture_close", trigger=cap.trigger,
                    status=cap.status, wall_s=round(cap.wall_s, 3),
                    entries_matched=cap.entries_matched)
            return summary
        finally:
            with self._lock:
                self._summarizing = False

    def _summarize(self, cap: Capture) -> Optional[Dict[str, Any]]:
        """Parse the closed capture, join against the registry + roofline,
        write ``profile_summary.json``, publish ``profile/*`` gauges.
        Never raises — a parse failure marks the ledger row and moves on."""
        try:
            parsed = parse_trace_dir(cap.dir)
            body = summarize_capture(parsed,
                                     top_k=self.config.hotspot_top_k,
                                     cost_join=_tpucost_join)
            cap.programs_matched = len(parsed.get("programs", {}))
            cap.entries_matched = len(body["entries"])
            cap.status = "parsed" if body["entries"] else "empty"
            summary = {
                "format": PROFILE_FORMAT,
                "capture": cap.to_json(),
                "captures": [c.to_json() for c in self.captures],
                "budget_remaining": self._budget,
                **body,
            }
            os.makedirs(os.path.dirname(self.summary_path), exist_ok=True)
            tmp = self.summary_path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(summary, fh, indent=2, sort_keys=True)
            os.replace(tmp, self.summary_path)
            with self._lock:   # bundle_context reads from other threads
                self.latest_summary = summary
            self._publish_entries(summary["entries"])
            logger.info(
                "profiler: capture %d (%s) parsed — %d program(s), "
                "%d entry row(s), summary at %s", cap.seq, cap.trigger,
                cap.programs_matched, cap.entries_matched,
                self.summary_path)
            return summary
        except Exception:
            logger.warning("profiler summary failed", exc_info=True)
            cap.status = "failed"
            return None

    def _publish_entries(self, entries: Dict[str, Any]) -> None:
        if self.registry is None:
            return
        for name, row in entries.items():
            self.registry.gauge(
                "profile/device_seconds",
                help="measured device seconds attributed to one entry "
                     "over the capture window").set(
                    row["device_s"], entry=name)
            self.registry.gauge(
                "profile/host_seconds",
                help="host dispatch seconds attributed to one entry over "
                     "the capture window").set(row["host_s"], entry=name)
            if row.get("measured_step_ms") is not None:
                self.registry.gauge(
                    "profile/measured_step_ms",
                    help="measured device ms per program invocation").set(
                        row["measured_step_ms"], entry=name)
            if row.get("predicted_step_ms") is not None:
                self.registry.gauge(
                    "profile/predicted_step_ms",
                    help="tpucost roofline prediction paired with the "
                         "measured capture").set(
                        row["predicted_step_ms"], entry=name,
                        bound=row.get("bound", "?"))
            if row.get("model_error") is not None:
                self.registry.gauge(
                    "profile/model_error",
                    help="measured / predicted step time (1.0 = the "
                         "roofline is exact; growth = widening model "
                         "error)").set(row["model_error"], entry=name)
            if row.get("measured_mfu") is not None:
                self.registry.gauge(
                    "profile/measured_mfu",
                    help="measured MFU over the capture window (pair "
                         "with tpucost mfu_ceiling)").set(
                        row["measured_mfu"], entry=name)

    def _prune(self) -> None:
        """keep-last-K on-disk capture dirs (never the open one)."""
        try:
            dirs = sorted(glob.glob(os.path.join(self.trace_dir,
                                                 "capture-*")))
            open_dir = self._open.dir if self._open is not None else None
            victims = [d for d in dirs if d != open_dir]
            for d in victims[:max(len(victims) - self.config.keep_last
                                  + (1 if open_dir else 0), 0)]:
                shutil.rmtree(d, ignore_errors=True)
        except OSError:
            pass

    # -- seams -------------------------------------------------------------
    def bundle_context(self) -> Optional[Dict[str, Any]]:
        """Flight-recorder context provider: a hang-prefire window still
        open at dump time is closed FIRST, so the bundle's summary covers
        the trace of the stall itself; otherwise the latest summary (or
        the bare ledger) is stapled."""
        cap = self._open
        if cap is not None and cap.trigger == "hang_prefire":
            self.close_window()
        if self.latest_summary is not None:
            return self.latest_summary
        if self.captures:
            return {"format": PROFILE_FORMAT,
                    "captures": [c.to_json() for c in self.captures],
                    "entries": {}}
        return None

    def close(self) -> None:
        """Session teardown: flush an open window (its summary still
        lands) and publish the final budget gauge."""
        self.close_window()
        if self.registry is not None and self.captures:
            self.registry.gauge(
                "profile/budget_remaining",
                help="capture-budget headroom left this session").set(
                    self._budget)


# ---------------------------------------------------------------------------
# SIGUSR2 (SIGUSR1 belongs to the flight recorder)

_ACTIVE_PROFILER: Optional[DeepProfiler] = None
_PREV_HANDLER: Any = None


def install_sigusr2(profiler: DeepProfiler) -> bool:
    """SIGUSR2 => request an on-demand capture window (opened at the next
    engine tick). Main-thread only, like the recorder's SIGUSR1."""
    global _ACTIVE_PROFILER, _PREV_HANDLER
    if threading.current_thread() is not threading.main_thread():
        return False
    if _ACTIVE_PROFILER is None:
        def _handler(signum, frame):
            prof = _ACTIVE_PROFILER
            if prof is not None:
                prof.request_capture("sigusr2")
        try:
            _PREV_HANDLER = signal.signal(signal.SIGUSR2, _handler)
        except (ValueError, OSError, AttributeError):
            return False
    _ACTIVE_PROFILER = profiler
    return True


def uninstall_sigusr2() -> None:
    global _ACTIVE_PROFILER, _PREV_HANDLER
    if _ACTIVE_PROFILER is None:
        return
    _ACTIVE_PROFILER = None
    if threading.current_thread() is threading.main_thread():
        try:
            signal.signal(signal.SIGUSR2,
                          _PREV_HANDLER or signal.SIG_DFL)
        except (ValueError, OSError, AttributeError):
            pass
    _PREV_HANDLER = None

"""Flight recorder — the black box a hung or crashed run leaves behind.

The motivating case is a bench run killed by its 900-second watchdog with
nothing to show for it — no stacks, no last span, no step history. This
module makes the next one a one-file diagnosis: an
**always-cheap bounded ring buffer** of recent observability events (span
begin/end, metric publishes, recompile-watchdog compiles, log lines,
heartbeats) plus a ``dump(dir)`` that writes a **self-contained crash
bundle**:

* ``MANIFEST.json`` — reason, stalled span, per-thread open-span stacks,
  exception info, environment summary, device inventory, registered tpuaudit
  entry fingerprints (which jitted programs existed when the run died);
* ``events.jsonl``  — the ring contents, oldest first;
* ``stacks.txt``    — per-thread Python stacks (``faulthandler`` +
  ``sys._current_frames`` formatted via ``traceback``);
* ``memory.json``   — ``device.memory_stats()`` per device + host RSS.

Dumps trigger on unhandled exception in ``train_batch``/``generate`` (the
engines call :meth:`Observability.crash_dump`), on **SIGUSR1**
(:func:`install_sigusr1` — how the bench parent asks a hung child for its
black box before SIGKILL), and on hang-watchdog fire
(``hangdetect.HangWatchdog``). Recording is a deque append under a lock —
never a device interaction — so it is safe at span-boundary cadence; the
expensive work (stack walks, memory stats, file writes) happens only at dump
time. ``python -m deepspeed_tpu.observability report --crash-dump <dir>``
summarizes a bundle (stdlib-only, runs anywhere the files land).
"""

from __future__ import annotations

import collections
import json
import logging
import os
import signal
import sys
import threading
import time
import traceback
from typing import Any, Dict, List, Optional

from ..utils.logging import logger

MANIFEST_NAME = "MANIFEST.json"
EVENTS_NAME = "events.jsonl"
STACKS_NAME = "stacks.txt"
MEMORY_NAME = "memory.json"


def _audit_fingerprints() -> List[Dict[str, Any]]:
    """Fingerprints of the jitted programs registered with tpuaudit at the
    moment of death — name + tags + declared collectives identify WHICH
    program variants existed without pinning any executable. A deployment
    without the tools/ tree contributes an empty list."""
    try:
        from tools.tpuaudit.registry import get_entry_points
    except ImportError:
        return []
    out = []
    try:
        for ep in get_entry_points():
            out.append({
                "name": ep.name,
                "tags": dict(ep.tags),
                "donate_argnums": list(ep.donate_argnums),
                "expected_collectives": sorted(ep.expected_collectives or ()),
            })
    except Exception:  # fingerprinting must never block a dump
        pass
    return out


def _thread_stacks_text() -> str:
    """Per-thread stacks, twice: faulthandler's raw form (matches what a
    fatal-signal dump would print) and traceback's named form (thread names,
    source lines)."""
    import faulthandler
    import io

    parts: List[str] = []
    buf = io.StringIO()
    try:
        names = {t.ident: t.name for t in threading.enumerate()}
        for ident, frame in sys._current_frames().items():
            buf.write(f"--- thread {names.get(ident, '?')} (ident {ident}) "
                      f"---\n")
            buf.write("".join(traceback.format_stack(frame)))
            buf.write("\n")
    except Exception:
        buf.write("<traceback stack walk failed>\n")
    parts.append(buf.getvalue())
    try:
        import tempfile

        with tempfile.TemporaryFile(mode="w+") as fh:
            faulthandler.dump_traceback(file=fh, all_threads=True)
            fh.seek(0)
            parts.append("=== faulthandler ===\n" + fh.read())
    except Exception:
        parts.append("=== faulthandler ===\n<unavailable>\n")
    return "\n".join(parts)


def _environment_summary() -> Dict[str, Any]:
    env = {k: v for k, v in os.environ.items()
           if k.startswith(("JAX_", "XLA_", "BENCH_", "DSTPU_", "TPU_",
                            "LIBTPU_"))}
    info: Dict[str, Any] = {
        "argv": list(sys.argv),
        "python": sys.version.split()[0],
        "platform": sys.platform,
        "cwd": os.getcwd(),
        "env": env,
    }
    try:
        import jax

        info["jax_version"] = jax.__version__
        info["backend"] = jax.default_backend()
        info["devices"] = [f"{d.platform}:{d.id}:{d.device_kind}"
                           for d in jax.local_devices()]
        info["process_index"] = jax.process_index()
        info["process_count"] = jax.process_count()
    except Exception:
        info["jax_version"] = None
    return info


class _RingLogHandler(logging.Handler):
    """Feeds framework log lines into the ring (WARNING+ by default — the
    steady-state-recompile warning and comm errors are exactly the lines a
    post-mortem wants)."""

    def __init__(self, recorder: "FlightRecorder",
                 level: int = logging.WARNING):
        super().__init__(level=level)
        self._recorder = recorder

    def emit(self, record: logging.LogRecord) -> None:
        try:
            self._recorder.record("log", level=record.levelname,
                                  message=record.getMessage()[:500])
        except Exception:  # a logging hook must never raise
            pass


class FlightRecorder:
    """Bounded ring of recent observability events + crash-bundle writer.

    One per enabled :class:`~deepspeed_tpu.observability.Observability`
    session. Thread-safe; ``record`` is O(1) (deque append + dict build).
    The recorder also mirrors the per-thread OPEN span stacks (from the span
    begin/end events it receives) so a dump can name what every thread was
    inside — the tracer's own stacks are thread-local and unreadable from
    the dumping thread.
    """

    def __init__(self, capacity: int = 4096, dump_dir: str = "./dstpu_crash",
                 clock=time.time):
        self.capacity = int(capacity)
        self.dump_dir = dump_dir
        self._clock = clock
        self._ring: collections.deque = collections.deque(maxlen=self.capacity)
        # RLock: the SIGUSR1 handler runs ON the interrupted thread and
        # calls record()/dump() — a plain Lock would self-deadlock if the
        # signal lands inside one of our own critical sections
        self._lock = threading.RLock()
        self._seq = 0
        # per-thread open-span mirror as (id(span), name) pairs: the pop on
        # span end matches by identity, like the tracer's own stack — a
        # name-based pop would collapse same-named nested spans
        self._open_spans: Dict[int, List[tuple]] = {}
        self._log_handler: Optional[_RingLogHandler] = None
        self.dumps: List[str] = []
        # name -> zero-arg callable consulted at dump time; its JSON-able
        # return value lands in the MANIFEST under that name (the request
        # tracer staples the in-flight trace tail through this seam)
        self.context_providers: Dict[str, Any] = {}

    # -- recording --------------------------------------------------------
    def record(self, kind: str, **fields: Any) -> None:
        with self._lock:
            self._seq += 1
            self._ring.append({"seq": self._seq, "t": self._clock(),
                               "kind": kind, **fields})

    def record_span(self, phase: str, span: Any) -> None:
        """Span begin/end feed (wired to ``SpanTracer.on_event``). Mirrors
        the open-span stack per thread alongside the ring entry."""
        tid = threading.get_ident()
        with self._lock:
            self._seq += 1
            ev: Dict[str, Any] = {"seq": self._seq, "t": self._clock(),
                                  "kind": f"span_{phase}", "name": span.name,
                                  "tid": tid}
            if phase == "end":
                ev["dur_s"] = round(span.duration_s, 6)
                stack = self._open_spans.get(tid)
                if stack:
                    # pop through unclosed children, like the tracer does
                    while stack and stack[-1][0] != id(span):
                        stack.pop()
                    if stack:
                        stack.pop()
                    if not stack:
                        self._open_spans.pop(tid, None)
            else:
                if span.attrs:
                    step = span.attrs.get("step")
                    if step is not None:
                        ev["step"] = step
                self._open_spans.setdefault(tid, []).append(
                    (id(span), span.name))
            self._ring.append(ev)

    def attach_logging(self, target: Optional[logging.Logger] = None,
                       level: int = logging.WARNING) -> None:
        if self._log_handler is None:
            self._log_handler = _RingLogHandler(self, level=level)
            (target or logger).addHandler(self._log_handler)

    def detach_logging(self, target: Optional[logging.Logger] = None) -> None:
        if self._log_handler is not None:
            (target or logger).removeHandler(self._log_handler)
            self._log_handler = None

    # -- inspection -------------------------------------------------------
    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._ring)

    def open_spans(self) -> Dict[int, List[str]]:
        with self._lock:
            return {tid: [name for _, name in stack]
                    for tid, stack in self._open_spans.items()}

    def innermost_open_span(self) -> Optional[str]:
        """Deepest open span across threads (main thread preferred) — the
        best 'where was it stuck' guess when no watchdog named one."""
        main_id = threading.main_thread().ident
        with self._lock:
            stack = self._open_spans.get(main_id)
            if stack:
                return stack[-1][1]
            for other in self._open_spans.values():
                if other:
                    return other[-1][1]
        return None

    # -- the crash bundle -------------------------------------------------
    def dump(self, directory: Optional[str] = None, reason: str = "manual",
             stalled_span: Optional[str] = None,
             exc: Optional[BaseException] = None,
             extra: Optional[Dict[str, Any]] = None) -> str:
        """Write one self-contained bundle and return its directory. Never
        raises (a broken dump path must not mask the original failure) —
        on failure it logs and returns ""."""
        try:
            return self._dump(directory, reason, stalled_span, exc, extra)
        except Exception:
            logger.error("flight-recorder dump failed", exc_info=True)
            return ""

    def _dump(self, directory, reason, stalled_span, exc, extra) -> str:
        base = directory or self.dump_dir
        stamp = time.strftime("%Y%m%d-%H%M%S")
        bundle = os.path.join(base, f"crash-{stamp}-{reason}")
        n = 1
        while os.path.exists(bundle):
            bundle = os.path.join(base, f"crash-{stamp}-{reason}.{n}")
            n += 1
        os.makedirs(bundle)

        events = self.snapshot()
        with open(os.path.join(bundle, EVENTS_NAME), "w") as fh:
            for ev in events:
                fh.write(json.dumps(ev) + "\n")

        with open(os.path.join(bundle, STACKS_NAME), "w") as fh:
            fh.write(_thread_stacks_text())

        open_spans = self.open_spans()
        if stalled_span is None:
            stalled_span = self.innermost_open_span()
        manifest: Dict[str, Any] = {
            "format": 1,
            "reason": reason,
            "wall_time": self._clock(),
            "pid": os.getpid(),
            "stalled_span": stalled_span,
            "open_spans": {str(tid): stack
                           for tid, stack in open_spans.items()},
            "ring_events": len(events),
            "ring_capacity": self.capacity,
            "audit_entries": _audit_fingerprints(),
            "environment": _environment_summary(),
            "files": [EVENTS_NAME, STACKS_NAME, MEMORY_NAME],
        }
        for key, provider in list(self.context_providers.items()):
            try:
                manifest[key] = provider()
            except Exception:   # a provider must never block the dump
                pass
        if exc is not None:
            manifest["exception"] = {
                "type": type(exc).__name__,
                "message": str(exc)[:2000],
                "traceback": "".join(traceback.format_exception(
                    type(exc), exc, exc.__traceback__))[-8000:],
            }
        if extra:
            manifest["extra"] = extra
        with open(os.path.join(bundle, MANIFEST_NAME), "w") as fh:
            json.dump(manifest, fh, indent=1)
        with self._lock:
            # dump() is reachable from the watchdog thread, SIGUSR1 and
            # crashing trainers at once; the bundle list must not lose
            # entries to a torn append
            self.dumps.append(bundle)

        # memory LAST, time-bounded, AFTER the manifest landed: on a wedged
        # remote backend device.memory_stats() is an RPC that can block
        # forever — the scenario this module exists for. The bundle must be
        # complete (manifest + events + stacks) before any device call, and
        # a hang-watchdog abort must not be held hostage by the poll.
        def _write_memory():
            from .memory import device_memory_stats, host_rss_bytes

            try:
                with open(os.path.join(bundle, MEMORY_NAME), "w") as fh:
                    json.dump({"host_rss_bytes": host_rss_bytes(),
                               "devices": device_memory_stats()}, fh,
                              indent=1)
            except Exception:
                pass

        mem_thread = threading.Thread(target=_write_memory, daemon=True,
                                      name="dstpu-flight-mem")
        mem_thread.start()
        mem_thread.join(timeout=5.0)
        logger.error(f"flight record dumped to {bundle} (reason={reason}"
                     + (f", stalled span '{stalled_span}'" if stalled_span
                        else "") + ")")
        return bundle


def find_latest_bundle(directory: str) -> Optional[str]:
    """Newest crash bundle under ``directory`` (by mtime), or None. The
    bench parent uses this to locate the dump a SIGUSR1'd child wrote."""
    try:
        candidates = [
            os.path.join(directory, d) for d in os.listdir(directory)
            if os.path.isfile(os.path.join(directory, d, MANIFEST_NAME))]
    except OSError:
        return None
    if not candidates:
        return None
    return max(candidates, key=os.path.getmtime)


_SIGUSR1_INSTALLED = False


def install_sigusr1(recorder: FlightRecorder) -> bool:
    """Install a SIGUSR1 handler that dumps ``recorder``'s flight record
    (chaining any previous callable handler). Signal handlers can only be
    installed from the main thread — returns False (and records why) when
    that, or a host without SIGUSR1, makes installation impossible. The
    process-wide handler is installed once and follows the session's
    CURRENT recorder via a module pointer, so repeated engine constructions
    never stack handlers."""
    global _SIGUSR1_INSTALLED, _ACTIVE_RECORDER
    _ACTIVE_RECORDER = recorder
    if not hasattr(signal, "SIGUSR1"):
        return False
    if not _SIGUSR1_INSTALLED:
        if threading.current_thread() is not threading.main_thread():
            logger.warning("SIGUSR1 flight-record handler not installed "
                           "(session created off the main thread)")
            return False
        previous = signal.getsignal(signal.SIGUSR1)

        # tpusync: disable=signal-unsafe-handler — dump-on-SIGUSR1 IS the
        # feature (last-resort diagnostics on a wedged process); the ring
        # lock is an RLock and the bundle write accepts the async-signal
        # risk in exchange for evidence
        def _handler(signum, frame):
            rec = _ACTIVE_RECORDER
            if rec is not None:
                rec.record("signal", signum=int(signum))
                rec.dump(reason="sigusr1")
            if callable(previous) and previous not in (signal.SIG_IGN,
                                                       signal.SIG_DFL):
                previous(signum, frame)

        try:
            signal.signal(signal.SIGUSR1, _handler)
        except (ValueError, OSError):
            return False
        _SIGUSR1_INSTALLED = True
    try:
        # Belt and braces: a Python-level handler only runs when the main
        # thread returns to the interpreter -- a process wedged inside native
        # XLA code (backend init, compile, a blocked dispatch) would never
        # dump. faulthandler's C-level handler writes raw per-thread stacks
        # immediately regardless, then chains into the handler above.
        # (Re-)registered per session so the output file follows the CURRENT
        # recorder's dump dir; a signal handler cannot open files, so the
        # handle must pre-exist. (Re-registration keeps the original chain
        # target: faulthandler captures the previous handler only once.)
        import faulthandler

        global _FAULTHANDLER_FH
        os.makedirs(recorder.dump_dir, exist_ok=True)
        new_fh = open(
            os.path.join(recorder.dump_dir, "faulthandler-sigusr1.txt"), "w")
        # register the NEW file before closing the old handle: if anything
        # above raised, the previous registration stays valid, and there is
        # never a window where faulthandler holds a closed (reusable) fd
        faulthandler.register(signal.SIGUSR1, file=new_fh,
                              all_threads=True, chain=True)
        old_fh, _FAULTHANDLER_FH = _FAULTHANDLER_FH, new_fh
        if old_fh is not None:
            old_fh.close()
    except Exception:
        pass    # best-effort: the Python-level dump still works
    return True


_FAULTHANDLER_FH = None


_ACTIVE_RECORDER: Optional[FlightRecorder] = None


def uninstall_sigusr1() -> None:
    """Detach the active recorder (the Python handler stays installed but
    no-ops -- same pattern as the recompile watchdog's listeners) and drop
    the C-level faulthandler registration with its file handle."""
    global _ACTIVE_RECORDER, _FAULTHANDLER_FH
    _ACTIVE_RECORDER = None
    try:
        import faulthandler

        if _FAULTHANDLER_FH is not None:
            faulthandler.unregister(signal.SIGUSR1)
            _FAULTHANDLER_FH.close()
            _FAULTHANDLER_FH = None
    except Exception:
        pass

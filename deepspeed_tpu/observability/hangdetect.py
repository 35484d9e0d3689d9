"""Hang/stall watchdog — a run that stops making progress dies loudly.

The failure mode this targets: device work stalls (lost backend, deadlocked
collective, wedged host callback), the host blocks inside a dispatch, and
the process sits silent until something external SIGKILLs it — losing every
byte of evidence. MegaScale-style hang diagnosis works the other way around: the
training process itself notices the stall, names what it was doing, writes
its own black box, and (optionally) exits with a distinct code.

Mechanics: the engine heartbeats at **span boundaries** — every span
begin/end (fwd/bwd/step/train_batch/checkpoint/inference), the comm census,
the pipeline census — through ``Observability``'s span-event dispatcher.
The watchdog keeps the last heartbeat (time + span name) and a rolling
window of recent step times; a check fires when no heartbeat has arrived
within

    ``deadline = max(hang_timeout_factor × rolling-median step time,
                     hang_timeout_floor_s)``

— median-based so a fleet of fast steps gets a tight deadline while a run
with 60 s steps is not killed by its own cadence, floored so compile-heavy
warmup (no step history yet) never false-fires. On fire it dumps a flight
record naming the stalled span (the last heartbeat's — for a host blocked
in a dispatch, the innermost open span it never exited), publishes
``hang/watchdog_fired``, and either keeps the process alive (default) or
aborts via ``os._exit(hang_exit_code)`` so the supervisor sees a distinct
exit code instead of a 900-second silence.

Everything is injectable for tests: ``clock`` (no real sleeps — drive
``check(now)`` directly), ``on_fire``, and the abort hook. The background
thread (``start()``) is just ``check()`` on a timer.
"""

from __future__ import annotations

import collections
import os
import statistics
import threading
import time
from typing import Any, Callable, Deque, Optional, Tuple

from ..utils.logging import logger


class HangWatchdog:
    """Heartbeat deadline watchdog. One per enabled observability session
    when ``ObservabilityConfig.hang_watchdog`` is on (opt-in: it owns a
    thread and may abort the process)."""

    def __init__(self, recorder: Optional[Any] = None,
                 registry: Optional[Any] = None,
                 timeout_factor: float = 8.0,
                 timeout_floor_s: float = 120.0,
                 poll_interval_s: float = 5.0,
                 abort: bool = False,
                 exit_code: int = 113,
                 window: int = 32,
                 clock: Callable[[], float] = time.monotonic,
                 on_fire: Optional[Callable[..., None]] = None,
                 abort_fn: Callable[[int], None] = os._exit):
        self.recorder = recorder
        self.registry = registry
        self.timeout_factor = float(timeout_factor)
        self.timeout_floor_s = float(timeout_floor_s)
        self.poll_interval_s = float(poll_interval_s)
        self.abort = bool(abort)
        self.exit_code = int(exit_code)
        # escalation threshold: with abort on, only the Nth fire (and later)
        # actually aborts — earlier fires dump evidence and leave the
        # process alive so a supervisor can attempt a SOFT restart when (if)
        # control returns. 1 = every fire aborts (the pre-escalation
        # behavior); the TrainingSession's dump→soft-restart→hard-restart
        # ladder sets this to hang_soft_restarts + 1.
        self.abort_after_fires = 1
        self.on_fire = on_fire
        # optional early hook, called ONCE per stall when the silence passes
        # `prefire_fraction × deadline` — the deep profiler opens a capture
        # window here so the eventual crash bundle carries a trace of the
        # stall forming, not just its aftermath. None (default) costs one
        # attribute check per check().
        self.on_prefire: Optional[Callable[..., None]] = None
        self.prefire_fraction = 0.5
        self._prefired_beat: Optional[float] = None
        # optional () -> dict merged into the fire dump's extra — the fleet
        # monitor uses it to say "blocked in the step-N gather, rank R never
        # arrived"; None (default) costs one attribute check per fire
        self.context_fn: Optional[Callable[[], dict]] = None
        self._abort_fn = abort_fn
        self._clock = clock
        self._lock = threading.Lock()
        self._last_beat: Optional[Tuple[float, str]] = None
        self._step_times: Deque[float] = collections.deque(maxlen=window)
        self._armed = False
        self.fired = 0
        self.last_fire: Optional[dict] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- feed (span-boundary cadence: must stay O(1)) ---------------------
    def heartbeat(self, name: str) -> None:
        with self._lock:
            self._last_beat = (self._clock(), name)
            self._armed = True

    def note_step_time(self, secs: float) -> None:
        """One completed step's wall seconds (train_batch span duration) —
        the rolling-median source for the deadline."""
        if secs > 0:
            with self._lock:
                self._step_times.append(float(secs))

    def disarm(self) -> None:
        """Suspend checking until the next heartbeat (run finished, or a
        legitimately unbounded host phase like a checkpoint download)."""
        with self._lock:
            self._armed = False

    # -- deadline ---------------------------------------------------------
    def deadline_s(self) -> float:
        with self._lock:
            if not self._step_times:
                return self.timeout_floor_s
            median = statistics.median(self._step_times)
        return max(self.timeout_factor * median, self.timeout_floor_s)

    # -- the check (thread body; tests call it directly) ------------------
    def check(self, now: Optional[float] = None) -> bool:
        """Returns True if the watchdog fired on this check."""
        with self._lock:
            if not self._armed or self._last_beat is None:
                return False
            beat_t, beat_name = self._last_beat
        now = self._clock() if now is None else now
        waited = now - beat_t
        deadline = self.deadline_s()
        if self.on_prefire is not None \
                and waited > self.prefire_fraction * deadline:
            with self._lock:
                # once per stall: the latch is the beat timestamp, so a
                # heartbeat (new stall) re-arms it
                prefire = (self._armed and self._last_beat is not None
                           and self._last_beat[0] == beat_t
                           and self._prefired_beat != beat_t)
                if prefire:
                    self._prefired_beat = beat_t
            if prefire:
                try:
                    self.on_prefire(stalled_span=beat_name, waited=waited,
                                    deadline=deadline)
                except Exception:
                    logger.warning("hang watchdog on_prefire hook failed",
                                   exc_info=True)
        if waited <= deadline:
            return False
        with self._lock:
            # re-check under the lock: a heartbeat may have landed between
            # the read above and here; and only ever fire once per stall
            if not self._armed or self._last_beat[0] != beat_t:
                return False
            self._armed = False
        self._fire(beat_name, waited, deadline)
        return True

    def _fire(self, stalled_span: str, waited: float, deadline: float) -> None:
        extra = {"waited_s": waited, "deadline_s": deadline}
        if self.context_fn is not None:
            try:
                extra.update(self.context_fn() or {})
            except Exception:
                logger.warning("hang watchdog context_fn failed",
                               exc_info=True)
        bundle = ""
        if self.recorder is not None:
            self.recorder.record("watchdog_fire", stalled_span=stalled_span,
                                 waited_s=round(waited, 3),
                                 deadline_s=round(deadline, 3))
            bundle = self.recorder.dump(reason="hang",
                                        stalled_span=stalled_span,
                                        extra=extra)
        with self._lock:
            # both under the lock, last_fire first: observers polling
            # `fired` see a complete last_fire (the threaded end-to-end
            # test races exactly this); the dump above stays outside the
            # lock so heartbeats never stall behind bundle IO
            self.last_fire = {"stalled_span": stalled_span,
                              "waited_s": waited, "deadline_s": deadline,
                              "bundle": bundle}
            self.fired += 1
        if self.registry is not None:
            self.registry.counter(
                "hang/watchdog_fired",
                help="hang watchdog deadline expiries").inc(span=stalled_span)
        aborting = self.abort and self.fired >= self.abort_after_fires
        logger.error(
            f"HANG WATCHDOG: no heartbeat for {waited:.1f}s "
            f"(deadline {deadline:.1f}s) — last activity was span "
            f"'{stalled_span}'"
            + (f"; flight record at {bundle}" if bundle else "")
            + (f"; aborting with exit code {self.exit_code}" if aborting
               else ""))
        if self.on_fire is not None:
            try:
                self.on_fire(stalled_span=stalled_span, waited=waited,
                             deadline=deadline, bundle=bundle)
            except Exception:
                logger.warning("hang watchdog on_fire hook failed",
                               exc_info=True)
        if aborting:
            # os._exit, not sys.exit: the whole point is escaping a process
            # whose main thread is wedged inside a dispatch — atexit hooks
            # touching the device would hang exactly the same way. The
            # flight record above IS the orderly shutdown.
            self._abort_fn(self.exit_code)

    # -- thread -----------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, name="dstpu-hang-watchdog", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while not self._stop.wait(self.poll_interval_s):
            try:
                self.check()
            except Exception:  # the watchdog must outlive its own bugs
                logger.warning("hang watchdog check failed", exc_info=True)

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=2 * self.poll_interval_s)
            self._thread = None

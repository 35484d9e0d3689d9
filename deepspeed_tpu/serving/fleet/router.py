"""FleetRouter — the data-plane front end over N serving replicas.

Exposes the same ``submit()/stream()/result()/cancel()`` surface as one
``ServingEngine`` and dispatches to a fleet of them:

* **Routing** — pluggable policies over the per-replica
  :class:`~.replica.ReplicaHealth` snapshot the router polls between
  scheduler iterations: ``round_robin``, ``least_queue`` (fewest in-flight
  requests), ``kv_occupancy`` (lowest arena occupancy) and ``affinity``
  (prefix-cache locality: the router remembers which replica served each
  first-prompt-block hash, so requests sharing a system prompt follow the
  warm prefix cache instead of re-prefilling it N times — the
  cross-replica prefix-cache admission hint). Every decision is counted by
  reason in ``fleet_serving/routing_decisions``.
* **Disaggregation** — replicas carry roles (``prefill`` / ``decode``):
  a request prefills on the prefill pool, then its KV blocks move to a
  decode replica through the :class:`~.disagg.KVHandoff` seam and decoding
  continues there, bit-identically (the sampling stream depends only on
  (engine seed, request seed, token index), never on which engine runs
  it). A handoff the decode pool cannot take — or whose TRANSFER fails
  mid-flight (``handoff_fail`` chaos fault, kv_import raising) after one
  retry on another decode replica — falls back to decoding in place,
  with both sides' blocks freed exactly once.
* **Self-healing** — the full detect → remediate → verify loop, not just
  detect-and-drain:

  - a dead replica (chaos ``replica_kill``, an exception out of its
    scheduler iteration) is drained: every in-flight request resubmits to
    a surviving replica in recompute mode (``submit_recovered``), which
    re-prefills prompt + streamed-tokens and continues the stream
    bit-exactly. A resubmission that finds every survivor momentarily
    full PARKS and retries on later iterations instead of burning the
    ``max_resubmits`` budget (the budget counts replica deaths, not full
    queues).
  - health **verdicts** go beyond "step() raised": a replica whose
    rolling median step time exceeds ``slow_factor ×`` the other
    replicas' medians (or the absolute ``step_time_slo_s``), or that
    breaches the fleet ``ttft_slo_s``, is **quarantined** — alive,
    draining its own work, but receiving no new traffic — for an
    exponentially backed-off window (the elastic agent's ladder, in
    router iterations).
  - a dead replica is **revived**: ``revive_replica()`` rebuilds its
    engine reusing the fleet-shared weights and the already-compiled
    program set of a surviving replica (cheap by construction — one
    arena allocation, zero compiles), then re-admits it through
    **probation**: its traffic share stays bounded
    (``probation_share``) until ``probation_requests`` requests complete
    cleanly, at which point it graduates to full routing weight.
  - the per-replica **circuit breaker** retires a replica whose
    incidents (deaths + quarantines) exceed ``breaker_incidents`` —
    a flapping replica is removed for good instead of flapping forever.
* **Overload control** — ``submit()`` sheds deadline-infeasible work
  up front: when the measured fleet TPOT says ``deadline_s`` cannot be
  met at the target replica's queue depth, the request is rejected
  immediately with :class:`Overloaded` (``retry_after_s`` set) instead of
  admitted to die. Under sustained pressure the router walks a
  **degraded-mode ladder** (``fleet_serving/degraded_mode``): rung 1
  suspends speculative decoding fleet-wide (freeing the draft arenas'
  block traffic), rung 2 stops following prefix-affinity admission hints
  (load beats locality), rung 3 sheds queued work — no-deadline /
  latest-deadline first — one victim per iteration. Calm iterations walk
  the ladder back down with hysteresis.

The router DRIVES its replicas (one scheduler iteration per replica per
``step()``); replica engines must not run their own driver threads.
``start()`` provides the fleet's background thread.
"""

from __future__ import annotations

import collections
import statistics
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

import numpy as np

from ...config.config import FleetConfig
from ...observability import get_session
from ...utils.logging import log_dist, logger
from ..scheduler import DEADLINE_EXCEEDED, FINISHED, QUEUED, QueueFull
from .disagg import (ArenaHandoff, KVHandoff,
                     register_handoff_audit_entries)
from .replica import (ROLE_DECODE, ROLE_MIXED, ROLE_PREFILL, Replica,
                      ReplicaDead, ReplicaRetired)

__all__ = ["FleetRouter", "FleetHandle", "FleetUnavailable", "Overloaded"]

RUNNING = "running"
F_FINISHED = "finished"
F_CANCELLED = "cancelled"
F_SHED = "shed"
F_DEADLINE = "deadline_exceeded"

# degraded-mode ladder rungs (the fleet_serving/degraded_mode gauge)
DEGRADED_NONE = 0          # normal service
DEGRADED_NO_SPEC = 1       # speculation suspended fleet-wide
DEGRADED_NO_AFFINITY = 2   # prefix-affinity hints ignored (load > locality)
DEGRADED_SHED = 3          # queued work shed, latest-deadline first


class FleetUnavailable(RuntimeError):
    """No alive replica can take the request."""


class Overloaded(RuntimeError):
    """The fleet cannot serve this request in time: either its deadline is
    infeasible at current queue depth + measured TPOT (admission shed), or
    the degraded-mode ladder shed it from the queue. ``retry_after_s`` is
    the structured back-off hint — resubmitting sooner just gets shed
    again."""

    def __init__(self, msg: str, retry_after_s: float):
        super().__init__(msg)
        self.retry_after_s = float(retry_after_s)


class _FleetRequest:
    """Router-side record of one client request: the original submission
    (the resubmit source of truth) plus the CURRENT engine binding."""

    def __init__(self, fid: int, prompt: np.ndarray, seed: int,
                 kwargs: Dict[str, Any], arrival_s: float):
        self.fid = fid
        self.prompt = prompt
        self.seed = seed
        self.kwargs = kwargs          # max_new_tokens/sampling/eos/tenant
        self.deadline_abs: Optional[float] = None
        self.state = RUNNING
        self.replica: Optional[Replica] = None
        self.u_req = None             # bound engine-side Request
        self.u_handle = None          # ... and its RequestHandle
        self.consumed = 0             # tokens drained off u_handle so far
        self.resubmits = 0
        self.handoffs = 0
        self.arrival_s = arrival_s
        self.first_token_s: Optional[float] = None
        self.finish_s: Optional[float] = None
        self.handle: Optional["FleetHandle"] = None
        self.retry_after_s = 0.0      # set when the ladder sheds this

    def bind(self, replica: Replica, u_handle) -> None:
        self.replica = replica
        self.u_handle = u_handle
        self.u_req = u_handle._req
        self.consumed = 0

    @property
    def done(self) -> bool:
        return self.state in (F_FINISHED, F_CANCELLED, F_SHED, F_DEADLINE)


class FleetHandle:
    """Client view of one fleet request: the same incremental streaming
    surface as ``RequestHandle``, stable across KV handoffs and replica
    deaths (the router rebinds the engine side underneath it)."""

    def __init__(self, router: "FleetRouter", fr: _FleetRequest):
        self._router = router
        self._fr = fr
        self._cond = threading.Condition()
        self._tokens: List[int] = []

    # -- router-side -------------------------------------------------------
    def _push(self, token: int) -> None:
        with self._cond:
            self._tokens.append(int(token))
            self._cond.notify_all()

    def _wake(self) -> None:
        with self._cond:
            self._cond.notify_all()

    # -- client-side -------------------------------------------------------
    @property
    def request_id(self) -> int:
        return self._fr.fid

    @property
    def state(self) -> str:
        return self._fr.state

    @property
    def done(self) -> bool:
        return self._fr.done

    @property
    def tokens(self) -> List[int]:
        with self._cond:
            return list(self._tokens)

    @property
    def ttft_s(self) -> Optional[float]:
        if self._fr.first_token_s is None:
            return None
        return self._fr.first_token_s - self._fr.arrival_s

    @property
    def tpot_s(self) -> Optional[float]:
        fr = self._fr
        if (fr.finish_s is None or fr.first_token_s is None
                or len(self._tokens) < 2):
            return None
        return (fr.finish_s - fr.first_token_s) / (len(self._tokens) - 1)

    @property
    def resubmits(self) -> int:
        return self._fr.resubmits

    @property
    def handoffs(self) -> int:
        return self._fr.handoffs

    def cancel(self) -> bool:
        return self._router.cancel(self)

    def stream(self, timeout_s: Optional[float] = None) -> Iterator[int]:
        """Yield tokens as generated; in step-driven mode this drives the
        ROUTER (one fleet iteration per starved pass)."""
        from ..session import drive_stream

        rt = self._router
        yield from drive_stream(
            self._cond, self._tokens, lambda: self._fr.done, rt.clock,
            lambda: rt.threaded, rt.step, lambda: rt._starvation_limit,
            f"fleet request {self._fr.fid}",
            "fleet stalled — no replica can make progress", timeout_s)

    def result(self, timeout_s: Optional[float] = None) -> np.ndarray:
        for _ in self.stream(timeout_s=timeout_s):
            pass
        if self._fr.state == F_CANCELLED:
            from ..session import RequestCancelled

            raise RequestCancelled(
                f"fleet request {self._fr.fid} was cancelled")
        if self._fr.state == F_DEADLINE:
            from ..session import DeadlineExceeded

            raise DeadlineExceeded(
                f"fleet request {self._fr.fid} missed its deadline")
        if self._fr.state == F_SHED:
            raise Overloaded(
                f"fleet request {self._fr.fid} was shed under overload "
                f"(degraded mode)", retry_after_s=self._fr.retry_after_s)
        return np.asarray(self.tokens, np.int32)


class FleetRouter:
    """Data-plane router over N serving replicas (see module docstring)."""

    def __init__(self, replicas: List[Replica],
                 config: Optional[FleetConfig] = None,
                 clock: Callable[[], float] = time.monotonic,
                 fault_plan: Any = None,
                 handoff: Optional[KVHandoff] = None):
        if not replicas:
            raise ValueError("FleetRouter needs at least one replica")
        self.replicas = list(replicas)
        self.config = config or FleetConfig()
        self.config.validate()
        self.clock = clock
        geoms = {(r.engine.config.block_size, r.engine.config.max_model_len)
                 for r in self.replicas}
        if len(geoms) > 1:
            raise ValueError(
                f"fleet replicas disagree on block geometry {sorted(geoms)}"
                " — affinity keys and KV handoffs need one (block_size, "
                "max_model_len)")
        self._block_size = self.replicas[0].engine.config.block_size
        for r in self.replicas:
            # the verdict window length is fleet policy, not replica state
            r.step_times = collections.deque(
                r.step_times, maxlen=self.config.health_window)
            r.warmup_left = self.config.health_warmup_steps
            # request traces + serve_goodput gauges carry the replica index
            r.engine.trace_tag = str(r.index)
            # the router owns the fleet's live tuner; replica engines must
            # not each grow their own
            r.engine._fleet_managed = True
        roles = {r.role for r in self.replicas}
        self.disagg = roles != {ROLE_MIXED}
        self.prefill_pool = [r for r in self.replicas
                             if r.role in (ROLE_PREFILL, ROLE_MIXED)]
        self.decode_pool = [r for r in self.replicas
                            if r.role in (ROLE_DECODE, ROLE_MIXED)]
        if self.disagg and (not self.prefill_pool or not self.decode_pool):
            raise ValueError(
                "disaggregated fleet needs at least one prefill and one "
                f"decode replica (roles: {sorted(roles)})")
        self.handoff = handoff or (ArenaHandoff() if self.disagg else None)
        if self.disagg:
            # fail FAST on arena-geometry mismatch: every prefill replica
            # must be able to hand blocks to every decode replica. Checked
            # once here — a HandoffGeometryError surfacing at transfer
            # time would be swallowed by the mid-flight retry/fallback
            # path and silently disable disaggregation
            from .disagg import _check_geometry, _EngineView

            for p in self.prefill_pool:
                for d in self.decode_pool:
                    if p.engine is not d.engine:
                        _check_geometry(_EngineView(p.engine),
                                        _EngineView(d.engine))
        if self.disagg:
            for r in self.prefill_pool:
                if r.role != ROLE_PREFILL:
                    continue
                r.engine.on_prefill_complete = (
                    lambda req, _r=r: self._handoff_from(_r, req))
            register_handoff_audit_entries(self.replicas[0].engine,
                                           self.handoff)
        self._lock = threading.RLock()
        self._fid = 0
        self._iterations = 0
        # fid -> live request; terminal requests are pruned (the client
        # keeps its handle) so a long-running router stays bounded
        self._requests: Dict[int, _FleetRequest] = {}
        self._by_engine: Dict[tuple, int] = {}   # (replica_idx, rid) -> fid
        # first-prompt-block hash -> replica index (bounded LRU): the
        # cross-replica prefix-cache admission hint
        self._affinity: "collections.OrderedDict[bytes, int]" = \
            collections.OrderedDict()
        self._rr = 0
        # host-side (policy, reason) -> count mirror of the
        # fleet_serving/routing_decisions counter, for obs-less callers
        # (the bench A/B reads this)
        self._decisions: "collections.Counter" = collections.Counter()
        self._handoff_ms = collections.deque(maxlen=8192)
        self._resubmit_count = 0
        self._death_count = 0
        self._handoff_fallbacks = 0
        self._handoff_failures = 0
        # resubmissions parked on QueueFull (every survivor momentarily
        # full): fids retried each iteration WITHOUT spending budget
        self._parked: List[int] = []
        # -- self-healing ledger --
        self._quarantine_count = 0
        self._revival_count = 0
        self._graduation_count = 0
        self._ttft_breaches = 0
        # death→revival iteration gaps (the bench's time-to-revival)
        self._revive_iters: List[int] = []
        # engines replaced by revivals: their latency reservoirs and token
        # counts must still pool into the close-time fleet-wide gauges,
        # and their close() (drafter teardown) must still run. Bounded:
        # each replica retires after <= breaker_incidents revivals
        self._replaced_engines: List[Any] = []
        # -- overload control state --
        self._degraded = DEGRADED_NONE
        # admission-estimate pad: the live tuner's deadline knob.
        # _estimate_completion_s scales by (1 + pad), so pad > 0 sheds
        # deadline-infeasible work earlier. Data-only: admission policy,
        # never a dispatch shape.
        self.admission_pad = 0.0
        # lazy live-tuner hook (autotuning.livetuner), consulted at step
        # cadence like the engines' goodput accountant: benches enable
        # observability after construction, and the disabled path must
        # wire nothing
        self._tuner = None
        self._tuner_obs = None
        self._pressure_streak = 0
        self._calm_streak = 0
        self._shed_count = 0
        # measured fleet TPOT (per-token seconds over finished requests)
        # and submitted token budgets — the admission estimator's inputs
        self._tpot_obs = collections.deque(maxlen=512)
        self._mnt_obs = collections.deque(maxlen=512)
        # fleet-level request ledger over ADMITTED requests:
        # submitted == finished + cancelled + shed + deadline_exceeded
        # (+ in flight). Admission-shed requests never enter it — they
        # were rejected before a handle existed (the shed METRIC counts
        # both kinds, by reason).
        self.submitted_count = 0
        self.finished_count = 0
        self.cancelled_count = 0
        self.shed_count_total = 0
        self.deadline_exceeded_count = 0
        self._starvation_limit = 2 * sum(
            r.engine.config.max_queue for r in self.replicas) + 8
        self._injector = None
        if fault_plan is not None:
            from ...observability.faultinject import FaultInjector

            obs = get_session()
            self._injector = FaultInjector(
                plan=fault_plan, rank=0, restart=0,
                registry=obs.registry if obs.enabled else None)
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._closed = False
        log_dist(f"fleet router ready: {len(self.replicas)} replicas "
                 f"(policy={self.config.policy}, "
                 f"disagg={'on' if self.disagg else 'off'}, "
                 f"auto_revive={'on' if self.config.auto_revive else 'off'})")

    # -- client API --------------------------------------------------------
    @property
    def threaded(self) -> bool:
        return self._thread is not None

    @property
    def degraded_mode(self) -> int:
        return self._degraded

    def in_flight(self) -> int:
        with self._lock:
            return len(self._requests)

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
               eos_token_id: Optional[int] = None, tenant: str = "default",
               deadline_s: Optional[float] = None, seed: int = 0,
               n: int = 1):
        """Route and enqueue one prompt; returns a :class:`FleetHandle`
        (a list of ``n`` for parallel sampling, non-disaggregated fleets
        only — a fork's shared blocks cannot span a handoff). Raises
        :class:`Overloaded` (with ``retry_after_s``) when ``deadline_s``
        is infeasible at the current queue depth and measured TPOT —
        shedding at admission instead of admitting the request to die."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if n < 1:
            raise ValueError(f"submit(n={n}): need n >= 1")
        if n > 1 and self.disagg:
            raise NotImplementedError(
                "parallel sampling (n > 1) is per-replica COW sharing — "
                "not supported through a disaggregated fleet")
        with self._lock:
            pool = self.prefill_pool if self.disagg else self.replicas
            replica, reason, hint = self._pick(pool, prompt)
            if replica is None:
                raise FleetUnavailable("no alive replica to route to")
            mnt = (max_new_tokens if max_new_tokens is not None
                   else replica.engine.config.default_max_new_tokens)
            if self.config.admission_control and deadline_s is not None:
                # all n parallel samples decode their own budget on the
                # picked replica — the feasibility estimate must carry it
                est = self._estimate_completion_s(replica, mnt * n)
                if est is not None and est > deadline_s:
                    self._count_shed("deadline_infeasible")
                    obs = get_session()
                    obs.flight_event("req_terminal", event="shed",
                                     reason="deadline_infeasible",
                                     tenant=tenant)
                    rt = obs.reqtrace
                    if rt is not None:
                        # a shed submission still leaves a (retained)
                        # trace: shed is a tail-retention outlier
                        t = rt.start(tenant=tenant, t=self.clock(),
                                     attrs={"deadline_s": deadline_s})
                        rt.finish(t, "shed", t=self.clock(),
                                  reason="deadline_infeasible",
                                  estimated_s=round(est, 4))
                    raise Overloaded(
                        f"deadline {deadline_s:.3f}s is infeasible: "
                        f"estimated completion {est:.3f}s at current "
                        "queue depth and measured TPOT",
                        retry_after_s=max(est - deadline_s,
                                          self._tpot_estimate() or 0.0))
            handles = replica.engine.submit(
                prompt, max_new_tokens=max_new_tokens,
                temperature=temperature, top_k=top_k, top_p=top_p,
                eos_token_id=eos_token_id, tenant=tenant,
                deadline_s=deadline_s, seed=seed, n=n)
            # the affinity admission hint (and the routing-decision count)
            # commits only for requests that were actually admitted — a
            # shed submission or an engine QueueFull must not point later
            # prefix-sharers at a replica that never served it
            self._commit_affinity_hint(hint)
            self._count_decision(reason, replica)
            if n == 1:
                handles = [handles]
            rt = get_session().reqtrace
            if rt is not None:
                # the routing decision joins each request's causal chain
                # (the trace itself was minted by engine.submit)
                for h in handles:
                    if h._req.trace is not None:
                        rt.event(h._req.trace, "routed",
                                 t=self.clock(), policy=self.config.policy,
                                 reason=reason, replica=str(replica.index))
            # every admitted request weighs into the estimator's average
            self._mnt_obs.extend([mnt] * n)
            now = self.clock()
            out = []
            for i, h in enumerate(handles):
                fr = _FleetRequest(
                    fid=self._fid, prompt=prompt.copy(), seed=seed + i,
                    kwargs=dict(
                        max_new_tokens=h._req.max_new_tokens,
                        temperature=float(temperature), top_k=int(top_k),
                        top_p=float(top_p), eos_token_id=eos_token_id,
                        tenant=tenant),
                    arrival_s=now)
                if deadline_s is not None:
                    fr.deadline_abs = now + deadline_s
                self._fid += 1
                self.submitted_count += 1
                fr.bind(replica, h)
                fr.handle = FleetHandle(self, fr)
                self._requests[fr.fid] = fr
                self._by_engine[(replica.index, h._req.rid)] = fr.fid
                out.append(fr.handle)
            return out[0] if n == 1 else out

    def cancel(self, handle: FleetHandle) -> bool:
        with self._lock:
            fr = handle._fr
            if fr.done:
                return False
            self._drain_tokens(fr)
            if fr.u_handle.done:     # finished just before the cancel
                self._settle(fr)
                return False
            if fr.replica.alive:
                fr.replica.engine.cancel(fr.u_handle)
            else:
                # the engine-side finish cannot run on a dead replica —
                # the router closes the trace itself
                self._trace_finish_fr(fr, "cancelled")
            self._finish_fr(fr, F_CANCELLED)
            return True

    # -- the fleet iteration ----------------------------------------------
    def step(self) -> bool:
        """One fleet iteration: apply scheduled faults, heal (revive dead
        replicas whose backoff expired, release quarantine into
        probation), drain dead replicas (resubmitting their requests,
        retrying parked ones), run one scheduler iteration on every alive
        replica with work — measuring its wall time for the health
        verdicts — then judge health, stream out newly emitted tokens and
        update the overload ladder."""
        with self._lock:
            if self._injector is not None:
                self._injector.before_router_step(self._iterations,
                                                  self.kill_replica)
            # drain strictly before heal: a revival must never resurrect a
            # replica whose stranded requests were not yet resubmitted —
            # the drain guard keys on r.alive
            self._drain_dead()
            self._heal()
            self._retry_parked()
            progress = False
            for r in self.replicas:
                if not r.alive or not r.engine.in_flight():
                    continue
                t0 = self.clock()
                try:
                    progress |= r.step()
                except ReplicaDead:
                    pass
                except Exception:
                    # a replica whose iteration raises is as dead as a
                    # crashed process: drain + resubmit next pass
                    logger.exception(
                        f"fleet replica {r.index} iteration failed — "
                        "marking dead")
                    self.kill_replica(r.index, reason="step-exception")
                else:
                    dt = self.clock() - t0
                    if self._injector is not None:
                        dt += self._injector.slow_penalty(self._iterations,
                                                          r.index)
                    r.note_step_time(dt)
            self._judge_health()
            for fr in list(self._requests.values()):
                if fr.replica.alive:
                    self._drain_tokens(fr)
                    self._settle(fr)
            self._update_overload()
            self._publish()
            it = self._iterations
            self._iterations += 1
        # the live tuner's decision tick runs OUTSIDE the router lock: the
        # controller takes its own lock and may re-enter router APIs
        # (set_replica_role), so in-lock invocation would knot the lock
        # graph (tools/tpusync). Still after _update_overload — the tuner
        # recomposes the spec flag on top of this iteration's ladder
        # verdict.
        tuner = self._maybe_tuner()
        if tuner is not None:
            tuner.on_iteration(it)
        return progress

    def _maybe_tuner(self):
        """The live tuner, created lazily once the observability session
        carries the ``tune.controller`` gate (benches enable it after
        warmup). Disabled path: one cached-bool check per iteration —
        nothing allocated, nothing dispatched."""
        if self._tuner is None:
            from ...observability import get_session

            obs = get_session()
            if obs is not self._tuner_obs:
                # probe once per session object: configure_observability
                # always builds a new session, so identity tracks
                # enable/replace without re-probing every iteration
                with self._lock:
                    self._tuner_obs = obs
                    if obs.enabled:
                        from ...autotuning.livetuner import maybe_make_tuner

                        self._tuner = maybe_make_tuner(self, obs)
        return self._tuner

    def reset_latency_stats(self) -> None:
        """Drop the router's handoff/decision/resubmit tallies AND every
        replica's latency reservoirs — benches call this after warmup so
        the published numbers (incl. the warmup handoff, which JIT-compiles
        kv_export/kv_import inside its timed span) describe the measured
        load, not compilation. The admission-control TPOT/budget estimator
        resets too: a warmup request's per-token time spans the decode
        compile, and one compile-scale sample in a small reservoir would
        declare every real deadline infeasible (shed requests never
        finish, so nothing would ever correct the poisoned median)."""
        with self._lock:
            self._handoff_ms.clear()
            self._handoff_fallbacks = 0
            self._handoff_failures = 0
            self._decisions.clear()
            self._resubmit_count = 0
            self._shed_count = 0
            self._revive_iters.clear()
            self._tpot_obs.clear()
            self._mnt_obs.clear()
        for r in self.replicas:
            if r.alive:
                r.engine.reset_latency_stats()
                r.engine.sched.handoffs_out = 0

    # -- replica lifecycle -------------------------------------------------
    def kill_replica(self, index: int, reason: str = "fault") -> None:
        """Mark a replica dead (chaos harness / health verdicts). Its
        in-flight requests resubmit on the next ``step()``; with
        ``auto_revive`` it is rebuilt after a backed-off wait and
        re-admitted through probation."""
        if not 0 <= index < len(self.replicas):
            raise ValueError(
                f"kill_replica({index}): fleet has "
                f"{len(self.replicas)} replicas (indices 0.."
                f"{len(self.replicas) - 1})")
        with self._lock:
            r = self.replicas[index]
            if not r.alive:
                return
            r.kill(reason)
            r.death_iteration = self._iterations
            r.revive_at = self._iterations + (
                self.config.revive_after_iterations
                * 2 ** min(r.deaths - 1, 5))
            self._death_count += 1
            obs = get_session()
            if obs.enabled:
                obs.registry.counter(
                    "fleet_serving/replica_deaths",
                    help="replicas the router declared dead").inc(
                        reason=reason)
            logger.warning(f"fleet replica {index} dead ({reason}); "
                           "draining its requests")

    def quarantine_replica(self, index: int, reason: str) -> None:
        """Health-verdict remediation short of a kill: the replica keeps
        stepping its in-flight work but receives no new traffic until an
        exponentially backed-off window expires, after which it re-enters
        via probation. A replica past the circuit-breaker incident budget
        is retired instead."""
        with self._lock:
            r = self.replicas[index]
            if not r.alive or r.quarantined:
                return
            if r.incidents + 1 > self.config.breaker_incidents:
                self._retire(r, f"breaker({reason})")
                return
            backoff = (self.config.quarantine_iterations
                       * 2 ** min(r.quarantines, 5))
            r.quarantine(reason, self._iterations + backoff)
            self._quarantine_count += 1
            obs = get_session()
            if obs.enabled:
                obs.registry.counter(
                    "fleet_serving/quarantines",
                    help="slow/SLO-breaching replicas quarantined (alive, "
                         "no new traffic)").inc(reason=reason)
            logger.warning(
                f"fleet replica {index} quarantined ({reason}) for "
                f"{backoff} iterations (incident "
                f"{r.incidents}/{self.config.breaker_incidents})")

    def revive_replica(self, index: int) -> bool:
        """Rebuild a dead replica's engine (fleet-shared weights + a
        surviving replica's compiled program set — one arena allocation,
        zero compiles) and re-admit it ON PROBATION. Returns False when
        the replica is already alive; raises :class:`ReplicaRetired` past
        the circuit breaker."""
        with self._lock:
            r = self.replicas[index]
            if r.alive:
                return False
            if r.retired:
                raise ReplicaRetired(
                    f"replica {index} is retired (circuit breaker)")
            if not r.drained:
                # a kill between iterations (or a caller racing the step
                # loop) may not have been drained yet — resubmit its
                # stranded requests BEFORE the engine is replaced, or they
                # would stay bound to the discarded incarnation forever
                self._drain_replica(r)
            donor = next((o for o in self.replicas
                          if o.alive and o is not r), None)
            engine = r.rebuild(donor)
            self._replaced_engines.append(r.engine)
            r.revive(engine, self.config.probation_requests)
            engine.trace_tag = str(r.index)   # the incarnation keeps the
            #   replica's identity on traces and serve_goodput gauges
            engine._fleet_managed = True
            # a fresh incarnation boots untuned; the live tuner's next
            # decision tick re-pushes its owned knobs fleet-wide
            # conservative: even with grafted programs, the incarnation's
            # first measured steps are not representative
            r.warmup_left = self.config.health_warmup_steps
            engine.spec_suspended = self._degraded >= DEGRADED_NO_SPEC
            if self.disagg and r.role == ROLE_PREFILL:
                engine.on_prefill_complete = (
                    lambda req, _r=r: self._handoff_from(_r, req))
            self._revival_count += 1
            death_it = getattr(r, "death_iteration", self._iterations)
            self._revive_iters.append(self._iterations - death_it)
            obs = get_session()
            if obs.enabled:
                obs.registry.counter(
                    "fleet_serving/revivals",
                    help="dead replicas rebuilt (shared weights + compiled "
                         "programs) and re-admitted via probation").inc()
            logger.warning(
                f"fleet replica {index} revived (probation: "
                f"{r.probation_left} clean requests to graduate)")
            return True

    def _retire(self, r: Replica, reason: str) -> None:
        """Circuit breaker tripped: permanently out of the fleet."""
        was_alive = r.alive
        r.retire()
        r.death_reason = reason
        obs = get_session()
        if obs.enabled:
            obs.registry.counter(
                "fleet_serving/replica_retirements",
                help="replicas past the circuit-breaker incident budget — "
                     "permanently removed, never revived").inc()
            if was_alive:
                obs.registry.counter(
                    "fleet_serving/replica_deaths",
                    help="replicas the router declared dead").inc(
                        reason="breaker")
        if was_alive:
            self._death_count += 1
        logger.error(
            f"fleet replica {r.index} RETIRED ({reason}): "
            f"{r.incidents} incidents > breaker budget "
            f"{self.config.breaker_incidents}")

    def _heal(self) -> None:
        """The remediation half of the loop, run at the top of every
        iteration: expired quarantines re-enter via probation; dead
        replicas past their revival backoff are rebuilt (or retired when
        the breaker budget is spent)."""
        for r in self.replicas:
            if r.retired:
                continue
            if r.quarantined and self._iterations >= r.quarantine_until:
                r.quarantined = False
                r.quarantine_reason = None
                r.probation_left = self.config.probation_requests
                # the window sampled DURING quarantine includes the very
                # evidence that convicted it — probation is judged on
                # fresh samples, or exit would instantly re-convict
                r.step_times.clear()
                logger.warning(
                    f"fleet replica {r.index} quarantine expired — on "
                    f"probation ({r.probation_left} clean requests)")
            if (not r.alive and self.config.auto_revive
                    and self._iterations >= r.revive_at):
                # revival itself is NOT an incident: retire only when the
                # budget is already exceeded (matching quarantine_replica,
                # whose +1 is the incident being added, and the manual
                # revive_replica path)
                if r.incidents > self.config.breaker_incidents:
                    self._retire(r, "breaker(revive)")
                    continue
                try:
                    self.revive_replica(r.index)
                except Exception:
                    logger.exception(
                        f"fleet replica {r.index} revival failed — "
                        "backing off")
                    r.revive_at = self._iterations + (
                        self.config.revive_after_iterations
                        * 2 ** min(r.deaths, 5))

    def _judge_health(self) -> None:
        """Step-time verdicts from the windows the iteration just fed: a
        replica whose rolling median exceeds the absolute SLO, or
        ``slow_factor ×`` the median of the OTHER candidates' medians, is
        quarantined. (TTFT-SLO breaches are judged where TTFT is stamped,
        in ``_drain_tokens``.)"""
        cands = [r for r in self.replicas
                 if r.alive and not r.quarantined]
        meds = {r.index: r.step_time_median() for r in cands}
        for r in cands:
            med = meds[r.index]
            if med is None:
                continue
            slo = self.config.step_time_slo_s
            if slo > 0 and med > slo:
                self._count_verdict("step_slo")
                self.quarantine_replica(r.index, "step_slo")
                continue
            # the relative verdict needs an absolute floor: at sub-floor
            # step times, scheduler noise makes any ratio meaningless
            if med < self.config.slow_min_step_s:
                continue
            others = [m for i, m in meds.items()
                      if i != r.index and m is not None]
            if others and med > self.config.slow_factor \
                    * statistics.median(others):
                self._count_verdict("slow")
                self.quarantine_replica(r.index, "slow")

    def _count_verdict(self, verdict: str) -> None:
        obs = get_session()
        if obs.enabled:
            obs.registry.counter(
                "fleet_serving/health_verdicts",
                help="non-healthy health verdicts by kind").inc(
                    verdict=verdict)

    # -- internals ---------------------------------------------------------
    def _trace_finish_fr(self, fr: _FleetRequest, state: str,
                         **attrs: Any) -> None:
        """Router-level terminal for a trace whose engine binding cannot
        record it (dead replica, shed-from-queue). Idempotent with the
        engine's own finish — the first terminal state wins."""
        trace = (getattr(fr.u_req, "trace", None)
                 if fr.u_req is not None else None)
        if trace is None:
            return
        rt = get_session().reqtrace
        if rt is not None:
            rt.finish(trace, state, t=self.clock(),
                      ttft_s=(fr.first_token_s - fr.arrival_s
                              if fr.first_token_s is not None else None),
                      **attrs)

    def _count_decision(self, reason: str, replica: Replica) -> None:
        self._decisions[(self.config.policy, reason)] += 1
        obs = get_session()
        if obs.enabled:
            obs.registry.counter(
                "fleet_serving/routing_decisions",
                help="requests routed, by policy decision reason").inc(
                    policy=self.config.policy, reason=reason,
                    replica=str(replica.index))

    def _count_shed(self, reason: str) -> None:
        self._shed_count += 1
        obs = get_session()
        if obs.enabled:
            obs.registry.counter(
                "fleet_serving/shed",
                help="requests shed under overload (admission "
                     "deadline-infeasibility or the degraded ladder)").inc(
                    reason=reason)

    def _tpot_estimate(self) -> Optional[float]:
        """Measured fleet per-token seconds (median over recent finished
        requests) — None until the first finished request with >= 2
        tokens reports one."""
        if not self._tpot_obs:
            return None
        return statistics.median(self._tpot_obs)

    def _estimate_completion_s(self, replica: Replica,
                               max_new_tokens: int) -> Optional[float]:
        """The admission-control feasibility model, deliberately simple
        and documented: completion ≈ TPOT × (own token budget + the
        target replica's queued backlog × mean submitted budget). None
        (no TPOT data yet) admits — the estimator only ever sheds on
        MEASURED evidence."""
        tpot = self._tpot_estimate()
        if tpot is None:
            return None
        h = replica.health()
        avg_mnt = (statistics.fmean(self._mnt_obs)
                   if self._mnt_obs else float(max_new_tokens))
        return ((1.0 + self.admission_pad)
                * tpot * (max_new_tokens + h.queue_depth * avg_mnt))

    def set_replica_role(self, index: int, role: str) -> None:
        """Reassign a replica's pool membership at runtime — the live
        tuner's prefill:decode ratio knob. Data-plane only: roles gate
        which pool ``_pick`` routes NEW work to; in-flight requests finish
        where they sit. Pure-prefill handoff wiring is fixed at
        construction, so runtime moves are restricted to the
        DECODE <-> MIXED edge (a mixed replica decodes its own prefills in
        place — no handoff seam to rewire), and the fleet must keep at
        least one prefill-capable and one decode-capable replica."""
        allowed = (ROLE_DECODE, ROLE_MIXED)
        with self._lock:
            r = self.replicas[index]
            if role == r.role:
                return
            if r.role not in allowed or role not in allowed:
                raise ValueError(
                    f"set_replica_role({index}, {role!r}): runtime role "
                    "moves are decode<->mixed only (prefill handoff "
                    "wiring is fixed at construction)")
            prev = r.role
            r.role = role
            pp = [x for x in self.replicas
                  if x.role in (ROLE_PREFILL, ROLE_MIXED)]
            dp = [x for x in self.replicas
                  if x.role in (ROLE_DECODE, ROLE_MIXED)]
            if not pp or not dp:
                r.role = prev
                raise ValueError(
                    f"set_replica_role({index}, {role!r}) would leave the "
                    "fleet without a prefill- or decode-capable replica")
            self.prefill_pool, self.decode_pool = pp, dp
            log_dist(f"fleet replica {index} role: {prev} -> {role}")

    def _affinity_key(self, prompt: np.ndarray) -> Optional[bytes]:
        if int(prompt.size) < self._block_size:
            return None
        import hashlib

        return hashlib.blake2b(
            np.ascontiguousarray(prompt[:self._block_size],
                                 np.int32).tobytes(),
            digest_size=16).digest()

    def _routable(self, r: Replica) -> bool:
        """May this replica receive NEW traffic right now? Quarantine
        blocks it outright; probation caps its share of the fleet's
        in-flight requests at ``probation_share`` (floor of one — a
        probation replica must be able to prove itself)."""
        if not r.routable():
            return False
        if r.probation_left > 0:
            cap = max(1, int(self.config.probation_share
                             * max(len(self._requests), 1)))
            if r.engine.in_flight() >= cap:
                return False
        return True

    def _pick(self, pool: List[Replica], prompt: np.ndarray):
        """(replica, decision reason, deferred affinity hint) under the
        configured policy. The eligibility ladder degrades gracefully:
        routable members of the pool, then routable members of the whole
        fleet, then ANY alive replica (quarantined/probation-capped
        included — live beats pure). The affinity hint is RETURNED, not
        written — the caller commits it only once the request is actually
        admitted (an admission-shed submission must not point later
        prefix-sharers at a replica that never served it)."""
        alive = [r for r in pool if self._routable(r)]
        degraded = not alive
        if degraded:
            alive = ([r for r in self.replicas if self._routable(r)]
                     or [r for r in self.replicas if r.alive])
        if not alive:
            return None, "no_replica", None
        policy = self.config.policy
        health = {r.index: r.health() for r in alive}
        reason = policy
        hint = None
        if policy == "round_robin":
            pick = alive[self._rr % len(alive)]
            self._rr += 1
        elif policy == "least_queue":
            pick = min(alive, key=lambda r: (health[r.index].in_flight,
                                             r.index))
        elif policy == "kv_occupancy":
            pick = min(alive, key=lambda r: health[r.index].load_key)
        else:   # affinity
            key = self._affinity_key(prompt)
            pick = None
            if self._degraded >= DEGRADED_NO_AFFINITY:
                # ladder rung 2: stop following warm hints — spilling to
                # the least-loaded replica beats locality under pressure
                reason = "degraded_spill"
            elif key is None:
                reason = "affinity_short"
            else:
                warm = self._affinity.get(key)
                if warm is None:
                    reason = "affinity_cold"
                else:
                    cand = self.replicas[warm]
                    if cand not in alive:
                        reason = "affinity_dead"
                    elif (health[cand.index].arena_occupancy
                          > self.config.affinity_overload):
                        reason = "affinity_overload"
                    else:
                        pick, reason = cand, "affinity_warm"
            if pick is None:
                pick = min(alive, key=lambda r: health[r.index].load_key)
            if key is not None and self._degraded < DEGRADED_NO_AFFINITY:
                hint = (key, pick.index)
        if degraded:
            reason += "_degraded"
        return pick, reason, hint

    def _commit_affinity_hint(self, hint) -> None:
        """The admission hint: later requests with this prefix follow the
        replica whose cache is (about to be) warm."""
        if hint is None:
            return
        key, index = hint
        self._affinity[key] = index
        self._affinity.move_to_end(key)
        while len(self._affinity) > 4096:
            self._affinity.popitem(last=False)

    def _drain_tokens(self, fr: _FleetRequest) -> None:
        """Move newly emitted tokens from the bound engine handle into the
        fleet handle (and stamp the fleet-level TTFT, judging the TTFT SLO
        against the serving replica)."""
        toks = fr.u_handle.tokens
        new = toks[fr.consumed:]
        if not new:
            return
        if fr.first_token_s is None:
            fr.first_token_s = self.clock()
            ttft = fr.first_token_s - fr.arrival_s
            obs = get_session()
            if obs.enabled:
                obs.registry.histogram(
                    "fleet_serving/ttft_ms",
                    help="fleet submit → first streamed token, "
                         "wall ms").observe(ttft * 1e3)
            slo = self.config.ttft_slo_s
            if slo > 0 and ttft > slo and fr.resubmits == 0 \
                    and fr.handoffs == 0:
                # a resubmitted request's TTFT indicts the DEAD replica,
                # not the survivor that picked up the recompute — and a
                # handed-off one's indicts the prefill side, never the
                # decode replica it is now bound to
                self._ttft_breaches += 1
                if obs.enabled:
                    obs.registry.counter(
                        "fleet_serving/health_ttft_breaches",
                        help="first tokens that missed the fleet TTFT "
                             "SLO").inc()
                self._count_verdict("ttft_slo")
                if self._degraded == DEGRADED_NONE:
                    # under declared overload a late first token indicts
                    # the FLEET, not the serving replica — quarantining
                    # (and ratcheting its breaker) would retire healthy
                    # capacity exactly when it is scarcest
                    self.quarantine_replica(fr.replica.index, "ttft_slo")
        for t in new:
            fr.handle._push(t)
        fr.consumed = len(toks)

    def _settle(self, fr: _FleetRequest) -> None:
        """Terminal-state propagation for the CURRENT binding."""
        # the engine handle's end, not the scheduler's state: it turns with
        # the last token delivered, which _drain_tokens has then seen
        if fr.done or not fr.u_handle.done:
            return
        if fr.u_req.state == FINISHED:
            state = F_FINISHED
        elif fr.u_req.state == DEADLINE_EXCEEDED:
            state = F_DEADLINE
        else:
            state = F_CANCELLED
        self._finish_fr(fr, state)

    def _finish_fr(self, fr: _FleetRequest, state: str) -> None:
        fr.state = state
        fr.finish_s = self.clock()
        self._requests.pop(fr.fid, None)
        if fr.replica is not None and fr.u_req is not None:
            self._by_engine.pop((fr.replica.index, fr.u_req.rid), None)
        if state == F_FINISHED:
            self.finished_count += 1
            tpot = fr.handle.tpot_s if fr.handle is not None else None
            if tpot is not None:
                self._tpot_obs.append(tpot)
            self._credit_probation(fr.replica)
        elif state == F_CANCELLED:
            self.cancelled_count += 1
        elif state == F_DEADLINE:
            self.deadline_exceeded_count += 1
        elif state == F_SHED:
            self.shed_count_total += 1
        fr.handle._wake()

    def _credit_probation(self, r: Optional[Replica]) -> None:
        """Clean service earns probation credit; graduation restores full
        routing weight. Called for a request FINISHING on the replica —
        and for a completed prefill + successful handoff (in a
        disaggregated fleet every request rebinds to a decode replica, so
        a probation PREFILL replica's service would otherwise never
        count and it could never graduate)."""
        if r is None or not r.alive or r.probation_left <= 0:
            return
        r.probation_left -= 1
        if r.probation_left == 0:
            self._graduation_count += 1
            obs = get_session()
            if obs.enabled:
                obs.registry.counter(
                    "fleet_serving/probation_graduations",
                    help="replicas that served their probation cleanly "
                         "and regained full routing weight").inc()
            logger.warning(f"fleet replica {r.index} graduated "
                           "probation — full routing weight")

    def _drain_dead(self) -> None:
        """Resubmit every request stranded on a dead replica: recompute
        from original prompt + streamed tokens on a surviving replica —
        the same bit-exactness contract as per-engine preemption. The
        resubmission budget is spent HERE (one unit per death), not on
        QueueFull retries."""
        for r in self.replicas:
            if r.alive or r.drained:
                continue
            self._drain_replica(r)

    def _drain_replica(self, r: Replica) -> None:
        r.drained = True
        # parked requests are still bound to the replica they were
        # ORIGINALLY drained from; a later death of that (revived) replica
        # must not budget them a second time or race _retry_parked into a
        # duplicate resubmission
        victims = [fr for fr in self._requests.values()
                   if fr.replica is r and not fr.done
                   and fr.fid not in self._parked]
        for fr in victims:
            fr.resubmits += 1
            if fr.resubmits > self.config.max_resubmits:
                logger.error(
                    f"fleet request {fr.fid}: resubmission budget "
                    f"({self.config.max_resubmits}) exhausted — "
                    "cancelling")
                self._trace_finish_fr(fr, "cancelled",
                                      reason="resubmit_budget")
                self._finish_fr(fr, F_CANCELLED)
                continue
            self._try_resubmit(fr)

    def _retry_parked(self) -> None:
        """Re-attempt resubmissions that found every survivor momentarily
        full — queue pressure drains as survivors step, so later
        iterations succeed without touching the death budget."""
        if not self._parked:
            return
        parked, self._parked = self._parked, []
        now = self.clock()
        for fid in parked:
            fr = self._requests.get(fid)
            if fr is None or fr.done:
                continue
            if fr.deadline_abs is not None and now > fr.deadline_abs:
                # nobody engine-side can expire a parked request (its
                # binding is the dead replica) — the router must
                self._trace_finish_fr(fr, "deadline_exceeded",
                                      reason="parked_past_deadline")
                self._finish_fr(fr, F_DEADLINE)
                obs = get_session()
                obs.flight_event("req_terminal", event="deadline_exceeded",
                                 fid=fr.fid, reason="parked_past_deadline")
                if obs.enabled:
                    obs.registry.counter(
                        "serving/requests_deadline_exceeded",
                        help="requests terminated at an iteration "
                             "boundary after their deadline passed").inc(
                                 tenant=fr.kwargs.get("tenant", "default"))
                continue
            self._try_resubmit(fr)

    def _try_resubmit(self, fr: _FleetRequest) -> None:
        """Bind ``fr`` to a surviving replica in recompute mode; parks it
        for later iterations when every candidate is QueueFull (a full
        queue is congestion, not a death — it must not burn the
        ``max_resubmits`` budget). Cancels only when NO replica is alive."""
        obs = get_session()
        tokens = fr.handle.tokens      # everything streamed IS recoverable
        # phase-matched pool preference: a request already decoding goes
        # back to the decode pool, one still prefilling to the prefill pool
        pool = ((self.decode_pool if tokens else self.prefill_pool)
                if self.disagg else self.replicas)
        deadline_s = (max(fr.deadline_abs - self.clock(), 0.0)
                      if fr.deadline_abs is not None else None)
        cands = ([r for r in pool if self._routable(r)]
                 or [r for r in self.replicas if r.alive])
        if not cands:
            logger.error(f"fleet request {fr.fid}: no alive replica for "
                         "the resubmission — cancelling")
            self._trace_finish_fr(fr, "cancelled", reason="fleet_dead")
            self._finish_fr(fr, F_CANCELLED)
            return
        # the trace survives the dead binding: the SAME trace_id continues
        # on the survivor at attempt + 1 (the resubmission causal link)
        trace = (getattr(fr.u_req, "trace", None)
                 if fr.u_req is not None else None)
        for target in sorted(cands, key=lambda r: r.health().load_key):
            try:
                h2 = target.engine.submit_recovered(
                    fr.prompt, tokens, seed=fr.seed,
                    deadline_s=deadline_s, **fr.kwargs)
            except QueueFull:
                continue
            self._by_engine.pop((fr.replica.index, fr.u_req.rid), None)
            dead_index = fr.replica.index
            fr.bind(target, h2)
            if trace is not None:
                h2._req.trace = trace
                rt = obs.reqtrace
                if rt is not None:
                    rt.resubmitted(trace, self.clock(),
                                   replica=target.index)
            obs.flight_event("req_terminal", event="resubmit", fid=fr.fid,
                             from_replica=dead_index,
                             to_replica=target.index,
                             trace_id=(trace.trace_id
                                       if trace is not None else None))
            if fr.fid in self._parked:
                self._parked.remove(fr.fid)
            # streamed tokens live engine-side in req.generated but were
            # never pushed to the NEW handle — nothing to re-drain
            self._by_engine[(target.index, h2._req.rid)] = fr.fid
            self._resubmit_count += 1
            self._count_decision("resubmit", target)
            if obs.enabled:
                obs.registry.counter(
                    "fleet_serving/resubmits",
                    help="requests resubmitted after a replica "
                         "death").inc()
            return
        # every survivor momentarily full: park and retry next iteration
        if fr.fid not in self._parked:
            self._parked.append(fr.fid)
            logger.warning(
                f"fleet request {fr.fid}: every surviving replica is "
                "full — parking the resubmission for later iterations")

    # -- overload control: the degraded-mode ladder ------------------------
    def _update_overload(self) -> None:
        """Walk the degraded ladder: ``overload_up_iterations`` of
        sustained pressure (mean alive arena occupancy / fleet queue
        depth) per rung up, ``overload_down_iterations`` of calm per rung
        down — hysteresis keeps the fleet from oscillating. Rung 3 sheds
        one queued victim per iteration while it holds."""
        alive = [r for r in self.replicas if r.alive]
        if not alive:
            return
        # pressure counts only IRRECLAIMABLE occupancy: unpinned
        # prefix-cache blocks evict on demand, and a warm cache
        # deliberately fills the pool — counting it would park a
        # long-running idle fleet at rung 3 forever
        def _occ(r):
            alloc, cache = r.engine.alloc, r.engine.prefix
            reclaimable = cache.reclaimable_blocks if cache else 0
            return ((alloc.blocks_in_use - reclaimable)
                    / max(alloc.capacity, 1))

        occ = statistics.fmean(_occ(r) for r in alive)
        qd = sum(r.engine.sched.queue_depth() for r in alive)
        pressure = occ >= self.config.overload_occupancy or (
            self.config.overload_queue_depth > 0
            and qd >= self.config.overload_queue_depth)
        if pressure:
            self._pressure_streak += 1
            self._calm_streak = 0
            if (self._pressure_streak
                    >= self.config.overload_up_iterations
                    and self._degraded < DEGRADED_SHED):
                self._set_degraded(self._degraded + 1)
                self._pressure_streak = 0
        else:
            self._calm_streak += 1
            self._pressure_streak = 0
            if (self._calm_streak >= self.config.overload_down_iterations
                    and self._degraded > DEGRADED_NONE):
                self._set_degraded(self._degraded - 1)
                self._calm_streak = 0
        if self._degraded >= DEGRADED_SHED:
            self._shed_one()

    def _set_degraded(self, rung: int) -> None:
        direction = "up" if rung > self._degraded else "down"
        self._degraded = rung
        for r in self.replicas:
            if r.alive:
                # rung 1: speculation must never cost anyone blocks under
                # pressure — suspend it fleet-wide (bit-exact: the verify
                # path with zero proposals IS the plain decode)
                r.engine.spec_suspended = rung >= DEGRADED_NO_SPEC
        obs = get_session()
        if obs.enabled:
            obs.registry.counter(
                "fleet_serving/degraded_transitions",
                help="degraded-mode ladder transitions").inc(
                    direction=direction, rung=str(rung))
        logger.warning(f"fleet degraded-mode ladder: rung {rung} "
                       f"({direction})")

    def _shed_one(self) -> None:
        """Rung 3: shed the lowest-priority queued (unadmitted) request —
        no-deadline work first, then latest deadline — so the work least
        likely to matter soonest pays for the overload."""
        cands = [fr for fr in self._requests.values()
                 if not fr.done and fr.u_req is not None
                 and fr.u_req.state == QUEUED]
        if not cands:
            return
        victim = min(cands, key=lambda fr: (
            fr.deadline_abs is not None,
            -(fr.deadline_abs or 0.0), -fr.fid))
        self._drain_tokens(victim)
        # the shed terminal must land BEFORE the engine cancel (the first
        # terminal state wins — this one is the truthful one)
        self._trace_finish_fr(victim, "shed", reason="degraded")
        get_session().flight_event(
            "req_terminal", event="shed", reason="degraded",
            fid=victim.fid, rung=self._degraded)
        if victim.replica.alive:
            victim.replica.engine.cancel(victim.u_handle)
        tpot = self._tpot_estimate() or 0.0
        victim.retry_after_s = max(
            tpot * (statistics.fmean(self._mnt_obs)
                    if self._mnt_obs else 1.0), 0.001)
        self._count_shed("degraded")
        self._finish_fr(victim, F_SHED)
        logger.warning(f"fleet request {victim.fid} shed (degraded rung "
                       f"{self._degraded}, retry_after_s="
                       f"{victim.retry_after_s:.3f})")

    # -- disaggregation: the prefill-complete hook -------------------------
    def _handoff_from(self, src: Replica, req) -> None:
        """Called by a prefill replica (engine lock held, inside this
        router's ``step``) the moment a request's last prefill chunk
        completed: move its KV blocks to a decode replica and rebind the
        fleet request there. A transfer that FAILS mid-flight (chaos
        ``handoff_fail``, kv_import raising) retries on up to
        ``handoff_retries`` other decode replicas; failure — like a dry
        decode pool — falls back to decoding in place. Destination blocks
        of a failed transfer are freed inside the transport; source
        blocks are released exactly once, on success only."""
        # Re-enter the router lock explicitly (RLock: free on the normal
        # path, where step() already holds it). The handoff mutates router
        # state — bind(), handoff tallies, probation credit — and must not
        # rely on every engine step being driven from under step()'s lock.
        with self._lock:
            fid = self._by_engine.get((src.index, req.rid))
            fr = self._requests.get(fid) if fid is not None else None
            if fr is None or fr.done:
                return
            cands = sorted(
                (r for r in self.decode_pool
                 if self._routable(r) and r.engine is not src.engine),
                key=lambda r: r.health().load_key)
            t0 = self.clock()
            obs = get_session()
            # arm the injected transfer failure ONCE for this handoff
            # event; the finally disarms an armament the seam never
            # reached (every candidate pool dry), or it would leak into a
            # later, unplanned handoff and break the deterministic-plan
            # contract
            injected = (self._injector is not None
                        and self._injector.take_handoff_fail(
                            self._iterations))
            if injected:
                self.handoff.inject_fail_next += 1
            try:
                self._handoff_attempts(src, req, fr, cands, t0, obs)
            finally:
                if injected and self.handoff.inject_fail_next > 0:
                    self.handoff.inject_fail_next -= 1

    def _handoff_attempts(self, src: Replica, req, fr: _FleetRequest,
                          cands: List[Replica], t0: float, obs) -> None:
        failures = 0
        rt = obs.reqtrace
        for dst in cands:
            try:
                dst_ids = self.handoff.transfer(src.engine, dst.engine,
                                                req.blocks, trace=req.trace)
            except Exception:
                # mid-flight transfer loss: the transport already freed
                # the destination blocks; the source request is untouched
                # and can retry or decode in place
                failures += 1
                self._handoff_failures += 1
                if obs.enabled:
                    obs.registry.counter(
                        "fleet_serving/handoff_failures",
                        help="KV handoff transfers that failed mid-flight "
                             "(retried once, then decoded in place)").inc()
                obs.flight_event(
                    "req_terminal", event="handoff_fail", fid=fr.fid,
                    src=src.index, dst=dst.index,
                    trace_id=(req.trace.trace_id
                              if req.trace is not None else None))
                if rt is not None and req.trace is not None:
                    rt.event(req.trace, "handoff_fail", t=self.clock(),
                             src=str(src.index), dst=str(dst.index))
                logger.warning(
                    f"fleet request {fr.fid}: KV handoff to replica "
                    f"{dst.index} failed mid-transfer "
                    f"(attempt {failures})", exc_info=True)
                if failures > self.config.handoff_retries:
                    break
                continue
            if dst_ids is None:
                continue            # decode pool dry on this replica
            # the remaining deadline crosses the handoff (like _resubmit's)
            # or the adopted request would sort last in the decode pool's
            # EDF queue behind every deadline-bearing arrival
            deadline_s = (max(fr.deadline_abs - self.clock(), 0.0)
                          if fr.deadline_abs is not None else None)
            try:
                h2 = dst.engine.adopt_prefilled(
                    prompt=req.prompt[:req.n_prompt],
                    n_prompt=req.n_prompt, generated=req.generated,
                    pending_token=req.pending_token, length=req.length,
                    blocks=dst_ids, seed=req.seed, sampling=req.sampling,
                    max_new_tokens=req.max_new_tokens,
                    eos_token_id=req.eos_token_id, tenant=req.tenant,
                    deadline_s=deadline_s)
            except QueueFull:
                dst.engine.alloc.free(dst_ids)
                continue
            # tokens emitted on the source (the prefill-completion first
            # token) must reach the fleet handle BEFORE the rebinding
            self._drain_tokens(fr)
            self._by_engine.pop((src.index, req.rid), None)
            fr.bind(dst, h2)
            fr.handoffs += 1
            self._by_engine[(dst.index, h2._req.rid)] = fr.fid
            if req.trace is not None:
                # the trace context rides the handoff seam: the SAME
                # trace_id continues on the destination replica
                h2._req.trace = req.trace
                if rt is not None:
                    rt.handoff_adopted(req.trace, self.clock(),
                                       src=src.index, dst=dst.index)
            src.engine.release_for_handoff(req)
            # a completed prefill handed off cleanly IS the prefill
            # replica's unit of service — its probation credit cannot
            # come from completions (those land on the decode pool)
            self._credit_probation(src)
            ms = (self.clock() - t0) * 1e3
            self._handoff_ms.append(ms)
            if src.engine._serve_acct is not None:
                # the transfer ran inside the SOURCE replica's iteration
                # (the on_prefill_complete hook) — bucket it as handoff
                # there so its scheduling_host remainder stays honest
                src.engine._serve_acct.note_phase("handoff", ms / 1e3)
            self._count_decision("disagg_decode", dst)
            if obs.enabled:
                obs.registry.counter(
                    "fleet_serving/handoffs",
                    help="prefill→decode KV block handoffs").inc()
                obs.registry.histogram(
                    "fleet_serving/handoff_ms",
                    help="KV export+import+adopt wall ms").observe(ms)
            return
        # nobody could take it: the request decodes on the prefill replica
        self._handoff_fallbacks += 1
        if obs.enabled:
            obs.registry.counter(
                "fleet_serving/handoff_fallbacks",
                help="handoffs the decode pool refused (request decodes "
                     "on its prefill replica)").inc()

    # -- telemetry ---------------------------------------------------------
    def _publish(self) -> None:
        obs = get_session()
        if not obs.enabled:
            return
        reg = obs.registry
        alive = 0
        for r in self.replicas:
            h = r.health()
            alive += int(h.alive)
            lbl = {"replica": str(r.index), "role": r.role}
            reg.gauge("fleet_serving/queue_depth",
                      help="per-replica admission queue depth").set(
                          h.queue_depth, **lbl)
            reg.gauge("fleet_serving/in_flight",
                      help="per-replica in-flight requests").set(
                          h.in_flight, **lbl)
            reg.gauge("fleet_serving/arena_occupancy",
                      help="per-replica allocated arena fraction").set(
                          round(h.arena_occupancy, 4), **lbl)
            reg.gauge("fleet_serving/decode_batch_occupancy",
                      help="per-replica decoding rows / max_seqs").set(
                          round(h.decode_batch_occupancy, 4), **lbl)
            reg.gauge("fleet_serving/kv_blocks_in_use",
                      help="per-replica allocated arena blocks").set(
                          h.kv_blocks_in_use, **lbl)
            # 0=dead, 1=serving, 2=quarantined, 3=probation, 4=retired
            state = (4 if r.retired else 0 if not r.alive
                     else 2 if r.quarantined
                     else 3 if r.probation_left > 0 else 1)
            reg.gauge("fleet_serving/health_state",
                      help="replica lifecycle state: 0=dead 1=serving "
                           "2=quarantined 3=probation 4=retired").set(
                          state, **lbl)
            if h.step_time_median_s is not None:
                reg.gauge("fleet_serving/health_step_time_ms",
                          help="per-replica rolling median iteration wall "
                               "ms (the slow-verdict input)").set(
                              round(h.step_time_median_s * 1e3, 3), **lbl)
        reg.gauge("fleet_serving/replicas_alive",
                  help="replicas the router considers serving").set(alive)
        reg.gauge("fleet_serving/requests_in_flight",
                  help="fleet requests not yet terminal").set(
                      len(self._requests))
        reg.gauge("fleet_serving/degraded_mode",
                  help="overload ladder rung: 0=normal 1=no-speculation "
                       "2=no-affinity 3=shedding").set(self._degraded)
        # fleet-wide serving goodput: emitted tokens per device-second
        # (each replica's accounted wall is one device-second stream)
        accts = [r.engine._serve_acct for r in self.replicas
                 if r.alive and r.engine._serve_acct is not None]
        if accts:
            tots = [a.totals() for a in accts]
            wall = sum(t["wall_s"] for t in tots)
            if wall > 0:
                reg.gauge(
                    "serve_goodput/fleet_tokens_per_device_sec",
                    help="fleet emitted tokens / summed per-replica "
                         "accounted wall seconds").set(
                        sum(t["tokens"] for t in tots) / wall)

    def publish_latency_gauges(self) -> None:
        """Close-time percentile gauges over the handoff reservoir — the
        ``report`` CLI's ``== fleet serving ==`` latency inputs."""
        obs = get_session()
        if not obs.enabled or not self._handoff_ms:
            return
        from ..api import _percentile

        xs = list(self._handoff_ms)
        obs.registry.gauge("fleet_serving/handoff_p50_ms").set(
            _percentile(xs, 0.50))
        obs.registry.gauge("fleet_serving/handoff_p99_ms").set(
            _percentile(xs, 0.99))

    # -- drivers -----------------------------------------------------------
    def run(self, max_steps: Optional[int] = None) -> int:
        """Step until every fleet request is terminal (tests/benches)."""
        steps = 0
        starved = 0
        while self.in_flight():
            progress = self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
            if progress:
                starved = 0
            else:
                starved += 1
                if starved > self._starvation_limit:
                    raise RuntimeError(
                        "fleet stalled: no replica can make progress "
                        f"({self.in_flight()} fleet requests in flight)")
        return steps

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._drive,
                                        name="dstpu-fleet", daemon=True)
        self._thread.start()

    def _drive(self) -> None:
        while not self._stop.is_set():
            try:
                if self.in_flight():
                    self.step()
                else:
                    self._stop.wait(0.002)
            except Exception:
                logger.exception("fleet driver step failed")
                get_session().crash_dump("fleet-step-exception")
                self._stop.wait(0.05)

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
            self._thread = None

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.stop()
        if self._tuner is not None:
            self._tuner.finalize()     # recommendations artifact
        self.publish_latency_gauges()
        # pool the replicas' latency reservoirs BEFORE their close()
        # publishes: each ServingEngine.close() sets the same unlabeled
        # serving/ttft_p50_ms / tpot / tokens_per_sec gauges, so the last
        # replica closed would otherwise stand in for the whole fleet
        ttft, tpot, tokens_out, wall = [], [], 0, 0.0
        engines = ([r.engine for r in self.replicas]
                   + self._replaced_engines)   # revivals must not drop
        #   the dead incarnations' served-request telemetry
        for eng in engines:
            ttft.extend(eng._ttft_samples)
            tpot.extend(eng._tpot_samples)
            tokens_out += eng._tokens_out
            wall = max(wall, eng.clock() - eng._started_s)
            try:
                eng.close()
            except Exception:
                logger.warning("fleet replica engine close failed",
                               exc_info=True)
        obs = get_session()
        if obs.enabled:
            from ..api import _percentile

            reg = obs.registry
            for name, samples in (("ttft", ttft), ("tpot", tpot)):
                if samples:
                    reg.gauge(f"serving/{name}_p50_ms").set(
                        _percentile(samples, 0.50))
                    reg.gauge(f"serving/{name}_p99_ms").set(
                        _percentile(samples, 0.99))
            if tokens_out:
                reg.gauge("serving/tokens_per_sec",
                          help="generated tokens / wall seconds").set(
                              tokens_out / max(wall, 1e-9))

"""Iteration-level continuous-batching scheduler (Orca, Yu et al. OSDI '22).

Device-free by design: the scheduler manipulates ``Request`` state, rows and
blocks; ``ServingEngine`` (api.py) executes the device programs it plans.
That split keeps every policy decision testable with an injectable clock and
zero sleeps (the ``hangdetect.py`` testing convention).

Policies:

* **Admission** — iteration-level: whenever a decode row is free and the
  pool can hold the request's first prefill chunk, a queued request joins
  the running batch. Under ``fairness='fair'``, the next request comes from
  the tenant with the least accumulated service (tokens processed), and
  within a tenant earliest-deadline-first (requests without a deadline sort
  last, then by arrival). ``'fcfs'`` is plain arrival order.
* **Chunked prefill** — one prompt chunk per iteration, interleaved with
  the decode step, so a long prompt cannot freeze time-to-first-token for
  everyone else (Sarathi-style).
* **Preemption by block eviction** — when the pool runs dry mid-decode, the
  most recently admitted other request is evicted: its blocks free
  immediately, and it re-queues in *recompute* mode (its re-prefill source
  is prompt + tokens generated so far; already-streamed tokens are never
  re-emitted). LIFO victim choice protects the oldest requests' latency.
  Freeing drops REFERENCES — blocks shared through the prefix cache stay
  resident for their other holders, and unpinned cache entries are evicted
  before any running request is.
* **Prefix sharing** — admission consults the content-hashed
  ``PrefixCache``: cached full prompt blocks are mapped straight into the
  new request's table (refcount++) and their prefill chunks never run.
  Writes into a shared block go copy-on-write (``cow_block_indices`` +
  ``alloc_for_cow``; the engine runs the device-side copy).
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from .paged_kv import (BlockAllocator, PrefixCache, blocks_for_tokens,
                       extend_block_list, truncate_block_list)

__all__ = ["Request", "SamplingParams", "Scheduler", "QueueFull",
           "QUEUED", "PREFILL", "DECODE", "FINISHED", "CANCELLED",
           "DEADLINE_EXCEEDED"]

QUEUED = "queued"
PREFILL = "prefill"
DECODE = "decode"
FINISHED = "finished"
CANCELLED = "cancelled"
DEADLINE_EXCEEDED = "deadline_exceeded"


class QueueFull(RuntimeError):
    """Backpressure: the serving queue is at ``max_queue`` in-flight
    requests — callers shed load or retry later."""


@dataclasses.dataclass
class SamplingParams:
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0


@dataclasses.dataclass
class Request:
    """One in-flight generation request. ``prompt`` is the CURRENT prefill
    source — after a preemption it becomes prompt+generated-so-far
    (recompute mode); ``n_prompt`` keeps the original prompt length for
    TTFT/budget accounting."""

    rid: int
    prompt: np.ndarray                       # (S,) int32 prefill source
    max_new_tokens: int
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    eos_token_id: Optional[int] = None
    tenant: str = "default"
    deadline_s: Optional[float] = None       # absolute (scheduler clock)
    seed: int = 0
    arrival_s: float = 0.0             # joined the queue: admission order
    submit_s: Optional[float] = None   # the caller entered submit(), before
    #   any wait for the engine's lock: where TTFT counts from
    # -- runtime state (scheduler-owned) --
    state: str = QUEUED
    row: Optional[int] = None                # decode-batch row while running
    blocks: List[int] = dataclasses.field(default_factory=list)
    prefill_pos: int = 0                     # tokens of `prompt` prefilled
    length: int = 0                          # KV tokens written for this row
    pending_token: Optional[int] = None      # sampled, not yet in the cache
    generated: List[int] = dataclasses.field(default_factory=list)
    n_prompt: int = 0                        # ORIGINAL prompt length
    resume: bool = False                     # recompute after preemption
    # incremental prefix-cache chain digest: key of the last registered
    # block + how many prompt blocks it covers (rebuilt on mismatch, e.g.
    # after preemption resets prefill_pos)
    chain_key: bytes = b""
    chain_blocks: int = 0
    preemptions: int = 0
    first_token_s: Optional[float] = None
    finish_s: Optional[float] = None
    # -- parallel-sampling fork (COW) --
    prefilled: bool = False            # blocks/KV pre-attached at fork:
    #   admission skips allocation AND prefill (straight to DECODE);
    #   cleared on preemption (recompute goes the normal path)
    fork_of: Optional[int] = None      # parent rid, for metrics/debugging
    # -- speculative decoding accounting (engine-owned) --
    spec_proposed: int = 0             # draft tokens this request verified
    spec_accepted: int = 0             # ... and accepted
    # -- request tracing (engine-owned; None unless the session's
    #    request_tracing gate is on — the disabled path carries a None) --
    trace: Optional[object] = None     # observability.reqtrace.ReqTrace

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.n_prompt == 0:
            self.n_prompt = int(self.prompt.size)

    @property
    def done(self) -> bool:
        return self.state in (FINISHED, CANCELLED, DEADLINE_EXCEEDED)

    @property
    def entry_s(self) -> float:
        """When the request reached the engine: entry to ``submit()`` where
        the engine stamped it, else its arrival in the queue (requests built
        directly, recovered or adopted ones)."""
        return self.submit_s if self.submit_s is not None else self.arrival_s

    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_s is None:
            return None
        return self.first_token_s - self.entry_s

    @property
    def tpot_s(self) -> Optional[float]:
        """Mean time per output token after the first."""
        if (self.finish_s is None or self.first_token_s is None
                or len(self.generated) < 2):
            return None
        return ((self.finish_s - self.first_token_s)
                / (len(self.generated) - 1))


class Scheduler:
    """Owns the queue, the decode rows, and the block pool accounting."""

    def __init__(self, config, allocator: Optional[BlockAllocator] = None,
                 clock: Callable[[], float] = time.monotonic,
                 prefix_cache: Optional[PrefixCache] = None):
        config.validate()
        self.config = config
        self.alloc = allocator or BlockAllocator(config.pool_blocks())
        self.prefix = prefix_cache
        self.clock = clock
        self.queued: List[Request] = []
        self.running: Dict[int, Request] = {}      # row -> request
        # called with the request on EVERY release (finish/cancel/preempt)
        # — the speculative drafter's device-state teardown hook
        self.on_release: Optional[Callable[[Request], None]] = None
        # called with the victim AFTER a preemption re-queued it — the
        # request tracer's eviction event (None costs one attribute check)
        self.on_preempt: Optional[Callable[[Request], None]] = None
        self._free_rows: List[int] = list(range(config.max_seqs))[::-1]
        self.service: Dict[str, float] = {}        # tenant -> tokens served
        self._admit_seq = 0
        # rid -> admission order, for RUNNING requests only (pruned on
        # release so a long-lived server's memory stays bounded)
        self._admit_index: Dict[int, int] = {}
        import collections

        # bounded trace of admission order (tests + debugging)
        self.admitted_log = collections.deque(maxlen=4096)
        self.preemption_count = 0
        # decode rows given back, by whatever ended or evicted a request
        # (the serving engine's step ahead waits out the chunk that takes one)
        self.rows_released = 0
        self.finished_count = 0
        self.cancelled_count = 0
        self.deadline_exceeded_count = 0
        # deadline-bearing requests currently queued/running: the O(1)
        # fast path for expire_deadlines — a no-deadline workload must not
        # pay a per-iteration scan for a feature it never uses
        self._deadline_reqs = 0
        self.handoffs_out = 0          # requests handed to another engine
        self.prefix_hits = 0           # admissions that reused ≥1 block
        self.prefix_hit_tokens = 0     # prompt tokens whose prefill was skipped
        self.prefix_lookup_tokens = 0  # prompt tokens of COMMITTED admissions

    # -- intake ------------------------------------------------------------
    def submit(self, req: Request) -> None:
        if len(self.queued) + len(self.running) >= self.config.max_queue:
            raise QueueFull(
                f"serving queue full ({self.config.max_queue} in-flight); "
                "shed load or raise serving.max_queue")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.rid}: max_new_tokens must be >= 1, got "
                f"{req.max_new_tokens}")
        limit = self.config.max_model_len
        if req.n_prompt + req.max_new_tokens > limit:
            raise ValueError(
                f"request {req.rid}: prompt ({req.n_prompt}) + "
                f"max_new_tokens ({req.max_new_tokens}) exceeds "
                f"serving.max_model_len={limit}")
        if req.n_prompt < 1:
            raise ValueError(f"request {req.rid}: empty prompt")
        req.arrival_s = self.clock()
        req.state = QUEUED
        if req.deadline_s is not None:
            self._deadline_reqs += 1
        self.queued.append(req)

    def submit_forked(self, req: Request) -> None:
        """Enqueue a COW-forked sibling: its blocks (shared, incref'd by
        the caller) and KV are already attached, so admission only needs a
        free decode row. Bypasses the ``max_queue`` check — the engine
        reserved fork capacity when the parent's ``submit(n=...)`` was
        accepted (pending siblings count toward its in_flight). A caller
        that pre-set ``arrival_s`` keeps it: a submit(n=...) sibling's
        TTFT clock starts at the client's submit, not the fork point."""
        if req.arrival_s == 0.0:
            req.arrival_s = self.clock()
        req.state = QUEUED
        req.prefilled = True
        if req.deadline_s is not None:
            self._deadline_reqs += 1
        self.queued.append(req)

    def cancel(self, req: Request) -> bool:
        if req.done:
            return False
        if req.state == QUEUED:
            self.queued.remove(req)
        # _release is a no-op for row-less requests but still frees any
        # blocks a queued request may hold (a request evicted mid-iteration
        # can transiently carry blocks) — skipping it here leaked them for
        # the server's lifetime
        self._release(req)
        self._note_terminal(req)
        req.state = CANCELLED
        req.finish_s = self.clock()
        self.cancelled_count += 1
        return True

    # -- bookkeeping -------------------------------------------------------
    def queue_depth(self) -> int:
        return len(self.queued)

    def in_flight(self) -> int:
        return len(self.queued) + len(self.running)

    def note_service(self, req: Request, tokens: int) -> None:
        self.service[req.tenant] = self.service.get(req.tenant, 0.0) + tokens

    def _release(self, req: Request) -> None:
        """Free the request's row and blocks (state left to the caller)."""
        if req.row is not None:
            del self.running[req.row]
            self._free_rows.append(req.row)
            req.row = None
            self.rows_released += 1
        if req.blocks:
            self.alloc.free(req.blocks)
            req.blocks = []
        self._admit_index.pop(req.rid, None)
        if self.on_release is not None:
            # tpusync: disable=callback-under-lock — engine-bound seam
            # (prefix-cache/drafter cleanup), not user code; block release
            # and its observers must be atomic
            self.on_release(req)

    def _note_terminal(self, req: Request) -> None:
        """Terminal-state bookkeeping shared by finish/cancel/handoff/
        expire (NOT preemption — a preempted request is still in flight)."""
        if req.deadline_s is not None:
            self._deadline_reqs = max(self._deadline_reqs - 1, 0)

    def finish(self, req: Request) -> None:
        self._release(req)
        self._note_terminal(req)
        req.state = FINISHED
        req.finish_s = self.clock()
        self.finished_count += 1

    def expire_deadlines(self, now: float) -> List[Request]:
        """Terminal-state the requests whose absolute deadline has passed —
        queued OR running: a request that can no longer meet its deadline
        must stop consuming decode rows and blocks to completion. Frees
        rows/blocks immediately (the bugfix: an expired request used to
        decode to its token budget while live requests waited on the pool)
        and returns the expired requests so the engine can count them and
        wake their handles. O(1) when no in-flight request carries a
        deadline — the common workload never pays for the scan."""
        if self._deadline_reqs == 0:
            return []
        expired = self._past_deadline(now)
        for req in expired:
            if req.state == QUEUED:
                self.queued.remove(req)
            self._release(req)
            self._note_terminal(req)
            req.state = DEADLINE_EXCEEDED
            req.finish_s = now
            self.deadline_exceeded_count += 1
        return expired

    def _past_deadline(self, now: float) -> List[Request]:
        return [r for r in [*self.queued, *self.running.values()]
                if r.deadline_s is not None and now > r.deadline_s]

    def deadline_due(self, clock) -> bool:
        """Whether ``expire_deadlines(clock())`` would end a request. The
        clock is read only where an in-flight request carries a deadline."""
        return self._deadline_reqs > 0 and bool(self._past_deadline(clock()))

    def release_handoff(self, req: Request) -> None:
        """Terminal release for a request whose KV was handed to ANOTHER
        engine (fleet prefill/decode disaggregation): frees this engine's
        row/blocks like ``finish`` but counts as a handoff, not a
        completion — the destination engine finishes the request and owns
        its completion ledger entry."""
        self._release(req)
        self._note_terminal(req)
        req.state = FINISHED
        req.finish_s = self.clock()
        self.handoffs_out += 1

    # -- admission ---------------------------------------------------------
    def _pick_next(self) -> Optional[Request]:
        if not self.queued:
            return None
        if self.config.fairness == "fcfs":
            return min(self.queued, key=lambda r: (r.arrival_s, r.rid))
        # fair: least-service tenant first (stable tie-break on name), then
        # EDF within the tenant (no deadline sorts last), then arrival
        tenant = min({r.tenant for r in self.queued},
                     key=lambda t: (self.service.get(t, 0.0), t))
        cands = [r for r in self.queued if r.tenant == tenant]
        return min(cands, key=lambda r: (
            r.deadline_s if r.deadline_s is not None else math.inf,
            r.arrival_s, r.rid))

    def admit(self) -> List[Request]:
        """Move queued requests onto free decode rows while their first
        chunk's blocks fit in the pool (admission never preempts a running
        request — only progress for already-admitted requests may evict;
        it MAY evict unpinned prefix-cache entries under pressure).

        Prefix sharing: a request whose prompt prefix is content-cached
        maps the cached blocks into its table (refcount++) and starts
        prefill AFTER them — those chunks are never run. The cached blocks
        are incref'd BEFORE the fresh allocation so cache-pressure eviction
        can never free the very blocks the admission is about to use."""
        admitted: List[Request] = []
        while self._free_rows:
            req = self._pick_next()
            if req is None:
                break
            if req.prefilled:
                # COW-forked sibling: KV and (shared) blocks already
                # attached — it only needs the row
                self.queued.remove(req)
                req.row = self._free_rows.pop()
                req.state = DECODE
                self.running[req.row] = req
                self._admit_index[req.rid] = self._admit_seq
                self._admit_seq += 1
                self.admitted_log.append(req.rid)
                admitted.append(req)
                continue
            cached_ids: List[int] = []
            n_cached = 0
            if self.prefix is not None:
                cached_ids, n_cached = self.prefix.match(req.prompt)
            if cached_ids:
                self.alloc.incref(cached_ids)
            first_target = min(n_cached + self.config.prefill_chunk,
                               int(req.prompt.size))
            need = max(blocks_for_tokens(first_target, self.config.block_size)
                       - len(cached_ids), 0)
            ids = self._alloc_evicting_cache(need)
            if ids is None:
                if cached_ids:
                    self.alloc.free(cached_ids)   # roll the increfs back
                break
            if self.prefix is not None:
                # stats at the COMMIT point only: a rolled-back admission
                # re-matching every iteration must not inflate the rate
                self.prefix_lookup_tokens += int(req.prompt.size)
            if cached_ids:
                req.blocks.extend(cached_ids)
                req.prefill_pos = n_cached
                req.length = n_cached
                self.prefix_hits += 1
                self.prefix_hit_tokens += n_cached
            req.blocks.extend(ids)
            self.queued.remove(req)
            req.row = self._free_rows.pop()
            req.state = PREFILL
            self.running[req.row] = req
            self._admit_index[req.rid] = self._admit_seq
            self._admit_seq += 1
            self.admitted_log.append(req.rid)
            admitted.append(req)
        return admitted

    def _alloc_evicting_cache(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` blocks, relieving pressure by evicting UNPINNED
        prefix-cache entries (LRU-first) — never by preempting a running
        request."""
        while True:
            ids = self.alloc.alloc(n)
            if ids is not None:
                return ids
            if self.prefix is None:
                return None
            if self.prefix.evict(n - self.alloc.blocks_free) <= 0:
                return None

    # -- block growth + preemption ----------------------------------------
    def ensure_blocks(self, req: Request, upto_tokens: int) -> bool:
        """Grow ``req``'s block list to cover positions [0, upto_tokens).
        When the pool is dry, relieves pressure in order of cost: first
        evict UNPINNED prefix-cache entries (no recompute anywhere), then
        evict the most recently admitted OTHER request and retry; returns
        False when nothing can be evicted (the caller skips this request
        for the iteration). Preempting a victim whose blocks are all
        shared may free nothing — the loop keeps evicting until the pool
        yields or the running set is exhausted."""
        need = blocks_for_tokens(upto_tokens, self.config.block_size) \
            - len(req.blocks)
        if need <= 0:
            return True
        while True:
            ids = self._alloc_evicting_cache(need)
            if ids is not None:
                req.blocks.extend(ids)
                return True
            if not self._preempt_one(exclude=req):
                return False

    def try_extend_blocks(self, req: Request, upto_tokens: int) -> bool:
        """Best-effort block growth for OPTIONAL work (the speculative
        verify extension): plain pool allocation — no cache eviction, no
        preemption. Speculation must never cost anyone else their blocks;
        a False here is the per-row auto-disable signal."""
        return extend_block_list(self.alloc, req.blocks, upto_tokens,
                                 self.config.block_size)

    def grows_without_preemption(self, reqs: List[Request]) -> bool:
        """Whether every request of ``reqs`` can take the page its NEXT
        token needs (``ensure_blocks(r, r.length + 1)``) and write there with
        nobody preempted and no block copied: the pages come from the free
        list or from unpinned prefix-cache entries, and no written page is
        shared. Asks only; takes nothing."""
        bs = self.config.block_size
        need = 0
        for r in reqs:
            need += max(blocks_for_tokens(r.length + 1, bs) - len(r.blocks),
                        0)
            if self.cow_block_indices(r, r.length, r.length + 1):
                return False
        return self.pages_without_preemption(need)

    def pages_without_preemption(self, need: int) -> bool:
        """Whether ``need`` pages come from the free list or from unpinned
        prefix-cache entries (which no request holds, so no program in
        flight reads them), with nobody preempted. Asks only."""
        short = need - self.alloc.blocks_free
        return short <= 0 or (self.prefix is not None
                              and self.prefix.can_evict(short))

    def truncate_blocks(self, req: Request, upto_tokens: int) -> int:
        """Positional rollback: free blocks past the ones covering
        positions [0, upto_tokens) — rejected speculative KV beyond the
        accepted length returns to the pool (see
        ``paged_kv.truncate_block_list``). Returns references dropped."""
        return truncate_block_list(self.alloc, req.blocks, upto_tokens,
                                   self.config.block_size)

    def alloc_for_cow(self, req: Request) -> Optional[int]:
        """One private block for a copy-on-write replacement in ``req``'s
        table — same pressure ladder as ensure_blocks. Returns the block
        id, or None when the pool cannot provide one this iteration."""
        while True:
            ids = self._alloc_evicting_cache(1)
            if ids is not None:
                return ids[0]
            if not self._preempt_one(exclude=req):
                return None

    def cow_block_indices(self, req: Request, start: int, end: int
                          ) -> List[int]:
        """Positions [start, end) are about to be written: the table
        indices whose physical block is SHARED (refcount > 1) and must be
        copied first — a writer may only touch exclusively-owned blocks."""
        if end <= start:
            return []
        bs = self.config.block_size
        return [bi for bi in range(start // bs, (end - 1) // bs + 1)
                if bi < len(req.blocks)
                and self.alloc.refcount(req.blocks[bi]) > 1]

    def note_prefill_progress(self, req: Request, old_pos: int,
                              new_pos: int) -> None:
        """Prefill advanced [old_pos → new_pos): register newly COMPLETED
        full prompt blocks with the prefix cache (idempotent — an existing
        chain key keeps its block). The chain digest threads through the
        request (one hash step per block); a position reset (preemption
        recompute) rebuilds it once."""
        if self.prefix is None:
            return
        bs = self.config.block_size
        first, last = old_pos // bs, new_pos // bs
        if req.chain_blocks != first:
            key = b""
            for bi in range(first):
                key = self.prefix.chain_key(req.prompt, key, bi)
            req.chain_key, req.chain_blocks = key, first
        for bi in range(first, last):
            req.chain_key = self.prefix.chain_key(req.prompt,
                                                  req.chain_key, bi)
            req.chain_blocks = bi + 1
            self.prefix.insert_key(req.chain_key, req.blocks[bi])

    def _preempt_one(self, exclude: Request) -> bool:
        victims = [r for r in self.running.values() if r is not exclude]
        if not victims:
            return False
        victim = max(victims, key=lambda r: self._admit_index[r.rid])
        self.preempt(victim)
        return True

    def preempt(self, req: Request) -> None:
        """Evict ``req``'s blocks and re-queue it in recompute mode: the new
        prefill source is prompt + generated-so-far minus the pending token
        (whose KV was never written); the stored ``pending_token`` is
        re-used on resume so the client stream never sees a duplicate — or,
        under temperature sampling, a diverged — token."""
        self.preemption_count += 1
        req.preemptions += 1
        self._release(req)
        if req.generated:
            req.prompt = np.concatenate(
                [req.prompt[:req.n_prompt],
                 np.asarray(req.generated[:-1], np.int32)]).astype(np.int32)
            req.pending_token = req.generated[-1]
            req.resume = True
        req.prefill_pos = 0
        req.length = 0
        req.prefilled = False   # a forked sibling recomputes like anyone
        req.state = QUEUED
        self.queued.append(req)
        if self.on_preempt is not None:
            # tpusync: disable=callback-under-lock — engine-bound seam
            # (drafter/KV bookkeeping), not user code; the requeue and its
            # observers must see one consistent preemption
            self.on_preempt(req)

    # -- iteration planning ------------------------------------------------
    def next_prefill(self) -> Optional[Request]:
        """The PREFILL-state request to advance this iteration — oldest
        admission first, so a chunked long prompt finishes in order."""
        cands = [r for r in self.running.values() if r.state == PREFILL]
        if not cands:
            return None
        return min(cands, key=lambda r: self._admit_index[r.rid])

    def decode_requests(self) -> List[Request]:
        return [r for r in self.running.values() if r.state == DECODE]

"""ServingEngine — the continuous-batching request front end.

The MII/FastGen analog for this stack: wraps an ``InferenceEngine`` (which
owns params, mesh and dtype discipline) with the paged KV arena
(``paged_kv.py``), the iteration-level scheduler (``scheduler.py``) and a
streaming session API (``session.py``).

One *iteration* (``step()``) is: admit queued requests onto free decode
rows → run at most one prefill chunk → run one decode step over every
decoding row → host-materialize the sampled tokens (the iteration's one
sync), stream them to handles, grow/free blocks. The device programs (the
chunk program, the decode program and, for a configuration whose layers
mix, the mixed step: below) are compiled exactly once per (shape)
configuration: occupancy, request mix and sampling settings are all *data*
(see ``docs/serving.md`` for the jit-cache discipline rationale).

What a token does on the host is split in two. ``_apply`` is what the NEXT
program's operands depend on (the request's tokens and length, the finish
test, row and blocks free) and runs as soon as the tokens are on the host.
``_flush`` is what only callers and measurement see (handles pushed and
woken, latency samples, counters, the request tracer). ``step()`` flushes
before it returns; the driver thread (``start()``) keeps an iteration's
decode tokens and flushes them right after it has enqueued the NEXT program
(``_run_program``), so the pushes and the callers they wake run while the
device works.

The driver thread also enqueues a decode step AHEAD: with the step before
it still running and that step's tokens not yet fetched, whenever nobody
waits for the device (``_ahead_held_by``, ``_rows_ahead``: rules about the
engine's state, each with a name, ``HELD_BY``; no setting). The step takes
its tokens on the device, from the array its predecessor returned; fetch,
apply, delivery, prepare and enqueue of the host's round then run in its
shadow. So ONE decode step may be in flight across an iteration boundary of
the driver thread (``_flight``); whatever needs a settled engine brings it
home first (``_bring_home``).

And INSIDE an iteration the driver thread enqueues the decode step BEHIND
THE CHUNK, with the chunk still running, whenever the chunk is not its
prompt's last (``_step_prefill``, ``_chunk_first_by``: rules with names
again, ``CHUNK_FIRST_BY``; no setting): such a chunk's token is read by
nobody and its request is no decode row, so the chunk's fetch and apply lie
in the decode program's shadow and no host round lies between the two
programs (``_chunk``).

Such an iteration then enqueues its prompt's NEXT CHUNK behind that decode
step, once the chunk is applied and with the step's tokens not yet fetched
(``_chunk_ahead``; ``CHUNK_HELD_BY`` names what keeps it back): all the chunk
needs is on the host by then, and fetch, apply and delivery of the step, the
iteration's tail, the next admission and the next step's prepare and enqueue
run in its shadow. So ONE chunk may be in flight across an iteration
boundary of the driver thread too (``_chunk``), never beside a step
(``_flight``): the next iteration finds it, asks ``_chunk_first_by`` as of
any chunk and enqueues its step behind it, or lands it first.
``_bring_home`` lands it for whoever needs a settled engine. Under ``step()``
no program is ever in flight across an iteration boundary.

Those are two of the THREE forms an iteration's enqueues take. The third is
the MIXED STEP, for a configuration whose layers mix (``paged_kv.mixes``: a
one-pass stack of plain attention layers with dense FFNs; decided by the
layers' kinds, no setting): where the two forms above would run a chunk that
is not its prompt's last and then the decode step behind it, the driver
thread asks the same rules BEFORE anything is enqueued and sends ONE program
that runs the chunk and the rows through one pass over the layers, so that
each weight is read once an iteration (``_step_prefill`` keeps the chunk
back, ``_mix``; ``_step_decode`` sends it with the rows; one ``_Enqueued``
carries both and one fetch lands both). It stays in flight as a step does
(``_flight``), and its successor, the rows beside the prompt's next chunk,
goes AHEAD of its fetch under the union of ``_rows_ahead``'s and
``_chunk_held_by``'s rules (less ``row_freed``, ``queued`` and ``fork``,
which never held a chunk ahead: the admission runs in the step's shadow, as
it does in a chunk's); a prompt's LAST chunk brings a first token, so
it always keeps the chunk program of its own (behind the mixed step in
flight: ``_chunk_ahead``). Whenever a name of ``CHUNK_FIRST_BY`` is given,
and for every configuration that does not mix, the iteration is the two
programs above.

Telemetry flows through the PR-2 observability substrate: ``serving/*``
metrics in the MetricsRegistry (ttft_ms, tpot_ms, queue_depth,
kv_blocks_in_use, preemptions, ...), the spans of ``docs/serving.md``'s table
(``serving/iteration`` and what it holds, ``serving/submit``,
``serving/request/*``; they reach the profiler's capture while one is open,
and ``serving/prefill_chunk`` / ``serving/decode`` also give the recompile
watchdog its attribution site), and tpuaudit entries of the same names.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..config.config import ServingConfig
from ..observability import get_session
from ..observability.memory import hbm_counts
from ..parallel import mesh as mesh_mod
from ..utils.logging import log_dist, logger
from . import paged_kv
from .scheduler import (CANCELLED, DECODE, PREFILL, Request,
                        SamplingParams, Scheduler)
from .session import RequestHandle

__all__ = ["ServingEngine", "init_serving"]

# tokens at a sequence's end that ``score_logprobs`` scores one at a time
# for a model with recurrent layers: a second or so of one-row steps
_SCORE_STEP_TAIL = 64

# a program whose jitted call, or whose wait for its tokens, lasted this
# long held the whole engine: the longest iteration the benchmark's cells
# know is 25 ms, and the freezes seen on the chip lasted 2-15 s
# (``ServingEngine.holds``)
HOLD_SECONDS = 0.5


# why a decode step was fetched with its successor NOT enqueued ahead of that
# fetch (``held_by`` on the ``serving/decode`` span that fetches it), in the
# order the rules are asked: ``_ahead_held_by`` gives the first six,
# ``_rows_ahead`` the next three, and ``step_mode`` is an iteration that is
# not the driver thread's
HELD_BY = ("row_freed", "queued", "fork", "prefill", "drafter", "deadline",
           "ends", "pages", "cow", "step_mode")

# why an iteration's chunk was fetched BEFORE its decode step was enqueued
# (``chunk_first_by`` on the span of that step): ``_step_locked`` gives
# ``step_mode``, ``drafter`` and ``more_chunks``, ``_chunk_first_by`` the rest
CHUNK_FIRST_BY = ("last_chunk", "pages", "no_rows", "drafter", "more_chunks",
                  "step_mode")

# why the decode step behind a chunk was fetched with its prompt's next chunk
# NOT enqueued ahead of that fetch (``chunk_held_by`` on the ``serving/decode``
# span that fetches it), in the order the rules are asked: ``_step_decode``
# gives ``dropped``, ``_chunk_held_by`` the rest
CHUNK_HELD_BY = ("dropped", "more_chunks", "pages", "cow")

# ``hbm_*`` go onto every this-many-th ``serving/iteration`` span: a peak
# loses nothing by it, and the allocator's statistics of every local device
# are not read inside each iteration's account (the goodput accountant
# publishes at the same cadence)
ACCOUNT_EVERY = 16


def _host_operands(call_args) -> tuple:
    """(number, bytes) of the arguments of a jitted call, at any depth, that
    are host values (numpy arrays and scalars, Python numbers), each of which
    the call transfers to the device before it can enqueue (a Python number
    at numpy's width): what ``<name>/dispatch`` carries as ``host_operands``
    and ``host_operand_bytes``."""
    import jax

    n = nbytes = 0
    for leaf in jax.tree_util.tree_leaves(call_args):
        if isinstance(leaf, (np.ndarray, np.generic)):
            n += 1
            nbytes += leaf.nbytes
        elif isinstance(leaf, (bool, int, float, complex)):
            n += 1
            nbytes += np.asarray(leaf).nbytes
    return n, nbytes


@dataclasses.dataclass(eq=False)
class _Enqueued:
    """A program behind its enqueue and before its fetch: the engine's clock
    before the call and behind it, and the call's seconds as far as they
    count towards a hold. A decode step also keeps its rows, each request
    with the row it held, and, where it was enqueued AHEAD or BEHIND A CHUNK
    still in flight, when that program's tokens came to the host: from there
    its interval counts. A chunk keeps its request so, where in the prompt
    it starts and how many tokens it holds, and, where it was enqueued AHEAD
    (behind a decode step not yet fetched: ``since`` is that step's fetch),
    whether the step had ended by then (``late``; None where nothing
    recorded asked), and how often its request had been preempted at the
    enqueue: one that lost its row since is not the request it ran for. A
    MIXED step is a decode step that also keeps its chunk (``chunk``: such
    a record with no tokens of its own; the step's one fetch lands both)."""
    name: str
    tok: Any
    t0: float
    t_call: float
    call_s: float
    rows: List[tuple] = ()
    since: Optional[float] = None
    start: int = 0
    tokens: int = 0
    ahead: bool = False
    late: Optional[int] = None
    preempted: int = 0
    chunk: Optional["_Enqueued"] = None


def _percentile(samples: List[float], q: float) -> float:
    xs = sorted(samples)
    return xs[min(len(xs) - 1, int(round(q * (len(xs) - 1))))]


class ServingEngine:
    """Continuous-batching serving over an ``InferenceEngine``'s params.

    ``draft_engine`` (an ``InferenceEngine`` over a smaller model) is
    required only for ``speculative.mode='draft'`` — its paged KV shares
    this engine's block pool (see ``speculative.py``)."""

    def __init__(self, engine, config: Optional[ServingConfig] = None,
                 clock: Callable[[], float] = time.monotonic,
                 draft_engine=None):
        self.engine = engine
        self.config = config or ServingConfig()
        self.config.validate()
        cfg = engine.model.config
        if (cfg.attention_layers or cfg.attention_scale is not None
                or cfg.attention_impl is not None):
            raise NotImplementedError(
                "serving does not support sliding-window/custom-scale "
                "attention models (GPT-Neo family) or a custom "
                "attention_impl yet — the paged read has no window, scale "
                "or impl operand")
        if cfg.position == "learned" and \
                self.config.max_model_len > cfg.max_seq_len:
            raise ValueError(
                f"serving.max_model_len={self.config.max_model_len} exceeds "
                f"the model's learned-position table ({cfg.max_seq_len})")
        self.blocks_per_seq = paged_kv.assert_block_divisible(
            self.config.max_model_len, self.config.block_size)
        # bucketing unification (the _bucket satellite): align the wrapped
        # engine's prompt buckets to the serving block size, so a prompt
        # padded for compile-bucket reasons never implies arena blocks the
        # true prompt cannot use
        engine.config.prompt_bucket = self.config.block_size
        self.clock = clock
        self._lock = threading.RLock()
        self.alloc = paged_kv.BlockAllocator(self.config.pool_blocks())
        # a model with recurrent layers (``transformer.recurrent_layers``:
        # delta-rule or state-space mixers) keeps, beside its pages, a
        # float32 state and a convolution tail a sequence in pools of
        # ``state_slots`` slots: decode row r owns slot r from admission to
        # release, and the last slot is scratch (rows that hold nothing,
        # and the sequence ``score_logprobs`` is scoring). A slot is never
        # cleared by the host: the chunk that starts at position 0 starts
        # it from zeros, on admission and on re-admission after a
        # preemption (recompute) alike. What cannot follow such state yet
        # is switched off or refused here and in ``_no_state_snapshot``
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from ..inference.kv_cache import ring_blocks
        from ..models.transformer import (MIXERS, attn_shape, expert_layers,
                                          latent_pools, moe_count_width,
                                          recurrent_layers, ring_layers,
                                          tail_runs)

        mixer, layers = recurrent_layers(cfg)
        self._recurrent_layers = len(layers)
        # a model whose mixer keeps a latent a token (``transformer.
        # latent_pools``) has one arena, ``"latent"``, and no ``"k"`` or
        # ``"v"``: blocks, the prefix cache, copy-on-write and preemption
        # are the pages'; what cannot read or move such a pool yet is
        # refused in ``_no_latent_read``
        self._latent_pools = latent_pools(cfg)
        # what a chunk's span counts of the prefill kernel's tile steps
        self._query_heads = (cfg.num_heads, cfg.head_dim)
        self._value_dim = attn_shape(cfg).value_dim
        # window layers keep their keys in a ring of pages a row, addressed
        # by the row's slot and bounded whatever the row's length; a model
        # may have rings, recurrent states, or both, and a row owns ONE slot
        # for whichever it has
        self._window_layers = len(ring_layers(cfg))
        self._ring_blocks = ring_blocks(cfg, self.config.prefill_chunk,
                                        self.config.block_size)
        # the stack's last runs keep nothing of a token: the chunk program
        # runs them, and the head, for a prompt's LAST chunk alone and is
        # told which chunk that is (``paged_kv.pack_chunk``'s ``last``)
        self._chunk_says_last = tail_runs(cfg) > 0
        # the span count of the (row, layer) states a step advanced
        self._recurrent_rows = mixer and MIXERS[mixer].rows_count
        # a looped stack (``loop_passes`` > 1): what the spans of its two
        # programs say of a run, the passes it made over the layers and the
        # pools of pages they wrote and read, one a (pass, layer); a block
        # of the allocator and of the prefix cache is its tokens in every
        # pool. {} for a stack that runs once: its spans stay as they were
        self._loop_counts = ({"loop_passes": cfg.loop_passes,
                              "pools": paged_kv.paged_pools(cfg)}
                             if cfg.loop_passes > 1 else {})
        self.state_slots = (self.config.max_seqs + 1
                            if self._recurrent_layers or self._window_layers
                            else 0)
        # the prefix cache shares PAGES between sequences; a recurrent
        # layer's state at the shared prefix's end, and a window layer's
        # ring, are in no page, so the cache is off for such a model (off,
        # not refused: it is a default)
        self.prefix = (paged_kv.PrefixCache(self.alloc,
                                            self.config.block_size)
                       if self.config.prefix_cache
                       and not self.state_slots else None)
        self.sched = Scheduler(self.config, allocator=self.alloc,
                               clock=clock, prefix_cache=self.prefix)
        # fleet identity on traces / serving-goodput labels (the router
        # overwrites it with the replica index before stepping)
        self.trace_tag = "0"
        # lazy ServeGoodput accountant (see _accountant: the bench builds
        # engines BEFORE enabling observability, so the gate is consulted
        # at step time, not construction)
        self._serve_acct = None
        self.sched.on_preempt = self._trace_preempt
        self._dtype = engine.config.dtype
        # made committed, as every program hands it back (replicated over
        # the engine's mesh), and made in place by a jitted function: a fresh
        # ``jnp.zeros`` is uncommitted, so the program that ran first
        # compiled a second time at its second call, for the arena its first
        # had returned (ROADMAP A13 (1): about a second of every cell's
        # set-up, which pays for the mixed step's load), and a
        # ``device_put`` of it may copy what fills most of the chip
        with mesh_mod.ambient(engine.mesh):
            self._arena = jax.jit(
                lambda: paged_kv.init_paged_cache(
                    cfg, self.config.pool_blocks() + 1,
                    self.config.block_size, self._dtype,
                    state_slots=self.state_slots,
                    ring_blocks=self._ring_blocks),
                out_shardings=NamedSharding(engine.mesh, PartitionSpec()))()
        # what the iteration's span says of the cache, read as the arena
        # was built: the bytes a token keeps over all pools of the rings
        # and of the pages, and how many pools each has
        def token_bytes(names):
            return sum(a.shape[0] * a.shape[-1] * a.dtype.itemsize
                       for name, a in self._arena.items() if name in names)

        self._ring_token_bytes = token_bytes(("wk", "wv"))
        self._page_token_bytes = token_bytes(paged_kv.PAGE_ARENAS)
        self._cache_layers = {
            "window_layers": (self._arena["wk"].shape[0]
                              if "wk" in self._arena else 0),
            "full_layers": max(a.shape[0] for name, a in self._arena.items()
                               if name in paged_kv.PAGE_ARENAS)}
        # an MoE model's two programs return their routing counts behind
        # the tokens (_program_counts); 0 = a dense model, whose programs and
        # spans know nothing of it. ``total`` counts the ROUTER's outputs a
        # layer, ``held`` the experts of a layer's stack (fewer where this
        # chip holds its share of them: ``moe_experts_held``)
        routed = len(expert_layers(cfg))    # not a leading dense layer
        self._moe_experts_total = cfg.moe_num_experts * routed
        self._moe_experts_held = cfg.experts_held * routed
        self._moe_choices = cfg.moe_top_k * routed          # a token
        moe = self._moe_experts_total > 0
        self._prefill = paged_kv.build_prefill_program(
            cfg, self.config.prefill_chunk, moe_counts=moe)
        self._decode = paged_kv.build_decode_program(cfg, moe_counts=moe)
        # the third program, for a configuration whose layers mix
        # (``paged_kv.mixes``: decided by their kinds, no setting): a chunk
        # that is not its prompt's last and the iteration's decode rows in
        # ONE pass over the layers (``_step_decode``). None: the engine is
        # the two programs' to the letter
        self._mixed = (paged_kv.build_mixed_program(
            cfg, self.config.prefill_chunk) if paged_kv.mixes(cfg) else None)
        # what the last decode program returned, on the device still (its
        # first ``max_seqs`` entries the tokens): the next one's last
        # operand, from which a step enqueued AHEAD takes its tokens. Placed
        # as the program places its result, so that the first call's
        # signature is every call's
        self._last_tokens = jax.device_put(
            np.zeros((self.config.max_seqs
                      + (moe_count_width(cfg) if moe else 0),), np.int32),
            NamedSharding(engine.mesh, PartitionSpec()))
        self._cow = paged_kv.build_cow_program()
        # teacher-forced scoring over the same arena (the RLHF second
        # serving pass — docs/rlhf.md); jit is lazy, so an engine that
        # never scores pays nothing
        self._score = paged_kv.build_score_program(cfg)
        self._cow_copies = 0
        self._published_cow = 0
        # rollout accounting: prefill dispatches + real tokens they
        # ingested — the fork/prefix reuse ratio's denominator-side
        # evidence (a candidate group of n samples must cost ONE prefill)
        self.prefill_chunks_run = 0
        self.prefill_tokens_run = 0
        self.weight_refreshes = 0
        # -- speculative decoding (off → the plain R×1 decode path) --
        from .speculative import make_drafter

        # kept for fleet replica revival: a rebuilt engine needs the same
        # drafter inputs the original was constructed with
        self._draft_engine = draft_engine
        # fleet degraded-mode rung 1: True skips the drafter (the verify
        # path with zero proposals IS the plain decode, so flipping this
        # mid-stream is bit-exact by construction)
        self.spec_suspended = False
        # prefill chunks per scheduler iteration — the live tuner's
        # chunked-prefill budget knob. Scheduling-only: N > 1 runs the
        # SAME compiled chunk program N times before the decode phase,
        # pulling TTFT forward under prefill backlog at some TPOT cost;
        # streams stay bit-exact at any setting
        self.prefill_chunks_per_iter = 1
        # set by FleetRouter: replicas are tuned fleet-wide, never solo
        self._fleet_managed = False
        # lazy live-tuner hook (single-engine deployments; see
        # FleetRouter._maybe_tuner for the fleet path); latched per
        # OBSERVABILITY SESSION, not once — benches replace the session
        # after warmup
        self._tuner = None
        self._tuner_obs = None
        self._drafter = make_drafter(self.config, engine, self.alloc,
                                     self.blocks_per_seq,
                                     draft_engine=draft_engine)
        self._verify = None
        if self._drafter is not None:
            self._no_state_snapshot("speculative decoding (rolling a "
                                    "rejected draft back)")
            self._no_latent_read("speculative decoding (a verify step: "
                                 "several queries a row, of many rows)")
            self._verify = paged_kv.build_verify_program(
                cfg, self.config.speculative.num_draft_tokens + 1)
            # one release point covers finish/cancel/preempt: the drafter
            # must drop its draft-arena blocks whenever the scheduler
            # releases the request's target blocks, or a preempted
            # request's draft KV would squat on the pool from the queue
            self.sched.on_release = self._drafter.release
        self._spec_dispatches = 0
        self._spec_emitted = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_disabled_rows = 0
        self._spec_draft_s = 0.0
        self._spec_verify_s = 0.0
        self._forks = 0
        self._published_spec = (0, 0, 0, 0)   # proposed/accepted/disp/disabled
        self._published_forks = 0
        self._base_rng = jax.random.PRNGKey(self.config.seed)
        self._rid = 0
        self._iterations = 0
        # rid -> handle for requests still in flight; pruned at finish/
        # cancel (the client keeps its own reference) so a long-running
        # server never accumulates per-request state
        self._handles: Dict[int, RequestHandle] = {}
        self._published_preemptions = 0
        # bounded latency reservoirs: percentiles over the most recent
        # window, constant memory at serving lifetimes
        import collections

        self._ttft_samples = collections.deque(maxlen=8192)
        self._tpot_samples = collections.deque(maxlen=8192)
        # per-request acceptance rates, recorded at finish (report p50)
        self._accept_samples = collections.deque(maxlen=8192)
        # parent rid -> sibling Requests awaiting the COW fork point
        # (parent prefill completion)
        self._pending_forks: Dict[int, List[Request]] = {}
        self._tokens_out = 0
        # tokens applied to their requests and not yet delivered to their
        # handles, in order: (request, token, first, finished). Host data
        # only, guarded by the engine lock
        self._undelivered: List[tuple] = []
        # True inside an iteration of the driver thread: what the next
        # program's operands do not need waits for that program's enqueue
        self._deferring = False
        # the decode step that is enqueued and not fetched, where the
        # driver thread left one at an iteration's end (``_step_decode``),
        # and the scheduler's count of rows given back when
        # ``_ahead_held_by`` last asked
        self._flight: Optional[_Enqueued] = None
        self._rows_released_seen = 0
        # why this iteration's chunk was fetched before its decode step was
        # enqueued (a name of ``CHUNK_FIRST_BY``), from ``_step_prefill`` to
        # the span of that step; None where no chunk ran, or the step went
        # behind it
        self._chunk_first: Optional[str] = None
        # the host values among a program's params and arena, (number,
        # bytes) by program name: counted at its first recorded dispatch
        # and kept (``_operand_counts``)
        self._fixed_operands: Dict[str, tuple] = {}
        # the chunk that is enqueued and not fetched, under the driver
        # thread: ``_step_decode`` enqueues its step behind it and lands it
        # in that step's shadow (``_step_prefill``), and may leave the
        # prompt's next one here at the iteration's end (``_chunk_ahead``)
        self._chunk: Optional[_Enqueued] = None
        # the chunk that ``_step_prefill`` prepared and did NOT enqueue,
        # (request, packed operands, start, tokens): ``_step_decode`` sends
        # it with its rows as one mixed step. Never set across an
        # iteration's end
        self._mix: Optional[tuple] = None
        # the chunk of the mixed step just fetched, with the step's
        # interval, until the span that holds the fetch has closed
        # (``_land``, ``_apply_landed``)
        self._landed: Optional[tuple] = None
        # the open iteration's span until its account (gauges, the span's
        # counts) is drawn up: behind its first enqueue when deferring, else
        # at its end
        self._unaccounted = None
        # programs that held the engine (``HOLD_SECONDS``), cumulative, and
        # each program's compiles so far (by the jitted function: the mixed
        # step shares the decode step's span names): a call that compiled is
        # no hold
        self.holds = 0
        self._compiles: Dict[Any, int] = {}
        self._started_s = clock()
        # fleet seam (serving/fleet): called with the request right after
        # its LAST prefill chunk completed and the first token was emitted,
        # while the engine lock is held. The disaggregation router uses it
        # to hand the sequence's KV blocks to a decode-pool engine; the
        # hook may release the request from this engine entirely
        # (``release_for_handoff``). None = single-engine serving.
        self.on_prefill_complete: Optional[Callable[[Request], None]] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._closed = False
        self._register_audit_entries()
        log_dist(
            f"serving engine ready: rows={self.config.max_seqs}, "
            f"blocks={self.config.pool_blocks()}x{self.config.block_size} "
            f"(+scratch), max_model_len={self.config.max_model_len}, "
            f"chunk={self.config.prefill_chunk}, arena="
            f"{paged_kv.paged_cache_memory_bytes(cfg, self.config.pool_blocks() + 1, self.config.block_size, self._dtype) / 2 ** 20:.0f}"
            " MiB")

    def _no_state_snapshot(self, what: str) -> None:
        """THE place that refuses, by name, what a model whose rows own a
        slot cannot do yet: whatever shares, copies or rolls back a
        sequence's pages would have to snapshot what its slot holds too (a
        recurrent state, a window's ring), and nothing takes such a snapshot
        (ROADMAP B-m5)."""
        if self.state_slots:
            raise NotImplementedError(
                f"{what} is not supported for a model with recurrent "
                "(linear-attention or state-space) layers or window layers: "
                "it needs a snapshot of what a sequence's slot holds (the "
                "state and convolution tail of serving/paged_kv.py's state "
                "pools, a window layer's ring of pages), which nothing "
                "takes yet; pages alone do not hold it")

    def _no_latent_read(self, what: str) -> None:
        """THE place that refuses, by name, what a model with a latent arena
        cannot do yet: its paged read is absorbed at one query a row and
        expanded for ONE row's chunk (``ops/paged_decode_attention.
        latent_paged_attention``), and the programs that move pages between
        engines move ``"k"`` and ``"v"`` (ROADMAP B-m3)."""
        if self._latent_pools:
            raise NotImplementedError(
                f"{what} is not supported for a model with latent attention "
                "(one pool of latents a sublayer in the arena's 'latent', no "
                "'k' and no 'v'): the paged read of such a pool is absorbed "
                "at one query a row and expanded for one row's chunk, and "
                "the hand-off programs move keys and values")

    # -- client API --------------------------------------------------------
    @property
    def threaded(self) -> bool:
        return self._thread is not None

    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
               eos_token_id: Optional[int] = None, tenant: str = "default",
               deadline_s: Optional[float] = None,
               seed: int = 0, n: int = 1):
        """Enqueue one prompt; returns a streaming handle immediately.
        ``deadline_s`` is relative to now (scheduler-clock seconds) and
        drives EDF ordering within the tenant. ``seed`` selects the
        request's sampling stream: draws depend only on (engine seed,
        request seed, output-token index) — reproducible regardless of how
        the scheduler batched the request, and stable across
        preemption/recompute. Raises ``scheduler.QueueFull`` past
        ``serving.max_queue`` in-flight requests (backpressure) and
        ``ValueError`` for prompts that cannot fit the ``max_model_len``
        budget.

        ``n > 1`` is parallel sampling: ONE prefill serves all ``n``
        samples — when it completes, ``n-1`` siblings fork the request's
        block table through the refcounted COW machinery (shared blocks,
        incref on fork; the first divergent write copies exactly one
        block). Sibling ``i`` samples with ``seed + i``, so each sample is
        bit-identical to a separately submitted request with that seed.
        Returns a list of ``n`` handles instead of one."""
        # TTFT counts from here: the wait for the engine's lock (the driver
        # thread holds it through an iteration) is time the caller spends
        entry_s = self.clock()
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if n < 1:
            raise ValueError(f"submit(n={n}): need n >= 1")
        if n > 1:
            self._no_state_snapshot("submit(n > 1) (forking at the end of "
                                    "the prompt)")
        obs = get_session()
        with obs.span("serving/submit", n_prompt=int(prompt.size)) as span:
            lock_wait = obs.span("serving/submit/lock_wait").begin()
            with self._lock:
                lock_wait.end()
                # pending (not-yet-forked) siblings hold real queue capacity:
                # submit_forked bypasses the scheduler's max_queue check, so
                # the reservation must be enforced here, against scheduler
                # occupancy PLUS every sibling still waiting for its fork
                in_flight = self.sched.in_flight() + self._pending_fork_count()
                if in_flight + n > self.config.max_queue:
                    from .scheduler import QueueFull

                    raise QueueFull(
                        f"serving queue cannot take {n} more request(s) "
                        f"({in_flight} in flight incl. pending forks, "
                        f"max_queue={self.config.max_queue})")

                if max_new_tokens is None:
                    max_new_tokens = self.config.default_max_new_tokens

                def make(rid, sd, fork_of=None):
                    return Request(
                        rid=rid, prompt=prompt.copy(),
                        max_new_tokens=max_new_tokens,
                        sampling=SamplingParams(
                            temperature=float(temperature),
                            top_k=int(top_k), top_p=float(top_p)),
                        eos_token_id=eos_token_id, tenant=tenant, seed=sd,
                        fork_of=fork_of, submit_s=entry_s,
                        deadline_s=(self.clock() + deadline_s
                                    if deadline_s is not None else None))

                req = make(self._rid, seed)
                self.sched.submit(req)   # raises before rid is consumed
                self._rid += 1
                span.annotate(rid=req.rid)
                self._trace_start(req)
                handle = RequestHandle(self, req)
                self._handles[req.rid] = handle
                if obs.enabled:
                    obs.registry.counter(
                        "serving/requests_submitted",
                        help="requests accepted into the serving queue").inc(
                            n, tenant=tenant)
                if n == 1:
                    return handle
                sibs, handles = [], [handle]
                for i in range(1, n):
                    sib = make(self._rid, seed + i, fork_of=req.rid)
                    sib.arrival_s = req.arrival_s   # queued with the parent:
                    #   the wait through the parent's prefill counts
                    self._rid += 1
                    self._trace_start(sib, parent_trace=req.trace)
                    sibs.append(sib)
                    h = RequestHandle(self, sib)
                    self._handles[sib.rid] = h
                    handles.append(h)
                self._pending_forks[req.rid] = sibs
                return handles

    def cancel(self, handle: RequestHandle) -> bool:
        cancelled = 0   # every cancellation this call caused — pre-fork
        #   siblings and parent-cascaded siblings included, so the
        #   requests_{submitted,completed,cancelled} ledger balances
        with self._lock:
            # what is enqueued and what is applied streams before the cancel
            # ends it.
            # tpusync: disable=lock-order-inversion — here and wherever an
            # engine is brought home: a last chunk landed there hands its
            # request on (the SE->FR edge of ``_iterate``), but an engine
            # under a router never has a chunk in flight: ``FleetRouter``
            # drives it by ``step()``, on the one thread that holds FR
            self._bring_home()
            req = handle._req
            # a sibling cancelled before its fork point never reached the
            # scheduler — cancel it directly
            if req.fork_of is not None:
                sibs = self._pending_forks.get(req.fork_of, [])
                if req in sibs:
                    sibs.remove(req)
                    req.state = CANCELLED
                    req.finish_s = self.clock()
                    self.sched.cancelled_count += 1
                    self._handles.pop(req.rid, None)
                    self._count_cancelled(1)
                    self._trace_finish(req, "cancelled")
                    handle._wake()
                    return True
            ok = self.sched.cancel(req)
            cancelled += int(ok)
            if ok:
                self._trace_finish(req, "cancelled")
            # a cancelled parent takes its un-forked siblings with it
            for sib in self._pending_forks.pop(req.rid, []):
                sh = self._handles.pop(sib.rid, None)
                sib.state = CANCELLED
                sib.finish_s = self.clock()
                self.sched.cancelled_count += 1
                cancelled += 1
                self._trace_finish(sib, "cancelled")
                if sh is not None:
                    sh._wake()
            self._handles.pop(req.rid, None)
        self._count_cancelled(cancelled)
        handle._wake()
        return ok

    @staticmethod
    def _count_cancelled(n: int) -> None:
        if n:
            obs = get_session()
            if obs.enabled:
                obs.registry.counter(
                    "serving/requests_cancelled",
                    help="requests cancelled before completion").inc(n)

    def _pending_fork_count(self) -> int:
        return sum(len(v) for v in self._pending_forks.values())

    # -- request tracing + serving goodput (observability) -----------------
    def _accountant(self):
        """Lazy ServeGoodput lookup: None until an enabled session with the
        ``serve_goodput`` gate exists (the disabled path wires nothing)."""
        acct = self._serve_acct
        if acct is None:
            obs = get_session()
            if obs.enabled and getattr(obs.config, "serve_goodput", False):
                from ..observability.servegoodput import ServeGoodput

                acct = self._serve_acct = ServeGoodput(
                    registry=obs.registry, replica=self.trace_tag,
                    clock=self.clock,
                    ttft_slo_ms=obs.config.serve_ttft_slo_ms,
                    tpot_slo_ms=obs.config.serve_tpot_slo_ms,
                    slo_budget=obs.config.serve_slo_budget)
        return acct

    def _maybe_tuner(self):
        """Lazy live-tuner lookup for SINGLE-engine deployments — fleet
        replicas return None unconditionally (the router owns the fleet's
        controller). Same discipline as :meth:`_accountant`: the disabled
        path is one cached-bool check, nothing allocated."""
        if self._fleet_managed:
            return None
        if self._tuner is None:
            obs = get_session()
            if obs is not self._tuner_obs:
                # probe once per session object: configure_observability
                # always builds a new session, so identity tracks
                # enable/replace without re-probing every iteration
                with self._lock:
                    self._tuner_obs = obs
                    if obs.enabled:
                        from ..autotuning.livetuner import maybe_make_tuner

                        self._tuner = maybe_make_tuner(self, obs)
        return self._tuner

    def _trace_start(self, req: Request, parent_trace=None) -> None:
        rt = get_session().reqtrace
        if rt is None:
            return
        req.trace = rt.start(
            tenant=req.tenant,
            # the queue wait starts where TTFT does: at entry to submit()
            t=req.submit_s if req.submit_s is not None else self.clock(),
            fork_of=(parent_trace.trace_id if parent_trace is not None
                     else None),
            attrs={"rid": req.rid, "seed": req.seed,
                   "n_prompt": req.n_prompt,
                   "max_new_tokens": req.max_new_tokens})
        if parent_trace is not None:
            rt.link_fork(parent_trace, req.trace)

    @staticmethod
    def _request_span(obs, name: str, req: Request, **counts: Any) -> None:
        """One boundary of a request's life, as a short span where it
        happens; all of one request's carry its ``rid`` (and its
        ``trace_id`` when request tracing is on)."""
        with obs.span(name, rid=req.rid, **counts) as span:
            if span.recording and req.trace is not None:
                span.annotate(trace_id=req.trace.trace_id)

    def _trace_admitted(self, obs, admitted: List[Request]) -> None:
        now = self.clock()
        rt = obs.reqtrace
        for req in admitted:
            self._request_span(
                obs, "serving/request/admitted", req, row=req.row,
                queue_wait_us=int((now - req.entry_s) * 1e6))
            if rt is not None and req.trace is not None:
                rt.admitted(req.trace, now, self.trace_tag, row=req.row)

    def _trace_preempt(self, req: Request) -> None:
        obs = get_session()
        self._request_span(obs, "serving/request/preempted", req)
        if req.trace is not None:
            rt = obs.reqtrace
            if rt is not None:
                rt.preempted(req.trace, self.clock(), self.trace_tag)

    def _trace_finish(self, req: Request, state: str, **attrs: Any) -> None:
        obs = get_session()
        self._request_span(obs, "serving/request/finished", req,
                           tokens=len(req.generated), state=state)
        if req.trace is None:
            return
        rt = obs.reqtrace
        if rt is not None:
            rt.finish(req.trace, state, t=self.clock(), ttft_s=req.ttft_s,
                      tokens=len(req.generated), replica=self.trace_tag,
                      **attrs)

    def _trace_dispatch(self, rt, trace):
        """Context manager marking ``trace`` as the compile-attribution
        target while a device dispatch is open (nullcontext when tracing
        is off)."""
        if rt is None:
            return contextlib.nullcontext()
        return rt.active(trace)

    def in_flight(self) -> int:
        """Requests holding queue capacity: queued + running + parallel-
        sampling siblings still waiting for their parent's fork point."""
        with self._lock:
            return self.sched.in_flight() + self._pending_fork_count()

    # -- fleet seams (serving/fleet: router resubmission + KV handoff) -----
    def submit_recovered(self, prompt, generated, *,
                         max_new_tokens: int, temperature: float = 0.0,
                         top_k: int = 0, top_p: float = 1.0,
                         eos_token_id: Optional[int] = None,
                         tenant: str = "default",
                         deadline_s: Optional[float] = None,
                         seed: int = 0) -> RequestHandle:
        """Resubmit a request that was mid-stream on a DEAD engine: enqueue
        it in exactly the state the preemption machinery leaves a
        recompute-mode request in — prefill source is the original prompt
        plus every already-streamed token except the last, which becomes
        the authoritative ``pending_token`` — so decode resumes at
        output-token index ``len(generated)`` under the identical
        (engine seed, request seed, token index) sampling stream and the
        continued output is bit-identical to an uninterrupted run.
        Already-streamed tokens are never re-emitted (the fleet handle
        holds them); does NOT count ``serving/requests_submitted`` — the
        dead engine already did, and the fleet-wide ledger must balance."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        generated = [int(t) for t in generated]
        with self._lock:
            if (self.sched.in_flight() + self._pending_fork_count() + 1
                    > self.config.max_queue):
                from .scheduler import QueueFull

                raise QueueFull(
                    "serving queue cannot take the recovered request "
                    f"(max_queue={self.config.max_queue})")
            req = Request(
                rid=self._rid, prompt=prompt.copy(),
                max_new_tokens=max_new_tokens,
                sampling=SamplingParams(temperature=float(temperature),
                                        top_k=int(top_k),
                                        top_p=float(top_p)),
                eos_token_id=eos_token_id, tenant=tenant, seed=seed,
                deadline_s=(self.clock() + deadline_s
                            if deadline_s is not None else None))
            if generated:
                req.prompt = np.concatenate(
                    [prompt, np.asarray(generated[:-1],
                                        np.int32)]).astype(np.int32)
                req.generated = list(generated)
                req.pending_token = generated[-1]
                req.resume = True
            self.sched.submit(req)    # raises before rid is consumed
            self._rid += 1
            if generated:
                # TTFT already happened on the dead engine — the unset-
                # timestamp catch in _apply must not restamp it here
                req.first_token_s = req.arrival_s
            handle = RequestHandle(self, req)
            self._handles[req.rid] = handle
            return handle

    def adopt_prefilled(self, *, prompt, n_prompt: int, generated,
                        pending_token: int, length: int, blocks: List[int],
                        seed: int, sampling: SamplingParams,
                        max_new_tokens: int,
                        eos_token_id: Optional[int] = None,
                        tenant: str = "default",
                        deadline_s: Optional[float] = None) -> RequestHandle:
        """Adopt a request whose KV already sits in THIS engine's arena
        (fleet KV handoff): ``blocks`` must be blocks of this engine's
        allocator, freshly imported with the request's first ``length``
        positions resident. The request joins the queue fully prefilled —
        admission only needs a decode row — and its decode continues at
        output-token index ``len(generated)``, bit-identical to never
        having moved. ``prompt`` is the ORIGINAL prompt (a later preemption
        rebuilds the recompute source from prompt[:n_prompt] + generated).
        Raises ``QueueFull`` when this engine cannot take the request —
        the caller still owns ``blocks`` and must free them."""
        self._no_state_snapshot("kv_import (adopting a sequence prefilled "
                                "on another engine)")
        self._no_latent_read("kv_import (adopting a sequence prefilled on "
                             "another engine)")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        with self._lock:
            if (self.sched.in_flight() + self._pending_fork_count() + 1
                    > self.config.max_queue):
                from .scheduler import QueueFull

                raise QueueFull(
                    "serving queue cannot adopt the handed-off request "
                    f"(max_queue={self.config.max_queue})")
            req = Request(
                rid=self._rid, prompt=prompt.copy(),
                max_new_tokens=max_new_tokens, sampling=sampling,
                eos_token_id=eos_token_id, tenant=tenant, seed=seed,
                n_prompt=int(n_prompt),
                deadline_s=(self.clock() + deadline_s
                            if deadline_s is not None else None))
            self._rid += 1
            req.generated = [int(t) for t in generated]
            req.pending_token = int(pending_token)
            req.length = int(length)
            req.prefill_pos = int(req.prompt.size)
            req.blocks = list(blocks)
            # every emitted token (incl. the prefill-completion one) was
            # streamed by the source engine; TTFT belongs to it
            req.first_token_s = self.clock()
            self.sched.submit_forked(req)
            handle = RequestHandle(self, req)
            self._handles[req.rid] = handle
            return handle

    def release_for_handoff(self, req: Request) -> None:
        """Release a request whose KV was exported to another engine:
        terminal for this engine (row/blocks freed, handle dropped)
        without touching the completion ledger."""
        with self._lock:
            self._bring_home()
            self.sched.release_handoff(req)
            handle = self._handles.pop(req.rid, None)
            if handle is not None:
                handle._wake()    # its stream continues on the other engine
            if req.trace is not None:
                rt = get_session().reqtrace
                if rt is not None:
                    rt.event(req.trace, "handoff_release", t=self.clock(),
                             replica=self.trace_tag)

    # -- weight flip (RLHF hybrid engine) ----------------------------------
    def note_weights_updated(self) -> int:
        """The wrapped engine's params were just refreshed in place (the
        hybrid-engine train→serve flip). The arena ALLOCATION survives —
        block pool, compiled prefill/decode/verify/cow/score programs and
        scheduler state are all keyed on shapes, which a weight refresh
        never changes — but cached KV CONTENT is a function of the params,
        so every prefix-cache entry is invalidated (its content hash
        describes bytes that no longer exist). Requires an idle engine:
        in-flight requests hold KV computed under the OLD weights and
        cannot be continued coherently. Returns the number of prefix-cache
        entries dropped."""
        with self._lock:
            self._bring_home()
            if self.sched.in_flight() or self._pending_fork_count():
                raise RuntimeError(
                    "weight flip with requests in flight "
                    f"({self.sched.in_flight()} scheduled, "
                    f"{self._pending_fork_count()} pending forks) — drain "
                    "the engine before refresh (their KV was computed "
                    "under the old weights)")
            self.weight_refreshes += 1
            dropped = 0
            if self.prefix is not None:
                dropped = self.prefix.clear()
            obs = get_session()
            if obs.enabled:
                obs.registry.counter(
                    "serving/weight_refreshes",
                    help="hybrid-engine weight flips absorbed without "
                         "arena realloc").inc()
                if dropped:
                    obs.registry.counter(
                        "serving/prefix_invalidations",
                        help="prefix-cache entries dropped by weight "
                             "flips (stale content hashes)").inc(dropped)
            return dropped

    # -- teacher-forced scoring (the RLHF second serving pass) -------------
    def score_logprobs(self, tokens, params: Optional[Any] = None
                       ) -> np.ndarray:
        """Per-position log-probabilities of a full sequence under
        ``params`` (default: the engine's current weights): returns
        ``logp`` of shape ``(len(tokens) - 1,)`` where ``logp[p]`` is the
        model's log-probability of ``tokens[p + 1]`` given
        ``tokens[:p + 1]``. Runs through the SAME paged arena in
        prefill-chunk-sized pieces over scratch blocks allocated from the
        pool (evicting unpinned prefix-cache entries under pressure, never
        preempting) and freed before returning. Passing a resharded
        frozen-reference tree as ``params`` reuses the one compiled score
        program — the RLHF reference-logprob pass costs zero extra
        compiles.

        A model whose rows own a slot (recurrent layers, window layers)
        scores a sequence's last ``_SCORE_STEP_TAIL`` tokens ONE at a time (the same program traced
        at a width of one): the model's one-token forms, which its decode
        program runs (``kda_decode_step``, ``mamba2_decode_step`` or
        ``mamba1_decode_step`` on the state pools in place, the paged decode
        kernel), carry on from the state and the pages that
        the chunks left. So a comparison of these log-probabilities with a
        reference covers the chunk form, the step form and the hand-over
        of one state between them."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        T = int(tokens.size)
        if T < 2:
            raise ValueError(f"score_logprobs needs >= 2 tokens, got {T}")
        if T > self.config.max_model_len:
            raise ValueError(
                f"score_logprobs: sequence of {T} tokens exceeds "
                f"serving.max_model_len={self.config.max_model_len}")
        C = self.config.prefill_chunk
        with self._lock:
            self._bring_home()
            need = paged_kv.blocks_for_tokens(T, self.config.block_size)
            ids = self.sched._alloc_evicting_cache(need)
            if ids is None:
                raise RuntimeError(
                    f"score_logprobs: cannot allocate {need} scratch "
                    f"blocks ({self.alloc.blocks_free} free) — score after "
                    "rollouts drain, or grow serving.num_blocks")
            try:
                bt = np.zeros((1, self.blocks_per_seq), np.int32)
                bt[0, :need] = ids
                if params is None:
                    params = self.engine.params
                out = np.zeros((T - 1,), np.float32)
                obs = get_session()
                # (start, tokens, the program's width). A chunk starts
                # the sequence (only a chunk at 0 starts a state from
                # zeros); the last position has no target, so no step
                body = (max(T - 1 - _SCORE_STEP_TAIL, 1)
                        if self.state_slots else T)
                pieces = ([(at, min(C, body - at), C)
                           for at in range(0, body, C)]
                          + [(at, 1, 1) for at in range(body, T - 1)])
                with mesh_mod.ambient(self.engine.mesh):
                    for start, n_valid, width in pieces:
                        chunk = np.zeros((1, width), np.int32)
                        chunk[0, :n_valid] = tokens[start:start + n_valid]
                        # the target for position p is tokens[p + 1]; the
                        # final sequence position has none
                        nt = min(n_valid, T - 1 - start)
                        tgt = np.zeros((1, width), np.int32)
                        if nt > 0:
                            tgt[0, :nt] = tokens[start + 1:start + 1 + nt]
                        with obs.span("serving/score_chunk",
                                      tokens=int(n_valid)):
                            lp, self._arena = self._score(
                                params, self._arena, bt, chunk, tgt,
                                np.asarray(start, np.int32),
                                np.asarray(n_valid, np.int32))
                            lp = np.asarray(lp)   # fence: chunk really ran
                        if nt > 0:
                            out[start:start + nt] = lp[0, :nt]
            finally:
                self.alloc.free(ids)
        return out

    # -- the iteration -----------------------------------------------------
    def step(self) -> bool:
        """One continuous-batching iteration; returns True when any request
        made progress (admission, a prefill chunk, a decode token, or a
        deadline expiry reclaiming its resources). Every token the step
        produced is delivered to its handle when it returns. The
        ``serving/iteration`` span opens before the engine lock is taken, so
        the wait for callers inside ``submit()`` is part of it
        (``.../lock_wait``)."""
        return self._iterate(defer=False)

    def _iterate(self, defer: bool) -> bool:
        """``step()``; with ``defer`` (the driver thread) the iteration's
        decode tokens are applied and kept, and delivered behind the next
        program this engine enqueues (``_run_program``), or by whoever
        needs a settled engine first (``_bring_home``); and a decode step
        may stay in flight at the iteration's end, for the next iteration
        to enqueue its successor ahead of its fetch, or a chunk, for the
        next iteration's step to go behind (``_step_decode``)."""
        obs = get_session()
        with obs.span("serving/iteration", cpu=True) as span:
            lock_wait = obs.span("serving/iteration/lock_wait",
                                 cpu=True).begin()
            with self._lock:
                lock_wait.end()
                acct = self._accountant()
                if acct is not None:
                    acct.iteration_begin(self.clock())
                self._deferring = defer
                self._unaccounted = span
                try:
                    # tpusync: disable=lock-order-inversion — the SE->FR
                    # edge (prefill-complete handoff, in _step_prefill) and
                    # the FR->SE edge (router submit/step) are both RLock
                    # re-entries on the one thread that drives a fleet:
                    # engines under a router are stepped only from
                    # FleetRouter.step, which already holds FR
                    progress = self._step_locked(obs)
                    if not defer or self._unaccounted is not None:
                        # nothing was enqueued to do it behind
                        self._settle(obs)
                    it = self._iterations
                    self._iterations += 1
                except BaseException:
                    # a chunk in flight is run again, as one whose fetch
                    # raised always was (a mixed step's chunk too, whose
                    # progress nobody applies behind a fetch that raised)
                    self._chunk = None
                    self._landed = None
                    raise
                finally:
                    self._deferring = False
                    self._unaccounted = None
                    if acct is not None:
                        acct.iteration_end(self.clock())
                        # gauge refresh at a cadence, always AFTER the
                        # window closed (wall and buckets stay consistent):
                        # per-iteration publishing would put O(window)
                        # breach-deque scans on the decode loop's critical
                        # path. close() publishes the final snapshot.
                        if acct.iterations % ACCOUNT_EVERY == 1:
                            acct.publish()
            # the live tuner's decision tick runs OUTSIDE the engine lock:
            # the controller is foreign code with its own lock, and its knob
            # writes are plain scheduling attributes — keeping it out of the
            # critical section keeps the lock graph acyclic (tools/tpusync).
            # The deep profiler's tick, same discipline: trigger polling and
            # window open/close do their own locking and may dispatch
            # (start_trace)
            tuner = self._maybe_tuner()
            prof = obs.profiler
            if tuner is not None or prof is not None:
                with obs.span("serving/ticks"):
                    if tuner is not None:
                        tuner.on_iteration(it)
                    if prof is not None:
                        prof.on_iteration(it)
        return progress

    def _step_locked(self, obs) -> bool:
        """The iteration's work, under the engine lock: each stretch of it
        lies in one span of ``docs/serving.md``'s table. With a decode step
        in flight (the driver thread left it there: ``_step_decode``) the
        iteration is that step's: the next one enqueued AHEAD of its fetch
        where the rules allow it, else the fetch alone, and today's
        iteration, admission first, is the next. With a CHUNK in flight
        (``_chunk_ahead`` left it there) the iteration is today's and that
        chunk is its chunk (``_step_prefill``). A MIXED step in flight is a
        chunk in flight in this: the admission runs first, in its shadow,
        as it does in a chunk's, and then its successor goes ahead of its
        fetch, or it is landed and the iteration is a settled engine's.
        ``step()`` brings what it finds in flight home and goes on."""
        flight = self._flight
        self._chunk_first = None
        self._mix = None
        if not self._deferring:
            if flight is not None or self._chunk is not None:
                self._bring_home("step_mode")
        elif flight is not None and flight.chunk is None:
            rows, held_by = self._rows_ahead(flight)
            if rows:
                return self._step_decode(rows)
            self._land(obs, flight, held_by=held_by)
            # its tokens wait, as those of every step that was fetched with
            # nothing behind it do, for the next enqueue (the chunk's, if a
            # caller is at the door): nothing is delivered into the gap
            self._account(obs)
            return True
        with obs.span("serving/admit") as span:
            # before admit: an already-expired queued request must
            # never take a decode row first
            expired = self._expire_deadlines()
            admitted = self.sched.admit()
            if admitted and (span.recording or obs.reqtrace is not None):
                self._trace_admitted(obs, admitted)
            span.annotate(admitted=len(admitted), expired=expired)
        progress = bool(expired or admitted)
        if self._flight is not None:
            rows, held_by = self._rows_ahead(flight)
            if rows:
                return self._step_decode(rows)
            self._land(obs, flight, held_by=held_by)
            progress = True
        drafting = self._drafter is not None and not self.spec_suspended
        chunks = max(int(self.prefill_chunks_per_iter), 1)
        for i in range(chunks):
            # the iteration's last chunk may stay in flight for the decode
            # step to be enqueued behind it: the driver thread's form only,
            # and never under a verify step, whose drafter runs first
            ran_chunk = self._step_prefill(
                first_by=("step_mode" if not self._deferring
                          else "drafter" if drafting
                          else "more_chunks" if i < chunks - 1 else None))
            progress |= ran_chunk
            if not ran_chunk:
                break
        chunk = self._chunk
        progress |= self._step_verify() if drafting else self._step_decode()
        if chunk is not None and self._chunk is chunk:
            # no decode step was enqueued behind it after all
            self._land_chunk(obs, chunk)
        return progress

    def _settle(self, obs, deferred: bool = False) -> None:
        """Deliver what is applied and undelivered, then draw up the open
        iteration's account, once: the registry's gauges and the iteration
        span's counts. ``deferred``: a program of this iteration is
        enqueued, so all of it runs while the device works."""
        self._flush(deferred)
        self._account(obs)

    def _account(self, obs) -> None:
        """The open iteration's account, once."""
        span = self._unaccounted
        if span is None:
            return
        self._unaccounted = None
        with obs.span("serving/publish"):
            self._publish_iteration()
            if span.recording:
                span.annotate(
                    it=self._iterations, queued=self.sched.queue_depth(),
                    running=len(self.sched.running),
                    blocks_in_use=self.alloc.blocks_in_use,
                    blocks_running=sum(
                        len(r.blocks) for r in self.sched.running.values()),
                    blocks_total=self.alloc.capacity,
                    preemptions=self.sched.preemption_count,
                    holds=self.holds,
                    **obs.tracer.gc_counts(),
                    **self._state_counts(),
                    **self._cache_counts(),
                    **(hbm_counts()
                       if self._iterations % ACCOUNT_EVERY == 0 else {}))

    def _expire_deadlines(self) -> int:
        """Deadline enforcement at decode time: a request whose absolute
        deadline passed finishes as ``deadline_exceeded`` NOW — rows and
        blocks free at this iteration boundary instead of decoding to its
        token budget — and its un-forked siblings (who could never fork
        anymore) expire with it. The ledger stays balanced:
        submitted == completed + cancelled + deadline_exceeded. Returns how
        many expired."""
        now = self.clock()
        expired = self.sched.expire_deadlines(now)
        if not expired:
            return 0
        from .scheduler import DEADLINE_EXCEEDED

        # a token applied last iteration reaches its handle before the
        # expiry ends the stream
        self._flush()

        for req in list(expired):
            for sib in self._pending_forks.pop(req.rid, []):
                sib.state = DEADLINE_EXCEEDED
                sib.finish_s = now
                self.sched.deadline_exceeded_count += 1
                expired.append(sib)
        obs = get_session()
        for req in expired:
            if obs.enabled:
                obs.registry.counter(
                    "serving/requests_deadline_exceeded",
                    help="requests terminated at an iteration boundary "
                         "after their deadline passed").inc(
                             tenant=req.tenant)
            # the ring carries the victim's id even with tracing disabled:
            # a crash bundle from a fleet incident names its requests
            obs.flight_event(
                "req_terminal", event="deadline_exceeded", rid=req.rid,
                tenant=req.tenant,
                trace_id=(req.trace.trace_id if req.trace is not None
                          else None))
            self._trace_finish(req, "deadline_exceeded")
            handle = self._handles.pop(req.rid, None)
            if handle is not None:
                handle._wake()
        return len(expired)

    def _state_counts(self) -> Dict[str, int]:
        """A model with recurrent layers: the slots of the state pools that
        hold a LIVE state (a running request's row once its first chunk has
        run; until then the slot still holds what the row's last owner left,
        which that chunk starts over) and the slots rows can own (scratch is
        not one). Nothing for any other model."""
        if not self.state_slots:
            return {}
        live = [r.length for r in self.sched.running.values()
                if r.length > 0]
        counts = {"state_slots_in_use": len(live),
                  "state_slots_total": self.config.max_seqs}
        if self._window_layers:
            # the keys a window layer holds for the live rows (a ring's
            # worth at most), over a window's worth for each of them
            ring = self._ring_blocks * self.config.block_size
            window = self.engine.model.config.attention_window
            counts.update(
                window_resident_tokens=self._window_layers * sum(
                    min(n, ring) for n in live),
                window_tokens_bound=self._window_layers * window * len(live))
        return counts

    def _cache_counts(self) -> Dict[str, int]:
        """What the running requests keep in the cache: the bytes of their
        tokens in the window layers' rings (a ring's worth a row at most;
        0 for a model with no ring) and those plus the bytes of the blocks
        they hold in the arenas of pages, beside the pools of each the
        arena was built with."""
        running = list(self.sched.running.values())
        ring = self._ring_blocks * self.config.block_size
        in_rings = self._ring_token_bytes * sum(
            min(r.length, ring) for r in running)
        in_pages = (self._page_token_bytes * self.config.block_size
                    * sum(len(r.blocks) for r in running))
        return {"ring_resident_bytes": in_rings,
                "cache_resident_bytes": in_rings + in_pages,
                **self._cache_layers}

    def _table_for(self, reqs: List[Request]) -> np.ndarray:
        """(len(reqs), MAXB) block table; unfilled entries → scratch 0."""
        bt = np.zeros((len(reqs), self.blocks_per_seq), np.int32)
        for i, r in enumerate(reqs):
            if r.blocks:
                bt[i, :len(r.blocks)] = r.blocks
        return bt

    @staticmethod
    def _sampling_arrays(reqs: List[Request]):
        return (np.asarray([r.sampling.temperature for r in reqs],
                           np.float32),
                np.asarray([r.sampling.top_k for r in reqs], np.int32),
                np.asarray([r.sampling.top_p for r in reqs], np.float32),
                np.asarray([r.seed for r in reqs], np.int32))

    @staticmethod
    def _sampled_rows(reqs) -> int:
        """Rows of a step with ``temperature > 0``: at 0 the program's
        sampler took the argmax and sorted nothing
        (``paged_kv.sample_rows``)."""
        return sum(r.sampling.temperature > 0 for r in reqs)

    def _make_writable(self, req: Request, start: int, end: int,
                       optional: bool = False) -> bool:
        """Copy-on-write: every block covering write positions
        [start, end) must be exclusively owned before the jitted program
        scatters into it. Shared blocks (prefix sharing, refcount > 1) are
        duplicated on device and swapped into the request's table; the
        sharers keep the original. Returns False when the pool can't
        provide a private copy this iteration — the caller skips the
        request; copies already made stay (they are real private blocks,
        the retry skips them). ``optional`` marks speculative work: the
        copy comes from plain allocation only — no cache eviction, no
        preemption — because speculation must never cost anyone else
        their blocks."""
        for bi in self.sched.cow_block_indices(req, start, end):
            if optional:
                ids = self.alloc.alloc(1)
                nid = ids[0] if ids else None
            else:
                nid = self.sched.alloc_for_cow(req)
            if nid is None:
                return False
            old = req.blocks[bi]
            obs = get_session()
            with mesh_mod.ambient(self.engine.mesh):
                with obs.span("serving/cow_copy"):
                    self._arena = self._cow(self._arena,
                                            np.asarray(old, np.int32),
                                            np.asarray(nid, np.int32))
            req.blocks[bi] = nid
            self.alloc.free([old])   # drop THIS request's shared reference
            self._cow_copies += 1
        return True

    def _run_program(self, obs, name: str, program, *args, trace=None):
        """Dispatch one jitted program over the arena (its first output the
        sampled tokens, its last the arena) and bring the tokens to the host:
        ``_enqueue``, then ``_fetch``. Between the two the device works and
        the host has nothing to wait for: an iteration of the driver thread
        delivers there what the last one applied and kept (``_settle``).
        THREE readings of the engine's clock: the first and the last feed
        the accountants and the request tracer (returns (tokens, t0, t1)),
        and with the one after the call they tell a program that held the
        engine (``_note_hold``), whether or not anything records. The spans
        stamp themselves, on the profiler's clock, and only while they
        record."""
        sent = self._enqueue(obs, name, program, *args, trace=trace)
        if self._deferring:
            self._settle(obs, deferred=True)
        tok, t1 = self._fetch(obs, sent)
        return tok, sent.t0, t1

    def _enqueue(self, obs, name: str, program, *args,
                 trace=None) -> "_Enqueued":
        """``<name>/dispatch``: the jitted call, which returns at enqueue,
        with the mesh and the request tracer's compile attribution
        (``trace``: whose dispatch this is) entered and left around it, so
        that nothing lies unnamed between ``<name>/prepare`` and the call.
        ``args`` is what the program takes behind the params and the arena:
        ONE numpy array, its step's operands packed (``paged_kv.pack_*``),
        which is the one transfer the call makes, and what is on the device
        already (the sampling key; the decode program's last tokens). A call
        that compiled (the jitted ``program``'s call cache grew) is set-up,
        not a hold, however long. The span's counts (``_operand_counts``) are
        taken before it opens: counting them is none of the enqueue."""
        span = obs.span(name + "/dispatch", category="phase")
        if span.recording:
            span.annotate(**self._operand_counts(name, args))
        t0 = self.clock()
        with span:
            with self._trace_dispatch(obs.reqtrace, trace):
                with mesh_mod.ambient(self.engine.mesh):
                    tok, *_, self._arena = program(
                        self.engine.params, self._arena, *args)
        t_call = self.clock()
        call_s = t_call - t0
        if self._loop_counts and obs.enabled:
            obs.registry.counter(
                "serving/loop_passes_run",
                help="passes over the layers that the enqueued decode and "
                     "prefill-chunk programs of a looped stack made").inc(
                self._loop_counts["loop_passes"])
        compiles = program._cache_size()
        if compiles != self._compiles.get(program):
            # the call traced and compiled (or read the compile cache): a
            # program's first
            self._compiles[program] = compiles
            call_s = 0.0
        return _Enqueued(name, tok, t0, t_call, call_s)

    def _operand_counts(self, name: str, args) -> Dict[str, int]:
        """``host_operands`` and ``host_operand_bytes`` of a dispatch of
        program ``name``, read only while its span records. The parameters
        and the arena are device arrays for the engine's life: their leaves
        are walked once a program name and the count kept; ``args`` is
        walked at every dispatch."""
        fixed = self._fixed_operands.get(name)
        if fixed is None:
            fixed = self._fixed_operands[name] = _host_operands(
                (self.engine.params, self._arena))
        n, nbytes = _host_operands(args)
        return {"host_operands": fixed[0] + n,
                "host_operand_bytes": fixed[1] + nbytes}

    def _fetch(self, obs, sent: "_Enqueued"):
        """``<name>/fetch``: the wait for an enqueued program's tokens
        (device time + D2H: the host's sync). Returns (tokens, the engine's
        clock behind them)."""
        with obs.span(sent.name + "/fetch", category="phase", cpu=True):
            tok = np.asarray(sent.tok)
        t1 = self.clock()
        fetch_s = t1 - sent.t_call
        if sent.call_s >= HOLD_SECONDS or fetch_s >= HOLD_SECONDS:
            self._note_hold(obs, sent.name, sent.call_s, fetch_s)
        return tok, t1

    def _note_hold(self, obs, name: str, call_s: float,
                   fetch_s: float) -> None:
        """A program held the engine: its jitted call, or the wait from the
        call's return to its tokens on the host (with what the driver thread
        delivers meanwhile), lasted ``HOLD_SECONDS`` or more. Counted on the
        engine and logged, whether or not anything records; the next
        ``serving/iteration`` span to settle carries the count."""
        self.holds += 1
        held = " and ".join(f"{phase} {secs:.3f} s" for phase, secs in
                            (("call", call_s), ("fetch", fetch_s))
                            if secs >= HOLD_SECONDS)
        logger.warning("serving hold: %s held the engine at iteration %d: "
                       "%s (%d so far)", name, self._iterations, held,
                       self.holds)
        if obs.enabled:
            obs.registry.counter(
                "serving/holds",
                help=f"programs whose call or fetch lasted {HOLD_SECONDS} s "
                     "or more").inc(program=name)

    def _program_counts(self, span, fetched: np.ndarray, n: int,
                        real_rows: int, tokens: int) -> np.ndarray:
        """The ``n`` sampled tokens of what an MoE model's program returned
        (``paged_kv._with_moe_counts``); the routing counts behind them go
        onto ``span``: over the real rows of this iteration and summed over
        the layers, the (token, expert) assignments that reached an expert
        held here, the held experts that had a row (of ``moe_experts_held``
        = experts of a layer's stack x layers; ``moe_experts_total`` = the
        router's ROUTED outputs x layers, the same number unless this chip
        holds a share) and the rows of each layer's largest expert; and for
        a model with zero-computation experts ``moe_zero_assignments``, the
        assignments that chose one (they reach no held expert and are in
        none of the other counts), beside ``moe_choices``, all the
        assignments the routers made: the program's real ``tokens`` x expert
        layers x experts a token. A model with
        recurrent layers also says how many (row, layer) states the program
        advanced: ``real_rows`` x its recurrent layers, as ``recurrent_rows``
        (delta-rule layers) or ``ssm_rows`` (state-space layers)."""
        if span.recording and self._recurrent_layers:
            span.annotate(**{self._recurrent_rows:
                             real_rows * self._recurrent_layers})
        if not self._moe_experts_total:
            return fetched
        if span.recording:
            assigned, touched, largest, *zero = (int(c) for c in fetched[n:])
            span.annotate(moe_assignments=assigned,
                          moe_experts_touched=touched,
                          moe_experts_total=self._moe_experts_total,
                          moe_experts_held=self._moe_experts_held,
                          moe_max_expert_rows=largest)
            if zero:    # a model with zero-computation experts alone
                span.annotate(moe_zero_assignments=zero[0],
                              moe_choices=tokens * self._moe_choices)
        return fetched[:n]

    def _step_prefill(self, first_by: Optional[str] = "step_mode") -> bool:
        """One chunk of the oldest prompt in prefill, enqueued, and fetched
        and applied too, unless nothing says it must be fetched first:
        neither the iteration (``first_by``, from ``_step_locked``: None for
        the driver thread's iteration, at its last chunk, with no drafter)
        nor the chunk and the decode rows (``_chunk_first_by``: it is not its
        prompt's last, and this iteration's decode rows can grow without a
        preemption). Then it stays in flight
        (``_chunk``): nothing the decode step's operands are reckoned from
        waits for it (its token is read by nobody, its request is no decode
        row), so ``_step_decode`` enqueues that step BEHIND it, the device
        goes from one program to the other with no host round between, and
        the chunk is fetched and applied in the decode program's shadow. A
        prompt's last chunk brings a first token and a new decode row: it is
        always fetched, applied and delivered before the decode step is
        prepared. Which rule had the chunk fetched first is kept for the
        span of the iteration's step (``_chunk_first``). A chunk that the
        last iteration enqueued AHEAD (``_chunk_ahead``) is this iteration's
        chunk, in flight already: the same rules are asked of it, before the
        decode rows take anything, and where one names itself it is landed
        here, in a span of its own.

        A configuration whose layers MIX (``_mixed``) asks the same rules
        with the chunk prepared and NOT yet enqueued, and where none names
        itself the chunk is kept (``_mix``) for ``_step_decode`` to send
        with its rows as ONE program; whenever a rule is named the
        iteration is the two programs' above."""
        obs = get_session()
        sent = self._chunk
        if sent is not None:
            (req, _), = sent.rows
            self._chunk_first = first_by or self._chunk_first_by(
                req, sent.start, sent.tokens)
            if self._chunk_first is not None:
                with self._chunk_span(obs, req, sent.start, True) as span:
                    if self._deferring:
                        self._settle(obs, deferred=True)
                    self._land_chunk(obs, sent, span)
            return True
        req = self.sched.next_prefill()
        if req is None:
            return False
        with self._chunk_span(obs, req, req.prefill_pos) as span:
            prepared = self._prepare_chunk(obs, req, req.prefill_pos)
            if prepared is None:
                return False    # the pool could not place it — wait
            self._chunk_first = first_by or self._chunk_first_by(
                req, *prepared[1:])
            if self._chunk_first is None and self._mixed is not None:
                self._mix = (req, *prepared)
                return True
            sent = self._enqueue_chunk(obs, req, *prepared)
            if self._chunk_first is None:
                self._chunk = sent
                return True
            if self._deferring:
                self._settle(obs, deferred=True)
            self._land_chunk(obs, sent, span)
        return True

    def _chunk_span(self, obs, req: Request, start: int, ahead: bool = False):
        """A ``serving/prefill_chunk`` span with what each of a chunk's
        spans carries: whose chunk, where in the prompt it starts, whether
        it was enqueued AHEAD (``_chunk_ahead``), and on a recorded span
        ``prefill_blocks`` and ``prefill_blocks_skipped`` (``ops/
        paged_decode_attention.prefill_block_counts``)."""
        span = obs.span("serving/prefill_chunk", cpu=True, rid=req.rid,
                        chunk_start=int(start), ahead=int(ahead),
                        sampled_rows=self._sampled_rows([req]),
                        **self._loop_counts)
        if span.recording and not self._latent_pools:
            # the kernels' module counts the (query, key) sub-blocks the
            # chunk's tiles span and those its tile steps leave out (a
            # latent pool's chunk reads expanded, by no such kernel)
            from ..ops.paged_decode_attention import prefill_block_counts
            C = self.config.prefill_chunk
            span.annotate(**prefill_block_counts(
                [start], [min(start + C, int(req.prompt.size))], C,
                *self._query_heads, self._arena["k"],
                value_dim=self._value_dim))
        return span

    def _prepare_chunk(self, obs, req: Request,
                       start: int) -> Optional[tuple]:
        """``serving/prefill_chunk/prepare`` of the chunk of ``req``'s prompt
        that starts at ``start`` (where its prefill stands, or will once the
        mixed step in flight is applied), under the chunk's span, which is
        open: its pages taken and made its own, its operands packed. Returns
        (packed, start, tokens), or None where the pool cannot place it."""
        C = self.config.prefill_chunk
        src = req.prompt
        n_valid = min(C, int(src.size) - start)
        with obs.span("serving/prefill_chunk/prepare", category="phase"):
            if not self.sched.ensure_blocks(req, start + n_valid):
                return None    # pool dry, nothing evictable
            if not self._make_writable(req, start, start + n_valid):
                return None    # a shared block needs a copy the pool
                #   can't give
            chunk = np.zeros((1, C), np.int32)
            chunk[0, :n_valid] = src[start:start + n_valid]
            last = start + n_valid == int(src.size)
            packed = paged_kv.pack_chunk(
                self._table_for([req]), chunk, start, n_valid,
                *self._sampling_arrays([req]),
                state_slot=([req.row] if self.state_slots else None),
                **({"last": [last]} if self._chunk_says_last else {}))
        return packed, int(start), int(n_valid)

    def _enqueue_chunk(self, obs, req: Request, packed, start: int,
                       tokens: int) -> "_Enqueued":
        """``serving/prefill_chunk/dispatch`` of a chunk that
        ``_prepare_chunk`` prepared: the chunk program of its own."""
        sent = self._enqueue(obs, "serving/prefill_chunk", self._prefill,
                             packed, self._base_rng, trace=req.trace)
        return self._chunk_of(sent, req, start, tokens)

    @staticmethod
    def _chunk_dropped(sent: "_Enqueued") -> bool:
        """Whether the chunk's request was cancelled, expired or preempted
        behind its enqueue: its progress is then applied to nobody."""
        (req, row), = sent.rows
        return (req.state != PREFILL or req.row != row
                or req.preemptions != sent.preempted)

    @staticmethod
    def _chunk_of(sent: "_Enqueued", req: Request, start: int,
                  tokens: int) -> "_Enqueued":
        """``sent`` as the record of ``req``'s chunk at ``start``."""
        sent.rows = [(req, req.row)]
        sent.start, sent.tokens = start, tokens
        sent.preempted = req.preemptions
        return sent

    def _chunk_held_by(self, req: Request,
                       start: Optional[int] = None,
                       rows_need: int = 0) -> Optional[str]:
        """What keeps the NEXT chunk of ``req``'s prompt, whose last chunk
        was just applied behind this iteration's decode step, from being
        enqueued behind that step while it is in flight: the first, in this
        order, of these names of ``CHUNK_HELD_BY``, or None. (``dropped``,
        which ``_step_decode`` gives before it asks here: the request ended
        or lost its row under its chunk, so the next chunk is another
        prompt's or this one's first again, and a prompt's first chunk is
        never enqueued ahead of anything.) ``more_chunks``: an iteration
        runs more chunks than one (the live tuner's knob), so the next one's
        first is fetched before its second is prepared. ``pages``: the pages
        the chunk needs would cost a request its row: they come neither from
        the free list nor from unpinned prefix-cache entries, which no
        request holds and no program in flight reads (in a pool that has run
        for a while every free page is such an entry). ``cow``: it would
        write into a shared block. So nobody is preempted and no block
        copied on behalf of a chunk ahead. Asked before anything is taken,
        as ``_rows_ahead`` asks. That this is the driver thread's iteration
        and that no drafter proposes need not be asked: the step went behind
        its chunk (``_step_locked``). Nor whose chunk the scheduler would
        run next: the oldest admission in prefill, which ``req`` was and is,
        whoever was admitted since (``Scheduler.next_prefill``).

        ``start``: where the chunk begins, if not where the request's
        prefill stands: behind a MIXED step in flight, whose chunk's progress
        is not applied yet (``_rows_ahead``); ``rows_need`` is then the pages
        that step's successor takes for its rows, from the same pool."""
        if max(int(self.prefill_chunks_per_iter), 1) > 1:
            return "more_chunks"
        if start is None:
            start = req.prefill_pos
        end = min(start + self.config.prefill_chunk, int(req.prompt.size))
        need = max(paged_kv.blocks_for_tokens(end, self.config.block_size)
                   - len(req.blocks), 0)
        if not self.sched.pages_without_preemption(need + rows_need):
            return "pages"
        if self.sched.cow_block_indices(req, start, end):
            return "cow"
        return None

    def _chunk_ahead(self, obs, req: Request, step: "_Enqueued",
                     start: Optional[int] = None) -> Optional[str]:
        """The next chunk of ``req``'s prompt, whose last chunk was just
        applied, prepared and enqueued BEHIND ``step``, the decode step that
        went behind that chunk and is not fetched yet: its span holds
        ``.../prepare`` and ``.../dispatch`` and says ``ahead`` 1, and whether
        the step had ended by the enqueue (``late``: one non-blocking
        question). It stays in flight (``_chunk``) for the next iteration,
        across the step's fetch, the delivery, the iteration's tail and the
        next admission; the device goes from the step to it with no host
        round between. Returns None, or the name of ``CHUNK_HELD_BY`` that
        kept it back: then the next iteration runs as it always did.
        ``start``: as ``_chunk_held_by``'s; ``step`` is then a mixed step
        and the chunk its prompt's LAST, which keeps a program of its own."""
        if start is None:
            start = req.prefill_pos
        held_by = self._chunk_held_by(req, start)
        if held_by is not None:
            return held_by
        with self._chunk_span(obs, req, start, True) as span:
            prepared = self._prepare_chunk(obs, req, start)
            if prepared is None:
                return "pages"
            sent = self._enqueue_chunk(obs, req, *prepared)
            sent.ahead = True
            if span.recording or obs.enabled:
                sent.late = int(step.tok.is_ready())
                span.annotate(late=sent.late)
            if obs.enabled:
                obs.registry.counter(
                    "serving/chunks_enqueued_ahead",
                    help="prefill chunks enqueued behind their prompt's "
                         "last chunk and its decode step, with that step's "
                         "tokens not yet on the host (the driver thread's "
                         "form)").inc()
            self._chunk = sent
        return None

    def _chunk_first_by(self, req: Request, start: int,
                        tokens: int) -> Optional[str]:
        """What keeps this iteration's decode step from being enqueued
        behind its chunk (``tokens`` of ``req``'s prompt from ``start`` on)
        while that is still in flight, or from being sent WITH it as one
        mixed step, as far as
        the chunk, the rows and their pages say (a name of
        ``CHUNK_FIRST_BY``), or None where nothing does. ``last_chunk``: the
        chunk is its prompt's last.
        ``no_rows``: no request decodes. ``pages``: a row's next token needs
        a page that only a preemption would free, or writes into a shared
        block. Else the page each one's next token needs comes from
        the free list or from an unpinned prefix-cache entry (which frees
        only blocks no request holds, and whoever takes one writes it in a
        program enqueued behind the chunk), and none of them writes into a
        shared block. So nobody is preempted and no block copied with the
        chunk's progress not yet applied; where either would be, the chunk
        is fetched and applied first. Asked before anything is taken."""
        if start + tokens == int(req.prompt.size):
            return "last_chunk"
        dec = self.sched.decode_requests()
        if not dec:
            return "no_rows"
        return (None if self.sched.grows_without_preemption(dec)
                else "pages")

    def _land_chunk(self, obs, sent: "_Enqueued", span=None) -> bool:
        """A chunk's token fetched and its progress applied
        (``serving/prefill_chunk/fetch`` and ``.../apply``, under ``span``:
        the chunk's span where it is still open, else one of its own, for a
        chunk that waited for the decode step's enqueue; ``tokens`` and the
        program's counts go onto the span that holds the fetch). A request
        that was cancelled, expired or preempted behind the chunk's enqueue
        is dropped as ``_land`` drops a row (``dropped_rows``): what the
        chunk wrote lies in pages and a state slot that their next owner
        writes over in a later program, and its progress is applied to
        nobody. A decode step enqueued behind the chunk counts its interval
        from here, and a chunk that was enqueued AHEAD, behind a decode step
        in flight, its own from that step's fetch (``_Enqueued.since``): no
        second is counted twice. The span of such a chunk says so (``ahead``,
        and ``late`` as its enqueue read it). Returns whether the progress
        was applied."""
        (req, row), = sent.rows
        if span is None:
            with self._chunk_span(obs, req, sent.start, sent.ahead) as span:
                return self._land_chunk(obs, sent, span)
        if self._chunk is sent:
            self._chunk = None
        tok, t1 = self._fetch(obs, sent)
        if self._flight is not None:
            self._flight.since = t1
        t0 = sent.t0 if sent.since is None else sent.since
        return self._apply_chunk(obs, sent, span, tok, t0, t1)

    def _apply_chunk(self, obs, sent: "_Enqueued", span, tok, t0: float,
                     t1: float) -> bool:
        """``serving/prefill_chunk/apply`` under ``span``: the progress of a
        chunk whose program ran from ``t0`` to ``t1``, as ``_land_chunk``
        says. ``tok`` is what the chunk program returned, or None for the
        chunk of a MIXED step, which returns nothing for it (it is never its
        prompt's last) and whose seconds the step's own account holds."""
        (req, _), = sent.rows
        n_valid = sent.tokens
        with obs.span("serving/prefill_chunk/apply", category="phase"):
            if tok is not None:
                tok = self._program_counts(span, tok, 1, real_rows=1,
                                           tokens=n_valid)
                if self._serve_acct is not None:
                    self._serve_acct.note_phase("prefill", t1 - t0)
            span.annotate(tokens=n_valid)   # the chunk ran: a span
            #   without the count is a chunk the pool could not place, or
            #   the prepare and dispatch of one that waited for its fetch
            if sent.late is not None:
                span.annotate(late=sent.late)
            self.prefill_chunks_run += 1
            self.prefill_tokens_run += n_valid
            if self._chunk_dropped(sent):
                span.annotate(dropped_rows=1)
                return False
            rt = obs.reqtrace
            if rt is not None and req.trace is not None:
                rt.interval(req.trace, "prefill", t0, t1,
                            kind="prefill_chunk", tokens=n_valid,
                            chunk_start=sent.start, replica=self.trace_tag)
            req.prefill_pos += n_valid
            req.length = req.prefill_pos
            # newly completed full prompt blocks become shareable prefix
            # cache
            self.sched.note_prefill_progress(req, sent.start, req.prefill_pos)
            self.sched.note_service(req, n_valid)
            if tok is not None and req.prefill_pos == int(req.prompt.size):
                self._finish_prefill(obs, req, int(tok[0]))
        return True

    def _finish_prefill(self, obs, req: Request, token: int) -> None:
        """The prompt's last chunk ran: the request decodes from here."""
        req.state = DECODE
        # the COW fork point for submit(n=...): siblings share the
        # freshly prefilled blocks BEFORE the parent can finish (a
        # max_new_tokens=1 parent releases its refs in _apply below;
        # the siblings' increfs keep the blocks alive)
        self._submit_pending_forks(req)
        if req.resume:
            # recompute after preemption: the stored pending token is
            # authoritative (identical under greedy; under temperature
            # sampling the resampled one may diverge) and was already
            # streamed — never re-emit
            req.resume = False
        else:
            # a first token is never kept: one push, and TTFT is a latency
            # callers feel
            self._apply(req, token, first=True)
            self._flush()
        if (self.on_prefill_complete is not None
                and req.state == DECODE):
            # still DECODE: a max_new_tokens=1 request already finished
            # in _apply above and has nothing left to hand off.
            # tpusync: disable=callback-under-lock — router-bound seam,
            # not user code; the handoff must see the request frozen at
            # prefill completion, so it runs under the engine lock
            self.on_prefill_complete(req)

    # -- parallel-sampling fork (COW) --------------------------------------
    def _submit_pending_forks(self, req: Request) -> None:
        """Parent finished prefill: attach each waiting sibling to the
        SAME physical blocks (incref — shared until first divergent write)
        and hand it to the scheduler, fully prefilled. The sibling's
        ``pending_token`` is the final prompt token at ``length =
        n_prompt - 1``: its first decode re-runs only that one position —
        a COW copy of at most one block — and samples its own first token
        with its own seed at output-token index 0, bit-identical to a
        separately submitted request."""
        sibs = self._pending_forks.pop(req.rid, None)
        if not sibs:
            return
        for sib in sibs:
            sib.blocks = list(req.blocks)
            self.alloc.incref(sib.blocks)
            sib.prefill_pos = int(sib.prompt.size)
            sib.length = sib.n_prompt - 1
            sib.pending_token = int(sib.prompt[-1])
            self.sched.submit_forked(sib)
            self._forks += 1

    def fork(self, handle: RequestHandle, n: int,
             seeds: Optional[List[int]] = None) -> List[RequestHandle]:
        """Mid-stream fork: ``n`` new samples branching off ``handle``'s
        request AT ITS CURRENT POSITION — shared prompt AND
        generated-so-far blocks (incref; first divergent write goes
        copy-on-write), inherited emitted tokens, divergence from the next
        token on (each sibling samples output-token index
        ``len(generated)`` with its own seed). The parent must be actively
        decoding. Returns the new handles."""
        if n < 1:
            raise ValueError(f"fork(n={n}): need n >= 1")
        self._no_state_snapshot("fork()")
        if seeds is not None and len(seeds) < n:
            raise ValueError(f"fork(n={n}): seeds has {len(seeds)} "
                             "entries — need one per sibling")
        with self._lock:
            self._bring_home()   # the parent's handle holds what siblings
            #   inherit, and its state is theirs
            req = handle._req
            if req.state != DECODE:
                raise ValueError(
                    f"request {req.rid}: fork requires an actively "
                    f"decoding request (state='{req.state}')")
            if (self.sched.in_flight() + self._pending_fork_count() + n
                    > self.config.max_queue):
                from .scheduler import QueueFull

                raise QueueFull(
                    f"serving queue cannot take {n} forked samples")
            out: List[RequestHandle] = []
            now = self.clock()
            for i in range(n):
                sib = Request(
                    rid=self._rid, prompt=req.prompt.copy(),
                    max_new_tokens=req.max_new_tokens,
                    sampling=req.sampling, eos_token_id=req.eos_token_id,
                    tenant=req.tenant,
                    seed=(seeds[i] if seeds is not None
                          else req.seed + i + 1),
                    fork_of=req.rid, n_prompt=req.n_prompt)
                self._rid += 1
                self._trace_start(sib, parent_trace=req.trace)
                sib.generated = list(req.generated)
                sib.pending_token = req.pending_token
                sib.length = req.length
                sib.prefill_pos = int(sib.prompt.size)
                sib.blocks = list(req.blocks)
                self.alloc.incref(sib.blocks)
                if sib.generated:
                    sib.first_token_s = now   # inherited tokens are
                    #   already streamed below — TTFT is fork-time
                self.sched.submit_forked(sib)
                h = RequestHandle(self, sib)
                for t in sib.generated:
                    h._push(t)
                self._handles[sib.rid] = h
                out.append(h)
                self._forks += 1
            return out

    def _ready_decode_rows(self, dec: List[Request]) -> List[Request]:
        """The decode-readiness discipline shared by the plain and
        speculative iterations, over ``sched.decode_requests()``: guarantee
        the pending token's block for every decoding row (this may evict),
        then keep only rows that are still DECODE, have block coverage for
        the incoming write, and whose write block is exclusively owned."""
        for r in dec:
            # re-check state INSIDE the loop: an earlier ensure_blocks may
            # have evicted this very request — growing a now-QUEUED request
            # would hand pool blocks to a non-running request (and, pool
            # dry, let it evict an active one)
            if r.state == DECODE:
                self.sched.ensure_blocks(r, r.length + 1)
        ready = []
        for r in dec:
            if r.state != DECODE:
                continue
            if len(r.blocks) * self.config.block_size <= r.length:
                continue
            # the incoming token's block must be exclusively owned —
            # writing into a prefix-shared block would corrupt the sharers
            if not self._make_writable(r, r.length, r.length + 1):
                continue
            ready.append(r)
        # a later row's COW may have preempted an earlier accepted row
        return [r for r in ready if r.state == DECODE]

    def _decode_operands(self, ready: List[Request], ahead: bool = False):
        """The decode program's operands, one row per decode row, packed
        into the ONE host array it takes (``paged_kv.pack_decode_rows``): a
        new array each step, since the dispatched call may still read the
        last one. ``ahead``: reckoned from the state the step in flight
        will leave, one token on in length and in the sampling stream, and
        the token itself -1: the program takes it from what that step
        returned, on the device."""
        R = self.config.max_seqs
        on = int(ahead)
        bt = np.zeros((R, self.blocks_per_seq), np.int32)
        lengths = np.zeros((R,), np.int32)
        tokens = np.zeros((R,), np.int32)
        temps = np.zeros((R,), np.float32)
        topks = np.zeros((R,), np.int32)
        topps = np.ones((R,), np.float32)
        seeds = np.zeros((R,), np.int32)
        steps = np.zeros((R,), np.int32)
        for r in ready:
            row = r.row
            bt[row, :len(r.blocks)] = r.blocks
            lengths[row] = r.length + on
            tokens[row] = -1 if ahead else r.pending_token
            temps[row] = r.sampling.temperature
            topks[row] = r.sampling.top_k
            topps[row] = r.sampling.top_p
            seeds[row] = r.seed
            steps[row] = len(r.generated) + on   # output-token index: the
            #   sampling stream is (engine seed, request seed, index) —
            #   schedule-independent and preemption-stable
        return paged_kv.pack_decode_rows(bt, lengths, tokens, temps, topks,
                                         topps, seeds, steps)

    def _ahead_held_by(self, flight: "_Enqueued") -> Optional[str]:
        """What keeps the decode step behind ``flight``, which is enqueued
        and not fetched, from being enqueued before that fetch, as far as
        the engine's state says (its rows and pages: ``_rows_ahead``): the
        first, in this order, of these names of ``HELD_BY``, or None where
        nothing waits for the device. ``row_freed``: a row has come free
        since this was last asked; a freed row is about to be taken, and the
        chunk that takes it must find the device as free as it would without
        a step ahead. ``queued``: the queue is not empty. ``fork``: a
        sibling waits for its fork. ``prefill``: a running request is not a
        row of ``flight`` (it is in prefill, or found no page). ``drafter``:
        a drafter proposes. ``deadline``: a deadline has passed.

        A MIXED step is asked neither ``row_freed``, ``queued`` nor
        ``fork``: its successor carries the same prompt's next chunk, and
        whoever takes a freed row, waits in the queue or waits for a fork
        at that prompt's end gets no chunk of its own before that prompt's
        last, step ahead or not (the oldest admission in prefill goes
        first); the admission itself has run by the time this is asked
        (``_step_locked``), as it does with a chunk in flight, which these
        three never held either (``_chunk_held_by``). And ``prefill`` asks
        it of the requests that DECODE: the ones in prefill are its chunk's
        and those that wait behind it."""
        if flight.chunk is None:
            freed = self.sched.rows_released != self._rows_released_seen
            self._rows_released_seen = self.sched.rows_released
            if freed:
                return "row_freed"
            if self.sched.queued:
                return "queued"
            if self._pending_forks:
                return "fork"
            others = len(self.sched.running)
        else:
            others = len(self.sched.decode_requests())
        if others != len(flight.rows):
            return "prefill"
        if self._drafter is not None and not self.spec_suspended:
            return "drafter"
        if self.sched.deadline_due(self.clock):
            return "deadline"
        return None

    def _rows_ahead(self, flight: "_Enqueued") -> tuple:
        """(rows, None): the rows of the step to enqueue AHEAD of
        ``flight``'s fetch: ``flight``'s rows less those that end there by
        their ``max_new_tokens``, each with the page its next position needs
        taken from the free list or from an UNPINNED prefix-cache entry,
        which no request holds and no program in flight reads (no
        preemption and no copy-on-write on behalf of a step ahead; until PR
        63 the free list alone, which a pool that has run for a while does
        not have: released blocks live on as cache entries, and every step
        of the latent cell's second generation of requests waited for its
        predecessor's fetch, 3 ms of host round in 19). Or (None, the name of
        ``HELD_BY`` that says why it must wait for that fetch):
        ``_ahead_held_by``'s, else ``ends`` (every row ends at ``flight``),
        ``pages`` (neither gives what the rows need) or ``cow``
        (a row would write into a shared block). The successor of a MIXED
        step carries its prompt's next chunk, so that chunk's rules are
        asked with the rows' (the union of the two): ``dropped`` (the
        chunk's request ended or lost its row under the step) and
        ``_chunk_held_by``'s names, for the chunk that begins where the
        step's ends; and ``pages`` is then the chunk's rule for the rows'
        pages too: they come from the free list or from UNPINNED
        prefix-cache entries, which no request holds and no program in
        flight reads (a pool that has run for a while has no free page: a
        decode step behind its chunk took its rows' pages so,
        ``_chunk_first_by``, and its successor of one program does). All of
        it is asked before anything is taken."""
        held_by = self._ahead_held_by(flight)
        if held_by is not None:
            return None, held_by
        # (a row whose request expired at the admission that ran in a mixed
        # step's shadow is its request's no more)
        rows = [r for r, row in flight.rows
                if r.state == DECODE and r.row == row
                and len(r.generated) + 1 < r.max_new_tokens]
        if not rows:
            return None, "ends"
        bs = self.config.block_size
        need = sum(max(paged_kv.blocks_for_tokens(r.length + 2, bs)
                       - len(r.blocks), 0) for r in rows)
        mixed = flight.chunk is not None
        if not self.sched.pages_without_preemption(need):
            return None, "pages"
        if any(self.sched.cow_block_indices(r, r.length + 1, r.length + 2)
               for r in rows):
            return None, "cow"
        if mixed:
            part = flight.chunk
            (req, _), = part.rows
            held_by = ("dropped" if self._chunk_dropped(part)
                       else self._chunk_held_by(
                           req, part.start + part.tokens, rows_need=need))
            if held_by is not None:
                return None, held_by
        for r in rows:
            # (``ensure_blocks`` evicts unpinned entries where the free list
            # is short, and was shown above to need nobody's row)
            self.sched.ensure_blocks(r, r.length + 2)
        return rows, None

    def _step_decode(self, ahead: Optional[List[Request]] = None) -> bool:
        """One decode step enqueued, and one brought to the host. ``ahead``
        (the driver thread, ``_rows_ahead``): the rows of a step that is
        enqueued with its predecessor in flight; the predecessor is then
        fetched and applied in this step's shadow, and its tokens are
        delivered at once. Else the step is enqueued behind the state the
        host holds, and fetched here too, unless the driver thread may run
        the next one ahead of it (``_ahead_held_by`` names nothing): then it
        stays in flight for the next iteration. With the iteration's chunk
        still in flight (``_chunk``: ``_step_prefill`` left it there) the
        step is enqueued BEHIND THE CHUNK, and then the chunk is landed, the
        prompt's next chunk enqueued behind the step where nothing keeps it
        back (``_chunk_ahead``), and the step landed, each in a span of its
        own; with a chunk ahead the step's tokens are delivered at once, the
        device being busy. While the span
        records it says of a step enqueued behind a program in flight
        whether it came too ``late`` (that program's tokens were ready at
        the enqueue: the device stood idle between the two), of a step
        whose chunk was fetched first which rule had it so
        (``chunk_first_by``), of a step fetched with no successor
        enqueued which rule held that one (``held_by``, in ``_land``), and
        of a step behind its chunk that was fetched with no chunk enqueued
        ahead which rule kept that one back (``chunk_held_by``).

        **The mixed step** (a configuration whose layers mix). Where
        ``_step_prefill`` kept the iteration's chunk back (``_mix``: no rule
        of ``CHUNK_FIRST_BY`` named itself), the rows and the chunk go as
        ONE program (``_mixed``), one record that carries both, and its span
        says ``mixed`` 1 and ``chunk_tokens``; it stays in flight under the
        rules of any step. Its successor (``ahead``, with ``_flight`` such a
        step: ``_rows_ahead`` asked the chunk's rules too) takes the
        prompt's next chunk, from where the step in flight will leave the
        prompt: a mixed step again where that chunk is not the last, and
        where it is, the chunk program of its own enqueued behind the step
        (``_chunk_ahead``) and no step ahead, so that the last chunk finds
        the next iteration as it always did."""
        obs = get_session()
        mix, self._mix = self._mix, None
        before = self._flight
        if ahead and before.chunk is not None:
            part = before.chunk
            (req, _), = part.rows
            start = part.start + part.tokens
            if start + self.config.prefill_chunk >= int(req.prompt.size):
                # the LAST chunk brings a first token, which must not wait
                # for the rows' walks and head: the chunk program of its
                # own, behind the step, and no successor ahead. The next
                # iteration finds it in flight and lands it first
                self._land_behind(
                    obs, before, self._chunk_ahead(obs, req, before, start))
                return True
            with self._chunk_span(obs, req, start, True):
                prepared = self._prepare_chunk(obs, req, start)
            # None: the pool could not place it after all, and the rows go
            # ahead as the decode step they are
            mix = prepared and (req, *prepared)
        dec = ahead or self.sched.decode_requests()
        chunk = self._chunk
        ready = []
        if dec:
            ready = self._enqueue_step(obs, dec, ahead, chunk, mix)
        if mix is not None and not ready:
            # no row could take its page after all: the chunk alone
            req, *prepared = mix
            with self._chunk_span(obs, req, prepared[1]) as span:
                sent = self._enqueue_chunk(obs, req, *prepared)
                if self._deferring:
                    self._settle(obs, deferred=True)
                self._land_chunk(obs, sent, span)
            return True
        if not ready:
            return False
        if chunk is not None:
            # neither program's span inside the other's. A request is still
            # in prefill (the chunk was not its prompt's last), so no STEP
            # stays in flight at the iteration's end: the rule is known
            # without being asked. That request's next chunk may
            sent = self._flight
            (req, _), = chunk.rows
            kept_by = (self._chunk_ahead(obs, req, sent)
                       if self._land_chunk(obs, chunk) else "dropped")
            self._land_behind(obs, sent, kept_by)
        return True

    def _land_behind(self, obs, sent: "_Enqueued",
                     kept_by: Optional[str]) -> None:
        """The step ``sent`` landed in a ``serving/decode`` span of its own
        with a request still in prefill (``held_by`` ``prefill``), behind
        the attempt to enqueue that request's next chunk behind it
        (``kept_by``: the name of ``CHUNK_HELD_BY`` that kept the chunk
        back, None where it went); with a chunk in flight the step's tokens
        are delivered at once, the device being busy."""
        with obs.span("serving/decode", cpu=True,
                      max_rows=self.config.max_seqs) as span:
            if kept_by is not None:
                span.annotate(chunk_held_by=kept_by)
            self._land(obs, sent, span, held_by="prefill")
            if self._chunk is not None:
                self._flush(deferred=True)
        self._apply_landed(obs)

    def _enqueue_step(self, obs, dec: List[Request],
                      ahead: Optional[List[Request]],
                      chunk: Optional["_Enqueued"],
                      mix: Optional[tuple]) -> List[Request]:
        """The ``serving/decode`` span of ``_step_decode`` that prepares and
        enqueues the step (decode rows alone, or with ``mix``, the prepared
        chunk, as one mixed step) and lands what the rules say is landed
        inside it. Returns the rows the step took; none: nothing was
        enqueued."""
        with obs.span("serving/decode", cpu=True,
                      max_rows=self.config.max_seqs) as span:
            with obs.span("serving/decode/prepare", category="phase"):
                ready = ahead or self._ready_decode_rows(dec)
                packed = (self._decode_operands(ready, bool(ahead))
                          if ready else None)
                mixed = bool(ready) and mix is not None
                req, chunk_packed, start, tokens = mix if mixed else (
                    None, None, 0, 0)
                span.annotate(rows=len(ready), ahead=int(bool(ahead)),
                              behind_chunk=int(bool(ready)
                                               and chunk is not None),
                              mixed=int(mixed), chunk_tokens=tokens,
                              sampled_rows=self._sampled_rows(ready),
                              **self._loop_counts)
                if ready and span.recording:
                    # the kernels' module counts the pages its walks meet
                    # and those that take the whole-tile form
                    from ..ops.paged_decode_attention import walk_page_counts
                    latent = bool(self._latent_pools)
                    span.annotate(**walk_page_counts(
                        paged_kv.packed_decode_lengths(packed),
                        self._arena["latent" if latent else "k"], latent))
                if ready and chunk is None and not mixed \
                        and self._chunk_first:
                    span.annotate(chunk_first_by=self._chunk_first)
            if not ready:
                return ready
            first_trace = (next((r.trace for r in ready
                                 if r.trace is not None), None)
                           if obs.reqtrace is not None else None)
            before = self._flight
            if mixed:
                sent = self._enqueue(
                    obs, "serving/decode", self._mixed, packed, chunk_packed,
                    self._base_rng, self._last_tokens, trace=first_trace)
                sent.chunk = self._chunk_of(
                    _Enqueued("serving/prefill_chunk", None, sent.t0,
                              sent.t_call, 0.0, ahead=bool(ahead)),
                    req, start, tokens)
            else:
                sent = self._enqueue(
                    obs, "serving/decode", self._decode, packed,
                    self._base_rng, self._last_tokens, trace=first_trace)
            self._flight = sent
            sent.rows = [(r, r.row) for r in ready]
            self._last_tokens = sent.tok
            behind = chunk or before
            if behind is not None and (span.recording or obs.enabled):
                # one non-blocking question to the array the engine holds
                late = int(behind.tok.is_ready())
                span.annotate(late=late)
                if mixed:
                    sent.chunk.late = late
                if late and obs.enabled:
                    obs.registry.counter(
                        "serving/steps_enqueued_late",
                        help="decode steps enqueued behind a program in "
                             "flight (ahead, or behind their chunk) that "
                             "had already ended: the host's round outlasted "
                             "it").inc()
            if mixed and obs.enabled:
                obs.registry.counter(
                    "serving/mixed_steps",
                    help="decode steps that carried a prompt chunk (not its "
                         "prompt's last) as ONE program with their rows (a "
                         "configuration whose layers mix)").inc()
            if self._deferring:
                self._settle(obs, deferred=True)
            if chunk is not None:
                if obs.enabled:
                    obs.registry.counter(
                        "serving/decodes_enqueued_behind_chunk",
                        help="decode steps enqueued with their iteration's "
                             "chunk not yet fetched (the driver thread's "
                             "form)").inc()
            elif before is not None:
                if obs.enabled:
                    obs.registry.counter(
                        "serving/steps_enqueued_ahead",
                        help="decode steps enqueued with their "
                             "predecessor's tokens not yet on the host "
                             "(the driver thread's form)").inc()
                self._land(obs, before, span)
                self._flush(deferred=True)   # at once: the device is busy
            elif held_by := ("step_mode" if not self._deferring
                             else self._ahead_held_by(sent)):
                self._land(obs, sent, span, held_by=held_by)
        self._apply_landed(obs)
        return ready

    def _land(self, obs, sent: "_Enqueued", span=None,
              held_by: Optional[str] = None) -> None:
        """A decode step's tokens fetched and applied
        (``serving/decode/fetch`` and ``.../apply``, under ``span``: the
        ``serving/decode`` span that is open, else one of its own).
        ``held_by``: the step is fetched with its successor not enqueued,
        and this name of ``HELD_BY`` is why; the span carries it. A row
        whose request ended behind the step's enqueue (by its
        ``eos_token_id`` at the step before, with this one ahead) is
        dropped: its token goes nowhere, and what it wrote lies in pages
        and a state slot that whoever takes them next writes over, in a
        program enqueued behind this one. The interval of a step enqueued
        ahead, or behind a chunk in flight, runs for the accountant and the
        request tracer from that program's fetch to its own, and that of
        whatever is enqueued behind this step from here: no second is
        counted twice. A MIXED step's fetch lands its chunk too, over the
        step's own interval: its progress is applied as soon as the span
        that holds the fetch has closed (``_apply_landed``: neither
        program's span lies inside the other's), which whoever handed
        ``span`` sees to."""
        if span is None:
            with obs.span("serving/decode", cpu=True,
                          max_rows=self.config.max_seqs) as span:
                self._land(obs, sent, span, held_by)
            return self._apply_landed(obs)
        if self._flight is sent:
            self._flight = None
        if held_by is not None:
            span.annotate(held_by=held_by)
        nxt, t1 = self._fetch(obs, sent)
        for behind in (self._flight, self._chunk):
            if behind is not None:
                behind.since = t1
        t0 = sent.t0 if sent.since is None else sent.since
        rt = obs.reqtrace
        acct = self._serve_acct
        if sent.chunk is not None:
            self._landed = (sent.chunk, t0, t1)
        with obs.span("serving/decode/apply", category="phase"):
            live = [(r, row) for r, row in sent.rows
                    if r.state == DECODE and r.row == row]
            nxt = self._program_counts(span, nxt, self.config.max_seqs,
                                       real_rows=len(sent.rows),
                                       tokens=len(sent.rows))
            if len(live) < len(sent.rows):
                span.annotate(dropped_rows=len(sent.rows) - len(live))
            if acct is not None:
                acct.note_phase("decode", t1 - t0)
            if rt is not None:
                for r, _ in live:
                    if r.trace is not None:
                        rt.note_decode(r.trace, t0, t1, batch=len(live),
                                       replica=self.trace_tag)
            for r, row in live:
                r.length += 1
                self.sched.note_service(r, 1)
                self._apply(r, int(nxt[row]))
            if not self._deferring:
                self._flush()
            if acct is not None:
                acct.note_phase("sample_host", self.clock() - t1)

    def _apply_landed(self, obs) -> None:
        """The progress of the chunk that the mixed step just fetched
        carried (``_land`` left it: ``_landed``), applied in a
        ``serving/prefill_chunk`` span of its own, behind the
        ``serving/decode`` span that holds the fetch, which says ``tokens``,
        ``ahead`` and ``late`` as every chunk's span does."""
        if self._landed is None:
            return
        (part, t0, t1), self._landed = self._landed, None
        (req, _), = part.rows
        with self._chunk_span(obs, req, part.start, part.ahead) as span:
            self._apply_chunk(obs, part, span, None, t0, t1)

    def _bring_home(self, held_by: Optional[str] = None) -> None:
        """A settled engine, for whoever needs one (under the engine lock):
        the decode step in flight, if the driver thread left one, is
        fetched and applied (``held_by``: as ``_land``; a caller that needs
        the engine settled gives none), or the chunk it left in flight
        (``_chunk_ahead``), and every applied token is delivered."""
        if self._flight is not None:
            self._land(get_session(), self._flight, held_by=held_by)
        if self._chunk is not None:
            self._land_chunk(get_session(), self._chunk)
        self._flush()

    def _step_verify(self) -> bool:
        """The speculative iteration: one R×(K+1) verify dispatch replaces
        the R×1 decode. Every decoding row rides it — rows with no (or
        pressure-disabled) proposals verify only their pending token,
        which IS the plain decode — so per-row proposal counts and
        acceptance mixes are data under ONE compiled program. Accepted
        tokens advance lengths/blocks on the host; rejected draft KV rolls
        back by position (whole blocks past the accepted length return to
        the pool)."""
        dec = self.sched.decode_requests()
        if not dec:
            return False
        obs = get_session()
        with obs.span("serving/verify", cpu=True,
                      max_rows=self.config.max_seqs) as span:
            return self._verify_rows(obs, span, dec)

    def _verify_rows(self, obs, span, dec: List[Request]) -> bool:
        """``_step_verify`` inside its ``serving/verify`` span, whose self
        time is the drafter and the plan."""
        # the guaranteed (pending-token) block may evict via
        # _ready_decode_rows — speculation itself never does
        ready = self._ready_decode_rows(dec)
        if not ready:
            return False
        spec = self.config.speculative
        K = spec.num_draft_tokens
        S = K + 1
        # per-row proposal budget: output budget (the verify emits up to
        # cap+1 tokens), model-length budget, and the global pool guard
        low_pool = self.alloc.blocks_free < spec.min_free_blocks
        caps = []
        for r in ready:
            cap = min(K,
                      r.max_new_tokens - len(r.generated) - 1,
                      self.config.max_model_len - r.length - 1)
            caps.append(0 if low_pool else max(cap, 0))
        t0 = self.clock()
        proposals = self._drafter.propose(ready, caps)
        draft_s = self.clock() - t0
        self._spec_draft_s += draft_s
        if self._serve_acct is not None:
            self._serve_acct.note_phase("draft", draft_s)
        # speculating may preempt nothing, but the drafter's catch-up runs
        # under the engine lock with live state — re-check anyway
        plan = []
        for r, cap, prop in zip(ready, caps, proposals):
            prop = np.asarray(prop, np.int32).reshape(-1)[:cap]
            n = int(prop.size)
            if n > 0 and not self.sched.try_extend_blocks(
                    r, r.length + 1 + n):
                # pool says no: speculate only as far as already-held
                # blocks reach (possibly 0) — never evict for speculation
                held = len(r.blocks) * self.config.block_size \
                    - (r.length + 1)
                n = max(min(n, held), 0)
                self._spec_disabled_rows += 1
            if n > 0 and not self._make_writable(
                    r, r.length + 1, r.length + 1 + n, optional=True):
                n = 0   # shared draft-range block with no COW budget
            plan.append((r, prop[:n]))
        # a later row's COW/extension bookkeeping may have preempted an
        # earlier planned row — plan only rows still decoding
        plan = [(r, p) for r, p in plan if r.state == DECODE]
        if not plan:
            return False
        R = self.config.max_seqs
        bt = np.zeros((R, self.blocks_per_seq), np.int32)
        lengths = np.zeros((R,), np.int32)
        tokens = np.zeros((R, S), np.int32)
        n_valid = np.zeros((R,), np.int32)
        temps = np.zeros((R,), np.float32)
        topks = np.zeros((R,), np.int32)
        topps = np.ones((R,), np.float32)
        seeds = np.zeros((R,), np.int32)
        steps = np.zeros((R,), np.int32)
        for r, prop in plan:
            row = r.row
            bt[row, :len(r.blocks)] = r.blocks
            lengths[row] = r.length
            tokens[row, 0] = r.pending_token
            if prop.size:
                tokens[row, 1:1 + prop.size] = prop
            n_valid[row] = 1 + prop.size
            temps[row] = r.sampling.temperature
            topks[row] = r.sampling.top_k
            topps[row] = r.sampling.top_p
            seeds[row] = r.seed
            steps[row] = len(r.generated)   # first output-token index of
            #   this dispatch — position j samples index steps+j, the
            #   exact key the non-speculative path uses
        span.annotate(rows=len(plan), tokens=int(n_valid.sum()),
                      sampled_rows=self._sampled_rows(r for r, _ in plan))
        if self._chunk_first:
            span.annotate(chunk_first_by=self._chunk_first)
        rt = obs.reqtrace
        first_trace = (next((r.trace for r, _ in plan
                             if r.trace is not None), None)
                       if rt is not None else None)
        sampled, t0, t1 = self._run_program(
            obs, "serving/verify", self._verify,
            paged_kv.pack_verify_rows(bt, lengths, tokens, n_valid, temps,
                                      topks, topps, seeds, steps),
            self._base_rng, trace=first_trace)
        with obs.span("serving/verify/apply", category="phase"):
            self._accept_verified(rt, plan, sampled, t0, t1)
        return True

    def _accept_verified(self, rt, plan, sampled, t0, t1) -> None:
        """A verify step's tokens on the host: acceptance row by row, the
        accepted tokens applied, the rejected drafts' blocks rolled back,
        and the delivery (``serving/verify/apply``)."""
        acct = self._serve_acct
        self._spec_verify_s += t1 - t0
        if acct is not None:
            acct.note_phase("verify", t1 - t0)
        if rt is not None:
            for r, _ in plan:
                if r.trace is not None:
                    rt.note_decode(r.trace, t0, t1, kind="verify",
                                   batch=len(plan), replica=self.trace_tag)
        self._spec_dispatches += 1
        for r, prop in plan:
            x = sampled[r.row]
            a = 0   # accepted drafts: x[j] (the sample after draft j)
            #   must CONFIRM draft j — first mismatch emits x[a] as the
            #   correction, full acceptance emits x[cap] as the bonus
            while a < prop.size and int(x[a]) == int(prop[a]):
                a += 1
            r.spec_proposed += int(prop.size)
            r.spec_accepted += a
            self._spec_proposed += int(prop.size)
            self._spec_accepted += a
            for t in x[:a + 1]:
                r.length += 1
                self.sched.note_service(r, 1)
                self._apply(r, int(t))
                self._spec_emitted += 1
                if r.done:
                    break   # EOS/budget mid-verify: later samples are
                    #   beyond the request's end — never emitted
            if not r.done:
                # positional rollback: whole blocks past the accepted
                # length go back to the pool; the drafter rolls its arena
                # back the same way
                self.sched.truncate_blocks(r, r.length)
                self._drafter.commit(r)
        self._flush()   # a verify step's tokens are never kept: its
        #   drafter runs on the host before the next enqueue
        if acct is not None:
            acct.note_phase("sample_host", self.clock() - t1)

    def _apply(self, req: Request, token: int, first: bool = False) -> None:
        """One sampled token, as far as the NEXT program's operands depend
        on it: the request's tokens, the finish test, and for a finished
        request its row and blocks free for the next admit. Whatever only
        callers and measurement see waits in ``_undelivered`` for
        ``_flush``."""
        # ``first`` marks the prefill-completion token; a submit(n=...)
        # sibling skips prefill entirely (admitted straight to DECODE with
        # the parent's KV) and its first token arrives through the
        # decode/verify path — catch it by the unset timestamp so TTFT/
        # TPOT cover forked samples too
        first = first or req.first_token_s is None
        if first:
            req.first_token_s = self.clock()
        req.generated.append(token)
        req.pending_token = token
        finished = (len(req.generated) >= req.max_new_tokens
                    or (req.eos_token_id is not None
                        and token == req.eos_token_id))
        if finished:
            self.sched.finish(req)
        self._undelivered.append((req, token, first, finished))

    def _flush(self, deferred: bool = False) -> None:
        """Deliver every applied token, in the order applied: handles pushed
        (the last push of a request ends its stream), latency samples,
        counters, the request tracer, the accountant. Under the engine lock,
        on the thread that holds it. ``deferred``: called with a program of
        this iteration enqueued (``_run_program``)."""
        pending = self._undelivered
        if not pending:
            return
        self._undelivered = []
        obs = get_session()
        with obs.span("serving/emit", tokens=len(pending),
                      deferred=int(deferred)) as span:
            for item in pending:
                self._deliver(obs, *item)
            if span.recording:
                span.annotate(finished=sum(item[3] for item in pending))
        self._tokens_out += len(pending)
        if self._serve_acct is not None:
            self._serve_acct.note_tokens(len(pending))
        if obs.enabled:
            obs.registry.counter(
                "serving/tokens_out",
                help="tokens delivered to request handles").inc(len(pending))
            if deferred:
                obs.registry.counter(
                    "serving/tokens_delivered_in_shadow",
                    help="of serving/tokens_out, those delivered with the "
                         "next program already enqueued (the driver "
                         "thread's form)").inc(len(pending))

    def _deliver(self, obs, req: Request, token: int, first: bool,
                 finished: bool) -> None:
        if first:
            self._request_span(obs, "serving/request/first_token", req,
                               ttft_us=int(req.ttft_s * 1e6))
            if obs.enabled:
                ttft_ms = req.ttft_s * 1e3
                self._ttft_samples.append(ttft_ms)
                obs.registry.histogram(
                    "serving/ttft_ms",
                    help="entry to submit() → first streamed token, wall "
                         "ms").observe(
                        ttft_ms, tenant=req.tenant)
        if req.trace is not None:
            # live progress marker: a crash dump's in-flight tail must say
            # how far each stuck request got (finish() re-stamps the
            # authoritative count from len(generated))
            req.trace.tokens += 1
        handle = self._handles.get(req.rid)
        if handle is not None:
            handle._push(token, last=finished)
        if not finished:
            return
        if self._drafter is not None and req.spec_proposed:
            self._accept_samples.append(
                req.spec_accepted / req.spec_proposed)
        if obs.enabled:
            obs.registry.counter(
                "serving/requests_completed",
                help="requests that finished generation").inc(
                    tenant=req.tenant)
            tpot = req.tpot_s
            if tpot is not None:
                self._tpot_samples.append(tpot * 1e3)
                obs.registry.histogram(
                    "serving/tpot_ms",
                    help="mean per-token wall ms after the first "
                         "token").observe(tpot * 1e3, tenant=req.tenant)
        if self._serve_acct is not None:
            ttft, tpot = req.ttft_s, req.tpot_s
            self._serve_acct.note_request(
                ttft_ms=ttft * 1e3 if ttft is not None else None,
                tpot_ms=tpot * 1e3 if tpot is not None else None)
        self._trace_finish(req, "finished")
        self._handles.pop(req.rid, None)   # the client holds its own
        #   reference; keeping ours would leak one handle per request
        #   over a server's lifetime

    def _publish_iteration(self) -> None:
        obs = get_session()
        if not obs.enabled:
            return
        reg = obs.registry
        reg.gauge("serving/queue_depth",
                  help="requests waiting for admission").set(
                      self.sched.queue_depth())
        reg.gauge("serving/kv_blocks_in_use",
                  help="allocated arena blocks").set(self.alloc.blocks_in_use)
        reg.gauge("serving/kv_blocks_peak",
                  help="peak allocated arena blocks").set(
                      self.alloc.peak_in_use)
        reg.gauge("serving/arena_occupancy",
                  help="allocated fraction of the block pool").set(
                      self.alloc.blocks_in_use / max(self.alloc.capacity, 1))
        reg.gauge("serving/decode_batch_occupancy",
                  help="decoding rows / max_seqs").set(
                      len(self.sched.decode_requests())
                      / self.config.max_seqs)
        reg.gauge("serving/kv_blocks_shared",
                  help="arena blocks referenced by more than one "
                       "holder (prefix sharing)").set(
                      self.alloc.blocks_shared)
        reg.gauge("serving/kv_blocks_shared_peak",
                  help="peak concurrently-shared arena blocks").set(
                      self.alloc.peak_shared)
        if self.prefix is not None:
            reg.gauge("serving/prefix_hit_rate",
                      help="prompt tokens served from the prefix cache / "
                           "prompt tokens of admitted requests").set(
                          self.sched.prefix_hit_tokens
                          / max(self.sched.prefix_lookup_tokens, 1))
            reg.gauge("serving/prefix_cache_blocks",
                      help="blocks pinned by the prefix cache").set(
                          self.prefix.cached_blocks)
        new_cow = self._cow_copies - self._published_cow
        if new_cow:
            reg.counter("serving/cow_copies",
                        help="copy-on-write block duplications (first "
                             "write into a shared block)").inc(new_cow)
            self._published_cow = self._cow_copies
        new_preempt = self.sched.preemption_count \
            - self._published_preemptions
        if new_preempt:
            reg.counter("serving/preemptions",
                        help="requests evicted from the arena "
                             "(recompute on re-admission)").inc(new_preempt)
            self._published_preemptions = self.sched.preemption_count
        new_forks = self._forks - self._published_forks
        if new_forks:
            reg.counter("serving/forks",
                        help="parallel-sampling siblings forked through "
                             "the COW block tables").inc(new_forks)
            self._published_forks = self._forks
        if self._drafter is not None:
            p0, a0, d0, x0 = self._published_spec
            dp = self._spec_proposed - p0
            da = self._spec_accepted - a0
            dd = self._spec_dispatches - d0
            dx = self._spec_disabled_rows - x0
            if dp:
                reg.counter("serving/spec_proposed_tokens",
                            help="draft tokens sent to verify").inc(dp)
            if da:
                reg.counter("serving/spec_accepted_tokens",
                            help="draft tokens the verify confirmed").inc(da)
            if dd:
                reg.counter("serving/spec_verify_dispatches",
                            help="R×(K+1) verify program dispatches").inc(dd)
            if dx:
                reg.counter("serving/spec_disabled_rows",
                            help="row-iterations that skipped speculation "
                                 "under pool pressure").inc(dx)
            self._published_spec = (self._spec_proposed,
                                    self._spec_accepted,
                                    self._spec_dispatches,
                                    self._spec_disabled_rows)
            reg.gauge("serving/spec_acceptance_rate",
                      help="accepted / proposed draft tokens").set(
                          self._spec_accepted
                          / max(self._spec_proposed, 1))
            reg.gauge("serving/spec_emitted_per_dispatch",
                      help="tokens emitted per target verify dispatch "
                           "(> 1 is the speculative win)").set(
                          self._spec_emitted
                          / max(self._spec_dispatches, 1))
            spent = self._spec_draft_s + self._spec_verify_s
            if spent > 0:
                reg.gauge("serving/spec_draft_time_share",
                          help="drafter wall share of the speculative "
                               "decode loop").set(self._spec_draft_s
                                                  / spent)
        # steady-state marker for the recompile watchdog: past warmup, a
        # recompile under a serving span is a shape-discipline bug
        obs.note_step(self._iterations)

    # -- drivers -----------------------------------------------------------
    def run(self, max_steps: Optional[int] = None) -> int:
        """Step until every in-flight request is terminal (tests/benches).
        Returns the number of iterations run."""
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
                if starved > 2 * self.config.max_queue + 4:
                    raise RuntimeError(
                        "serving stalled: no request can make progress "
                        f"({self.sched.queue_depth()} queued, "
                        f"{self.alloc.blocks_free} free blocks) — the block "
                        "pool or row count is too small for the workload")
        return steps

    def start(self) -> None:
        """Background driver thread (the 'server' mode): steps while work is
        in flight, idles cheaply otherwise."""
        if self._thread is not None:
            return
        self._stop.clear()
        self._thread = threading.Thread(target=self._drive,
                                        name="dstpu-serving", daemon=True)
        self._thread.start()

    def _drive(self) -> None:
        try:
            while not self._stop.is_set():
                try:
                    # the poll takes the engine lock, behind callers in
                    # submit(): that wait, like the one when nothing is in
                    # flight, is the driver outside an iteration
                    with get_session().span("serving/idle"):
                        busy = self.in_flight()
                        if not busy:
                            # the last iteration's tokens, and a step
                            # whose every row ended under it, or a chunk
                            # whose request did: no program will be
                            # enqueued to deliver them behind
                            self._flush_locked()
                            self._stop.wait(0.002)
                    if busy:
                        self._iterate(defer=True)
                except Exception:
                    logger.exception("serving driver step failed")
                    get_session().crash_dump("serving-step-exception")
                    self._stop.wait(0.05)
        finally:
            self._flush_locked()

    def _flush_locked(self) -> None:
        with self._lock:
            self._bring_home()

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
            self._thread = None
        if t is None or not t.is_alive():
            # a step or a chunk that a driver's loop on some other thread
            # left in flight (the thread's own loop brings its own home)
            self._flush_locked()

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.stop()
        if self._tuner is not None:
            self._tuner.finalize()     # recommendations artifact
        if self._drafter is not None:
            self._drafter.close()
        if self._serve_acct is not None:
            self._serve_acct.publish()   # final bucket snapshot
        self.publish_latency_gauges()

    def publish_latency_gauges(self) -> None:
        """Host-side percentile gauges (the registry histogram keeps only
        count/sum/min/max): serving/ttft_p50_ms, p99, tpot p50/p99, and the
        end-to-end tokens/s — the ``report`` CLI's ``== serving ==``
        inputs."""
        obs = get_session()
        if not obs.enabled:
            return
        reg = obs.registry
        for name, samples in (("ttft", self._ttft_samples),
                              ("tpot", self._tpot_samples)):
            if samples:
                reg.gauge(f"serving/{name}_p50_ms").set(
                    _percentile(list(samples), 0.50))
                reg.gauge(f"serving/{name}_p99_ms").set(
                    _percentile(list(samples), 0.99))
        if self._accept_samples:
            reg.gauge("serving/spec_acceptance_p50",
                      help="per-request draft acceptance rate, median "
                           "over finished requests").set(
                          _percentile(list(self._accept_samples), 0.50))
        wall = max(self.clock() - self._started_s, 1e-9)
        reg.gauge("serving/tokens_per_sec",
                  help="generated tokens / wall seconds").set(
                      self._tokens_out / wall)

    def reset_latency_stats(self) -> None:
        """Drop the host-side latency reservoirs and restart the
        tokens/s window — benches call this after their warmup request so
        the published p50/p99/tokens_per_sec describe the measured load,
        not program compilation. The speculative ledger resets too: the
        warmup's verify/draft dispatches JIT-compile inside the timed
        accumulators, which would otherwise dominate draft_time_share and
        skew acceptance/emitted-per-dispatch."""
        with self._lock:
            self._bring_home()   # into the window that ends here
            self._ttft_samples.clear()
            self._tpot_samples.clear()
            self._accept_samples.clear()
            self._tokens_out = 0
            self._started_s = self.clock()
            self._spec_dispatches = 0
            self._spec_emitted = 0
            self._spec_proposed = 0
            self._spec_accepted = 0
            self._spec_disabled_rows = 0
            self._spec_draft_s = 0.0
            self._spec_verify_s = 0.0
            self._forks = 0
            # published snapshots must rewind with the raw counts or the
            # next _publish_iteration would compute negative counter deltas
            self._published_spec = (0, 0, 0, 0)
            self._published_forks = 0
            if self._serve_acct is not None:
                # warmup iterations carry compile-scale phases — the
                # published buckets must describe the measured load
                self._serve_acct.reset()

    # -- tpuaudit ----------------------------------------------------------
    def _audit_args_prefill(self):
        import jax
        import jax.numpy as jnp

        C, MAXB = self.config.prefill_chunk, self.blocks_per_seq
        return (self.engine._params_sds(),
                self._arena_sds(),
                jax.ShapeDtypeStruct(
                    paged_kv.chunk_shape(MAXB, C,
                                         bool(self.state_slots),
                                         self._chunk_says_last),
                    jnp.int32),
                jax.ShapeDtypeStruct((2,), jnp.uint32))

    def _arena_sds(self):
        from ..inference.kv_cache import paged_cache_shape_struct

        return paged_cache_shape_struct(
            self.engine.model.config, self.config.pool_blocks() + 1,
            self.config.block_size, self._dtype,
            state_slots=self.state_slots, ring_blocks=self._ring_blocks)

    def _register_audit_entries(self) -> List[str]:
        try:
            from tools.tpuaudit.registry import (StaleEntryError,
                                                 register_entry_point)
        except ImportError:
            return []
        try:
            import weakref

            import jax
            import jax.numpy as jnp

            wself = weakref.ref(self)
            expected = self.engine._audit_expected_collectives()
            R, MAXB = self.config.max_seqs, self.blocks_per_seq
            C = self.config.prefill_chunk
            # all params-consuming programs here serve the SAME weight tree
            # as the underlying InferenceEngine — same policy, same
            # exchange group (tools/tpushard cross-checks the chain)
            shard = self.engine._shard_tag()

            def build_prefill():
                eng = wself()
                if eng is None:
                    raise StaleEntryError("serving/prefill_chunk: "
                                          "engine gone")
                return eng._prefill, eng._audit_args_prefill(), {}

            def build_decode():
                eng = wself()
                if eng is None:
                    raise StaleEntryError("serving/decode: engine gone")
                args = (eng.engine._params_sds(), eng._arena_sds(),
                        jax.ShapeDtypeStruct(
                            paged_kv.decode_rows_shape(R, MAXB), jnp.int32),
                        jax.ShapeDtypeStruct((2,), jnp.uint32),
                        jax.ShapeDtypeStruct(eng._last_tokens.shape,
                                             jnp.int32))
                return eng._decode, args, {}

            register_entry_point(
                "serving/prefill_chunk", build=build_prefill,
                donate_argnums=(1,), expected_collectives=expected,
                mesh=self.engine.mesh,
                tags={"engine": "ServingEngine", "chunk": C,
                      "max_blocks": MAXB,
                      # one chunked-prefill run ingests C prompt tokens
                      "tokens_per_step": C, "shard": shard,
                      # lowered module name ("jit_<program>") — the deep
                      # profiler keys measured device time back to this
                      # entry through it
                      "program": "prefill_chunk"})
            register_entry_point(
                "serving/decode", build=build_decode, donate_argnums=(1,),
                expected_collectives=expected, mesh=self.engine.mesh,
                tags={"engine": "ServingEngine", "rows": R,
                      "max_blocks": MAXB,
                      # one decode iteration emits one token per row
                      "tokens_per_step": R, "shard": shard,
                      "program": "decode"})

            if self._mixed is not None:
                def build_mixed():
                    eng = wself()
                    if eng is None:
                        raise StaleEntryError("serving/mixed_step: "
                                              "engine gone")
                    params, arena, rows, key, last = build_decode()[1]
                    return (eng._mixed, (params, arena, rows,
                                         eng._audit_args_prefill()[2], key,
                                         last), {})

                register_entry_point(
                    "serving/mixed_step", build=build_mixed,
                    donate_argnums=(1,), expected_collectives=expected,
                    mesh=self.engine.mesh,
                    tags={"engine": "ServingEngine", "rows": R, "chunk": C,
                          "max_blocks": MAXB,
                          # a token a row and the chunk's C prompt tokens
                          "tokens_per_step": R + C, "shard": shard,
                          "program": "mixed_step"})

            def build_cow():
                eng = wself()
                if eng is None:
                    raise StaleEntryError("serving/cow_copy: engine gone")
                i32 = jnp.int32
                return (eng._cow, (eng._arena_sds(),
                                   jax.ShapeDtypeStruct((), i32),
                                   jax.ShapeDtypeStruct((), i32)), {})

            # pure arena block copy: slice-select + slice-update along the
            # (replicated) block axis — no resharding, hence no collectives
            # regardless of the engine's TP/EP declarations
            register_entry_point(
                "serving/cow_copy", build=build_cow, donate_argnums=(0,),
                expected_collectives=(), mesh=self.engine.mesh,
                tags={"engine": "ServingEngine",
                      "block_size": self.config.block_size,
                      "program": "cow_copy"})
            def build_score():
                eng = wself()
                if eng is None:
                    raise StaleEntryError("serving/score_chunk: engine gone")
                i32 = jnp.int32
                args = (eng.engine._params_sds(), eng._arena_sds(),
                        jax.ShapeDtypeStruct((1, MAXB), i32),
                        jax.ShapeDtypeStruct((1, C), i32),
                        jax.ShapeDtypeStruct((1, C), i32),
                        jax.ShapeDtypeStruct((), i32),
                        jax.ShapeDtypeStruct((), i32))
                return eng._score, args, {}

            # the RLHF teacher-forced scoring pass: prefill-shaped forward
            # returning target logprobs instead of samples — same engine
            # collectives, same arena donation
            register_entry_point(
                "serving/score_chunk", build=build_score,
                donate_argnums=(1,), expected_collectives=expected,
                mesh=self.engine.mesh,
                tags={"engine": "ServingEngine", "chunk": C,
                      "max_blocks": MAXB,
                      # one scoring chunk ingests C sequence tokens
                      "tokens_per_step": C, "shard": shard,
                      "program": "score_chunk"})
            names = ["serving/prefill_chunk", "serving/decode",
                     "serving/cow_copy", "serving/score_chunk"]
            if self._drafter is not None:
                names += self._register_spec_audit_entries(
                    register_entry_point, StaleEntryError, wself, expected)
            return names
        except Exception:   # registration must never take serving down
            logger.warning("tpuaudit serving registration failed",
                           exc_info=True)
            return []

    def _register_spec_audit_entries(self, register_entry_point,
                                     StaleEntryError, wself,
                                     expected) -> List[str]:
        import jax
        import jax.numpy as jnp

        R, MAXB = self.config.max_seqs, self.blocks_per_seq
        S = self.config.speculative.num_draft_tokens + 1
        i32 = jnp.int32

        def build_verify():
            eng = wself()
            if eng is None:
                raise StaleEntryError("serving/verify: engine gone")
            args = (eng.engine._params_sds(), eng._arena_sds(),
                    jax.ShapeDtypeStruct(
                        paged_kv.verify_rows_shape(R, MAXB, S), i32),
                    jax.ShapeDtypeStruct((2,), jnp.uint32))
            return eng._verify, args, {}

        register_entry_point(
            "serving/verify", build=build_verify, donate_argnums=(1,),
            expected_collectives=expected, mesh=self.engine.mesh,
            tags={"engine": "ServingEngine", "rows": R, "spec_tokens": S,
                  "max_blocks": MAXB,
                  # conservative floor: one verify dispatch emits AT LEAST
                  # one token per row (acceptance only adds to this)
                  "tokens_per_step": R,
                  "shard": self.engine._shard_tag(),
                  "program": "verify"})
        names = ["serving/verify"]
        drafter = self._drafter
        if not hasattr(drafter, "_decode"):    # host-side drafter: no
            return names                       # device programs to audit
        from ..inference.kv_cache import paged_cache_shape_struct

        dcfg = drafter.engine.model.config
        dexp = drafter.engine._audit_expected_collectives()
        C = drafter.draft_chunk
        # the draft model is a separate weight tree — its own shard group so
        # tpushard never cross-compares draft params with target params
        from ..parallel.rules import shard_tag
        dshard = shard_tag("serving", axes=drafter.engine.model.axes,
                           params_arg=0, expert_parallel=True,
                           group="serving-draft")

        def draft_arena_sds(eng):
            return paged_cache_shape_struct(
                dcfg, self.config.pool_blocks() + 1,
                self.config.block_size, eng._drafter._dtype)

        def build_draft_decode():
            eng = wself()
            if eng is None:
                raise StaleEntryError("serving/draft_decode: engine gone")
            args = (eng._drafter.engine._params_sds(), draft_arena_sds(eng),
                    jax.ShapeDtypeStruct(
                        paged_kv.decode_rows_shape(R, MAXB), i32),
                    jax.ShapeDtypeStruct((2,), jnp.uint32))
            return eng._drafter._decode, args, {}

        def build_draft_prefill():
            eng = wself()
            if eng is None:
                raise StaleEntryError("serving/draft_prefill: engine gone")
            args = (eng._drafter.engine._params_sds(), draft_arena_sds(eng),
                    jax.ShapeDtypeStruct(
                        paged_kv.chunk_shape(MAXB, C, False), i32),
                    jax.ShapeDtypeStruct((2,), jnp.uint32))
            return eng._drafter._prefill, args, {}

        register_entry_point(
            "serving/draft_decode", build=build_draft_decode,
            donate_argnums=(1,), expected_collectives=dexp,
            mesh=drafter.engine.mesh,
            tags={"engine": "ServingEngine", "rows": R,
                  "draft_model": True, "tokens_per_step": R,
                  # the drafter's decode lowers to the same jit_decode
                  # module name as the target's — the profiler attributes
                  # the program to the target entry and marks it shared
                  "shard": dshard, "program": "decode"})
        register_entry_point(
            "serving/draft_prefill", build=build_draft_prefill,
            donate_argnums=(1,), expected_collectives=dexp,
            mesh=drafter.engine.mesh,
            tags={"engine": "ServingEngine", "chunk": C,
                  "draft_model": True, "tokens_per_step": C,
                  "shard": dshard, "program": "prefill_chunk"})
        return names + ["serving/draft_decode", "serving/draft_prefill"]


def _apply_boot_recommendations(scfg: ServingConfig,
                                recommendations: Any) -> "tuple":
    """Resolve + apply a ``tune_recommendations.json`` to the serving
    config before engine construction (boot is the only recompile-safe
    moment for shape knobs). ``recommendations``: a path, an already-loaded
    artifact dict, or ``"auto"`` (newest artifact in the run dir). Returns
    ``(applied, refused)`` provenance lists and publishes
    ``tune/recommendations_{applied,refused}`` counters; a bad artifact is
    refused with a named reason, never a boot failure."""
    from ..autotuning.livetuner import (apply_recommendations,
                                        discover_recommendations,
                                        load_recommendations)
    from ..observability import get_registry

    applied: List[dict] = []
    refused: List[dict] = []
    artifact: Optional[dict] = None
    if isinstance(recommendations, dict):
        artifact = recommendations
    else:
        path = recommendations
        if path == "auto":
            path = discover_recommendations()
            if path is None:
                logger.info("tune recommendations: auto-discovery found "
                            "no artifact; booting with configured shapes")
                return applied, refused
        try:
            artifact = load_recommendations(str(path))
        except ValueError as e:
            refused.append({"knob": "*", "reason": str(e),
                            "path": str(path)})
            logger.warning(
                f"tune recommendations: REFUSED artifact {path}: {e}")
    if artifact is not None:
        applied, refused2 = apply_recommendations(scfg, artifact)
        refused += refused2
    reg = get_registry()
    for row in applied:
        reg.counter(
            "tune/recommendations_applied",
            help="offline shape recommendations applied at engine "
                 "boot").inc(knob=row["knob"])
    for row in refused:
        reg.counter(
            "tune/recommendations_refused",
            help="offline shape recommendations refused at boot (named "
                 "reason)").inc(knob=row["knob"],
                                reason=row["reason"].split(":", 1)[0])
    return applied, refused


def init_serving(model=None, serving_config: Optional[Any] = None,
                 clock: Callable[[], float] = time.monotonic,
                 draft_model=None, recommendations: Optional[Any] = None,
                 **init_inference_kwargs) -> ServingEngine:
    """Build an ``InferenceEngine`` (same surface as ``init_inference``) and
    wrap it in a ``ServingEngine``. ``serving_config``: a ``ServingConfig``
    or plain dict. ``draft_model`` (for ``speculative.mode='draft'``): a
    model name/instance for the drafter — built on the same dtype so its
    paged arena shares the serving block pool cleanly. ``recommendations``:
    a ``tune_recommendations.json`` path, loaded artifact dict, or
    ``"auto"`` — the previous run's offline shape advice (speculative K,
    block pool, chunk width) applied to the config at boot with provenance
    (``engine.recommendations_applied``)."""
    from ..inference import init_inference

    if isinstance(serving_config, dict):
        serving_config = ServingConfig.from_dict(serving_config)
    scfg = serving_config or ServingConfig()
    rec_applied: List[dict] = []
    rec_refused: List[dict] = []
    if recommendations is not None:
        rec_applied, rec_refused = _apply_boot_recommendations(
            scfg, recommendations)
        if rec_applied:
            scfg.validate()   # applied shapes must still be a legal config
    # the offline arena is unused by serving, but a shared engine may still
    # serve generate() calls — keep its budget at least the serving budget
    init_inference_kwargs.setdefault("max_out_tokens", scfg.max_model_len)
    engine = init_inference(model=model, **init_inference_kwargs)
    draft_engine = None
    if draft_model is not None:
        draft_engine = init_inference(
            model=draft_model, dtype=engine.config.dtype,
            max_out_tokens=scfg.max_model_len)
    serving = ServingEngine(engine, scfg, clock=clock,
                            draft_engine=draft_engine)
    serving.recommendations_applied = rec_applied
    serving.recommendations_refused = rec_refused
    return serving

"""Speculative decoding over the paged KV arena — drafters + acceptance.

Decode is the serving layer's latency floor: every emitted token costs one
full target-model dispatch. Speculative decoding (Leviathan et al. 2023)
buys multiple tokens per dispatch: a cheap **drafter** proposes up to K
continuation tokens per request, the target model scores all of them in ONE
``R×(K+1)`` verify program (``paged_kv.build_verify_program``), and the
host keeps the longest accepted prefix. Two drafters ship:

* ``NgramDrafter`` — prompt-lookup (model-free, host-side, zero extra HBM):
  the request's trailing n-gram is matched against its own prompt+output
  history and the continuation of the most recent earlier occurrence is
  proposed. Excellent on repetitive/extractive text, free everywhere else.
* ``DraftModelDrafter`` — a smaller ``TransformerModel`` drafts
  autoregressively. Its paged KV lives in a sibling arena indexed by the
  SAME ``BlockAllocator`` as the target's (block ids are allocated from one
  pool), so draft KV spends the same HBM budget and feels the same
  eviction pressure as everything else; the drafter never preempts — when
  the pool can't extend a row's draft blocks, that row simply stops
  speculating until pressure clears.

**Acceptance rule (lossless + bit-stable).** The verify program samples
EVERY position with the key the non-speculative decode would use for that
output-token index: ``fold_in(fold_in(base_key, request_seed),
token_index)``. Let ``x_j`` be the target's sample after feeding token j
(``x_0`` after the pending token, ``x_j`` after draft ``d_j``). The host
emits ``x_0``, then accepts draft ``d_{j+1}`` — and emits ``x_{j+1}`` —
while ``x_j == d_{j+1}``. Every emitted token is therefore EXACTLY the
token the non-speculative path would have sampled at that index (same
logits — the accepted prefix pins the same context — same key), so
speculation changes latency, never output: greedy speculation is
bit-identical to vanilla greedy ``generate()``, and temperature sampling
is bit-identical to the non-speculative serving stream. This trades a
little acceptance probability against classic modified-residual rejection
sampling (acceptance ``E[p(draft)]`` instead of ``Σ min(p, q)``) to keep
the repo-wide reproducibility contract: output depends only on (engine
seed, request seed, token index), never on scheduling — or speculation.

Rollback is positional: the arena layout is left-aligned
(column == absolute position), so rejected draft KV is simply dead weight
past the accepted length — never read (causality over true positions) and
overwritten in place when real tokens reach those positions. The scheduler
frees whole blocks past the accepted length (``truncate_blocks``); the
draft arena rolls back the same way through ``Drafter.commit``.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from ..utils.logging import logger
from . import paged_kv
from .scheduler import Request

__all__ = ["Drafter", "NgramDrafter", "DraftModelDrafter", "make_drafter",
           "request_stream"]


def request_stream(req: Request) -> np.ndarray:
    """The request's full committed token stream: original prompt plus
    every emitted token (the pending one included). Stable across
    preemption — ``req.prompt`` absorbs generated tokens in recompute mode
    but ``req.prompt[:n_prompt] + generated`` is invariant."""
    return np.concatenate(
        [req.prompt[:req.n_prompt],
         np.asarray(req.generated, np.int32)]).astype(np.int32)


class Drafter:
    """Proposal source for speculative decoding.

    The engine calls ``propose`` once per iteration with the rows that will
    verify this round and a per-row token budget; after the verify it calls
    ``commit`` per row with the post-acceptance request state, and
    ``release`` when a request leaves the arena (finish/cancel/preempt).
    ``dispatches`` counts the drafter's own device dispatches (0 for
    host-side drafters) — the bench's draft-overhead accounting."""

    name = "null"

    def __init__(self):
        self.dispatches = 0

    def propose(self, reqs: List[Request],
                caps: List[int]) -> List[np.ndarray]:
        """Up to ``caps[i]`` proposed continuation tokens for ``reqs[i]``,
        given its committed stream (the pending token is the last stream
        entry — proposals continue AFTER it). May return fewer (or none):
        proposal counts are data, not shape."""
        raise NotImplementedError

    def commit(self, req: Request) -> None:
        """Verify landed: ``req.length``/``generated`` reflect the accepted
        tokens. Drafters with device state roll their KV back here."""

    def release(self, req: Request) -> None:
        """Request left the arena (finished/cancelled/preempted)."""

    def close(self) -> None:
        """Engine shutdown: drop any device state."""


class NgramDrafter(Drafter):
    """Prompt-lookup decoding (model-free): propose the continuation of the
    most recent earlier occurrence of the request's trailing n-gram in its
    own prompt+output history. Tried longest-first from ``ngram_max`` down
    to ``ngram_min``; no match proposes nothing (that row runs as plain
    decode inside the same verify dispatch). Host-side and stateless —
    zero HBM, zero dispatches, correct by construction under preemption."""

    name = "ngram"

    def __init__(self, ngram_max: int = 3, ngram_min: int = 1):
        super().__init__()
        if not 1 <= ngram_min <= ngram_max:
            raise ValueError(f"need 1 <= ngram_min ({ngram_min}) <= "
                             f"ngram_max ({ngram_max})")
        self.ngram_max = int(ngram_max)
        self.ngram_min = int(ngram_min)

    def _lookup(self, ctx: np.ndarray, k: int) -> np.ndarray:
        L = int(ctx.size)
        for n in range(self.ngram_max, self.ngram_min - 1, -1):
            if L < n + 2:        # need the suffix plus an earlier match
                continue
            pat = ctx[L - n:]
            # candidate starts j with j+n < L: the match must end before
            # the suffix starts contributing its own continuation
            wins = np.lib.stride_tricks.sliding_window_view(ctx[:L - 1], n)
            hits = np.flatnonzero((wins == pat).all(axis=1))
            if hits.size == 0:
                continue
            j = int(hits[-1])            # most recent occurrence
            return ctx[j + n:j + n + k].astype(np.int32)
        return np.zeros((0,), np.int32)

    def propose(self, reqs: List[Request],
                caps: List[int]) -> List[np.ndarray]:
        return [self._lookup(request_stream(r), k) if k > 0
                else np.zeros((0,), np.int32)
                for r, k in zip(reqs, caps)]


class _DraftState:
    """Per-request draft-arena bookkeeping: ``length`` stream tokens whose
    KV is valid in the draft arena, backed by ``blocks``."""

    __slots__ = ("blocks", "length")

    def __init__(self):
        self.blocks: List[int] = []
        self.length = 0


class DraftModelDrafter(Drafter):
    """A smaller model drafts autoregressively in its own paged arena.

    The draft arena mirrors the target pool's geometry — same block size,
    same block count, ids allocated from the SAME ``BlockAllocator`` — so
    draft KV is a first-class tenant of the serving HBM budget: a
    speculating request holds blocks for its draft context in addition to
    its target context, and when the pool tightens the drafter backs off
    (per-row, allocation-failure-driven) rather than evicting anyone.

    Drafting is batched and greedy: one R×1 draft decode program (same
    builder as the target's) runs K times per iteration, every speculating
    row advancing together; rows that fell behind (an all-accepted round
    leaves the last draft token un-fed) re-feed known stream tokens through
    the same loop, and a freshly admitted or recomputed request catches up
    through the draft prefill program in chunks. Greedy proposals maximise
    the exact-match acceptance probability ``p_target(argmax q)`` for
    peaked target distributions and keep the drafter RNG-free."""

    name = "draft"

    def __init__(self, draft_engine, config, allocator, blocks_per_seq: int):
        super().__init__()
        import jax

        self.engine = draft_engine
        self.config = config
        self.alloc = allocator
        self.blocks_per_seq = int(blocks_per_seq)
        cfg = draft_engine.model.config
        self._cfg = cfg
        self._dtype = draft_engine.config.dtype
        spec = config.speculative
        self.draft_chunk = spec.draft_chunk or config.prefill_chunk
        from ..parallel import mesh as mesh_mod

        self._mesh_mod = mesh_mod
        with mesh_mod.ambient(draft_engine.mesh):
            self._arena = paged_kv.init_paged_cache(
                cfg, config.pool_blocks() + 1, config.block_size,
                self._dtype)
        self._decode = paged_kv.build_decode_program(cfg)
        self._prefill = paged_kv.build_prefill_program(cfg, self.draft_chunk)
        self._state: Dict[int, _DraftState] = {}
        self._key = jax.random.PRNGKey(0)   # greedy drafts never draw

    # -- bookkeeping -------------------------------------------------------
    def state_for(self, req: Request) -> _DraftState:
        st = self._state.get(req.rid)
        if st is None:
            st = self._state[req.rid] = _DraftState()
        return st

    def _ensure_blocks(self, st: _DraftState, upto_tokens: int) -> bool:
        """Grow the draft block list to cover ``upto_tokens`` positions —
        same optional-work discipline as the target arena's verify
        extension (shared helper: plain allocation, no eviction ladder).
        Returns False when the pool says no."""
        return paged_kv.extend_block_list(self.alloc, st.blocks,
                                          upto_tokens,
                                          self.config.block_size)

    def _truncate(self, st: _DraftState) -> None:
        paged_kv.truncate_block_list(self.alloc, st.blocks, st.length,
                                     self.config.block_size)

    # -- catch-up ----------------------------------------------------------
    def _prefill_catchup(self, req: Request, st: _DraftState,
                         target_len: int, obs) -> None:
        """Bring the draft KV from ``st.length`` to ``target_len`` stream
        tokens via the (1, C) draft prefill program — admission and
        post-preemption recompute; the steady-state ≤1-token gap rides the
        batched decode loop instead."""
        stream = request_stream(req)
        C = self.draft_chunk
        z1 = np.zeros((1,), np.float32)
        zi = np.zeros((1,), np.int32)
        o1 = np.ones((1,), np.float32)
        bt = np.zeros((1, self.blocks_per_seq), np.int32)
        bt[0, :len(st.blocks)] = st.blocks
        while st.length < target_len:
            n_valid = min(C, target_len - st.length)
            chunk = np.zeros((1, C), np.int32)
            chunk[0, :n_valid] = stream[st.length:st.length + n_valid]
            with self._mesh_mod.ambient(self.engine.mesh):
                with obs.span("serving/draft_prefill", tokens=int(n_valid)):
                    tok, _last, self._arena = self._prefill(
                        self.engine.params, self._arena,
                        paged_kv.pack_chunk(bt, chunk, st.length, n_valid,
                                            z1, zi, o1, zi), self._key)
                    np.asarray(tok)     # fence
            self.dispatches += 1
            st.length += n_valid

    # -- the drafter contract ----------------------------------------------
    def propose(self, reqs: List[Request],
                caps: List[int]) -> List[np.ndarray]:
        obs = _obs()
        R = self.config.max_seqs
        jobs = []    # [list_index, req, state, queue of known tokens]
        max_iters = 0
        for i, (req, cap) in enumerate(zip(reqs, caps)):
            if cap <= 0:
                continue
            st = self.state_for(req)
            # the draft writes positions [st.length, req.length + cap):
            # catch-up + pending + cap-1 drafts — all-or-nothing budget
            if not self._ensure_blocks(st, req.length + cap):
                continue   # pool pressure: this row sits the round out
            if req.length - st.length > 1:
                self._prefill_catchup(req, st, req.length, obs)
            stream = request_stream(req)
            # residual ≤1-token gap plus the pending token (always un-fed)
            queue = [int(t) for t in stream[st.length:]]
            jobs.append((i, req, st, queue))
            max_iters = max(max_iters, cap + len(queue) - 1)
        out = [np.zeros((0,), np.int32) for _ in reqs]
        if not jobs:
            return out
        props: Dict[int, List[int]] = {j[0]: [] for j in jobs}
        last: Dict[int, int] = {}
        zR = np.zeros((R,), np.float32)
        ziR = np.zeros((R,), np.int32)
        oR = np.ones((R,), np.float32)
        for _ in range(max_iters):
            bt = np.zeros((R, self.blocks_per_seq), np.int32)
            lengths = np.zeros((R,), np.int32)
            tokens = np.zeros((R,), np.int32)
            fed: List[tuple] = []
            for i, req, st, queue in jobs:
                if len(props[i]) >= caps[i]:
                    continue            # row done: rides scratch this step
                if queue:
                    tok = queue.pop(0)
                    emits = not queue   # the queue's LAST entry is the
                    #   pending token — its output is the first proposal;
                    #   earlier entries are catch-up (outputs discarded)
                else:
                    tok = last[i]       # feed the previous proposal back
                    emits = True
                row = req.row
                bt[row, :len(st.blocks)] = st.blocks
                lengths[row] = st.length
                tokens[row] = tok
                fed.append((i, st, row, emits))
            if not fed:
                break
            with self._mesh_mod.ambient(self.engine.mesh):
                with obs.span("serving/draft_decode"):
                    nxt, self._arena = self._decode(
                        self.engine.params, self._arena,
                        paged_kv.pack_decode_rows(bt, lengths, tokens, zR,
                                                  ziR, oR, ziR, ziR),
                        self._key)
                    nxt = np.asarray(nxt)
            self.dispatches += 1
            for i, st, row, emits in fed:
                st.length += 1
                if emits:
                    tok = int(nxt[row])
                    props[i].append(tok)
                    last[i] = tok
        for i, _req, _st, _queue in jobs:
            out[i] = np.asarray(props[i], np.int32)
        return out

    def commit(self, req: Request) -> None:
        st = self._state.get(req.rid)
        if st is None:
            return
        # the valid draft prefix is whatever it fed that the verify kept:
        # committed stream tokens only — rejected draft KV rolls back by
        # position exactly like the target arena
        st.length = min(st.length, req.length)
        self._truncate(st)

    def release(self, req: Request) -> None:
        st = self._state.pop(req.rid, None)
        if st is not None and st.blocks:
            self.alloc.free(st.blocks)

    def close(self) -> None:
        for st in self._state.values():
            if st.blocks:
                self.alloc.free(st.blocks)
        # tpusync: disable=unguarded-shared-write — shutdown-ordered:
        # close() runs after ServingEngine.close() stopped the driver
        # thread, so no release() can race it
        self._state.clear()


def _obs():
    from ..observability import get_session

    return get_session()


def make_drafter(config, target_engine, allocator, blocks_per_seq: int,
                 draft_engine=None) -> Optional[Drafter]:
    """Build the drafter ``config.speculative`` asks for (None when off).
    ``draft_engine`` is an ``InferenceEngine`` over the (smaller) draft
    model — required for mode='draft', vocab-checked against the target;
    ``allocator`` is the serving pool's ``BlockAllocator`` (the draft
    arena shares it)."""
    spec = config.speculative
    if spec.mode == "off":
        return None
    if spec.mode == "ngram":
        return NgramDrafter(spec.ngram_max, spec.ngram_min)
    if draft_engine is None:
        raise ValueError(
            "speculative.mode='draft' needs a draft model: pass "
            "draft_model= to init_serving (or draft_engine= to "
            "ServingEngine)")
    tv = target_engine.model.config.vocab_size
    dv = draft_engine.model.config.vocab_size
    if tv != dv:
        raise ValueError(
            f"draft model vocab ({dv}) != target vocab ({tv}) — draft "
            "proposals would index a different token space")
    if draft_engine.config.dtype != target_engine.config.dtype:
        logger.warning(
            "draft model dtype %s != target dtype %s — allowed, but the "
            "draft arena spends pool blocks at its own width",
            draft_engine.config.dtype, target_engine.config.dtype)
    return DraftModelDrafter(
        draft_engine, config, allocator=allocator,
        blocks_per_seq=blocks_per_seq)

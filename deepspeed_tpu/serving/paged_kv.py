"""Paged KV arena — the device half of the serving layer.

The inference engine's arena reserves a full ``T_max`` row per sequence
(``inference/kv_cache.py``); at serving concurrency that wastes HBM
proportional to the spread of sequence lengths. Here the arena is a shared
pool of fixed-size **blocks** (vLLM's PagedAttention, Kwon et al. SOSP '23):

* ``BlockAllocator`` — host-side free list over the pool. Block 0 is a
  reserved scratch block (inactive decode rows and prompt-chunk padding
  write there); allocatable ids are 1..num_blocks.
* Who writes what: the decode and verify programs write a ROW a token; the
  prefill-chunk and score programs write a run of positions and hand it
  down as ``paged_run=(start, n_valid)``, and the model writes a run of a
  page or more as WHOLE PAGES (the pages gathered, the run laid over them,
  one scatter back; pages with none of its positions sent to scratch). The
  bytes are the same. On the chip two token rows share each 32-bit word of
  the arena and a page is 16 whole tiles, so 256 row updates cost a chunk
  program a quarter of its time (``models/transformer._write_pages``).
* ``build_prefill_program`` / ``build_decode_program`` /
  ``build_mixed_program`` — the three jitted serving programs; the third,
  a prompt chunk that is not its prompt's last and the decode rows in ONE
  pass over the layers, only for a configuration whose layers mix
  (``mixes``: a one-pass stack of plain attention layers with dense FFNs;
  recurrent, ring, cross, expert and looped kinds keep the two). All are
  **shape-static**: the block table
  ``(rows, max_blocks)`` and per-row lengths are data, not shapes, so one
  compiled decode program serves every occupancy the scheduler produces
  (the jit-cache analog of the reference's CUDA-graph discipline). The
  attention read walks the block table
  (``ops/paged_decode_attention.paged_attention``): on TPU the Pallas paged
  kernels DMA only each row's RESIDENT pages, elsewhere the jnp paged
  reference reads the same table. Both address a layer's pool inside the
  ``(L, NUM_BLOCKS, BLOCK, K*D)`` arena; neither slices it out.
* ``PrefixCache`` + refcounted ``BlockAllocator`` + ``build_cow_program``
  — prefix sharing: full prompt blocks are content-hash cached, a new
  request whose prompt prefix is cached maps those blocks into its table
  (refcount++) and skips their prefill chunks entirely; the first write
  into a shared block triggers a device-side copy-on-write.
* ``sample_rows`` — per-row greedy/temperature/top-k/top-p sampling with
  *array-valued* knobs, so requests with different sampling settings share
  one decode program. The greedy path is bit-identical to
  ``inference/engine._sample`` at ``temperature=0``. A ``lax.cond`` on the
  rows' temperatures decides on the device what runs: a step whose rows
  are all greedy takes the argmax and sorts nothing, a step with a sampled
  row sorts the vocabulary once (``vmap`` over it would defeat the
  ``cond`` and run both branches).

The model-side write/read lives in ``models/transformer._softmax_mixer``
(paged branch): the layout is left-aligned — token at position ``p`` sits in
block ``table[p // BLOCK]`` offset ``p % BLOCK`` — so a key's gathered
column IS its position and causality over true positions is the entire
validity story.
"""

from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..inference.kv_cache import (PAGE_ARENAS, assert_block_divisible,
                                  cache_slots,
                                  blocks_for_tokens, init_paged_cache,
                                  paged_cache_memory_bytes, paged_pools)

__all__ = ["BlockAllocator", "BlockAllocatorError", "PrefixCache",
           "blocks_for_tokens", "assert_block_divisible", "init_paged_cache",
           "paged_cache_memory_bytes", "paged_pools",
           "build_prefill_program",
           "build_decode_program", "build_mixed_program", "mixes",
           "build_verify_program",
           "build_score_program", "build_cow_program",
           "build_kv_export_program", "build_kv_import_program",
           "sample_rows", "extend_block_list", "truncate_block_list",
           "pack_decode_rows", "unpack_decode_rows", "pack_verify_rows",
           "unpack_verify_rows", "pack_chunk", "unpack_chunk",
           "decode_rows_shape", "verify_rows_shape", "chunk_shape"]


class BlockAllocatorError(RuntimeError):
    """Allocator invariant violation (double free, foreign block)."""


class BlockAllocator:
    """Refcounted free-list allocator over the arena's allocatable blocks
    (1..capacity).

    Prefix sharing (copy-on-write block tables) makes one physical block
    appear in several sequences' tables, so every allocated block carries a
    reference count: ``alloc`` hands out blocks at refcount 1, ``incref``
    adds a sharer, and ``free`` DROPS ONE REFERENCE — the block returns to
    the free list only when its last reference is dropped. Callers that
    never share (the pre-COW code paths) see the exact PR-6 semantics.

    Invariants (tested in tests/unit/test_serving.py):
      * ``blocks_in_use + blocks_free == capacity`` at all times;
      * a block is never handed out twice without reaching refcount 0;
      * dropping a reference that is not held raises (double free);
      * block 0 (scratch) is never allocated.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        self.capacity = int(num_blocks)
        # LIFO free list, lowest ids first out — deterministic for tests
        self._free: List[int] = list(range(self.capacity, 0, -1))
        self._refs: Dict[int, int] = {}
        self.peak_in_use = 0
        self.peak_shared = 0
        self.total_allocs = 0

    @property
    def blocks_in_use(self) -> int:
        return len(self._refs)

    @property
    def blocks_free(self) -> int:
        return len(self._free)

    @property
    def blocks_shared(self) -> int:
        """Blocks referenced by more than one holder (the sharing win)."""
        return sum(1 for r in self._refs.values() if r > 1)

    def refcount(self, block_id: int) -> int:
        return self._refs.get(block_id, 0)

    def alloc(self, n: int) -> Optional[List[int]]:
        """``n`` fresh block ids at refcount 1, or None when the pool can't
        satisfy the request (caller decides whether to wait, evict cached
        prefixes, or preempt) — partial allocations never happen."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        for b in ids:
            self._refs[b] = 1
        self.total_allocs += n
        self.peak_in_use = max(self.peak_in_use, len(self._refs))
        return ids

    def incref(self, ids: List[int]) -> None:
        """Add one reference per id — a new sharer of an allocated block."""
        for b in ids:
            if b not in self._refs:
                raise BlockAllocatorError(
                    f"incref of block {b} which is not allocated")
        for b in ids:
            self._refs[b] += 1
        # tpusync: disable=unguarded-shared-write — engine-owned: every
        # runtime path holds ServingEngine._lock; the allocator itself is
        # documented single-owner and takes no lock of its own
        self.peak_shared = max(self.peak_shared, self.blocks_shared)

    def free(self, ids: List[int]) -> None:
        """Drop one reference per id; a block is recycled only when its
        LAST reference goes — freeing a shared block never takes it away
        from the other holders."""
        for b in ids:
            if b not in self._refs:
                raise BlockAllocatorError(
                    f"free of block {b} which is not allocated "
                    "(double free or foreign id)")
            self._refs[b] -= 1
            if self._refs[b] == 0:
                del self._refs[b]
                # tpusync: disable=unguarded-shared-write — engine-owned,
                # synchronized under ServingEngine._lock (see incref)
                self._free.append(b)


def extend_block_list(alloc: BlockAllocator, blocks: List[int],
                      upto_tokens: int, block_size: int) -> bool:
    """Grow ``blocks`` (a block-table list) to cover ``upto_tokens``
    positions by PLAIN pool allocation — no cache eviction, no preemption.
    This is the optional-work discipline shared by the speculative verify
    extension and the draft arena: speculation must never cost anyone
    else their blocks. Returns False when the pool says no (the per-row
    auto-disable signal); ``blocks`` is unchanged in that case."""
    need = blocks_for_tokens(upto_tokens, block_size) - len(blocks)
    if need <= 0:
        return True
    ids = alloc.alloc(need)
    if ids is None:
        return False
    blocks.extend(ids)
    return True


def truncate_block_list(alloc: BlockAllocator, blocks: List[int],
                        upto_tokens: int, block_size: int) -> int:
    """Positional rollback shared by the target and draft arenas: drop one
    reference on every block of ``blocks`` past the ones covering
    positions [0, upto_tokens) — rejected speculative KV beyond the
    accepted length is dead weight (never read: causality over true
    positions). A shared (prefix-cache/fork) block stays resident for its
    other holders. Returns the number of references dropped."""
    keep = blocks_for_tokens(upto_tokens, block_size)
    dropped = len(blocks) - keep
    if dropped > 0:
        alloc.free(blocks[keep:])
        del blocks[keep:]
        return dropped
    return 0


class PrefixCache:
    """Content-hashed prompt-prefix → physical-block cache (vLLM/SGLang
    automatic prefix caching).

    Keys are CHAIN hashes: block i's key digests block i-1's key plus block
    i's tokens, so a block is reusable only under the exact same prefix.
    Only FULL prompt blocks are cached — their KV content is immutable once
    written (the arena layout is position-exact, so identical tokens at
    identical positions produce identical KV bytes under fixed params).

    The cache holds ONE pin reference per cached block. Entries whose block
    no request references (allocator refcount == 1) are evictable LRU-first
    under pool pressure; entries shared with live requests are pinned —
    eviction never frees a block somebody still reads.
    """

    def __init__(self, allocator: BlockAllocator, block_size: int):
        self.alloc = allocator
        self.block_size = int(block_size)
        self._entries: "OrderedDict[bytes, int]" = OrderedDict()
        self.inserts = 0
        self.evictions = 0
        self.invalidations = 0   # entries dropped by weight-flip clear()

    @property
    def cached_blocks(self) -> int:
        return len(self._entries)

    @property
    def reclaimable_blocks(self) -> int:
        """Cached blocks held ONLY by the cache pin (allocator refcount 1):
        evictable on demand, so load/occupancy signals must not count them
        as pressure — a warm cache deliberately fills the pool."""
        return sum(1 for b in self._entries.values()
                   if self.alloc.refcount(b) == 1)

    @staticmethod
    def _chain(prev: bytes, tokens: np.ndarray) -> bytes:
        h = hashlib.blake2b(prev, digest_size=16)
        h.update(np.ascontiguousarray(tokens, np.int32).tobytes())
        return h.digest()

    def chain_key(self, prompt: np.ndarray, prev: bytes,
                  block_index: int) -> bytes:
        """One incremental chain step: the key of block ``block_index``
        given its predecessor's key — callers registering blocks in order
        thread the digest instead of rehashing from block 0 (O(P) per
        request, not O(P^2))."""
        BS = self.block_size
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        return self._chain(prev, prompt[block_index * BS:
                                        (block_index + 1) * BS])

    def match(self, prompt) -> Tuple[List[int], int]:
        """Longest cached chain of full blocks for ``prompt``. Returns
        ``(block_ids, n_tokens)`` with ``n_tokens`` capped at
        ``len(prompt) - 1``: at least one prompt token always re-prefills,
        because the request's first sampled token needs the final prompt
        position's logits. When the cap bites (every prompt block cached),
        the last block is handed back SHARED and the re-prefilled token's
        write triggers copy-on-write. Does NOT take references or count
        hit statistics — the caller does both when it COMMITS to using
        the blocks (a rolled-back admission must not inflate the hit
        rate)."""
        BS = self.block_size
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        ids: List[int] = []
        key = b""
        for i in range(int(prompt.size) // BS):
            key = self._chain(key, prompt[i * BS:(i + 1) * BS])
            bid = self._entries.get(key)
            if bid is None:
                break
            self._entries.move_to_end(key)     # LRU recency
            ids.append(bid)
        n = min(len(ids) * BS, int(prompt.size) - 1)
        if n < 1:
            return [], 0
        return ids, n

    def insert_key(self, key: bytes, block_id: int) -> bool:
        """Register a fully-prefilled block under its (caller-threaded)
        chain key, pinning it with one cache reference. A key that is
        already cached keeps its existing block (no double pin)."""
        if key in self._entries:
            self._entries.move_to_end(key)
            return False
        self.alloc.incref([block_id])
        self._entries[key] = block_id
        # tpusync: disable=unguarded-shared-write — engine-owned cache,
        # synchronized under ServingEngine._lock like its allocator
        self.inserts += 1
        return True

    def insert(self, prompt: np.ndarray, block_index: int,
               block_id: int) -> bool:
        """Convenience form of ``insert_key`` that rehashes the chain from
        block 0 — tests and one-off callers; the scheduler threads the
        digest incrementally instead."""
        key = b""
        for i in range(block_index + 1):
            key = self.chain_key(prompt, key, i)
        return self.insert_key(key, block_id)

    def clear(self) -> int:
        """Drop EVERY entry, pinned or not, releasing the cache's one pin
        reference per block — the weight-flip invalidation rule
        (``docs/rlhf.md``): cached KV bytes are a pure function of
        (tokens, positions, params), so a parameter refresh makes every
        content hash stale at once. Blocks shared with a live request stay
        resident for that request (``free`` drops one reference); callers
        flip with the engine idle, so normally the whole cache returns to
        the free list. Returns the number of entries dropped."""
        n = len(self._entries)
        for bid in self._entries.values():
            self.alloc.free([bid])
        self._entries.clear()
        self.invalidations += n
        return n

    def can_evict(self, need: int) -> bool:
        """Whether ``evict(need)`` would free ``need`` blocks: that many
        entries are unpinned. Looks LRU-first, as ``evict`` does, and stops
        at the ``need``-th."""
        for bid in self._entries.values():
            if need <= 0:
                break
            need -= self.alloc.refcount(bid) == 1
        return need <= 0

    def evict(self, need: int) -> int:
        """Drop up to ``need`` UNPINNED entries (blocks only the cache
        holds), LRU-first, returning their blocks to the free list.
        Returns the number actually freed — pinned entries (shared with a
        live request) are never touched."""
        freed = 0
        for key in list(self._entries):
            if freed >= need:
                break
            bid = self._entries[key]
            if self.alloc.refcount(bid) == 1:
                del self._entries[key]
                self.alloc.free([bid])
                freed += 1
                self.evictions += 1
        return freed


# ---------------------------------------------------------------------------
# per-row sampling
# ---------------------------------------------------------------------------


def sample_rows(logits: jax.Array, base_key: jax.Array,
                temperature: jax.Array, top_k: jax.Array, top_p: jax.Array,
                seeds: jax.Array, steps: jax.Array) -> jax.Array:
    """Per-row sampling with array-valued knobs: ``logits`` (R, V);
    ``temperature``/``top_p`` (R,) float32; ``top_k`` (R,) int32 (0 = off).
    Rows with ``temperature <= 0`` take the greedy token — the same
    fp32 argmax as ``inference/engine._sample``, so serving greedy output
    is bit-identical to offline ``generate()``.

    The work follows what the rows ask, decided on the device under ONE
    ``lax.cond`` on ``any(temperature > 0)``: a batch with no sampled row
    (empty rows carry temperature 0) runs the argmax and nothing else; a
    batch with a sampled row also runs the divide, ONE sort of the
    vocabulary, the softmax, the cumulative sum and the categorical draw,
    and its greedy rows still keep the argmax. The predicate is a
    replicated scalar under a sharded vocabulary. Under ``vmap`` a ``cond``
    lowers to a ``select`` that computes both branches: nobody maps this
    function, and whoever does pays for the sort in every batch again.

    Each row draws from ``fold_in(fold_in(base_key, seeds[r]), steps[r])``
    — ``seeds`` the request's sampling seed, ``steps`` its output-token
    index — so a request's stream depends only on (engine seed, request
    seed, token index), NOT on how the scheduler batched it or on which
    branch its neighbours sent the batch down: reproducible across runs and
    bit-stable across preemption/recompute."""
    V = logits.shape[-1]
    greedy = jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)

    def sample():
        # the float32 copy of the logits is the branch's own: made before
        # the cond it would be written out in every greedy step too
        scaled = (logits.astype(jnp.float32)
                  / jnp.maximum(temperature, 1e-6)[:, None])
        # top-k: keep scores >= the k-th largest (per row, traced k). The
        # filter is monotone, so applied to the sorted row it gives the
        # sorted row of the filtered scores: one sort serves top-k and top-p
        desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        kth = jnp.take_along_axis(
            desc, jnp.clip(top_k - 1, 0, V - 1)[:, None], axis=1)
        on = top_k[:, None] > 0
        scaled = jnp.where(on & (scaled < kth), -jnp.inf, scaled)
        desc = jnp.where(on & (desc < kth), -jnp.inf, desc)
        # top-p over the (possibly top-k-filtered) scores; top-1 always
        # survives
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs < top_p[:, None]).at[:, 0].set(True)
        cutoff = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                         keepdims=True)
        scaled = jnp.where(scaled >= cutoff, scaled, -jnp.inf)
        keys = jax.vmap(
            lambda s, t: jax.random.fold_in(jax.random.fold_in(base_key, s),
                                            t))(seeds, steps)
        sampled = jax.vmap(jax.random.categorical)(keys, scaled)
        return jnp.where(temperature <= 0.0, greedy,
                         sampled.astype(jnp.int32))

    return jax.lax.cond(jnp.any(temperature > 0.0), sample, lambda: greedy)


# ---------------------------------------------------------------------------
# packed operands: one int32 host array a dispatch
# ---------------------------------------------------------------------------

# Each layout names a program's operands in the order of its arguments, with
# the int32 columns each takes. The float32 ones cross as their bit patterns
# (``ndarray.view(int32)`` on the host, ``bitcast_convert_type`` in the
# program), so no value is rounded on the way
_F32_COLUMNS = frozenset({"temperature", "top_p"})
_SAMPLING_COLUMNS = (("temperature", 1), ("top_k", 1), ("top_p", 1),
                     ("seeds", 1))


def _decode_columns(max_blocks: int):
    """The decode program's ``(R, MAXB + 7)`` rows."""
    return (("block_table", max_blocks), ("lengths", 1), ("tokens", 1),
            *_SAMPLING_COLUMNS, ("steps", 1))


def _verify_columns(max_blocks: int, num_tokens: int):
    """The verify program's ``(R, MAXB + S + 7)`` rows: the decode rows
    with ``S`` token columns for the one, and ``n_valid`` behind them."""
    return (("block_table", max_blocks), ("lengths", 1),
            ("tokens", num_tokens), ("n_valid", 1), *_SAMPLING_COLUMNS,
            ("steps", 1))


def _chunk_columns(max_blocks: int, chunk: int, state_slot: bool,
                   last: bool = False):
    """The prefill-chunk program's flat ``(MAXB + C + 6,)`` vector, one
    entry longer for a model whose rows own a slot (``state_slot``: recurrent
    layers, window layers) and one
    for a model whose stack ends in runs that only a prompt's LAST chunk
    needs (``last``: ``models/transformer.tail_runs``)."""
    return (("block_table", max_blocks), ("chunk", chunk), ("start", 1),
            ("n_valid", 1), *_SAMPLING_COLUMNS,
            *([("state_slot", 1)] if state_slot else []),
            *([("last", 1)] if last else []))


def _width(columns) -> int:
    return sum(width for _, width in columns)


def decode_rows_shape(rows: int, max_blocks: int):
    """The shape of ``pack_decode_rows``' array."""
    return rows, _width(_decode_columns(max_blocks))


def verify_rows_shape(rows: int, max_blocks: int, num_tokens: int):
    """The shape of ``pack_verify_rows``' array."""
    return rows, _width(_verify_columns(max_blocks, num_tokens))


def chunk_shape(max_blocks: int, chunk_tokens: int, state_slot: bool,
                last: bool = False):
    """The shape of ``pack_chunk``'s vector."""
    return (_width(_chunk_columns(max_blocks, chunk_tokens, state_slot,
                                  last)),)


def _pack(columns, rows: int, operands) -> np.ndarray:
    """Host side of a layout: a NEW ``(rows, sum of columns)`` int32 array
    holding ``operands``, which come in the columns' order."""
    out = np.empty((rows, _width(columns)), np.int32)
    at = 0
    for (name, width), value in zip(columns, operands, strict=True):
        value = np.asarray(value, np.float32 if name in _F32_COLUMNS
                           else np.int32)
        out[:, at:at + width] = value.view(np.int32).reshape(rows, width)
        at += width
    return out


def _unpack(columns, packed: jax.Array):
    """Program side of a layout: ``packed`` (rows, sum of columns) taken
    apart into one ``(rows, columns)`` array an operand, in the columns'
    order, the float32 operands as float32 again."""
    if (packed.shape[-1] != _width(columns)
            or min(width for _, width in columns) < 1):
        raise ValueError(f"packed operands of shape {packed.shape} do not "
                         f"hold the columns {columns}")
    out, at = [], 0
    for name, width in columns:
        value = packed[:, at:at + width]
        if name in _F32_COLUMNS:
            value = jax.lax.bitcast_convert_type(value, jnp.float32)
        out.append(value)
        at += width
    return out


def pack_decode_rows(block_table, lengths, tokens, temperature, top_k,
                     top_p, seeds, steps) -> np.ndarray:
    """The decode program's operands, ``block_table`` (R, MAXB) and the
    rest (R,), as its one ``(R, MAXB + 7)`` int32 host array."""
    rows, max_blocks = np.shape(block_table)
    return _pack(_decode_columns(max_blocks), rows,
                 (block_table, lengths, tokens, temperature, top_k, top_p,
                  seeds, steps))


def packed_decode_lengths(packed: np.ndarray) -> np.ndarray:
    """The ``lengths`` column of ``pack_decode_rows``' host array, (R,): 0
    for a row no request holds."""
    return packed[:, packed.shape[1] - _width(_decode_columns(0))]


def unpack_decode_rows(packed: jax.Array):
    """``pack_decode_rows``' inverse, in the program: its arguments, in
    its order, with their shapes and dtypes."""
    max_blocks = packed.shape[1] - _width(_decode_columns(0))
    table, *rest = _unpack(_decode_columns(max_blocks), packed)
    return (table, *(column[:, 0] for column in rest))


def pack_verify_rows(block_table, lengths, tokens, n_valid, temperature,
                     top_k, top_p, seeds, steps) -> np.ndarray:
    """The verify program's operands, ``block_table`` (R, MAXB), ``tokens``
    (R, S) and the rest (R,), as its one ``(R, MAXB + S + 7)`` int32 host
    array."""
    rows, max_blocks = np.shape(block_table)
    return _pack(_verify_columns(max_blocks, np.shape(tokens)[1]), rows,
                 (block_table, lengths, tokens, n_valid, temperature, top_k,
                  top_p, seeds, steps))


def unpack_verify_rows(packed: jax.Array, num_tokens: int):
    """``pack_verify_rows``' inverse, in the program."""
    max_blocks = packed.shape[1] - _width(_verify_columns(0, num_tokens))
    table, lengths, tokens, *rest = _unpack(
        _verify_columns(max_blocks, num_tokens), packed)
    return (table, lengths[:, 0], tokens,
            *(column[:, 0] for column in rest))


def pack_chunk(block_table, chunk, start, n_valid, temperature, top_k,
               top_p, seeds, state_slot=None, last=None) -> np.ndarray:
    """The prefill-chunk program's operands (``block_table`` (1, MAXB),
    ``chunk`` (1, C), ``start`` / ``n_valid`` (), the four sampling values
    (1,), for a model with recurrent layers ``state_slot`` (1,) and, for
    one whose stack ends in ``tail_runs``, ``last`` (1,): 1 where the chunk
    is its prompt's last) as its one flat int32 host array."""
    operands = [block_table, chunk, start, n_valid, temperature, top_k,
                top_p, seeds]
    operands += [extra for extra in (state_slot, last) if extra is not None]
    return _pack(_chunk_columns(np.shape(block_table)[1], np.shape(chunk)[1],
                                state_slot is not None, last is not None),
                 1, operands)[0]


def unpack_chunk(packed: jax.Array, chunk_tokens: int, state_slot: bool,
                 last: bool = False):
    """``pack_chunk``'s inverse, in the program: its arguments in its
    order, ``state_slot`` None where the layout holds none, and ``last``
    (1,) behind it only where the layout holds one."""
    max_blocks = packed.shape[0] - _width(
        _chunk_columns(0, chunk_tokens, state_slot, last))
    table, chunk, start, n_valid, *rest = _unpack(
        _chunk_columns(max_blocks, chunk_tokens, state_slot, last),
        packed[None])
    rest = [column[0] for column in rest]
    if not state_slot:
        rest.insert(len(_SAMPLING_COLUMNS), None)
    return (table, chunk, start[0, 0], n_valid[0, 0], *rest)


# ---------------------------------------------------------------------------
# the serving programs: chunk, decode and (where the layers mix) both as one
# ---------------------------------------------------------------------------


def _with_moe_counts(tokens: jax.Array, counts: jax.Array) -> jax.Array:
    """An MoE model's routing counts ride behind the sampled tokens in the
    ONE int32 array the host fetches: ``[tokens..., assignments, experts
    with a row, rows of the largest expert]``, the three summed over the
    layers (``models/transformer.forward(moe_counts=True)``)."""
    return jnp.concatenate([tokens.astype(jnp.int32), counts])


def _run_operands(tokens: int, start: jax.Array, n_valid: jax.Array):
    """``(write_mask (1, C), positions (1, C))`` of a run of ``tokens`` = C
    positions from ``start`` on whose first ``n_valid`` are real (a prompt
    or scoring chunk). Pad queries ride position -1 (the inactive
    convention): a pad position past the written range would otherwise widen
    the read path's residency window onto scratch/recycled pages, whose
    nonfinite residue must never touch live rows."""
    offs = jnp.arange(tokens, dtype=jnp.int32)
    write_mask = (offs < n_valid)[None]
    return write_mask, jnp.where(write_mask, (start + offs)[None], -1)


def build_prefill_program(cfg, chunk_tokens: int, moe_counts: bool = False):
    """Jitted prefill-chunk program over the paged arena, for chunks of
    ``chunk_tokens`` tokens (C): the one static split of its flat operand
    vector, as ``num_tokens`` is of ``build_verify_program``'s rows.

    Args (all shapes static per max_blocks):
      params, cache          — model params / paged arena (arena DONATED)
      packed (MAXB + C + 6,) int32 — ``pack_chunk`` of the operands below,
                               one entry longer where ``cache`` holds
                               recurrent state; taken apart in the program
                               (``unpack_chunk``)
      base_key               — the engine's sampling key (constant)

    What ``packed`` holds, in its order:
      block_table (1, MAXB)  — the request's physical block ids
      chunk (1, C) int32     — prompt tokens, zero-padded past ``n_valid``
      start () int32         — absolute position of chunk[0]
      n_valid () int32       — real tokens in this chunk (pad writes land in
                               the scratch block; pad logits are never read)
      temperature/top_k/top_p/seeds (1,) — the request's sampling knobs
                               (temperature and top_p as float32 bit
                               patterns)
      state_slot (1,) int32  — a model with recurrent layers only: the
                               request's slot in the state pools, which ride
                               in ``cache`` beside the pages (its decode row)
      last (1,) int32        — a model whose stack ends in ``tail_runs``
                               only: 1 where the chunk is its prompt's last.
                               Any other chunk runs the stack up to those
                               runs and neither them nor the head: its token
                               is 0 and its logits zeros

    Returns (token (1,), last_logits (1, V) f32, cache): ``token`` samples
    the position-``n_valid-1`` logits at output-token index 0 — the
    request's FIRST generated token when this was the final chunk, ignored
    otherwise. With ``moe_counts`` (an MoE model; the serving engine's own
    programs) ``token`` is (4,): the token, then the chunk's routing counts
    over its ``n_valid`` real tokens (``_with_moe_counts``).
    """
    from ..models.transformer import tail_runs

    step = _chunk_step(cfg, moe_counts)
    has_last = tail_runs(cfg) > 0

    def prefill_chunk(params, cache, packed, base_key):
        operands = list(unpack_chunk(packed, chunk_tokens,
                                     cache_slots(cache) > 0, has_last))
        last = operands.pop() if has_last else None
        return step(params, cache, *operands, base_key, last)

    return jax.jit(prefill_chunk, donate_argnums=(1,))


def _chunk_step(cfg, moe_counts: bool = False):
    """The prefill-chunk program behind its unpacking: the operands that
    ``build_prefill_program`` names, each an argument (``state_slot`` None
    for a model with no recurrent layers)."""
    from ..models.transformer import forward as model_forward
    from ..models.transformer import tail_runs

    # a stack whose last runs keep nothing of a token (a cross-decoder)
    # runs them for the chunk's last real token alone, the one whose logits
    # are read, and only where the chunk is its prompt's last (``last``; a
    # caller that hands none gets them for every chunk)
    last_only = tail_runs(cfg) > 0

    def chunk_step(params, cache, block_table, chunk, start, n_valid,
                   temperature, top_k, top_p, seeds, state_slot, base_key,
                   last=None):
        write_mask, pos = _run_operands(chunk.shape[1], start, n_valid)
        tail = {}
        if last_only:
            # the token whose logits are read, or -1: none of this chunk's
            wanted = jnp.maximum(n_valid - 1, 0)
            if last is not None:
                wanted = jnp.where(last[0] > 0, wanted, -1)
            tail["last_token"] = wanted[None]
        logits, cache, _, *counts = model_forward(
            params, chunk, cfg, cache=cache, positions=pos,
            block_table=block_table, paged_write_mask=write_mask,
            moe_counts=moe_counts, state_slots=state_slot,
            paged_run=(start, n_valid), **tail)
        if last_only:
            read = logits[:, 0].astype(jnp.float32)
        else:
            read = jnp.take_along_axis(
                logits, jnp.maximum(n_valid - 1, 0)[None, None, None],
                axis=1)[:, 0].astype(jnp.float32)
        tok = sample_rows(read, base_key, temperature, top_k, top_p,
                          seeds, jnp.zeros((1,), jnp.int32))
        if moe_counts:
            tok = _with_moe_counts(tok, counts[0])
        return tok, read, cache

    return chunk_step


def build_decode_program(cfg, moe_counts: bool = False):
    """Jitted one-token decode step over the paged arena for a fixed row
    count R. Inactive rows carry an all-zero block table and length 0 — their
    writes land in the scratch block and their sampled tokens are ignored by
    the host — so occupancy changes never respecialize the program.

    Args: params, cache (DONATED), packed (R, MAXB + 7) int32, base_key,
    last (optional).
    ``packed`` is ``pack_decode_rows`` of the step's operands, taken apart
    in the program (``unpack_decode_rows``); a row of it holds, in this
    order: block_table (MAXB columns), lengths (tokens already in cache —
    the incoming token's position), tokens, temperature / top_k / top_p /
    seeds (temperature and top_p as float32 bit patterns), steps (the row's
    output-token index, for the schedule-independent sampling stream).
    ``last`` is what the last call of this program returned as
    ``next_token``, on the device still: a row whose ``tokens`` entry is
    negative takes its token from there, so that a step can be enqueued
    before its predecessor's tokens have reached the host (the serving
    engine's step ahead). Without it every token comes through ``packed``.
    Returns (next_token (R,), cache). An MoE model keeps rows of length 0
    out of the routing; with ``moe_counts`` (the serving engine's own
    program) ``next_token`` is (R + 3,): the tokens, then the step's routing
    counts over the rows that hold a request (``_with_moe_counts``).
    A model with recurrent layers keeps row r's state, and one with window
    layers row r's ring, in slot r of the pools in ``cache``
    (``kv_cache.cache_slots``); a row that holds nothing is sent to the last
    slot, scratch, and advances nothing.
    """
    step = _decode_step(cfg, moe_counts)

    def decode(params, cache, packed, base_key, last=None):
        return step(params, cache, *unpack_decode_rows(packed), base_key,
                    last)

    return jax.jit(decode, donate_argnums=(1,))


def _decode_step(cfg, moe_counts: bool = False):
    """The decode program behind its unpacking: the operands that
    ``build_decode_program`` names, each an argument."""
    from ..models.transformer import forward as model_forward

    def decode_step(params, cache, block_table, lengths, tokens,
                    temperature, top_k, top_p, seeds, steps, base_key,
                    last=None):
        if last is not None:
            tokens = jnp.where(tokens < 0, last[:tokens.shape[0]], tokens)
        # a row that holds a request has at least its prompt in the cache.
        # The mask also sends an empty row's write to the scratch block,
        # which is where its all-zero table sent it anyway. A dense model
        # routes nothing, and its program stays as it was.
        n_slots = cache_slots(cache)    # a state's or a ring's, a row
        live = ((lengths > 0)[:, None]
                if cfg.moe_num_experts > 0 or n_slots else None)
        slots = None
        if n_slots:
            slots = jnp.where(lengths > 0,
                              jnp.arange(lengths.shape[0], dtype=jnp.int32),
                              n_slots - 1)
        logits, cache, _, *counts = model_forward(
            params, tokens[:, None], cfg, cache=cache,
            positions=lengths[:, None], block_table=block_table,
            paged_write_mask=live, moe_counts=moe_counts, state_slots=slots)
        nxt = sample_rows(logits[:, -1], base_key, temperature, top_k,
                          top_p, seeds, steps)
        if moe_counts:
            nxt = _with_moe_counts(nxt, counts[0])
        return nxt, cache

    return decode_step


def mixes(cfg) -> bool:
    """THE predicate of the mixed step: whether a configuration's non-last
    prompt chunk and the iteration's decode rows can run as one program
    (``build_mixed_program``). Decided by what its layers are, never by a
    name: a stack that runs once (no ``loop_passes``, no ``layer_runs``)
    whose every mixer is the plain ``"attn"`` of ``MIXERS`` (it keeps pages
    of its own and hands nothing between a chunk kernel and a step kernel)
    with a dense FFN behind it. A recurrent mixer's state, a window's ring
    and a cross layer's borrowed pool pass from the chunk program's kernel
    to the step program's, an expert FFN's routing mask and counts are a
    token's, and a looped stack's only traffic is one-chunk prompts: those
    keep the two programs."""
    from ..models.transformer import LAYER_KINDS, layer_kinds

    return (cfg.loop_passes == 1 and not cfg.layer_runs
            and cfg.moe_num_experts == 0
            and all(LAYER_KINDS[kind] == ("attn", True)
                    for kind in layer_kinds(cfg)))


def build_mixed_program(cfg, chunk_tokens: int):
    """Jitted MIXED step over the paged arena, for a configuration that
    ``mixes``: a prompt chunk that is NOT its prompt's last and the
    iteration's decode rows through ONE pass over the layers. The R row
    tokens and the C chunk tokens are embedded as one flat run of R + C, so
    every per-token product of a layer (norms, projections, FFN) is one
    product over R + C rows and each weight is read once an iteration, not
    once a program; only the mixer's write and read over the pages split
    (``models/transformer._attend_mixed``): the chunk's as the chunk
    program's, the rows' as the decode program's. The head runs over the R
    row tokens alone: a chunk that is not the last has no token anybody
    reads (a last chunk's is its request's first token, which must not wait
    for the rows' walks: it keeps the chunk program).

    Args: params, cache (DONATED), rows (R, MAXB + 7) int32
    (``pack_decode_rows``), chunk (MAXB + C + 6,) int32 (``pack_chunk``; its
    sampling values ride along unread), base_key, last (optional: as the
    decode program's). Returns (next_token (R,), cache), the decode
    program's own result: a row draws from its own stream (``seeds``,
    ``steps``), so the tokens are those of the chunk program followed by the
    decode program, and so are the arena's bytes."""
    from ..models.transformer import forward as model_forward

    if not mixes(cfg):
        raise ValueError("this configuration's layers do not mix "
                         "(paged_kv.mixes)")

    def mixed_step(params, cache, rows, chunk, base_key, last=None):
        (row_table, lengths, tokens, temperature, top_k, top_p, seeds,
         steps) = unpack_decode_rows(rows)
        table, ids, start, n_valid, *_ = unpack_chunk(chunk, chunk_tokens,
                                                      False)
        if last is not None:
            tokens = jnp.where(tokens < 0, last[:tokens.shape[0]], tokens)
        write_mask, pos = _run_operands(chunk_tokens, start, n_valid)
        logits, cache, _ = model_forward(
            params, jnp.concatenate([tokens[None], ids], axis=1), cfg,
            cache=cache, positions=lengths[:, None], block_table=row_table,
            mixed_chunk=dict(positions=pos, block_table=table,
                             write_mask=write_mask,
                             paged_run=(start, n_valid)))
        return sample_rows(logits[:, -1], base_key, temperature, top_k,
                           top_p, seeds, steps), cache

    return jax.jit(mixed_step, donate_argnums=(1,))


def build_verify_program(cfg, num_tokens: int):
    """Jitted speculative-decoding verify step: the R×1 decode program
    generalized to R×S (S = ``num_tokens`` = K+1 draft slots + the pending
    token). Row r feeds ``tokens[r] = [pending, d_1 .. d_K]`` at absolute
    positions ``lengths[r] + 0..S-1`` — the left-aligned column==position
    invariant makes the causal read over drafted positions exact — and the
    target model scores ALL of them in one dispatch.

    Speculation is data, not shape: ``n_valid`` (R,) int32 is each row's
    real token count this iteration (1 = plain decode, 1+k = k proposed
    drafts, 0 = inactive row riding scratch); positions past ``n_valid``
    write to the scratch block and their samples are ignored by the host.
    One compiled program serves every per-row proposal/acceptance mix.

    Sampling: position j of row r draws through the SAME
    ``fold_in(fold_in(base_key, seeds[r]), steps[r] + j)`` key the
    non-speculative decode would use for that output-token index — so the
    host's accept rule (keep sampled tokens while they equal the draft,
    emit the first divergence as the correction) is lossless rejection
    sampling whose emitted stream is BIT-IDENTICAL to the non-speculative
    path at any temperature, greedy included (see
    ``serving/speculative.py`` for the acceptance math).

    Args: params, cache (DONATED), packed (R, MAXB + S + 7) int32,
    base_key. ``packed`` is ``pack_verify_rows`` of the step's operands,
    taken apart in the program (``unpack_verify_rows``); a row of it holds,
    in this order: block_table (MAXB columns), lengths, tokens (S columns),
    n_valid, temperature / top_k / top_p / seeds (temperature and top_p as
    float32 bit patterns), steps (the row's FIRST output-token index this
    iteration).
    Returns (sampled (R, S) int32, cache): ``sampled[r, j]`` is the target
    sample after token j — the host emits ``sampled[r, 0..a]`` where ``a``
    is the accepted-draft count.
    """
    if num_tokens < 2:
        raise ValueError(f"build_verify_program(num_tokens={num_tokens}): "
                         "need the pending token plus >= 1 draft slot")
    step = _verify_step(cfg)

    def verify(params, cache, packed, base_key):
        return step(params, cache, *unpack_verify_rows(packed, num_tokens),
                    base_key)

    return jax.jit(verify, donate_argnums=(1,))


def _verify_step(cfg):
    """The verify program behind its unpacking: the operands that
    ``build_verify_program`` names, each an argument."""
    from ..models.transformer import forward as model_forward

    def verify_step(params, cache, block_table, lengths, tokens, n_valid,
                    temperature, top_k, top_p, seeds, steps, base_key):
        R, S = tokens.shape
        offs = jnp.arange(S, dtype=jnp.int32)
        write_mask = offs[None] < n_valid[:, None]
        # invalid slots (beyond the row's proposal count, and every slot
        # of an inactive row) ride position -1 — see ``_run_operands``: pad
        # positions past the written range would widen the residency
        # window onto scratch/recycled pages
        pos = jnp.where(write_mask, lengths[:, None] + offs[None], -1)
        logits, cache, _ = model_forward(params, tokens, cfg, cache=cache,
                                         positions=pos,
                                         block_table=block_table,
                                         paged_write_mask=write_mask)
        flat = logits.reshape(R * S, logits.shape[-1]).astype(jnp.float32)
        sampled = sample_rows(flat, base_key,
                              jnp.repeat(temperature, S),
                              jnp.repeat(top_k, S), jnp.repeat(top_p, S),
                              jnp.repeat(seeds, S),
                              (steps[:, None] + offs[None]).reshape(-1))
        return sampled.reshape(R, S), cache

    return verify_step


def build_score_program(cfg):
    """Jitted teacher-forced scoring chunk over the paged arena — the RLHF
    second serving pass (``docs/rlhf.md``): instead of sampling, it returns
    the log-probability the model assigns to given TARGET tokens. Same
    chunked discipline and block-table shapes as the prefill program, so it
    rides the SAME arena and pool (scratch blocks allocated per scored
    sequence, freed after) with zero extra HBM and one compiled program per
    chunk width.

    Args (shapes static per (C, max_blocks) pair):
      params, cache          — scoring params / paged arena (arena DONATED).
                               ``params`` is an argument, not a capture, so
                               the policy pass (π_old logprobs) and the
                               frozen-reference pass share ONE compiled
                               program
      block_table (1, MAXB)  — the scoring scratch blocks
      chunk (1, C) int32     — sequence tokens, zero-padded past ``n_valid``
      targets (1, C) int32   — targets[0, j] is the token whose logprob
                               position ``start + j`` should yield (the
                               next sequence token); pad entries score
                               garbage the host never reads
      start/n_valid () int32 — chunk position / real token count

    Returns (logp (1, C) f32, cache): per-position log softmax mass on the
    target token (``transformer.gather_target_logprobs`` — the TP-safe
    one-hot contraction).
    """
    from ..models.transformer import forward as model_forward
    from ..models.transformer import gather_target_logprobs

    def score_chunk(params, cache, block_table, chunk, targets, start,
                    n_valid):
        write_mask, pos = _run_operands(chunk.shape[1], start, n_valid)
        # a scored sequence's recurrent state, and its ring, live in the
        # scratch slot: no decode row owns it, and its first chunk starts
        # the state from zeros and the ring over
        slots = (jnp.full((1,), cache_slots(cache) - 1, jnp.int32)
                 if cache_slots(cache) else None)
        logits, cache, _ = model_forward(params, chunk, cfg, cache=cache,
                                         positions=pos,
                                         block_table=block_table,
                                         paged_write_mask=write_mask,
                                         state_slots=slots,
                                         paged_run=(start, n_valid))
        return gather_target_logprobs(logits, targets), cache

    return jax.jit(score_chunk, donate_argnums=(1,))


def build_kv_export_program():
    """Jitted KV-handoff export: gather one request's resident blocks out of
    the (NOT donated — other requests keep reading it) source arena into a
    dense ``(L, MAXB, BLOCK, K*D)`` transfer buffer, one program for any
    block count. ``ids`` is the request's block list padded to MAXB with the
    scratch block 0 — pad lanes carry scratch garbage the import writes
    straight back into the destination's scratch block, so residency is
    data, never shape. On a shared mesh this plus ``build_kv_import_program``
    is an in-HBM copy; a cross-host transport later replaces only the
    buffer's journey between the two programs (the ``KVHandoff`` seam in
    ``serving/fleet/disagg.py``)."""

    def kv_export(cache, ids):
        return cache["k"][:, ids], cache["v"][:, ids]

    return jax.jit(kv_export)


def build_kv_import_program():
    """Jitted KV-handoff import: scatter an exported transfer buffer into
    freshly allocated blocks of the (donated) destination arena. ``ids`` is
    the destination block list padded to MAXB with scratch 0 — duplicate
    pad writes land in the scratch block, whose content is never read."""

    def kv_import(cache, buf_k, buf_v, ids):
        return {**cache, "k": cache["k"].at[:, ids].set(buf_k),
                "v": cache["v"].at[:, ids].set(buf_v)}

    return jax.jit(kv_import, donate_argnums=(0,))


def build_cow_program():
    """Jitted copy-on-write block copy: duplicate physical block ``src``
    into ``dst`` across every layer of the (donated) arena. ``src``/``dst``
    are traced int32 scalars, so ONE compiled program serves every copy —
    the scheduler runs it before the first write into a block whose
    refcount is > 1 (prefix sharing), giving the writer a private copy
    while readers keep the original."""

    def cow_copy(cache, src, dst):
        return {**cache, **{name: cache[name].at[:, dst].set(
            cache[name][:, src]) for name in PAGE_ARENAS if name in cache}}

    return jax.jit(cow_copy, donate_argnums=(0,))

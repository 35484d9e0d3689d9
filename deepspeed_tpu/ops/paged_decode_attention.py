"""Pallas paged attention: block-table-aware decode + chunked-prefill kernels.

The serving layer's arena is a shared pool of fixed-size KV blocks
(``serving/paged_kv.py``; vLLM's PagedAttention, Kwon et al. SOSP '23). A
read that materializes a dense ``(R, MAXB*BLOCK, K, D)`` view per layer per
step (``arena[block_table]``) makes every decode token pay HBM traffic
proportional to the *pool view*, not the tokens actually resident. These
kernels walk each row's block table instead and DMA only **resident** pages
(``paged_attention``, at the end of the module, is the one entry the model
calls and the one place kernel or reference is chosen):

* ``paged_decode_attention`` — single-query decode. The grid runs over the
  rows only and the arenas stay in HBM (``pl.ANY``): a row's step walks ITS
  resident pages itself, ``ceil(length / BLOCK)`` of them read from the
  scalar-prefetched table, a tile of P pages at a time — one async copy a
  page, ``arena[layer, table[row, page]]`` into slot p of one of two
  ``(P, BLOCK, K*D)`` VMEM buffers, the next tile's copies (or, after a
  row's last tile, the first tile of the row below) started before the
  current tile is computed. Nothing is issued, and no step taken, for a
  table slot past a row's last resident page: the work follows the tokens
  in the cache, not ``max_model_len``. P is derived (``_pages_per_tile``:
  what k + v, two buffers each, fit the VMEM budget, up to 256 keys a
  tile). Where a page is small enough that its descriptors and not its
  bytes set the walk's pace (``_unrolls_whole_tiles``: under 64 KiB a
  side), a tile whose every page is resident takes its starts unrolled and
  ONE wait a side (``_page_copies``, ``whole_tiles``); a row's last tile,
  and every tile of wider pages, keeps a loop of starts and a loop of
  waits. All heads of a tile are
  scored in ONE product against q laid out
  block-diagonally over the ``K*D`` lanes, and weigh the values in one
  more: bf16 keys and values as stored, scores, softmax state and
  accumulator in float32, ``p`` into the value product as float32 (three
  bf16 terms stacked into the one product, ``_dot_f32``). GQA-native (KV
  heads never expanded), alibi in-kernel; a tile's tail past the row's
  length is masked by true position, in the scores and in v.
* ``latent_decode_attention`` — the decode walk over ONE pool that is keys
  and values both (the absorbed read of a latent pool: one key-value head
  as wide as a page, its values the page's first lanes). A page is copied
  ONCE, into one tile that both products read; with one key-value head q is
  its own block-diagonal form, so there is no product before or after the
  walk; the value product and the accumulator run over the value lanes
  alone. What set its pace was not the products (they run at the MXU's own
  pace) but the descriptors, a serial scalar chain a page: so a tile holds
  twice the keys (``_pages_per_tile`` with one side), a tile known whole
  takes its starts unrolled and ONE wait as the two-pool walk's does, and
  the tile's body is built once a buffer, so that every address in it is
  static (the two-pool walk takes its buffer as a traced value: built once
  a buffer it read 2-4% faster alone, and cost every program that holds it
  a second more of lowering, PERF.md section 6, PR 66). The arithmetic is
  the two-pool walk's, term for term.
* ``paged_prefill_attention`` — the same walk under a chunk of queries: C
  queries a row at absolute positions ``start..start+C-1``, of which the
  row's ``length`` says how many are real (``start + n_valid`` keys). The
  grid runs over the rows; a row's step copies ITS resident pages up to its
  last real token, a tile at a time into the same two buffers, the next
  tile in flight (``_chunk_tile_pages``: 1,024 keys a tile, under a budget
  of the kernel's own), and flash-accumulates every head against each
  tile. No copy and no step for a pad slot's page or a table slot past the
  real length; a row of length 0 writes zeros. What ONE tile step executes
  (PERF.md section 6, PR 69): a group of heads (``_heads_per_group``: the
  KV heads of one 128-lane slab of the page, and their query heads) is
  whole slabs. q is laid out block-diagonally over the slab once a row (a
  head's rows hold its D lanes where its KV head's lie in the page, zeros
  in the others'; ``scale`` folded in where that is exact), the group's
  heads stacked along the rows, so ONE product against the slab as it is
  stored scores them all and no head is ever sliced out of q, k, v or the
  accumulator; running max (and sum) are lane-replicated, a row a query.
  The chunk's queries go in query blocks and a tile's keys in sub-blocks
  (``_chunk_blocks``), and a (query block, tile) VISIT computes the tile's
  first sub-blocks up to the block's last query and no further
  (``_tile_extent``): nothing above the chunk's diagonal, past the row's
  keys, below every query's window or for pad queries alone; a visit every
  query sees whole takes no mask, any other builds ONE mask for all its
  groups. The products take q, k and v as they are stored (bf16 x bf16 as
  served), summed in float32; scores, running max, sum and accumulator
  stay float32; ``p`` is ROUNDED to the values' dtype for the value
  product, as the reference below and the training flash kernels round it
  (with a chunk of rows in the product the three-term float32 ``p`` of the
  decode walk would triple it). Where a slab holds more heads than one,
  the value product of a head carries ONES in the lanes of the slab's
  other heads, so the MXU sums ``p``'s rows there and the running sum
  rides the accumulator (``_sums_in_slab``; the flash forward's form). So
  a later chunk never materializes the gathered view either.

Layout contract (shared with ``models/transformer._layer_forward``): the
arena is LEFT-ALIGNED — the token at absolute position ``p`` sits in block
``table[p // BLOCK]`` at offset ``p % BLOCK`` — so a key's (page, offset)
coordinate IS its position: causality over true positions is the entire
validity story and the alibi key bias is exact by construction.

The kernels take the WHOLE arena ``(L, NUM_BLOCKS, BLOCK, K*D)`` and a
``layer`` index, never one layer's pool. ``layer`` (a traced int32 scalar:
the model's layer scan hands down its loop index) rides as a
scalar-prefetch operand and the kernels' copies put it in front of the page
id, so a page's DMA starts at ``arena[layer, table[row, page]]`` where the
arena lies. A custom call needs each operand as a buffer of its own: handed
``arena[layer]``, XLA materialises that pool (185 MiB at OPT-1.3B's serving
size) before the call and copies it back after the write, four copies a
layer that cost more than the attention itself and grow with the arena, not
with the tokens in it. A caller with a single pool passes ``pool[None]`` and
layer 0.

A page is ``(BLOCK, K*D)``: a token's KV heads lie side by side in the lane
dimension. The TPU lowering takes a block whose last two dims are multiples
of (8, 128) or the whole array dims, and stores arrays in such tiles: a
``(BLOCK, K, D)`` page cannot be blocked one head at a time, and at head_dim
64 is padded to twice its size. A ``(BLOCK, K*D)`` page is lane-dense as
stored, and the kernels slice heads out of it by static lane offsets.

The three kernels share the walk's copies and nothing else
(``_page_copies``, ``_first_tile``, ``_tile_arrives``, over a tuple of
sides: keys and values, or the one pool). The latent walk asks for
``whole_tiles``, the two-pool walk where ``_unrolls_whole_tiles`` says its
pages gain by it; the kernel under a chunk of queries does not, its copies
being noise beside its products, and keeps a loop of starts and a loop of
waits for every tile. ``walk_page_counts`` tells the serving engine how
many of a step's pages the form takes, by the same two rules, and
``prefill_block_counts`` how many of the sub-blocks a chunk's tiles span
its tile steps leave out, by the kernel's own.

``reference_paged_attention`` is the pure-jnp oracle and CPU fallback:
GQA-native over the view gathered straight from the arena
(``arena[layer, block_table]``; no head expansion, no (B,S,T) mask
materialization).
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# k + v tiles of pages, two buffers each, in both walks — ONE budget shared
# with the dense decode kernel's tile sizing
from .decode_attention import VMEM_KV_BUDGET as _VMEM_PAGE_BUDGET
from .decode_attention import tiled_vmem_bytes
from .flash_attention import _fold_scale, _scaled
from . import registry

# keys a tile of a walk holds at most (and at least 128, where the budget
# allows): see ``_pages_per_tile``. A chunk of queries has the rows to feed
# a wider tile, and a budget of its own (``_chunk_tile_pages``)
_TILE_KEYS = 256
_CHUNK_TILE_KEYS = 1024
# the chunk kernel's k + v tiles, two buffers each: it asks for its VMEM
# itself (``vmem_limit_bytes``), so its tiles are not held to the decode
# walks' budget
_CHUNK_PAGE_BUDGET = 16 << 20


def _check_page_fits(block_size: int, width: int, dtype,
                     sides: int = 2) -> None:
    """``sides`` (k + v, or one pool that is both) ``(block_size, width)``
    pages, double-buffered, as VMEM holds them."""
    per_page = 2 * sides * tiled_vmem_bytes(block_size, width, dtype)
    if per_page > _VMEM_PAGE_BUDGET:
        raise ValueError(
            f"paged attention KV pages do not fit VMEM: block_size "
            f"{block_size} x {width} lanes ({jnp.dtype(dtype).name}) needs "
            f"{per_page} B double-buffered — shrink serving.block_size or "
            "shard KV heads (tensor parallelism)")


def _kv_heads(arena: jax.Array, n_heads: int, head_dim: int) -> int:
    width = arena.shape[-1]
    if arena.ndim != 4 or width % head_dim != 0 \
            or n_heads % (width // head_dim) != 0:
        raise ValueError(
            f"paged arena must be (L, NUM_BLOCKS, BLOCK, K*D) with K "
            f"dividing n_heads {n_heads} at head_dim {head_dim}, got "
            f"{arena.shape} (a single pool goes in as pool[None], layer 0)")
    return width // head_dim


def _value_dim(v_arena: jax.Array, kv_heads: int) -> int:
    """A head's values in ``v_arena`` (..., K * Dv): the values need not be
    as wide as the keys, but they are the same heads."""
    if v_arena.ndim != 4 or v_arena.shape[-1] % kv_heads:
        raise ValueError(
            f"the values' arena must be (L, NUM_BLOCKS, BLOCK, K*Dv) with "
            f"the keys' K = {kv_heads}, got {v_arena.shape}")
    return v_arena.shape[-1] // kv_heads


def _layer_operand(layer) -> jax.Array:
    """``layer`` as the (1,) int32 scalar-prefetch operand."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


# ---------------------------------------------------------------------------
# decode: one query token per row
# ---------------------------------------------------------------------------


def _pages_per_tile(block_size: int, width: int, dtype,
                    max_keys: int = _TILE_KEYS, sides: int = 2,
                    budget: int = _VMEM_PAGE_BUDGET) -> int:
    """Pages one tile of a walk holds — derived, not set: the largest power
    of two whose tiles, ``sides`` of them (k + v) with two buffers each, fit
    the VMEM budget as VMEM lays them out, capped at ``max_keys`` keys a
    tile for k + v (at least one page: ``_check_page_fits`` guards that
    one). A walk with ONE side spends the second's room on keys: twice as
    many a tile."""
    pages = 1
    while (2 * pages * block_size <= max_keys * 2 // sides
           and 2 * sides * tiled_vmem_bytes(2 * pages * block_size, width,
                                            dtype) <= budget):
        pages *= 2
    return pages


# the two-pool walk takes a whole tile's starts unrolled where a page, one
# side of it, is smaller than this. Measured, the walk alone and forced
# either way (PERF.md section 6, PR 66): at 64 KiB a page (2,048 lanes of
# bf16, 16 keys) the form reads nothing at any rows tried, 16 or 64 of them,
# 40 to 2,000 tokens long (a page's bytes take longer to arrive than its
# descriptors to issue), and costs a call of 16 short rows 0.5-0.9 us; at
# 40, 32 and 8 KiB it reads -4, -5 and -9% at 64 rows of some hundred to
# 2,000 tokens, and nothing at 16 short ones
_WHOLE_TILE_PAGE_BYTES = 64 * 1024


def _unrolls_whole_tiles(block_size: int, width: int, dtype) -> bool:
    """Whether the two-pool decode walk over pages ``(block_size, width)``
    asks ``_page_copies`` for ``whole_tiles``: by the page's bytes, which
    say whether its descriptors or its bytes set the walk's pace."""
    return (block_size * width * jnp.dtype(dtype).itemsize
            < _WHOLE_TILE_PAGE_BYTES)


def walk_page_counts(lengths, arena, latent: bool = False) -> Dict[str, int]:
    """What a decode step's walks over ``arena`` (..., BLOCK, lanes) meet,
    from its rows' ``lengths`` (host integers): ``walk_pages``, the resident
    pages summed over the rows, and ``walk_pages_whole``, those of them
    whose copies take the whole-tile form of ``_page_copies``: the pages of
    tiles whose every page is resident (all but a row's last tile, and that
    one where its pages fill it), where the walk asks for the form at all
    (``latent``, the one-pool walk: always; the two-pool walk by
    ``_unrolls_whole_tiles``, so 0 over wide pages)."""
    block, width = arena.shape[-2:]
    pages = (np.asarray(lengths) + (block - 1)) // block
    whole = 0
    if latent or _unrolls_whole_tiles(block, width, arena.dtype):
        tile = _pages_per_tile(block, width, arena.dtype,
                               sides=1 if latent else 2)
        whole = int((pages // tile).sum()) * tile
    return {"walk_pages": int(pages.sum()), "walk_pages_whole": whole}


def _dot_f32(a, b, b_dim: int):
    """``a`` (M, C) times ``b``, contracted over ``b``'s dim ``b_dim``,
    summed in float32 with every bit of ``a``, whatever ``b``'s dtype: ``a``
    goes in as the sum of as many terms of that dtype as hold it exactly (a
    float32 is three bfloat16), stacked along the rows of ONE product. So
    few rows stream through the MXU here that more of them cost little, where
    a float32 product would cost six passes and a cast of ``b``."""
    M = a.shape[0]
    terms = pl.cdiv(jnp.finfo(a.dtype).nmant + 1, jnp.finfo(b.dtype).nmant + 1)
    parts, rest = [], a.astype(jnp.float32)
    for _ in range(terms - 1):
        parts.append(rest.astype(b.dtype).astype(jnp.float32))
        rest = rest - parts[-1]
    stacked = jnp.concatenate(parts + [rest], axis=0).astype(b.dtype)
    out = jax.lax.dot_general(stacked, b, (((1,), (b_dim,)), ((), ())),
                              preferred_element_type=jnp.float32)
    return sum(out[i * M:(i + 1) * M] for i in range(terms))


def _page_copies(bt_ref, len_ref, layer, sides, sems, whole_tiles=False):
    """The walk's copies, for every kernel: ``each_copy(row, tile, slot)``
    starts (or, with ``wait``, waits for) the copy of every RESIDENT page of
    ``row``'s tile ``tile``, ``arena[layer, table[row, page]]``, into slot p
    of buffer ``slot`` — nothing for a table slot past the row's last
    resident page. ``sides``: the ``(arena, buffer)`` pairs a page is copied
    for (keys and values, or one pool that is both).

    ``whole_tiles`` (the latent walk, and the two-pool walk over pages
    small enough to gain by it, ``_unrolls_whole_tiles``; not the kernel
    under a chunk of queries, whose copies are noise beside its products):
    a tile whose every page is resident (all but a row's last) takes its
    starts UNROLLED, so that the scalar work of one page (the table read,
    the address, the bounds checks) is packed beside the next one's and not
    a loop's serial chain, and ONE wait for the tile's bytes (a copy's wait
    takes its byte count off the semaphore, so a descriptor as large as the
    tile waits for all of its pages). The unrolled starts are ONE traced
    body (``fori_loop(..., unroll=True)``): a kernel is traced and lowered
    anew for every program that holds it, before the compile cache is
    asked, and P bodies traced apart cost every set-up a second a kernel. A
    row's LAST tile keeps its loops: each form that took it out of them
    (unrolled under predicates, by the bits of its count, down a tree of
    halves: PERF.md section 6, PR 66) read nothing on the chip and tripled
    the kernel's text."""
    P, BS = sides[0][1].shape[1:3]

    def each_copy(row, tile, slot, wait=False):
        first = tile * P
        resident = jnp.minimum(pl.cdiv(len_ref[row], BS) - first, P)

        def page(p, carry):
            blk = bt_ref[row, first + p]
            for side, (hbm, buf) in enumerate(sides):
                copy = pltpu.make_async_copy(hbm.at[layer, blk],
                                             buf.at[slot, p],
                                             sems.at[side, slot])
                copy.wait() if wait else copy.start()
            return carry

        if not whole_tiles:
            jax.lax.fori_loop(0, resident, page, 0)
            return

        @pl.when(resident == P)
        def _whole_tile():
            if not wait:
                jax.lax.fori_loop(0, P, page, 0, unroll=True)
                return
            for side, (_, buf) in enumerate(sides):
                pltpu.make_async_copy(buf.at[slot], buf.at[slot],
                                      sems.at[side, slot]).wait()

        @pl.when(resident < P)
        def _last_tile():
            jax.lax.fori_loop(0, resident, page, 0)

    return each_copy


def _first_tile(each_copy, len_ref, slot_ref, row):
    """The buffer ``row``'s first tile is in: the row above started it
    beside its own last tile, unless that row was empty (or is none), and
    then the row starts it here."""
    base = slot_ref[0]

    @pl.when((row == 0) | (len_ref[jnp.maximum(row - 1, 0)] == 0))
    def _own_first_tile():
        each_copy(row, 0, base)

    return base


def _tile_arrives(each_copy, vbuf, row, rows, t, n_tiles, slot, length,
                  lanes=None):
    """``row``'s tile ``t`` arrives in buffer ``slot``. The next tile's
    copies go out first: this row's, or after its last the first of the row
    below. A tile's tail past the row's length holds what was there before
    (another row's pages, or whatever the buffer started with): the kernels
    mask its scores, and its v is zeroed here: 0 * NaN is NaN (``lanes``:
    how many of a page's first lanes are its values; None: all of them)."""
    _, P, BS, W = vbuf.shape
    W = W if lanes is None else lanes

    @pl.when(t + 1 < n_tiles)
    def _next_tile():
        each_copy(row, t + 1, 1 - slot)

    @pl.when((t + 1 == n_tiles) & (row + 1 < rows))
    def _next_row():
        each_copy(jnp.minimum(row + 1, rows - 1), 0, 1 - slot)

    each_copy(row, t, slot, wait=True)

    @pl.when((t + 1) * P * BS > length)
    def _zero_tail():
        pos = (t * P * BS
               + jax.lax.broadcasted_iota(jnp.int32, (P, BS, W), 0) * BS
               + jax.lax.broadcasted_iota(jnp.int32, (P, BS, W), 1))
        vbuf[slot, :, :, :W] = jnp.where(
            pos < length, vbuf[slot, :, :, :W].astype(jnp.float32),
            0.0).astype(vbuf.dtype)


def _softmax_step(s, m_scr, l_scr):
    """One tile of a decode walk's online softmax: the masked float32 scores
    ``s`` (N, TK) against the running max and sum (kept across a row's tiles,
    a head a row, broadcast over the lanes). Returns ``p`` (N, TK) and the
    factor that brings what was accumulated so far to the new max."""
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[:] = jnp.broadcast_to(
        corr * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
        l_scr.shape)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    return p, corr


def _decode_kernel(bt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, alibi_ref,
                   o_ref, kbuf, vbuf, sems, lane, diag, qbd, acc, m_scr,
                   l_scr, slot_ref, *of_values, scale: float, n_heads: int,
                   kv_heads: int, has_alibi: bool, lo_ref=None,
                   sink_ref=None):
    r = pl.program_id(0)
    R = pl.num_programs(0)
    _, P, BS, W = kbuf.shape
    TK = P * BS
    N, D = q_ref.shape[1:]
    G = n_heads // kv_heads
    # values as wide as the keys take their blocks out of the accumulator
    # with the masks that laid q out; narrower ones have a pair of their own
    lane_v, diag_v = of_values or (lane, diag)
    Wv, Dv = vbuf.shape[3], o_ref.shape[2]
    layer = layer_ref[0]
    length = len_ref[r]
    n_tiles = pl.cdiv(length, TK)

    each_copy = _page_copies(
        bt_ref, len_ref, layer, ((k_hbm, kbuf), (v_hbm, vbuf)), sems,
        whole_tiles=_unrolls_whole_tiles(BS, W, kbuf.dtype))

    @pl.when(r == 0)
    def _first_row():
        slot_ref[0] = 0
        # all heads of a tile are scored in one product against q laid out
        # block-diagonally, (N, K*D): row n holds q[n] in its KV head's D
        # lanes. ``lane`` tiles a (., D) array K times along the lanes (and
        # takes the blocks back out of the accumulator at the end): each
        # output is a single term, so exact. ``diag`` keeps a head's own block
        lane[:] = (jax.lax.broadcasted_iota(jnp.int32, (D, W), 1) % D
                   == jax.lax.broadcasted_iota(jnp.int32, (D, W), 0)
                   ).astype(lane.dtype)
        diag[:] = (jax.lax.broadcasted_iota(jnp.int32, (N, W), 1) // D
                   == jax.lax.broadcasted_iota(jnp.int32, (N, W), 0) // G
                   ).astype(diag.dtype)
        if of_values:
            lane_v[:] = (jax.lax.broadcasted_iota(jnp.int32, (Dv, Wv), 1) % Dv
                         == jax.lax.broadcasted_iota(jnp.int32, (Dv, Wv), 0)
                         ).astype(lane_v.dtype)
            diag_v[:] = (jax.lax.broadcasted_iota(jnp.int32, (N, Wv), 1) // Dv
                         == jax.lax.broadcasted_iota(jnp.int32, (N, Wv), 0)
                         // G).astype(diag_v.dtype)

    @pl.when(n_tiles == 0)
    def _empty_row():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(n_tiles > 0)
    def _row():
        base = _first_tile(each_copy, len_ref, slot_ref, r)
        acc[:] = jnp.zeros_like(acc)
        if sink_ref is None:
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
        else:
            # a head's sink is one more score that weighs no value: the
            # running maximum starts at it and the running sum at its own 1
            m_scr[:] = jnp.broadcast_to(sink_ref[0][:, None], m_scr.shape)
            l_scr[:] = jnp.ones_like(l_scr)
        qbd[:] = (_dot_f32(q_ref[0], lane[:], 0) * diag[:]).astype(qbd.dtype)

        def tile(t, carry):
            slot = (base + t) % 2
            _tile_arrives(each_copy, vbuf, r, R, t, n_tiles, slot, length)
            k = kbuf[slot].reshape(TK, W).astype(qbd.dtype)
            s = jax.lax.dot_general(
                qbd[:], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # (N, TK)
            col = t * TK + jax.lax.broadcasted_iota(jnp.int32, (1, TK), 1)
            if has_alibi:
                # left-aligned layout: the tile's column IS the key position
                s = s + alibi_ref[0][:, None] * col.astype(jnp.float32)
            live = col < length
            if lo_ref is not None:
                # a window: the keys below its first are out of sight
                live = live & (col >= lo_ref[r])
            p, corr = _softmax_step(jnp.where(live, s, NEG_INF), m_scr, l_scr)
            # p goes into the value product as float32, not rounded to v's
            acc[:] = acc[:] * corr + _dot_f32(
                p, vbuf[slot].reshape(TK, Wv), 0)              # (N, K*Dv)
            return carry

        jax.lax.fori_loop(0, n_tiles, tile, 0)
        slot_ref[0] = (base + n_tiles) % 2
        out = _dot_f32(acc[:] * diag_v[:], lane_v[:], 1)       # (N, Dv)
        o_ref[0] = (out / l_scr[:, :1]).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_arena: jax.Array,
                           v_arena: jax.Array, layer,
                           block_table: jax.Array, lengths: jax.Array,
                           alibi: Optional[jax.Array] = None,
                           scale: Optional[float] = None,
                           interpret: bool = False,
                           lo: Optional[jax.Array] = None,
                           name: str = "paged_decode_attention",
                           sink: Optional[jax.Array] = None
                           ) -> jax.Array:
    """q (R, N, D) — one new token per row; k_arena (L, NUM_BLOCKS, BLOCK,
    K*D) and v_arena (L, NUM_BLOCKS, BLOCK, K*Dv) — the whole shared arena
    (the values of a head need not be as wide as its keys: the walk copies
    each side's pages as they are and returns (R, N, Dv)); layer — int32
    scalar (may be traced),
    the layer whose pool is read; block_table (R, MAXB) int32 physical page
    ids (unfilled entries 0 = scratch); lengths (R,) int32 — valid keys per
    row INCLUDING the just-written token (0 ⇒ inactive row, output zeros).
    Returns (R, N, D). Reads only each row's resident pages of that layer.
    ``lo`` (R,) int32, a window's form: row r sees the keys ``lo[r] <= key <
    lengths[r]`` of the pages its table names, which the caller starts at
    the window's first page (``paged_attention``); ``name`` is the kernel's
    in a trace. ``sink`` (N,) float32: a learned score a head that takes its
    share of the softmax's mass and weighs no value, ``p_j = exp(s_j - m) /
    (exp(sink - m) + sum_j' exp(s_j' - m))``: the running maximum starts at
    it and the running sum at 1."""
    R, N, D = q.shape
    K = _kv_heads(k_arena, N, D)
    BS, W = k_arena.shape[2:]
    Dv = _value_dim(v_arena, K)
    Wv = K * Dv
    _check_page_fits(BS, W, k_arena.dtype)
    pages = _pages_per_tile(BS, W, k_arena.dtype)
    scale = scale if scale is not None else D ** -0.5
    has_alibi = alibi is not None
    alibi_arr = (alibi.astype(jnp.float32).reshape(1, N) if has_alibi
                 else jnp.zeros((1, N), jnp.float32))
    # the products take q and the keys in the wider of their two dtypes
    pd = jnp.promote_types(q.dtype, k_arena.dtype)
    windowed = lo is not None
    per_head = [alibi_arr] + ([] if sink is None else [
        sink.astype(jnp.float32).reshape(1, N)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + windowed,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((1, N, D), lambda r, *_: (r, 0, 0)),
            # the arenas stay where they lie: the kernel copies pages itself
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            # alibi's slopes and the sinks, a float a head
            *[pl.BlockSpec((1, N), lambda r, *_: (0, 0))] * len(per_head),
        ],
        out_specs=pl.BlockSpec((1, N, Dv), lambda r, *_: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages, BS, W), k_arena.dtype),
            pltpu.VMEM((2, pages, BS, Wv), v_arena.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),          # (k | v, buffer)
            pltpu.VMEM((D, W), jnp.bfloat16),         # lane, 0/1: exact
            pltpu.VMEM((N, W), jnp.float32),          # diag
            pltpu.VMEM((N, W), pd),                   # block-diagonal q
            pltpu.VMEM((N, Wv), jnp.float32),
            pltpu.VMEM((N, LANES), jnp.float32),
            pltpu.VMEM((N, LANES), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
            # the values' own lane and diag, where they are not the keys'
            *([pltpu.VMEM((Dv, Wv), jnp.bfloat16),
               pltpu.VMEM((N, Wv), jnp.float32)] if Dv != D else []),
        ],
    )
    body = functools.partial(_decode_kernel, scale=scale, n_heads=N,
                             kv_heads=K, has_alibi=has_alibi)
    scalars = (block_table.astype(jnp.int32), lengths.astype(jnp.int32),
               _layer_operand(layer))
    if windowed:
        scalars += (lo.astype(jnp.int32),)

    def kernel(*refs):
        # the optional operands, each where the call puts it: ``lo`` behind
        # the scalars, the sinks behind alibi
        refs, given = list(refs), {}
        if sink is not None:
            given["sink_ref"] = refs.pop(len(scalars) + 4)
        if windowed:
            given["lo_ref"] = refs.pop(3)
        body(*refs, **given)

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, N, Dv), q.dtype),
        # rows in order: a row starts the copies of the next one's first tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=name,
        interpret=interpret,
    )(*scalars, q, k_arena, v_arena, *per_head)


# ---------------------------------------------------------------------------
# decode over ONE pool: one key-value head as wide as a page, whose values
# are the page's first lanes (the absorbed read of a latent pool)
# ---------------------------------------------------------------------------

# the walk's name in a trace, whatever implements it
LATENT_DECODE = "latent_decode_attention"


def _latent_decode_kernel(bt_ref, len_ref, layer_ref, q_ref, hbm, o_ref, buf,
                          sems, acc, m_scr, l_scr, slot_ref, *, scale: float):
    r = pl.program_id(0)
    R = pl.num_programs(0)
    _, P, BS, W = buf.shape
    TK = P * BS
    V = acc.shape[1]
    # the score takes q and the keys in the wider of their two dtypes
    pd = jnp.promote_types(q_ref.dtype, buf.dtype)
    length = len_ref[r]
    n_tiles = pl.cdiv(length, TK)
    # keys and values are ONE tile of pages: one copy a page
    each_copy = _page_copies(bt_ref, len_ref, layer_ref[0], ((hbm, buf),),
                             sems, whole_tiles=True)

    @pl.when(r == 0)
    def _first_row():
        slot_ref[0] = 0

    @pl.when(n_tiles == 0)
    def _empty_row():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(n_tiles > 0)
    def _row():
        base = _first_tile(each_copy, len_ref, slot_ref, r)
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

        def tile(t, slot):
            _tile_arrives(each_copy, buf, r, R, t, n_tiles, slot, length,
                          lanes=V)
            # with one key-value head q IS its block-diagonal form and the
            # accumulator its own output: no product before or after the walk
            s = jax.lax.dot_general(
                q_ref[0].astype(pd), buf[slot].reshape(TK, W).astype(pd),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # (N, TK)
            col = t * TK + jax.lax.broadcasted_iota(jnp.int32, (1, TK), 1)
            p, corr = _softmax_step(jnp.where(col < length, s, NEG_INF),
                                    m_scr, l_scr)
            # p as float32, as the two-pool walk weighs its values
            acc[:] = acc[:] * corr + _dot_f32(
                p, buf[slot, :, :, :V].reshape(TK, V), 0)      # (N, V)

        def in_its_buffer(t, carry):
            # the body is built once a buffer, so that every address of a
            # tile (a page's place in VMEM among them) is known when the
            # kernel is built: a page's start is 16 bundles, not 22
            for slot in (0, 1):
                pl.when((base + t) % 2 == slot)(
                    functools.partial(tile, t, slot))
            return carry

        jax.lax.fori_loop(0, n_tiles, in_its_buffer, 0)
        slot_ref[0] = (base + n_tiles) % 2
        o_ref[0] = (acc[:] / l_scr[:, :1]).astype(o_ref.dtype)


def latent_decode_attention(q: jax.Array, arena: jax.Array, layer,
                            block_table: jax.Array, lengths: jax.Array,
                            values: int, scale: Optional[float] = None,
                            interpret: bool = False) -> jax.Array:
    """The decode walk over ONE pool: q (R, N, W) — one new token per row, a
    head's query as wide as a page; arena (POOLS, NUM_BLOCKS, BLOCK, W) — a
    token's ONE key-value head is its row of a page, and its values that
    row's first ``values`` lanes (whole lane tiles); ``layer``,
    ``block_table`` and ``lengths`` as in ``paged_decode_attention``.
    Returns (R, N, values): multi-query attention whose keys and values are
    the same bytes. A page is copied ONCE into one tile that both products
    read (twice the keys a tile for it); the arithmetic is the two-pool
    walk's, term for term: the pool as stored, float32 scores, softmax state
    and accumulator, ``p`` as float32 in three bfloat16 terms."""
    R, N, W = q.shape
    BS = arena.shape[2]
    if arena.ndim != 4 or arena.shape[3] != W or values % LANES \
            or not 0 < values <= W:
        raise ValueError(
            f"a one-pool walk takes q (R, N, W) against (POOLS, NUM_BLOCKS, "
            f"BLOCK, W) with the values a page's first whole lane tiles, got "
            f"q {q.shape}, arena {arena.shape}, values {values}")
    _check_page_fits(BS, W, arena.dtype, sides=1)
    pages = _pages_per_tile(BS, W, arena.dtype, sides=1)
    scale = scale if scale is not None else W ** -0.5
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((1, N, W), lambda r, *_: (r, 0, 0)),
            # the pool stays where it lies: the kernel copies pages itself
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, N, values), lambda r, *_: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages, BS, W), arena.dtype),
            pltpu.SemaphoreType.DMA((1, 2)),          # (the one side, buffer)
            pltpu.VMEM((N, values), jnp.float32),
            pltpu.VMEM((N, LANES), jnp.float32),
            pltpu.VMEM((N, LANES), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, N, values), q.dtype),
        # rows in order: a row starts the copies of the next one's first tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=LATENT_DECODE,
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      _layer_operand(layer), q, arena)


# ---------------------------------------------------------------------------
# chunked prefill: C queries per row at positions start..start+C-1
# ---------------------------------------------------------------------------


def _heads_per_group(kv_heads: int, head_dim: int,
                     value_dim: Optional[int] = None) -> int:
    """KV heads the prefill kernel takes out of a tile at a time: the fewest
    whose lanes make a slab that a loop can address in the ``(BLOCK, K*D)``
    page — a multiple of 128 (two heads at head_dim 64, one at 128; two at
    keys of 192 beside values of 128, whose slabs are 384 and 256), in the
    keys' page and in the values' alike, else the whole page."""
    for hp in range(1, kv_heads):
        if kv_heads % hp == 0 and (hp * head_dim) % LANES == 0 \
                and (hp * (value_dim or head_dim)) % LANES == 0:
            return hp
    return kv_heads


# what one visit of the prefill kernel's tile step takes (``_chunk_blocks``):
# the keys of a tile in sub-blocks of at most this many, and the stacked rows
# of a group's heads (a query block's, times the heads) at most that many
_BLOCK_KEYS = 512
_BLOCK_ROWS = 2048
# sublanes a packed 16-bit tile holds: a head's rows in the kernel's scratch
# start on such a tile whatever the chunk
_ROW_TILE = 16


def _chunk_tile_pages(block_size: int, width: int, dtype) -> int:
    """Pages a tile of the walk under a chunk of queries holds
    (``_pages_per_tile`` at the chunk kernel's cap and budget: 1,024 keys at
    every served page)."""
    return _pages_per_tile(block_size, width, dtype, _CHUNK_TILE_KEYS,
                           budget=_CHUNK_PAGE_BUDGET)


def _chunk_blocks(chunk: int, heads: int, pages: int, block_size: int):
    """``(QB, PB)``: the queries of a query block and the PAGES of a key
    sub-block of the prefill kernel's tile step — derived, not set. A group
    of ``heads`` query heads stacks a query block's rows a head, and the
    float32 scores of those rows against a tile are what the step holds:
    the chunk is halved until they are ``_BLOCK_ROWS`` rows at most (whole
    row tiles; a small chunk is one block). A tile's ``pages`` (a power of
    two) part into the largest runs of whole pages that hold at most
    ``_BLOCK_KEYS`` keys, one page at least."""
    qb = chunk
    while heads * qb > _BLOCK_ROWS and qb % (2 * _ROW_TILE) == 0:
        qb //= 2
    pb = 1
    while 2 * pb <= pages and 2 * pb * block_size <= _BLOCK_KEYS:
        pb *= 2
    return qb, pb


def _chunk_geometry(chunk: int, n_heads: int, kv_heads: int, block_size: int,
                    width: int, dtype, value_dim: Optional[int] = None):
    """``(pages, HP, QB, PB)`` of a call of the prefill kernel, from its
    static shapes (``width``: the lanes of a token's keys; ``value_dim``: a
    head's values where they are not as wide as its keys): the pages of a
    tile, the KV heads of a group, the queries of a query block and the
    pages of a key sub-block."""
    pages = _chunk_tile_pages(block_size, width, dtype)
    HP = _heads_per_group(kv_heads, width // kv_heads, value_dim)
    QB, PB = _chunk_blocks(chunk, n_heads // kv_heads * HP, pages,
                           block_size)
    return pages, HP, QB, PB


# what a call of the prefill kernel may hold in VMEM and make in a visit
# (``_chunk_vmem``): a chunk whose queries would pass it goes down as ROWS
# (``_chunk_parts``). Every chunk served before keys were 192 wide under 64
# heads stays one row (the widest, 1,024 queries of 32 heads of 64 over 8
# key-value heads, asks 93 MiB)
_CHUNK_VMEM_BUDGET = 96 << 20


def _chunk_vmem(chunk: int, n_heads: int, kv_heads: int, head_dim: int,
                value_dim: int, block_size: int, q_dtype, kv_dtype) -> int:
    """Bytes a call of the prefill kernel asks of VMEM under ``chunk``
    queries a row: what it HOLDS (the k and v tiles, two buffers each; q and
    the output as the pipeline double-buffers them; q block-diagonal, the
    accumulator and the statistics) and what a VISIT makes (a group's slabs
    of a tile: k, v, and v under each head's ones; its (rows, tile) blocks
    of scores: float32 scores, p, p rounded, a mask)."""
    N, K, D, Dv, BS = n_heads, kv_heads, head_dim, value_dim, block_size
    pages, HP, QB, _ = _chunk_geometry(chunk, N, K, BS, K * D, kv_dtype, Dv)
    pd = jnp.promote_types(q_dtype, kv_dtype)
    rows = N // K * HP * pl.cdiv(QB, _ROW_TILE) * _ROW_TILE
    stats = 1 if _sums_in_slab(HP) else 2
    held = (2 * tiled_vmem_bytes(pages * BS, K * D, kv_dtype)
            + 2 * tiled_vmem_bytes(pages * BS, K * Dv, kv_dtype)
            + 2 * tiled_vmem_bytes(chunk, N * D, q_dtype)
            + 2 * tiled_vmem_bytes(chunk, N * Dv, q_dtype)
            + K // HP * (chunk // QB) * (
                tiled_vmem_bytes(rows, HP * D, pd)
                + tiled_vmem_bytes(rows, HP * Dv, jnp.float32)
                + stats * tiled_vmem_bytes(rows, LANES, jnp.float32)))
    visit = (tiled_vmem_bytes(pages * BS, HP * D, pd)
             + (1 + HP) * tiled_vmem_bytes(pages * BS, HP * Dv, pd)
             + 4 * tiled_vmem_bytes(rows, pages * BS, jnp.float32))
    return held + visit


def _chunk_parts(chunk: int, *sizes) -> int:
    """How many ROWS a row's chunk of queries goes down as (``sizes``:
    ``_chunk_vmem``'s other arguments): the fewest equal parts, a power of
    two of whole row tiles each, that the kernel holds within
    ``_CHUNK_VMEM_BUDGET``. A part is a row of the kernel's grid with its
    own start and length over the same table, so a later part's walk passes
    the earlier parts' keys again: copies, which are noise beside a chunk's
    products."""
    parts = 1
    while (_chunk_vmem(chunk // parts, *sizes) > _CHUNK_VMEM_BUDGET
           and chunk % (2 * parts * _ROW_TILE) == 0):
        parts *= 2
    return parts


def _part_rows(start, lengths, chunk: int, parts: int, xp):
    """``(start, lengths)`` of the ``parts`` rows that each row's chunk of
    ``chunk`` queries goes down as, row-major: a part starts where the one
    before it ends, and holds the row's keys up to its own last real query
    (0 where all its queries are pad)."""
    at = start[:, None] + xp.arange(parts)[None] * (chunk // parts)
    held = xp.where(at < lengths[:, None],
                    xp.minimum(lengths[:, None], at + chunk // parts), 0)
    return at.reshape(-1), held.reshape(-1)


def _sums_in_slab(heads_per_group: int) -> bool:
    """Whether the running sum of a head's ``p`` rides the accumulator: a
    slab of more heads than one leaves each the others' lanes, and ones
    there have the value product sum ``p``'s rows; a slab that is one head's
    keeps a lane-replicated sum of its own."""
    return heads_per_group > 1


def _tile_extent(start, length, q0, k0, QB: int, KB: int, nk: int,
                 window: Optional[int], xp):
    """``(n, whole)``: what the query block ``start + q0 ...`` (QB queries)
    computes of the tile whose first key is ``k0``, in a row of ``length``
    keys: its first ``n`` key sub-blocks (KB keys each, ``nk`` a tile) —
    those that reach up to the block's last query and no further, 0 where
    the tile lies above the diagonal, past the row's keys, below every
    query's window, or all of the block's queries are pad — and ``whole``
    where every query sees every key of them, so that no mask is needed. A
    pad query stands at the row's last real position. The kernel asks it of
    traced scalars and ``prefill_block_counts`` of host integers (``xp``:
    jnp or numpy)."""
    q_lo = xp.minimum(start + q0, length - 1)
    q_hi = xp.minimum(start + q0 + QB - 1, length - 1)
    n = xp.clip((q_hi - k0) // KB + 1, 0, nk)
    n = xp.where(start + q0 < length, n, 0)
    last = k0 + n * KB - 1
    whole = last <= q_lo
    if window is not None:
        n = xp.where(last > q_lo - window, n, 0)
        whole = whole & (k0 > q_hi - window)
    return n, whole


def prefill_block_counts(start, lengths, chunk: int, n_heads: int,
                         head_dim: int, arena,
                         window: Optional[int] = None,
                         value_dim: Optional[int] = None) -> Dict[str, int]:
    """What the prefill kernel's tile steps meet under a chunk of ``chunk``
    queries a row, ``n_heads`` heads of ``head_dim``, from the rows'
    ``start`` and ``lengths`` (host integers, as ``paged_prefill_attention``
    takes them) and the ``arena`` (..., BLOCK, lanes) it walks:
    ``prefill_blocks``, the (query block, key sub-block) pairs that the
    rows' tiles span, and ``prefill_blocks_skipped``, those of them the
    kernel does not compute (``_tile_extent``: past a row's keys, above the
    diagonal, below the window, all pad). By the kernel's own rules:
    ``_pages_per_tile``, ``_heads_per_group``, ``_chunk_blocks``, and
    ``_chunk_parts`` where a chunk goes down as rows (``value_dim``: a
    head's values where they are not as wide as its keys; q taken to be of
    the arena's dtype)."""
    block, width = arena.shape[-2:]
    kv_heads, value_dim = width // head_dim, value_dim or head_dim
    start, lengths = (np.asarray(a, np.int64).reshape(-1)
                      for a in (start, lengths))
    parts = _chunk_parts(chunk, n_heads, kv_heads, head_dim, value_dim,
                         block, arena.dtype, arena.dtype)
    if parts > 1:
        start, lengths = _part_rows(start, lengths, chunk, parts, np)
        chunk //= parts
    pages, _, QB, PB = _chunk_geometry(chunk, n_heads, kv_heads, block,
                                       width, arena.dtype, value_dim)
    TK, KB, nk = pages * block, PB * block, pages // PB
    start, lengths = start.reshape(-1, 1), lengths.reshape(-1, 1)
    tiles = -(-lengths // TK)                                   # (rows, 1)
    computed = 0
    for q0 in range(0, chunk, QB):
        k0 = np.arange(int(tiles.max(initial=0)))[None] * TK    # (1, tiles)
        n, _ = _tile_extent(start, lengths, q0, k0, QB, KB, nk, window, np)
        computed += int(np.where(k0 < tiles * TK, n, 0).sum())
    blocks = int(tiles.sum()) * nk * (chunk // QB)
    return {"prefill_blocks": blocks,
            "prefill_blocks_skipped": blocks - computed}


def _prefill_kernel(bt_ref, start_ref, len_ref, layer_ref, q_ref, k_hbm,
                    v_hbm, alibi_ref, o_ref, kbuf, vbuf, sems, qbd, acc,
                    m_scr, *rest, scale: float, n_heads: int, kv_heads: int,
                    has_alibi: bool, block_pages: int,
                    window: Optional[int] = None, sink_ref=None):
    *l_scr, slot_ref = rest
    b = pl.program_id(0)
    B = pl.num_programs(0)
    _, P, BS, W = kbuf.shape
    TK = P * BS
    PB = block_pages
    KB, nk = PB * BS, P // PB
    C = q_ref.shape[1]
    D = W // kv_heads
    Dv = vbuf.shape[3] // kv_heads      # a head's values: D, or narrower
    G = n_heads // kv_heads
    # a group's scratch: (query blocks, its heads x QBP rows, the slab)
    n_groups, nq, rows, WG = qbd.shape
    HP = WG // D                        # KV heads a group, G * HP queries'
    WGv = HP * Dv                       # the group's slab of the values
    QBP = rows // (G * HP)              # a head's rows: QB, up to a row tile
    QB = C // nq
    # the sum of a head's p rides the lanes its slab leaves it, else l_scr
    sums_in_acc = not l_scr
    pd = qbd.dtype
    scale_s = 1.0 if _fold_scale(pd, scale) else scale
    start = start_ref[b]
    length = len_ref[b]
    n_tiles = pl.cdiv(length, TK)
    each_copy = _page_copies(bt_ref, len_ref, layer_ref[0],
                             ((k_hbm, kbuf), (v_hbm, vbuf)), sems)

    def lanes(g, width):
        """Group ``g``'s slab of ``width`` lanes: where there is more than
        one group a slab is whole 128-lane tiles, so a loop can address it."""
        first = g * width
        return pl.ds(first if isinstance(first, int)
                     else pl.multiple_of(first, LANES), width)

    def each(n, body):
        """``body(i)`` for i < n: a loop, unless there is one turn."""
        if n == 1:
            return body(0)

        def step(i, carry):
            body(i)
            return carry

        jax.lax.fori_loop(0, n, step, 0)

    def queries(qb):
        return pl.ds(qb * QB if isinstance(qb, int)
                     else pl.multiple_of(qb * QB, QB), QB)

    def head_rows(j):
        return slice(j * QBP, j * QBP + QB)

    own = [jax.lax.broadcasted_iota(jnp.int32, (1, WGv), 1) // Dv == h
           for h in range(HP)]

    @pl.when(b == 0)
    def _first_row():
        slot_ref[0] = 0
        if QBP != QB:
            # the rows between a head's queries and its next row tile
            qbd[:] = jnp.zeros_like(qbd)

    @pl.when(n_tiles == 0)
    def _empty_row():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(n_tiles > 0)
    def _row():
        base = _first_tile(each_copy, len_ref, slot_ref, b)
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        for l in l_scr:
            l[:] = jnp.zeros_like(l)

        def sinks(g, qb):
            # a head's sink is one more score that weighs no value: its
            # running maximum starts at it and its running sum at 1, in the
            # lanes of the accumulator or in a sum of its own
            for j in range(G * HP):
                m_scr[g, qb, head_rows(j), :] = jnp.full(
                    (QB, LANES), sink_ref[0, g * G * HP + j])
                if sums_in_acc:
                    acc[g, qb, head_rows(j), :] = jnp.broadcast_to(
                        jnp.where(own[j // G], 0.0, 1.0), (QB, WGv))
                else:
                    l_scr[0][g, qb, head_rows(j), :] = jnp.ones(
                        (QB, LANES), jnp.float32)

        if sink_ref is not None:
            each(n_groups, lambda g: each(nq, functools.partial(sinks, g)))

        def lay_out(g, qb):
            # q block-diagonal over its group's slab: a head's rows hold its
            # D lanes where its KV head's lie in the page and zeros in the
            # others', so that ONE product against the slab as it is stored
            # scores every head of the group (an added zero changes no
            # sum); the heads stacked along the rows. ``scale`` rides on q
            # where that is exact
            x = q_ref[0, queries(qb), lanes(g, G * HP * D)]
            for j in range(G * HP):
                h = j // G
                piece, _ = _scaled(x[:, j * D:(j + 1) * D].astype(pd), scale)
                beside = [jnp.zeros((QB, w), pd)
                          for w in (h * D, WG - (h + 1) * D)]
                qbd[g, qb, head_rows(j), :] = jnp.concatenate(
                    [part for part in (beside[0], piece, beside[1])
                     if part.shape[1]], axis=1)

        each(n_groups, lambda g: each(nq, functools.partial(lay_out, g)))

        def group(g, *, slot, qb, k0, keys, keep):
            # the tile's first ``keys`` keys (static: whole sub-blocks)
            k = kbuf[slot, :keys // BS, :, lanes(g, WG)].reshape(keys, WG)
            v = vbuf[slot, :keys // BS, :, lanes(g, WGv)].reshape(keys, WGv)
            s = jax.lax.dot_general(
                qbd[g, qb], k.astype(pd), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # (rows, keys)
            if scale_s != 1.0:
                s = s * scale_s
            if has_alibi:
                # left-aligned layout: a column IS the key position
                col = (k0 + jax.lax.broadcasted_iota(
                    jnp.int32, (1, keys), 1)).astype(jnp.float32)
                slope = jnp.concatenate(
                    [jnp.full((QBP, 1), alibi_ref[0, g * G * HP + j])
                     for j in range(G * HP)], axis=0)
                s = s + slope * col
            if keep is not None:
                s = jnp.where(keep, s, NEG_INF)
            m_prev = m_scr[g, qb]                               # lane-replicated
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            m_scr[g, qb] = m_new
            p = jnp.exp(s - m_new[:, :1])
            if sums_in_acc:
                # ones in the lanes of the slab's other heads: the value
                # product sums p's rows into them, and the running sum is
                # rescaled with the accumulator it rides in. p is rounded to
                # the values' dtype, as the reference and the training flash
                # kernels round it
                p = p.astype(v.dtype)
                pv = jnp.concatenate([
                    jax.lax.dot_general(
                        p[h * G * QBP:(h + 1) * G * QBP],
                        jnp.where(own[h], v, jnp.ones_like(v)),
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    for h in range(HP)], axis=0)
            else:
                l_scr[0][g, qb] = corr * l_scr[0][g, qb] + jnp.sum(
                    p, axis=1, keepdims=True)
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            acc[g, qb] = (acc[g, qb] * (corr if WGv == LANES else corr[:, :1])
                          + pv)

        def visit(qb, *, t, slot, keys, masked):
            k0 = t * TK
            keep = None
            if masked:
                # ONE mask a visit, for every group: a query sees the keys
                # at or below its position and none past the row's real
                # tokens (a pad query stands at the last of them: finite,
                # and never read)
                col = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, keys), 1)
                qpos = jnp.minimum(
                    start + qb * QB + jax.lax.broadcasted_iota(
                        jnp.int32, (rows, 1), 0) % QBP, length - 1)
                keep = col <= qpos                              # (rows, keys)
                if window is not None:
                    keep = keep & (col > qpos - window)
            each(n_groups, functools.partial(group, slot=slot, qb=qb, k0=k0,
                                             keys=keys, keep=keep))

        def tile(t, carry):
            slot = (base + t) % 2
            _tile_arrives(each_copy, vbuf, b, B, t, n_tiles, slot, length)

            def block(qb):
                n, whole = _tile_extent(start, length, qb * QB, t * TK, QB,
                                        KB, nk, window, jnp)
                # a body a number of sub-blocks, and one more for a whole
                # tile that needs no mask (fewer sub-blocks are the
                # diagonal's, or a ragged row's end: masked, or as good as)
                for sub in range(1, nk + 1):
                    at = dict(t=t, slot=slot, keys=sub * KB)
                    if sub < nk:
                        pl.when(n == sub)(functools.partial(
                            visit, qb, masked=True, **at))
                        continue
                    for masked in (False, True):
                        pl.when((n == sub) & (whole != masked))(
                            functools.partial(visit, qb, masked=masked, **at))

            each(nq, block)
            return carry

        jax.lax.fori_loop(0, n_tiles, tile, 0)
        slot_ref[0] = (base + n_tiles) % 2

        def finish(g, qb):
            out = []
            for j in range(G * HP):
                h = j // G
                a = acc[g, qb, head_rows(j), :]
                if sums_in_acc:
                    other = (h + 1) % HP * Dv
                    l = a[:, other:other + 1]
                else:
                    l = l_scr[0][g, qb, head_rows(j), :1]
                # a block of pad queries alone was never computed: zeros
                out.append(a[:, h * Dv:(h + 1) * Dv]
                           / jnp.where(l == 0.0, 1.0, l))
            o_ref[0, queries(qb), lanes(g, G * HP * Dv)] = (
                out[0] if len(out) == 1
                else jnp.concatenate(out, axis=1)).astype(o_ref.dtype)

        each(n_groups, lambda g: each(nq, functools.partial(finish, g)))


def paged_prefill_attention(q: jax.Array, k_arena: jax.Array,
                            v_arena: jax.Array, layer,
                            block_table: jax.Array, start: jax.Array,
                            lengths: Optional[jax.Array] = None,
                            alibi: Optional[jax.Array] = None,
                            scale: Optional[float] = None,
                            interpret: bool = False,
                            window: Optional[int] = None,
                            sink: Optional[jax.Array] = None) -> jax.Array:
    """Chunked-prefill attention through the block table: q (B, C, N, D) —
    C contiguous queries per row at absolute positions ``start[b] + s``
    (the serving ``prefill_chunk`` contract; the chunk's own keys must
    already be scatter-written into the arena); arenas (L, NUM_BLOCKS,
    BLOCK, K*D) and the int32 scalar ``layer`` to read, as in
    ``paged_decode_attention``; lengths (B,) int32 — the keys a row holds
    up to and including its last REAL query (``start + n_valid``; None: the
    whole chunk is real; 0 ⇒ inactive row, output zeros). Returns
    (B, C, N, D). The decode walk under a chunk of queries: the grid runs
    over the rows, a row's step copies ITS ``ceil(length / BLOCK)`` resident
    pages from the arenas in HBM, a tile of pages at a time with the next in
    flight, and flash-accumulates every head against each tile.

    How a tile step's blocks are derived (``_chunk_blocks``, from the call's
    static shapes alone): a GROUP is the KV heads of one 128-lane slab of
    the page with their query heads (``_heads_per_group``; the whole page
    where it has no such slab); a QUERY BLOCK is the chunk, halved while
    the group's heads times its queries, the rows one product stacks,
    exceed ``_BLOCK_ROWS``; a tile's keys part into SUB-BLOCKS of the whole
    pages that hold ``_BLOCK_KEYS`` keys at most. A (query block, tile)
    visit computes the tile's first sub-blocks up to the block's last
    query (``_tile_extent``): none of a tile above the chunk's diagonal,
    past the row's keys, below every query's window, or for pad queries
    alone; a visit every query sees whole takes no mask, any other builds
    ONE mask for all its groups. Inside a visit a group's heads are whole
    slabs: q lies block-diagonally over the slab (laid out once a row,
    ``scale`` folded in where that is exact), so one product scores the
    group's heads, stacked along its rows, against the slab as stored; no
    head is sliced out of q, k, v or the accumulator. Running max (and
    sum) are lane-replicated, a row a query. The products take q, k and v
    as they are stored and sum in float32; ``p`` is rounded to the values'
    dtype for the value product. Where a slab holds more heads than one
    (``_sums_in_slab``), a head's value product carries ones in the lanes
    of the slab's OTHER heads and so the sum of ``p`` (of the rounded
    ``p``: the weights that were applied), rescaled with the accumulator it
    rides in; a slab that is one head's keeps a float32 sum of its own.
    Queries past a row's real tokens come out finite (zeros, where their
    block holds no real query) and mean nothing. ``window`` (static): a
    query sees its own key and the ``window - 1`` before it, of the pages
    the table names, which the caller starts at the window's first page
    (``paged_attention``). The values' arena may be narrower a head than the
    keys' (L, NUM_BLOCKS, BLOCK, K*Dv): the result is (B, C, N, Dv), and a
    group's slab is whole lane tiles in both. ``sink`` (N,) float32: a
    learned score a head that takes its share of the softmax's mass and
    weighs no value (``paged_decode_attention``). A chunk whose queries the
    kernel cannot hold (64 heads of 192 under 1,024 queries: q alone is 25
    MB) goes down as rows of fewer (``_chunk_parts``)."""
    B, C, N, D = q.shape
    K = _kv_heads(k_arena, N, D)
    BS, W = k_arena.shape[2:]
    Dv = _value_dim(v_arena, K)
    _check_page_fits(BS, W, k_arena.dtype)
    if lengths is None:
        lengths = start + C
    sizes = (N, K, D, Dv, BS, q.dtype, k_arena.dtype)
    parts = _chunk_parts(C, *sizes)
    if parts > 1:
        starts, held = _part_rows(start, lengths, C, parts, jnp)
        return paged_prefill_attention(
            q.reshape(B * parts, C // parts, N, D), k_arena, v_arena, layer,
            jnp.repeat(block_table, parts, axis=0), starts, held,
            alibi=alibi, scale=scale, interpret=interpret, window=window,
            sink=sink).reshape(B, C, N, Dv)
    pages, HP, QB, PB = _chunk_geometry(C, N, K, BS, W, k_arena.dtype, Dv)
    G = N // K
    scale = scale if scale is not None else D ** -0.5
    has_alibi = alibi is not None
    alibi_arr = (alibi.astype(jnp.float32).reshape(1, N) if has_alibi
                 else jnp.zeros((1, N), jnp.float32))
    # the products take q and the keys in the wider of their two dtypes
    pd = jnp.promote_types(q.dtype, k_arena.dtype)
    # a group's scratch: its G * HP query heads stacked along the rows, each
    # on a row tile of its own, a query block at a time; as wide as the slab
    rows = G * HP * pl.cdiv(QB, _ROW_TILE) * _ROW_TILE
    per_group = (K // HP, C // QB, rows)
    # the running sum needs lanes of its own where a slab is one head's
    stats = [per_group + (LANES,)] * (1 if _sums_in_slab(HP) else 2)
    per_head = [alibi_arr] + ([] if sink is None else [
        sink.astype(jnp.float32).reshape(1, N)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[
            # a token's heads side by side in the lanes, as the arena has
            # them: (B, C, N, D) goes in and comes out without a transpose
            pl.BlockSpec((1, C, N * D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            # alibi's slopes and the sinks, a scalar a head
            *[pl.BlockSpec(memory_space=pltpu.SMEM)] * len(per_head),
        ],
        out_specs=pl.BlockSpec((1, C, N * Dv), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages, BS, W), k_arena.dtype),
            pltpu.VMEM((2, pages, BS, K * Dv), v_arena.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),          # (k | v, buffer)
            pltpu.VMEM(per_group + (HP * D,), pd),    # block-diagonal q
            pltpu.VMEM(per_group + (HP * Dv,), jnp.float32),
            *(pltpu.VMEM(shape, jnp.float32) for shape in stats),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(_prefill_kernel, scale=scale, n_heads=N,
                               kv_heads=K, has_alibi=has_alibi,
                               block_pages=PB,
                               **({} if window is None
                                  else {"window": int(window)}))
    if sink is not None:
        def kernel(*refs, _body=kernel):    # the sinks ride behind alibi
            refs = list(refs)
            _body(*refs, sink_ref=refs.pop(8))

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, N * Dv), q.dtype),
        # rows in order: a row starts the copies of the next one's first
        # tile. What the kernel holds and a visit makes, and 4 MiB more that
        # are the compiler's own (a tile's tail zeroed in float32, a page's
        # copies)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_chunk_vmem(C, *sizes) + (4 << 20)),
        name="paged_prefill_attention",
        interpret=interpret,
    )(block_table.astype(jnp.int32), start.astype(jnp.int32),
      lengths.astype(jnp.int32), _layer_operand(layer),
      q.reshape(B, C, N * D), k_arena, v_arena, *per_head)
    return out.reshape(B, C, N, Dv)


# ---------------------------------------------------------------------------
# jnp oracle / CPU fallback
# ---------------------------------------------------------------------------


def reference_paged_attention(q: jax.Array, k_arena: jax.Array,
                              v_arena: jax.Array, layer,
                              block_table: jax.Array, positions: jax.Array,
                              alibi: Optional[jax.Array] = None,
                              scale: Optional[float] = None,
                              window: Optional[int] = None,
                              sink: Optional[jax.Array] = None) -> jax.Array:
    """GQA-native jnp paged attention — parity oracle for both kernels and
    the CPU serving fallback. q (B, S, N, D); positions (B, S) absolute
    query positions (decode: the row's length-1; negative ⇒ row inactive,
    output zeros); arenas (L, NUM_BLOCKS, BLOCK, K*D) and the ``layer`` to
    read, gathered as ``arena[layer, block_table]`` — no pool-sized
    intermediate; mask is causality over true positions (left-aligned
    layout: gathered column == position), and under a ``window`` the
    query's own key and the ``window - 1`` before it (a table that is a ring
    of pages repeats them past the ring's length: every column in sight
    holds the position it stands for). The values may be narrower a head
    than the keys (``v_arena`` (..., K*Dv); the result (B, S, N, Dv));
    ``sink`` (N,): a score a head that joins the softmax's sum and weighs
    no value."""
    B, S, N, D = q.shape
    K = _kv_heads(k_arena, N, D)
    Dv = _value_dim(v_arena, K)
    BS = k_arena.shape[2]
    MAXB = block_table.shape[1]
    T = MAXB * BS
    G = N // K
    scale = scale if scale is not None else D ** -0.5
    kk = k_arena[layer, block_table].reshape(B, T, K, D)
    vv = v_arena[layer, block_table].reshape(B, T, K, Dv)
    # zero v beyond each row's max resident position: masked columns get
    # softmax weight 0, but 0 × NaN = NaN — scratch/recycled pages may
    # carry nonfinite residue (e.g. KV written under briefly-poisoned
    # params in an RLHF run), and it must never leak into live rows (the
    # Pallas kernels zero their edge-padded v rows for the same reason)
    colmask = (jnp.arange(T, dtype=jnp.int32)[None]
               <= jnp.max(positions, axis=1)[:, None])      # (B, T)
    vv = jnp.where(colmask[:, :, None, None], vv, 0)
    q5 = q.reshape(B, S, K, G, D)
    s = jnp.einsum("bskgd,btkd->bkgst", q5, kk).astype(jnp.float32) * scale
    col = jnp.arange(T, dtype=jnp.int32)
    if alibi is not None:
        al = alibi.astype(jnp.float32).reshape(K, G)
        s = s + al[None, :, :, None, None] * col.astype(jnp.float32)
    keep = col[None, None, :] <= positions[:, :, None]          # (B, S, T)
    if window is not None:
        keep = keep & (col[None, None, :] > positions[:, :, None] - window)
    s = jnp.where(keep[:, None, None, :, :], s, NEG_INF)
    if sink is None:
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    else:
        # the sink as one more column, dropped behind the softmax
        column = jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(1, K, G, 1, 1), s.shape[:-1] + (1,))
        p = jax.nn.softmax(jnp.concatenate([s, column], axis=-1),
                           axis=-1)[..., :-1].astype(q.dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", p, vv)
    # rows whose position is negative have an all-masked score row; the
    # softmax then returns uniform weights — zero them explicitly so
    # inactive rows are exactly 0 like the kernel
    inactive = (positions < 0)[:, :, None, None]
    o = jnp.where(inactive[:, :, None], 0.0, o.reshape(B, S, K, G, Dv))
    return o.reshape(B, S, N, Dv)


# ---------------------------------------------------------------------------
# the one place a paged read is chosen
# ---------------------------------------------------------------------------


def paged_attention(q: jax.Array, k_arena: jax.Array,
                    v_arena: Optional[jax.Array], layer,
                    block_table: jax.Array, positions: jax.Array,
                    alibi: Optional[jax.Array] = None,
                    scale: Optional[float] = None,
                    window: Optional[int] = None,
                    name: Optional[str] = None,
                    values: Optional[int] = None,
                    sink: Optional[jax.Array] = None) -> jax.Array:
    """The model's paged read, after its scatter: q (B, S, N, D) at absolute
    ``positions`` (B, S) against ``arena[layer]`` through ``block_table``;
    returns (B, S, N, D). Where the Pallas kernels run (``ops/registry``'s
    platform probe) one query a row takes the decode walk and S > 1 the
    prefill kernel, which reads ``positions[:, 0]`` as the row's start and
    the largest position + 1 as its length: the serving programs' contract
    that S > 1 queries sit at ``start + 0..S-1`` (slots past a row's real
    tokens ride position -1 and are never read; a row of them all holds
    nothing). Anywhere else: ``reference_paged_attention``.

    ``window`` (static): a query sees its own key and the ``window - 1``
    before it. The kernels' walks then START at the window's first page:
    they are handed the table from that page on, as many pages as a window
    and S queries can span, and positions counted from that page's first
    key, so that the pages below it cost no copy and no step, whatever the
    row's length; the keys of that first page that lie below the window are
    masked (``lo``, ``window``). ``name``: the decode walk's in a trace.
    ``sink`` (N,): a learned score a head in the softmax's sum.

    ``v_arena`` None: ``k_arena`` is ONE pool that is keys and values both,
    one key-value head as wide as a page whose values are the page's first
    ``values`` lanes, read with neither alibi nor a window and returned (B,
    S, N, ``values``). Its kernel is ``latent_decode_attention``, one query
    a row (a chunk of queries reads a latent pool expanded:
    ``latent_paged_attention``)."""
    if v_arena is None:
        if alibi is not None or window is not None or sink is not None:
            raise ValueError("a one-pool read takes neither alibi nor a "
                             "window nor sinks")
        if not registry.kernels_active():
            return reference_paged_attention(
                q, k_arena, k_arena, layer, block_table, positions,
                scale=scale)[..., :values]
        if q.shape[1] != 1:
            raise ValueError("the one-pool kernel reads one query a row, "
                             f"got {q.shape[1]}")
        return latent_decode_attention(
            q[:, 0], k_arena, layer, block_table, positions[:, 0] + 1,
            values, scale)[:, None]
    sunk = {} if sink is None else {"sink": sink}
    if not registry.kernels_active():
        return reference_paged_attention(q, k_arena, v_arena, layer,
                                         block_table, positions, alibi=alibi,
                                         scale=scale, window=window, **sunk)
    named = {**sunk, **({} if name is None else {"name": name})}
    if window is None:
        if q.shape[1] == 1:
            return paged_decode_attention(q[:, 0], k_arena, v_arena, layer,
                                          block_table, positions[:, 0] + 1,
                                          alibi=alibi, scale=scale,
                                          **named)[:, None]
        return paged_prefill_attention(q, k_arena, v_arena, layer,
                                       block_table, positions[:, 0],
                                       jnp.max(positions, axis=1) + 1,
                                       alibi=alibi, scale=scale, **sunk)
    S, BS = q.shape[1], k_arena.shape[2]
    start = positions[:, 0]
    below = jnp.maximum(start - (window - 1), 0)        # the window's first
    first = below // BS
    pages = (window + S - 2) // BS + 2
    block_table = jnp.take_along_axis(
        block_table, jnp.minimum(
            first[:, None] + jnp.arange(pages, dtype=jnp.int32),
            block_table.shape[1] - 1), axis=1)
    ends = jnp.maximum(jnp.max(positions, axis=1) + 1 - first * BS, 0)
    if S == 1:
        return paged_decode_attention(
            q[:, 0], k_arena, v_arena, layer, block_table, ends, alibi=alibi,
            scale=scale, lo=below - first * BS, **named)[:, None]
    return paged_prefill_attention(q, k_arena, v_arena, layer, block_table,
                                   start - first * BS, ends, alibi=alibi,
                                   scale=scale, window=window, **sunk)


# ---------------------------------------------------------------------------
# a latent pool: one latent and one roped key a token, no head's keys or values
# ---------------------------------------------------------------------------

# heads an expanded read attends at a time: their scores, (heads, C, T) in
# float32, are what bounds a chunk's memory
_EXPANDED_HEADS = 8


def latent_absorbed_attention(q_nope: jax.Array, q_rope: jax.Array,
                              wk_b: jax.Array, wv_b: jax.Array,
                              arena: jax.Array, layer,
                              block_table: jax.Array, positions: jax.Array,
                              scale: float) -> jax.Array:
    """The read of a latent pool with the expansion ABSORBED into the query
    and the output: ``qt_h = q_nope_h W_kb_h`` (a latent wide), score ``qt_h
    . c + q_rope_h . k_rope``, ``o_h = (sum p c) W_vb_h``. That is
    multi-query attention with ONE key-value head as wide as the page, whose
    values are the page's first ``R`` lanes: ``paged_attention`` takes the
    ONE pool (``latent_decode_attention``, the walk under the name
    ``LATENT_DECODE``: a page copied once, the latents mixed and nothing
    else written). q spans the page's lanes for the score: the absorbed
    query, the roped one, zeros over the pad. No key or value of any head
    is ever made."""
    R = wk_b.shape[-1]
    unused = arena.shape[-1] - R - q_rope.shape[-1]     # a page's pad lanes
    q = jnp.concatenate(
        [jnp.einsum("bsnd,ndr->bsnr", q_nope, wk_b), q_rope,
         jnp.zeros(q_rope.shape[:-1] + (unused,), q_rope.dtype)], axis=-1)
    mixed = paged_attention(q, arena, None, layer, block_table, positions,
                            scale=scale, values=R)
    return jnp.einsum("bsnr,nrv->bsnv", mixed, wv_b)


def latent_expanded_attention(q_nope: jax.Array, q_rope: jax.Array,
                              wk_b: jax.Array, wv_b: jax.Array,
                              arena: jax.Array, layer,
                              block_table: jax.Array, positions: jax.Array,
                              scale: float) -> jax.Array:
    """The read of a latent pool with every resident token's keys and
    values EXPANDED: the row's pages gathered through its table, ``k_h = [c
    W_kb_h | k_rope]`` and ``v_h = c W_vb_h`` made for ``_EXPANDED_HEADS``
    heads at a time, and plain causal attention over true positions (a pad
    query, position -1, gives zeros). The work follows the table's length,
    not the tokens in it: a chunk's read, where the absorbed form would
    carry the chunk's queries a latent wide."""
    B, S, N, _ = q_nope.shape
    R, Dv = wk_b.shape[-1], wv_b.shape[-1]
    T = block_table.shape[1] * arena.shape[2]
    rows = arena[layer, block_table].reshape(B, T, arena.shape[-1])
    col = jnp.arange(T, dtype=jnp.int32)
    # nothing of a page past the row's last position is read: 0 x NaN is NaN
    rows = jnp.where((col[None] <= jnp.max(positions, axis=1)[:, None])
                     [..., None], rows, 0)
    c, k_rope = rows[..., :R], rows[..., R:R + q_rope.shape[-1]]
    keep = (col[None, None] <= positions[:, :, None])[:, None]  # (B,1,S,T)
    G = min(N, _EXPANDED_HEADS)
    while N % G:
        G -= 1

    def heads(g):
        def mine(a, axis):
            return jax.lax.dynamic_slice_in_dim(a, g * G, G, axis)

        k_nope = jnp.einsum("btr,ndr->btnd", c, mine(wk_b, 0))
        v = jnp.einsum("btr,nrv->btnv", c, mine(wv_b, 0))
        s = (jnp.einsum("bsnd,btnd->bnst", mine(q_nope, 2), k_nope)
             + jnp.einsum("bsnd,btd->bnst", mine(q_rope, 2), k_rope)
             ).astype(jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(keep, s, NEG_INF), axis=-1)
        return jnp.einsum("bnst,btnv->bsnv", p.astype(v.dtype), v)

    out = jax.lax.map(heads, jnp.arange(N // G, dtype=jnp.int32))
    out = jnp.moveaxis(out, 0, 2).reshape(B, S, N, Dv)
    return jnp.where((positions < 0)[:, :, None, None], 0, out)


def latent_paged_attention(q_nope: jax.Array, q_rope: jax.Array,
                           wk_b: jax.Array, wv_b: jax.Array,
                           arena: jax.Array, layer, block_table: jax.Array,
                           positions: jax.Array, scale: float) -> jax.Array:
    """A latent-attention layer's paged read, after its write: ``q_nope``
    (B, S, N, Dn) and the roped ``q_rope`` (B, S, N, Dr) at absolute
    ``positions`` (B, S) against pool ``layer`` of ``arena`` (POOLS,
    NUM_BLOCKS, BLOCK, LANES), a token's latent (R), the roped key all heads
    share (Dr) and zeros up to whole lane tiles, through ``block_table``; ``wk_b`` (N, Dn, R) and ``wv_b`` (N, R,
    Dv) expand a latent into a head's keys and values. Returns (B, S, N,
    Dv). THE place the form of that read is chosen: one query a row reads
    absorbed, a chunk of queries expanded; both compute the same numbers."""
    read = (latent_absorbed_attention if q_nope.shape[1] == 1
            else latent_expanded_attention)
    return read(q_nope, q_rope, wk_b, wv_b, arena, layer, block_table,
                positions, scale)

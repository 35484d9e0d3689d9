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
  tile). All heads of a tile are scored in ONE product against q laid out
  block-diagonally over the ``K*D`` lanes, and weigh the values in one
  more: bf16 keys and values as stored, scores, softmax state and
  accumulator in float32, ``p`` into the value product as float32 (three
  bf16 terms stacked into the one product, ``_dot_f32``). GQA-native (KV
  heads never expanded), alibi in-kernel; a tile's tail past the row's
  length is masked by true position, in the scores and in v.
* ``paged_prefill_attention`` — the chunked-prefill mate: C queries at
  absolute positions ``start..start+C-1`` read prior context through the
  same table, flash-accumulating page by page (grid ``(B, K, MAXB)``), so a
  later chunk never materializes the gathered view either.

Layout contract (shared with ``models/transformer._layer_forward``): the
arena is LEFT-ALIGNED — the token at absolute position ``p`` sits in block
``table[p // BLOCK]`` at offset ``p % BLOCK`` — so a key's (page, offset)
coordinate IS its position: causality over true positions is the entire
validity story and the alibi key bias is exact by construction.

The kernels take the WHOLE arena ``(L, NUM_BLOCKS, BLOCK, K*D)`` and a
``layer`` index, never one layer's pool. ``layer`` (a traced int32 scalar:
the model's layer scan hands down its loop index) rides as a third
scalar-prefetch operand and the k/v index maps put it in front of the page
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

``reference_paged_attention`` is the pure-jnp oracle and CPU fallback:
GQA-native over the view gathered straight from the arena
(``arena[layer, block_table]``; no head expansion, no (B,S,T) mask
materialization).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
# k + v pages (prefill) or tiles of pages (decode), two buffers each — ONE
# budget shared with the dense decode kernel's tile sizing
from .decode_attention import VMEM_KV_BUDGET as _VMEM_PAGE_BUDGET
from .decode_attention import tiled_vmem_bytes
from . import registry

# keys a tile of the decode walk holds at most (and at least 128, where the
# budget allows): see ``_pages_per_tile``
_TILE_KEYS = 256


def _check_page_fits(block_size: int, width: int, dtype) -> None:
    """k + v ``(block_size, width)`` pages, double-buffered, as VMEM holds
    them."""
    per_page = 4 * tiled_vmem_bytes(block_size, width, dtype)
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


def _layer_operand(layer) -> jax.Array:
    """``layer`` as the (1,) int32 scalar-prefetch operand."""
    return jnp.asarray(layer, jnp.int32).reshape(1)


# ---------------------------------------------------------------------------
# decode: one query token per row
# ---------------------------------------------------------------------------


def _pages_per_tile(block_size: int, width: int, dtype) -> int:
    """Pages one tile of the decode walk holds — derived, not set: the
    largest power of two whose k + v tiles, two buffers each, fit the VMEM
    budget as VMEM lays them out, capped at ``_TILE_KEYS`` keys a tile (at
    least one page: ``_check_page_fits`` guards that one)."""
    pages = 1
    while (2 * pages * block_size <= _TILE_KEYS
           and 4 * tiled_vmem_bytes(2 * pages * block_size, width, dtype)
           <= _VMEM_PAGE_BUDGET):
        pages *= 2
    return pages


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


def _decode_kernel(bt_ref, len_ref, layer_ref, q_ref, k_hbm, v_hbm, alibi_ref,
                   o_ref, kbuf, vbuf, sems, lane, diag, qbd, acc, m_scr,
                   l_scr, slot_ref, *, scale: float, n_heads: int,
                   kv_heads: int, has_alibi: bool):
    r = pl.program_id(0)
    R = pl.num_programs(0)
    _, P, BS, W = kbuf.shape
    TK = P * BS
    N, D = q_ref.shape[1:]
    G = n_heads // kv_heads
    layer = layer_ref[0]
    length = len_ref[r]
    n_tiles = pl.cdiv(length, TK)

    def each_copy(row, tile, slot, wait=False):
        """Start (or wait for) the copy of every RESIDENT page of ``row``'s
        tile ``tile`` into buffer ``slot``: nothing for a table slot past
        the row's last resident page."""
        first = tile * P
        resident = jnp.minimum(pl.cdiv(len_ref[row], BS) - first, P)

        def page(p, carry):
            blk = bt_ref[row, first + p]
            for side, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                copy = pltpu.make_async_copy(hbm.at[layer, blk],
                                             buf.at[slot, p],
                                             sems.at[side, slot])
                copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, resident, page, 0)

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

    @pl.when(n_tiles == 0)
    def _empty_row():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(n_tiles > 0)
    def _row():
        # the buffer this row's first tile is in: the row above started it
        # beside its own last tile, unless that row was empty (or is none)
        base = slot_ref[0]

        @pl.when((r == 0) | (len_ref[jnp.maximum(r - 1, 0)] == 0))
        def _own_first_tile():
            each_copy(r, 0, base)

        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        qbd[:] = (_dot_f32(q_ref[0], lane[:], 0) * diag[:]).astype(qbd.dtype)

        def tile(t, carry):
            slot = (base + t) % 2
            # the next tile's copies go out before this one is computed:
            # this row's, or after its last the first of the row below
            @pl.when(t + 1 < n_tiles)
            def _next_tile():
                each_copy(r, t + 1, 1 - slot)

            @pl.when((t + 1 == n_tiles) & (r + 1 < R))
            def _next_row():
                each_copy(jnp.minimum(r + 1, R - 1), 0, 1 - slot)

            each_copy(r, t, slot, wait=True)

            # a tile's tail past the row's length holds what was there
            # before: another row's pages, or whatever the buffer started
            # with. Scores are masked below; v is zeroed: 0 * NaN is NaN
            @pl.when((t + 1) * TK > length)
            def _zero_tail():
                pos = (t * TK
                       + jax.lax.broadcasted_iota(jnp.int32, (P, BS, W), 0)
                       * BS
                       + jax.lax.broadcasted_iota(jnp.int32, (P, BS, W), 1))
                vbuf[slot] = jnp.where(pos < length,
                                       vbuf[slot].astype(jnp.float32),
                                       0.0).astype(vbuf.dtype)

            k = kbuf[slot].reshape(TK, W).astype(qbd.dtype)
            s = jax.lax.dot_general(
                qbd[:], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale    # (N, TK)
            col = t * TK + jax.lax.broadcasted_iota(jnp.int32, (1, TK), 1)
            if has_alibi:
                # left-aligned layout: the tile's column IS the key position
                s = s + alibi_ref[0][:, None] * col.astype(jnp.float32)
            s = jnp.where(col < length, s, NEG_INF)
            m_prev = m_scr[:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[:] = jnp.broadcast_to(
                corr * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
                l_scr.shape)
            m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
            # p goes into the value product as float32, not rounded to v's
            acc[:] = acc[:] * corr + _dot_f32(
                p, vbuf[slot].reshape(TK, W), 0)               # (N, K*D)
            return carry

        jax.lax.fori_loop(0, n_tiles, tile, 0)
        slot_ref[0] = (base + n_tiles) % 2
        out = _dot_f32(acc[:] * diag[:], lane[:], 1)           # (N, D)
        o_ref[0] = (out / l_scr[:, :1]).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_arena: jax.Array,
                           v_arena: jax.Array, layer,
                           block_table: jax.Array, lengths: jax.Array,
                           alibi: Optional[jax.Array] = None,
                           scale: Optional[float] = None,
                           interpret: bool = False) -> jax.Array:
    """q (R, N, D) — one new token per row; k/v_arena (L, NUM_BLOCKS, BLOCK,
    K*D) — the whole shared arena; layer — int32 scalar (may be traced),
    the layer whose pool is read; block_table (R, MAXB) int32 physical page
    ids (unfilled entries 0 = scratch); lengths (R,) int32 — valid keys per
    row INCLUDING the just-written token (0 ⇒ inactive row, output zeros).
    Returns (R, N, D). Reads only each row's resident pages of that layer."""
    R, N, D = q.shape
    K = _kv_heads(k_arena, N, D)
    BS, W = k_arena.shape[2:]
    _check_page_fits(BS, W, k_arena.dtype)
    pages = _pages_per_tile(BS, W, k_arena.dtype)
    scale = scale if scale is not None else D ** -0.5
    has_alibi = alibi is not None
    alibi_arr = (alibi.astype(jnp.float32).reshape(1, N) if has_alibi
                 else jnp.zeros((1, N), jnp.float32))
    # the products take q and the keys in the wider of their two dtypes
    pd = jnp.promote_types(q.dtype, k_arena.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((1, N, D), lambda r, *_: (r, 0, 0)),
            # the arenas stay where they lie: the kernel copies pages itself
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, N), lambda r, *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, N, D), lambda r, *_: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages, BS, W), k_arena.dtype),
            pltpu.VMEM((2, pages, BS, W), v_arena.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),          # (k | v, buffer)
            pltpu.VMEM((D, W), jnp.bfloat16),         # lane, 0/1: exact
            pltpu.VMEM((N, W), jnp.float32),          # diag
            pltpu.VMEM((N, W), pd),                   # block-diagonal q
            pltpu.VMEM((N, W), jnp.float32),
            pltpu.VMEM((N, LANES), jnp.float32),
            pltpu.VMEM((N, LANES), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    kernel = functools.partial(_decode_kernel, scale=scale, n_heads=N,
                               kv_heads=K, has_alibi=has_alibi)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, N, D), q.dtype),
        # rows in order: a row starts the copies of the next one's first tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_decode_attention",
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      _layer_operand(layer), q, k_arena, v_arena, alibi_arr)


# ---------------------------------------------------------------------------
# chunked prefill: C queries per row at positions start..start+C-1
# ---------------------------------------------------------------------------


def _heads_per_step(kv_heads: int, head_dim: int) -> int:
    """KV heads one prefill grid step reads: the fewest whose lanes make a
    legal block of the ``(BLOCK, K*D)`` page — a multiple of 128 (two heads
    at head_dim 64, one at 128), else the whole page."""
    for hp in range(1, kv_heads):
        if kv_heads % hp == 0 and (hp * head_dim) % LANES == 0:
            return hp
    return kv_heads


def _prefill_kernel(bt_ref, start_ref, layer_ref, q_ref, k_ref, v_ref,
                    alibi_ref, o_ref, acc, m_scr, l_scr, *, scale: float,
                    bs: int, C: int, has_alibi: bool):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    st = start_ref[b]
    HP, GC, D = q_ref.shape[1:]

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # a page is visible iff it holds positions <= the last query (st + C - 1)
    @pl.when(j * bs < st + C)
    def _step():
        col = j * bs + jax.lax.broadcasted_iota(jnp.int32, (GC, bs), 1)
        # query row r = (g, c): its absolute position is st + (r mod C)
        qpos = st + jax.lax.broadcasted_iota(jnp.int32, (GC, bs), 0) % C
        for h in range(HP):                           # static: HP is 1 or 2
            q = q_ref[0, h].astype(jnp.float32) * scale   # (GC, D), rows (g, c)
            k = k_ref[0, :, h * D:(h + 1) * D].astype(jnp.float32)  # (bs, D)
            v = v_ref[0, :, h * D:(h + 1) * D].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if has_alibi:
                s = s + alibi_ref[0, h][:, None] * col.astype(jnp.float32)
            s = jnp.where(col <= qpos, s, NEG_INF)
            m_prev = m_scr[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_scr[h] = jnp.broadcast_to(
                corr * l_scr[h, :, :1] + jnp.sum(p, axis=1, keepdims=True),
                l_scr.shape[1:])
            acc[h] = acc[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_scr[:, :, :1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / safe).astype(o_ref.dtype)


def paged_prefill_attention(q: jax.Array, k_arena: jax.Array,
                            v_arena: jax.Array, layer,
                            block_table: jax.Array, start: jax.Array,
                            alibi: Optional[jax.Array] = None,
                            scale: Optional[float] = None,
                            interpret: bool = False) -> jax.Array:
    """Chunked-prefill attention through the block table: q (B, C, N, D) —
    C contiguous queries per row at absolute positions ``start[b] + s``
    (the serving ``prefill_chunk`` contract; the chunk's own keys must
    already be scatter-written into the arena); arenas (L, NUM_BLOCKS,
    BLOCK, K*D) and the int32 scalar ``layer`` to read, as in
    ``paged_decode_attention``. Returns (B, C, N, D). Grid (B, K/HP, MAXB):
    each group of HP KV heads (``_heads_per_step``) flash-accumulates its
    G*C query rows per head, page by page; pages past ``start + C`` never
    move."""
    B, C, N, D = q.shape
    K = _kv_heads(k_arena, N, D)
    BS = k_arena.shape[2]
    MAXB = block_table.shape[1]
    G = N // K
    GC = G * C
    HP = _heads_per_step(K, D)
    _check_page_fits(BS, HP * D, k_arena.dtype)
    scale = scale if scale is not None else D ** -0.5
    has_alibi = alibi is not None
    # (B, C, N, D) -> (B, K, G*C, D): head-major rows grouped by KV head so
    # one grid step's queries share the page it just DMA'd
    qk = q.reshape(B, C, K, G, D).transpose(0, 2, 3, 1, 4).reshape(
        B, K, GC, D)
    if has_alibi:
        # per-row slopes, expanded host-side to match the (g, c) row order
        # (in-kernel gather by r // C would need an unsupported dynamic
        # index; a (K, G*C) operand is trivially small)
        alibi_arr = jnp.broadcast_to(
            alibi.astype(jnp.float32).reshape(K, G)[:, :, None],
            (K, G, C)).reshape(K // HP, HP, GC)
    else:
        alibi_arr = jnp.zeros((K // HP, HP, GC), jnp.float32)

    def _page(b, kb, j, bt_ref, start_ref, layer_ref):
        npages = jnp.maximum((start_ref[b] + C + BS - 1) // BS, 1)
        return (layer_ref[0], bt_ref[b, jnp.minimum(j, npages - 1)], 0, kb)

    def _heads(b, kb, j, *_):
        return (b, kb, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, K // HP, MAXB),
        in_specs=[
            pl.BlockSpec((1, HP, GC, D), _heads),
            pl.BlockSpec((None, 1, BS, HP * D), _page),
            pl.BlockSpec((None, 1, BS, HP * D), _page),
            pl.BlockSpec((1, HP, GC), lambda b, kb, j, *_: (kb, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, HP, GC, D), _heads),
        scratch_shapes=[
            pltpu.VMEM((HP, GC, D), jnp.float32),
            pltpu.VMEM((HP, GC, LANES), jnp.float32),
            pltpu.VMEM((HP, GC, LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(_prefill_kernel, scale=scale, bs=BS, C=C,
                               has_alibi=has_alibi)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, GC, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="paged_prefill_attention",
        interpret=interpret,
    )(block_table.astype(jnp.int32), start.astype(jnp.int32),
      _layer_operand(layer), qk, k_arena, v_arena, alibi_arr)
    return out.reshape(B, K, G, C, D).transpose(0, 3, 1, 2, 4).reshape(
        B, C, N, D)


# ---------------------------------------------------------------------------
# jnp oracle / CPU fallback
# ---------------------------------------------------------------------------


def reference_paged_attention(q: jax.Array, k_arena: jax.Array,
                              v_arena: jax.Array, layer,
                              block_table: jax.Array, positions: jax.Array,
                              alibi: Optional[jax.Array] = None,
                              scale: Optional[float] = None) -> jax.Array:
    """GQA-native jnp paged attention — parity oracle for both kernels and
    the CPU serving fallback. q (B, S, N, D); positions (B, S) absolute
    query positions (decode: the row's length-1; negative ⇒ row inactive,
    output zeros); arenas (L, NUM_BLOCKS, BLOCK, K*D) and the ``layer`` to
    read, gathered as ``arena[layer, block_table]`` — no pool-sized
    intermediate; mask is causality over true positions (left-aligned
    layout: gathered column == position)."""
    B, S, N, D = q.shape
    K = _kv_heads(k_arena, N, D)
    BS = k_arena.shape[2]
    MAXB = block_table.shape[1]
    T = MAXB * BS
    G = N // K
    scale = scale if scale is not None else D ** -0.5
    kk = k_arena[layer, block_table].reshape(B, T, K, D)
    vv = v_arena[layer, block_table].reshape(B, T, K, D)
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
    s = jnp.where(keep[:, None, None, :, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", p, vv)
    # rows whose position is negative have an all-masked score row; the
    # softmax then returns uniform weights — zero them explicitly so
    # inactive rows are exactly 0 like the kernel
    inactive = (positions < 0)[:, :, None, None]
    o = jnp.where(inactive[:, :, None], 0.0, o.reshape(B, S, K, G, D))
    return o.reshape(B, S, N, D)


# ---------------------------------------------------------------------------
# the one place a paged read is chosen
# ---------------------------------------------------------------------------


def paged_attention(q: jax.Array, k_arena: jax.Array, v_arena: jax.Array,
                    layer, block_table: jax.Array, positions: jax.Array,
                    alibi: Optional[jax.Array] = None) -> jax.Array:
    """The model's paged read, after its scatter: q (B, S, N, D) at absolute
    ``positions`` (B, S) against ``arena[layer]`` through ``block_table``;
    returns (B, S, N, D). Where the Pallas kernels run (``ops/registry``'s
    platform probe) one query a row takes the decode walk and S > 1 the
    prefill kernel, which reads ``positions[:, 0]`` as the row's start: the
    serving programs' contract that S > 1 queries sit at ``start + 0..S-1``
    (slots past a row's real tokens ride position -1 and are never read).
    Anywhere else: ``reference_paged_attention``."""
    if not registry.kernels_active():
        return reference_paged_attention(q, k_arena, v_arena, layer,
                                         block_table, positions, alibi=alibi)
    if q.shape[1] == 1:
        return paged_decode_attention(q[:, 0], k_arena, v_arena, layer,
                                      block_table, positions[:, 0] + 1,
                                      alibi=alibi)[:, None]
    return paged_prefill_attention(q, k_arena, v_arena, layer, block_table,
                                   positions[:, 0], alibi=alibi)

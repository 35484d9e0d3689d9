"""Pallas paged attention: block-table-aware decode + chunked-prefill kernels.

The serving layer's arena is a shared pool of fixed-size KV blocks
(``serving/paged_kv.py``; vLLM's PagedAttention, Kwon et al. SOSP '23). The
jnp read path materializes a dense ``(R, MAXB*BLOCK, K, D)`` view per layer
per step (``arena[block_table]``), so every decode token pays HBM traffic
proportional to the *pool view*, not the tokens actually resident. These
kernels walk each row's block table instead and DMA only **resident** pages:

* ``paged_decode_attention`` — single-query decode. Grid ``(R, MAXB)``; the
  block table and per-row lengths ride as scalar-prefetch operands, so the
  k/v BlockSpec index maps resolve ``table[row, page]`` *before* the pipeline
  issues the page's DMA. Non-resident trailing pages re-request the row's
  last resident page — consecutive identical block indices make the Pallas
  pipeline skip the copy, so a row with 3 live pages out of 64 costs 3 page
  DMAs, not 64. GQA-native (KV heads never expanded), alibi in-kernel.
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
materialization) — also measurably leaner than the PR-6 gather +
``dot_product_attention`` path that it replaces.
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
# k + v pages, double-buffered by the pipeline — ONE budget shared with
# the dense decode kernel's tile sizing
from .decode_attention import VMEM_KV_BUDGET as _VMEM_PAGE_BUDGET
from .decode_attention import tiled_vmem_bytes


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


def _decode_kernel(bt_ref, len_ref, layer_ref, q_ref, k_ref, v_ref, alibi_ref,
                   o_ref, acc, m_scr, l_scr, *, scale: float, bs: int,
                   n_heads: int, kv_heads: int, has_alibi: bool):
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    G = n_heads // kv_heads
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # page j holds positions [j*bs, (j+1)*bs) — all-future pages are skipped
    # (their DMA was already elided by the clamped index map)
    @pl.when(j * bs < length)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale      # (N, D)
        D = q.shape[-1]
        k = k_ref[0].astype(jnp.float32)              # (bs, K*D)
        v = v_ref[0].astype(jnp.float32)              # (bs, K*D)
        parts = []
        for kh in range(kv_heads):
            qg = q[kh * G:(kh + 1) * G]               # (G, D) static slice
            parts.append(jax.lax.dot_general(
                qg, k[:, kh * D:(kh + 1) * D], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))  # (G, bs)
        s = jnp.concatenate(parts, axis=0)            # (N, bs)
        col = j * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        if has_alibi:
            # left-aligned layout: the page column IS the key position
            s = s + alibi_ref[0][:, None] * col.astype(jnp.float32)
        s = jnp.where(col < length, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[:] = jnp.broadcast_to(
            corr * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_scr.shape)
        outs = []
        for kh in range(kv_heads):
            pg = p[kh * G:(kh + 1) * G]
            outs.append(jax.lax.dot_general(
                pg, v[:, kh * D:(kh + 1) * D], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        acc[:] = acc[:] * corr + jnp.concatenate(outs, axis=0)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)            # length-0 rows → 0
        o_ref[0] = (acc[:] / safe).astype(o_ref.dtype)


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
    BS = k_arena.shape[2]
    MAXB = block_table.shape[1]
    _check_page_fits(BS, K * D, k_arena.dtype)
    scale = scale if scale is not None else D ** -0.5
    has_alibi = alibi is not None
    alibi_arr = (alibi.astype(jnp.float32).reshape(1, N) if has_alibi
                 else jnp.zeros((1, N), jnp.float32))

    def _page(b, j, bt_ref, len_ref, layer_ref):
        # clamp to the row's last resident page: trailing grid steps
        # re-request the same block index, which the pipeline recognizes
        # and skips the DMA — only resident pages move
        last = jnp.maximum((len_ref[b] + BS - 1) // BS - 1, 0)
        return (layer_ref[0], bt_ref[b, jnp.minimum(j, last)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R, MAXB),
        in_specs=[
            pl.BlockSpec((1, N, D), lambda b, j, *_: (b, 0, 0)),
            # the layer dim is squeezed: the kernel sees (1, BS, K*D) pages
            pl.BlockSpec((None, 1, BS, K * D), _page),
            pl.BlockSpec((None, 1, BS, K * D), _page),
            pl.BlockSpec((1, N), lambda b, j, *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, N, D), lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((N, D), jnp.float32),
            pltpu.VMEM((N, LANES), jnp.float32),
            pltpu.VMEM((N, LANES), jnp.float32),
        ],
    )
    kernel = functools.partial(_decode_kernel, scale=scale, bs=BS,
                               n_heads=N, kv_heads=K, has_alibi=has_alibi)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, N, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
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

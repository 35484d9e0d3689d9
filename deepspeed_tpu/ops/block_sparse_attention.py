"""Block-sparse flash attention — Pallas kernels that SKIP inactive blocks.

Reference: ``ops/sparse_attention/`` (the Triton matmul/softmax kernels driven
by a SparsityConfig block layout, sparse_self_attention.py:12). The reference
materialises block-sparse SDD/DSD matmuls; the TPU-native formulation keeps
the flash-attention online-softmax structure (ops/flash_attention.py) and
makes the *grid* sparse instead:

  * host side (static, numpy): the fine-grained head layout (config.block
    granularity, e.g. 16) is tiled into 128x128 kernel tiles. For every
    (head, q-tile) the ACTIVE k-tiles are collected into a padded list, and
    each tile's token-level submask is deduplicated into a small unique-mask
    table (structured layouts repeat a handful of tile patterns).
  * kernel side: the k-tile list + mask ids ride as scalar-prefetch operands
    (`pltpu.PrefetchScalarGridSpec`) so the BlockSpec index maps follow the
    sparse structure — inactive tiles are never fetched or computed. This is
    the standard Mosaic sparse-attention pattern (cf. splash attention).

Compute/HBM cost is O(active tiles), not O(S^2/tile^2): a 10%-dense BigBird
layout does ~10% of the dense-kernel work. Padding slots point at the
all-zero mask id, which contributes exp(-inf)=0 — bitwise-identical to not
visiting them.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
TILE = 128          # kernel tile edge (q and kv)


@dataclasses.dataclass(frozen=True, eq=False)
class TilePlan:
    """Static sparse execution plan for one (layout, seq_len). Holds numpy
    (not device) arrays and hashes by identity so it can ride jit/custom_vjp
    static argument slots; build once per (config, S) and reuse."""

    kidx: np.ndarray      # (N, nq, A)  int32 — k-tile index per slot
    mid: np.ndarray       # (N, nq, A)  int32 — mask-table id per slot
    qidx_t: np.ndarray    # (N, nk, At) int32 — transposed: q-tiles per k-tile
    mid_t: np.ndarray     # (N, nk, At) int32
    masks: np.ndarray     # (U, TILE, TILE) int32 — unique tile submasks
    density: float        # active / total tiles (for introspection/benches)


def build_tile_plan(layout: np.ndarray, fine_block: int, seq_len: int
                    ) -> TilePlan:
    """Fine block layout (N, S/fb, S/fb) → padded active-tile lists + unique
    tile-mask table. Pure numpy; cache per (config, S)."""
    N = layout.shape[0]
    if seq_len % TILE:
        raise ValueError(f"seq_len {seq_len} must be a multiple of {TILE}")
    nt = seq_len // TILE
    r = TILE // fine_block  # fine blocks per tile edge
    if r * fine_block != TILE:
        raise ValueError(f"config.block ({fine_block}) must divide {TILE}")

    mask_table: Dict[bytes, int] = {}
    masks = []

    def mask_id(m: np.ndarray) -> int:
        key = m.tobytes()
        if key not in mask_table:
            mask_table[key] = len(masks)
            masks.append(m)
        return mask_table[key]

    zero_id = mask_id(np.zeros((TILE, TILE), np.int32))

    lists: list = [[[] for _ in range(nt)] for _ in range(N)]
    lists_t: list = [[[] for _ in range(nt)] for _ in range(N)]
    active = 0
    for h in range(N):
        fine = layout[h]
        for i in range(nt):
            for j in range(nt):
                sub = fine[i * r:(i + 1) * r, j * r:(j + 1) * r]
                if not sub.any():
                    continue
                active += 1
                tile_mask = np.kron(sub, np.ones((fine_block, fine_block),
                                                 np.int32))
                m = mask_id(np.ascontiguousarray(tile_mask))
                lists[h][i].append((j, m))
                lists_t[h][j].append((i, m))

    def pad(ls, width):
        idx = np.zeros((N, nt, width), np.int32)
        mid = np.full((N, nt, width), zero_id, np.int32)
        for h in range(N):
            for i in range(nt):
                for a, (j, m) in enumerate(ls[h][i]):
                    idx[h, i, a] = j
                    mid[h, i, a] = m
        return idx, mid

    A = max(1, max(len(ls) for head in lists for ls in head))
    At = max(1, max(len(ls) for head in lists_t for ls in head))
    kidx, mid = pad(lists, A)
    qidx_t, mid_t = pad(lists_t, At)
    return TilePlan(kidx=kidx, mid=mid, qidx_t=qidx_t, mid_t=mid_t,
                    masks=np.stack(masks),
                    density=active / float(N * nt * nt))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(kidx_ref, mid_ref, q_ref, k_ref, v_ref, m_ref, o_ref, lse_ref,
                acc, m_scr, l_scr, *, scale: float, causal: bool, na: int):
    n, i, a = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(a == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale              # (TILE, D)
    k = k_ref[0, 0].astype(jnp.float32)                      # (TILE, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    mask = m_ref[0] != 0                                     # (TILE, TILE)
    if causal:
        j = kidx_ref[n, i, a]
        row = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0) + i * TILE
        col = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1) + j * TILE
        mask = mask & (col <= row)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, :1]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # NEG_INF is finite, so a row that has seen no unmasked key would get
    # p = exp(NEG_INF - NEG_INF) = 1 per column; keep such rows at l == 0 so
    # the finalize zero-output branch actually fires.
    p = jnp.where(m_new <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
    correction = jnp.exp(m_prev - m_new)
    l_new = correction * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
    acc[:] = acc[:] * correction + jax.lax.dot_general(
        p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(a == na - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[:] / safe_l).astype(o_ref.dtype)
        # rows with no active key anywhere: lse = -inf-ish, output 0
        lse = jnp.where(l == 0.0, NEG_INF, m_scr[:, :1] + jnp.log(safe_l))
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref[0, 0].shape)


def _sparse_fwd(q, k, v, plan: TilePlan, *, causal: bool, scale: float,
                interpret: bool):
    B, N, S, D = q.shape
    nq, A = plan.kidx.shape[1], plan.kidx.shape[2]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, N, nq, A),
        in_specs=[
            pl.BlockSpec((1, 1, TILE, D), lambda b, n, i, a, ki, mi: (b, n, i, 0)),
            pl.BlockSpec((1, 1, TILE, D),
                         lambda b, n, i, a, ki, mi: (b, n, ki[n, i, a], 0)),
            pl.BlockSpec((1, 1, TILE, D),
                         lambda b, n, i, a, ki, mi: (b, n, ki[n, i, a], 0)),
            pl.BlockSpec((1, TILE, TILE),
                         lambda b, n, i, a, ki, mi: (mi[n, i, a], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, TILE, D), lambda b, n, i, a, ki, mi: (b, n, i, 0)),
            pl.BlockSpec((1, 1, TILE, LANES),
                         lambda b, n, i, a, ki, mi: (b, n, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((TILE, D), jnp.float32),
            pltpu.VMEM((TILE, LANES), jnp.float32),
            pltpu.VMEM((TILE, LANES), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, na=A),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, N, S, D), q.dtype),
                   jax.ShapeDtypeStruct((B, N, S, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(plan.kidx), jnp.asarray(plan.mid), q, k, v,
      jnp.asarray(plan.masks))
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(kidx_ref, mid_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, m_ref, dq_ref, acc, *, scale: float,
                   causal: bool, na: int):
    n, i, a = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(a == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    mask = m_ref[0] != 0
    if causal:
        j = kidx_ref[n, i, a]
        row = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0) + i * TILE
        col = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1) + j * TILE
        mask = mask & (col <= row)
    s = jnp.where(mask, s, NEG_INF)
    lse = lse_ref[0, 0][:, :1]
    # lse == NEG_INF marks key-less rows (see _fwd_kernel); their exp(s-lse)
    # would be exp(0) = 1 because NEG_INF is finite — force p (hence ds) to 0.
    p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
    do = do_ref[0, 0].astype(jnp.float32)
    dp = jax.lax.dot_general(do, v_ref[0, 0].astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0, 0][:, :1])
    acc[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    @pl.when(a == na - 1)
    def _finalize():
        dq_ref[0, 0] = (acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(qidx_ref, mid_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, m_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale: float, causal: bool, na: int):
    n, j, a = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(a == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # the transposed mask list stores the SAME tile mask (q-major); rows of
    # s here are q positions of tile qidx[n,j,a], columns are this k tile
    mask = m_ref[0] != 0
    if causal:
        i = qidx_ref[n, j, a]
        row = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 0) + i * TILE
        col = jax.lax.broadcasted_iota(jnp.int32, (TILE, TILE), 1) + j * TILE
        mask = mask & (col <= row)
    s = jnp.where(mask, s, NEG_INF)
    lse = lse_ref[0, 0][:, :1]
    p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
    do = do_ref[0, 0].astype(jnp.float32)
    dv_acc[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v_ref[0, 0].astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0, 0][:, :1])
    dk_acc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(a == na - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _sparse_bwd(causal, scale, interpret, plan: TilePlan, residuals, grads):
    q, k, v, o, lse = residuals
    do = grads[0]
    B, N, S, D = q.shape
    nq, A = plan.kidx.shape[1], plan.kidx.shape[2]
    nk, At = plan.qidx_t.shape[1], plan.qidx_t.shape[2]

    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (B, N, S, LANES))
    lse_pad = jnp.broadcast_to(lse[..., None], (B, N, S, LANES))

    dq_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, N, nq, A),
        in_specs=[
            pl.BlockSpec((1, 1, TILE, D), lambda b, n, i, a, ki, mi: (b, n, i, 0)),
            pl.BlockSpec((1, 1, TILE, D),
                         lambda b, n, i, a, ki, mi: (b, n, ki[n, i, a], 0)),
            pl.BlockSpec((1, 1, TILE, D),
                         lambda b, n, i, a, ki, mi: (b, n, ki[n, i, a], 0)),
            pl.BlockSpec((1, 1, TILE, D), lambda b, n, i, a, ki, mi: (b, n, i, 0)),
            pl.BlockSpec((1, 1, TILE, LANES),
                         lambda b, n, i, a, ki, mi: (b, n, i, 0)),
            pl.BlockSpec((1, 1, TILE, LANES),
                         lambda b, n, i, a, ki, mi: (b, n, i, 0)),
            pl.BlockSpec((1, TILE, TILE),
                         lambda b, n, i, a, ki, mi: (mi[n, i, a], 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, 1, TILE, D),
                                lambda b, n, i, a, ki, mi: (b, n, i, 0))],
        scratch_shapes=[pltpu.VMEM((TILE, D), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, na=A),
        grid_spec=dq_spec,
        out_shape=[jax.ShapeDtypeStruct((B, N, S, D), q.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(plan.kidx), jnp.asarray(plan.mid), q, k, v, do, lse_pad,
      delta, jnp.asarray(plan.masks))[0]

    dkv_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, N, nk, At),
        in_specs=[
            pl.BlockSpec((1, 1, TILE, D),
                         lambda b, n, j, a, qi, mi: (b, n, qi[n, j, a], 0)),
            pl.BlockSpec((1, 1, TILE, D), lambda b, n, j, a, qi, mi: (b, n, j, 0)),
            pl.BlockSpec((1, 1, TILE, D), lambda b, n, j, a, qi, mi: (b, n, j, 0)),
            pl.BlockSpec((1, 1, TILE, D),
                         lambda b, n, j, a, qi, mi: (b, n, qi[n, j, a], 0)),
            pl.BlockSpec((1, 1, TILE, LANES),
                         lambda b, n, j, a, qi, mi: (b, n, qi[n, j, a], 0)),
            pl.BlockSpec((1, 1, TILE, LANES),
                         lambda b, n, j, a, qi, mi: (b, n, qi[n, j, a], 0)),
            pl.BlockSpec((1, TILE, TILE),
                         lambda b, n, j, a, qi, mi: (mi[n, j, a], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, TILE, D), lambda b, n, j, a, qi, mi: (b, n, j, 0)),
            pl.BlockSpec((1, 1, TILE, D), lambda b, n, j, a, qi, mi: (b, n, j, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((TILE, D), jnp.float32),
                        pltpu.VMEM((TILE, D), jnp.float32)],
    )
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, na=At),
        grid_spec=dkv_spec,
        out_shape=[jax.ShapeDtypeStruct((B, N, S, D), k.dtype),
                   jax.ShapeDtypeStruct((B, N, S, D), v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(jnp.asarray(plan.qidx_t), jnp.asarray(plan.mid_t), q, k, v, do, lse_pad,
      delta, jnp.asarray(plan.masks))
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _block_sparse(q, k, v, plan, causal, scale, interpret):
    o, _ = _sparse_fwd(q, k, v, plan, causal=causal, scale=scale,
                       interpret=interpret)
    return o


def _block_sparse_fwd_rule(q, k, v, plan, causal, scale, interpret):
    o, lse = _sparse_fwd(q, k, v, plan, causal=causal, scale=scale,
                         interpret=interpret)
    return o, (q, k, v, o, lse)


def _block_sparse_bwd_rule(plan, causal, scale, interpret, residuals, g):
    q, k, v, o, lse = residuals
    dq, dk, dv = _sparse_bwd(causal, scale, interpret, plan,
                             (q, k, v, o, lse), (g,))
    return dq, dk, dv


_block_sparse.defvjp(_block_sparse_fwd_rule, _block_sparse_bwd_rule)


# Mosaic materialises scalar-dependent index-map state per grid step in SMEM
# (1 MB); measured on v5e: 4096-step grids compile, 32768-step grids exceed
# SMEM by ~1K. Conservative ceiling between the two:
MAX_GRID_STEPS = 8192


def sparse_grid_steps(batch: int, plan: TilePlan) -> int:
    """Largest grid-step count across the fwd/dq and dkv kernels — callers
    pre-check kernel eligibility (sparse_self_attention auto-fallback). The
    transposed dkv grid can be much wider than the fwd grid (global-column
    layouts: every q-tile hits k-tile 0, so At ~ nq while A stays small)."""
    fwd = batch * plan.kidx.shape[0] * plan.kidx.shape[1] * plan.kidx.shape[2]
    dkv = (batch * plan.qidx_t.shape[0] * plan.qidx_t.shape[1]
           * plan.qidx_t.shape[2])
    return max(fwd, dkv)


def block_sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           plan: TilePlan, causal: bool = False,
                           scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """Sparse flash attention over a TilePlan. q/k/v are (B, S, N, D) (model
    layout); returns (B, S, N, D). Differentiable (custom VJP with sparse
    dq/dkv kernels)."""
    B, S, N, D = q.shape
    if not interpret and sparse_grid_steps(B, plan) > MAX_GRID_STEPS:
        raise ValueError(
            f"sparse grid has {sparse_grid_steps(B, plan)} steps > "
            f"{MAX_GRID_STEPS} — the scalar-prefetch bookkeeping would "
            "exceed TPU SMEM. Split the batch (vmap/chunk) or use the "
            "dense-mask path (sparse_self_attention(use_kernel=False))")
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    o = _block_sparse(qh, kh, vh, plan, causal, scale, interpret)
    return jnp.swapaxes(o, 1, 2)

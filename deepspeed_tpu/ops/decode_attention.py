"""Pallas decode attention: single-query attention over the KV-cache arena.

TPU-native analog of the reference's inference ``softmax_context`` op
(csrc/transformer/inference/csrc/pt_binding.cpp attention path + softmax.cu,
incl. its alibi variant) — the memory-bandwidth-bound op of autoregressive
decoding: each step streams the whole cache once.

Design points (vs the training flash kernel):
  * GQA-native — KV heads are NOT expanded; each KV head's block is read once
    and shared by its G = N/K query heads (the reference expands per-head —
    on TPU that would multiply the only thing that matters here, HBM reads).
  * cache layout (B, T, K, D) is consumed directly (no per-step transpose).
  * per-head matmuls are tiny (G×D @ D×bt); that is fine — the op is
    bandwidth-bound, the MXU is not the limiter.
  * key-validity mask (B, T) doubles as the causal mask: the engine marks
    exactly the written cache slots valid.
  * optional ALiBi slopes (key-position-linear bias; the query term is
    softmax-shift-invariant).

jnp reference implementation is below (also GQA-native) — parity oracle and
CPU fallback.
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
# VMEM budget for double-buffered k+v blocks: at K=32,D=128 a 512-token
# f32 block sits ~100KB over the 16MB limit (observed on v5e), so budget
# half of VMEM. ONE constant shared with ops/paged_decode_attention.py —
# the two kernels sizing their KV tiles against different budgets would
# rot independently.
VMEM_KV_BUDGET = 8 << 20


def tiled_vmem_bytes(rows: int, lanes: int, dtype) -> int:
    """Bytes a ``(rows, lanes)`` slab — the last two dims of a block — takes
    in VMEM. It is laid out in (sublane, 128-lane) tiles — 8 sublanes of
    32-bit words, so 16 rows of bf16, 32 of int8 — and a short dim is padded
    up to its tile: a ``(K, 64)`` slab takes twice its nominal bytes, which
    is what put the B>=8 decode of a 32x64 model 100 KB over the scoped
    limit."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * max(4 // itemsize, 1)
    return (-(-rows // sublanes) * sublanes * -(-lanes // LANES) * LANES
            * itemsize)


def _kernel(q_ref, k_ref, v_ref, valid_ref, alibi_ref, kpos_ref, o_ref,
            acc, m_scr, l_scr, *, scale: float, bt: int, t_total: int,
            n_heads: int, kv_heads: int, has_alibi: bool):
    jt = pl.program_id(1)
    njt = pl.num_programs(1)
    G = n_heads // kv_heads
    D = q_ref.shape[-1]

    @pl.when(jt == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    q = q_ref[0].astype(jnp.float32) * scale          # (N, D)
    k = k_ref[0].astype(jnp.float32)                  # (bt, K, D)
    v = v_ref[0].astype(jnp.float32)                  # (bt, K, D)
    if t_total % bt != 0:
        # zero v's edge-padded rows: the pad is arbitrary bits (NaN under
        # the interpreter) and 0 * NaN would poison the p @ v accumulation
        # even though the scores there are masked to NEG_INF
        vrow = jt * bt + jax.lax.broadcasted_iota(jnp.int32, (bt, 1, 1), 0)
        v = jnp.where(vrow < t_total, v, 0.0)

    # s[n, t] per KV-head group: (G, D) @ (D, bt) — statically unrolled over
    # the (small) KV-head count
    parts = []
    for kh in range(kv_heads):
        qg = q[kh * G:(kh + 1) * G]                    # (G, D) static slice
        s_kh = jax.lax.dot_general(qg, k[:, kh, :], (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        parts.append(s_kh)                             # (G, bt)
    s = jnp.concatenate(parts, axis=0)                 # (N, bt)

    if has_alibi:
        # key POSITIONS ride as an operand (per-row — ragged batches give
        # generated keys their true positions, not arena columns)
        s = s + alibi_ref[0][:, None] * kpos_ref[0, 0][None, :]
    mask = (valid_ref[0, 0] != 0)[None, :]             # (1, bt)
    if t_total % bt != 0:
        # the final KV tile overruns the cache — its k/v/valid/kpos reads
        # are edge-padded garbage, so mask by true column (the valid-mask
        # alone can't be trusted there: the padding isn't 0-filled)
        col = jt * bt + jax.lax.broadcasted_iota(jnp.int32, (1, bt), 1)
        mask = mask & (col < t_total)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                             # (N, bt)
    corr = jnp.exp(m_prev - m_new)
    l_scr[:] = jnp.broadcast_to(corr * l_scr[:, :1]
                                + jnp.sum(p, axis=1, keepdims=True), l_scr.shape)
    outs = []
    for kh in range(kv_heads):
        pg = p[kh * G:(kh + 1) * G]                    # (G, bt) static slice
        outs.append(jax.lax.dot_general(pg, v[:, kh, :], (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))
    acc[:] = acc[:] * corr + jnp.concatenate(outs, axis=0)        # (N, D)
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(jt == njt - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / safe).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     valid: jax.Array, alibi: Optional[jax.Array] = None,
                     scale: Optional[float] = None,
                     key_positions: Optional[jax.Array] = None,
                     interpret: bool = False) -> jax.Array:
    """q (B, N, D) — one new token; k/v_cache (B, T, K, D); valid (B, T)
    marks live cache slots (causal + padding in one mask). Returns (B, N, D).
    Any T works: a final tile that overruns the cache is edge-padded by the
    pipeline and masked in-kernel (bucketed non-multiple cache lengths used
    to silently fall back to jnp attention).
    ``key_positions`` (B, T): true per-row key positions for the alibi bias
    (ragged batches — defaults to the arena column index)."""
    B, N, D = q.shape
    T, K = k_cache.shape[1], k_cache.shape[2]
    # the double-buffered k/v blocks must fit scoped VMEM (see
    # VMEM_KV_BUDGET above)
    per_t = 4 * tiled_vmem_bytes(K, D, k_cache.dtype)   # k+v, 2 buffers
    budget = VMEM_KV_BUDGET
    # bt is a middle block dim so sub-128 values are legal (the last-two-dims
    # tiling rule applies to (K, D), taken whole); grid = ceil(T/bt), the
    # final partial tile is masked by true column in-kernel
    bt = next((b for b in (512, 256, 128, 64, 32)
               if b * per_t <= budget), None)
    if bt is None:
        raise ValueError(
            f"decode_attention KV blocks do not fit VMEM: {K} kv-heads x "
            f"head_dim {D} ({k_cache.dtype}) needs {per_t} B/token — reduce "
            "kv heads per device (tensor parallelism) or cache dtype")
    scale = scale if scale is not None else D ** -0.5
    has_alibi = alibi is not None
    alibi_arr = (alibi.astype(jnp.float32).reshape(1, N) if has_alibi
                 else jnp.zeros((1, N), jnp.float32))
    valid3 = valid.astype(jnp.float32)[:, None, :]     # (B, 1, T)
    # kpos rides per-ROW only for ragged alibi; otherwise a shared (1,1,T)
    # arange (alibi) or a never-read dummy (no alibi) with a b-ignoring
    # index map — no per-step (B,T) materialisation on non-alibi models
    per_row = key_positions is not None
    if per_row:
        kpos3 = key_positions.astype(jnp.float32)[:, None, :]  # (B, 1, T)
    elif has_alibi:
        kpos3 = jnp.arange(T, dtype=jnp.float32)[None, None, :]
    else:
        kpos3 = jnp.zeros((1, 1, T), jnp.float32)
    kpos_map = ((lambda b, t: (b, 0, t)) if per_row
                else (lambda b, t: (0, 0, t)))

    kernel = functools.partial(_kernel, scale=scale, bt=bt, t_total=T,
                               n_heads=N, kv_heads=K, has_alibi=has_alibi)
    out = pl.pallas_call(
        kernel,
        grid=(B, pl.cdiv(T, bt)),
        in_specs=[
            pl.BlockSpec((1, N, D), lambda b, t: (b, 0, 0)),
            pl.BlockSpec((1, bt, K, D), lambda b, t: (b, t, 0, 0)),
            pl.BlockSpec((1, bt, K, D), lambda b, t: (b, t, 0, 0)),
            pl.BlockSpec((1, 1, bt), lambda b, t: (b, 0, t)),
            pl.BlockSpec((1, N), lambda b, t: (0, 0)),
            pl.BlockSpec((1, 1, bt), kpos_map),
        ],
        out_specs=pl.BlockSpec((1, N, D), lambda b, t: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, N, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((N, D), jnp.float32),
            pltpu.VMEM((N, LANES), jnp.float32),
            pltpu.VMEM((N, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="decode_attention",
        interpret=interpret,
    )(q, k_cache, v_cache, valid3, alibi_arr, kpos3)
    return out


def reference_decode_attention(q: jax.Array, k_cache: jax.Array,
                               v_cache: jax.Array, valid: jax.Array,
                               alibi: Optional[jax.Array] = None,
                               scale: Optional[float] = None,
                               key_positions: Optional[jax.Array] = None
                               ) -> jax.Array:
    """GQA-native jnp oracle (no KV expansion: batched over KV heads)."""
    B, N, D = q.shape
    T, K = k_cache.shape[1], k_cache.shape[2]
    G = N // K
    scale = scale if scale is not None else D ** -0.5
    q4 = (q * scale).reshape(B, K, G, D)
    s = jnp.einsum("bkgd,btkd->bkgt", q4.astype(jnp.float32),
                   k_cache.astype(jnp.float32))        # (B, K, G, T)
    if alibi is not None:
        al = alibi.astype(jnp.float32).reshape(K, G)
        kpos = (jnp.broadcast_to(jnp.arange(T, dtype=jnp.float32), (B, T))
                if key_positions is None
                else key_positions.astype(jnp.float32))
        s = s + al[None, :, :, None] * kpos[:, None, None, :]
    s = jnp.where((valid != 0)[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgt,btkd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, N, D).astype(q.dtype)

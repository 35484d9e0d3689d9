"""Pallas flash attention (forward + backward) for TPU.

TPU-native replacement for the reference's attention kernels: the fused
training softmax/attention path in ``csrc/transformer/softmax_kernels.cu`` +
``ds_transformer_cuda.cpp`` and the inference ``softmax_context`` op
(csrc/transformer/inference/csrc/softmax.cu). Online-softmax tiling (Flash
Attention 2 schedule): the KV loop is the innermost sequential grid dimension,
with running max/denominator kept in VMEM scratch; causal blocks above the
diagonal are skipped entirely.

Layouts: q (B, N, S, D); k, v (B, N, T, D) — callers with GQA expand KV heads
before the call (wrapper does it). All matmuls accumulate in fp32 on the MXU.

The backward pass is two Pallas kernels (dq, and dkv) following the standard
FA2 recomputation scheme with the forward's logsumexp as residual.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _block_sizes(s: int, t: int) -> Tuple[int, int]:
    """Pick (bq, bk) power-of-two blocks. Measured on v5e at B32/N12/S1024/D64:
    (128,128) 17.8ms fwd vs (1024,1024) 8.0ms — large tiles keep the MXU busy
    and amortise grid overhead; the fp32 score tile is capped at 4MB VMEM so
    long sequences fall back to (1024,1024) tiling with causal block-skip.
    Blocks are always >=128 (inputs are padded up), keeping the TPU sublane
    rule (multiples of 8) satisfied for any raw sequence length."""

    def pick(n: int, cap: int = 1024) -> int:
        b = 128
        while b < min(n, cap):
            b *= 2
        return b

    bq, bk = pick(s), pick(t)
    while bq * bk > 1 << 20:  # 4MB fp32 score tile budget
        if bq >= bk:
            bq //= 2
        else:
            bk //= 2
    return bq, bk


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, kvm_ref, slopes_ref, o_ref, lse_ref,
                acc, m_scr, l_scr, *, scale: float, causal: bool,
                bq: int, bk: int, kv_len: int, has_mask: bool,
                has_alibi: bool):
    i = pl.program_id(2)   # q block
    j = pl.program_id(3)   # kv block
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)

    # causal: skip blocks strictly above the diagonal
    run = True
    if causal:
        run = j * bk <= i * bq + (bq - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
        if has_alibi:
            # key-position-linear bias (query term is softmax-shift-invariant)
            s = s + slopes_ref[0, 0, 0] * col.astype(jnp.float32)
        mask = col < kv_len
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + i * bq
            mask = mask & (col <= row)
        if has_mask:
            mask = mask & (kvm_ref[0, 0] != 0)[None, :]      # key-padding (bk,)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]                                # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)            # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                               # (bq, bk)
        correction = jnp.exp(m_prev - m_new)                 # (bq, 1)
        l_new = correction * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc[:] = acc[:] * correction + jax.lax.dot_general(
            p, v_ref[0, 0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[:] / safe_l).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(safe_l)
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref[0, 0].shape)


def _fwd(q: jax.Array, k: jax.Array, v: jax.Array, kvm: jax.Array,
         slopes: jax.Array, *,
         causal: bool, scale: float, kv_len: int, has_mask: bool,
         has_alibi: bool, interpret: bool = False):
    B, N, S, D = q.shape
    T = k.shape[2]
    bq, bk = _block_sizes(S, T)
    grid = (B, N, S // bq, T // bk)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, kv_len=kv_len, has_mask=has_mask,
                               has_alibi=has_alibi)
    out_shape = [
        jax.ShapeDtypeStruct((B, N, S, D), q.dtype),
        jax.ShapeDtypeStruct((B, N, S, LANES), jnp.float32),  # lse (lane-padded)
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, n, i, j: (b, n, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, n, i, j: (b, n, j, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, n, i, j: (b, n, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, n, i, j: (b, 0, j)),
            pl.BlockSpec((1, 1, LANES), lambda b, n, i, j: (n, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, n, i, j: (b, n, i, 0)),
            pl.BlockSpec((1, 1, bq, LANES), lambda b, n, i, j: (b, n, i, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v, kvm, slopes)
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kvm_ref,
                   slopes_ref, dq_ref, acc, *, scale: float, causal: bool,
                   bq: int, bk: int, kv_len: int, has_mask: bool,
                   has_alibi: bool):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    run = True
    if causal:
        run = j * bk <= i * bq + (bq - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale
        k = k_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
        if has_alibi:
            s = s + slopes_ref[0, 0, 0] * col.astype(jnp.float32)
        mask = col < kv_len
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + i * bq
            mask = mask & (col <= row)
        if has_mask:
            mask = mask & (kvm_ref[0, 0] != 0)[None, :]
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, :1])                 # (bq, bk)
        do = do_ref[0, 0].astype(jnp.float32)                 # (bq, D)
        dp = jax.lax.dot_general(do, v_ref[0, 0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, :1])                # (bq, bk)
        acc[:] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _finalize():
        dq_ref[0, 0] = (acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kvm_ref,
                    slopes_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale: float, causal: bool, bq: int, bk: int, kv_len: int,
                    has_mask: bool, has_alibi: bool):
    j = pl.program_id(2)   # kv block (outer)
    i = pl.program_id(3)   # q block (inner, sequential)
    ni = pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        run = j * bk <= i * bq + (bq - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale           # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                   # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + j * bk
        if has_alibi:
            s = s + slopes_ref[0, 0, 0] * col.astype(jnp.float32)
        mask = col < kv_len
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + i * bq
            mask = mask & (col <= row)
        if has_mask:
            mask = mask & (kvm_ref[0, 0] != 0)[None, :]
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0, 0][:, :1])                 # (bq, bk)
        do = do_ref[0, 0].astype(jnp.float32)
        dv_acc[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0, 0].astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0, 0][:, :1])
        dk_acc[:] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(i == ni - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(causal: bool, scale: float, kv_len: int, has_mask: bool,
         has_alibi: bool, interpret: bool, residuals, grads):
    q, k, v, kvm, slopes, o, lse = residuals
    do = grads[0]
    B, N, S, D = q.shape
    T = k.shape[2]
    bq, bk = _block_sizes(S, T)

    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (B, N, S, LANES))
    lse_pad = jnp.broadcast_to(lse[..., None], (B, N, S, LANES))

    common_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, n, x, y: (b, n, x, 0)),      # q
        pl.BlockSpec((1, 1, bk, D), lambda b, n, x, y: (b, n, y, 0)),      # k
        pl.BlockSpec((1, 1, bk, D), lambda b, n, x, y: (b, n, y, 0)),      # v
        pl.BlockSpec((1, 1, bq, D), lambda b, n, x, y: (b, n, x, 0)),      # do
        pl.BlockSpec((1, 1, bq, LANES), lambda b, n, x, y: (b, n, x, 0)),  # lse
        pl.BlockSpec((1, 1, bq, LANES), lambda b, n, x, y: (b, n, x, 0)),  # delta
        pl.BlockSpec((1, 1, bk), lambda b, n, x, y: (b, 0, y)),            # kv mask
        pl.BlockSpec((1, 1, LANES), lambda b, n, x, y: (n, 0, 0)),         # slopes
    ]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, kv_len=kv_len, has_mask=has_mask,
                          has_alibi=has_alibi),
        grid=(B, N, S // bq, T // bk),
        in_specs=common_specs,
        out_specs=[pl.BlockSpec((1, 1, bq, D), lambda b, n, x, y: (b, n, x, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, N, S, D), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        name="flash_attention_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse_pad, delta, kvm, slopes)[0]

    # dkv: swap loop order — kv block outer (parallel), q block inner (sequential)
    swapped_specs = [
        pl.BlockSpec((1, 1, bq, D), lambda b, n, y, x: (b, n, x, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, n, y, x: (b, n, y, 0)),
        pl.BlockSpec((1, 1, bk, D), lambda b, n, y, x: (b, n, y, 0)),
        pl.BlockSpec((1, 1, bq, D), lambda b, n, y, x: (b, n, x, 0)),
        pl.BlockSpec((1, 1, bq, LANES), lambda b, n, y, x: (b, n, x, 0)),
        pl.BlockSpec((1, 1, bq, LANES), lambda b, n, y, x: (b, n, x, 0)),
        pl.BlockSpec((1, 1, bk), lambda b, n, y, x: (b, 0, y)),
        pl.BlockSpec((1, 1, LANES), lambda b, n, y, x: (n, 0, 0)),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, kv_len=kv_len, has_mask=has_mask,
                          has_alibi=has_alibi),
        grid=(B, N, T // bk, S // bq),
        in_specs=swapped_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, n, y, x: (b, n, y, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, n, y, x: (b, n, y, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, N, T, D), k.dtype),
                   jax.ShapeDtypeStruct((B, N, T, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        name="flash_attention_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse_pad, delta, kvm, slopes)
    return dq, dk, dv, jnp.zeros_like(kvm), jnp.zeros_like(slopes)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def _flash_core(q, k, v, kvm, slopes, causal: bool, scale: float,
                kv_len: int, has_mask: bool, has_alibi: bool,
                interpret: bool):
    o, _ = _fwd(q, k, v, kvm, slopes, causal=causal, scale=scale,
                kv_len=kv_len, has_mask=has_mask, has_alibi=has_alibi,
                interpret=interpret)
    return o


def _flash_core_fwd(q, k, v, kvm, slopes, causal, scale, kv_len, has_mask,
                    has_alibi, interpret):
    o, lse = _fwd(q, k, v, kvm, slopes, causal=causal, scale=scale,
                  kv_len=kv_len, has_mask=has_mask, has_alibi=has_alibi,
                  interpret=interpret)
    return o, (q, k, v, kvm, slopes, o, lse)


def _flash_core_bwd(causal, scale, kv_len, has_mask, has_alibi, interpret,
                    residuals, g):
    return _bwd(causal, scale, kv_len, has_mask, has_alibi, interpret,
                residuals, (g,))


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask=None, causal: bool = True,
                    scale: Optional[float] = None,
                    alibi: Optional[jax.Array] = None,
                    interpret: bool = False) -> jax.Array:
    """Drop-in replacement for models.transformer.dot_product_attention:
    q (B,S,N,D), k/v (B,T,Kh,D); returns (B,S,N,D). (B,T) key-padding masks
    and per-head ALiBi slopes (N,) run in-kernel; only full (B,S,T)
    attention masks (rare — decode path, which has its own kernel) fall
    back to the jnp path."""
    if mask is not None and mask.ndim != 2:
        from ..models.transformer import dot_product_attention

        return dot_product_attention(q, k, v, mask, causal=causal,
                                     alibi=alibi)
    B, S, N, D = q.shape
    T, K = k.shape[1], k.shape[2]
    if K != N:  # GQA: expand KV heads (wrapper-level; kernel sees MHA)
        k = jnp.repeat(k, N // K, axis=2)
        v = jnp.repeat(v, N // K, axis=2)
    scale = scale if scale is not None else D ** -0.5
    # (B,S,N,D) -> (B,N,S,D)
    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))
    bq, bk = _block_sizes(S, T)
    qt = _pad_to(qt, 2, bq)
    kt = _pad_to(kt, 2, bk)
    vt = _pad_to(vt, 2, bk)
    has_mask = mask is not None
    # float32 so the custom_vjp cotangent is an ordinary zero array
    kvm = (mask.astype(jnp.float32) if has_mask
           else jnp.ones((B, T), jnp.float32))[:, None, :]  # (B,1,T): TPU
    # needs sublane dim == full array dim for the tiny mask block
    kvm = _pad_to(kvm, 2, bk)
    has_alibi = alibi is not None
    slopes1 = (alibi.astype(jnp.float32).reshape(N) if has_alibi
               else jnp.zeros((N,), jnp.float32))
    # (N, 1, LANES) lane-broadcast layout so per-head blocks satisfy the TPU
    # tiling rules and the kernel reads a static [0,0,0] scalar
    slopes = jnp.broadcast_to(slopes1[:, None, None], (N, 1, LANES))
    o = _flash_core(qt, kt, vt, kvm, slopes, causal, scale, T, has_mask,
                    has_alibi, interpret)
    return o[:, :, :S].swapaxes(1, 2)


def make_attention_impl(interpret: bool = False):
    """attention_impl hook for TransformerConfig (ALiBi runs in-kernel —
    the reference softmax.cu alibi variant)."""

    def impl(q, k, v, mask, causal=True, alibi=None):
        return flash_attention(q, k, v, mask=mask, causal=causal,
                               alibi=alibi, interpret=interpret)

    return impl

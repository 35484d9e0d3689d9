"""Weight-only int8 matmul — the dequant happens on VMEM tiles inside the
kernel, overlapped with the int8 HBM DMA.

Reference: the int8 inference GEMMs of DeepSpeed-Inference
(``csrc/transformer/inference/csrc/gelu.cu`` quantized variants and the
MoQ/quantizer kernels, ``inference/engine.py`` dtype=torch.int8 path).

Why a kernel: XLA lowers ``x @ (q8.astype(bf16) * s)`` as a full-size
convert feeding the MXU, scheduled at VPU rate BEFORE the matmul — on a
memory-bound decode step that serialises convert + matmul and is slower
than the bf16 baseline. Here each (bk, bn) int8 tile is converted in VMEM
right after its DMA lands, while the next tile streams in: HBM cost is the
int8 bytes (half of bf16), convert cost hides under the DMA.

Decode-phase use: activations are (tokens<=8, K) matvecs, so M pads to the
8-sublane minimum and the grid runs over (N, K) weight tiles.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BK = 1024     # preferred contraction tile (1MB int8 DMA per step amortises
BN = 1024     # grid overhead; measured faster than 512 tiles on v5e decode)


def _tile(n: int, cap: int) -> int:
    """Largest power-of-two tile <= cap dividing n (callers guarantee
    n % 128 == 0) — tiling with true divisors instead of padding avoids
    materialising padded copies of big weights inside the decode loop."""
    t = cap
    while n % t:
        t //= 2
    return t


def _kernel(x_ref, q_ref, s_ref, o_ref, acc, *, nk: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    x = x_ref[:]                                      # (M, bk) — native dtype
    w = q_ref[:].astype(x.dtype)                      # (bk, bn): int8 values
    #   are exact in bf16 (8 mantissa bits) and the MXU takes bf16 directly
    acc[:] += jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _finalize():
        o_ref[:] = (acc[:] * s_ref[0].astype(jnp.float32)[None, :]
                    ).astype(o_ref.dtype)


def int8_matmul(x: jax.Array, q8: jax.Array, scale: jax.Array,
                out_dtype=None, interpret: bool = False) -> jax.Array:
    """x (M, K) @ dequant(q8 (K, N), scale (1, N)) -> (M, N). Per-output-
    channel scales apply to the accumulator (exact refactoring of
    ``x @ (q8 * s)``)."""
    M, K = x.shape
    N = q8.shape[1]
    if K % 128 or N % 128:
        raise ValueError(f"int8_matmul needs K,N % 128 == 0, got {K}x{N}")
    out_dtype = out_dtype or x.dtype
    mpad = (-M) % 8
    if mpad:
        x = jnp.pad(x, ((0, mpad), (0, 0)))
    Mp = x.shape[0]
    bk, bn = _tile(K, BK), _tile(N, BN)
    nk = K // bk
    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(N // bn, nk),
        in_specs=[
            pl.BlockSpec((Mp, bk), lambda n, k: (0, k)),
            pl.BlockSpec((bk, bn), lambda n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((Mp, bn), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((Mp, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, q8, scale)
    return out[:M]


def reference_int8_matmul(x, q8, scale, out_dtype=None):
    """Oracle: dense dequant then matmul."""
    out_dtype = out_dtype or x.dtype
    w = q8.astype(jnp.float32) * scale.astype(jnp.float32)
    return (x.astype(jnp.float32) @ w).astype(out_dtype)


# ---------------------------------------------------------------------------
# W8A8 decode GEMM: s8 x s8 on the MXU (dynamic activation quantization)
# ---------------------------------------------------------------------------
#
# The weight-only kernel above is VPU-BOUND, not DMA-bound: converting a
# (1024, 1024) int8 tile to bf16 costs ~1M VPU lane-ops (~2 us) while its
# DMA takes ~1.3 us at v5e HBM rate — the convert cannot hide, capping the
# kernel near ~60% of the int8 bandwidth roofline (exactly the r04
# bench_infer_int8 deficit). Feeding the MXU s8 x s8 removes the weight
# convert entirely: only the (M<=8, K) ACTIVATION row quantizes per call
# (K elements, trivial). Per-token absmax scaling keeps the decode GEMV's
# numerics within int8 rounding of the weight-only path (the reference's
# int8 path quantizes activations too — quantize_activation in
# csrc/transformer/inference/csrc/pt_binding.cpp).


def quantize_activation_rows(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(M, K) float -> (int8 values, (M, 1) fp32 per-row scales)."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    s = jnp.where(absmax == 0.0, 1.0, absmax / 127.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)
    return q.astype(jnp.int8), s


def _kernel_a8(x_ref, sx_ref, q_ref, s_ref, o_ref, acc, *, nk: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    # s8 x s8 -> s32 rides the MXU's native 8-bit path — no weight convert
    acc[:] += jax.lax.dot_general(
        x_ref[:], q_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(k == nk - 1)
    def _finalize():
        o_ref[:] = (acc[:].astype(jnp.float32)
                    * sx_ref[:].astype(jnp.float32)
                    * s_ref[0].astype(jnp.float32)[None, :]
                    ).astype(o_ref.dtype)


def int8_a8_matmul(x: jax.Array, q8: jax.Array, scale: jax.Array,
                   out_dtype=None, interpret: bool = False) -> jax.Array:
    """W8A8: x (M, K) float is row-quantized to int8 on the fly, then
    s8 x s8 -> s32 MXU GEMM with the product of row/channel scales applied
    at the end. Decode-phase drop-in for :func:`int8_matmul` when dynamic
    activation quantization is acceptable."""
    M, K = x.shape
    N = q8.shape[1]
    if K % 128 or N % 128:
        raise ValueError(f"int8_a8_matmul needs K,N % 128 == 0, got {K}x{N}")
    out_dtype = out_dtype or x.dtype
    xq, sx = quantize_activation_rows(x)
    mpad = (-M) % 8
    if mpad:
        xq = jnp.pad(xq, ((0, mpad), (0, 0)))
        sx = jnp.pad(sx, ((0, mpad), (0, 0)))
    Mp = xq.shape[0]
    bk, bn = _tile(K, BK), _tile(N, BN)
    nk = K // bk
    out = pl.pallas_call(
        functools.partial(_kernel_a8, nk=nk),
        grid=(N // bn, nk),
        in_specs=[
            pl.BlockSpec((Mp, bk), lambda n, k: (0, k)),
            pl.BlockSpec((Mp, 1), lambda n, k: (0, 0)),
            pl.BlockSpec((bk, bn), lambda n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((Mp, bn), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((Mp, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xq, sx, q8, scale)
    return out[:M]


def reference_int8_a8_matmul(x, q8, scale, out_dtype=None):
    """Oracle: explicit activation quantization + integer matmul."""
    out_dtype = out_dtype or x.dtype
    xq, sx = quantize_activation_rows(x)
    acc = xq.astype(jnp.int32) @ q8.astype(jnp.int32)
    return (acc.astype(jnp.float32) * sx * scale.astype(jnp.float32)
            ).astype(out_dtype)


def _kernel4_a8(xl_ref, xh_ref, sx_ref, q_ref, s_ref, o_ref, acc, *,
                nk2: int, bk2: int, gs: int, K2: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    q = q_ref[:].astype(jnp.int32)
    lo = (((q & 0xF) ^ 8) - 8).astype(jnp.int8)    # s8, NOT bf16: the dots
    hi = (((q >> 4) ^ 8) - 8).astype(jnp.int8)     # ride the 8-bit MXU path
    pl_lo = jax.lax.dot_general(xl_ref[:], lo, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
    pl_hi = jax.lax.dot_general(xh_ref[:], hi, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
    g_lo = jax.lax.div(k * bk2, gs)
    g_hi = jax.lax.div(K2 + k * bk2, gs)
    s_lo = s_ref[pl.ds(g_lo, 1), :].astype(jnp.float32)
    s_hi = s_ref[pl.ds(g_hi, 1), :].astype(jnp.float32)
    acc[:] += pl_lo.astype(jnp.float32) * s_lo \
        + pl_hi.astype(jnp.float32) * s_hi

    @pl.when(k == nk2 - 1)
    def _finalize():
        o_ref[:] = (acc[:] * sx_ref[:].astype(jnp.float32)
                    ).astype(o_ref.dtype)


def int4_a8_matmul(x: jax.Array, q4: jax.Array, scale: jax.Array,
                   out_dtype=None, interpret: bool = False) -> jax.Array:
    """W4A8: activation rows quantize to s8 on the fly; packed int4 weight
    tiles unpack to s8 IN VMEM (no bf16 convert) and both nibble planes
    ride the MXU's s8xs8 path. Removes the int4 body's convert ops —
    docs/quant_decode_analysis.md quantifies the remaining unpack cost."""
    M, K = x.shape
    K2, N = q4.shape
    if K != 2 * K2:
        raise ValueError(f"x K={K} vs packed K/2={K2}")
    G = scale.shape[0]
    gs = K // G
    out_dtype = out_dtype or x.dtype
    xq, sx = quantize_activation_rows(x)
    mpad = (-M) % 8
    if mpad:
        xq = jnp.pad(xq, ((0, mpad), (0, 0)))
        sx = jnp.pad(sx, ((0, mpad), (0, 0)))
    Mp = xq.shape[0]
    if K2 % 128 or N % 128:
        raise ValueError(f"int4_a8_matmul needs K/2,N % 128 == 0, "
                         f"got {K2}x{N}")
    bk2 = _tile(K2, BK)
    if G > 1:
        bk2 = min(bk2, _tile(gs, BK))
    bn = _tile(N, BN)
    nk2 = K2 // bk2
    out = pl.pallas_call(
        functools.partial(_kernel4_a8, nk2=nk2, bk2=bk2, gs=gs, K2=K2),
        grid=(N // bn, nk2),
        in_specs=[
            pl.BlockSpec((Mp, bk2), lambda n, k: (0, k)),
            pl.BlockSpec((Mp, bk2), lambda n, k: (0, k + nk2)),
            pl.BlockSpec((Mp, 1), lambda n, k: (0, 0)),
            pl.BlockSpec((bk2, bn), lambda n, k: (k, n)),
            pl.BlockSpec((G, bn), lambda n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((Mp, bn), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((Mp, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xq, xq, sx, q4, scale)
    return out[:M]


def reference_int4_a8_matmul(x, q4, scale, out_dtype=None):
    """Oracle: explicit activation quantization + integer matmul over the
    unpacked int4 values (scales applied per group)."""
    out_dtype = out_dtype or x.dtype
    xq, sx = quantize_activation_rows(x)
    q = q4.astype(jnp.int32)
    lo = ((q & 0xF) ^ 8) - 8
    hi = ((q >> 4) ^ 8) - 8
    w = jnp.concatenate([lo, hi], axis=-2)                 # (K, N) int
    K, N = w.shape
    G = scale.shape[0]
    # per-group integer partial products, scaled per (group, channel)
    accs = jnp.einsum(
        "mgk,gkn->mgn",
        xq.astype(jnp.float32).reshape(xq.shape[0], G, K // G),
        w.astype(jnp.float32).reshape(G, K // G, N))
    out = (accs * scale.astype(jnp.float32)[None]).sum(axis=1)
    return (out * sx).astype(out_dtype)


# ---------------------------------------------------------------------------
# int4: nibble-packed weights + per-group scales
# ---------------------------------------------------------------------------
#
# Reference: the 4-bit groupwise quantizer kernels
# (csrc/quantization/quantize.cu, csrc/includes/quantization_utils.h:468 —
# Params<qType, numBits=4> packs two values per int8).
#
# Packing layout: rows [0, K/2) ride in the LOW nibble, rows [K/2, K) in the
# HIGH nibble of a (K/2, N) uint8 array. Unpacking then never interleaves
# rows — each uint8 tile yields two CONTIGUOUS weight tiles (rows k and
# k + K/2), which pair with two x tiles fed through separate BlockSpecs.
# Scales are per (group, out-channel): s (G, N), groups contiguous along K.


def quantize_int4(w: jax.Array, group_size: int | None = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """(..., K, N) float → (q4 (..., K/2, N) uint8, s (..., G, N) fp32).
    Symmetric, qmax=7. ``group_size`` groups along K (None => one group per
    output channel). Leading dims (stacked layers) ride along."""
    *lead, K, N = w.shape
    if K % 2:
        raise ValueError(f"int4 packing needs even K, got {K}")
    gs = group_size or K
    if K % gs or (group_size and (K // 2) % gs):
        raise ValueError(f"group_size {gs} must divide K/2 ({K // 2})")
    G = K // gs
    w32 = w.astype(jnp.float32).reshape(*lead, G, gs, N)
    absmax = jnp.max(jnp.abs(w32), axis=-2)                  # (..., G, N)
    s = jnp.where(absmax == 0.0, 1.0, absmax / 7.0)
    q = jnp.clip(jnp.round(w32 / s[..., None, :]), -7, 7).astype(jnp.int32)
    q = q.reshape(*lead, K, N)
    lo = q[..., :K // 2, :] & 0xF
    hi = (q[..., K // 2:, :] & 0xF) << 4
    return (lo | hi).astype(jnp.uint8), s


def unpack_int4(q4: jax.Array, s: jax.Array, out_dtype=jnp.float32
                ) -> jax.Array:
    """Dense dequant oracle: (..., K/2, N) uint8 + (..., G, N) scales →
    (..., K, N)."""
    q = q4.astype(jnp.int32)
    lo = ((q & 0xF) ^ 8) - 8            # sign-extend 4-bit two's complement
    hi = ((q >> 4) ^ 8) - 8
    w = jnp.concatenate([lo, hi], axis=-2).astype(jnp.float32)  # (..., K, N)
    *lead, K, N = w.shape
    G = s.shape[-2]
    w = w.reshape(*lead, G, K // G, N) * s[..., None, :].astype(jnp.float32)
    return w.reshape(*lead, K, N).astype(out_dtype)


def _kernel4(xl_ref, xh_ref, q_ref, s_ref, o_ref, acc, *, nk2: int, bk2: int,
             gs: int, K2: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    q = q_ref[:].astype(jnp.int32)                     # (bk2, bn) packed
    lo = (((q & 0xF) ^ 8) - 8).astype(xl_ref.dtype)    # int4 exact in bf16
    hi = (((q >> 4) ^ 8) - 8).astype(xl_ref.dtype)
    pl_lo = jax.lax.dot_general(xl_ref[:], lo, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    pl_hi = jax.lax.dot_general(xh_ref[:], hi, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # per-k-tile group scales applied to the partial products — exact as
    # long as each k tile lies inside one group (enforced by the caller);
    # s rides as a full (G, bn) block, the group row picked dynamically
    g_lo = jax.lax.div(k * bk2, gs)
    g_hi = jax.lax.div(K2 + k * bk2, gs)
    s_lo = s_ref[pl.ds(g_lo, 1), :].astype(jnp.float32)
    s_hi = s_ref[pl.ds(g_hi, 1), :].astype(jnp.float32)
    acc[:] += pl_lo * s_lo + pl_hi * s_hi

    @pl.when(k == nk2 - 1)
    def _finalize():
        o_ref[:] = acc[:].astype(o_ref.dtype)


def int4_matmul(x: jax.Array, q4: jax.Array, scale: jax.Array,
                out_dtype=None, interpret: bool = False) -> jax.Array:
    """x (M, K) @ dequant(q4 (K/2, N), s (G, N)) -> (M, N). Each packed tile
    dequants to TWO weight tiles in VMEM (quarter the HBM bytes of bf16)."""
    M, K = x.shape
    K2, N = q4.shape
    if K != 2 * K2:
        raise ValueError(f"x K={K} vs packed K/2={K2}")
    G = scale.shape[0]
    gs = K // G
    out_dtype = out_dtype or x.dtype
    mpad = (-M) % 8
    if mpad:
        x = jnp.pad(x, ((0, mpad), (0, 0)))
    Mp = x.shape[0]
    if K2 % 128 or N % 128:
        raise ValueError(f"int4_matmul needs K/2,N % 128 == 0, got {K2}x{N}")
    bk2 = _tile(K2, BK)
    if G > 1:
        # k tiles must not straddle group boundaries
        bk2 = min(bk2, _tile(gs, BK))
    bn = _tile(N, BN)
    nk2 = K2 // bk2

    out = pl.pallas_call(
        functools.partial(_kernel4, nk2=nk2, bk2=bk2, gs=gs, K2=K2),
        grid=(N // bn, nk2),
        in_specs=[
            pl.BlockSpec((Mp, bk2), lambda n, k: (0, k)),
            pl.BlockSpec((Mp, bk2), lambda n, k: (0, k + nk2)),
            pl.BlockSpec((bk2, bn), lambda n, k: (k, n)),
            pl.BlockSpec((G, bn), lambda n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((Mp, bn), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((Mp, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, x, q4, scale)
    return out[:M]


def reference_int4_matmul(x, q4, scale, out_dtype=None):
    """Oracle: dense unpack+dequant then matmul."""
    out_dtype = out_dtype or x.dtype
    w = unpack_int4(q4, scale, jnp.float32)
    return (x.astype(jnp.float32) @ w).astype(out_dtype)

"""Pallas flash attention (forward + backward) for TPU.

TPU-native replacement for the reference's attention kernels: the fused
training softmax/attention path in ``csrc/transformer/softmax_kernels.cu`` +
``ds_transformer_cuda.cpp`` and the inference ``softmax_context`` op
(csrc/transformer/inference/csrc/softmax.cu). Online-softmax tiling (Flash
Attention 2 schedule): the KV loop is the innermost sequential grid dimension,
with running max/denominator kept in VMEM scratch.

What one tile step executes follows from what the call can see (see "which
part of a tile runs" below). Causal tiles above the diagonal are skipped; a
tile wholly below it builds no mask at all; a square tile on the diagonal
masks with a compile-time constant, and the backward kernels walk it in
strips so that only the part under the diagonal is multiplied. The
key-length mask exists only where keys were padded, the key-padding mask only
where the caller gave one.

Layouts: q (B, N, S, D); k, v (B, N, T, D) — callers with GQA expand KV heads
before the call (wrapper does it). Operands enter the MXU in the dtype they
are stored in (bf16 stays bf16; probabilities and score gradients are rounded
to it for their products); every product is summed in fp32, and scores,
running max and sum, ``lse``, ``delta`` and the accumulators are fp32. A
product whose result is only D wide fills D of the MXU's 128 columns, so
the backward kernels compute dq, dk and dv TRANSPOSED, (D, rows): the large
matrix (p, ds) becomes the MXU's weights as it lies and the D-wide one is
streamed, half the passes at D = 64 and no transpose of p or ds; each
accumulator is transposed once, when its block is written.

The backward pass is two Pallas kernels (dq, and dkv) following the standard
FA2 recomputation scheme with the forward's logsumexp as residual.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128
STRIP = 256   # a diagonal tile is walked in strips of this many rows/columns
# checkpoint_name tags of the forward kernel's two results, (o, lse): a
# jax.checkpoint policy that saves these names keeps them for the backward
# kernels, which otherwise get them back by running the forward kernel again
SAVED_RESIDUALS = ("flash_attention_o", "flash_attention_lse")


def _block_sizes(s: int, t: int) -> Tuple[int, int]:
    """Pick (bq, bk) power-of-two blocks, a function of (S, T) alone: the
    largest tile up to 1024 a side whose fp32 score tile stays within 4MB of
    VMEM. Blocks are always >=128 (inputs are padded up), keeping the TPU
    sublane rule (multiples of 8) satisfied for any raw sequence length."""

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
# which part of a tile runs, and what it masks
# ---------------------------------------------------------------------------
#
# A (bq, bk) tile of the causal grid is skipped (wholly above the diagonal),
# visible (wholly at or below it) or crossed by it. A crossed tile whose
# corners the diagonal joins (bq == bk) is a DIAGONAL tile: its mask is a
# compile-time constant (the tile's offsets cancel), so the compiler keeps
# a select only in the vregs the diagonal crosses and drops the vector work
# of those above it. The backward kernels, which the MXU bounds, also WALK
# a diagonal tile in strips of STRIP rows, each multiplying only the
# columns at or left of its rows; the forward, which vector work bounds,
# runs it whole (on the chip the walk cost it more in small products than
# it saved: PERF.md section 6, PR 42). Any other crossed tile runs whole
# under the causal mask. A visible tile builds no causal mask. The
# key-length mask (col < kv_len) exists only where the keys were padded
# (``ragged``) and then only in the last column of tiles and in crossed
# ones; the key-padding mask, where the caller gave one, is on every tile.


def _tile_kind(i, j, bq: int, bk: int):
    """(runs, visible) of causal tile (i, j); ints or traced scalars."""
    return j * bk <= i * bq + (bq - 1), (j + 1) * bk - 1 <= i * bq


def _strips(kind: str, bq: int, bk: int, walk: bool):
    """The rectangles (rows, cols, causal) of one tile that run: the whole
    tile, or with ``walk`` the strips of a diagonal one."""
    if not walk or kind != "diagonal" or bq <= STRIP:
        return [(slice(0, bq), slice(0, bk), kind != "visible")]
    return [(slice(a, a + STRIP), slice(0, a + STRIP), True)
            for a in range(0, bq, STRIP)]


def _tile_plan(s: int, t: int, causal: bool = True, walk: bool = True):
    """What a kernel executes for (S, T), as a function of the shapes alone:
    the rectangles (r0, r1, c0, c1, masked) of the padded score matrix that
    are multiplied (``walk``: the backward kernels; without it the forward).
    Tests hold it to the causal triangle."""
    bq, bk = _block_sizes(s, t)
    sp, tp = -(-s // bq) * bq, -(-t // bk) * bk
    ragged = t < tp
    rects = []
    for i in range(sp // bq):
        for j in range(tp // bk):
            runs, visible = _tile_kind(i, j, bq, bk) if causal else (True, True)
            if not runs:
                continue
            kind = ("visible" if visible else
                    "diagonal" if bq == bk else "crossed")
            edge = ragged and (j == tp // bk - 1 or not visible)
            for rows, cols, diag in _strips(kind, bq, bk, walk):
                rects.append((i * bq + rows.start, i * bq + rows.stop,
                              j * bk + cols.start, j * bk + cols.stop,
                              diag or edge))
    return rects


def _by_kind(i, j, last_col, *, causal: bool, bq: int, bk: int, ragged: bool,
             body):
    """Run ``body(kind, len_mask)`` for grid tile (i, j) under the ``pl.when``
    that its kind asks for; ``last_col``: j is the last column of tiles."""

    def when(pred, kind, len_mask):
        if pred is None:
            body(kind, len_mask)
        else:
            pl.when(pred)(lambda: body(kind, len_mask))

    def both(a, b):
        return b if a is None else a & b

    def visible(pred):
        if ragged:
            when(both(pred, last_col), "visible", True)
            when(both(pred, jnp.logical_not(last_col)), "visible", False)
        else:
            when(pred, "visible", False)

    if not causal:
        return visible(None)
    runs, vis = _tile_kind(i, j, bq, bk)
    visible(vis)
    when(runs & jnp.logical_not(vis),
         "diagonal" if bq == bk else "crossed", ragged)


def _fold_scale(dtype, scale: float) -> bool:
    """Whether ``scale`` goes onto q before the product (exact: float32
    operands, or a power of two) or onto the float32 scores after it."""
    return dtype == jnp.float32 or math.frexp(scale)[0] == 0.5


def _scaled(q, scale: float):
    """(q, what is left to put on the scores): ``scale`` goes onto q, in q's
    own dtype, where that is exact."""
    if not _fold_scale(q.dtype, scale):
        return q, scale
    return (q.astype(jnp.float32) * scale).astype(q.dtype), 1.0


def _part_scores(q, scale_s: float, rows, cols, diag: bool, *, kind: str,
                 len_mask: bool, i, j, k_ref, kvm_ref, slope, bq: int,
                 bk: int, kv_len: int):
    """Float32 scores of the (rows, cols) part of tile (i, j), rows ``q``
    against the keys ``k_ref[cols]``, biased and masked as this part needs
    and no further: ``diag`` the causal corner, ``len_mask`` the padded
    keys, ``kvm_ref`` the key-padding block or None, ``slope`` ALiBi's."""
    s = jax.lax.dot_general(q, k_ref[0, 0, cols, :], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if scale_s != 1.0:
        s = s * scale_s
    keep = None
    if slope is not None or len_mask:
        col = (jax.lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1)
               + (cols.start + j * bk))
        if slope is not None:
            # key-position-linear bias (query term is softmax-shift-invariant)
            s = s + slope * col.astype(jnp.float32)
        if len_mask:
            keep = col < kv_len
    if kvm_ref is not None:
        kvm = kvm_ref[0, :, cols] != 0                        # (1, c)
        keep = kvm if keep is None else keep & kvm
    if diag:
        # on a diagonal tile i * bq == j * bk: the tile's offsets cancel
        row0, col0 = ((rows.start, cols.start) if kind == "diagonal" else
                      (rows.start + i * bq, cols.start + j * bk))
        tri = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + row0
               >= jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + col0)
        keep = tri if keep is None else keep & tri
    return s if keep is None else jnp.where(keep, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, kvm_ref, slopes_ref, o_ref, lse_ref,
                acc, m_scr, *l_scr, scale: float, causal: bool,
                bq: int, bk: int, kv_len: int, ragged: bool, has_mask: bool,
                has_alibi: bool):
    i = pl.program_id(2)   # q block
    j = pl.program_id(3)   # kv block
    nj = pl.num_programs(3)
    d = o_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        for l in l_scr:
            l[:] = jnp.zeros_like(l)

    def body(kind, len_mask):
        # the whole tile in one piece (see "which part of a tile runs")
        q, scale_s = _scaled(q_ref[0, 0], scale)              # (bq, D)
        s = _part_scores(
            q, scale_s, slice(0, bq), slice(0, bk), kind != "visible",
            kind=kind, len_mask=len_mask, i=i, j=j, k_ref=k_ref,
            kvm_ref=kvm_ref if has_mask else None,
            slope=slopes_ref[0, 0, 0] if has_alibi else None,
            bq=bq, bk=bk, kv_len=kv_len)                      # (bq, bk)
        m_prev = m_scr[:, :1]                                 # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                                # (bq, bk)
        correction = jnp.exp(m_prev - m_new)                  # (bq, 1)
        v = v_ref[0, 0]
        for l in l_scr:
            l[:] = jnp.broadcast_to(
                correction * l[:, :1] + jnp.sum(p, axis=1, keepdims=True),
                l.shape)
        if not l_scr:
            # The head leaves half of the accumulator's lanes empty (``_fwd``
            # then gives no ``l_scr``): a block of ones beside v has the MXU
            # sum p's rows into them, so the running sum rides in acc[:, d:]
            # and is rescaled with it.
            v = jnp.concatenate([v, jnp.ones_like(v)], axis=1)
        acc[:] = acc[:] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    _by_kind(i, j, j == nj - 1, causal=causal, bq=bq, bk=bk, ragged=ragged,
             body=body)

    @pl.when(j == nj - 1)
    def _finalize():
        l = l_scr[0][:, :1] if l_scr else acc[:, d:d + 1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[:, :d] / safe_l).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(safe_l)
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref[0, 0].shape)


# ``_fwd`` and ``_bwd`` are jitted so that a kernel's body, which the masks'
# and the walk's several bodies made slower to trace, is traced once for its
# shapes: without it a program that is traced twice (the engine's train step
# is, and the forward once more for its residuals) pays for each again.
_STATIC = ("causal", "scale", "kv_len", "has_mask", "has_alibi", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd(q: jax.Array, k: jax.Array, v: jax.Array, kvm: jax.Array,
         slopes: jax.Array, *,
         causal: bool, scale: float, kv_len: int, has_mask: bool,
         has_alibi: bool, interpret: bool = False):
    B, N, S, D = q.shape
    T = k.shape[2]
    bq, bk = _block_sizes(S, T)
    grid = (B, N, S // bq, T // bk)

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, kv_len=kv_len, ragged=kv_len < T,
                               has_mask=has_mask, has_alibi=has_alibi)
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
        # acc, running max, and the running sum unless it rides in acc
        scratch_shapes=(
            [pltpu.VMEM((bq, LANES), jnp.float32)] * 2 if 2 * D == LANES else
            [pltpu.VMEM((bq, D), jnp.float32)]
            + [pltpu.VMEM((bq, LANES), jnp.float32)] * 2),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v, kvm, slopes)
    return o, lse[..., 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _p_and_ds(s, do, rows, cols, v_ref, lse_ref, delta_ref):
    """Recomputed probabilities and score gradients of one part of a tile
    from its scores, float32: p = exp(s - lse), ds = p * (do v^T - delta)."""
    p = jnp.exp(s - lse_ref[0, 0, rows, :1])                  # (r, c)
    dp = jax.lax.dot_general(do, v_ref[0, 0, cols, :],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, p * (dp - delta_ref[0, 0, rows, :1])


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kvm_ref,
                   slopes_ref, dq_ref, acc, *, scale: float, causal: bool,
                   bq: int, bk: int, kv_len: int, ragged: bool,
                   has_mask: bool, has_alibi: bool):
    i = pl.program_id(2)
    j = pl.program_id(3)
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    def body(kind, len_mask):
        scores = functools.partial(
            _part_scores, kind=kind, len_mask=len_mask, i=i, j=j, k_ref=k_ref,
            kvm_ref=kvm_ref if has_mask else None,
            slope=slopes_ref[0, 0, 0] if has_alibi else None,
            bq=bq, bk=bk, kv_len=kv_len)
        for rows, cols, diag in _strips(kind, bq, bk, walk=True):
            q, scale_s = _scaled(q_ref[0, 0, rows, :], scale)
            _, ds = _p_and_ds(scores(q, scale_s, rows, cols, diag),
                              do_ref[0, 0, rows, :], rows, cols, v_ref,
                              lse_ref, delta_ref)
            k = k_ref[0, 0, cols, :]
            # dq^T = k^T ds^T, (D, r): see the module docstring
            acc[:, rows] += jax.lax.dot_general(
                k, ds.astype(k.dtype), (((0,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

    _by_kind(i, j, j == nj - 1, causal=causal, bq=bq, bk=bk, ragged=ragged,
             body=body)

    @pl.when(j == nj - 1)
    def _finalize():
        dq_ref[0, 0] = (acc[:].T * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, kvm_ref,
                    slopes_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                    scale: float, causal: bool, bq: int, bk: int, kv_len: int,
                    ragged: bool, has_mask: bool, has_alibi: bool):
    j = pl.program_id(2)   # kv block (outer)
    i = pl.program_id(3)   # q block (inner, sequential)
    nj = pl.num_programs(2)
    ni = pl.num_programs(3)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def body(kind, len_mask):
        scores = functools.partial(
            _part_scores, kind=kind, len_mask=len_mask, i=i, j=j, k_ref=k_ref,
            kvm_ref=kvm_ref if has_mask else None,
            slope=slopes_ref[0, 0, 0] if has_alibi else None,
            bq=bq, bk=bk, kv_len=kv_len)
        for rows, cols, diag in _strips(kind, bq, bk, walk=True):
            q, scale_s = _scaled(q_ref[0, 0, rows, :], scale)  # (r, D)
            do = do_ref[0, 0, rows, :]
            p, ds = _p_and_ds(scores(q, scale_s, rows, cols, diag), do,
                              rows, cols, v_ref, lse_ref, delta_ref)
            # dv^T = do^T p and dk^T = q^T ds, (D, c): p and ds as they lie
            dv_acc[:, cols] += jax.lax.dot_general(
                do, p.astype(do.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[:, cols] += jax.lax.dot_general(
                q, ds.astype(q.dtype), (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    _by_kind(i, j, j == nj - 1, causal=causal, bq=bq, bk=bk, ragged=ragged,
             body=body)

    @pl.when(i == ni - 1)
    def _finalize():
        dk = dk_acc[:].T
        if not _fold_scale(q_ref.dtype, scale):
            dk = dk * scale   # q went in unscaled: its scale goes on here
        dk_ref[0, 0] = dk.astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].T.astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=_STATIC)
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
    static = dict(scale=scale, causal=causal, bq=bq, bk=bk, kv_len=kv_len,
                  ragged=kv_len < T, has_mask=has_mask, has_alibi=has_alibi)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **static),
        grid=(B, N, S // bq, T // bk),
        in_specs=common_specs,
        out_specs=[pl.BlockSpec((1, 1, bq, D), lambda b, n, x, y: (b, n, x, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, N, S, D), q.dtype)],
        scratch_shapes=[pltpu.VMEM((D, bq), jnp.float32)],
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
        functools.partial(_bwd_dkv_kernel, **static),
        grid=(B, N, T // bk, S // bq),
        in_specs=swapped_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), lambda b, n, y, x: (b, n, y, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, n, y, x: (b, n, y, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, N, T, D), k.dtype),
                   jax.ShapeDtypeStruct((B, N, T, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((D, bk), jnp.float32),
                        pltpu.VMEM((D, bk), jnp.float32)],
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
    # lse compact, (B, N, S): the backward lane-broadcasts it again
    o, lse = map(checkpoint_name, (o, lse), SAVED_RESIDUALS)
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

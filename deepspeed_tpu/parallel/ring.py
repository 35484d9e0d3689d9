"""Ring attention — sequence parallelism by rotating KV chunks over ICI.

The second long-context scheme next to Ulysses (parallel/sequence.py).
Reference lineage: v0.9.2 has neither (SURVEY §5 — its long-context story is
block-sparse attention); later DeepSpeed added Ulysses, and ring attention
(Liu et al.) is the standard TPU-native alternative the task brief calls
first-class. Design:

  * tokens stay sharded over the 'seq' axis end-to-end (activations,
    q/k/v) — nothing ever materialises the full sequence;
  * each of the sp steps computes blockwise attention of the LOCAL queries
    against the currently-held KV chunk, merged with an online-softmax
    running (max, denom, acc) state — flash attention's math at chunk
    granularity;
  * the KV pair then rotates one hop around the ring (`ppermute` on ICI),
    overlapping the next chunk's transfer with compute;
  * causality is decided per (query-chunk, key-chunk) pair from absolute
    chunk ids: later chunks are masked entirely, the diagonal chunk gets the
    triangular mask, earlier chunks are dense;
  * backward = jax.grad through the unrolled loop — XLA reverses each
    ppermute, which is exactly the reverse KV rotation of the published
    ring-attention backward.

Runs inside a partial-manual ``shard_map`` over the 'seq' axis (data/model
stay automatic, so ZeRO/TP compose).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from .mesh import SEQ_AXIS, get_mesh

_RING_ENABLED = False


def set_ring_attention(enabled: bool) -> None:
    """Engine hook: ParallelConfig.sequence_parallel_impl == 'ring'."""
    global _RING_ENABLED
    _RING_ENABLED = enabled


def ring_attention_enabled() -> bool:
    if not _RING_ENABLED:
        return False
    from .sequence import _in_manual_pipe

    if _in_manual_pipe():
        # a nested explicit-mesh shard_map under the pipeline's manual trace
        # is rejected by JAX; the engine refuses ring+PP up front, this
        # guard covers direct forward() calls
        return False
    try:
        return int(get_mesh().shape.get(SEQ_AXIS, 1)) > 1
    except Exception:
        return False


def _ring_body(q, k, v, *, sp: int, scale: float, causal: bool):
    """Per-shard body (manual over 'seq'). q/k/v (B, S_loc, N, D) local
    chunks; returns (B, S_loc, N, D)."""
    my = lax.axis_index(SEQ_AXIS)
    B, S, N, D = q.shape
    q32 = q.astype(jnp.float32) * scale

    m = jnp.full((B, N, S, 1), -1e30, jnp.float32)
    l = jnp.zeros((B, N, S, 1), jnp.float32)
    acc = jnp.zeros((B, N, S, D), jnp.float32)
    k_c, v_c = k, v
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    local = jnp.arange(S)
    for step in range(sp):
        # double-buffer: issue the NEXT chunk's rotation before this step's
        # compute so XLA overlaps the ICI transfer with the einsums
        if step + 1 < sp:
            k_next = lax.ppermute(k_c, SEQ_AXIS, perm)
            v_next = lax.ppermute(v_c, SEQ_AXIS, perm)
        # after `step` rotations this shard holds chunk (my - step) mod sp
        src = (my - step) % sp
        s_ij = jnp.einsum("bsnd,btnd->bnst", q32,
                          k_c.astype(jnp.float32))         # (B,N,S,S)
        if causal:
            q_pos = my * S + local                          # (S,)
            k_pos = src * S + local
            keep = k_pos[None, :] <= q_pos[:, None]         # (S,S)
            s_ij = jnp.where(keep[None, None], s_ij, -1e30)
        m_new = jnp.maximum(m, jnp.max(s_ij, axis=-1, keepdims=True))
        p = jnp.exp(s_ij - m_new)
        corr = jnp.exp(m - m_new)
        l = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum("bnst,btnd->bnsd", p,
                                      v_c.astype(jnp.float32))
        m = m_new
        if step + 1 < sp:
            k_c, v_c = k_next, v_next

    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / safe_l).swapaxes(1, 2)                     # (B,S,N,D)
    return out.astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   mask=None, causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Sequence-parallel attention over the 'seq' mesh axis. q (B,S,N,D) with
    the token dim seq-sharded (global view — this function wraps the
    shard_map). GQA KV heads are expanded by the caller side (same contract
    as flash_attention). Padding masks are not supported in ring mode (long-
    context pretraining packs sequences instead)."""
    if mask is not None:
        raise NotImplementedError(
            "ring attention does not take padding masks — pack sequences "
            "(the standard long-context pretraining setup) or use Ulysses "
            "(sequence_parallel_impl='ulysses')")
    mesh = get_mesh()
    sp = int(mesh.shape[SEQ_AXIS])
    B, S, N, D = q.shape
    K = k.shape[2]
    if K != N:
        k = jnp.repeat(k, N // K, axis=2)
        v = jnp.repeat(v, N // K, axis=2)
    if S % sp != 0:
        raise ValueError(f"sequence {S} not divisible by seq axis {sp}")
    scale = scale if scale is not None else D ** -0.5

    import functools

    body = functools.partial(_ring_body, sp=sp, scale=scale, causal=causal)
    # partial-manual: only the 'seq' axis is named; batch keeps whatever
    # (expert, data) sharding the surrounding jit gives it automatically
    spec = P(None, SEQ_AXIS, None, None)
    fn = shard_map(body, mesh=mesh,
                       in_specs=(spec, spec, spec),
                       out_specs=spec,
                       check_vma=False,
                       axis_names={SEQ_AXIS})
    return fn(q, k, v)

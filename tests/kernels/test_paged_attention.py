"""Paged-attention kernel parity (interpret mode on CPU).

The serving acceptance story rests on three read paths producing the same
attention: the dense ``arena[block_table]`` gather view (PR-6 baseline,
``paged_impl='gather'``), the GQA-native jnp paged reference (CPU serving
fallback), and the Pallas paged kernels (TPU; interpret-mode here). Every
test pins two of them against each other across ragged occupancy, GQA and
alibi — the greedy bit-exactness smoke in tests/unit/test_serving.py then
covers the end-to-end program.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import (alibi_slopes,
                                              dot_product_attention)
from deepspeed_tpu.ops import (decode_attention, paged_decode_attention,
                               paged_prefill_attention,
                               reference_decode_attention,
                               reference_paged_attention)

INTERPRET = True


def _pool(nb=9, bs=16, k=2, d=32, seed=0, dtype=jnp.float32):
    """k/v pools in the arena layout: (NUM_BLOCKS, BLOCK, K*D)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return (jax.random.normal(ks[0], (nb, bs, k * d), dtype),
            jax.random.normal(ks[1], (nb, bs, k * d), dtype))


def _ragged_tables(bs=16, maxb=4):
    """Three rows at different occupancy; physical pages deliberately
    non-contiguous and out of order."""
    bt = np.zeros((3, maxb), np.int32)
    bt[0, :3] = [5, 1, 7]
    bt[1, :1] = [3]
    bt[2, :4] = [8, 2, 4, 6]
    lengths = np.array([bs * 2 + 5, 9, bs * 4], np.int32)
    return jnp.asarray(bt), jnp.asarray(lengths)


def _dense_view(pool, bt, d=32):
    nb, bs, kd = pool.shape
    b, maxb = bt.shape
    return pool[bt].reshape(b, maxb * bs, kd // d, d)


class TestPagedDecodeKernel:
    @pytest.mark.parametrize("n,k", [(4, 4), (4, 2), (8, 2)])
    def test_matches_reference_ragged_gqa(self, n, k):
        kp, vp = _pool(k=k)
        bt, lengths = _ragged_tables()
        q = jax.random.normal(jax.random.PRNGKey(3), (3, n, 32))
        out = paged_decode_attention(q, kp, vp, bt, lengths,
                                     interpret=INTERPRET)
        ref = reference_paged_attention(q[:, None], kp, vp, bt,
                                        lengths[:, None] - 1)[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_alibi_uses_true_positions(self):
        kp, vp = _pool(k=2)
        bt, lengths = _ragged_tables()
        n = 4
        q = jax.random.normal(jax.random.PRNGKey(4), (3, n, 32))
        al = alibi_slopes(n)
        out = paged_decode_attention(q, kp, vp, bt, lengths, alibi=al,
                                     interpret=INTERPRET)
        ref = reference_paged_attention(q[:, None], kp, vp, bt,
                                        lengths[:, None] - 1, alibi=al)[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_inactive_row_outputs_zero(self):
        kp, vp = _pool()
        bt, lengths = _ragged_tables()
        lengths = lengths.at[1].set(0)          # inactive decode row
        q = jax.random.normal(jax.random.PRNGKey(5), (3, 4, 32))
        out = paged_decode_attention(q, kp, vp, bt, lengths,
                                     interpret=INTERPRET)
        assert bool(jnp.all(out[1] == 0))

    def test_reference_matches_dense_gather_path(self):
        """The jnp paged reference (CPU serving fallback) computes the
        same attention as the PR-6 gather + dot_product_attention path —
        what 'paged_kernel=off' A/Bs against."""
        kp, vp = _pool(k=2)
        bt, lengths = _ragged_tables()
        n = 4
        q1 = jax.random.normal(jax.random.PRNGKey(6), (3, 1, n, 32))
        pos = lengths[:, None] - 1
        ref = reference_paged_attention(q1, kp, vp, bt, pos)
        kk, vv = _dense_view(kp, bt), _dense_view(vp, bt)
        col = jnp.arange(kk.shape[1], dtype=jnp.int32)
        full = (col[None, None, :] <= pos[:, :, None]).astype(jnp.int32)
        want = dot_product_attention(q1, kk, vv, full, causal=False)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


class TestPagedPrefillKernel:
    # heads per grid step: 4 (4x32 lanes), the whole page (2x32), 2 (64-wide
    # heads pair up), 1 (128-wide heads)
    @pytest.mark.parametrize("n,k,d", [(4, 4, 32), (8, 2, 32), (4, 4, 64),
                                       (4, 2, 128)])
    def test_chunk_matches_reference(self, n, k, d):
        kp, vp = _pool(k=k, d=d)
        bt = jnp.asarray(np.array([[5, 1, 7, 0], [3, 8, 0, 0]], np.int32))
        start = jnp.asarray(np.array([21, 0], np.int32))
        C = 16
        q = jax.random.normal(jax.random.PRNGKey(7), (2, C, n, d))
        pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        out = paged_prefill_attention(q, kp, vp, bt, start,
                                      interpret=INTERPRET)
        ref = reference_paged_attention(q, kp, vp, bt, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_chunk_alibi(self):
        kp, vp = _pool(k=2)
        bt = jnp.asarray(np.array([[5, 1, 7, 0]], np.int32))
        start = jnp.asarray(np.array([17], np.int32))
        n, C = 4, 16
        q = jax.random.normal(jax.random.PRNGKey(8), (1, C, n, 32))
        al = alibi_slopes(n)
        pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        out = paged_prefill_attention(q, kp, vp, bt, start, alibi=al,
                                      interpret=INTERPRET)
        ref = reference_paged_attention(q, kp, vp, bt, pos, alibi=al)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_chunk_matches_dense_gather_path(self):
        kp, vp = _pool(k=2)
        bt = jnp.asarray(np.array([[5, 1, 7, 0]], np.int32))
        start = jnp.asarray(np.array([21], np.int32))
        n, C = 4, 16
        q = jax.random.normal(jax.random.PRNGKey(9), (1, C, n, 32))
        pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        out = paged_prefill_attention(q, kp, vp, bt, start,
                                      interpret=INTERPRET)
        kk, vv = _dense_view(kp, bt), _dense_view(vp, bt)
        col = jnp.arange(kk.shape[1], dtype=jnp.int32)
        full = (col[None, None, :] <= pos[:, :, None]).astype(jnp.int32)
        want = dot_product_attention(q, kk, vv, full, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


class TestDecodeAttentionUnalignedCache:
    """The T % 128 gate is gone: the final KV tile is edge-padded by the
    pipeline and masked by true column in-kernel, so bucketed non-multiple
    cache lengths stay on the kernel instead of silently falling back to
    jnp attention."""

    @pytest.mark.parametrize("t", [100, 160, 257, 64])
    def test_non_multiple_cache_length(self, t):
        ks = jax.random.split(jax.random.PRNGKey(10), 3)
        q = jax.random.normal(ks[0], (2, 4, 32))
        kc = jax.random.normal(ks[1], (2, t, 2, 32))
        vc = jax.random.normal(ks[2], (2, t, 2, 32))
        valid = jnp.asarray(
            (np.arange(t)[None, :] < np.array([t - 3, t // 2])[:, None]
             ).astype(np.int32))
        out = decode_attention(q, kc, vc, valid, interpret=INTERPRET)
        ref = reference_decode_attention(q, kc, vc, valid)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_non_multiple_with_alibi_key_positions(self):
        t = 100
        ks = jax.random.split(jax.random.PRNGKey(11), 4)
        q = jax.random.normal(ks[0], (2, 4, 32))
        kc = jax.random.normal(ks[1], (2, t, 2, 32))
        vc = jax.random.normal(ks[2], (2, t, 2, 32))
        valid = jnp.asarray(
            (np.arange(t)[None, :] < np.array([t - 7, 41])[:, None]
             ).astype(np.int32))
        al = alibi_slopes(4)
        kpos = jnp.asarray(np.tile(np.arange(t, dtype=np.float32), (2, 1)))
        out = decode_attention(q, kc, vc, valid, alibi=al,
                               key_positions=kpos, interpret=INTERPRET)
        ref = reference_decode_attention(q, kc, vc, valid, alibi=al,
                                         key_positions=kpos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

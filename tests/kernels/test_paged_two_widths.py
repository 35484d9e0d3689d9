"""The paged kernels where a head's keys and values differ in width and a
softmax has a learned sink a head (interpret mode on CPU): keys 192 and
values 128 wide, groups of 16 and of 8 query heads a key-value head, with
and without a sink, with and without a window, against
``reference_paged_attention``; the reference itself against a dense softmax
written out by hand; and a chunk that the prefill kernel cannot hold, which
goes down as rows."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import (paged_decode_attention,
                               paged_prefill_attention,
                               reference_paged_attention)

paged_module = importlib.import_module(
    "deepspeed_tpu.ops.paged_decode_attention")

L, NB, BS, MAXB, ROWS = 2, 40, 16, 12, 3
LAYER = 1
# (query heads, key-value heads): groups of 16 and of 8
GROUPS = {"16-a-group": (32, 2), "8-a-group": (32, 4)}
TOL = 2e-5


def _case(heads, kv_heads, d=192, dv=128, seed=0, sink=True):
    rng = np.random.default_rng(seed)
    ka = jnp.asarray(rng.normal(size=(L, NB, BS, kv_heads * d)), jnp.float32)
    va = jnp.asarray(rng.normal(size=(L, NB, BS, kv_heads * dv)),
                     jnp.float32)
    bt = jnp.asarray(rng.permutation(np.arange(1, NB))[:ROWS * MAXB]
                     .reshape(ROWS, MAXB), jnp.int32)
    sinks = (jnp.asarray(rng.normal(size=(heads,)), jnp.float32)
             if sink else None)
    return rng, ka, va, bt, ({} if sinks is None else {"sink": sinks})


@pytest.mark.parametrize("window", [None, 24], ids=["full", "window"])
@pytest.mark.parametrize("sink", [False, True], ids=["no-sink", "sink"])
@pytest.mark.parametrize("group", sorted(GROUPS))
def test_decode_walk_of_two_widths(group, sink, window):
    heads, kv_heads = GROUPS[group]
    rng, ka, va, bt, kw = _case(heads, kv_heads, sink=sink)
    lengths = jnp.asarray([37, 0, 150], jnp.int32)
    q = jnp.asarray(rng.normal(size=(ROWS, heads, 192)), jnp.float32)
    want = reference_paged_attention(q[:, None], ka, va, LAYER, bt,
                                     (lengths - 1)[:, None], window=window,
                                     **kw)[:, 0]
    lo = {} if window is None else {"lo": jnp.maximum(lengths - window, 0)}
    got = paged_decode_attention(q, ka, va, LAYER, bt, lengths,
                                 interpret=True, **lo, **kw)
    assert got.shape == (ROWS, heads, 128)
    assert float(jnp.abs(got - want).max()) < TOL
    assert not np.asarray(got[1]).any()         # the empty row: zeros


@pytest.mark.parametrize("window", [None, 24], ids=["full", "window"])
@pytest.mark.parametrize("sink", [False, True], ids=["no-sink", "sink"])
@pytest.mark.parametrize("group", sorted(GROUPS))
def test_prefill_kernel_of_two_widths(group, sink, window):
    heads, kv_heads = GROUPS[group]
    rng, ka, va, bt, kw = _case(heads, kv_heads, seed=1, sink=sink)
    C = 32
    start = jnp.asarray([16, 0, 96], jnp.int32)
    n_valid = jnp.asarray([C, 0, C - 5], jnp.int32)
    lengths = jnp.where(n_valid > 0, start + n_valid, 0)
    q = jnp.asarray(rng.normal(size=(ROWS, C, heads, 192)), jnp.float32)
    at = jnp.arange(C)[None]
    pos = jnp.where(at < n_valid[:, None], start[:, None] + at, -1)
    want = reference_paged_attention(q, ka, va, LAYER, bt, pos,
                                     window=window, **kw)
    got = paged_prefill_attention(q, ka, va, LAYER, bt, start, lengths,
                                  interpret=True, window=window, **kw)
    assert got.shape == (ROWS, C, heads, 128)
    real = (pos >= 0)[:, :, None, None]
    assert float(jnp.abs(jnp.where(real, got - want, 0)).max()) < TOL


def test_the_reference_is_the_softmax_with_one_more_score():
    """By hand, a head at a time: `p_j = exp(s_j - m) / (exp(sink - m) +
    sum exp(s_j' - m))` over the window's keys, values 128 of keys 192."""
    heads, kv_heads = 8, 2
    rng, ka, va, bt, kw = _case(heads, kv_heads, seed=2)
    length, window = 70, 24
    q = jnp.asarray(rng.normal(size=(1, 1, heads, 192)), jnp.float32)
    got = np.asarray(reference_paged_attention(
        q, ka, va, LAYER, bt[:1], jnp.asarray([[length - 1]]),
        window=window, **kw))[0, 0]
    keys = np.asarray(ka[LAYER, bt[0]]).reshape(-1, kv_heads, 192)[:length]
    values = np.asarray(va[LAYER, bt[0]]).reshape(-1, kv_heads, 128)[:length]
    for n in range(heads):
        k, v = keys[-window:, n // 4], values[-window:, n // 4]
        s = k @ np.asarray(q[0, 0, n]) / np.sqrt(192.0)
        sink = float(kw["sink"][n])
        m = max(s.max(), sink)
        p = np.exp(s - m) / (np.exp(sink - m) + np.exp(s - m).sum())
        assert np.abs(p @ v - got[n]).max() < TOL
        assert p.sum() < 1.0        # the sink took its share


def test_a_chunk_the_kernel_cannot_hold_goes_down_as_rows(monkeypatch):
    """Under a budget that 64 queries of these heads pass, the chunk is
    rows of 16 over the same table, each with its own start and length; the
    result is the whole chunk's, and the counts of blocks are the rows'."""
    heads, kv_heads = GROUPS["8-a-group"]
    rng, ka, va, bt, kw = _case(heads, kv_heads, seed=3)
    sizes = (heads, kv_heads, 192, 128, BS, jnp.float32, jnp.float32)
    assert paged_module._chunk_parts(64, *sizes) == 1
    monkeypatch.setattr(paged_module, "_CHUNK_VMEM_BUDGET",
                        paged_module._chunk_vmem(32, *sizes))
    assert paged_module._chunk_parts(64, *sizes) == 2
    C = 64
    start = jnp.asarray([16, 0, 96], jnp.int32)
    n_valid = jnp.asarray([C, 0, 20], jnp.int32)
    lengths = jnp.where(n_valid > 0, start + n_valid, 0)
    starts, held = paged_module._part_rows(np.asarray(start),
                                           np.asarray(lengths), C, 2, np)
    assert starts.tolist() == [16, 48, 0, 32, 96, 128]
    assert held.tolist() == [48, 80, 0, 0, 116, 0]
    q = jnp.asarray(rng.normal(size=(ROWS, C, heads, 192)), jnp.float32)
    at = jnp.arange(C)[None]
    pos = jnp.where(at < n_valid[:, None], start[:, None] + at, -1)
    for window in (None, 24):
        want = reference_paged_attention(q, ka, va, LAYER, bt, pos,
                                         window=window, **kw)
        got = paged_prefill_attention(q, ka, va, LAYER, bt, start, lengths,
                                      interpret=True, window=window, **kw)
        real = (pos >= 0)[:, :, None, None]
        assert float(jnp.abs(jnp.where(real, got - want, 0)).max()) < TOL
    whole = paged_module.prefill_block_counts(
        np.asarray(start), np.asarray(lengths), C, heads, 192, ka,
        value_dim=128)
    rows = paged_module.prefill_block_counts(starts, held, C // 2, heads,
                                             192, ka, value_dim=128)
    assert whole == rows and whole["prefill_blocks"] > 0


def test_every_chunk_served_before_stays_one_row():
    """The budget parts the chunk of 64 heads of 192 alone: the widest call
    of the cells that were there (1,024 queries of 32 heads of 64 over 8
    key-value heads) stays one row, and so its program is what it was."""
    bf16 = jnp.bfloat16
    for chunk, heads, kv_heads, d in ((1024, 32, 8, 64), (256, 64, 8, 128),
                                      (256, 32, 2, 128), (256, 40, 10, 128),
                                      (256, 32, 32, 64), (128, 16, 16, 128)):
        assert paged_module._chunk_parts(chunk, heads, kv_heads, d, d, 16,
                                         bf16, bf16) == 1
    assert paged_module._chunk_parts(1024, 64, 4, 192, 128, 16, bf16,
                                     bf16) == 4
    assert paged_module._chunk_parts(1024, 64, 8, 192, 128, 16, bf16,
                                     bf16) == 8
    assert paged_module._heads_per_group(4, 192, 128) == 2
    assert paged_module._heads_per_group(8, 64) == 2

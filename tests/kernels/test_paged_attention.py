"""Paged-attention kernel parity (interpret mode on CPU).

The serving acceptance story rests on the read paths producing the same
attention: the GQA-native jnp paged reference (CPU serving fallback) and the
Pallas paged kernels (TPU; interpret-mode here), each also held to a dense
``arena[layer, block_table]`` view that the test builds by hand. Every test
pins two of them against each other across ragged occupancy, GQA and alibi
— the greedy bit-exactness smoke in tests/unit/test_serving.py then covers
the end-to-end program.

All take the whole arena ``(L, NUM_BLOCKS, BLOCK, K*D)`` and a layer
index. The kernels are held, at every layer of a 3-layer arena, to the
reference on that layer's pool alone (``arena[layer][None]``, layer 0), and
the reference to a dense view sliced by hand — so neither side's layer
addressing is checked against itself.
"""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.presets import transformer_config
from deepspeed_tpu.models.transformer import (alibi_slopes,
                                              dot_product_attention)
from deepspeed_tpu.ops import registry
from deepspeed_tpu.ops import (decode_attention, paged_decode_attention,
                               paged_prefill_attention,
                               reference_decode_attention,
                               reference_paged_attention)

# the module, which ``deepspeed_tpu.ops`` shadows with the function of its name
paged_module = importlib.import_module(
    "deepspeed_tpu.ops.paged_decode_attention")

INTERPRET = True
LAYERS = (0, 1, 2)      # first, middle, last of the 3-layer arena


def _arena(nb=9, bs=16, k=2, d=32, seed=0, dtype=jnp.float32):
    """k/v arenas (L, NUM_BLOCKS, BLOCK, K*D); every layer's pool differs."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    shape = (len(LAYERS), nb, bs, k * d)
    return (jax.random.normal(ks[0], shape, dtype),
            jax.random.normal(ks[1], shape, dtype))


def _pool_reference(q, ka, va, layer, *args, **kwargs):
    """The reference on ``layer``'s pool alone, as a 1-layer arena."""
    return reference_paged_attention(q, ka[layer][None], va[layer][None], 0,
                                     *args, **kwargs)


def _ragged_tables(bs=16, maxb=4):
    """Three rows at different occupancy; physical pages deliberately
    non-contiguous and out of order."""
    bt = np.zeros((3, maxb), np.int32)
    bt[0, :3] = [5, 1, 7]
    bt[1, :1] = [3]
    bt[2, :4] = [8, 2, 4, 6]
    lengths = np.array([bs * 2 + 5, 9, bs * 4], np.int32)
    return jnp.asarray(bt), jnp.asarray(lengths)


# the decode walk's edges, in keys: a table of 40 pages is two and a half
# tiles of the 16 pages that the arenas of these tests give a tile
BS, MAXB = 16, 40
TILE = 16 * BS
WALKS = {
    "empty": (0, 0, 0),
    "one-token": (1, 1, 1),
    "one-page": (BS, BS, BS),
    "one-tile": (TILE, TILE, TILE),
    "tile-plus-one": (TILE + 1, TILE + 1, TILE + 1),
    "whole-table": (MAXB * BS, MAXB * BS, MAXB * BS),
    # very different rows in one call, empty ones between the others: a
    # row's first tile is started by the row above, unless that one is empty
    "mixed": (0, 1, TILE + 1, 0, 0, MAXB * BS, BS, TILE, 2 * TILE + 5, 0),
}


def _walk_tables(lengths, nb, maxb=MAXB, seed=0):
    """A table for rows of these lengths: every row's pages drawn from one
    shuffle of the pool, so neither contiguous nor ascending; unfilled
    entries 0, the scratch block."""
    pages = np.random.default_rng(seed).permutation(np.arange(1, nb))
    bt, at = np.zeros((len(lengths), maxb), np.int32), 0
    for r, n in enumerate(lengths):
        held = -(-n // BS)
        bt[r, :held] = pages[at:at + held]
        at += held
    assert at <= nb - 1
    return jnp.asarray(bt), jnp.asarray(np.asarray(lengths, np.int32))


def _poison_what_no_row_reads(arena, bt, lengths):
    """NaN in every key slot past a row's length: the tail of its last page,
    and every page that no row holds (block 0 among them)."""
    live = np.zeros(arena.shape[1:3], bool)
    for row, n in zip(np.asarray(bt), np.asarray(lengths)):
        for j in range(-(-int(n) // BS)):
            live[row[j], :min(BS, int(n) - j * BS)] = True
    return jnp.where(jnp.asarray(live)[None, :, :, None], arena, jnp.nan)


# the chunk walk's edges, a row as (start, real tokens): the arenas of these
# tests give the prefill kernel a tile of 64 pages
CHUNK_TILE = 64 * BS
CHUNKS = {
    "start-0": [(0, 16)],
    "ends-on-a-tile-edge": [(CHUNK_TILE - 16, 16)],
    "ends-one-past-the-edge": [(CHUNK_TILE - 15, 16)],
    "straddles-two-tiles": [(CHUNK_TILE - 8, 16)],
    "several-tiles": [(2 * CHUNK_TILE + 40, 16)],
    # fewer real tokens than slots: the pad queries' keys were never written
    "short-chunk": [(37, 5)],
    "one-token": [(CHUNK_TILE, 1)],
    # very different rows in one call, empty ones among them: a row's first
    # tile is started by the row above, unless that one is empty
    "mixed": [(0, 16), (CHUNK_TILE - 8, 16), (0, 0), (0, 0),
              (2 * CHUNK_TILE + 40, 9), (17, 1), (0, 0)],
    # the speculative verify step: 5 slots a row (not a multiple of 8),
    # 1-5 of them real, one row that holds nothing
    "verify": [(100, 5), (0, 0), (CHUNK_TILE - 2, 3), (7, 1)],
    "small-table": [(21, 16), (0, 0), (0, 9)],
}
CHUNK_SLOTS = {"verify": 5}     # every other case: 16 slots a row


# the tile step's sub-blocks (PR 69): chunks large enough to have them. A
# tile of these arenas is 1,024 keys in two key sub-blocks of 512; a case is
# (slots a row, heads, KV heads, head size, rows as (start, real tokens),
# alibi)
TILE_STEPS = {
    # the row's keys end inside its second tile's FIRST key sub-block
    "ends-inside-a-first-sub-block": (256, 4, 4, 64, [(1024, 100)], False),
    "ends-on-a-tile-edge": (256, 4, 4, 64, [(768, 256)], False),
    "ends-one-past-a-sub-block": (256, 4, 4, 64, [(256, 256), (257, 256)],
                                  False),
    # fewer real tokens than slots: the pad queries' keys were never written
    "ragged-last-chunk": (256, 4, 4, 64, [(1536, 77)], False),
    # a row that holds nothing between two live rows: the row below starts
    # its own first tile, in the buffer the slot's parity names
    "empty-row-between-live-rows": (256, 4, 4, 64,
                                    [(256, 256), (0, 0), (1024, 130)], False),
    "heads-of-128": (256, 4, 4, 128, [(300, 256)], False),
    "grouped-queries-of-128": (256, 8, 2, 128, [(1024, 256), (0, 9)], False),
    "alibi-over-a-slab-of-two-heads": (256, 4, 2, 64,
                                       [(300, 256), (0, 256)], True),
    # 1,024 queries: 8 heads a group stack 8,192 rows, so two query blocks
    # of 512; the first sees nothing of its tile's second sub-block
    "chunk-of-1024-four-query-heads-a-kv-head": (
        1024, 8, 2, 64, [(1024, 1024), (0, 700)], False),
    "chunk-of-1024-a-head-a-kv-head": (1024, 4, 4, 64, [(512, 1024)], False),
    "chunk-of-1024-heads-of-128": (1024, 2, 2, 128, [(0, 1024), (1024, 5)],
                                   False),
}


def _tile_step_case(case, seed=0, dtype=jnp.float32):
    """(q, k arena, v arena, table, start, lengths, positions, real, alibi)
    of ``TILE_STEPS[case]``, by ``_chunk_case``'s rules."""
    C, n, k, d, rows, alibi = TILE_STEPS[case]
    nb = 1 + sum(-(-(s + v) // BS) for s, v in rows)
    ka, va = _arena(nb=nb, k=k, d=d, seed=seed + 20, dtype=dtype)
    out = _chunk_rows(rows, C, nb, n, d, maxb=max(
        -(-(s + v) // BS) for s, v in rows), seed=seed, dtype=dtype)
    return (out[0], ka, va) + out[1:] + (alibi_slopes(n) if alibi else None,)


def _chunk_rows(rows, C, nb, n, d, maxb=3 * 64 + 8, seed=0,
                dtype=jnp.float32):
    """(q, block_table, start, lengths, positions, real) of rows given as
    (start, real tokens), C slots each: positions -1 on the slots past a
    row's real tokens, as the serving programs send them; ``real`` marks the
    queries a caller reads."""
    bt, lengths = _walk_tables([s + v for s, v in rows], nb, maxb=maxb,
                               seed=seed)
    start = jnp.asarray(np.array([s for s, _ in rows], np.int32))
    real = np.arange(C)[None] < np.array([v for _, v in rows])[:, None]
    pos = jnp.where(jnp.asarray(real),
                    start[:, None] + jnp.arange(C, dtype=jnp.int32)[None], -1)
    q = jax.random.normal(jax.random.PRNGKey(16 + seed),
                          (len(rows), C, n, d), dtype)
    return q, bt, start, lengths, pos, real


def _chunk_case(case, nb, n, d, **kwargs):
    """``_chunk_rows`` of ``CHUNKS[case]``."""
    return _chunk_rows(CHUNKS[case], CHUNK_SLOTS.get(case, 16), nb, n, d,
                       **kwargs)


def _dense_view(arena, layer, bt, d=32):
    pool = arena[layer]
    nb, bs, kd = pool.shape
    b, maxb = bt.shape
    return pool[bt].reshape(b, maxb * bs, kd // d, d)


def _count_started_copies(monkeypatch):
    """A list that grows by one for every page copy a kernel starts."""
    started = []

    class Counted:
        def __init__(self, copy):
            self.copy = copy

        def start(self):
            jax.debug.callback(lambda: started.append(1))
            self.copy.start()

        def wait(self):
            self.copy.wait()

    real = paged_module.pltpu.make_async_copy
    monkeypatch.setattr(paged_module.pltpu, "make_async_copy",
                        lambda *a: Counted(real(*a)))
    return started


class TestPagedDecodeKernel:
    @pytest.mark.parametrize("layer", LAYERS)
    @pytest.mark.parametrize("n,k", [(4, 4), (4, 2), (8, 2)])
    def test_matches_reference_ragged_gqa(self, n, k, layer):
        ka, va = _arena(k=k)
        bt, lengths = _ragged_tables()
        q = jax.random.normal(jax.random.PRNGKey(3), (3, n, 32))
        out = paged_decode_attention(q, ka, va, layer, bt, lengths,
                                     interpret=INTERPRET)
        ref = _pool_reference(q[:, None], ka, va, layer, bt,
                              lengths[:, None] - 1)[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("layer", LAYERS)
    def test_alibi_uses_true_positions(self, layer):
        ka, va = _arena(k=2)
        bt, lengths = _ragged_tables()
        n = 4
        q = jax.random.normal(jax.random.PRNGKey(4), (3, n, 32))
        al = alibi_slopes(n)
        out = paged_decode_attention(q, ka, va, layer, bt, lengths, alibi=al,
                                     interpret=INTERPRET)
        ref = _pool_reference(q[:, None], ka, va, layer, bt,
                              lengths[:, None] - 1, alibi=al)[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("n,k,d", [(4, 2, 32), (8, 2, 32), (2, 2, 128)])
    @pytest.mark.parametrize("walk", sorted(WALKS))
    def test_walk_matches_reference_at_its_edges(self, walk, n, k, d):
        """Lengths of 0, 1, a page, exactly a tile, a tile and a token, the
        whole table, and all of them in one call; what lies past a row's
        length is NaN, in k and in v, and never reaches the output."""
        assert paged_module._pages_per_tile(BS, k * d, jnp.float32) * BS \
            == TILE
        ka, va = _arena(nb=161, k=k, d=d, seed=1)
        bt, lengths = _walk_tables(WALKS[walk], 161)
        q = jax.random.normal(jax.random.PRNGKey(12), (len(lengths), n, d))
        ref = _pool_reference(q[:, None], ka, va, 1, bt,
                              lengths[:, None] - 1)[:, 0]
        out = paged_decode_attention(
            q, _poison_what_no_row_reads(ka, bt, lengths),
            _poison_what_no_row_reads(va, bt, lengths), 1, bt, lengths,
            interpret=INTERPRET)
        assert bool(jnp.all(jnp.isfinite(out)))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
        empty = np.asarray(lengths) == 0
        assert not np.asarray(out)[empty].any()

    @pytest.mark.parametrize("walk", ["mixed", "whole-table"])
    def test_walk_alibi_over_several_tiles(self, walk):
        ka, va = _arena(nb=161, k=2, seed=2)
        bt, lengths = _walk_tables(WALKS[walk], 161, seed=3)
        n = 4
        q = jax.random.normal(jax.random.PRNGKey(13), (len(lengths), n, 32))
        al = alibi_slopes(n)
        out = paged_decode_attention(q, ka, va, 2, bt, lengths, alibi=al,
                                     interpret=INTERPRET)
        ref = _pool_reference(q[:, None], ka, va, 2, bt,
                              lengths[:, None] - 1, alibi=al)[:, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_walk_in_the_served_dtype(self, dtype=jnp.bfloat16, tol=2e-2):
        """bf16 as served: p goes into the value product as float32 (three
        bf16 terms), so the kernel lies closer to the reference computed in
        float32 from the same bf16 arena than bf16's own step."""
        ka, va = _arena(nb=161, k=2, seed=4, dtype=dtype)
        bt, lengths = _walk_tables(WALKS["mixed"], 161, seed=5)
        q = jax.random.normal(jax.random.PRNGKey(14),
                              (len(lengths), 4, 32), dtype)
        out = paged_decode_attention(q, ka, va, 0, bt, lengths,
                                     interpret=INTERPRET)
        assert out.dtype == dtype
        f32 = jnp.float32
        ref = _pool_reference(q.astype(f32)[:, None], ka.astype(f32),
                              va.astype(f32), 0, bt, lengths[:, None] - 1)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref[:, 0]), atol=tol, rtol=tol)

    @pytest.mark.parametrize("maxb", [MAXB, 128, 512])
    @pytest.mark.parametrize("walk", ["mixed", "one-token", "empty"])
    def test_copies_follow_resident_pages_not_the_table(self, walk, maxb,
                                                        monkeypatch):
        """What the PR is for, without a chip: the kernel starts one copy of
        k and one of v for each RESIDENT page, ceil(length / BLOCK) a row,
        and none for a table slot past it, however wide the table is."""
        started = _count_started_copies(monkeypatch)
        lengths = WALKS[walk]
        ka, va = _arena(nb=161, k=2, seed=6)
        bt, lens = _walk_tables(lengths, 161, maxb=maxb)
        q = jax.random.normal(jax.random.PRNGKey(15), (len(lengths), 4, 32))
        out = paged_decode_attention(q, ka, va, 1, bt, lens,
                                     interpret=INTERPRET)
        jax.block_until_ready(out)
        jax.effects_barrier()
        resident = sum(-(-n // BS) for n in lengths)
        assert len(started) == 2 * resident

    def test_inactive_row_outputs_zero(self):
        ka, va = _arena()
        bt, lengths = _ragged_tables()
        lengths = lengths.at[1].set(0)          # inactive decode row
        q = jax.random.normal(jax.random.PRNGKey(5), (3, 4, 32))
        out = paged_decode_attention(q, ka, va, 1, bt, lengths,
                                     interpret=INTERPRET)
        assert bool(jnp.all(out[1] == 0))


# the one-pool walk's edges: a page of 256 lanes whose first 128 are its
# values, 8 heads over the ONE key-value head; a tile holds 512 keys (twice
# the two-pool walk's), a table of 80 pages is two and a half of them
LATENT_W, LATENT_V, LATENT_HEADS, LATENT_MAXB = 256, 128, 8, 80
LATENT_TILE = 2 * TILE
# rows of different lengths in one call: one that ends inside a page (and
# inside a tile), an empty one, a few tokens, one that ends on a tile's edge,
# the whole table, exactly a page, and a row whose first tile the empty rows
# above it did not start
LATENT_ROWS = (LATENT_TILE + 5, 0, 9, 2 * LATENT_TILE, LATENT_MAXB * BS, 0,
               0, BS, LATENT_TILE)
LATENT_WALKS = {
    # (pool, dtype, NaN in what no row reads, tolerance)
    "rows-of-every-length": (0, jnp.float32, False, 2e-5),
    "a-pool-that-is-not-the-first": (2, jnp.float32, False, 2e-5),
    "nan-past-a-rows-length": (1, jnp.float32, True, 2e-5),
    "bfloat16": (1, jnp.bfloat16, False, 2e-2),
    "bfloat16-nan-past-a-rows-length": (0, jnp.bfloat16, True, 2e-2),
    # not the one-pool walk: one key-value head in TWO arenas, the GQA way
    "two-arenas-one-kv-head": (1, jnp.float32, True, 2e-5),
}


class TestLatentDecodeKernel:
    @pytest.mark.parametrize("case", sorted(LATENT_WALKS))
    def test_one_pool_walk_is_the_two_pool_walk(self, case):
        """``latent_decode_attention`` on ONE pool against the parent's form
        of the same read (the pool handed to ``paged_decode_attention`` as
        keys AND as values at one key-value head, the value lanes cut out of
        the result) to float32 round-off, and against the reference; and a
        one-key-value-head caller with two arenas still reads what the
        reference reads."""
        pool, dtype, poison, tol = LATENT_WALKS[case]
        f32 = jnp.float32
        assert paged_module._pages_per_tile(BS, LATENT_W, dtype, sides=1) \
            * BS == LATENT_TILE
        nb = 1 + sum(-(-n // BS) for n in LATENT_ROWS)
        arena, other = (
            a.astype(dtype)
            for a in _arena(nb=nb, k=1, d=LATENT_W, seed=7, dtype=f32))
        bt, lengths = _walk_tables(LATENT_ROWS, nb, maxb=LATENT_MAXB, seed=8)
        q = jax.random.normal(jax.random.PRNGKey(17),
                              (len(LATENT_ROWS), LATENT_HEADS, LATENT_W),
                              f32).astype(dtype)
        scale = 0.11
        dirty = (functools.partial(_poison_what_no_row_reads, bt=bt,
                                   lengths=lengths) if poison
                 else lambda a: a)
        empty = np.asarray(lengths) == 0
        if case == "two-arenas-one-kv-head":
            out = paged_decode_attention(q, dirty(arena), dirty(other), pool,
                                         bt, lengths, scale=scale,
                                         interpret=INTERPRET)
            ref = _pool_reference(q[:, None], arena, other, pool, bt,
                                  lengths[:, None] - 1, scale=scale)[:, 0]
        else:
            out = paged_module.latent_decode_attention(
                q, dirty(arena), pool, bt, lengths, LATENT_V, scale,
                interpret=INTERPRET)
            assert out.shape == q.shape[:2] + (LATENT_V,)
            assert out.dtype == dtype
            parent = paged_decode_attention(
                q, dirty(arena), dirty(arena), pool, bt, lengths,
                scale=scale, interpret=INTERPRET)[..., :LATENT_V]
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(parent, np.float32),
                atol=2e-6 if dtype == f32 else 2e-2, rtol=0)
            ref = _pool_reference(
                q.astype(f32)[:, None], arena.astype(f32), arena.astype(f32),
                pool, bt, lengths[:, None] - 1,
                scale=scale)[:, 0, :, :LATENT_V]
        assert bool(jnp.all(jnp.isfinite(out)))
        assert np.abs(np.asarray(out, np.float32)[~empty]).max() > 0.1
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), atol=tol, rtol=tol)
        assert not np.asarray(out, np.float32)[empty].any()

    def test_one_pool_walk_copies_a_page_once(self, monkeypatch):
        """What the walk is for, without a chip: ONE copy is started for
        each resident page (a whole tile's unrolled, a row's last tile's in
        a loop), none for a table slot past a row's length."""
        started = _count_started_copies(monkeypatch)
        nb = 1 + sum(-(-n // BS) for n in LATENT_ROWS)
        arena, _ = _arena(nb=nb, k=1, d=LATENT_W, seed=9)
        bt, lengths = _walk_tables(LATENT_ROWS, nb, maxb=LATENT_MAXB)
        q = jax.random.normal(jax.random.PRNGKey(18),
                              (len(LATENT_ROWS), LATENT_HEADS, LATENT_W))
        out = paged_module.latent_decode_attention(
            q, arena, 1, bt, lengths, LATENT_V, 0.1, interpret=INTERPRET)
        jax.block_until_ready(out)
        jax.effects_barrier()
        assert len(started) == sum(-(-n // BS) for n in LATENT_ROWS)

    @pytest.mark.parametrize("layer", LAYERS)
    def test_reference_matches_dense_gather_path(self, layer):
        """The jnp paged reference (CPU serving fallback) computes the
        same attention as a dense gather view + dot_product_attention,
        built here by hand, on the layer it is given (here as a traced
        scalar, as the layer scan gives it)."""
        ka, va = _arena(k=2)
        bt, lengths = _ragged_tables()
        n = 4
        q1 = jax.random.normal(jax.random.PRNGKey(6), (3, 1, n, 32))
        pos = lengths[:, None] - 1
        ref = jax.jit(reference_paged_attention)(
            q1, ka, va, jnp.int32(layer), bt, pos)
        kk, vv = _dense_view(ka, layer, bt), _dense_view(va, layer, bt)
        col = jnp.arange(kk.shape[1], dtype=jnp.int32)
        full = (col[None, None, :] <= pos[:, :, None]).astype(jnp.int32)
        want = dot_product_attention(q1, kk, vv, full, causal=False)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


class TestPagedPrefillKernel:
    # KV heads a group (the slab of lanes the kernel's loop addresses): 4
    # (4x32 lanes), the whole page (2x32), 2 (64-wide heads pair up), 1
    # (128-wide heads)
    @pytest.mark.parametrize("layer", LAYERS)
    @pytest.mark.parametrize("n,k,d", [(4, 4, 32), (8, 2, 32), (4, 4, 64),
                                       (4, 2, 128)])
    def test_chunk_matches_reference(self, n, k, d, layer):
        ka, va = _arena(k=k, d=d)
        bt = jnp.asarray(np.array([[5, 1, 7, 0], [3, 8, 0, 0]], np.int32))
        start = jnp.asarray(np.array([21, 0], np.int32))
        C = 16
        q = jax.random.normal(jax.random.PRNGKey(7), (2, C, n, d))
        pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        out = paged_prefill_attention(q, ka, va, layer, bt, start,
                                      interpret=INTERPRET)
        ref = _pool_reference(q, ka, va, layer, bt, pos)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("layer", LAYERS)
    def test_chunk_alibi(self, layer):
        ka, va = _arena(k=2)
        bt = jnp.asarray(np.array([[5, 1, 7, 0]], np.int32))
        start = jnp.asarray(np.array([17], np.int32))
        n, C = 4, 16
        q = jax.random.normal(jax.random.PRNGKey(8), (1, C, n, 32))
        al = alibi_slopes(n)
        pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        out = paged_prefill_attention(q, ka, va, layer, bt, start, alibi=al,
                                      interpret=INTERPRET)
        ref = _pool_reference(q, ka, va, layer, bt, pos, alibi=al)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("layer", LAYERS)
    def test_chunk_matches_dense_gather_path(self, layer):
        ka, va = _arena(k=2)
        bt = jnp.asarray(np.array([[5, 1, 7, 0]], np.int32))
        start = jnp.asarray(np.array([21], np.int32))
        n, C = 4, 16
        q = jax.random.normal(jax.random.PRNGKey(9), (1, C, n, 32))
        pos = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]
        out = paged_prefill_attention(q, ka, va, layer, bt, start,
                                      interpret=INTERPRET)
        kk, vv = _dense_view(ka, layer, bt), _dense_view(va, layer, bt)
        col = jnp.arange(kk.shape[1], dtype=jnp.int32)
        full = (col[None, None, :] <= pos[:, :, None]).astype(jnp.int32)
        want = dot_product_attention(q, kk, vv, full, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("n,k,d", [(4, 2, 32), (8, 2, 32), (2, 2, 128)])
    @pytest.mark.parametrize("case", sorted(set(CHUNKS) - {"small-table"}))
    def test_walk_matches_reference_at_its_edges(self, case, n, k, d):
        """A chunk at position 0, ending on a tile's edge and one past it,
        across two tiles, after several, with fewer real tokens than slots,
        5 slots a row with an empty row among them, and all of these in one
        call; GQA 8 over 2 and head size 128. What lies past a row's real
        tokens is NaN, in k and in v, the scratch page too, and never
        reaches the output: the queries past them come out finite."""
        assert paged_module._chunk_tile_pages(BS, k * d, jnp.float32) * BS \
            == CHUNK_TILE
        ka, va = _arena(nb=321, k=k, d=d, seed=7)
        q, bt, start, lengths, pos, real = _chunk_case(case, 321, n, d)
        ref = _pool_reference(q, ka, va, 1, bt, pos)
        out = paged_prefill_attention(
            q, _poison_what_no_row_reads(ka, bt, lengths),
            _poison_what_no_row_reads(va, bt, lengths), 1, bt, start,
            lengths, interpret=INTERPRET)
        assert bool(jnp.all(jnp.isfinite(out)))
        np.testing.assert_allclose(np.asarray(out)[real],
                                   np.asarray(ref)[real],
                                   atol=2e-5, rtol=2e-5)
        assert not np.asarray(out)[np.asarray(lengths) == 0].any()

    @pytest.mark.parametrize("case", ["several-tiles", "mixed", "verify"])
    def test_walk_alibi_over_several_tiles(self, case):
        ka, va = _arena(nb=321, k=2, seed=8)
        n = 4
        q, bt, start, lengths, pos, real = _chunk_case(case, 321, n, 32,
                                                       seed=3)
        al = alibi_slopes(n)
        out = paged_prefill_attention(q, ka, va, 2, bt, start, lengths,
                                      alibi=al, interpret=INTERPRET)
        ref = _pool_reference(q, ka, va, 2, bt, pos, alibi=al)
        np.testing.assert_allclose(np.asarray(out)[real],
                                   np.asarray(ref)[real],
                                   atol=2e-5, rtol=2e-5)

    def test_walk_in_the_served_dtype(self, dtype=jnp.bfloat16, tol=2e-2):
        """bf16 as served: q, k and v go into the products as stored, p
        rounded to bf16 for the value product, everything summed in
        float32 — within the decode walk's 2e-2 of the reference computed in
        float32 from the same bf16 arena."""
        ka, va = _arena(nb=321, k=2, seed=9, dtype=dtype)
        q, bt, start, lengths, pos, real = _chunk_case(
            "mixed", 321, 4, 32, seed=5, dtype=dtype)
        out = paged_prefill_attention(q, ka, va, 0, bt, start, lengths,
                                      interpret=INTERPRET)
        assert out.dtype == dtype
        f32 = jnp.float32
        ref = _pool_reference(q.astype(f32), ka.astype(f32), va.astype(f32),
                              0, bt, pos)
        np.testing.assert_allclose(np.asarray(out, np.float32)[real],
                                   np.asarray(ref)[real], atol=tol, rtol=tol)

    @pytest.mark.parametrize("case,maxb", [
        ("small-table", 4), ("mixed", 160), ("mixed", 512), ("verify", 128),
        ("short-chunk", 128), ("several-tiles", 512)])
    def test_copies_follow_resident_pages_not_the_table(self, case, maxb,
                                                        monkeypatch):
        """The decode walk's count, for a chunk: one copy of k and one of v
        for each page up to a row's last REAL token, ceil(length / BLOCK) a
        row, and none for a table slot past it (nor for a pad slot's page),
        however wide the table is."""
        started = _count_started_copies(monkeypatch)
        ka, va = _arena(nb=321, k=2, seed=6)
        q, bt, start, lengths, _, _ = _chunk_case(case, 321, 4, 32,
                                                  maxb=maxb)
        out = paged_prefill_attention(q, ka, va, 1, bt, start, lengths,
                                      interpret=INTERPRET)
        jax.block_until_ready(out)
        jax.effects_barrier()
        resident = sum(-(-(s + v) // BS) for s, v in CHUNKS[case])
        assert len(started) == 2 * resident

    @pytest.mark.parametrize("case", sorted(TILE_STEPS))
    def test_tile_steps_compute_every_block_that_holds_something(self, case):
        """Chunks of 256 and 1,024 queries, a head and four heads a KV head,
        heads of 64 (two a slab) and of 128, under alibi: a row whose keys
        end inside a tile's first key sub-block, on a tile's edge and one
        past a sub-block's, a ragged last chunk, an empty row between live
        ones. What lies past a row's real tokens is NaN in k and in v."""
        q, ka, va, bt, start, lengths, pos, real, al = _tile_step_case(case)
        ref = _pool_reference(q, ka, va, 1, bt, pos, alibi=al)
        out = paged_prefill_attention(
            q, _poison_what_no_row_reads(ka, bt, lengths),
            _poison_what_no_row_reads(va, bt, lengths), 1, bt, start,
            lengths, alibi=al, interpret=INTERPRET)
        assert bool(jnp.all(jnp.isfinite(out)))
        np.testing.assert_allclose(np.asarray(out)[real],
                                   np.asarray(ref)[real],
                                   atol=2e-5, rtol=2e-5)
        assert not np.asarray(out)[np.asarray(lengths) == 0].any()

    @pytest.mark.parametrize("case", [
        "ends-inside-a-first-sub-block", "empty-row-between-live-rows",
        "chunk-of-1024-four-query-heads-a-kv-head"])
    def test_tile_steps_in_the_served_dtype(self, case, dtype=jnp.bfloat16,
                                            tol=2e-2):
        """bf16 as served, where a slab of two heads carries the sum of the
        ROUNDED p in the lanes of the other head: within the walk's 2e-2 of
        the reference computed in float32 from the same bf16 arena."""
        q, ka, va, bt, start, lengths, pos, real, _ = _tile_step_case(
            case, seed=1, dtype=dtype)
        out = paged_prefill_attention(q, ka, va, 2, bt, start, lengths,
                                      interpret=INTERPRET)
        assert out.dtype == dtype
        f32 = jnp.float32
        ref = _pool_reference(q.astype(f32), ka.astype(f32), va.astype(f32),
                              2, bt, pos)
        np.testing.assert_allclose(np.asarray(out, np.float32)[real],
                                   np.asarray(ref)[real], atol=tol, rtol=tol)

    @pytest.mark.parametrize("window", [None, 512, 300])
    @pytest.mark.parametrize("chunk,n,k,d", [(256, 32, 32, 64),
                                             (1024, 32, 8, 64),
                                             (256, 16, 16, 128),
                                             (16, 4, 2, 32)])
    def test_block_counts_match_a_count_over_the_mask(self, chunk, n, k, d,
                                                      window):
        """``prefill_block_counts`` against brute force: of the (query
        block, key sub-block) pairs that a row's tiles span, the kernel
        needs those in which some REAL query sees some key; it computes a
        tile's sub-blocks up to the last it needs (under a window the first
        of them may lie below every query's window: computed, and masked),
        and none of a tile it needs nothing of."""
        arena = jax.ShapeDtypeStruct((3, 64, BS, k * d), jnp.bfloat16)
        pages, _, QB, PB = paged_module._chunk_geometry(
            chunk, n, k, BS, k * d, arena.dtype)
        TK, KB = pages * BS, PB * BS
        rng = np.random.default_rng(chunk + (window or 0))
        rows = [(0, chunk), (chunk, chunk), (3 * chunk, chunk), (TK, 1),
                (TK - 1, min(chunk, 2)), (0, 0)] + [
            (int(s), int(v)) for s, v in zip(
                rng.integers(0, 4 * TK, 20), rng.integers(1, chunk + 1, 20))]
        for s, v in rows:
            length = s + v if v else 0
            spanned = needed = computed = 0
            key = np.arange(-(-length // TK) * TK)
            for q0 in range(0, chunk, QB):
                qpos = s + q0 + np.arange(QB)
                qpos = qpos[qpos < length]
                sees = (key[None] <= qpos[:, None]) & (key[None] < length)
                if window is not None:
                    sees &= key[None] > qpos[:, None] - window
                need = sees.reshape(len(qpos), len(key) // KB, KB).any(
                    axis=(0, 2))
                spanned += len(need)
                needed += int(need.sum())
                for tile in need.reshape(-1, TK // KB):
                    computed += int(np.flatnonzero(tile).max(initial=-1)) + 1
            got = paged_module.prefill_block_counts(
                [s], [length], chunk, n, d, arena, window=window)
            assert got == {"prefill_blocks": spanned,
                           "prefill_blocks_skipped": spanned - computed}, \
                (s, v)
            if window is None:
                assert computed == needed, (s, v)

    def test_whole_chunk_is_real_where_no_length_is_given(self):
        ka, va = _arena(k=2)
        bt = jnp.asarray(np.array([[5, 1, 7, 0]], np.int32))
        start = jnp.asarray(np.array([21], np.int32))
        q = jax.random.normal(jax.random.PRNGKey(10), (1, 16, 4, 32))
        np.testing.assert_array_equal(
            np.asarray(paged_prefill_attention(q, ka, va, 1, bt, start,
                                               interpret=INTERPRET)),
            np.asarray(paged_prefill_attention(q, ka, va, 1, bt, start,
                                               start + 16,
                                               interpret=INTERPRET)))


class TestPagedForwardWritesInPlace:
    """A paged ``forward`` step over a 3-layer arena full of other
    sequences' rows: the arena goes in whole and comes back with exactly the
    rows (layer, blk, off) of this step rewritten, and kernels and reference
    see the same thing there."""

    BS, NB = 4, 10

    @pytest.fixture(scope="class")
    def model(self):
        cfg = transformer_config("opt-125m", dtype=jnp.float32,
                                 hidden_size=64, num_layers=len(LAYERS),
                                 num_heads=4, vocab_size=96, max_seq_len=32)
        return cfg, T.init_params(jax.random.PRNGKey(0), cfg)

    @pytest.fixture
    def interpreted_kernels(self, monkeypatch):
        """The model's kernel branch, run by the Pallas interpreter."""
        monkeypatch.setattr(registry, "kernels_active", lambda: True)
        for module, names in (("paged_decode_attention",
                               ("paged_decode_attention",
                                "paged_prefill_attention")),
                              ("normalization", ("fused_layer_norm",))):
            mod = importlib.import_module(f"deepspeed_tpu.ops.{module}")
            for name in names:
                monkeypatch.setattr(mod, name, functools.partial(
                    getattr(mod, name), interpret=True))

    def _step(self, step):
        """(ids, positions, block_table, write_mask, written (blk, off))."""
        if step == "decode":
            # rows at lengths 5, 0 (inactive: table of zeros) and 11
            bt = np.array([[7, 2, 0], [0, 0, 0], [4, 9, 1]], np.int32)
            lengths = np.array([5, 0, 11], np.int32)
            ids = np.array([[3], [0], [17]], np.int32)
            written = {(2, 1), (0, 0), (1, 3)}
            return ids, lengths[:, None], bt, None, written
        # one prompt's second chunk: 6 slots from position 4, 5 of them real
        bt = np.array([[5, 8, 3]], np.int32)
        ids = np.arange(11, 17, dtype=np.int32)[None]
        mask = (np.arange(6) < 5)[None]
        pos = np.where(mask, 4 + np.arange(6)[None], -1).astype(np.int32)
        written = {(8, 0), (8, 1), (8, 2), (8, 3), (3, 0), (0, 0)}
        return ids, pos, bt, mask, written

    def _forward(self, model, step):
        cfg, params = model
        ids, pos, bt, mask, _ = self._step(step)
        ks = jax.random.split(jax.random.PRNGKey(1), 2)
        shape = (len(LAYERS), self.NB, self.BS,
                 cfg.num_kv_heads * cfg.head_dim)
        arena = {"k": jax.random.normal(ks[0], shape, jnp.float32),
                 "v": jax.random.normal(ks[1], shape, jnp.float32)}
        # under jit, as the serving programs run it: the layer index is
        # traced and the arena is the scan's carry
        fwd = jax.jit(functools.partial(T.forward, cfg=cfg))
        logits, new, _ = fwd(
            params, jnp.asarray(ids), cache=arena, positions=jnp.asarray(pos),
            block_table=jnp.asarray(bt),
            paged_write_mask=None if mask is None else jnp.asarray(mask))
        return arena, new, np.asarray(logits)

    @pytest.mark.parametrize("step", ["decode", "chunk"])
    def test_only_the_written_rows_change_in_every_layer(self, model, step):
        arena, new, _ = self._forward(model, step)
        written = self._step(step)[-1]
        touched = np.zeros((len(LAYERS), self.NB, self.BS), bool)
        for blk, off in written:
            touched[:, blk, off] = True
        for side in ("k", "v"):
            before, after = np.asarray(arena[side]), np.asarray(new[side])
            assert after.shape == before.shape
            np.testing.assert_array_equal(after[~touched], before[~touched])
            changed = (after != before).any(axis=-1)
            np.testing.assert_array_equal(changed, touched)
            # each layer wrote its own keys: no layer's rows equal another's
            for blk, off in written - {(0, 0)}:
                assert not np.array_equal(after[0, blk, off],
                                          after[-1, blk, off])

    @pytest.mark.parametrize("step", ["decode", "chunk"])
    def test_kernels_match_reference_through_forward(self, model, step,
                                                     interpreted_kernels,
                                                     monkeypatch):
        """Through ``forward``: the kernels (interpreted) against the
        reference, whose gather of ``arena[layer, block_table]`` the tests
        above hold to a dense view built by hand."""
        _, new_k, logits_k = self._forward(model, step)
        monkeypatch.setattr(registry, "kernels_active", lambda: False)
        _, new_r, logits_r = self._forward(model, step)
        ids, pos, *_ = self._step(step)
        live = np.asarray(pos) >= 0            # pad logits are never read
        np.testing.assert_allclose(logits_k[live], logits_r[live],
                                   atol=2e-4, rtol=2e-4)
        # block 0 is scratch: a pad query's output, and so the next
        # layer's pad keys, differ by path and are never read
        for side in ("k", "v"):
            np.testing.assert_allclose(np.asarray(new_k[side])[:, 1:],
                                       np.asarray(new_r[side])[:, 1:],
                                       atol=2e-5, rtol=2e-5)
        np.testing.assert_array_equal(logits_r[live].argmax(-1),
                                      logits_k[live].argmax(-1))

    @pytest.mark.parametrize("field,value", [
        ("attention_layers", ("global", "local", "global")),
        ("attention_scale", 1.0),
        ("attention_impl", dot_product_attention)])
    def test_paged_forward_refuses_what_it_has_no_operand_for(self, model,
                                                              field, value):
        """The paged read takes no window, no custom scale and no custom
        attention: a paged call names the one it was asked for and stops,
        where the gather view once served it in silence."""
        import dataclasses

        cfg, params = model
        ids, pos, bt, *_ = self._step("decode")
        shape = (len(LAYERS), self.NB, self.BS,
                 cfg.num_kv_heads * cfg.head_dim)
        arena = {"k": jnp.zeros(shape), "v": jnp.zeros(shape)}
        with pytest.raises(NotImplementedError, match=field):
            T.forward(params, jnp.asarray(ids),
                      dataclasses.replace(cfg, **{field: value}),
                      cache=arena, positions=jnp.asarray(pos),
                      block_table=jnp.asarray(bt))


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

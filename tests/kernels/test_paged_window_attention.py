"""The paged kernels' window form and differential attention's pairs
(interpret mode on the CPU), float32 against float32 at 1e-5.

A window layer keeps its keys in a RING of pages a row
(`models/transformer._ring_table`): the table repeats the ring's pages, so a
table entry past the ring's length names a page that holds NEWER positions.
`paged_attention(window=...)` hands the kernels the table from the window's
first page on and positions counted from that page, so the walk starts there
whatever the row's length. Each case is held to a dense computation by hand
over the positions the window holds, taken out of the ring by position."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.ops import registry
from test_paged_copy_paths import COPY_PATHS, _copy_path_rows

paged = importlib.import_module("deepspeed_tpu.ops.paged_decode_attention")

F32 = jnp.float32
TOL = 1e-5
BS, K, D, N = 4, 2, 32, 4
WINDOW, RING = 8, 5         # a ring of 5 pages: 20 positions for 8 + 12


@pytest.fixture
def kernels(monkeypatch):
    """The kernel branch of `paged_attention`, its kernels in interpret
    mode."""
    monkeypatch.setattr(registry, "kernels_active", lambda: True)
    for name in ("paged_decode_attention", "paged_prefill_attention"):
        monkeypatch.setattr(paged, name, functools.partial(
            getattr(paged, name), interpret=True))


def _ring(rows, length, seed=0):
    """A pool of rings behind a scratch page, filled as the model fills it:
    row r's position p in page 1 + r * RING + (p // BS) % RING; whatever a
    newer position has not overwritten is an older one's (or poison where
    nothing was written). -> (k pool, v pool, table, keys, values by
    position)."""
    rng = np.random.default_rng(seed)
    keys = rng.standard_normal((rows, length, K * D)).astype(np.float32)
    vals = rng.standard_normal((rows, length, K * D)).astype(np.float32)
    pool_k = np.full((1, 1 + rows * RING, BS, K * D), 1e4, np.float32)
    pool_v = np.full((1, 1 + rows * RING, BS, K * D), np.nan, np.float32)
    for r in range(rows):
        for p in range(length):
            page = 1 + r * RING + (p // BS) % RING
            pool_k[0, page, p % BS] = keys[r, p]
            pool_v[0, page, p % BS] = vals[r, p]
    maxb = -(-length // BS) + 2
    table = (1 + np.arange(rows)[:, None] * RING
             + np.arange(maxb)[None] % RING).astype(np.int32)
    return (jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(table),
            keys, vals)


def _by_hand(q, keys, vals, pos, window):
    """q (N, D) at position `pos` over keys/values (T, K * D) by position."""
    lo = max(0, pos - window + 1)
    out = np.zeros((N, D), np.float64)
    for n in range(N):
        kv = n // (N // K)
        k = keys[lo:pos + 1, kv * D:(kv + 1) * D].astype(np.float64)
        v = vals[lo:pos + 1, kv * D:(kv + 1) * D].astype(np.float64)
        s = k @ q[n].astype(np.float64) / np.sqrt(D)
        p = np.exp(s - s.max())
        out[n] = (p / p.sum()) @ v
    return out


@pytest.mark.parametrize("path", ["reference", "kernel"])
@pytest.mark.parametrize("lengths", [(3, 8, 9), (12, 16, 17), (37, 40, 33)],
                         ids=["inside-the-window", "one-to-two-windows",
                              "the-ring-has-come-round"])
def test_a_decode_row_sees_its_window_and_nothing_else(path, lengths,
                                                       request):
    """Rows shorter than the window, at it, past it; the window's first key
    on a page's first row (length 16: keys 8..15) and inside a page; a ring
    that has come round, whose first table entries name pages that hold
    newer positions. The v pool is NaN and the k pool huge wherever nothing
    was written or the position is out of sight... of every row."""
    if path == "kernel":
        request.getfixturevalue("kernels")
    pool_k, pool_v, table, keys, vals = _ring(3, max(lengths))
    # each row's own length: what lies past it in its ring is NEWER data of
    # the same fill, which the causal mask hides
    rng = np.random.default_rng(7)
    q = rng.standard_normal((3, 1, N, D)).astype(np.float32)
    pos = np.asarray(lengths, np.int32)[:, None] - 1
    got = paged.paged_attention(jnp.asarray(q), pool_k, pool_v, 0, table,
                                jnp.asarray(pos), window=WINDOW)
    for r in range(3):
        want = _by_hand(q[r, 0], keys[r], vals[r], int(pos[r, 0]), WINDOW)
        assert np.abs(np.asarray(got[r, 0]) - want).max() < TOL


@pytest.mark.parametrize("path", ["reference", "kernel"])
@pytest.mark.parametrize("start,valid", [(0, 12), (5, 12), (24, 12), (27, 7)],
                         ids=["from-zero", "inside-a-page", "ring-round",
                              "ragged"])
def test_a_chunk_of_queries_each_sees_its_own_window(path, start, valid,
                                                     request):
    """12 queries a row over a window of 8: the first queries look back past
    the chunk's start, the last see only the chunk; pad queries (position
    -1) are never read."""
    if path == "kernel":
        request.getfixturevalue("kernels")
    C = 12
    pool_k, pool_v, table, keys, vals = _ring(2, start + valid)
    rng = np.random.default_rng(start)
    q = rng.standard_normal((2, C, N, D)).astype(np.float32)
    offs = np.arange(C)[None]
    pos = np.where(offs < valid, start + offs, -1).astype(np.int32)
    pos = np.broadcast_to(pos, (2, C))
    got = paged.paged_attention(jnp.asarray(q), pool_k, pool_v, 0, table,
                                jnp.asarray(pos), window=WINDOW)
    for r in range(2):
        for s in range(valid):
            want = _by_hand(q[r, s], keys[r], vals[r], start + s, WINDOW)
            assert np.abs(np.asarray(got[r, s]) - want).max() < TOL, (r, s)


def test_the_walk_starts_at_the_windows_first_page(kernels, monkeypatch):
    """A row of 40 tokens under a window of 8: the decode kernel is handed
    the 3 or 4 pages the window can span and a length counted from the first
    of them, not the row's 10 pages."""
    seen = {}
    inner = paged.paged_decode_attention

    def spy(q, k, v, layer, table, lengths, **kw):
        seen.update(table=np.asarray(table), lengths=np.asarray(lengths),
                    lo=np.asarray(kw["lo"]))
        return inner(q, k, v, layer, table, lengths, **kw)

    monkeypatch.setattr(paged, "paged_decode_attention", spy)
    pool_k, pool_v, table, _, _ = _ring(1, 40)
    q = jnp.ones((1, 1, N, D), F32)
    paged.paged_attention(q, pool_k, pool_v, 0, table,
                          jnp.asarray([[38]], jnp.int32), window=WINDOW)
    # keys 31..38: pages 7, 8, 9 of the row, ring pages 2, 3, 4
    assert seen["table"].shape == (1, (WINDOW + 1 - 2) // BS + 2)
    assert list(seen["table"][0][:3]) == [1 + 2, 1 + 3, 1 + 4]
    assert seen["lengths"][0] == 39 - 28 and seen["lo"][0] == 31 - 28


# ---------------------------------------------------------------------------
# differential attention's pairs as heads the kernels know
# ---------------------------------------------------------------------------


def _diff_by_hand(q, k, v, lam):
    """The published two-call form for ONE query position: q (N, D) heads,
    k, v (T, K, D); pair j = heads (2j, 2j + 1) on keys (2g, 2g + 1) and the
    value [v_2g, v_2g+1], g = j // (pairs / groups) -> (pairs, 2 D)."""
    pairs, groups = N // 2, K // 2
    out = np.zeros((pairs, 2 * D))
    for j in range(pairs):
        g = j // (pairs // groups)
        V = np.concatenate([v[:, 2 * g], v[:, 2 * g + 1]], axis=-1)
        maps = []
        for half in (0, 1):
            s = k[:, 2 * g + half] @ q[2 * j + half] / np.sqrt(D)
            p = np.exp(s - s.max())
            maps.append((p / p.sum()) @ V)
        out[j] = maps[0] - lam * maps[1]
    return out


@pytest.mark.parametrize("path", ["reference", "kernel"])
def test_the_pairs_fold_into_heads_of_twice_the_size(path, request):
    """`_diff_pairs` lays a query head in the half of its own key and sets
    the pair's keys and values side by side: the paged read over the SAME
    arena rows then gives each map over the group's wide value, and the
    subtraction is the published form's."""
    if path == "kernel":
        request.getfixturevalue("kernels")
    rng = np.random.default_rng(2)
    T_, Kh = 11, K                  # 4 query heads, 2 key heads: ONE group
    q = rng.standard_normal((1, 1, N, D)).astype(np.float32)
    k = rng.standard_normal((T_, Kh, D)).astype(np.float32)
    v = rng.standard_normal((T_, Kh, D)).astype(np.float32)
    pool_k = np.zeros((1, 5, BS, Kh * D), np.float32)
    pool_v = np.zeros((1, 5, BS, Kh * D), np.float32)
    table = np.asarray([[2, 4, 1, 0]], np.int32)
    for p in range(T_):
        pool_k[0, table[0, p // BS], p % BS] = k[p].reshape(-1)
        pool_v[0, table[0, p // BS], p % BS] = v[p].reshape(-1)
    cfg = None
    qf, _, _ = T._diff_pairs(cfg, jnp.asarray(q), None, None)
    assert qf.shape == (1, 1, N, 2 * D)
    got = paged.paged_attention(qf, jnp.asarray(pool_k), jnp.asarray(pool_v),
                                0, jnp.asarray(table),
                                jnp.asarray([[T_ - 1]], jnp.int32),
                                scale=D ** -0.5)
    maps = np.asarray(got)[0, 0].reshape(N // 2, 2, 2 * D)
    lam = 0.37
    want = _diff_by_hand(q[0, 0], k, v, lam)
    assert np.abs(maps[:, 0] - lam * maps[:, 1] - want).max() < TOL
    # keys and values fold by a reshape: the rows of the arena are the same
    _, kf, vf = T._diff_pairs(cfg, jnp.asarray(q), jnp.asarray(k)[None],
                              jnp.asarray(v)[None])
    np.testing.assert_array_equal(np.asarray(kf).reshape(T_, -1),
                                  k.reshape(T_, -1))
    assert vf.shape == (1, T_, Kh // 2, 2 * D)


# the windowed call at the lengths that take each path of the walk's copies
# (test_paged_copy_paths.py's, in the places it puts them): pages of 16 keys,
# a tile of 16 pages; two tiles and a key are Phi's 33 pages
PAGE, TILE_PAGES = 16, 16


@pytest.mark.parametrize("path", sorted(COPY_PATHS))
def test_the_windowed_walk_takes_every_path_of_the_copies(path):
    """``paged_decode_attention`` as ``paged_attention`` calls it under a
    window: a table that starts at the window's first page, lengths counted
    from that page's first key and ``lo``, the keys of that page below the
    window; a row of the length first, under an empty row, under a full one
    and above one. Each row against the reference under a window of its
    own, ``length - lo`` keys."""
    rows = _copy_path_rows(COPY_PATHS[path], 33 * PAGE)
    assert paged._pages_per_tile(PAGE, K * D, F32) == TILE_PAGES
    nb = 1 + sum(-(-n // PAGE) for n in rows)
    rng = np.random.default_rng(31)
    pool_k, pool_v = (jnp.asarray(rng.standard_normal(
        (2, nb, PAGE, K * D)).astype(np.float32)) for _ in range(2))
    pages = rng.permutation(np.arange(1, nb))
    table, at = np.zeros((len(rows), 33), np.int32), 0
    for r, n in enumerate(rows):
        held = -(-n // PAGE)
        table[r, :held] = pages[at:at + held]
        at += held
    lo = np.asarray([min(5 + r, max(n - 1, 0)) for r, n in enumerate(rows)],
                    np.int32)
    q = jnp.asarray(rng.standard_normal((len(rows), N, D)).astype(np.float32))
    got = paged.paged_decode_attention(
        q, pool_k, pool_v, 1, jnp.asarray(table),
        jnp.asarray(rows, jnp.int32), lo=jnp.asarray(lo), interpret=True)
    assert bool(jnp.all(jnp.isfinite(got)))
    for r, n in enumerate(rows):
        if n == 0:
            assert not np.asarray(got[r]).any()
            continue
        want = paged.reference_paged_attention(
            q[r][None, None], pool_k, pool_v, 1, jnp.asarray(table[r][None]),
            jnp.asarray([[n - 1]], jnp.int32), window=n - int(lo[r]))[0, 0]
        assert np.abs(np.asarray(got[r]) - np.asarray(want)).max() < 2e-5, r


# a chunk of 256 queries under a window of 512 over pages of 16 keys (Phi's
# call, at few heads): (heads, KV heads, head size, start, real tokens)
WINDOWED_CHUNKS = {
    # every query's window reaches below the chunk's start; the first
    # queries' lower edge lies inside the first page the walk is handed
    "crosses-the-windows-lower-edge": (4, 4, 64, 700, 256),
    "inside-the-window": (4, 4, 64, 100, 256),
    "deep-in-a-long-row-ragged": (4, 2, 128, 1500, 100),
    # the handed pages end one key past a tile (512 keys): 511 + 2 keys
    "one-key-into-the-second-tile": (4, 4, 64, 511, 2),
}


@pytest.mark.parametrize("case", sorted(WINDOWED_CHUNKS))
def test_a_large_chunk_under_a_window_of_512(case, kernels, monkeypatch):
    """``paged_attention(window=512)`` through the prefill kernel's tile
    steps (a tile of 512 keys in two sub-blocks; the table cut to the
    window's first page) against the reference over the whole table."""
    n, k, d, start, valid = WINDOWED_CHUNKS[case]
    C, page, window = 256, 16, 512
    nb = 1 + -(-(start + C) // page)
    rng = np.random.default_rng(start)
    pool_k, pool_v = (jnp.asarray(rng.standard_normal(
        (2, nb, page, k * d)).astype(np.float32)) for _ in range(2))
    table = jnp.asarray(rng.permutation(np.arange(1, nb))[None]
                        .astype(np.int32))
    q = jnp.asarray(rng.standard_normal((1, C, n, d)).astype(np.float32))
    offs = np.arange(C)[None]
    pos = jnp.asarray(np.where(offs < valid, start + offs, -1)
                      .astype(np.int32))
    got = paged.paged_attention(q, pool_k, pool_v, 1, table, pos,
                                window=window)
    want = paged.reference_paged_attention(q, pool_k, pool_v, 1, table, pos,
                                           window=window)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert np.abs(np.asarray(got) - np.asarray(want))[0, :valid].max() < 2e-5

"""Every path of a decode walk's copies (interpret mode on CPU).

``ops/paged_decode_attention._page_copies`` starts a WHOLE tile's pages
unrolled and waits for them once, one wait a side as large as the tile; a
row's last tile keeps a loop of starts and a loop of waits, and a row whose
predecessor holds nothing starts its own first tile. A row of each length
that takes another of those paths (and, at 5, 9 and 15 pages, the counts at
which a last tile's form would branch), in every place a row can stand to
the rows around it, against the reference, for the two-pool walk (MHA, and
GQA with alibi) and the one-pool walk. The windowed call's cases are
``test_paged_window_attention.py``'s. A file of its own beside
``test_paged_attention.py``, whose helpers it takes: the driver hands a file
to ONE worker, and that one is the suite's longest.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.transformer import alibi_slopes
from deepspeed_tpu.ops import paged_decode_attention
from test_paged_attention import (BS, INTERPRET, LATENT_HEADS, LATENT_MAXB,
                                  LATENT_V, LATENT_W, MAXB, TILE, _arena,
                                  _poison_what_no_row_reads, _pool_reference,
                                  _walk_tables, paged_module)

# the lengths that take each path of a walk's copies (``_page_copies``):
# nothing, a token, a page less a key, a page, a tile less a key, a tile, a
# tile and a key, two whole tiles, two and a key, and last tiles of 5, 9 and
# 15 pages (one, two and three bits of the count above the lowest)
COPY_PATHS = {
    "empty": 0, "one-token": 1, "page-less-a-key": BS - 1, "one-page": BS,
    "tile-less-a-key": TILE - 1, "one-tile": TILE, "tile-and-a-key": TILE + 1,
    "two-tiles": 2 * TILE, "two-tiles-and-a-key": 2 * TILE + 1,
    "5-pages": 5 * BS, "9-pages": 9 * BS, "15-pages": 15 * BS,
}


def _copy_path_rows(length, full):
    """``length`` as a call's FIRST row, as a row under an empty one (both
    start their own first tile), under a full one (which started it beside
    its last tile) and above one (whose first tile it starts), and an empty
    row above a full one and below one: the ``_first_tile`` rule."""
    return (length, 0, full, length, full, 0, length)


@pytest.mark.parametrize("n,k,alibi", [(4, 4, False), (8, 2, True)],
                         ids=["mha", "gqa-alibi"])
@pytest.mark.parametrize("path", sorted(COPY_PATHS))
def test_every_path_of_the_copies(path, n, k, alibi):
    """A row of each length that takes another path of the walk's copies
    (whole tiles unrolled and awaited once, a last tile in its loops), in
    every place a row can stand to the rows around it."""
    rows = _copy_path_rows(COPY_PATHS[path], MAXB * BS)
    nb = 1 + sum(-(-r // BS) for r in rows)
    ka, va = _arena(nb=nb, k=k, seed=21)
    assert paged_module._unrolls_whole_tiles(*ka.shape[-2:], ka.dtype)
    bt, lengths = _walk_tables(rows, nb, seed=22)
    q = jax.random.normal(jax.random.PRNGKey(23), (len(rows), n, 32))
    al = {"alibi": alibi_slopes(n)} if alibi else {}
    ref = _pool_reference(q[:, None], ka, va, 2, bt,
                          lengths[:, None] - 1, **al)[:, 0]
    out = paged_decode_attention(
        q, _poison_what_no_row_reads(ka, bt, lengths),
        _poison_what_no_row_reads(va, bt, lengths), 2, bt, lengths,
        interpret=INTERPRET, **al)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(out)[np.asarray(lengths) == 0].any()


@pytest.mark.parametrize("path", ["empty", "9-pages", "tile-and-a-key",
                                  "two-tiles-and-a-key"])
def test_wide_pages_keep_the_loops_of_copies(path):
    """Over pages of 64 KiB a side the two-pool walk asks for no whole-tile
    form (``_unrolls_whole_tiles``: their bytes set its pace): every tile in
    a loop of starts and a loop of waits, the same rows, the same answer."""
    rows = _copy_path_rows(COPY_PATHS[path], MAXB * BS)
    nb = 1 + sum(-(-r // BS) for r in rows)
    ka, va = _arena(nb=nb, k=4, d=256, seed=27)
    assert not paged_module._unrolls_whole_tiles(*ka.shape[-2:], ka.dtype)
    bt, lengths = _walk_tables(rows, nb, seed=28)
    q = jax.random.normal(jax.random.PRNGKey(29), (len(rows), 4, 256))
    ref = _pool_reference(q[:, None], ka, va, 2, bt,
                          lengths[:, None] - 1)[:, 0]
    out = paged_decode_attention(
        q, _poison_what_no_row_reads(ka, bt, lengths),
        _poison_what_no_row_reads(va, bt, lengths), 2, bt, lengths,
        interpret=INTERPRET)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(out)[np.asarray(lengths) == 0].any()


@pytest.mark.parametrize("pages", [0, 1, 5, 9, 15, 17, 31, 32, 33, 64,
                                   65])
@pytest.mark.parametrize("tail", [0, 1], ids=["whole-pages", "less-a-key"])
def test_every_path_of_the_one_pool_copies(pages, tail):
    """The two-pool walk's ``test_every_path_of_the_copies`` at the
    one-pool walk's tile of 32 pages: rows of ``pages`` pages, the last
    one a key short."""
    length = max(pages * BS - tail, 0)
    rows = _copy_path_rows(length, LATENT_MAXB * BS)
    nb = 1 + sum(-(-r // BS) for r in rows)
    arena, _ = _arena(nb=nb, k=1, d=LATENT_W, seed=24)
    bt, lengths = _walk_tables(rows, nb, maxb=LATENT_MAXB, seed=25)
    q = jax.random.normal(jax.random.PRNGKey(26),
                          (len(rows), LATENT_HEADS, LATENT_W))
    out = paged_module.latent_decode_attention(
        q, _poison_what_no_row_reads(arena, bt, lengths), 1, bt, lengths,
        LATENT_V, 0.11, interpret=INTERPRET)
    ref = _pool_reference(q[:, None], arena, arena, 1, bt,
                          lengths[:, None] - 1,
                          scale=0.11)[:, 0, :, :LATENT_V]
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    assert not np.asarray(out)[np.asarray(lengths) == 0].any()

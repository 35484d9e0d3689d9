"""A run of positions written into the paged arena a page at a time
(``models/transformer._write_pages``) leaves the bytes the row scatter
leaves: in every block but scratch, for any ``start`` (a page's first
position or not), any ``n_valid`` and any table."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import transformer as T

LAYERS, NUM_BLOCKS, BLOCK, MAXB, CHUNK = 2, 24, 4, 6, 8
VIEW = MAXB * BLOCK
# the run's first position: 0, a page's first, inside a page, and (with the
# whole chunk) a run whose last position lies in the table's last page, from
# inside a page and from a page's first position
STARTS = {"zero": 0, "aligned": 8, "unaligned": 5, "last-page": VIEW - CHUNK - 1,
          "last-page-aligned": VIEW - CHUNK}
N_VALID = {"none": 0, "one": 1, "page-less-one": BLOCK - 1, "chunk": CHUNK}


def _case(start, n_valid, width, rows, seed=0):
    """An arena of noise, ``rows`` requests with private blocks behind a
    first page that they SHARE (a cached prefix), and a run a row: the
    second row's begins elsewhere and is one shorter."""
    rng = np.random.default_rng(seed)
    arena = rng.standard_normal((LAYERS, NUM_BLOCKS, BLOCK, width))
    ids = rng.permutation(np.arange(1, NUM_BLOCKS))
    shared, ids = ids[0], ids[1:]
    table = ids[:rows * MAXB].reshape(rows, MAXB).astype(np.int32)
    starts = np.array([start, min(start + BLOCK + 1, VIEW - CHUNK)][:rows])
    valid = np.array([n_valid, max(n_valid - 1, 0)][:rows])
    # the shared page lies before every run, or there is none to share
    if starts.min() >= BLOCK:
        table[:, 0] = shared
    new = rng.standard_normal((rows, CHUNK, width))
    return (jnp.asarray(arena, jnp.bfloat16), table,
            jnp.asarray(new, jnp.bfloat16), starts.astype(np.int32),
            valid.astype(np.int32), int(shared))


def _row_scatter(arena, layer, table, new, starts, valid):
    """The write as the decode and verify programs make it, a row a token."""
    out = np.array(arena.astype(jnp.float32))
    new = np.asarray(new.astype(jnp.float32))
    for b in range(table.shape[0]):
        for s in range(valid[b]):
            p = starts[b] + s
            out[layer, table[b, p // BLOCK], p % BLOCK] = new[b, s]
    return out


@pytest.mark.parametrize("rows", [1, 2])
@pytest.mark.parametrize("width", [8, 24])
@pytest.mark.parametrize("n_valid", sorted(N_VALID))
@pytest.mark.parametrize("start", sorted(STARTS))
def test_pages_hold_what_the_row_scatter_writes(start, n_valid, width, rows):
    arena, table, new, starts, valid, shared = _case(
        STARTS[start], N_VALID[n_valid], width, rows)
    layer = 1

    @jax.jit
    def write(arena, table, new, starts, valid):
        blk, at, off = T._run_pages(table, BLOCK, CHUNK, starts, valid)
        return T._write_pages(arena, jnp.int32(layer), new, blk, at,
                              off), blk

    got, blk = write(arena, jnp.asarray(table), new, jnp.asarray(starts),
                     jnp.asarray(valid))
    got = np.asarray(got.astype(jnp.float32))
    want = _row_scatter(arena, layer, table, new, starts, valid)
    np.testing.assert_array_equal(got[:, 1:], want[:, 1:])

    # what the run does not cover is as it was: the other layer, every block
    # outside the run's pages (the shared one among them), and inside its
    # pages the positions before `start` and from `start + n_valid` on
    before = np.asarray(arena.astype(jnp.float32))
    np.testing.assert_array_equal(got[0], before[0])
    written = np.zeros((NUM_BLOCKS, BLOCK), bool)
    for b in range(rows):
        for p in range(starts[b], starts[b] + valid[b]):
            written[table[b, p // BLOCK], p % BLOCK] = True
    assert not written[shared].any()
    untouched = ~written
    untouched[0] = False                       # scratch holds anything
    np.testing.assert_array_equal(got[layer][untouched],
                                  before[layer][untouched])
    # and a page with no position of the run was sent to scratch, not
    # written back where another request may be reading it
    blk = np.asarray(blk)
    assert set(blk.ravel()) - {0} == set(np.flatnonzero(written.any(axis=1)))


@pytest.mark.parametrize("start,n_valid", [(0, 8), (5, 8), (13, 3), (9, 0)])
def test_the_forward_pass_writes_a_run_as_pages_or_as_rows_alike(start,
                                                                 n_valid):
    """``forward(paged_run=...)`` through a whole tiny model: the arena and
    the logits are those of the same call without it."""
    from deepspeed_tpu.inference.kv_cache import init_paged_cache
    from deepspeed_tpu.models.presets import transformer_config

    cfg = transformer_config("tiny", dtype=jnp.float32)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    cache = init_paged_cache(cfg, NUM_BLOCKS, BLOCK, jnp.float32)
    cache = {k: jnp.asarray(rng.standard_normal(v.shape), v.dtype)
             for k, v in cache.items()}
    table = jnp.asarray(rng.permutation(np.arange(1, NUM_BLOCKS))[:MAXB][None],
                        jnp.int32)
    chunk = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, CHUNK)), jnp.int32)
    offs = jnp.arange(CHUNK, dtype=jnp.int32)
    mask = (offs < n_valid)[None]
    pos = jnp.where(mask, (start + offs)[None], -1)

    def run(**how):
        return T.forward(params, chunk, cfg, cache=cache, positions=pos,
                         block_table=table, paged_write_mask=mask, **how)

    rows_logits, rows_cache, _ = run()
    page_logits, page_cache, _ = run(
        paged_run=(jnp.int32(start), jnp.int32(n_valid)))
    for side in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(page_cache[side])[:, 1:],
                                      np.asarray(rows_cache[side])[:, 1:])
    np.testing.assert_array_equal(np.asarray(page_logits)[0, :n_valid],
                                  np.asarray(rows_logits)[0, :n_valid])


# ---------------------------------------------------------------------------
# the mixed step: a chunk that is not its prompt's last and the decode rows
# as ONE program (``paged_kv.build_mixed_program``) against the chunk program
# followed by the decode program on the same arena
# ---------------------------------------------------------------------------

ROWS, M_BLOCK, M_MAXB, M_CHUNK, M_BLOCKS = 4, 4, 10, 8, 64

# name: (chunk start, n_valid, row lengths (0: a row that holds nothing),
#        rows that take their token from ``last``, temperature of the rows)
MIXED_CASES = {
    "whole-chunk": (0, M_CHUNK, (5, 9, 17, 3), (), 0.0),
    "n_valid-short": (8, 5, (5, 9, 17, 3), (), 0.0),
    "inactive-rows": (8, M_CHUNK, (0, 9, 0, 3), (), 0.0),
    "tokens-from-last": (16, M_CHUNK, (5, 9, 17, 3), (0, 2), 0.0),
    "mid-page-after-a-cache-hit": (6, 7, (5, 9, 17, 3), (1,), 0.0),
    "seeded-sampling": (8, M_CHUNK, (5, 0, 17, 3), (3,), 0.9),
    "one-row": (0, 3, (0, 0, 12, 0), (), 0.0),
    # the other position kinds and norms: learned with an offset, rotary
    # with grouped heads' rms norm, alibi
    "opt-shaped": (6, 7, (5, 0, 17, 3), (2,), 0.0, "tiny-opt"),
    "llama-shaped": (6, 7, (5, 0, 17, 3), (2,), 0.7, "tiny-llama"),
    "bloom-shaped": (6, 7, (5, 0, 17, 3), (2,), 0.0, "tiny-bloom"),
}


@pytest.mark.parametrize("case", sorted(MIXED_CASES))
def test_the_mixed_step_is_the_chunk_program_then_the_decode_program(case):
    """The rows' tokens are equal and the arena is equal byte for byte, the
    scratch block with the rest (the two parts write in the two programs'
    order)."""
    from deepspeed_tpu.inference.kv_cache import init_paged_cache
    from deepspeed_tpu.models.presets import transformer_config
    from deepspeed_tpu.serving import paged_kv

    start, n_valid, lengths, from_last, temperature, *model = \
        MIXED_CASES[case]
    cfg = transformer_config(*model or ["tiny"], dtype=jnp.float32)
    assert paged_kv.mixes(cfg)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(sum(map(ord, case)))
    arena = init_paged_cache(cfg, M_BLOCKS, M_BLOCK, jnp.float32)
    arena = {k: np.asarray(rng.standard_normal(v.shape), np.float32)
             for k, v in arena.items()}
    ids = rng.permutation(np.arange(1, M_BLOCKS)).astype(np.int32)
    # the chunk's request and every row own their pages; a row that holds
    # nothing has an all-zero table
    chunk_table = ids[:M_MAXB][None]
    row_table = ids[M_MAXB:M_MAXB * (ROWS + 1)].reshape(ROWS, M_MAXB).copy()
    lengths = np.asarray(lengths, np.int32)
    row_table[lengths == 0] = 0
    tokens = rng.integers(0, cfg.vocab_size, (ROWS,)).astype(np.int32)
    last = rng.integers(0, cfg.vocab_size, (ROWS,)).astype(np.int32)
    sent = tokens.copy()
    sent[list(from_last)] = -1
    live = lengths > 0
    rows = paged_kv.pack_decode_rows(
        row_table, lengths, np.where(live, sent, 0),
        np.where(live, temperature, 0.0), np.where(live, 7, 0),
        np.where(live, 0.9, 1.0), np.arange(ROWS) + 11,
        np.where(live, lengths % 5, 0))
    ids_c = np.zeros((1, M_CHUNK), np.int32)
    ids_c[0, :n_valid] = rng.integers(0, cfg.vocab_size, (n_valid,))
    chunk = paged_kv.pack_chunk(chunk_table, ids_c, start, n_valid,
                                [0.0], [0], [1.0], [3])
    key = jax.random.PRNGKey(5)

    def fresh():
        return {k: jnp.asarray(v) for k, v in arena.items()}

    prefill = paged_kv.build_prefill_program(cfg, M_CHUNK)
    decode = paged_kv.build_decode_program(cfg)
    mixed = paged_kv.build_mixed_program(cfg, M_CHUNK)
    # the engine's ONE sampling key, in all three programs: the streams are
    # told apart by (seed, step), and the same draws are what is compared
    _, _, two = prefill(params, fresh(), chunk, key)
    want, two = decode(params, two, rows, key,  # tpulint: disable=key-reuse
                       jnp.asarray(last))
    got, one = mixed(params, fresh(), rows, chunk,
                     key,  # tpulint: disable=key-reuse
                     jnp.asarray(last))
    np.testing.assert_array_equal(np.asarray(got)[live],
                                  np.asarray(want)[live])
    for side in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(one[side]),
                                      np.asarray(two[side]))
    # and the step wrote: the chunk's positions and a token a live row
    assert n_valid == 0 or not np.array_equal(
        np.asarray(one["k"])[:, chunk_table[0, start // M_BLOCK]],
        arena["k"][:, chunk_table[0, start // M_BLOCK]])


@pytest.mark.parametrize("preset,mixing", [
    ("tiny", True), ("tiny-opt", True), ("tiny-llama", True),
    ("tiny-bloom", True), ("tiny-gptj", True), ("tiny-gptneox", True),
    ("tiny-olmoe", False), ("tiny-solar-open2", False),
    ("tiny-nemotron-3-super", False), ("tiny-phi4flash", False),
    ("tiny-ouro", False)])
def test_only_a_one_pass_stack_of_plain_attention_and_dense_ffns_mixes(
        preset, mixing):
    """Decided by the layers' kinds, the passes and the FFN, never by a
    name: experts, recurrent, ring and cross kinds and a looped stack keep
    the two programs."""
    from deepspeed_tpu.models.presets import transformer_config
    from deepspeed_tpu.serving import paged_kv

    cfg = transformer_config(preset, dtype=jnp.float32)
    assert paged_kv.mixes(cfg) is mixing
    if not mixing:
        with pytest.raises(ValueError, match="do not mix"):
            paged_kv.build_mixed_program(cfg, 16)

"""The packed operands of the serving programs (``serving/paged_kv.py``): a
step's host operands travel in ONE int32 array, the float32 ones as their
bit patterns, and the program takes it apart on the device.

Round trips of each layout under ``jax.jit``, bit for bit; and the tiny
engine end to end against the same engine running the programs' bodies on
loose operands, greedy and sampled rows in one batch."""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.config.config import ServingConfig
from deepspeed_tpu.inference import init_inference
from deepspeed_tpu.serving import paged_kv
from deepspeed_tpu.serving.api import ServingEngine

R, MAXB, S, C = 6, 8, 3, 16
F32_MAX = np.finfo(np.float32).max
I32 = np.iinfo(np.int32)


def _rows(rng):
    """Operands of a decode step whose values sit at the edges: the table
    full to MAXB with the largest ids, every float the issue names, seeds
    below zero."""
    table = rng.integers(1, I32.max, (R, MAXB)).astype(np.int32)
    table[0] = I32.max
    return dict(
        block_table=table,
        lengths=np.asarray([0, 1, 127, 2047, 5, 9], np.int32),
        tokens=np.asarray([0, 50271, 7, 1, 2, 3], np.int32),
        temperature=np.asarray([0.0, 1.0, 0.7, 1e-8, F32_MAX, 0.3],
                               np.float32),
        top_k=np.asarray([0, 1, 50, 50272, 5, 0], np.int32),
        top_p=np.asarray([1.0, 0.0, 0.9, 1e-8, F32_MAX, 0.95], np.float32),
        seeds=np.asarray([0, -1, I32.min, I32.max, -12345, 7], np.int32),
        steps=np.asarray([0, 1, 511, 1023, 3, 4], np.int32))


def _chunk(rng, state_slot, last=False):
    table = rng.integers(1, I32.max, (1, MAXB)).astype(np.int32)
    got = dict(
        block_table=table,
        chunk=rng.integers(0, 50272, (1, C)).astype(np.int32),
        start=np.asarray(1792, np.int32), n_valid=np.asarray(C, np.int32),
        temperature=np.asarray([1e-8], np.float32),
        top_k=np.asarray([40], np.int32),
        top_p=np.asarray([0.7], np.float32),
        seeds=np.asarray([-7], np.int32))
    if state_slot:
        got["state_slot"] = np.asarray([63], np.int32)
    if last:
        got["last"] = np.asarray([1], np.int32)
    return got


def _verify(rng):
    rows = _rows(rng)
    rows["tokens"] = rng.integers(0, 50272, (R, S)).astype(np.int32)
    rows["n_valid"] = np.asarray([0, 1, S, 2, 1, S], np.int32)
    return rows


LAYOUTS = {
    "decode": (_rows, paged_kv.pack_decode_rows,
               paged_kv.unpack_decode_rows,
               paged_kv.decode_rows_shape(R, MAXB), (R, MAXB + 7)),
    "chunk": (lambda rng: _chunk(rng, False), paged_kv.pack_chunk,
              lambda p: paged_kv.unpack_chunk(p, C, False)[:-1],
              paged_kv.chunk_shape(MAXB, C, False), (MAXB + C + 6,)),
    "chunk_with_state_slot": (
        lambda rng: _chunk(rng, True), paged_kv.pack_chunk,
        lambda p: paged_kv.unpack_chunk(p, C, True),
        paged_kv.chunk_shape(MAXB, C, True), (MAXB + C + 7,)),
    "chunk_that_says_last": (
        lambda rng: _chunk(rng, True, True), paged_kv.pack_chunk,
        lambda p: paged_kv.unpack_chunk(p, C, True, True),
        paged_kv.chunk_shape(MAXB, C, True, True), (MAXB + C + 8,)),
    "chunk_that_says_last_without_a_state_slot": (
        lambda rng: _chunk(rng, False, True), paged_kv.pack_chunk,
        lambda p: (lambda got: got[:-2] + got[-1:])(
            paged_kv.unpack_chunk(p, C, False, True)),
        paged_kv.chunk_shape(MAXB, C, False, True), (MAXB + C + 7,)),
    "verify": (_verify, paged_kv.pack_verify_rows,
               lambda p: paged_kv.unpack_verify_rows(p, S),
               paged_kv.verify_rows_shape(R, MAXB, S), (R, MAXB + S + 7)),
}


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("layout", LAYOUTS)
def test_round_trip_is_bit_for_bit(layout):
    """``pack`` on the host, the inverse under ``jax.jit``: every operand
    comes back with its shape, its dtype and its bits."""
    make, pack, unpack, said, shape = LAYOUTS[layout]
    want = make(np.random.default_rng(0))
    # the pack functions take a program's operands in its own order
    order = [n for n in inspect.signature(pack).parameters if n in want]
    packed = pack(*(want[n] for n in order))
    assert packed.dtype == np.int32 and packed.shape == shape == said
    got = jax.jit(unpack)(packed)
    assert len(got) == len(order)
    for name, value in zip(order, got):
        assert value.shape == want[name].shape, name
        assert value.dtype == want[name].dtype, name
        np.testing.assert_array_equal(_bits(value), _bits(want[name]), name)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_a_new_array_each_call(layout):
    """A dispatched call may still read the last step's array (the CPU
    backend may alias a numpy argument): nothing is kept and written over."""
    make, pack, *_ = LAYOUTS[layout]
    want = make(np.random.default_rng(1))
    a, b = pack(**want), pack(**want)
    assert not np.shares_memory(a, b)
    assert all(not np.shares_memory(a, v) for v in want.values())
    np.testing.assert_array_equal(a, b)


def test_chunk_without_a_state_slot_says_none():
    packed = paged_kv.pack_chunk(**_chunk(np.random.default_rng(2), False))
    assert paged_kv.unpack_chunk(jnp.asarray(packed), C, False)[-1] is None
    packed = paged_kv.pack_chunk(**_chunk(np.random.default_rng(2), False,
                                          True))
    got = paged_kv.unpack_chunk(jnp.asarray(packed), C, False, True)
    assert got[-2] is None and int(got[-1][0]) == 1


@pytest.mark.parametrize("unpack,shape", [
    (paged_kv.unpack_decode_rows, (R, 7)),
    (lambda p: paged_kv.unpack_verify_rows(p, MAXB + S), (R, MAXB + S + 7)),
    (lambda p: paged_kv.unpack_chunk(p, MAXB + C, False), (MAXB + C + 6,)),
    (lambda p: paged_kv.unpack_chunk(p, C + 6, True), (C + 6 + 7,)),
], ids=["decode", "verify", "chunk", "chunk_with_state_slot"])
def test_an_array_with_no_room_for_a_table_is_refused(unpack, shape):
    """The table's width is what the other columns leave: a program built
    for more token columns than the array has left over says so when it is
    traced, and reads no column as another."""
    with pytest.raises(ValueError, match="do not hold"):
        jax.eval_shape(unpack, jax.ShapeDtypeStruct(shape, jnp.int32))


# ---------------------------------------------------------------------------
# end to end: the packed programs against their bodies on loose operands


@pytest.fixture(scope="module")
def tiny_engine():
    return init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)


class Loose:
    """A program of the packed calling convention that runs the unpacked
    body: its ``operands`` is the tuple of loose host arrays that the
    patched pack function handed through. What the engine passes behind
    the key (the decode program's last tokens) goes to the body as it is."""

    calls = 0

    def __init__(self, step, tail=()):
        self.program = jax.jit(step, donate_argnums=(1,))
        self.tail = tail

    def __call__(self, params, cache, operands, key, *on_device):
        Loose.calls += 1
        return self.program(params, cache, *operands, *self.tail, key,
                            *on_device)

    def _cache_size(self):
        return self.program._cache_size()


# greedy rows and sampled rows in one batch, more requests than rows
SAMPLING = [dict(), dict(temperature=0.8, top_k=20, top_p=0.9, seed=-5),
            dict(temperature=1.0, seed=11), dict(),
            dict(temperature=0.7, top_p=0.5, seed=2147483647),
            dict(temperature=1.3, top_k=3, seed=3)]


def _serve(tiny_engine, speculative=False, sampling=SAMPLING):
    """The token streams of six requests with prompts of one to three
    chunks; the prompts repeat themselves, so that an n-gram drafter has
    something to propose."""
    spec = ({"mode": "ngram", "num_draft_tokens": 2} if speculative
            else {"mode": "off"})
    srv = ServingEngine(tiny_engine, ServingConfig(
        block_size=16, num_blocks=48, max_seqs=4, max_model_len=128,
        prefill_chunk=16, max_queue=64, prefix_cache=False,
        speculative=spec))
    handles = [srv.submit(np.tile(np.arange(1 + i, 8 + i), 6)[:9 + 7 * i],
                          max_new_tokens=6 + i, **kw)
               for i, kw in enumerate(sampling)]
    srv.run()
    out = [[int(t) for t in h.result()] for h in handles]
    srv.close()
    return out


@pytest.fixture
def loose_programs(monkeypatch):
    """Every engine built from here on calls the programs' bodies on loose
    operands, as the programs were called before they took one array."""
    monkeypatch.setattr(paged_kv, "pack_decode_rows",
                        lambda *operands: operands)
    monkeypatch.setattr(paged_kv, "pack_verify_rows",
                        lambda *operands: operands)
    monkeypatch.setattr(
        paged_kv, "pack_chunk",
        lambda table, chunk, start, n_valid, *sampling, state_slot=None: (
            table, chunk, np.asarray(start, np.int32),
            np.asarray(n_valid, np.int32), *sampling))
    monkeypatch.setattr(
        paged_kv, "build_decode_program",
        lambda cfg, moe_counts=False: Loose(paged_kv._decode_step(cfg)))
    monkeypatch.setattr(
        paged_kv, "build_prefill_program",
        lambda cfg, chunk_tokens, moe_counts=False: Loose(
            paged_kv._chunk_step(cfg), tail=(None,)))
    monkeypatch.setattr(
        paged_kv, "build_verify_program",
        lambda cfg, num_tokens: Loose(paged_kv._verify_step(cfg)))


@pytest.mark.parametrize("speculative", [False, True],
                         ids=["decode_and_chunk", "verify"])
def test_token_streams_are_those_of_the_unpacked_bodies(
        tiny_engine, request, speculative):
    """Same prompts, seeds and sampling settings: the engine on its packed
    programs gives, token for token, what it gives with the programs'
    bodies called on loose operands."""
    packed = _serve(tiny_engine, speculative)
    # the sampled rows did sample: theirs are not the greedy streams
    greedy = _serve(tiny_engine, speculative, [{}] * len(SAMPLING))
    assert [len(t) for t in packed] == [6, 7, 8, 9, 10, 11]
    assert [p == g for p, g in zip(packed, greedy)] == [
        not kw for kw in SAMPLING]
    request.getfixturevalue("loose_programs")
    before = Loose.calls
    assert _serve(tiny_engine, speculative) == packed
    assert Loose.calls - before >= 20

"""Phi-4-mini-flash-reasoning (`phi4flash`: a self-decoder of Mamba-1 and
window layers, ONE full layer whose keys and values the cross layers read,
gated memory units on the value the last Mamba-1 layer hands on, differential
attention throughout) against its plain float32 reference. CPU, float32,
seeded weights, `tiny-phi4flash`: 8 layers (two (mamba1, swa) periods,
(mamba1, full), (gmu, cross)), a window of 8 keys, 8 query heads in 4 pairs
over 2 groups.

Tolerance: float32 on both sides, so the program and the reference differ by
rounding alone: the served log-probabilities read 1e-6 from the reference's.
The limit is 1e-5 and every control must read a hundred times the limit or
more (no window 0.39, a window of one key more 0.12 or less 0.15, cross
layers on their own keys 0.07, `m` behind the gate 0.006, `lam0` of another
layer 0.09, no `(1 - lam0)` 0.20, no `D x` 0.011).
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceConfig, InferenceEngine
from deepspeed_tpu.inference.kv_cache import (init_paged_cache,
                                              paged_cache_memory_bytes,
                                              ring_blocks,
                                              state_pool_memory_bytes)
from deepspeed_tpu.models import create_model
from deepspeed_tpu.models.presets import phi4flash_runs
from deepspeed_tpu.models.transformer import (forward, paged_layers,
                                              param_axes, recurrent_layers,
                                              ring_layers, tail_runs)
from deepspeed_tpu.serving import ServingConfig, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOL = 1e-5
SEED = 5
WINDOW = 8
REF_ARGS = dict(sliding_window=WINDOW, layer_norm_eps=1e-5,
                num_attention_heads=8, num_key_value_heads=4, mb_per_layer=2)
# each a wrong model that must FAIL: the reference's control arguments
CONTROLS = {
    "no-window": dict(window=False),
    "a-window-of-one-key-more": dict(window_shift=1),
    "a-window-of-one-key-less": dict(window_shift=-1),
    "cross-layers-on-their-own-keys": dict(cross_own_kv=True),
    "memory-behind-the-gate": dict(memory_after_gate=True),
    "lam0-of-another-layer": dict(lam0_shift=2),
    "no-one-minus-lam0": dict(one_minus_lam0=False),
    "no-skip-term": dict(skip=False),
}


def _reference():
    path = os.path.join(REPO, "benchmarks", "references", "phi4flash.py")
    spec = importlib.util.spec_from_file_location("reference_phi4flash", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


@pytest.fixture(scope="module")
def tiny():
    model = create_model("tiny-phi4flash")
    params = model.init(jax.random.PRNGKey(SEED))
    ids = np.random.default_rng(0).integers(0, 256, (2, 61)).astype(np.int32)
    return model, params, ids


def _serving(model, params, **kw):
    engine = InferenceEngine(model, InferenceConfig(dtype=jnp.float32,
                                                    seed=3), params=params)
    shape = dict(num_blocks=64, block_size=4, max_seqs=4, prefill_chunk=12,
                 max_model_len=128)
    shape.update(kw)
    return ServingEngine(engine, ServingConfig(**shape))


def _ref(fn, params, ids, **changed):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, i: getattr(REF, fn)(
            p, i, **dict(REF_ARGS, **changed)))(params, ids))


def test_the_stack_is_the_published_order():
    assert phi4flash_runs(32) == ((("mamba1", "swa"), 8),
                                  (("mamba1", "full"), 1),
                                  (("gmu", "cross"), 7))
    cfg = create_model("tiny-phi4flash", num_layers=32).config
    kinds = cfg.layer_pattern
    assert len(kinds) == 32
    for i, kind in enumerate(kinds):
        want = (("mamba1" if i <= 16 else "gmu") if i % 2 == 0 else
                "swa" if i < 16 else "full" if i == 17 else "cross")
        assert kind == want, (i, kind)
    assert paged_layers(cfg) == (17,)
    assert ring_layers(cfg) == tuple(range(1, 16, 2))
    assert recurrent_layers(cfg) == ("mamba1", tuple(range(0, 17, 2)))
    assert tail_runs(cfg) == 1
    with pytest.raises(ValueError, match="divisible by 4"):
        phi4flash_runs(10)


def test_the_published_sizes_count_the_published_parameters():
    """3.85 B by the shapes alone (nothing is allocated)."""
    model = create_model("phi-4-mini-flash-reasoning", dtype=jnp.bfloat16)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    by_kind = {k: count(v) // jax.tree.leaves(v)[0].shape[0]
               for k, v in shapes["layers"].items()}
    ffn = 3 * 2560 * 10240
    assert by_kind["gmu"] - ffn - 4 * 2560 == 2 * 2560 * 5120
    assert by_kind["cross"] - ffn - 4 * 2560 \
        == 2 * 2560 * 2560 + 2 * 2560 + 4 * 64 + 128 + 1
    assert by_kind["swa"] - by_kind["cross"] == 2 * (2560 * 1280 + 1280)
    assert by_kind["mamba1"] - ffn - 4 * 2560 == (
        2560 * 10240 + 5 * 5120 + 5120 * 192 + 160 * 5120 + 5120
        + 16 * 5120 + 5120 + 5120 * 2560)
    total = count(shapes)
    assert 3.84e9 < total < 3.86e9
    assert set(jax.tree.structure(param_axes(model.config)).node_data()[1]) \
        == set(jax.tree.structure(shapes).node_data()[1])


@pytest.mark.parametrize("chunk", [12, 4], ids=["chunk-wider-than-window",
                                                 "chunk-narrower"])
def test_served_scores_against_the_reference(tiny, chunk):
    """`score_logprobs` (the harness's `correct`): chunks, then the last
    tokens a step at a time, over a row several windows and chunks long."""
    model, params, ids = tiny
    served = _serving(model, params, prefill_chunk=chunk)
    seq = np.random.default_rng(1).integers(0, 256, 100).astype(np.int32)
    got = served.score_logprobs(seq)
    want = _ref("next_token_logprobs", params, seq[None])[0]
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_wrong_model_fails(tiny, control):
    model, params, ids = tiny
    served = _serving(model, params)
    seq = np.random.default_rng(1).integers(0, 256, 100).astype(np.int32)
    got = served.score_logprobs(seq)
    wrong = _ref("next_token_logprobs", params, seq[None],
                 **CONTROLS[control])[0]
    assert np.abs(got - wrong).max() > 100 * TOL


def _paged_logits(model, params, seq, chunks, width, slot, cache, table,
                  last_only=False):
    """`seq` through `forward` in paged mode as the serving programs call
    it: the prompt in the ragged `chunks` (each padded to `width`), then a
    token a step; the logits of every position (`last_only`: of each chunk's
    last real token, by the chunk program's half-depth form; `"prompt"`: a
    chunk that is not the prompt's last names no token, as the engine's)."""
    cfg = model.config
    slots = jnp.asarray([slot], jnp.int32)
    prompt = sum(chunks)

    @jax.jit
    def run(cache, tokens, pos, mask, start, n):
        run_of = {} if tokens.shape[1] == 1 else {"paged_run": (start, n)}
        if last_only and tokens.shape[1] > 1:
            run_of["last_token"] = jnp.where(
                (last_only != "prompt") | (start + n == prompt),
                jnp.maximum(n - 1, 0), -1)[None]
        logits, cache, _ = forward(params, tokens, cfg, cache=cache,
                                   positions=pos, block_table=table,
                                   paged_write_mask=mask, state_slots=slots,
                                   **run_of)
        return logits, cache

    out, start = [], 0
    for n in chunks:
        chunk = np.zeros((1, width), np.int32)
        chunk[0, :n] = seq[start:start + n]
        mask = (np.arange(width) < n)[None]
        pos = np.where(mask, start + np.arange(width)[None], -1)
        logits, cache = run(cache, jnp.asarray(chunk), jnp.asarray(pos),
                            jnp.asarray(mask), jnp.int32(start), jnp.int32(n))
        out.append(np.asarray(logits)[0, :1 if last_only else n])
        start += n
    for p in range(start, len(seq)):
        logits, cache = run(cache, jnp.asarray(seq[p:p + 1])[None],
                            jnp.asarray([[p]]), jnp.ones((1, 1), bool),
                            jnp.int32(p), jnp.int32(1))
        out.append(np.asarray(logits)[0])
    return np.concatenate(out), cache


@pytest.mark.parametrize("chunks,width", [((16, 16, 9), 16), ((14, 2, 1, 7), 16),
                                          ((4, 4, 4, 3), 4), ((3,), 16)])
def test_ragged_chunks_then_decode_against_the_full_pass(tiny, chunks,
                                                         width):
    """Prefill in ragged chunks, wider than the window of 8 and narrower,
    then decoding through the ring, the full pool and the state pools,
    LOGITS against the reference's full forward pass; a row runs several
    windows deep. Then the same slot again for another sequence, whose
    first chunk starts its state from zeros and writes its ring over."""
    model, params, ids = tiny
    cfg = model.config
    ring = ring_blocks(cfg, width, 4)
    assert ring == -(-(WINDOW + width) // 4)
    cache = init_paged_cache(cfg, 20, 4, jnp.float32, state_slots=3,
                             ring_blocks=ring)
    assert cache["k"].shape == (1, 20, 4, 4 * 8)    # pages: the full layer
    assert cache["wk"].shape == (2, 1 + 3 * ring, 4, 4 * 8)
    assert cache["state"].shape == (3, 3, 16, 128)
    assert cache["tail"].shape == (3, 3, 3, 128)
    table = jnp.asarray([list(range(1, 17)) + [0] * 4], jnp.int32)
    with jax.default_matmul_precision("highest"):
        for row in (0, 1):
            seq = ids[row]
            got, cache = _paged_logits(model, params, seq, chunks, width, 1,
                                       cache, table)
            want = _ref("logits", params, seq[None])[0]
            assert np.abs(got - want).max() < TOL
    # the slots and rings no sequence was given stayed as they were made
    assert not np.asarray(cache["state"])[:, [0, 2]].any()
    assert np.asarray(cache["state"])[:, 1].any()
    rings = np.asarray(cache["wk"])[:, 1:].reshape(2, 3, ring, 4, 32)
    assert not rings[:, [0, 2]].any() and rings[:, 1].any()
    assert not np.asarray(cache["wk"])[:, 0].any()      # scratch: masked-off
    #   writes only, and a chunk's are whole pages of what was there


def test_the_half_depth_chunk_gives_the_last_tokens_logits(tiny):
    """The chunk program runs the cross-decoder for a chunk's last real
    token alone: the same logits there as the whole depth at every
    position, and the same pages, rings and states behind it."""
    model, params, ids = tiny
    cfg = model.config
    chunks, seq = (16, 16, 9), ids[0]
    table = jnp.asarray([list(range(1, 17)) + [0] * 4], jnp.int32)
    fresh = lambda: init_paged_cache(cfg, 20, 4, jnp.float32, state_slots=2,
                                     ring_blocks=ring_blocks(cfg, 16, 4))
    with jax.default_matmul_precision("highest"):
        whole, cache_w = _paged_logits(model, params, seq[:41], chunks, 16, 0,
                                       fresh(), table)
        half, cache_h = _paged_logits(model, params, seq[:41], chunks, 16, 0,
                                      fresh(), table, last_only=True)
        ahead, cache_a = _paged_logits(model, params, seq[:41], chunks, 16, 0,
                                       fresh(), table, last_only="prompt")
    ends = np.cumsum(chunks) - 1
    assert half.shape == (3, 256)
    assert np.abs(half - whole[ends]).max() < TOL
    # a chunk that is not its prompt's last runs neither the cross-decoder
    # nor the head: zeros for logits nobody reads, the last chunk's as above
    assert not ahead[:2].any()
    np.testing.assert_array_equal(ahead[2], half[2])
    for name in cache_w:
        np.testing.assert_array_equal(np.asarray(cache_w[name]),
                                      np.asarray(cache_h[name]))
        np.testing.assert_array_equal(np.asarray(cache_w[name]),
                                      np.asarray(cache_a[name]))


def test_the_chunk_program_runs_the_cross_decoder_for_a_last_chunk_alone(
        tiny):
    """The engine tells the chunk program which chunk is its prompt's last
    (`pack_chunk`'s `last`): any other leaves the cache the same bytes and
    comes back with zeros for the logits nobody reads; the last one's token
    is the one the program gives when it is told nothing."""
    from deepspeed_tpu.serving import paged_kv

    model, params, ids = tiny
    served = _serving(model, params)
    assert served._chunk_says_last
    C = served.config.prefill_chunk
    table = np.zeros((1, served.blocks_per_seq), np.int32)
    table[0, :8] = np.arange(1, 9)
    chunk = np.asarray(ids[0][:C], np.int32)[None]
    one, zero = np.ones((1,), np.float32), np.zeros((1,), np.int32)
    told = jax.jit(paged_kv._chunk_step(model.config))

    def run(last):
        return told(params, served._arena, jnp.asarray(table),
                    jnp.asarray(chunk), jnp.int32(0), jnp.int32(C),
                    0 * one, zero, one, zero, zero, served._base_rng,
                    *([] if last is None else [jnp.asarray([last])]))

    (tok_n, logits_n, cache_n), (tok_1, logits_1, cache_1), \
        (tok_0, logits_0, cache_0) = run(None), run(1), run(0)
    assert int(tok_1[0]) == int(tok_n[0])
    np.testing.assert_array_equal(np.asarray(logits_1), np.asarray(logits_n))
    assert np.asarray(logits_n).any() and not np.asarray(logits_0).any()
    assert int(tok_0[0]) == 0
    for name in cache_n:
        np.testing.assert_array_equal(np.asarray(cache_n[name]),
                                      np.asarray(cache_0[name]))
        np.testing.assert_array_equal(np.asarray(cache_n[name]),
                                      np.asarray(cache_1[name]))
    # the engine's own program reads the flag out of its packed operands
    packed = paged_kv.pack_chunk(table, chunk, 0, C, 0 * one, zero, one,
                                 zero, state_slot=zero, last=[0])
    assert packed.shape == paged_kv.chunk_shape(served.blocks_per_seq, C,
                                                True, True)
    tok, logits, served._arena = served._prefill(params, served._arena,
                                                 packed, served._base_rng)
    assert int(tok[0]) == 0 and not np.asarray(logits).any()


def test_served_sequences_against_the_reference(tiny):
    """Through `init_serving`'s engine: more requests than rows, prompts of
    one to six ragged chunks, answers several windows long; every greedy
    token is the reference's best by its LOGITS."""
    model, params, _ = tiny
    served = _serving(model, params)
    assert served.prefix is None                      # off, not refused
    assert served.state_slots == 5
    rng = np.random.default_rng(0)
    sent = []
    for n in (45, 70, 10, 33, 64, 5):
        prompt = rng.integers(0, 256, n).astype(np.int32)
        sent.append((prompt, served.submit(
            prompt, max_new_tokens=int(rng.integers(5, 30)))))
    served.run()
    for prompt, handle in sent:
        full = np.concatenate([prompt, np.asarray(handle.result(), np.int32)])
        want = _ref("logits", params, full[None])[0]
        best = want[len(prompt) - 1:-1]
        chosen = best[np.arange(len(best)), full[len(prompt):]]
        assert (best.max(-1) - chosen).max() < TOL
    assert served.alloc.blocks_in_use == 0


def test_a_preempted_sequence_is_recomputed_to_the_same_tokens(tiny):
    """A pool far too small for the load: eviction and recompute. A
    re-admitted sequence's first chunk starts at 0: it starts its state slot
    from zeros and writes its ring from the first page on, so what comes
    out is what an engine with room gives, token for token."""
    model, params, _ = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 250, rng.integers(20, 60)).astype(np.int32)
               for _ in range(6)]
    small = _serving(model, params, num_blocks=36)
    handles = [small.submit(p, max_new_tokens=12) for p in prompts]
    small.run()
    assert small.sched.preemption_count > 0
    roomy = _serving(model, params)
    for p, h in zip(prompts, handles):
        want = roomy.submit(p, max_new_tokens=12)
        roomy.run()
        np.testing.assert_array_equal(h.result(), want.result())
    assert small.alloc.blocks_in_use == 0


def test_what_follows_a_sequences_state_is_refused_by_name(tiny):
    model, params, _ = tiny
    served = _serving(model, params)
    with pytest.raises(NotImplementedError, match="recurrent"):
        served.submit(np.arange(9, dtype=np.int32), max_new_tokens=2, n=2)
    handle = served.submit(np.arange(9, dtype=np.int32), max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="recurrent"):
        served.fork(handle, 2)


def test_a_window_layers_bytes_a_row_do_not_grow_with_the_row():
    cfg = create_model("phi-4-mini-flash-reasoning",
                       dtype=jnp.bfloat16).config
    # ONE layer of 32 keeps pages: 20 kv heads x 64, k and v, bfloat16
    assert paged_cache_memory_bytes(cfg, 10, 16, jnp.bfloat16) \
        == 2 * 1 * 10 * 16 * 1280 * 2
    ring = ring_blocks(cfg, 256, 16)
    assert ring == (512 + 256) // 16
    # 9 Mamba-1 layers keep a state (16 x 5,120 float32) and a tail (3 x
    # 5,120); 8 window layers a ring of 768 tokens a slot and one scratch
    # page, whatever max_model_len is
    assert state_pool_memory_bytes(cfg, 65, jnp.bfloat16, (ring, 16)) \
        == 9 * 65 * (16 * 5120 * 4 + 3 * 5120 * 2) \
        + 2 * 8 * (1 + 65 * ring) * 16 * 1280 * 2
    with pytest.raises(ValueError, match="ring"):
        init_paged_cache(create_model("tiny-phi4flash").config, 4, 4,
                         jnp.float32, state_slots=2)


def test_training_and_the_dense_cache_are_refused_by_name(tiny):
    model, params, _ = tiny
    engine = InferenceEngine(model, InferenceConfig(dtype=jnp.float32),
                             params=params)
    with pytest.raises(NotImplementedError, match="layer_runs"):
        engine.generate(np.arange(20, dtype=np.int32)[None],
                        max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="layer_runs"):
        model.loss_fn(params, {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    # a kind that reads what another layer made stands in a stack of runs
    with pytest.raises(AssertionError, match="layer_runs"):
        create_model("tiny-phi4flash", layer_runs=(),
                     layer_pattern=("gmu", "cross"))

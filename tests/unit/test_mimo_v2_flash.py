"""MiMo-V2-Flash (`mimo_v2_flash`: window layers with a learned sink a query
head and their own count of key-value heads beside full layers, keys wider
than values, rope on the first third of a head at a base a form, values
scaled, a leading dense layer, sigmoid-routed experts with a choice-only
bias; a window's ring addressed by a row's slot in a model with NO recurrent
layer) against its plain float32 reference. CPU, float32, seeded weights,
`tiny-mimo-v2-flash`: 8 layers in three runs, `(full_dense), (swa, swa,
full), (swa, swa, swa, full)`, so a full layer's pool is NOT its place among
its kind; 8 query heads over 2 key-value heads in a full layer and 4 in a
window layer, keys 24 and values 16 wide, rope on 8, a window of 8 keys;
16 experts, 3 a token.

Tolerance: float32 on both sides, so the program and the reference differ by
rounding alone: the served log-probabilities read 1e-6 from the reference's.
The limit is 1e-5 and every control reads a hundred times the limit or more.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceConfig, InferenceEngine
from deepspeed_tpu.inference.kv_cache import (cache_slots, init_paged_cache,
                                              paged_cache_memory_bytes,
                                              ring_blocks,
                                              state_pool_memory_bytes)
from deepspeed_tpu.models import create_model
from deepspeed_tpu.models.presets import mimo_runs, transformer_config
from deepspeed_tpu.models.transformer import (AttnForm, attn_shape,
                                              expert_layers, layer_places,
                                              page_widths, paged_layers,
                                              param_axes, recurrent_layers,
                                              ring_layers, tail_runs)
from deepspeed_tpu.parallel.moe import moe_mlp
from deepspeed_tpu.serving import ServingConfig, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOL = 1e-5
SEED = 5
PATTERN = (0, 1, 1, 0, 1, 1, 1, 0)
REF_ARGS = dict(hybrid_layer_pattern=PATTERN, moe_layer_freq=(0,) + (1,) * 7,
                num_attention_heads=8, num_key_value_heads=2,
                swa_num_key_value_heads=4, partial_rotary_factor=0.334,
                rope_theta=5e6, swa_rope_theta=1e4, sliding_window=8,
                attention_value_scale=0.707, num_experts_per_tok=3,
                layernorm_epsilon=1e-5, norm_topk_prob=True,
                add_swa_attention_sink_bias=True,
                add_full_attention_sink_bias=False)
CHUNK = 12      # the engine's chunk in these tests
# each a wrong model that must FAIL: the reference's arguments changed
CONTROLS = {
    "no-sink": dict(add_swa_attention_sink_bias=False),
    "a-sink-on-the-full-layers-too": dict(add_full_attention_sink_bias=True),
    "a-window-of-7-keys": dict(sliding_window=7),
    "a-window-of-9-keys": dict(sliding_window=9),
    "the-full-layers-heads-in-the-window-layers": dict(
        swa_num_key_value_heads=2),
    "the-window-layers-heads-in-the-full-layers": dict(
        num_key_value_heads=4),
    "rope-on-all-of-a-head": dict(rope_all=True),
    "the-window-layers-rope-base-in-the-full-layers": dict(rope_theta=1e4),
    "values-not-scaled": dict(attention_value_scale=1.0),
    "weights-not-renormalised": dict(norm_topk_prob=False),
    "bias-added-to-the-weights": dict(bias_in_weights=True),
}


def _reference():
    path = os.path.join(REPO, "benchmarks", "references", "mimo_v2_flash.py")
    spec = importlib.util.spec_from_file_location("reference_mimo", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


@pytest.fixture(scope="module")
def tiny():
    model = create_model("tiny-mimo-v2-flash")
    params = model.init(jax.random.PRNGKey(SEED))
    # the choice-only bias ten times its draw (std 0.1): under the
    # renormalisation a bias of std 0.01 moves the weights by 2e-4 alone
    for kind in ("swa", "full"):
        params["layers"][kind]["router_bias"] *= 10.0
    # and q and k four times theirs: matrices of std 0.02 give scores so
    # near to equal that a wrong rope base moves the result by 4e-4 alone
    for stack in params["layers"].values():
        stack["attn"]["wq"] *= 4.0
        stack["attn"]["wk"] *= 4.0
    ids = np.random.default_rng(0).integers(0, 256, (2, 101)).astype(np.int32)
    return model, params, ids


def _serving(model, params, **kw):
    engine = InferenceEngine(model, InferenceConfig(dtype=jnp.float32,
                                                    seed=3), params=params)
    shape = dict(num_blocks=96, block_size=4, max_seqs=3,
                 prefill_chunk=CHUNK, max_model_len=128)
    shape.update(kw)
    return ServingEngine(engine, ServingConfig(**shape))


def _ref(fn, params, ids, **changed):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, i: getattr(REF, fn)(
            p, i, **dict(REF_ARGS, **changed)))(params, ids))


PUBLISHED = transformer_config("mimo-v2-flash")


def test_the_stack_is_the_published_order():
    window = ("swa",) * 5 + ("full",)
    assert PUBLISHED.layer_runs == (
        (("full_dense",), 1), (("swa",) * 4 + ("full",), 1), (window, 7))
    assert paged_layers(PUBLISHED) == (0, 5, 11, 17, 23, 29, 35, 41, 47)
    assert len(ring_layers(PUBLISHED)) == 39
    assert recurrent_layers(PUBLISHED) == (None, ())
    assert expert_layers(PUBLISHED) == tuple(range(1, 48))
    assert tail_runs(PUBLISHED) == 0
    assert PUBLISHED.rotary_dim == 64
    # the benchmark's stack: layer 0 and ONE whole period
    stage = transformer_config("mimo-v2-flash", num_layers=7)
    assert stage.layer_runs == ((("full_dense",), 1), (window, 1))
    assert mimo_runs(PATTERN, (0,) + (1,) * 7, 8) == (
        (("full_dense",), 1), (("swa", "swa", "full"), 1),
        (("swa", "swa", "swa", "full"), 1))
    # a full layer's pool is its place among the layers of its MIXER
    places = layer_places(create_model("tiny-mimo-v2-flash").config)
    assert [(p["kind"], p["layer"], p["pool"]) for p in places
            if p["kind"].startswith("full")] == [
        ("full_dense", 0, 0), ("full", 0, 1), ("full", 1, 2)]


def test_a_forms_sizes_are_stated_once():
    full, window = (attn_shape(PUBLISHED, AttnForm(window=w))
                    for w in (False, True))
    assert (full.heads, full.kv_heads, full.key_dim, full.value_dim,
            full.rope_theta, full.sink) == (64, 4, 192, 128, 5e6, False)
    assert (window.heads, window.kv_heads, window.key_dim, window.value_dim,
            window.rope_theta, window.sink) == (64, 8, 192, 128, 1e4, True)
    assert page_widths(PUBLISHED) == (768, 512)
    assert page_widths(PUBLISHED, window=True) == (1536, 1024)
    # every other model: one count, one width, no sink, in every form
    other = transformer_config("phi-4-mini-flash-reasoning")
    assert attn_shape(other, AttnForm(window=True)) == attn_shape(other)
    assert page_widths(other) == page_widths(other, True) == (20 * 64,) * 2


def test_the_published_sizes_count_the_published_parameters():
    """A window layer's attention 94.37 M, a full one's 89.13 M; the arena
    at the cell's shape: pages for the 2 full layers, rings for the 5
    window layers, no state and no tail."""
    cfg = transformer_config("mimo-v2-flash", num_layers=7,
                             moe_experts_held=16, vocab_size=19072)
    shapes = jax.eval_shape(
        lambda: create_model("mimo-v2-flash", num_layers=7,
                             moe_experts_held=16,
                             vocab_size=19072).init(jax.random.PRNGKey(0)))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    assert count(shapes["layers"]["swa"]["attn"]) == 5 * 94_371_904
    assert count(shapes["layers"]["full"]["attn"]) == 89_128_960
    assert count(shapes) == 3_429_955_392
    assert set(param_axes(cfg)["layers"]["swa"]["attn"]) == {
        "wq", "wk", "wv", "wo", "sink"}
    assert "sink" not in param_axes(cfg)["layers"]["full"]["attn"]
    assert paged_cache_memory_bytes(cfg, 20481, 16, jnp.bfloat16) \
        == 20481 * 16 * 2 * (768 + 512) * 2
    ring = ring_blocks(cfg, 1024, 16)
    assert ring == 72
    assert state_pool_memory_bytes(cfg, 33, jnp.bfloat16, (ring, 16)) \
        == (1 + 33 * 72) * 16 * 5 * (1536 + 1024) * 2 + 33 * 4


def test_the_arena_has_rings_and_no_state(tiny):
    cache = init_paged_cache(tiny[0].config, 9, 4, jnp.float32,
                             state_slots=4, ring_blocks=5)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (3, 9, 4, 48), "v": (3, 9, 4, 32), "slots": (4,),
        "wk": (5, 21, 4, 96), "wv": (5, 21, 4, 64)}
    assert cache_slots(cache) == 4
    assert cache_slots({"k": cache["k"], "v": cache["v"]}) == 0
    with pytest.raises(ValueError, match="state_slots"):
        init_paged_cache(tiny[0].config, 9, 4, jnp.float32, ring_blocks=5)


@pytest.mark.parametrize("chunk", [CHUNK, 8, 32])
def test_served_scores_against_the_reference(tiny, chunk):
    """101 tokens through the chunk program and then, for the last 64, the
    one-token steps: past a dozen windows of 8 keys and several times round
    a ring (a window and a chunk of pages of 4)."""
    model, params, ids = tiny
    serving = _serving(model, params, prefill_chunk=chunk)
    assert "tail" not in serving._arena and serving.state_slots == 4
    want = _ref("next_token_logprobs", params, ids)
    for row in range(2):
        got = serving.score_logprobs(ids[row])
        assert np.abs(got - want[row]).max() < TOL


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_wrong_model_fails(tiny, control):
    model, params, ids = tiny
    got = _serving(model, params).score_logprobs(ids[0])
    wrong = _ref("next_token_logprobs", params, ids[:1],
                 **CONTROLS[control])[0]
    assert np.abs(got - wrong).max() > 100 * TOL, control


def test_rows_are_admitted_freed_and_admitted_again_without_a_slot_leaking(
        tiny):
    """Seven requests over three rows, prompts of one to five ragged chunks:
    a row's ring is written over by its next owner, every greedy token is
    the reference's best by a margin or a tie within the tolerance, and the
    allocator ends with every block and no row held."""
    model, params, _ = tiny
    serving = _serving(model, params)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 256, int(n)).astype(np.int32)
               for n in (5, 13, 30, 57, 24, 41, 9)]
    handles = [serving.submit(p, max_new_tokens=20) for p in prompts]
    rows = set()
    while not all(h.done for h in handles):
        serving.step()
        rows |= {r.row for r in serving.sched.running.values()}
    assert rows == {0, 1, 2}
    assert not serving.sched.running
    assert serving.alloc.blocks_free == serving.alloc.capacity
    for prompt, handle in zip(prompts, handles):
        tokens = np.asarray(handle.result(), np.int32)
        assert tokens.size == 20
        seq = np.concatenate([prompt, tokens])
        logits = _ref("logits", params, seq[None])[0]
        at = logits[prompt.size - 1:-1]
        assert (at.max(-1) - at[np.arange(20), tokens]).max() < 1e-4


def test_what_follows_a_rings_slot_is_refused_by_name(tiny):
    model, params, ids = tiny
    serving = _serving(model, params)
    assert serving.prefix is None       # off: a ring is in no page
    with pytest.raises(NotImplementedError, match="window layers"):
        serving.submit(ids[0, :9], max_new_tokens=2, n=2)
    with pytest.raises(NotImplementedError, match="window layers"):
        serving.fork(serving.submit(ids[0, :9], max_new_tokens=2), 2)


def test_a_window_layers_bytes_a_row_do_not_grow_with_the_row(tiny):
    model, params, ids = tiny
    serving = _serving(model, params)
    counts = []
    handle = serving.submit(ids[0, :40], max_new_tokens=30)
    while not handle.done:
        serving.step()
        if serving.sched.running:
            counts.append(serving._cache_counts())
    ring = serving._ring_blocks * 4 * 5 * (96 + 64) * 4
    assert max(c["ring_resident_bytes"] for c in counts) == ring
    assert counts[-1]["cache_resident_bytes"] > counts[0][
        "cache_resident_bytes"] >= counts[0]["ring_resident_bytes"]
    assert counts[-1]["window_layers"] == 5 and counts[-1]["full_layers"] == 3


def test_training_and_the_dense_cache_are_refused_by_name(tiny):
    model, params, ids = tiny
    with pytest.raises(NotImplementedError, match="paged cache"):
        model.loss_fn(params, {"input_ids": jnp.asarray(ids[:, :16])})
    engine = InferenceEngine(model, InferenceConfig(dtype=jnp.float32),
                             params=params)
    with pytest.raises(NotImplementedError):
        engine.generate(ids[:1, :8], max_new_tokens=2)


def test_the_shares_add_up(tiny):
    """One expert layer's FFN over all 16 shares of its 16 routed experts
    (one expert held a share, the router whole on each: counted once): the
    parts that the program's shares give add up to the uncut reference's
    layer, and each share is the reference's share."""
    model, params, _ = tiny
    cfg = model.config
    layer = jax.tree.map(lambda a: a[1], {
        k: params["layers"]["swa"][k]
        for k in ("router", "router_bias", "mlp")})
    h = jnp.asarray(20 * np.random.default_rng(4).normal(size=(1, 40, 64)),
                    jnp.float32)
    same = lambda a: a
    with jax.default_matmul_precision("highest"):
        whole, _ = REF._experts(layer, layer["mlp"], h, 3, True, same)
        parts = []
        for share in range(16):
            order = np.r_[share, np.setdiff1d(np.arange(16), [share])]
            held = dict(router=layer["router"][:, order],
                        router_bias=layer["router_bias"][order],
                        mlp=jax.tree.map(lambda a: a[share:share + 1],
                                         layer["mlp"]))
            out, _, counts = moe_mlp(
                h, held["router"], held["mlp"], cfg.activation,
                top_k=cfg.moe_top_k, norm_topk_prob=True, infer=True,
                with_counts=True, score_func="sigmoid",
                choice_bias=held["router_bias"], routed_scale=1.0)
            want, _ = REF._experts(held, held["mlp"], h, 3, True, same)
            assert np.abs(np.asarray(out - want)).max() < TOL
            assert 0 <= int(counts[0]) <= 40
            parts.append(out)
    assert np.abs(np.asarray(sum(parts) - whole)).max() < TOL
    assert np.abs(np.asarray(whole)).max() > 0.01

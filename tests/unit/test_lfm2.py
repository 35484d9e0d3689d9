"""LFM2 (`lfm2_moe`: gated short-convolution layers whose only state is a
tail of two rows, grouped-query layers with an RMSNorm over each head's
values of q and k, leading DENSE layers under the same mixers as the expert
layers, sigmoid-routed experts with a choice-only bias) against its plain
float32 reference. CPU, float32, seeded weights, `tiny-lfm2`: 7 layers in
four runs, `(conv_dense) x 2, (attn) x 1, (conv) x 2, (attn, conv) x 1`, so
a convolution layer's pool is NOT its place among its kind; 8 experts, 3 a
token; 4 query heads over 2 key-value heads of 16.

Tolerance: float32 on both sides, so the program and the reference differ by
rounding alone: the served log-probabilities read 5e-7 from the reference's.
The limit is 1e-5 and every control reads ten times the limit or more (the
choice-only bias added to the weights 1.7e-4: a bias of std 0.01 under a
renormalisation; no renormalisation 9e-3; the tail not carried over a chunk
boundary 1.2e-3; the B gate left out 0.04, the C gate 0.06; the norm over
the whole projection 0.01; experts in the leading layers 0.02).
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
                                              state_pool_memory_bytes)
from deepspeed_tpu.models import create_model
from deepspeed_tpu.models.presets import lfm2_runs, transformer_config
from deepspeed_tpu.models.transformer import (MIXERS, Step, expert_layers,
                                              forward, layer_places,
                                              paged_layers, param_axes,
                                              recurrent_layers, tail_runs)
from deepspeed_tpu.serving import ServingConfig, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOL = 1e-5
SEED = 5
TYPES = ("conv", "conv", "full_attention", "conv", "conv", "full_attention",
         "conv")
REF_ARGS = dict(layer_types=TYPES, num_dense_layers=2, num_attention_heads=4,
                num_key_value_heads=2, num_experts_per_tok=3,
                rope_theta=1e6, norm_eps=1e-5, norm_topk_prob=True,
                use_expert_bias=True, routed_scaling_factor=1)
CHUNK = 12      # the engine's chunk in these tests (`tail_cut` reads it)
# each a wrong model that must FAIL: the reference's control arguments
CONTROLS = {
    "bias-added-to-the-weights": dict(bias_in_weights=True),
    "no-renormalisation": dict(norm_topk_prob=False),
    "tail-not-carried-over-a-chunk-boundary": dict(tail_cut=CHUNK),
    "no-B-gate": dict(gate_b=False),
    "no-C-gate": dict(gate_c=False),
    "norm-over-the-whole-projection": dict(norm_per_head=False),
    "experts-in-the-leading-layers": dict(experts_in_leading_layers=True),
}


def _reference():
    path = os.path.join(REPO, "benchmarks", "references", "lfm2.py")
    spec = importlib.util.spec_from_file_location("reference_lfm2", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


@pytest.fixture(scope="module")
def tiny():
    model = create_model("tiny-lfm2")
    params = model.init(jax.random.PRNGKey(SEED))
    ids = np.random.default_rng(0).integers(0, 256, (2, 61)).astype(np.int32)
    return model, params, ids


def _serving(model, params, **kw):
    engine = InferenceEngine(model, InferenceConfig(dtype=jnp.float32,
                                                    seed=3), params=params)
    shape = dict(num_blocks=64, block_size=4, max_seqs=4,
                 prefill_chunk=CHUNK, max_model_len=128)
    shape.update(kw)
    return ServingEngine(engine, ServingConfig(**shape))


def _ref(fn, params, ids, args=REF_ARGS, **changed):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, i: getattr(REF, fn)(
            p, i, **dict(args, **changed)))(params, ids))


PUBLISHED = transformer_config("lfm2-8b-a1b")


def test_the_stack_is_the_published_order():
    assert PUBLISHED.layer_runs == (
        (("conv_dense",), 2), (("attn", "conv", "conv", "conv"), 4),
        (("attn", "conv", "conv"), 2))
    assert paged_layers(PUBLISHED) == (2, 6, 10, 14, 18, 21)
    assert recurrent_layers(PUBLISHED) == (
        "shortconv", tuple(i for i in range(24)
                           if i not in (2, 6, 10, 14, 18, 21)))
    assert expert_layers(PUBLISHED) == tuple(range(2, 24))
    assert tail_runs(PUBLISHED) == 0
    # the benchmark's stage: the first 12, three whole periods of c c A c
    stage = transformer_config("lfm2-8b-a1b", num_layers=12)
    assert stage.layer_runs == ((("conv_dense",), 2),
                                (("attn", "conv", "conv", "conv"), 2),
                                (("attn", "conv"), 1))
    assert stage.layer_pattern == PUBLISHED.layer_pattern[:12]
    assert len(paged_layers(stage)) == 3 and len(expert_layers(stage)) == 10
    # a depth that splits into more than one run, both mixers, dense layers
    cfg = create_model("tiny-lfm2").config
    assert cfg.layer_runs == ((("conv_dense",), 2), (("attn",), 1),
                              (("conv",), 2), (("attn", "conv"), 1))
    assert lfm2_runs(("conv",) * 3, 0) == ((("conv",), 3),)
    with pytest.raises(NotImplementedError, match="layer_types"):
        lfm2_runs(("conv", "sliding_attention"), 1)


def test_a_layers_pool_is_its_place_among_its_mixers_layers():
    """THE one answer (`layer_places`): weights and bank by kind, cache
    pools by mixer. The first expert layer's bank index is 0, the first
    attention layer's pool index 0, and a convolution layer under experts
    keeps its tail BEHIND the leading dense layers' tails."""
    places = layer_places(create_model("tiny-lfm2").config)
    assert [p["kind"] for p in places] == [
        "conv_dense", "conv_dense", "attn", "conv", "conv", "attn", "conv"]
    assert [p["layer"] for p in places] == [0, 1, 0, 0, 1, 1, 2]
    assert [p["pool"] for p in places] == [0, 1, 0, 2, 3, 1, 4]
    stage = layer_places(transformer_config("lfm2-8b-a1b", num_layers=12))
    first_expert = next(p for p in stage if p["kind"] in ("attn", "conv"))
    assert first_expert == {"kind": "attn", "layer": 0, "pool": 0}
    assert [p["pool"] for p in stage if p["kind"].startswith("conv")] \
        == list(range(9))
    # a model of one kind: the layer's own index is its pool's
    assert all(p["layer"] == p["pool"] == i for i, p in enumerate(
        layer_places(transformer_config("tiny-llama"))))


def test_the_tree_has_dense_leading_layers_and_no_state(tiny):
    model, params, _ = tiny
    cfg = model.config
    layers = params["layers"]
    assert set(layers) == {"conv_dense", "conv", "attn"}
    lead = layers["conv_dense"]
    assert lead["dense"]["w_gate"].shape == (2, 64, 128)
    assert not {"router", "router_bias", "mlp"} & set(lead)
    for kind, n in (("conv", 3), ("attn", 2)):
        assert layers[kind]["router"].shape == (n, 64, 8)
        assert layers[kind]["router_bias"].dtype == jnp.float32
        assert np.asarray(layers[kind]["router_bias"]).any()   # drawn
        assert layers[kind]["mlp"]["w_down"].shape == (n, 8, 32, 64)
        assert "dense" not in layers[kind]
    assert layers["attn"]["attn"]["q_norm"].shape == (2, 16)    # a head's
    assert layers["conv"]["shortconv"]["conv_w"].shape == (3, 3, 64)
    assert "lm_head" not in params                              # tied
    assert set(jax.tree.structure(param_axes(cfg)).node_data()[1]) \
        == set(jax.tree.structure(params).node_data()[1])
    cache = init_paged_cache(cfg, 20, 4, jnp.float32, state_slots=3)
    assert set(cache) == {"k", "v", "tail"}                 # no "state"
    assert cache["k"].shape == (2, 20, 4, 2 * 16)
    assert cache["tail"].shape == (5, 3, 2, 64)
    assert MIXERS["shortconv"].state(cfg) == (None, 3, 64)
    assert MIXERS["shortconv"].rows_count == "conv_rows"
    with pytest.raises(ValueError, match="state_slots"):
        init_paged_cache(cfg, 20, 4, jnp.float32)


def test_the_published_sizes_count_the_published_parameters():
    """8,339.9 M by the shapes alone (nothing is allocated), and the first
    stage's 3,928.7 M; the leading layers' FFN is the published
    intermediate_size wide and they have no router."""
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    whole = create_model("lfm2-8b-a1b", dtype=jnp.bfloat16)
    shapes = jax.eval_shape(whole.init, jax.random.PRNGKey(0))
    assert count(shapes) == 8_339_930_560
    by_kind = {k: count(v) // jax.tree.leaves(v)[0].shape[0]
               for k, v in shapes["layers"].items()}
    assert by_kind == {"conv_dense": 60_827_648, "conv": 369_174_560,
                       "attn": 362_877_088}
    assert shapes["layers"]["conv_dense"]["dense"]["w_up"].shape \
        == (2, 2048, 7168)
    assert "router" not in shapes["layers"]["conv_dense"]
    stage = create_model("lfm2-8b-a1b", dtype=jnp.bfloat16, num_layers=12)
    assert count(jax.eval_shape(stage.init, jax.random.PRNGKey(0))) \
        == 3_928_728_256
    # 3 attention layers keep pages (8 heads of 64, k and v); 9 convolution
    # layers a tail of 2 rows of 2,048 a slot and nothing else
    assert paged_cache_memory_bytes(stage.config, 10, 16, jnp.bfloat16) \
        == 2 * 3 * 10 * 16 * 512 * 2
    assert state_pool_memory_bytes(stage.config, 17, jnp.bfloat16) \
        == 9 * 17 * 2 * 2048 * 2


def test_the_mixer_with_no_cache_against_the_reference(tiny):
    """The first of its three modes (no cache: a sequence from a zero
    tail), on one layer's weights; the other two are the served tests'."""
    model, params, _ = tiny
    p = jax.tree.map(lambda a: a[1], params["layers"]["conv"]["shortconv"])
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 19, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got, cache = MIXERS["shortconv"].apply(model.config, h, p, Step())
        want = REF._short_conv(p, h)
    assert cache is None
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL


@pytest.mark.parametrize("chunk,block", [(12, 4), (8, 4), (2, 2), (1, 1)])
def test_served_scores_against_the_reference(tiny, chunk, block):
    """`score_logprobs` (the harness's `correct`): the whole forward in
    chunks that cut the row mid-way, of TWO tokens (the tail's length) and
    of ONE (shorter than the tail), then the last tokens a step at a time."""
    model, params, _ = tiny
    served = _serving(model, params, prefill_chunk=chunk, block_size=block,
                      num_blocks=256 // block)
    seq = np.random.default_rng(1).integers(0, 256, 70).astype(np.int32)
    got = served.score_logprobs(seq)
    want = _ref("next_token_logprobs", params, seq[None])[0]
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_wrong_model_fails(tiny, control):
    model, params, _ = tiny
    served = _serving(model, params)
    seq = np.random.default_rng(1).integers(0, 256, 70).astype(np.int32)
    got = served.score_logprobs(seq)
    wrong = _ref("next_token_logprobs", params, seq[None],
                 **CONTROLS[control])[0]
    assert np.abs(got - wrong).max() > 10 * TOL


def _paged_logits(model, params, seq, chunks, width, slot, cache, table):
    """`seq` through `forward` in paged mode as the serving programs call
    it: the prompt in the ragged `chunks` (each padded to `width`), then a
    token a step; the logits of every position."""
    cfg = model.config
    slots = jnp.asarray([slot], jnp.int32)

    @jax.jit
    def run(cache, tokens, pos, mask, start, n):
        run_of = {} if tokens.shape[1] == 1 else {"paged_run": (start, n)}
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
        out.append(np.asarray(logits)[0, :n])
        start += n
    for p in range(start, len(seq)):
        logits, cache = run(cache, jnp.asarray(seq[p:p + 1])[None],
                            jnp.asarray([[p]]), jnp.ones((1, 1), bool),
                            jnp.int32(p), jnp.int32(1))
        out.append(np.asarray(logits)[0])
    return np.concatenate(out), cache


@pytest.mark.parametrize("chunks,width", [
    ((16, 16, 9), 16), ((14, 2, 1, 7), 16), ((4, 4, 4, 3), 4),
    ((1, 1, 2, 1, 6), 8), ((3,), 16)])
def test_ragged_chunks_then_decode_against_the_full_pass(tiny, chunks,
                                                         width):
    """Prefill in ragged chunks (cut mid-way; of one token and of two,
    shorter than the tail and as long; a chunk of one right behind the
    row's start, where the tail is part zeros), then decoding through the
    pages and the tail slots, LOGITS against the reference's full forward
    pass. Then the SAME slot for another sequence, with what the first left
    there made worse: its first chunk starts from a zero tail."""
    model, params, ids = tiny
    cfg = model.config
    cache = init_paged_cache(cfg, 20, 4, jnp.float32, state_slots=3)
    table = jnp.asarray([list(range(1, 17)) + [0] * 4], jnp.int32)
    with jax.default_matmul_precision("highest"):
        for row in (0, 1):
            seq = ids[row]
            got, cache = _paged_logits(model, params, seq, chunks, width, 1,
                                       cache, table)
            want = _ref("logits", params, seq[None])[0]
            assert np.abs(got - want).max() < TOL
            # the slots no sequence was given stayed as they were made, and
            # every convolution layer wrote its own pool of the slot
            tails = np.asarray(cache["tail"])
            assert not tails[:, [0, 2]].any()
            assert all(tails[pool, 1].any() for pool in range(5))
            cache["tail"] = cache["tail"].at[:, 1].add(7.0)     # stale


def test_served_sequences_against_the_reference(tiny):
    """Through `init_serving`'s engine: more requests than rows, prompts of
    one to six ragged chunks, so that rows of different lengths decode in
    one step; every greedy token is the reference's best by its LOGITS, and
    a slot given to a later request starts from a zero tail."""
    model, params, _ = tiny
    served = _serving(model, params)
    assert served.prefix is None                      # off, not refused
    assert served.state_slots == 5
    assert served._recurrent_rows == "conv_rows"
    assert served._moe_experts_total == 8 * 5 and served._moe_choices == 15
    rng = np.random.default_rng(0)
    sent = []
    for n in (45, 70, 10, 33, 64, 5, 1, 2):
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
    re-admitted sequence's first chunk starts at 0 and so from a zero tail,
    and what comes out is what an engine with room gives, token for token."""
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
    """A tail is per-sequence state like any other: the prefix cache is off,
    and fork, n > 1 and speculation are refused by the ONE place that
    refuses them (`ServingEngine._no_state_snapshot`)."""
    model, params, _ = tiny
    served = _serving(model, params, prefix_cache=True)
    assert served.prefix is None
    with pytest.raises(NotImplementedError, match="recurrent.*snapshot"):
        served.submit(np.arange(9, dtype=np.int32), max_new_tokens=2, n=2)
    handle = served.submit(np.arange(9, dtype=np.int32), max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="recurrent.*snapshot"):
        served.fork(handle, 2)
    with pytest.raises(NotImplementedError, match="speculative.*snapshot"):
        _serving(model, params,
                 speculative={"mode": "ngram", "num_draft_tokens": 2})


def test_training_and_the_dense_cache_are_refused_by_name(tiny):
    model, params, _ = tiny
    engine = InferenceEngine(model, InferenceConfig(dtype=jnp.float32),
                             params=params)
    with pytest.raises(NotImplementedError, match="layer_runs"):
        engine.generate(np.arange(20, dtype=np.int32)[None],
                        max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="layer_runs"):
        model.loss_fn(params, {"input_ids": jnp.zeros((1, 8), jnp.int32)})
    with pytest.raises(AssertionError, match="dense_ffn_hidden_size"):
        create_model("tiny-lfm2", dense_ffn_hidden_size=None)


@pytest.mark.parametrize("how,stack,types,dense", [
    # a leading dense layer under ATTENTION that expert layers have too
    ("runs", {}, ("full_attention",) * 3, 1),
    # kinds that share a mixer in ONE period of a layer_pattern: the same
    # indices through `forward`'s own scan, two periods deep
    ("pattern", dict(layer_runs=(), layer_pattern=(
        "conv_dense", "attn", "conv")), ("conv", "full_attention", "conv"), 1),
])
def test_other_stacks_of_the_same_kinds(how, stack, types, dense):
    """What the next family with a leading dense layer reuses: the kinds
    `attn_dense` and `conv_dense`, pools by mixer, banks by kind."""
    layers = len(types) * (2 if how == "pattern" else 1)
    model = create_model("tiny-lfm2", num_layers=layers, layer_types=types,
                         num_dense_layers=dense, **stack)
    params = model.init(jax.random.PRNGKey(SEED))
    cfg = model.config
    if how == "runs":
        assert cfg.layer_runs == ((("attn_dense",), 1), (("attn",), 2))
        assert [p["pool"] for p in layer_places(cfg)] == [0, 1, 2]
        args = dict(REF_ARGS, layer_types=types, num_dense_layers=dense)
        served = _serving(model, params)
        seq = np.random.default_rng(1).integers(0, 256, 50).astype(np.int32)
        got = served.score_logprobs(seq)
        want = _ref("next_token_logprobs", params, seq[None], args)[0]
        assert np.abs(got - want).max() < TOL
        return
    # the pattern is a period; the reference needs the order it spells,
    # where EVERY period starts with a dense layer: no family's, so the
    # program is held to ITSELF, a stack of runs of the same layers
    assert [p["pool"] for p in layer_places(cfg)] == [0, 0, 1, 2, 1, 3]
    runs = create_model(
        "tiny-lfm2", num_layers=layers, layer_types=types,
        num_dense_layers=dense,
        layer_runs=((("conv_dense", "attn", "conv"), 2),))
    seq = np.random.default_rng(1).integers(0, 256, 50).astype(np.int32)
    got = _serving(model, params).score_logprobs(seq)
    want = _serving(runs, params).score_logprobs(seq)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=TOL)

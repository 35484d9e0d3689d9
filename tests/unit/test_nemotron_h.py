"""Nemotron 3 Super (`nemotron_h`: layers that are ONE function each, a
Mamba-2 state-space mixer, a GQA attention mixer with no positional term or
an FFN of sigmoid-routed relu-squared experts in a latent beside a shared
expert; this chip may hold a share of the experts) against its plain float32
reference. CPU, float32, seeded weights, `tiny-nemotron-3-super` (the first
11 layers of the source's pattern, `MEMEMEM*EME`), whole and as a share (64
experts of which 8 are held, the rehearsal size of the benchmark's
configuration).

Tolerance: float32 on both sides, so the program and the reference differ by
rounding alone (the chunked recurrence against the token-by-token one, the
expert mix summed over 3 chosen experts here and over every held one there):
the full forward's logits read 4e-7 from the reference's. The limit is 2e-5,
fifty times that, and every control must read ten times the limit or more:
the state kept in bfloat16 (3e-4 to 4e-4 over ten seeds where no expert
swaps, 1e-2 where one does), no `routed_scaling_factor` (0.05 to 0.24),
weights renormalised over the held experts only (2.6e-2), the whole model in
float8 (0.23), no convolution (0.89), no `D x` (1.0).

The seed's matrices are used FOUR TIMES as large (`_louder`): at the init's
std of 0.02 and a width of 64 a matmul shrinks its input to a sixth, the
routed part lies four matmuls deep, and with or without its factor of 5 it
would move the logits by 3e-5, under what any limit here could tell.

Near-ties: the router's sigmoid scores of a token's k-th and (k+1)-th expert
can lie closer than float32 rounding of its input, and the program and the
reference may then choose different experts. The seed is chosen once so that
no (token, layer) of the test sequences comes closer than `MARGIN`
(`test_router_margin`: 5e-5 at this seed, whole and as a share).
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
from deepspeed_tpu.models.presets import _SIZES, nemotron_h_pattern
from deepspeed_tpu.models.transformer import (ffn_layers, forward,
                                              layer_stacks, layers_with_mixer,
                                              param_axes, recurrent_layers)
from deepspeed_tpu.parallel.moe import moe_mlp, route_topk
from deepspeed_tpu.serving import ServingConfig, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOL = 2e-5
MARGIN = 1e-5       # least gap between the k-th and the (k+1)-th score
SEED = 9
TOP_K = 3
PUBLISHED = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
             "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
REF_ARGS = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                mamba_num_heads=8, mamba_head_dim=16, n_groups=2,
                ssm_state_size=16, conv_kernel=4, num_experts_per_tok=TOP_K,
                norm_topk_prob=True, routed_scaling_factor=5,
                layer_norm_epsilon=1e-5, hybrid_override_pattern=PUBLISHED)
SHARE = dict(moe_num_experts=64, moe_experts_held=8)
# the wrong and the cheaper models that the tolerance has to tell from the
# right one (scripts/check_nemotron_h_on_chip.py reads them on the chip)
CONTROLS = {"all-in-float8": dict(mantissa_bits=3),
            "state-in-bfloat16": dict(state_dtype=jnp.bfloat16),
            "no-skip": dict(skip=False),
            "no-convolution": dict(conv=False),
            "no-routed-scale": dict(routed_scaling_factor=1),
            "renormalised-over-held": dict(renorm_over_held=True)}


def _reference():
    path = os.path.join(REPO, "benchmarks", "references", "nemotron_h.py")
    spec = importlib.util.spec_from_file_location("reference_nemotron_h",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


def _louder(params):
    """The layers' matrices times four (the convolution's taps, of std 0.5,
    as they are)."""
    return dict(params, layers=jax.tree_util.tree_map_with_path(
        lambda path, a: a * 4.0 if a.ndim >= 3
        and "conv_w" not in jax.tree_util.keystr(path) else a,
        params["layers"]))


@pytest.fixture(scope="module", params=["whole", "share"])
def tiny(request):
    model = create_model("tiny-nemotron-3-super",
                         **(SHARE if request.param == "share" else {}))
    params = _louder(model.init(jax.random.PRNGKey(SEED)))
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 53)))
    return model, params, ids


def _serving(model, params, **kw):
    engine = InferenceEngine(model, InferenceConfig(dtype=jnp.float32,
                                                    seed=3), params=params)
    shape = dict(num_blocks=40, block_size=16, max_seqs=4, prefill_chunk=32,
                 max_model_len=128)
    shape.update(kw)
    return ServingEngine(engine, ServingConfig(**shape))


def _ref(fn, params, ids, **changed):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, i: getattr(REF, fn)(
            p, i, **dict(REF_ARGS, **changed)))(params, ids))


def test_the_stack_is_the_published_patterns_prefix(tiny):
    """`num_layers` counts the SOURCE's layers; the preset carries the whole
    published pattern and a smaller depth runs its first characters. One
    stacked tree a kind, in layer order, each with ONE norm."""
    model, params, _ = tiny
    cfg = model.config
    full = _SIZES["nemotron-3-super-120b-a12b"]
    assert full["layer_pattern"] == nemotron_h_pattern(PUBLISHED)
    assert (full["layer_pattern"].count("mamba2_mixer"),
            full["layer_pattern"].count("ffn"),
            full["layer_pattern"].count("attn_mixer")) == (40, 40, 8)
    assert cfg.num_layers == 11
    assert cfg.layer_pattern == nemotron_h_pattern("MEMEMEM*EME")
    cut = create_model("nemotron-3-super-120b-a12b", num_layers=11).config
    assert cut.layer_pattern == cfg.layer_pattern
    assert recurrent_layers(cfg) == ("mamba2", (0, 2, 4, 6, 9))
    assert layers_with_mixer(cfg, "attn") == (7,)
    assert ffn_layers(cfg) == (1, 3, 5, 8, 10)
    stacks = layer_stacks(params["layers"], cfg)
    assert sorted(stacks) == ["attn_mixer", "ffn", "mamba2_mixer"]
    assert sorted(stacks["mamba2_mixer"]) == ["ln1", "mamba2"]
    assert sorted(stacks["attn_mixer"]) == ["attn", "ln1"]
    assert sorted(stacks["ffn"]) == ["latent", "ln2", "mlp", "router",
                                     "router_bias", "shared"]
    held = cfg.experts_held
    assert stacks["ffn"]["mlp"]["w_up"].shape == (5, held, 32, 48)
    assert stacks["ffn"]["mlp"]["w_down"].shape == (5, held, 48, 32)
    assert stacks["ffn"]["shared"]["w_up"].shape == (5, 64, 96)
    assert stacks["ffn"]["router"].shape == (5, 64, cfg.moe_num_experts)
    assert stacks["mamba2_mixer"]["mamba2"]["w_in"].shape \
        == (5, 64, 128 + (128 + 2 * 2 * 16) + 8)
    axes = param_axes(cfg)
    leaf = lambda x: isinstance(x, tuple)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, axes, is_leaf=leaf))
    with pytest.raises(NotImplementedError, match="FFN kinds that differ"):
        nemotron_h_pattern("ME-")


def test_the_published_sizes_count_the_published_parameters():
    """120.67 B in all by the layers' equations: 109.64 M a Mamba-2 layer,
    35.66 M an attention layer, 54.53 M an expert layer beside its 512
    experts of 5.505 M."""
    model = create_model("nemotron-3-super-120b-a12b")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    size = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    stacks = shapes["layers"]
    assert round(size(stacks["mamba2_mixer"]) / 40 / 1e6, 2) == 109.64
    assert round(size(stacks["attn_mixer"]) / 8 / 1e6, 2) == 35.66
    experts = size(stacks["ffn"]["mlp"])
    assert round((size(stacks["ffn"]) - experts) / 40 / 1e6, 2) == 54.53
    assert round(experts / 40 / 512 / 1e6, 3) == 5.505
    assert round(size(shapes) / 1e9, 2) == 120.67


def test_router_margin(tiny):
    """No (token, layer) of the test sequence is a near-tie at the k-th
    score, so the tolerance below is of rounding and not of routing."""
    model, params, ids = tiny
    with jax.default_matmul_precision("highest"):
        chosen = REF.router_choices(params, ids, **REF_ARGS)
    assert chosen.shape == (5, 2, 53, TOP_K)
    gaps = []
    route = route_topk

    def recording(gates, choice, k, normalize):
        top = jax.lax.top_k(choice, k + 1)[0]
        jax.debug.callback(lambda g: gaps.append(float(g)),
                           (top[:, k - 1] - top[:, k]).min())
        return route(gates, choice, k, normalize)

    from deepspeed_tpu.parallel import moe

    moe.route_topk = recording
    try:
        forward(params, ids, model.config)
        jax.effects_barrier()
    finally:
        moe.route_topk = route
    assert len(gaps) == 5 and min(gaps) > MARGIN, gaps


def test_full_forward_against_the_reference(tiny):
    model, params, ids = tiny
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(
            lambda p, i: forward(p, i, model.config)[0])(params, ids))
    want = _ref("logits", params, ids)
    assert np.abs(got - want).max() < TOL
    for name, changed in CONTROLS.items():
        if name == "renormalised-over-held" \
                and not model.config.moe_experts_held:
            continue        # all experts held: the same model
        off = np.abs(got - _ref("logits", params, ids, **changed)).max()
        assert off > 10 * TOL, (name, off)


def _paged_logits(model, params, seq, chunks, slot, cache, table):
    """`seq` through `forward` in paged mode as the serving programs call
    it: the prompt in the ragged `chunks` (each padded to 32), then a token
    a step; the logits of every position."""
    cfg = model.config
    slots = jnp.asarray([slot], jnp.int32)

    @jax.jit
    def run(cache, tokens, pos, mask):
        logits, cache, _ = forward(params, tokens, cfg, cache=cache,
                                   positions=pos, block_table=table,
                                   paged_write_mask=mask, state_slots=slots)
        return logits, cache

    out, start = [], 0
    for n in chunks:
        chunk = np.zeros((1, 32), np.int32)
        chunk[0, :n] = seq[start:start + n]
        mask = (np.arange(32) < n)[None]
        pos = np.where(mask, start + np.arange(32)[None], -1)
        logits, cache = run(cache, jnp.asarray(chunk), jnp.asarray(pos),
                            jnp.asarray(mask))
        out.append(np.asarray(logits)[0, :n])
        start += n
    for p in range(start, len(seq)):
        logits, cache = run(cache, jnp.asarray(seq[p:p + 1])[None],
                            jnp.asarray([[p]]), jnp.ones((1, 1), bool))
        out.append(np.asarray(logits)[0])
    return np.concatenate(out), cache


@pytest.mark.parametrize("chunks", [(32, 9), (30, 2, 1, 7), (3,)])
def test_ragged_chunks_then_decode_against_the_full_pass(tiny, chunks):
    """Prefill in ragged chunks (each padded to 32), then decoding through
    the pages and the state pools, LOGITS against the reference's full
    forward pass. A chunk boundary lies inside the convolution's 4 taps (a
    chunk of 2, then one of 1: the new tail takes rows of the old one) and
    across them; the steps start 3 tokens in (a tail still partly zeros).
    Then the same slot again for another sequence, whose first chunk starts
    it from zeros whatever the slot held."""
    model, params, ids = tiny
    cfg = model.config
    cache = init_paged_cache(cfg, 12, 16, jnp.float32, state_slots=3)
    assert cache["k"].shape[0] == 1             # pages: the attention layer
    assert cache["state"].shape == (5, 3, 2, 16, 4 * 16)
    assert cache["tail"].shape == (5, 3, 3, 128 + 2 * 2 * 16)
    table = jnp.asarray([[1, 2, 3, 4, 0, 0, 0, 0]], jnp.int32)
    with jax.default_matmul_precision("highest"):
        for row in (0, 1):
            seq = np.asarray(ids[row])
            got, cache = _paged_logits(model, params, seq, chunks, 1, cache,
                                       table)
            want = _ref("logits", params, seq[None])[0]
            assert np.abs(got - want).max() < TOL
    # the slots no sequence was given stayed as they were made
    assert not np.asarray(cache["state"])[:, [0, 2]].any()
    assert np.asarray(cache["state"])[:, 1].any()


def test_served_sequences_against_the_reference(tiny):
    """Through `init_serving`'s engine: more requests than rows, prompts of
    one to three ragged chunks; every greedy token is the reference's best
    by its LOGITS and the served log-probabilities (`score_logprobs`, the
    harness's `correct`) are the reference's."""
    model, params, _ = tiny
    served = _serving(model, params)
    assert served.prefix is None                      # off, not refused
    assert served.state_slots == 5
    rng = np.random.default_rng(0)
    sent = []
    for n in (45, 70, 10, 33, 64, 5):
        prompt = rng.integers(0, 256, n).astype(np.int32)
        sent.append((prompt, served.submit(
            prompt, max_new_tokens=int(rng.integers(5, 20)))))
    served.run()
    for prompt, handle in sent:
        full = np.concatenate([prompt, np.asarray(handle.result(), np.int32)])
        want = _ref("logits", params, full[None])[0]
        best = want[len(prompt) - 1:-1]
        chosen = best[np.arange(len(best)), full[len(prompt):]]
        assert (best.max(-1) - chosen).max() < TOL
        lp = served.score_logprobs(full)
        want_lp = np.take_along_axis(
            np.asarray(jax.nn.log_softmax(want[:-1])), full[1:, None],
            axis=-1)[:, 0]
        assert np.abs(lp - want_lp).max() < TOL
    assert served.alloc.blocks_in_use == 0


def test_a_preempted_sequence_is_recomputed_to_the_same_logits(tiny):
    """A pool far too small for the load: eviction and recompute. A
    re-admitted sequence's first chunk starts at 0 and so starts its slot
    from zeros; what comes out is what an engine with room gives, token for
    token, and the reference's logits choose it."""
    model, params, _ = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 250, rng.integers(20, 60)).astype(np.int32)
               for _ in range(6)]
    small = _serving(model, params, num_blocks=10)
    handles = [small.submit(p, max_new_tokens=10) for p in prompts]
    small.run()
    assert small.sched.preemption_count > 0
    roomy = _serving(model, params)
    for p, h in zip(prompts, handles):
        want = roomy.submit(p, max_new_tokens=10)
        roomy.run()
        np.testing.assert_array_equal(h.result(), want.result())
        full = np.concatenate([p, np.asarray(h.result(), np.int32)])
        got = _ref("logits", params, full[None])[0][len(p) - 1:-1]
        chosen = got[np.arange(10), full[len(p):]]
        assert (got.max(-1) - chosen).max() < TOL
    assert small.alloc.blocks_in_use == 0


def _expert_layer(experts=64, **overrides):
    model = create_model("tiny-nemotron-3-super", moe_num_experts=experts,
                         **overrides)
    params = _louder(model.init(jax.random.PRNGKey(SEED)))
    layer = jax.tree.map(lambda a: a[1], params["layers"]["ffn"])
    x = jnp.asarray(np.random.default_rng(5).standard_normal((2, 19, 64)),
                    jnp.float32)
    return model.config, layer, x


def _moe(layer, x, router=None, bias=None, stack=None, scale=5.0):
    return moe_mlp(x, layer["router"] if router is None else router,
                   layer["mlp"] if stack is None else stack, "relu2",
                   top_k=TOP_K, norm_topk_prob=True, infer=True,
                   score_func="sigmoid",
                   choice_bias=layer["router_bias"] if bias is None else bias,
                   with_counts=True, latent=layer["latent"],
                   routed_scale=scale)


def test_the_shares_add_up_to_the_uncut_layer():
    """The share test of the `model-configs` guide: 64 experts over 8 chips.
    Chip c holds experts 8c..8c+7 (told so by its stack of 8 beside a router
    of 64 whose first 8 outputs are its own); each computes its experts'
    part of the routed sum in the latent and projects it out. The 8 parts,
    with the shared expert counted ONCE (and the projections into and out of
    the latent being linear, each counted once too), add up to what the
    uncut reference gives for the whole layer."""
    _, layer, x = _expert_layer()
    with jax.default_matmul_precision("highest"):
        whole, chosen = REF._experts(layer, layer["mlp"], x, TOP_K, True, 5.0,
                                     False, lambda a: a)
        parts, reached = [], 0
        for chip in range(8):
            mine = np.roll(np.arange(64), -8 * chip)      # its 8 come first
            part, _, counts = _moe(
                layer, x, layer["router"][:, mine],
                layer["router_bias"][mine],
                jax.tree.map(lambda w: w[8 * chip:8 * chip + 8],
                             layer["mlp"]))
            parts.append(part)
            reached += int(counts[0])
        shared = REF._experts(layer, jax.tree.map(lambda w: w[:0],
                                                  layer["mlp"]),
                              x, TOP_K, True, 5.0, False, lambda a: a)[0]
    # every assignment reached exactly one chip's held experts
    assert reached == 2 * 19 * TOP_K
    routed = np.asarray(whole - shared)
    assert np.abs(np.asarray(sum(parts)) - routed).max() \
        < 1e-4 * np.abs(routed).max()
    # and a share alone is not the layer: the parts are real
    assert np.abs(np.asarray(parts[0]) - routed).max() \
        > 0.1 * np.abs(routed).max()
    assert chosen.shape == (2, 19, TOP_K)


def test_a_choice_only_bias_moves_the_choice_and_not_the_weights():
    """A bias large enough to choose expert 0 for every token: the program's
    routed part is the reference's under the same bias, and expert 0's
    weight is still its own score over the chosen scores' sum."""
    _, layer, x = _expert_layer()
    bias = layer["router_bias"].at[0].set(10.0)
    with jax.default_matmul_precision("highest"):
        plain, _, _ = _moe(layer, x)
        got, _, _ = _moe(layer, x, bias=bias)
        want, chosen = REF._experts(dict(layer, router_bias=bias),
                                    layer["mlp"], x, TOP_K, True, 5.0, False,
                                    lambda a: a)
        shared = REF._experts(layer, jax.tree.map(lambda w: w[:0],
                                                  layer["mlp"]),
                              x, TOP_K, True, 5.0, False, lambda a: a)[0]
    assert (np.asarray(chosen) == 0).any(-1).all()
    routed = np.asarray(want - shared)
    assert np.abs(np.asarray(got) - routed).max() \
        < 1e-4 * np.abs(routed).max()
    assert np.abs(np.asarray(got - plain)).max() > 0.01 * np.abs(routed).max()
    scores = jax.nn.sigmoid(x.reshape(-1, 64) @ layer["router"])
    idx, w = route_topk(scores, scores + bias, TOP_K, True)
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)


def test_the_routed_scale_scales_the_routed_part_alone():
    """`routed_scaling_factor` 5 against 1: the routed part fivefold, and in
    the layer's whole output the shared expert's part not at all."""
    cfg5, layer, x = _expert_layer()
    assert cfg5.moe_routed_scale == 5.0
    with jax.default_matmul_precision("highest"):
        five, _, _ = _moe(layer, x, scale=5.0)
        one, _, _ = _moe(layer, x, scale=1.0)
    np.testing.assert_allclose(five, 5.0 * np.asarray(one), rtol=1e-5,
                               atol=1e-7)
    # through the model: the layer's output moves by four routed parts
    x_in = jnp.asarray(np.random.default_rng(1).integers(0, 256, (1, 9)))
    outs = {}
    for scale in (5.0, 1.0):
        model = create_model("tiny-nemotron-3-super", num_layers=2,
                             moe_routed_scale=scale)
        params = _louder(model.init(jax.random.PRNGKey(SEED)))
        with jax.default_matmul_precision("highest"):
            outs[scale] = forward(params, x_in, model.config)[0]
    assert np.abs(np.asarray(outs[5.0] - outs[1.0])).max() > 1e-3
    # the capacity plans know neither the latent nor the scale, and say so
    with pytest.raises(NotImplementedError, match="dropless"):
        moe_mlp(x, layer["router"], layer["mlp"], "relu2", top_k=TOP_K,
                infer=False, latent=layer["latent"])


def test_pages_are_for_the_attention_layers_alone():
    cfg = create_model("tiny-nemotron-3-super", **SHARE).config
    # one layer of eleven keeps pages: 2 kv heads x 32, k and v, float32
    assert paged_cache_memory_bytes(cfg, 10, 16, jnp.float32) \
        == 2 * 1 * 10 * 16 * 64 * 4
    # five keep a state (8 heads x 16 x 16 float32) and a tail (3 x 192)
    assert state_pool_memory_bytes(cfg, 5, jnp.float32) \
        == 5 * 5 * (8 * 16 * 16 * 4 + 3 * 192 * 4)
    # the tail is the model's dtype, the state float32 whatever it is
    cache = init_paged_cache(cfg, 4, 16, jnp.bfloat16, state_slots=2)
    assert cache["state"].dtype == jnp.float32
    assert cache["tail"].dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="state_slots"):
        init_paged_cache(cfg, 4, 16, jnp.float32)


def test_the_dense_cache_and_training_plans_are_refused_by_name(tiny):
    """What `ServingEngine` refuses over a recurrent state is
    `test_solar_open2.py`'s, a case a kind; here what the model itself
    refuses."""
    model, params, _ = tiny
    engine = InferenceEngine(model, InferenceConfig(dtype=jnp.float32),
                             params=params)
    with pytest.raises(NotImplementedError, match="dense"):
        engine.generate(np.arange(20, dtype=np.int32)[None],
                        max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="post-norm"):
        bad = create_model("tiny-nemotron-3-super", parallel_residual=True)
        forward(bad.init(jax.random.PRNGKey(0)), jnp.zeros((1, 4), jnp.int32),
                bad.config)

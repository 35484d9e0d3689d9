"""Solar Open 2 (layers of two kinds: a gated NoPE GQA layer, then three
gated delta-rule layers; sigmoid-routed experts of which this chip may hold
a share, and a shared expert) against its plain float32 reference. CPU,
float32, seeded weights, `tiny-solar-open2`, whole and as a share (64 experts
of which 8 are held, the rehearsal size of the benchmark's configuration).

Tolerance: float32 on both sides, so the program and the reference differ by
rounding alone (the chunked recurrence against the token-by-token one, the
expert mix summed over 3 chosen experts here and over every held one
there): the full forward's logits read 6e-7 from the reference's, the
served log-probabilities 1e-6. The limit is 1e-4: a hundred times the
reading, and a tenth of what the nearest control reads (the state kept in
bfloat16 1e-3 to 9e-3; weights renormalised over the held experts 6e-3 to
2e-2; no shared expert 3e-2 to 9e-2; beta without its 2, no decay, no
convolution 0.18 to 1.0).

Near-ties: the router's sigmoid scores of a token's k-th and (k+1)-th expert
can lie closer than float32 rounding of its input, and the program and the
reference may then choose different experts. The seed is chosen once so that
no (token, layer) of the test sequences comes closer than `MARGIN`
(`test_router_margin`).
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
from deepspeed_tpu.models.transformer import (forward, layer_stacks,
                                              layers_of_kind, param_axes)
from deepspeed_tpu.observability import recorded_spans, reset_session
from deepspeed_tpu.parallel.moe import moe_mlp, route_topk
from deepspeed_tpu.serving import ServingConfig, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOL = 1e-4
MARGIN = 1e-5       # least gap between the k-th and the (k+1)-th score: a
#                     hundred roundings of a float32 score near 1
SEED = 1
TOP_K = 3
REF_ARGS = dict(num_heads=4, head_dim=32, num_experts_per_tok=TOP_K,
                rms_norm_eps=1e-5, norm_topk_prob=True,
                routed_scaling_factor=1, gqa_interval=3, use_gqa_gate=True,
                kda_allow_neg_eigval=True, use_rope=False)
SHARE = dict(moe_num_experts=64, moe_experts_held=8)
# the wrong and the cheaper models that the tolerance has to tell from the
# right one (scripts/check_solar_open2_on_chip.py reads the same six on the chip)
CONTROLS = {"all-in-float8": dict(mantissa_bits=3),
            "state-in-bfloat16": dict(state_dtype=jnp.bfloat16),
            "beta-without-its-2": dict(beta_scale=1.0),
            "no-decay": dict(decay=False),
            "no-convolution": dict(conv=False),
            "no-shared-expert": dict(shared=False),
            "renormalised-over-held": dict(renorm_over_held=True)}


def _reference():
    path = os.path.join(REPO, "benchmarks", "references", "solar_open2.py")
    spec = importlib.util.spec_from_file_location("reference_solar", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


@pytest.fixture(scope="module", params=["whole", "share"])
def tiny(request):
    model = create_model("tiny-solar-open2",
                         **(SHARE if request.param == "share" else {}))
    params = model.init(jax.random.PRNGKey(SEED))
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


def test_the_stack_has_a_tree_a_kind_in_the_published_order(tiny):
    model, params, _ = tiny
    cfg = model.config
    assert cfg.layer_pattern == ("attn", "kda", "kda", "kda")
    assert layers_of_kind(cfg, "attn") == (0,)        # the softmax layer LEADS
    assert layers_of_kind(cfg, "kda") == (1, 2, 3)
    stacks = layer_stacks(params["layers"], cfg)
    assert sorted(stacks) == ["attn", "kda"]
    assert stacks["attn"]["attn"]["wq"].shape == (1, 64, 4 * 32)
    assert "kda" not in stacks["attn"] and "attn" not in stacks["kda"]
    assert stacks["kda"]["kda"]["wq"].shape == (3, 64, 4 * 16)
    held = cfg.moe_experts_held or cfg.moe_num_experts
    for kind, n in (("attn", 1), ("kda", 3)):
        assert stacks[kind]["router"].shape == (n, 64, cfg.moe_num_experts)
        assert stacks[kind]["mlp"]["w_up"].shape == (n, held, 64, 32)
        assert stacks[kind]["shared"]["w_up"].shape == (n, 64, 32)
    axes = param_axes(cfg)
    assert (jax.tree.structure(jax.tree.map(lambda a: 0, params))
            == jax.tree.structure(jax.tree.map(
                lambda a: 0, axes, is_leaf=lambda x: isinstance(x, tuple))))
    # an all-alike model is the period of one: its tree is the flat one
    opt = create_model("tiny-opt")
    flat = opt.init(jax.random.PRNGKey(0))["layers"]
    assert layer_stacks(flat, opt.config) == {"attn": flat}


def test_router_margin(tiny):
    """No (token, layer) of the test sequence is a near-tie at the k-th
    score, so the tolerance below is of rounding and not of routing."""
    _, params, ids = tiny
    with jax.default_matmul_precision("highest"):
        chosen = REF.router_choices(params, ids, **REF_ARGS)
    assert chosen.shape == (4, 2, 53, TOP_K)
    model = tiny[0]
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
    assert len(gaps) == 4 and min(gaps) > MARGIN, gaps


def test_full_forward_against_the_reference(tiny):
    model, params, ids = tiny
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(
            lambda p, i: forward(p, i, model.config)[0])(params, ids))
    want = _ref("logits", params, ids)
    assert np.abs(got - want).max() < TOL
    for name, changed in CONTROLS.items():
        if name == "renormalised-over-held" and not model.config.moe_experts_held:
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


def test_ragged_chunks_then_decode_against_the_full_pass(tiny):
    """Prefill in ragged chunks (the last one padded), then decoding through
    the pages and the state pools, LOGITS against the reference's full
    forward pass; then the same slot again for another sequence, whose first
    chunk starts it from zeros whatever the slot held."""
    model, params, ids = tiny
    cfg = model.config
    cache = init_paged_cache(cfg, 12, 16, jnp.float32, state_slots=3)
    assert cache["k"].shape[0] == 1                   # pages: the softmax layer
    assert cache["state"].shape == (3, 3, 4, 16, 16)
    assert cache["tail"].shape == (3, 3, 3, 3 * 4 * 16)
    table = jnp.asarray([[1, 2, 3, 4, 0, 0, 0, 0]], jnp.int32)
    with jax.default_matmul_precision("highest"):
        for row, chunks in ((0, (32, 9)), (1, (32, 5))):
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
    and the served log-probabilities (`score_logprobs`, the harness's
    `correct`) are the reference's."""
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
        want_lp = _ref("next_token_logprobs", params, full[None])[0]
        assert np.abs(lp - want_lp).max() < TOL
    assert served.alloc.blocks_in_use == 0


def test_the_scored_tail_goes_a_token_at_a_time(tiny, monkeypatch):
    """`score_logprobs` of a model with recurrent layers: chunks, then the
    last tokens through the one-token forms (what the decode program runs),
    on the state and the pages the chunks left; a model without such layers
    is scored in chunks alone."""
    from deepspeed_tpu.serving import api

    model, params, _ = tiny
    monkeypatch.setattr(api, "_SCORE_STEP_TAIL", 9)
    full = np.random.default_rng(7).integers(0, 256, 80).astype(np.int32)

    def widths_of(served):
        widths, score = [], served._score
        served._score = lambda p, arena, table, chunk, *rest: (
            widths.append(chunk.shape[1]),
            score(p, arena, table, chunk, *rest))[1]
        return widths, served.score_logprobs(full)

    widths, lp = widths_of(_serving(model, params))
    assert widths == [32, 32, 32] + [1] * 9        # 70 in chunks, then 9
    want = _ref("next_token_logprobs", params, full[None])[0]
    assert np.abs(lp - want).max() < TOL
    opt = create_model("tiny-opt")
    widths, _ = widths_of(_serving(opt, opt.init(jax.random.PRNGKey(0))))
    assert widths == [32, 32, 32]


def test_a_preempted_sequence_is_recomputed_to_the_same_logits(tiny):
    """A pool far too small for the load: eviction and recompute. A
    re-admitted sequence's first chunk starts at 0 and so starts its slot
    from zeros; what comes out is what an engine with room gives, token for
    token, and the reference's log-probabilities."""
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


def test_the_shares_add_up_to_the_uncut_layer():
    """The share test of the `model-configs` guide: 64 experts over 8 chips.
    Chip c holds experts 8c..8c+7 (told so by its stack of 8 beside a router
    of 64 whose first 8 outputs are its own); each computes its experts'
    part of the routed sum for the tokens routed to them. The 8 parts, and
    the shared expert counted ONCE, add up to what the uncut reference gives
    for the whole layer."""
    model = create_model("tiny-solar-open2", moe_num_experts=64)
    cfg = model.config
    params = model.init(jax.random.PRNGKey(SEED))
    layer = jax.tree.map(lambda a: a[1], params["layers"]["kda"])
    x = jnp.asarray(np.random.default_rng(5).standard_normal((2, 19, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, chosen = REF._experts(layer, layer["mlp"], x, TOP_K, True, 1.0,
                                     True, False)
        parts, reached = [], 0
        for chip in range(8):
            mine = np.roll(np.arange(64), -8 * chip)      # its 8 come first
            part, _, counts = moe_mlp(
                x, layer["router"][:, mine],
                jax.tree.map(lambda w: w[8 * chip:8 * chip + 8],
                             layer["mlp"]),
                "swiglu", top_k=TOP_K, norm_topk_prob=True, infer=True,
                score_func="sigmoid", choice_bias=layer["router_bias"][mine],
                with_counts=True)
            parts.append(part)
            reached += int(counts[0])
        shared = REF._experts(layer, jax.tree.map(lambda w: w[:0],
                                                  layer["mlp"]),
                              x, TOP_K, True, 1.0, True, False)[0]
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
    rng = np.random.default_rng(7)
    scores = jax.nn.sigmoid(jnp.asarray(rng.standard_normal((200, 64)),
                                        jnp.float32))
    bias = jnp.asarray(rng.standard_normal((64,)) * 0.05, jnp.float32)
    plain_idx, plain_w = route_topk(scores, scores, TOP_K, True)
    idx, w = route_topk(scores, scores + bias, TOP_K, True)
    moved = (np.sort(idx, -1) != np.sort(plain_idx, -1)).any(-1)
    assert 0.05 < moved.mean() < 0.95             # it chooses other experts
    # and every weight is its expert's own score over the chosen scores' sum
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-6)
    # where the bias left the choice alone, the weights are the same numbers
    same = ~moved
    np.testing.assert_allclose(np.sort(np.asarray(w)[same], -1),
                               np.sort(np.asarray(plain_w)[same], -1),
                               rtol=1e-6)


def test_an_assignment_to_an_absent_expert_costs_no_row():
    """A router of 64 over a stack of 8: only what reaches experts 0..7 is
    laid out for the expert matmuls, and the counts say so."""
    model = create_model("tiny-solar-open2", **SHARE)
    params = model.init(jax.random.PRNGKey(SEED))
    layer = jax.tree.map(lambda a: a[0], params["layers"]["kda"])
    x = jnp.asarray(np.random.default_rng(8).standard_normal((1, 40, 64)),
                    jnp.float32)
    out, _, counts = moe_mlp(x, layer["router"], layer["mlp"], "swiglu",
                             top_k=TOP_K, infer=True, score_func="sigmoid",
                             choice_bias=layer["router_bias"],
                             with_counts=True)
    scores = jax.nn.sigmoid(x[0] @ layer["router"])
    idx, _ = route_topk(scores, scores + layer["router_bias"], TOP_K, True)
    reached = np.asarray(idx) < 8
    assert 0 < reached.sum() < idx.size
    assert int(counts[0]) == reached.sum()
    assert int(counts[1]) == len(set(np.asarray(idx)[reached].tolist()))
    # a token none of whose experts is held gets nothing from the routed part
    nothing = ~reached.any(-1)
    assert nothing.any() and not np.asarray(out)[0, nothing].any()
    # the capacity plans know none of this, and say so
    with pytest.raises(NotImplementedError, match="dropless"):
        moe_mlp(x, layer["router"], layer["mlp"], "swiglu", top_k=TOP_K,
                infer=False)


def test_pages_are_for_the_softmax_layers_alone():
    cfg = create_model("tiny-solar-open2", **SHARE).config
    opt = create_model("tiny-opt").config
    # one layer of four keeps pages: 2 kv heads x 32, k and v, float32
    assert paged_cache_memory_bytes(cfg, 10, 16, jnp.float32) \
        == 2 * 1 * 10 * 16 * 64 * 4
    assert paged_cache_memory_bytes(opt, 10, 16, jnp.float32) \
        == 2 * 2 * 10 * 16 * 64 * 4
    assert state_pool_memory_bytes(cfg, 5, jnp.float32) \
        == 3 * 5 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)
    assert state_pool_memory_bytes(opt, 5, jnp.float32) == 0
    assert sorted(init_paged_cache(opt, 4, 16, jnp.float32)) == ["k", "v"]
    with pytest.raises(ValueError, match="state_slots"):
        init_paged_cache(cfg, 4, 16, jnp.float32)


@pytest.fixture(scope="module", params=["delta-rule", "delta-rule-share",
                                        "state-space"])
def recurrent(request):
    """A model of each kind of layer that keeps a state: the delta rule
    (whole and as a share) and the Mamba-2 state-space mixer."""
    if request.param == "state-space":
        model = create_model("tiny-nemotron-3-super")
    else:
        model = create_model("tiny-solar-open2",
                             **(SHARE if "share" in request.param else {}))
    return model, model.init(jax.random.PRNGKey(SEED))


def test_what_cannot_follow_recurrent_state_is_refused_by_name(recurrent):
    model, params = recurrent
    served = _serving(model, params)
    prompt = np.arange(20, dtype=np.int32)
    with pytest.raises(NotImplementedError, match="snapshot"):
        served.submit(prompt, max_new_tokens=4, n=2)
    handle = served.submit(prompt, max_new_tokens=8)
    while handle._req.state != "decode":
        served.step()
    with pytest.raises(NotImplementedError, match=r"fork\(\).*snapshot"):
        served.fork(handle, 2)
    with pytest.raises(NotImplementedError, match="kv_import.*snapshot"):
        served.adopt_prefilled(prompt=prompt, n_prompt=20, generated=[1],
                               pending_token=1, length=20, blocks=[],
                               seed=0, sampling=handle._req.sampling,
                               max_new_tokens=4)
    from deepspeed_tpu.serving.fleet.disagg import ArenaHandoff

    with pytest.raises(NotImplementedError, match="kv_export.*snapshot"):
        ArenaHandoff().transfer(served, served, [1])
    served.run()
    assert len(handle.result()) == 8
    engine = InferenceEngine(model, InferenceConfig(dtype=jnp.float32),
                             params=params)
    with pytest.raises(NotImplementedError, match="speculative.*snapshot"):
        ServingEngine(engine, ServingConfig(
            num_blocks=40, block_size=16, max_seqs=4, prefill_chunk=32,
            max_model_len=128,
            speculative={"mode": "ngram", "num_draft_tokens": 2}))
    # the dense cache of the inference engine holds no recurrent state
    with pytest.raises(NotImplementedError, match="dense"):
        engine.generate(prompt[None], max_new_tokens=2)
    # a model of one kind is refused nothing
    opt = create_model("tiny-opt")
    plain = _serving(opt, opt.init(jax.random.PRNGKey(0)))
    assert plain.prefix is not None and plain.state_slots == 0
    assert len(plain.submit(prompt, max_new_tokens=3, n=2)) == 2


def test_spans_count_slots_states_and_held_experts(tmp_path):
    """Under a profiler capture, as the benchmark's `--trace 1` opens it."""
    model = create_model("tiny-solar-open2", **SHARE)
    params = model.init(jax.random.PRNGKey(SEED))
    served = _serving(model, params)
    reset_session()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        handles = [served.submit(np.arange(5, 5 + n, dtype=np.int32),
                                 max_new_tokens=4) for n in (21, 40)]
        served.run()
        [h.result() for h in handles]
    finally:
        jax.profiler.stop_trace()
    recorded = recorded_spans()
    reset_session()
    its = [s["attrs"] for s in recorded if s["name"] == "serving/iteration"]
    assert all(i["state_slots_total"] == 4 for i in its)
    assert max(i["state_slots_in_use"] for i in its) == 2
    # a slot holds a live state from its row's first chunk on, not from
    # admission: the count lags the rows that run
    assert all(i["state_slots_in_use"] <= i["running"] for i in its)
    assert any(i["state_slots_in_use"] < i["running"] for i in its)
    chunks = [s["attrs"] for s in recorded
              if s["name"] == "serving/prefill_chunk"]
    steps = [s["attrs"] for s in recorded if s["name"] == "serving/decode"
             and s["attrs"].get("rows")]
    assert [c["tokens"] for c in chunks] == [21, 32, 8]
    for c in chunks:            # one sequence a chunk, 3 recurrent layers
        assert c["recurrent_rows"] == 3
    assert steps
    for s in steps:
        assert s["recurrent_rows"] == 3 * s["rows"]
        assert s["moe_experts_total"] == 64 * 4       # the router's width
        assert s["moe_experts_held"] == 8 * 4
        assert s["moe_experts_touched"] <= s["moe_experts_held"]
        # an eighth of the 3 x 4 assignments a row reaches a held expert,
        # on average: never all of them
        assert s["moe_assignments"] < TOP_K * 4 * s["rows"]
    # a dense model's spans carry none of it
    opt = create_model("tiny-opt")
    plain = _serving(opt, opt.init(jax.random.PRNGKey(0)))
    assert plain._state_counts() == {}

"""LongCat-Flash (`longcat_flash`: every layer a DOUBLE layer, two
latent-attention (MLA) sublayers each with a dense FFN and ONE
shortcut-connected FFN of routed and zero-computation experts across both;
one pool of latents a sublayer, no keys and no values) against its plain
float32 reference. CPU, float32, seeded weights, `tiny-longcat-flash`: 2
double layers, hidden 64, 4 heads of 16 + 8 (values 12), latent 24, 16
routed and 8 zero-computation experts, 5 a token.

The seed's draw is SPICED (`_spiced`): norm scales drawn away from one and
the router's columns and the query and key projections widened, so that no
scale is left untested, attention is not flat and the tokens choose
different experts (at a width of 64 a router of std 0.02 gives
nearly uniform scores, and the bias alone would choose).

Tolerance: float32 on both sides, so the program and the reference differ by
rounding alone: the served log-probabilities read 2e-6 or less from the
reference's. The limit is 1e-5 and every control must read a hundred times
the limit or more.
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
                                              paged_pools)
from deepspeed_tpu.models import create_model
from deepspeed_tpu.models.transformer import (forward, latent_pools,
                                              latent_page_width, latent_width,
                                              moe_count_width,
                                              param_axes)
from deepspeed_tpu.ops.paged_decode_attention import (
    latent_absorbed_attention, latent_expanded_attention)
from deepspeed_tpu.parallel.moe import moe_mlp
from deepspeed_tpu.serving import ServingConfig, ServingEngine, paged_kv

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOL = 1e-5
SEED = 5
REF_ARGS = dict(num_attention_heads=4, moe_topk=5, routed_scaling_factor=6,
                zero_expert_num=8, zero_expert_type="identity",
                rms_norm_eps=1e-5, rope_theta=1e7, mla_scale_q_lora=True,
                mla_scale_kv_lora=True, kv_lora_rank=24, qk_rope_head_dim=8)
# each another model, and each must read far over the limit
CONTROLS = {"no-scale-on-c": dict(scale_c=False),
            "no-scale-on-q": dict(scale_q=False),
            "zero-weights-without-the-factor": dict(zero_weight_scaled=False),
            "shortcut-joined-after-the-first-sublayer": dict(join_after=0),
            "renormalised-weights": dict(renormalise=True),
            "rope-on-all-of-a-head": dict(rope_all=True)}


def _reference():
    path = os.path.join(REPO, "benchmarks", "references", "longcat_flash.py")
    spec = importlib.util.spec_from_file_location("reference_longcat", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


def _spiced(params, seed=0):
    rng = np.random.default_rng(seed)

    def scale(a):
        return jnp.asarray(rng.uniform(0.5, 1.5, a.shape), a.dtype)

    layers = dict(params["layers"])
    layers["ln1"] = {"scale": scale(layers["ln1"]["scale"])}
    layers["ln2"] = {"scale": scale(layers["ln2"]["scale"])}
    mla = layers["mla"]     # and scores of order one, or softmax is flat
    layers["mla"] = dict(mla, q_norm=scale(mla["q_norm"]),
                         kv_norm=scale(mla["kv_norm"]),
                         wq_b=6.0 * mla["wq_b"], wk_b=6.0 * mla["wk_b"],
                         wkv_a=4.0 * mla["wkv_a"])
    layers["router"] = layers["router"] * 8.0
    return dict(params, layers=layers,
                final_norm={"scale": scale(params["final_norm"]["scale"])})


@pytest.fixture(scope="module")
def tiny():
    model = create_model("tiny-longcat-flash")
    params = _spiced(model.init(jax.random.PRNGKey(SEED)))
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


def test_the_published_sizes_count_the_published_parameters():
    """By the shapes alone (nothing is allocated): one MLA 90.6 M, one dense
    FFN 226.5 M, the router 4.7 M, 638.9 M a layer beside its experts, one
    routed expert 37.7 M; 8 pools of 576 values in 640 lanes a token at 4
    layers."""
    model = create_model("longcat-flash-chat", dtype=jnp.bfloat16,
                         num_layers=4, moe_experts_held=16, vocab_size=16384)
    cfg = model.config
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    L = shapes["layers"]
    mla = (6144 * 1536 + 1536 + 1536 * 64 * 192 + 6144 * 576 + 512
           + 2 * 64 * 128 * 512 + 64 * 128 * 6144)
    assert count(L["mla"]) == 4 * 2 * mla and 90.5e6 < mla < 90.7e6
    assert count(L["dense"]) == 4 * 2 * 3 * 6144 * 12288
    assert L["router"].shape == (4, 6144, 768)
    assert L["router_bias"].shape == (4, 768)
    assert L["mlp"]["w_up"].shape == (4, 16, 6144, 2048)
    beside = count({k: v for k, v in L.items() if k != "mlp"}) // 4
    assert 638.8e6 < beside < 639.0e6
    assert count(L["mlp"]) == 4 * 16 * 3 * 6144 * 2048
    total = count(shapes)
    assert 10.34e9 < 2 * total < 10.36e9
    assert (latent_pools(cfg), latent_width(cfg), latent_page_width(cfg),
            paged_pools(cfg)) == (8, 576, 640, 0)
    assert paged_cache_memory_bytes(cfg, 1, 1, jnp.bfloat16) == 8 * 640 * 2
    arena = jax.eval_shape(lambda: init_paged_cache(cfg, 10241, 16,
                                                    jnp.bfloat16))
    assert set(arena) == {"latent"}
    assert arena["latent"].shape == (8, 10241, 16, 640)
    axes = param_axes(cfg)
    same = jax.tree.map(lambda a, s: len(a) == len(s.shape), axes, shapes,
                        is_leaf=lambda a: isinstance(a, tuple))
    assert all(jax.tree.leaves(same))
    assert not paged_kv.mixes(cfg)


def test_every_other_model_is_as_it_was():
    """No latent pool, three routing counts, an arena of keys and values."""
    plain = create_model("tiny-llama").config
    assert (latent_pools(plain), moe_count_width(plain)) == (0, 3)
    assert set(init_paged_cache(plain, 4, 4, jnp.float32)) == {"k", "v"}
    with pytest.raises(AssertionError, match="zero-computation"):
        create_model("tiny-nemotron-3-super", moe_zero_experts=4)
    with pytest.raises(AssertionError, match="sublayers"):
        create_model("tiny-longcat-flash", norm="layernorm")


@pytest.fixture(scope="module")
def scored(tiny):
    """A row of 100 tokens through `score_logprobs` (the harness's
    `correct`) in chunks of 12: the expanded read, then the last token a
    step through the absorbed one."""
    model, params, _ = tiny
    served = _serving(model, params)
    assert set(served._arena) == {"latent"}
    assert served._arena["latent"].shape == (2 * 2, 65, 4, 128)
    seq = np.random.default_rng(1).integers(0, 256, 100).astype(np.int32)
    return seq, served.score_logprobs(seq)


def test_served_scores_against_the_reference(tiny, scored):
    _, params, _ = tiny
    seq, got = scored
    want = _ref("next_token_logprobs", params, seq[None])[0]
    assert np.abs(got - want).max() < TOL
    # and in two chunks for nine
    again = _serving(*tiny[:2], prefill_chunk=64).score_logprobs(seq)
    assert np.abs(again - want).max() < TOL


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_wrong_model_fails(tiny, scored, control):
    _, params, _ = tiny
    seq, got = scored
    wrong = _ref("next_token_logprobs", params, seq[None],
                 **CONTROLS[control])[0]
    assert np.abs(got - wrong).max() > 100 * TOL


def _paged_logits(cfg, params, seq, chunks, width, cache, table):
    """`seq` through `forward` in paged mode as the serving programs call
    it: the prompt in the ragged `chunks` (each padded to `width`), then a
    token a step; the logits of every position."""
    @jax.jit
    def run(cache, tokens, pos, mask, start, n):
        run_of = {} if tokens.shape[1] == 1 else {"paged_run": (start, n)}
        logits, cache, _ = forward(params, tokens, cfg, cache=cache,
                                   positions=pos, block_table=table,
                                   paged_write_mask=mask, **run_of)
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


@pytest.mark.parametrize("chunks,width", [((16, 16, 9), 16),
                                          ((14, 2, 1, 7), 16)])
def test_ragged_chunks_then_decode_against_the_full_pass(tiny, chunks,
                                                         width):
    """Prefill in ragged chunks, then decoding through the latent pools,
    LOGITS against the reference's full forward pass; every sublayer's pool
    holds the row, the pools differ, and nothing else is written."""
    model, params, ids = tiny
    cfg = model.config
    cache = init_paged_cache(cfg, 20, 4, jnp.float32)
    assert cache["latent"].shape == (4, 20, 4, 128)    # 2 layers x 2 sublayers
    table = jnp.asarray([list(range(1, 17)) + [0] * 4], jnp.int32)
    with jax.default_matmul_precision("highest"):
        for row in (0, 1):
            seq = ids[row]
            got, cache = _paged_logits(cfg, params, seq, chunks, width,
                                       cache, table)
            want = _ref("logits", params, seq[None])[0]
            assert np.abs(got - want).max() < TOL
    pools = np.asarray(cache["latent"])[:, 1:17].reshape(4, -1)
    assert pools.any(axis=-1).all()
    assert min(np.abs(pools[a] - pools[b]).max()
               for a in range(4) for b in range(a)) > 0.01
    assert not np.asarray(cache["latent"])[:, 17:].any()
    assert not np.asarray(cache["latent"])[..., 24 + 8:].any()  # the pad


@pytest.mark.parametrize("queries", [1, 7], ids=["a-step", "a-chunk"])
def test_the_absorbed_read_is_the_expanded_read(queries):
    """The two forms of the paged read of a latent pool compute the same
    numbers: rows of different lengths (one of them empty), heads in two
    groups of the expanded read, a pool that is not the first."""
    rng = np.random.default_rng(2)
    B, N, Dn, Dr, R, Dv, BS, MAXB = 3, 12, 16, 8, 24, 12, 4, 6
    f = lambda *s: jnp.asarray(0.5 * rng.normal(size=s), jnp.float32)
    arena = f(3, 1 + B * MAXB, BS, R + Dr + 3)    # 3 lanes of pad, never read
    table = jnp.asarray(1 + np.arange(B * MAXB).reshape(B, MAXB), jnp.int32)
    last = np.array([20, 9, -1])                    # the last query's place
    offs = np.arange(queries)[None] - (queries - 1)
    pos = np.where((last[:, None] >= 0) & (last[:, None] + offs >= 0),
                   last[:, None] + offs, -1).astype(np.int32)
    args = (f(B, queries, N, Dn), f(B, queries, N, Dr), f(N, Dn, R),
            f(N, R, Dv), arena, jnp.int32(1), table, jnp.asarray(pos),
            (Dn + Dr) ** -0.5)
    with jax.default_matmul_precision("highest"):
        absorbed = np.asarray(latent_absorbed_attention(*args))
        expanded = np.asarray(latent_expanded_attention(*args))
    assert np.abs(absorbed).max() > 0.1
    assert np.abs(absorbed - expanded).max() < TOL
    assert not expanded[pos < 0].any() and not absorbed[pos < 0].any()


def test_the_shares_add_up(tiny):
    """One layer's expert FFN over 4 shares of its 16 routed experts: the
    routed parts of all shares plus the identity term ONCE are the uncut
    reference's output; each share is the reference's share, and the
    counts are the held assignments and, beside them, the zero ones."""
    model, params, _ = tiny
    cfg = model.config
    layer = jax.tree.map(lambda a: a[1], {
        k: params["layers"][k] for k in ("router", "router_bias", "mlp")})
    h = jnp.asarray(np.random.default_rng(4).normal(size=(1, 40, 64)),
                    jnp.float32)
    args = dict(REF_ARGS)
    with jax.default_matmul_precision("highest"):
        whole, identity = REF.moe_parts(layer, h[0], **args)
        parts, zero_counts = [], []
        for share in range(4):
            mine = np.r_[share * 4:share * 4 + 4]
            order = np.r_[mine, np.setdiff1d(np.arange(16), mine),
                          16:24]
            held = dict(layer, router=layer["router"][:, order],
                        router_bias=layer["router_bias"][order],
                        mlp=jax.tree.map(lambda a: a[mine], layer["mlp"]))
            out, _, counts = moe_mlp(
                h, held["router"], held["mlp"], cfg.activation,
                top_k=cfg.moe_top_k, norm_topk_prob=False, infer=True,
                with_counts=True, score_func="softmax",
                choice_bias=held["router_bias"],
                routed_scale=cfg.moe_routed_scale,
                zero_experts=cfg.moe_zero_experts)
            want, same_identity = REF.moe_parts(held, h[0], **args)
            assert np.abs(np.asarray(same_identity - identity)).max() < TOL
            assert np.abs(np.asarray(out[0] - want - identity)).max() < TOL
            parts.append(out[0] - identity)
            assert counts.shape == (4,)
            zero_counts.append(int(counts[3]))
            held_assignments = int(counts[0])
            assert 0 < held_assignments < 40 * 5
    total = sum(parts) + identity
    assert np.abs(np.asarray(total - (whole + identity))).max() < TOL
    assert np.abs(np.asarray(identity)).max() > 0.1
    assert len(set(zero_counts)) == 1 and 0 < zero_counts[0] < 40 * 5


def test_served_sequences_against_the_reference(tiny):
    """Through `init_serving`'s engine: more requests than rows, prompts of
    one to six ragged chunks; every greedy token is the reference's best by
    its LOGITS. The prefix cache is on: a block is a run of tokens in every
    pool, a second request with the first's prompt shares its blocks, and
    copy-on-write copies a block of the latent arena."""
    model, params, _ = tiny
    served = _serving(model, params)
    assert served.prefix is not None
    rng = np.random.default_rng(0)
    sent = []
    for n in (45, 70, 10, 33, 64, 5):
        prompt = rng.integers(0, 256, n).astype(np.int32)
        sent.append((prompt, served.submit(
            prompt, max_new_tokens=int(rng.integers(5, 30)))))
    served.run()
    again = served.submit(sent[1][0], max_new_tokens=8)
    served.run()
    assert served.prefix.cached_blocks > 0
    np.testing.assert_array_equal(again.result(), sent[1][1].result()[:8])
    for prompt, handle in sent:
        full = np.concatenate([prompt, np.asarray(handle.result(), np.int32)])
        want = _ref("logits", params, full[None])[0]
        best = want[len(prompt) - 1:-1]
        chosen = best[np.arange(len(best)), full[len(prompt):]]
        assert (best.max(-1) - chosen).max() < TOL
    before = np.asarray(served._arena["latent"])
    served._arena = served._cow(served._arena, jnp.int32(3), jnp.int32(60))
    after = np.asarray(served._arena["latent"])
    np.testing.assert_array_equal(after[:, 60], before[:, 3])
    assert before[:, 3].any()


def test_a_preempted_sequence_is_recomputed_to_the_same_tokens(tiny):
    """A pool far too small for the load: eviction and recompute, every
    sublayer's pool written again from the first page on."""
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
    small.prefix.clear()        # what is left is the prefix cache's
    assert small.alloc.blocks_in_use == 0


def test_the_spans_count_the_zero_assignments(tiny, tmp_path):
    """`serving/decode` and `serving/prefill_chunk` carry, beside the three
    counts over the HELD experts, the assignments that chose a
    zero-computation expert: the reference's router says how many."""
    from deepspeed_tpu.config.config import ObservabilityConfig
    from deepspeed_tpu.observability import (configure_observability,
                                             recorded_spans, reset_session)

    model, params, _ = tiny
    reset_session()
    configure_observability(ObservabilityConfig(
        enabled=True, output_dir=str(tmp_path / "obs"),
        flight_recorder=False))
    try:
        served = _serving(model, params)
        prompt = np.arange(20, dtype=np.int32)
        handle = served.submit(prompt, max_new_tokens=6)
        served.run()
        spans = [s for s in recorded_spans()
                 if "moe_zero_assignments" in s.get("attrs", {})]
        full = np.concatenate([prompt, np.asarray(handle.result(), np.int32)])
        chosen = _ref("router_choices", params, full[None])[:, 0]  # (L, S, k)
        for name, fed in (("serving/prefill_chunk", chosen[:, :20]),
                          ("serving/decode", chosen[:, 20:-1])):
            mine = [s["attrs"] for s in spans if s["name"] == name]
            assert sum(a["moe_zero_assignments"] for a in mine) \
                == int((fed >= 16).sum()) > 0
            assert sum(a["moe_assignments"] for a in mine) \
                == int((fed < 16).sum())
            assert all(a["moe_experts_total"] == 2 * 16 for a in mine)
        plain = create_model("tiny-olmoe")
        once = _serving(plain, plain.init(jax.random.PRNGKey(0)))
        assert once._last_tokens.shape == (4 + 3,)
        assert served._last_tokens.shape == (4 + 4,)
    finally:
        reset_session()


def test_what_cannot_read_a_latent_pool_refuses_by_name(tiny):
    """The dense cache keeps keys and values of every head; a verify step
    and the hand-off between engines have no latent form yet. Without a
    cache the whole forward runs, and so does its gradient."""
    from deepspeed_tpu.config.config import SpeculativeConfig

    model, params, ids = tiny
    engine = InferenceEngine(model, InferenceConfig(dtype=jnp.float32),
                             params=params)
    with pytest.raises(NotImplementedError, match="latent"):
        engine.generate(np.arange(20, dtype=np.int32)[None],
                        max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="latent attention"):
        ServingEngine(engine, ServingConfig(
            num_blocks=64, block_size=4, max_seqs=4, prefill_chunk=12,
            max_model_len=128,
            speculative=SpeculativeConfig(mode="ngram", num_draft_tokens=2)))
    served = _serving(model, params)
    with pytest.raises(NotImplementedError, match="kv_import"):
        served._no_latent_read("kv_import (adopting a sequence prefilled on "
                               "another engine)")
    assert served._mixed is None
    batch = {"input_ids": jnp.asarray(ids[:, :24])}
    loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
    with jax.default_matmul_precision("highest"):
        want = REF.loss(params, batch["input_ids"], **REF_ARGS)
    assert abs(float(loss) - float(want)) < 1e-5
    assert float(jnp.abs(grads["layers"]["mla"]["wk_b"]).max()) > 0


def _published_model():
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers.models.longcat_flash")
    from transformers.models.longcat_flash import (LongcatFlashConfig,
                                                   LongcatFlashForCausalLM)

    torch.manual_seed(0)
    config = LongcatFlashConfig(
        vocab_size=256, hidden_size=64, num_layers=2, num_hidden_layers=4,
        num_attention_heads=4, ffn_hidden_size=128, q_lora_rank=32,
        kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
        head_dim=8, v_head_dim=12, moe_topk=5, n_routed_experts=16,
        zero_expert_num=8, expert_ffn_hidden_size=32,
        routed_scaling_factor=6.0, rope_theta=1e7, rms_norm_eps=1e-5,
        max_position_embeddings=128, attn_implementation="eager")
    published = LongcatFlashForCausalLM(config).float().eval()
    with torch.no_grad():
        for name, p in published.named_parameters():
            if "layernorm" in name or name.endswith("norm.weight"):
                p.uniform_(0.5, 1.5)
        for layer in published.model.layers:
            layer.mlp.router.classifier.weight.mul_(8.0)
            layer.mlp.router.e_score_correction_bias.normal_(0.0, 0.04)
    return torch, published


def test_the_reference_follows_the_published_code():
    """The installed `LongcatFlashForCausalLM` at the tiny size, random
    weights, copied into the program's parameter tree: the reference gives
    its logits. A guard beside the count: skipped where torch or the family
    is not installed."""
    torch, published = _published_model()
    t = lambda w: jnp.asarray(w.detach().numpy())
    stack = lambda f: jnp.stack([jnp.stack([f(layer, i) for i in (0, 1)])
                                 for layer in published.model.layers])
    once = lambda f: jnp.stack([f(layer) for layer in published.model.layers])
    N, Dn, Dv, R = 4, 16, 12, 24
    kv_b = lambda l, i: t(l.self_attn[i].kv_b_proj.weight).reshape(
        N, Dn + Dv, R)
    experts = lambda name: once(lambda l: jnp.stack(
        [t(getattr(l.mlp.experts[e], name).weight).T for e in range(16)]))
    params = {
        "embed": {"tokens": t(published.model.embed_tokens.weight)},
        "final_norm": {"scale": t(published.model.norm.weight)},
        "lm_head": t(published.lm_head.weight).T,
        "layers": {
            "ln1": {"scale": stack(lambda l, i: t(l.input_layernorm[i].weight))},
            "ln2": {"scale": stack(
                lambda l, i: t(l.post_attention_layernorm[i].weight))},
            "mla": {
                "wq_a": stack(lambda l, i: t(l.self_attn[i].q_a_proj.weight).T),
                "q_norm": stack(
                    lambda l, i: t(l.self_attn[i].q_a_layernorm.weight)),
                "wq_b": stack(lambda l, i: t(l.self_attn[i].q_b_proj.weight).T),
                "wkv_a": stack(lambda l, i: t(
                    l.self_attn[i].kv_a_proj_with_mqa.weight).T),
                "kv_norm": stack(
                    lambda l, i: t(l.self_attn[i].kv_a_layernorm.weight)),
                "wk_b": stack(lambda l, i: kv_b(l, i)[:, :Dn]),
                "wv_b": stack(lambda l, i: jnp.swapaxes(kv_b(l, i)[:, Dn:],
                                                        1, 2)),
                "wo": stack(lambda l, i: t(l.self_attn[i].o_proj.weight).T)},
            "dense": {name: stack(lambda l, i, n=src: t(
                getattr(l.mlps[i], n).weight).T) for name, src in (
                    ("w_gate", "gate_proj"), ("w_up", "up_proj"),
                    ("w_down", "down_proj"))},
            "router": once(lambda l: t(l.mlp.router.classifier.weight).T),
            "router_bias": once(
                lambda l: t(l.mlp.router.e_score_correction_bias)),
            "mlp": {"w_gate": experts("gate_proj"),
                    "w_up": experts("up_proj"),
                    "w_down": experts("down_proj")}}}
    model = create_model("tiny-longcat-flash")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, params) \
        == jax.tree.map(lambda a: a.shape, shapes)
    ids = np.random.default_rng(0).integers(0, 256, (2, 37))
    with torch.no_grad():
        want = published(torch.as_tensor(ids)).logits.numpy()
    got = _ref("logits", params, jnp.asarray(ids, jnp.int32))
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() < 2e-5
    # and so does the program, whole and without a cache
    with jax.default_matmul_precision("highest"):
        mine = np.asarray(forward(params, jnp.asarray(ids, jnp.int32),
                                  model.config)[0])
    assert np.abs(mine - want).max() < 2e-5

"""A decode step keeps q as rows until its product and bias are whole
(``models/transformer._qkv_heads``, PR 53): the numbers are those of the
formulation that reshapes first, bit for bit, for every mixer that takes the
path, and what is served is what ``generate`` gives."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.inference import init_inference
from deepspeed_tpu.inference.kv_cache import init_paged_cache
from deepspeed_tpu.models import transformer as T
from deepspeed_tpu.models.presets import transformer_config
from deepspeed_tpu.serving import ServingConfig, ServingEngine

NUM_BLOCKS, BLOCK, MAXB, ROWS = 24, 4, 5, 3
# the mixers that take the path: biased (OPT: learned positions, LayerNorm);
# q and k normed hidden-wide before the heads are split and roped, kv heads
# fewer than heads (OLMoE's form, grouped)
MIXERS = {"biased": ("tiny-opt", {}),
          "qk-norm-rope-gqa": ("tiny-olmoe", {"num_kv_heads": 2})}


def _reshape_first(cfg, h, p, cached=False, divided=False):
    """``_qkv_heads`` as it stood before PR 53: nothing between q's product
    and its heads (``cached``, PR 60, and ``divided``, PR 61: taken and not
    read)."""
    B, S, _ = h.shape
    N, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = (T._qeinsum("bsh,hd->bsd", h, p[w], cfg.dtype,
                          a8=cfg.a8_decode) for w in ("wq", "wk", "wv"))
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = T._norm(q, p["q_norm"], None, "rmsnorm", cfg.norm_eps)
        k = T._norm(k, p["k_norm"], None, "rmsnorm", cfg.norm_eps)
    return (q.reshape(B, S, N, D), k.reshape(B, S, K, D),
            v.reshape(B, S, K, D))


def _model(mixer, dtype):
    preset, overrides = MIXERS[mixer]
    cfg = transformer_config(preset, dtype=dtype, **overrides)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    # the biases and the norms' scales are drawn, not the zeros and ones of
    # a fresh model: a term dropped or misplaced has to show
    attn = dict(params["layers"]["attn"])
    for name in sorted({"bq", "bk", "bv", "q_norm", "k_norm"} & set(attn)):
        attn[name] = jnp.asarray(
            rng.standard_normal(attn[name].shape) * 0.5
            + name.endswith("_norm"), attn[name].dtype)
    return cfg, {**params, "layers": {**params["layers"], "attn": attn}}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("mixer", sorted(MIXERS))
def test_a_decode_step_is_the_reshape_first_step_bit_for_bit(
        monkeypatch, mixer, dtype):
    cfg, params = _model(mixer, dtype)
    mixer_params = params["layers"]["attn"]
    assert ("bq" in mixer_params) == (mixer == "biased")
    rng = np.random.default_rng(1)
    cache = {k: jnp.asarray(rng.standard_normal(v.shape), v.dtype)
             for k, v in init_paged_cache(cfg, NUM_BLOCKS, BLOCK,
                                          dtype).items()}
    table = jnp.asarray(rng.permutation(np.arange(1, NUM_BLOCKS))
                        [:ROWS * MAXB].reshape(ROWS, MAXB), jnp.int32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (ROWS, 1)),
                         jnp.int32)
    # each row at a position of its own; the last row is empty (masked)
    pos = jnp.asarray([[7], [0], [MAXB * BLOCK - 1]], jnp.int32)
    mask = jnp.asarray([[True], [True], [False]])

    def step():
        return jax.jit(lambda p, c: T.forward(
            p, tokens, cfg, cache=c, positions=pos, block_table=table,
            paged_write_mask=mask)[:2])(params, cache)

    logits, arena = step()
    seen = []
    monkeypatch.setattr(T, "_qkv_heads",
                        lambda *a, **kw: seen.append(1) or _reshape_first(*a, **kw))
    want_logits, want_arena = step()
    assert seen, "the mixer no longer reads its projections by that name"
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    for side in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(arena[side]),
                                      np.asarray(want_arena[side]))
        # and the step wrote: the arena is not what it was
        assert not np.array_equal(np.asarray(arena[side]),
                                  np.asarray(cache[side]))


@pytest.mark.parametrize("rows", [1, 2], ids=["decode", "two-positions"])
def test_only_a_step_of_one_position_holds_q_back(rows):
    """The rule reads the step's shape and nothing else: one position a row
    puts ONE barrier a layer kind in the program (q's), more positions (a
    chunk, a verify step, a training sequence) none."""
    cfg, params = _model("biased", jnp.float32)
    h = jnp.zeros((ROWS, rows, cfg.hidden_size), jnp.float32)
    p = jax.tree.map(lambda a: a[0], params["layers"]["attn"])
    text = str(jax.make_jaxpr(lambda h: T._qkv_heads(cfg, h, p))(h))
    assert text.count("optimization_barrier") == (1 if rows == 1 else 0)


@pytest.mark.parametrize("mixer", sorted(MIXERS))
def test_serving_gives_the_tokens_of_generate(mixer):
    preset, overrides = MIXERS[mixer]
    engine = init_inference(preset, dtype=jnp.float32, max_out_tokens=128,
                            **overrides)
    srv = ServingEngine(engine, ServingConfig(
        block_size=16, num_blocks=32, max_seqs=4, max_model_len=128,
        prefill_chunk=16, max_queue=64))
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, 250, (n,)) for n in (11, 23, 5)]
    handles = [srv.submit(p, max_new_tokens=7) for p in prompts]
    srv.run()
    for prompt, handle in zip(prompts, handles):
        want = np.asarray(engine.generate(prompt[None], max_new_tokens=7))[0]
        np.testing.assert_array_equal(handle.result(), want)

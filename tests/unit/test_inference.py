"""Inference stack tests — analog of reference tests/unit/inference/
test_inference.py (HF model × dtype matrix) and the KV-cache/generate
correctness checks the CUDA kernels get via ds_attention tests.

Key oracles:
  * generate() greedy == naive no-cache argmax loop (KV-cache correctness)
  * our forward == HuggingFace torch forward after state-dict import
    (the injection-policy/auto-TP parity check, per family)
  * tp=2 == tp=1 generation on the virtual mesh
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference import init_inference
from deepspeed_tpu.models import create_model


def naive_greedy(model, params, prompt, n_new):
    """Oracle: recompute the full forward for every generated token."""
    ids = jnp.asarray(prompt, jnp.int32)
    out = []
    for _ in range(n_new):
        logits, _ = model.apply(params, {"input_ids": ids})
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), -1).astype(jnp.int32)
        out.append(nxt)
        ids = jnp.concatenate([ids, nxt[:, None]], axis=1)
    return jnp.stack(out, axis=1)


@pytest.mark.parametrize("preset", ["tiny", "tiny-llama", "tiny-bloom",
                                    "tiny-opt", "tiny-gptj", "tiny-gptneox"])
@pytest.mark.slow
def test_cache_logits_match_full_forward(preset):
    """Teacher-forced KV-cache correctness: prefill + per-token decode steps
    must reproduce the full-forward logits at every position."""
    from deepspeed_tpu.inference import kv_cache
    from deepspeed_tpu.models.transformer import forward

    engine = init_inference(preset, dtype=jnp.float32, max_out_tokens=128)
    cfg = engine.model.config
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, 250, size=(2, 20)), jnp.int32)
    S_prompt = 12

    full, _, _ = forward(engine.params, ids, cfg)
    cache = kv_cache.init_cache(cfg, 2, 128, jnp.float32)
    valid = jnp.zeros((2, 128), jnp.int32).at[:, :S_prompt].set(1)
    lg, cache, _ = forward(engine.params, ids[:, :S_prompt], cfg,
                           attention_mask=valid, cache=cache, start_pos=0)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, :S_prompt]),
                               atol=1e-4, rtol=1e-4)
    for pos in range(S_prompt, 20):
        valid = valid.at[:, pos].set(1)
        lg, cache, _ = forward(engine.params, ids[:, pos:pos + 1], cfg,
                               attention_mask=valid, cache=cache,
                               start_pos=pos)
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full[:, pos]),
                                   atol=1e-4, rtol=1e-4,
                                   err_msg=f"decode step at pos {pos}")


@pytest.mark.slow
def test_generate_matches_naive_loop():
    """Greedy generate == naive full-recompute loop. Token mismatches are
    accepted only at genuine fp32 near-ties (top-2 gap < 1e-4), after which
    the prefixes legitimately diverge and comparison stops."""
    engine = init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 250, size=(2, 12))
    n_new = 8
    got = np.asarray(engine.generate(prompt, max_new_tokens=n_new))
    for b in range(prompt.shape[0]):
        ids = jnp.asarray(prompt[b:b + 1], jnp.int32)
        for i in range(n_new):
            logits, _ = engine.model.apply(engine.params, {"input_ids": ids})
            row = np.asarray(logits[0, -1], np.float32)
            best = int(row.argmax())
            if got[b, i] != best:
                top2 = np.sort(row)[-2:]
                assert top2[1] - row[got[b, i]] < 1e-4, (
                    f"batch {b} step {i}: got {got[b, i]} want {best} "
                    f"(gap {top2[1] - row[got[b, i]]:.2e} — not a tie)")
                break
            ids = jnp.concatenate([ids, jnp.asarray([[best]], jnp.int32)], 1)


@pytest.mark.slow
def test_generate_positions_not_bucket_shifted():
    """Decoded tokens must take positions from the TRUE prompt length, not
    the compile bucket (regression: prompt 12 bucketed to 64 gave the first
    generated token position 64). Amplified position embeddings make any
    offset flip the argmax."""
    engine = init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)
    engine.params = dict(engine.params)
    engine.params["pos"] = engine.params["pos"] * 50.0
    prompt = np.random.RandomState(7).randint(0, 250, (1, 12))
    got = np.asarray(engine.generate(prompt, max_new_tokens=5))
    ids = jnp.asarray(prompt, jnp.int32)
    want = []
    for _ in range(5):
        logits, _ = engine.model.apply(engine.params, {"input_ids": ids})
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32), -1).astype(jnp.int32)
        want.append(int(nxt[0]))
        ids = jnp.concatenate([ids, nxt[:, None]], 1)
    np.testing.assert_array_equal(got[0], want)


def test_generate_ragged_prompts_right_padded():
    engine = init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)
    rng = np.random.RandomState(1)
    full = rng.randint(0, 250, size=(2, 10))
    mask = np.ones((2, 10), np.int32)
    mask[1, 6:] = 0  # second prompt is 6 tokens long
    got = engine.generate(full, attention_mask=mask, max_new_tokens=4)
    # row 1 must match generating from the unpadded 6-token prompt, provided
    # positions agree: re-run with the short prompt right-padded the same way
    short = engine.generate(full[1:2, :10] * mask[1:2],
                            attention_mask=mask[1:2], max_new_tokens=4)
    np.testing.assert_array_equal(np.asarray(got[1:2]), np.asarray(short))


@pytest.mark.parametrize("preset", ["tiny", "tiny-llama", "tiny-bloom"])
# learned + rope + alibi (per-row key positions in the bias)
def test_generate_ragged_matches_solo_prompt(preset):
    """Exact ragged positions: a short row in a ragged batch must generate
    the SAME tokens as serving that prompt alone at its true width — decode
    positions are per-row (len_b, len_b+1, ...), not the padded array
    width."""
    engine = init_inference(preset, dtype=jnp.float32, max_out_tokens=128)
    rng = np.random.RandomState(3)
    full = rng.randint(0, 250, size=(2, 10)).astype(np.int64)
    mask = np.ones((2, 10), np.int32)
    mask[1, 6:] = 0
    full[1, 6:] = 0
    got = np.asarray(engine.generate(full, attention_mask=mask,
                                     max_new_tokens=4))
    solo = np.asarray(engine.generate(full[1:2, :6], max_new_tokens=4))
    np.testing.assert_array_equal(got[1:2], solo)


def test_arena_allocated_once_and_reused(monkeypatch):
    """The KV arena is engine-owned: repeated generate() calls at the same
    batch size must not re-allocate it (reference InferenceContext
    workspace discipline)."""
    from deepspeed_tpu.inference import kv_cache

    engine = init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)
    calls = []
    orig = kv_cache.init_cache

    def counting(*a, **kw):
        calls.append(a)
        return orig(*a, **kw)

    monkeypatch.setattr(kv_cache, "init_cache", counting)
    prompt = np.arange(8)[None]
    a = np.asarray(engine.generate(prompt, max_new_tokens=4))
    b = np.asarray(engine.generate(prompt, max_new_tokens=4))
    c = np.asarray(engine.generate(prompt, max_new_tokens=4))
    assert len(calls) == 1, f"arena allocated {len(calls)} times"
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(b, c)
    assert 1 in engine._arena


def test_generate_eos_stops():
    engine = init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)
    prompt = np.arange(8)[None]
    toks = engine.generate(prompt, max_new_tokens=12, eos_token_id=None)
    # pick the first generated token as a fake EOS — regenerate with it
    eos = int(np.asarray(toks)[0, 0])
    toks2 = np.asarray(engine.generate(prompt, max_new_tokens=12,
                                       eos_token_id=eos))
    hit = np.where(toks2[0] == eos)[0]
    assert hit.size > 0
    # after the first EOS everything is EOS
    assert (toks2[0, hit[0]:] == eos).all()


@pytest.mark.slow
def test_generate_temperature_reproducible():
    engine = init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)
    prompt = np.arange(8)[None]
    a = engine.generate(prompt, max_new_tokens=6, temperature=0.8, top_k=20, seed=3)
    b = engine.generate(prompt, max_new_tokens=6, temperature=0.8, top_k=20, seed=3)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(a).shape == (1, 6)


@pytest.mark.slow
def test_ttft_reported():
    engine = init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)
    out, ttft = engine.generate(np.arange(8)[None], max_new_tokens=2,
                                return_ttft=True)
    assert ttft > 0.0
    assert np.asarray(out).shape == (1, 2)


@pytest.mark.slow
def test_tensor_parallel_generation_matches(devices8):
    prompt = np.arange(10)[None]
    e1 = init_inference("tiny-llama", dtype=jnp.float32, max_out_tokens=128)
    t1 = e1.generate(prompt, max_new_tokens=6)
    from deepspeed_tpu.parallel import mesh as mesh_mod

    mesh_mod.reset_mesh()
    e2 = init_inference("tiny-llama", dtype=jnp.float32, max_out_tokens=128,
                        tensor_parallel=2)
    # same weights: re-shard e1's params onto e2's mesh
    e2.params = jax.tree.map(
        lambda x, s: jax.device_put(np.asarray(x), s), e1.params,
        e2.param_shardings)
    t2 = e2.generate(prompt, max_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))


@pytest.mark.parametrize("preset", ["tiny", "tiny-llama"])
@pytest.mark.slow
def test_kernel_prefill_decode_branches(preset, monkeypatch):
    """Drive the Pallas prefill/decode cache branches on CPU via interpret
    mode (on TPU they are the default; CPU normally takes the jnp path)."""
    import deepspeed_tpu.models.transformer as T
    from deepspeed_tpu.inference import kv_cache
    from deepspeed_tpu.models.transformer import forward
    from deepspeed_tpu.ops import registry

    engine = init_inference(preset, dtype=jnp.float32, max_out_tokens=128)
    cfg = engine.model.config
    full_ref, _, _ = forward(engine.params,
                             jnp.asarray(np.arange(20)[None] % 250, jnp.int32),
                             cfg)

    import importlib

    fa = importlib.import_module("deepspeed_tpu.ops.flash_attention")
    da = importlib.import_module("deepspeed_tpu.ops.decode_attention")
    monkeypatch.setattr(registry, "kernels_active", lambda: True)
    monkeypatch.setattr(T, "default_attention_impl",
                        lambda: fa.make_attention_impl(interpret=True))
    monkeypatch.setattr(da, "decode_attention",
                        lambda *a, **k: _DA_ORIG(*a, **{**k, "interpret": True}))
    nrm = importlib.import_module("deepspeed_tpu.ops.normalization")
    monkeypatch.setattr(nrm, "fused_layer_norm",
                        lambda x, s, b, eps=1e-5, rms=False: _FLN_ORIG(
                            x, s, b, eps, rms, True))

    ids = jnp.asarray(np.arange(20)[None] % 250, jnp.int32)
    cache = kv_cache.init_cache(cfg, 1, 128, jnp.float32)
    valid = jnp.zeros((1, 128), jnp.int32).at[:, :12].set(1)
    lg, cache, _ = forward(engine.params, ids[:, :12], cfg,
                           attention_mask=valid, cache=cache, start_pos=0)
    np.testing.assert_allclose(np.asarray(lg[:, :12]),
                               np.asarray(full_ref[:, :12]),
                               atol=1e-3, rtol=1e-3)
    for pos in range(12, 16):
        valid = valid.at[:, pos].set(1)
        lg, cache, _ = forward(engine.params, ids[:, pos:pos + 1], cfg,
                               attention_mask=valid, cache=cache,
                               start_pos=pos)
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full_ref[:, pos]),
                                   atol=1e-3, rtol=1e-3,
                                   err_msg=f"kernel decode at pos {pos}")


# original kernel entries, captured before any monkeypatching
from deepspeed_tpu.ops.decode_attention import decode_attention as _DA_ORIG  # noqa: E402
from deepspeed_tpu.ops.normalization import fused_layer_norm as _FLN_ORIG  # noqa: E402


# ---------------------------------------------------------------------------
# HF parity (the reference's per-architecture container/policy correctness)
# ---------------------------------------------------------------------------


def _hf_logits(hf_model, ids):
    import torch

    with torch.no_grad():
        return hf_model(torch.tensor(ids)).logits.float().numpy()


def _ours_logits(preset, hf_model, ids):
    engine = init_inference(preset, dtype=jnp.float32, max_out_tokens=128,
                            hf_model=hf_model)
    return np.asarray(engine.forward(ids))


@pytest.mark.slow
def test_hf_import_gpt2():
    transformers = pytest.importorskip("transformers")
    __import__("torch").manual_seed(10)
    cfg = transformers.GPT2Config(
        vocab_size=256, n_positions=128, n_embd=64, n_layer=2, n_head=4,
        attn_pdrop=0.0, embd_pdrop=0.0, resid_pdrop=0.0)
    hf = transformers.GPT2LMHeadModel(cfg).eval()
    ids = np.random.RandomState(0).randint(0, 256, (2, 16))
    np.testing.assert_allclose(_ours_logits("tiny", hf, ids),
                               _hf_logits(hf, ids), atol=2e-3, rtol=2e-3)


def test_hf_import_llama():
    transformers = pytest.importorskip("transformers")
    __import__("torch").manual_seed(11)
    cfg = transformers.LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, attention_dropout=0.0)
    hf = transformers.LlamaForCausalLM(cfg).eval()
    ids = np.random.RandomState(1).randint(0, 256, (2, 16))
    np.testing.assert_allclose(_ours_logits("tiny-llama", hf, ids),
                               _hf_logits(hf, ids), atol=2e-3, rtol=2e-3)


def test_hf_import_opt():
    transformers = pytest.importorskip("transformers")
    __import__("torch").manual_seed(12)
    cfg = transformers.OPTConfig(
        vocab_size=256, hidden_size=64, ffn_dim=256, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=128,
        word_embed_proj_dim=64, do_layer_norm_before=True, dropout=0.0)
    hf = transformers.OPTForCausalLM(cfg).eval()
    ids = np.random.RandomState(2).randint(0, 256, (2, 16))
    np.testing.assert_allclose(_ours_logits("tiny-opt", hf, ids),
                               _hf_logits(hf, ids), atol=2e-3, rtol=2e-3)


@pytest.mark.slow
def test_hf_import_gptj():
    """GPT-J: parallel residual + partial INTERLEAVED rotary (converted to
    rotate-half at import) + biased untied head."""
    transformers = pytest.importorskip("transformers")
    __import__("torch").manual_seed(14)
    cfg = transformers.GPTJConfig(
        vocab_size=256, n_positions=128, n_embd=64, n_layer=2, n_head=4,
        rotary_dim=8, attn_pdrop=0.0, embd_pdrop=0.0, resid_pdrop=0.0)
    hf = transformers.GPTJForCausalLM(cfg).eval()
    ids = np.random.RandomState(4).randint(0, 256, (2, 16))
    np.testing.assert_allclose(_ours_logits("tiny-gptj", hf, ids),
                               _hf_logits(hf, ids), atol=2e-3, rtol=2e-3)


@pytest.mark.slow
def test_hf_import_gptneo():
    """GPT-Neo: alternating global/LOCAL (sliding-window) attention, and
    UNSCALED attention scores — seq 16 > window 8 so the local mask
    actually binds in this test."""
    transformers = pytest.importorskip("transformers")
    __import__("torch").manual_seed(17)
    cfg = transformers.GPTNeoConfig(
        vocab_size=256, max_position_embeddings=128, hidden_size=64,
        num_layers=2, num_heads=4, intermediate_size=256,
        attention_types=[[["global", "local"], 1]], window_size=8,
        attention_dropout=0.0, embed_dropout=0.0, resid_dropout=0.0)
    hf = transformers.GPTNeoForCausalLM(cfg).eval()
    ids = np.random.RandomState(6).randint(0, 256, (2, 16))
    np.testing.assert_allclose(_ours_logits("tiny-gptneo", hf, ids),
                               _hf_logits(hf, ids), atol=2e-3, rtol=2e-3)
    # generation parity (decode path windows over true positions)
    engine = init_inference("tiny-gptneo", dtype=jnp.float32,
                            max_out_tokens=128, hf_model=hf)
    import torch

    with torch.no_grad():
        want = hf.generate(torch.tensor(ids[:1, :12]), max_new_tokens=6,
                           do_sample=False).numpy()[:, 12:]
    got = np.asarray(engine.generate(ids[:1, :12], max_new_tokens=6))
    np.testing.assert_array_equal(got[:, :6], want)


@pytest.mark.slow
def test_hf_import_clip_text():
    """CLIP text encoder (the Stable Diffusion text tower the reference's
    clip container injects): pre-LN CAUSAL encoder with quick_gelu.
    Hidden-state parity via the tied-embedding inversion (bert pattern)."""
    transformers = pytest.importorskip("transformers")
    __import__("torch").manual_seed(18)
    cfg = transformers.CLIPTextConfig(
        vocab_size=256, hidden_size=64, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=77, attention_dropout=0.0,
        hidden_act="quick_gelu")
    hf = transformers.CLIPTextModel(cfg).eval()
    ids = np.random.RandomState(7).randint(0, 256, (2, 16))
    np.testing.assert_allclose(_ours_logits("tiny-clip", hf, ids),
                               _encoder_expected(hf, ids),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.slow
def test_hf_import_gptneox():
    """GPT-NeoX: fused per-head qkv interleave + parallel residual with its
    own post-attention LN + 25% rotate-half rotary."""
    transformers = pytest.importorskip("transformers")
    __import__("torch").manual_seed(15)
    cfg = transformers.GPTNeoXConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=256, rotary_pct=0.25,
        max_position_embeddings=128, use_parallel_residual=True,
        hidden_dropout=0.0, attention_dropout=0.0)
    hf = transformers.GPTNeoXForCausalLM(cfg).eval()
    ids = np.random.RandomState(5).randint(0, 256, (2, 16))
    np.testing.assert_allclose(_ours_logits("tiny-gptneox", hf, ids),
                               _hf_logits(hf, ids), atol=2e-3, rtol=2e-3)


def _encoder_expected(hf, ids, **kw):
    """HF encoder last_hidden_state mapped through the shared embedding —
    the linear map our tied 'logits' apply, so hidden parity <=> logit
    parity."""
    import torch

    with torch.no_grad():
        hidden = hf(torch.tensor(ids), **kw).last_hidden_state
        E = hf.get_input_embeddings().weight
        return (hidden @ E.T).float().numpy()


@pytest.mark.slow
def test_hf_import_bert():
    """BERT: the NON-CAUSAL post-LN encoder path end to end — bidirectional
    attention, token-type embeddings, LN after each residual, no final
    norm."""
    transformers = pytest.importorskip("transformers")
    torch = __import__("torch")
    torch.manual_seed(16)
    cfg = transformers.BertConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=256,
        max_position_embeddings=128, type_vocab_size=2, hidden_act="gelu",
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    hf = transformers.BertModel(cfg).eval()
    ids = np.random.RandomState(6).randint(0, 256, (2, 16))
    ours = _ours_logits("tiny-bert", hf, ids)
    np.testing.assert_allclose(ours, _encoder_expected(hf, ids),
                               atol=2e-3, rtol=2e-3)
    # bidirectionality probe: flipping a LATER token must change EARLIER
    # positions' outputs (a causal model would leave them untouched)
    ids2 = ids.copy()
    ids2[:, -1] = (ids2[:, -1] + 1) % 256
    ours2 = _ours_logits("tiny-bert", hf, ids2)
    assert np.abs(ours2[:, 0] - ours[:, 0]).max() > 1e-4
    # token types flow through
    engine = init_inference("tiny-bert", dtype=jnp.float32,
                            max_out_tokens=128, hf_model=hf)
    tti = np.zeros_like(ids)
    tti[:, 8:] = 1
    from deepspeed_tpu.models.transformer import forward as fwd

    got = np.asarray(fwd(engine.params, jnp.asarray(ids), engine.model.config,
                         token_type_ids=jnp.asarray(tti))[0])
    np.testing.assert_allclose(
        got, _encoder_expected(hf, ids, token_type_ids=torch.tensor(tti)),
        atol=2e-3, rtol=2e-3)


@pytest.mark.slow
def test_hf_import_distilbert():
    transformers = pytest.importorskip("transformers")
    __import__("torch").manual_seed(17)
    cfg = transformers.DistilBertConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, hidden_dim=256,
        max_position_embeddings=128, dropout=0.0, attention_dropout=0.0,
        activation="gelu", sinusoidal_pos_embds=False)
    hf = transformers.DistilBertModel(cfg).eval()
    ids = np.random.RandomState(7).randint(0, 256, (2, 16))
    np.testing.assert_allclose(_ours_logits("tiny-distilbert", hf, ids),
                               _encoder_expected(hf, ids),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.slow
def test_hf_import_bloom():
    transformers = pytest.importorskip("transformers")
    __import__("torch").manual_seed(13)
    cfg = transformers.BloomConfig(
        vocab_size=256, hidden_size=64, n_layer=2, n_head=4,
        attention_dropout=0.0, hidden_dropout=0.0)
    hf = transformers.BloomForCausalLM(cfg).eval()
    ids = np.random.RandomState(3).randint(0, 256, (2, 16))
    np.testing.assert_allclose(_ours_logits("tiny-bloom", hf, ids),
                               _hf_logits(hf, ids), atol=2e-3, rtol=2e-3)


def test_hf_import_generate_end_to_end():
    transformers = pytest.importorskip("transformers")
    __import__("torch").manual_seed(14)
    cfg = transformers.GPT2Config(
        vocab_size=256, n_positions=128, n_embd=64, n_layer=2, n_head=4,
        attn_pdrop=0.0, embd_pdrop=0.0, resid_pdrop=0.0)
    hf = transformers.GPT2LMHeadModel(cfg).eval()
    engine = init_inference("tiny", dtype=jnp.float32, max_out_tokens=128,
                            hf_model=hf)
    prompt = np.random.RandomState(4).randint(0, 256, (1, 8))
    ours = np.asarray(engine.generate(prompt, max_new_tokens=6))

    import torch

    with torch.no_grad():
        hf_out = hf.generate(torch.tensor(prompt), max_new_tokens=6,
                             do_sample=False, pad_token_id=0)
    np.testing.assert_array_equal(ours[0], hf_out[0, 8:].numpy())


def test_checkpoint_roundtrip_into_inference(tmp_path):
    """save_16bit_model output loads into init_inference (reference
    checkpoint-sharded load path, test_checkpoint_sharding.py analog)."""
    model = create_model("tiny", dtype=jnp.float32)
    engine, *_ = deepspeed_tpu.initialize(
        model=model,
        config={"train_micro_batch_size_per_gpu": 2,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0}})
    path = engine.save_16bit_model(str(tmp_path), "weights.npz")
    from deepspeed_tpu.parallel import mesh as mesh_mod

    mesh_mod.reset_mesh()
    inf = init_inference("tiny", dtype=jnp.float32, max_out_tokens=128,
                         checkpoint=path)
    ids = np.arange(8)[None]
    got = np.asarray(inf.forward(ids))
    want = np.asarray(jax.jit(lambda p, b: model.apply(p, b)[0])(
        engine.params, {"input_ids": jnp.asarray(ids)}))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_top_p_restricts_support():
    from deepspeed_tpu.inference.engine import _sample

    # peaked distribution: token 0 has ~92% mass; top_p=0.5 must always pick it
    logits = jnp.asarray([[5.0, 2.0, 1.0, 0.0]])
    picks = {int(_sample(logits, jax.random.PRNGKey(i), 1.0, 0, 0.5)[0])
             for i in range(20)}
    assert picks == {0}
    # near-flat top-3: the nucleus must contain MORE than the argmax
    # (regression: a max-instead-of-min cutoff made any top_p<1 greedy)
    logits = jnp.asarray([[2.0, 1.9, 1.8, -5.0]])
    picks = {int(_sample(logits, jax.random.PRNGKey(i), 1.0, 0, 0.95)[0])
             for i in range(200)}
    assert picks == {0, 1, 2}, picks
    # top_p=1.0 with high temperature samples beyond token 0
    picks = {int(_sample(logits, jax.random.PRNGKey(i), 5.0, 0, 1.0)[0])
             for i in range(50)}
    assert len(picks) > 1


@pytest.mark.slow
def test_generate_top_p_runs():
    engine = init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)
    out = engine.generate(np.arange(8)[None], max_new_tokens=5,
                          temperature=0.8, top_p=0.9, seed=1)
    assert np.asarray(out).shape == (1, 5)


def test_top_p_zero_is_greedy():
    from deepspeed_tpu.inference.engine import _sample

    logits = jnp.asarray([[5.0, 2.0, 1.0, 0.0]])
    picks = {int(_sample(logits, jax.random.PRNGKey(i), 5.0, 0, 0.0)[0])
             for i in range(20)}
    assert picks == {0}


class TestMoEInference:
    """MoE expert-parallel inference (reference DeepSpeedMoEInference,
    ops/transformer/inference/moe_inference.py:160, and the ep groups built
    in inference/engine.py:274): gate+dispatch run inside prefill/decode,
    expert banks shard over the mesh 'expert' axis, and cache-mode routing
    is exact (no capacity drops, no RTS)."""

    def test_forward_matches_training_model(self):
        """Parity: InferenceEngine.forward == the training model's apply on
        a moe-tiny (same cache=None code path, same routing)."""
        engine = init_inference("moe-tiny", dtype=jnp.float32,
                                max_out_tokens=128)
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 250, (2, 16)),
                          jnp.int32)
        got = engine.forward(ids)
        want, _ = engine.model.apply(engine.params, {"input_ids": ids})
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("overrides", [
        {},                                           # top-2 default
        {"moe_top_k": 1},                             # switch-style top-1
        {"moe_use_residual": True},                   # PR-MoE
    ])
    def test_cache_logits_match_full_forward(self, overrides):
        """Teacher-forced KV-cache correctness on an MoE model: prefill +
        decode steps reproduce full-forward logits at every position.
        moe_drop_tokens=False so the no-cache oracle routes exactly too."""
        from deepspeed_tpu.inference import kv_cache
        from deepspeed_tpu.models.transformer import forward

        engine = init_inference("moe-tiny", dtype=jnp.float32,
                                max_out_tokens=128, moe_drop_tokens=False,
                                **overrides)
        cfg = engine.model.config
        ids = jnp.asarray(np.random.RandomState(1).randint(0, 250, (2, 18)),
                          jnp.int32)
        S_prompt = 10
        full, _, _ = forward(engine.params, ids, cfg)
        cache = kv_cache.init_cache(cfg, 2, 128, jnp.float32)
        valid = jnp.zeros((2, 128), jnp.int32).at[:, :S_prompt].set(1)
        lg, cache, _ = forward(engine.params, ids[:, :S_prompt], cfg,
                               attention_mask=valid, cache=cache, start_pos=0)
        np.testing.assert_allclose(np.asarray(lg),
                                   np.asarray(full[:, :S_prompt]),
                                   atol=1e-4, rtol=1e-4)
        for pos in range(S_prompt, 18):
            valid = valid.at[:, pos].set(1)
            lg, cache, _ = forward(engine.params, ids[:, pos:pos + 1], cfg,
                                   attention_mask=valid, cache=cache,
                                   start_pos=pos)
            np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                       np.asarray(full[:, pos]),
                                       atol=1e-4, rtol=1e-4,
                                       err_msg=f"decode step at pos {pos}")

    def test_prefill_nodrop_even_when_training_drops(self):
        """Cache-mode routing must ignore the model's training-time capacity
        limit: an over-capacity prompt token still gets its expert output
        (full forward with drops != prefill without — they must differ on a
        config where drops actually occur, and prefill must equal the
        no-drop oracle)."""
        import dataclasses

        from deepspeed_tpu.inference import kv_cache
        from deepspeed_tpu.models.transformer import forward

        engine = init_inference("moe-tiny", dtype=jnp.float32,
                                max_out_tokens=128,
                                moe_capacity_factor=0.25, moe_min_capacity=1)
        cfg = engine.model.config
        ids = jnp.asarray(np.random.RandomState(2).randint(0, 250, (2, 32)),
                          jnp.int32)
        cache = kv_cache.init_cache(cfg, 2, 128, jnp.float32)
        valid = jnp.zeros((2, 128), jnp.int32).at[:, :32].set(1)
        prefill, _, _ = forward(engine.params, ids, cfg,
                                attention_mask=valid, cache=cache,
                                start_pos=0)
        nodrop_cfg = dataclasses.replace(cfg, moe_drop_tokens=False)
        oracle, _, _ = forward(engine.params, ids, nodrop_cfg)
        np.testing.assert_allclose(np.asarray(prefill), np.asarray(oracle),
                                   atol=1e-4, rtol=1e-4)
        dropped, _, _ = forward(engine.params, ids, cfg)   # training path
        assert np.abs(np.asarray(prefill) - np.asarray(dropped)).max() > 1e-3

    @pytest.mark.slow
    def test_generate_greedy_matches_naive(self):
        engine = init_inference("moe-tiny", dtype=jnp.float32,
                                max_out_tokens=128, moe_drop_tokens=False)
        prompt = np.random.RandomState(3).randint(0, 250, (2, 10))
        got = np.asarray(engine.generate(prompt, max_new_tokens=6))
        want = np.asarray(naive_greedy(engine.model, engine.params,
                                       prompt, 6))
        np.testing.assert_array_equal(got, want)

    def test_ep2_generation_matches_single(self, devices8):
        """Expert-parallel generate == single-device generate, and the
        expert banks really shard over the 'expert' axis."""
        from deepspeed_tpu.parallel import mesh as mesh_mod

        prompt = np.arange(10)[None] % 250
        e1 = init_inference("moe-tiny", dtype=jnp.float32, max_out_tokens=128)
        t1 = e1.generate(prompt, max_new_tokens=6)
        mesh_mod.reset_mesh()
        e2 = init_inference("moe-tiny", dtype=jnp.float32, max_out_tokens=128,
                            expert_parallel=2)
        spec = e2.param_shardings["layers"]["mlp"]["w_up"].spec
        assert mesh_mod.EXPERT_AXIS in spec, spec
        e2.params = jax.tree.map(
            lambda x, s: jax.device_put(np.asarray(x), s), e1.params,
            e2.param_shardings)
        t2 = e2.generate(prompt, max_new_tokens=6)
        np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))

    @pytest.mark.slow
    def test_ep2_tp2_generation_matches_single(self, devices8):
        """ep=2 x tp=2 over 4 devices — the MoE analog of auto-TP."""
        from deepspeed_tpu.parallel import mesh as mesh_mod

        prompt = np.arange(12)[None] % 250
        e1 = init_inference("moe-tiny", dtype=jnp.float32, max_out_tokens=128)
        t1 = e1.generate(prompt, max_new_tokens=5)
        mesh_mod.reset_mesh()
        e2 = init_inference("moe-tiny", dtype=jnp.float32, max_out_tokens=128,
                            expert_parallel=2, tensor_parallel=2)
        e2.params = jax.tree.map(
            lambda x, s: jax.device_put(np.asarray(x), s), e1.params,
            e2.param_shardings)
        t2 = e2.generate(prompt, max_new_tokens=5)
        np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))

    def test_ep_validation(self):
        with pytest.raises(ValueError, match="requires an MoE"):
            init_inference("tiny", expert_parallel=2)
        with pytest.raises(ValueError, match="must divide"):
            init_inference("moe-tiny", expert_parallel=3)

    def test_ep2_int8_expert_banks_sharded(self, devices8):
        """Quantized MoE load must keep the expert banks SHARDED over the
        'expert' axis (regression: tp==1 gating replicated them, losing
        exactly the EP memory scaling)."""
        from deepspeed_tpu.parallel import mesh as mesh_mod

        mesh_mod.reset_mesh()
        e = init_inference("moe-tiny", dtype="int8", max_out_tokens=128,
                           expert_parallel=2, moe_drop_tokens=False)
        w_up = e.params["layers"]["mlp"]["w_up"]
        assert "expert" in getattr(w_up.sharding, "spec", ())
        # really partitioned: each device holds half the experts
        shard_elems = w_up.addressable_shards[0].data.size
        assert shard_elems == w_up.size // 2
        out = e.generate(np.arange(8)[None] % 250, max_new_tokens=3)
        assert np.asarray(out).shape == (1, 3)

    @pytest.mark.slow
    def test_moe_composes_with_int8_weights(self):
        """MoE + weight-only int8: dense projections quantize, expert banks
        stay dense (quantize_model_weights contract) and generation stays
        self-consistent."""
        e = init_inference("moe-tiny", dtype="int8", max_out_tokens=128,
                           moe_drop_tokens=False)
        # expert banks dense, attention projections quantized
        l = e.params["layers"]
        assert isinstance(l["attn"]["wq"], dict) and "q8" in l["attn"]["wq"]
        assert not isinstance(l["mlp"]["w_up"], dict)
        prompt = np.random.RandomState(5).randint(0, 250, (1, 10))
        out = np.asarray(e.generate(prompt, max_new_tokens=5))
        # greedy self-consistency against the engine's own full forward
        ids = jnp.asarray(prompt, jnp.int32)
        for i in range(3):
            logits = e.forward(ids)
            nxt = int(jnp.argmax(logits[0, -1].astype(jnp.float32)))
            assert nxt == out[0, i]
            ids = jnp.concatenate([ids, jnp.asarray([[nxt]], jnp.int32)], 1)


class TestW8A8:
    """dtype='w8a8': int8 weights + dynamic int8 activation quantization on
    decode-shaped GEMMs (s8xs8 MXU). Storage identical to int8 weight-only;
    only the decode compute path differs."""

    def test_config_normalisation_and_validation(self):
        from deepspeed_tpu.inference.engine import InferenceConfig

        cfg = InferenceConfig(dtype="w8a8")
        assert cfg.quantize_bits == 8 and cfg.quantize_activations
        assert cfg.dtype == jnp.bfloat16
        cfg4 = InferenceConfig(dtype="w4a8")
        assert cfg4.quantize_bits == 4 and cfg4.quantize_activations
        with pytest.raises(ValueError, match="W8A8/W4A8"):
            InferenceConfig(dtype="bf16", quantize_activations=True)

    @pytest.mark.slow
    def test_generate_engine_path(self):
        """Same weights served w8a8 vs int8 weight-only through the engine.
        On CPU the s8 kernel gate never engages (kernel numerics are pinned
        in tests/kernels TestInt8A8Matmul), so the two engines must produce
        IDENTICAL tokens here — this checks the engine plumbing (config
        threading, per-engine isolation), not the kernel."""
        e_int8 = init_inference("tiny", dtype="int8", max_out_tokens=128)
        e_a8 = init_inference("tiny", dtype="w8a8", max_out_tokens=128)
        assert e_a8.model.config.a8_decode is True
        assert e_int8.model.config.a8_decode is False   # per-engine config
        e_a8.params = e_int8.params
        prompt = np.random.RandomState(0).randint(0, 250, (1, 12))
        out8 = np.asarray(e_int8.generate(prompt, max_new_tokens=4))
        outa = np.asarray(e_a8.generate(prompt, max_new_tokens=4))
        np.testing.assert_array_equal(out8, outa)

    def test_w8a8_tp_rejected(self, devices8):
        with pytest.raises(NotImplementedError, match="W8A8"):
            init_inference("tiny-llama", dtype="w8a8", tensor_parallel=2)


@pytest.mark.slow
class TestInt8WeightOnly:
    """Weight-only quantized inference (reference init_inference dtype=int8
    kernel-injection mode): storage halves, logits stay close, generate is
    self-consistent (greedy == its own full-forward argmax)."""

    def test_logits_close_and_storage_halved(self):
        from deepspeed_tpu.models.core import tree_bytes

        e16 = init_inference("tiny", dtype=jnp.bfloat16, max_out_tokens=128)
        e8 = init_inference("tiny", dtype="int8", max_out_tokens=128)
        assert e8.config.quantize_bits == 8
        # same underlying weights for a fair numeric comparison
        from deepspeed_tpu.models.transformer import quantize_model_weights

        e8.params = jax.jit(quantize_model_weights)(e16.params)

        prompt = np.random.RandomState(0).randint(0, 250, size=(2, 16))
        l16 = np.asarray(e16.forward(prompt), np.float32)
        l8 = np.asarray(e8.forward(prompt), np.float32)
        cos = (l16.ravel() @ l8.ravel()) / (
            np.linalg.norm(l16) * np.linalg.norm(l8))
        assert cos > 0.99, f"cosine {cos}"

        def matmul_bytes(tree):
            return sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(tree))

        w16 = matmul_bytes(e16.params["layers"]["attn"])
        w8 = matmul_bytes(e8.params["layers"]["attn"])
        assert w8 < 0.62 * w16          # int8 + scales + bf16 biases

    def test_generate_self_consistent(self):
        engine = init_inference("tiny", dtype="int8", max_out_tokens=128)
        prompt = np.random.RandomState(1).randint(0, 250, size=(1, 12))
        got = np.asarray(engine.generate(prompt, max_new_tokens=6))
        ids = jnp.asarray(prompt, jnp.int32)
        for i in range(6):
            logits, _ = engine.model.apply(engine.params, {"input_ids": ids})
            best = int(np.asarray(logits[0, -1], np.float32).argmax())
            assert got[0, i] == best, f"step {i}"
            ids = jnp.concatenate([ids, jnp.asarray([[best]], jnp.int32)], 1)

    @pytest.mark.slow
    def test_int8_tp_matches_single(self, devices8):
        """Quantized auto-TP: q8/scale leaves shard per the dense weight's
        TP rules; tp=2 generation matches tp=1 (same quantized weights)."""
        from deepspeed_tpu.parallel import mesh as mesh_mod

        prompt = np.arange(10)[None]
        e1 = init_inference("tiny-llama", dtype="int8", max_out_tokens=128)
        t1 = np.asarray(e1.generate(prompt, max_new_tokens=6))
        mesh_mod.reset_mesh()
        e2 = init_inference("tiny-llama", dtype="int8", tensor_parallel=2,
                            max_out_tokens=128)
        e2.params = jax.tree.map(
            lambda x, s: jax.device_put(np.asarray(x), s), e1.params,
            e2._quantized_shardings())
        t2 = np.asarray(e2.generate(prompt, max_new_tokens=6))
        np.testing.assert_array_equal(t1, t2)
        # the packed weight really is sharded over the model axis
        wq = e2.params["layers"]["attn"]["wq"]["q8"]
        assert "model" in str(wq.sharding.spec)


@pytest.mark.slow
class TestInt4WeightOnly:
    """4-bit weight-only inference (reference 4-bit groupwise quantizer
    kernels, csrc/includes/quantization_utils.h:468): storage quarters,
    logits stay close, generate is self-consistent."""

    def test_logits_close_and_storage_quartered(self):
        e16 = init_inference("tiny", dtype=jnp.bfloat16, max_out_tokens=128)
        e4 = init_inference("tiny", dtype="int4", max_out_tokens=128,
                            config={"quantize_groups": 32, "dtype": "int4"})
        assert e4.config.quantize_bits == 4
        from deepspeed_tpu.models.transformer import quantize_model_weights

        e4.params = jax.jit(lambda p: quantize_model_weights(
            p, bits=4, group_size=32))(e16.params)

        prompt = np.random.RandomState(0).randint(0, 250, size=(2, 16))
        l16 = np.asarray(e16.forward(prompt), np.float32)
        l4 = np.asarray(e4.forward(prompt), np.float32)
        cos = (l16.ravel() @ l4.ravel()) / (
            np.linalg.norm(l16) * np.linalg.norm(l4))
        assert cos > 0.97, f"cosine {cos}"

        def matmul_bytes(tree):
            return sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(tree))

        w16 = matmul_bytes(e16.params["layers"]["attn"])
        w4 = matmul_bytes(e4.params["layers"]["attn"])
        assert w4 < 0.40 * w16          # packed nibbles + scales + biases

    def test_generate_self_consistent(self):
        engine = init_inference("tiny", dtype="int4", max_out_tokens=128)
        prompt = np.random.RandomState(1).randint(0, 250, size=(1, 12))
        got = np.asarray(engine.generate(prompt, max_new_tokens=6))
        ids = jnp.asarray(prompt, jnp.int32)
        for i in range(6):
            logits, _ = engine.model.apply(engine.params, {"input_ids": ids})
            best = int(np.asarray(logits[0, -1], np.float32).argmax())
            assert got[0, i] == best, f"step {i}"
            ids = jnp.concatenate([ids, jnp.asarray([[best]], jnp.int32)], 1)

    @pytest.mark.slow
    def test_int4_tp_matches_single(self, devices8):
        from deepspeed_tpu.parallel import mesh as mesh_mod

        prompt = np.arange(10)[None]
        e1 = init_inference("tiny-llama", dtype="int4", max_out_tokens=128)
        t1 = np.asarray(e1.generate(prompt, max_new_tokens=6))
        mesh_mod.reset_mesh()
        e2 = init_inference("tiny-llama", dtype="int4", tensor_parallel=2,
                            max_out_tokens=128)
        e2.params = jax.tree.map(
            lambda x, s: jax.device_put(np.asarray(x), s), e1.params,
            e2._quantized_shardings())
        t2 = np.asarray(e2.generate(prompt, max_new_tokens=6))
        np.testing.assert_array_equal(t1, t2)

    def test_groups_require_int4(self):
        from deepspeed_tpu.inference.engine import InferenceConfig

        with pytest.raises(ValueError, match="int4"):
            InferenceConfig(dtype="int8", quantize_groups=64)


def test_tp_world_reads_ambient_mesh(devices8):
    """The quantized-GEMM kernel gate must see the mesh context the engines
    trace under — NOT the module-global mesh the inference engine never sets
    (regression: a global-mesh read returned 1 under tp=2). The probe reads
    the framework's ambient tracker (public API — the deprecated
    pxla.thread_resources read is gone); outside any framework mesh context
    it must fail SAFE by disabling the single-shard kernel route."""
    import numpy as _np
    from jax.sharding import Mesh

    from deepspeed_tpu.models.transformer import _tp_world
    from deepspeed_tpu.parallel import mesh as mesh_mod

    assert _tp_world() > 1  # no ambient mesh: kernel route disabled (safe)
    mesh = Mesh(_np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    with mesh_mod.ambient(mesh):
        assert _tp_world() == 2
    tp1 = Mesh(_np.array(jax.devices()).reshape(8, 1), ("data", "model"))
    with mesh_mod.ambient(tp1):
        assert _tp_world() == 1
    assert _tp_world() > 1

"""Named model presets — the families the reference targets with injection
policies (module_inject/containers/{gpt2,opt,bloom,gptj,gptneo,gptneox,llama}
and the BASELINE configs: GPT-2 125M, OPT-1.3B, Llama-7B, BLOOM-7B), and
since then OLMoE (``olmoe-1b-7b``, ``tiny-olmoe``) and Solar Open 2
(``solar-open2-250b``, ``tiny-solar-open2``: layers of two kinds, three
gated delta-rule linear-attention layers to one gated NoPE GQA layer, a
shared expert beside sigmoid-routed ones; served through ``init_serving``,
whole or as one chip's share of its experts via ``moe_experts_held``) and
Nemotron 3 Super (``nemotron-3-super-120b-a12b``, ``tiny-nemotron-3-super``:
layers that are ONE function each, a Mamba-2 state-space mixer, a GQA
attention mixer or an FFN of experts in a latent; served the same way) and
Phi-4-mini-flash-reasoning (``phi-4-mini-flash-reasoning``,
``tiny-phi4flash``: a self-decoder of Mamba-1 and window layers, one full
layer, and a cross-decoder of gated memory units and layers that read the
full layer's pages, all with differential attention; served the same way,
and only so) and Ouro (``ouro-2.6b``, ``tiny-ouro``: a looped stack, the
same layers run ``loop_passes`` times with a norm behind each half of a
layer, the final norm closing every pass and an exit gate reading it; served
the same way, one pool of pages a (pass, layer)) and LongCat-Flash
(``longcat-flash-chat``, ``tiny-longcat-flash``: every layer a DOUBLE layer,
two latent-attention (MLA) sublayers each with a dense FFN and one
shortcut-connected FFN of routed and zero-computation experts across both;
served the same way, one pool of latents a sublayer and no keys or
values) and LFM2 (``lfm2-8b-a1b``, ``tiny-lfm2``: gated short-convolution
layers whose only state is a tail of two rows, grouped-query layers with a
norm a head, two leading DENSE layers under the same mixers as the expert
layers; served the same way, a stack of ``layer_runs``)."""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp

from .core import Model
from .transformer import TransformerConfig, build_model


def phi4flash_runs(num_layers: int) -> tuple:
    """The published order of the ``phi4flash`` family (``mb_per_layer`` 2)
    as ``layer_runs``: Mamba-1 on the even layers of the self-decoder and a
    window layer on its odd ones, the full layer at ``L/2 + 1``, then gated
    memory units on the even layers and cross layers on the odd ones. 32
    layers: 8 x (mamba1, swa), (mamba1, full), 7 x (gmu, cross)."""
    if num_layers % 4 or num_layers < 8:
        raise ValueError(f"phi4flash: {num_layers} layers; the published "
                         "rule wants a depth divisible by 4, of 8 or more")
    half = num_layers // 2
    return ((("mamba1", "swa"), half // 2), (("mamba1", "full"), 1),
            (("gmu", "cross"), half // 2 - 1))


def lfm2_runs(layer_types, num_dense_layers: int) -> tuple:
    """The published ``layer_types`` of the ``lfm2_moe`` family (``"conv"`` a
    gated short convolution, ``"full_attention"`` grouped-query attention)
    and its ``num_dense_layers`` (so many leading layers have a dense FFN,
    every other one experts) as ``layer_runs``: each layer's kind, the equal
    neighbours merged into runs of one-layer periods and what repeats
    folded into periods. The first 12 of the published 24: ``(conv_dense)
    x 2, (attn, conv, conv, conv) x 2, (attn, conv) x 1``."""
    mixers = {"conv": "conv", "full_attention": "attn"}
    if set(layer_types) - set(mixers):
        raise NotImplementedError(
            f"layer_types {sorted(set(layer_types) - set(mixers))}: the "
            f"lfm2 family has {sorted(mixers)}")
    kinds = [mixers[t] + ("_dense" if i < num_dense_layers else "")
             for i, t in enumerate(layer_types)]
    runs, alone, i = [], [], 0
    while i < len(kinds):
        # the longest stretch from here on that is whole repeats of a period
        # (the shortest period wins a tie: a run of equal layers is ones)
        best = ((kinds[i],), 1)
        for width in range(1, (len(kinds) - i) // 2 + 1):
            period = tuple(kinds[i:i + width])
            n = 1
            while tuple(kinds[i + n * width:i + (n + 1) * width]) == period:
                n += 1
            if n > 1 and n * width > best[1] * len(best[0]):
                best = (period, n)
        if best[1] == 1:        # repeats nothing: one period with what
            alone.append(kinds[i])      # follows, up to the next repeat
        else:
            runs += [(tuple(alone), 1)] * bool(alone) + [best]
            alone = []
        i += best[1] * len(best[0])
    return tuple(runs + [(tuple(alone), 1)] * bool(alone))


def mimo_runs(hybrid_layer_pattern, moe_layer_freq, num_layers: int) -> tuple:
    """The published ``hybrid_layer_pattern`` (0 a full-attention layer, 1 a
    window layer) and ``moe_layer_freq`` (0 a dense FFN, 1 experts) of the
    ``mimo_v2_flash`` family as ``layer_runs``. A period ends with its full
    layer, and equal neighbours merge into one run: all 48 layers are
    ``(full_dense) x 1, (swa x 4, full) x 1, (swa x 5, full) x 7``. Fewer
    layers are layer 0 and the pattern's LAST ``num_layers - 1``: the
    leading dense layer once and whole periods of five window layers and a
    full one, as every period behind the first is (7 layers: ``(full_dense)
    x 1, (swa x 5, full) x 1``)."""
    n = len(hybrid_layer_pattern)
    layers = [0, *range(n - (num_layers - 1), n)][:min(num_layers, n)]
    kinds = [("swa" if hybrid_layer_pattern[i] else "full")
             + ("" if moe_layer_freq[i] else "_dense") for i in layers]
    if "swa_dense" in kinds:
        raise NotImplementedError("a window layer over a dense FFN has no "
                                  "layer kind")
    periods, period = [], []
    for kind in kinds:
        period.append(kind)
        if kind.startswith("full"):
            periods.append(tuple(period))
            period = []
    periods += [tuple(period)] * bool(period)
    runs = []
    for period in periods:
        if runs and runs[-1][0] == period:
            runs[-1] = (period, runs[-1][1] + 1)
        else:
            runs.append((period, 1))
    return tuple(runs)


def nemotron_h_pattern(hybrid_override_pattern: str) -> tuple:
    """The published ``hybrid_override_pattern`` of the ``nemotron_h``
    family, a character a layer, as a ``layer_pattern``: ``M`` a Mamba-2
    mixer, ``*`` an attention mixer, ``E`` an FFN of experts. ``-`` (a dense
    FFN beside expert ones: FFN kinds that differ by layer) has no form yet."""
    kinds = {"M": "mamba2_mixer", "*": "attn_mixer", "E": "ffn"}
    if set(hybrid_override_pattern) - set(kinds):
        raise NotImplementedError(
            f"hybrid_override_pattern {hybrid_override_pattern!r}: only "
            f"{sorted(kinds)} have a layer kind (a dense '-' layer beside "
            "expert layers needs FFN kinds that differ by layer)")
    return tuple(kinds[c] for c in hybrid_override_pattern)


# family defaults: (norm, position, activation, tie)
_FAMILIES: Dict[str, Dict[str, Any]] = {
    "gpt2": dict(norm="layernorm", position="learned", activation="gelu",
                 tie_embeddings=True),
    "opt": dict(norm="layernorm", position="learned", activation="relu",
                tie_embeddings=True),
    "bloom": dict(norm="layernorm", position="alibi", activation="gelu",
                  tie_embeddings=True, embed_norm=True),
    "gptj": dict(norm="layernorm", position="rope", activation="gelu",
                 tie_embeddings=False, parallel_residual=True,
                 lm_head_bias=True),
    "gptneox": dict(norm="layernorm", position="rope", activation="gelu",
                    tie_embeddings=False, parallel_residual=True),
    # GPT-Neo: alternating global/local (sliding-window 256) attention,
    # UNSCALED attention scores (HF GPTNeoSelfAttention has no 1/sqrt(d))
    "gptneo": dict(norm="layernorm", position="learned", activation="gelu",
                   tie_embeddings=True, attention_scale=1.0,
                   attention_layers=("global", "local"),
                   attention_window=256),
    # CLIP text encoder (reference containers/clip.py HFCLIPLayerPolicy —
    # the Stable Diffusion text tower): pre-LN, CAUSAL attention,
    # quick_gelu; tie_embeddings so logits = hidden @ E^T (the encoder
    # surface — parity tests invert it)
    "clip": dict(norm="layernorm", position="learned",
                 activation="quick_gelu", tie_embeddings=True, causal=True),
    "bert": dict(norm="layernorm", norm_position="post", position="learned",
                 activation="gelu-exact", tie_embeddings=True, causal=False,
                 embed_norm=True, type_vocab_size=2, final_norm=False,
                 norm_eps=1e-12),
    "distilbert": dict(norm="layernorm", norm_position="post",
                       position="learned", activation="gelu-exact",
                       tie_embeddings=True, causal=False, embed_norm=True,
                       final_norm=False, norm_eps=1e-12),
    "llama": dict(norm="rmsnorm", position="rope", activation="swiglu",
                  tie_embeddings=False, norm_eps=1e-6),
    "mistral": dict(norm="rmsnorm", position="rope", activation="swiglu",
                    tie_embeddings=False, norm_eps=1e-6),
    # OLMoE (Muennighoff et al. 2024, arXiv:2409.02060; HF model_type
    # "olmoe"): a Llama block with RMSNorm over the whole q and k
    # projections, and in every layer 8 of 64 SwiGLU experts, the router's
    # softmax weights used as they are (norm_topk_prob false); no shared or
    # residual expert, no biases
    "olmoe": dict(norm="rmsnorm", position="rope", activation="swiglu",
                  tie_embeddings=False, norm_eps=1e-5, qk_norm=True,
                  moe_num_experts=64, moe_top_k=8,
                  moe_norm_topk_prob=False),
    # Solar Open 2 (upstage/Solar-Open2-250B config.json, model_type
    # "solar_open2"): periods of four layers, a softmax layer FIRST
    # (gqa_layers 0, 4, 8, ...: GQA, no positional term at all, a sigmoid
    # output gate) and then three gated delta-rule layers with per-channel
    # decay (ops/kda.py; negative eigenvalues allowed: beta up to 2); every
    # layer's FFN is sigmoid-scored top-k experts (a choice-only bias, the
    # chosen weights renormalised) plus one shared expert; no biases
    "solar-open2": dict(norm="rmsnorm", position="none", activation="swiglu",
                        tie_embeddings=False, norm_eps=1e-5, attn_gate=True,
                        layer_pattern=("attn", "kda", "kda", "kda"),
                        moe_score_func="sigmoid", moe_router_bias=True,
                        moe_norm_topk_prob=True,
                        moe_shared_experts=1),
    # NVIDIA Nemotron 3 Super (nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16
    # config.json, model_type "nemotron_h"): every layer is x + f(norm(x))
    # with ONE f, named by a character of hybrid_override_pattern (the size
    # preset's layer_pattern): a Mamba-2 state-space mixer, GQA attention
    # with no positional term at all, or LatentMoE: sigmoid scores with a
    # choice-only bias, the chosen weights renormalised and times
    # routed_scaling_factor, relu-squared experts that are not gated and run
    # in a latent, one shared expert on the full width; no biases
    # LongCat-Flash (meituan-longcat/LongCat-Flash-Chat config.json,
    # model_type "longcat_flash"; arXiv:2509.01322): every layer is the
    # "shortcut" double layer (``transformer.SUBLAYERS``): two latent-attention
    # sublayers (rope on the last rotary_dim values of a query head and on
    # one key a token, theta 1e7, neighbours paired), each with a dense
    # SwiGLU FFN, and ONE FFN of experts read at the first sublayer and joined
    # behind the second: softmax scores over routed AND zero-computation
    # (identity) experts, a choice-only bias, the chosen weights times
    # routed_scaling_factor and NOT renormalised; no biases
    "longcat-flash": dict(norm="rmsnorm", position="rope",
                          activation="swiglu", tie_embeddings=False,
                          norm_eps=1e-5, rope_theta=1e7,
                          layer_pattern=("shortcut",),
                          moe_score_func="softmax", moe_router_bias=True,
                          moe_norm_topk_prob=False, moe_routed_scale=6.0),
    # LiquidAI/LFM2-8B-A1B config.json (model_type "lfm2_moe"): every layer
    # x + mixer(rms(x)) then x + ffn(rms(x)); the mixer a gated short
    # convolution (conv_L_cache taps, no bias: ``transformer.
    # _shortconv_mixer``) or grouped-query attention with an RMSNorm over
    # each head's values of q and k before rope (theta 1e6); the first
    # num_dense_layers layers a dense SwiGLU FFN, the others SwiGLU experts
    # by sigmoid scores with a choice-only bias, renormalised, times
    # routed_scaling_factor 1, no shared expert; the head tied; no biases.
    # The order of its layers is ``lfm2_runs`` of the size preset's
    # ``layer_types`` (a prefix of it at fewer layers) and num_dense_layers
    "lfm2": dict(norm="rmsnorm", position="rope", activation="swiglu",
                 tie_embeddings=True, norm_eps=1e-5, rope_theta=1e6,
                 qk_norm="head", moe_score_func="sigmoid",
                 moe_router_bias=True, moe_norm_topk_prob=True,
                 moe_routed_scale=1.0),
    # XiaomiMiMo/MiMo-V2-Flash config.json (model_type "mimo_v2_flash"):
    # pre-RMSNorm halves, no bias; softmax attention in two forms, full
    # layers (rope base rope_theta) and window layers of their own count of
    # key-value heads, their own rope base (swa_rope_theta) and a learned
    # sink a query head (add_swa_attention_sink_bias); keys wider than
    # values, rope on the first partial_rotary_factor of a key's values,
    # halves rotated, values times attention_value_scale; a leading dense
    # SwiGLU layer, then SwiGLU experts by sigmoid scores with a choice-only
    # bias (noaux_tc, one group), renormalised, no shared expert; the head
    # untied. The order of its layers is ``mimo_runs`` of the size preset's
    # ``hybrid_layer_pattern`` and ``moe_layer_freq``
    "mimo-v2-flash": dict(norm="rmsnorm", position="rope",
                          activation="swiglu", tie_embeddings=False,
                          norm_eps=1e-5, rope_theta=5e6,
                          window_rope_theta=1e4, window_sink=True,
                          value_scale=0.707, moe_score_func="sigmoid",
                          moe_router_bias=True, moe_norm_topk_prob=True,
                          moe_routed_scale=1.0),
    "nemotron-h": dict(norm="rmsnorm", position="none", activation="relu2",
                       tie_embeddings=False, norm_eps=1e-5,
                       moe_score_func="sigmoid", moe_router_bias=True,
                       moe_norm_topk_prob=True, moe_shared_experts=1,
                       mamba_conv_taps=4),
    # microsoft/Phi-4-mini-flash-reasoning (SambaY, arXiv:2507.06607): no
    # positional term anywhere; the Mamba-1 sizes are the family's
    # convention (state 16, 4 taps, expand 2, step rank hidden / 16)
    "phi4flash": dict(norm="layernorm", position="none", activation="swiglu",
                      tie_embeddings=True, norm_eps=1e-5, diff_attn=True,
                      mamba_state_size=16, mamba_conv_taps=4,
                      mamba_expand=2),
    # ByteDance/Ouro-2.6B (LoopLM, arXiv:2510.25741; model_type "ouro"): a
    # Llama block with a second RMSNorm BEHIND each half (sandwich), the
    # whole stack run total_ut_steps times with the final norm closing every
    # pass, an exit gate a pass; no biases but the gate's
    "ouro": dict(norm="rmsnorm", norm_position="sandwich", position="rope",
                 activation="swiglu", tie_embeddings=False, norm_eps=1e-6,
                 rope_theta=1e6, loop_passes=4, loop_exit_threshold=1.0),
}

# size presets: hidden, layers, heads, kv_heads, vocab, max_seq
_SIZES: Dict[str, Dict[str, Any]] = {
    "gpt2-125m": dict(family="gpt2", hidden_size=768, num_layers=12, num_heads=12,
                      vocab_size=50257, max_seq_len=1024),
    "gpt2-350m": dict(family="gpt2", hidden_size=1024, num_layers=24, num_heads=16,
                      vocab_size=50257, max_seq_len=1024),
    "gpt2-1.3b": dict(family="gpt2", hidden_size=2048, num_layers=24, num_heads=32,
                      vocab_size=50257, max_seq_len=2048),
    "opt-125m": dict(family="opt", hidden_size=768, num_layers=12, num_heads=12,
                     vocab_size=50272, max_seq_len=2048),
    "opt-1.3b": dict(family="opt", hidden_size=2048, num_layers=24, num_heads=32,
                     vocab_size=50272, max_seq_len=2048),
    "opt-6.7b": dict(family="opt", hidden_size=4096, num_layers=32, num_heads=32,
                     vocab_size=50272, max_seq_len=2048),
    "llama-7b": dict(family="llama", hidden_size=4096, num_layers=32, num_heads=32,
                     vocab_size=32000, max_seq_len=4096, ffn_hidden_size=11008),
    "llama-13b": dict(family="llama", hidden_size=5120, num_layers=40, num_heads=40,
                      vocab_size=32000, max_seq_len=4096, ffn_hidden_size=13824),
    "bloom-7b": dict(family="bloom", hidden_size=4096, num_layers=30, num_heads=32,
                     vocab_size=250880, max_seq_len=2048),
    "gptj-6b": dict(family="gptj", hidden_size=4096, num_layers=28,
                    num_heads=16, vocab_size=50400, max_seq_len=2048,
                    rotary_dim=64),
    "gptneo-1.3b": dict(family="gptneo", hidden_size=2048, num_layers=24,
                        num_heads=16, vocab_size=50257, max_seq_len=2048),
    "gptneo-2.7b": dict(family="gptneo", hidden_size=2560, num_layers=32,
                        num_heads=20, vocab_size=50257, max_seq_len=2048),
    "gptneox-20b": dict(family="gptneox", hidden_size=6144, num_layers=44,
                        num_heads=64, vocab_size=50432, max_seq_len=2048,
                        rotary_dim=24),    # rotary_pct 0.25 of head_dim 96
    "bert-base": dict(family="bert", hidden_size=768, num_layers=12,
                      num_heads=12, vocab_size=30522, max_seq_len=512),
    "bert-large": dict(family="bert", hidden_size=1024, num_layers=24,
                       num_heads=16, vocab_size=30522, max_seq_len=512),
    "distilbert-base": dict(family="distilbert", hidden_size=768,
                            num_layers=6, num_heads=12, vocab_size=30522,
                            max_seq_len=512),
    # tiny debug models (reference tests/unit/simple_model.py scale)
    "tiny": dict(family="gpt2", hidden_size=64, num_layers=2, num_heads=4,
                 vocab_size=256, max_seq_len=128),
    "tiny-llama": dict(family="llama", hidden_size=64, num_layers=2, num_heads=4,
                       num_kv_heads=2, vocab_size=256, max_seq_len=128,
                       ffn_hidden_size=128),
    "tiny-opt": dict(family="opt", hidden_size=64, num_layers=2, num_heads=4,
                     vocab_size=256, max_seq_len=128),
    "tiny-bloom": dict(family="bloom", hidden_size=64, num_layers=2, num_heads=4,
                       vocab_size=256, max_seq_len=128),
    "tiny-gptj": dict(family="gptj", hidden_size=64, num_layers=2,
                      num_heads=4, vocab_size=256, max_seq_len=128,
                      rotary_dim=8),
    "tiny-gptneox": dict(family="gptneox", hidden_size=64, num_layers=2,
                         num_heads=4, vocab_size=256, max_seq_len=128,
                         rotary_dim=4),
    "tiny-gptneo": dict(family="gptneo", hidden_size=64, num_layers=2,
                        num_heads=4, vocab_size=256, max_seq_len=128,
                        attention_window=8),
    "tiny-clip": dict(family="clip", hidden_size=64, num_layers=2,
                      num_heads=4, vocab_size=256, max_seq_len=77),
    "clip-vit-l-text": dict(family="clip", hidden_size=768, num_layers=12,
                            num_heads=12, ffn_hidden_size=3072,
                            vocab_size=49408, max_seq_len=77),
    "tiny-bert": dict(family="bert", hidden_size=64, num_layers=2,
                      num_heads=4, vocab_size=256, max_seq_len=128),
    "tiny-distilbert": dict(family="distilbert", hidden_size=64,
                            num_layers=2, num_heads=4, vocab_size=256,
                            max_seq_len=128),
    # allenai/OLMoE-1B-7B-0125-Instruct config.json (1.3B active of 6.9B)
    "olmoe-1b-7b": dict(family="olmoe", hidden_size=2048, num_layers=16,
                        num_heads=16, num_kv_heads=16, ffn_hidden_size=1024,
                        vocab_size=50304, max_seq_len=4096),
    # 3 experts a token: neither 1 nor 2, so a fall-through to a top-1 or
    # top-2 plan cannot pass the tests
    "tiny-olmoe": dict(family="olmoe", hidden_size=64, num_layers=2,
                       num_heads=4, num_kv_heads=4, ffn_hidden_size=32,
                       vocab_size=256, max_seq_len=128, moe_num_experts=8,
                       moe_top_k=3),
    # upstage/Solar-Open2-250B config.json (250B, 15B active). The low-rank
    # pairs' rank (= the head size) is the published layer's, not a key of
    # the config; max_seq_len bounds nothing (no position table)
    "solar-open2-250b": dict(family="solar-open2", hidden_size=4096,
                             num_layers=48, num_heads=64, num_kv_heads=8,
                             head_size=128, ffn_hidden_size=1280,
                             dense_ffn_hidden_size=10240,
                             kda_num_heads=64, kda_head_dim=128,
                             kda_gate_rank=128, moe_num_experts=320,
                             moe_top_k=8, vocab_size=196608,
                             max_seq_len=1048576),
    # heads wider than hidden / heads, as in the real one (4 x 32 on 64)
    "tiny-solar-open2": dict(family="solar-open2", hidden_size=64,
                             num_layers=4, num_heads=4, num_kv_heads=2,
                             head_size=32, ffn_hidden_size=32,
                             dense_ffn_hidden_size=128,
                             kda_num_heads=4, kda_head_dim=16,
                             kda_gate_rank=16, moe_num_experts=16,
                             moe_top_k=3, vocab_size=256, max_seq_len=128),
    # nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 config.json (120.7B, 12B
    # active): 88 layers, 40 Mamba-2, 40 LatentMoE, 8 attention; num_layers
    # counts the SOURCE's layers, and fewer run a prefix of the pattern. The
    # multi-token-prediction module (num_nextn_predict_layers 1) is a
    # drafter beside the model and is not built
    "nemotron-3-super-120b-a12b": dict(
        family="nemotron-h", hidden_size=4096, num_layers=88,
        layer_pattern=nemotron_h_pattern(
            "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
        num_heads=32, num_kv_heads=2, head_size=128,
        mamba_num_heads=128, mamba_head_dim=64, mamba_n_groups=8,
        mamba_state_size=128, ffn_hidden_size=2688, moe_latent_size=1024,
        moe_shared_ffn_hidden_size=5376, moe_num_experts=512, moe_top_k=22,
        moe_routed_scale=5.0, vocab_size=131072, max_seq_len=262144),
    # the same first 11 layers (5 mixers of each recurrent kind's state, one
    # attention layer, 5 expert layers); 4 heads a group, so pairs of heads
    # lie inside one; 3 experts a token
    "tiny-nemotron-3-super": dict(
        family="nemotron-h", hidden_size=64, num_layers=11,
        layer_pattern=nemotron_h_pattern("MEMEMEM*EME"),
        num_heads=4, num_kv_heads=2, head_size=32,
        mamba_num_heads=8, mamba_head_dim=16, mamba_n_groups=2,
        mamba_state_size=16, ffn_hidden_size=48, moe_latent_size=32,
        moe_shared_ffn_hidden_size=96, moe_num_experts=16, moe_top_k=3,
        moe_routed_scale=5.0, vocab_size=256, max_seq_len=128),
    # microsoft/Phi-4-mini-flash-reasoning config.json (3.85 B): 40 query
    # and 20 key-value heads of 64, window 512; the order of its layers is
    # ``phi4flash_runs`` of the depth; max_seq_len bounds nothing
    "phi-4-mini-flash-reasoning": dict(
        family="phi4flash", hidden_size=2560, num_layers=32, num_heads=40,
        num_kv_heads=20, ffn_hidden_size=10240, attention_window=512,
        vocab_size=200064, max_seq_len=262144),
    # 8 layers: two (mamba1, swa) periods, (mamba1, full), (gmu, cross); a
    # window shorter than a chunk can be, heads in two groups of pairs
    "tiny-phi4flash": dict(
        family="phi4flash", hidden_size=64, num_layers=8, num_heads=8,
        num_kv_heads=4, ffn_hidden_size=96, attention_window=8,
        vocab_size=256, max_seq_len=128),
    # ByteDance/Ouro-2.6B config.json (2.67 B, run four times over)
    "ouro-2.6b": dict(family="ouro", hidden_size=2048, num_layers=48,
                      num_heads=16, num_kv_heads=16, head_size=128,
                      ffn_hidden_size=5632, vocab_size=49152,
                      max_seq_len=65536),
    # three passes: neither one nor two, so a stack run once, or a pass that
    # reads the pool before or behind its own, cannot pass the tests
    "tiny-ouro": dict(family="ouro", hidden_size=64, num_layers=4,
                      num_heads=4, num_kv_heads=4, head_size=16,
                      ffn_hidden_size=96, vocab_size=256, max_seq_len=128,
                      loop_passes=3),
    # meituan-longcat/LongCat-Flash-Chat config.json (560 B, 18.6-31.3 B
    # active): 28 double layers; ffn_hidden_size is an EXPERT's width, the
    # dense FFNs' is dense_ffn_hidden_size (the source's ffn_hidden_size)
    "longcat-flash-chat": dict(
        family="longcat-flash", hidden_size=6144, num_layers=28,
        num_heads=64, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, rotary_dim=64, v_head_dim=128,
        dense_ffn_hidden_size=12288, ffn_hidden_size=2048,
        moe_num_experts=512, moe_zero_experts=256, moe_top_k=12,
        vocab_size=131072, max_seq_len=131072),
    # 5 experts a token of 16 routed and 8 zero-computation ones; values
    # narrower than keys, as in the real one
    "tiny-longcat-flash": dict(
        family="longcat-flash", hidden_size=64, num_layers=2, num_heads=4,
        q_lora_rank=32, kv_lora_rank=24, qk_nope_head_dim=16, rotary_dim=8,
        v_head_dim=12, dense_ffn_hidden_size=128, ffn_hidden_size=32,
        moe_num_experts=16, moe_zero_experts=8, moe_top_k=5,
        vocab_size=256, max_seq_len=128),
    # LiquidAI/LFM2-8B-A1B config.json (8.34 B, 1.5 B active): 18 short
    # convolutions and 6 attention layers (32 query heads over 8 key-value
    # heads of 64); ffn_hidden_size is an EXPERT's width (the source's
    # moe_intermediate_size), the two leading dense FFNs'
    # dense_ffn_hidden_size (its intermediate_size)
    "lfm2-8b-a1b": dict(
        family="lfm2", hidden_size=2048, num_layers=24,
        layer_types=("conv", "conv", "full_attention", "conv", "conv", "conv",
                     "full_attention", "conv", "conv", "conv",
                     "full_attention", "conv", "conv", "conv",
                     "full_attention", "conv", "conv", "conv",
                     "full_attention", "conv", "conv", "full_attention",
                     "conv", "conv"),
        num_dense_layers=2, num_heads=32, num_kv_heads=8,
        dense_ffn_hidden_size=7168, ffn_hidden_size=1792, shortconv_taps=3,
        moe_num_experts=32, moe_top_k=4, vocab_size=65536,
        max_seq_len=128000),
    # 7 layers in three runs: (conv_dense) x 2, (attn, conv, conv) x 1 and
    # (attn, conv) x 1, so a convolution's pool index is not its place
    # among its kind; 3 experts a token of 8
    "tiny-lfm2": dict(
        family="lfm2", hidden_size=64, num_layers=7,
        layer_types=("conv", "conv", "full_attention", "conv", "conv",
                     "full_attention", "conv"),
        num_dense_layers=2, num_heads=4, num_kv_heads=2,
        dense_ffn_hidden_size=128, ffn_hidden_size=32, shortconv_taps=3,
        moe_num_experts=8, moe_top_k=3, vocab_size=256, max_seq_len=128),
    # XiaomiMiMo/MiMo-V2-Flash config.json (309 B, 15 B active): 9 full
    # layers of 4 key-value heads and 39 window layers (128 keys) of 8, 64
    # query heads everywhere, keys 192 and values 128 wide, rope on the
    # first int(192 x 0.334) = 64 values; ffn_hidden_size is an EXPERT's
    # width (the source's moe_intermediate_size), the leading dense FFN's
    # dense_ffn_hidden_size (its intermediate_size)
    "mimo-v2-flash": dict(
        family="mimo-v2-flash", hidden_size=4096, num_layers=48,
        hybrid_layer_pattern=(0, 1, 1, 1, 1, 0) + (1, 1, 1, 1, 1, 0) * 7,
        moe_layer_freq=(0,) + (1,) * 47,
        num_heads=64, num_kv_heads=4, window_kv_heads=8, head_size=192,
        v_head_dim=128, partial_rotary_factor=0.334, attention_window=128,
        dense_ffn_hidden_size=16384, ffn_hidden_size=2048,
        moe_num_experts=256, moe_top_k=8, vocab_size=152576,
        max_seq_len=262144),
    # 8 layers in three runs: (full_dense), (swa, swa, full) and (swa, swa,
    # swa, full), so a full layer's pool is not its place among its kind; 2
    # key-value heads in a full layer and 4 in a window layer, keys 24 and
    # values 16 wide, rope on 8; 3 experts a token of 16
    "tiny-mimo-v2-flash": dict(
        family="mimo-v2-flash", hidden_size=64, num_layers=8,
        hybrid_layer_pattern=(0, 1, 1, 0, 1, 1, 1, 0),
        moe_layer_freq=(0,) + (1,) * 7,
        num_heads=8, num_kv_heads=2, window_kv_heads=4, head_size=24,
        v_head_dim=16, rotary_dim=8, attention_window=8,
        dense_ffn_hidden_size=128, ffn_hidden_size=32, moe_num_experts=16,
        moe_top_k=3, vocab_size=256, max_seq_len=128),
    # GShard/Switch-style 8-expert GPT (BASELINE tracked config #4)
    "moe-tiny": dict(family="gpt2", hidden_size=64, num_layers=2, num_heads=4,
                     vocab_size=256, max_seq_len=128, moe_num_experts=8),
    "moe-gpt-125m-8e": dict(family="gpt2", hidden_size=768, num_layers=12,
                            num_heads=12, vocab_size=50257, max_seq_len=1024,
                            moe_num_experts=8),
    "moe-gpt-350m-8e": dict(family="gpt2", hidden_size=1024, num_layers=24,
                            num_heads=16, vocab_size=50257, max_seq_len=1024,
                            moe_num_experts=8),
}


def transformer_config(preset: str, dtype=jnp.float32, **overrides) -> TransformerConfig:
    if preset not in _SIZES:
        raise ValueError(f"unknown preset '{preset}' (known: {sorted(_SIZES)})")
    spec = dict(_SIZES[preset])
    family = spec.pop("family")
    kwargs = dict(_FAMILIES[family])
    kwargs.update(spec)
    kwargs.update(overrides)
    if family == "phi4flash":
        kwargs.setdefault("layer_runs", phi4flash_runs(kwargs["num_layers"]))
    if family == "lfm2":
        types, dense = kwargs.pop("layer_types"), kwargs.pop("num_dense_layers")
        kwargs.setdefault("layer_runs", lfm2_runs(
            tuple(types)[:kwargs["num_layers"]], dense))
    if family == "mimo-v2-flash":
        factor = kwargs.pop("partial_rotary_factor", None)
        if factor is not None:
            kwargs.setdefault("rotary_dim", int(kwargs["head_size"] * factor))
        pattern = kwargs.pop("hybrid_layer_pattern")
        freq = kwargs.pop("moe_layer_freq")
        kwargs.setdefault("layer_runs", mimo_runs(
            tuple(pattern), tuple(freq), kwargs["num_layers"]))
    return TransformerConfig(dtype=dtype, **kwargs)


def create_model(preset: str, dtype=jnp.float32, **overrides) -> Model:
    cfg = transformer_config(preset, dtype=dtype, **overrides)
    return build_model(cfg, name=preset)


def available_presets():
    return sorted(_SIZES)

"""Unified decoder-only transformer (GPT-2 / Llama families).

The reference implements transformer compute three times (fused training kernel
``csrc/transformer/``, inference kernels ``csrc/transformer/inference/``, and
per-architecture injected modules). Here ONE functional decoder covers both
families through config switches:

  GPT-2 family : LayerNorm(+bias), learned positions, GELU MLP, tied embeddings
  Llama family : RMSNorm, RoPE, SwiGLU MLP, GQA (n_kv_heads < n_heads)

Layers are **stacked and scanned** (`lax.scan` over a leading layer dim) so XLA
compiles one layer program regardless of depth — the TPU-idiomatic equivalent
of the reference's per-layer kernel launch loop — with `jax.checkpoint` for
activation rematerialisation (reference: activation_checkpointing/).

Attention is pluggable: the engine can swap in the Pallas flash-attention
kernel (ops/flash_attention.py) via ``attention_impl``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .core import (EMBED, EXPERT, HEADS, KV_HEADS, LAYERS, MLP, Model, SEQ,
                   VOCAB)


# a layer's kind -> (its mixer, a key of ``MIXERS``, or None; whether an FFN
# follows it: WHICH one, a key of ``FFNS``, is ``ffn_of``'s word).
# "attn" and "kda" are a whole block, norm, mixer, add, norm, FFN, add; the
# others are ONE function under one norm and one add, for a family whose
# layers are a mixer alone or an FFN alone. "swa", "full" and "cross" are the
# softmax mixer in three forms (``AttnForm``: a window's ring of pages, pages
# of its own, another layer's pages), "mamba1" the selective scan and "gmu" a
# gate on the value the last "mamba1" layer handed on. "shortcut" is a DOUBLE
# layer (``SUBLAYERS``, ``_sublayers_forward``): the "mla" mixer and a dense
# FFN twice over, and the model's expert FFN once across both. "conv" is the
# gated short convolution (``_shortconv_mixer``) under the model's FFN.
# Where the second entry is a key of ``FFNS`` the kind's layers have THAT
# FFN whatever the model's other layers have: "conv_dense", "attn_dense" and
# "full_dense" are a family's leading dense layers (its num_dense_layers /
# first_k_dense_replace), ``dense_ffn_hidden_size`` wide, under the mixer
# that its expert layers have too. Kinds that share a mixer share its cache
# pools: ``layer_places`` says which pool is a layer's
LAYER_KINDS = {"attn": ("attn", True), "kda": ("kda", True),
               "attn_mixer": ("attn", False),
               "mamba2_mixer": ("mamba2", False),
               "ffn": (None, True),
               "mamba1": ("mamba1", True), "swa": ("swa", True),
               "full": ("full", True), "cross": ("cross", True),
               "gmu": ("gmu", True), "shortcut": ("mla", True),
               "conv": ("shortconv", True),
               "conv_dense": ("shortconv", "dense"),
               "attn_dense": ("attn", "dense"),
               "full_dense": ("full", "dense")}
# a kind whose layer is SUBLAYERS[kind] sublayers in sequence, norm, mixer,
# add, norm, dense FFN (``dense_ffn_hidden_size`` wide), add, each with its
# own weights (a leading axis of that length on the leaves ``ln1``, the
# mixer's, ``ln2`` and ``dense``) and its own pool in the arena, ``2 * layer
# + sublayer``; the layer's expert FFN reads the FIRST sublayer's normed FFN
# input and what it gives joins the stream behind the LAST sublayer's FFN
# (a shortcut-connected MoE, arXiv:2509.01322). Every other kind: one
SUBLAYERS = {"shortcut": 2}
# taps of the "kda" mixer's depthwise convolution over time (the published
# short_conv_kernel_size of the one family that has the mixer)
KDA_CONV_TAPS = 4


@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None      # None => MHA
    ffn_hidden_size: Optional[int] = None   # None => 4*hidden (gelu) / llama rule (swiglu)
    max_seq_len: int = 1024
    norm: str = "layernorm"                 # layernorm | rmsnorm
    norm_position: str = "pre"              # pre | post | sandwich (post:
    #   BERT-family encoders — LN applied AFTER each residual add, no final
    #   norm; sandwich: pre, and a norm of its own BEHIND each half, on what
    #   the mixer and the FFN add to the residual: x + R2(mixer(R1(x))))
    position: str = "learned"               # learned | rope | alibi | none
    #   (none: no positional term anywhere; causality alone orders tokens)
    embed_norm: bool = False                # LayerNorm after embedding (BLOOM)
    activation: str = "gelu"                # gelu | relu | relu2 | swiglu
    #   (relu2: relu squared, not gated)
    tie_embeddings: bool = True
    causal: bool = True                     # False: bidirectional encoder
    parallel_residual: bool = False         # x + attn(ln1(x)) + mlp(ln2(x))
    # GPT-Neo family: per-layer attention pattern ('global'|'local', cycled
    # over layers) with a sliding window for local layers; non-empty routes
    # attention through the windowed jnp path (the flash kernel has no
    # window operand; training and the dense cache only). attention_scale:
    # None => 1/sqrt(head_dim); GPT-Neo uses unscaled scores (1.0).
    attention_layers: tuple = ()
    attention_window: int = 256             # keys a query of a window layer
    #   sees, its own included: the 'local' layers of ``attention_layers``
    #   and the "swa" layers of ``layer_runs``, which the serving layer runs
    #   through the paged kernels over a ring of pages a slot, a slot a row
    # what a family's WINDOW layers ("swa") have that its full layers do not
    # (``attn_shape`` is the one reader): their own count of key-value
    # heads (None: ``num_kv_heads``), their own rope base (None:
    # ``rope_theta``) and a learned SINK a query head, a float that takes
    # its share of the softmax's mass and adds no value (arXiv:2309.17453)
    window_kv_heads: Optional[int] = None
    window_rope_theta: Optional[float] = None
    window_sink: bool = False
    value_scale: float = 1.0                # v = value_scale * (h W_v), in
    #   every softmax layer: the cached value is the scaled one (the
    #   published attention_value_scale)
    attention_scale: Optional[float] = None
    #   (GPT-J/GPT-NeoX; GPT-J shares one LN — its import aliases ln2=ln1)
    rotary_dim: Optional[int] = None        # partial rotary: rope on the
    #   first rotary_dim dims of each head (GPT-J/NeoX), None => full head
    type_vocab_size: int = 0                # >0: token-type embeddings (BERT)
    final_norm: bool = True                 # False: no norm after the last
    #   layer (post-LN encoders norm inside the block)
    lm_head_bias: bool = False              # untied head carries a bias (GPT-J)
    norm_eps: float = 1e-5
    qk_norm: Any = False                    # True: RMSNorm over the WHOLE q
    #   and the whole k projection (N*D / K*D wide), before the split into
    #   heads and before rope (OLMoE, OLMo-2); "head": over each HEAD's D
    #   values, one weight of D shared by the heads, before rope (LFM2):
    #   part of the architecture, not a knob
    rope_theta: float = 10000.0
    dropout: float = 0.0              # embed/attn-out/mlp-out dropout rate.
    #   Applied only when dropout_enabled (the TrainEngine sets it; eval and
    #   inference run dropout-free). Attention-PROBABILITY dropout is not
    #   implemented (it would live inside the flash kernel) — these are the
    #   residual-path sites of the reference transformer kernel.
    dropout_enabled: bool = False     # draws derive from activations (no rng
    #   arg in loss_fn): deterministic per (params, batch), varies per step
    dtype: Any = jnp.float32                # compute/param dtype
    scan_unroll: int = 1                    # lax.scan unroll factor over layers
    pld_enabled: bool = False               # progressive layer drop: batch
    #   carries 'pld_theta'; layer i keeps with p = 1-(1-theta)*(i+1)/L
    # random-LTD (reference data_routing/basic_layer.py:14): listed layers run
    # on a random ltd_keep-token subset; dropped tokens skip the layer
    ltd_enabled: bool = False
    ltd_layers: Optional[Tuple[int, ...]] = None  # None => all but first/last
    ltd_keep: int = 0                       # tokens kept per LTD layer; STATIC
    #   (the schedule changes it only at quantised boundaries, so each value
    #   is one extra jit trace — same discipline as the seqlen curriculum)
    act_quant_bits: int = 0           # >0: fake-quantize layer input
    #   activations (QAT; reference QuantAct) — the engine sets it from the
    #   compression schedule; STATIC (one re-jit per boundary)
    remat: bool = False                     # activation checkpointing over layers
    remat_policy: str = "full"              # full | dots (save matmul outputs
    #   and the flash forward kernel's o and lse, recompute the rest — reference
    #   partition_activations analog) | offload-dots: resolve_remat_policy
    attention_impl: Optional[Callable] = None  # None => platform default
    #   (Pallas flash attention on TPU, jnp elsewhere); callable overrides
    # MoE (reference deepspeed/moe): >0 experts turns every layer's FFN into a
    # gated expert bank with top_k routing + load-balancing aux loss
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_norm_topk_prob: bool = True   # the k > 1 chosen router
    #   probabilities are renormalised to sum to 1 (GShard top-2, Mixtral);
    #   the published norm_topk_prob, false for OLMoE. A single expert's
    #   weight is always its raw probability (Switch top-1)
    moe_capacity_factor: float = 1.25
    moe_min_capacity: int = 4
    moe_aux_loss_coef: float = 0.01
    moe_drop_tokens: bool = True      # False => infinite capacity (C = T)
    moe_use_rts: bool = False         # random token selection (top-1 only)
    moe_dispatch: str = "sparse"      # 'sparse' scatter/gather dispatch or
    #   'einsum' dense one-hot (the GShard/reference formulation; fallback)
    moe_use_residual: bool = False    # PR-MoE: dense residual MLP + learned
    #   2-way coefficient mix (reference moe/layer.py use_residual)
    moe_experts_held: int = 0         # >0: this chip's share of an
    #   expert-parallel deployment. The router keeps moe_num_experts
    #   outputs and chooses over all of them; the expert stack holds the
    #   FIRST moe_experts_held, and an assignment to an absent expert is
    #   dropped before the expert matmuls (parallel/moe._grouped_experts):
    #   the layer computes its own experts' part of the result. 0 = all
    moe_shared_experts: int = 0       # a dense FFN of the model's
    #   activation and of width moe_shared_experts * ffn_hidden_size (or
    #   moe_shared_ffn_hidden_size) added to every token, unweighted
    moe_shared_ffn_hidden_size: Optional[int] = None
    moe_latent_size: int = 0          # >0: the routed experts run in a
    #   latent of this width: one projection hidden -> latent before them
    #   and one back after their weighted sum (experts latent x ffn); the
    #   router and the shared expert read the full width
    moe_routed_scale: float = 1.0     # the chosen weights, after their
    #   renormalisation, times this (the published routed_scaling_factor)
    moe_score_func: str = "softmax"   # softmax | sigmoid: the router's scores
    #   over all experts, in float32
    moe_router_bias: bool = False     # a per-expert bias added to the scores
    #   for the CHOICE of experts only, never to their weights
    moe_zero_experts: int = 0         # router outputs BEHIND the
    #   moe_num_experts routed ones that have no matrices: a token that
    #   chooses one gets its weight times the token itself (zero-computation
    #   experts of type identity). The router is moe_num_experts +
    #   moe_zero_experts wide and chooses over all of them
    dense_ffn_hidden_size: Optional[int] = None   # the width of a family's
    #   dense FFNs where its ``ffn_hidden_size`` is an expert's: the two of a
    #   "shortcut" layer read it, and a family's leading dense layers (the
    #   kinds "conv_dense" and "attn_dense")
    # layers of more than one kind. ``layer_pattern`` names the KIND
    # (``LAYER_KINDS``) of each layer of one period, cycled over the depth
    # (num_layers a multiple of it); a pattern LONGER than the depth is a
    # family's whole published order, of which the first num_layers are run
    # as one period; () = every layer "attn". "attn": softmax attention as
    # configured above, then the FFN; "kda": the gated delta rule with
    # per-channel decay (ops/kda.py), kda_num_heads heads of kda_head_dim
    # for keys and values alike, a depthwise causal convolution of
    # KDA_CONV_TAPS taps and SiLU on q, k and v, decay and output gate
    # through low-rank pairs of kda_gate_rank, beta = 2 * sigmoid (negative
    # eigenvalues allowed), then the FFN; "attn_mixer", "mamba2_mixer" and
    # "ffn": one function alone (the Mamba-2 state-space mixer:
    # ops/mamba2.py, ``_mamba2_mixer``). Each kind keeps its own stacked
    # parameter tree and each mixer its own cache entry (pages / a state
    # and a convolution tail)
    layer_pattern: tuple = ()
    # a stack that is no one period: RUNS of equal periods, ((kinds of a
    # period, ...), periods) each, in order; ``layer_pattern`` is then the
    # whole order they spell and ``forward`` scans each run (paged mode
    # only). The kinds that read what ANOTHER layer made ("cross" the pages
    # of the "full" layer before it, "gmu" the value the last "mamba1" layer
    # handed on) and a window's ring ("swa") exist only in such a stack
    layer_runs: tuple = ()
    diff_attn: bool = False           # differential attention
    #   (arXiv:2410.05258) in the "swa", "full" and "cross" layers: adjacent
    #   heads pair, two softmax maps a pair over values twice as wide,
    #   subtracted (``_diff_pairs``)
    mamba_expand: int = 2             # "mamba1": inner width / hidden (its
    #   step's projection has rank ceil(hidden / 16), the family's rule)
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    mamba_n_groups: int = 1
    mamba_state_size: int = 0
    mamba_conv_taps: int = 4
    shortconv_taps: int = 3           # the "shortconv" mixer's depthwise
    #   convolution over time (the published conv_L_cache): a sequence keeps
    #   the last ``taps - 1`` rows of its input and no other state
    head_size: Optional[int] = None         # None => hidden_size / num_heads
    attn_gate: bool = False                 # y = (attn * sigmoid(x W_g)) W_o
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_gate_rank: int = 0
    # a looped stack (arXiv:2510.25741): the SAME layers run ``loop_passes``
    # times, the final norm closing every pass and feeding the next; a query
    # of pass t sees the keys of pass t alone, so the serving arena keeps one
    # pool a (pass, layer) (``Step.pool_index``). An exit gate reads every
    # pass's output, ``lam_t = sigmoid(h_t w + b)``, and the head reads the
    # first pass whose cumulated exit probability reaches
    # ``loop_exit_threshold`` (the last at 1 and over: nothing is selected).
    # 1 pass is every other model
    loop_passes: int = 1
    loop_exit_threshold: float = 1.0
    # latent attention (MLA, arXiv:2405.04434; the "mla" mixer,
    # ``_latent_mixer``): queries through a pair of rank ``q_lora_rank``,
    # heads of ``qk_nope_head_dim`` + ``rotary_dim`` values of which the LAST
    # ``rotary_dim`` (the published qk_rope_head_dim) are roped; keys and
    # values expanded from ONE latent of ``kv_lora_rank`` a token, beside one
    # roped key of ``rotary_dim`` shared by all heads; values
    # ``v_head_dim`` wide. The arena keeps the latent and the roped key, no
    # key or value of any head (``Mixer.keeps`` "latent")
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    v_head_dim: int = 0               # a head's VALUES, in the latent mixer
    #   and in every softmax mixer alike (0: as wide as its keys, head_dim)
    a8_decode: bool = False           # W8A8: decode-shaped int8 weight sites
    #   quantize the activation row too and ride the MXU's s8xs8 path
    #   (set by InferenceEngine from InferenceConfig.quantize_activations;
    #   docs/quant_decode_analysis.md)

    def __post_init__(self):
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.ffn_hidden_size is None:
            if self.activation == "swiglu":
                self.ffn_hidden_size = int(8 * self.hidden_size / 3 / 64 + 0.999) * 64
            else:
                self.ffn_hidden_size = 4 * self.hidden_size
        assert self.head_size or self.hidden_size % self.num_heads == 0
        assert self.num_heads % self.num_kv_heads == 0
        assert self.num_heads % (self.window_kv_heads or 1) == 0
        self.layer_runs = tuple((tuple(kinds), int(n))
                                for kinds, n in self.layer_runs)
        if self.layer_runs:
            self.layer_pattern = tuple(
                kind for kinds, n in self.layer_runs for kind in kinds * n)
            assert len(self.layer_pattern) == self.num_layers, \
                (self.layer_runs, self.num_layers)
        self.layer_pattern = tuple(self.layer_pattern)[:self.num_layers]
        if self.layer_pattern:
            assert set(self.layer_pattern) <= set(LAYER_KINDS), \
                self.layer_pattern
            assert self.num_layers % len(self.layer_pattern) == 0
            # a layer's weights and its expert bank lie at its place among
            # its KIND, its mixer's cache pools at its place among the
            # layers of that MIXER, whatever their kinds (``layer_places``)
            mixers = {LAYER_KINDS[k][0] for k in self.layer_pattern}
            assert sum(1 for m in mixers if m and MIXERS[m].state) <= 1, \
                "the state pools hold one kind of recurrent state"
            records = [MIXERS[m] for m in mixers if m]
            if any(r.keeps not in ("pages", "latent") and not r.state
                   for r in records):
                # what addresses a row's ring by its slot, or reads what
                # another layer made, is built by ``_run_layers`` alone
                assert self.layer_runs, \
                    f"{self.layer_pattern}: kinds of a stack of layer_runs"
        if self.moe_experts_held:
            assert 0 < self.moe_experts_held <= self.moe_num_experts
        assert self.norm_position in ("pre", "post", "sandwich"), \
            self.norm_position
        if self.norm_position == "sandwich":
            assert not self.parallel_residual, \
                "a norm behind each half has no parallel-residual form"
        if set(self.layer_pattern) & set(SUBLAYERS):
            assert (self.norm, self.norm_position, self.activation) == (
                "rmsnorm", "pre", "swiglu") and not self.parallel_residual \
                and self.moe_num_experts and self.dense_ffn_hidden_size, \
                "a layer of sublayers is pre-RMSNorm SwiGLU halves under " \
                "an expert FFN, its dense FFNs dense_ffn_hidden_size wide"
        if any(LAYER_KINDS[k][1] == "dense" for k in self.layer_pattern):
            assert self.dense_ffn_hidden_size, \
                "a dense layer's FFN is dense_ffn_hidden_size wide"
        assert self.qk_norm in (False, True, "head"), self.qk_norm
        if self.diff_attn:
            assert self.v_head_dim in (0, self.head_dim) and not (
                self.window_kv_heads or self.window_sink), \
                "differential pairs fold keys and values of ONE width, the " \
                "same heads in every form"
        if self.moe_zero_experts:
            assert self.moe_num_experts and not self.moe_latent_size, \
                "zero-computation experts stand behind routed ones of the " \
                "model's own width"
        assert self.loop_passes >= 1
        if self.loop_passes > 1:
            # a pass ends in the final norm; pools are a (pass, layer) of
            # softmax layers, and a recurrent state a pass has no pool yet
            assert self.final_norm and set(layer_kinds(self)) == {"attn"} \
                and not self.moe_num_experts, \
                "a looped stack is dense softmax layers under a final norm"

    @property
    def head_dim(self) -> int:
        return self.head_size or self.hidden_size // self.num_heads

    @property
    def moe_dropless_only(self) -> bool:
        """The router is one that only the dropless path of
        ``parallel/moe.moe_mlp`` computes (no capacity plan, no aux loss)."""
        return bool(self.moe_experts_held or self.moe_router_bias
                    or self.moe_score_func != "softmax"
                    or self.moe_latent_size or self.moe_routed_scale != 1.0
                    or self.moe_zero_experts)

    @property
    def shared_ffn_hidden_size(self) -> int:
        """The width of the shared expert's FFN."""
        return (self.moe_shared_ffn_hidden_size
                or self.moe_shared_experts * self.ffn_hidden_size)

    @property
    def experts_held(self) -> int:
        """Experts in a layer's stack (the router is moe_num_experts wide)."""
        return self.moe_experts_held or self.moe_num_experts


def require_one_pass(cfg: TransformerConfig, what: str) -> None:
    """THE refusal of whatever runs the stack of layers itself, a layer or a
    stage at a time, and so once: a looped stack (``loop_passes`` > 1) run
    once is another model."""
    if cfg.loop_passes > 1:
        raise NotImplementedError(
            f"{what} runs the layers once over, and this is a looped stack "
            f"(loop_passes={cfg.loop_passes}: the same layers run "
            "that many times, the final norm closing every pass); only "
            "models/transformer.forward makes the passes")


def eval_config(cfg: TransformerConfig) -> TransformerConfig:
    """Config COPY with training regularisers off (dropout, random-LTD).
    Engines trace eval programs against this copy instead of toggling shared
    config fields (a mutate-restore window is not thread-safe and a
    concurrent train trace would silently compile regulariser-free)."""
    return dataclasses.replace(cfg, dropout_enabled=False, ltd_keep=0)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init_params(rng: jax.Array, cfg: TransformerConfig) -> Dict[str, Any]:
    H = cfg.hidden_size
    V = cfg.vocab_size
    # fixed key slots (branch-independent): 0 embed, 1 pos, 2 layers base,
    # 3 lm_head — the layers base key feeds init_layer_params, which draws
    # per (leaf, layer) via fold_in so any layer RANGE can be initialised
    # without materialising the full stack (the param-offload tier streams
    # block inits; slicing a whole-leaf draw kept the full RNG pipeline
    # live in HBM)
    ks = jax.random.split(rng, 16)
    std = 0.02

    def normal(key, shape, s=std):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(cfg.dtype)

    params: Dict[str, Any] = {
        "embed": {"tokens": normal(ks[0], (V, H))},
    }
    if cfg.position == "learned":
        params["pos"] = normal(ks[1], (cfg.max_seq_len, H), 0.01)
    if cfg.type_vocab_size > 0:
        params["type_embed"] = normal(ks[4], (cfg.type_vocab_size, H))
    if cfg.embed_norm:
        params["embed_norm"] = {"scale": jnp.ones((H,), cfg.dtype),
                                "bias": jnp.zeros((H,), cfg.dtype)}

    params["layers"] = init_layer_params(ks[2], cfg, 0, cfg.num_layers)

    if cfg.final_norm:
        params["final_norm"] = {"scale": jnp.ones((H,), cfg.dtype)}
        if cfg.norm == "layernorm":
            params["final_norm"]["bias"] = jnp.zeros((H,), cfg.dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(ks[3], (H, V))
        if cfg.lm_head_bias:
            params["lm_head_b"] = jnp.zeros((V,), cfg.dtype)
    if cfg.loop_passes > 1:
        # the exit gate, one output; a bias that is not zero, or leaving it
        # out would go untested
        params["exit_gate"] = {"w": normal(ks[5], (H,)),
                               "b": normal(ks[6], (), 0.5)}
    return params


def layer_kinds(cfg: TransformerConfig) -> Tuple[str, ...]:
    """The kind (``LAYER_KINDS``) of each layer of one period of the stack."""
    return cfg.layer_pattern or ("attn",)


def layers_of_kind(cfg: TransformerConfig, kind: str) -> Tuple[int, ...]:
    """The layers (global indices, ascending) whose mixer is ``kind``."""
    pattern = layer_kinds(cfg)
    return tuple(i for i in range(cfg.num_layers)
                 if pattern[i % len(pattern)] == kind)


def layers_with_mixer(cfg: TransformerConfig, mixer: str) -> Tuple[int, ...]:
    """The layers (global indices, ascending) whose mixer is ``mixer``,
    whether or not an FFN follows it in the layer."""
    return tuple(sorted(i for kind in set(layer_kinds(cfg))
                        if LAYER_KINDS[kind][0] == mixer
                        for i in layers_of_kind(cfg, kind)))


def layer_places(cfg: TransformerConfig) -> Tuple[Dict[str, Any], ...]:
    """THE answer to "which stack entry, which pool" for each layer of the
    stack, in order: ``kind`` (``LAYER_KINDS``); ``layer``, its place among
    the layers of its KIND, where its weights lie in the kind's stacked tree
    and its experts in the kind's bank (``Step.layer_index``); ``pool``, its
    place among the layers of its MIXER whatever their kinds, where the
    mixer's pages, tail and state lie in the cache (``Step.pool_index``;
    None for a layer with no mixer). The two differ only where kinds share a
    mixer (a leading dense layer and the expert layers under one mixer);
    ``forward`` and ``_run_layers`` reckon both a period at a time with
    ``_period_places``, which counts as this does."""
    pattern = layer_kinds(cfg)
    runs = cfg.layer_runs or ((pattern, cfg.num_layers // len(pattern)),)
    above: Dict[str, int] = {}          # layers of each kind so far
    pools_above: Dict[Any, int] = {}    # and of each mixer
    places = []
    for kinds, periods in runs:
        mixers = [LAYER_KINDS[kind][0] for kind in kinds]
        for p in range(periods):
            for kind, mixer, (n, j, of_mixer, jm) in zip(
                    kinds, mixers, _period_places(kinds)):
                places.append({
                    "kind": kind, "layer": above.get(kind, 0) + p * n + j,
                    "pool": (pools_above.get(mixer, 0) + p * of_mixer + jm
                             if mixer else None)})
        for kind in set(kinds):
            above[kind] = above.get(kind, 0) + kinds.count(kind) * periods
        for mixer in set(mixers):
            pools_above[mixer] = (pools_above.get(mixer, 0)
                                  + mixers.count(mixer) * periods)
    return tuple(places)


def _period_places(kinds: Tuple[str, ...]) -> Tuple[Tuple[int, ...], ...]:
    """For each layer of one period of ``kinds``: ``(layers of its kind in
    the period, its place among them, layers of its MIXER in the period,
    its place among those)``; a layer's index in period p is then ``layers
    above the run + p * count + place``, by kind and by mixer alike."""
    mixers = [LAYER_KINDS[kind][0] for kind in kinds]
    return tuple((kinds.count(kind), kinds[:j].count(kind),
                  mixers.count(mixers[j]), mixers[:j].count(mixers[j]))
                 for j, kind in enumerate(kinds))


def recurrent_layers(cfg: TransformerConfig
                     ) -> Tuple[Optional[str], Tuple[int, ...]]:
    """(the model's recurrent mixer, the layers that have it): THE answer to
    "does this layer keep a state and a convolution tail a sequence, not
    pages". ``(None, ())`` for a model of softmax layers alone."""
    for mixer, record in MIXERS.items():
        layers = record.state and layers_with_mixer(cfg, mixer)
        if layers:
            return mixer, layers
    return None, ()


def _layers_keeping(cfg: TransformerConfig, what: str) -> Tuple[int, ...]:
    return tuple(sorted(
        i for mixer, record in MIXERS.items() if record.keeps == what
        for i in layers_with_mixer(cfg, mixer)))


def paged_layers(cfg: TransformerConfig) -> Tuple[int, ...]:
    """The layers that keep pages of their own in the serving arena's
    ``"k"`` and ``"v"`` (a window layer keeps a ring a row, a cross layer
    reads another's pages)."""
    return _layers_keeping(cfg, "pages")


def sublayer_leaves(cfg: TransformerConfig, kind: str) -> Tuple[str, ...]:
    """The leaves of a layer of ``SUBLAYERS`` that its sublayers own (a
    leading axis of sublayers behind the layers'); every other leaf is the
    layer's one."""
    return ("ln1", MIXERS[LAYER_KINDS[kind][0]].name, "ln2",
            *FFNS[ffn_of(cfg, kind, sublayer=True)].axes(cfg))


def latent_pools(cfg: TransformerConfig) -> int:
    """Pools of the serving arena's ``"latent"``: one a SUBLAYER of each
    layer whose mixer keeps a latent a token in place of keys and values
    (0: the model has no such layer, and its arena is ``"k"`` and ``"v"``)."""
    return sum(SUBLAYERS.get(kind, 1) * len(layers_of_kind(cfg, kind))
               for kind in set(layer_kinds(cfg))
               if LAYER_KINDS[kind][0]
               and MIXERS[LAYER_KINDS[kind][0]].keeps == "latent")


def latent_width(cfg: TransformerConfig) -> int:
    """Values a token keeps in a pool of ``"latent"``: the latent and,
    behind it, the one roped key all heads share."""
    return cfg.kv_lora_rank + (cfg.rotary_dim or 0)


def latent_page_width(cfg: TransformerConfig) -> int:
    """Lanes a token takes in a pool of ``"latent"``: ``latent_width`` in
    whole lane tiles of 128, the rest zeros. The chip lays 576 values out
    in 640 lanes whatever the array says, and its DMA copies whole tiles
    alone (Mosaic: "slice shape along dimension 3 must be aligned to tiling
    (128), but is 576"), so the pad costs the arena nothing it did not
    cost already and lets the paged walk copy a page."""
    return -(-latent_width(cfg) // 128) * 128


def moe_count_width(cfg: TransformerConfig) -> int:
    """Entries of an MoE layer's routing counts (``parallel/moe.moe_mlp``):
    what the experts' record says (``Ffn.count_width``)."""
    return FFNS["experts"].count_width(cfg)


def ring_layers(cfg: TransformerConfig) -> Tuple[int, ...]:
    """The window layers, which keep ``attention_window`` keys a row in a
    ring of pages (the pools ``"wk"`` and ``"wv"``)."""
    return _layers_keeping(cfg, "ring")


def pool_readers(cfg: TransformerConfig) -> Tuple[int, ...]:
    """The layers of a stack of runs that read the arena's ``"k"`` and
    ``"v"``: the full layers, which also write them, and the cross layers
    (``()`` for any other stack: the count is the span's, not a cache's)."""
    if not cfg.layer_runs:
        return ()
    return tuple(sorted(i for kind in ("full", "cross")
                        for i in layers_of_kind(cfg, kind)))


def tail_runs(cfg: TransformerConfig) -> int:
    """How many of the stack's LAST runs keep nothing of a token (no pages,
    ring or state: they read what earlier layers made): a prompt chunk runs
    them for its last real token alone (``forward``'s ``last_token``)."""
    n = 0
    for kinds, _ in reversed(cfg.layer_runs):
        records = [MIXERS[LAYER_KINDS[k][0]] for k in kinds]
        if any(r.keeps or r.state for r in records):
            break
        n += 1
    return n


def ffn_of(cfg: TransformerConfig, kind: str,
           sublayer: bool = False) -> Optional[str]:
    """THE answer to "which FFN has a layer of this kind": a key of ``FFNS``,
    or None where ``LAYER_KINDS`` says no FFN follows the mixer. The kind
    names it (a leading dense layer's ``"dense"``) or leaves it to the whole
    configuration, which answers the same for every such kind. ``sublayer``: the FFN EACH sublayer of a kind of ``SUBLAYERS`` has
    beside the layer's one (None for any other kind)."""
    if sublayer:
        return "dense" if kind in SUBLAYERS else None
    if not LAYER_KINDS[kind][1]:
        return None
    if LAYER_KINDS[kind][1] is not True:
        return LAYER_KINDS[kind][1]     # the kind's own, whatever the model's
    if cfg.moe_num_experts > 0:
        return "experts"
    return "swiglu" if cfg.activation == "swiglu" else "biased"


def ffn_layers(cfg: TransformerConfig) -> Tuple[int, ...]:
    """The layers that have an FFN (every one, but for a family whose
    layers are one function each)."""
    return tuple(i for kind in set(layer_kinds(cfg)) if ffn_of(cfg, kind)
                 for i in layers_of_kind(cfg, kind))


def expert_layers(cfg: TransformerConfig) -> Tuple[int, ...]:
    """The layers whose FFN routes (``Ffn.count_width``): every layer that
    has an FFN, but for a family's leading dense layers."""
    return tuple(sorted(
        i for kind in set(layer_kinds(cfg))
        if ffn_of(cfg, kind) and FFNS[ffn_of(cfg, kind)].count_width(cfg)
        for i in layers_of_kind(cfg, kind)))


def layer_stacks(layers: Dict[str, Any], cfg: TransformerConfig
                 ) -> Dict[str, Any]:
    """``params["layers"]`` as ``{kind: stacked tree}``. A stack of ONE kind
    is the tree itself, leaves ``(num_layers, ...)``, as every model had it
    before layers came in kinds; a stack of several kinds keeps one tree a
    kind, leaves ``(layers of that kind, ...)`` in layer order."""
    kinds = set(layer_kinds(cfg))
    return layers if len(kinds) > 1 else {next(iter(kinds)): layers}


def _resid_std(cfg: TransformerConfig) -> float:
    """GPT-2-style scaled init on residual-writing projections."""
    return 0.02 / (2 * cfg.num_layers) ** 0.5


def init_layer_params(base_key: jax.Array, cfg: TransformerConfig,
                      lo: Any, blen: int) -> Dict[str, Any]:
    """Layer-stack params for layers [lo, lo+blen): leaves shaped
    (blen, ...). Draws are per (leaf, layer) — ``fold_in(fold_in(base, tag),
    layer_idx)`` — so ANY range reproduces exactly the same values the full
    init produces (ZeRO-3 param offload inits one block at a time). A stack
    of several kinds (``layer_stacks``) is initialised whole."""
    H, L = cfg.hidden_size, cfg.num_layers

    def one_layer(li, kind="attn"):
        def normal(tag, shape, s=0.02, sub=None):
            k = jax.random.fold_in(jax.random.fold_in(base_key, tag), li)
            if sub is not None:     # a sublayer's own draw
                k = jax.random.fold_in(k, sub)
            return (jax.random.normal(k, shape, jnp.float32) * s
                    ).astype(cfg.dtype)

        def uniform(tag, shape, lo, hi):
            k = jax.random.fold_in(jax.random.fold_in(base_key, tag), li)
            return jax.random.uniform(k, shape, jnp.float32, lo, hi)

        mixer, ffn = LAYER_KINDS[kind][0], ffn_of(cfg, kind)
        layer: Dict[str, Any] = {}
        if kind in SUBLAYERS:
            # each sublayer's norms, mixer and dense FFN, stacked on a
            # leading axis; the layer's own FFN below is its one
            dense = FFNS[ffn_of(cfg, kind, sublayer=True)]

            def sublayer(i):
                draw = partial(normal, sub=i)
                return {"ln1": {"scale": jnp.ones((H,), cfg.dtype)},
                        MIXERS[mixer].name: MIXERS[mixer].init(cfg, draw,
                                                               uniform),
                        "ln2": {"scale": jnp.ones((H,), cfg.dtype)},
                        **dense.init(cfg, draw)}

            layer = jax.tree.map(
                lambda *a: jnp.stack(a),
                *(sublayer(i) for i in range(SUBLAYERS[kind])))
        elif mixer is not None:
            layer["ln1"] = {"scale": jnp.ones((H,), cfg.dtype)}
            layer[MIXERS[mixer].name] = MIXERS[mixer].init(cfg, normal,
                                                           uniform)
            if "lam_q1" in layer[MIXERS[mixer].name]:
                # the published lambda_init of the layer's place in the
                # WHOLE stack: a constant a layer, not learned
                layer[MIXERS[mixer].name]["lam_init"] = (
                    0.8 - 0.6 * jnp.exp(-0.3 * li.astype(jnp.float32)))
        if ffn is not None:     # else a mixer alone: no FFN of any kind
            if kind not in SUBLAYERS:
                layer["ln2"] = {"scale": jnp.ones((H,), cfg.dtype)}
            layer.update(FFNS[ffn].init(cfg, normal))
        if cfg.norm_position == "sandwich":
            for ln in ("ln1", "ln2"):
                if ln in layer:
                    layer[ln + "_post"] = {"scale": jnp.ones((H,), cfg.dtype)}
        if cfg.norm == "layernorm":
            for ln in ("ln1", "ln2", "ln1_post", "ln2_post"):
                if ln in layer:
                    layer[ln]["bias"] = jnp.zeros((H,), cfg.dtype)
        return layer

    kinds = sorted(set(layer_kinds(cfg)))
    if len(kinds) == 1:
        return jax.vmap(partial(one_layer, kind=kinds[0]))(
            lo + jnp.arange(blen))
    if not (isinstance(lo, int) and lo == 0 and blen == L):
        raise NotImplementedError(
            "a stack of several kinds of layer is initialised whole; a "
            "range of its layers has no one stacked tree")
    return {kind: jax.vmap(partial(one_layer, kind=kind))(
        jnp.asarray(layers_of_kind(cfg, kind))) for kind in kinds}


def param_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Logical-axis tree mirroring init_params — drives TP/ZeRO sharding."""
    ln = {"scale": (LAYERS, EMBED)}
    if cfg.norm == "layernorm":
        ln = {"scale": (LAYERS, EMBED), "bias": (LAYERS, EMBED)}
    sandwich = cfg.norm_position == "sandwich"
    kinds = sorted(set(layer_kinds(cfg)))
    by_kind = {}
    for kind in kinds:
        mixer, ffn = LAYER_KINDS[kind][0], ffn_of(cfg, kind)
        by_kind[kind] = {**({} if ffn is None
                            else {"ln2": dict(ln), **FFNS[ffn].axes(cfg)}),
                         **({"ln2_post": dict(ln)}
                            if sandwich and ffn is not None else {}),
                         **({} if mixer is None
                            else {"ln1": dict(ln), MIXERS[mixer].name:
                                  MIXERS[mixer].axes(cfg)}),
                         **({"ln1_post": dict(ln)}
                            if sandwich and mixer is not None else {})}
        if kind in SUBLAYERS:
            # the sublayers' leaves carry their axis behind the layers'
            by_kind[kind].update(
                FFNS[ffn_of(cfg, kind, sublayer=True)].axes(cfg))
            by_kind[kind].update(jax.tree.map(
                lambda a: (a[0], None) + a[1:],
                {name: by_kind[kind][name]
                 for name in sublayer_leaves(cfg, kind)},
                is_leaf=lambda a: isinstance(a, tuple)))
    axes: Dict[str, Any] = {
        "embed": {"tokens": (VOCAB, EMBED)},
        "layers": by_kind if len(kinds) > 1 else by_kind[kinds[0]],
    }
    if cfg.final_norm:
        axes["final_norm"] = ({"scale": (EMBED,), "bias": (EMBED,)}
                              if cfg.norm == "layernorm"
                              else {"scale": (EMBED,)})
    if cfg.position == "learned":
        axes["pos"] = (SEQ, EMBED)
    if cfg.type_vocab_size > 0:
        axes["type_embed"] = (None, EMBED)
    if cfg.embed_norm:
        axes["embed_norm"] = {"scale": (EMBED,), "bias": (EMBED,)}
    if not cfg.tie_embeddings:
        axes["lm_head"] = (EMBED, VOCAB)
        if cfg.lm_head_bias:
            axes["lm_head_b"] = (VOCAB,)
    if cfg.loop_passes > 1:
        axes["exit_gate"] = {"w": (EMBED,), "b": ()}
    return axes


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------


def _tp_world() -> int:
    """Model-axis size of the AMBIENT mesh context at trace time — the
    quantized-GEMM Pallas route is single-shard only (a pallas_call over
    model-sharded weights would need a manual shard_map); TP runs take the
    jnp dequant path, which XLA partitions. Reads the framework's ambient
    mesh (``parallel.mesh.ambient`` — every engine trace site enters the
    mesh through it), falling back to the public
    ``jax.sharding.get_abstract_mesh`` for ``use_mesh`` users. NOT the
    module-global mesh, which the inference engine never sets (and whose
    lazy default would be a side effect here)."""
    from ..parallel.mesh import MODEL_AXIS, ambient_mesh

    m = ambient_mesh()
    if m is not None:
        return int(dict(m.shape).get(MODEL_AXIS, 1))
    try:
        am = jax.sharding.get_abstract_mesh()
        shape = dict(getattr(am, "shape", {}) or {})
        if shape:
            return int(shape.get(MODEL_AXIS, 1))
    except Exception:
        pass
    # fail UNSAFE-proof: outside any framework mesh context we cannot rule
    # out sharded weights (e.g. a bare `with mesh:` trace) — disable the
    # single-shard kernel route rather than risk a pallas_call over them
    return 1 << 30


def _per_shard(fn: Callable, args: tuple, dims: tuple):
    """Call a Pallas kernel on each device's shard of its operands. GSPMD
    cannot partition a Mosaic kernel ("wrap the call in a shard_map"), so
    under an ambient mesh of several devices the kernel runs inside one.

    ``dims`` gives, per operand, the mesh axes each array dim is split over
    (None: whole on every device); an entry is dropped where the axes'
    product does not divide the dim, which costs redundant work, never a
    wrong answer — every kernel routed here is independent along the dims
    it is split on (batch rows, heads). ``None`` operands pass through; the
    result has the first operand's shape and split. Inside the pipeline's
    manual region 'pipe' is already manual and is left out."""
    from ..parallel.mesh import PIPE_AXIS, ambient_mesh
    from ..parallel.sequence import _in_manual_pipe

    mesh = ambient_mesh()
    if mesh is None or mesh.size == 1:
        return fn(*args)
    from jax.sharding import PartitionSpec as P

    manual = set(mesh.axis_names)
    in_pipe = _in_manual_pipe()
    if in_pipe:
        manual.discard(PIPE_AXIS)

    def spec(shape, axes_per_dim):
        out = []
        for size, axes in zip(shape, axes_per_dim):
            names = tuple(a for a in ((axes,) if isinstance(axes, str)
                                      else axes or ()) if a in manual)
            ways = 1
            for a in names:
                ways *= int(mesh.shape[a])
            out.append(names if ways > 1 and size % ways == 0 else None)
        return P(*out)

    live = [i for i, a in enumerate(args) if a is not None]

    def body(*shards):
        full = list(args)
        for i, a in zip(live, shards):
            full[i] = a
        return fn(*full)

    in_specs = tuple(spec(args[i].shape, dims[i]) for i in live)
    # nested in the pipeline's shard_map the context mesh (with 'pipe'
    # manual) is the one to split further
    return jax.shard_map(
        body, mesh=None if in_pipe else mesh, in_specs=in_specs,
        out_specs=in_specs[0], axis_names=manual,
        check_vma=False)(*(args[i] for i in live))


def _require_impl_kwarg(impl: Callable, kwarg: str, why: str) -> None:
    """A custom attention_impl must DECLARE every kwarg a model feature
    needs — failing loud beats silently dropping a bias or swapping in the
    reference implementation."""
    import inspect

    sig = inspect.signature(impl)
    if (kwarg not in sig.parameters
            and not any(p.kind is inspect.Parameter.VAR_KEYWORD
                        for p in sig.parameters.values())):
        raise TypeError(
            f"custom attention_impl must accept a {kwarg}= kwarg for {why} "
            f"(signature is {sig})")


def _flash_attention(q, k, v, mask, causal=True, alibi=None):
    """The Pallas flash kernel as an ``attention_impl``, per shard under a
    mesh: the layout parallel/sequence.py pins around attention is batch
    over the data axes, full sequence, heads over ('seq', 'model'). q and kv
    heads split the same number of ways or not at all, so every shard keeps
    whole GQA groups."""
    from ..ops.flash_attention import flash_attention
    from ..parallel.mesh import (DATA_SHARD, MODEL_AXIS, SEQ_AXIS,
                                 get_model_parallel_world_size,
                                 get_sequence_parallel_world_size)

    def kernel(q, k, v, mask, alibi):
        return flash_attention(q, k, v, mask=mask, causal=causal,
                               alibi=alibi)

    if mask is not None and mask.ndim != 2:
        # a full (B, S, T) mask takes flash_attention's jnp route, which
        # GSPMD partitions itself
        return kernel(q, k, v, mask, alibi)
    heads = (SEQ_AXIS, MODEL_AXIS)
    if k.shape[2] % (get_sequence_parallel_world_size()
                     * get_model_parallel_world_size()):
        heads = None
    qkv = (DATA_SHARD, None, heads, None)
    return _per_shard(kernel, (q, k, v, mask, alibi),
                      (qkv, qkv, qkv, (DATA_SHARD, None), (heads,)))


def default_attention_impl() -> Callable:
    """Platform-resolved attention: Pallas flash attention on TPU, plain-jnp
    elsewhere. This is what ``attention_impl=None`` means."""
    from ..ops import registry

    return (_flash_attention if registry.kernels_active()
            else dot_product_attention)


def active_attention_impl(cfg: "TransformerConfig") -> str:
    """Introspection for benches/tests: which attention path will run."""
    from ..ops import registry

    if cfg.attention_impl is not None:
        return "custom"
    return "flash_attention" if registry.kernels_active() else "jnp"


def _activation_derived_key(h: jax.Array, salt: int) -> jax.Array:
    """Deterministic PRNG key from activation content — loss_fn carries no
    rng argument, so stochastic features (RTS, PLD) derive their draws from
    the data: varies across batches/steps, reproducible for a given input."""
    seed = jax.lax.bitcast_convert_type(jnp.sum(h.astype(jnp.float32)),
                                        jnp.int32)
    return jax.random.fold_in(jax.random.PRNGKey(salt), seed)


def resolve_remat_policy(cfg: "TransformerConfig"):
    """remat_policy knob → jax.checkpoint policy.

    "dots" keeps every matmul output and the two results of the flash
    forward kernel, ``o`` and the compact (B, N, S) ``lse``, which
    ``ops/flash_attention`` tags with ``SAVED_RESIDUALS``: a Pallas call is
    no dot, so with the dots alone the backward runs ``flash_attention_fwd``
    a second time only to get them back. Everything else (elementwise,
    norms) is recomputed, and with the jnp attention or a custom
    ``attention_impl`` nothing carries the names and only dots are saved.
    Measured on v5e (PR 48, dots alone -> with the names, seq 2048):
    opt-1.3b-d8.train-x1 ``train_step_dev_ms`` 249.57 -> 239.07,
    ``train_tok_s`` 32,763 -> 34,202 (+4.4%); opt-1.3b.train-zero3-x4
    360.51 -> 339.83, 45,353 -> 48,102 (+6.1%). The price is the stacked
    ``o`` (33.5 MB a layer in bf16 at batch 4 and 32 heads of 64, twice
    that as the chip tiles a 64-wide minor dim) and 1 MB of ``lse``.
    "full" (policy ``None``) keeps what it kept, the layer's input alone,
    and recomputes the forward kernel with the rest of the layer;
    "offload-dots" is left as it was too (no benchmark cell runs it).

    "offload-dots" is the reference's cpu_checkpointing
    (activation_checkpointing/checkpointing.py): saved matmul outputs live
    in pinned HOST memory instead of HBM — XLA streams them out during
    forward and back in for backward (the hand-written
    copy_to_device/partition machinery dissolves into the offload policy).
    Accelerator backends only; trades PCIe traffic for HBM residency on
    long sequences."""
    if cfg.remat_policy == "dots":
        from ..ops.flash_attention import SAVED_RESIDUALS

        policies = jax.checkpoint_policies
        return policies.save_from_both_policies(
            policies.dots_with_no_batch_dims_saveable,
            policies.save_only_these_names(*SAVED_RESIDUALS))
    if cfg.remat_policy == "offload-dots":
        return jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host")
    return None


def quantize_model_weights(params: Dict[str, Any], bits: int = 8,
                           donate: bool = False,
                           group_size: Optional[int] = None,
                           shardings: Optional[Dict[str, Any]] = None
                           ) -> Dict[str, Any]:
    """Weight-only quantization for inference (reference int8/int4
    kernel-injection mode, ``inference/quantization``,
    ``csrc/includes/quantization_utils.h:468`` 4-bit packing): matmul weights
    (attention qkv/o, dense MLP, untied lm_head) become
    ``{"q8": int8, "s": fp32 per-output-channel scale}`` (8-bit) or
    ``{"q4": nibble-packed uint8 (K/2, N), "s": (G, N) group scales}``
    (4-bit). Embedding stays dense (the token gather reads rows);
    biases/norms stay dense; MoE expert banks are left dense (moe_mlp
    consumes them directly). HBM weight traffic — the decode-phase
    roofline — drops ~2x (int8) / ~4x (int4)."""
    assert bits in (4, 8)
    qmax = float(2 ** (bits - 1) - 1)

    if bits == 4:
        from ..ops.quant_matmul import quantize_int4

        def _quant_math(w):
            q4, s = quantize_int4(w, group_size)
            return {"q4": q4, "s": s}
    else:
        def _quant_math(w):
            w32 = w.astype(jnp.float32)
            absmax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)
            s = jnp.where(absmax == 0.0, 1.0, absmax / qmax)
            q = jnp.clip(jnp.round(w32 / s), -qmax, qmax).astype(jnp.int8)
            return {"q8": q, "s": s}

    # donate=True quantizes leaf-by-leaf, freeing each bf16 leaf as its int8
    # replacement materialises — a whole-tree jit would transiently hold both
    # copies (OOM at 7B on a 16GB chip). The explicit delete() matters: a
    # backend that ignores donation would otherwise keep every source buffer
    # alive until GC, which surfaces as a lazy OOM at the first fence.
    if donate:
        # out_shardings per leaf: under TP the quantized pair lands SHARDED
        # directly — routing through the default device first would need
        # the whole quantized tree resident on one chip, defeating TP's
        # memory scaling at load. One jit wrapper per distinct sharding so
        # same-shape leaves (wq/wk/wv) still share a compile.
        jits: Dict[Any, Any] = {}

        def quant(w, sh=None):
            key = (None if sh is None
                   else tuple(sorted((k, v) for k, v in sh.items())))
            if key not in jits:
                jits[key] = jax.jit(_quant_math, donate_argnums=0,
                                    out_shardings=sh)
            out = jits[key](w)
            jax.block_until_ready(out)
            try:
                w.delete()
            except Exception:
                pass                     # already consumed by donation
            return out
    else:
        def quant(w, sh=None):
            return _quant_math(w)

    def sh_of(*path):
        node = shardings
        if node is None:
            return None
        for p in path:
            node = node[p]
        return node

    params = dict(params)
    layers = dict(params["layers"])
    attn = dict(layers["attn"])
    for name in ("wq", "wk", "wv", "wo"):
        attn[name] = quant(attn[name], sh_of("layers", "attn", name))
    layers["attn"] = attn
    mlp = dict(layers["mlp"])
    if mlp["w_up"].ndim == 3:   # a matrix a layer, which ``_qeinsum`` reads:
        # a dense MLP only (an expert bank, a matrix an expert, stays dense)
        for name in ("w_up", "w_gate", "w_down"):
            if name in mlp:
                mlp[name] = quant(mlp[name], sh_of("layers", "mlp", name))
        layers["mlp"] = mlp
    params["layers"] = layers
    if "lm_head" in params:
        params["lm_head"] = quant(params["lm_head"], sh_of("lm_head"))
    return params


def _qeinsum(spec: str, x: jax.Array, w: Any, dtype: Any,
             a8: bool = False) -> jax.Array:
    """Weight-site einsum with on-the-fly int8 dequant.

    Decode-shaped calls (few tokens) route through the Pallas int8 matmul
    (ops/quant_matmul.py) where each weight tile converts in VMEM under the
    int8 DMA — XLA's own lowering converts the FULL weight at VPU rate
    before the matmul, which is slower than bf16 on a memory-bound step.
    Larger (prefill/training-shaped) calls use the XLA path with the scale
    on the output; the optimization barrier stops XLA hoisting the
    loop-invariant dequantized weight stack out of the token/layer loops
    (hoisting materialises full-precision weights — OOM at 7B/16GB)."""
    from ..ops import registry

    if isinstance(w, dict) and "q8" in w:
        q8, s = w["q8"], w["s"]
        B, S = x.shape[0], x.shape[1]
        if (S * B <= 8 and q8.ndim == 2 and registry.kernels_active()
                and _tp_world() == 1
                and q8.shape[0] % 128 == 0 and q8.shape[1] % 128 == 0):
            from ..ops.quant_matmul import int8_a8_matmul, int8_matmul

            fn = int8_a8_matmul if a8 else int8_matmul
            out = fn(x.reshape(B * S, -1), q8, s, out_dtype=dtype)
            return out.reshape(x.shape[:-1] + (q8.shape[1],))
        x, q8 = lax.optimization_barrier((x, q8))
        out = jnp.einsum(spec, x, q8.astype(dtype))
        return out * s[..., 0, :].astype(dtype)
    if isinstance(w, dict) and "q4" in w:
        from ..ops.quant_matmul import unpack_int4

        q4, s = w["q4"], w["s"]
        B, S = x.shape[0], x.shape[1]
        K2, N = q4.shape[-2:]
        G = s.shape[-2]
        gs = 2 * K2 // G
        if (S * B <= 8 and q4.ndim == 2 and registry.kernels_active()
                and _tp_world() == 1
                and K2 % 128 == 0 and N % 128 == 0
                and (G == 1 or gs % 128 == 0)):
            from ..ops.quant_matmul import int4_a8_matmul, int4_matmul

            fn = int4_a8_matmul if a8 else int4_matmul
            out = fn(x.reshape(B * S, -1), q4, s, out_dtype=dtype)
            return out.reshape(x.shape[:-1] + (N,))
        x, q4 = lax.optimization_barrier((x, q4))
        return jnp.einsum(spec, x, unpack_int4(q4, s, dtype))
    return jnp.einsum(spec, x, w)


def _swiglu(cfg: "TransformerConfig", h: jax.Array,
            w: Dict[str, Any]) -> jax.Array:
    """``down(silu(gate(h)) * up(h))``: a dense SwiGLU FFN (the whole FFN
    of a dense layer; the shared expert of an MoE one)."""
    gate = _qeinsum("bsh,hf->bsf", h, w["w_gate"], cfg.dtype, a8=cfg.a8_decode)
    up = _qeinsum("bsh,hf->bsf", h, w["w_up"], cfg.dtype, a8=cfg.a8_decode)
    return _qeinsum("bsf,fh->bsh", jax.nn.silu(gate) * up, w["w_down"],
                    cfg.dtype, a8=cfg.a8_decode)


def _plain_ffn(cfg: "TransformerConfig", h: jax.Array,
               w: Dict[str, Any]) -> jax.Array:
    """``down(act(up(h)))`` with no gate and no bias: the shared expert of a
    family whose experts are not gated (``relu2``: relu squared; anything
    else: the experts' tanh GELU, ``parallel/moe.expert_activation``)."""
    from ..parallel.moe import expert_activation

    up = _qeinsum("bsh,hf->bsf", h, w["w_up"], cfg.dtype, a8=cfg.a8_decode)
    inner = expert_activation(cfg.activation, up.astype(jnp.float32))
    return _qeinsum("bsf,fh->bsh", inner.astype(cfg.dtype), w["w_down"],
                    cfg.dtype, a8=cfg.a8_decode)


def _dropout(x: jax.Array, cfg: "TransformerConfig", salt: int) -> jax.Array:
    """Inverted dropout on a residual-path tensor; active only when the
    engine enabled it (training). Key derives from the tensor's content —
    varies across steps/batches/layers, reproducible for a given input."""
    if not (cfg.dropout > 0.0 and cfg.dropout_enabled):
        return x
    keep = 1.0 - cfg.dropout
    mask = jax.random.bernoulli(_activation_derived_key(x, salt), keep,
                                x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x)).astype(x.dtype)


def _fused_norm(x, scale, bias, kind: str, eps: float):
    """The Pallas norm kernel, per shard under a mesh: rows are independent,
    so (B, S, H) splits over batch and tokens."""
    from ..ops.normalization import fused_layer_norm
    from ..parallel.mesh import DATA_SHARD, SEQ_AXIS

    rows = ((DATA_SHARD, SEQ_AXIS, None) if x.ndim == 3
            else (None,) * x.ndim)
    return _per_shard(
        lambda x, s, b: fused_layer_norm(x, s, b, eps, kind == "rmsnorm"),
        (x, scale, bias), (rows, (None,), (None,)))


def _norm(x: jax.Array, scale: jax.Array, bias: Optional[jax.Array],
          kind: str, eps: float) -> jax.Array:
    from ..ops import registry

    if registry.kernels_active():
        return _fused_norm(x, scale, bias, kind, eps)
    x32 = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        out = x32 * lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        out = (x32 - mean) * lax.rsqrt(var + eps) * scale.astype(jnp.float32)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


def rope_table(positions: jax.Array, head_dim: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for the given absolute positions, shape (..., head_dim/2)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (..., D/2)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, S, n, D); cos/sin: (S, D/2) shared or (B, S, D/2) per-row
    (ragged-batch decode positions)."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    if cos.ndim == 3:
        c = cos[:, :, None, :]
        s = sin[:, :, None, :]
    else:
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1).astype(x.dtype)


def alibi_slopes(n_heads: int) -> jax.Array:
    """ALiBi per-head slopes (HF BloomModel build_alibi_tensor formula;
    reference alibi path: csrc/transformer/inference/csrc/softmax.cu)."""
    import math

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    closest = 2 ** math.floor(math.log2(n_heads))
    slopes = pow2_slopes(closest)
    if closest != n_heads:
        extra = pow2_slopes(2 * closest)
        slopes += extra[0::2][: n_heads - closest]
    return jnp.asarray(slopes, jnp.float32)


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          mask: Optional[jax.Array], causal: bool = True,
                          alibi: Optional[jax.Array] = None,
                          key_positions: Optional[jax.Array] = None,
                          window: Optional[jax.Array] = None,
                          scale: Optional[float] = None) -> jax.Array:
    """Plain-XLA reference attention. q: (B,S,N,D); k,v: (B,T,K,D) with GQA
    broadcast. Softmax in fp32 (reference softmax kernels are fp32-accum).
    ``alibi``: per-head slopes (N,) — the key-position-linear bias (the
    query-position term is softmax-shift-invariant, so slope*k_pos
    suffices). ``key_positions`` (B, T): true per-row key positions for the
    alibi bias (ragged decode — defaults to the column index). ``window``:
    sliding-window width as a (traced) scalar — queries attend only to
    keys within ``window`` positions back; <=0 means unlimited (so a
    per-layer mix of global/local layers scans with one program).
    ``scale``: score multiplier, default 1/sqrt(D)."""
    B, S, N, D = q.shape
    T, K = k.shape[1], k.shape[2]
    if K != N:
        k = jnp.repeat(k, N // K, axis=2)
        v = jnp.repeat(v, N // K, axis=2)
    scale = (D ** -0.5) if scale is None else scale
    scores = jnp.einsum("bsnd,btnd->bnst", q, k).astype(jnp.float32) * scale
    if alibi is not None:
        kpos = (jnp.arange(T, dtype=jnp.float32)[None]
                if key_positions is None
                else key_positions.astype(jnp.float32))
        scores = scores + alibi[None, :, None, None] * kpos[:, None, None, :]
    neg = jnp.finfo(jnp.float32).min
    if causal or window is not None:
        # query at absolute position (T - S + s) attends to keys <= that position
        q_pos = jnp.arange(S)[:, None] + (T - S)
        k_pos = jnp.arange(T)[None, :]
        keep = (k_pos <= q_pos) if causal else jnp.bool_(True)
        if window is not None:
            keep = keep & ((window <= 0) | (q_pos - k_pos < window))
        scores = jnp.where(keep[None, None], scores, neg)
    if mask is not None:
        # (B,T) key-padding mask or (B,S,T) full attention mask
        if mask.ndim == 2:
            scores = jnp.where(mask[:, None, None, :].astype(bool), scores, neg)
        else:
            scores = jnp.where(mask[:, None, :, :].astype(bool), scores, neg)
    from ..parallel.sequence import scores_spec, constrain as _sp_constrain

    sspec = scores_spec(N)
    if sspec is not None:
        # pin the (B,N,S,T) layout to heads-over-('seq','model') so the
        # softmax-backward reductions (B,N,S) stay in the attention region's
        # natural sharding instead of XLA resharding them S-over-'seq' via
        # involuntary full remat (zero3×TP×SP dryrun)
        scores = _sp_constrain(scores, sspec)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if sspec is not None:
        probs = _sp_constrain(probs, sspec)
    return jnp.einsum("bnst,btnd->bsnd", probs, v)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Step:
    """What one call of ``forward`` hands each layer beside the activations
    and the layer's params: THE list of a step's operands. ``forward``
    builds it once from its arguments, which its docstring explains, and its
    scan bodies close over it (it is never a scan operand, so it is no
    pytree) and set what differs a layer (``cache``, ``layer_index``,
    ``window``) with ``dataclasses.replace``; a function on the way down
    names the fields it reads and no other.

    ``cache`` is None (training), this layer's slice of the dense cache,
    k/v of shape (B, T_max, K, D) and scalar ``index``
    (``_attend_dense_cache``), or, with ``block_table``, the WHOLE arena
    ``{"k","v": (L, NUM_BLOCKS, BLOCK, K*D)}`` of the serving layer, which
    comes back as the new cache: ``layer_index`` (int32 scalar, the layer's
    place among the layers of its KIND) says which layer's pool this block
    writes and reads inside it. A pool is never sliced out of the arena: a
    custom call's operand is a buffer of its own, so a slice is a pool-sized
    copy in and another out (ops/paged_decode_attention.py). A recurrent
    mixer keeps its state pools in the same dict (``_kda_mixer``), a slot a
    row (``state_slots``). ``write_mask`` (B, S), ``forward``'s
    ``paged_write_mask``, routes masked-off tokens (prompt chunk padding) to
    the scratch block 0 instead of the row's blocks, keeps them out of an
    MoE layer's routing and is what a recurrent mixer calls ``real``. S > 1
    queries of a row sit at ``positions[b, 0] + arange(S)`` (the chunk,
    verify and score programs).

    ``expert_banks`` (inference, an MoE model): by kind of layer the model's
    WHOLE expert stacks ``(L, E, ...)`` in place of ``layer["mlp"]``, with
    ``layer_index`` saying which layer this is - ``forward`` keeps them out
    of the layer scan's slicing, because the grouped-matmul kernel can read
    a touched expert where it lies in the stack but not out of a slice that
    XLA would first have to copy (``ops/moe_grouped_matmul.py``).

    **A mixed step** (``serving/paged_kv.build_mixed_program``): the
    activations are ONE flat run ``(1, R + C, H)``, R decode rows' tokens
    and then a prompt chunk's C, so that every per-token product of a layer
    reads its weight once for both. ``divide`` is R, where the run divides;
    the step's own ``positions`` (R, 1), ``block_table`` (R, MAXB) and
    ``write_mask`` are the ROWS' as the decode program gives them, and
    ``chunk`` holds the second part's: a ``Step`` with the ``positions``
    (1, C), ``block_table`` (1, MAXB), ``write_mask`` (1, C) and
    ``paged_run`` of the chunk program. Only the mixer's write and read over
    the pages split there (``_attend_paged``); nothing else on the way down
    reads either field."""
    mask: Optional[jax.Array] = None        # (B, T) key padding or (B, S, T)
    positions: Optional[jax.Array] = None   # (S,) shared or (B, S) a row
    cache: Optional[Dict[str, jax.Array]] = None
    block_table: Optional[jax.Array] = None     # (B, MAX_BLOCKS)
    write_mask: Optional[jax.Array] = None
    layer_index: Optional[jax.Array] = None
    pool_index: Optional[jax.Array] = None      # the pool of the cache that
    #   this layer's mixer writes and reads, where it is not ``layer_index``
    #   (``_pool_of``): a looped stack's pool of "k" and "v" in this pass,
    #   pass x layers + ``layer_index``; a sublayer's; and the layer's place
    #   among the layers of its MIXER where kinds share one
    #   (``layer_places``). None: the layer's own index is its pool's
    state_slots: Optional[jax.Array] = None     # (B,)
    paged_run: Optional[Tuple[jax.Array, jax.Array]] = None
    static_prefill: bool = False    # the dense cache is written from 0 on
    key_positions: Optional[jax.Array] = None   # (B, T), ragged alibi decode
    window: Optional[jax.Array] = None      # this layer's sliding-window
    #   width (traced scalar, <=0 = global): attention_layers models
    #   (GPT-Neo), which take the windowed jnp attention path in training
    #   and over the dense cache. A "swa" layer's window is static
    #   (``cfg.attention_window``) and rides the paged kernels
    moe_counts: bool = False
    expert_banks: Optional[Dict[str, Any]] = None
    memory: Optional[jax.Array] = None      # (B, S, inner): what the last
    #   "mamba1" layer handed on, for the "gmu" layers of the same step
    shared_layer: Optional[int] = None      # a "cross" layer: the place
    #   among the layers that keep pages of the one whose pool it reads
    sublayer_stacks: Optional[Dict[str, Any]] = None    # inference, a kind
    #   of ``SUBLAYERS``: by kind the WHOLE stacks ``(L, sublayers, ...)`` of
    #   the leaves its sublayers own, kept out of the layer scan's slicing
    #   like ``expert_banks``: sliced a layer, the compiler copies a layer's
    #   two sublayers out of the stack before each reads its half (1.3 GB a
    #   layer at the published widths, PERF.md PR 63); taken at (layer,
    #   sublayer) each product reads its matrix where it lies
    divide: Optional[int] = None    # a mixed step: the flat run's first
    #   ``divide`` tokens are decode rows, a token a row; the rest a chunk
    chunk: Optional["Step"] = None  # a mixed step: the chunk's operands


def window_table(cfg: TransformerConfig) -> jax.Array:
    """(L,) int32 per-layer sliding-window widths from the cycled
    ``attention_layers`` pattern (0 = global/unlimited). ONE builder shared
    by the resident scan and the param-offload block programs — the
    pattern expansion diverging between engines would silently change
    which layers are local."""
    pat = cfg.attention_layers
    return jnp.array(
        [cfg.attention_window if pat[i % len(pat)] == "local" else 0
         for i in range(cfg.num_layers)], jnp.int32)


def pld_gate(cfg: TransformerConfig, h: jax.Array, h_new: jax.Array,
             aux: jax.Array, idx: jax.Array, pld_theta: jax.Array):
    """Stochastic depth (reference progressive_layer_drop.py): layer i
    keeps with p = 1 - (1-theta)(i+1)/L, deeper layers drop more; kept
    outputs scaled 1/p for an unbiased expectation. The draw derives from
    the activations (loss_fn has no rng argument) so it varies across
    steps/batches but stays deterministic. ONE implementation shared by
    the resident layer scan and the param-offload block programs — the
    gate math diverging between engines would silently change the model.
    Returns (mixed h, rescaled aux)."""
    L = cfg.num_layers
    # floor keeps the 1/keep_p rescale finite even when theta has decayed
    # to ~0 for the deepest layer (0/0 NaN otherwise)
    keep_p = jnp.maximum(1.0 - (1.0 - pld_theta) * (idx + 1.0) / L, 0.01)
    key = jax.random.fold_in(_activation_derived_key(h, 17),
                             idx.astype(jnp.int32))
    gate = jax.random.bernoulli(key, keep_p).astype(jnp.float32)
    h_mixed = h + ((gate / keep_p)
                   * (h_new - h).astype(jnp.float32)).astype(h.dtype)
    # same 1/keep_p rescale as the residual — otherwise deep layers'
    # router balancing term is down-weighted in expectation
    return h_mixed, aux * gate / keep_p


def _single_chip_kernels() -> bool:
    """A recurrent mixer's one-token Pallas kernel runs where kernels are
    active and no mesh of several devices makes XLA partition the layer."""
    from ..ops import registry
    from ..parallel.mesh import ambient_mesh

    mesh = ambient_mesh()
    return registry.kernels_active() and (mesh is None or mesh.size == 1)


def _pool_of(step: Step) -> Optional[jax.Array]:
    """THE answer to "which pool of the cache is this layer's": its mixer's
    pages, tail and state lie at ``step.pool_index`` where the step names
    one, else at the layer's own index."""
    return step.layer_index if step.pool_index is None else step.pool_index


def _with_conv_history(x: jax.Array, step: Step, taps: int
                       ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """A recurrent mixer's convolution input ``x`` (B, S, W) behind the
    ``taps - 1`` rows that came before it -> ``(ext (B, taps - 1 + S, W),
    fresh)``: zeros with no cache and for a row whose first position is 0
    (``fresh`` (B,), None with no cache), else the row's slot of the pool
    ``"tail"``."""
    if step.cache is None:
        tail = jnp.zeros((x.shape[0], taps - 1, x.shape[-1]), x.dtype)
        fresh = None
    else:
        fresh = step.positions[:, 0] == 0
        tail = jnp.where(fresh[:, None, None], 0, step.cache["tail"][
            _pool_of(step), step.state_slots])
    return jnp.concatenate([tail.astype(x.dtype), x], axis=1), fresh


def _kda_mixer(cfg: TransformerConfig, h: jax.Array, p: Dict[str, Any],
               step: Step
               ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """The "kda" mixer of ``_layer_forward``: the gated delta rule with
    per-channel decay (``ops/kda.py`` has the recurrence) over the normed
    input ``h`` (B, S, H) -> (its contribution to the residual, new cache).

    ``q~ = h W_q`` and likewise k, v; on each a depthwise causal convolution
    over time and SiLU; ``q`` and ``k`` l2-normalised a head (eps 1e-6), ``q``
    times ``1/sqrt(d)``; decay ``g = -exp(A_log) * softplus((h W_f1) W_f2 +
    dt_bias)`` a channel, ``beta = 2 * sigmoid(h W_b)`` a head;
    the recurrence; ``y = (RMSNorm_head(o) * sigmoid((h W_g1) W_g2)) W_o``.

    With no cache a sequence starts from a zero state and zero convolution
    history. A cache is the serving layer's ``{"state": (layers of this
    kind, slots, heads, d, d) float32, "tail": (..., slots, taps - 1, 3 *
    heads * d)}`` beside the pages; ``step.layer_index`` says which of this
    kind's layers this is and ``step.state_slots`` (B,) which slot each row
    owns. A row whose first position is 0 starts from zeros whatever its
    slot held (a sequence is reset as data, on admission and on re-admission
    after a preemption alike). ``real``, the step's ``write_mask`` (B, S),
    marks the tokens that exist, a prefix of each row: the others (a ragged
    chunk's padding, a decode row that holds nothing) write nothing - beta
    0, decay 1, and the tail is taken from the last REAL rows."""
    from ..ops import kda as kda_ops

    f32 = jnp.float32
    cache, real = step.cache, step.write_mask
    at = (_pool_of(step), step.state_slots)     # this layer's, each row's
    B, S, _ = h.shape
    KH, KD, taps = cfg.kda_num_heads, cfg.kda_head_dim, KDA_CONV_TAPS
    W = KH * KD
    qkv = jnp.concatenate(
        [jnp.einsum("bsh,hd->bsd", h, p[w]) for w in ("wq", "wk", "wv")],
        axis=-1)                                            # (B, S, 3W)
    ext, fresh = _with_conv_history(qkv, step, taps)
    conv = jnp.concatenate([p["conv_q"], p["conv_k"], p["conv_v"]],
                           axis=-1).astype(f32)             # (taps, 3W)
    mixed = sum(conv[j] * ext[:, j:j + S].astype(f32) for j in range(taps))
    q, k, v = (a.reshape(B, S, KH, KD)
               for a in jnp.split(jax.nn.silu(mixed), 3, axis=-1))

    def l2norm(a):
        return a * lax.rsqrt((a * a).sum(-1, keepdims=True) + 1e-6)

    def low_rank(down, up):         # (h W_down) W_up, float32 out
        return jnp.einsum("bsr,rd->bsd",
                          jnp.einsum("bsh,hr->bsr", h, p[down]),
                          p[up]).astype(f32)

    q, k = l2norm(q) * KD ** -0.5, l2norm(k)
    g = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(
        low_rank("wf1", "wf2") + p["dt_bias"].astype(f32)
    ).reshape(B, S, KH, KD)
    beta = 2.0 * jax.nn.sigmoid(
        jnp.einsum("bsh,hn->bsn", h, p["wb"]).astype(f32))
    if real is not None:
        g = jnp.where(real[..., None, None], g, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)

    new_cache = None
    if cache is None:
        o, _ = kda_ops.kda_chunk(q, k, v, g, beta,
                                 jnp.zeros((B, KH, KD, KD), f32))
    else:
        if S == 1:
            advance = (kda_ops.kda_decode_step if _single_chip_kernels()
                       else kda_ops.reference_kda_decode_step)
            o, states = advance(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], cache["state"], *at)
            o = o[:, None]
        else:
            start = jnp.where(fresh[:, None, None, None], 0.0,
                              cache["state"][at])
            o, end = kda_ops.kda_chunk(q, k, v, g, beta, start)
            states = cache["state"].at[at].set(
                end.astype(cache["state"].dtype))
        new_cache = {**cache, "state": states,
                     "tail": cache["tail"].at[at].set(
                         _last_real_rows(ext, real, taps - 1).astype(
                             cache["tail"].dtype))}

    o = o * lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg.norm_eps) \
        * p["o_norm"].astype(f32)
    y = (o.reshape(B, S, W)
         * jax.nn.sigmoid(low_rank("wg1", "wg2"))).astype(h.dtype)
    return jnp.einsum("bsd,dh->bsh", y, p["wo"]), new_cache


def _mamba2_mixer(cfg: TransformerConfig, h: jax.Array, p: Dict[str, Any],
                  step: Step
                  ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """The "mamba2" mixer of ``_layer_forward``: the Mamba-2 state-space
    recurrence (``ops/mamba2.py``) over the normed input ``h`` (B, S, H) ->
    (its contribution to the residual, new cache).

    ``[z | xBC | dt] = h W_in``; on ``xBC`` a depthwise causal convolution
    over time with a bias, then SiLU, and the split into ``x`` (heads x head
    dim) and the groups' ``B`` and ``C``; ``dt = softplus(dt + dt_bias)`` and
    ``A = -exp(A_log)`` a head; the recurrence on a float32 state, plus
    ``D x``; ``y = RMSNorm_grouped(y * silu(z)) W_out``, the norm over each
    group's channels.

    The cache, the layer's index, the rows' slots, a row's start at position
    0 and ``real`` are ``_kda_mixer``'s, with ``"state"`` in the layout of
    ``ops/mamba2.pack_states`` and a tail of ``xBC``'s width; a token that
    does not exist has ``dt`` 0 and writes nothing."""
    from ..ops import mamba2 as ssm

    f32 = jnp.float32
    cache, real = step.cache, step.write_mask
    at = (_pool_of(step), step.state_slots)     # this layer's, each row's
    B, S, _ = h.shape
    MH, P, G, N, taps = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                         cfg.mamba_n_groups, cfg.mamba_state_size,
                         cfg.mamba_conv_taps)
    inner = MH * P
    z, xbc, dt = jnp.split(jnp.einsum("bsh,hd->bsd", h, p["w_in"]),
                           [inner, 2 * inner + 2 * G * N], axis=-1)
    ext, fresh = _with_conv_history(xbc, step, taps)
    conv = p["conv_w"].astype(f32)
    mixed = jax.nn.silu(p["conv_b"].astype(f32) + sum(
        conv[j] * ext[:, j:j + S].astype(f32) for j in range(taps)))
    x, Bm, Cm = jnp.split(mixed, [inner, inner + G * N], axis=-1)
    x = x.reshape(B, S, MH, P)
    Bm, Cm = Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N)
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
    if real is not None:
        dt = jnp.where(real[..., None], dt, 0.0)
    A = -jnp.exp(p["A_log"].astype(f32))

    new_cache = None
    if cache is None:
        y, _ = ssm.mamba2_chunk(x, dt, A, Bm, Cm,
                                jnp.zeros((B, G, N, MH // G * P), f32),
                                packed=True)
    else:
        if S == 1:
            advance = (ssm.mamba2_decode_step if _single_chip_kernels()
                       else ssm.reference_mamba2_decode_step)
            y, states = advance(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                                cache["state"], *at)
            y = y[:, None]
        else:
            start = jnp.where(fresh[:, None, None, None], 0.0,
                              cache["state"][at])
            y, end = ssm.mamba2_chunk(x, dt, A, Bm, Cm, start, packed=True)
            states = cache["state"].at[at].set(
                end.astype(cache["state"].dtype))
        new_cache = {**cache, "state": states,
                     "tail": cache["tail"].at[at].set(
                         _last_real_rows(ext, real, taps - 1).astype(
                             cache["tail"].dtype))}

    y = (y + p["D"].astype(f32)[:, None] * x).reshape(B, S, inner)
    y = (y * jax.nn.silu(z.astype(f32))).reshape(B, S, G, inner // G)
    y = y * lax.rsqrt((y * y).mean(-1, keepdims=True) + cfg.norm_eps)
    y = (y.reshape(B, S, inner) * p["norm"].astype(f32)).astype(h.dtype)
    return jnp.einsum("bsd,dh->bsh", y, p["w_out"]), new_cache


def _last_real_rows(ext: jax.Array, real: Optional[jax.Array],
                    n: int) -> jax.Array:
    """A convolution's new tail: of ``ext`` (B, n + S, W), the old tail and
    then this call's rows, the last ``n`` rows that exist (``real`` (B, S)
    marks a prefix of each row's tokens; None: all of them)."""
    B = ext.shape[0]
    n_real = (jnp.full((B,), ext.shape[1] - n, jnp.int32) if real is None
              else real.sum(axis=1, dtype=jnp.int32))
    return jax.vmap(lambda rows, k: lax.dynamic_slice_in_dim(
        rows, k, n, axis=0))(ext, n_real)


def _run_pages(block_table: jax.Array, block: int, S: int,
               start: jax.Array, n_valid: jax.Array
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The pages that a run of positions ``[start, start + n_valid)`` a row
    can touch, of at most ``S`` positions: ``start`` and ``n_valid`` are ()
    or (B,), ``start`` any position (not only a page's first). Returns
    ``(blk (B, P), new (B, P * block), off (B,))``: the
    P = (S + block - 2) // block + 1 physical blocks from ``start``'s page
    on, a page that holds no position of the run (or lies past the table)
    sent to the scratch block 0; which positions of those pages the run
    writes; and where in its first page it begins."""
    B, max_blocks = block_table.shape
    start = jnp.broadcast_to(start, (B,))[:, None]
    end = start + jnp.broadcast_to(n_valid, (B,))[:, None]
    P = (S + block - 2) // block + 1
    first = start // block
    page = first + jnp.arange(P, dtype=jnp.int32)                  # (B, P)
    held = (page * block < end) & (end > start) & (page < max_blocks)
    blk = jnp.where(held, jnp.take_along_axis(
        block_table, jnp.minimum(page, max_blocks - 1), axis=1), 0)
    at = first * block + jnp.arange(P * block, dtype=jnp.int32)    # (B, P*BS)
    return blk, (at >= start) & (at < end), start[:, 0] % block


def _write_pages(arena: jax.Array, layer: jax.Array, rows: jax.Array,
                 blk: jax.Array, new: jax.Array,
                 off: jax.Array) -> jax.Array:
    """``rows`` (B, S, W), a run of positions, written into ``arena``
    (L, NUM_BLOCKS, BLOCK, W) as the whole pages that ``_run_pages`` names
    (``blk``, ``new``, ``off``): the pages are gathered, the run's rows laid
    over them from row ``off`` on, every position outside ``new`` keeps what
    its page held, and ONE scatter of (BLOCK, W) windows puts them back
    where they lie (the arena is the layer scan's carry: no pool is
    copied). The bytes of every block but scratch are those of the row
    scatter."""
    B, S, W = rows.shape
    P, block = blk.shape[1], arena.shape[2]
    old = arena[layer, blk].reshape(B, P * block, W)
    # row s of the run at row off + s of its pages, by a pad and a slice: on
    # the chip a tenth faster than a dynamic_update_slice into `old`, and
    # 17 single-page updates take half as long again (PERF.md, PR 49)
    padded = jnp.pad(rows, ((0, 0), (block, P * block - S), (0, 0)))
    laid = jnp.stack([lax.dynamic_slice_in_dim(padded[b], block - off[b],
                                               P * block) for b in range(B)])
    pages = jnp.where(new[..., None], laid, old)
    return arena.at[layer, blk].set(pages.reshape(B, P, block, W))


def _qkv_heads(cfg: TransformerConfig, h: jax.Array, p: Dict[str, Any],
               cached: bool = False, divided: bool = False,
               form: Optional["AttnForm"] = None
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The softmax mixer's projections of ``h`` (B, S, H) as heads: q
    (B, S, N, D), k (B, S, K, D) and v (B, S, K, Dv) by the ``form``'s
    ``attn_shape``, biased, scaled and normed, not yet roped. ``cached``:
    the step keeps a cache (an inference program); ``divided``: the run
    divides behind the heads (a mixed step)."""
    B, S, _ = h.shape
    shape = attn_shape(cfg, form)
    N, K, D, Dv = shape.heads, shape.kv_heads, shape.key_dim, shape.value_dim
    q = _qeinsum("bsh,hd->bsd", h, p["wq"], cfg.dtype, a8=cfg.a8_decode)
    k = _qeinsum("bsh,hd->bsd", h, p["wk"], cfg.dtype, a8=cfg.a8_decode)
    v = _qeinsum("bsh,hd->bsd", h, p["wv"], cfg.dtype, a8=cfg.a8_decode)
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.value_scale != 1.0:
        v = v * jnp.asarray(cfg.value_scale, v.dtype)
    # rope with no norm over the rows before it: nothing stands between k's
    # product and the heads its rope wants, in a chunk as in a step
    # (a norm a head stands behind the split into heads, as rope does)
    bare_rope = (cached and cfg.position == "rope"
                 and cfg.qk_norm in (False, "head"))
    if S == 1 or bare_rope or divided:
        # a decode step: q's product (and bias) is whole as ROWS before
        # anything splits it into heads, as k's and v's are (they go back to
        # rows for the page write). Left to fold the reshape into the
        # product, the chip's compiler makes the product heads-major and
        # pays with the WEIGHT: sliced out of the layer stack and transposed
        # every step, two passes over it before the product's one (PERF.md,
        # PR 53). The heads are then a relayout of B rows, not of H x N*D
        q = lax.optimization_barrier(q)
    if bare_rope or divided:
        # k as well (a norm over the rows parts the two, and a model with no
        # rope writes k to the pages as the rows it is): the compiler
        # re-laid the WHOLE stack of wk at a step's entry, and of wq too at
        # a chunk's, 0.4 GB copied each at 48 layers of 2,048 (PERF.md, PR 60)
        k = lax.optimization_barrier(k)
    if divided:
        # and v, in a mixed step: the run's two parts are sliced out of the
        # heads, and with the slices folded into the three products the
        # compiler sliced each weight out of its stack and transposed it, a
        # layer (8 MB each at a width of 2,048; PERF.md, PR 61)
        v = lax.optimization_barrier(v)
    if cfg.qk_norm == "head":
        # over each head's D values, one weight of D for all heads (the
        # published Lfm2MoeAttention's q_layernorm and k_layernorm), before
        # rope; in float32 and plain: D is half a lane tile
        def head_norm(a, w):
            a = a.reshape(B, S, -1, D).astype(jnp.float32)
            return (a * lax.rsqrt((a * a).mean(-1, keepdims=True)
                                  + cfg.norm_eps)
                    * w.astype(jnp.float32)).astype(cfg.dtype)

        return (head_norm(q, p["q_norm"]), head_norm(k, p["k_norm"]),
                v.reshape(B, S, K, Dv))
    if cfg.qk_norm:
        # over all heads at once (the published OlmoeAttention: q_norm and
        # k_norm are hidden-wide), before the heads are split and roped
        q = _norm(q, p["q_norm"], None, "rmsnorm", cfg.norm_eps)
        k = _norm(k, p["k_norm"], None, "rmsnorm", cfg.norm_eps)
    return (q.reshape(B, S, N, D), k.reshape(B, S, K, D),
            v.reshape(B, S, K, Dv))


def _rope_qk(cfg: TransformerConfig, q: jax.Array, k: jax.Array,
             positions: jax.Array, form: Optional["AttnForm"] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """q and k with the rotary embedding of ``positions``, at the ``form``'s
    base (a model of another position kind: as they came)."""
    if cfg.position != "rope":
        return q, k
    D = cfg.head_dim
    rd = cfg.rotary_dim or D
    cos, sin = rope_table(positions, rd, attn_shape(cfg, form).rope_theta)
    if rd < D:
        # partial rotary (GPT-J/NeoX): rope on the first rd dims only.
        # (GPT-J's interleaved convention is handled at import time by
        # permuting the rotary columns of wq/wk into rotate-half order.)
        q = jnp.concatenate(
            [apply_rope(q[..., :rd], cos, sin), q[..., rd:]], axis=-1)
        k = jnp.concatenate(
            [apply_rope(k[..., :rd], cos, sin), k[..., rd:]], axis=-1)
        return q, k
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin)


def _attention_fn(cfg: TransformerConfig, window: Optional[jax.Array]
                  ) -> Tuple[Callable, Optional[jax.Array]]:
    """(the attention a read without pages calls: the custom
    ``attention_impl``, the platform's default, or the windowed jnp path;
    the per-head alibi slopes of a model that has them)."""
    alibi = alibi_slopes(cfg.num_heads) if cfg.position == "alibi" else None
    if alibi is not None and cfg.attention_impl is not None:
        _require_impl_kwarg(cfg.attention_impl, "alibi",
                            "position='alibi' models (BLOOM) — silently "
                            "dropping the alibi bias would change the model")
    if window is None and cfg.attention_scale is None:
        return cfg.attention_impl or default_attention_impl(), alibi
    if cfg.attention_impl is not None:
        raise NotImplementedError(
            "custom attention_impl + sliding-window/custom-scale "
            "attention (GPT-Neo family) is not supported — silently "
            "replacing the custom impl with the windowed jnp path "
            "would change the model")
    # windowed / custom-scale attention routes through the jnp path
    # (the flash kernel has neither operand); window is applied at the
    # call sites — the decode fallback needs TRUE positions, not
    # the end-aligned convention inside dot_product_attention
    return partial(dot_product_attention, scale=cfg.attention_scale), alibi


@dataclasses.dataclass(frozen=True)
class AttnForm:
    """What tells the softmax mixer's forms apart: what a layer projects,
    which pool it writes and which it reads (docs/models.md)."""
    window: bool = False    # keys and values go to a RING of pages a row
    #   (the pools "wk" and "wv", addressed by the row's slot) and a query
    #   sees ``cfg.attention_window`` of them
    cross: bool = False     # q and the output projection alone: the keys
    #   and values are those of the layer ``step.shared_layer`` of "k", "v"


@dataclasses.dataclass(frozen=True)
class AttnShape:
    """The sizes of a softmax layer in one form, stated ONCE (``attn_shape``)
    for the init, the projections, the rope, the pages and the benchmark's
    cost functions alike."""
    heads: int          # query heads
    kv_heads: int       # key-value heads: ``heads // kv_heads`` queries each
    key_dim: int        # a head's keys and queries
    value_dim: int      # a head's values, and its share of the output
    rope_theta: float
    sink: bool          # a learned sink a query head in the softmax


def attn_shape(cfg: TransformerConfig,
               form: Optional[AttnForm] = None) -> AttnShape:
    """THE answer to "how many heads, how wide, which rope base, a sink or
    not" for a softmax layer of ``form`` (None: the plain "attn" mixer). A
    window layer has what ``window_kv_heads``, ``window_rope_theta`` and
    ``window_sink`` say; every other form the model's own."""
    window = form is not None and form.window
    return AttnShape(
        heads=cfg.num_heads,
        kv_heads=(window and cfg.window_kv_heads) or cfg.num_kv_heads,
        key_dim=cfg.head_dim, value_dim=cfg.v_head_dim or cfg.head_dim,
        rope_theta=(window and cfg.window_rope_theta) or cfg.rope_theta,
        sink=bool(window and cfg.window_sink))


def page_widths(cfg: TransformerConfig, window: bool = False
                ) -> Tuple[int, int]:
    """(lanes of a token's keys, of its values) in a pool of pages: the
    arena's ``"k"`` and ``"v"``, or with ``window`` the rings' ``"wk"`` and
    ``"wv"``; a token's key-value heads side by side."""
    shape = attn_shape(cfg, AttnForm(window=window))
    return shape.kv_heads * shape.key_dim, shape.kv_heads * shape.value_dim


def _ring_table(cache: Dict[str, jax.Array], slots: jax.Array,
                max_blocks: int) -> jax.Array:
    """The block table of a window layer, (B, max_blocks), made in the
    program: the ring of the row's slot, a run of pages of the pools
    ``"wk"`` and ``"wv"`` behind their scratch page 0, repeated. Position p
    then lies where the paged write and read look for it, in table entry
    ``p // BLOCK`` at offset ``p % BLOCK``; a page is written over when the
    ring comes round, which is after every query that could see it
    (``inference/kv_cache.ring_blocks``)."""
    from ..inference.kv_cache import cache_slots

    ring = (cache["wk"].shape[1] - 1) // cache_slots(cache)
    return (1 + slots[:, None] * ring
            + jnp.arange(max_blocks, dtype=jnp.int32)[None] % ring)


def _attend_paged(cfg: TransformerConfig, q: jax.Array, k: jax.Array,
                  v: jax.Array, step: Step, form: Optional[AttnForm] = None,
                  sink: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The "attn" mixer's write and read over the serving layer's pages;
    with a ``form``, over a window's ring (no ``k``: the read alone).
    ``sink`` (N,): the layer's learned sinks, where its form has them."""
    # PAGED serving path (deepspeed_tpu/serving/paged_kv.py): token at
    # absolute position p lands in physical block block_table[b, p//BS]
    # at offset p%BS — a scatter write. The layout is left-aligned
    # (column == true position), so causality over true positions is
    # the whole validity story and keys' alibi column bias is exact by
    # construction. The read walks the table and is shape-static: one
    # compiled program covers any arena occupancy (the jit-cache analog
    # of vLLM's PagedAttention block tables).
    from ..ops.paged_decode_attention import paged_attention

    if step.divide is not None:
        return _attend_mixed(cfg, q, k, v, step)
    cache, block_table = step.cache, step.block_table
    layer = _pool_of(step)
    pos = step.positions            # (B, S): ``forward`` takes no other
    kn, vn, read = "k", "v", {}
    if form is not None:
        # the heads as ``_diff_pairs`` folds them: twice as wide, 1/sqrt of
        # the model's own head size
        # a full layer's walk by whether later layers share its pool
        read = {"scale": cfg.head_dim ** -0.5,
                "name": ("shared_kv_decode_attention"
                         if "cross" in layer_kinds(cfg)
                         else "full_kv_decode_attention")}
        if sink is not None:
            read["sink"] = sink
        if form.window:
            kn, vn = "wk", "wv"
            block_table = _ring_table(cache, step.state_slots,
                                      block_table.shape[1])
            read.update(window=cfg.attention_window,
                        name="window_decode_attention")
        if form.cross:
            return paged_attention(q, cache[kn], cache[vn], step.shared_layer,
                                   block_table, pos, **read), cache
    B, S, K, D = k.shape
    q, k = _rope_qk(cfg, q, k, step.positions, form)
    _, alibi = _attention_fn(cfg, None)
    BSz = cache[kn].shape[2]
    k_rows = k.reshape(B, S, K * D).astype(cache[kn].dtype)
    v_rows = v.reshape(B, S, -1).astype(cache[vn].dtype)
    write = _page_writer(step, block_table, BSz, S)
    ck = write(cache[kn], layer, k_rows)
    cv = write(cache[vn], layer, v_rows)
    attn = paged_attention(q, ck, cv, layer, block_table, pos, alibi=alibi,
                           **read)
    return attn, {**cache, kn: ck, vn: cv}


def _page_writer(step: Step, block_table: jax.Array, BSz: int,
                 S: int) -> Callable:
    """``write(arena, pool, rows) -> arena``: the step's S tokens a row,
    ``rows`` (B, S, W), written into pool ``pool`` of an arena of pages at
    the step's ``positions`` through ``block_table``. Where they go is
    reckoned once, here, for every arena the step writes."""
    if step.paged_run is not None and S >= BSz:
        # a RUN of a page or more (a prompt or scoring chunk): whole
        # pages, not rows. The arena's tiling on the chip packs two
        # consecutive token rows into every 32-bit word and makes a
        # 16-row page 16 whole tiles, so a row update is a half-word
        # write into tiles the next row touches again: 256 of them cost
        # a chunk program a quarter of its time (PERF.md, PR 49)
        pages = _run_pages(block_table, BSz, S, *step.paged_run)
        return lambda arena, pool, rows: _write_pages(arena, pool, rows,
                                                      *pages)
    # one token a row (decode) or fewer than a page (verify): rows
    T_view = block_table.shape[1] * BSz
    wpos = jnp.minimum(step.positions, T_view - 1)  # pad writes in-range
    blk = jnp.take_along_axis(block_table, wpos // BSz, axis=1)
    off = wpos % BSz
    if step.write_mask is not None:
        # chunk padding / inactive decode rows write to scratch
        # block 0
        blk = jnp.where(step.write_mask, blk, 0)
        off = jnp.where(step.write_mask, off, 0)
    # ONE scatter into the 4-D arena, which the layer scan carries:
    # it updates the carry in place, only the written rows move. An
    # arena row is one token's K*D lanes
    # (ops/paged_decode_attention.py)
    return lambda arena, pool, rows: arena.at[pool, blk, off].set(rows)


def _attend_mixed(cfg: TransformerConfig, q: jax.Array, k: jax.Array,
                  v: jax.Array, step: Step
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``_attend_paged`` of a mixed step: ``q``, ``k`` and ``v`` are the
    heads of the flat run (1, R + C, ...). Its last C tokens go down as the
    chunk program's (``(1, C)``: whole pages written, the prefill read) and
    its first R = ``step.divide`` as the decode program's (``(R, 1)``: a row
    scattered a token, the decode read through each row's own table and
    length), in the order the two programs ran in, each with the ``Step``
    fields it has there; their outputs are laid back into the flat run. A
    chunk's request is no decode row, so neither part reads a page the other
    writes but scratch, which nobody reads."""
    R = step.divide
    rows = dataclasses.replace(step, divide=None, chunk=None)
    part = step.chunk
    chunk = dataclasses.replace(
        rows, positions=part.positions, block_table=part.block_table,
        write_mask=part.write_mask, paged_run=part.paged_run)
    out_c, cache = _attend_paged(cfg, q[:, R:], k[:, R:], v[:, R:], chunk)
    out_r, cache = _attend_paged(
        cfg, *(jnp.swapaxes(a[:, :R], 0, 1) for a in (q, k, v)),
        dataclasses.replace(rows, cache=cache))
    return jnp.concatenate([jnp.swapaxes(out_r, 0, 1), out_c], axis=1), cache


def _attend_dense_cache(cfg: TransformerConfig, q: jax.Array, k: jax.Array,
                        v: jax.Array, step: Step
                        ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The "attn" mixer's write and read over the dense cache of
    ``inference/engine.py``: k and v (B, T_max, K, D) and a scalar
    ``index``, this layer's slice."""
    from ..ops import registry

    cache, mask, window = step.cache, step.mask, step.window
    B, S = q.shape[:2]
    q, k = _rope_qk(cfg, q, k, step.positions)
    attn_fn, alibi = _attention_fn(cfg, window)
    idx = cache["index"]
    ck = lax.dynamic_update_slice(cache["k"], k, (0, idx, 0, 0))
    cv = lax.dynamic_update_slice(cache["v"], v, (0, idx, 0, 0))
    new_cache = {"k": ck, "v": cv, "index": idx + S}
    T = ck.shape[1]
    kernels = (cfg.attention_impl is None and registry.kernels_active()
               and window is None and cfg.attention_scale is None)
    akw = {} if alibi is None else {"alibi": alibi}
    if S == 1 and kernels:
        # single-token decode → Pallas decode kernel (GQA-native, reads
        # the arena without head expansion; alibi in-kernel)
        from ..ops.decode_attention import decode_attention

        causal_valid = (jnp.arange(T)[None, :] <= idx).astype(jnp.int32)
        if mask is not None:
            # AND with causal so unwritten arena slots are never live,
            # matching the jnp fallback's causal_mask * mask semantics
            valid = mask * causal_valid
        else:
            valid = jnp.broadcast_to(causal_valid, (B, T))
        return decode_attention(q[:, 0], ck, cv, valid, alibi=alibi,
                                key_positions=step.key_positions
                                )[:, None], new_cache
    if step.static_prefill and S > 1 and kernels and T % 128 == 0:
        # prefill from position 0: queries sit at absolute rows 0..S-1, so
        # the flash kernel's 0-based causal col<=row over the arena is
        # exact and the (B, T_max) validity mask covers padding +
        # unwritten slots — keeps the TTFT path on the flash kernel
        # instead of a (B,S,T) mask fallback. Kernel-only: the jnp path's
        # causal convention is end-aligned (q at T-S), so it must not
        # take this branch.
        valid = (mask if mask is not None else
                 jnp.broadcast_to(
                     (jnp.arange(T)[None, :] < S).astype(jnp.int32), (B, T)))
        return attn_fn(q, ck, cv, valid, causal=True, **akw), new_cache
    # causal over absolute positions: query s sits at idx+s, keys valid <= that
    q_pos = idx + jnp.arange(S)
    k_pos = jnp.arange(T)
    causal_mask = (k_pos[None, :] <= q_pos[:, None])            # (S,T)
    if window is not None:
        # sliding window over TRUE positions (decode: q at idx+s)
        causal_mask = causal_mask & (
            (window <= 0)
            | (q_pos[:, None] - k_pos[None, :] < window))
    causal_mask = causal_mask.astype(jnp.int32)
    full = jnp.broadcast_to(causal_mask[None], (B, S, T))
    if mask is not None:  # (B, T_prompt) padding mask padded to T by caller
        full = full * mask[:, None, :]
    if alibi is not None and step.key_positions is not None:
        akw["key_positions"] = step.key_positions
        if cfg.attention_impl is None:
            attn_fn = dot_product_attention
        else:
            _require_impl_kwarg(
                cfg.attention_impl, "key_positions",
                "ragged alibi decode — silently swapping in the "
                "reference attention would change the model's "
                "performance profile")
    return attn_fn(q, ck, cv, full, causal=False, **akw), new_cache


def _attend_train(cfg: TransformerConfig, q: jax.Array, k: jax.Array,
                  v: jax.Array, step: Step) -> Tuple[jax.Array, None]:
    """The "attn" mixer's read with no cache: the sequence against itself,
    under sequence parallelism between its two reshards."""
    # SP reshard around attention. Ulysses: sequence gathered, heads
    # scattered over ('seq','model') — XLA lowers the constraint to the
    # head-scatter all-to-all. Ring: tokens STAY seq-sharded; KV chunks
    # rotate inside ring_attention instead. Training path only (no cache).
    from ..parallel.ring import ring_attention, ring_attention_enabled
    from ..parallel.sequence import attn_out_spec, heads_spec, constrain

    N, K = q.shape[2], k.shape[2]
    use_ring = ring_attention_enabled() and cfg.attention_impl is None
    qspec, kspec = heads_spec(N), heads_spec(K)
    if not use_ring and qspec is not None and kspec is not None:
        # two-step reshard: first pin the natural post-reshape layout
        # (tokens over 'seq', heads over 'model') so the head-scatter
        # all-to-all is a 4D→4D transition — without this, the BACKWARD
        # of the (B,S,N·D)→(B,S,N,D) reshape sees a heads-over-4-way
        # cotangent and XLA falls into involuntary full remat
        nat_q, nat_k = attn_out_spec(N), attn_out_spec(K)
        if nat_q is not None and nat_k is not None:
            q = constrain(q, nat_q)
            k = constrain(k, nat_k)
            v = constrain(v, nat_k)
        q = constrain(q, qspec)
        k = constrain(k, kspec)
        v = constrain(v, kspec)
    q, k = _rope_qk(cfg, q, k, step.positions)
    attn_fn, alibi = _attention_fn(cfg, step.window)
    if use_ring:
        if alibi is not None:
            raise NotImplementedError(
                "ring attention + alibi is not supported yet — use "
                "sequence_parallel_impl='ulysses' for BLOOM-family models")
        return ring_attention(q, k, v, mask=step.mask, causal=True), None
    kw = {} if step.window is None else {"window": step.window}
    if alibi is not None:
        kw["alibi"] = alibi
    attn = attn_fn(q, k, v, step.mask, causal=cfg.causal, **kw)
    out_spec = attn_out_spec(N)
    if out_spec is not None:
        # Ulysses inverse all-to-all on the 4D tensor (see attn_out_spec)
        attn = constrain(attn, out_spec)
    return attn, None


def _diff_pairs(cfg: TransformerConfig, q: jax.Array,
                k: Optional[jax.Array], v: Optional[jax.Array]):
    """Differential attention's pairs as heads the paged kernels know: the
    key heads ``2g`` and ``2g + 1`` side by side are ONE key head of twice
    the size, and so are the value heads (the group's 2 D wide value); a
    query head is laid in the half of its own key, ``[q, 0]`` for the first
    of a pair and ``[0, q]`` for the second, so that its product with the
    wide key is its product with its own (the zeros add exactly nothing)
    and the group's four query heads share the wide head as GQA heads do.
    The arena's rows are the bytes they were: K * D lanes a token."""
    B, S, N, D = q.shape
    zeros = jnp.zeros_like(q)
    first = (jnp.arange(N) % 2 == 0)[:, None]
    q = jnp.where(first, jnp.concatenate([q, zeros], axis=-1),
                  jnp.concatenate([zeros, q], axis=-1))
    if k is None:
        return q, None, None
    wide = k.shape[:2] + (k.shape[2] // 2, 2 * D)
    return q, k.reshape(wide), v.reshape(wide)


def _diff_combine(cfg: TransformerConfig, attn: jax.Array,
                  p: Dict[str, Any]) -> jax.Array:
    """``attn`` (B, S, N, 2 D), the two maps of each pair over the group's
    wide value -> (B, S, N * D): ``RMSNorm(a1 - lam a2; g) * (1 - lam0)``,
    ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``, in float32."""
    f32 = jnp.float32
    B, S, N, W = attn.shape
    lam0 = p["lam_init"].astype(f32)
    lam = (jnp.exp(jnp.sum(p["lam_q1"].astype(f32) * p["lam_k1"].astype(f32)))
           - jnp.exp(jnp.sum(p["lam_q2"].astype(f32)
                             * p["lam_k2"].astype(f32))) + lam0)
    pairs = attn.astype(f32).reshape(B, S, N // 2, 2, W)
    o = pairs[:, :, :, 0] - lam * pairs[:, :, :, 1]
    o = o * lax.rsqrt((o * o).mean(-1, keepdims=True) + cfg.norm_eps)
    o = o * p["subln"].astype(f32) * (1.0 - lam0)
    return o.reshape(B, S, N // 2 * W).astype(attn.dtype)


def _softmax_mixer(cfg: TransformerConfig, h: jax.Array, p: Dict[str, Any],
                   step: Step
                   ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """The "attn" mixer of ``_layer_forward`` (``_softmax`` in no form)."""
    return _softmax(cfg, h, p, step, None)


def _softmax(cfg: TransformerConfig, h: jax.Array, p: Dict[str, Any],
             step: Step, form: Optional[AttnForm]
             ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """The softmax mixer of ``_layer_forward``: softmax attention over the
    normed input ``h`` -> (its contribution to the residual, new cache).
    The projections, one of three reads by what the step keeps (nothing,
    the dense cache, pages), the output gate and projection. With a
    ``form`` (the kinds "swa", "full", "cross"): over the serving layer's
    pages alone, a window's ring or another layer's pool, and as
    differential attention where the model has it."""
    B, S, _ = h.shape
    if form is not None:
        if step.cache is None or step.block_table is None:
            raise NotImplementedError(
                "a window, full or cross layer of a stack of layer_runs runs "
                "over the serving layer's paged cache: a cross layer reads "
                "the full layer's pages and a window layer its row's ring, "
                "which training and the dense cache do not keep")
        if form.cross:
            q = _qeinsum("bsh,hd->bsd", h, p["wq"], cfg.dtype,
                         a8=cfg.a8_decode)
            if "bq" in p:
                q = q + p["bq"]
            if S == 1:
                q = lax.optimization_barrier(q)     # see ``_qkv_heads``
            heads = (q.reshape(B, S, cfg.num_heads, cfg.head_dim), None, None)
        else:
            heads = _qkv_heads(cfg, h, p, cached=True, form=form)
        if cfg.diff_attn:
            heads = _diff_pairs(cfg, *heads)
        attn, new_cache = _attend_paged(cfg, *heads, step, form,
                                        p.get("sink"))
        if cfg.diff_attn:
            attn = _diff_combine(cfg, attn, p)
    else:
        attend = (_attend_train if step.cache is None else
                  _attend_dense_cache if step.block_table is None else
                  _attend_paged)
        attn, new_cache = attend(
            cfg, *_qkv_heads(cfg, h, p, cached=step.cache is not None,
                             divided=step.divide is not None), step)
    attn = attn.reshape(B, S, -1)       # (B, S, N * Dv)
    if "wg" in p:
        # the output gate: elementwise and full-rank, from the layer's input
        gate = _qeinsum("bsh,hd->bsd", h, p["wg"], cfg.dtype,
                        a8=cfg.a8_decode)
        attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
            attn.dtype)
    attn_out = _qeinsum("bsd,dh->bsh", attn, p["wo"], cfg.dtype,
                        a8=cfg.a8_decode)
    if "bo" in p:
        attn_out = attn_out + p["bo"]
    return attn_out, new_cache


def _attn_init(cfg: TransformerConfig, normal, uniform,
               form: Optional[AttnForm] = None) -> Dict[str, Any]:
    H = cfg.hidden_size
    shape = attn_shape(cfg, form)
    N, K, D, Dv = shape.heads, shape.kv_heads, shape.key_dim, shape.value_dim
    p = {
        "wq": normal(0, (H, N * D)),
        "wk": normal(1, (H, K * D)),
        "wv": normal(2, (H, K * Dv)),
        "wo": normal(3, (N * Dv, H), _resid_std(cfg)),
    }
    if cfg.qk_norm == "head":
        p["q_norm"] = jnp.ones((D,), cfg.dtype)
        p["k_norm"] = jnp.ones((D,), cfg.dtype)
    elif cfg.qk_norm:
        p["q_norm"] = jnp.ones((N * D,), cfg.dtype)
        p["k_norm"] = jnp.ones((K * D,), cfg.dtype)
    if cfg.attn_gate:
        p["wg"] = normal(11, (H, N * D))
    if cfg.norm == "layernorm":
        p["bq"] = jnp.zeros((N * D,), cfg.dtype)
        p["bk"] = jnp.zeros((K * D,), cfg.dtype)
        p["bv"] = jnp.zeros((K * Dv,), cfg.dtype)
        p["bo"] = jnp.zeros((H,), cfg.dtype)
    return p


def _attn_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    attn = {"wq": (LAYERS, EMBED, HEADS), "wk": (LAYERS, EMBED, KV_HEADS),
            "wv": (LAYERS, EMBED, KV_HEADS), "wo": (LAYERS, HEADS, EMBED)}
    if cfg.norm == "layernorm":
        attn.update({"bq": (LAYERS, HEADS), "bk": (LAYERS, KV_HEADS),
                     "bv": (LAYERS, KV_HEADS), "bo": (LAYERS, EMBED)})
    if cfg.qk_norm == "head":
        attn.update({"q_norm": (LAYERS, None), "k_norm": (LAYERS, None)})
    elif cfg.qk_norm:
        attn.update({"q_norm": (LAYERS, HEADS), "k_norm": (LAYERS, KV_HEADS)})
    if cfg.attn_gate:
        attn["wg"] = (LAYERS, EMBED, HEADS)
    return attn


def _kda_init(cfg: TransformerConfig, normal, uniform) -> Dict[str, Any]:
    H = cfg.hidden_size
    KH, KD, r = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_gate_rank
    W = KH * KD
    return {
        "wq": normal(20, (H, W)), "wk": normal(21, (H, W)),
        "wv": normal(22, (H, W)), "wo": normal(23, (W, H), _resid_std(cfg)),
        # taps of the depthwise convolution over time, oldest first
        "conv_q": normal(24, (KDA_CONV_TAPS, W), 0.5),
        "conv_k": normal(25, (KDA_CONV_TAPS, W), 0.5),
        "conv_v": normal(26, (KDA_CONV_TAPS, W), 0.5),
        "wf1": normal(27, (H, r)), "wf2": normal(28, (r, W)),
        "wg1": normal(29, (H, r)), "wg2": normal(30, (r, W)),
        "wb": normal(31, (H, KH)),
        # decay g = -exp(A_log) * softplus(. + dt_bias): a rate of
        # 1 to 16 a head times a step of 0.001 to 0.1 a channel
        # (softplus^-1 of it), so a token keeps 20% to 99.9%
        "A_log": jnp.log(uniform(32, (KH,), 1.0, 16.0)),
        "dt_bias": (lambda dt: dt + jnp.log(-jnp.expm1(-dt)))(
            jnp.exp(uniform(33, (W,), jnp.log(1e-3), jnp.log(0.1)))),
        "o_norm": jnp.ones((KD,), cfg.dtype),
    }


def _kda_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    return {**{w: (LAYERS, EMBED, HEADS) for w in ("wq", "wk", "wv")},
            "wo": (LAYERS, HEADS, EMBED),
            **{c: (LAYERS, None, HEADS) for c in ("conv_q", "conv_k",
                                                    "conv_v")},
            "wf1": (LAYERS, EMBED, None), "wf2": (LAYERS, None, HEADS),
            "wg1": (LAYERS, EMBED, None), "wg2": (LAYERS, None, HEADS),
            "wb": (LAYERS, EMBED, None), "A_log": (LAYERS, None),
            "dt_bias": (LAYERS, HEADS), "o_norm": (LAYERS, None)}


def _kda_state(cfg: TransformerConfig):
    H, d = cfg.kda_num_heads, cfg.kda_head_dim
    return (H, d, d), KDA_CONV_TAPS, 3 * H * d


def _mamba2_init(cfg: TransformerConfig, normal, uniform) -> Dict[str, Any]:
    H = cfg.hidden_size
    MH, P = cfg.mamba_num_heads, cfg.mamba_head_dim
    inner = MH * P
    conv = inner + 2 * cfg.mamba_n_groups * cfg.mamba_state_size
    return {
        # [z | x B C | dt], as the published in_proj lays them
        "w_in": normal(40, (H, inner + conv + MH)),
        # taps of the depthwise convolution over time, oldest first
        "conv_w": normal(41, (cfg.mamba_conv_taps, conv), 0.5),
        "conv_b": normal(42, (conv,)),
        # the published init: a step dt of 0.001 to 0.1 a head (its
        # inverse softplus, floored at 1e-4), a rate of 1 to 16, D 1
        "dt_bias": (lambda dt: dt + jnp.log(-jnp.expm1(-dt)))(
            jnp.maximum(jnp.exp(uniform(
                43, (MH,), jnp.log(1e-3), jnp.log(0.1))), 1e-4)),
        "A_log": jnp.log(uniform(44, (MH,), 1.0, 16.0)),
        "D": jnp.ones((MH,), jnp.float32),
        "norm": jnp.ones((inner,), cfg.dtype),
        "w_out": normal(45, (inner, H), _resid_std(cfg)),
    }


def _mamba2_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    return {"w_in": (LAYERS, EMBED, HEADS), "w_out": (LAYERS, HEADS, EMBED),
            "conv_w": (LAYERS, None, HEADS), "conv_b": (LAYERS, HEADS),
            "norm": (LAYERS, HEADS),
            **{a: (LAYERS, None) for a in ("dt_bias", "A_log", "D")}}


def _mamba2_state(cfg: TransformerConfig):
    H, P, G, N = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                  cfg.mamba_n_groups, cfg.mamba_state_size)
    return (G, N, H // G * P), cfg.mamba_conv_taps, H * P + 2 * G * N


# what the softmax mixer's subtree holds for its keys and values, which a
# cross layer lacks: it reads another layer's
_CROSS_LACKS = ("wk", "wv", "bk", "bv", "k_norm")


# where a drawn sink stands. Random projections of std 0.02 give scores near
# 0, so a window's keys weigh about their number together and a sink of s
# takes e^s / (e^s + keys) of the mass: at 0 under 1% of a window of 128
# keys, which a bfloat16 model's own rounding hides (leaving the sinks out
# read 0.51-0.58 beside sound readings of 0.17-0.34, PERF.md section 6, PR
# 70); at 3 +- 1 it takes 5-30%, as a trained sink takes a large share
SINK_MEAN = 3.0


def _form_init(cfg: TransformerConfig, normal, uniform,
               form: AttnForm) -> Dict[str, Any]:
    """The softmax mixer's subtree in one of its forms: the projections'
    biases drawn (std 0.02); a cross layer has a query and an output
    projection alone; differential attention adds four
    vectors of a head's size (normal, std 0.1, as published) and the scale
    of the pairs' norm; a form with a sink a float a query head, drawn with
    a spread of 1 around ``SINK_MEAN``."""
    p = _attn_init(cfg, normal, uniform, form)
    if attn_shape(cfg, form).sink:
        p["sink"] = SINK_MEAN + normal(58, (cfg.num_heads,), 1.0).astype(
            jnp.float32)
    for tag, name in enumerate(("bq", "bk", "bv", "bo")):
        if name in p:       # drawn: a zero bias would leave a term untested
            p[name] = normal(54 + tag, p[name].shape)
    if form.cross:
        p = {k: v for k, v in p.items() if k not in _CROSS_LACKS}
    if cfg.diff_attn:
        D = cfg.head_dim
        for tag, name in enumerate(("lam_q1", "lam_k1", "lam_q2", "lam_k2")):
            p[name] = normal(50 + tag, (D,), 0.1).astype(jnp.float32)
        p["subln"] = jnp.ones((2 * D,), cfg.dtype)
    return p


def _form_axes(cfg: TransformerConfig, form: AttnForm) -> Dict[str, Any]:
    attn = _attn_axes(cfg)
    if form.cross:
        attn = {k: v for k, v in attn.items() if k not in _CROSS_LACKS}
    if cfg.diff_attn:
        attn.update({name: (LAYERS, None) for name in (
            "lam_q1", "lam_k1", "lam_q2", "lam_k2", "subln")},
            lam_init=(LAYERS,))
    if attn_shape(cfg, form).sink:
        attn["sink"] = (LAYERS, None)
    return attn


def _mamba1_sizes(cfg: TransformerConfig) -> Tuple[int, int, int]:
    """(inner width, state a channel, rank of the step's projection)."""
    return (cfg.mamba_expand * cfg.hidden_size, cfg.mamba_state_size,
            -(-cfg.hidden_size // 16))


def _mamba1_init(cfg: TransformerConfig, normal, uniform) -> Dict[str, Any]:
    H = cfg.hidden_size
    I, N, R = _mamba1_sizes(cfg)
    return {
        "w_in": normal(60, (H, 2 * I)),         # [x | z]
        # taps of the depthwise convolution over time, oldest first
        "conv_w": normal(61, (cfg.mamba_conv_taps, I), 0.5),
        "conv_b": normal(62, (I,)),
        "w_x": normal(63, (I, R + 2 * N)),      # [step | B | C]
        # the published init of the step's projection: uniform in
        # +-rank^-0.5, and a step of 0.001 to 0.1 a channel (its inverse
        # softplus, floored at 1e-4)
        "w_dt": uniform(64, (R, I), -R ** -0.5, R ** -0.5).astype(cfg.dtype),
        "dt_bias": (lambda dt: dt + jnp.log(-jnp.expm1(-dt)))(
            jnp.maximum(jnp.exp(uniform(
                65, (I,), jnp.log(1e-3), jnp.log(0.1))), 1e-4)),
        # a rate of 1 to 16 a (state, channel), transposed as the state
        # pool lies (``ops/mamba1.py``); the skip 1
        "A_log": jnp.log(uniform(66, (N, I), 1.0, 16.0)),
        "D": jnp.ones((I,), jnp.float32),
        "w_out": normal(67, (I, H), _resid_std(cfg)),
    }


def _mamba1_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    return {"w_in": (LAYERS, EMBED, HEADS), "w_out": (LAYERS, HEADS, EMBED),
            "conv_w": (LAYERS, None, HEADS), "conv_b": (LAYERS, HEADS),
            "w_x": (LAYERS, HEADS, None), "w_dt": (LAYERS, None, HEADS),
            "dt_bias": (LAYERS, HEADS), "A_log": (LAYERS, None, HEADS),
            "D": (LAYERS, HEADS)}


def _mamba1_state(cfg: TransformerConfig):
    I, N, _ = _mamba1_sizes(cfg)
    return (N, I), cfg.mamba_conv_taps, I


def _mamba1_mixer(cfg: TransformerConfig, h: jax.Array, p: Dict[str, Any],
                  step: Step):
    """The "mamba1" mixer of ``_layer_forward``: the selective scan
    (``ops/mamba1.py``) over the normed input ``h`` (B, S, H) -> (its
    contribution to the residual, new cache, the value it hands on).

    ``[x | z] = h W_in``; on ``x`` a depthwise causal convolution over time
    with a bias, then SiLU; ``[r | B | C] = x W_x``; ``dt = softplus(r W_dt +
    dt_bias)`` a channel and ``A = -exp(A_log)`` a (state, channel); the
    recurrence on a float32 state, plus ``D x``: that is ``m``, handed on
    to the "gmu" layers of the same step BEFORE the gate; ``out = (m *
    silu(z)) W_out``.

    The cache, the layer's index, the rows' slots, a row's start at position
    0 and ``real`` are ``_kda_mixer``'s, with ``"state"`` (layers of this
    kind, slots, state, inner) and a tail of ``x``'s width; a token that
    does not exist has ``dt`` 0 and writes nothing."""
    from ..ops import mamba1 as ssm

    f32 = jnp.float32
    cache, real = step.cache, step.write_mask
    at = (_pool_of(step), step.state_slots)     # this layer's, each row's
    B, S, _ = h.shape
    I, N, R = _mamba1_sizes(cfg)
    taps = cfg.mamba_conv_taps
    x, z = jnp.split(jnp.einsum("bsh,hd->bsd", h, p["w_in"]), 2, axis=-1)
    ext, fresh = _with_conv_history(x, step, taps)
    conv = p["conv_w"].astype(f32)
    x = jax.nn.silu(p["conv_b"].astype(f32) + sum(
        conv[j] * ext[:, j:j + S].astype(f32) for j in range(taps))
    ).astype(h.dtype)
    r, Bm, Cm = jnp.split(jnp.einsum("bsd,dr->bsr", x, p["w_x"]),
                          [R, R + N], axis=-1)
    dt = jax.nn.softplus(jnp.einsum("bsr,rd->bsd", r, p["w_dt"]).astype(f32)
                         + p["dt_bias"].astype(f32))
    if real is not None:
        dt = jnp.where(real[..., None], dt, 0.0)
    A = -jnp.exp(p["A_log"].astype(f32))

    new_cache = None
    if cache is None:
        y, _ = ssm.mamba1_recurrence(x, dt, A, Bm, Cm,
                                     jnp.zeros((B, N, I), f32))
    else:
        kernels = _single_chip_kernels()
        if S == 1:
            advance = (ssm.mamba1_decode_step if kernels
                       else ssm.reference_mamba1_decode_step)
            y, states = advance(x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                                cache["state"], *at)
            y = y[:, None]
        else:
            start = jnp.where(fresh[:, None, None], 0.0, cache["state"][at])
            scan = ssm.mamba1_chunk_scan if kernels else ssm.mamba1_recurrence
            y, end = scan(x, dt, A, Bm, Cm, start)
            states = cache["state"].at[at].set(
                end.astype(cache["state"].dtype))
        new_cache = {**cache, "state": states,
                     "tail": cache["tail"].at[at].set(
                         _last_real_rows(ext, real, taps - 1).astype(
                             cache["tail"].dtype))}

    y = y + p["D"].astype(f32) * x.astype(f32)
    out = (y * jax.nn.silu(z.astype(f32))).astype(h.dtype)
    return (jnp.einsum("bsd,dh->bsh", out, p["w_out"]), new_cache,
            y.astype(h.dtype))


def _shortconv_init(cfg: TransformerConfig, normal, uniform
                    ) -> Dict[str, Any]:
    H = cfg.hidden_size
    return {
        "w_in": normal(100, (H, 3 * H)),        # [B | C | u]
        # taps of the depthwise convolution over time, oldest first
        "conv_w": normal(101, (cfg.shortconv_taps, H), 0.5),
        "w_out": normal(102, (H, H), _resid_std(cfg)),
    }


def _shortconv_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    return {"w_in": (LAYERS, EMBED, HEADS), "conv_w": (LAYERS, None, HEADS),
            "w_out": (LAYERS, HEADS, EMBED)}


def _shortconv_state(cfg: TransformerConfig):
    # no state: the convolution's tail is all a sequence keeps
    return None, cfg.shortconv_taps, cfg.hidden_size


def _shortconv_mixer(cfg: TransformerConfig, h: jax.Array, p: Dict[str, Any],
                     step: Step
                     ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """The "shortconv" mixer of ``_layer_forward``, a gated short
    convolution over the normed input ``h`` (B, S, H) -> (its contribution
    to the residual, new cache).

    ``[B | C | u] = h W_in``; ``z = B * u``; on ``z`` a depthwise causal
    convolution over time (``shortconv_taps`` taps, no bias, no activation):
    ``y``; ``out = (C * y) W_out``. Between the two products it is ``taps``
    multiply-adds a value, which XLA fuses: no kernel.

    The cache, the layer's pool, the rows' slots, a row's start at position
    0 and ``real`` are ``_kda_mixer``'s, but there is NO ``"state"``: the
    pool ``"tail"`` (layers of this mixer, slots, taps - 1, H) holds a
    sequence's last ``taps - 1`` rows of ``z``, and that is all it keeps. A
    token that does not exist leaves the tail as it was
    (``_last_real_rows``)."""
    f32 = jnp.float32
    S = h.shape[1]
    taps = cfg.shortconv_taps
    b, c, u = jnp.split(jnp.einsum("bsh,hd->bsd", h, p["w_in"]), 3, axis=-1)
    ext, _ = _with_conv_history(b * u, step, taps)
    conv = p["conv_w"].astype(f32)
    y = sum(conv[j] * ext[:, j:j + S].astype(f32) for j in range(taps))
    new_cache = None
    if step.cache is not None:
        tails = step.cache["tail"]
        new_cache = {**step.cache, "tail": tails.at[
            _pool_of(step), step.state_slots].set(_last_real_rows(
                ext, step.write_mask, taps - 1).astype(tails.dtype))}
    out = (c.astype(f32) * y).astype(h.dtype)
    return jnp.einsum("bsd,dh->bsh", out, p["w_out"]), new_cache


def _gmu_init(cfg: TransformerConfig, normal, uniform) -> Dict[str, Any]:
    H, I = cfg.hidden_size, _mamba1_sizes(cfg)[0]
    return {"w_in": normal(70, (H, I)),
            "w_out": normal(71, (I, H), _resid_std(cfg))}


def _gmu_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    return {"w_in": (LAYERS, EMBED, HEADS), "w_out": (LAYERS, HEADS, EMBED)}


def _gmu_mixer(cfg: TransformerConfig, h: jax.Array, p: Dict[str, Any],
               step: Step) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
    """The "gmu" mixer of ``_layer_forward``, a gated memory unit: ``out =
    (silu(h W_1) * m) W_2`` with ``m`` (``step.memory``) what the last
    "mamba1" layer handed on for the same token. It keeps nothing."""
    f32 = jnp.float32
    if step.memory is None:
        raise NotImplementedError(
            "a gated memory unit reads the value a mamba1 layer of the same "
            "step handed on: it runs in a stack of layer_runs")
    gate = jnp.einsum("bsh,hd->bsd", h, p["w_in"]).astype(f32)
    y = (jax.nn.silu(gate) * step.memory.astype(f32)).astype(h.dtype)
    return jnp.einsum("bsd,dh->bsh", y, p["w_out"]), step.cache


# epsilon of the two norms inside the "mla" mixer: their class's default
# in the published code, whatever the model's ``norm_eps``
LATENT_NORM_EPS = 1e-6


def _latent_init(cfg: TransformerConfig, normal, uniform) -> Dict[str, Any]:
    H, N = cfg.hidden_size, cfg.num_heads
    Rq, R = cfg.q_lora_rank, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.rotary_dim, cfg.v_head_dim
    return {
        "wq_a": normal(90, (H, Rq)), "q_norm": jnp.ones((Rq,), cfg.dtype),
        "wq_b": normal(91, (Rq, N * (Dn + Dr))),
        "wkv_a": normal(92, (H, R + Dr)), "kv_norm": jnp.ones((R,), cfg.dtype),
        # the published kv_b_proj in two, each a head at a time as the
        # absorbed read multiplies by it: a head's keys (Dn, R), q side, and
        # its values (R, Dv), output side
        "wk_b": normal(93, (N, Dn, R)), "wv_b": normal(94, (N, R, Dv)),
        "wo": normal(95, (N * Dv, H), _resid_std(cfg)),
    }


def _latent_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    return {"wq_a": (LAYERS, EMBED, None), "q_norm": (LAYERS, None),
            "wq_b": (LAYERS, None, HEADS), "wkv_a": (LAYERS, EMBED, None),
            "kv_norm": (LAYERS, None), "wk_b": (LAYERS, HEADS, None, None),
            "wv_b": (LAYERS, HEADS, None, None), "wo": (LAYERS, HEADS, EMBED)}


def _rope_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """The rotary embedding over the pairs of NEIGHBOURS (2j, 2j + 1) of
    ``x`` (B, S, n, D), as the latent family publishes it; the result lies
    with every pair's first values in front of the second ones, for queries
    and keys alike, so their products are the published ones."""
    return apply_rope(jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1),
                      cos, sin)


def _latent_mixer(cfg: TransformerConfig, h: jax.Array, p: Dict[str, Any],
                  step: Step
                  ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """The "mla" mixer of a layer: multi-head latent attention over the
    normed input ``h`` (B, S, H) -> (its contribution to the residual, new
    cache).

    ``cq = RMSNorm(h W_qa)``; ``q = (cq W_qb) * sqrt(H / q_lora_rank)`` a
    head, its first ``qk_nope_head_dim`` values as they are and its last
    ``rotary_dim`` roped. ``[ckv | kr] = h W_kva``; ``c = RMSNorm(ckv) *
    sqrt(H / kv_lora_rank)``; ``k_rope = rope(kr)``, ONE a token for all
    heads. A head's keys are ``[c W_kb_h | k_rope]`` and its values ``c
    W_vb_h``; scores times ``(qk_nope_head_dim + rotary_dim) ** -0.5``,
    causal softmax, ``out = concat_h(p v_h) W_o``. Both inner norms take
    ``LATENT_NORM_EPS``; no bias anywhere.

    What a token KEEPS is ``[c | k_rope]``, ``latent_width`` values (and
    zeros up to ``latent_page_width`` lanes) in pool
    ``step.pool_index`` of the arena's ``"latent"``; the read over the
    pages is ``ops/paged_decode_attention.latent_paged_attention``'s, which
    never makes a cached token's keys or values for one query a row. With
    no cache the sequence attends to itself through the expanded keys and
    values; the dense cache (``inference/engine.py``) has no latent entry
    and is refused."""
    from ..ops.paged_decode_attention import latent_paged_attention

    f32 = jnp.float32
    B, S, H = h.shape
    N, Rq, R = cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank
    Dn, Dr, Dv = cfg.qk_nope_head_dim, cfg.rotary_dim, cfg.v_head_dim
    scale = (Dn + Dr) ** -0.5

    def rms(x, g, by=1.0):      # in float32, the scale with the norm's
        x32 = x.astype(f32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        return (x32 * lax.rsqrt(var + LATENT_NORM_EPS) * g.astype(f32)
                * by).astype(x.dtype)

    cq = rms(jnp.einsum("bsh,hr->bsr", h, p["wq_a"]), p["q_norm"])
    q = jnp.einsum("bsr,rd->bsd", cq, p["wq_b"])
    kv = jnp.einsum("bsh,hr->bsr", h, p["wkv_a"])
    if step.cache is not None:
        q, kv = lax.optimization_barrier((q, kv))   # see ``_qkv_heads``
    q = (q.reshape(B, S, N, Dn + Dr).astype(f32) * (H / Rq) ** 0.5
         ).astype(h.dtype)
    c = rms(kv[..., :R], p["kv_norm"], (H / R) ** 0.5)
    cos, sin = rope_table(step.positions, Dr, cfg.rope_theta)
    q_nope, q_rope = q[..., :Dn], _rope_pairs(q[..., Dn:], cos, sin)
    k_rope = _rope_pairs(kv[..., None, R:], cos, sin)[:, :, 0]     # (B, S, Dr)

    if step.cache is None:
        k_nope = jnp.einsum("btr,ndr->btnd", c, p["wk_b"])
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope[:, :, None], (B, S, N, Dr))], axis=-1)
        v = jnp.einsum("btr,nrv->btnv", c, p["wv_b"])
        attn = dot_product_attention(
            jnp.concatenate([q_nope, q_rope], axis=-1), k, v, step.mask,
            causal=cfg.causal, scale=scale)
        new_cache = None
    elif step.block_table is None:
        raise NotImplementedError(
            "a latent-attention layer runs without a cache or over the "
            "serving layer's paged arena, which keeps a pool of latents: "
            "the dense cache (inference/engine.py) keeps keys and values "
            "of every head and has no latent entry")
    else:
        arena = step.cache["latent"]
        pad = jnp.zeros((B, S, arena.shape[-1] - R - Dr), c.dtype)
        rows = jnp.concatenate([c, k_rope, pad], axis=-1).astype(arena.dtype)
        arena = _page_writer(step, step.block_table, arena.shape[2], S)(
            arena, step.pool_index, rows)
        attn = latent_paged_attention(
            q_nope, q_rope, p["wk_b"], p["wv_b"], arena, step.pool_index,
            step.block_table, step.positions, scale)
        new_cache = {**step.cache, "latent": arena}
    out = jnp.einsum("bsd,dh->bsh", attn.reshape(B, S, N * Dv), p["wo"])
    return out, new_cache


@dataclasses.dataclass(frozen=True)
class Mixer:
    """What a mixer IS: every function that must tell one mixer from
    another asks its record in ``MIXERS`` (docs/models.md)."""
    name: str           # the key of its parameter subtree in a layer
    init: Callable      # (cfg, normal, uniform) -> that subtree, ONE layer's
    axes: Callable      # (cfg) -> the subtree's logical axes
    apply: Callable     # (cfg, h, params, step) -> (out, new cache), and a
    #   third value where the mixer ``hands_on``
    state: Optional[Callable] = None    # None: the mixer keeps nothing a
    #   sequence beside pages; else (cfg) -> (a slot's state shape, or None
    #   where the tail is all it keeps; the convolution's taps; its width)
    rows_count: Optional[str] = None    # the span count of states advanced
    keeps: Optional[str] = None     # "pages": a pool of its own in the
    #   arena's "k" and "v"; "ring": a window of pages a row in "wk" and
    #   "wv"; "latent": a pool of its own in the arena's "latent", which
    #   then has no "k" and no "v"; None: no keys of its own (a state, or
    #   what another layer made)
    hands_on: bool = False          # its third result rides the step's
    #   carry as ``Step.memory``


def _form(form: AttnForm, keeps: Optional[str]) -> Mixer:
    """The softmax mixer's record in one of its forms."""
    def apply(cfg, h, p, step):
        return _softmax(cfg, h, p, step, form)

    return Mixer("attn", partial(_form_init, form=form),
                 partial(_form_axes, form=form), apply, keeps=keeps)


# keyed by the mixer's name in ``LAYER_KINDS``; nothing reads it at import
MIXERS: Dict[str, Mixer] = {
    "attn": Mixer("attn", _attn_init, _attn_axes, _softmax_mixer,
                  keeps="pages"),
    "kda": Mixer("kda", _kda_init, _kda_axes, _kda_mixer, _kda_state,
                 "recurrent_rows"),
    "mamba2": Mixer("mamba2", _mamba2_init, _mamba2_axes, _mamba2_mixer,
                    _mamba2_state, "ssm_rows"),
    "mamba1": Mixer("mamba1", _mamba1_init, _mamba1_axes, _mamba1_mixer,
                    _mamba1_state, "ssm_rows", hands_on=True),
    "swa": _form(AttnForm(window=True), "ring"),
    "full": _form(AttnForm(), "pages"),
    "cross": _form(AttnForm(cross=True), None),
    "gmu": Mixer("gmu", _gmu_init, _gmu_axes, _gmu_mixer),
    "shortconv": Mixer("shortconv", _shortconv_init, _shortconv_axes,
                       _shortconv_mixer, _shortconv_state, "conv_rows"),
    "mla": Mixer("mla", _latent_init, _latent_axes, _latent_mixer,
                 keeps="latent"),
}


def _mixer_half(cfg: TransformerConfig, x: jax.Array, layer: Dict[str, Any],
                mixer: str, has_ffn: bool, step: Step):
    """The first half of ``_layer_forward`` for a layer that has a mixer:
    norm, mixer and its add -> ``(x, the FFN's normed input or None where no
    FFN follows, the mixer's output, new cache)``. ``x`` comes back with the
    mixer's output added but under ``parallel_residual``, which adds it
    beside the FFN's. A mixer that ``hands_on`` sets ``step.memory`` for
    the layers below: a fifth value, the memory as it now stands."""
    post_ln = cfg.norm_position == "post"
    if post_ln:
        h = x      # post-LN (BERT family): raw input feeds attention; the
        #            norm is applied after each residual add below
    else:
        h = _norm(x, layer["ln1"]["scale"], layer["ln1"].get("bias"),
                  cfg.norm, cfg.norm_eps)
    if cfg.act_quant_bits and step.cache is None:
        # activation QAT (reference QuantAct): quantize the attention input
        from ..compression.compress import fake_quant_activation

        h = fake_quant_activation(h, cfg.act_quant_bits)
    attn_out, new_cache, *handed = MIXERS[mixer].apply(
        cfg, h, layer[MIXERS[mixer].name], step)
    memory = [] if step.memory is None else [handed[0] if handed
                                             else step.memory]
    if cfg.norm_position == "sandwich":
        attn_out = _norm(attn_out, layer["ln1_post"]["scale"],
                         layer["ln1_post"].get("bias"), cfg.norm,
                         cfg.norm_eps)
    if step.cache is None:
        from ..parallel.sequence import constrain, hidden_spec, sequence_parallel_enabled

        attn_out = _dropout(attn_out, cfg, salt=31)
        if sequence_parallel_enabled():
            attn_out = constrain(attn_out, hidden_spec())
    if not has_ffn:
        return (x + attn_out, None, attn_out, new_cache, *memory)
    if cfg.parallel_residual:
        # GPT-J/NeoX: x + attn(ln1(x)) + mlp(ln2(x)) — one residual add,
        # the MLP reads the ORIGINAL x through its own norm
        h = _norm(x, layer["ln2"]["scale"], layer["ln2"].get("bias"),
                  cfg.norm, cfg.norm_eps)
    elif post_ln:
        # BERT family: norm AFTER the residual add; the normed sum feeds MLP
        x = _norm(x + attn_out, layer["ln1"]["scale"],
                  layer["ln1"].get("bias"), cfg.norm, cfg.norm_eps)
        h = x
    else:
        x = x + attn_out
        h = _norm(x, layer["ln2"]["scale"], layer["ln2"].get("bias"),
                  cfg.norm, cfg.norm_eps)
    return (x, h, attn_out, new_cache, *memory)


@dataclasses.dataclass(frozen=True)
class Ffn:
    """What an FFN IS, as ``Mixer`` is what a mixer is: every function that
    must tell one FFN from another asks its record in ``FFNS``, and
    ``ffn_of`` alone says which one a kind's layer has (docs/models.md)."""
    init: Callable      # (cfg, normal) -> ONE layer's FFN leaves, each under
    #   its top-level name in the layer
    axes: Callable      # (cfg) -> the same names' logical axes
    apply: Callable     # (cfg, h, layer, step, kind) -> (out, aux, *counts):
    #   the FFN over the normed input ``h`` (``layer``: the layer's whole
    #   tree); ``counts`` with ``step.moe_counts``, from an FFN that routes
    count_width: Callable = lambda cfg: 0   # (cfg) -> entries of ``counts``
    whole: Optional[str] = None     # the leaf whose WHOLE stack stays out of
    #   the layer scan's slicing at inference (``Step.expert_banks``)


_MATRIX_AXES = {"w_gate": (LAYERS, EMBED, MLP), "w_up": (LAYERS, EMBED, MLP),
                "b_up": (LAYERS, MLP), "w_down": (LAYERS, MLP, EMBED),
                "b_down": (LAYERS, EMBED)}


def _biased_init(cfg: TransformerConfig, normal, name: str = "mlp",
                 tags: Tuple[int, int] = (9, 10)) -> Dict[str, Any]:
    H, F = cfg.hidden_size, cfg.ffn_hidden_size
    return {name: {"w_up": normal(tags[0], (H, F)),
                   "b_up": jnp.zeros((F,), cfg.dtype),
                   "w_down": normal(tags[1], (F, H), _resid_std(cfg)),
                   "b_down": jnp.zeros((H,), cfg.dtype)}}


def _biased_axes(cfg: TransformerConfig, name: str = "mlp") -> Dict[str, Any]:
    return {name: {k: _MATRIX_AXES[k]
                   for k in ("w_up", "b_up", "w_down", "b_down")}}


def _biased_ffn(cfg: TransformerConfig, h: jax.Array, layer: Dict[str, Any],
                step: Step, kind: str):
    """``down(act(up(h) + b_up)) + b_down``: the two-matrix FFN."""
    w = layer["mlp"]
    inner = _qeinsum("bsh,hf->bsf", h, w["w_up"], cfg.dtype, a8=cfg.a8_decode) + w["b_up"]
    if cfg.activation == "relu":
        inner = jax.nn.relu(inner)
    elif cfg.activation == "relu2":
        inner = jnp.square(jax.nn.relu(inner))
    elif cfg.activation == "quick_gelu":
        # CLIP's x*sigmoid(1.702x) (HF QuickGELUActivation)
        inner = inner * jax.nn.sigmoid(1.702 * inner)
    else:
        inner = jax.nn.gelu(inner,
                            approximate=cfg.activation != "gelu-exact")
    return (_qeinsum("bsf,fh->bsh", inner, w["w_down"], cfg.dtype, a8=cfg.a8_decode) + w["b_down"],
            jnp.float32(0.0))


def _swiglu_ffn(name: str, width: Callable, tags: Tuple[int, int, int]
                ) -> Ffn:
    """The SwiGLU FFN's record in one of its forms: its leaves under
    ``name``, ``width(cfg)`` wide, drawn under ``tags`` (gate, up, down)."""
    def init(cfg, normal):
        H, F = cfg.hidden_size, width(cfg)
        return {name: {"w_gate": normal(tags[0], (H, F)),
                       "w_up": normal(tags[1], (H, F)),
                       "w_down": normal(tags[2], (F, H), _resid_std(cfg))}}

    def axes(cfg):
        return {name: {k: _MATRIX_AXES[k]
                       for k in ("w_gate", "w_up", "w_down")}}

    def apply(cfg, h, layer, step, kind):
        return _swiglu(cfg, h, layer[name]), jnp.float32(0.0)

    return Ffn(init, axes, apply)


def _experts_init(cfg: TransformerConfig, normal) -> Dict[str, Any]:
    H, F, E = cfg.hidden_size, cfg.ffn_hidden_size, cfg.moe_num_experts
    held, resid_std = cfg.experts_held, _resid_std(cfg)
    gated = cfg.activation == "swiglu"
    # the router's outputs: the routed experts, then the zero-computation
    # ones, which have no matrices below
    layer = {"router": normal(4, (H, E + cfg.moe_zero_experts))}
    if cfg.moe_router_bias:
        # nonzero, or the choice-only bias would go untested; small, as a
        # trained one is: sigmoid scores of the most probable experts lie
        # within 0.005 of each other, and a bias of 0.1 would choose the same
        # experts for every token. Softmax scores sum to one: beside them,
        # their mean, 1 / outputs (at 768 outputs the 12th largest score is
        # 0.012, and a bias of 0.01 gave 1 output in 64 to every token)
        outputs = E + cfg.moe_zero_experts
        layer["router_bias"] = normal(
            12, (outputs,), 0.01 if cfg.moe_score_func == "sigmoid"
            else 1.0 / outputs).astype(jnp.float32)
    if cfg.moe_shared_experts:
        Fs = cfg.shared_ffn_hidden_size
        layer["shared"] = {"w_up": normal(14, (H, Fs)),
                           "w_down": normal(15, (Fs, H), resid_std)}
        if gated:
            layer["shared"]["w_gate"] = normal(13, (H, Fs))
    if cfg.moe_latent_size:
        layer["latent"] = {
            "w_in": normal(16, (H, cfg.moe_latent_size)),
            "w_out": normal(17, (cfg.moe_latent_size, H), resid_std)}
    if cfg.moe_use_residual:
        layer.update(_biased_init(cfg, normal, "res_mlp", (5, 6)))
        layer["res_coef"] = {"w": normal(7, (H, 2)),
                             "b": jnp.zeros((2,), cfg.dtype)}
    He = cfg.moe_latent_size or H       # the experts' own width
    layer["mlp"] = {
        "w_up": normal(9, (held, He, F)),
        # in a latent it is the projection out of it that writes to the
        # residual stream, not an expert's own
        "w_down": normal(10, (held, F, He),
                         0.02 if cfg.moe_latent_size else resid_std)}
    if gated:
        layer["mlp"]["w_gate"] = normal(8, (held, He, F))
    return layer


def _experts_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    gate = ("w_gate",) if cfg.activation == "swiglu" else ()
    wide = None if cfg.moe_latent_size else EMBED   # the experts' width
    axes = {"router": (LAYERS, EMBED, None),
            "mlp": {"w_up": (LAYERS, EXPERT, wide, MLP),
                    "w_down": (LAYERS, EXPERT, MLP, wide),
                    **{k: (LAYERS, EXPERT, wide, MLP) for k in gate}}}
    if cfg.moe_router_bias:
        axes["router_bias"] = (LAYERS, None)
    if cfg.moe_shared_experts:
        axes["shared"] = {k: _MATRIX_AXES[k]
                          for k in ("w_up", "w_down") + gate}
    if cfg.moe_latent_size:
        axes["latent"] = {"w_in": (LAYERS, EMBED, None),
                          "w_out": (LAYERS, None, EMBED)}
    if cfg.moe_use_residual:
        axes.update(_biased_axes(cfg, "res_mlp"))
        axes["res_coef"] = {"w": (LAYERS, EMBED, None), "b": (LAYERS, None)}
    return axes


def _experts_ffn(cfg: TransformerConfig, h: jax.Array, layer: Dict[str, Any],
                 step: Step, kind: str):
    """A layer's FFN of experts over the normed input ``h`` -> ``(out, aux,
    *counts)``: the routed experts (``parallel/moe.moe_mlp``; ``counts`` with
    ``step.moe_counts``), the shared expert and the PR-MoE residual where
    the model has them."""
    from ..parallel.moe import moe_mlp

    # cache mode == inference: moe_mlp then routes exactly (no capacity
    # drops, no RTS) and keeps padding rows out of the routing
    # (a router the capacity plans cannot express routes so always)
    infer = step.cache is not None or cfg.moe_dropless_only
    rts_rng = (_activation_derived_key(h, 0)
               if (cfg.moe_use_rts and not infer) else None)
    banks = (step.expert_banks or {}).get(kind)
    mlp_out, aux, *counts = moe_mlp(
        h, layer["router"], layer["mlp"] if banks is None else banks,
        cfg.activation,
        expert_layer=None if banks is None else step.layer_index,
        top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity_factor,
        min_capacity=cfg.moe_min_capacity,
        drop_tokens=cfg.moe_drop_tokens, use_rts=cfg.moe_use_rts,
        rng=rts_rng, dispatch_impl=cfg.moe_dispatch,
        norm_topk_prob=cfg.moe_norm_topk_prob, infer=infer,
        row_mask=step.write_mask, with_counts=step.moe_counts,
        score_func=cfg.moe_score_func,
        choice_bias=layer.get("router_bias"),
        latent=layer.get("latent"), routed_scale=cfg.moe_routed_scale,
        zero_experts=cfg.moe_zero_experts)
    if "shared" in layer:
        # the shared expert: one dense FFN beside the routed ones, on
        # the full width, added to every token unweighted
        mlp_out = mlp_out + (_swiglu if "w_gate" in layer["shared"]
                             else _plain_ffn)(cfg, h, layer["shared"])
    if cfg.moe_use_residual:
        # PR-MoE (reference moe/layer.py:120): dense MLP in parallel,
        # mixed by a learned softmax coefficient over (moe, dense)
        inner = jnp.einsum("bsh,hf->bsf", h, layer["res_mlp"]["w_up"]) \
            + layer["res_mlp"]["b_up"]
        inner = jax.nn.gelu(inner, approximate=True)
        res_out = jnp.einsum("bsf,fh->bsh", inner,
                             layer["res_mlp"]["w_down"]) \
            + layer["res_mlp"]["b_down"]
        coef = jax.nn.softmax(
            (jnp.einsum("bsh,hc->bsc", h, layer["res_coef"]["w"])
             + layer["res_coef"]["b"]).astype(jnp.float32), axis=-1
        ).astype(h.dtype)
        mlp_out = mlp_out * coef[..., 0:1] + res_out * coef[..., 1:2]
    return (mlp_out, aux, *counts)


# keyed by what ``ffn_of`` answers; nothing reads it at import. "dense" is
# the SwiGLU FFN in its second form: a sublayer's own (``SUBLAYERS``),
# ``dense_ffn_hidden_size`` wide, with its own leaf and draws
FFNS: Dict[str, Ffn] = {
    "biased": Ffn(_biased_init, _biased_axes, _biased_ffn),
    "swiglu": _swiglu_ffn("mlp", lambda cfg: cfg.ffn_hidden_size, (8, 9, 10)),
    # three counts, and a fourth BEHIND them, the assignments to
    # zero-computation experts, only where the model has such experts
    "experts": Ffn(_experts_init, _experts_axes, _experts_ffn,
                   count_width=lambda cfg: 3 + (cfg.moe_zero_experts > 0),
                   whole="mlp"),
    "dense": _swiglu_ffn("dense", lambda cfg: cfg.dense_ffn_hidden_size,
                         (80, 81, 82)),
}


def _banks_apart(cfg: TransformerConfig, stacks: Dict[str, Any]
                 ) -> Tuple[Dict[str, Any], Optional[Dict[str, Any]]]:
    """``(stacks without their banks, banks by kind or None)``: at inference
    an MoE model's expert stacks stay whole, outside the layer scan's
    slicing, and go down with the layer's index (``Step.expert_banks``)."""
    whole = {kind: FFNS[ffn_of(cfg, kind)].whole for kind in stacks
             if ffn_of(cfg, kind) and FFNS[ffn_of(cfg, kind)].whole}
    if not whole:
        return stacks, None
    return ({kind: {k: v for k, v in tree.items() if k != whole.get(kind)}
             for kind, tree in stacks.items()},
            {kind: stacks[kind][leaf] for kind, leaf in whole.items()})


def _layer_forward(cfg: TransformerConfig, x: jax.Array, layer: Dict[str, Any],
                   step: Step, kind: str = "attn"):
    """One decoder block: ``x + mixer(norm(x))``, then the FFN. ``kind``
    (``LAYER_KINDS``) names the mixer and says whether the FFN follows;
    ``layer`` holds this layer's (unstacked) params and ``step`` the
    call's other operands.

    Returns ``(x, new_cache, aux)``; with ``step.moe_counts`` (an MoE model)
    a fourth value, this layer's routing counts (``parallel/moe.moe_mlp``;
    zeros for a layer that has no FFN); with ``step.memory`` (a stack with
    gated memory units) a last one, the memory as it stands below this
    layer."""
    if kind in SUBLAYERS:
        return _sublayers_forward(cfg, x, layer, step, kind)
    cache = step.cache
    mixer, ffn = LAYER_KINDS[kind][0], ffn_of(cfg, kind)
    has_ffn = ffn is not None
    post_ln = cfg.norm_position == "post"
    if not (mixer and has_ffn) and (post_ln or cfg.parallel_residual):
        raise NotImplementedError(
            "a layer that is a mixer alone or an FFN alone is x + f(norm(x)): "
            "it has no post-norm or parallel-residual form")
    new_cache = cache
    memory = [] if step.memory is None else [step.memory]
    if mixer is not None:
        x, h, attn_out, new_cache, *memory = _mixer_half(
            cfg, x, layer, mixer, has_ffn, step)
    else:
        attn_out = None
        h = _norm(x, layer["ln2"]["scale"], layer["ln2"].get("bias"),
                  cfg.norm, cfg.norm_eps)
    if not has_ffn:
        return (x, new_cache, jnp.float32(0.0), *(
            [jnp.zeros((moe_count_width(cfg),), jnp.int32)]
            if step.moe_counts else []), *memory)
    if cfg.act_quant_bits and cache is None:
        from ..compression.compress import fake_quant_activation

        h = fake_quant_activation(h, cfg.act_quant_bits)   # MLP input
    mlp_out, aux, *counts = FFNS[ffn].apply(cfg, h, layer, step, kind)
    if step.moe_counts and not counts:
        # a dense layer of a model whose other layers route: it assigns
        # nothing, and says so in the place the counts have
        counts = [jnp.zeros((moe_count_width(cfg),), jnp.int32)]
    if cfg.norm_position == "sandwich":
        mlp_out = _norm(mlp_out, layer["ln2_post"]["scale"],
                        layer["ln2_post"].get("bias"), cfg.norm, cfg.norm_eps)
    if cache is None:
        mlp_out = _dropout(mlp_out, cfg, salt=37)
    if cfg.parallel_residual:
        x = x + attn_out + mlp_out
    elif post_ln:
        x = _norm(x + mlp_out, layer["ln2"]["scale"],
                  layer["ln2"].get("bias"), cfg.norm, cfg.norm_eps)
    else:
        x = x + mlp_out
    return (x, new_cache, aux, *counts, *memory)


def _sublayers_forward(cfg: TransformerConfig, x: jax.Array,
                       layer: Dict[str, Any], step: Step, kind: str):
    """A layer of ``SUBLAYERS[kind]`` sublayers (the published double layer
    of a shortcut-connected MoE) -> ``_layer_forward``'s results:

        for i in sublayers:
            a = x + mixer_i(norm(x; ln1_i));  h = norm(a; ln2_i)
            if i == 0: s = ffn(h)                 # the layer's: read here ...
            x = a + dense_i(h)                    # the sublayer's own
        x = x + s                                 # ... joined here

    The leaves ``ln1``, the mixer's, ``ln2`` and ``dense`` carry the
    sublayers on their leading axis; sublayer i writes and reads pool
    ``SUBLAYERS[kind] * step.layer_index + i`` (``Step.pool_index``). The
    expert FFN (router, bias, bank) is the layer's one."""
    mixer, n = LAYER_KINDS[kind][0], SUBLAYERS[kind]
    ffn = FFNS[ffn_of(cfg, kind)]
    dense = FFNS[ffn_of(cfg, kind, sublayer=True)]
    if step.sublayer_stacks is None:
        halves, at_layer = {name: layer[name]
                            for name in sublayer_leaves(cfg, kind)}, ()
    else:   # the whole stacks, this layer's taken where it lies
        halves, at_layer = step.sublayer_stacks[kind], (step.layer_index,)
    cache = step.cache
    for i in range(n):
        sub = jax.tree.map(lambda a: a[at_layer + (i,)], halves)
        at = dataclasses.replace(step, cache=cache, pool_index=(
            None if step.layer_index is None else n * step.layer_index + i))
        x, h, _, cache = _mixer_half(cfg, x, sub, mixer, True, at)
        if i == 0:
            shortcut, aux, *counts = ffn.apply(cfg, h, layer, step, kind)
        x = x + dense.apply(cfg, h, sub, at, kind)[0]
    return (x + shortcut, cache, aux, *counts)


def forward(params: Dict[str, Any], input_ids: jax.Array,
            cfg: TransformerConfig,
            attention_mask: Optional[jax.Array] = None,
            cache: Optional[Dict[str, Any]] = None,
            start_pos: Any = 0,
            pld_theta: Optional[jax.Array] = None,
            positions: Optional[jax.Array] = None,
            token_type_ids: Optional[jax.Array] = None,
            key_positions: Optional[jax.Array] = None,
            block_table: Optional[jax.Array] = None,
            paged_write_mask: Optional[jax.Array] = None,
            moe_counts: bool = False,
            state_slots: Optional[jax.Array] = None,
            paged_run: Optional[Tuple[jax.Array, jax.Array]] = None,
            last_token: Optional[jax.Array] = None,
            mixed_chunk: Optional[Dict[str, Any]] = None
            ) -> Tuple[jax.Array, Optional[Dict[str, Any]], jax.Array]:
    """Token ids (B,S) → (logits (B,S,V), new_cache, moe_aux_loss). With
    ``cache``, runs in decode mode (cache is a per-layer stacked pytree; see
    inference/kv_cache.py). ``positions``: explicit absolute positions, (S,)
    shared or (B, S) per-row — ragged batches decode with each row's TRUE
    token index (the KV arena column stays uniform; only the position
    values differ).

    ``block_table`` (B, MAX_BLOCKS) switches the cache to the PAGED layout
    ``{"k","v": (L, NUM_BLOCKS, BLOCK, K*D)}`` (serving layer); ``positions``
    is then REQUIRED — per-row absolute write positions — and
    ``paged_write_mask`` (B, S) routes padding writes to the scratch block.
    ``paged_run`` ``(start, n_valid)``, each () or (B,): the caller's word
    that row b's ``positions`` are the run ``start + arange(S)`` and its
    mask ``arange(S) < n_valid`` (a prompt or scoring chunk): a run of a
    page or more is then written a whole page at a time, to the same bytes
    (``_attend_paged``).
    The paged read has no window, custom-scale or custom-impl operand: a
    model with ``attention_layers``, ``attention_scale`` or
    ``attention_impl`` is refused. ``moe_counts`` (paged mode, an MoE
    model) adds a fourth result: int32 ``[assignments, experts with a row,
    rows of the largest expert]`` summed over the layers, from the rows
    that ``paged_write_mask`` keeps (``parallel/moe.moe_mlp``).

    **Layers of several kinds** (``cfg.layer_pattern``): the stack is
    scanned a PERIOD a step, each kind's stacked tree sliced by the period
    (``layer_stacks``), the period's layers run in their published order
    inside the step; a stack of one kind is the period of one, and its scan
    is the scan there always was. In paged mode the cache holds pages for
    the "attn" layers alone, ``(layers of that kind, ...)``, and for "kda"
    layers the pools ``"state"`` and ``"tail"`` (``_kda_mixer``), with
    ``state_slots`` (B,) naming each row's slot; the dense cache of
    ``inference/engine.py`` has no such entry and refuses such a model.

    **A stack of runs** (``cfg.layer_runs``; paged mode only): each run of
    equal periods is one scan (``_run_layers``), and the value a "mamba1"
    layer hands on rides the carry to the "gmu" layers. ``last_token`` (B,)
    int32, a prompt chunk's word: the stack's last runs that keep nothing of
    a token (``tail_runs``: the cross-decoder) run for token ``last_token[b]``
    of each row alone, and the logits are (B, 1, V), that token's; where
    every entry is below 0 (a chunk that is not its prompt's last) neither
    they nor the head run, and the logits are zeros.

    **A mixed step** (``mixed_chunk``; paged mode, a one-pass stack of
    "attn" layers with a dense FFN: ``serving/paged_kv.mixes``):
    ``input_ids`` is ONE flat run (1, R + C), R decode rows' tokens and then
    a prompt chunk's C. ``positions`` (R, 1), ``block_table`` (R, MAXB) and
    ``paged_write_mask`` are the rows', as the decode program hands them;
    ``mixed_chunk`` holds the chunk's ``positions`` (1, C), ``block_table``
    (1, MAXB), ``write_mask`` (1, C) and ``paged_run``, as the chunk program
    hands them. Every per-token product runs once over the R + C tokens; the
    mixer's page write and read alone split (``Step.divide``). The logits
    are the ROWS', (R, 1, V): nobody reads a chunk's that is not its
    prompt's last, and no other chunk rides a mixed step."""
    B, S = input_ids.shape
    routes = bool(expert_layers(cfg))       # some layer's FFN routes
    divide = None
    if mixed_chunk is not None:
        divide = S - mixed_chunk["positions"].shape[1]
        if (block_table is None or cfg.layer_runs or cfg.loop_passes > 1
                or routes or B != 1 or divide < 1
                or positions is None or positions.shape != (divide, 1)):
            raise ValueError(
                "a mixed step is one flat run (1, R + C) over the paged "
                "cache of a one-pass stack with dense FFNs, with the rows' "
                "(R, 1) positions")
    if block_table is not None:
        for operand in ("attention_layers", "attention_scale",
                        "attention_impl"):
            if getattr(cfg, operand) not in (None, ()):
                raise NotImplementedError(
                    f"paged attention (block_table given) has no operand "
                    f"for cfg.{operand} — reading the arena without it "
                    "would silently change the model")
    x = params["embed"]["tokens"][input_ids].astype(cfg.dtype)
    if positions is None:
        positions = jnp.arange(S) + start_pos
    if cfg.position == "learned":
        at = positions if divide is None else jnp.concatenate(
            [positions.reshape(1, divide), mixed_chunk["positions"]], axis=1)
        x = x + params["pos"][at].astype(cfg.dtype)
    if cfg.type_vocab_size > 0:
        # BERT segment embeddings; absent ids mean segment 0 (HF default)
        tti = (jnp.zeros((B, S), jnp.int32) if token_type_ids is None
               else token_type_ids)
        x = x + params["type_embed"][tti].astype(cfg.dtype)
    if cfg.embed_norm:
        x = _norm(x, params["embed_norm"]["scale"],
                  params["embed_norm"].get("bias"), "layernorm", cfg.norm_eps)
    if cache is None:
        x = _dropout(x, cfg, salt=29)

    if block_table is not None and (cache is None or positions is None
                                    or positions.ndim != 2):
        raise ValueError("paged mode (block_table) requires cache= and "
                         "explicit (B, S) positions")
    if moe_counts and (block_table is None or not routes):
        raise ValueError("moe_counts needs an MoE model in paged mode")
    static_prefill = (cache is not None and block_table is None
                      and isinstance(start_pos, int) and start_pos == 0)

    use_pld = (cfg.pld_enabled and cache is None and pld_theta is not None)
    use_ltd = (cfg.ltd_enabled and cache is None and 0 < cfg.ltd_keep < S)
    L = cfg.num_layers
    use_win = bool(cfg.attention_layers)
    if use_win:
        # per-layer sliding window (GPT-Neo): 'local' layers get the
        # window, 'global' layers 0 (= unlimited); the pattern cycles over
        # layers like HF's attention_types expansion
        win_table = window_table(cfg)
    if cache is None and (use_win or cfg.attention_scale is not None):
        from ..parallel.ring import ring_attention_enabled

        if ring_attention_enabled():
            # ring_attention has no window operand and hardcodes
            # 1/sqrt(head_dim); a custom scale (GPT-Neo uses 1.0) would be
            # silently dropped
            raise NotImplementedError(
                "attention_layers (sliding-window) and custom "
                "attention_scale models + ring attention are not supported "
                "— use sequence_parallel_impl='ulysses'")
    if use_ltd:
        # default mirrors the engine (engine.py random-LTD init): all but the
        # first and last layer; degenerate depths keep at least one layer
        ltd_layers = (cfg.ltd_layers if cfg.ltd_layers is not None
                      else tuple(range(1, L - 1)) if L > 2
                      else tuple(range(L - 1, L)))
        ltd_flags = jnp.array([1.0 if i in ltd_layers else 0.0
                               for i in range(L)], jnp.float32)

    looped = cfg.loop_passes > 1
    if looped and (use_pld or use_ltd or use_win
                   or (cache is not None and block_table is None)):
        raise NotImplementedError(
            "a looped stack (loop_passes > 1) runs without a cache or over "
            "the serving layer's paged cache, which keeps one pool a (pass, "
            "layer): the dense cache (inference/engine.py) keeps one a "
            "layer, and progressive layer drop, random-LTD and per-layer "
            "windows index a stack that runs once")
    if cfg.layer_runs:
        if block_table is None or use_pld or use_ltd or use_win:
            raise NotImplementedError(
                "a stack of layer_runs runs over the serving layer's paged "
                "cache alone: its window layers keep a ring of pages a row "
                "and its cross layers read another layer's pages, which "
                "training and the dense cache (inference/engine.py) do not "
                "keep; progressive layer drop, random-LTD and per-layer "
                "windows index a stack of one period")
        logits, new_cache, *moe_totals = _run_layers(
            cfg, params, x, Step(
                mask=attention_mask, positions=positions, cache=dict(cache),
                block_table=block_table, write_mask=paged_write_mask,
                state_slots=state_slots, paged_run=paged_run,
                moe_counts=moe_counts), last_token)
        return (logits, new_cache, jnp.float32(0.0), *moe_totals)

    # a period of the layer pattern is one step of the scan: each kind's
    # stacked tree goes in sliced by the period (several layers of a kind in
    # a period: leaves (periods, that many, ...)), and `run_period` walks the
    # period's layers in order. A stack of one kind is the period of one:
    # its tree goes in as it is and the walk is one call.
    pattern = layer_kinds(cfg)
    P = len(pattern)
    per = {kind: pattern.count(kind) for kind in pattern}
    several = len(per) > 1
    if several and (use_pld or use_ltd or use_win or (
            cache is not None and block_table is None)):
        raise NotImplementedError(
            "a stack of several kinds of layer runs without a cache or over "
            "the serving layer's paged cache and state pools: the dense "
            "cache (inference/engine.py) holds no recurrent state, and "
            "progressive layer drop, random-LTD and per-layer windows index "
            "a stack of one kind")
    if (cache is not None and recurrent_layers(cfg)[1]
            and state_slots is None):
        raise ValueError("a model with recurrent layers needs state_slots "
                         "beside its cache: each row's slot in the pools")
    stacks = layer_stacks(params["layers"], cfg)
    banks = None
    if cache is not None:
        stacks, banks = _banks_apart(cfg, stacks)
    # and so do the leaves that the sublayers of a double layer own
    # (``Step.sublayer_stacks``)
    halves = None
    if cache is not None and set(stacks) & set(SUBLAYERS):
        halves = {kind: {k: tree[k] for k in sublayer_leaves(cfg, kind)}
                  for kind, tree in stacks.items() if kind in SUBLAYERS}
        stacks = {kind: {k: v for k, v in tree.items()
                         if k not in halves.get(kind, ())}
                  for kind, tree in stacks.items()}
    periods = {kind: tree if per[kind] == 1 else jax.tree.map(
        lambda a, n=per[kind]: a.reshape((L // P, n) + a.shape[1:]), tree)
        for kind, tree in stacks.items()}
    layers = periods if several else periods[pattern[0]]
    with_idx = use_pld or use_win or banks is not None or several
    step = Step(mask=attention_mask, positions=positions, cache=cache,
                block_table=block_table, write_mask=paged_write_mask,
                state_slots=state_slots, paged_run=paged_run,
                static_prefill=static_prefill, key_positions=key_positions,
                moe_counts=moe_counts, expert_banks=banks,
                sublayer_stacks=halves, divide=divide,
                chunk=None if divide is None else Step(**mixed_chunk))

    def run_period(layers, pidx, one_layer, h, *acc):
        """``one_layer(h, kind, layer, layer index among its kind, its
        mixer's pool where that is another index, *acc) -> (h, *acc)`` for
        each layer of period ``pidx``, in order."""
        for kind, (n, j, of_mixer, jm) in zip(pattern,
                                              _period_places(pattern)):
            layer = layers[kind] if several else layers
            if n > 1:
                layer = jax.tree.map(lambda a: a[j], layer)
            kidx = pidx if n == 1 or pidx is None else pidx * n + j
            # kinds that share a mixer share its pools (``layer_places``)
            pool = (None if pidx is None or (of_mixer, jm) == (n, j)
                    else pidx * of_mixer + jm)
            h, *acc = one_layer(h, kind, layer, kidx, pool, *acc)
        return (h, *acc)

    def block(carry, layer_and_cache):
        h, aux_acc = carry
        layer, layer_cache, idx, ltd_flag = layer_and_cache
        at = dataclasses.replace(
            step, cache=layer_cache,
            window=win_table[idx.astype(jnp.int32)] if use_win else None)
        if use_ltd:
            # gather a random sorted token subset, run the layer on it,
            # scatter back — dropped tokens keep their input activations
            # (reference RandomLayerTokenDrop + token_sort/gather_scatter
            # kernels; sorted indices preserve the causal order so the
            # subset's causal mask is exact)
            def ltd_branch(hh):
                # trace-time import: runtime already depends on models, so the
                # reverse module-level import would be circular
                from ..runtime.data_pipeline.random_ltd import (
                    gather_tokens, sample_token_subset, scatter_tokens)

                key = jax.random.fold_in(_activation_derived_key(hh, 23),
                                         idx.astype(jnp.int32))
                kept, _ = sample_token_subset(key, S, cfg.ltd_keep)
                part = gather_tokens(hh, kept)
                msk = (None if attention_mask is None
                       else jnp.take(attention_mask, kept, axis=1))
                out, _, aux = _layer_forward(
                    cfg, part, layer, dataclasses.replace(
                        at, mask=msk, positions=jnp.take(positions, kept)))
                return scatter_tokens(hh, out, kept), aux

            def full_branch(hh):
                out, _, aux = _layer_forward(cfg, hh, layer, at)
                return out, aux

            h_new, aux = lax.cond(ltd_flag > 0, ltd_branch, full_branch, h)
            new_cache = None
        elif several:
            def one_layer(h, kind, layer, kidx, pool, aux_sum):
                h, _, aux = _layer_forward(cfg, h, layer, at, kind=kind)
                return h, aux_sum + aux

            h_new, aux = run_period(layer, None, one_layer, h,
                                    jnp.float32(0.0))
            new_cache = None
        else:
            h_new, new_cache, aux = _layer_forward(
                cfg, h, layer, at if banks is None else dataclasses.replace(
                    at, layer_index=idx.astype(jnp.int32)), kind=pattern[0])
        if use_pld:
            h_new, aux = pld_gate(cfg, h, h_new, aux, idx, pld_theta)
        return (h_new, aux_acc + aux), new_cache

    block_fn = block
    if cfg.remat and cache is None:
        block_fn = jax.checkpoint(block, prevent_cse=False,
                                  policy=resolve_remat_policy(cfg))

    if block_table is None:
        # one scan, with or without the dense cache; what an inactive
        # stochastic feature would read is None (None rides the pytree
        # untouched)
        xs = (layers, cache,
              (jnp.arange(L // P, dtype=jnp.float32)
               if with_idx or use_ltd else None),
              ltd_flags if use_ltd else None)

        def scan_layers(x):
            return lax.scan(block_fn, (x, jnp.float32(0.0)), xs,
                            unroll=cfg.scan_unroll if cache is None else 1)

        if looped:
            # no cache (the dense one was refused above): a pass keeps
            # nothing, and the sequence attends to itself in each
            x, new_cache = _run_passes(
                cfg, params, x, None,
                lambda h, _, t: (scan_layers(h)[0][0], None))
            aux_total = jnp.float32(0.0)    # a looped stack has no experts
        else:
            (x, aux_total), new_cache = scan_layers(x)
    else:
        # PAGED: the layer scan's CARRY is the arena itself, and the body
        # hands it down whole with the layer index. _layer_forward scatters
        # the new rows into it at (idx, blk, off), or a run's whole pages at
        # (idx, blk) — in place on the carry — and the paged kernels address
        # arena[idx, page] where it lies. The body must never take a layer's
        # pool out (dynamic_index_in_dim) and put it back: a Pallas call's
        # operand is a buffer of its own, so
        # XLA then copies the pool out and in around every kernel and
        # scatter — four 185 MiB copies a layer at OPT-1.3B's serving size,
        # 54 ms of a 73 ms decode iteration on the v5e (PERF.md, PR 26).
        # tests/kernels/test_tpu_compile.py holds the compiled programs to
        # it. The same holds for a recurrent layer's state pool, which rides
        # the carry beside the pages and is addressed (layer of its kind,
        # slot) where it lies. window/PLD/LTD are training- or
        # dense-cache-only features (a sliding-window model was refused
        # above).
        def scan_paged(x, arena, pools_above=None):
            """The stack once over the arena; ``pools_above``: a looped
            stack's pools of the passes before this one."""
            def paged_block(carry, layers_and_idx):
                h, aux_acc, arena, *counts_acc = carry
                layers, pidx = layers_and_idx

                def one_layer(h, kind, layer, kidx, pool, aux_sum, arena,
                              *counts_sum):
                    h, arena, aux, *counts = _layer_forward(
                        cfg, h, layer, dataclasses.replace(
                            step, cache=arena, layer_index=kidx,
                            pool_index=(pool if pools_above is None
                                        else pools_above + kidx)),
                        kind=kind)
                    return (h, aux_sum + aux, arena,
                            *(a + c for a, c in zip(counts_sum, counts)))

                return run_period(layers, pidx, one_layer, h, aux_acc, arena,
                                  *counts_acc), None

            return lax.scan(
                paged_block,
                (x, jnp.float32(0.0), arena,
                 *([jnp.zeros((moe_count_width(cfg),), jnp.int32)]
                   if moe_counts else [])),
                (layers, jnp.arange(L // P, dtype=jnp.int32)))[0]

        if looped:
            # passes x layers, two nested scans with the ARENA in the carry
            # of both: pass t writes and reads the pools [t L, (t + 1) L) of
            # its L layers that keep pages, and the weights are the inner
            # scan's operand, whatever the pass
            def stack(h, arena, t):
                h, _, arena = scan_paged(h, arena, t * len(paged_layers(cfg)))
                return h, arena

            x, new_cache = _run_passes(cfg, params, x, dict(cache), stack)
            aux_total = jnp.float32(0.0)    # a looped stack has no experts
        else:
            x, aux_total, new_cache, *moe_totals = scan_paged(x, dict(cache))

    if divide is not None:
        x = jnp.swapaxes(x[:, :divide], 0, 1)   # the rows' tokens alone
    logits = head_logits(params, x, cfg, normed=looped)
    if moe_counts:
        return logits, new_cache, aux_total, moe_totals[0]
    return logits, new_cache, aux_total


def _run_passes(cfg: TransformerConfig, params: Dict[str, Any], x: jax.Array,
                arena: Optional[Dict[str, jax.Array]], stack: Callable
                ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """A looped stack (``cfg.loop_passes`` > 1): ``stack(h, arena, t) ->
    (h, arena)``, the layers once over, run ``loop_passes`` times as ONE
    ``lax.scan`` whose carry is the activations and the arena; the final norm
    closes every pass, and what it gives is the next pass's input. Returns
    ``(the head's input, arena)``: the last pass's output at an
    exit threshold of 1 and over, where nothing is selected and the gate is
    not computed; under 1, of each token the output of the first pass t
    whose cumulated exit probability ``sum_{j<=t} p_j`` reaches the
    threshold (the last where none does), ``p_t = lam_t prod_{j<t}(1 -
    lam_j)``, ``lam_t = sigmoid(h_t w + b)`` in float32, and the last pass
    takes what is left. Every pass runs whatever the gate says: a token that
    has left still owes the later passes its keys."""
    T, threshold = cfg.loop_passes, cfg.loop_exit_threshold
    gated = threshold < 1.0
    f32 = jnp.float32

    def one_pass(carry, t):
        h, arena, *gate = carry
        h, arena = stack(h, arena, t)
        h = _final_norm(params, h, cfg)
        if gated:
            chosen, cum, left, done = gate
            g = params["exit_gate"]
            lam = jax.nn.sigmoid(
                jnp.einsum("bsh,h->bs", h.astype(f32), g["w"].astype(f32),
                           precision=lax.Precision.HIGHEST)
                + g["b"].astype(f32))
            last = t == T - 1
            cum = cum + jnp.where(last, left, lam * left)
            take = ~done & ((cum >= threshold) | last)
            gate = [jnp.where(take[..., None], h, chosen), cum,
                    left * (1.0 - lam), done | take]
        return (h, arena, *gate), None

    B, S, _ = x.shape
    gate = ([jnp.zeros_like(x), jnp.zeros((B, S), f32), jnp.ones((B, S), f32),
             jnp.zeros((B, S), bool)] if gated else [])
    (h, arena, *gate), _ = lax.scan(
        one_pass, (x, arena, *gate), jnp.arange(T, dtype=jnp.int32))
    return (gate[0] if gated else h), arena


def _run_layers(cfg: TransformerConfig, params: Dict[str, Any], x: jax.Array,
                step: Step, last_token: Optional[jax.Array]):
    """The layers of a stack of runs (``cfg.layer_runs``) over the paged
    cache, and the head -> ``(logits, new cache)`` and, with
    ``step.moe_counts``, the routing counts summed over the layers. Each run
    of equal periods is ONE ``lax.scan`` over its periods (a run of one
    period is run as it stands), so the program holds a period of each run,
    not the depth. The carry is the activations, the arena and the state
    pools, as in ``forward``'s paged scan, the value the last "mamba1" layer
    handed on and the routing counts. A kind's stacked tree stays whole
    outside the scans, its expert bank apart from it (``_banks_apart``), and
    a layer is taken out of it by its place among its kind, where the run
    stands plus where the period stands in it: a slice of the stack as a
    scan operand would be a copy of the run's weights a step. Its mixer's
    pool is its place among the layers of that MIXER, counted the same way
    (``_period_places``, ``layer_places``).

    ``last_token`` (B,): before the stack's ``tail_runs`` the carry is
    narrowed to that token of each row (activations, memory, positions and
    mask), and the rest of the stack and the head run at a width of one,
    under ONE ``lax.cond``: where no row names a token (all below 0) they do
    not run, and the logits are zeros."""
    stacks, banks = _banks_apart(cfg, layer_stacks(params["layers"], cfg))
    step = dataclasses.replace(step, expert_banks=banks)
    B, S, _ = x.shape
    has_memory = any(MIXERS[LAYER_KINDS[k][0]].hands_on
                     for k in layer_kinds(cfg))
    memory = (jnp.zeros((B, S, _mamba1_sizes(cfg)[0]), x.dtype)
              if has_memory else None)
    counts = (jnp.zeros((moe_count_width(cfg),), jnp.int32)
              if step.moe_counts else None)
    runs = cfg.layer_runs
    mixer_of = {kind: LAYER_KINDS[kind][0] for kind in stacks}
    # layers of each kind, and of each mixer, above a run
    above = [dict.fromkeys(stacks, 0)]
    pools_above = [dict.fromkeys(mixer_of.values(), 0)]
    for kinds, periods in runs:
        mixers = [mixer_of[kind] for kind in kinds]
        above.append({kind: n + kinds.count(kind) * periods
                      for kind, n in above[-1].items()})
        pools_above.append({mixer: n + mixers.count(mixer) * periods
                            for mixer, n in pools_above[-1].items()})

    def run(r, carry, step):
        kinds, periods = runs[r]
        base, pools = above[r], pools_above[r]
        pages_above = sum(n for mixer, n in pools.items()
                          if mixer and MIXERS[mixer].keeps == "pages")

        def period(carry, pidx):
            h, arena, memory, counts = carry
            for kind, (n, j, of_mixer, jm) in zip(kinds,
                                                  _period_places(kinds)):
                kidx = base[kind] + pidx * n + j
                mixer = mixer_of[kind]
                pool = (None if (pools[mixer], of_mixer, jm)
                        == (base[kind], n, j)
                        else pools[mixer] + pidx * of_mixer + jm)
                layer = jax.tree.map(lambda a: a[kidx], stacks[kind])
                h, arena, _, *rest = _layer_forward(
                    cfg, h, layer, dataclasses.replace(
                        step, cache=arena, layer_index=kidx, pool_index=pool,
                        memory=memory, shared_layer=pages_above - 1),
                    kind=kind)
                if counts is not None:
                    counts = counts + rest.pop(0)
                memory = rest[0] if rest else None
            return (h, arena, memory, counts), None

        if periods == 1:
            return period(carry, 0)[0]
        return lax.scan(period, carry,
                        jnp.arange(periods, dtype=jnp.int32))[0]

    tail_from = len(runs) - (tail_runs(cfg) if last_token is not None else 0)
    carry = (x, step.cache, memory, counts)
    for r in range(tail_from):
        carry = run(r, carry, step)
    h, arena, memory, counts = carry
    totals = [] if counts is None else [counts]
    if tail_from == len(runs):
        return (head_logits(params, h, cfg), arena, *totals)

    def last(a):
        return jnp.take_along_axis(
            a, jnp.maximum(last_token, 0).reshape(
                (B, 1) + (1,) * (a.ndim - 2)), axis=1)

    narrow = dataclasses.replace(
        step, positions=last(step.positions), paged_run=None,
        moe_counts=False,
        write_mask=(None if step.write_mask is None
                    else last(step.write_mask)))

    def tail(h, memory):        # these runs keep nothing: the arena is read
        carry = (h, arena, memory, None)
        for r in range(tail_from, len(runs)):
            carry = run(r, carry, narrow)
        return head_logits(params, carry[0], cfg)

    operands = (last(h), None if memory is None else last(memory))
    logits = jax.eval_shape(tail, *operands)
    return (lax.cond(jnp.any(last_token >= 0), tail,
                     lambda h, memory: jnp.zeros(logits.shape, logits.dtype),
                     *operands), arena, *totals)


def _final_norm(params: Dict[str, Any], x: jax.Array,
                cfg: TransformerConfig) -> jax.Array:
    return _norm(x, params["final_norm"]["scale"],
                 params["final_norm"].get("bias"), cfg.norm, cfg.norm_eps)


def head_logits(params: Dict[str, Any], x: jax.Array,
                cfg: TransformerConfig, normed: bool = False) -> jax.Array:
    """Final norm + output projection — THE one head implementation (the
    pipeline and param-offload executors call it too; a config knob added
    here must not be re-implemented there). ``normed``: ``x`` has the final
    norm behind it (a looped stack's every pass ends in it)."""
    if cfg.final_norm and not normed:
        x = _final_norm(params, x, cfg)
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsh,vh->bsv", x, params["embed"]["tokens"])
    else:
        logits = _qeinsum("bsh,hv->bsv", x, params["lm_head"], cfg.dtype, a8=cfg.a8_decode)
        if "lm_head_b" in params:
            logits = logits + params["lm_head_b"]
    return logits


def gather_target_logprobs(logits: jax.Array,
                           targets: jax.Array) -> jax.Array:
    """Per-position log softmax mass on ``targets`` (``logits[..., V]`` →
    ``(...)`` fp32), via the TP-safe one-hot masked-sum contraction — the
    shared implementation behind the RLHF score program and policy loss.
    ``take_along_axis`` over a vocab dim TP shards over 'model'
    miscompiles in the XLA CPU SPMD partitioner (see the rationale in
    :func:`cross_entropy_loss`, which interleaves the same contraction
    with its -100 label masking)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    one_hot = targets[..., None] == jnp.arange(logits.shape[-1],
                                               dtype=targets.dtype)
    picked = jnp.sum(jnp.where(one_hot, logits, 0.0), axis=-1)
    return picked - lse


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: Optional[jax.Array] = None) -> jax.Array:
    """Next-token cross entropy with fp32 accumulation; labels == -100 are
    ignored (HF convention used throughout the reference tests). Computed as
    logsumexp - picked_logit so no fp32 (B,S,V) log-softmax buffer is ever
    materialised (the (B,S,V) upcast fuses into the reduction)."""
    valid = labels != -100
    if mask is not None:
        valid = valid & mask.astype(bool)
    safe_labels = jnp.where(valid, labels, 0)
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)          # (B,S)
    # picked logit via a one-hot masked sum, NOT take_along_axis: gathering
    # along a vocab dim that TP shards over 'model' miscompiles in the XLA
    # CPU SPMD partitioner (NaN in the gathered values under tp×sp meshes —
    # the numerics-sentinel triage of the zero3×TP×SP dryrun; the
    # de-optimized program is clean). The compare+select fuses into the
    # reduction, and each vocab shard contributes its local partial sum —
    # the standard TP-safe cross-entropy contraction.
    one_hot = safe_labels[..., None] == jnp.arange(
        logits.shape[-1], dtype=safe_labels.dtype)
    picked = jnp.sum(jnp.where(one_hot, logits.astype(jnp.float32), 0.0),
                     axis=-1)
    token_loss = jnp.where(valid, lse - picked, 0.0)
    return token_loss.sum() / jnp.maximum(valid.sum(), 1)


def build_model(cfg: TransformerConfig, name: str = "transformer") -> Model:
    """Bundle init/apply/loss/axes for the engine."""

    def init(rng):
        return init_params(rng, cfg)

    def apply(params, batch, cache=None, start_pos=0):
        logits, new_cache, _ = forward(params, batch["input_ids"], cfg,
                                       attention_mask=batch.get("attention_mask"),
                                       cache=cache, start_pos=start_pos)
        return logits, new_cache

    def make_loss(c: TransformerConfig):
        def loss_fn(params, batch):
            logits, _, aux = forward(params, batch["input_ids"], c,
                                     attention_mask=batch.get("attention_mask"),
                                     pld_theta=batch.get("pld_theta"))
            labels = batch.get("labels")
            if labels is None:
                labels = jnp.concatenate(
                    [batch["input_ids"][:, 1:],
                     jnp.full((batch["input_ids"].shape[0], 1), -100, batch["input_ids"].dtype)],
                    axis=1)
            loss = cross_entropy_loss(logits, labels, batch.get("attention_mask"))
            if c.moe_num_experts > 0:
                loss = loss + c.moe_aux_loss_coef * aux / max(c.num_layers, 1)
            return loss

        return loss_fn

    def init_layer_block(rng, lo, blen):
        return init_layer_params(jax.random.split(rng, 16)[2], cfg, lo, blen)

    def eval_loss_fn(params, batch):
        # derive the eval copy at TRACE time so live-config mutations the
        # engine makes at compression boundaries (act_quant_bits) reach
        # eval on the next retrace — a build-time copy would freeze them
        return make_loss(eval_config(cfg))(params, batch)

    return Model(init=init, apply=apply, loss_fn=make_loss(cfg),
                 eval_loss_fn=eval_loss_fn,
                 init_layer_block=init_layer_block,
                 axes=param_axes(cfg), config=cfg, name=name)

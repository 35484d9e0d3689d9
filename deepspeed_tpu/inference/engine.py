"""InferenceEngine — analog of ``deepspeed.init_inference`` →
``InferenceEngine`` (reference inference/engine.py:89, deepspeed/__init__.py:260).

The reference engine rewrites an HF torch module in place (injection policies
→ fused CUDA modules), builds an mp group, and manages a global KV workspace.
Here the same capabilities are jit programs over a param pytree:

  model rewrite     → family state-dict import (hf_import.py) + the platform
                      kernel registry (flash/decode Pallas kernels resolve per
                      backend — the "kernel inject" analog, zero surgery)
  mp/tp group       → mesh 'model' axis; params sharded by logical-axis rules
  KV workspace      → kv_cache.py arena pytree threaded through jit steps
  CUDA-graph        → jit cache discipline: static shapes (prompt buckets,
                      fixed arena), one compiled prefill + one decode program

``generate`` = jitted prefill (the TTFT path) + ``lax.scan`` decode loop with
greedy/temperature/top-k sampling, early-EOS masking.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.core import Model, cast_floating
from ..models.presets import create_model
from ..observability import get_session
from ..parallel import mesh as mesh_mod
from ..utils.logging import log_dist, logger
from . import kv_cache
from .hf_import import import_hf_model, import_hf_state_dict, load_flat_weights_tree


@dataclasses.dataclass
class InferenceConfig:
    """Reference DeepSpeedInferenceConfig (inference/config.py) surface,
    TPU-shaped: bf16 is the native dtype (the reference explicitly rejects
    bf16 — a CUDA-kernel limitation that does not apply here)."""

    dtype: Any = jnp.bfloat16
    tensor_parallel: int = 1           # tp_size
    expert_parallel: int = 1           # ep_size — MoE models: expert banks
    #   sharded over the mesh 'expert' axis; gate+dispatch run in the decode
    #   path and XLA lowers the (E, C, H) exchange to the all-to-all the
    #   reference's DeepSpeedMoEInference issues explicitly
    #   (moe_inference.py:160, inference/engine.py:274 _create_ep_parallel_group)
    max_out_tokens: int = 1024         # KV arena length (prompt + generated)
    replace_with_kernel_inject: bool = True   # platform Pallas kernels
    checkpoint: Optional[str] = None   # flat-npz path (save_16bit_model output)
    seed: int = 0
    quantize_bits: Optional[int] = None  # 8/4 => weight-only int8/int4
    #   storage (reference int8/int4 kernel-injection + groupwise quantizer
    #   kernels): matmul weights quantized per output channel (int8) or per
    #   (group, channel) with nibble packing (int4), dequant fused into the
    #   GEMM — halves/quarters decode-phase HBM weight traffic.
    #   dtype='int8'/'int4' sets this.
    quantize_groups: Optional[int] = None  # int4 group size along K (None =>
    #   one group per output channel; reference quantization_settings groups)
    quantize_activations: bool = False  # W8A8 decode: per-row dynamic int8
    #   activation quantization feeds the MXU's native s8xs8 path — removes
    #   the weight-convert VPU bottleneck of the weight-only kernel (the
    #   reference's int8 path also quantizes activations,
    #   pt_binding.cpp quantize_activation). dtype='w8a8' sets this.
    compile_cache: bool = True         # persistent XLA compile cache
    #   (utils/compile_cache.py says where it lives)
    prompt_bucket: int = 64            # prompt-length compile bucket: prompts
    #   pad up to a multiple of this, bounding the number of distinct
    #   compiled prefill programs. The serving layer pins it to its KV
    #   block_size so a bucketed prompt never reserves arena blocks the
    #   true prompt can't use (ServingEngine does this at construction).

    def __post_init__(self):
        # dtype='int8' is storage quantization, not a compute dtype — the
        # normalisation lives here so the config-dict path can't slip a
        # string dtype into create_model/cast_floating (which would astype
        # the weights to int8 and silently destroy them)
        if self.dtype in ("int8", jnp.int8):
            self.quantize_bits = 8
            self.dtype = jnp.bfloat16
        elif self.dtype in ("w8a8",):
            self.quantize_bits = 8
            self.quantize_activations = True
            self.dtype = jnp.bfloat16
        elif self.dtype in ("w4a8",):
            self.quantize_bits = 4
            self.quantize_activations = True
            self.dtype = jnp.bfloat16
        elif self.dtype in ("int4",):
            self.quantize_bits = 4
            self.dtype = jnp.bfloat16
        elif isinstance(self.dtype, str):
            self.dtype = {"bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
                          "fp16": jnp.float16, "float16": jnp.float16,
                          "fp32": jnp.float32, "float32": jnp.float32,
                          }.get(self.dtype) or _reject_dtype(self.dtype)
        if self.quantize_bits not in (None, 4, 8):
            raise NotImplementedError(
                f"quantize_bits={self.quantize_bits}: 8 (per-channel) and "
                "4 (nibble-packed, groupwise) are supported")
        if self.quantize_groups is not None and self.quantize_bits != 4:
            raise ValueError("quantize_groups applies to int4 only")
        if self.prompt_bucket < 1:
            raise ValueError(f"prompt_bucket must be >= 1, got "
                             f"{self.prompt_bucket}")
        if self.quantize_activations and self.quantize_bits not in (4, 8):
            raise ValueError("quantize_activations (W8A8/W4A8) requires "
                             "int8 or int4 weights (dtype='w8a8'/'w4a8')")


def _reject_dtype(name: str):
    raise ValueError(f"unknown inference dtype '{name}' (use bf16/fp16/fp32 "
                     "or 'int8' for weight-only quantization)")


def _bucket(n: int, mult: int = 64) -> int:
    """Prompt-length bucket: bounds the number of distinct compiled prefill
    programs (the reference's CUDA-graph shape discipline)."""
    return max(mult, ((n + mult - 1) // mult) * mult)


def _sample(logits, rng, temperature: float, top_k: int,
            top_p: float = 1.0) -> jax.Array:
    """Greedy / temperature / top-k / top-p sampling — the ONE sampling
    rule, used for the first token and every decode step alike."""
    logits = logits.astype(jnp.float32)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        # nucleus: keep the smallest prefix of descending-prob tokens whose
        # cumulative mass reaches top_p (the top-1 token always survives)
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = (cum - probs < top_p).at[..., 0].set(True)
        cutoff = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits >= cutoff, logits, -jnp.inf)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


class InferenceEngine:
    """Owns sharded params + the KV arena + compiled prefill/decode programs."""

    def __init__(self, model: Model, config: InferenceConfig,
                 params: Optional[Any] = None, mesh: Optional[Mesh] = None):
        if config.compile_cache:
            from ..utils.compile_cache import enable_compile_cache

            enable_compile_cache()
        self.model = model
        self.config = config
        if mesh is None:
            from ..config.config import ParallelConfig

            tp_req = max(1, config.tensor_parallel)
            ep_req = max(1, config.expert_parallel)
            mesh = mesh_mod.build_mesh(
                ParallelConfig(tensor_parallel_size=tp_req,
                               expert_parallel_size=ep_req,
                               data_parallel_size=ep_req),
                devices=jax.devices()[:tp_req * ep_req])
        self.mesh = mesh
        tp = int(self.mesh.shape[mesh_mod.MODEL_AXIS])
        ep = int(self.mesh.shape.get(mesh_mod.EXPERT_AXIS, 1))
        cfg = model.config
        if cfg is None:
            raise ValueError("model.config is required for inference (the "
                             "KV-cache arena is sized from it)")
        if cfg.num_kv_heads % max(tp, 1) != 0:
            raise ValueError(f"tensor_parallel={tp} must divide "
                             f"num_kv_heads={cfg.num_kv_heads}")
        if ep > 1:
            if cfg.moe_num_experts <= 0:
                raise ValueError(f"expert_parallel={ep} requires an MoE "
                                 "model (moe_num_experts > 0)")
            if cfg.moe_num_experts % ep != 0:
                raise ValueError(
                    f"expert_parallel={ep} must divide "
                    f"moe_num_experts={cfg.moe_num_experts}")
        if config.quantize_activations:
            # W8A8/W4A8 engage through the decode-kernel gate; a config
            # where the gate can never pass must not silently publish
            # weight-only numbers under the a8 label
            mode = "w8a8" if config.quantize_bits == 8 else "w4a8"
            wo = "int8" if config.quantize_bits == 8 else "int4"
            if tp > 1:
                raise NotImplementedError(
                    f"quantize_activations ({mode.upper()}) + "
                    "tensor_parallel > 1 is not supported — the s8xs8 "
                    f"decode kernel is single-device (weight-only {wo} "
                    "supports TP)")
            # per-site gate preview: int8 sites need K,N % 128; int4 packs
            # K/2, so its CONTRACTION dim must be % 256 (output dims stay
            # % 128)
            k_align = 128 if config.quantize_bits == 8 else 256
            H = cfg.hidden_size
            ND, F, V = (cfg.num_heads * cfg.head_dim, cfg.ffn_hidden_size,
                        cfg.vocab_size)
            sites = {"attn qkv": (H, ND), "attn out": (ND, H),
                     "mlp in": (H, F), "mlp out": (F, H)}
            if not cfg.tie_embeddings:
                sites["lm_head"] = (H, V)
            bad_sites = [name for name, (kd, nd) in sites.items()
                         if kd % k_align or nd % 128]
            if (config.quantize_bits == 4 and config.quantize_groups
                    and config.quantize_groups % 128):
                bad_sites = list(sites)
            if bad_sites:
                logger.warning(
                    f"{mode}: the s8xs8 kernel gate will not engage for "
                    f"site(s) {bad_sites} (K-alignment {k_align}, "
                    f"N-alignment 128"
                    f"{', groups ' + str(config.quantize_groups) if config.quantize_groups else ''}"
                    f") — those sites serve the weight-only {wo} path")
            cfg.a8_decode = True

        # the 'serving' policy from the rule registry: TP only, no fsdp axis
        # (reference inference shards qkv/mlp across the mp group,
        # replicating the rest); MoE expert banks additionally shard their
        # leading E dim over 'expert'
        self._param_shapes = jax.eval_shape(model.init,
                                            jax.random.PRNGKey(0))
        from ..parallel.rules import get_policy

        specs = get_policy("serving").param_specs(
            self._param_shapes, model.axes, expert_parallel=True)
        self.param_shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P))

        if params is None:
            with mesh_mod.ambient(self.mesh):
                if config.quantize_bits:
                    # init + quantize in ONE program: XLA liveness frees each
                    # full-precision weight as its int8 replacement is
                    # produced — materialising the whole bf16 tree first
                    # OOMs at 13B on a 16GB chip
                    from ..models.transformer import quantize_model_weights

                    # shardings matter for BOTH tp>1 (sliced dense sites)
                    # and ep>1 (expert banks over the 'expert' axis) —
                    # gating on tp alone silently replicated MoE experts
                    q_sh = (self._quantized_shardings()
                            if tp > 1 or ep > 1 else None)
                    params = jax.jit(lambda key: quantize_model_weights(
                        cast_floating(model.init(key), config.dtype),
                        bits=config.quantize_bits,
                        group_size=config.quantize_groups),
                        out_shardings=q_sh)(
                            jax.random.PRNGKey(config.seed))
                else:
                    params = jax.jit(
                        lambda key: cast_floating(model.init(key), config.dtype),
                        out_shardings=self.param_shardings)(
                            jax.random.PRNGKey(config.seed))
        elif config.quantize_bits:
            # quantize BEFORE any tree-wide device_put: each host weight leaf
            # transfers, quantizes, and frees individually, so a model whose
            # full-precision weights exceed HBM (13B bf16 = 26GB on a 16GB
            # chip) still loads — only its int8 form ever resides on device
            from ..models.transformer import quantize_model_weights

            params = cast_floating(params, config.dtype)
            q_sh = (self._quantized_shardings()
                    if tp > 1 or ep > 1 else None)
            params = quantize_model_weights(params,
                                            bits=config.quantize_bits,
                                            donate=True,
                                            group_size=config.quantize_groups,
                                            shardings=q_sh)
            if q_sh is not None:
                # quantized leaves already landed sharded; this put only
                # moves the remaining dense leaves (and no-ops the rest)
                params = jax.tree.map(
                    lambda x, s: jax.device_put(jnp.asarray(x), s),
                    params, q_sh)
            else:
                params = jax.tree.map(jnp.asarray, params)  # host leaves
        else:
            params = cast_floating(params, config.dtype)
            params = jax.tree.map(
                lambda x, s: jax.device_put(np.asarray(x), s),
                params, self.param_shardings)
        self.params = params

        self._prefill_cache: Dict[Tuple, Any] = {}
        self._decode_cache: Dict[Tuple, Any] = {}
        # engine-owned KV arena, allocated once per batch size and donated
        # through prefill/decode each call (reference InferenceContext
        # allocates its workspace once, inference_context.h:49) — per-call
        # allocation is wasted HBM traffic at serving cadence
        self._arena: Dict[int, Any] = {}
        self._fwd = None
        self._generate_calls = 0   # observability step counter (watchdog)
        n = sum(int(p.size) for p in jax.tree.leaves(self.params))
        log_dist(f"inference engine ready: {n / 1e6:.1f}M params, tp={tp}, "
                 f"ep={ep}, "
                 f"dtype={jnp.dtype(config.dtype).name}, "
                 f"arena={config.max_out_tokens} tokens "
                 f"({kv_cache.cache_memory_bytes(cfg, 1, config.max_out_tokens, config.dtype) / 2**20:.0f}"
                 f" MiB/seq)")

    def _quantized_shardings(self) -> Any:
        """Sharding tree for the QUANTIZED params: each quantized site's
        packed weight inherits the dense weight's TP spec (same axis
        semantics; int4's packed K/2 keeps the K-axis placement) and its
        scales shard on the output-channel axis only — the reference's
        auto-TP slicing applied to the q8/scale pair."""
        from ..models.transformer import quantize_model_weights

        def one(spec_sh):
            spec = spec_sh.spec
            out_axis = spec[-1] if len(spec) else None
            return {
                "q8": spec_sh,            # placeholder keys; matched below
                "s": NamedSharding(self.mesh, P(*([None] * max(
                    len(spec) - 1, 1) + [out_axis]))),
            }

        # derive structure by quantizing the SHAPES already computed at init
        q_shapes = jax.eval_shape(
            lambda t: quantize_model_weights(
                t, bits=self.config.quantize_bits,
                group_size=self.config.quantize_groups), self._param_shapes)

        def walk(qnode, dense_sh):
            if isinstance(qnode, dict) and ("q8" in qnode or "q4" in qnode):
                key = "q8" if "q8" in qnode else "q4"
                built = one(dense_sh)
                return {key: built["q8"], "s": built["s"]}
            if isinstance(qnode, dict):
                return {k: walk(v, dense_sh[k]) for k, v in qnode.items()}
            return dense_sh

        return walk(q_shapes, self.param_shardings)

    # -- tpuaudit registration (tools/tpuaudit) ------------------------------
    def _audit_expected_collectives(self) -> frozenset:
        """Collectives the serving programs are allowed to contain: TP
        activation reductions/gathers, MoE dispatch all-to-alls. A
        single-device engine declares none — any collective in its program
        is a sharding bug."""
        exp: set = set()
        if int(self.mesh.shape[mesh_mod.MODEL_AXIS]) > 1:
            exp |= {"all-reduce", "all-gather"}
        if int(self.mesh.shape.get(mesh_mod.EXPERT_AXIS, 1)) > 1:
            exp |= {"all-to-all", "all-reduce", "all-gather"}
        return frozenset(exp)

    def register_audit_entries(self, batch_size: int = 1,
                               prompt_len: int = 64,
                               max_new_tokens: int = 8,
                               temperature: float = 0.0, top_k: int = 0,
                               top_p: float = 1.0,
                               eos_token_id: Optional[int] = None) -> list:
        """Register the prefill and decode programs with the tpuaudit
        auditor (``python -m tools.tpuaudit``) WITHOUT generating: the
        programs are built (jit-wrapped, untraced) and handed over with
        abstract arguments mirroring a ``generate`` call of this shape."""
        try:
            from tools.tpuaudit import registry as _audit  # noqa: F401 — probe
        except ImportError:
            return []
        names = []
        B, S_pad = batch_size, _bucket(prompt_len, self.config.prompt_bucket)
        key_p = (B, S_pad)
        if key_p not in self._prefill_cache:
            self._prefill_cache[key_p] = self._prefill_fn(S_pad)
        names.append(self._register_prefill_audit(B, S_pad))
        n_rest = max_new_tokens - 1
        if n_rest > 0:
            key_d = (B, n_rest, float(temperature), int(top_k), float(top_p),
                     eos_token_id, False)
            if key_d not in self._decode_cache:
                self._decode_cache[key_d] = self._decode_fn(
                    n_rest, temperature, top_k, top_p, eos_token_id)
            names.append(self._register_decode_audit(key_d))
        return [n for n in names if n]

    def _cache_sds(self, B: int):
        return jax.eval_shape(lambda: kv_cache.init_cache(
            self.model.config, B, self.config.max_out_tokens,
            self.config.dtype))

    def _params_sds(self):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), self.params)

    def _register_prefill_audit(self, B: int, S_pad: int) -> Optional[str]:
        try:
            from tools.tpuaudit.registry import (StaleEntryError,
                                                 register_entry_point)
        except ImportError:
            return None
        try:
            import weakref

            wself = weakref.ref(self)

            def build():
                # everything abstract is synthesized HERE, at audit time —
                # registration itself (which rides every first-shape
                # generate call) stays a dict insert, and only a weakref to
                # the engine is captured so a replaced engine's params/arena
                # are never pinned by the registry
                eng = wself()
                if eng is None:
                    raise StaleEntryError("inference/prefill: engine gone")
                T = eng.config.max_out_tokens
                args = (eng._params_sds(),
                        jax.ShapeDtypeStruct((B, S_pad), jnp.int32),
                        jax.ShapeDtypeStruct((B, T), jnp.int32),
                        eng._cache_sds(B))
                return eng._prefill_cache[(B, S_pad)], args, {}

            register_entry_point(
                "inference/prefill", build=build, donate_argnums=(3,),
                expected_collectives=self._audit_expected_collectives(),
                mesh=self.mesh,
                tags={"engine": "InferenceEngine", "batch": B,
                      "prompt_bucket": S_pad,
                      # prefill ingests the whole padded prompt per run
                      "tokens_per_step": B * S_pad,
                      "shard": self._shard_tag()})
            return "inference/prefill"
        except Exception:   # registration must never take serving down
            logger.warning("tpuaudit prefill registration failed",
                           exc_info=True)
            return None

    def _register_decode_audit(self, key_d: Tuple) -> Optional[str]:
        try:
            from tools.tpuaudit.registry import (StaleEntryError,
                                                 register_entry_point)
        except ImportError:
            return None
        try:
            import weakref

            B, n_rest = key_d[0], key_d[1]
            wself = weakref.ref(self)

            def build():
                eng = wself()
                if eng is None:
                    raise StaleEntryError("inference/decode: engine gone")
                args = (eng._params_sds(), eng._cache_sds(B),
                        jax.ShapeDtypeStruct((B, eng.config.max_out_tokens),
                                             jnp.int32),
                        jax.ShapeDtypeStruct((B,), jnp.int32),
                        jax.ShapeDtypeStruct((B,), jnp.int32),
                        jax.ShapeDtypeStruct((), jnp.float32),
                        jax.ShapeDtypeStruct((2,), jnp.uint32))
                return eng._decode_cache[key_d], args, {}

            register_entry_point(
                "inference/decode", build=build, donate_argnums=(1,),
                expected_collectives=self._audit_expected_collectives(),
                mesh=self.mesh,
                tags={"engine": "InferenceEngine", "batch": B,
                      "new_tokens": n_rest,
                      # one decode program emits n_rest tokens per row
                      "tokens_per_step": B * n_rest,
                      "shard": self._shard_tag()})
            return "inference/decode"
        except Exception:
            logger.warning("tpuaudit decode registration failed",
                           exc_info=True)
            return None

    def _shard_tag(self) -> dict:
        """tools/tpushard placement contract: the params argument follows
        the registry's 'serving' policy; every program consuming these
        weights (prefill↔decode, the ServingEngine programs over this
        engine) shares the 'serving' exchange group, so the analyzer
        cross-checks the chain's layouts."""
        from ..parallel.rules import shard_tag

        return shard_tag("serving", axes=self.model.axes, params_arg=0,
                         expert_parallel=True, group="serving")

    # -- plain forward (reference InferenceEngine.forward / module call) -----
    def forward(self, input_ids, attention_mask=None):
        """Full-sequence logits, no cache."""
        if self._fwd is None:
            self._fwd = jax.jit(lambda p, b: self.model.apply(p, b)[0])
        batch = {"input_ids": jnp.asarray(input_ids)}
        if attention_mask is not None:
            batch["attention_mask"] = jnp.asarray(attention_mask)
        with mesh_mod.ambient(self.mesh):
            return self._fwd(self.params, batch)

    __call__ = forward

    # -- generate ------------------------------------------------------------
    def _prefill_fn(self, S_pad: int):
        cfg = self.model.config
        from ..models.transformer import forward as model_forward

        def prefill(params, ids, mask, cache):
            logits, cache, _ = model_forward(params, ids, cfg,
                                             attention_mask=mask,
                                             cache=cache, start_pos=0)
            return logits, cache

        return jax.jit(prefill, donate_argnums=(3,))

    def _decode_fn(self, n_new: int, temperature: float, top_k: int,
                   top_p: float, eos_token_id: Optional[int],
                   ragged: bool = False):
        cfg = self.model.config
        T_max = self.config.max_out_tokens
        from ..models.transformer import forward as model_forward

        # RAGGED alibi batches need TRUE key positions in the bias — arena
        # columns equal positions for the right-padded prompt part, but
        # generated keys at column S+t sit at position len_b+t per row.
        # Uniform batches keep kpos=None (the column default is exact and
        # custom attention_impls without the kwarg keep working).
        use_kpos = ragged and cfg.position == "alibi"

        def decode(params, cache, valid, first_tok, lengths, s_width, rng):
            kpos = None
            if use_kpos:
                col = jnp.arange(T_max, dtype=jnp.float32)[None]
                shift = (s_width - lengths.astype(jnp.float32))[:, None]
                kpos = col - shift * (col >= s_width)

            def step(carry, rng):
                cache, valid, tok, pos, done = carry
                idx = cache["index"][0]
                # the incoming token becomes a valid key at ARENA column idx
                # (uniform across rows); its POSITION is per-row — a ragged
                # row's first decode token sits at its true prompt length,
                # not the padded array width
                valid = jax.lax.dynamic_update_slice(
                    valid, jnp.ones((valid.shape[0], 1), valid.dtype), (0, idx))
                logits, cache, _ = model_forward(
                    params, tok[:, None], cfg,
                    attention_mask=valid, cache=cache, start_pos=idx,
                    positions=pos[:, None], key_positions=kpos)
                nxt = _sample(logits[:, -1], rng, temperature, top_k, top_p)
                if eos_token_id is not None:
                    nxt = jnp.where(done, eos_token_id, nxt)
                    done = done | (nxt == eos_token_id)
                return (cache, valid, nxt, pos + 1, done), nxt

            done = jnp.zeros(first_tok.shape, bool)
            rngs = jax.random.split(rng, n_new)
            (cache, valid, _, _, _), toks = jax.lax.scan(
                step, (cache, valid, first_tok, lengths, done), rngs)
            return jnp.moveaxis(toks, 0, 1), cache  # (B, n_new)

        return jax.jit(decode, donate_argnums=(1,))

    def generate(self, input_ids, attention_mask=None, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 return_ttft: bool = False):
        """Unhandled-exception guard around :meth:`_generate`: dump the
        flight record (ring + stacks + open spans) before the exception
        unwinds — a no-op without an enabled recorder. See ``_generate``
        for the generation semantics."""
        try:
            return self._generate(
                input_ids, attention_mask=attention_mask,
                max_new_tokens=max_new_tokens, temperature=temperature,
                top_k=top_k, top_p=top_p, eos_token_id=eos_token_id,
                seed=seed, return_ttft=return_ttft)
        except Exception as e:
            get_session().crash_dump("generate-exception", exc=e,
                                     call=self._generate_calls)
            raise

    def _generate(self, input_ids, attention_mask=None, max_new_tokens: int = 32,
                  temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
                  eos_token_id: Optional[int] = None, seed: int = 0,
                  return_ttft: bool = False):
        """Prompt ids (B, S) → generated ids (B, max_new_tokens).

        Ragged prompts: pass ``attention_mask`` (B, S); prompts are treated
        as right-padded. Decoded tokens take each row's TRUE next positions
        (len_b, len_b+1, ...) — and alibi models bias keys by their true
        per-row positions too — so batched ragged generation matches
        serving each prompt alone, BLOOM included.
        ``return_ttft``: also return wall seconds to first token (prefill)."""
        cfg = self.model.config
        ids = jnp.asarray(np.asarray(input_ids), jnp.int32)
        B, S = ids.shape
        S_pad = _bucket(S, self.config.prompt_bucket)
        T_max = self.config.max_out_tokens
        if S_pad + max_new_tokens > T_max:
            raise ValueError(
                f"prompt ({S_pad} padded) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_out_tokens={T_max} — raise InferenceConfig."
                f"max_out_tokens (the reference raises the same in "
                f"inference_context.h workspace sizing)")
        mask = (jnp.ones((B, S), jnp.int32) if attention_mask is None
                else jnp.asarray(np.asarray(attention_mask), jnp.int32))
        ids_pad = jnp.pad(ids, ((0, 0), (0, S_pad - S)))
        # valid-key mask over the whole arena, prompt part filled
        valid = jnp.zeros((B, T_max), jnp.int32)
        valid = valid.at[:, :S].set(mask)

        key_p = (B, S_pad)
        if key_p not in self._prefill_cache:
            self._prefill_cache[key_p] = self._prefill_fn(S_pad)
            self._register_prefill_audit(B, S_pad)
        n_rest = max_new_tokens - 1
        ragged = attention_mask is not None and bool(
            np.any(np.asarray(mask).sum(-1) != S))
        key_d = (B, n_rest, float(temperature), int(top_k), float(top_p),
                 eos_token_id, ragged)
        if n_rest > 0 and key_d not in self._decode_cache:
            self._decode_cache[key_d] = self._decode_fn(
                n_rest, temperature, top_k, top_p, eos_token_id,
                ragged=ragged)
            self._register_decode_audit(key_d)

        with mesh_mod.ambient(self.mesh):
            cache = self._arena.pop(B, None)
            # single-workspace policy (reference InferenceContext): a batch
            # size change frees the old arena instead of pinning one arena
            # per B seen over the process lifetime
            self._arena.clear()
            if cache is None:
                cache = kv_cache.init_cache(cfg, B, T_max, self.config.dtype)
            else:
                # reuse the engine-owned arena: reset the write cursor; the
                # stale keys stay masked by `valid` and are overwritten as
                # prefill/decode proceed
                cache = {**cache, "index": jnp.zeros_like(cache["index"])}
            # TTFT brackets prefill + first-token sampling; the explicit
            # block_until_ready is the async-dispatch fence that makes the
            # wall-clock real (the tpulint wallclock-timing-without-sync
            # contract). The two readings are this function's own (the span
            # is the shared no-op when nothing records), so return_ttft
            # works without telemetry.
            obs = get_session()
            t_prefill = time.perf_counter()
            with obs.span("inference/prefill", batch=B,
                          prompt_tokens=int(S)):
                logits, cache = self._prefill_cache[key_p](
                    self.params, ids_pad, valid, cache)
                # rewind the write cursor from the padded to the true prompt
                # length: decoded tokens must take positions S, S+1, ... — the
                # junk keys prefill wrote in the padding slots stay masked and
                # get overwritten as decoding proceeds
                cache = {**cache, "index": jnp.full_like(cache["index"], S)}
                lengths = mask.sum(-1)
                last = jnp.take_along_axis(
                    logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
                rng, r_first = jax.random.split(jax.random.PRNGKey(seed))
                first = _sample(last, r_first, temperature, top_k, top_p)
                first = jax.block_until_ready(first)
            ttft = time.perf_counter() - t_prefill
            if n_rest == 0:
                out = first[:, None]
            else:
                decode_span = obs.span("inference/decode",
                                       sync=True, batch=B,
                                       new_tokens=int(n_rest))
                with decode_span:
                    rest, cache = self._decode_cache[key_d](
                        self.params, cache, valid, first, lengths,
                        jnp.float32(S), rng)
                    out = jnp.concatenate([first[:, None], rest], axis=1)
                # publish only when the span actually synced (a disabled or
                # rank-gated tracer hands back a non-syncing span): an
                # unfenced duration times the enqueue, not the decode
                if decode_span.sync and decode_span.duration_s > 0:
                    obs.registry.gauge(
                        "inference/decode_tokens_per_sec").set(
                            B * n_rest / decode_span.duration_s)
            self._arena[B] = cache
            if obs.enabled:
                obs.registry.histogram(
                    "inference/ttft_ms",
                    help="prefill + first token wall ms").observe(
                        ttft * 1e3, batch=B)
                obs.registry.gauge(
                    "inference/kv_cache_occupancy",
                    help="fraction of the KV arena holding live tokens"
                ).set((S + max_new_tokens) / T_max, batch=B)
                obs.note_step(self._generate_calls)
                obs.maybe_record_memory(self._generate_calls)
                self._generate_calls += 1
        return (out, ttft) if return_ttft else out


# ---------------------------------------------------------------------------


def init_inference(model=None, config=None, tensor_parallel: Optional[int] = None,
                   dtype=None, max_out_tokens: Optional[int] = None,
                   checkpoint: Optional[str] = None, hf_model=None,
                   hf_state_dict=None, mesh: Optional[Mesh] = None,
                   replace_with_kernel_inject: bool = True,
                   expert_parallel: Optional[int] = None,
                   **model_overrides) -> InferenceEngine:
    """Analog of ``deepspeed.init_inference`` (reference __init__.py:260).

    ``model``: a ``Model`` bundle or a preset name (e.g. "bloom-7b",
    "llama-7b" — the per-architecture injection-policy registry analog).
    Weights: ``hf_model`` / ``hf_state_dict`` (HF import + TP sharding =
    auto-TP), ``checkpoint`` (flat npz from save_16bit_model), else random.
    """
    if isinstance(config, dict):
        config = InferenceConfig(**config)
    cfg = config or InferenceConfig()
    if tensor_parallel is not None:
        cfg.tensor_parallel = int(tensor_parallel)
    if expert_parallel is not None:
        cfg.expert_parallel = int(expert_parallel)
    if dtype is not None:
        # normalisation (incl. 'int8' → weight-only quantization) happens in
        # InferenceConfig.__post_init__ — rebuild so it applies
        cfg.dtype = dtype
        cfg.__post_init__()
    if max_out_tokens is not None:
        cfg.max_out_tokens = int(max_out_tokens)
    cfg.replace_with_kernel_inject = replace_with_kernel_inject
    if checkpoint is not None:
        cfg.checkpoint = checkpoint

    family = None
    if isinstance(model, str):
        from ..models.presets import _SIZES

        family = (_SIZES[model]["family"] if model in _SIZES else model)
        model = create_model(model, dtype=cfg.dtype,
                             max_seq_len=max(cfg.max_out_tokens, 128),
                             **model_overrides)
    if model is None:
        raise ValueError("model is required: a Model bundle or preset name")

    params = None
    if hf_model is not None:
        params = import_hf_model(hf_model, model.config,
                                 family or model.name)
    elif hf_state_dict is not None:
        params = import_hf_state_dict(hf_state_dict, model.config,
                                      family or model.name)
    elif cfg.checkpoint is not None:
        if cfg.checkpoint.startswith("megatron:"):
            # Megatron-LM mp_rank_XX checkpoint dir: TP shards merged into
            # the logical layout (the MegatronSDLoader analog,
            # inference/megatron_import.py); target TP resharding then
            # falls out of device_put like every other load
            from .megatron_import import load_megatron_checkpoint

            params = load_megatron_checkpoint(
                cfg.checkpoint[len("megatron:"):], model.config)
        else:
            params = load_flat_weights_tree(cfg.checkpoint)
    return InferenceEngine(model, cfg, params=params, mesh=mesh)

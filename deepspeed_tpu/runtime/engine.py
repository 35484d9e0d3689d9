"""The training engine.

TPU-native analog of ``DeepSpeedEngine`` (reference runtime/engine.py:181,
3267 LoC) and ``deepspeed.initialize`` (deepspeed/__init__.py:58). The
reference engine wraps an nn.Module and orchestrates hooks, buckets, streams
and NCCL by hand; here the engine builds ONE jitted SPMD train-step whose
sharding annotations (from parallel/zero.py) make XLA emit the same dataflow:

  forward/backward   — jax.value_and_grad traced over the model's loss_fn
  grad accumulation  — lax.scan over the microbatch dim (reference: GAS loop)
  DP grad averaging  — mean over the 'data' axis via sharding constraints
                       (reference: allreduce_gradients engine.py:1736)
  ZeRO 0-3           — parallel/zero.py sharding plan (see its docstring)
  fp16               — dynamic loss scale + overflow skip (runtime/fp16/*)
  bf16               — bf16 params + fp32 master (runtime/bf16_optimizer.py)

API parity: ``initialize()`` returns (engine, optimizer, dataloader,
lr_scheduler); the engine exposes ``train_batch``, ``forward``/``backward``/
``step`` (staged emulation), ``save_checkpoint``/``load_checkpoint``,
config accessors, and throughput logging.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..comm.comms_logging import configure_comms_logger
from ..config.config import Config, load_config
from ..models.core import Model, cast_floating, param_count
from ..observability.memory import hbm_counts
from ..parallel import mesh as mesh_mod
from ..parallel.zero import (ZeroShardingPlan, as_named, build_sharding_plan,
                             describe_plan, optimizer_state_specs)
from ..utils.logging import log_dist, logger
from ..utils.timer import (BACKWARD_GLOBAL_TIMER, FORWARD_GLOBAL_TIMER,
                           STEP_GLOBAL_TIMER, SynchronizedWallClockTimer,
                           ThroughputTimer, TRAIN_BATCH_TIMER)
from .dataloader import DeepSpeedDataLoader, RepeatingLoader
from .loss_scaler import LossScaleState, create_loss_scaler, has_overflow
from .lr_schedules import build_lr_schedule
from .optimizer import MixedPrecisionOptimizer, OptimizerState, StepStats, build_optimizer


def _batch_tokens(batch) -> int:
    """Tokens consumed by ONE execution of a program fed ``batch`` (a pytree
    of arrays or ShapeDtypeStructs): the full ``input_ids`` extent for token
    batches — including any leading gas dim — else the example count of the
    first leaf (feature dims dropped). Registered as the ``tokens_per_step``
    audit tag so tpucost can turn its roofline bound into tokens/sec."""
    if isinstance(batch, dict) and "input_ids" in batch:
        return int(np.prod(np.shape(batch["input_ids"])))
    leaves = jax.tree.leaves(batch)
    if not leaves:
        return 0
    shape = tuple(np.shape(leaves[0]))
    return int(np.prod(shape[:-1] if len(shape) > 1 else shape))


class TrainEngine:
    """One engine instance per process; owns sharded state + jitted step."""

    def __init__(self, model: Model, config: Config, mesh: Optional[Mesh] = None,
                 optimizer: Optional[MixedPrecisionOptimizer] = None,
                 lr_scheduler=None, training_data=None, collate_fn=None,
                 rng: Optional[jax.Array] = None):
        if config.compile_cache.enabled:
            from ..utils.compile_cache import enable_compile_cache

            enable_compile_cache(config.compile_cache.min_compile_time_secs)
        # observability session first: model transforms (pipelinize), mesh
        # build and step compiles below all publish through it; the disabled
        # default is a shared no-op so tier-1 cost is zero
        from ..observability import configure_observability

        self._obs = configure_observability(config.observability)
        opt_name = config.optimizer.type.lower()
        self._onebit = opt_name in ("onebitadam", "onebitlamb", "zerooneadam")
        if self._onebit:
            # compressed-gradient comm needs full local grads per dp rank:
            # incompatible with grad/param sharding and non-data axes
            # (reference OnebitAdam has the same ZeRO<=1 constraint)
            if config.zero_optimization.stage > 1:
                raise ValueError(
                    f"{config.optimizer.type}: 1-bit compression requires "
                    f"ZeRO stage <= 1 (got {config.zero_optimization.stage})")
            par = config.parallel
            if (par.tensor_parallel_size > 1 or par.sequence_parallel_size > 1
                    or par.pipeline_parallel_size > 1
                    or par.expert_parallel_size > 1):
                raise ValueError(
                    f"{config.optimizer.type}: compressed allreduce is "
                    "data-parallel only (tp/sp/pp/ep must be 1)")
            if opt_name == "zerooneadam" and config.fp16.enabled:
                raise NotImplementedError(
                    "zerooneadam + fp16 dynamic loss scaling is not "
                    "supported: an overflow-skipped step would desynchronize "
                    "the variance schedule (inner counter reverts) from the "
                    "dense-comm schedule (outer counter advances) — use bf16")
        if opt_name == "cpuadam" and \
                config.zero_optimization.offload_optimizer.device != "cpu":
            raise ValueError(
                "optimizer 'cpuadam' is the host-offloaded Adam — set "
                "zero_optimization.offload_optimizer.device='cpu' (refusing "
                "to silently run plain device Adam)")
        self._nvme_offload = (
            config.zero_optimization.offload_optimizer.device == "nvme")
        if self._nvme_offload:
            # ZeRO-Infinity tier (docs/offload_design.md tier 2): the swapper
            # owns the optimizer math, so only the Adam family is swappable —
            # the reference has the same restriction (swappable_optimizer)
            if opt_name not in ("adam", "adamw", "fusedadam", "cpuadam"):
                raise ValueError(
                    f"offload_optimizer.device='nvme' supports the Adam "
                    f"family only, got '{config.optimizer.type}'")
            if config.fp16.enabled:
                raise NotImplementedError(
                    "nvme offload + fp16 dynamic loss scaling is not "
                    "supported (overflow-skip needs resident state); use bf16")
            # multi-process: the swapper partitions state by ADDRESSABLE
            # region of the grad sharding, so each process's swap dir holds
            # only its shards (the reference's per-dp-rank partition swap)
            if config.parallel.pipeline_parallel_size > 1:
                raise NotImplementedError("nvme offload + pipeline "
                                          "parallelism is not supported")
        self._param_offload_tier = config.zero_optimization.offload_param.device
        if self._param_offload_tier != "none":
            # ZeRO-3 param offload (docs/offload_design.md tier 3): the train
            # step becomes a host-driven loop streaming layer blocks through
            # HBM (runtime/param_offload.py); the executor owns ALL optimizer
            # state (host fp32), so it composes with neither the resident
            # optimizer paths nor the compressed-comm step
            if config.zero_optimization.stage < 3:
                raise ValueError(
                    "offload_param requires ZeRO stage 3 (reference "
                    "constraint: params are partitioned before offload)")
            if opt_name not in ("adam", "adamw", "fusedadam", "cpuadam"):
                raise ValueError(
                    f"offload_param supports the Adam family only, got "
                    f"'{config.optimizer.type}' (the streamed update is "
                    "swap-aware AdamW, the reference's restriction too)")
            if self._onebit:
                raise ValueError(
                    "offload_param is incompatible with 1-bit optimizers")
            if self._nvme_offload:
                raise ValueError(
                    "offload_param subsumes optimizer-state offload (its "
                    "fp32 state is host-resident already) — leave "
                    "offload_optimizer.device='none'")
            if config.zero_optimization.offload_optimizer.device == "cpu":
                raise ValueError(
                    "offload_param subsumes optimizer-state offload — leave "
                    "offload_optimizer.device='none'")
            # multi-process: each process streams only its addressable
            # shards (runtime/param_offload.py _put_leaves/_writeback_shards
            # — the reference's per-dp-rank partition swap); the executor
            # gates the combinations it cannot honour per-process
            if config.parallel.pipeline_parallel_size > 1:
                raise NotImplementedError(
                    "offload_param + pipeline parallelism is not supported "
                    "(the segmented step IS a pipeline over layer blocks)")
            # these gates must read the CONFIG (the engine only sets the
            # model-config flags later, after the executor is built).
            # progressive_layer_drop composes: the executor's block
            # programs take the block's global base layer index + theta
            # and apply the SAME pld_gate as the resident scan
            de = config.data_efficiency
            if (de.enabled and isinstance(de.data_routing, dict)
                    and de.data_routing.get("random_ltd", {}).get("enabled")):
                raise NotImplementedError(
                    "offload_param + random_ltd is not supported")
            ct = config.compression_training
            # weight/activation quantization COMPOSE (the block programs
            # apply the same transform with per-layer scales; boundaries
            # rebuild via set_compression). Pruning and the MoQ eigenvalue
            # schedule cannot:
            if any((ct.sparse_pruning, ct.row_pruning, ct.head_pruning,
                    ct.channel_pruning)):
                raise NotImplementedError(
                    "offload_param + pruning compression is not supported "
                    "(magnitude thresholds couple across the full layer "
                    "stack, which a streamed block cannot reproduce)")
            wq_sp = ((ct.weight_quantization or {})
                     .get("shared_parameters", {}))
            if wq_sp.get("eigenvalue", {}).get("enabled"):
                raise NotImplementedError(
                    "offload_param + MoQ eigenvalue scheduling is not "
                    "supported (the HVP power iteration needs resident "
                    "params)")
        if (config.zero_optimization.offload_optimizer.device == "cpu"
                and jax.default_backend() not in ("tpu", "gpu")):
            raise ValueError(
                "offload_optimizer.device='cpu' needs an accelerator backend "
                "with host memory kinds (XLA CPU cannot lower host-pinned "
                "jit operands)")
        pp = config.parallel.pipeline_parallel_size
        if pp > 1 and config.zero_optimization.stage >= 2:
            # same constraint as the reference (pipe/engine.py:56): pipeline
            # composes with ZeRO-1 (sharded optimizer states) but not with
            # sharded grads/params across the data axis
            raise ValueError("pipeline parallelism supports ZeRO stage <= 1 "
                             f"(got stage {config.zero_optimization.stage})")
        if pp > 1 and not model.pipelined:
            from ..parallel.pipeline import pipelinize_model

            model = pipelinize_model(model, pp)
        self.model = model
        self.mesh = mesh if mesh is not None else mesh_mod.build_mesh(config.parallel)
        mesh_mod.set_mesh(self.mesh)
        from ..parallel.ring import set_ring_attention

        ring = config.parallel.sequence_parallel_impl == "ring"
        if ring and config.parallel.pipeline_parallel_size > 1:
            raise ValueError(
                "sequence_parallel_impl='ring' does not compose with "
                "pipeline parallelism yet (nested manual shard_maps); use "
                "'ulysses'")
        if (ring and model.config is not None
                and getattr(model.config, "attention_impl", None) is not None):
            raise ValueError(
                "sequence_parallel_impl='ring' replaces the attention "
                "implementation — it cannot be combined with a custom "
                "attention_impl (the ring setting would be silently dropped)")
        set_ring_attention(ring)
        # SP ranks share the batch (tokens are sharded, not samples) — only
        # the (expert x data) axes multiply the batch (reference Ulysses
        # semantics; total dp subdivides into expert groups)
        dp_world = mesh_mod.get_data_parallel_world_size(self.mesh)
        self.config = config.resolve_batch_sizes(dp_world)
        self._dp_world = dp_world
        configure_comms_logger(self.config.comms_logger, world_size=dp_world)

        # precision
        self.compute_dtype = {"bfloat16": jnp.bfloat16, "float16": jnp.float16,
                              "float32": jnp.float32}[self.config.precision_dtype]
        self.loss_scaler = create_loss_scaler(
            fp16_enabled=self.config.fp16.enabled,
            dynamic=self.config.fp16.dynamic_loss_scale,
            static_scale=self.config.fp16.loss_scale or 1.0,
            initial_scale_power=self.config.fp16.initial_scale_power,
            scale_window=self.config.fp16.loss_scale_window,
            min_scale=self.config.fp16.min_loss_scale,
            hysteresis=self.config.fp16.hysteresis)

        # lr schedule + optimizer
        self.lr_scheduler = lr_scheduler
        if self.lr_scheduler is None and self.config.scheduler is not None:
            self.lr_scheduler = build_lr_schedule(self.config.scheduler.type,
                                                  self.config.scheduler.params)
        self.optimizer = optimizer if optimizer is not None else build_optimizer(
            self.config, self.lr_scheduler)

        # ---- sharded state construction (zero.Init equivalent) ----------
        rng = rng if rng is not None else jax.random.PRNGKey(self.config.seed)
        param_shapes = jax.eval_shape(model.init, rng)
        ep = self.config.parallel.expert_parallel_size
        if ep > 1:
            # experts shard over the dedicated 'expert' mesh axis; each expert
            # is replicated across its 'data'-axis ranks — the reference's
            # expert + expert-data group structure (groups.py:108/156), ep<=dp
            n_experts = getattr(model.config, "moe_num_experts", 0) if model.config else 0
            if n_experts and n_experts % ep != 0:
                raise ValueError(
                    f"moe_num_experts={n_experts} must be divisible by "
                    f"expert_parallel_size={ep}")
        self._fsdp_min_size = (
            self.config.zero_optimization.stage3_param_persistence_threshold
            if self.config.zero_stage >= 3 else 2 ** 11)
        self.plan: ZeroShardingPlan = build_sharding_plan(
            self.config.zero_stage, param_shapes, model.axes,
            expert_parallel=ep > 1, fsdp_min_size=self._fsdp_min_size)
        self.param_shardings = as_named(self.plan.param_specs, self.mesh)
        logger.info(describe_plan(self.plan, jax.tree.leaves(param_shapes)
                                  and param_shapes or {}))

        def _init_cast(key):
            return cast_floating(model.init(key), self.compute_dtype)

        self._param_offload = None
        if self._param_offload_tier != "none":
            # the executor owns materialisation: init must never hold the
            # full tree in HBM (the point is params > HBM) — on accelerators
            # it inits on device and streams each block to pinned host; on
            # the CPU backend (tests) a plain jit is already host-resident
            from .param_offload import ParamOffloadExecutor

            self._param_offload = ParamOffloadExecutor(
                model, self.mesh, self.plan, self.config,
                lr_schedule=self.optimizer.lr_schedule,
                init_fn=_init_cast, rng=rng,
                compute_dtype=self.compute_dtype,
                loss_scaler=(self.loss_scaler if self.fp16_enabled()
                             else None))
            self._n_params = self._param_offload.n_params
            self.params = None
        else:
            with mesh_mod.ambient(self.mesh):
                self.params = jax.jit(_init_cast,
                                      out_shardings=self.param_shardings)(rng)

        # optimizer + scaler state, sharded per plan (NVMe offload: the state
        # lives in swap files instead — nothing is materialised in HBM)
        self._nvme_swapper = None
        if self._nvme_offload:
            from .swap import NVMeOptimizerSwapper

            off_cfg = self.config.zero_optimization.offload_optimizer
            opt_params = dict(self.config.optimizer.params)
            self._nvme_swapper = NVMeOptimizerSwapper(
                swap_dir=os.path.join(
                    off_cfg.nvme_path,
                    f"dstpu_swap_p{jax.process_index()}"),
                lr=float(opt_params.get("lr", 1e-3)),
                betas=tuple(opt_params.get("betas", (0.9, 0.999))),
                eps=float(opt_params.get("eps", 1e-8)),
                weight_decay=float(opt_params.get("weight_decay", 0.0)),
                adam_w_mode=opt_params.get(
                    "adam_w_mode", self.config.optimizer.type.lower() != "adam"),
                sub_group_bytes=
                    self.config.zero_optimization.sub_group_size * 12,
                aio_config={"block_size": self.config.aio.block_size,
                            "queue_depth": self.config.aio.queue_depth,
                            "thread_count": self.config.aio.thread_count})
            self._nvme_swapper.init_from_params(
                self.params,
                grad_shardings=as_named(self.plan.grad_specs, self.mesh))
            self.opt_state = None
        elif self._param_offload is not None:
            self.opt_state = None     # the executor owns all optimizer state
        else:
            master_shardings_tree = self._opt_state_shardings()
            with mesh_mod.ambient(self.mesh):
                self.opt_state = jax.jit(
                    self.optimizer.init,
                    out_shardings=master_shardings_tree)(self.params)
        self.scaler_state: LossScaleState = self.loss_scaler.init()

        # 1-bit compression state: per-rank worker residual + per-chunk
        # server residual (reference OnebitAdam error-feedback buffers)
        self._comp_state = None
        if self._onebit:
            n_total = sum(int(p.size) for p in jax.tree.leaves(self.params))
            npad = n_total + ((-n_total) % dp_world)
            with mesh_mod.ambient(self.mesh):
                self._comp_state = {
                    "worker": jax.device_put(
                        jnp.zeros((dp_world, npad), jnp.float32),
                        NamedSharding(self.mesh, P(mesh_mod.DATA_AXIS, None))),
                    "server": jax.device_put(
                        jnp.zeros((npad,), jnp.float32),
                        NamedSharding(self.mesh, P(mesh_mod.DATA_AXIS))),
                }

        # dataloader
        self.training_dataloader = None
        if training_data is not None:
            # each process loads its share of the global batch; single-host
            # that is the whole thing (multi-host assembly: _globalize_batch)
            per_process = (self.train_micro_batch_size_per_gpu() * dp_world
                           // jax.process_count())
            self.training_dataloader = DeepSpeedDataLoader(
                training_data, batch_size=per_process,
                collate_fn=collate_fn, seed=self.config.seed)

        # curriculum learning (reference engine.py:1653 seqlen curriculum)
        self._curriculum = None
        if self.config.curriculum_learning.enabled:
            if self.config.curriculum_learning.curriculum_type != "seqlen":
                raise NotImplementedError(
                    "only curriculum_type='seqlen' is implemented (the "
                    "reference's primary mode); difficulty-indexed data "
                    "selection is runtime/data_pipeline.CurriculumDataSampler")
            from .data_pipeline import CurriculumScheduler

            cl = self.config.curriculum_learning
            self._curriculum = CurriculumScheduler({
                "min_difficulty": cl.min_difficulty,
                "max_difficulty": cl.max_difficulty,
                "schedule_type": cl.schedule_type,
                "schedule_config": dict(cl.schedule_config)})

        # dropout: the config carries the rate; only the TRAIN engine turns
        # it on (inference/eval run the deterministic model)
        if (self.model.config is not None
                and getattr(self.model.config, "dropout", 0.0) > 0.0):
            self.model.config.dropout_enabled = True

        # progressive layer drop (reference engine.py:283 / :1648 theta kwarg)
        self._pld = None
        if self.config.progressive_layer_drop.enabled:
            if self.model.pipelined:
                raise NotImplementedError(
                    "progressive_layer_drop with pipeline parallelism is "
                    "not supported yet")
            if self._onebit:
                raise NotImplementedError(
                    "progressive_layer_drop with 1-bit optimizers is not "
                    "supported (the compressed step's batch specs assume "
                    "token-shaped leaves)")
            if self.model.config is None:
                raise NotImplementedError(
                    "progressive_layer_drop needs a transformer Model (the "
                    "layer scan applies the stochastic depth gate)")
            from .progressive_layer_drop import ProgressiveLayerDrop

            pld_cfg = self.config.progressive_layer_drop
            self._pld = ProgressiveLayerDrop(theta=pld_cfg.theta,
                                             gamma=pld_cfg.gamma)
            self.model.config.pld_enabled = True

        # random-LTD (reference data_pipeline/data_routing/basic_layer.py:14 +
        # scheduler.py:38): listed layers run on a scheduled random token
        # subset. The kept count is shape-affecting, so train_batch
        # re-specialises the step at schedule boundaries.
        self._random_ltd = None
        de_cfg = self.config.data_efficiency
        ltd_cfg = (de_cfg.data_routing.get("random_ltd", {})
                   if de_cfg.enabled and isinstance(de_cfg.data_routing, dict)
                   else {})
        if ltd_cfg.get("enabled"):
            if self.model.pipelined or self.model.config is None:
                raise NotImplementedError(
                    "random_ltd needs a non-pipelined transformer Model "
                    "(the layer scan applies the token gather/scatter)")
            if self._onebit:
                raise NotImplementedError(
                    "random_ltd with 1-bit optimizers is not supported")
            from .data_pipeline import RandomLTDScheduler

            self._random_ltd = RandomLTDScheduler(
                ltd_cfg.get("random_ltd_schedule", ltd_cfg))
            n_layers = self.model.config.num_layers
            layer_ids = ltd_cfg.get("random_ltd_layer_id")
            if layer_ids is None:
                # default: all but the first and last layer (the reference's
                # usual config); degenerate depths keep at least one layer
                layer_ids = (range(1, n_layers - 1) if n_layers > 2
                             else range(n_layers - 1, n_layers))
            self.model.config.ltd_enabled = True
            self.model.config.ltd_layers = tuple(int(i) for i in layer_ids)

        # compression (reference compress.py:95 init_compression + scheduler)
        self._compression_plan = None
        self._compression_active = frozenset()
        comp_cfg = {k: v for k, v in {
            "weight_quantization": self.config.compression_training.weight_quantization,
            "activation_quantization": self.config.compression_training.activation_quantization,
            "sparse_pruning": self.config.compression_training.sparse_pruning,
            "row_pruning": self.config.compression_training.row_pruning,
            "head_pruning": self.config.compression_training.head_pruning,
            "channel_pruning": self.config.compression_training.channel_pruning,
        }.items() if v}
        if comp_cfg:
            from ..compression import CompressionScheduler, init_compression

            if self.model.pipelined:
                raise NotImplementedError(
                    "compression_training with pipeline parallelism is not "
                    "supported yet")
            self._compression_plan = init_compression(comp_cfg)
            self._compression_sched = CompressionScheduler(self._compression_plan)
            self._compression_active = self._compression_sched.active_methods(0)
            if "activation_quantization" in self._compression_plan.methods:
                if self.model.config is None:
                    raise NotImplementedError(
                        "activation_quantization needs a transformer Model "
                        "(the quantizer sits on layer inputs inside the "
                        "scan; a config-less Model has no hook point)")
                # schedule_offset=0: active from the very first step — the
                # boundary check below only fires on CHANGES
                self._apply_act_quant(self._compression_active)
            if self._param_offload is not None and self._compression_active:
                self._param_offload.set_compression(
                    self._compression_plan, self._compression_active)
        # MoQ: eigenvalue-driven per-layer quantization bits (reference
        # engine.py:1479 block_eigenvalue -> quantizer.different_precision)
        self._moq_eigenvalue = None
        wq_raw = (self.config.compression_training.weight_quantization
                  or {}) if self._compression_plan is not None else {}
        ev_cfg = (wq_raw.get("shared_parameters", {}) or {}).get(
            "eigenvalue", {})
        if ev_cfg.get("enabled"):
            if self.model.config is None or self.model.pipelined:
                raise NotImplementedError(
                    "MoQ eigenvalue scheduling needs a non-pipelined "
                    "transformer Model (per-layer blocks come from the "
                    "stacked layer tree)")
            from .eigenvalue import Eigenvalue

            self._moq_eigenvalue = Eigenvalue(
                verbose=ev_cfg.get("verbose", False),
                max_iter=int(ev_cfg.get("max_iter", 10)),
                tol=float(ev_cfg.get("tol", 1e-2)),
                stability=float(ev_cfg.get("stability", 1e-6)))
            self._moq_eval_step = int(ev_cfg.get("eval_step", 100))
            # MoQ ramp length: an average-sensitivity layer walks
            # start_bits -> target_bits over this many steps (independent of
            # schedule_offset_end, which DEACTIVATES the method entirely)
            self._moq_ramp = int(ev_cfg.get("ramp_steps",
                                            10 * self._moq_eval_step))
            self._moq_rng = jax.random.PRNGKey(self.config.seed + 101)

        # bookkeeping
        self.global_steps = 0
        self.micro_steps = 0
        self._skipped_steps = 0
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size(), start_step=0,
            steps_per_output=self.steps_per_print())
        self._skipped_accum = None
        self._steps_since_sync = 0
        self._tput_window_start = None
        self._staged_grads = None
        self._staged_count = 0
        self._compiled_step = None
        self._compiled_micro = None
        self._eval_step = None
        self._last_lr = float(self.config.optimizer.params.get("lr", 0.0))
        self._monitor = None
        self._profiling = False
        self._profile_span = None

        # numerics sentinel: fused into the jitted train step the engine
        # builds itself; the host-driven executors (param offload, NVMe
        # swap) and the compressed-comm step run their update outside that
        # program, so the sentinel is disabled (loudly) there
        self._numerics = self._obs.numerics
        self._numerics_state = None
        if self._numerics is not None and (
                self._param_offload is not None
                or self._nvme_swapper is not None or self._onebit):
            logger.warning(
                "observability.numerics_sentinel is not supported with "
                "offload_param / NVMe offload / 1-bit optimizers (the "
                "update runs outside the single jitted step) — disabling")
            self._numerics = None
        if self._numerics is not None:
            # session close force-checks the device flags so a trip in the
            # final (step % check_steps) window is still reported; weakref
            # so the sentinel never pins a replaced engine
            import weakref

            wself = weakref.ref(self)

            def _flush_numerics():
                eng = wself()
                if eng is not None:
                    eng.check_numerics(force=True)

            self._numerics.attach_flush(_flush_numerics)

        if self._obs.goodput is not None:
            self._wire_goodput()
        if self._obs.fleet is not None:
            self._wire_fleet_health()

        n = (self._n_params if self.params is None
             else param_count(self.params))
        log_dist(f"engine ready: {n / 1e6:.1f}M params, zero_stage={self.config.zero_stage}, "
                 f"dtype={self.config.precision_dtype}, mesh={dict(self.mesh.shape)}, "
                 f"micro_batch={self.train_micro_batch_size_per_gpu()}, "
                 f"gas={self.gradient_accumulation_steps()}")

    # -- config accessors (reference engine.py:456-819) -------------------
    def train_batch_size(self) -> int:
        return self.config.train_batch_size

    def train_micro_batch_size_per_gpu(self) -> int:
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self) -> int:
        return self.config.gradient_accumulation_steps

    def steps_per_print(self) -> int:
        return self.config.steps_per_print

    def zero_optimization_stage(self) -> int:
        return self.config.zero_stage

    def gradient_clipping(self) -> float:
        return self.config.gradient_clipping

    def fp16_enabled(self) -> bool:
        return self.config.fp16.enabled

    def bfloat16_enabled(self) -> bool:
        return self.config.bf16.enabled

    def wall_clock_breakdown(self) -> bool:
        return self.config.wall_clock_breakdown

    def get_lr(self):
        """Current learning rate. Host-side when a scheduler exists; otherwise
        evaluates the optimizer's schedule at the current step (a tiny device
        computation — fine at user-call cadence)."""
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "get_last_lr"):
            return self.lr_scheduler.get_last_lr()
        try:
            return [float(self.optimizer.lr_schedule(self.global_steps))]
        except Exception:
            return [self._last_lr]

    def get_global_step(self) -> int:
        return self.global_steps

    @property
    def cur_scale(self) -> float:
        return float(self.scaler_state.scale)

    @property
    def skipped_steps(self) -> int:
        """Total overflow-skipped steps. Reading drains the pending device
        counter (a sync) — steady-state code paths never read it."""
        if self._skipped_accum is not None:
            self._skipped_steps += int(self._skipped_accum)
            self._skipped_accum = None
        return self._skipped_steps

    @skipped_steps.setter
    def skipped_steps(self, value: int) -> None:
        self._skipped_steps = value
        self._skipped_accum = None

    # -- sharding helpers -------------------------------------------------
    def _opt_state_shardings(self):
        state_shapes = jax.eval_shape(self.optimizer.init, self.params)
        specs = optimizer_state_specs(state_shapes, self.params, self.plan.master_specs)
        shardings = jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                                 is_leaf=lambda x: isinstance(x, P))
        if self.config.zero_optimization.offload_optimizer.device == "cpu":
            # ZeRO-Offload tier 1 (reference stage_1_and_2.py:1021 cpu_offload,
            # cpu_adam): master weights + moments live in pinned host memory —
            # the jitted step streams them over PCIe, XLA overlapping the
            # transfers with compute (docs/offload_design.md)
            shardings = jax.tree.map(
                lambda s: s.with_memory_kind("pinned_host"), shardings)
        return shardings

    def _batch_sharding(self, batch: Any, leading_gas: bool) -> Any:
        sp = int(self.mesh.shape[mesh_mod.SEQ_AXIS])

        def spec(x):
            nd = np.ndim(x)
            axes: list = [None] * nd
            pos = 1 if leading_gas else 0
            if nd > pos:
                axes[pos] = mesh_mod.DATA_SHARD
            # token dim sharded over 'seq' when SP is on and divisible
            if sp > 1 and nd > pos + 1 and np.shape(x)[pos + 1] % sp == 0:
                axes[pos + 1] = mesh_mod.SEQ_AXIS
            return NamedSharding(self.mesh, P(*axes))

        return jax.tree.map(spec, batch)

    def _globalize_batch(self, batch: Any, leading_gas: bool) -> Any:
        """Host-local batch → global sharded arrays. Single-host: plain
        device_put. Multi-host: every process holds only ITS slice of the
        global batch (the dataloader yields per-process shares), assembled
        with make_array_from_process_local_data (round-1 advisory: device_put
        of a local slice onto a global sharding needs the global array)."""
        shardings = self._batch_sharding(batch, leading_gas)
        if jax.process_count() == 1:
            return jax.device_put(batch, shardings)
        return jax.tree.map(
            lambda x, s: jax.make_array_from_process_local_data(
                s, np.asarray(x)), batch, shardings)

    def _build_onebit_train_step(self) -> Callable:
        """Train step with compressed-gradient data-parallel comm (reference
        OnebitAdam/ZeroOneAdam: dense warmup for ``freeze_step`` steps, then
        error-feedback int8 two-phase allreduce — comm/compressed.py)."""
        optimizer = self.optimizer
        loss_scaler = self.loss_scaler
        model = self.model
        gas = self.gradient_accumulation_steps()
        fp16 = self.fp16_enabled()
        W = self._dp_world
        freeze = int(self.config.optimizer.params.get("freeze_step", 100))
        # 0/1 Adam (reference zoadam.py): DENSE allreduce on the exponential
        # variance-update schedule, compressed on all other steps
        is_zoadam = self.config.optimizer.type.lower() == "zerooneadam"
        zo_scaler = int(self.config.optimizer.params.get(
            "var_update_scaler", 16))
        zo_freeze = int(self.config.optimizer.params.get(
            "var_freeze_step", 100000))
        mesh = self.mesh
        from ..comm.compressed import (compressed_allreduce_flat,
                                       tree_flatten_pad, tree_unflatten_like)

        def micro_loss(params, mb, scale):
            loss = model.loss_fn(params, mb)
            return loss * scale / gas, loss

        grad_fn = jax.value_and_grad(micro_loss, has_aux=True)

        def data_body(params, batch, scale, worker_res, server_res, count):
            worker = worker_res[0]                  # (npad,) this rank

            def one_micro(carry, mb):
                (_, loss), grads = grad_fn(params, mb, scale)
                return jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                    carry, grads), loss

            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                params)
            if gas == 1:
                grads, losses = one_micro(zero, jax.tree.map(lambda x: x[0],
                                                             batch))
                losses = losses[None]
            else:
                grads, losses = jax.lax.scan(one_micro, zero, batch)

            flat, _, _ = tree_flatten_pad(grads, W)

            def dense():
                return (jax.lax.pmean(flat, mesh_mod.DATA_AXIS), worker,
                        server_res)

            def compressed():
                return compressed_allreduce_flat(flat, worker, server_res,
                                                 mesh_mod.DATA_AXIS)

            use_dense = count < freeze
            if is_zoadam:
                from .optimizer import zero_one_var_step

                use_dense = use_dense | zero_one_var_step(
                    count, zo_scaler, zo_freeze)
            flat_avg, w2, s2 = jax.lax.cond(use_dense, dense, compressed)
            grads_avg = tree_unflatten_like(flat_avg, grads)
            loss_avg = jax.lax.pmean(jnp.mean(losses.astype(jnp.float32)),
                                     mesh_mod.DATA_AXIS)
            return grads_avg, loss_avg, w2[None], s2

        def train_step(params, opt_state, scaler_state, comp_state, batch):
            scale = scaler_state.scale if fp16 else jnp.float32(1.0)
            batch_specs = jax.tree.map(
                lambda x: P(None, mesh_mod.DATA_AXIS), batch)
            body = shard_map(
                data_body, mesh=mesh,
                in_specs=(P(), batch_specs, P(), P(mesh_mod.DATA_AXIS, None),
                          P(mesh_mod.DATA_AXIS), P()),
                out_specs=(P(), P(), P(mesh_mod.DATA_AXIS, None),
                           P(mesh_mod.DATA_AXIS)),
                check_vma=False, axis_names={mesh_mod.DATA_AXIS})
            grads, mean_loss, w2, s2 = body(params, batch, scale,
                                            comp_state["worker"],
                                            comp_state["server"],
                                            opt_state.count)
            if fp16:
                inv = 1.0 / scale
                grads = jax.tree.map(lambda g: g * inv, grads)
                overflow = has_overflow(grads)
            else:
                overflow = jnp.asarray(False)
            new_params, new_opt_state, stats = optimizer.apply(
                params, grads, opt_state, skip_update=overflow)
            new_scaler = loss_scaler.update(scaler_state, overflow)
            new_comp = {"worker": w2, "server": s2}
            return (new_params, new_opt_state, new_scaler, new_comp,
                    mean_loss, stats)

        opt_shardings = self._opt_state_shardings()
        comp_shardings = {
            "worker": NamedSharding(self.mesh, P(mesh_mod.DATA_AXIS, None)),
            "server": NamedSharding(self.mesh, P(mesh_mod.DATA_AXIS)),
        }
        return jax.jit(
            train_step,
            in_shardings=(self.param_shardings, opt_shardings, None,
                          comp_shardings, None),
            out_shardings=(self.param_shardings, opt_shardings, None,
                           comp_shardings, None, None),
            donate_argnums=(0, 1, 3))

    # -- the jitted step --------------------------------------------------
    def _build_train_step(self) -> Callable:
        optimizer = self.optimizer
        loss_scaler = self.loss_scaler
        model = self.model
        gas = self.gradient_accumulation_steps()
        grad_specs = self.plan.grad_specs
        fp16 = self.fp16_enabled()

        offload = self.config.zero_optimization.offload_optimizer.device == "cpu"
        if offload:
            # ZeRO-Offload: master+moments stay pinned_host (see
            # _opt_state_shardings); the update itself runs host-side via
            # compute_on — grads/params stream D2H, updated params H2D, and
            # device HBM never holds the fp32 optimizer state (the reference's
            # cpu_adam path, with XLA scheduling the PCIe transfers)
            from jax.experimental.compute_on import compute_on

            host = lambda ns: ns.with_memory_kind("pinned_host")
            grad_host_sh = jax.tree.map(host, as_named(grad_specs, self.mesh))
            param_host_sh = jax.tree.map(host, self.param_shardings)
            scalar_host = NamedSharding(self.mesh, P(),
                                        memory_kind="pinned_host")
            host_apply = compute_on("device_host")(jax.jit(
                lambda p, g, st, sk: optimizer.apply(p, g, st, skip_update=sk)))

            def apply_update(params, grads, opt_state, skip):
                grads_h = jax.tree.map(jax.device_put, grads, grad_host_sh)
                params_h = jax.tree.map(jax.device_put, params, param_host_sh)
                skip_h = jax.device_put(skip, scalar_host)
                new_p_h, new_state, stats = host_apply(params_h, grads_h,
                                                       opt_state, skip_h)
                new_params = jax.tree.map(jax.device_put, new_p_h,
                                          self.param_shardings)
                # scalars computed host-side come back to device memory so
                # the step outputs have a uniform layout
                dev_scalar = NamedSharding(self.mesh, P())
                stats = jax.tree.map(
                    lambda x: jax.device_put(x, dev_scalar), stats)
                return new_params, new_state, stats
        else:
            def apply_update(params, grads, opt_state, skip):
                return optimizer.apply(params, grads, opt_state,
                                       skip_update=skip)

        pipelined = model.pipelined
        sentinel = self._numerics

        # QAT straight-through: compression transform inside the
        # differentiation path; the step is rebuilt when the scheduler's
        # active-method set changes (one recompile per boundary)
        base_loss_fn = self._compression_wrap(model.loss_fn)

        def micro_loss(params, mb, scale):
            loss = base_loss_fn(params, mb)
            return loss * scale / gas, loss

        grad_fn = jax.value_and_grad(micro_loss, has_aux=True)

        # pipelined models provide the explicit 1F1B executor (O(P) activation
        # residency); fall back to autodiff of the stacked loss otherwise
        pipe_grad_fn = model.grad_fn
        if pipelined and pipe_grad_fn is None:
            def pipe_grad_fn(params, batch, scale):
                def pipe_loss(p, b):
                    return model.loss_fn(p, b) * scale

                loss_scaled, grads = jax.value_and_grad(pipe_loss)(params, batch)
                return loss_scaled / scale, grads

        def train_step(params, opt_state, scaler_state, num_state, batch):
            scale = scaler_state.scale if fp16 else jnp.float32(1.0)

            def one_micro(carry, mb):
                grads_acc = carry
                (_, loss), grads = grad_fn(params, mb, scale)
                grads = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                     grads_acc, grads)
                return grads, loss

            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if pipelined:
                loss, grads = pipe_grad_fn(params, batch, scale)
                grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
                losses = loss[None]
            elif gas == 1:
                squeeze = jax.tree.map(lambda x: x[0], batch)
                grads, losses = one_micro(zero_grads, squeeze)
                losses = losses[None]
            else:
                grads, losses = jax.lax.scan(one_micro, zero_grads, batch)

            # ZeRO-2/3: constrain grads onto the data axis => reduce-scatter
            grads = jax.lax.with_sharding_constraint(
                grads, as_named(grad_specs, mesh_mod.get_mesh()))

            if fp16:
                inv = 1.0 / scale
                grads = jax.tree.map(lambda g: g * inv, grads)
                overflow = has_overflow(grads)
            else:
                overflow = jnp.asarray(False)
            # gradient_predivide_factor: in the reference's default postscale
            # path the bucket divides by predivide before the sum and
            # multiplies by predivide/world after (allreduce_bucket,
            # engine.py:2152) — net effect on the mean is NONE; under
            # prescale_gradients the factor is ignored. Our grads are already
            # exact means, so both modes are no-ops here; the knobs stay for
            # config compatibility. (Round-1 advisory: we wrongly divided by
            # predivide under prescale, changing the effective grad scale.)

            mean_loss = jnp.mean(losses.astype(jnp.float32))
            skip = overflow
            new_num_state = num_state
            if sentinel is not None:
                # fused in-program check on values the step already holds:
                # loss mean + unscaled accumulated grads. No extra program,
                # no host sync, no collective kinds beyond the step's own
                # (the isfinite reductions partition like the loss mean).
                # An fp16 scaler overflow suppresses the nonfinite-grads
                # bit: periodic inf grads are the DynamicLossScaler's
                # expected backoff signal, not a numerics fault.
                new_num_state, tripped = sentinel.observe(
                    num_state, mean_loss, grads,
                    suppress_grads=overflow if fp16 else None)
                if sentinel.skip_in_step:
                    # action='skip_step': a poisoned update never lands —
                    # ride the overflow-skip path on device
                    skip = skip | tripped
            new_params, new_opt_state, stats = apply_update(
                params, grads, opt_state, skip)
            new_scaler = loss_scaler.update(scaler_state, overflow)
            return (new_params, new_opt_state, new_scaler, new_num_state,
                    mean_loss, stats)

        opt_shardings = self._opt_state_shardings()
        return jax.jit(
            train_step,
            in_shardings=(self.param_shardings, opt_shardings, None, None,
                          None),
            out_shardings=(self.param_shardings, opt_shardings, None, None,
                           None, None),
            donate_argnums=(0, 1))

    def _build_nvme_grads_step(self) -> Callable:
        """Device half of the NVMe-offload step: loss + accumulated grads +
        global grad norm; the optimizer update runs host-side in the swapper
        (reference PipelinedOptimizerSwapper + cpu_adam split)."""
        from .optimizer import _global_norm

        model, gas = self.model, self.gradient_accumulation_steps()
        grad_specs = self.plan.grad_specs

        def grads_step(params, batch):
            def one_micro(carry, mb):
                grads_acc = carry
                loss, grads = jax.value_and_grad(model.loss_fn)(params, mb)
                grads = jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                                     grads_acc, grads)
                return grads, loss

            zero_grads = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if gas == 1:
                grads, losses = one_micro(zero_grads,
                                          jax.tree.map(lambda x: x[0], batch))
                losses = losses[None]
            else:
                grads, losses = jax.lax.scan(one_micro, zero_grads, batch)
            grads = jax.tree.map(lambda g: g / gas, grads)
            grads = jax.lax.with_sharding_constraint(
                grads, as_named(grad_specs, mesh_mod.get_mesh()))
            return grads, jnp.mean(losses.astype(jnp.float32)), _global_norm(grads)

        return jax.jit(grads_step, in_shardings=(self.param_shardings, None))

    # -- public train API -------------------------------------------------
    def train_batch(self, data_iter: Optional[Iterable] = None,
                    batch: Optional[Any] = None) -> jax.Array:
        """Run one full training step (gas microbatches) — analog of
        PipelineEngine.train_batch / the reference train loop of
        forward+backward+step over GAS microbatches."""
        gas = self.gradient_accumulation_steps()
        if batch is None:
            source = data_iter if data_iter is not None else self.training_dataloader
            if source is None:
                raise ValueError("no data: pass batch=, data_iter=, or training_data")
            it = iter(source) if not hasattr(source, "__next__") else source
            micros = [next(it) for _ in range(gas)]
            batch = jax.tree.map(lambda *xs: jnp.stack(xs), *micros)
        else:
            leading = jax.tree.leaves(batch)[0].shape[0]
            if leading != gas:
                raise ValueError(
                    f"batch leading dim {leading} != gradient_accumulation_steps {gas}; "
                    f"shape must be (gas, micro_batch*dp, ...)")

        if self._pld is not None:
            # theta decays per step; a traced scalar input, so no recompiles
            theta = self._pld.update_state(self.global_steps)
            batch = dict(batch)
            batch["pld_theta"] = jnp.full((gas,), theta, jnp.float32)
        if self._curriculum is not None:
            # seqlen curriculum: truncate the token dim to the current
            # difficulty (reference engine.py:1653); each distinct length is
            # one extra jit trace, bounded by the schedule's quantisation
            diff = self._curriculum.update_difficulty(self.global_steps)
            batch = jax.tree.map(
                lambda x: x[:, :, :diff] if np.ndim(x) == 3 else x, batch)
        if self._random_ltd is not None:
            # kept-token count is shape-affecting → re-specialise the step at
            # schedule boundaries (bounded by the schedule's quantisation)
            seq_len = int(jax.tree.leaves(batch)[0].shape[-1])
            keep = min(self._random_ltd.get_seq_len(self.global_steps), seq_len)
            if keep != self.model.config.ltd_keep:
                self.model.config.ltd_keep = keep
                self._compiled_step = None
        if self._compression_plan is not None:
            act = self._compression_sched.active_methods(self.global_steps)
            if act != self._compression_active:
                self._compression_active = act
                self._compiled_step = None    # re-specialise at the boundary
                self._eval_step = None        # eval sees the same boundary
                self._apply_act_quant(act)
                if self._param_offload is not None:
                    # streamed analog of the re-specialisation: rebuild the
                    # segment programs with the new active set (also picks
                    # up the act_quant_bits config change at retrace)
                    self._param_offload.set_compression(
                        self._compression_plan, act)
            if (self._moq_eigenvalue is not None
                    and "weight_quantization" in act
                    and self.global_steps % self._moq_eval_step == 0):
                self._update_moq_bits(batch)

        if self._compiled_step is None and self._param_offload is None:
            self._compiled_step = (
                self._build_nvme_grads_step() if self._nvme_swapper is not None
                else self._build_onebit_train_step() if self._onebit
                else self._build_train_step())
            self._register_step_audit(batch)

        # Steady-state path is SYNC-FREE: no host<->device scalar fetches per
        # step (each one drains the TPU queue).
        # Device-side counters accumulate lazily; materialised only at
        # steps_per_print boundaries (reference logs at the same cadence).
        breakdown = self.wall_clock_breakdown()
        if breakdown:
            self.timers(TRAIN_BATCH_TIMER).start(synchronize=True)
        obs = self._obs
        if obs.enabled:
            # batch bytes about to cross host->device (metadata read only)
            obs.registry.counter(
                "comm/host_to_device/bytes",
                help="training batch bytes transferred to device").inc(
                    sum(int(getattr(x, "nbytes", 0))
                        for x in jax.tree.leaves(batch)))
        _batch_span = obs.span("train_batch", cpu=True,
                               step=self.global_steps)
        if _batch_span.recording:
            _batch_span.annotate(**hbm_counts())
        _batch_span.begin()
        try:
            with mesh_mod.ambient(self.mesh):
                with obs.span("train_batch/h2d"):
                    batch = self._globalize_batch(batch, leading_gas=True)
                loss, stats = self._dispatch_train_step(batch)
        except Exception as e:
            # black-box dump before the exception unwinds: the ring, the
            # open-span stack and the per-thread stacks at THIS moment are
            # what a post-mortem needs (no-op without a flight recorder)
            obs.crash_dump("train_batch-exception", exc=e,
                           step=self.global_steps)
            raise
        finally:
            _batch_span.end()
        self.global_steps += 1
        self.micro_steps += gas
        self._skipped_accum = (stats.skipped.astype(jnp.int32)
                               if self._skipped_accum is None
                               else self._skipped_accum + stats.skipped)
        if obs.enabled:
            obs.note_step(self.global_steps)
            obs.maybe_record_memory(self.global_steps)
            if obs.profiler is not None:
                obs.profiler.on_step(self.global_steps)
        # cadence-gated flag materialisation (the sentinel's ONE host sync);
        # between cadence steps this is a single modulo. Raises NumericsTrip
        # under action='abort' — after dumping the bundle.
        self.check_numerics()
        if obs.fleet is not None:
            # lazy device scalars: materialised only on a cadence step,
            # inside the fleet gather (the documented cadence cost)
            obs.fleet.note_step(self.global_steps, loss=loss,
                                grad_norm=stats.grad_norm)
        if breakdown:
            self.timers(TRAIN_BATCH_TIMER).stop(synchronize=True)
            self.timers.log([TRAIN_BATCH_TIMER])
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "step"):
            self.lr_scheduler.step()
        if self.global_steps % self.steps_per_print() == 0:
            self._sync_step_stats(stats)
            log_dist(f"step={self.global_steps} loss={float(loss):.4f} "
                     f"lr={self._last_lr:.3e} grad_norm={float(stats.grad_norm):.3f} "
                     f"skipped={self.skipped_steps} "
                     f"throughput={self.tput_timer.avg_samples_per_sec():.1f} samples/s")
            self._publish_metrics(float(loss), float(stats.grad_norm))
        self._steps_since_sync += 1
        self._tput_window_start = self._tput_window_start or time.time()
        return loss

    def check_numerics(self, force: bool = False) -> None:
        """Materialise and act on the numerics sentinel's device flags —
        at ``numerics_check_steps`` cadence (train_batch calls this every
        step), or immediately with ``force=True`` (session close flushes
        the final window through here)."""
        if self._numerics is None or self._numerics_state is None:
            return
        try:
            cleared = self._numerics.maybe_check(
                self._numerics_state, self.global_steps, force=force)
        except Exception:
            # abort raises AFTER logging+bundling: clear the handled flags
            # before the exception escapes, or the close-time flush (and a
            # supervisor that catches-and-continues) re-reports the SAME
            # trip with a duplicate bundle
            self._numerics_state = self._numerics.cleared(
                self._numerics_state)
            raise
        if cleared is not None:
            self._numerics_state = cleared

    def _dispatch_train_step(self, batch: Any):
        """Route one globalized batch through whichever step executor this
        engine built (offload / NVMe / 1-bit / plain jit) — the body
        ``train_batch`` wraps in its span. Returns (loss, StepStats)."""
        with self._obs.span("train_batch/dispatch"):
            if self._param_offload is not None:
                # host-driven segmented step: params stream through HBM per
                # layer block (runtime/param_offload.py)
                loss, grad_norm, skipped = (
                    self._param_offload.train_step(batch))
                if self._param_offload.scaler_state is not None:
                    # the executor owns the fp16 scale across its deferred
                    # updates; mirror it for introspection/checkpointing
                    self.scaler_state = self._param_offload.scaler_state
                lr = float(self.optimizer.lr_schedule(self.global_steps))
                stats = StepStats(grad_norm=jnp.float32(grad_norm),
                                  skipped=jnp.asarray(skipped),
                                  lr=jnp.float32(lr))
            elif self._nvme_swapper is not None:
                # device: loss+grads; host: pipelined NVMe swap + Adam. The
                # grad-norm fetch is a host sync, but the swap loop is
                # host-driven anyway — no extra queue drain
                grads, loss, grad_norm = self._compiled_step(self.params, batch)
                clip = self.config.gradient_clipping
                scale = 1.0
                if clip and clip > 0:
                    scale = min(clip / (float(grad_norm) + 1e-6), 1.0)
                lr = float(self.optimizer.lr_schedule(self.global_steps))
                self._nvme_swapper.lr = lr
                self.params = self._nvme_swapper.step_update(
                    self.params, grads, grad_scale=scale)
                del grads
                stats = StepStats(grad_norm=grad_norm,
                                  skipped=jnp.asarray(False),
                                  lr=jnp.float32(lr))
            elif self._onebit:
                (self.params, self.opt_state, self.scaler_state,
                 self._comp_state, loss, stats) = self._compiled_step(
                    self.params, self.opt_state, self.scaler_state,
                    self._comp_state, batch)
            else:
                if self._numerics is not None and self._numerics_state is None:
                    self._numerics_state = self._numerics.init_state()
                (self.params, self.opt_state, self.scaler_state,
                 self._numerics_state, loss, stats) = self._compiled_step(
                    self.params, self.opt_state, self.scaler_state,
                    self._numerics_state, batch)
        return loss, stats

    def _compression_wrap(self, fn):
        """Wrap a loss fn with the ACTIVE compression transform (QAT
        straight-through). The single site both the train-step builder and
        eval_loss use — so train and eval can never diverge on which
        methods apply; callers re-jit at schedule boundaries."""
        if self._compression_plan is None or not self._compression_active:
            return fn
        from ..compression import apply_compression

        plan, active = self._compression_plan, self._compression_active
        return lambda p, b: fn(
            apply_compression(p, plan, active,
                              handled_elsewhere=frozenset(
                                  {"activation_quantization"})), b)

    def _apply_act_quant(self, active) -> None:
        """Activation QAT toggles through the model config (the quantizer
        sits on layer INPUTS inside the scan; one re-jit per boundary)."""
        if self.model.config is None:
            return
        aq = 0
        if "activation_quantization" in active:
            p = self._compression_plan.methods[
                "activation_quantization"]["params"]
            aq = int(p.get("bits", p.get("target_bits", 8)))
        self.model.config.act_quant_bits = aq

    def _update_moq_bits(self, batch: Any) -> None:
        """MoQ: recompute per-layer quantization bits from layer Hessian
        eigenvalues (sensitivity). More sensitive layers (larger |eig|)
        quantize LATER along the start_bits→target_bits schedule — the
        reference's eigenvalue-scaled quantization periods
        (engine.py:1479, runtime/quantize.py)."""
        wq = self._compression_plan.methods["weight_quantization"]
        p = wq["params"]
        start = int(p.get("start_bits", 16))
        target = int(p.get("target_bits", 8))
        off = int(wq.get("schedule_offset", 0))
        ramp = int(self._moq_ramp)
        # progress is UNCAPPED before the per-layer division: a layer with
        # sensitivity rel reaches target at step off + rel*ramp — sensitive
        # layers quantize later but always get there (a capped prog would
        # freeze rel>1 layers at intermediate bits forever)
        prog = max(0.0, (self.global_steps - off) / max(1, ramp))
        mb = jax.tree.map(lambda x: x[0], batch)
        rng = jax.random.fold_in(self._moq_rng, self.global_steps)
        evs = self._moq_eigenvalue.compute_layer_eigenvalues(
            self.model.loss_fn, self.params, mb, rng)
        evs_arr = np.abs(np.asarray(evs, np.float64)) + 1e-12
        rel = evs_arr / evs_arr.mean()          # >1 => more sensitive
        eff = np.clip(prog / rel, 0.0, 1.0)     # sensitive => slower
        lo, hi = min(start, target), max(start, target)
        bits = tuple(int(b) for b in np.clip(
            np.round(start - (start - target) * eff), lo, hi))
        if wq.get("layer_bits") != bits:
            wq["layer_bits"] = bits
            self._compiled_step = None
            self._eval_step = None
            log_dist(f"MoQ eigenvalue schedule: layer bits -> {bits}")

    def _sync_step_stats(self, stats: StepStats) -> None:
        """Materialise lazily-accumulated device counters (one queue drain)."""
        _ = self.skipped_steps  # property drains _skipped_accum
        self._last_lr = float(stats.lr)
        if self._tput_window_start is not None and self._steps_since_sync > 0:
            self.tput_timer.add_window(time.time() - self._tput_window_start,
                                       self._steps_since_sync)
        self._tput_window_start = time.time()
        self._steps_since_sync = 0

    def mark_step_boundary(self) -> None:
        """Exclude upcoming host work (eval, checkpointing, data stalls) from
        the throughput window. Called automatically by eval_loss and
        save_checkpoint."""
        if self._tput_window_start is not None and self._steps_since_sync > 0:
            self.tput_timer.add_window(time.time() - self._tput_window_start,
                                       self._steps_since_sync)
            self._steps_since_sync = 0
        self._tput_window_start = None

    # -- forward/backward/step staged emulation (reference API parity) ----
    def forward(self, batch: Any) -> jax.Array:
        """Compute microbatch loss; with backward() and step() this emulates
        the reference's three-call protocol. grads are computed at backward."""
        if self.model.pipelined:
            raise RuntimeError(
                "the staged forward/backward/step protocol is not available for "
                "pipelined models — use train_batch() (the reference has the "
                "same restriction: PipelineEngine only exposes train_batch)")
        if self._pld is not None:
            raise RuntimeError(
                "progressive_layer_drop is driven by train_batch (per-step "
                "theta injection); the staged forward/backward/step protocol "
                "would silently run the full model")
        if self._nvme_swapper is not None:
            raise RuntimeError(
                "nvme offload drives the optimizer from train_batch (the "
                "swap pipeline wraps the whole step) — the staged "
                "forward/backward/step protocol is not available")
        if self._param_offload is not None:
            raise RuntimeError(
                "offload_param drives the whole step from train_batch (the "
                "host streaming loop owns fwd/bwd/update) — the staged "
                "forward/backward/step protocol is not available")
        if self._random_ltd is not None:
            raise RuntimeError(
                "random_ltd is driven by train_batch (per-step kept-token "
                "schedule + step re-specialisation); the staged "
                "forward/backward/step protocol would silently skip it")
        if self._compression_plan is not None:
            raise RuntimeError(
                "compression_training is driven by train_batch (the schedule "
                "advances on its step counter and the QAT transform is "
                "rebuilt at boundaries); the staged forward/backward/step "
                "protocol would silently train uncompressed")
        if self._compiled_micro is None:
            model, gas, fp16 = self.model, self.gradient_accumulation_steps(), self.fp16_enabled()

            def micro(params, mb, scale):
                loss = model.loss_fn(params, mb)
                return loss * scale / gas, loss

            self._compiled_micro = jax.jit(jax.value_and_grad(micro, has_aux=True))
        self._pending_batch = self._globalize_batch(batch, leading_gas=False)
        scale = self.scaler_state.scale if self.fp16_enabled() else jnp.float32(1.0)
        with mesh_mod.ambient(self.mesh):
            with self._obs.span("fwd", step=self.global_steps):
                (scaled_loss, loss), grads = self._compiled_micro(
                    self.params, self._pending_batch, scale)
        self._pending_grads = grads
        self._pending_loss = loss
        return loss

    def backward(self, loss: Optional[jax.Array] = None) -> None:
        """Accumulate the grads computed in forward (reference engine.backward)."""
        if getattr(self, "_pending_grads", None) is None:
            raise RuntimeError("backward() called before forward()")
        with self._obs.span("bwd", step=self.global_steps):
            if self._staged_grads is None:
                self._staged_grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32), self._pending_grads)
            else:
                self._staged_grads = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32),
                    self._staged_grads, self._pending_grads)
        self._pending_grads = None
        self._staged_count += 1
        self.micro_steps += 1

    def is_gradient_accumulation_boundary(self) -> bool:
        return self._staged_count >= self.gradient_accumulation_steps()

    def step(self) -> None:
        """Apply the optimizer at the GAS boundary (reference engine.step)."""
        if not self.is_gradient_accumulation_boundary():
            return
        grads = self._staged_grads
        with self._obs.span("step", step=self.global_steps):
            if self.fp16_enabled():
                inv = 1.0 / self.scaler_state.scale
                grads = jax.tree.map(lambda g: g * inv, grads)
                overflow = has_overflow(grads)
            else:
                overflow = jnp.asarray(False)
            with mesh_mod.ambient(self.mesh):
                self.params, self.opt_state, stats = self.optimizer.apply(
                    self.params, grads, self.opt_state, skip_update=overflow)
        self.scaler_state = self.loss_scaler.update(self.scaler_state, overflow)
        if bool(stats.skipped):
            self._skipped_steps += 1
        self._staged_grads = None
        self._staged_count = 0
        self.global_steps += 1
        if self._obs.enabled:
            self._obs.note_step(self.global_steps)
            self._obs.maybe_record_memory(self.global_steps)
            if self._obs.profiler is not None:
                self._obs.profiler.on_step(self.global_steps)
        self._last_lr = float(stats.lr)
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "step"):
            self.lr_scheduler.step()

    def eval_loss(self, batch: Any) -> jax.Array:
        self.mark_step_boundary()
        if self._param_offload is not None:
            with mesh_mod.ambient(self.mesh):
                batch = self._globalize_batch(batch, leading_gas=False)
                return self._param_offload.eval_forward(batch)
        if self.model.pipelined:
            # the pipelined loss_fn needs an (M, mb, ...) stack; for a plain
            # eval microbatch wrap it as a single-microbatch stack
            batch = jax.tree.map(lambda x: x[None], batch)
        built = self._eval_step is None
        self._ensure_eval_step()
        if built:
            self._register_eval_audit(batch)
        with mesh_mod.ambient(self.mesh):
            with self._obs.span("eval", step=self.global_steps):
                return self._eval_step(self.params, batch)

    def _ensure_eval_step(self) -> None:
        if self._eval_step is None:
            # eval_loss_fn derives an eval-mode config (regularisers off) at
            # trace time — no shared-config mutation, and the jitted step is
            # cached so repeated eval calls don't retrace; the cache is
            # invalidated at compression boundaries so eval evaluates the
            # SAME compressed module the train step differentiates
            if self.model.eval_loss_fn is not None:
                self._eval_step = jax.jit(
                    self._compression_wrap(self.model.eval_loss_fn))
            else:
                cfg = self.model.config
                loss_fn = self.model.loss_fn
                if cfg is not None and hasattr(cfg, "dropout_enabled"):
                    # custom Model without eval_loss_fn: toggle the shared
                    # config's regularisers off around EVERY trace (the
                    # wrapper body runs at trace time only — including
                    # shape-driven retraces, and after train_batch has
                    # raised ltd_keep). build_model-produced Models carry a
                    # config-copy eval_loss_fn and never take this path.
                    def eval_fn(params, batch):
                        keep = getattr(cfg, "ltd_keep", 0)
                        drop = cfg.dropout_enabled
                        cfg.ltd_keep, cfg.dropout_enabled = 0, False
                        try:
                            return loss_fn(params, batch)
                        finally:
                            cfg.ltd_keep, cfg.dropout_enabled = keep, drop

                    self._eval_step = jax.jit(self._compression_wrap(eval_fn))
                else:
                    self._eval_step = jax.jit(self._compression_wrap(loss_fn))

    # -- tpuaudit registration (tools/tpuaudit) ---------------------------
    def register_audit_entries(self, micro_batch: Any,
                               prefix: str = "train") -> list:
        """Register this engine's jitted programs with the tpuaudit
        program auditor (``python -m tools.tpuaudit``), without running a
        step: ``micro_batch`` is ONE example microbatch (host arrays are
        fine — only shapes/dtypes reach the auditor). Returns the
        registered entry names; a deployment without the ``tools/`` tree
        (or a param-offload engine, whose step is a host-driven loop, not
        one program) registers nothing."""
        if self._param_offload is not None:
            return []
        try:
            from tools.tpuaudit import registry as _audit  # noqa: F401 — probe
        except ImportError:
            return []
        gas = self.gradient_accumulation_steps()
        stacked = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                (gas,) + tuple(np.shape(x)),
                getattr(x, "dtype", None) or np.asarray(x).dtype),
            micro_batch)
        names = []
        if self._compiled_step is None:
            self._compiled_step = (
                self._build_nvme_grads_step() if self._nvme_swapper is not None
                else self._build_onebit_train_step() if self._onebit
                else self._build_train_step())
        names.append(self._register_step_audit(stacked, prefix=prefix))
        micro_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                tuple(np.shape(x)),
                getattr(x, "dtype", None) or np.asarray(x).dtype),
            micro_batch)
        if self.model.pipelined:
            micro_sds = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct((1,) + x.shape, x.dtype),
                micro_sds)
        self._ensure_eval_step()
        names.append(self._register_eval_audit(micro_sds, prefix=prefix))
        return [n for n in names if n]

    def _expected_collectives(self, train: bool) -> frozenset:
        """The collective kinds this engine's programs are ALLOWED to
        contain, derived from the parallel/ZeRO config — tpuaudit flags
        anything beyond this set as an undeclared GSPMD reshard. On a
        single-device mesh the set is empty: any collective is a bug."""
        par = self.config.parallel
        z = self.config.zero_stage
        exp: set = set()
        if self.mesh.size > 1:
            exp.add("all-reduce")          # grad/loss averaging over 'data'
        if train and z >= 1:
            exp.add("all-gather")          # sharded master -> full params
        if train and z >= 2:
            exp |= {"reduce-scatter", "all-to-all"}   # grad sharding
        if z >= 3:
            exp.add("all-gather")          # fwd param gathers (eval too)
        if par.tensor_parallel_size > 1:
            exp |= {"all-gather", "all-to-all"}       # activation reshards
        if par.sequence_parallel_size > 1:
            exp |= {"all-gather", "all-to-all", "collective-permute"}
        if par.pipeline_parallel_size > 1:
            exp |= {"collective-permute", "all-gather"}
        if par.expert_parallel_size > 1:
            # the expert dispatch is an (E, C, H) all-to-all by intent, but
            # on small meshes GSPMD lowers it (and the batch<->expert-bank
            # reshards) to collective-permute pairs — the auditor caught the
            # permutes as undeclared on the moe-tiny ep=2 engine
            exp |= {"all-to-all", "all-gather", "collective-permute"}
        if self._onebit and train:
            # compressed allreduce (comm/compressed.py): chunk exchange is an
            # explicit all_to_all, scale/result distribution an all_gather —
            # the auditor flagged both as undeclared on the 1-bit engine
            exp |= {"all-to-all", "all-gather"}
        return frozenset(exp)

    def _register_step_audit(self, stacked_batch: Any,
                             prefix: str = "train") -> Optional[str]:
        """Register the compiled train step (whatever variant this engine
        built) under ``<prefix>/step``. Called from train_batch right after
        the step specializes, so re-specializations (compression boundaries,
        random-LTD) re-register the CURRENT program."""
        try:
            from tools.tpuaudit.registry import (StaleEntryError,
                                                 abstract_tree,
                                                 abstract_with_shardings,
                                                 register_entry_point)
        except ImportError:
            return None
        try:
            import weakref

            # args are ShapeDtypeStruct trees (shapes only, no buffers);
            # the step executable itself is looked up through a weakref at
            # audit time so the registry never pins a replaced engine
            batch_sds = abstract_with_shardings(
                stacked_batch, self._batch_sharding(stacked_batch,
                                                    leading_gas=True))
            params_sds = abstract_tree(self.params)
            suppress = set()
            if self._nvme_swapper is not None:
                # params update host-side in the swapper; the device program
                # intentionally returns grads without donating params
                args = (params_sds, batch_sds)
                donate: Tuple[int, ...] = ()
                suppress.add("missed-donation")
            elif self._onebit:
                args = (params_sds, abstract_tree(self.opt_state),
                        abstract_tree(self.scaler_state),
                        abstract_tree(self._comp_state), batch_sds)
                donate = (0, 1, 3)
            else:
                # the numerics-state slot exists even with the sentinel off
                # (None = empty pytree), mirroring the step signature
                num_sds = (abstract_tree(self._numerics.init_state())
                           if self._numerics is not None else None)
                args = (params_sds, abstract_tree(self.opt_state),
                        abstract_tree(self.scaler_state), num_sds, batch_sds)
                donate = (0, 1)
            name = f"{prefix}/step"
            wself = weakref.ref(self)

            def build():
                eng = wself()
                if eng is None or eng._compiled_step is None:
                    raise StaleEntryError(f"{name}: engine was torn down")
                return eng._compiled_step, args, {}

            register_entry_point(
                name, build=build,
                donate_argnums=donate,
                expected_collectives=self._expected_collectives(train=True),
                suppress=frozenset(suppress), mesh=self.mesh,
                compile=not self.model.pipelined,  # 1F1B compiles are heavy
                tags={"engine": "TrainEngine",
                      "zero_stage": self.config.zero_stage,
                      # tokens processed by ONE execution of this program
                      # (all gas microbatches) — tpucost's roofline turns
                      # it into a predicted tokens/sec bound
                      "tokens_per_step": _batch_tokens(stacked_batch),
                      "shard": self._shard_tag(group=prefix),
                      # lowered module name ("jit_train_step") — the deep
                      # profiler's attribution key back to this entry
                      "program": "train_step"})
            return name
        except Exception:  # registration must never take training down
            logger.warning("tpuaudit step registration failed", exc_info=True)
            return None

    def _register_eval_audit(self, batch: Any,
                             prefix: str = "train") -> Optional[str]:
        try:
            from tools.tpuaudit.registry import (StaleEntryError,
                                                 abstract_tree,
                                                 register_entry_point)
        except ImportError:
            return None
        try:
            import weakref

            name = f"{prefix}/eval"
            args = (abstract_tree(self.params), abstract_tree(batch))
            wself = weakref.ref(self)

            def build():
                eng = wself()
                if eng is None or eng._eval_step is None:
                    raise StaleEntryError(f"{name}: engine was torn down")
                return eng._eval_step, args, {}

            register_entry_point(
                name, build=build, donate_argnums=(),
                expected_collectives=self._expected_collectives(train=False),
                mesh=self.mesh, compile=not self.model.pipelined,
                tags={"engine": "TrainEngine",
                      "tokens_per_step": _batch_tokens(batch),
                      "shard": self._shard_tag(group=prefix)})
            return name
        except Exception:
            logger.warning("tpuaudit eval registration failed", exc_info=True)
            return None

    def _shard_tag(self, group: str) -> dict:
        """The tools/tpushard placement contract for this engine's programs:
        the params argument follows the ZeRO param placement from the rule
        registry; entries in one ``group`` exchange live buffers (step and
        eval consume the same params tree), so the analyzer cross-checks
        their layouts."""
        from ..parallel.rules import shard_tag

        return shard_tag(
            "fsdp" if self.config.zero_stage >= 3 else "tp",
            axes=self.model.axes, params_arg=0,
            expert_parallel=self.config.parallel.expert_parallel_size > 1,
            fsdp_min_size=self._fsdp_min_size, group=group)

    # -- profiling (reference flops_profiler engine hooks + NVTX ranges) --
    def get_flops_profile(self):
        """Per-module FLOPs/params breakdown + compiled-program cost
        (reference FlopsProfiler.print_model_profile data)."""
        from ..profiling import transformer_breakdown

        cfg = self.model.config
        if cfg is None:
            raise ValueError("flops profile needs a transformer Model")
        prof = transformer_breakdown(
            cfg, self.train_micro_batch_size_per_gpu(), cfg.max_seq_len)
        return {"profile": prof, "table": prof.table()}

    def print_model_profile(self, batch_size: Optional[int] = None,
                            seq_len: Optional[int] = None,
                            output_file: Optional[str] = None) -> None:
        """MEASURED per-module latency/GFLOPs tree (reference
        FlopsProfiler.print_model_profile, profiler.py:239): runs the
        engine's model segment-by-segment and prints depth-0/1/2 rows with
        median wall ms, XLA-counted GFLOPs, params and achieved FLOPS."""
        from ..profiling import get_model_profile

        cfg = self.model.config
        if cfg is None:
            raise ValueError("flops profile needs a transformer Model")
        if self._param_offload is not None:
            raise NotImplementedError(
                "print_model_profile materialises the full dense model on "
                "device — a param-offload engine exists because that does "
                "NOT fit; use engine._param_offload.overlap_report() and "
                "get_flops_profile() (analytic) instead")
        get_model_profile(
            self.model,
            batch_size or self.train_micro_batch_size_per_gpu(),
            seq_len or min(cfg.max_seq_len, 512),
            print_profile=True, measured=True, output_file=output_file)

    def start_profile(self, log_dir: Optional[str] = None) -> None:
        """jax profiler trace (the nsys/NVTX analog; view in XProf).

        Double-start guarded (``jax.profiler.start_trace`` would raise an
        opaque backend error mid-run otherwise); the trace dir defaults to
        ``ObservabilityConfig.profile_dir``; the profiled region is recorded
        as a span so the trace window shows up in the observability export."""
        if self._profiling:
            raise RuntimeError(
                "start_profile() called while a profiler trace is already "
                "active — call stop_profile() first")
        prof = getattr(self._obs, "profiler", None)
        if log_dir is None and prof is not None:
            # deep profiler present: the manual window rides its ledger —
            # capture dir management, parse + measured-vs-predicted summary
            # on stop, profile/* metrics (an explicit log_dir keeps the raw
            # path: the operator asked for a specific directory)
            cap = prof.open_window("manual")
            if cap is None:
                raise RuntimeError(
                    "start_profile(): a triggered capture window is "
                    "already open — it closes at its iteration/wall bound")
            self._profiling = True
            self._profile_capture = cap
            self._profile_span = self._obs.span(
                "profile", category="profiler", dir=cap.dir).begin()
            return
        log_dir = log_dir or self.config.observability.profile_dir
        jax.profiler.start_trace(log_dir)
        self._profiling = True
        self._profile_span = self._obs.span(
            "profile", category="profiler", dir=log_dir).begin()

    def stop_profile(self) -> None:
        if not self._profiling:
            logger.warning("stop_profile() called with no active profiler "
                           "trace — ignoring")
            return
        if getattr(self, "_profile_capture", None) is not None:
            prof = getattr(self._obs, "profiler", None)
            if prof is not None:
                prof.close_window()
            self._profile_capture = None
        else:
            jax.profiler.stop_trace()
        self._profiling = False
        if self._profile_span is not None:
            self._profile_span.end()
            self._profile_span = None

    # -- goodput ----------------------------------------------------------
    def _wire_goodput(self) -> None:
        """Hand the goodput accountant the workload shape: global tokens per
        step, fwd+bwd FLOPs per chip per step (what the ``goodput/mfu``
        gauge divides by peak), and the attached chip's peak from the
        autotuning cost model. Pure host arithmetic — never a device sync."""
        from ..autotuning.cost_model import peak_flops_for

        peak = peak_flops_for(jax.devices()[0].device_kind)
        gas = self.gradient_accumulation_steps()
        micro = self.train_micro_batch_size_per_gpu()
        cfg = self.model.config
        try:
            if cfg is not None:
                from ..profiling import transformer_breakdown

                seq = int(getattr(cfg, "max_seq_len", 1024))
                prof = transformer_breakdown(cfg, micro, seq)
                # fwd+bwd ~ 3x fwd flops (the flops profiler's 1:2 rule)
                flops_per_step = 3.0 * prof.total_flops * gas
                tokens_per_step = float(self.train_batch_size()) * seq
                source = "flops-profiler"
            else:
                n = (self._n_params if self.params is None
                     else param_count(self.params))
                # config-less model: 6N training flops per sample-as-token
                flops_per_step = 6.0 * float(n) * micro * gas
                tokens_per_step = float(self.train_batch_size())
                source = "param-count"
            self._obs.goodput.set_workload(
                tokens_per_step=tokens_per_step,
                flops_per_step=flops_per_step,
                peak_flops=peak, source=source)
        except Exception:  # telemetry must never take the engine down
            logger.warning("goodput workload wiring failed", exc_info=True)

    # -- fleet health ------------------------------------------------------
    def _wire_fleet_health(self) -> None:
        """Wire the optional per-replica param-checksum probe into the fleet
        monitor. ZeRO ≤ 2 only: stage 3 shards the params over 'data', so
        replica copies (the thing SDC corrupts divergently) don't exist."""
        if not self.config.observability.fleet_param_checksum:
            return
        if self.config.zero_stage >= 3 or self.params is None:
            logger.warning(
                "observability.fleet_param_checksum needs replicated "
                "parameter copies (ZeRO stage <= 2, resident params) — "
                "disabling the checksum probe; loss/grad-norm agreement "
                "still checks")
            return
        try:
            from ..observability import build_replica_checksum_probe

            probe = build_replica_checksum_probe(self.mesh,
                                                 self.plan.param_specs)

            def checksum():
                with mesh_mod.ambient(self.mesh):
                    return probe(self.params)

            self._obs.fleet.set_checksum_fn(checksum)
            self._register_fleet_probe_audit(probe)
        except Exception:  # telemetry must never take the engine down
            logger.warning("fleet checksum probe wiring failed",
                           exc_info=True)

    def _register_fleet_probe_audit(self, probe) -> None:
        """Declare the checksum probe's program to tpuaudit: its only
        collective is the psum over the non-data axes (none on a pure-DP
        mesh)."""
        try:
            from tools.tpuaudit.registry import (StaleEntryError,
                                                 abstract_tree,
                                                 register_entry_point)
        except ImportError:
            return
        try:
            import weakref

            wself = weakref.ref(self)
            args = (abstract_tree(self.params),)

            def build():
                eng = wself()
                if eng is None:
                    raise StaleEntryError(
                        "train/fleet_probe: engine was torn down")
                return probe, args, {}

            non_data = any(self.mesh.shape[a] > 1
                           for a in self.mesh.axis_names
                           if a != mesh_mod.DATA_AXIS)
            register_entry_point(
                "train/fleet_probe", build=build, donate_argnums=(),
                expected_collectives=(frozenset({"all-reduce"}) if non_data
                                      else frozenset()),
                mesh=self.mesh, tags={"engine": "TrainEngine"})
        except Exception:
            logger.warning("fleet probe audit registration failed",
                           exc_info=True)

    # -- monitor ----------------------------------------------------------
    def _publish_metrics(self, loss: float, grad_norm: float) -> None:
        """Publish step stats through the observability metrics registry and
        hand the scalarized snapshot to THIS engine's monitor writers
        (CSV/TB/WandB) — the registry is the single event source, and the
        monitor stays engine-scoped (it is deliberately not attached as a
        global-registry exporter: the registry is a process singleton, so a
        global attachment would keep feeding every engine's metrics into
        every other engine's monitors for the life of the process)."""
        reg = self._obs.registry
        names = ["Train/Samples/train_loss", "Train/Samples/lr",
                 "Train/Samples/grad_norm", "Train/Samples/throughput"]
        if self._monitor is None:
            from ..monitor.monitor import MonitorMaster

            self._monitor = MonitorMaster(self.config.monitor)
        reg.gauge("Train/Samples/train_loss").set(loss)
        reg.gauge("Train/Samples/lr").set(self._last_lr)
        reg.gauge("Train/Samples/grad_norm").set(grad_norm)
        reg.gauge("Train/Samples/throughput").set(
            self.tput_timer.avg_samples_per_sec())
        if (self._param_offload is not None
                and self._param_offload.last_step_stats):
            st = self._param_offload.last_step_stats
            reg.gauge("Train/Offload/h2d_gbps").set(st["achieved_h2d_gbps"])
            reg.gauge("Train/Offload/total_gbps").set(
                st["achieved_total_gbps"])
            names += ["Train/Offload/h2d_gbps", "Train/Offload/total_gbps"]
        if self._obs.goodput is not None:
            # gauges are refreshed every step by note_step; the monitor
            # writers see them at the same steps_per_print cadence as loss
            names += ["goodput/goodput_fraction", "goodput/mfu",
                      "goodput/tokens_per_sec", "goodput/seconds",
                      "goodput/wall_seconds", "goodput/steps"]
        events = reg.publish(self.global_steps, names=names)
        if self._monitor.enabled:
            self._monitor.write_events(events)

    # -- checkpoint (reference engine.py:2792 save_checkpoint) ------------
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[Dict] = None,
                        save_latest: bool = True,
                        async_save: bool = False) -> str:
        from .checkpoint import save_checkpoint as _save

        self.mark_step_boundary()
        tag = tag or f"global_step{self.global_steps}"
        client_state = dict(client_state or {})
        client_state.update({
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
            "skipped_steps": self.skipped_steps,
            "loss_scale": float(self.scaler_state.scale),
            "lr_scheduler": (self.lr_scheduler.state_dict()
                             if self.lr_scheduler is not None
                             and hasattr(self.lr_scheduler, "state_dict") else None),
        })
        params = self.params
        opt_state = self.opt_state
        extra_arrays = extra_writes = None
        if self._param_offload is not None:
            if jax.process_count() > 1:
                # layer params + state go as per-region shard files (each
                # process writes only its addressable regions); resident
                # trees ride the normal writer as global arrays
                (params, opt_state, extra_arrays,
                 extra_writes) = self._param_offload.region_checkpoint()
            else:
                params = self._param_offload.params_for_checkpoint()
                opt_state = self._param_offload.opt_state_arrays()
            if async_save:
                # the executor updates its host numpy storage IN PLACE every
                # step — snapshot before handing to the background writer or
                # the checkpoint tears between step N and N+1
                copy_np = lambda x: (np.array(x) if isinstance(x, np.ndarray)
                                     else x)
                params = jax.tree.map(copy_np, params)
                opt_state = jax.tree.map(copy_np, opt_state)
                if extra_writes:
                    extra_writes = [(f, np.array(d)) for f, d in extra_writes]
        with self._obs.span("checkpoint/save", tag=tag, sync=True):
            path = _save(save_dir, tag, params=params, opt_state=opt_state,
                         client_state=client_state, save_latest=save_latest,
                         tag_validation=self.config.checkpoint.tag_validation,
                         async_save=async_save, extra_arrays=extra_arrays,
                         extra_writes=extra_writes)
        if self._nvme_swapper is not None:
            # the swap files ARE the optimizer state — snapshot them into the
            # checkpoint (reference use_node_local_storage semantics); one
            # dir per process, since each swap dir holds only that process's
            # addressable state regions. Under async_save the returned path
            # is the FINAL tag dir, which only exists once the background
            # commit renames the staging tree into place — wait for it, or
            # the snapshot would create the final dir early and the rename
            # would sweep it aside as a replaced-tag leftover.
            if async_save:
                from .checkpoint import wait_pending

                wait_pending()
            self._nvme_swapper.snapshot_to(
                os.path.join(path, f"nvme_state_p{jax.process_index()}"))
        log_dist(f"saved checkpoint {path}")
        return path

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None,
                        load_optimizer_states: bool = True,
                        load_lr_scheduler_states: bool = True,
                        verify: bool = False) -> Tuple[Optional[str], Dict]:
        from .checkpoint import load_checkpoint as _load

        if self._param_offload is not None:
            po = self._param_offload
            # shape-skeleton templates — the loader reads only shapes/dtypes
            # from them, so nothing is materialised (multi-process safe)
            ptree = po.checkpoint_template()
            psh = dict(po._res_shardings)
            psh["layers"] = jax.tree.map(lambda _: "host", ptree["layers"])
            opt_tpl = None
            if load_optimizer_states:
                ost = po.opt_state_template()
                host_of = lambda t: jax.tree.map(lambda _: "host", t)
                osh = {"step": "host",
                       "layer_master": host_of(ost["layer_master"]),
                       "layer_m": host_of(ost["layer_m"]),
                       "layer_v": host_of(ost["layer_v"]),
                       "res_master": po._res_shardings,
                       "res_m": po._res_shardings,
                       "res_v": po._res_shardings}
                opt_tpl = (ost, osh)
            with mesh_mod.ambient(self.mesh):
                result = _load(load_dir, tag,
                               params_template=(ptree, psh),
                               opt_template=opt_tpl, verify=verify)
            if result is None:
                return None, {}
            params, opt_state, client_state = result
            po.load_params(params)
            if opt_state is not None:
                po.load_opt_state(opt_state)
            else:
                # params-only load: the executor's own step counter drives
                # its lr_schedule and Adam bias correction — resync or the
                # next step silently applies lr_schedule(0)
                po.step_count = client_state.get("global_steps", 0)
            self.global_steps = client_state.get("global_steps", 0)
            self.micro_steps = client_state.get("micro_steps", 0)
            self.skipped_steps = client_state.get("skipped_steps", 0)
            if "loss_scale" in client_state:
                self.scaler_state = self.scaler_state._replace(
                    scale=jnp.float32(client_state["loss_scale"]))
                if po.scaler_state is not None:
                    po.scaler_state = self.scaler_state
            if (load_lr_scheduler_states and self.lr_scheduler is not None
                    and client_state.get("lr_scheduler") is not None
                    and hasattr(self.lr_scheduler, "load_state_dict")):
                self.lr_scheduler.load_state_dict(client_state["lr_scheduler"])
            log_dist(f"loaded checkpoint from {load_dir} (tag={tag or 'latest'})")
            return load_dir, client_state

        load_resident_opt = (load_optimizer_states
                             and self._nvme_swapper is None)
        opt_shardings = self._opt_state_shardings() if load_resident_opt else None
        with mesh_mod.ambient(self.mesh):
            with self._obs.span("checkpoint/load", sync=True):
                result = _load(load_dir, tag,
                               params_template=(self.params, self.param_shardings),
                               opt_template=((self.opt_state, opt_shardings)
                                             if load_resident_opt else None),
                               verify=verify)
        if result is None:
            return None, {}
        params, opt_state, client_state = result
        self.params = params
        if opt_state is not None:
            self.opt_state = opt_state
        if load_optimizer_states and self._nvme_swapper is not None:
            snap = f"nvme_state_p{jax.process_index()}"
            # _checkpoint_tag names the tag _load ACTUALLY restored — under
            # verify-fallback that may be an older tag than 'latest', and
            # the swap snapshot must come from the same tag as the params
            base = os.path.join(load_dir,
                                tag or client_state.get("_checkpoint_tag",
                                                        ""))
            if not os.path.isdir(os.path.join(base, snap)):
                # resolve via 'latest' the same way _load did
                latest = os.path.join(load_dir, "latest")
                if os.path.exists(latest):
                    with open(latest) as f:
                        base = os.path.join(load_dir, f.read().strip())
            src = os.path.join(base, snap)
            if not os.path.isdir(src) and jax.process_count() == 1:
                # pre-per-process checkpoints used a single 'nvme_state'
                # dir; restore_snapshot migrates their format-1 manifest
                legacy = os.path.join(base, "nvme_state")
                if os.path.isdir(legacy):
                    src = legacy
            if not os.path.isdir(src):
                raise RuntimeError(
                    f"checkpoint has no {snap} snapshot at {src} — "
                    "cannot restore NVMe optimizer state (pass "
                    "load_optimizer_states=False to restore params only; "
                    "note the snapshot is per-process — resuming under a "
                    "different process topology needs the universal "
                    "checkpoint path)")
            self._nvme_swapper.restore_snapshot(
                src, client_state.get("global_steps", 0))
        self.global_steps = client_state.get("global_steps", 0)
        self.micro_steps = client_state.get("micro_steps", 0)
        self.skipped_steps = client_state.get("skipped_steps", 0)
        if "loss_scale" in client_state:
            # (offload runs restore their scaler in the branch above)
            self.scaler_state = self.scaler_state._replace(
                scale=jnp.float32(client_state["loss_scale"]))
        if (load_lr_scheduler_states and self.lr_scheduler is not None
                and client_state.get("lr_scheduler") is not None
                and hasattr(self.lr_scheduler, "load_state_dict")):
            self.lr_scheduler.load_state_dict(client_state["lr_scheduler"])
        log_dist(f"loaded checkpoint from {load_dir} (tag={tag or 'latest'})")
        return load_dir, client_state

    def save_16bit_model(self, save_dir: str, save_filename: str = "model_fp16.npz") -> str:
        """Reference save_16bit_model/_zero3_consolidated_16bit_state_dict
        (engine.py:3146-3213): consolidated half-precision weights."""
        from .checkpoint import save_flat_weights

        os.makedirs(save_dir, exist_ok=True)
        path = os.path.join(save_dir, save_filename)
        params = (self._param_offload.params_for_checkpoint()
                  if self._param_offload is not None else self.params)
        save_flat_weights(params, path)
        return path


# ---------------------------------------------------------------------------


def initialize(args=None, model: Optional[Model] = None, optimizer=None,
               model_parameters=None, training_data=None, lr_scheduler=None,
               mesh: Optional[Mesh] = None, config=None, rng=None,
               collate_fn=None) -> Tuple[TrainEngine, Any, Any, Any]:
    """Analog of ``deepspeed.initialize`` (reference deepspeed/__init__.py:58).
    Returns (engine, optimizer, training_dataloader, lr_scheduler)."""
    if model is None:
        raise ValueError("model is required (a deepspeed_tpu.models.Model bundle)")
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    cfg = load_config(config)
    engine = TrainEngine(model=model, config=cfg, mesh=mesh, optimizer=optimizer,
                         lr_scheduler=lr_scheduler, training_data=training_data,
                         collate_fn=collate_fn, rng=rng)
    dataloader = engine.training_dataloader
    if dataloader is not None:
        dataloader = RepeatingLoader(dataloader)
    return engine, engine.optimizer, dataloader, engine.lr_scheduler

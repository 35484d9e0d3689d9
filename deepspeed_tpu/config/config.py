"""The framework config tree.

TPU-native analog of ``deepspeed/runtime/config.py`` (``DeepSpeedConfig``,
reference :674) plus the per-feature pydantic models scattered through the
reference (``runtime/zero/config.py``, ``inference/config.py``,
``monitor/config.py``, ...). One JSON file / dict drives everything; the batch
triad ``train_batch_size = micro_batch * grad_accum * dp_world`` is resolved
exactly like ``_set_batch_related_parameters`` (reference runtime/config.py:888).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Any, Dict, List, Mapping, Optional, Union

from .base import ConfigError, ConfigModel

# ---------------------------------------------------------------------------
# precision
# ---------------------------------------------------------------------------


@dataclass
class FP16Config(ConfigModel):
    """Reference: ``runtime/fp16`` config section (runtime/config.py FP16 keys)."""

    enabled: bool = False
    auto_cast: bool = False
    loss_scale: float = 0.0  # 0 => dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    min_loss_scale: float = 1.0

    @property
    def dynamic_loss_scale(self) -> bool:
        return self.loss_scale == 0.0


@dataclass
class BF16Config(ConfigModel):
    """bf16 is the natural TPU dtype; mirrors the reference ``bf16`` section."""

    enabled: bool = False


# ---------------------------------------------------------------------------
# optimizer / scheduler
# ---------------------------------------------------------------------------


@dataclass
class OptimizerConfig(ConfigModel):
    """Reference: ``optimizer`` JSON section (runtime/config.py get_optimizer_params)."""

    type: str = "adamw"
    params: Dict[str, Any] = field(default_factory=dict)

    def validate(self) -> None:
        known = {"adam", "adamw", "lamb", "adagrad", "sgd", "lion",
                 "onebitadam", "onebitlamb", "zerooneadam", "fusedadam", "cpuadam"}
        if self.type.lower() not in known:
            raise ConfigError(f"unknown optimizer type '{self.type}' (known: {sorted(known)})")


@dataclass
class SchedulerConfig(ConfigModel):
    """Reference: ``scheduler`` JSON section → runtime/lr_schedules.py."""

    type: str = "WarmupLR"
    params: Dict[str, Any] = field(default_factory=dict)


# ---------------------------------------------------------------------------
# ZeRO
# ---------------------------------------------------------------------------


@dataclass
class OffloadParamConfig(ConfigModel):
    """Reference: runtime/zero/offload_config.py (DeepSpeedZeroOffloadParamConfig)."""

    device: str = "none"  # none | cpu | nvme
    nvme_path: str = "/local_nvme"
    buffer_count: int = 5
    buffer_size: int = 100_000_000
    max_in_cpu: int = 1_000_000_000
    pin_memory: bool = False

    def validate(self) -> None:
        if self.device not in ("none", "cpu", "nvme"):
            raise ConfigError(f"offload_param.device must be none|cpu|nvme, got {self.device}")


@dataclass
class OffloadOptimizerConfig(ConfigModel):
    """Reference: runtime/zero/offload_config.py (DeepSpeedZeroOffloadOptimizerConfig)."""

    device: str = "none"
    nvme_path: str = "/local_nvme"
    buffer_count: int = 4
    pin_memory: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    fast_init: bool = False
    ratio: float = 1.0

    def validate(self) -> None:
        if self.device not in ("none", "cpu", "nvme"):
            raise ConfigError(f"offload_optimizer.device must be none|cpu|nvme, got {self.device}")


@dataclass
class ZeroConfig(ConfigModel):
    """Reference: runtime/zero/config.py:76 (DeepSpeedZeroConfig).

    On TPU, the stages are sharding policies over the ``data`` mesh axis:
      stage 0 — replicated params/grads/opt-state (pure DP, grads psum'd)
      stage 1 — optimizer state sharded
      stage 2 — optimizer state + gradients sharded (grad reduce-scatter)
      stage 3 — parameters sharded too (FSDP; XLA inserts per-layer allgather)
    Bucket/overlap knobs from the reference are accepted for config
    compatibility but are no-ops: XLA schedules collective overlap itself.
    """

    stage: int = 0
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = 500_000_000
    allgather_partitions: bool = True
    allgather_bucket_size: int = 500_000_000
    overlap_comm: bool = False
    load_from_fp32_weights: bool = True
    elastic_checkpoint: bool = False
    offload_param: OffloadParamConfig = field(default_factory=OffloadParamConfig)
    offload_optimizer: OffloadOptimizerConfig = field(default_factory=OffloadOptimizerConfig)
    sub_group_size: int = 1_000_000_000
    stage3_max_live_parameters: int = 1_000_000_000
    stage3_max_reuse_distance: int = 1_000_000_000
    stage3_prefetch_bucket_size: int = 50_000_000
    stage3_param_persistence_threshold: int = 100_000
    stage3_gather_16bit_weights_on_model_save: bool = False
    ignore_unused_parameters: bool = True
    round_robin_gradients: bool = False
    zero_hpz_partition_size: int = 1
    zero_quantized_weights: bool = False

    DEPRECATED = {
        "stage3_gather_fp16_weights_on_model_save": (
            "stage3_gather_16bit_weights_on_model_save", "renamed in reference v0.6"),
        "cpu_offload": (None, "use offload_optimizer.device=cpu"),
        "cpu_offload_params": (None, "use offload_param.device=cpu"),
    }

    def validate(self) -> None:
        if not 0 <= self.stage <= 3:
            raise ConfigError(f"zero_optimization.stage must be in [0,3], got {self.stage}")


# ---------------------------------------------------------------------------
# parallel topology
# ---------------------------------------------------------------------------


@dataclass
class ParallelConfig(ConfigModel):
    """Mesh-axis degrees. The reference scatters these (mpu for TP, PipelineModule
    for PP, MoE kwargs for EP); here they are first-class config so the engine
    can build one ``jax.sharding.Mesh`` with axes (pipe, data, seq, model).
    ``data`` is the ZeRO/FSDP axis. 0 means "infer from world size"."""

    data_parallel_size: int = 0
    tensor_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    sequence_parallel_size: int = 1
    expert_parallel_size: int = 1
    # ulysses: all-to-all head scatter (parallel/sequence.py)
    # ring:    rotating-KV blockwise attention (parallel/ring.py)
    sequence_parallel_impl: str = "ulysses"

    def validate(self) -> None:
        for name in ("tensor_parallel_size", "pipeline_parallel_size",
                     "sequence_parallel_size", "expert_parallel_size"):
            if getattr(self, name) < 1:
                raise ConfigError(f"{name} must be >= 1")
        if self.sequence_parallel_impl not in ("ulysses", "ring"):
            raise ConfigError("sequence_parallel_impl must be 'ulysses' or "
                              f"'ring', got '{self.sequence_parallel_impl}'")


# ---------------------------------------------------------------------------
# aux feature configs
# ---------------------------------------------------------------------------


@dataclass
class ActivationCheckpointingConfig(ConfigModel):
    """Reference: runtime/activation_checkpointing/config.py:27-43. On TPU this
    maps to ``jax.checkpoint`` policies over the layer scan."""

    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-specific: jax.checkpoint policy name
    policy: str = "nothing_saveable"  # nothing_saveable | dots_saveable | dots_with_no_batch_dims_saveable


@dataclass
class CommsLoggerConfig(ConfigModel):
    """Reference: deepspeed/comm/config.py."""

    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: List[str] = field(default_factory=list)


@dataclass
class FlopsProfilerConfig(ConfigModel):
    """Reference: profiling/config.py."""

    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


@dataclass
class TensorboardConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJob"


@dataclass
class WandbConfig(ConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed_tpu"


@dataclass
class CSVConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedTPUJob"


@dataclass
class MonitorConfig(ConfigModel):
    """Reference: monitor/config.py → MonitorMaster fan-out writers."""

    tensorboard: TensorboardConfig = field(default_factory=TensorboardConfig)
    wandb: WandbConfig = field(default_factory=WandbConfig)
    csv_monitor: CSVConfig = field(default_factory=CSVConfig)


@dataclass
class TuneConfig(ConfigModel):
    """Closed-loop telemetry (``observability/timeseries.py`` +
    ``autotuning/livetuner.py``): the metric time-series store and the
    live-signal serving controller that walks DATA-ONLY knobs against
    measured SLO burn. Off by default — the disabled path allocates no
    store and wires no controller (zero extra dispatches, zero compiles,
    watchdog-asserted in tests)."""

    enabled: bool = False              # master gate: the time-series store
    store_capacity: int = 512          # retained points per series ring
    store_max_series: int = 4096       # series cap (overflow counted)
    store_ewma_alpha: float = 0.2      # EWMA smoothing for derived stats
    timeseries_file: str = "timeseries.jsonl"  # close-time ring export
    # -- the online controller (needs enabled=True too) --
    controller: bool = False           # walk serving knobs on router cadence
    interval_iterations: int = 32      # decision cadence (router iterations)
    hold_iterations: int = 64          # post-move hold before judging
    hysteresis: float = 0.05           # |relative objective delta| ignored
    burn_ceiling: float = 1.0          # SLO burn-rate constraint (SRE
    #   convention: 1.0 = spending the error budget exactly on schedule)
    burn_weight: float = 1.0           # objective penalty per unit of burn
    #   over the ceiling
    max_moves: int = 0                 # total knob moves allowed (0 = no cap)
    knobs: List[str] = field(default_factory=lambda: [
        "spec", "chunk_budget", "role_ratio", "deadline_pad",
        "overload_threshold"])
    recommendations_file: str = "tune_recommendations.json"  # shape-knob
    #   (speculative K, block size, mesh) advice — between-session only,
    #   NEVER walked online (jit-cache discipline)

    KNOWN_KNOBS = ("spec", "chunk_budget", "role_ratio", "deadline_pad",
                   "overload_threshold")

    def validate(self) -> None:
        if self.store_capacity < 2:
            raise ConfigError("observability.tune.store_capacity must be "
                              ">= 2 (a trend needs two points)")
        if self.store_max_series < 1:
            raise ConfigError(
                "observability.tune.store_max_series must be >= 1")
        if not 0.0 < self.store_ewma_alpha <= 1.0:
            raise ConfigError(
                "observability.tune.store_ewma_alpha must be in (0, 1]")
        if self.interval_iterations < 1:
            raise ConfigError(
                "observability.tune.interval_iterations must be >= 1")
        if self.hold_iterations < 1:
            raise ConfigError(
                "observability.tune.hold_iterations must be >= 1")
        if self.hysteresis < 0:
            raise ConfigError("observability.tune.hysteresis must be >= 0")
        if self.burn_ceiling <= 0:
            raise ConfigError("observability.tune.burn_ceiling must be > 0")
        if self.burn_weight < 0:
            raise ConfigError("observability.tune.burn_weight must be >= 0")
        if self.max_moves < 0:
            raise ConfigError("observability.tune.max_moves must be >= 0 "
                              "(0 = uncapped)")
        for k in self.knobs:
            if k not in self.KNOWN_KNOBS:
                raise ConfigError(
                    f"observability.tune.knobs: unknown knob '{k}' "
                    f"(known: {list(self.KNOWN_KNOBS)})")


@dataclass
class ProfilingConfig(ConfigModel):
    """Triggered deep profiling (``observability/profiler.py``): bounded
    ``jax.profiler`` capture windows opened on demand (SIGUSR2 / engine
    ``start_profile``), on a step schedule, or by telemetry the session
    already collects (SLO-burn over ceiling, goodput-slope collapse,
    steady-state recompile, hang-watchdog pre-fire) — parsed into
    per-entry device/host seconds and paired against the tpucost roofline
    (``profile_summary.json``). Off by default: the disabled path wires no
    hooks and never touches ``jax.profiler`` (zero extra dispatches or
    compiles, watchdog-asserted in tests)."""

    enabled: bool = False
    trace_dir: str = ""                # "" => <output_dir>/profile
    window_iterations: int = 8         # engine iterations/steps per window
    window_wall_s: float = 120.0       # hard wall ceiling on an open window
    profile_every_steps: int = 0       # scheduled windows (0 = off)
    capture_budget: int = 8            # total captures per session — a
    #   flapping trigger can never fill the disk
    keep_last: int = 4                 # on-disk capture dirs retained
    cooldown_iterations: int = 256     # per-trigger re-arm delay
    check_interval_iterations: int = 16  # telemetry-trigger poll cadence
    trigger_burn: bool = True          # TTFT/TPOT SLO burn over ceiling
    burn_ceiling: float = 2.0          # EWMA burn rate that opens a window
    trigger_goodput_slope: bool = True  # goodput EWMA slope collapse
    slope_floor: float = -0.01         # goodput_fraction slope per step
    trigger_recompile: bool = True     # steady-state recompile observed
    trigger_hang: bool = True          # hang-watchdog pre-fire capture
    hang_prefire_fraction: float = 0.5  # open at this fraction of deadline
    sigusr2: bool = True               # SIGUSR2 => on-demand window
    summary_file: str = "profile_summary.json"   # measured-vs-predicted
    hotspot_top_k: int = 5             # HLO-op hotspots kept per entry

    def validate(self) -> None:
        if self.window_iterations < 1:
            raise ConfigError(
                "observability.profiling.window_iterations must be >= 1")
        if self.window_wall_s <= 0:
            raise ConfigError(
                "observability.profiling.window_wall_s must be > 0")
        if self.profile_every_steps < 0:
            raise ConfigError(
                "observability.profiling.profile_every_steps must be >= 0 "
                "(0 = no schedule)")
        if self.capture_budget < 1:
            raise ConfigError(
                "observability.profiling.capture_budget must be >= 1")
        if self.keep_last < 1:
            raise ConfigError(
                "observability.profiling.keep_last must be >= 1")
        if self.cooldown_iterations < 0:
            raise ConfigError(
                "observability.profiling.cooldown_iterations must be >= 0")
        if self.check_interval_iterations < 1:
            raise ConfigError(
                "observability.profiling.check_interval_iterations must "
                "be >= 1")
        if self.burn_ceiling <= 0:
            raise ConfigError(
                "observability.profiling.burn_ceiling must be > 0")
        if not 0.0 < self.hang_prefire_fraction < 1.0:
            raise ConfigError(
                "observability.profiling.hang_prefire_fraction must be in "
                "(0, 1) — 1.0 would capture after the watchdog already "
                "fired")
        if self.hotspot_top_k < 1:
            raise ConfigError(
                "observability.profiling.hotspot_top_k must be >= 1")


@dataclass
class ObservabilityConfig(ConfigModel):
    """Gate for ``deepspeed_tpu.observability`` — span tracer, metrics
    registry file output, recompile watchdog, memory gauges. Off by default:
    a disabled session writes no files and records nothing but spans while a
    ``jax.profiler`` capture is open; the monitor writers still work
    independently of this switch."""

    enabled: bool = False
    output_dir: str = ""               # "" => ./dstpu_obs
    trace_file: str = "trace.jsonl"            # append-only span records
    metrics_file: str = "metrics.jsonl"        # registry snapshot dump
    all_ranks: bool = False            # False => rank-0 only (reference norm)
    max_spans: int = 100_000           # in-memory span cap (JSONL unaffected)
    recompile_watchdog: bool = True    # jax.monitoring compile listeners
    steady_state_step: int = 10        # recompiles past this step warn
    memory_poll_steps: int = 10        # device-memory gauge cadence
    profile_dir: str = "/tmp/dstpu_trace"  # engine.start_profile() trace dir
    # flight recorder: bounded ring of recent events + crash-bundle dump
    # (observability/flightrecorder.py); active whenever the session is
    # enabled — recording is a deque append, dump only on crash/signal/hang
    flight_recorder: bool = True
    flight_ring_size: int = 4096       # events kept in the ring
    flight_dump_dir: str = ""          # "" => <output_dir>/crash
    flight_sigusr1: bool = True        # SIGUSR1 => dump (main thread only)
    # hang watchdog thread (observability/hangdetect.py): opt-in — it spawns
    # a thread and can abort the process, so an enabled session does not get
    # one implicitly
    hang_watchdog: bool = False
    hang_timeout_factor: float = 8.0   # deadline = max(k*median step, floor)
    hang_timeout_floor_s: float = 120.0
    hang_poll_interval_s: float = 5.0  # watchdog thread check cadence
    hang_abort: bool = False           # fire => os._exit(hang_exit_code)
    hang_exit_code: int = 113          # distinct from python/jax exit codes
    # goodput accounting (observability/goodput.py): step-time buckets +
    # goodput_fraction / mfu / tokens_per_sec gauges; span-derived, so the
    # per-step cost is a few dict updates
    goodput: bool = True
    # fleet health (observability/fleethealth.py): cross-rank aggregation of
    # per-rank health stats at a step cadence, straggler detection, and the
    # replica-divergence/SDC sentinel. The cadence step pays one host sync
    # (materialising loss/grad-norm) plus one cross-process gather; every
    # other step costs nothing.
    fleet_health: bool = False
    fleet_cadence_steps: int = 10      # aggregate every N steps
    fleet_straggler_factor: float = 2.0  # straggler: step time > k * median
    fleet_window: int = 32             # rolling step-time window per rank
    fleet_divergence_tolerance: float = 1e-4  # relative spread that trips
    fleet_param_checksum: bool = False  # per-replica param checksum compare
    # numerics sentinel (observability/numerics.py): fused isfinite +
    # loss-spike check INSIDE the jitted train step; the flag is a device
    # scalar threaded through the step (no extra program, no host sync) and
    # is materialised every numerics_check_steps steps
    numerics_sentinel: bool = False
    numerics_action: str = "warn"      # warn | skip_step | abort
    numerics_check_steps: int = 10     # host-side flag check cadence
    numerics_spike_factor: float = 0.0  # loss > k * EMA trips; 0 disables
    numerics_spike_warmup_steps: int = 20  # steps before spike check arms
    # request-scoped serving traces (observability/reqtrace.py): a trace_id
    # minted at submit follows the request through routing, queue wait,
    # prefill chunks, KV handoffs, decode participation, preemption,
    # resubmission and fork lineage. Head sampling decides at mint
    # (trace_sample_rate); tail retention ALWAYS keeps outliers
    # (deadline_exceeded, shed, preempted, resubmitted, TTFT > SLO).
    request_tracing: bool = False
    trace_sample_rate: float = 1.0     # head-sampled fraction of traces
    trace_keep: int = 1024             # retained traces in memory (Chrome
    #   export / bench top-k); the JSONL keeps everything retained
    trace_max_events: int = 256        # events kept per trace (aggregates
    #   stay exact past the cap; dropped_events counts the overflow)
    trace_decode_sample: int = 16      # record every Nth decode/verify
    #   participation event per request (never per-token)
    trace_ttft_slo_ms: float = 0.0     # TTFT outlier threshold (0 = off)
    reqtrace_file: str = "reqtrace.jsonl"          # retained-trace records
    reqtrace_chrome_file: str = "reqtrace_chrome.json"  # chrome export
    # serving goodput accountant (observability/servegoodput.py):
    # per-iteration wall-time buckets on ServingEngine.step (prefill/
    # decode/verify/draft/sample-host/scheduling-host/handoff/compile/idle
    # — buckets sum to wall), per replica, plus TTFT/TPOT SLO burn rates
    serve_goodput: bool = False
    serve_ttft_slo_ms: float = 0.0     # burn-rate SLOs (0 = gauge off)
    serve_tpot_slo_ms: float = 0.0
    serve_slo_budget: float = 0.01     # allowed breach fraction: burn rate
    #   = observed breach fraction / this (1.0 = spending on budget)
    # closed-loop telemetry (observability/timeseries.py +
    # autotuning/livetuner.py): metric time-series store + live-signal
    # serving controller — docs/observability.md "Closed loop"
    tune: TuneConfig = field(default_factory=TuneConfig)
    # triggered deep profiling (observability/profiler.py): telemetry-
    # triggered jax.profiler capture windows + per-entry device-time
    # attribution — docs/observability.md "Deep profiling"
    profiling: ProfilingConfig = field(default_factory=ProfilingConfig)

    def validate(self) -> None:
        if isinstance(self.tune, dict):
            # direct-constructor convenience (same pattern as
            # ServingConfig.speculative): from_dict coerces nested
            # configs, the plain dataclass constructor does not
            self.tune = TuneConfig.from_dict(self.tune)
        self.tune.validate()
        if isinstance(self.profiling, dict):
            self.profiling = ProfilingConfig.from_dict(self.profiling)
        self.profiling.validate()
        if self.max_spans < 1:
            raise ConfigError("observability.max_spans must be >= 1")
        if self.memory_poll_steps < 1:
            raise ConfigError("observability.memory_poll_steps must be >= 1")
        if self.steady_state_step < 0:
            raise ConfigError("observability.steady_state_step must be >= 0")
        if self.flight_ring_size < 1:
            raise ConfigError("observability.flight_ring_size must be >= 1")
        if self.hang_timeout_factor <= 0:
            raise ConfigError("observability.hang_timeout_factor must be > 0")
        if self.hang_timeout_floor_s <= 0:
            raise ConfigError("observability.hang_timeout_floor_s must be > 0")
        if self.hang_poll_interval_s <= 0:
            raise ConfigError("observability.hang_poll_interval_s must be > 0")
        if not 1 <= self.hang_exit_code <= 255:
            raise ConfigError("observability.hang_exit_code must be in 1..255")
        if self.fleet_cadence_steps < 1:
            raise ConfigError("observability.fleet_cadence_steps must be >= 1")
        if self.fleet_straggler_factor <= 1.0:
            raise ConfigError(
                "observability.fleet_straggler_factor must be > 1 (a factor "
                "<= 1 would flag the median rank itself)")
        if self.fleet_window < 1:
            raise ConfigError("observability.fleet_window must be >= 1")
        if self.fleet_divergence_tolerance < 0:
            raise ConfigError(
                "observability.fleet_divergence_tolerance must be >= 0")
        if self.numerics_action not in ("warn", "skip_step", "abort"):
            raise ConfigError(
                "observability.numerics_action must be warn|skip_step|abort, "
                f"got '{self.numerics_action}'")
        if self.numerics_check_steps < 1:
            raise ConfigError(
                "observability.numerics_check_steps must be >= 1")
        if self.numerics_spike_factor < 0:
            raise ConfigError(
                "observability.numerics_spike_factor must be >= 0 "
                "(0 disables the loss-spike check)")
        if self.numerics_spike_warmup_steps < 0:
            raise ConfigError(
                "observability.numerics_spike_warmup_steps must be >= 0")
        if not 0.0 <= self.trace_sample_rate <= 1.0:
            raise ConfigError(
                "observability.trace_sample_rate must be in [0, 1], got "
                f"{self.trace_sample_rate}")
        if self.trace_keep < 1:
            raise ConfigError("observability.trace_keep must be >= 1")
        if self.trace_max_events < 8:
            raise ConfigError(
                "observability.trace_max_events must be >= 8 (a trace needs "
                "room for its causal chain)")
        if self.trace_decode_sample < 1:
            raise ConfigError(
                "observability.trace_decode_sample must be >= 1")
        if self.trace_ttft_slo_ms < 0:
            raise ConfigError(
                "observability.trace_ttft_slo_ms must be >= 0")
        if self.serve_ttft_slo_ms < 0 or self.serve_tpot_slo_ms < 0:
            raise ConfigError(
                "observability.serve_{ttft,tpot}_slo_ms must be >= 0")
        if not 0.0 < self.serve_slo_budget <= 1.0:
            raise ConfigError(
                "observability.serve_slo_budget must be in (0, 1], got "
                f"{self.serve_slo_budget}")


@dataclass
class ResilienceConfig(ConfigModel):
    """Self-healing training session policy (``runtime/session.py`` /
    ``deepspeed_tpu.run_training_session``) — what the supervisor does when
    the observability layer names a failure. The detection side lives in
    :class:`ObservabilityConfig` (numerics sentinel, hang watchdog, fleet
    health); this section is the remediation side: which policy each
    failure class maps to, the rollback/restart budgets, and the
    checkpoint cadence that bounds how much work a rollback loses. See
    docs/resilience.md for the failure→policy table."""

    save_dir: str = ""                 # checkpoint root ("" => the session's
    #   save_dir argument is required)
    checkpoint_every_steps: int = 50   # save cadence — the rollback horizon
    verify_checkpoints: bool = True    # crc-verify on load; fall back to the
    #   previous good tag on corruption (runtime/checkpoint.py)
    on_numerics: str = "rollback"      # NumericsTrip (action='abort') →
    #   rollback | skip | raise
    on_crash: str = "raise"            # other train_batch exceptions →
    #   rollback | raise (raise: a bug should fail loudly, not retry-loop)
    on_hang: str = "escalate"          # hang-watchdog fires → escalate
    #   (dump → soft restart → hard restart) | off (leave watchdog policy)
    hang_soft_restarts: int = 1        # in-process soft-restart budget: a
    #   hang past it escalates to the agent — RecoveryExhausted when
    #   control returned (worker exits nonzero), the watchdog's own
    #   hang_exit_code abort when it never did
    max_rollbacks: int = 3             # rollback budget per incarnation —
    #   past it the failure re-raises (a persistent fault must escalate to
    #   the agent, not rollback-loop forever)
    straggler_patience: int = 2        # consecutive fleet straggler verdicts
    #   against the same rank before an eviction request
    min_world: int = 1                 # never request eviction below this
    #   world size (the agent's min_workers floors the actual shrink too)
    record_losses: bool = True         # keep the per-step loss series on the
    #   session (one host sync per step — disable for production runs)

    def validate(self) -> None:
        if self.checkpoint_every_steps < 1:
            raise ConfigError(
                "resilience.checkpoint_every_steps must be >= 1")
        if self.on_numerics not in ("rollback", "skip", "raise"):
            raise ConfigError(
                "resilience.on_numerics must be rollback|skip|raise, "
                f"got '{self.on_numerics}'")
        if self.on_crash not in ("rollback", "raise"):
            raise ConfigError("resilience.on_crash must be rollback|raise, "
                              f"got '{self.on_crash}'")
        if self.on_hang not in ("escalate", "off"):
            raise ConfigError("resilience.on_hang must be escalate|off, "
                              f"got '{self.on_hang}'")
        if self.hang_soft_restarts < 0:
            raise ConfigError("resilience.hang_soft_restarts must be >= 0")
        if self.max_rollbacks < 0:
            raise ConfigError("resilience.max_rollbacks must be >= 0")
        if self.straggler_patience < 1:
            raise ConfigError("resilience.straggler_patience must be >= 1")
        if self.min_world < 1:
            raise ConfigError("resilience.min_world must be >= 1")


@dataclass
class SpeculativeConfig(ConfigModel):
    """Speculative decoding (``deepspeed_tpu/serving/speculative.py``):
    a drafter proposes up to ``num_draft_tokens`` continuation tokens per
    request per iteration and the target model scores them all in ONE
    R×(K+1) verify dispatch. Acceptance is lossless AND bit-stable: every
    position samples with the request's (engine seed, request seed,
    output-token-index) key — the exact key the non-speculative decode
    would use — so speculation changes latency, never tokens.
    ``num_draft_tokens`` is the only SHAPE parameter (the verify program's
    token width); everything else — per-row proposal counts, acceptance
    mixes, pressure-disabled rows — is data."""

    mode: str = "off"                  # 'off' | 'ngram' | 'draft'
    num_draft_tokens: int = 4          # K: verify program width is K+1
    ngram_max: int = 3                 # prompt-lookup match length (tried
    ngram_min: int = 1                 # longest-first down to ngram_min)
    min_free_blocks: int = 0           # below this many free pool blocks,
    #   no row proposes (global pressure guard); per-row disable is
    #   automatic when a row's speculative block extension cannot be
    #   allocated without preempting — speculation never preempts
    draft_chunk: int = 0               # draft-model prefill catch-up chunk
    #   (tokens); 0 => the serving prefill_chunk

    def validate(self) -> None:
        if self.mode not in ("off", "ngram", "draft"):
            raise ConfigError("speculative.mode must be 'off', 'ngram' or "
                              f"'draft', got '{self.mode}'")
        if self.num_draft_tokens < 1:
            raise ConfigError("speculative.num_draft_tokens must be >= 1")
        if not 1 <= self.ngram_min <= self.ngram_max:
            raise ConfigError(
                f"speculative ngram lengths need 1 <= ngram_min "
                f"({self.ngram_min}) <= ngram_max ({self.ngram_max})")
        if self.min_free_blocks < 0:
            raise ConfigError("speculative.min_free_blocks must be >= 0")
        if self.draft_chunk < 0:
            raise ConfigError("speculative.draft_chunk must be >= 0")


@dataclass
class ServingConfig(ConfigModel):
    """Continuous-batching serving layer (``deepspeed_tpu/serving``) — the
    MII/FastGen analog: paged KV arena + iteration-level scheduler +
    streaming front end. Every knob here is a STATIC shape parameter of the
    two serving programs (prefill-chunk and decode), so changing one after
    engine construction means a recompile — the jit-cache discipline the
    whole layer is built around."""

    block_size: int = 16               # KV tokens per arena block
    num_blocks: int = 0                # allocatable blocks in the shared
    #   pool (excluding the reserved scratch block); 0 => fully provisioned
    #   (max_seqs × blocks-per-sequence — no sharing pressure, never
    #   preempts). Undersize it deliberately to share HBM across requests;
    #   the scheduler preempts by block eviction when the pool runs dry.
    max_seqs: int = 8                  # decode batch rows (max concurrent
    #   decoding sequences; admission is iteration-level — rows recycle)
    max_model_len: int = 256           # per-sequence token budget
    #   (prompt + generated); must split into whole blocks
    prefill_chunk: int = 64            # tokens per prefill chunk — long
    #   prompts prefill in chunks interleaved with decode steps so TTFT of
    #   queued requests stays bounded (Sarathi/Orca-style chunked prefill);
    #   must be a multiple of block_size so a chunk never strands a
    #   partially-used block it can't finish
    max_queue: int = 256               # backpressure: submit() beyond this
    #   many in-flight (queued + running) requests raises
    fairness: str = "fair"             # 'fair' (least-service tenant first,
    #   EDF within a tenant) | 'fcfs' (arrival order)
    default_max_new_tokens: int = 64
    seed: int = 0                      # sampling stream seed
    prefix_cache: bool = True          # content-hashed prompt-prefix
    #   sharing: cached full blocks join a new request's table by refcount
    #   (copy-on-write on first divergent write) and their prefill chunks
    #   are skipped entirely
    speculative: SpeculativeConfig = field(
        default_factory=SpeculativeConfig)  # draft/verify speculative
    #   decoding over the same arena; 'draft' mode additionally needs a
    #   draft model passed to ServingEngine/init_serving

    def blocks_per_seq(self) -> int:
        return self.max_model_len // self.block_size

    def pool_blocks(self) -> int:
        """Allocatable pool size (0 => fully provisioned)."""
        return (self.num_blocks if self.num_blocks
                else self.max_seqs * self.blocks_per_seq())

    def validate(self) -> None:
        if isinstance(self.speculative, dict):
            # direct-constructor convenience: ServingConfig(speculative=
            # {"mode": "ngram"}) — from_dict coerces nested configs, the
            # plain dataclass constructor does not
            self.speculative = SpeculativeConfig.from_dict(self.speculative)
        if self.block_size < 1:
            raise ConfigError("serving.block_size must be >= 1")
        if self.max_model_len < 1:
            raise ConfigError("serving.max_model_len must be >= 1")
        if self.max_model_len % self.block_size != 0:
            raise ConfigError(
                f"serving.max_model_len={self.max_model_len} must be a "
                f"multiple of block_size={self.block_size} (whole-block "
                "sequence budget — see inference/kv_cache.py)")
        if self.prefill_chunk < 1:
            raise ConfigError("serving.prefill_chunk must be >= 1")
        if self.prefill_chunk % self.block_size != 0:
            raise ConfigError(
                f"serving.prefill_chunk={self.prefill_chunk} must be a "
                f"multiple of block_size={self.block_size} — a chunk that "
                "ends mid-block would allocate a block it cannot fill")
        if self.max_seqs < 1:
            raise ConfigError("serving.max_seqs must be >= 1")
        if self.max_queue < 1:
            raise ConfigError("serving.max_queue must be >= 1")
        if self.num_blocks and self.num_blocks < self.blocks_per_seq():
            raise ConfigError(
                f"serving.num_blocks={self.num_blocks} cannot hold even one "
                f"max-length sequence ({self.blocks_per_seq()} blocks) — "
                "the scheduler could never make progress")
        if self.fairness not in ("fair", "fcfs"):
            raise ConfigError("serving.fairness must be 'fair' or 'fcfs', "
                              f"got '{self.fairness}'")
        if self.default_max_new_tokens < 1:
            raise ConfigError("serving.default_max_new_tokens must be >= 1")
        self.speculative.validate()
        if (self.speculative.mode != "off"
                and self.speculative.num_draft_tokens + 1
                > self.max_model_len):
            raise ConfigError(
                f"speculative.num_draft_tokens="
                f"{self.speculative.num_draft_tokens} cannot exceed "
                f"serving.max_model_len={self.max_model_len} - 1 — the "
                "verify program's token width would outgrow every "
                "sequence budget")
        if (self.speculative.mode == "draft"
                and self.speculative.draft_chunk % self.block_size != 0):
            raise ConfigError(
                f"speculative.draft_chunk={self.speculative.draft_chunk} "
                f"must be a multiple of block_size={self.block_size} "
                "(the draft prefill chunks the same block-aligned arena)")


@dataclass
class FleetConfig(ConfigModel):
    """Serving fleet (``deepspeed_tpu/serving/fleet``): a data-plane router
    over N ``ServingEngine`` replicas, optionally split into prefill and
    decode pools (DistServe-style disaggregation with KV block handoff)."""

    policy: str = "kv_occupancy"   # routing policy: 'round_robin' |
    #   'least_queue' (fewest in-flight requests) | 'kv_occupancy' (lowest
    #   arena occupancy, tie-broken by queue) | 'affinity' (prefix-cache
    #   locality: requests sharing a first prompt block follow earlier
    #   ones to the replica whose prefix cache is warm)
    affinity_overload: float = 0.85  # arena occupancy above which an
    #   affinity-warm replica is skipped (locality never beats liveness)
    max_resubmits: int = 3         # per-request resubmission budget across
    #   replica deaths; exhausting it cancels the request
    handoff_retries: int = 1       # a handoff whose TRANSFER fails (chaos
    #   handoff_fail / kv_import raising) retries on this many other decode
    #   replicas before falling back to decoding in place (a handoff the
    #   decode pool cannot TAKE falls back immediately — degraded but live)
    # -- replica health verdicts (router-measured, host-side) --
    health_window: int = 8         # rolling step-time samples per replica
    #   a verdict needs before the slow detector trusts the median
    health_warmup_steps: int = 4   # per-incarnation measured steps to
    #   DISCARD before sampling begins: the first dispatches JIT-compile
    #   inside the measured span, and compile jitter must never convict
    #   a healthy replica
    slow_factor: float = 3.0       # quarantine a replica whose rolling
    #   median step time exceeds factor × the median of the OTHER alive
    #   replicas' medians (relative straggler detection, like
    #   fleet_straggler_factor on the training side)
    slow_min_step_s: float = 0.25  # absolute floor for the RELATIVE slow
    #   verdict: a replica under this median is never convicted by ratio
    #   alone — at sub-floor step times, scheduler noise makes any ratio
    #   meaningless (3ms vs 1ms is not a straggler)
    step_time_slo_s: float = 0.0   # absolute per-iteration SLO: a replica
    #   whose rolling median step time exceeds this is quarantined
    #   regardless of the fleet (0 = off)
    ttft_slo_s: float = 0.0        # fleet TTFT SLO: a first token arriving
    #   later than this after submit counts a health breach against the
    #   serving replica and quarantines it (0 = off)
    # -- quarantine / revival ladder (iteration-denominated: deterministic
    #    under the injectable clock AND under the real driver thread) --
    quarantine_iterations: int = 16  # base quarantine length; doubles per
    #   repeat offense (the elastic agent's backoff ladder, in router
    #   iterations instead of seconds)
    auto_revive: bool = True       # dead replicas are rebuilt (shared
    #   weights + already-compiled programs) and re-admitted via probation
    revive_after_iterations: int = 8   # death → revival-attempt backoff
    #   base, doubling per death of the same replica
    breaker_incidents: int = 4     # per-replica circuit breaker: more than
    #   this many incidents (deaths + quarantines) retires the replica
    #   permanently — a flapping replica must not flap forever
    probation_requests: int = 3    # clean completions a revived/
    #   un-quarantined replica needs before regaining full routing weight
    probation_share: float = 0.25  # max fraction of the fleet's in-flight
    #   requests a probation replica may hold (floor of one)
    # -- overload control --
    admission_control: bool = True  # deadline-infeasibility shedding in
    #   submit(): a request whose deadline cannot be met at current queue
    #   depth + measured TPOT raises Overloaded(retry_after_s=...) instead
    #   of being admitted to die
    overload_occupancy: float = 0.92   # mean alive-replica arena occupancy
    #   that counts as overload pressure
    overload_queue_depth: int = 0  # fleet-wide queued (unadmitted) requests
    #   that count as pressure (0 = occupancy signal only)
    overload_up_iterations: int = 4    # consecutive pressured iterations
    #   per degraded-ladder rung up
    overload_down_iterations: int = 8  # consecutive calm iterations per
    #   rung down (hysteresis: recovery is slower than degradation)

    def validate(self) -> None:
        if self.policy not in ("round_robin", "least_queue",
                               "kv_occupancy", "affinity"):
            raise ConfigError(
                "fleet.policy must be 'round_robin', 'least_queue', "
                f"'kv_occupancy' or 'affinity', got '{self.policy}'")
        if not 0.0 < self.affinity_overload <= 1.0:
            raise ConfigError("fleet.affinity_overload must be in (0, 1], "
                              f"got {self.affinity_overload}")
        if self.max_resubmits < 0:
            raise ConfigError("fleet.max_resubmits must be >= 0")
        if self.handoff_retries < 0:
            raise ConfigError("fleet.handoff_retries must be >= 0")
        if self.health_window < 2:
            raise ConfigError("fleet.health_window must be >= 2")
        if self.health_warmup_steps < 0:
            raise ConfigError("fleet.health_warmup_steps must be >= 0")
        if self.slow_factor <= 1.0:
            raise ConfigError("fleet.slow_factor must be > 1.0 — a factor "
                              "at/below 1 quarantines the median replica")
        if self.slow_min_step_s < 0:
            raise ConfigError("fleet.slow_min_step_s must be >= 0")
        if self.step_time_slo_s < 0 or self.ttft_slo_s < 0:
            raise ConfigError("fleet SLOs must be >= 0 (0 = off)")
        if self.quarantine_iterations < 1:
            raise ConfigError("fleet.quarantine_iterations must be >= 1")
        if self.revive_after_iterations < 1:
            raise ConfigError("fleet.revive_after_iterations must be >= 1")
        if self.breaker_incidents < 1:
            raise ConfigError("fleet.breaker_incidents must be >= 1")
        if self.probation_requests < 1:
            raise ConfigError("fleet.probation_requests must be >= 1")
        if not 0.0 < self.probation_share <= 1.0:
            raise ConfigError("fleet.probation_share must be in (0, 1], "
                              f"got {self.probation_share}")
        if not 0.0 < self.overload_occupancy <= 1.0:
            raise ConfigError("fleet.overload_occupancy must be in (0, 1]")
        if self.overload_queue_depth < 0:
            raise ConfigError("fleet.overload_queue_depth must be >= 0")
        if self.overload_up_iterations < 1 \
                or self.overload_down_iterations < 1:
            raise ConfigError(
                "fleet.overload_{up,down}_iterations must be >= 1")


@dataclass
class RLHFConfig(ConfigModel):
    """RLHF post-training (``deepspeed_tpu/rlhf`` — the DeepSpeed-Chat
    step-3 analog over the hybrid engine v2): per-iteration
    generate → score → train → flip, with rollouts running through the
    serving stack (continuous batching, prefix sharing, ``fork(n)``
    candidate groups, optional speculative decoding) and every rollout
    bit-exactly replayable from its manifest (docs/rlhf.md)."""

    algo: str = "grpo"             # 'grpo' (group-normalized advantages,
    #   no critic) | 'ppo' (PPO-clip with batch-whitened reward advantages)
    group_n: int = 4               # candidate samples per prompt — ONE
    #   prefill + n-1 COW forks through the refcounted block tables
    temperature: float = 0.7       # rollout sampling
    top_k: int = 0
    top_p: float = 1.0
    max_new_tokens: int = 32       # rollout response budget
    eos_token_id: Optional[int] = None
    clip_ratio: float = 0.2        # PPO clip epsilon on the policy ratio
    kl_coef: float = 0.05          # k3-estimator KL penalty vs the frozen
    #   reference (0 disables; the reference pass is skipped entirely)
    whiten_advantages: bool = True  # 'ppo' only: normalize rewards across
    #   the batch before broadcasting them as advantages
    replay_verify: bool = False    # after every rollout phase, replay the
    #   manifest with speculation toggled OPPOSITE and assert bit-exact
    #   token streams (the determinism contract, continuously enforced —
    #   one extra serving pass per iteration)

    def validate(self) -> None:
        if self.algo not in ("grpo", "ppo"):
            raise ConfigError(
                f"rlhf.algo must be 'grpo' or 'ppo', got '{self.algo}'")
        if self.group_n < 1:
            raise ConfigError("rlhf.group_n must be >= 1")
        if self.algo == "grpo" and self.group_n < 2:
            raise ConfigError(
                "rlhf.algo='grpo' needs group_n >= 2 — the advantage is "
                "normalized within each prompt's candidate group")
        if self.temperature < 0:
            raise ConfigError("rlhf.temperature must be >= 0")
        if self.max_new_tokens < 1:
            raise ConfigError("rlhf.max_new_tokens must be >= 1")
        if self.clip_ratio <= 0:
            raise ConfigError("rlhf.clip_ratio must be > 0")
        if self.kl_coef < 0:
            raise ConfigError("rlhf.kl_coef must be >= 0")


@dataclass
class ElasticityConfig(ConfigModel):
    """Reference: elasticity/config.py — pure batch/world-size math."""

    enabled: bool = False
    max_train_batch_size: int = 2000
    micro_batch_sizes: List[int] = field(default_factory=lambda: [2, 4, 6])
    min_gpus: int = 1
    max_gpus: int = 10000
    min_time: int = 0
    version: float = 0.1
    ignore_non_elastic_batch_info: bool = False
    prefer_larger_batch: bool = True


@dataclass
class CurriculumConfig(ConfigModel):
    """Reference: curriculum_learning section (legacy) / data_efficiency."""

    enabled: bool = False
    curriculum_type: str = "seqlen"
    min_difficulty: int = 1
    max_difficulty: int = 10
    schedule_type: str = "fixed_linear"
    schedule_config: Dict[str, Any] = field(default_factory=dict)


@dataclass
class AIOConfig(ConfigModel):
    """Reference: the ``aio`` section (runtime/config.py) driving csrc/aio knobs."""

    block_size: int = 1048576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True


@dataclass
class CheckpointConfig(ConfigModel):
    """Reference: checkpoint section keys (tag_validation etc.)."""

    tag_validation: str = "Warn"  # Ignore | Warn | Fail
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write_pipeline: bool = False
    async_save: bool = False

    def validate(self) -> None:
        if self.tag_validation.lower() not in ("ignore", "warn", "fail"):
            raise ConfigError("checkpoint.tag_validation must be Ignore|Warn|Fail")


@dataclass
class ProgressiveLayerDropConfig(ConfigModel):
    """Reference: progressive_layer_drop section (runtime/engine.py:283,
    progressive_layer_drop.py:10)."""

    enabled: bool = False
    theta: float = 0.5
    gamma: float = 0.001

    def validate(self) -> None:
        # theta is the keep-probability floor the decay converges to; 0 would
        # drive the deepest layer's keep_p to 0 (and its 1/keep_p rescale
        # unbounded), so require a positive limit
        if not 0.0 < self.theta <= 1.0:
            raise ConfigError(
                f"progressive_layer_drop.theta must be in (0,1], got {self.theta}")
        if self.gamma < 0.0:
            raise ConfigError(
                f"progressive_layer_drop.gamma must be >= 0, got {self.gamma}")


@dataclass
class DataEfficiencyConfig(ConfigModel):
    enabled: bool = False
    seed: int = 1234
    data_sampling: Dict[str, Any] = field(default_factory=dict)
    data_routing: Dict[str, Any] = field(default_factory=dict)


@dataclass
class CompressionConfig(ConfigModel):
    """Reference: compression/config.py — accepted wholesale; consumed by
    deepspeed_tpu.compression."""

    weight_quantization: Dict[str, Any] = field(default_factory=dict)
    activation_quantization: Dict[str, Any] = field(default_factory=dict)
    sparse_pruning: Dict[str, Any] = field(default_factory=dict)
    row_pruning: Dict[str, Any] = field(default_factory=dict)
    head_pruning: Dict[str, Any] = field(default_factory=dict)
    channel_pruning: Dict[str, Any] = field(default_factory=dict)
    layer_reduction: Dict[str, Any] = field(default_factory=dict)


# ---------------------------------------------------------------------------
# root config
# ---------------------------------------------------------------------------


@dataclass
class CompileCacheConfig(ConfigModel):
    """Persistent XLA compilation cache (jax_compilation_cache_dir).

    The analog of the reference's JIT-extension build cache (op_builder
    caches compiled .so files under TORCH_EXTENSIONS_DIR): compiled step
    programs survive process restarts. Essential at the >10B offload tier,
    where the segment programs can take minutes to compile — with the cache
    they compile ONCE (optionally incrementally, see
    ``ParamOffloadExecutor.compile_step_programs``) and every later run
    loads them from disk. Default on; the directory is
    ``JAX_COMPILATION_CACHE_DIR`` when set, else a fixed path in the
    checkout (``utils/compile_cache.py``) — not a setting here."""

    enabled: bool = True
    min_compile_time_secs: float = 1.0


@dataclass
class Config(ConfigModel):
    """Root config — analog of ``DeepSpeedConfig`` (runtime/config.py:674)."""

    train_batch_size: int = 0
    train_micro_batch_size_per_gpu: int = 0
    gradient_accumulation_steps: int = 0

    steps_per_print: int = 10
    wall_clock_breakdown: bool = False
    dump_state: bool = False
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    gradient_clipping: float = 0.0
    disable_allgather: bool = False
    communication_data_type: Optional[str] = None
    seed: int = 1234

    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    scheduler: Optional[SchedulerConfig] = None
    fp16: FP16Config = field(default_factory=FP16Config)
    bf16: BF16Config = field(default_factory=BF16Config)
    zero_optimization: ZeroConfig = field(default_factory=ZeroConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    activation_checkpointing: ActivationCheckpointingConfig = field(
        default_factory=ActivationCheckpointingConfig)
    comms_logger: CommsLoggerConfig = field(default_factory=CommsLoggerConfig)
    flops_profiler: FlopsProfilerConfig = field(default_factory=FlopsProfilerConfig)
    monitor: MonitorConfig = field(default_factory=MonitorConfig)
    observability: ObservabilityConfig = field(
        default_factory=ObservabilityConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    rlhf: RLHFConfig = field(default_factory=RLHFConfig)
    elasticity: ElasticityConfig = field(default_factory=ElasticityConfig)
    curriculum_learning: CurriculumConfig = field(default_factory=CurriculumConfig)
    progressive_layer_drop: ProgressiveLayerDropConfig = field(
        default_factory=ProgressiveLayerDropConfig)
    data_efficiency: DataEfficiencyConfig = field(default_factory=DataEfficiencyConfig)
    compression_training: CompressionConfig = field(default_factory=CompressionConfig)
    aio: AIOConfig = field(default_factory=AIOConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    compile_cache: CompileCacheConfig = field(default_factory=CompileCacheConfig)

    # monitor sections may also appear at top level (reference accepts both)
    tensorboard: Optional[TensorboardConfig] = None
    wandb: Optional[WandbConfig] = None
    csv_monitor: Optional[CSVConfig] = None

    DEPRECATED = {
        "train_micro_batch_size": ("train_micro_batch_size_per_gpu", "renamed"),
        "gradient_accumulation_dtype": (None, "grad accumulation is fp32 on TPU"),
    }

    def __post_init__(self):
        # lift top-level monitor sections into .monitor (reference behavior)
        if self.tensorboard is not None:
            self.monitor = self.monitor.replace(tensorboard=self.tensorboard)
        if self.wandb is not None:
            self.monitor = self.monitor.replace(wandb=self.wandb)
        if self.csv_monitor is not None:
            self.monitor = self.monitor.replace(csv_monitor=self.csv_monitor)

    # -- batch triad ------------------------------------------------------
    def resolve_batch_sizes(self, dp_world_size: int) -> "Config":
        """Resolve (train_batch_size, micro_batch, grad_accum) given the data-
        parallel world size. Mirrors reference runtime/config.py:888
        ``_set_batch_related_parameters``: any two determine the third; one
        given infers the rest with grad_accum=1; none → error."""
        tb, mb, ga = self.train_batch_size, self.train_micro_batch_size_per_gpu, self.gradient_accumulation_steps
        if tb and mb and ga:
            if tb != mb * ga * dp_world_size:
                raise ConfigError(
                    f"train_batch_size ({tb}) != micro_batch ({mb}) * grad_accum ({ga}) "
                    f"* dp_world ({dp_world_size})")
        elif tb and mb:
            ga, rem = divmod(tb, mb * dp_world_size)
            if rem or ga < 1:
                raise ConfigError(
                    f"train_batch_size {tb} not divisible by micro_batch {mb} * dp {dp_world_size}")
        elif tb and ga:
            mb, rem = divmod(tb, ga * dp_world_size)
            if rem or mb < 1:
                raise ConfigError(
                    f"train_batch_size {tb} not divisible by grad_accum {ga} * dp {dp_world_size}")
        elif mb and ga:
            tb = mb * ga * dp_world_size
        elif tb:
            mb, rem = divmod(tb, dp_world_size)
            if rem:
                raise ConfigError(f"train_batch_size {tb} not divisible by dp world {dp_world_size}")
            ga = 1
        elif mb:
            ga = 1
            tb = mb * dp_world_size
        else:
            raise ConfigError(
                "one of train_batch_size / train_micro_batch_size_per_gpu must be set")
        return self.replace(train_batch_size=tb, train_micro_batch_size_per_gpu=mb,
                            gradient_accumulation_steps=ga)

    def validate(self) -> None:
        if self.fp16.enabled and self.bf16.enabled:
            raise ConfigError("fp16 and bf16 cannot both be enabled")
        if self.gradient_clipping < 0:
            raise ConfigError("gradient_clipping must be >= 0")

    # -- convenience ------------------------------------------------------
    @property
    def precision_dtype(self) -> str:
        if self.bf16.enabled:
            return "bfloat16"
        if self.fp16.enabled:
            return "float16"
        return "float32"

    @property
    def zero_stage(self) -> int:
        return self.zero_optimization.stage


def load_config(config: Union[str, Mapping[str, Any], Config, None]) -> Config:
    """Accept a path, a dict, an existing Config, or None (defaults)."""
    if config is None:
        return Config()
    if isinstance(config, Config):
        return config
    if isinstance(config, str):
        with open(config) as fh:
            config = json.load(fh)
    if not isinstance(config, Mapping):
        raise ConfigError(f"config must be a path, dict, or Config — got {type(config)}")
    return Config.from_dict(config)

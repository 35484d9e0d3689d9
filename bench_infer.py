#!/usr/bin/env python
"""Inference benchmark — TTFT + decode throughput (BASELINE tracked config #5,
the driver's "DS-Inference p50 TTFT" metric).

Prints ONE JSON line:
    {"metric": ..., "value": <p50 TTFT ms>, "unit": "ms",
     "decode_tokens_per_sec": ..., "roofline_frac": ..., "vs_baseline": ...}

``--serving`` (or BENCH_INFER_MODE=serving): continuous-batching load test
instead — synthetic Poisson arrivals with mixed prompt lengths through
``ServingEngine`` (deepspeed_tpu/serving), reporting TTFT p50/p99, time per
output token, tokens/s and arena occupancy, with the serving/* metrics
dumped to BENCH_metrics_serve.jsonl. ``--paged-kernel on|off`` pins one
read path; unset runs the A/B (Pallas paged kernels vs dense gather view)
over the same trace plus a prefix-reuse workload (shared 1k-token system
prompt, two rounds), recording the TTFT/TPOT deltas and each arm's tpucost
arena-read bytes. ``--spec ngram|draft`` runs a speculative-decoding A/B
instead (that drafter vs spec-off, SAME trace with a repetitive-text
share): acceptance rate, proposed-vs-emitted tokens,
emitted-per-target-dispatch, drafter time share, TTFT/TPOT deltas and the
per-arm verify-program tpucost land in the record and
BENCH_metrics_serve.jsonl. ``--fleet N`` routes the same trace through a
``FleetRouter`` over N serving replicas instead: a routing-policy A/B
(round-robin vs KV-occupancy-aware) against a single-engine baseline,
with per-replica peak occupancy, routing decisions by reason, and —
with ``--disagg`` (prefill/decode pools + KV block handoff) — the
handoff latency p50/p99 in the record. ``--chaos plan.json`` (fleet mode
only) arms the same deterministic fault plans the chaos_serve gate uses
(replica_kill / replica_slow / replica_flap / handoff_fail, steps =
post-warmup router iterations) and records the self-healing ledger —
deaths, quarantines, revivals, mean time-to-revival (iterations), shed
rate — per arm; ``--deadline S`` gives every request a deadline so
admission-control shedding engages. Knobs (env): BENCH_SERVE_REQUESTS,
BENCH_SERVE_RATE (req/s), BENCH_SERVE_PROMPT (max prompt len),
BENCH_SERVE_NEW, BENCH_SERVE_ROWS, BENCH_SERVE_BLOCK, BENCH_SERVE_BLOCKS,
BENCH_SERVE_LEN, BENCH_SERVE_CHUNK, BENCH_SERVE_SYS (shared-prefix len),
BENCH_SERVE_PREFIX_REQS, BENCH_SERVE_PAGED_KERNEL (= the flag),
BENCH_SERVE_SPEC (= --spec), BENCH_SERVE_SPEC_K (draft tokens/iteration),
BENCH_SERVE_DRAFT_MODEL (draft-arm model), BENCH_SERVE_REPEAT
(repetitive-prompt fraction; default 0.5 when speculating, else 0).

Decode is HBM-bandwidth-bound: the roofline is
    BW / (param_bytes + live-KV bytes per token);
``vs_baseline`` reports achieved/roofline — 1.0 == the chip's memory system
is saturated (the analog of the reference's kernel-injected decode claim).

Model: largest preset that fits the attached chip (env BENCH_INFER_MODEL to
override; weights are random — zero-egress environment — which does not
change the memory-bound timing).

Like bench.py, the measurement runs in a watchdogged child
(``bench_common.py``): a hang gets SIGUSR1 (flight-record dump) then
SIGKILL, and the skip record carries ``failure_kind`` + the bundle path.
The parent imports neither jax nor deepspeed_tpu — a parent that
initialises a backend holds the chip its child needs.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_common import run_watchdogged  # noqa: E402

def hbm_bandwidth() -> float:
    """Attached chip's HBM bytes/s — the shared cost-model table, so the
    measured roofline_frac and tpucost's predicted numbers can never be
    computed against different bandwidths."""
    import jax

    from deepspeed_tpu.autotuning.cost_model import hbm_bw_for

    return hbm_bw_for(jax.devices()[0].device_kind)


def predict_main() -> None:
    """BENCH_PREDICT=1 child mode: the analytic decode roofline for this
    bench's config, host-side (no engine, no params — weight bytes come
    from the analytic param count, KV bytes from ``cache_memory_bytes``).
    Decode MFU is tiny by nature (memory-bound); the number still pins the
    skip record to THIS config's ceiling."""
    import jax.numpy as jnp

    from deepspeed_tpu.autotuning.cost_model import (hbm_bw_for,
                                                     peak_flops_for)
    from deepspeed_tpu.inference import cache_memory_bytes
    from deepspeed_tpu.models import create_model
    from deepspeed_tpu.profiling import transformer_breakdown

    model_name = os.environ.get("BENCH_INFER_MODEL", "llama-7b")
    prompt_len = int(os.environ.get("BENCH_INFER_PROMPT", 512))
    n_new = int(os.environ.get("BENCH_INFER_NEW", 64))
    dtype_name = os.environ.get("BENCH_INFER_DTYPE", "bf16")
    model = create_model(model_name, dtype=jnp.bfloat16)
    cfg = model.config
    n = transformer_breakdown(cfg, 1, 1).total_params
    weight_bytes = {"int8": 1.0, "w8a8": 1.0,
                    "int4": 0.5, "w4a8": 0.5}.get(dtype_name, 2.0)
    live = prompt_len + n_new // 2
    # KV stays bf16 for every allowed BENCH_INFER_DTYPE: the quant modes are
    # weight-storage-only and InferenceConfig normalizes their compute/arena
    # dtype to bf16 — matching main()'s engine.config.dtype sizing
    kv = cache_memory_bytes(cfg, 1, live, jnp.bfloat16)
    roofline_tps = hbm_bw_for(None) / (n * weight_bytes + kv)
    print(json.dumps({
        # ~2N matmul flops per decoded token against the chip's peak
        "predicted_mfu": round(roofline_tps * 2 * n / peak_flops_for(None),
                               6),
        "predicted_decode_tokens_per_sec": round(roofline_tps, 1),
        "source": "analytic-roofline",
    }))


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.inference import init_inference

    # TTFT / decode spans and kv-cache metrics land in a metrics JSONL next
    # to the BENCH record so the trajectory keeps per-phase breakdowns
    # (BENCH_OBS=0 opts out)
    if os.environ.get("BENCH_OBS", "1") == "1":
        from deepspeed_tpu.config.config import ObservabilityConfig
        from deepspeed_tpu.observability import configure_observability

        configure_observability(ObservabilityConfig(
            enabled=True,
            output_dir=os.environ.get("BENCH_OBS_DIR",
                                      "bench_results/obs_infer")))

    model_name = os.environ.get("BENCH_INFER_MODEL", "llama-7b")
    prompt_len = int(os.environ.get("BENCH_INFER_PROMPT", 512))
    n_new = int(os.environ.get("BENCH_INFER_NEW", 64))
    arena = int(os.environ.get("BENCH_INFER_ARENA", 1024))
    # 'int8'/'int4' => weight-only quantized storage (compute bf16): halves/
    # quarters the weight side of the decode roofline denominator
    dtype_name = os.environ.get("BENCH_INFER_DTYPE", "bf16")
    if dtype_name not in ("bf16", "int8", "int4", "w8a8", "w4a8"):
        raise SystemExit(f"BENCH_INFER_DTYPE must be bf16|int8|int4|w8a8|"
                         f"w4a8, got '{dtype_name}' — refusing to run a "
                         "mislabelled benchmark")
    dtype = jnp.bfloat16 if dtype_name == "bf16" else dtype_name

    try:
        engine = init_inference(model_name, dtype=dtype, max_out_tokens=arena)
        cfg = engine.model.config
        rng = np.random.RandomState(0)
        prompt = rng.randint(0, cfg.vocab_size, (1, prompt_len))

        # warmup (compiles prefill + decode)
        engine.generate(prompt, max_new_tokens=n_new)
    except Exception as e:  # noqa: BLE001 — structured OOM record below
        msg = str(e)
        if "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower():
            print(json.dumps({
                "metric": f"{model_name}_{dtype_name}_p50_ttft_ms",
                "value": None, "unit": "ms", "vs_baseline": None,
                "oom": True,
                "single_chip_caveat": (
                    f"{model_name} at {dtype_name} exceeds one chip's HBM "
                    "(use int8/int4 weight storage or TP>1)"),
                "reason": msg[-300:],
            }))
        raise

    ttfts = []
    t_all = []
    for _ in range(5):
        t0 = time.perf_counter()
        out, ttft = engine.generate(prompt, max_new_tokens=n_new,
                                    return_ttft=True)
        np.asarray(out)  # fence
        t_all.append(time.perf_counter() - t0)
        ttfts.append(ttft)
    p50_ttft = sorted(ttfts)[len(ttfts) // 2]
    p50_all = sorted(t_all)[len(t_all) // 2]
    decode_tps = (n_new - 1) / (p50_all - p50_ttft)

    param_bytes = sum(int(p.size) * p.dtype.itemsize
                      for p in jax.tree.leaves(engine.params))
    # live KV read per decode token (valid region ~ prompt + half the gen);
    # sized at the ENGINE's arena dtype — the roofline denominator must not
    # silently assume bf16 for an fp16/fp32 engine
    from deepspeed_tpu.inference import cache_memory_bytes

    live = prompt_len + n_new // 2
    kv_bytes = cache_memory_bytes(cfg, 1, live, engine.config.dtype)
    roofline_tps = hbm_bandwidth() / (param_bytes + kv_bytes)
    frac = decode_tps / roofline_tps

    from deepspeed_tpu.observability import get_session

    obs = get_session()
    if obs.enabled:
        obs.registry.gauge("bench/p50_ttft_ms").set(p50_ttft * 1e3)
        obs.registry.gauge("bench/decode_tokens_per_sec").set(decode_tps)
        obs.dump_metrics(path=os.environ.get("BENCH_METRICS_JSONL",
                                             "BENCH_metrics_infer.jsonl"),
                         metric=f"{model_name}_{dtype_name}_p50_ttft_ms")
        obs.close(export=False)   # already exported to the bench paths

    record = {
        "metric": f"{model_name}_{dtype_name}_p50_ttft_ms",
        "value": round(p50_ttft * 1e3, 2),
        "unit": "ms",
        "decode_tokens_per_sec": round(decode_tps, 1),
        "roofline_frac": round(frac, 4),
        "vs_baseline": round(frac, 4),
    }
    # static cost vectors for the prefill/decode programs generate() just
    # ran (registered with the audit registry at first generate); the next
    # on-chip round reports measured-vs-predicted side by side
    if os.environ.get("BENCH_COST", "1") == "1":
        from bench_common import cost_vector_record

        cost = cost_vector_record("inference/decode")
        if cost is not None:
            record["tpucost"] = cost
            prefill = cost_vector_record("inference/prefill")
            if prefill is not None:
                record["tpucost_prefill"] = prefill
    print(json.dumps(record))


def _serve_load(srv, prompts, arrivals, n_new, deadline_s=None):
    """Drive one Poisson-arrival load through a ServingEngine (or a
    FleetRouter — same surface). Returns (handles, wall_seconds,
    admission_sheds): with ``deadline_s`` set, a fleet under pressure may
    shed deadline-infeasible submissions with ``Overloaded`` — those count
    as sheds, not handles."""
    from deepspeed_tpu.serving.fleet import Overloaded

    t0 = time.perf_counter()
    handles = []
    sheds = 0
    i = 0
    n_requests = len(prompts)
    while i < n_requests or srv.in_flight():
        # every srv.step() host-materializes its sampled tokens
        # (np.asarray inside the iteration) — the clock reads below are
        # fenced by construction, the linter just can't see through step()
        now = time.perf_counter() - t0  # tpulint: disable=wallclock-timing-without-sync
        while i < n_requests and arrivals[i] <= now:
            try:
                handles.append(srv.submit(prompts[i], max_new_tokens=n_new,
                                          deadline_s=deadline_s))
            except Overloaded:
                sheds += 1
            i += 1
        if srv.in_flight():
            srv.step()
        elif i < n_requests:
            time.sleep(min(arrivals[i] - now, 0.01))
    wall = time.perf_counter() - t0  # tpulint: disable=wallclock-timing-without-sync
    return handles, wall, sheds


def _configure_bench_obs(tune=False, ttft_slo_ms=0.0, tpot_slo_ms=0.0):
    from deepspeed_tpu.config.config import (ObservabilityConfig,
                                             ProfilingConfig, TuneConfig)
    from deepspeed_tpu.observability import configure_observability

    tune_cfg = TuneConfig()
    if tune:
        # the tuned A/B arm: store + controller on, cadence short enough
        # to act within a bench-scale trace
        tune_cfg = TuneConfig(
            enabled=True, controller=True,
            interval_iterations=int(
                os.environ.get("BENCH_SERVE_TUNE_INTERVAL", 8)),
            hold_iterations=int(
                os.environ.get("BENCH_SERVE_TUNE_HOLD", 16)))
    # BENCH_PROFILE=1: deep-profiler capture windows during the serving
    # trace — scheduled every BENCH_PROFILE_EVERY iterations (plus any
    # telemetry triggers), with profile_summary.json's measured-vs-
    # predicted rows landing next to the bench record
    prof_cfg = ProfilingConfig()
    if os.environ.get("BENCH_PROFILE", "0") == "1":
        prof_cfg = ProfilingConfig(
            enabled=True,
            profile_every_steps=int(
                os.environ.get("BENCH_PROFILE_EVERY", 64)),
            window_iterations=int(
                os.environ.get("BENCH_PROFILE_WINDOW", 8)))
    configure_observability(ObservabilityConfig(
        enabled=True,
        output_dir=os.environ.get("BENCH_OBS_DIR",
                                  "bench_results/obs_serve"),
        # request traces (BENCH_TRACE=0 opts out): head-sample everything —
        # the arm dumps Chrome timelines for its top-3 TTFT outliers
        request_tracing=os.environ.get("BENCH_TRACE", "1") == "1",
        # per-iteration serving wall-time buckets; the arm records carry
        # the bucket shares and the gauges land in the metrics JSONL
        serve_goodput=True,
        # nonzero only for the autotune A/B: burn rates are its outcome
        # metric AND the live tuner's input signal
        serve_ttft_slo_ms=ttft_slo_ms,
        serve_tpot_slo_ms=tpot_slo_ms,
        tune=tune_cfg, profiling=prof_cfg))


def _arm_observability_stats(stats, tag, accts):
    """Fold the observability arm outputs into one arm's stats dict: the
    serve_goodput bucket shares (per accountant) and a Chrome trace of the
    top-3 TTFT-outlier request timelines (BENCH_TRACE=0 opt-out)."""
    from deepspeed_tpu.observability import get_session

    obs = get_session()
    if not obs.enabled:
        return
    shares = {rep: a.bucket_shares() for rep, a in accts if a is not None}
    if shares:
        stats["serve_goodput"] = (next(iter(shares.values()))
                                  if len(shares) == 1 else shares)
    if obs.reqtrace is not None:
        path = os.path.join(obs.output_dir, f"trace_top_{tag}.json")
        top = obs.reqtrace.export_chrome_top(path, k=3, key="ttft_ms")
        if top:
            stats["trace_outliers"] = {"chrome_trace": path,
                                       "trace_ids": top}


def _load_stats(handles, wall):
    """Latency/throughput aggregation shared by the single-engine and
    fleet arms — one implementation so the numbers the fleet record is
    compared against are computed identically. Requests that never
    streamed a token (shed from the queue / expired deadlines under a
    chaos plan) have no TTFT and stay out of the percentiles."""
    from deepspeed_tpu.serving.api import _percentile as p

    ttfts = sorted(h.ttft_s for h in handles if h.ttft_s is not None)
    tpots = sorted(h.tpot_s for h in handles if h.tpot_s is not None)
    total_tokens = sum(len(h.tokens) for h in handles)
    return {
        "p50_ttft_ms": round(p(ttfts, 0.50) * 1e3, 2) if ttfts else None,
        "p99_ttft_ms": round(p(ttfts, 0.99) * 1e3, 2) if ttfts else None,
        "tpot_ms": round(p(tpots, 0.50) * 1e3, 3) if tpots else None,
        "tokens_per_sec": round(total_tokens / wall, 1),
        "requests_per_sec": round(len(handles) / wall, 2),
    }


def _serve_one_mode(engine, scfg_kwargs, paged_kernel, prompts, arrivals,
                    prefix_prompts, n_new, block, enable_obs=False,
                    spec_mode="off", draft_engine=None, deadline_s=None):
    """One A/B arm: build a ServingEngine with ``paged_kernel`` (and
    optionally a speculative-decoding arm via ``spec_mode``), run the
    Poisson load, then the prefix-reuse workload (every request shares one
    long system prompt — round 2 should hit the prefix cache). Returns the
    arm's stats dict. ``enable_obs`` turns the observability session on
    for THIS arm, strictly AFTER its warmup — compile-scale TTFTs never
    land in the serving histograms, and the metrics JSONL describes
    exactly one configuration (the primary arm), not a blend of both."""
    import numpy as np

    from deepspeed_tpu.serving import ServingConfig, ServingEngine
    from deepspeed_tpu.serving.api import _percentile as p

    spec_cfg = {"mode": spec_mode}
    if spec_mode != "off":
        spec_cfg["num_draft_tokens"] = int(
            os.environ.get("BENCH_SERVE_SPEC_K", 4))
    srv = ServingEngine(engine, ServingConfig(paged_kernel=paged_kernel,
                                              speculative=spec_cfg,
                                              **scfg_kwargs),
                        draft_engine=draft_engine)
    # warmup: compile the serving programs off the clock, BEFORE the
    # observability session exists
    srv.submit(prompts[0][: max(block, 8)], max_new_tokens=2).result()
    if enable_obs:
        _configure_bench_obs()
    srv.reset_latency_stats()

    handles, wall, _ = _serve_load(srv, prompts, arrivals, n_new,
                                   deadline_s=deadline_s)
    stats = _load_stats(handles, wall)
    if deadline_s is not None:
        stats["deadline_exceeded"] = srv.sched.deadline_exceeded_count
    stats.update({
        "arena_peak_blocks": srv.alloc.peak_in_use,
        "arena_peak_occupancy": round(
            srv.alloc.peak_in_use / srv.alloc.capacity, 4),
        "preemptions": srv.sched.preemption_count,
    })
    if spec_mode != "off":
        # the proposed-vs-emitted ledger: how many tokens each target
        # dispatch actually bought (> 1 is the speculative win)
        stats["spec"] = {
            "mode": spec_mode,
            "proposed_tokens": srv._spec_proposed,
            "accepted_tokens": srv._spec_accepted,
            "acceptance_rate": round(
                srv._spec_accepted / max(srv._spec_proposed, 1), 4),
            "emitted_tokens": srv._spec_emitted,
            "verify_dispatches": srv._spec_dispatches,
            "emitted_per_dispatch": round(
                srv._spec_emitted / max(srv._spec_dispatches, 1), 3),
            "draft_time_share": round(
                srv._spec_draft_s
                / max(srv._spec_draft_s + srv._spec_verify_s, 1e-9), 4),
            "pressure_disabled_rows": srv._spec_disabled_rows,
        }
    # prefix-reuse workload: round 1 populates the cache, round 2 (same
    # shared system prompt, fresh tails) should skip the shared chunks —
    # the TTFT ratio IS the prefix-sharing win
    if prefix_prompts:
        # snapshot the counters so the reported rate describes the reuse
        # workload alone, not the (mostly-miss) Poisson load before it
        hit0 = srv.sched.prefix_hit_tokens
        look0 = srv.sched.prefix_lookup_tokens
        r1, _, _ = _serve_load(srv, prefix_prompts[0],
                               np.zeros(len(prefix_prompts[0])), n_new)
        r2, _, _ = _serve_load(srv, prefix_prompts[1],
                               np.zeros(len(prefix_prompts[1])), n_new)
        ttft1 = sorted(h.ttft_s for h in r1)
        ttft2 = sorted(h.ttft_s for h in r2)
        stats["prefix_reuse"] = {
            "cold_p50_ttft_ms": round(p(ttft1, 0.50) * 1e3, 2),
            "warm_p50_ttft_ms": round(p(ttft2, 0.50) * 1e3, 2),
            "prefix_hit_rate": round(
                (srv.sched.prefix_hit_tokens - hit0)
                / max(srv.sched.prefix_lookup_tokens - look0, 1), 4),
            "blocks_shared_peak": srv.alloc.peak_shared,
            "cow_copies": srv._cow_copies,
        }
    if os.environ.get("BENCH_COST", "1") == "1":
        # the cost vector of THIS arm's registered serving/decode program —
        # bytes_accessed is the arena-read traffic the A/B is about
        from bench_common import cost_vector_record

        cost = cost_vector_record("serving/decode")
        if cost is not None:
            stats["tpucost"] = cost
        if spec_mode != "off":
            # the R×(K+1) verify program this arm actually dispatched —
            # its static cost against the R×1 decode is the speculative
            # FLOPs overhead the acceptance rate has to amortize
            vcost = cost_vector_record("serving/verify")
            if vcost is not None:
                stats["tpucost_verify"] = vcost
    if enable_obs:
        _arm_observability_stats(
            stats, f"{paged_kernel}_{spec_mode}",
            [("0", srv._serve_acct)])
    srv.close()
    return stats


def _serve_autotune_arm(engine, scfg_kwargs, paged_kernel, prompts,
                        arrivals, n_new, block, fleet_n, tuned,
                        ttft_slo_ms=50.0, tpot_slo_ms=3.0,
                        deadline_s=None):
    """One closed-loop A/B arm: the SAME engine config and Poisson trace
    (with its mid-trace load shift) either static (``tuned=False``) or
    with the live tuner walking knobs against measured burn. Both arms
    own an observability session (burn is the measured outcome); only the
    tuned arm's session carries the time-series store + controller, and it
    runs LAST so the exported metrics JSONL describes the tuned fleet.
    Returns ``(stats, token_streams)`` — the streams feed the bit-exactness
    check (data-only knobs must not change a single sampled token)."""
    from deepspeed_tpu.serving import ServingConfig, ServingEngine

    scfg = ServingConfig(paged_kernel=paged_kernel, **scfg_kwargs)
    if fleet_n:
        from deepspeed_tpu.config.config import FleetConfig
        from deepspeed_tpu.serving.fleet import FleetRouter, build_replicas

        replicas = build_replicas(engine, scfg, fleet_n)
        srv = FleetRouter(replicas, FleetConfig(policy="kv_occupancy"))
        engines = [r.engine for r in replicas]
    else:
        srv = ServingEngine(engine, scfg)
        engines = [srv]
    # warmup: compile off the clock, BEFORE the observability session —
    # the tuner must never see (or cause) a compile
    srv.submit(prompts[0][: max(block, 8)], max_new_tokens=2).result()
    _configure_bench_obs(tune=tuned, ttft_slo_ms=ttft_slo_ms,
                         tpot_slo_ms=tpot_slo_ms)
    srv.reset_latency_stats()

    handles, wall, sheds = _serve_load(srv, prompts, arrivals, n_new,
                                       deadline_s=deadline_s)
    stats = _load_stats(handles, wall)
    streams = [list(map(int, h.tokens)) for h in handles]
    # measured outcome: worst-replica burn + mean goodput fraction from
    # the serve_goodput accountants (the same signals the tuner read)
    accts = [e._serve_acct for e in engines if e._serve_acct is not None]
    totals = [a.totals() for a in accts]
    if totals:
        # burn keys are absent until a request finished in the window
        stats["slo_burn"] = {
            "ttft": round(max(t.get("ttft_slo_burn_rate", 0.0)
                              for t in totals), 4),
            "tpot": round(max(t.get("tpot_slo_burn_rate", 0.0)
                              for t in totals), 4),
            "goodput_fraction": round(
                sum(t["goodput_fraction"] for t in totals) / len(totals),
                4),
        }
    if sheds:
        stats["admission_sheds"] = sheds
    tuner = srv._tuner
    if tuned and tuner is not None:
        rep = tuner.report()
        stats["autotune"] = {
            "moves": rep["moves"],
            "rollbacks": rep["rollbacks"],
            "knobs_final": rep["knobs"],
            "objective": {"initial": rep["objective_initial"],
                          "last": rep["objective_last"]},
            # the knob trajectory, decision by decision
            "trajectory": [
                {"iteration": d["iteration"], "kind": d["kind"],
                 "knob": d["knob"], "action": d["action"],
                 "reason": d["reason"], "from": d["from"], "to": d["to"]}
                for d in rep["decisions"]],
        }
        from deepspeed_tpu.observability import get_session

        obs = get_session()
        if obs.enabled:
            stats["autotune"]["recommendations_file"] = (
                tuner.export_recommendations(os.path.join(
                    obs.output_dir,
                    obs.config.tune.recommendations_file)))
    srv.close()
    return stats, streams


def _serve_fleet_arm(engine, scfg_kwargs, paged_kernel, n, policy, disagg,
                     prompts, arrivals, n_new, block, enable_obs=False,
                     chaos_plan=None, deadline_s=None):
    """One fleet arm: N serving replicas behind a FleetRouter under
    ``policy`` (optionally split into prefill/decode pools), driven through
    the SAME Poisson trace — and the same ``paged_kernel`` read path — as
    the single-engine baseline. Returns the arm's stats dict: fleet-level
    TTFT/TPOT/throughput, per-replica peak occupancy, routing decisions by
    reason, and (disagg) the KV-handoff latency histogram.

    ``chaos_plan`` (``--chaos plan.json``) arms the router's fault
    injector AFTER warmup — plan steps are post-warmup router iterations —
    and the arm's record gains the self-healing ledger: deaths,
    quarantines, time-to-revival (iterations dead), shed rate."""
    from deepspeed_tpu.config.config import FleetConfig
    from deepspeed_tpu.serving import ServingConfig
    from deepspeed_tpu.serving.api import _percentile as p
    from deepspeed_tpu.serving.fleet import (ROLE_DECODE, ROLE_PREFILL,
                                             FleetRouter, build_replicas)

    roles = None
    if disagg:
        n_prefill = max(n // 2, 1)
        roles = ([ROLE_PREFILL] * n_prefill
                 + [ROLE_DECODE] * (n - n_prefill))
    replicas = build_replicas(
        engine, ServingConfig(paged_kernel=paged_kernel, **scfg_kwargs), n,
        roles=roles)
    router = FleetRouter(replicas, FleetConfig(policy=policy))
    # warmup: compile the serving (and, disagg, the kv_export/kv_import
    # handoff) programs off the clock, BEFORE the observability session
    router.submit(prompts[0][: max(block, 8)], max_new_tokens=2).result()
    if enable_obs:
        _configure_bench_obs()
    # drops the warmup handoff's compile-scale latency sample too
    router.reset_latency_stats()
    if chaos_plan is not None:
        # armed strictly after warmup, with the iteration counter zeroed:
        # plan steps mean "measured-load iterations", never compile time
        from deepspeed_tpu.observability.faultinject import FaultInjector

        router._injector = FaultInjector(plan=chaos_plan, rank=0,
                                         restart=0)
        router._iterations = 0
    # ledger baseline: the warmup submit is pre-measurement traffic and
    # must stay out of the chaos shed-rate denominator
    submitted0 = router.submitted_count

    handles, wall, admission_sheds = _serve_load(
        router, prompts, arrivals, n_new, deadline_s=deadline_s)
    stats = _load_stats(handles, wall)
    if chaos_plan is not None:
        # drive the healing loop to quiescence so time-to-revival and the
        # ledger describe a CLOSED loop, not a snapshot mid-remediation
        for _ in range(256):
            router.step()
            if all(r.alive or r.retired for r in router.replicas):
                break
        attempts = (router.submitted_count - submitted0) + admission_sheds
        stats["chaos"] = {
            "deaths": router._death_count,
            "quarantines": router._quarantine_count,
            "revivals": router._revival_count,
            "graduations": router._graduation_count,
            "retirements": sum(r.retired for r in router.replicas),
            "resubmits": router._resubmit_count,
            "handoff_failures": router._handoff_failures,
            "time_to_revival_iters": (
                round(sum(router._revive_iters)
                      / len(router._revive_iters), 1)
                if router._revive_iters else None),
            "shed": {
                "admission": admission_sheds,
                "degraded": router.shed_count_total,
                "rate": round((admission_sheds
                               + router.shed_count_total)
                              / max(attempts, 1), 4)},
            "degraded_mode_final": router.degraded_mode,
        }
    stats.update({
        "policy": policy,
        "per_replica": [
            {"replica": r.index, "role": r.role,
             "peak_blocks": r.engine.alloc.peak_in_use,
             "peak_occupancy": round(
                 r.engine.alloc.peak_in_use / r.engine.alloc.capacity, 4),
             "preemptions": r.engine.sched.preemption_count,
             "handoffs_out": r.engine.sched.handoffs_out}
            for r in replicas],
        "routing_decisions": {
            f"{pol}/{reason}": int(c)
            for (pol, reason), c in sorted(router._decisions.items())},
    })
    if disagg:
        xs = sorted(router._handoff_ms)
        stats["handoffs"] = {
            "count": len(xs),
            "fallbacks": router._handoff_fallbacks,
            "p50_ms": round(p(xs, 0.50), 3) if xs else None,
            "p99_ms": round(p(xs, 0.99), 3) if xs else None,
        }
    if enable_obs:
        _arm_observability_stats(
            stats, f"fleet{n}_{policy}",
            [(str(r.index), r.engine._serve_acct) for r in replicas])
    router.close()
    return stats


def serving_main() -> None:
    """Continuous-batching load test: Poisson arrivals over a synthetic
    request trace, real-time injected between scheduler iterations.
    ``--paged-kernel on|off`` pins one read path; unset runs the A/B
    (paged kernels vs dense gather view) over the same trace and reports
    the TTFT/TPOT deltas plus each arm's tpucost arena-read bytes."""
    import numpy as np

    model_name = os.environ.get("BENCH_INFER_MODEL", "llama-7b")
    dtype_name = os.environ.get("BENCH_INFER_DTYPE", "bf16")
    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS", 32))
    rate = float(os.environ.get("BENCH_SERVE_RATE", 8.0))      # req/s
    prompt_max = int(os.environ.get("BENCH_SERVE_PROMPT", 256))
    n_new = int(os.environ.get("BENCH_SERVE_NEW", 32))
    rows = int(os.environ.get("BENCH_SERVE_ROWS", 8))
    block = int(os.environ.get("BENCH_SERVE_BLOCK", 16))
    sys_len = int(os.environ.get("BENCH_SERVE_SYS", 1024))   # shared prefix
    prefix_reqs = int(os.environ.get("BENCH_SERVE_PREFIX_REQS", 8))
    max_len = int(os.environ.get("BENCH_SERVE_LEN",
                                 max(prompt_max, sys_len + 32) + n_new))
    max_len = -(-max_len // block) * block      # whole-block budget
    num_blocks = int(os.environ.get("BENCH_SERVE_BLOCKS",
                                    rows * (max_len // block) * 3 // 4))
    chunk = int(os.environ.get("BENCH_SERVE_CHUNK", max(block, 64)))
    chunk = -(-chunk // block) * block
    ab_flag = os.environ.get("BENCH_SERVE_PAGED_KERNEL", "")
    # primary arm LAST: the observability session turns on just before it
    modes = {"on": ["auto"], "off": ["off"]}.get(ab_flag, ["off", "auto"])
    spec_flag = os.environ.get("BENCH_SERVE_SPEC", "off")
    if spec_flag not in ("off", "ngram", "draft"):
        raise SystemExit("--spec must be 'off', 'ngram' or 'draft'")
    fleet_n = int(os.environ.get("BENCH_SERVE_FLEET", "0"))
    disagg = os.environ.get("BENCH_SERVE_DISAGG", "0") == "1"
    if fleet_n < 0:
        raise SystemExit("--fleet needs N >= 0 (0, the default, disables "
                         "fleet mode)")
    if disagg and fleet_n < 2:
        raise SystemExit("--disagg needs --fleet N with N >= 2 "
                         "(at least one prefill and one decode replica)")
    if fleet_n and spec_flag != "off":
        raise SystemExit("--fleet and --spec are separate A/Bs — "
                         "run them in two invocations")
    chaos_spec = os.environ.get("BENCH_SERVE_CHAOS", "")
    chaos_plan = None
    if chaos_spec:
        if not fleet_n:
            raise SystemExit("--chaos drives the FLEET's self-healing "
                             "loop — pair it with --fleet N")
        from deepspeed_tpu.observability.faultinject import load_plan

        # validates the plan up front; a bare path means @path
        chaos_plan = load_plan(
            chaos_spec if chaos_spec.startswith(("@", "[", "{"))
            else "@" + chaos_spec)
    deadline_env = os.environ.get("BENCH_SERVE_DEADLINE", "")
    deadline_s = float(deadline_env) if deadline_env else None
    if spec_flag != "off":
        # the speculative A/B replaces the paged-kernel A/B: both spec
        # arms run the SAME read path (primary) over the SAME trace
        modes = modes[-1:]
    repeat_frac = float(os.environ.get(
        "BENCH_SERVE_REPEAT", 0.5 if spec_flag != "off" else 0.0))

    import jax.numpy as jnp

    from deepspeed_tpu.inference import init_inference

    dtype = jnp.bfloat16 if dtype_name == "bf16" else dtype_name
    metric = f"{model_name}_{dtype_name}_serving_p50_ttft_ms"
    scfg_kwargs = dict(block_size=block, num_blocks=num_blocks,
                       max_seqs=rows, max_model_len=max_len,
                       prefill_chunk=chunk,
                       max_queue=max(2 * n_requests, 64))
    try:
        engine = init_inference(model_name, dtype=dtype,
                                max_out_tokens=max_len)
        cfg = engine.model.config
        rng = np.random.RandomState(0)
        # mixed lengths: uniform over [prompt_max/4, prompt_max]
        lens = rng.randint(max(prompt_max // 4, 1), prompt_max + 1,
                           size=n_requests)
        prompts = [rng.randint(0, cfg.vocab_size, (int(n),)) for n in lens]
        # repetitive-text share (speculation workload: prompt-lookup and
        # draft acceptance both feed on repeated structure) — same trace
        # for every arm, so deltas are apples-to-apples
        for i in range(int(round(repeat_frac * n_requests))):
            pat = rng.randint(0, cfg.vocab_size, (rng.randint(4, 12),))
            prompts[i] = np.tile(pat, -(-int(lens[i]) // pat.size)
                                 )[:int(lens[i])]
        arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_requests))
        # prefix-reuse workload: a shared system prompt + short unique
        # tails, two rounds with DIFFERENT tails (only the prefix repeats)
        sys_len = min(sys_len, max_len - n_new - 32)
        system = rng.randint(0, cfg.vocab_size, (sys_len,))
        prefix_prompts = [
            [np.concatenate([system,
                             rng.randint(0, cfg.vocab_size, (8 + r,))])
             for r in range(prefix_reqs)]
            for _ in range(2)] if sys_len >= block else []
    except Exception as e:  # noqa: BLE001 — structured OOM record
        msg = str(e)
        if "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower():
            print(json.dumps({
                "metric": metric, "value": None, "unit": "ms",
                "vs_baseline": None, "oom": True, "reason": msg[-300:],
            }))
        raise

    obs_wanted = os.environ.get("BENCH_OBS", "1") == "1"
    autotune_flag = os.environ.get("BENCH_SERVE_AUTOTUNE", "off")
    if autotune_flag == "on":
        # closed-loop A/B: static arm vs live-tuner arm over the SAME
        # trace, re-timed with a mid-trace load shift (arrival rate
        # triples halfway) so the tuner has a regime change to react to
        if spec_flag != "off" or chaos_plan is not None:
            raise SystemExit("--autotune is its own A/B — run --spec / "
                             "--chaos in separate invocations")
        shift_rng = np.random.RandomState(7)
        n_half = n_requests // 2
        gaps = np.concatenate([
            shift_rng.exponential(1.0 / rate, size=n_half),
            shift_rng.exponential(1.0 / (3.0 * rate),
                                  size=n_requests - n_half)])
        shift_arrivals = np.cumsum(gaps)
        primary_mode = modes[-1]
        metric = (f"{model_name}_{dtype_name}_autotune"
                  f"{f'_fleet{fleet_n}' if fleet_n else ''}"
                  "_serving_p50_ttft_ms")
        # both arms measure burn against the SAME SLOs (or the deltas
        # mean nothing); defaults target a CPU-scale tiny-model trace
        ttft_slo = float(os.environ.get("BENCH_SERVE_TTFT_SLO_MS", 50.0))
        tpot_slo = float(os.environ.get("BENCH_SERVE_TPOT_SLO_MS", 3.0))
        static, static_streams = _serve_autotune_arm(
            engine, scfg_kwargs, primary_mode, prompts, shift_arrivals,
            n_new, block, fleet_n, tuned=False, ttft_slo_ms=ttft_slo,
            tpot_slo_ms=tpot_slo, deadline_s=deadline_s)
        from deepspeed_tpu.observability import get_session

        # close the static arm's session BEFORE the tuned arm's warmup:
        # its compile must not trip the live session's recompile watchdog
        if get_session().enabled:
            get_session().close(export=False)
        tuned, tuned_streams = _serve_autotune_arm(
            engine, scfg_kwargs, primary_mode, prompts, shift_arrivals,
            n_new, block, fleet_n, tuned=True, ttft_slo_ms=ttft_slo,
            tpot_slo_ms=tpot_slo, deadline_s=deadline_s)
        obs = get_session()
        if obs.enabled:
            obs.dump_metrics(path=os.environ.get("BENCH_METRICS_JSONL",
                                                 "BENCH_metrics_serve"
                                                 ".jsonl"),
                             metric=metric)
            obs.close(export=False)
        sb, tb = static.get("slo_burn", {}), tuned.get("slo_burn", {})
        record = {
            "metric": metric,
            "value": tuned["p50_ttft_ms"],
            "unit": "ms",
            "vs_baseline": None,
            "autotune_ab": {
                "static": static,
                "tuned": tuned,
                # the headline: burn and goodput deltas (tuned - static;
                # negative burn delta = the tuner bought SLO health)
                "ttft_burn_delta": (round(tb["ttft"] - sb["ttft"], 4)
                                    if sb and tb else None),
                "tpot_burn_delta": (round(tb["tpot"] - sb["tpot"], 4)
                                    if sb and tb else None),
                "goodput_delta": (round(tb["goodput_fraction"]
                                        - sb["goodput_fraction"], 4)
                                  if sb and tb else None),
                # data-only knobs: every sampled token identical
                "streams_match": static_streams == tuned_streams,
            },
        }
        print(json.dumps(record))
        return
    if fleet_n:
        # fleet mode: single-engine baseline, then the routing-policy A/B
        # (round-robin vs occupancy-aware) over the SAME trace; the
        # occupancy arm runs LAST and owns the obs session, so the metrics
        # JSONL carries the fleet_serving/* per-replica gauges
        primary_mode = modes[-1]
        metric = (f"{model_name}_{dtype_name}_fleet{fleet_n}"
                  f"{'_disagg' if disagg else ''}_serving_p50_ttft_ms")
        single = _serve_one_mode(engine, scfg_kwargs, primary_mode,
                                 prompts, arrivals, [], n_new, block,
                                 deadline_s=deadline_s)
        fleet_arms = {}
        for i, policy in enumerate(("round_robin", "kv_occupancy")):
            fleet_arms[policy] = _serve_fleet_arm(
                engine, scfg_kwargs, primary_mode, fleet_n, policy, disagg,
                prompts, arrivals, n_new, block,
                enable_obs=(obs_wanted and i == 1),
                chaos_plan=chaos_plan, deadline_s=deadline_s)
        primary = fleet_arms["kv_occupancy"]

        from deepspeed_tpu.observability import get_session

        obs = get_session()
        if obs.enabled:
            obs.dump_metrics(path=os.environ.get("BENCH_METRICS_JSONL",
                                                 "BENCH_metrics_serve"
                                                 ".jsonl"),
                             metric=metric)
            obs.close(export=False)
        rr = fleet_arms["round_robin"]
        record = {
            "metric": metric,
            "value": primary["p50_ttft_ms"],
            "unit": "ms",
            "vs_baseline": None,
            "fleet": fleet_n,
            "disagg": disagg,
            "chaos": bool(chaos_plan),
            "paged_kernel": "on" if primary_mode == "auto" else "off",
            "single_engine": single,
            "fleet_ab": {
                "round_robin": rr,
                "kv_occupancy": primary,
                # occupancy-aware routing's win over blind round-robin
                "ttft_p50_delta_pct": round(
                    100.0 * (rr["p50_ttft_ms"] - primary["p50_ttft_ms"])
                    / max(rr["p50_ttft_ms"], 1e-9), 2),
            },
            # the scale-out headline: fleet throughput / one engine's
            "tokens_per_sec_vs_single": round(
                primary["tokens_per_sec"]
                / max(single["tokens_per_sec"], 1e-9), 3),
            "ttft_p50_vs_single_pct": round(
                100.0 * (single["p50_ttft_ms"] - primary["p50_ttft_ms"])
                / max(single["p50_ttft_ms"], 1e-9), 2),
        }
        print(json.dumps(record))
        return
    arms = {}
    spec_arms = {}
    if spec_flag != "off":
        draft_engine = None
        if spec_flag == "draft":
            draft_name = os.environ.get("BENCH_SERVE_DRAFT_MODEL",
                                        model_name)
            draft_engine = init_inference(draft_name, dtype=dtype,
                                          max_out_tokens=max_len)
        # both speculative arms ride the primary read path over the SAME
        # trace; the speculative arm runs LAST (it owns the obs session)
        for i, sm in enumerate(["off", spec_flag]):
            spec_arms[sm] = _serve_one_mode(
                engine, scfg_kwargs, modes[0], prompts, arrivals,
                prefix_prompts if sm == spec_flag else [], n_new, block,
                enable_obs=(obs_wanted and i == 1), spec_mode=sm,
                draft_engine=(draft_engine if sm == "draft" else None),
                deadline_s=deadline_s)
        arms["on" if modes[0] == "auto" else "off"] = spec_arms[spec_flag]
    else:
        for i, mode in enumerate(modes):
            label = "on" if mode == "auto" else "off"
            arms[label] = _serve_one_mode(
                engine, scfg_kwargs, mode, prompts, arrivals,
                prefix_prompts, n_new, block,
                enable_obs=(obs_wanted and i == len(modes) - 1),
                deadline_s=deadline_s)

    primary = arms.get("on") or arms["off"]

    from deepspeed_tpu.observability import get_session

    obs = get_session()
    if obs.enabled:
        obs.dump_metrics(path=os.environ.get("BENCH_METRICS_JSONL",
                                             "BENCH_metrics_serve.jsonl"),
                         metric=metric)
        obs.close(export=False)

    record = {
        "metric": metric,
        "value": primary["p50_ttft_ms"],
        "unit": "ms",
        "vs_baseline": None,
        "paged_kernel": "on" if "on" in arms else "off",
        "spec": spec_flag,
    }
    record.update({k: v for k, v in primary.items() if k != "tpucost"})
    if primary.get("tpucost") is not None:
        record["tpucost"] = primary["tpucost"]
    if spec_arms:
        off, on = spec_arms["off"], spec_arms[spec_flag]
        ab = {"off": off, spec_flag: on,
              "ttft_p50_delta_pct": round(
                  100.0 * (off["p50_ttft_ms"] - on["p50_ttft_ms"])
                  / max(off["p50_ttft_ms"], 1e-9), 2)}
        if on.get("tpot_ms") and off.get("tpot_ms"):
            # the speculative headline: TPOT bought per target dispatch
            ab["tpot_delta_pct"] = round(
                100.0 * (off["tpot_ms"] - on["tpot_ms"])
                / max(off["tpot_ms"], 1e-9), 2)
        if on.get("tpucost_verify") and off.get("tpucost"):
            ab["verify_vs_decode_flops"] = {
                "verify": on["tpucost_verify"].get("flops"),
                "decode": off["tpucost"].get("flops")}
        record["spec_ab"] = ab
    if len(arms) == 2:
        on, off = arms["on"], arms["off"]
        ab = {"on": on, "off": off,
              "ttft_p50_delta_pct": round(
                  100.0 * (off["p50_ttft_ms"] - on["p50_ttft_ms"])
                  / max(off["p50_ttft_ms"], 1e-9), 2)}
        if on.get("tpot_ms") and off.get("tpot_ms"):
            ab["tpot_delta_pct"] = round(
                100.0 * (off["tpot_ms"] - on["tpot_ms"])
                / max(off["tpot_ms"], 1e-9), 2)
        if on.get("tpucost") and off.get("tpucost"):
            ab["arena_read_bytes"] = {
                "on": on["tpucost"].get("bytes_accessed"),
                "off": off["tpucost"].get("bytes_accessed")}
        record["paged_kernel_ab"] = ab
    print(json.dumps(record))


if __name__ == "__main__":
    serving = ("--serving" in sys.argv[1:]
               or os.environ.get("BENCH_INFER_MODE") == "serving")
    argv = sys.argv[1:]
    for i, a in enumerate(argv):
        # --paged-kernel on|off pins one A/B arm; unset runs both
        if a == "--paged-kernel" and i + 1 < len(argv):
            os.environ["BENCH_SERVE_PAGED_KERNEL"] = argv[i + 1]
        elif a.startswith("--paged-kernel="):
            os.environ["BENCH_SERVE_PAGED_KERNEL"] = a.split("=", 1)[1]
        # --spec ngram|draft runs that speculative arm vs spec-off over
        # the SAME Poisson trace (acceptance rate, proposed-vs-emitted,
        # per-arm verify tpucost); 'off'/unset keeps speculation out
        elif a == "--spec" and i + 1 < len(argv):
            os.environ["BENCH_SERVE_SPEC"] = argv[i + 1]
        elif a.startswith("--spec="):
            os.environ["BENCH_SERVE_SPEC"] = a.split("=", 1)[1]
        # --fleet N routes the trace through a FleetRouter over N serving
        # replicas (routing-policy A/B vs a single-engine baseline);
        # --disagg splits the replicas into prefill/decode pools with KV
        # block handoff between them
        elif a == "--fleet" and i + 1 < len(argv):
            os.environ["BENCH_SERVE_FLEET"] = argv[i + 1]
        elif a.startswith("--fleet="):
            os.environ["BENCH_SERVE_FLEET"] = a.split("=", 1)[1]
        elif a == "--disagg":
            os.environ["BENCH_SERVE_DISAGG"] = "1"
        # --chaos plan.json drives the fleet arms through a deterministic
        # fault plan (replica_kill/slow/flap, handoff_fail) and records
        # the self-healing ledger: time-to-revival, shed rate, ...
        elif a == "--chaos" and i + 1 < len(argv):
            os.environ["BENCH_SERVE_CHAOS"] = argv[i + 1]
        elif a.startswith("--chaos="):
            os.environ["BENCH_SERVE_CHAOS"] = a.split("=", 1)[1]
        # --deadline S gives every benched request a deadline, engaging
        # admission-control shedding under pressure
        elif a == "--deadline" and i + 1 < len(argv):
            os.environ["BENCH_SERVE_DEADLINE"] = argv[i + 1]
        elif a.startswith("--deadline="):
            os.environ["BENCH_SERVE_DEADLINE"] = a.split("=", 1)[1]
        # --autotune on runs the closed-loop A/B: live tuner vs static
        # config over the same mid-trace-load-shift Poisson trace
        elif a == "--autotune" and i + 1 < len(argv):
            os.environ["BENCH_SERVE_AUTOTUNE"] = argv[i + 1]
        elif a.startswith("--autotune="):
            os.environ["BENCH_SERVE_AUTOTUNE"] = a.split("=", 1)[1]
    if os.environ.get("BENCH_SERVE_PAGED_KERNEL", "") not in ("", "on",
                                                              "off"):
        raise SystemExit("--paged-kernel must be 'on' or 'off'")
    if os.environ.get("BENCH_SERVE_SPEC", "off") not in ("off", "ngram",
                                                         "draft"):
        raise SystemExit("--spec must be 'off', 'ngram' or 'draft'")
    if os.environ.get("BENCH_SERVE_AUTOTUNE", "off") not in ("off", "on"):
        raise SystemExit("--autotune must be 'on' or 'off'")
    if os.environ.get("BENCH_PREDICT") == "1":
        predict_main()
    elif os.environ.get("BENCH_CHILD") == "1":
        serving_main() if serving else main()
    else:
        if serving:
            # the watchdogged child re-runs this file argv-less; mode rides
            # the environment (as does BENCH_SERVE_PAGED_KERNEL)
            os.environ["BENCH_INFER_MODE"] = "serving"
        model = os.environ.get("BENCH_INFER_MODEL", "llama-7b")
        dtype = os.environ.get("BENCH_INFER_DTYPE", "bf16")
        suffix = "serving_p50_ttft_ms" if serving else "p50_ttft_ms"
        obs_dir = "bench_results/obs_serve" if serving \
            else "bench_results/obs_infer"
        run_watchdogged(
            f"{model}_{dtype}_{suffix}", "ms", os.path.abspath(__file__),
            crash_dir=os.path.join(
                os.environ.get("BENCH_OBS_DIR", obs_dir), "crash"))

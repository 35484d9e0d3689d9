#!/usr/bin/env python
"""Benchmark harness — run by the driver on real TPU hardware.

Prints exactly ONE JSON line to stdout:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Benchmark: GPT-2 125M causal-LM training throughput on one chip, bf16,
tokens/sec (BASELINE.json tracked config #1). ``vs_baseline`` reports
MFU / 0.5 — the fraction of the driver's north-star (≥50% MFU) achieved,
so 1.0 == target reached.

Outage handling: a backend can be transiently unavailable (a bare
``UNAVAILABLE`` traceback is not a record). The parent runs the measurement
in a watchdogged child immediately (no extra backend init when the backend
is healthy); only when the child fails with a backend-down signature does
it fall back to a bounded probe/retry ladder and one re-run
(``bench_common.py``). If the
backend never comes up — or the child hangs past the watchdog (SIGUSR1
flight-record dump, then SIGKILL) — it prints a parseable skip record
    {"metric": ..., "value": null, "unit": ..., "vs_baseline": null,
     "skipped": true, "failure_kind": "hang|backend-init|crash",
     "reason": ...}
and exits 0 so the round still has a structured result; a hang's reason
carries the crash-bundle path and the stalled span name. Genuine bench
bugs (non-backend failures) still exit non-zero with the child's stderr.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench_common import run_watchdogged  # noqa: E402

METRIC = "gpt2_125m_bf16_train_tokens_per_sec_per_chip"
UNIT = "tokens/s"


def peak_flops_per_chip() -> float:
    """bf16 peak for the attached chip generation (the cost model's table)."""
    import jax

    from deepspeed_tpu.autotuning.cost_model import peak_flops_for

    return peak_flops_for(jax.devices()[0].device_kind)


def predict_main() -> None:
    """BENCH_PREDICT=1 child mode: the ANALYTIC predicted MFU for this
    bench's exact config, host-side (CPU jax, no engine, no params). This is
    what a backend-outage skip record carries as ``predicted_mfu`` — the
    static half of the measured-vs-predicted pairing, computable when the
    measured half isn't."""
    import jax.numpy as jnp

    from deepspeed_tpu.autotuning.cost_model import (TpuCostModel,
                                                     peak_flops_for)
    from deepspeed_tpu.models import create_model
    from deepspeed_tpu.profiling import transformer_breakdown

    batch = int(os.environ.get("BENCH_BATCH", 32))
    seq = int(os.environ.get("BENCH_SEQ", 1024))
    model = create_model("gpt2-125m", dtype=jnp.bfloat16, max_seq_len=seq)
    cfg = model.config
    n = transformer_breakdown(cfg, batch, seq).total_params
    flops_per_token = 6 * n + 12 * cfg.num_layers * cfg.hidden_size * seq
    # mfu=1.0: predict the CEILING (roofline + overhead), not the 50% target
    cm = TpuCostModel(model_info={
        "num_params": n, "hidden_size": cfg.hidden_size,
        "num_layers": cfg.num_layers, "seq_length": seq,
        "vocab_size": cfg.vocab_size}, mfu=1.0)
    tps = cm.predict_throughput({"train_micro_batch_size_per_gpu": batch})
    print(json.dumps({
        "predicted_mfu": round(tps * flops_per_token / peak_flops_for(None),
                               4),
        "predicted_tokens_per_sec": round(tps, 1),
        "source": "analytic-roofline",
    }))


def main() -> None:
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import create_model

    batch, seq = int(os.environ.get("BENCH_BATCH", 32)), int(os.environ.get("BENCH_SEQ", 1024))
    remat = os.environ.get("BENCH_REMAT", "1") == "1"
    remat_policy = os.environ.get("BENCH_REMAT_POLICY", "dots")
    # full layer unroll: measured 115.2k tok/s vs 101.6k with the 12-layer
    # scan on v5e (XLA pipelines across layer boundaries); partial unroll
    # (2 or 6) is WORSE than either — all-or-nothing
    unroll = int(os.environ.get("BENCH_UNROLL", 12))
    model = create_model("gpt2-125m", dtype=jnp.bfloat16, remat=remat,
                         remat_policy=remat_policy, scan_unroll=unroll,
                         max_seq_len=seq)

    # the Pallas kernels must actually be the hot path on TPU (round-1 miss:
    # kernels existed but the bench ran plain-jnp attention)
    from deepspeed_tpu.models.transformer import active_attention_impl

    if jax.default_backend() == "tpu":
        impl = active_attention_impl(model.config)
        assert impl == "flash_attention", (
            f"expected Pallas flash attention on TPU, resolved '{impl}'")
    cfg = {
        "train_micro_batch_size_per_gpu": batch,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 1000,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 0},
        # per-phase breakdown next to the end-to-end number: spans + comm
        # census + compile/memory telemetry land in a metrics JSONL so the
        # perf trajectory carries more than one scalar (BENCH_OBS=0 opts out)
        "observability": {
            "enabled": os.environ.get("BENCH_OBS", "1") == "1",
            "output_dir": os.environ.get("BENCH_OBS_DIR",
                                         "bench_results/obs_train"),
            # fleet-health smoke: per-rank step-time skew lands in the
            # metrics JSONL, and the bench record carries it as
            # step_time_skew (single-host: a 1-rank fleet, skew 0.0 — the
            # wiring is what the smoke proves). Cadence defaults to
            # warmup(2) + step count so exactly ONE gather runs, on the
            # LAST timed step (global-step counting includes the warmup),
            # right where the loop's own float(loss) sync lands — the
            # tracked tokens/sec number stays comparable. The numerics
            # sentinel is deliberately NOT enabled here: its isfinite
            # reductions compile into the hot step.
            "fleet_health": True,
            "fleet_cadence_steps": int(os.environ.get(
                "BENCH_FLEET_CADENCE",
                2 + int(os.environ.get("BENCH_STEPS", 30)))),
            # BENCH_PROFILE=1: deep-profiler capture windows mid-bench —
            # a scheduled window every BENCH_PROFILE_EVERY steps, parsed
            # into profile_summary.json (measured vs tpucost-predicted
            # step time for train/step) next to the metrics JSONL
            "profiling": {
                "enabled": os.environ.get("BENCH_PROFILE", "0") == "1",
                "profile_every_steps": int(os.environ.get(
                    "BENCH_PROFILE_EVERY", 10)),
                "window_iterations": int(os.environ.get(
                    "BENCH_PROFILE_WINDOW", 4)),
            },
        },
    }
    engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)

    rng = jax.random.PRNGKey(0)
    ids = jax.random.randint(rng, (1, batch, seq), 0, model.config.vocab_size)
    batch_tree = {"input_ids": ids}

    # warmup (compile); float() forces materialisation
    for _ in range(2):
        loss = engine.train_batch(batch=batch_tree)
    float(loss)

    steps = int(os.environ.get("BENCH_STEPS", 30))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch=batch_tree)
    float(loss)
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * steps / dt
    n_params = sum(int(p.size) for p in jax.tree.leaves(engine.params))
    cfg_m = model.config
    # training flops/token: 6*N for matmul params + attention 12*L*H*S per token
    flops_per_token = 6 * n_params + 12 * cfg_m.num_layers * cfg_m.hidden_size * seq
    mfu = tokens_per_sec * flops_per_token / peak_flops_per_chip()

    from deepspeed_tpu.observability import get_session

    obs = get_session()
    metrics_path = os.environ.get("BENCH_METRICS_JSONL",
                                  "BENCH_metrics_train.jsonl")
    if obs.enabled:
        obs.registry.gauge("bench/tokens_per_sec").set(tokens_per_sec)
        obs.registry.gauge("bench/mfu").set(mfu)
        obs.dump_metrics(path=metrics_path,
                         metric=METRIC, steps=steps, batch=batch, seq=seq)
        obs.close(export=False)   # already exported to the bench paths

    from bench_common import fleet_skew_from_metrics

    record = {
        "metric": METRIC,
        "value": round(tokens_per_sec, 1),
        "unit": UNIT,
        "vs_baseline": round(mfu / 0.5, 4),
    }
    skew = fleet_skew_from_metrics(metrics_path if obs.enabled else None)
    if skew is not None:
        record["step_time_skew"] = round(skew, 4)

    # static cost vector for the step program the loop just ran (the
    # engine registered it with the audit registry at first train_batch):
    # the record carries measured-vs-predicted MFU side by side, so the
    # r03-style trajectory shows how far each round sat from its own
    # program's ceiling. BENCH_COST=0 opts out (the AOT re-extraction
    # costs one uncached host compile).
    if os.environ.get("BENCH_COST", "1") == "1":
        from bench_common import cost_vector_record

        cost = cost_vector_record("train/step")
        if cost is not None:
            record["tpucost"] = cost
            record["measured_vs_predicted_mfu"] = [
                round(mfu, 4), cost["predicted_mfu"]]
    print(json.dumps(record))


if __name__ == "__main__":
    if os.environ.get("BENCH_PREDICT") == "1":
        predict_main()
    elif os.environ.get("BENCH_CHILD") == "1":
        main()
    else:
        run_watchdogged(
            METRIC, UNIT, os.path.abspath(__file__),
            crash_dir=os.path.join(
                os.environ.get("BENCH_OBS_DIR", "bench_results/obs_train"),
                "crash"))

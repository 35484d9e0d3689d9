#!/usr/bin/env python
"""ZeRO + offload benchmark — BASELINE tracked config #2 (ZeRO Adam on
OPT-1.3B). Prints ONE JSON line.

The single-chip showcase of the offload tier (reference ZeRO-Offload blog
claim: 1.4B trainable on one V100-16GB, docs/_posts/2021-03-08-zero3-offload):
OPT-1.3B AdamW training on one 16 GB chip — the fp32 master + moments
(~15.6 GB, 12 bytes/param) live in host memory via
``offload_optimizer.device='cpu'``; HBM holds only bf16 params + grads +
remat'd activations. Without offload this config does not fit.

``vs_baseline`` = MFU / 0.5 (same north-star normalisation as bench.py).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from bench import peak_flops_per_chip


def _maybe_report_oom(e: Exception, metric: str, preset: str) -> None:
    """On device OOM, print a structured record instead of only a traceback:
    a resident-ZeRO config that physically exceeds one chip's HBM (BASELINE
    tracked config #2 as specified: OPT-1.3B Adam => ~21 GB fp32 state +
    bf16 params/grads on a 16 GB v5e) is an honest single-chip result, not a
    harness failure — partitioned ZeRO states need world > 1 to shrink."""
    msg = str(e)
    if "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower():
        print(json.dumps({
            "metric": metric, "value": None, "unit": "tokens/s",
            "vs_baseline": None, "oom": True,
            "single_chip_caveat": (
                f"{preset} resident ZeRO does not fit one chip's HBM "
                "(fp32 Adam state is 12 bytes/param; ZeRO partitioning "
                "reduces per-chip state only at world > 1) — the offload "
                "variants are the single-chip path"),
            "reason": msg[-300:],
        }))


def main() -> None:
    import deepspeed_tpu
    from deepspeed_tpu.models import create_model

    preset = os.environ.get("BENCH_ZERO_MODEL", "opt-1.3b")
    batch = int(os.environ.get("BENCH_ZERO_BATCH", 4))
    seq = int(os.environ.get("BENCH_ZERO_SEQ", 1024))
    stage = int(os.environ.get("BENCH_ZERO_STAGE", 2))
    offload = os.environ.get("BENCH_ZERO_OFFLOAD", "cpu")
    # BENCH_ZERO_PARAM_OFFLOAD=cpu|nvme: ZeRO-3 param offload — the whole
    # model's params stream through HBM per layer block (llama-7b trains on
    # one 16 GB chip; bf16 params alone are 13.5 GB). Forces stage 3 and
    # takes over the optimizer-state placement (host fp32).
    param_offload = os.environ.get("BENCH_ZERO_PARAM_OFFLOAD", "none")
    kw = {}
    if os.environ.get("BENCH_ZERO_LAYERS"):     # depth override: scale probes
        kw["num_layers"] = int(os.environ["BENCH_ZERO_LAYERS"])
    model = create_model(preset, dtype=jnp.bfloat16, remat=True,
                         remat_policy="dots", max_seq_len=seq, **kw)
    if param_offload != "none":
        stage, offload = 3, "none"
        zero_cfg = {"stage": 3,
                    "offload_param": {
                        "device": param_offload,
                        "buffer_size": int(os.environ.get(
                            "BENCH_ZERO_BUFFER", 800_000_000))}}
    else:
        zero_cfg = {"stage": stage}
        if offload != "none":
            zero_cfg["offload_optimizer"] = {"device": offload}
    cfg = {
        "train_micro_batch_size_per_gpu": batch,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 1000,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": zero_cfg,
    }
    tag = (f"param_offload-{param_offload}" if param_offload != "none"
           else f"offload-{offload}")
    metric = f"{preset}_zero{stage}_{tag}_train_tokens_per_sec_per_chip"
    try:
        engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
    except Exception as e:  # noqa: BLE001 — structured OOM record below
        _maybe_report_oom(e, metric, preset)
        raise

    # BENCH_ZERO_WARM=<seconds>: AOT-compile the offload segment programs
    # into the persistent XLA cache under a wall-clock budget, then exit.
    # Re-run until it reports remaining=0, then run the bench normally —
    # this is how >10B models fit a per-command time limit
    # (docs/offload_design.md scale status).
    warm = float(os.environ.get("BENCH_ZERO_WARM", 0))
    if warm > 0 and engine._param_offload is not None:
        done = engine._param_offload.compile_step_programs(
            (batch, seq), budget_s=warm)
        print(json.dumps({"metric": "warm_compile", "compiled": done}))
        return

    ids = jax.random.randint(jax.random.PRNGKey(0), (1, batch, seq), 0,
                             model.config.vocab_size)
    batch_tree = {"input_ids": ids}
    # BENCH_WARMUP: compile/stream warmup steps before timing (at the >10B
    # offload tier each step is minutes — 1 suffices once the compile cache
    # is warm)
    try:
        for _ in range(int(os.environ.get("BENCH_WARMUP", 2))):
            float(engine.train_batch(batch=batch_tree))
    except Exception as e:  # noqa: BLE001 — structured OOM record below
        _maybe_report_oom(e, metric, preset)
        raise

    steps = int(os.environ.get("BENCH_STEPS", 5))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch=batch_tree)
    float(loss)
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * steps / dt
    n_params = (engine._n_params if engine.params is None
                else sum(int(p.size) for p in jax.tree.leaves(engine.params)))
    cfg_m = model.config
    flops_per_token = (6 * n_params
                       + 12 * cfg_m.num_layers * cfg_m.hidden_size * seq)
    mfu = tokens_per_sec * flops_per_token / peak_flops_per_chip()
    print(json.dumps({
        "metric": metric,
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "params": n_params,
        "vs_baseline": round(mfu / 0.5, 4),
    }))


if __name__ == "__main__":
    main()

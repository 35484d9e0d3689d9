"""A training cell (`kind: train`): `initialize(...).train_batch()` on
batches fed from the host.

The window is a sequence of groups of `steps_per_group` consecutive
`train_batch` calls with one `block_until_ready` at the end of each group (a
training loop that logs every k steps; inside a group dispatch stays
asynchronous). `train_tok_s` is all the tokens of the window over all of its
time, so a stall anywhere in it is in the number; the tokens of one group
over the MEDIAN group time, which one stalled group does not move, goes out
as a counter for the per-layer metric `train_group_median_tok_s`. The group
times are printed, so a stall can be told from a slow run.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict

import numpy as np

from . import stats, traffic as traffic_mod
from .device import TARGET
from .program import (build_model, program_seed, reference_args,
                      reference_module)


# one group of steps is traced (the trace stops at the end of the group in
# which it started): every step is the same program
TRACE_SECONDS = 0.0


def run(cell, seed: int, seconds: float, tracer, devs, counter,
        setup) -> Dict[str, Any]:
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.models.transformer import active_attention_impl
    from deepspeed_tpu.parallel.mesh import DATA_SHARD, build_mesh
    from jax.profiler import TraceAnnotation
    from jax.sharding import NamedSharding, PartitionSpec

    t = cell.traffic
    model = build_model(cell, **t.get("model_options", {}))
    cfg = model.config
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=dict(t["engine"], seed=program_seed(seed)),
        mesh=build_mesh(devices=devs))
    setup.mark("engine")
    rows = t["engine"]["train_micro_batch_size_per_gpu"] * len(devs)
    seq, k = int(t["sequence"]), int(t["steps_per_group"])
    batches = traffic_mod.train_batches(seed, int(t["feed_batches"]), rows,
                                        seq, cfg.vocab_size)
    tokens_per_group = rows * seq * k
    problems = []
    want = "flash_attention" if TARGET["platform"] == "tpu" else "jnp"
    if active_attention_impl(cfg) != want:
        problems.append(f"attention resolves to "
                        f"'{active_attention_impl(cfg)}', not '{want}'")

    # the plain reference's loss on the first batch with the first step's
    # parameters: one float32 forward pass, outside the window
    ref = t["reference"]
    ids0 = jax.device_put(
        batches[0][0],
        NamedSharding(engine.mesh, PartitionSpec(DATA_SHARD, None)))
    reference, ref_args = reference_module(cell), reference_args(cell)
    with jax.default_matmul_precision("highest"):
        ref_loss = float(jax.jit(
            lambda p, ids: reference.loss(p, ids, **ref_args))(
                engine.params, ids0))

    setup.mark("reference")
    step_no = 0

    def steps(n, losses):
        nonlocal step_no
        t0 = time.perf_counter()
        for _ in range(n):
            with TraceAnnotation("train/feed"):
                batch = {"input_ids": batches[step_no % len(batches)]}
            with TraceAnnotation("train/step"):
                losses.append(engine.train_batch(batch=batch))
            step_no += 1
        with TraceAnnotation("train/group_sync"):
            jax.block_until_ready(losses[-1])
        return time.perf_counter() - t0

    warm = []
    steps(int(t["warmup_steps"]), warm)   # the first compiles, or loads
    first_loss = float(warm[0])

    counter.arm()
    group_seconds, losses = [], []
    setup_s = setup.done("warm_up")
    t_window = time.perf_counter()
    while time.perf_counter() - t_window < seconds or len(group_seconds) < 2:
        tracer.maybe_start(time.perf_counter() - t_window)
        group_seconds.append(steps(k, losses))
        tracer.maybe_stop(time.perf_counter() - t_window)
    window_s = time.perf_counter() - t_window
    tracer.stop()
    counter.disarm()

    losses = [float(x) for x in losses]
    # all of the window's time. Between two groups an untraced run only reads
    # the clock; a traced run starts and stops the profiler there, seconds
    # that are the harness's own, so it counts the groups' time alone
    rates = stats.group_rates(tokens_per_group, group_seconds,
                              None if tracer.on else window_s)
    print(json.dumps({"group_seconds": [round(g, 6) for g in group_seconds],
                      "window_s": round(window_s, 6),
                      "steps_per_group": k,
                      "tokens_per_group": tokens_per_group}), flush=True)
    third = max(1, len(group_seconds) // 3) * k
    head, tail = np.mean(losses[:third]), np.mean(losses[-third:])
    print(json.dumps({"loss_first_step": first_loss, "loss_reference": ref_loss,
                      "loss_first_groups": float(head),
                      "loss_last_groups": float(tail)}), flush=True)
    if not np.isfinite(losses).all():
        problems.append("non-finite loss in the window")
    if not tail < head:
        problems.append(f"loss did not fall: {head:.4f} -> {tail:.4f}")
    if not abs(first_loss - ref_loss) <= float(ref["loss_atol"]):
        problems.append(f"first step's loss {first_loss:.5f} is not within "
                        f"{ref['loss_atol']} of the reference's "
                        f"{ref_loss:.5f}")
    return {
        "problems": problems,
        "attempted": len(losses), "failed": int((~np.isfinite(losses)).sum()),
        "end_to_end": {"train_tok_s": rates["window_tok_s"],
                       "setup_s": setup_s},
        "counters": {"window_tok_s": rates["window_tok_s"],
                     "median_tok_s": rates["median_tok_s"],
                     "compiles_in_window": counter.count,
                     "tokens_per_step": rows * seq,
                     "rows_per_chip": rows // len(devs), "sequence": seq},
        "model_config": cfg,
    }

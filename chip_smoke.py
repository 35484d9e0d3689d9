#!/usr/bin/env python
"""The quickest proof that the train and serve paths still start on the chip.

    python chip_smoke.py              one TPU chip: device, kernels, train, serve
    python chip_smoke.py --chips 4    four chips, one process: ZeRO-3 against
                                      ZeRO-1 and nothing else

One process; run it only through the chip tool. Every phase goes through the
entry points a user calls (``deepspeed_tpu.initialize`` / ``init_serving``) at
published widths with weights made from ``--seed``, and checks what comes out
against the repo's own references. A phase that fails ends the run with a
non-zero exit code and no result line; without a TPU the first phase fails.

The LAST line of stdout is the result and nothing else:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
Times printed on earlier lines are one run each, not a benchmark.
"""

import argparse
import gc
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# What a run must find on the machine. tests/unit/test_chip_smoke.py steers
# these to rehearse the control flow on the CPU; no option of the program does.
TARGET = {
    "platform": "tpu",
    "attention_impl": "flash_attention",
    "interpret": False,       # Pallas interpreter (CPU rehearsal)
    # what the compiled text of each program must hold: the Pallas kernels
    # by the names ops/ gives them (fused_layer_norm is a tpu_custom_call
    # too, so the target alone proves nothing), ZeRO-3's collectives
    "in_program": {
        "train": ("tpu_custom_call", "flash_attention_fwd",
                  "flash_attention_bwd_dq", "flash_attention_bwd_dkv"),
        "serving/prefill_chunk": ("tpu_custom_call",
                                  "paged_prefill_attention"),
        "serving/decode": ("tpu_custom_call", "paged_decode_attention"),
        "zero3": ("all-gather", "reduce-scatter", "flash_attention_fwd"),
    },
}

# Published widths, full depth. The serving arena takes ARENA_SHARE of the
# chip's memory (bf16 weights are 2.6 GB of 16 GB; the rest is the plain-path
# engine's small arena, prefill activations and the 50k-wide logits).
FULL = {
    "train": dict(model="gpt2-125m", seq=1024, micro_batch=32, steps=8,
                  unroll=12),          # all 12 layers unrolled
    "serve": dict(model="opt-1.3b", max_model_len=2048, block_size=16,
                  prefill_chunk=256, max_seqs=16, num_blocks=None,
                  requests=8, prompt_min=64, prompt_max=512, new_tokens=32),
    "zero": dict(model="opt-1.3b", seq=1024, micro_batch=2, steps=3),
}
TINY = {
    "train": dict(model="tiny", seq=64, micro_batch=2, steps=4, unroll=2),
    "serve": dict(model="tiny-opt", max_model_len=128, block_size=16,
                  prefill_chunk=32, max_seqs=4, num_blocks=40,
                  requests=3, prompt_min=20, prompt_max=70, new_tokens=4),
    "zero": dict(model="tiny-opt", seq=64, micro_batch=2, steps=3),
}
ARENA_SHARE = 0.55
# bf16 keeps 8 bits of mantissa: the paged kernels (f32 softmax over bf16 KV)
# and the plain forward round differently in every one of 24 layers
LOGPROB_ATOL = 0.1
LOSS_RTOL = 2e-2


class SmokeFailure(Exception):
    """A phase ran and what came out was wrong."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def device_label() -> str:
    d = jax.devices()
    return f"{d[0].device_kind} x{len(d)}"


# ---------------------------------------------------------------------------
# device


def phase_device(chips: int) -> dict:
    from importlib import metadata

    import jaxlib

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    devs = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say("device", f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
                  f"libtpu {libtpu}")
    say("device", f"platform={devs[0].platform} kind={devs[0].device_kind} "
                  f"count={len(devs)}")
    check(devs[0].platform == TARGET["platform"],
          f"need a {TARGET['platform']} device, JAX found "
          f"'{devs[0].platform}' — not carrying on without the chip")
    check(len(devs) >= chips, f"need {chips} device(s), JAX found {len(devs)}")
    say("device", f"compile cache: {enable_compile_cache()}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# kernels: each compiled Pallas kernel against its jnp reference, small input


def phase_kernels() -> None:
    from deepspeed_tpu import ops
    from deepspeed_tpu.models.transformer import (alibi_slopes,
                                                  dot_product_attention)

    it = TARGET["interpret"]
    rng = np.random.RandomState(0)
    worst = 0.0

    def rnd(*shape, dtype=jnp.bfloat16, scale=1.0):
        return jnp.asarray(rng.randn(*shape) * scale, dtype)

    def close(name, got, want, tol=3e-2):
        """max|got - want| within ``tol`` of the reference's scale (bf16
        inputs; the jnp references themselves run at the chip's default
        matmul precision)."""
        nonlocal worst
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        err = float(np.abs(got - want).max())
        worst = max(worst, err)
        say("kernels", f"{name:<40} max|err|={err:.2e}")
        check(np.isfinite(got).all(), f"{name}: non-finite output")
        check(err <= tol * max(1.0, float(np.abs(want).max())),
              f"{name}: kernel and reference disagree (max|err| {err:.3e})")

    # training path: flash attention fwd + bwd, fused layer norm
    q, k, v = (rnd(2, 256, 4, 64) for _ in range(3))

    def flash_loss(q, k, v):
        return jnp.sum(ops.flash_attention(q, k, v, causal=True, interpret=it)
                       .astype(jnp.float32) ** 2)

    def dense_loss(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, None, causal=True)
                       .astype(jnp.float32) ** 2)

    close("flash_attention fwd",
          ops.flash_attention(q, k, v, causal=True, interpret=it),
          dot_product_attention(q, k, v, None, causal=True))
    for name, g, w in zip("qkv", jax.grad(flash_loss, (0, 1, 2))(q, k, v),
                          jax.grad(dense_loss, (0, 1, 2))(q, k, v)):
        close(f"flash_attention bwd d{name}", g, w, tol=5e-2)
    x, sc, b = rnd(4, 128, 768), rnd(768, dtype=jnp.float32), \
        rnd(768, dtype=jnp.float32)
    close("fused_layer_norm",
          ops.fused_layer_norm(x, sc, b, 1e-5, False, it),
          ops.reference_layer_norm(x, sc, b))

    # serving path: paged decode + chunked prefill over a ragged block table
    # (arenas are (L, NUM_BLOCKS, BLOCK, K*D) and the kernels read one layer
    # inside them; pages out of order on purpose). The reference reads the
    # same layer's pool handed over alone, as a 1-layer arena
    heads, d, bs, layer = 4, 64, 16, 1
    ka, va = rnd(3, 12, bs, heads * d), rnd(3, 12, bs, heads * d)
    kp, vp = ka[layer][None], va[layer][None]
    bt = jnp.asarray([[5, 1, 7, 9], [3, 0, 0, 0], [8, 2, 4, 6]], jnp.int32)
    lengths = jnp.asarray([2 * bs + 5, 9, 4 * bs], jnp.int32)
    qd = rnd(3, heads, d)
    close("paged_decode_attention",
          ops.paged_decode_attention(qd, ka, va, layer, bt, lengths,
                                     interpret=it),
          ops.reference_paged_attention(qd[:, None], kp, vp, 0, bt,
                                        lengths[:, None] - 1)[:, 0])
    start = jnp.asarray([21, 0, 40], jnp.int32)
    qc = rnd(3, 16, heads, d)
    pos = start[:, None] + jnp.arange(16, dtype=jnp.int32)[None]
    close("paged_prefill_attention",
          ops.paged_prefill_attention(qc, ka, va, layer, bt, start,
                                      interpret=it),
          ops.reference_paged_attention(qc, kp, vp, 0, bt, pos))

    # offline generate(): dense decode with ragged alibi key positions
    qf, kc, vc = (rnd(2, 8, 64, dtype=jnp.float32),
                  rnd(2, 256, 8, 64, dtype=jnp.float32),
                  rnd(2, 256, 8, 64, dtype=jnp.float32))
    valid = jnp.broadcast_to(
        (jnp.arange(256)[None] < 100).astype(jnp.int32), (2, 256))
    col = jnp.arange(256, dtype=jnp.float32)
    kpos = jnp.stack([col, col - 30.0 * (col >= 50)])
    al = alibi_slopes(8)
    close("decode_attention alibi+key_positions",
          ops.decode_attention(qf, kc, vc, valid, alibi=al,
                               key_positions=kpos, interpret=it),
          ops.reference_decode_attention(qf, kc, vc, valid, alibi=al,
                                         key_positions=kpos))

    # quantized decode GEMMs
    xq = rnd(8, 2048)
    w = rnd(2048, 1024, dtype=jnp.float32, scale=0.02)
    q8 = jnp.clip(jnp.round(w / 0.01), -127, 127).astype(jnp.int8)
    s8 = jnp.full((1, 1024), 0.01, jnp.float32)
    q4, s4 = ops.quantize_int4(w, group_size=128)
    for name, fn, ref, wq, s in (
            ("int8_matmul", ops.int8_matmul, ops.reference_int8_matmul,
             q8, s8),
            ("int8_a8_matmul", ops.int8_a8_matmul,
             ops.reference_int8_a8_matmul, q8, s8),
            ("int4_matmul", ops.int4_matmul, ops.reference_int4_matmul,
             q4, s4),
            ("int4_a8_matmul", ops.int4_a8_matmul,
             ops.reference_int4_a8_matmul, q4, s4)):
        close(name, fn(xq, wq, s, interpret=it),
              ref(xq, wq, s, out_dtype=jnp.float32))

    # block-sparse attention, with a query tile that attends to nothing
    layout = np.zeros((1, 2, 2), np.int64)
    layout[0, 0, 0] = 1
    plan = ops.build_tile_plan(layout, 128, 256)
    qs, ks, vs = (rnd(1, 256, 1, 64, dtype=jnp.float32) for _ in range(3))
    out = ops.block_sparse_attention(qs, ks, vs, plan, interpret=it)
    close("block_sparse_attention active rows", out[:, :128],
          dot_product_attention(qs[:, :128], ks[:, :128], vs[:, :128], None,
                                causal=False))
    check(float(jnp.abs(out[:, 128:]).max()) == 0.0,
          "block_sparse_attention: the empty query tile is not zero")
    say("kernels", f"all within tolerance on {device_label()} "
                   f"(worst max|err| {worst:.2e})")


# ---------------------------------------------------------------------------
# shared by the engine phases


def require_in_program(ep, needles) -> None:
    """``ep`` is a program an engine registered with the repo's program
    auditor (tools/tpuaudit): its compiled HLO — what actually runs, out of
    the persistent cache when the engine has compiled it — holds each of
    ``needles``."""
    from tools.tpuaudit.core import trace_entry

    text = trace_entry(ep, do_compile=True)[2].as_text()
    for needle in needles:
        check(needle in text, f"compiled {ep.name} holds no '{needle}'")
    say(ep.name, f"compiled program holds {', '.join(needles)}")


def registered(name: str):
    from tools.tpuaudit.registry import get_entry_points

    return get_entry_points([name])[0]


def train_config(micro_batch: int, zero_stage: int, seed: int) -> dict:
    """A plain AdamW bf16 training config, observability off."""
    return {
        "seed": seed,
        "train_micro_batch_size_per_gpu": micro_batch,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 1000,
        "optimizer": {"type": "adamw",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": zero_stage},
    }


def run_steps(engine, batch, steps: int):
    """``steps`` train_batch calls on one repeated batch. Returns (losses,
    seconds of the first step — compile included —, mean seconds of the warm
    steps after the second)."""
    t0 = time.perf_counter()
    losses = [engine.train_batch(batch=batch)]
    jax.block_until_ready(losses[0])
    first = time.perf_counter() - t0
    losses.append(engine.train_batch(batch=batch))
    jax.block_until_ready(losses[1])
    t0 = time.perf_counter()
    for _ in range(steps - 2):
        losses.append(engine.train_batch(batch=batch))
    jax.block_until_ready(losses[-1])
    warm = (time.perf_counter() - t0) / (steps - 2)
    return [float(x) for x in losses], first, warm


# ---------------------------------------------------------------------------
# train: gpt2-125m, one chip


def phase_train(p: dict, seed: int) -> None:
    import deepspeed_tpu
    from deepspeed_tpu.models import create_model
    from deepspeed_tpu.models.transformer import active_attention_impl
    from deepspeed_tpu.parallel.mesh import build_mesh

    model = create_model(p["model"], dtype=jnp.bfloat16, remat=True,
                         remat_policy="dots", scan_unroll=p["unroll"],
                         max_seq_len=p["seq"])
    cfg = model.config
    say("train", f"{p['model']}: hidden {cfg.hidden_size}, {cfg.num_layers} "
                 f"layers, {cfg.num_heads} heads, vocab {cfg.vocab_size}, "
                 f"seq {p['seq']}, micro-batch {p['micro_batch']}, bf16, "
                 f"AdamW, ZeRO-0")
    impl = active_attention_impl(cfg)
    check(impl == TARGET["attention_impl"],
          f"attention resolves to '{impl}', not '{TARGET['attention_impl']}'")
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=train_config(p["micro_batch"], 0, seed),
        mesh=build_mesh(devices=jax.devices()[:1]))
    ids = np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (1, p["micro_batch"], p["seq"])).astype(np.int32)
    losses, first, warm = run_steps(engine, {"input_ids": ids}, p["steps"])
    say("train", "loss " + " ".join(f"{x:.4f}" for x in losses))
    check(all(np.isfinite(losses)), "non-finite training loss")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses[0]} -> "
          f"{losses[-1]}")
    tokens = p["micro_batch"] * p["seq"]
    say("train", f"device={device_label()} first step {first:.1f} s (compile "
                 f"included), warm step {warm * 1e3:.1f} ms, "
                 f"{tokens / warm:,.0f} tokens/s — one run, not a benchmark")
    require_in_program(registered("train/step"),
                       TARGET["in_program"]["train"])


# ---------------------------------------------------------------------------
# serve: opt-1.3b, one chip, Pallas paged kernels against the plain forward


def phase_serve(p: dict, seed: int) -> None:
    import deepspeed_tpu
    from deepspeed_tpu.inference.engine import InferenceConfig
    from deepspeed_tpu.inference.kv_cache import paged_cache_memory_bytes
    from deepspeed_tpu.models import create_model
    from deepspeed_tpu.models.transformer import gather_target_logprobs
    from deepspeed_tpu.serving import ServingConfig

    model = create_model(p["model"], dtype=jnp.bfloat16)
    cfg = model.config
    num_blocks = p["num_blocks"]
    if num_blocks is None:      # fill the chip the way a deployment would
        limit = jax.devices()[0].memory_stats()["bytes_limit"]
        per_block = paged_cache_memory_bytes(cfg, 1, p["block_size"],
                                             jnp.bfloat16)
        num_blocks = int(ARENA_SHARE * limit) // per_block
    shape = dict(block_size=p["block_size"],
                 max_model_len=p["max_model_len"],
                 prefill_chunk=p["prefill_chunk"])
    serving = deepspeed_tpu.init_serving(
        model=model,
        serving_config=ServingConfig(max_seqs=p["max_seqs"],
                                     num_blocks=num_blocks, **shape),
        config=InferenceConfig(dtype=jnp.bfloat16, seed=seed))
    arena_gb = paged_cache_memory_bytes(
        cfg, num_blocks + 1, p["block_size"], jnp.bfloat16) / 2 ** 30
    say("serve", f"{p['model']}: hidden {cfg.hidden_size}, {cfg.num_layers} "
                 f"layers, {cfg.num_heads} heads, vocab {cfg.vocab_size}, "
                 f"bf16; arena {num_blocks} blocks x {p['block_size']} tokens "
                 f"= {arena_gb:.2f} GiB, {p['max_seqs']} decode rows, chunk "
                 f"{p['prefill_chunk']}")
    programs = [registered(name)
                for name in ("serving/prefill_chunk", "serving/decode")]

    rng = np.random.RandomState(seed)
    lens = np.linspace(p["prompt_min"], p["prompt_max"],
                       p["requests"]).astype(int)

    def serve_round(label):
        """Fresh random prompts in, every request finished, tokens in range."""
        prompts = [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
                   for n in lens]
        t0 = time.perf_counter()
        handles = [serving.submit(pr, max_new_tokens=p["new_tokens"])
                   for pr in prompts]
        streamed = list(handles[0].stream(timeout_s=900.0))  # drives the engine
        outs = [h.result(timeout_s=900.0) for h in handles]
        wall = time.perf_counter() - t0
        check(streamed == outs[0].tolist(), "stream() and result() disagree")
        for h, out in zip(handles, outs):
            check(h.state == "finished" and len(out) == p["new_tokens"],
                  f"request {h.request_id}: state {h.state}, {len(out)} of "
                  f"{p['new_tokens']} tokens")
            check(((out >= 0) & (out < cfg.vocab_size)).all(),
                  f"request {h.request_id}: token out of range")
        n_in, n_out = int(lens.sum()), sum(len(o) for o in outs)
        say("serve", f"device={device_label()} {label}: {len(outs)} requests "
                     f"finished, prompts {lens.tolist()}, {n_in} tokens in and "
                     f"{n_out} out in {wall:.1f} s — one run, not a benchmark")
        return prompts, outs

    prompts, outs = serve_round("first round (compile included)")
    serve_round("second round (compiled, blocks recycled)")

    # same weights, plain path: the forward over the whole sequence, no
    # cache and no table. Score the longest prompt plus what was generated
    # for it on both (several chunks: later ones read earlier pages through
    # the table) and generate from the shortest prompt again.
    seq = np.concatenate([prompts[-1], outs[-1]])
    lp_kernel = serving.score_logprobs(seq)
    lp_plain = np.asarray(gather_target_logprobs(
        serving.engine.forward(seq[None, :-1]), jnp.asarray(seq[None, 1:])))[0]
    diff = float(np.abs(lp_kernel - lp_plain).max())
    say("serve", f"prefill log-probs over {len(seq)} tokens, paged kernels "
                 f"vs the plain forward: max|diff| {diff:.3e} (tolerance "
                 f"{LOGPROB_ATOL}, bf16), mean log-prob "
                 f"{float(lp_kernel.mean()):.3f}")
    check(np.isfinite(lp_kernel).all() and np.isfinite(lp_plain).all(),
          "non-finite log-probs")
    check(diff <= LOGPROB_ATOL,
          f"paged kernels and the plain forward disagree: max|diff| {diff}")
    again = np.asarray(serving.engine.generate(
        prompts[0][None], max_new_tokens=p["new_tokens"]))[0]
    same = int((again == outs[0]).sum())
    say("serve", f"greedy tokens, served vs generate(), shortest prompt: "
                 f"{same} of {len(again)} equal (near-ties of random weights "
                 f"may flip in bf16)")
    serving.close()

    for ep in programs:
        require_in_program(ep, TARGET["in_program"][ep.name])


# ---------------------------------------------------------------------------
# --chips 4: ZeRO-3 data-parallel training against ZeRO-1, one process


def phase_zero(p: dict, seed: int, devices) -> None:
    import deepspeed_tpu
    from deepspeed_tpu.models import create_model
    from deepspeed_tpu.parallel.mesh import build_mesh

    n = len(devices)
    ids = None
    results = {}
    for stage in (3, 1):
        model = create_model(p["model"], dtype=jnp.bfloat16, remat=True,
                             remat_policy="dots", max_seq_len=p["seq"])
        if ids is None:
            ids = np.random.RandomState(seed).randint(
                0, model.config.vocab_size,
                (1, n * p["micro_batch"], p["seq"])).astype(np.int32)
        before = [(d.memory_stats() or {}).get("bytes_in_use", 0)
                  for d in devices]
        engine, *_ = deepspeed_tpu.initialize(
            model=model, config=train_config(p["micro_batch"], stage, seed),
            mesh=build_mesh(devices=devices))
        n_params = sum(int(x.size) for x in jax.tree.leaves(engine.params))
        used = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
        if None not in used:       # the CPU backend reports none
            used = [(u - b) / 2 ** 30 for u, b in zip(used, before)]
            say(f"zero{stage}", "engine state per device (GiB): "
                + " ".join(f"{u:.2f}" for u in used))
            check(max(used) < 1.25 * min(used),
                  f"ZeRO-{stage} state is piled up, not spread: {used}")
            if stage == 3:
                # bf16 params + fp32 master and two moments: 14 B/param
                whole = 14 * n_params / 2 ** 30
                check(max(used) < 0.5 * whole,
                      f"ZeRO-3 holds {max(used):.2f} GiB on one device of "
                      f"{whole:.2f} GiB of state — not partitioned")
        losses, first, warm = run_steps(engine, {"input_ids": ids},
                                        p["steps"])
        say(f"zero{stage}", f"{p['model']} {n_params / 1e9:.2f}B params, "
            f"{n} devices, global batch {ids.shape[1]} x {p['seq']}: loss "
            + " ".join(f"{x:.4f}" for x in losses))
        say(f"zero{stage}", f"device={device_label()} first step {first:.1f} s"
            f" (compile included), warm step {warm * 1e3:.0f} ms — one run, "
            "not a benchmark")
        check(all(np.isfinite(losses)), f"ZeRO-{stage}: non-finite loss")
        if stage == 3:
            require_in_program(registered("train/step"),
                               TARGET["in_program"]["zero3"])
        results[stage] = losses
        del engine, model
        gc.collect()
    worst = max(abs(a - b) / abs(b)
                for a, b in zip(results[3], results[1]))
    say("zero", f"ZeRO-3 vs ZeRO-1 loss, worst relative difference "
                f"{worst:.2e} (tolerance {LOSS_RTOL})")
    check(worst <= LOSS_RTOL, "ZeRO-3 and ZeRO-1 losses disagree")
    check(results[3][-1] < results[3][0], "ZeRO-3 loss did not fall")


# ---------------------------------------------------------------------------


def watch_compiles() -> dict:
    """Count what JAX compiles from here on: seconds in the backend compiler
    (a load from the persistent cache counts, and is short), programs, and
    how many of them the cache served."""
    from jax import monitoring

    seen = {"seconds": 0.0, "programs": 0, "cache_hits": 0}

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen["seconds"] += seconds
            seen["programs"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return seen


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the ZeRO-3 vs ZeRO-1 phase, on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, tokens and prompts are made from it")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    compiles = watch_compiles()
    try:
        device = phase_device(args.chips)
        if args.chips == 4:
            phase_zero(FULL["zero"], args.seed, jax.devices()[:4])
        else:
            phase_kernels()
            phase_train(FULL["train"], args.seed)
            gc.collect()     # the train engine's state leaves the chip
            phase_serve(FULL["serve"], args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    say("done", f"{time.perf_counter() - t0:.0f} s in all, "
                f"{compiles['seconds']:.1f} s of it compiling "
                f"{compiles['programs']} programs ({compiles['cache_hits']} "
                f"came from the persistent cache)")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""MoE training benchmark — BASELINE tracked config #4 (8-expert GPT,
all-to-all dispatch). Prints ONE JSON line.

On one chip the expert all-to-all is intra-device (the dispatch/combine
einsums still run); multi-chip EP rides the same program with the expert
axis sharded — dry-run validated by __graft_entry__/tests, measured here
for per-chip throughput.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from bench import peak_flops_per_chip


def _layer0_drop_rate(engine, cfg_m, ids, batch, seq, k) -> float:
    """Routing stats on the exact pre-MLP hidden of layer 0 (learned-pos
    decoder path: embed + attention sub-block + ln2)."""
    import jax

    from deepspeed_tpu.models.transformer import (_norm,
                                                  dot_product_attention)
    from deepspeed_tpu.parallel.moe import topk_plan

    p = engine.params
    l0 = jax.tree.map(lambda x: x[0], p["layers"])
    B, S, H = batch, seq, cfg_m.hidden_size
    N, D = cfg_m.num_heads, cfg_m.head_dim

    @jax.jit
    def pre_mlp_hidden(params, ids):
        x = params["embed"]["tokens"][ids].astype(jnp.float32)
        if cfg_m.position == "learned":
            x = x + params["pos"][jnp.arange(S)].astype(jnp.float32)
        h = _norm(x, l0["ln1"]["scale"], l0["ln1"].get("bias"),
                  cfg_m.norm, cfg_m.norm_eps)
        q = (h @ l0["attn"]["wq"].astype(jnp.float32)
             + l0["attn"].get("bq", 0.0)).reshape(B, S, N, D)
        kk = (h @ l0["attn"]["wk"].astype(jnp.float32)
              + l0["attn"].get("bk", 0.0)).reshape(B, S, N, D)
        v = (h @ l0["attn"]["wv"].astype(jnp.float32)
             + l0["attn"].get("bv", 0.0)).reshape(B, S, N, D)
        attn = dot_product_attention(q, kk, v, None, causal=True)
        out = (attn.reshape(B, S, N * D) @ l0["attn"]["wo"].astype(jnp.float32)
               + l0["attn"].get("bo", 0.0))
        x = x + out
        h2 = _norm(x, l0["ln2"]["scale"], l0["ln2"].get("bias"),
                   cfg_m.norm, cfg_m.norm_eps)
        return (h2.reshape(B * S, H)
                @ l0["router"].astype(jnp.float32))

    logits = pre_mlp_hidden(p, ids)
    plan = topk_plan(logits, k, cfg_m.moe_capacity_factor,
                     cfg_m.moe_min_capacity)
    kept = float(plan.valid.sum())
    return 1.0 - kept / (batch * seq * k)


def main() -> None:
    import deepspeed_tpu
    from deepspeed_tpu.models import create_model

    batch = int(os.environ.get("BENCH_BATCH", 16))
    seq = int(os.environ.get("BENCH_SEQ", 1024))
    # 350m-8e (~1.7B total params) exceeds one v5e's HBM with optimizer
    # state; the 125m-8e variant (~560M) is the single-chip default
    preset = os.environ.get("BENCH_MOE_MODEL", "moe-gpt-125m-8e")
    # unlike the dense bench, full unroll does NOT pay here: the expert
    # dispatch/combine einsums dominate (25.1k tok/s unrolled vs 25.7k
    # scanned on v5e) and the unrolled 8-expert program OOMs compile
    unroll = int(os.environ.get("BENCH_UNROLL", 1))
    dispatch = os.environ.get("BENCH_MOE_DISPATCH", "sparse")
    remat = os.environ.get("BENCH_REMAT", "1") == "1"
    model = create_model(preset, dtype=jnp.bfloat16, remat=remat,
                         remat_policy="dots", scan_unroll=unroll,
                         max_seq_len=seq, moe_dispatch=dispatch)
    cfg = {
        "train_micro_batch_size_per_gpu": batch,
        "steps_per_print": 1000,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-4}},
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 0},
    }
    engine, *_ = deepspeed_tpu.initialize(model=model, config=cfg)
    ids = jax.random.randint(jax.random.PRNGKey(0), (1, batch, seq), 0,
                             model.config.vocab_size)
    tree = {"input_ids": ids}
    for _ in range(2):
        loss = engine.train_batch(batch=tree)
    float(loss)
    steps = int(os.environ.get("BENCH_STEPS", 8))
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch(batch=tree)
    float(loss)
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * steps / dt
    cfg_m = model.config
    # active params per token: dense part + top_k of E experts + router
    n_all = sum(int(p.size) for p in jax.tree.leaves(engine.params))
    expert_params = sum(int(p.size) for p in
                        jax.tree.leaves(engine.params["layers"]["mlp"]))
    active = (n_all - expert_params
              + expert_params * cfg_m.moe_top_k // cfg_m.moe_num_experts)
    flops_per_token = 6 * active + 12 * cfg_m.num_layers * cfg_m.hidden_size * seq

    # ---- roofline accounting (VERDICT r2 #9, r3 weak #1) ----------------
    # einsum: the dense (T,EC)x(T,H) one-hot contraction pays 2*T*E*C*H
    # flops each way — at E=8, cap 1.25, top-2 that is ~5x the expert MLP
    # itself, so that formulation is dispatch-BOUND.
    # sparse (default): dispatch is a GATHER (no flops) and combine is a
    # (T,K,H) gather + weighted sum — dispatch cost scales with routed
    # tokens and the roofline is set by expert compute again.
    from deepspeed_tpu.parallel.moe import _capacity

    H, F, L = cfg_m.hidden_size, cfg_m.ffn_hidden_size, cfg_m.num_layers
    E, k = cfg_m.moe_num_experts, cfg_m.moe_top_k
    T = batch * seq
    C = _capacity(T, E, cfg_m.moe_capacity_factor * (2 if k == 2 else 1),
                  cfg_m.moe_min_capacity)
    n_mat = 3 if cfg_m.activation == "swiglu" else 2
    expert_fwd = 2 * E * C * H * F * n_mat            # per layer
    if cfg_m.moe_dispatch == "einsum":
        dispatch_fwd = 2 * (2 * T * E * C * H)        # dispatch + combine
    else:
        dispatch_fwd = 2 * T * k * H                  # sparse combine only
    # extra fwd flops beyond what 6*active already counts: experts run on
    # CAPACITY slots (E*C >= k*T tokens) plus the dense dispatch einsums
    moe_extra = L * (expert_fwd + dispatch_fwd) - L * 2 * T * (
        expert_params // L) * k // E
    # train = fwd + bwd (2x) + remat recompute (~1x) => 4x forward cost for
    # the MoE layers (dots policy recomputes the einsums)
    total_step_flops = flops_per_token * T + 4 * moe_extra
    roofline_tps = peak_flops_per_chip() * T / total_step_flops
    dispatch_frac = (4 * L * dispatch_fwd) / total_step_flops

    # capacity-drop rate on the TRUE layer-0 router input (embed + attention
    # sub-block + ln2, replicated with the model's own helpers — raw token
    # embeddings route differently): fraction of (token, expert) assignments
    # that exceeded capacity
    drop_rate = _layer0_drop_rate(engine, cfg_m, ids[0], batch, seq, k)

    mfu = tokens_per_sec * flops_per_token / peak_flops_per_chip()
    print(json.dumps({
        "metric": f"{preset}_bf16_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "active_param_mfu": round(mfu, 4),
        "vs_baseline": round(mfu / 0.5, 4),
        "vs_roofline": round(tokens_per_sec / roofline_tps, 4),
        "roofline_tokens_per_sec": round(roofline_tps, 1),
        "dispatch_flops_frac": round(dispatch_frac, 4),
        "capacity_drop_rate": round(drop_rate, 4),
        "dispatch_impl": dispatch,
    }))


if __name__ == "__main__":
    main()

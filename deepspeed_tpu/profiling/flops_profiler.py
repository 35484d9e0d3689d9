"""FLOPs profiler.

Reference: ``deepspeed/profiling/flops_profiler/profiler.py:23`` — there, a
monkey-patched torch counts MACs per module via hooks. Under jit that
machinery dissolves: XLA already knows the program cost. Two complementary
sources are combined:

  * ``jax.stages.Compiled.cost_analysis()`` — the compiler's own whole-program
    flops / bytes-accessed estimate (exact for what actually runs, including
    fusion effects);
  * an analytic per-module breakdown from the ``TransformerConfig`` — the
    per-module tree the reference prints (attention / MLP / embedding / head
    per layer), which the compiled program cannot attribute.

``get_model_profile`` mirrors the reference's public helper of the same name
(flops_profiler/profiler.py get_model_profile): model + batch shape → total
flops/MACs/params + formatted per-module table.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple


# -- humanised formatting (reference profiler.py number_to_string etc.) ------

def number_string(n: float, units: Optional[str] = None, precision: int = 2) -> str:
    for cut, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "K")):
        if abs(n) >= cut:
            return f"{n / cut:.{precision}f} {suffix}{units or ''}"
    return f"{n:.{precision}f} {units or ''}"


def flops_string(f: float, precision: int = 2) -> str:
    return number_string(f, "FLOPs", precision)


def params_string(p: float, precision: int = 2) -> str:
    return number_string(p, "", precision).strip()


def duration_string(sec: float, precision: int = 2) -> str:
    if sec >= 1:
        return f"{sec:.{precision}f} s"
    if sec >= 1e-3:
        return f"{sec * 1e3:.{precision}f} ms"
    return f"{sec * 1e6:.{precision}f} us"


# -- compiled-program cost ---------------------------------------------------


def compiled_cost(compiled) -> Dict[str, float]:
    """flops / bytes from a ``jax.stages.Compiled`` (XLA cost analysis).

    Delegates to the tpucost extraction helpers — the single implementation
    of compiled-artifact metric parsing (``tools/tpucost/extract.py``), the
    same one the CI cost gate reads, so the profiler and the gate can never
    disagree on what a program costs. A deployment shipped without the
    ``tools/`` tree degrades to {} (the same contract as a backend without
    cost analysis)."""
    try:
        from tools.tpucost.extract import cost_analysis_dict
    except ImportError:
        return {}
    cost = cost_analysis_dict(compiled)
    if not cost:
        return {}
    return {"flops": cost["flops"], "bytes_accessed": cost["bytes_accessed"]}


# -- analytic transformer breakdown -----------------------------------------


@dataclasses.dataclass
class FlopsProfile:
    total_params: int
    total_flops: float            # forward flops for the given batch
    per_module: Dict[str, Dict[str, float]]
    batch_size: int
    seq_len: int

    def flops_per_token(self) -> float:
        return self.total_flops / max(self.batch_size * self.seq_len, 1)

    def table(self, step_time: Optional[float] = None,
              peak_flops: Optional[float] = None) -> str:
        lines = [f"{'module':<16}{'params':>12}{'fwd FLOPs':>16}{'share':>8}",
                 "-" * 52]
        for name, row in self.per_module.items():
            share = row["flops"] / self.total_flops if self.total_flops else 0
            lines.append(f"{name:<16}{params_string(row['params']):>12}"
                         f"{number_string(row['flops'], ''):>16}{share:>7.1%}")
        lines.append("-" * 52)
        lines.append(f"{'total':<16}{params_string(self.total_params):>12}"
                     f"{number_string(self.total_flops, ''):>16}")
        if step_time:
            # fwd+bwd ~ 3x fwd flops (reference uses the same 1:2 rule)
            achieved = 3 * self.total_flops / step_time
            lines.append(f"step time {duration_string(step_time)}  "
                         f"achieved {flops_string(achieved)}/s"
                         + (f"  MFU {achieved / peak_flops:.1%}"
                            if peak_flops else ""))
        return "\n".join(lines)


def transformer_breakdown(cfg, batch_size: int, seq_len: int) -> FlopsProfile:
    """Analytic per-module forward profile for a TransformerConfig (MACs*2)."""
    H, L, V, F = (cfg.hidden_size, cfg.num_layers, cfg.vocab_size,
                  cfg.ffn_hidden_size)
    N, K, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    T = batch_size * seq_len                      # tokens
    E = max(cfg.moe_num_experts, 1)
    topk = cfg.moe_top_k if cfg.moe_num_experts else 1

    qkv_params = H * (N * D) + 2 * H * (K * D)
    attn_params = qkv_params + (N * D) * H
    if cfg.activation == "swiglu":
        mlp_params_one = 3 * H * F
        mlp_flops_tok = 2 * 3 * H * F
    else:
        mlp_params_one = 2 * H * F
        mlp_flops_tok = 2 * 2 * H * F
    mlp_params = mlp_params_one * E
    router_params = H * cfg.moe_num_experts if cfg.moe_num_experts else 0

    per_module = {
        "embedding": {"params": V * H, "flops": 0.0},
        "attention": {"params": L * attn_params,
                      "flops": T * L * (2 * attn_params
                                        + 4 * seq_len * N * D)},
        "mlp": {"params": L * (mlp_params + router_params),
                "flops": T * L * (mlp_flops_tok * topk
                                  + 2 * router_params)},
        "norms": {"params": L * (2 * H) * (2 if cfg.norm == "layernorm" else 1)
                  + H, "flops": T * L * 8 * H},
        "lm_head": {"params": 0 if cfg.tie_embeddings else H * V,
                    "flops": T * 2 * H * V},
    }
    if cfg.position == "learned":
        per_module["embedding"]["params"] += cfg.max_seq_len * H
    total_params = sum(int(m["params"]) for m in per_module.values())
    total_flops = sum(m["flops"] for m in per_module.values())
    return FlopsProfile(total_params=total_params, total_flops=total_flops,
                        per_module=per_module, batch_size=batch_size,
                        seq_len=seq_len)


def get_model_profile(model, batch_size: int, seq_len: int,
                      print_profile: bool = False,
                      measured: bool = False,
                      output_file: Optional[str] = None
                      ) -> Tuple[float, float, int]:
    """Reference get_model_profile parity: returns (flops, macs, params).

    ``measured=True`` additionally RUNS the model and prints the
    ``print_model_profile`` analog (reference profiler.py:239): a depth tree
    with measured wall latency, XLA-counted GFLOPs, params, and achieved
    FLOPS per module — depth 0 model, depth 1 embedding/layers/head, depth
    2 every individual layer block."""
    prof = transformer_breakdown(model.config, batch_size, seq_len)
    if measured:
        mp = measured_model_profile(model, batch_size, seq_len)
        text = mp.table()
        if output_file:
            with open(output_file, "w") as fh:
                fh.write(text + "\n")
        elif print_profile:
            print(text)
        return mp.total_flops, mp.total_flops / 2, prof.total_params
    if print_profile:
        print(prof.table())
    return prof.total_flops, prof.total_flops / 2, prof.total_params


# -- measured per-module tree (print_model_profile analog) -------------------


@dataclasses.dataclass
class ModuleMeasurement:
    name: str
    depth: int
    latency_s: float              # measured median wall time
    flops: float                  # XLA cost analysis (analytic fallback)
    params: int

    def achieved_flops_per_s(self) -> float:
        return self.flops / self.latency_s if self.latency_s > 0 else 0.0


@dataclasses.dataclass
class MeasuredProfile:
    """The measured module tree. ``modules`` is depth-first: the depth-0
    root, then each depth-1 group with its depth-2 children."""

    modules: list
    total_flops: float
    total_latency_s: float
    batch_size: int
    seq_len: int

    def table(self) -> str:
        head = (f"{'module':<24}{'params':>10}{'latency':>12}"
                f"{'GFLOPs':>10}{'FLOPS':>15}{'% time':>8}")
        lines = ["-" * 28 + " measured model profile " + "-" * 28,
                 f"batch {self.batch_size} x seq {self.seq_len} "
                 f"(forward; segment-jitted measurement)", head, "-" * 80]
        for m in self.modules:
            pct = (m.latency_s / self.total_latency_s
                   if self.total_latency_s else 0.0)
            lines.append(
                f"{'  ' * m.depth + m.name:<24}{params_string(m.params):>10}"
                f"{duration_string(m.latency_s):>12}"
                f"{m.flops / 1e9:>10.3f}"
                f"{flops_string(m.achieved_flops_per_s(), 1):>15}"
                f"{pct:>7.1%}")
        lines.append("-" * 80)
        return "\n".join(lines)


def _median_time(fn, args, repeats: int, warmup: int) -> float:
    import time as _time

    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(repeats):
        t0 = _time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(_time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def _segment_flops(jitted, args, fallback: float) -> float:
    try:
        cost = compiled_cost(jitted.lower(*args).compile())
    except Exception:
        return fallback
    return cost.get("flops") or fallback


def measured_model_profile(model, batch_size: int, seq_len: int,
                           repeats: int = 5, warmup: int = 2
                           ) -> MeasuredProfile:
    """Measure the forward pass module-by-module (reference
    print_model_profile, profiler.py:239 — there via module hooks; under
    jit, each stage becomes its own compiled segment timed with a device
    fence). Segment boundaries follow the model's real stages — embedding,
    every layer block (`_layer_forward`, the SAME function the full forward
    scans), final norm + lm_head — so per-layer numbers are the truth of
    the layer program, modulo cross-stage fusion the monolithic jit would
    additionally do (the reference's hooks perturb timing the same way)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..models.transformer import (Step, _layer_forward, _norm,
                                      eval_config, head_logits,
                                      require_one_pass, window_table)

    cfg = eval_config(model.config)
    require_one_pass(cfg, "the per-layer profile")
    # per-layer sliding windows (GPT-Neo attention_layers): each timed layer
    # must see ITS window, exactly as forward()'s scan passes it — else
    # 'local' layers would be profiled as all-global attention
    win_table = window_table(cfg) if cfg.attention_layers else None
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    ids = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size,
                                         (batch_size, seq_len)), jnp.int32)
    positions = jnp.arange(seq_len)

    # --- embedding segment (mirrors forward()'s embed stage) ---
    def embed_fn(p, i):
        x = p["embed"]["tokens"][i].astype(cfg.dtype)
        if cfg.position == "learned":
            x = x + p["pos"][positions].astype(cfg.dtype)
        if cfg.type_vocab_size > 0:
            x = x + p["type_embed"][
                jnp.zeros_like(i)].astype(cfg.dtype)
        if cfg.embed_norm:
            x = _norm(x, p["embed_norm"]["scale"],
                      p["embed_norm"].get("bias"), "layernorm", cfg.norm_eps)
        return x

    embed_jit = jax.jit(embed_fn)
    x = embed_jit(params, ids)

    # --- one compiled layer program, timed per layer's weights ---
    def layer_fn(layer, h, window):
        return _layer_forward(
            cfg, h, layer, Step(positions=positions, window=window))[0]

    layer_jit = jax.jit(layer_fn)

    def win(i: int):
        return win_table[i] if win_table is not None else None
    head_jit = jax.jit(lambda p, h: head_logits(p, h, cfg))

    analytic = transformer_breakdown(cfg, batch_size, seq_len)
    L = max(cfg.num_layers, 1)
    per_layer_analytic = (analytic.per_module["attention"]["flops"]
                          + analytic.per_module["mlp"]["flops"]
                          + analytic.per_module["norms"]["flops"]) / L

    def leaf_params(tree):
        return sum(int(p.size) for p in jax.tree.leaves(tree))

    layer0 = jax.tree.map(lambda p: p[0], params["layers"])
    embed_flops = _segment_flops(embed_jit, (params, ids), 0.0)
    layer_flops = _segment_flops(layer_jit, (layer0, x, win(0)),
                                 per_layer_analytic)
    head_flops = _segment_flops(head_jit, (params, x),
                                analytic.per_module["lm_head"]["flops"])

    t_embed = _median_time(embed_jit, (params, ids), repeats, warmup)
    layer_meas = []
    h = x
    for i in range(cfg.num_layers):
        layer_i = jax.tree.map(lambda p: p[i], params["layers"])
        t_i = _median_time(layer_jit, (layer_i, h, win(i)), repeats, warmup)
        layer_meas.append(t_i)
        h = layer_jit(layer_i, h, win(i))
    t_head = _median_time(head_jit, (params, h), repeats, warmup)

    layer_params = leaf_params(params["layers"]) // max(cfg.num_layers, 1)
    embed_params = leaf_params({k: v for k, v in params.items()
                                if k in ("embed", "pos", "type_embed",
                                         "embed_norm")})
    head_params = leaf_params({k: v for k, v in params.items()
                               if k in ("final_norm", "lm_head", "lm_head_b")})

    total_lat = t_embed + sum(layer_meas) + t_head
    total_flops = embed_flops + layer_flops * cfg.num_layers + head_flops
    modules = [
        ModuleMeasurement("model", 0, total_lat, total_flops,
                          leaf_params(params)),
        ModuleMeasurement("embedding", 1, t_embed, embed_flops, embed_params),
        ModuleMeasurement(f"layers (x{cfg.num_layers})", 1, sum(layer_meas),
                          layer_flops * cfg.num_layers,
                          layer_params * cfg.num_layers),
    ]
    for i, t_i in enumerate(layer_meas):
        modules.append(ModuleMeasurement(f"layer.{i}", 2, t_i, layer_flops,
                                         layer_params))
    modules.append(ModuleMeasurement("final_norm+lm_head", 1, t_head,
                                     head_flops, head_params))
    return MeasuredProfile(modules=modules, total_flops=total_flops,
                           total_latency_s=total_lat, batch_size=batch_size,
                           seq_len=seq_len)

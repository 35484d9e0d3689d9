"""Pipeline parallelism — TPU-native SPMD execution.

Analog of ``deepspeed/runtime/pipe/`` (``PipelineModule`` module.py:85,
``PipelineEngine`` engine.py:40, ``p2p.py``). The reference runs an
instruction interpreter per rank with pickled-meta p2p sends; on TPU the whole
pipeline is ONE jitted SPMD program over a partial-manual ``shard_map`` on the
'pipe' mesh axis (other axes stay automatic so TP/DP/ZeRO composes):

  * **training** = ``pipelined_grad_fn``: an explicit 1F1B executor scanning
    the interleaved step sequence of ``schedule.TrainSchedule`` — per-stage
    ``jax.vjp`` with a rotating ≤min(P,M)-slot input buffer (O(P) activation
    residency, the schedule.py:212 bound), stage-level recompute in backward,
    real branch skips on bubble steps, stage-0-only embedding, psum'd
    tied/replicated grads (ReduceTiedGrads);
  * **eval** = ``pipelined_loss_fn``: forward-only fill-drain scan;
  * stage-to-stage transfer is a ``ppermute`` ring shift both directions
    (SendActivation/RecvActivation down, SendGrad/RecvGrad up);
  * layer params are stacked, the leading stage dim sharded over 'pipe'.

Layer partitioning policies (uniform / parameters / type:regex) are kept for
API parity with ``PipelineModule._partition_layers`` (module.py:353).
"""

from __future__ import annotations

import re
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from ..models.core import LAYERS, Model
from ..utils.logging import logger
from .mesh import DATA_SHARD, MODEL_AXIS, PIPE_AXIS, SEQ_AXIS, get_mesh

PIPE_STAGE = "pipe_stage"   # logical axis for the stacked stage dim


# ---------------------------------------------------------------------------
# layer partitioning (reference module.py:353 _partition_layers)
# ---------------------------------------------------------------------------


def _record_schedule_census(schedule: str, num_stages: int, batch) -> None:
    """Publish the pipeline schedule's shape into the observability registry.

    Runs in the HOST wrapper around the shard_map body — i.e. at jit trace
    time, once per compiled program (a census, like the comms logger's traced
    events), never per step. The bubble fraction is the canonical
    (P-1)/(M+P-1) pipeline idle share — the number every PP perf PR is trying
    to push down."""
    from ..observability import get_session

    obs = get_session()
    if not obs.enabled:
        return
    # trace-time census is also a liveness heartbeat for the hang watchdog
    obs.heartbeat("pipeline/census")
    import numpy as _np

    # static shape metadata, concrete at trace time (never a device sync)
    # tpulint: disable=host-sync-in-jit
    M = int(_np.shape(jax.tree.leaves(batch)[0])[0])
    reg = obs.registry
    reg.counter("pipeline/traces",
                help="pipeline program specializations").inc(
                    schedule=schedule)
    reg.gauge("pipeline/stages").set(num_stages, schedule=schedule)
    reg.gauge("pipeline/microbatches").set(M, schedule=schedule)
    reg.gauge("pipeline/bubble_fraction",
              help="(P-1)/(M+P-1) schedule idle share").set(
                  (num_stages - 1) / max(M + num_stages - 1, 1),
                  schedule=schedule)


def partition_uniform(num_items: int, num_parts: int) -> List[int]:
    """Boundaries of a uniform split (reference runtime/utils.py:541); the
    remainder is distributed one-per-stage from the front."""
    chunk, residual = divmod(num_items, num_parts)
    return [min(p * chunk + min(p, residual), num_items)
            for p in range(num_parts + 1)]


def partition_balanced(weights: Sequence[float], num_parts: int) -> List[int]:
    """Boundaries minimizing the max part weight (reference
    runtime/utils.py:603 partition_balanced, prefix-sum + binary search)."""
    weights = list(weights)
    n = len(weights)
    prefix = np.concatenate([[0.0], np.cumsum(weights)])

    def parts_for(limit: float) -> Optional[List[int]]:
        bounds = [0]
        for _ in range(num_parts):
            start = bounds[-1]
            # furthest end with weight(start, end) <= limit
            end = int(np.searchsorted(prefix, prefix[start] + limit, side="right") - 1)
            end = max(end, start + 1)  # at least one item per part
            end = min(end, n)
            bounds.append(end)
        return bounds if bounds[-1] >= n else None

    lo = max(weights) if weights else 0.0
    hi = float(prefix[-1])
    for _ in range(40):
        mid = (lo + hi) / 2
        if parts_for(mid) is not None:
            hi = mid
        else:
            lo = mid
    result = parts_for(hi)
    result[-1] = n
    return result


def partition_layers(layers: Sequence[Any], num_stages: int,
                     method: str = "uniform") -> List[int]:
    """Stage boundaries for a layer list. Methods mirror the reference:
    'uniform' | 'parameters' (balance by param count) | 'type:regex'
    (balance count of layers whose class name matches)."""
    method = method.lower()
    if method == "uniform":
        return partition_uniform(len(layers), num_stages)
    if method == "parameters":
        weights = [float(getattr(l, "num_params", 1) or 1) for l in layers]
        return partition_balanced(weights, num_stages)
    if method.startswith("type:"):
        pattern = method.split(":", 1)[1]
        weights = [1.0 if re.search(pattern, type(l).__name__, re.IGNORECASE) else 0.0
                   for l in layers]
        if sum(weights) == 0:
            raise ValueError(f"no layer matches type regex '{pattern}'")
        return partition_balanced(weights, num_stages)
    raise ValueError(f"unknown partition method '{method}'")


class LayerSpec:
    """Deferred layer construction (reference pipe/module.py:29) — records a
    builder + args; ``build()`` instantiates. num_params estimated lazily for
    'parameters' partitioning."""

    def __init__(self, typename: Callable, *args, **kwargs):
        self.typename = typename
        self.args = args
        self.kwargs = kwargs

    def build(self):
        return self.typename(*self.args, **self.kwargs)

    def __repr__(self):
        return f"LayerSpec({getattr(self.typename, '__name__', self.typename)})"


# ---------------------------------------------------------------------------
# SPMD pipelined transformer loss
# ---------------------------------------------------------------------------


def _split_stages(layer_tree: Any, num_stages: int) -> Any:
    """(L, ...) stacked layer params → (P, L/P, ...)."""

    def reshape(x):
        L = x.shape[0]
        assert L % num_stages == 0, (
            f"num_layers {L} not divisible by pipeline stages {num_stages}")
        return x.reshape(num_stages, L // num_stages, *x.shape[1:])

    return jax.tree.map(reshape, layer_tree)


def _merge_stages(layer_tree: Any) -> Any:
    return jax.tree.map(lambda x: x.reshape(-1, *x.shape[2:]), layer_tree)


def _needs_fp32_body() -> bool:
    # round-1 carried an fp32-body workaround for an XLA SPMD partitioner
    # crash (bf16 + model-sharded operands under manual-pipe shard_map). The
    # training path now runs the explicit 1F1B executor in bf16; this eval-
    # path probe is retained as a switch should the partitioner regress.
    return False


def _stage_helpers(cfg):
    """Shared per-stage building blocks for BOTH the eval fill-drain loss and
    the 1F1B grad executor — one definition so train grads and eval losses
    can never structurally diverge (embed_norm incident of round 2)."""
    from ..models.transformer import (Step, _layer_forward, _norm,
                                      cross_entropy_loss, require_one_pass,
                                      resolve_remat_policy)

    require_one_pass(cfg, "pipeline parallelism")

    aux_coef = (cfg.moe_aux_loss_coef / max(cfg.num_layers, 1)
                if cfg.moe_num_experts > 0 else 0.0)
    if getattr(cfg, "attention_layers", ()):
        raise NotImplementedError(
            "pipeline parallelism + attention_layers (sliding-window, "
            "GPT-Neo) is not supported: stage loops have no global layer "
            "index, so local layers would silently run global")

    def embed_fn(et, token_ids, positions, dtype):
        x = et["embed"]["tokens"][token_ids].astype(dtype)
        if cfg.position == "learned":
            x = x + et["pos"][positions].astype(dtype)
        if cfg.embed_norm:
            x = _norm(x, et["embed_norm"]["scale"],
                      et["embed_norm"].get("bias"), "layernorm", cfg.norm_eps)
        return x

    def stage_apply(stage_layers, x, mask, positions):
        def block(h, layer):
            h, _, aux = _layer_forward(cfg, h, layer, Step(mask, positions))
            return h, aux

        block_fn = (jax.checkpoint(block, prevent_cse=False,
                                   policy=resolve_remat_policy(cfg))
                    if cfg.remat else block)
        x, auxs = lax.scan(block_fn, x, stage_layers,
                           unroll=cfg.scan_unroll)
        return x, jnp.sum(auxs)

    def head_loss(et, h, lbl, msk):
        from ..models.transformer import head_logits

        return cross_entropy_loss(head_logits(et, h, cfg), lbl, msk)

    def derive_labels(ids):
        return jnp.concatenate(
            [ids[:, :, 1:], jnp.full((*ids.shape[:2], 1), -100, ids.dtype)],
            axis=2)

    return embed_fn, stage_apply, head_loss, derive_labels, aux_coef


def pipelined_loss_fn(cfg, num_stages: int):
    """Build loss_fn(params, batch) where batch leaves have a leading
    microbatch dim M and params['layers'] leaves have leading stage dim P.

    The returned function must run under jit with the global mesh active.
    """
    (embed_helper, stage_apply, head_loss_fn, derive_labels,
     aux_coef) = _stage_helpers(cfg)

    def body(stage_arr, layers_stacked, embed_tree, batch):
        """Runs per-pipe-group (manual over 'pipe'; data/seq/model auto).
        stage_arr: (1,) i32 — this stage's index (an arange fed through the
        shard_map, sharded over 'pipe'; ``lax.axis_index`` would lower to a
        partition-id instruction the SPMD partitioner for the remaining
        AUTO axes rejects — the test_pipeline standalone failure).
        layers_stacked leaves: (1, Lp, ...) — this stage's layers.
        embed_tree: full non-layer params (replicated over pipe).
        batch leaves: (M, mb, S)."""
        stage_id = stage_arr[0]
        P_ = lax.psum(1, PIPE_AXIS)   # static: psum of a python int
        stage_layers = jax.tree.map(lambda x: x[0], layers_stacked)
        body_dtype = jnp.float32 if _needs_fp32_body() else cfg.dtype
        ids = batch["input_ids"]
        attn_mask = batch.get("attention_mask")          # (M, mb, S) or None
        labels = batch.get("labels")
        if labels is None:
            labels = derive_labels(ids)
        M, mb, S = ids.shape
        positions = jnp.arange(S)
        H = cfg.hidden_size

        def embed(token_ids):
            return embed_helper(embed_tree, token_ids, positions, body_dtype)

        n_ticks = M + P_ - 1

        def tick(carry, t):
            recv, aux_acc = carry
            mb_idx = t - stage_id                       # microbatch this stage works on
            src_idx = jnp.clip(mb_idx, 0, M - 1)
            my_ids = lax.dynamic_index_in_dim(ids, src_idx, axis=0, keepdims=False)
            my_mask = (lax.dynamic_index_in_dim(attn_mask, src_idx, 0, keepdims=False)
                       if attn_mask is not None else None)
            # stage 0 embeds fresh microbatches; others consume the ring buffer
            x = jnp.where(stage_id == 0, embed(my_ids), recv)
            x, aux = stage_apply(stage_layers, x, my_mask, positions)
            valid = (mb_idx >= 0) & (mb_idx < M)
            aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
            # keep the permuted activation replicated over model/seq — a
            # model-sharded carry through collective-permute crashes the XLA
            # CPU partitioner and adds no value (H dim is replicated anyway)
            from .sequence import constrain as _constrain

            x = _constrain(x, P(DATA_SHARD, None, None))
            recv_next = lax.ppermute(x, PIPE_AXIS,
                                     [(i, (i + 1) % P_) for i in range(P_)])
            return (recv_next, aux_acc), x

        init = (jnp.zeros((mb, S, H), body_dtype), jnp.float32(0.0))
        (_, aux_total), xs = lax.scan(tick, init, jnp.arange(n_ticks))  # (ticks, mb, S, H)

        # microbatch m finishes on the last stage at tick m + P - 1: its output
        # block is xs[P-1 : P-1+M]. Head+loss run ONCE, on the last stage only
        # (lax.cond branches at runtime — other stages skip the vocab matmul).
        outs = lax.dynamic_slice_in_dim(xs, P_ - 1, M, axis=0)  # (M, mb, S, H)

        def last_stage_loss():
            def one(h, lbl, msk):
                return head_loss_fn(embed_tree, h, lbl, msk)

            if attn_mask is not None:
                losses = jax.vmap(one)(outs, labels, attn_mask)
            else:
                losses = jax.vmap(lambda h, l: one(h, l, None))(outs, labels)
            return losses.mean()

        mb_loss = lax.cond(stage_id == P_ - 1, last_stage_loss,
                           lambda: jnp.float32(0.0))
        # MoE router aux: every stage contributes its layers' balancing term
        # (round-1 advisory: this was silently dropped under PP)
        mb_loss = mb_loss + aux_coef * aux_total / M
        return lax.psum(mb_loss, PIPE_AXIS)

    def loss_fn(params, batch):
        mesh = get_mesh()
        _record_schedule_census("fill_drain", num_stages, batch)
        layers_in = params["layers"]
        embed_tree = {k: v for k, v in params.items() if k != "layers"}
        if _needs_fp32_body():
            # bf16 operands + model-axis sharding under the manual-'pipe'
            # shard_map trip an XLA SPMD partitioner check
            # (spmd_partitioner_util.cc subgroup mismatch); upcast at the
            # shard_map boundary so sharded collectives move fp32. Params
            # stay bf16 at rest; grads flow back through the cast.
            cast32 = lambda x: (x.astype(jnp.float32)
                                if jnp.issubdtype(x.dtype, jnp.floating) else x)
            layers_in = jax.tree.map(cast32, layers_in)
            embed_tree = jax.tree.map(cast32, embed_tree)
        layer_specs = jax.tree.map(lambda _: P(PIPE_AXIS), layers_in)
        embed_specs = jax.tree.map(lambda _: P(), embed_tree)
        batch_specs = jax.tree.map(lambda _: P(), batch)
        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P(PIPE_AXIS), layer_specs, embed_specs, batch_specs),
            out_specs=P(),
            check_vma=False,
            axis_names={PIPE_AXIS})
        return fn(jnp.arange(num_stages, dtype=jnp.int32), layers_in,
                  embed_tree, batch)

    return loss_fn


def pipelined_grad_fn(cfg, num_stages: int):
    """Explicit 1F1B executor: returns grad_fn(params, batch, scale) →
    (mean_loss, grads) — the TPU rendering of the reference PipelineEngine's
    instruction loop (pipe/engine.py:1287 _exec_schedule) executing
    ``TrainSchedule`` (schedule.py:137; index math :184-206).

    Unlike jax.grad through the forward scan (which retains O(M) per-tick
    residuals), this walks the interleaved fwd/bwd schedule itself:

      * per stage, at most ``min(P, M)`` stage-input activations are live
        (the rotating ``xbuf`` — reference num_pipe_buffers bound,
        schedule.py:212), restoring 1F1B's O(P) activation residency;
      * backward recomputes the stage forward from the stored input and
        seeds ``jax.vjp`` with the received upstream grad (activation
        rematerialisation at stage granularity);
      * bubble steps execute NO layer compute (lax.cond with a per-device
        scalar predicate — real branches under manual shard_map, not selects);
      * only stage 0 embeds; only the last stage runs head+loss;
      * embedding/head grads are produced on stage 0 / last stage and psum'd
        over 'pipe' at the end — the reference's ReduceTiedGrads;
      * MoE router aux-loss is part of each stage's vjp objective, so PP×MoE
        trains with the balancing term (round-1 advisory: it was dropped).
    """
    (embed_helper, stage_apply_helper, head_loss_helper, derive_labels,
     aux_coef) = _stage_helpers(cfg)

    def body(stage_arr, layers_stacked, embed_tree, batch, scale):
        # stage index from a pipe-sharded arange, NOT lax.axis_index — the
        # partition-id lowering of axis_index breaks the partitioner for the
        # remaining auto axes (see pipelined_loss_fn.body)
        s = stage_arr[0]
        P_ = lax.psum(1, PIPE_AXIS)   # static: psum of a python int
        stage_layers = jax.tree.map(lambda x: x[0], layers_stacked)
        ids = batch["input_ids"]                        # (M, mb, S)
        attn_mask = batch.get("attention_mask")
        labels = batch.get("labels")
        if labels is None:
            labels = derive_labels(ids)
        M, mb, S = ids.shape
        positions = jnp.arange(S)
        H = cfg.hidden_size
        nbuf = min(num_stages, M)

        def embed_fn(et, token_ids):
            return embed_helper(et, token_ids, positions, cfg.dtype)

        def stage_apply(sp, x, mask):
            return stage_apply_helper(sp, x, mask, positions)

        def head_loss(et, h, lbl, msk):
            return head_loss_helper(et, h, lbl, msk)

        def micro_slice(tree3, m):
            return lax.dynamic_index_in_dim(tree3, jnp.clip(m, 0, M - 1),
                                            axis=0, keepdims=False)

        zeros_act = jnp.zeros((mb, S, H), cfg.dtype)
        zero_gsp = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                stage_layers)
        zero_get = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                embed_tree)

        def step_fn(carry, t):
            recv_act, recv_grad, xbuf, gsp, get_, loss_acc = carry
            is_fwd = ((t + s) % 2) == 0
            m_fwd = t // 2 - s // 2
            m_bwd = t // 2 - P_ + 1 + s // 2
            m = jnp.where(is_fwd, m_fwd, m_bwd)
            valid = (m >= 0) & (m < M)
            my_ids = micro_slice(ids, m)
            my_lbl = micro_slice(labels, m)
            my_msk = micro_slice(attn_mask, m) if attn_mask is not None else None
            slot = jnp.clip(m, 0, M - 1) % nbuf
            is_last = s == P_ - 1

            def fwd_branch():
                x_in = lax.cond(s == 0,
                                lambda: embed_fn(embed_tree, my_ids),
                                lambda: recv_act)
                x_out, _ = stage_apply(stage_layers, x_in, my_msk)
                new_xbuf = lax.dynamic_update_index_in_dim(xbuf, x_in, slot, 0)
                return x_out, zeros_act, new_xbuf, gsp, get_, loss_acc

            def bwd_branch():
                x_stored = lax.dynamic_index_in_dim(xbuf, slot, axis=0,
                                                    keepdims=False)

                def objective(sp_, et_, x_):
                    x_in = lax.cond(s == 0,
                                    lambda: embed_fn(et_, my_ids),
                                    lambda: x_)
                    x_out, aux = stage_apply(sp_, x_in, my_msk)

                    def last():
                        return head_loss(et_, x_out, my_lbl, my_msk)

                    def mid():
                        return jnp.vdot(x_out.astype(jnp.float32),
                                        recv_grad.astype(jnp.float32))

                    raw = lax.cond(is_last, last, lambda: jnp.float32(0.0))
                    main = lax.cond(is_last, lambda: raw * (scale / M), mid)
                    obj = main + (scale / M) * aux_coef * aux
                    return obj, raw + aux_coef * aux

                obj, vjp, raw_loss = jax.vjp(objective, stage_layers,
                                             embed_tree, x_stored,
                                             has_aux=True)
                dsp, det, dx = vjp(jnp.float32(1.0))
                new_gsp = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), gsp, dsp)
                new_get = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), get_, det)
                return (zeros_act, dx.astype(cfg.dtype), xbuf, new_gsp,
                        new_get, loss_acc + raw_loss / M)

            def noop():
                return zeros_act, zeros_act, xbuf, gsp, get_, loss_acc

            x_send, g_send, xbuf2, gsp2, get2, loss2 = lax.cond(
                valid, lambda: lax.cond(is_fwd, fwd_branch, bwd_branch), noop)

            recv_act_next = lax.ppermute(
                x_send, PIPE_AXIS, [(i, (i + 1) % P_) for i in range(num_stages)])
            recv_grad_next = lax.ppermute(
                g_send, PIPE_AXIS, [((i + 1) % P_, i) for i in range(num_stages)])
            return (recv_act_next, recv_grad_next, xbuf2, gsp2, get2,
                    loss2), None

        total_steps = 2 * (M + num_stages - 1)
        init = (zeros_act, zeros_act,
                jnp.zeros((nbuf, mb, S, H), cfg.dtype),
                zero_gsp, zero_get, jnp.float32(0.0))
        (_, _, _, gsp, get_, loss_acc), _ = lax.scan(
            step_fn, init, jnp.arange(total_steps))

        # replicated embed/head grads: sum stage contributions (reference
        # _exec_reduce_tied_grads); stage grads stay pipe-sharded
        get_ = jax.tree.map(lambda g: lax.psum(g, PIPE_AXIS), get_)
        gsp = jax.tree.map(lambda g: g[None], gsp)     # re-add stage dim
        loss = lax.psum(loss_acc, PIPE_AXIS)
        return gsp, get_, loss

    def grad_fn(params, batch, scale=jnp.float32(1.0)):
        mesh = get_mesh()
        _record_schedule_census("1f1b", num_stages, batch)
        layers_in = params["layers"]
        embed_tree = {k: v for k, v in params.items() if k != "layers"}
        layer_specs = jax.tree.map(lambda _: P(PIPE_AXIS), layers_in)
        embed_specs = jax.tree.map(lambda _: P(), embed_tree)
        batch_specs = jax.tree.map(lambda _: P(), batch)
        fn = shard_map(
            body, mesh=mesh,
            in_specs=(P(PIPE_AXIS), layer_specs, embed_specs, batch_specs,
                      P()),
            out_specs=(layer_specs, embed_specs, P()),
            check_vma=False,
            axis_names={PIPE_AXIS})
        g_layers, g_embed, loss = fn(jnp.arange(num_stages, dtype=jnp.int32),
                                     layers_in, embed_tree, batch,
                                     jnp.float32(scale))
        grads = dict(g_embed)
        grads["layers"] = g_layers
        return loss, grads

    return grad_fn


def _register_audit_entry_points(cfg, num_stages: int, init, loss_fn,
                                 grad_fn) -> None:
    """Register the stage programs with tpuaudit (tools/tpuaudit). The build
    thunks synthesize abstract params/batch at AUDIT time (nothing traces at
    registration), and the mesh resolves lazily to the ambient one — the
    engine that pipelinized this model installs its mesh before any audit
    can run. The declared collectives are the pipeline's contract: the
    stage-to-stage ppermute ring and the tied-grad/loss psums, plus the
    all-gathers GSPMD issues for the automatic (data/model) axes — an
    all-to-all here would mean the partitioner is rerouting activations."""
    try:
        from tools.tpuaudit.registry import register_entry_point
    except ImportError:     # deployed without the tools/ tree
        return

    expected = frozenset({"collective-permute", "all-reduce", "all-gather"})

    def abstract_args(wrap_scale: bool):
        params = jax.eval_shape(init, jax.random.PRNGKey(0))
        S = int(min(cfg.max_seq_len, 32))
        batch = {"input_ids": jax.ShapeDtypeStruct((num_stages, 1, S),
                                                   jnp.int32)}
        if wrap_scale:
            fn = jax.jit(lambda p, b: grad_fn(p, b, jnp.float32(1.0)))
        else:
            fn = jax.jit(loss_fn)
        return fn, (params, batch), {}

    register_entry_point(
        "pipeline/loss_fn", build=lambda: abstract_args(False),
        expected_collectives=expected, mesh=get_mesh, compile=False,
        tags={"stages": num_stages, "schedule": "fill_drain"})
    register_entry_point(
        "pipeline/grad_fn", build=lambda: abstract_args(True),
        expected_collectives=expected, mesh=get_mesh, compile=False,
        # the grads alias the params by construction; donation is owned by
        # the ENGINE-level train step this fn is embedded in, so a
        # standalone jit of it legitimately donates nothing
        suppress=frozenset({"missed-donation"}),
        tags={"stages": num_stages, "schedule": "1f1b"})


def pipelinize_model(model: Model, num_stages: int) -> Model:
    """Transform a (transformer) Model into its pipelined variant:
    layers reshaped (L, ...) → (P, Lp, ...) with the stage dim sharded over
    'pipe'; loss_fn consumes a whole microbatch stack (M, mb, S) per call.
    The reference equivalent is wrapping layers in PipelineModule."""
    cfg = model.config
    if cfg is None:
        raise ValueError("pipelinize_model requires a transformer Model (with config)")
    if num_stages <= 1:
        return model

    base_init = model.init

    def init(rng):
        params = base_init(rng)
        params["layers"] = _split_stages(params["layers"], num_stages)
        return params

    axes = dict(model.axes)
    axes["layers"] = jax.tree.map(
        lambda ax: (PIPE_STAGE,) + tuple(ax),
        model.axes["layers"],
        is_leaf=lambda x: isinstance(x, tuple) and all(
            e is None or isinstance(e, str) for e in x))
    # Under PP, embedding/head stay vocab-replicated: a model-sharded vocab dim
    # consumed inside the manual-pipe shard_map (CE's take_along_axis gather)
    # trips an XLA SPMD partitioner check (spmd_partitioner_util.cc). The
    # vocab matmul still TP-shards on its contraction side; only the table
    # layout is denser. Revisit when the partitioner handles it.
    axes["embed"] = {"tokens": (None, "embed")}
    if "lm_head" in axes:
        axes["lm_head"] = ("embed", None)

    from ..models.transformer import eval_config
    from ..observability import get_session

    with get_session().span("pipeline/build", stages=num_stages,
                            layers=cfg.num_layers):
        loss_fn = pipelined_loss_fn(cfg, num_stages)
        eval_loss_fn = pipelined_loss_fn(eval_config(cfg), num_stages)
        grad_fn = pipelined_grad_fn(cfg, num_stages)
    _register_audit_entry_points(cfg, num_stages, init, loss_fn, grad_fn)

    def apply(params, batch, **kw):
        # unpipelined eval path: merge stages back and run the plain forward
        from ..models.transformer import forward

        merged = dict(params)
        merged["layers"] = _merge_stages(params["layers"])
        logits, new_cache, _ = forward(merged, batch["input_ids"], cfg,
                                       attention_mask=batch.get("attention_mask"), **kw)
        return logits, new_cache

    return Model(init=init, apply=apply, loss_fn=loss_fn, axes=axes,
                 config=cfg, name=f"{model.name}-pp{num_stages}",
                 pipelined=True, num_stages=num_stages, grad_fn=grad_fn,
                 eval_loss_fn=eval_loss_fn)

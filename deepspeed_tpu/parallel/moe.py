"""Mixture-of-Experts: gating + dispatch (expert parallelism).

TPU-native analog of ``deepspeed/moe/`` (``MoE`` layer.py:16, ``MOELayer`` +
``TopKGate`` sharded_moe.py:420/343, ``top1gating`` :179, ``top2gating`` :277,
``Experts`` experts.py, ``_AllToAll`` :90). Same gating semantics — softmax
router, capacity factor, load-balancing aux loss (GShard l_aux = E·Σ me·ce),
optional no-drop jitter — expressed as einsum dispatch/combine (the GShard
formulation the reference also uses). The explicit NCCL all-to-all becomes a
sharding constraint on the dispatched (E, C, H) tensor: when the expert dim is
sharded over 'data' (EP folded over DP, reference groups.py:108 constraint),
XLA lowers the token exchange to exactly that all-to-all.

Expert gradients: because expert weights are *sharded* (not replicated) over
'data', SPMD autodiff never averages them across data ranks — the behavior the
reference implements manually with expert_data_parallel_group
(runtime/engine.py:2238).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .mesh import EXPERT_AXIS, get_expert_parallel_world_size, get_mesh
from .sequence import constrain
from jax.sharding import PartitionSpec as P


class GateOutput(NamedTuple):
    combine: jax.Array    # (T, E, C) — combine weights
    dispatch: jax.Array   # (T, E, C) bool — dispatch mask
    aux_loss: jax.Array   # scalar load-balancing loss
    # diagnostics
    expert_counts: jax.Array  # (E,) tokens routed per expert (pre-drop)


class GatePlan(NamedTuple):
    """Index-form gating decision: each token's K (expert, queue-slot)
    assignments. This is what the sparse dispatch consumes DIRECTLY —
    dispatch cost scales with routed tokens (O(T·K·H) gathers), not with
    the dense (T, E·C) one-hot contraction whose FLOPs dominate the step
    at realistic E/capacity (the reference pays that einsum too,
    sharded_moe.py:90 — this is where we beat it)."""

    expert_idx: jax.Array     # (T, K) int32 — chosen expert per assignment
    slot_pos: jax.Array       # (T, K) int32 — 0-based slot in expert queue
    weight: jax.Array         # (T, K) f32 — combine weight, 0 where dropped
    valid: jax.Array          # (T, K) bool — kept within capacity
    capacity: int             # static C
    aux_loss: jax.Array       # scalar load-balancing loss
    expert_counts: jax.Array  # (E,) tokens routed per expert (pre-drop)


def _capacity(num_tokens: int, num_experts: int, capacity_factor: float,
              min_capacity: int = 4) -> int:
    """Reference sharded_moe.py:157 _capacity."""
    cap = int(num_tokens / num_experts * capacity_factor)
    return max(cap, min_capacity)


def _one_hot(x: jax.Array, n: int) -> jax.Array:
    return jax.nn.one_hot(x, n, dtype=jnp.float32)


def route_topk(gates: jax.Array, choice: jax.Array, k: int,
               normalize: bool) -> Tuple[jax.Array, jax.Array]:
    """The router's decision for every token: ``lax.top_k`` over ``choice``
    (the float32 softmax ``gates`` themselves, or noised logits for the
    RSample policy) -> ``(expert_idx (T, k) int32, weight (T, k) f32)``, most
    probable first. The weights are the chosen experts' probabilities, and
    where ``normalize`` and ``k > 1`` they are divided by their sum (GShard
    top-2, Mixtral; the published ``norm_topk_prob``); a single expert keeps
    its raw probability (Switch), or the router would get no gradient."""
    E = gates.shape[-1]
    if not 1 <= k <= E:
        raise ValueError(f"moe top_k={k} must be between 1 and the number "
                         f"of experts ({E})")
    _, idx = jax.lax.top_k(choice, k)
    weight = jnp.take_along_axis(gates, idx, axis=-1)
    if normalize and k > 1:
        weight = weight / jnp.maximum(weight.sum(-1, keepdims=True), 1e-9)
    return idx.astype(jnp.int32), weight


def topk_plan(logits: jax.Array, k: int, capacity_factor: float = 1.0,
              min_capacity: int = 4, drop_tokens: bool = True,
              normalize: bool = True,
              noisy_gate_policy: Optional[str] = None,
              rng: Optional[jax.Array] = None,
              use_rts: bool = False) -> GatePlan:
    """Capacity-limited top-``k`` gating in index form: softmax in float32
    over all experts, the ``k`` most probable, each with a slot in its
    expert's queue. ``k`` of 1 and 2 are the reference's ``top1gating``
    (sharded_moe.py:179) and ``top2gating`` (:277): capacity from
    ``k * capacity_factor``, the load-balancing loss from the FIRST choice
    (GShard l_aux = E * sum(me * ce)), queues filled choice by choice (every
    first choice before any second), top-2's weights renormalised.

    ``drop_tokens=False`` - infinite capacity (C=T; the reference computes a
    dynamic max-count capacity, which jit cannot - C=T is the static-shape
    equivalent; prefer capacity_factor at scale). ``use_rts`` - Random Token
    Selection (sharded_moe.py:220), top-1 only: over-capacity tokens are
    chosen by random priority instead of sequence order (needs ``rng``).
    ``noisy_gate_policy='RSample'`` (top-1 only, needs ``rng``) chooses by
    Gumbel-noised logits and weighs by the clean probability."""
    T, E = logits.shape
    if (use_rts or noisy_gate_policy) and k != 1:
        raise ValueError("use_rts (Random Token Selection) and "
                         "noisy_gate_policy are top-1 only, as in the "
                         "reference (sharded_moe.py top1gating)")
    C = T if not drop_tokens else _capacity(T, E, k * capacity_factor,
                                            min_capacity)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)      # (T, E)
    choice = gates
    if noisy_gate_policy == "RSample" and rng is not None:
        choice = logits + jax.random.gumbel(rng, logits.shape)
    idx, weight = route_topk(gates, choice, k, normalize)            # (T, k)
    masks = [_one_hot(idx[:, j], E) for j in range(k)]               # (T, E)

    # aux loss: E * mean_e(frac_tokens_e * mean_gate_e)  (GShard eq.) -
    # computed on the first choice's PRE-RTS mask, as in the reference
    aux = jnp.sum(gates.mean(axis=0) * masks[0].mean(axis=0)) * E
    counts = sum(masks).sum(axis=0)

    if use_rts and drop_tokens and rng is not None and C < T:
        # keep a RANDOM capacity-subset per expert (reference mask1_rand +
        # _top_idx): top-C random priorities, then positions as usual
        pri = masks[0] * jax.random.uniform(rng, masks[0].shape, jnp.float32)
        _, top_idx = jax.lax.top_k(pri.T, C)                        # (E, C)
        sel = jnp.zeros((E, T), jnp.float32).at[
            jnp.arange(E)[:, None], top_idx].set(1.0)
        masks[0] = masks[0] * sel.T
        counts = masks[0].sum(axis=0)

    # queue positions: every first choice, then every second, ...
    pos, valid = [], []
    ahead = jnp.zeros((E,), jnp.float32)
    for mask in masks:
        in_queue = (jnp.cumsum(mask, axis=0) + ahead[None, :]) * mask  # 1-based
        valid.append(((in_queue <= C) & (mask > 0)).any(axis=-1))
        pos.append((in_queue.sum(-1) - 1.0).clip(0).astype(jnp.int32))
        ahead = ahead + mask.sum(axis=0)
    valid = jnp.stack(valid, axis=1)
    return GatePlan(expert_idx=idx, slot_pos=jnp.stack(pos, axis=1),
                    weight=jnp.where(valid, weight, 0.0), valid=valid,
                    capacity=C, aux_loss=aux, expert_counts=counts)


def _densify(plan: GatePlan, num_experts: int) -> GateOutput:
    """(T, K) index form → (T, E, C) dense combine/dispatch (the GShard
    einsum formulation; kept as the fallback path + for gating tests)."""
    E, C = num_experts, plan.capacity
    K = plan.expert_idx.shape[1]
    combine = jnp.zeros((), jnp.float32)
    dispatch = None
    for kk in range(K):   # keeps peak at (T,E,C), not (T,K,E,C)
        e_oh = _one_hot(plan.expert_idx[:, kk], E) > 0          # (T, E)
        c_oh = _one_hot(plan.slot_pos[:, kk], C) > 0            # (T, C)
        d = (e_oh[:, :, None] & c_oh[:, None, :]
             & plan.valid[:, kk, None, None])                   # (T, E, C)
        combine = combine + plan.weight[:, kk, None, None] * d
        dispatch = d if dispatch is None else (dispatch | d)
    return GateOutput(combine=combine, dispatch=dispatch,
                      aux_loss=plan.aux_loss,
                      expert_counts=plan.expert_counts)


def top1gating(logits: jax.Array, capacity_factor: float = 1.0,
               min_capacity: int = 4, noisy_gate_policy: Optional[str] = None,
               rng: Optional[jax.Array] = None, drop_tokens: bool = True,
               use_rts: bool = False) -> GateOutput:
    """Dense (T, E, C) rendering of :func:`topk_plan` at k = 1."""
    return _densify(topk_plan(logits, 1, capacity_factor, min_capacity,
                              drop_tokens, noisy_gate_policy=noisy_gate_policy,
                              rng=rng, use_rts=use_rts), logits.shape[1])


def top2gating(logits: jax.Array, capacity_factor: float = 1.0,
               min_capacity: int = 4, drop_tokens: bool = True) -> GateOutput:
    """Dense (T, E, C) rendering of :func:`topk_plan` at k = 2."""
    return _densify(topk_plan(logits, 2, capacity_factor, min_capacity,
                              drop_tokens), logits.shape[1])


def _ep_active(num_experts: int) -> bool:
    try:
        ep = get_expert_parallel_world_size()
    except Exception:
        return False
    return ep > 1 and num_experts % ep == 0


def expert_activation(activation: str, up: jax.Array) -> jax.Array:
    """An expert's activation where it is not gated: ``relu2`` is relu
    squared, anything else the tanh GELU these experts always had."""
    if activation == "relu2":
        return jnp.square(jax.nn.relu(up))
    return jax.nn.gelu(up, approximate=True)


def _expert_ffn(dispatched: jax.Array, experts: Dict[str, jax.Array],
                activation: str, E: int) -> jax.Array:
    """(E, C, H) → (E, C, H) batched expert MLPs, EP-constrained."""
    if _ep_active(E):
        # EP: expert dim sharded over 'data' — XLA inserts the all-to-all here
        dispatched = constrain(dispatched, P(EXPERT_AXIS, None, None))
    if activation == "swiglu":
        g = jnp.einsum("ech,ehf->ecf", dispatched, experts["w_gate"])
        u = jnp.einsum("ech,ehf->ecf", dispatched, experts["w_up"])
        inner = jax.nn.silu(g) * u
    else:
        inner = expert_activation(
            activation, jnp.einsum("ech,ehf->ecf", dispatched,
                                   experts["w_up"]))
    expert_out = jnp.einsum("ecf,efh->ech", inner, experts["w_down"])
    if _ep_active(E):
        expert_out = constrain(expert_out, P(EXPERT_AXIS, None, None))
    return expert_out


# -- scatter-free sparse dispatch/combine ------------------------------------
#
# Autodiff of a plain ``xt[token_of_slot]`` gather emits a scatter-add over
# the (E·C, H) dispatched tensor in the backward pass — TPU's weakest
# primitive (r04: sparse dispatch at 0.38 of its compute roofline, and the
# two big backward scatters are the gap). The gating plan already holds the
# exact inverse maps, so both backward passes are re-expressed as gathers
# via custom VJPs:
#
#   dispatch bwd:  dxt[t]     = Σ_k valid[t,k] · ddisp[slot[t,k]]
#   combine  bwd:  dy[s]      = filled[s] · wt_of_slot[s] · dout[tok_of_slot[s]]
#                  dweight[t,k] = valid[t,k] · <dout[t], y[slot[t,k]]>
#
# Exactness: every in-range slot has exactly one writer (queue positions are
# unique per expert), unfilled slots are weighted 0 in the combine so their
# cotangents are identically zero, and dropped (invalid) assignments carry
# weight 0. Pinned against the einsum formulation (values AND grads) in
# test_moe_tp_sp.py.


@jax.custom_vjp
def _dispatch_gather(xt, token_of_slot, slot, valid):
    return xt[token_of_slot]


def _dispatch_gather_fwd(xt, token_of_slot, slot, valid):
    return xt[token_of_slot], (slot, valid)


def _dispatch_gather_bwd(res, dd):
    slot, valid = res
    take = jnp.where(valid, slot, 0)
    dxt = (dd[take] * valid[..., None].astype(dd.dtype)).sum(axis=1)
    return dxt, None, None, None


_dispatch_gather.defvjp(_dispatch_gather_fwd, _dispatch_gather_bwd)


@jax.custom_vjp
def _combine_gather(y, weight, slot, valid, token_of_slot, wt_of_slot,
                    filled):
    take = jnp.where(valid, slot, 0)
    return (weight[..., None] * y[take]).sum(axis=1)


def _combine_gather_fwd(y, weight, slot, valid, token_of_slot, wt_of_slot,
                        filled):
    out = _combine_gather(y, weight, slot, valid, token_of_slot, wt_of_slot,
                          filled)
    return out, (y, weight, slot, valid, token_of_slot, wt_of_slot, filled)


def _combine_gather_bwd(res, dout):
    y, weight, slot, valid, token_of_slot, wt_of_slot, filled = res
    dy = (dout[token_of_slot]
          * (wt_of_slot * filled)[:, None].astype(dout.dtype))
    take = jnp.where(valid, slot, 0)
    dweight = ((dout[:, None, :] * y[take]).sum(axis=-1)
               * valid.astype(dout.dtype))
    return dy, dweight.astype(weight.dtype), None, None, None, None, None


_combine_gather.defvjp(_combine_gather_fwd, _combine_gather_bwd)


def _grouped_experts(xt: jax.Array, gates: jax.Array,
                     experts: Dict[str, jax.Array], activation: str, k: int,
                     normalize: bool, real: Optional[jax.Array],
                     layer: Optional[jax.Array] = None,
                     choice: Optional[jax.Array] = None,
                     routed_scale: float = 1.0, zero_experts: int = 0
                     ) -> Tuple[jax.Array, jax.Array]:
    """Dropless expert compute over the ASSIGNED rows only: the ``T x k``
    assignments are laid out sorted by expert, each expert's group padded to
    the row tile (``ops/moe_grouped_matmul.group_layout``), and the expert
    matmuls run as grouped matmuls over those rows - the Pallas kernel
    ``moe_grouped_matmul`` where kernels are active, its ``jnp`` twin
    elsewhere. No ``(E, T, H)`` tensor exists; an expert nobody chose does no
    work and its weights are not read. Rows where ``real`` is false (decode
    rows with no request, a prompt chunk's padding) are not routed at all and
    come back zero. With ``layer`` the weights are the model's whole
    ``(L, E, ...)`` stacks, read in place at that layer (see the kernel).
    ``choice`` (T, E): what the k experts are chosen by where that is not
    ``gates`` (a choice-only bias). ``gates`` may be wider than the stacks
    hold experts: an assignment to an expert past the held ones is dropped
    here like a row that is not ``real`` (``moe_mlp``: a chip's share).
    ``routed_scale`` multiplies the chosen weights after their
    renormalisation. The LAST ``zero_experts`` of the router's outputs are
    zero-computation experts of type identity: no matrices, no row and no
    tile; a token that chose one gets its weight (scaled like the others)
    times the token itself, computed here in full whatever share of the
    routed experts is held. Returns ``(out (T, H), counts)`` with ``counts``
    int32 ``[assignments, experts with a row, rows of the largest expert]``
    over the HELD experts and, only with ``zero_experts``, a fourth behind
    them: the assignments to zero-computation experts."""
    from ..ops.moe_grouped_matmul import (group_layout, moe_grouped_matmul,
                                          reference_grouped_matmul,
                                          tile_rows)
    from ..ops import registry
    from .mesh import ambient_mesh

    T, H = xt.shape
    E = experts["w_up"].shape[-3]       # held here; the router may be wider
    idx, weight = route_topk(gates, gates if choice is None else choice, k,
                             normalize)                           # (T, k)
    if routed_scale != 1.0:
        weight = weight * routed_scale
    flat = idx.reshape(-1)
    chosen = flat[:, None] == jnp.arange(E, dtype=jnp.int32)[None, :]
    if E < gates.shape[-1]:
        flat = jnp.minimum(flat, E - 1)     # an absent expert indexes nothing
    if real is not None:
        chosen = chosen & jnp.repeat(real, k)[:, None]            # (T*k, E)
    sizes = chosen.sum(axis=0, dtype=jnp.int32)                   # (E,)
    tm = tile_rows(T * k, E, xt.dtype)
    row_start, tile_expert, used = group_layout(sizes, T * k, tm)
    rows = tile_expert.shape[0] * tm
    # an assignment's padded row: its expert's first row + its place among
    # that expert's assignments (a counting sort; order within an expert is
    # token order). Unrouted assignments go to a dump row that is cut off.
    rank = (jnp.cumsum(chosen, axis=0, dtype=jnp.int32) - 1)
    rank = jnp.take_along_axis(rank, flat[:, None], axis=1)[:, 0]
    routed = chosen.any(axis=1)
    slot = jnp.where(routed, row_start[flat] + rank, rows)        # (T*k,)
    token_of_row = jnp.zeros((rows + 1,), jnp.int32).at[slot].set(
        jnp.repeat(jnp.arange(T, dtype=jnp.int32), k))[:rows]
    xs = xt[token_of_row]           # padding rows read token 0, unread after

    mesh = ambient_mesh()
    if registry.kernels_active() and (mesh is None or mesh.size == 1):
        mm = moe_grouped_matmul
    else:       # CPU; or XLA partitions the layer over the mesh itself
        mm = reference_grouped_matmul
    f32 = jnp.float32
    if activation == "swiglu":      # gate and up in one pass over the rows
        inner = mm(xs, experts["w_up"], tile_expert, used, layer,
                   gate=experts["w_gate"])
    else:
        up = mm(xs, experts["w_up"], tile_expert, used, layer).astype(f32)
        inner = expert_activation(activation, up).astype(xt.dtype)
    y = mm(inner, experts["w_down"], tile_expert, used, layer)

    # rows past the used tiles were never written: select, do not multiply
    picked = jnp.where(routed[:, None], y[jnp.minimum(slot, rows - 1)], 0)
    out = (picked.astype(f32).reshape(T, k, H)
           * weight[..., None]).sum(axis=1)
    if zero_experts:
        is_zero = idx >= gates.shape[-1] - zero_experts           # (T, k)
        if real is not None:
            is_zero = is_zero & real[:, None]
        out = out + (jnp.where(is_zero, weight, 0.0).sum(axis=1)[:, None]
                     * xt.astype(f32))
    out = out.astype(xt.dtype)
    counts = _routing_counts(sizes)
    if zero_experts:
        counts = jnp.concatenate(
            [counts, is_zero.sum(dtype=jnp.int32)[None]])
    return out, counts


def _routing_counts(sizes: jax.Array) -> jax.Array:
    """(E,) rows per expert -> int32 ``[assignments, experts with a row,
    rows of the largest expert]``."""
    n = sizes.astype(jnp.int32)
    return jnp.stack([n.sum(), (n > 0).sum(dtype=jnp.int32), n.max()])


def moe_mlp(x: jax.Array, router_w: jax.Array, experts: Dict[str, jax.Array],
            activation: str, top_k: int = 2, capacity_factor: float = 1.25,
            min_capacity: int = 4, drop_tokens: bool = True,
            use_rts: bool = False, rng: Optional[jax.Array] = None,
            dispatch_impl: str = "sparse", norm_topk_prob: bool = True,
            infer: bool = False, row_mask: Optional[jax.Array] = None,
            with_counts: bool = False,
            expert_layer: Optional[jax.Array] = None,
            score_func: str = "softmax",
            choice_bias: Optional[jax.Array] = None,
            latent: Optional[Dict[str, jax.Array]] = None,
            routed_scale: float = 1.0, zero_experts: int = 0):
    """MoE FFN for one layer. x (B, S, H); router_w (H, E); experts:
    w_up/w_down (+w_gate for swiglu) with leading expert dim E - or, with
    ``expert_layer`` (int32 scalar), the model's whole stacks with a layer
    dim in front of it, of which that layer is used: the grouped kernel then
    reads the touched experts where they lie, and no layer's bank is copied
    out of the stack for it.
    Returns (out (B,S,H), aux_loss scalar), and with ``with_counts`` a third
    value: int32 ``[assignments, experts with a row, rows of the largest
    expert]`` of this layer.

    ``top_k`` is any number of experts a token from 1 to E (softmax in
    float32 over all experts, then the k most probable); ``norm_topk_prob``
    says whether their weights are renormalised (``route_topk``).
    ``score_func`` "sigmoid" scores each expert on its own instead (float32
    too); ``choice_bias`` (E,) is added to the scores for the CHOICE of the
    k experts and never to their weights. Both exist at inference with the
    experts whole on the chip (the dropless path below); the capacity plans
    refuse them, and these two with them: ``latent`` ``{"w_in": (H, Z),
    "w_out": (Z, H)}``, experts that run in a latent of width ``Z`` (their
    matrices ``Z x F`` and ``F x Z``): the router reads ``x``, the experts
    ``x W_in``, and the weighted sum of what the chosen and held experts
    give goes back through ``W_out``, so an absent expert's part is left
    out BEFORE that projection; ``routed_scale``, a factor on the chosen
    weights after their renormalisation; ``zero_experts``, the router's
    LAST outputs that are zero-computation experts (identity: weight times
    the token, ``_grouped_experts``), counted beside the three counts.

    **A chip's share of the experts.** Where the stacks hold FEWER experts
    than the router has outputs, they are the router's first ones, held here
    as one chip of an expert-parallel deployment holds its own: scores,
    choice and weights are over all E as published, an assignment to an
    expert that is not held is dropped before the expert matmuls (it costs
    no row, no tile and no weight traffic), and the result is this chip's
    experts' part of the layer. Nothing stands in for the absent chips or
    for the exchange with them. The counts then count what reached a held
    expert.

    Training (``infer`` false) runs the capacity plan (``topk_plan``) under
    ``dispatch_impl``:
      * ``"sparse"`` (default) - scatter/gather dispatch: a (E*C,) int32
        token-of-slot map is built by scatter, tokens reach their expert
        queue by GATHER (O(E*C*H) bytes, no FLOPs) and return by a (T, K)
        gather + weighted sum (O(T*K*H) FLOPs). Dispatch cost scales with
        the routed tokens - at E=8/top-2/cap 1.25 the dense formulation
        burns ~4x the expert compute in the one-hot contraction alone.
      * ``"einsum"`` - the GShard (T,E,C) one-hot einsum formulation (what
        the reference computes, sharded_moe.py:90); equivalence-tested
        against sparse.

    Inference (``infer``: a KV cache is present) routes exactly - no
    capacity drops, no RTS: a dropped decode token would silently lose its
    FFN output (the reference's DeepSpeedMoEInference routes without
    training-time limits too, moe_inference.py:160). Which compute runs is
    decided HERE, by what can be observed, and is no option:
      * experts whole on every chip (``_ep_active(E)`` false): the dropless
        grouped path (``_grouped_experts``) - work and weight traffic follow
        the T*k assignments, and ``row_mask`` (B, S) keeps padding rows out
        of the routing. ``dispatch_impl`` plays no part.
      * experts sharded over chips: the capacity plan with ``C = T`` and its
        ``(E, C, H)`` sharding constraint, which XLA lowers to the
        all-to-all. A dropless exchange over chips needs the group sizes on
        every chip before the exchange, which nothing here does yet;
        ``row_mask`` is not applied there (padding rows are routed as real
        ones, as before) and the counts include them."""
    B, S, H = x.shape
    E = router_w.shape[-1]
    T = B * S
    xt = x.reshape(T, H)
    logits = xt.astype(jnp.float32) @ router_w.astype(jnp.float32)
    if dispatch_impl not in ("sparse", "einsum"):
        raise ValueError(f"unknown moe dispatch_impl {dispatch_impl!r} "
                         "(expected 'sparse' or 'einsum')")
    if score_func not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown moe score_func {score_func!r} "
                         "(expected 'softmax' or 'sigmoid')")
    held = experts["w_up"].shape[-3]
    if infer and not _ep_active(E):
        scores = (jax.nn.softmax(logits, axis=-1) if score_func == "softmax"
                  else jax.nn.sigmoid(logits))
        out, counts = _grouped_experts(
            xt if latent is None else xt @ latent["w_in"], scores, experts,
            activation, top_k, norm_topk_prob,
            None if row_mask is None else row_mask.reshape(T), expert_layer,
            choice=(None if choice_bias is None
                    else scores + choice_bias.astype(jnp.float32)),
            routed_scale=routed_scale, zero_experts=zero_experts)
        if latent is not None:
            out = out @ latent["w_out"]
        out, aux = out.reshape(B, S, H), jnp.float32(0.0)
        return (out, aux, counts) if with_counts else (out, aux)
    if (held != E or score_func != "softmax" or choice_bias is not None
            or latent is not None or routed_scale != 1.0 or zero_experts):
        raise NotImplementedError(
            "a share of the experts, sigmoid scores, a choice-only bias, "
            "experts in a latent, a scale on the routed sum and "
            "zero-computation experts "
            "exist on the dropless inference path only "
            "(parallel/moe._grouped_experts): the capacity plans of "
            "training, and of experts sharded over chips, have none of them")
    if expert_layer is not None:    # XLA fuses this slice into the einsums
        experts = jax.tree.map(lambda w: w[expert_layer], experts)
    plan = topk_plan(logits, top_k, capacity_factor, min_capacity,
                     drop_tokens=drop_tokens and not infer,
                     normalize=norm_topk_prob,
                     use_rts=use_rts and not infer, rng=rng)
    out = _capacity_experts(xt, plan, experts, activation, dispatch_impl)
    out = out.reshape(B, S, H)
    if not with_counts:
        return out, plan.aux_loss
    return out, plan.aux_loss, _routing_counts(plan.expert_counts)


def _capacity_experts(xt: jax.Array, plan: GatePlan,
                      experts: Dict[str, jax.Array], activation: str,
                      dispatch_impl: str) -> jax.Array:
    """(T, H) tokens through the experts under a capacity plan: dispatch to
    the (E, C, H) queues, the batched expert MLPs, combine. Returns (T, H)."""
    T, H = xt.shape
    E = experts["w_up"].shape[0]
    C = plan.capacity

    if dispatch_impl == "einsum":
        gate = _densify(plan, E)
        dispatch = gate.dispatch.astype(xt.dtype)                 # (T, E, C)
        dispatched = jnp.einsum("tec,th->ech", dispatch, xt)      # (E, C, H)
        expert_out = _expert_ffn(dispatched, experts, activation, E)
        return jnp.einsum("tec,ech->th", gate.combine.astype(xt.dtype),
                          expert_out)

    # ---- sparse dispatch -------------------------------------------------
    # flat slot id per (token, assignment); dropped tokens write to a dump
    # slot that is sliced off, so every in-range slot has EXACTLY one writer
    # (queue positions are unique per expert by construction)
    slot = plan.expert_idx * C + plan.slot_pos                    # (T, K)
    slot_in = jnp.where(plan.valid, slot, E * C)
    tok = jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[:, None], slot_in.shape)
    # slot-indexed inverse maps, built by SCALAR scatters (T·K elements —
    # the only scatters in the whole path; the (E·C, H) tensors below move
    # exclusively through gathers, forward AND backward)
    token_of_slot = jnp.zeros((E * C + 1,), jnp.int32).at[
        slot_in.reshape(-1)].set(tok.reshape(-1))[:E * C]         # (E·C,)
    wt_of_slot = jnp.zeros((E * C + 1,), jnp.float32).at[
        slot_in.reshape(-1)].set(plan.weight.reshape(-1))[:E * C]
    filled = jnp.zeros((E * C + 1,), jnp.bool_).at[
        slot_in.reshape(-1)].set(plan.valid.reshape(-1))[:E * C]

    # unfilled slots read token 0 — their values never reach the output
    # (combine weights them 0) and their cotangents are exactly zero
    dispatched = _dispatch_gather(xt, token_of_slot, slot, plan.valid
                                  ).reshape(E, C, H)
    expert_out = _expert_ffn(dispatched, experts, activation, E)

    y = expert_out.reshape(E * C, H)
    return _combine_gather(y, plan.weight.astype(xt.dtype), slot, plan.valid,
                           token_of_slot, wt_of_slot, filled)     # (T, H)

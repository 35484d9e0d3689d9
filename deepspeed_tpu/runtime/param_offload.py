"""ZeRO-3 parameter offload: host/NVMe-resident params streamed per layer block.

Reference capability: ZeRO-3 Offload / ZeRO-Infinity parameter swap — params
live off-device and are fetched per sub-module around use
(``runtime/zero/partition_parameters.py:601`` ``_convert_to_deepspeed_param``
+ fetch/release hooks, ``runtime/zero/partitioned_param_coordinator.py:432``
prefetch, ``runtime/swap_tensor/partitioned_param_swapper.py:36`` NVMe), which
is what lets a 40B-param model train on a single 16 GB device.

TPU-native design (docs/offload_design.md tier 3): XLA cannot lower
host-resident operands into arbitrary jitted compute, so instead of hooks
inside one giant jit the TRAIN STEP ITSELF becomes a host-driven loop over
layer blocks — the same software-pipeline shape the NVMe optimizer swapper
already uses (``runtime/swap/optimizer_swapper.py``):

  forward:   for g in 0..G-1:  prefetch block g+1 (H2D, async)
                               x_{g+1} = block_fwd(block_g, x_g)   [jit, cached]
             boundary activations x_0..x_G are the only remat stash
  head:      loss, (dres, dx_G) = head_vjp(resident, x_G, labels)  [jit]
  backward:  for g in G-1..0:  prefetch block g-1
                               dx_g, dgrads_g = block_vjp(block_g, x_g, dx_G)
                               update block g in place (fused AdamW) OR
                               accumulate dgrads_g into host fp32 (gas > 1)
  embed/head params ("resident") stay in HBM with device optimizer state.

Every block shares one compiled fwd/vjp/update executable (identical shapes;
the remainder block adds at most one more trace). Peak HBM = resident params
+ ≤2 streamed blocks + G boundary activations — independent of L.

Storage backends for the off-device state (bf16 params + fp32 master/moments,
14 bytes/param):

* ``pinned`` (default on accelerator backends): per-block jax arrays with
  ``memory_kind='pinned_host'`` — DEVICE-ADJACENT host RAM. Fetch is a
  PCIe-speed ``device_put`` between memory spaces; the update jit writes its
  outputs straight back to pinned host via ``out_shardings``, so the Python
  process never touches the bytes.
* ``np`` (CPU backend — tests — and the bf16 params of the nvme tier):
  plain numpy, mutated in place; the nvme tier stages the param blocks
  through aio-written flat files (one per block) with read-ahead.
"""

from __future__ import annotations

import os
import time as _time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel import mesh as mesh_mod
from ..utils.logging import logger


def _tree_leaves_with_path(tree):
    return jax.tree_util.tree_flatten_with_path(tree)


def _safe_sharding(mesh, spec: P, shape: Tuple[int, ...]) -> NamedSharding:
    """Explicit device_put (unlike jit out_shardings) rejects shardings that
    don't divide the dim evenly — drop the spec on any non-divisible dim
    (those leaves ride replicated on that dim, matching XLA's padding-free
    behavior for host streams)."""
    axes = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, a in zip(shape, axes):
        if a is None:
            out.append(None)
            continue
        names = a if isinstance(a, tuple) else (a,)
        size = int(np.prod([mesh.shape[n] for n in names]))
        out.append(a if dim % size == 0 else None)
    return NamedSharding(mesh, P(*out))


def pinned_host_supported() -> bool:
    """True when the backend can run the pinned-host streaming path. The XLA
    CPU backend nominally exposes the memory kind but its SPMD partitioner
    rejects the placement annotations (RET_CHECK has_sharding, observed on
    the 8-device virtual mesh) — tests exercise the numpy backend instead;
    measured on the attached v5e: pinned↔HBM moves at 400-800 GB/s."""
    if jax.default_backend() == "cpu":
        return False
    try:
        jax.devices()[0].memory("pinned_host")
        return True
    except Exception:
        return False


class _NVMeParamStore:
    """bf16 layer-block params in flat aio files (the
    ``partitioned_param_swapper`` analog). One file per block; leaves are
    packed back-to-back. Supports async read-ahead of the next block."""

    def __init__(self, swap_dir: str, aio_config: Optional[Dict] = None):
        os.makedirs(swap_dir, exist_ok=True)
        self.swap_dir = swap_dir
        aio = aio_config or {}
        from ..ops.aio import AIOHandle

        self._read_pool = AIOHandle(
            block_size=aio.get("block_size", 1 << 20),
            queue_depth=aio.get("queue_depth", 8),
            num_threads=aio.get("thread_count", 2))
        self._write_pool = AIOHandle(
            block_size=aio.get("block_size", 1 << 20),
            queue_depth=aio.get("queue_depth", 8),
            num_threads=aio.get("thread_count", 2))
        # block -> list of (shape, dtype, nbytes) set at first write
        self._layout: Dict[int, List[Tuple[Tuple[int, ...], Any, int]]] = {}
        self._pending: Dict[int, np.ndarray] = {}   # block -> raw read buffer

    def _file(self, g: int) -> str:
        return os.path.join(self.swap_dir, f"params.block{g:04d}.bin")

    def write_block(self, g: int, leaves: List[np.ndarray],
                    wait: bool = True) -> None:
        self._layout[g] = [(l.shape, l.dtype, l.nbytes) for l in leaves]
        flat = np.empty((sum(l.nbytes for l in leaves),), np.uint8)
        off = 0
        for l in leaves:
            raw = np.ascontiguousarray(l).view(np.uint8).reshape(-1)
            flat[off:off + raw.size] = raw
            off += raw.size
        self._write_pool.async_pwrite(flat, self._file(g))
        if wait:
            self._write_pool.wait()

    def prefetch_block(self, g: int) -> None:
        if g in self._pending or g not in self._layout:
            return
        nbytes = sum(n for _, _, n in self._layout[g])
        buf = np.empty((nbytes,), np.uint8)
        self._read_pool.async_pread(buf, self._file(g))
        self._pending[g] = buf

    def read_block(self, g: int) -> List[np.ndarray]:
        self.prefetch_block(g)
        self._read_pool.wait()
        buf = self._pending.pop(g)
        leaves, off = [], 0
        for shape, dtype, nbytes in self._layout[g]:
            leaves.append(buf[off:off + nbytes].view(dtype).reshape(shape))
            off += nbytes
        return leaves

    def flush(self) -> None:
        self._write_pool.wait()

    def close(self) -> None:
        self._read_pool.close()
        self._write_pool.close()


class ParamOffloadExecutor:
    """Host-driven segmented train step for ``offload_param.device`` in
    {"cpu", "nvme"}. Owns the streamed layer params and ALL optimizer state;
    the engine delegates train/eval/checkpoint to it."""

    def __init__(self, model, mesh, plan, config, *, lr_schedule: Callable,
                 init_fn: Callable, rng, compute_dtype, loss_scaler=None):
        cfg = model.config
        if cfg is None:
            raise ValueError("offload_param requires a transformer Model")
        if getattr(cfg, "ltd_enabled", False):
            raise NotImplementedError(
                "offload_param + random_ltd is not supported (the "
                "kept-token gather/scatter changes activation shapes "
                "inside the shared block program)")
        self.cfg = cfg
        self.mesh = mesh
        self.config = config
        self._model = model
        self._compression = None      # (plan, active) — set_compression
        self.lr_schedule = lr_schedule
        self.compute_dtype = compute_dtype
        zo = config.zero_optimization
        self.device_tier = zo.offload_param.device        # "cpu" | "nvme"
        opt_params = dict(config.optimizer.params)
        self.betas = tuple(opt_params.get("betas", (0.9, 0.999)))
        self.eps = float(opt_params.get("eps", 1e-8))
        self.weight_decay = float(opt_params.get("weight_decay", 0.0))
        self.adam_w_mode = config.optimizer.type.lower() != "adam"
        self.grad_clip = float(config.gradient_clipping or 0.0)
        self.gas = config.gradient_accumulation_steps
        self.step_count = 0
        # fp16 dynamic loss scaling: the scaled backward seeds flow through
        # every block vjp; overflow is detected on the ACCUMULATED grad
        # norms before any update commits (the reference's CheckOverflow-
        # before-step pattern), so an overflow step skips cleanly — this
        # forces the deferred-update (non-fused) path
        self.loss_scaler = loss_scaler
        self.scaler_state = loss_scaler.init() if loss_scaler else None
        # DSTPU_OFFLOAD_FENCE=1: block on each block's update before moving
        # on. The async dispatch queue otherwise admits many in-flight
        # block fetches/updates; at the >10B tier the transient HBM+pinned
        # copies can outrun deallocation and crash the worker — fencing
        # bounds residency to ~one block at some pipelining cost
        self._fence = os.environ.get("DSTPU_OFFLOAD_FENCE", "0") == "1"
        # DSTPU_OFFLOAD_LEAF_UPDATE=1: run the AdamW update per LEAF instead
        # of per block — peak update HBM drops from ~18x block bytes to
        # ~18x the largest leaf, at ~2 extra dispatches per (leaf, block).
        # This is what lets 13B+ blocks (0.6 GB -> 11 GB update working
        # set) fit a 16 GB chip alongside activations
        self._leaf_split = (
            os.environ.get("DSTPU_OFFLOAD_LEAF_UPDATE", "0") == "1")
        # pinned-host storage whenever the backend has the memory kind; the
        # nvme tier needs numpy buffers for the aio files
        self._pinned = (self.device_tier == "cpu" and pinned_host_supported())
        if (jax.process_count() > 1 and not self._pinned
                and (self.gas > 1 or self.grad_clip > 0.0
                     or loss_scaler is not None)):
            raise NotImplementedError(
                "multi-process offload_param on the numpy/nvme tier "
                "supports the fused step only (gas=1, no grad clipping): "
                "the host-side grad accumulators are process-local and "
                "their norm would miss other processes' shards; the pinned "
                "tier (TPU backends) accumulates in global arrays and has "
                "no such restriction")

        # -- shapes / block split (no materialisation yet) -----------------
        shapes = jax.eval_shape(init_fn, rng)
        kv_shapes, self._layers_treedef = _tree_leaves_with_path(
            shapes["layers"])
        layer_shapes = [l for _, l in kv_shapes]
        L = int(layer_shapes[0].shape[0])
        self.num_layers = L
        bytes_per_layer = sum(
            int(np.prod(l.shape)) * l.dtype.itemsize // L
            for l in layer_shapes)
        per = max(1, int(zo.offload_param.buffer_size) // max(bytes_per_layer, 1))
        self.layers_per_block = min(L, per)
        self.num_blocks = -(-L // self.layers_per_block)
        self._bounds = [(g * self.layers_per_block,
                         min((g + 1) * self.layers_per_block, L))
                        for g in range(self.num_blocks)]
        self.n_params = sum(int(np.prod(l.shape))
                            for l in jax.tree.leaves(shapes))

        # per-leaf tails/dtypes (post compute-dtype cast) — the abstract
        # block signature compile_step_programs lowers against
        self._leaf_tails = [tuple(l.shape[1:]) for l in layer_shapes]
        self._leaf_dtypes = [
            self.compute_dtype if jnp.issubdtype(l.dtype, jnp.floating)
            else l.dtype for l in layer_shapes]
        # streamed bytes of one block's params (compute dtype) and of its
        # fp32 optimizer slices — the units of the overlap accounting
        self._block_bytes = [
            sum((hi - lo) * int(np.prod(t)) * jnp.dtype(d).itemsize
                for t, d in zip(self._leaf_tails, self._leaf_dtypes))
            for lo, hi in self._bounds]
        self._block_elems = [
            sum((hi - lo) * int(np.prod(t)) for t in self._leaf_tails)
            for lo, hi in self._bounds]
        self.last_step_stats: Optional[Dict[str, float]] = None

        # resident / block shardings
        res_shapes = {k: v for k, v in shapes.items() if k != "layers"}
        res_specs = {k: v for k, v in plan.param_specs.items() if k != "layers"}
        self._res_shardings = jax.tree.map(
            lambda x, s: _safe_sharding(mesh, s, tuple(x.shape)),
            res_shapes, res_specs)
        layer_specs = [s for _, s in _tree_leaves_with_path(
            plan.param_specs["layers"])[0]]
        # non-leading dims are identical across blocks and the leading
        # (layer) dim is never sharded, so one set serves every block
        self._block_shardings = [
            _safe_sharding(mesh, s,
                           (self.layers_per_block,) + tuple(l.shape[1:]))
            for s, l in zip(layer_specs, layer_shapes)]
        if self._pinned:
            self._pinned_shardings = [
                s.with_memory_kind("pinned_host")
                for s in self._block_shardings]

        # -- materialise params + optimizer state --------------------------
        G = self.num_blocks

        def _block_leaves_fn():
            """The ONE block-init core both accelerator tiers share: cast +
            flatten of the model's layer-range hook (a casting fix must
            apply to pinned and nvme alike or the tiers would silently
            initialise from different weights)."""
            from ..models.core import cast_floating

            def block_leaves(key, lo, blen: int):
                tree = cast_floating(model.init_layer_block(key, lo, blen),
                                     self.compute_dtype)
                return [l for _, l in _tree_leaves_with_path(tree)[0]]

            return block_leaves

        if self._pinned:
            # per-BLOCK init jits: each call draws the model init and keeps
            # only one block's slice (dynamic offset → one compiled program
            # serves every full block; XLA fuses the slice into the RNG, so
            # neither the full tree nor a full leaf set is ever live in HBM;
            # a single whole-tree init jit OOMed at 7B with all the host
            # transfers in flight). The slices are bit-identical to the
            # resident engine's init — same key, same draws.
            def init_res(key):
                params = init_fn(key)
                resident = {k: v for k, v in params.items() if k != "layers"}
                res_master = jax.tree.map(
                    lambda x: x.astype(jnp.float32), resident)
                return resident, res_master

            pin = list(self._pinned_shardings)
            with mesh_mod.ambient(mesh):
                self.resident, self._res_master = jax.jit(
                    init_res,
                    out_shardings=(self._res_shardings,
                                   self._res_shardings))(rng)
                self._pblocks, self._pmaster, self._pm, self._pv = (
                    [], [], [], [])
                if model.init_layer_block is not None:
                    # per-block init via the model's layer-range hook: peak
                    # HBM = one block of layers (dynamic lo → one compiled
                    # program for all full blocks)
                    block_leaves = _block_leaves_fn()

                    def init_block(key, lo, blen: int):
                        blk = block_leaves(key, lo, blen)
                        ma = [b.astype(jnp.float32) for b in blk]
                        z = [jnp.zeros(b.shape, jnp.float32) for b in blk]
                        return blk, ma, z, [x for x in z]

                    fn = jax.jit(init_block, static_argnums=(2,),
                                 out_shardings=(pin, pin, pin, pin))
                    for lo, hi in self._bounds:
                        blk, ma, m_, v_ = fn(rng, lo, hi - lo)
                        self._pblocks.append(list(blk))
                        self._pmaster.append(list(ma))
                        self._pm.append(list(m_))
                        self._pv.append(list(v_))
                else:
                    # fallback for custom Models: per-leaf dynamic-slice
                    # programs — only the selected leaf survives DCE, so
                    # peak HBM = one full leaf's init pipeline
                    def init_leaf_block(key, lo, leaf_idx: int, blen: int):
                        params = init_fn(key)
                        leaves = [l for _, l in _tree_leaves_with_path(
                            params["layers"])[0]]
                        b = jax.lax.dynamic_slice_in_dim(
                            leaves[leaf_idx], lo, blen, axis=0)
                        ma = b.astype(jnp.float32)
                        z = jnp.zeros(b.shape, jnp.float32)
                        return b, ma, z, z

                    wrappers = [
                        jax.jit(init_leaf_block, static_argnums=(2, 3),
                                out_shardings=(psh, psh, psh, psh))
                        for psh in self._pinned_shardings]
                    for lo, hi in self._bounds:
                        blk, ma, m_, v_ = [], [], [], []
                        for i, fn in enumerate(wrappers):
                            b, a, mm, vv = fn(rng, lo, i, hi - lo)
                            blk.append(b)
                            ma.append(a)
                            m_.append(mm)
                            v_.append(vv)
                        self._pblocks.append(blk)
                        self._pmaster.append(ma)
                        self._pm.append(m_)
                        self._pv.append(v_)
            self._host_layers = None
            self._master = self._m = self._v = None
            self._store = None
        else:
            # numpy backend (CPU tests / nvme file tier)
            if jax.default_backend() == "cpu":
                # CPU: a plain jit is host-resident already
                with mesh_mod.ambient(mesh):
                    params = jax.jit(init_fn)(rng)
                kv, _ = _tree_leaves_with_path(params["layers"])
                # np.array (copy): np views over jax buffers are read-only,
                # and this storage is updated in place every step
                layer_leaves = [np.array(l) for _, l in kv]
                resident_dev = {k: v for k, v in params.items()
                                if k != "layers"}
            elif model.init_layer_block is not None:
                # accelerator + nvme tier: per-block init on device,
                # device_get to np — never the full tree in HBM
                def res_only(key):
                    params = init_fn(key)
                    return {k: v for k, v in params.items() if k != "layers"}

                with mesh_mod.ambient(mesh):
                    resident_dev = jax.jit(
                        res_only, out_shardings=self._res_shardings)(rng)
                    fn = jax.jit(_block_leaves_fn(), static_argnums=(2,))
                    layer_leaves = [
                        np.empty((L,) + tuple(l.shape[1:]),
                                 jnp.dtype(l.dtype))
                        for l in layer_shapes]
                    for lo, hi in self._bounds:
                        for dst, src in zip(layer_leaves,
                                            jax.device_get(
                                                fn(rng, lo, hi - lo))):
                            dst[lo:hi] = np.asarray(src)
            else:
                # custom Model on an accelerator: stream the whole-tree init
                # to pinned host, then pull to np (one-time cost)
                host_sh = jax.tree.map(
                    lambda s: s.with_memory_kind("pinned_host"),
                    {"layers": jax.tree_util.tree_unflatten(
                        self._layers_treedef,
                        [_safe_sharding(mesh, s, tuple(l.shape))
                         for s, l in zip(layer_specs, layer_shapes)]),
                     **self._res_shardings})
                with mesh_mod.ambient(mesh):
                    params = jax.jit(init_fn, out_shardings=host_sh)(rng)
                kv, _ = _tree_leaves_with_path(params["layers"])
                layer_leaves = [np.array(l) for _, l in kv]
                resident_dev = jax.tree.map(
                    lambda x, s: jax.device_put(np.asarray(x), s),
                    {k: v for k, v in params.items() if k != "layers"},
                    self._res_shardings)
            self._host_layers: Optional[List[np.ndarray]] = layer_leaves
            self._store: Optional[_NVMeParamStore] = None
            if self.device_tier == "nvme":
                self._store = _NVMeParamStore(
                    os.path.join(zo.offload_param.nvme_path,
                                 f"dstpu_param_swap_p{jax.process_index()}"),
                    aio_config={"block_size": config.aio.block_size,
                                "queue_depth": config.aio.queue_depth,
                                "thread_count": config.aio.thread_count})
                for g, (lo, hi) in enumerate(self._bounds):
                    self._store.write_block(
                        g, [l[lo:hi] for l in layer_leaves], wait=False)
                self._store.flush()
                self._host_layers = None   # files own the bf16 params now
            self._master = [l.astype(np.float32) for l in layer_leaves]
            self._m = [np.zeros_like(x) for x in self._master]
            self._v = [np.zeros_like(x) for x in self._master]
            self.resident = jax.tree.map(
                lambda x, s: jax.device_put(x, s), resident_dev,
                self._res_shardings)
            self._res_master = jax.tree.map(
                lambda x: jnp.asarray(x, jnp.float32), self.resident)
        self._res_m = jax.tree.map(jnp.zeros_like, self._res_master)
        self._res_v = jax.tree.map(jnp.zeros_like, self._res_master)
        self._acc = None                  # gas>1 grad accumulators (lazy)

        self._build_step_fns(model)
        state_gb = self.n_params * 14 / 1e9
        logger.info(
            f"param offload ({self.device_tier}"
            f"{'/pinned' if self._pinned else ''}): {L} layers in "
            f"{self.num_blocks} blocks of {self.layers_per_block} "
            f"({bytes_per_layer * self.layers_per_block / 1e6:.0f} MB/block "
            f"in HBM; ~{state_gb:.2f} GB params+state off-device)")

    def set_compression(self, plan, active) -> None:
        """(Re)bind the QAT compression transform and rebuild the segment
        programs — the engine calls this at every schedule boundary, the
        streamed analog of its _compiled_step re-specialisation. Per-layer
        quantization scales (compression/compress.py) make the block-wise
        application identical to the resident full-stack one."""
        self._compression = (plan, frozenset(active)) if active else None
        self._build_step_fns(self._model)

    def _compression_wrap(self, tree):
        """Apply the active QAT transform inside a traced segment program.
        ``tree`` is either the resident params or {'layers': block} — the
        same dotted paths the resident engine's transform sees."""
        if self._compression is None:
            return tree
        from ..compression import apply_compression

        plan, active = self._compression
        return apply_compression(tree, plan, active,
                                 handled_elsewhere=frozenset(
                                     {"activation_quantization"}))

    # -- compiled segments (shared across blocks) --------------------------
    def _build_step_fns(self, model) -> None:
        from ..models.transformer import (Step, _dropout, _layer_forward,
                                          _norm, _qeinsum, cross_entropy_loss,
                                          eval_config, require_one_pass,
                                          resolve_remat_policy)

        cfg = self.cfg
        require_one_pass(cfg, "parameter offload (a block of layers a step)")

        def make_fns(c):
            def embed_fwd(resident, ids):
                resident = self._compression_wrap(resident)
                B, S = ids.shape
                x = resident["embed"]["tokens"][ids].astype(c.dtype)
                positions = jnp.arange(S)
                if c.position == "learned":
                    x = x + resident["pos"][positions].astype(c.dtype)
                if c.type_vocab_size > 0:
                    # segment-0 embedding, matching the resident forward with
                    # token_type_ids=None (models/transformer.py); keeps the
                    # type_embed grad flowing to row 0 instead of silently
                    # zero (ADVICE r3 medium finding)
                    x = x + resident["type_embed"][0].astype(c.dtype)
                if c.embed_norm:
                    x = _norm(x, resident["embed_norm"]["scale"],
                              resident["embed_norm"].get("bias"), "layernorm",
                              c.norm_eps)
                return _dropout(x, c, salt=29)

            win_table = None
            if c.attention_layers:
                from ..models.transformer import window_table

                win_table = window_table(c)

            def block_fwd(block_leaves, x, mask, lo, theta):
                """(x, moe_aux_sum) for one layer block — aux threads the
                MoE load-balancing loss through the segmented step (the
                resident loss adds coef*aux/L; non-MoE models carry a DCE'd
                zero). ``lo``: the block's GLOBAL base layer index (traced,
                so one program serves every block) — per-layer features
                (PLD stochastic depth, GPT-Neo sliding windows) index their
                schedules with lo+i exactly like the resident scan.
                ``theta``: PLD survival parameter (None when disabled)."""
                from ..models.transformer import pld_gate

                block = jax.tree_util.tree_unflatten(self._layers_treedef,
                                                     block_leaves)
                block = self._compression_wrap({"layers": block})["layers"]
                S = x.shape[1]
                positions = jnp.arange(S)
                blen = jax.tree.leaves(block)[0].shape[0]

                def body(carry, layer_i):
                    layer, i = layer_i
                    h, aux = carry
                    idx = (lo + i).astype(jnp.float32)
                    window = (win_table[(lo + i).astype(jnp.int32)]
                              if win_table is not None else None)
                    h2, _, a = _layer_forward(c, h, layer, Step(
                        mask=mask, positions=positions, window=window))
                    if c.pld_enabled and theta is not None:
                        h2, a = pld_gate(c, h, h2, a, idx, theta)
                    return (h2, aux + a), None

                fn = body
                if c.remat:
                    fn = jax.checkpoint(body, prevent_cse=False,
                                        policy=resolve_remat_policy(c))
                (x, aux), _ = jax.lax.scan(
                    fn, (x, jnp.float32(0.0)),
                    (block, jnp.arange(blen, dtype=jnp.int32)))
                return x, aux

            def head_loss(resident, x, labels, mask, scale):
                """(scaled ce loss, unscaled loss). ``scale`` is the fp16
                loss scale — seeds the whole backward sweep (the cotangents
                this vjp emits feed every block_vjp)."""
                from ..models.transformer import head_logits

                resident = self._compression_wrap(resident)
                loss = cross_entropy_loss(head_logits(resident, x, c),
                                          labels, mask)
                return loss * scale, loss

            return embed_fwd, block_fwd, head_loss

        embed_fwd, block_fwd, head_loss = make_fns(cfg)
        self._embed_fwd = jax.jit(embed_fwd)
        self._block_fwd = jax.jit(block_fwd)
        self._head_vjp = jax.jit(
            jax.value_and_grad(head_loss, argnums=(0, 1), has_aux=True))

        def block_vjp(block_leaves, x_in, mask, dy, daux, lo, theta):
            _, pull = jax.vjp(
                lambda bl, xx: block_fwd(bl, xx, mask, lo, theta),
                block_leaves, x_in)
            dbl, dx = pull((dy, daux))
            return dx, dbl

        self._block_vjp = jax.jit(block_vjp)

        def embed_vjp(resident, ids, dx):
            _, pull = jax.vjp(lambda r: embed_fwd(r, ids), resident)
            return pull(dx)[0]

        self._embed_vjp = jax.jit(embed_vjp)

        b1, b2 = self.betas

        def adamw_leaves(params, grads, master, m, v, step, lr, gscale):
            def upd(p, g, mm, vv, ma):
                g = g.astype(jnp.float32) * gscale
                if self.weight_decay != 0.0 and not self.adam_w_mode:
                    g = g + self.weight_decay * ma
                mm = b1 * mm + (1 - b1) * g
                vv = b2 * vv + (1 - b2) * g * g
                u = (mm / (1 - b1 ** step)) / (
                    jnp.sqrt(vv / (1 - b2 ** step)) + self.eps)
                if self.weight_decay != 0.0 and self.adam_w_mode:
                    u = u + self.weight_decay * ma
                ma = ma - lr * u
                return ma.astype(p.dtype), ma, mm, vv

            out = [upd(p, g, mm, vv, ma) for p, g, mm, vv, ma in
                   zip(params, grads, m, v, master)]
            return ([o[0] for o in out], [o[1] for o in out],
                    [o[2] for o in out], [o[3] for o in out])

        def sqnorm(ls):
            return sum(jnp.vdot(l.astype(jnp.float32), l.astype(jnp.float32))
                       for l in ls)

        self._sqnorm = jax.jit(sqnorm)

        if self._pinned:
            # the updated block streams straight back to pinned host via
            # out_shardings — the Python process never holds the bytes (no
            # donation: inputs are HBM, outputs pinned; different spaces)
            pin = list(self._pinned_shardings)
            self._block_update = jax.jit(
                adamw_leaves, out_shardings=(pin, pin, pin, pin))
            self._leaf_update_fns = [
                jax.jit(adamw_leaves,
                        out_shardings=(([p],) * 4))
                for p in self._pinned_shardings]

            def acc_add(acc, g, inv):
                # acc arrives pinned; compute needs device operands, so hop
                # through device memory inside the jit (traceable device_put)
                acc_d = [jax.device_put(a, s)
                         for a, s in zip(acc, self._block_shardings)]
                new = [a + x.astype(jnp.float32) * inv
                       for a, x in zip(acc_d, g)]
                # running sq-norm rides along so the boundary never has to
                # re-read the accumulators just to compute the grad norm
                return new, sqnorm(new)

            self._acc_add = jax.jit(acc_add, out_shardings=(pin, None))
            leaf_tails = [tuple(p.shape[1:]) for p in self._pblocks[0]]
            self._acc_zeros = jax.jit(
                lambda: [[jnp.zeros((hi - lo,) + tail, jnp.float32)
                          for tail in leaf_tails]
                         for (lo, hi) in self._bounds],
                out_shardings=[[sh.with_memory_kind("pinned_host")
                                for sh in self._block_shardings]
                               for _ in self._bounds])
        else:
            self._block_update = jax.jit(adamw_leaves,
                                         donate_argnums=(0, 2, 3, 4))
            one = jax.jit(adamw_leaves, donate_argnums=(0, 2, 3, 4))
            self._leaf_update_fns = [one] * len(self._block_shardings)

        def res_update(params, grads, master, m, v, step, lr, gscale):
            leaves_p, td = jax.tree.flatten(params)
            leaves = adamw_leaves(leaves_p, jax.tree.leaves(grads),
                                  jax.tree.leaves(master),
                                  jax.tree.leaves(m), jax.tree.leaves(v),
                                  step, lr, gscale)
            return tuple(jax.tree.unflatten(td, ls) for ls in leaves)

        self._res_update = jax.jit(res_update, donate_argnums=(0, 2, 3, 4))

        # eval-mode (regularisers off) forward segments
        e_embed, e_block, e_head = make_fns(eval_config(cfg))
        self._eval_embed = jax.jit(e_embed)
        self._eval_block = jax.jit(e_block)
        self._eval_head = jax.jit(e_head)

    # -- multi-process host<->device helpers -------------------------------
    # Each process moves ONLY its addressable shards — the reference's
    # per-dp-rank partition swap (partitioned_param_swapper.py:36,
    # stage3.py _configure_offloading). Host buffers stay full-shaped per
    # process; regions owned by other processes go stale and are never
    # read (make_array_from_callback queries owned index regions only).
    def _put_leaves(self, host_leaves: List[np.ndarray],
                    shardings) -> List[jax.Array]:
        if jax.process_count() == 1:
            # single dispatch for the whole block (a per-leaf loop costs a
            # host round-trip per leaf)
            return jax.device_put(host_leaves, shardings)
        return [jax.make_array_from_callback(tuple(h.shape), s,
                                             lambda idx, h=h: h[idx])
                for h, s in zip(host_leaves, shardings)]

    @staticmethod
    def _writeback_shards(dsts: List[np.ndarray],
                          arrs: List[jax.Array]) -> None:
        for dst, arr in zip(dsts, arrs):
            for s in arr.addressable_shards:
                dst[s.index] = np.asarray(s.data)

    # -- block fetch/store -------------------------------------------------
    def _block_host_leaves(self, g: int) -> List[np.ndarray]:
        """NUMPY leaves of block g (np backends; pinned uses device_get)."""
        lo, hi = self._bounds[g]
        if self._pinned:
            return [np.asarray(x) for x in jax.device_get(self._pblocks[g])]
        if self._store is not None:
            return self._store.read_block(g)
        return [l[lo:hi] for l in self._host_layers]

    def _fetch_block(self, g: int) -> List[jax.Array]:
        if self._pinned:
            # pinned blocks are GLOBAL jax arrays already — device_put is a
            # pure memory-space reshard and is multi-process-safe as is
            return jax.device_put(self._pblocks[g], self._block_shardings)
        return self._put_leaves(self._block_host_leaves(g),
                                self._block_shardings)

    def _prefetch(self, g: int) -> None:
        if self._store is not None and 0 <= g < self.num_blocks:
            self._store.prefetch_block(g)

    def _store_block(self, g: int, dev_leaves: List[jax.Array]) -> None:
        if self._pinned:
            # dev_leaves already carry pinned_host shardings (update jit
            # out_shardings) — just rebind
            self._pblocks[g] = dev_leaves
            return
        lo, hi = self._bounds[g]
        if jax.process_count() > 1:
            if self._store is not None:
                blen = hi - lo
                host = [np.empty((blen,) + t, jnp.dtype(d))
                        for t, d in zip(self._leaf_tails, self._leaf_dtypes)]
                self._writeback_shards(host, dev_leaves)
                self._store.write_block(g, host, wait=False)
            else:
                self._writeback_shards(
                    [l[lo:hi] for l in self._host_layers], dev_leaves)
            return
        host = [np.asarray(x) for x in jax.device_get(dev_leaves)]
        if self._store is not None:
            self._store.write_block(g, host, wait=False)
        else:
            for dst, src in zip(self._host_layers, host):
                dst[lo:hi] = src

    def _opt_slices_on_device(self, g: int):
        """Stream this block's fp32 master/moments H2D, sharded like the
        params (same shapes → same specs)."""
        if self._pinned:
            return jax.device_put(
                (self._pmaster[g], self._pm[g], self._pv[g]),
                (self._block_shardings,) * 3)
        lo, hi = self._bounds[g]
        if jax.process_count() > 1:
            return tuple(
                self._put_leaves([x[lo:hi] for x in xs],
                                 self._block_shardings)
                for xs in (self._master, self._m, self._v))
        return jax.device_put(
            tuple([x[lo:hi] for x in xs]
                  for xs in (self._master, self._m, self._v)),
            (self._block_shardings,) * 3)

    def _writeback_opt(self, g: int, new_ma, new_m, new_v) -> None:
        if self._pinned:
            self._pmaster[g] = new_ma
            self._pm[g] = new_m
            self._pv[g] = new_v
            return
        lo, hi = self._bounds[g]
        if jax.process_count() > 1:
            for dsts, arrs in ((self._master, new_ma), (self._m, new_m),
                               (self._v, new_v)):
                self._writeback_shards([x[lo:hi] for x in dsts], arrs)
            return
        for dst, src in zip(self._master, jax.device_get(new_ma)):
            dst[lo:hi] = src
        for dst, src in zip(self._m, jax.device_get(new_m)):
            dst[lo:hi] = src
        for dst, src in zip(self._v, jax.device_get(new_v)):
            dst[lo:hi] = src

    # -- AOT warm-compile --------------------------------------------------
    def compile_step_programs(self, micro_batch_shape: Tuple[int, int],
                              *, budget_s: Optional[float] = None,
                              ids_dtype=jnp.int32) -> Dict[str, float]:
        """AOT-compile the shared per-block step programs into the
        persistent XLA compile cache, one program at a time.

        Why this exists: at the >10B tier the first train_batch compiles
        every segment program back-to-back — minutes each, which can blow
        any per-command wall-clock budget (the recorded llama-13b blocker,
        docs/offload_design.md). With ``budget_s`` the method compiles
        programs in a FIXED order and stops before starting a program once
        the budget is spent; re-running resumes instantly (persistent-cache
        hits take ~ms) and picks up where it left off, so arbitrarily large
        models warm up under any command time limit. After warming, the
        first real step's trace hits the cache for every program.

        Returns {program_name: seconds} for programs compiled in THIS call
        (cache hits come back in milliseconds and are included).

        Shardings: block/resident/optimizer-state signatures carry their
        exact runtime shardings; batch ids/labels carry the engine's batch
        sharding. Boundary activations (x/dy) are jit OUTPUTS whose layout
        the compiler picks — on a single-device mesh (the >HBM scale tier
        this targets) every layout is trivially identical, so the warm is
        exact; on multi-device meshes the block programs may still retrace
        once at the first step."""

        from ..parallel.mesh import batch_spec

        B, S = micro_batch_shape
        mesh = self.mesh
        cdt = self.cfg.dtype
        H = self.cfg.hidden_size
        fused = self._fused

        def sds(shape, dtype, sharding=None):
            return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                        sharding=sharding)

        def block_sig(blen, dtype_override=None):
            return [sds((blen,) + t,
                        dtype_override or d, sh)
                    for t, d, sh in zip(self._leaf_tails, self._leaf_dtypes,
                                        self._block_shardings)]

        def from_arrays(tree):
            return jax.tree.map(
                lambda a: sds(a.shape, a.dtype,
                              getattr(a, "sharding", None)), tree)

        resident = from_arrays(self.resident)
        res_f32 = from_arrays(self._res_master)
        # no explicit shardings on batch/activation avals: the runtime
        # passes computed values whose (single-device) shardings normalise
        # to the default — attaching a NamedSharding here changes the jit
        # cache key and the warmed executable is never reused
        ids = sds((B, S), ids_dtype)
        x = sds((B, S, H), cdt)
        labels = sds((B, S), ids_dtype)

        blens = sorted({hi - lo for lo, hi in self._bounds}, reverse=True)
        jobs: List[Tuple[str, Any, Tuple]] = []
        for blen in blens:
            blk = block_sig(blen)
            gblk = block_sig(blen)          # vjp cotangents share leaf dtype
            f32b = block_sig(blen, jnp.float32)
            tag = f"@L{blen}" if len(blens) > 1 else ""
            # the non-fused (gas/clip) path feeds fp32 ACCUMULATED grads to
            # the update; the fused path feeds raw compute-dtype cotangents
            upd_grads = gblk if fused else f32b
            # strong-typed scalar: the runtime theta is batch['pld_theta'][mi]
            # (strong f32) — a Python float would lower weak-typed and the
            # warmed executables would never be reused
            theta = (jnp.float32(0.5)
                     if getattr(self.cfg, "pld_enabled", False) else None)
            jobs += [
                (f"block_fwd{tag}", self._block_fwd, (blk, x, None, 0,
                                                      theta)),
                (f"block_vjp{tag}", self._block_vjp, (blk, x, None, x, 0.0,
                                                      0, theta)),
                (f"block_update{tag}", self._block_update,
                 (blk, upd_grads, f32b, f32b, f32b, 2, 1e-4, 1.0)),
                (f"sqnorm{tag}", self._sqnorm, (gblk,)),
            ]
            if not fused:
                if self._pinned:
                    jobs.append((f"acc_add{tag}", self._acc_add,
                                 ([sds(s.shape, jnp.float32,
                                       s.sharding.with_memory_kind(
                                           "pinned_host"))
                                   for s in f32b], gblk, 1.0 / self.gas)))
        jobs += [
            ("head_vjp", self._head_vjp, (resident, x, labels, None, 1.0)),
            ("embed_fwd", self._embed_fwd, (resident, ids)),
            ("embed_vjp", self._embed_vjp, (resident, ids, x)),
            ("sqnorm_res", self._sqnorm,
             (jax.tree.leaves(res_f32),)),
            ("res_update", self._res_update,
             (resident, res_f32, res_f32, res_f32, res_f32, 2, 1e-4, 1.0)),
        ]

        done: Dict[str, float] = {}
        t_start = _time.perf_counter()
        with mesh_mod.ambient(mesh):
            for name, fn, args in jobs:
                if (budget_s is not None
                        and _time.perf_counter() - t_start > budget_s):
                    logger.info(
                        f"compile_step_programs: budget {budget_s:.0f}s "
                        f"spent after {len(done)}/{len(jobs)} programs — "
                        "re-run to resume (persistent cache)")
                    break
                t0 = _time.perf_counter()
                fn.lower(*args).compile()
                done[name] = round(_time.perf_counter() - t0, 3)
                logger.info(f"compiled {name}: {done[name]:.1f}s")
        return done

    def _apply_block_update(self, g: int, dev_block, grads_dev, step, lr,
                            gscale) -> None:
        """Fetch block g's optimizer state, run AdamW, store params + state
        back — whole-block by default; per-leaf under
        DSTPU_OFFLOAD_LEAF_UPDATE (bounds the update working set to one
        leaf for >10B blocks on small-HBM chips)."""
        if not self._leaf_split:
            master, m, v = self._opt_slices_on_device(g)
            new_p, new_ma, new_m, new_v = self._block_update(
                dev_block, grads_dev, master, m, v, step, lr, gscale)
            self._store_block(g, new_p)
            self._writeback_opt(g, new_ma, new_m, new_v)
            if self._fence:
                jax.block_until_ready(new_v)
            return
        lo, hi = self._bounds[g]
        nps, nmas, nms, nvs = [], [], [], []
        for i in range(len(dev_block)):
            sh = self._block_shardings[i]
            if self._pinned:
                ma, mm, vv = jax.device_put(
                    (self._pmaster[g][i], self._pm[g][i], self._pv[g][i]),
                    (sh,) * 3)
            else:
                ma = self._put_leaves([self._master[i][lo:hi]], [sh])[0]
                mm = self._put_leaves([self._m[i][lo:hi]], [sh])[0]
                vv = self._put_leaves([self._v[i][lo:hi]], [sh])[0]
            np_, nma, nm, nv = self._leaf_update_fns[i](
                [dev_block[i]], [grads_dev[i]], [ma], [mm], [vv],
                step, lr, gscale)
            nps.append(np_[0])
            nmas.append(nma[0])
            nms.append(nm[0])
            nvs.append(nv[0])
            if self._fence:
                jax.block_until_ready(nv[0])
        self._store_block(g, nps)
        self._writeback_opt(g, nmas, nms, nvs)

    # -- the train step ----------------------------------------------------
    def _labels_of(self, mb):
        labels = mb.get("labels")
        if labels is None:
            ids = mb["input_ids"]
            labels = jnp.concatenate(
                [ids[:, 1:], jnp.full((ids.shape[0], 1), -100, ids.dtype)],
                axis=1)
        return labels

    def _init_acc(self) -> None:
        if self._acc is not None:
            return
        if self._pinned:
            self._acc = self._acc_zeros()    # jit cached in _build_step_fns
        else:
            self._acc = [np.zeros(m.shape, np.float32) for m in self._master]

    def train_step(self, batch_stack: Any) -> Tuple[jax.Array, float, bool]:
        """One full step over (gas, mb, ...) microbatches. Returns
        (mean_loss, grad_norm, skipped) — ``skipped`` is True for an fp16
        overflow step (no state was touched; scale backed off). Records
        ``last_step_stats`` (wall time + streamed bytes + achieved
        host<->device bandwidth — the fetch/compute overlap evidence)."""

        t_step0 = _time.perf_counter()
        self.step_count += 1
        step = self.step_count
        lr = float(self.lr_schedule(step - 1))
        G, gas = self.num_blocks, self.gas
        fused = self._fused
        scale = (float(jax.device_get(self.scaler_state.scale))
                 if self.scaler_state is not None else 1.0)
        # MoE aux loss: coef/L per accumulated aux unit; its gradient enters
        # each block vjp as the aux output's cotangent
        aux_coef = (float(self.cfg.moe_aux_loss_coef)
                    / max(self.cfg.num_layers, 1)
                    if getattr(self.cfg, "moe_num_experts", 0) else 0.0)

        if not fused:
            self._init_acc()
        res_grads_total = None
        losses = []
        sq_parts: List[jax.Array] = []    # fused path: per-block grad sq-norms
        acc_sq: Dict[int, jax.Array] = {}  # pinned acc path: running norms

        for mi in range(gas):
            mb = jax.tree.map(lambda x: x[mi], batch_stack)
            ids = mb["input_ids"]
            mask = mb.get("attention_mask")
            labels = self._labels_of(mb)
            theta = mb.get("pld_theta")   # engine injects per step when PLD

            # ---- forward: stream blocks, stash boundary activations ----
            x = self._embed_fwd(self.resident, ids)
            acts = [x]
            aux_total = None
            self._prefetch(0)
            dev_block = self._fetch_block(0)
            for g in range(G):
                self._prefetch(g + 1)
                nxt = self._fetch_block(g + 1) if g + 1 < G else None
                x, aux_g = self._block_fwd(dev_block, x, mask,
                                           self._bounds[g][0], theta)
                acts.append(x)
                aux_total = aux_g if aux_total is None else aux_total + aux_g
                # keep only the LAST block resident (bwd starts there);
                # earlier blocks are dropped and re-fetched in the sweep
                dev_block = nxt if nxt is not None else dev_block

            # ---- head + backward sweep ----
            (_, loss), (dres, dx) = self._head_vjp(self.resident, acts[G],
                                                   labels, mask, scale)
            if aux_coef:
                loss = loss + aux_coef * aux_total
            losses.append(loss)
            daux = scale * aux_coef
            inv_gas = 1.0 / gas
            for g in range(G - 1, -1, -1):
                self._prefetch(g - 1)
                if dev_block is None:
                    dev_block = self._fetch_block(g)
                nxt = self._fetch_block(g - 1) if g > 0 else None
                dx, dblock = self._block_vjp(dev_block, acts[g], mask, dx,
                                             daux, self._bounds[g][0],
                                             theta)
                if fused:
                    # separate vjp/norm/update dispatches measured FASTER
                    # than one fused program here: the fused program puts
                    # the whole update on the dx dependency chain, stalling
                    # block g-1's vjp behind g's optimizer math
                    sq_parts.append(self._sqnorm(dblock))
                    self._apply_block_update(g, dev_block, dblock, step, lr,
                                             1.0)
                elif self._pinned:
                    self._acc[g], acc_sq[g] = self._acc_add(
                        self._acc[g], dblock, inv_gas)
                else:
                    lo, hi = self._bounds[g]
                    for dst, src in zip(self._acc,
                                        jax.device_get(dblock)):
                        dst[lo:hi] += np.asarray(src, np.float32) * inv_gas
                dev_block = nxt
                del dblock
            dres_embed = self._embed_vjp(self.resident, ids, dx)
            res_g = jax.tree.map(
                lambda a, b: (a.astype(jnp.float32)
                              + b.astype(jnp.float32)) * inv_gas,
                dres, dres_embed)
            res_grads_total = (res_g if res_grads_total is None else
                               jax.tree.map(jnp.add, res_grads_total, res_g))
            acts = None

        # ---- grad norm / clip + deferred updates ----
        gscale = 1.0
        if fused:
            sq_parts.append(self._sqnorm(jax.tree.leaves(res_grads_total)))
            grad_norm = float(jnp.sqrt(sum(sq_parts)))
        if not fused:
            if self._pinned:
                # the running norms came back with the last micro's acc_add
                # — no extra pinned→HBM read pass
                sq = sum(float(acc_sq[g]) for g in range(G))
            else:
                sq = sum(float(np.vdot(a, a)) for a in self._acc)
            sq += float(self._sqnorm(jax.tree.leaves(res_grads_total)))
            grad_norm = float(np.sqrt(sq)) / scale   # true (unscaled) norm
            if self.loss_scaler is not None:
                overflow = not np.isfinite(grad_norm)
                self.scaler_state = self.loss_scaler.update(
                    self.scaler_state, jnp.asarray(overflow))
                if overflow:
                    # skip BEFORE any state commits (reference
                    # CheckOverflow-then-step); scale already backed off
                    mean_loss = jnp.mean(jnp.stack(
                        [l.astype(jnp.float32) for l in losses]))
                    if self._pinned:
                        self._acc = None
                    else:
                        for a in self._acc:
                            a[...] = 0.0
                    self.step_count -= 1   # Adam bias correction untouched
                    jax.block_until_ready(mean_loss)
                    self._record_step_stats(t_step0, skipped=True)
                    return mean_loss, 0.0, True
            gscale = 1.0 / scale
            if self.grad_clip > 0.0 and grad_norm > self.grad_clip:
                gscale = self.grad_clip / (grad_norm + 1e-6) / scale
            for g in range(G):
                self._prefetch(g + 1)
                dev_block = self._fetch_block(g)
                if self._pinned:
                    acc_dev = jax.device_put(self._acc[g],
                                             self._block_shardings)
                else:
                    lo, hi = self._bounds[g]
                    acc_dev = jax.device_put([a[lo:hi] for a in self._acc],
                                             self._block_shardings)
                self._apply_block_update(g, dev_block, acc_dev, step, lr,
                                         gscale)
            # zero the accumulators for the next step
            if self._pinned:
                self._acc = None
            else:
                for a in self._acc:
                    a[...] = 0.0

        (self.resident, self._res_master, self._res_m,
         self._res_v) = self._res_update(
            self.resident, res_grads_total, self._res_master, self._res_m,
            self._res_v, step, lr, gscale)
        if self._store is not None:
            self._store.flush()
        mean_loss = jnp.mean(jnp.stack([l.astype(jnp.float32)
                                        for l in losses]))
        # fence on the LAST dispatched program: device execution is
        # in-order, so this covers every fetch/compute/update of the step —
        # the wall time is the true step time, not the dispatch time. The
        # engine fetches the loss right after, so the fence costs nothing.
        jax.block_until_ready(jax.tree.leaves(self._res_v))
        self._record_step_stats(t_step0)
        return mean_loss, grad_norm, False

    def _record_step_stats(self, t_step0: float, skipped: bool = False
                           ) -> None:
        wall = _time.perf_counter() - t_step0
        h2d, d2h = self.stream_bytes_per_step(include_update=not skipped)
        self.last_step_stats = {
            "wall_s": round(wall, 4),
            "h2d_bytes": h2d, "d2h_bytes": d2h,
            "achieved_h2d_gbps": round(h2d / wall / 1e9, 3),
            "achieved_total_gbps": round((h2d + d2h) / wall / 1e9, 3),
            "skipped": skipped,
        }

    # -- streaming instrumentation (VERDICT r4 #5: prove overlap) ----------
    @property
    def _fused(self) -> bool:
        """Single-dispatch update path (no accumulation/clip/scaler) — the
        ONE definition train_step, program warm-up and the byte accounting
        all share."""
        return (self.gas == 1 and self.grad_clip == 0.0
                and self.loss_scaler is None)

    def stream_bytes_per_step(self, include_update: bool = True
                              ) -> Tuple[int, int]:
        """Dominant streamed bytes of ONE train_step as (host->device,
        device->host). Counted from the loop structure: per microbatch the
        forward fetches every block and the backward re-fetches all but the
        last; the update pass (skipped on fp16 overflow —
        ``include_update=False``) moves the fp32 master+moments (12 B/elem)
        both ways, the new params back out, and — non-fused only — the
        fp32 grad accumulator in (4 B/elem, plus per-micro accumulator
        round trips on the pinned tier)."""
        P_bytes = sum(self._block_bytes)
        elems = sum(self._block_elems)
        last = self._block_bytes[-1]
        opt_bytes = 12 * elems
        per_micro_h2d = 2 * P_bytes - last
        if self._fused:
            h2d = per_micro_h2d + opt_bytes
            d2h = P_bytes + opt_bytes
        else:
            h2d = self.gas * per_micro_h2d       # fwd+bwd sweeps
            d2h = 0
            if include_update:
                h2d += (P_bytes                   # update-pass param fetch
                        + 4 * elems               # grad accumulator in
                        + opt_bytes)
                d2h += P_bytes + opt_bytes
            if self._pinned:
                # pinned acc_add round-trips the fp32 accumulator per micro
                d2h += self.gas * 4 * elems
                h2d += max(self.gas - 1, 0) * 4 * elems
            else:
                # numpy/NVMe tier: every microbatch device_gets each
                # block's grads for host accumulation
                d2h += self.gas * P_bytes
        return int(h2d), int(d2h)

    def measure_stream_peak(self, sweeps: int = 2) -> float:
        """Pure-fetch bandwidth: stream every block host->device with no
        compute in between. At most TWO blocks stay resident (the real
        step's window) — holding the whole stack would OOM exactly the
        >HBM models this executor exists for — while the 2-deep window
        still lets consecutive DMAs pipeline. Returns GB/s."""

        def sweep():
            prev = None
            for g in range(self.num_blocks):
                cur = self._fetch_block(g)
                if prev is not None:
                    jax.block_until_ready(prev)
                prev = cur
            jax.block_until_ready(prev)

        sweep()   # warm (first touch maps pages / opens files)
        t0 = _time.perf_counter()
        for _ in range(sweeps):
            sweep()
        dt = _time.perf_counter() - t0
        return sweeps * sum(self._block_bytes) / dt / 1e9

    def overlap_report(self, batch_stack: Any) -> Dict[str, float]:
        """Fetch-vs-compute overlap evidence for one step shape:

        * ``t_fetch_s``   — pure streaming time of the step's h2d bytes at
          the measured peak bandwidth;
        * ``t_compute_s`` — the step's fwd+bwd programs run with a single
          resident block (no streaming);
        * ``t_step_s``    — a real (streamed) step;
        * ``overlap_efficiency`` — (t_fetch + t_compute - t_step) /
          min(t_fetch, t_compute): 1.0 = the shorter phase fully hides
          under the longer, 0 = fully serialized;
        * ``h2d_utilization`` — achieved h2d rate of the real step vs the
          measured pure-fetch peak.
        """

        peak_gbps = self.measure_stream_peak()
        loss, _, _ = self.train_step(batch_stack)   # warm compile
        float(loss)
        for _ in range(8):   # fp16 warm-up overflows back the scale off
            loss, _, skipped = self.train_step(batch_stack)
            float(loss)
            if not skipped:
                break
        else:
            raise RuntimeError("overlap_report: every measured step "
                               "overflowed — lower initial_scale_power")
        stats = dict(self.last_step_stats or {})
        t_step = stats["wall_s"]

        # compute-only proxy: the same fwd+bwd programs over ONE resident
        # block reused G times (same shapes/program, no streaming)
        mb = jax.tree.map(lambda x: x[0], batch_stack)
        ids, mask = mb["input_ids"], mb.get("attention_mask")
        labels = self._labels_of(mb)
        dev_block = self._fetch_block(0)
        jax.block_until_ready(dev_block)
        G = self.num_blocks
        t0 = _time.perf_counter()
        for _ in range(self.gas):
            x = self._embed_fwd(self.resident, ids)
            acts = [x]
            for g in range(G):
                x, _ = self._block_fwd(dev_block, x, mask,
                                       self._bounds[g][0], None)
                acts.append(x)
            (_, l2), (dres, dx) = self._head_vjp(self.resident, acts[G],
                                                 labels, mask, 1.0)
            for g in range(G - 1, -1, -1):
                dx, dblock = self._block_vjp(dev_block, acts[g], mask, dx,
                                             0.0, self._bounds[g][0], None)
        jax.block_until_ready(dx)
        t_compute = _time.perf_counter() - t0
        t_fetch = stats["h2d_bytes"] / (peak_gbps * 1e9)
        eff = (t_fetch + t_compute - t_step) / max(min(t_fetch, t_compute),
                                                   1e-9)
        stats.update({
            "peak_h2d_gbps": round(peak_gbps, 3),
            "t_fetch_s": round(t_fetch, 4),
            "t_compute_s": round(t_compute, 4),
            "t_step_s": t_step,
            "overlap_efficiency": round(max(0.0, min(eff, 1.0)), 4),
            "h2d_utilization": round(
                stats["achieved_h2d_gbps"] / peak_gbps, 4),
        })
        return stats

    # -- eval --------------------------------------------------------------
    def eval_forward(self, mb: Any) -> jax.Array:
        ids = mb["input_ids"]
        mask = mb.get("attention_mask")
        labels = self._labels_of(mb)
        x = self._eval_embed(self.resident, ids)
        aux_total = None
        self._prefetch(0)
        for g in range(self.num_blocks):
            self._prefetch(g + 1)
            x, aux_g = self._eval_block(self._fetch_block(g), x, mask,
                                        self._bounds[g][0], None)
            aux_total = aux_g if aux_total is None else aux_total + aux_g
        _, loss = self._eval_head(self.resident, x, labels, mask, 1.0)
        if getattr(self.cfg, "moe_num_experts", 0):
            loss = loss + (float(self.cfg.moe_aux_loss_coef)
                           / max(self.cfg.num_layers, 1)) * aux_total
        return loss

    # -- checkpoint integration -------------------------------------------
    def params_for_checkpoint(self) -> Any:
        """Full params tree: resident device leaves + assembled host layer
        leaves (np, (L, ...))."""
        if jax.process_count() > 1:
            raise NotImplementedError(
                "full-tree assembly of multi-process offloaded params is "
                "not possible (each process holds only its addressable "
                "regions) — save_checkpoint uses region_checkpoint() for "
                "this; only the consolidated save_16bit_model export "
                "remains single-process")
        if self._pinned or self._store is not None:
            first = self._block_host_leaves(0)
            full = [np.empty((self.num_layers,) + tuple(l.shape[1:]), l.dtype)
                    for l in first]
            for g, (lo, hi) in enumerate(self._bounds):
                leaves = first if g == 0 else self._block_host_leaves(g)
                for dst, src in zip(full, leaves):
                    dst[lo:hi] = src
            leaves = full
        else:
            leaves = self._host_layers
        tree = dict(self.resident)
        tree["layers"] = jax.tree_util.tree_unflatten(self._layers_treedef,
                                                      leaves)
        return tree

    # -- multi-process region checkpointing --------------------------------
    def _layer_leaf_keys(self) -> List[str]:
        """Flatten keys of the layer leaves in checkpoint convention
        ('layers##attn##wq', ...), ordered like the executor's leaf lists."""
        from .checkpoint import _SEP, _flatten_with_keys

        n = len(self._leaf_tails)
        dummy = jax.tree_util.tree_unflatten(self._layers_treedef,
                                             list(range(n)))
        flat = _flatten_with_keys({"layers": dummy})
        keys = [None] * n
        for key, idx in flat.items():
            keys[idx] = key
        return keys

    def checkpoint_template(self) -> Any:
        """Shape skeleton of the FULL params tree (resident arrays + stacked
        layer SDS) — the checkpoint loader only reads shapes/dtypes from the
        template, so nothing is materialised (multi-process safe)."""
        L = self.num_layers
        leaves = [jax.ShapeDtypeStruct((L,) + t, d)
                  for t, d in zip(self._leaf_tails, self._leaf_dtypes)]
        tree = dict(self.resident)
        tree["layers"] = jax.tree_util.tree_unflatten(self._layers_treedef,
                                                      leaves)
        return tree

    def opt_state_template(self) -> Dict[str, Any]:
        L = self.num_layers
        f32 = [jax.ShapeDtypeStruct((L,) + t, jnp.float32)
               for t in self._leaf_tails]
        return {"step": np.int64(0), "layer_master": f32,
                "layer_m": list(f32), "layer_v": list(f32),
                "res_master": self._res_master, "res_m": self._res_m,
                "res_v": self._res_v}

    def region_checkpoint(self):
        """(params_tree, opt_tree, extra_arrays, extra_writes) for a
        multi-process save: resident state rides the normal writer (global
        jax arrays); layer params + their optimizer state become per-REGION
        shard files — each process writes only its addressable regions, and
        every process computes the identical full shard metadata (the
        reference's per-dp-rank ZeRO checkpoint shards, engine.py:3136).
        Blocks are walked OUTER so host residency stays bounded at one
        block (the nvme tier reads each block file once)."""
        from .checkpoint import _SEP, _fname, _index_to_bounds, _to_numpy
        from .checkpoint import unique_shards

        proc = jax.process_index()
        keys = self._layer_leaf_keys()
        full_keys: List[Tuple[str, Any]] = []   # (full_key, dtype) per emit
        for i, key in enumerate(keys):
            full_keys.append((f"params{_SEP}{key}", self._leaf_dtypes[i]))
        for name in ("layer_master", "layer_m", "layer_v"):
            for i in range(len(keys)):
                full_keys.append((f"opt{_SEP}{name}{_SEP}{i}", jnp.float32))

        extra_arrays = {
            fk: {"shape": [self.num_layers] + list(
                     self._leaf_tails[n % len(keys)]),
                 "dtype": str(jnp.dtype(dt)), "shards": []}
            for n, (fk, dt) in enumerate(full_keys)}
        extra_writes: List[Tuple[str, np.ndarray]] = []
        sids = {fk: 0 for fk, _ in full_keys}

        def from_shards(arr, idx):
            for s in arr.addressable_shards:
                if s.index == idx:
                    return np.asarray(s.data)
            raise KeyError(f"no addressable shard {idx}")

        for g, (lo, hi) in enumerate(self._bounds):
            bh = None if self._pinned else self._block_host_leaves(g)
            for i in range(len(keys)):
                blk_shape = (hi - lo,) + self._leaf_tails[i]
                sources = [("params", lambda idx, i=i:
                            from_shards(self._pblocks[g][i], idx)
                            if self._pinned else bh[i][idx])]
                for kind, name, np_src in (
                        ("master", "layer_master", self._master),
                        ("m", "layer_m", self._m), ("v", "layer_v", self._v)):
                    if self._pinned:
                        arr = {"master": self._pmaster, "m": self._pm,
                               "v": self._pv}[kind][g][i]
                        sources.append((name, lambda idx, a=arr:
                                        from_shards(a, idx)))
                    else:
                        sources.append((name, lambda idx, s=np_src[i]:
                                        s[lo:hi][idx]))
                for src_tag, data_of in sources:
                    fk = (f"params{_SEP}{keys[i]}" if src_tag == "params"
                          else f"opt{_SEP}{src_tag}{_SEP}{i}")
                    for dev, idx in unique_shards(self._block_shardings[i],
                                                  blk_shape):
                        inner = _index_to_bounds(idx, blk_shape)
                        bounds = ([[lo + inner[0][0], lo + inner[0][1]]]
                                  + inner[1:])
                        fname = _fname(fk, sids[fk])
                        sids[fk] += 1
                        extra_arrays[fk]["shards"].append(
                            {"file": fname, "bounds": bounds})
                        if dev.process_index == proc:
                            extra_writes.append(
                                (fname, _to_numpy(data_of(idx))))

        params = {k: v for k, v in self.resident.items()}
        opt = {"step": np.int64(self.step_count),
               "res_master": self._res_master, "res_m": self._res_m,
               "res_v": self._res_v}
        return params, opt, extra_arrays, extra_writes

    def load_params(self, tree: Any) -> None:
        kv, _ = _tree_leaves_with_path(tree["layers"])
        leaves = [np.asarray(l) for _, l in kv]
        if self._pinned:
            for g, (lo, hi) in enumerate(self._bounds):
                self._pblocks[g] = [
                    jax.device_put(l[lo:hi], s) for l, s in
                    zip(leaves, self._pinned_shardings)]
                self._pmaster[g] = [
                    jax.device_put(l[lo:hi].astype(np.float32), s)
                    for l, s in zip(leaves, self._pinned_shardings)]
        elif self._store is not None:
            for g, (lo, hi) in enumerate(self._bounds):
                self._store.write_block(g, [l[lo:hi] for l in leaves],
                                        wait=False)
            self._store.flush()
            self._master = [l.astype(np.float32) for l in leaves]
        else:
            for dst, src in zip(self._host_layers, leaves):
                dst[...] = src
            self._master = [l.astype(np.float32) for l in leaves]
        resident = {k: v for k, v in tree.items() if k != "layers"}

        def as_res(x, s):
            # restored resident leaves may be GLOBAL jax arrays spanning
            # other processes (multi-process load) — np.asarray would
            # throw; device_put reshards globally instead
            if isinstance(x, jax.Array):
                return x if x.sharding == s else jax.device_put(x, s)
            return jax.device_put(np.asarray(x), s)

        self.resident = jax.tree.map(as_res, resident, self._res_shardings)
        self._res_master = jax.tree.map(
            lambda x: jnp.asarray(x, jnp.float32), self.resident)

    def _opt_leaves_np(self, which: str) -> List[np.ndarray]:
        if not self._pinned:
            src = {"master": self._master, "m": self._m, "v": self._v}[which]
            return list(src)
        blocks = {"master": self._pmaster, "m": self._pm,
                  "v": self._pv}[which]
        full = [np.empty((self.num_layers,) + tuple(s.shape[1:]), np.float32)
                for s in blocks[0]]
        for g, (lo, hi) in enumerate(self._bounds):
            for dst, src in zip(full, jax.device_get(blocks[g])):
                dst[lo:hi] = np.asarray(src)
        return full

    def opt_state_arrays(self) -> Dict[str, Any]:
        """Optimizer state for checkpoint: layer m/v/master (np) + resident
        trees + step counter."""
        return {
            "step": np.int64(self.step_count),
            "layer_master": self._opt_leaves_np("master"),
            "layer_m": self._opt_leaves_np("m"),
            "layer_v": self._opt_leaves_np("v"),
            "res_master": self._res_master,
            "res_m": self._res_m,
            "res_v": self._res_v,
        }

    def load_opt_state(self, state: Dict[str, Any]) -> None:
        self.step_count = int(state["step"])
        masters = [np.asarray(x, np.float32) for x in state["layer_master"]]
        ms = [np.asarray(x, np.float32) for x in state["layer_m"]]
        vs = [np.asarray(x, np.float32) for x in state["layer_v"]]
        if self._pinned:
            for g, (lo, hi) in enumerate(self._bounds):
                put = lambda leaves: [
                    jax.device_put(l[lo:hi], s) for l, s in
                    zip(leaves, self._pinned_shardings)]
                self._pmaster[g] = put(masters)
                self._pm[g] = put(ms)
                self._pv[g] = put(vs)
        else:
            self._master, self._m, self._v = masters, ms, vs
        def put32(x, s):
            if isinstance(x, jax.Array):   # global array (multi-process)
                x = x.astype(jnp.float32)
                return x if x.sharding == s else jax.device_put(x, s)
            return jax.device_put(np.asarray(x, np.float32), s)

        self._res_master = jax.tree.map(put32, state["res_master"],
                                        self._res_shardings)
        self._res_m = jax.tree.map(put32, state["res_m"], self._res_shardings)
        self._res_v = jax.tree.map(put32, state["res_v"], self._res_shardings)

    def close(self) -> None:
        if self._store is not None:
            self._store.close()

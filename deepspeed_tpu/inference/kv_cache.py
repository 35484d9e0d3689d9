"""KV-cache arena: preallocated per-layer key/value buffers.

TPU-native analog of the reference's ``InferenceContext`` workspace
(csrc/transformer/inference/includes/inference_context.h:49) which sizes one
global GPU arena from ``max_out_tokens`` and hands each layer a slice, and of
the per-layer ``layer_past`` tracking in
model_implementations/transformers/ds_transformer.py:86.

Here the arena is a pytree of stacked per-layer buffers, shaped to scan with
the stacked layer params (models/transformer.py forward):

    {"k": (L, B, T_max, KV_HEADS, HEAD_DIM),
     "v": (L, B, T_max, KV_HEADS, HEAD_DIM),
     "index": (L,) int32}              # write cursor per layer (all equal)

Static T_max keeps every decode step the same XLA program (the reference's
CUDA-graph discipline becomes jit-cache discipline); tokens are written with
``lax.dynamic_update_slice`` at the cursor.

**Paged arena** (the serving layer, ``deepspeed_tpu/serving``): instead of
one ``T_max`` row per sequence, the time axis is carved into fixed-size
blocks shared by every in-flight request (vLLM's PagedAttention block
tables, Kwon et al. SOSP '23):

    {"k": (L, NUM_BLOCKS, BLOCK, KV_HEADS * HEAD_DIM),
     "v": (L, NUM_BLOCKS, BLOCK, KV_HEADS * HEAD_DIM)}

(``L`` pools: a layer's, or in a looped stack a (pass, layer)'s,
``paged_pools``; a block is then a run of tokens in every pass's pools.)

A token's KV heads share one lane-dense row: the TPU stores and blocks
arrays in (8, 128) tiles of the last two dims, so a trailing
``(KV_HEADS, 64)`` would be padded to twice its size and could not be read
a head at a time (``ops/paged_decode_attention.py``).

**A latent arena.** A model whose mixer keeps ONE latent a token in place of
the keys and values of every head (``Mixer.keeps`` "latent": latent
attention, MLA) has no ``"k"`` and no ``"v"`` but one arena of pages

    {"latent": (POOLS, NUM_BLOCKS, BLOCK, LANES)}

a pool a sublayer that has the mixer (``models/transformer.latent_pools``),
a token's latent with the one roped key all heads share behind it
(``latent_width``: 512 + 64 = 576 values for the published family, 4.5
lane tiles) and zeros up to whole lane tiles (``latent_page_width``: 640,
what the chip lays 576 out in anyway). Blocks, tables, scratch and the
allocator are the pages'.

Block 0 is a reserved scratch block: writes of inactive decode rows and
prompt-chunk padding land there, so the jit program needs no write-masking
branch. A host-side free list (``serving/paged_kv.BlockAllocator``) owns
blocks 1.. and hands each sequence a block table ``(MAX_BLOCKS,)`` of
physical ids; attention reads walk it inside the arena, layer ``l``'s
pages at ``k[l, block_table]`` — a shape-static lookup, so one decode
program serves any occupancy, and a layer's pool is never sliced out of the
arena (``models/transformer.forward``, paged branch).

**Two kinds of state.** Pages are what a softmax layer keeps: ``L`` above
counts the layers whose mixer is "attn" (``TransformerConfig.layer_pattern``),
not the model's depth. A layer whose mixer is recurrent
(``models/transformer.recurrent_layers``: the delta rule "kda", the
state-space "mamba2") keeps no pages but, for each sequence, a float32
state and a convolution tail of fixed size; they live in two pools beside
the pages, in the same dict (``_state_shapes``):

    {"state": (RECURRENT_LAYERS, SLOTS, HEADS, D, D) float32,      "kda"
              (RECURRENT_LAYERS, SLOTS, GROUPS, N, K P) float32,  "mamba2"
     "tail":  (RECURRENT_LAYERS, SLOTS, TAPS - 1, the convolution's width)}

A slot belongs to a decode row (``serving/api.py``); the last slot is
scratch. The programs address a row's slot where it lies, as they address a
page, and a sequence whose chunk starts at position 0 starts from zeros
whatever the slot held.

``dtype`` is mandatory throughout: a default here let call sites silently
allocate a bf16 arena for an fp32 (or fp16) engine — the arena dtype must
come from ``InferenceConfig.dtype``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np


def init_cache(cfg, batch_size: int, max_seq_len: int, dtype
               ) -> Dict[str, jax.Array]:
    """Allocate the arena for ``cfg`` (a TransformerConfig)."""
    L = cfg.num_layers
    K = cfg.num_kv_heads
    D = cfg.head_dim
    shape = (L, batch_size, max_seq_len, K, D)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "index": jnp.zeros((L,), jnp.int32),
    }


def cache_memory_bytes(cfg, batch_size: int, max_seq_len: int,
                       dtype=jnp.bfloat16) -> int:
    """Arena footprint — the sizing arithmetic the reference does in
    InferenceContext::GenWorkSpace (inference_context.h:121)."""
    itemsize = jnp.dtype(dtype).itemsize
    return (2 * cfg.num_layers * batch_size * max_seq_len
            * cfg.num_kv_heads * cfg.head_dim * itemsize)


# ---------------------------------------------------------------------------
# paged arena (serving layer)
# ---------------------------------------------------------------------------


def blocks_for_tokens(n_tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``n_tokens`` KV entries."""
    return -(-max(int(n_tokens), 0) // int(block_size))


def assert_block_divisible(max_seq_len: int, block_size: int) -> int:
    """``max_seq_len`` must split into whole blocks — a ragged tail block
    would make the gathered view wider than the sequence budget and break
    the one-program shape discipline. Returns blocks per sequence."""
    if block_size <= 0:
        raise ValueError(f"block_size must be positive, got {block_size}")
    if max_seq_len % block_size != 0:
        raise ValueError(
            f"max_seq_len={max_seq_len} is not divisible by "
            f"block_size={block_size} — the paged arena needs whole blocks "
            "(pick a block size that divides the sequence budget)")
    return max_seq_len // block_size


def _paged_layers(cfg) -> int:
    """Layers that keep pages of their own: those whose mixer is softmax
    attention over all of a sequence (a window layer keeps a ring for each
    slot, a cross layer reads another layer's pages)."""
    from ..models.transformer import paged_layers

    return len(paged_layers(cfg))


def ring_blocks(cfg, chunk_tokens: int, block_size: int) -> int:
    """Pages of a window layer's ring a row (0: the model has no such
    layer): what ``attention_window`` keys and the ``chunk_tokens`` queries
    of the widest program can see together, whole pages. A page is written
    over only by a position a ring's length ahead, so every key in sight of
    a query is resident when it reads, whatever the row's length: the bound
    on a window layer's bytes a row."""
    from ..models.transformer import ring_layers

    if not ring_layers(cfg):
        return 0
    return -(-(cfg.attention_window + chunk_tokens) // block_size)


def paged_pools(cfg) -> int:
    """Pools of pages in the arena's ``"k"`` and in its ``"v"``: one a
    layer that keeps pages and, in a looped stack (``loop_passes``), one a
    (pass, layer), pass-major: a query of a pass sees that pass's keys."""
    return cfg.loop_passes * _paged_layers(cfg)


def _paged_shapes(cfg, num_blocks: int, block_size: int,
                  window: bool = False, pools: int = 0) -> tuple:
    """The shapes of a pool of keys and of its values: ``"k"`` and ``"v"``
    or, with ``window``, the rings ``"wk"`` and ``"wv"`` (``pools`` of
    them). A token's key-value heads lie side by side in the lanes, the
    form's own count of them, keys and values each as wide as they are
    (``models/transformer.page_widths``)."""
    from ..models.transformer import page_widths

    return tuple((pools or paged_pools(cfg), num_blocks, block_size, lanes)
                 for lanes in page_widths(cfg, window))


# the names an arena of pages can have in the cache's dict: what a block of
# the allocator is a run of tokens in (copy-on-write copies a block in each)
PAGE_ARENAS = ("k", "v", "latent")


def _page_shapes(cfg, num_blocks: int, block_size: int) -> Dict[str, tuple]:
    """The arenas of pages by name: ``"k"`` and ``"v"``, or for a model
    whose mixer keeps a latent a token ``"latent"`` alone."""
    from ..models.transformer import latent_page_width, latent_pools

    pools = latent_pools(cfg)
    if pools:
        return {"latent": (pools, num_blocks, block_size,
                           latent_page_width(cfg))}
    return dict(zip(("k", "v"), _paged_shapes(cfg, num_blocks, block_size)))


def cache_slots(cache) -> int:
    """Slots of the per-sequence pools in ``cache`` (0: it holds pages
    alone): a recurrent mixer's ``"tail"`` has one a slot, and a cache
    whose only per-sequence pools are window rings says it with
    ``"slots"`` (``_state_shapes``). THE reader of either, for the programs
    that send a row to its slot and for the ring's table."""
    if "tail" in cache:
        return cache["tail"].shape[1]
    return cache["slots"].shape[0] if "slots" in cache else 0


def _state_shapes(cfg, state_slots: int, dtype,
                  ring: tuple = (0, 0)) -> Dict[str, Any]:
    """The second kind of per-sequence state, beside pages, a SLOT a
    sequence: for each recurrent layer and slot a float32 state a head (a
    delta-rule layer's matrix; a state-space layer's, in
    ``ops/mamba2.pack_states``' or ``ops/mamba1``'s layout; none for a gated
    short convolution, whose ``Mixer.state`` names no state shape) and the
    last ``taps - 1`` rows of the convolution's input (in the model's
    dtype), which every such mixer keeps (``"tail"``); and for each window
    layer and slot a ring of ``ring`` = (pages, tokens a page) for its keys
    and one for its values, behind one scratch page (``"wk"``, ``"wv"``:
    arenas of pages as ``"k"`` and ``"v"`` are, which the paged kernels read
    through the table ``models/transformer._ring_table`` makes). A model may
    have either or both; one with rings and no recurrent layer has no tail
    to say how many slots there are, and keeps ``"slots"``, an int32 a slot
    that nothing writes (``cache_slots`` reads the one or the other).
    ``{}`` for a model with no such layer."""
    from ..models.transformer import MIXERS, recurrent_layers, ring_layers

    mixer, layers = recurrent_layers(cfg)
    n, windows = len(layers), len(ring_layers(cfg))
    if not n and not windows:
        return {}
    if state_slots < 1:
        raise ValueError("a model with recurrent or window layers needs "
                         "state_slots: a slot a decode row and one scratch")
    if n:
        state, taps, width = MIXERS[mixer].state(cfg)
        shapes = {"tail": ((n, state_slots, taps - 1, width), dtype)}
        if state is not None:   # a short convolution keeps a tail alone
            shapes = {"state": ((n, state_slots) + state, jnp.float32),
                      **shapes}
    else:
        shapes = {"slots": ((state_slots,), jnp.int32)}
    if windows:
        pages, block_size = ring
        if pages < 1:
            raise ValueError("a model with window layers needs the pages of "
                             "a row's ring (kv_cache.ring_blocks)")
        wk, wv = _paged_shapes(cfg, 1 + state_slots * pages, block_size,
                               window=True, pools=windows)
        shapes.update(wk=(wk, dtype), wv=(wv, dtype))
    return shapes


def init_paged_cache(cfg, num_blocks: int, block_size: int, dtype,
                     state_slots: int = 0, ring_blocks: int = 0
                     ) -> Dict[str, jax.Array]:
    """Allocate the paged arena: ``num_blocks`` INCLUDES the reserved
    scratch block 0 (allocatable blocks are 1..num_blocks-1). Pages exist
    for the softmax layers only; a model with recurrent layers gets, in the
    same dict, the pools ``"state"`` and ``"tail"`` of ``state_slots`` slots
    and one with window layers the rings ``"wk"`` and ``"wv"`` of
    ``ring_blocks`` pages a slot (``_state_shapes``), zeroed."""
    if num_blocks < 2:
        raise ValueError(f"num_blocks={num_blocks}: need the scratch block "
                         "plus at least one allocatable block")
    return {**{name: jnp.zeros(shape, dtype) for name, shape
               in _page_shapes(cfg, num_blocks, block_size).items()},
            **{name: jnp.zeros(sh, dt) for name, (sh, dt)
               in _state_shapes(cfg, state_slots, dtype,
                                (ring_blocks, block_size)).items()}}


def paged_cache_memory_bytes(cfg, num_blocks: int, block_size: int,
                             dtype) -> int:
    """The pages' footprint (what an arena of ``num_blocks`` costs; the
    state pools are sized by rows, not blocks: ``state_pool_memory_bytes``)."""
    itemsize = jnp.dtype(dtype).itemsize
    return sum(int(np.prod(shape)) for shape in _page_shapes(
        cfg, num_blocks, block_size).values()) * itemsize


def state_pool_memory_bytes(cfg, state_slots: int, dtype,
                            ring: tuple = (0, 0)) -> int:
    """The state pools' footprint, a window layer's rings with them; 0 for
    a model with neither."""
    return sum(int(np.prod(sh)) * jnp.dtype(dt).itemsize for sh, dt
               in _state_shapes(cfg, state_slots, dtype, ring).values())


def paged_cache_shape_struct(cfg, num_blocks: int, block_size: int,
                             dtype, state_slots: int = 0,
                             ring_blocks: int = 0) -> Dict[str, Any]:
    return {**{name: jax.ShapeDtypeStruct(shape, dtype) for name, shape
               in _page_shapes(cfg, num_blocks, block_size).items()},
            **{name: jax.ShapeDtypeStruct(sh, dt) for name, (sh, dt)
               in _state_shapes(cfg, state_slots, dtype,
                                (ring_blocks, block_size)).items()}}

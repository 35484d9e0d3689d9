"""Pallas grouped matmul: the expert matmuls of a dropless MoE layer.

At inference no token is dropped, so the capacity plan of training
(``parallel/moe.py``: an ``(E, C, H)`` dispatched tensor) has to set
``C = T`` and runs every expert over ``T`` slots whatever the router chose:
``E x T`` slots of work for ``T x k`` assignments, and every expert's
weights read whether a token went there or not. This kernel computes the
assigned rows only. The rows are laid out sorted by expert, each expert's
group padded to a multiple of the row tile ``tm`` (``group_layout``), so a
tile of ``tm`` rows belongs to exactly one expert and is one MXU matmul
against that expert's ``(K, N)`` matrix:

    out[tile i] = lhs[tile i] @ rhs[tile_expert[i]]

Grid ``(N / tn, TILES)``, the tile index innermost: consecutive tiles of one
expert ask for the same ``(expert, n)`` weight block, and the pipeline skips
a copy whose block index did not change, so each TOUCHED expert's matrix
moves from HBM once per call and an expert with no row is never read.
``TILES`` is the static worst case (``max_tiles``); the tiles past the
``used`` ones ask for the last used tile's blocks again (no copy) and skip
their matmul, so the time follows the assignments, not the bound.
``tile_expert`` and ``used`` ride as scalar-prefetch operands: the index
maps resolve them before a block's DMA is issued.

**The weight block is an expert's WHOLE matrix wherever that fits**
(``_weight_block_cols``): ``tn = N``, the grid ``(1, TILES)``, one
contiguous copy a touched expert. A few rows against a matrix is a copy
with a product hidden behind it, and what the copy costs is its geometry: a
block of some of the columns is a strided copy, a run a 16-row band of the
matrix, and on a v5e runs under 32 KiB moved at 63-84% of the memory's
nominal rate where a whole matrix moves at 89-90% (``PERF.md`` section 6,
PR 68; ``scripts/time_moe_grouped_matmul_on_chip.py`` repeats it). A row
tile also comes once, not once a column step. The rule reads the operands'
shapes and dtype and nothing else. The weight buffers of a call, its
matrices x the pipeline's 2 buffers x one block, get ``_WEIGHT_VMEM_BYTES``
(64 MiB) of a core's 128 MiB of VMEM: a matrix of up to 32 MiB is one block
of a one-matrix call and a matrix of up to 16 MiB (16,777,216 bytes) one
block of a gated call; a matrix a byte larger comes as the widest column
blocks that are a multiple of the lanes, divide ``N`` and fit the same
share. The call raises its ``vmem_limit_bytes`` to those buffers and
``_REST_VMEM_BYTES`` (16 MiB, the default of a kernel) for the row tiles and
the float32 products beside them (``_vmem_bytes``). The contraction is one
``dot`` over all of ``K`` under either block, so a row's sums keep their
order.

**A gated expert's gate and up are ONE call** (``gate=``): the row tile comes
once, the two matrices' blocks side by side, and the tile written is
``silu(rows @ gate) * (rows @ up)``, each product rounded to the stored
dtype before the float32 gating, which is what two calls and a fusion
between them gave. A SwiGLU layer is two calls (gate with up; down), any
other expert two one-matrix calls with its activation between them.
**Every call is named ``moe_grouped_matmul``**: the benchmark's readers find
the kernel's device time by that name and reckon its work from the spans'
counts of assignments and touched experts, which no form of the call
changes.

The weights may be a model's whole stack ``(L, E, K, N)`` with the ``layer``
to use (a traced int32 scalar: the layer scan's index), a third
scalar-prefetch operand that the weight block's index map puts in front of
the expert. A custom call's operand is a buffer of its own: handed one
layer's ``(E, K, N)`` slice of the stack, XLA copies ALL of that layer's
experts out before every call (268 MB a matrix at OLMoE-1B-7B's widths),
which is the traffic this kernel exists to avoid - the lesson of the paged
arena (``ops/paged_decode_attention.py``) again.

``reference_grouped_matmul`` is the plain ``jnp`` path (CPU, and wherever a
mesh of several devices makes XLA partition the layer) and the oracle the
kernel is tested against.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MAX_TILE_ROWS = 128            # one MXU pass on a v5e
# what a call's weight buffers may hold of a v5e core's 128 MiB of VMEM: the
# matrices of the call (one, or a gated expert's two) x the pipeline's 2
# buffers x one block. A block is an expert's whole (K, N) matrix where that
# fits (16 MiB a block of a gated call, 32 MiB of a one-matrix call), else
# the widest column block that does
_WEIGHT_VMEM_BYTES = 64 * 2 ** 20
# beside them: the row tiles in and out (two buffers each) and the float32
# products of a tile, within what a kernel gets by default
_REST_VMEM_BYTES = 16 * 2 ** 20


def tile_rows(rows: int, groups: int, dtype) -> int:
    """Rows per tile for ``rows`` assignments over ``groups`` experts: the
    power of two nearest above the mean group, between the dtype's sublane
    packing (8 rows of float32, 16 of bfloat16) and one MXU pass. A decode
    step (2 rows an expert) pads little; a prefill chunk fills the MXU."""
    tm = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    while tm < MAX_TILE_ROWS and tm * groups < rows:
        tm *= 2
    return tm


def max_tiles(rows: int, groups: int, tm: int) -> int:
    """The most tiles any split of ``rows`` rows into ``groups`` groups can
    need when each group is padded to a multiple of ``tm``."""
    return -(-rows // tm) + min(groups, rows)


def group_layout(sizes: jax.Array, rows: int, tm: int
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``sizes`` (E,) int32 rows per expert (summing to at most ``rows``) ->
    ``(row_start (E,), tile_expert (TILES,), used (1,))``: the padded row at
    which each expert's group starts, the expert each tile belongs to (the
    tiles past the ``used`` ones repeat the last used tile's) and how many
    tiles hold rows."""
    E = sizes.shape[0]
    tiles_per = (sizes + tm - 1) // tm
    tile_end = jnp.cumsum(tiles_per)
    used = tile_end[-1]
    row_start = (tile_end - tiles_per) * tm
    i = jnp.minimum(jnp.arange(max_tiles(rows, E, tm), dtype=jnp.int32),
                    jnp.maximum(used - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, i, side="right"), E - 1)
    return (row_start.astype(jnp.int32), tile_expert.astype(jnp.int32),
            used.astype(jnp.int32).reshape(1))


def _weight_block_cols(K: int, N: int, dtype, matrices: int = 1) -> int:
    """Columns of one ``(K, tn)`` weight block of a call that streams
    ``matrices`` stacks: all of ``N`` where a whole matrix fits the call's
    share of the weight buffers (one contiguous copy an expert), else the
    most lanes that divide ``N`` within it (a narrow ``N`` is never cut)."""
    budget = (_WEIGHT_VMEM_BYTES // (2 * matrices)
              // (K * jnp.dtype(dtype).itemsize))
    if N % LANES or N <= budget:
        return N
    tn = LANES
    for cand in range(LANES, N, LANES):
        if N % cand == 0 and cand <= budget:
            tn = cand
    return tn


def _vmem_bytes(K: int, tn: int, dtype, matrices: int) -> int:
    """What a call asks of VMEM: its weight buffers and the rest."""
    return (2 * matrices * K * tn * jnp.dtype(dtype).itemsize
            + _REST_VMEM_BYTES)


def _gmm_kernel(tile_expert_ref, used_ref, layer_ref, x_ref, *refs):
    del tile_expert_ref, layer_ref         # read by the index maps
    *w_refs, o_ref = refs                  # (up,) or (gate, up)

    @pl.when(pl.program_id(1) < used_ref[0])
    def _tile():
        x = x_ref[...]
        # each product rounded to the stored dtype, as a call of its own
        # would hand it on
        outs = [jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32
                        ).astype(o_ref.dtype) for w_ref in w_refs]
        if len(outs) == 2:
            gate, up = (o.astype(jnp.float32) for o in outs)
            o_ref[...] = (jax.nn.silu(gate) * up).astype(o_ref.dtype)
        else:
            o_ref[...] = outs[0]


def _stack(rhs: jax.Array, layer) -> Tuple[jax.Array, jax.Array]:
    """(rhs as (L, E, K, N), layer as the (1,) int32 operand)."""
    if rhs.ndim == 3:
        rhs, layer = rhs[None], 0
    elif layer is None:
        raise ValueError("a (L, E, K, N) weight stack needs its layer")
    return rhs, jnp.asarray(layer, jnp.int32).reshape(1)


def moe_grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                       tile_expert: jax.Array, used: jax.Array,
                       layer=None, interpret: bool = False,
                       gate: Optional[jax.Array] = None) -> jax.Array:
    """lhs (TILES * tm, K) rows sorted by expert and padded per expert to
    the tile (``group_layout``); rhs (E, K, N), or (L, E, K, N) with the
    int32 scalar ``layer`` (may be traced); tile_expert (TILES,) int32;
    used (1,) int32. Returns (TILES * tm, N) in lhs's dtype. Rows of tiles
    past ``used`` are not written: mask them where they are read.

    With ``gate``, a second stack of ``rhs``'s shape, the call is a gated
    expert's first half in one pass over the rows:
    ``silu(lhs @ gate) * (lhs @ rhs)``, each product rounded to lhs's dtype
    before the float32 gating, as two calls and a fusion between them give
    it."""
    rows, K = lhs.shape
    rhs, layer = _stack(rhs, layer)
    stacks = [rhs] if gate is None else [_stack(gate, layer)[0], rhs]
    N = rhs.shape[-1]
    tiles = tile_expert.shape[0]
    tm = rows // tiles
    tn = _weight_block_cols(K, N, rhs.dtype, len(stacks))

    def tile(i, used_ref):
        return jnp.minimum(i, jnp.maximum(used_ref[0] - 1, 0))

    # layer and expert are squeezed: the kernel sees a (K, tn) block
    weights = pl.BlockSpec((None, None, K, tn),
                           lambda n, i, te, used, layer:
                           (layer[0], te[i], 0, n))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N // tn, tiles),
        in_specs=[
            pl.BlockSpec((tm, K),
                         lambda n, i, te, used, layer: (tile(i, used), 0)),
        ] + [weights] * len(stacks),
        out_specs=pl.BlockSpec(
            (tm, tn), lambda n, i, te, used, layer: (tile(i, used), n)),
    )
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(K, tn, rhs.dtype, len(stacks))),
        name="moe_grouped_matmul",
        interpret=interpret,
    )(tile_expert, used, layer, lhs, *stacks)


def reference_grouped_matmul(lhs: jax.Array, rhs: jax.Array,
                             tile_expert: jax.Array, used: jax.Array,
                             layer=None,
                             gate: Optional[jax.Array] = None) -> jax.Array:
    """The same product in plain ``jnp``: each tile against its expert's
    matrix, gathered; tiles past ``used`` come back zero. With ``gate``,
    the gated pass of ``moe_grouped_matmul``."""
    if gate is not None:
        g, up = (reference_grouped_matmul(lhs, w, tile_expert, used, layer
                                          ).astype(jnp.float32)
                 for w in (gate, rhs))
        return (jax.nn.silu(g) * up).astype(lhs.dtype)
    rows, K = lhs.shape
    rhs, layer = _stack(rhs, layer)
    tiles = tile_expert.shape[0]
    out = jnp.einsum("tmk,tkn->tmn", lhs.reshape(tiles, rows // tiles, K),
                     rhs[layer[0], tile_expert],
                     preferred_element_type=jnp.float32)
    live = jnp.arange(tiles)[:, None, None] < used[0]
    return jnp.where(live, out, 0).astype(lhs.dtype).reshape(rows, -1)

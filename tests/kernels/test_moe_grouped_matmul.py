"""`moe_grouped_matmul` (interpret mode on CPU) against its `jnp` path and
against a matmul per row with that row's expert, over uneven groups: an
empty expert, one expert holding every row, groups that are no multiple of
the tile, no row at all; one matrix an expert, and a gated expert's gate
and up in one pass. And the rule that chooses a call's weight block."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.moe_grouped_matmul import (_vmem_bytes,
                                                  _weight_block_cols,
                                                  group_layout, max_tiles,
                                                  moe_grouped_matmul,
                                                  reference_grouped_matmul,
                                                  tile_rows)

E, K, N = 4, 128, 256

# rows per expert, of ROWS = 40 assignments
GROUPS = {
    "uneven": [13, 0, 26, 1],           # an empty expert, no multiple of 16
    "one-holds-all": [0, 0, 40, 0],
    "even": [10, 10, 10, 10],
    "first-and-last": [3, 0, 0, 37],
    "tile-multiples": [16, 0, 16, 0],
    "fewer-than-the-bound": [1, 1, 1, 0],   # most tiles past `used`
    "no-row": [0, 0, 0, 0],
}
ROWS = 40


def _laid_out(sizes, tm, dtype, seed=0, rows=ROWS):
    """Rows sorted by expert, each group padded to the tile with junk that
    must not reach another group; (lhs, rhs, layout, the expert per row)."""
    sizes = jnp.asarray(sizes, jnp.int32)
    row_start, tile_expert, used = group_layout(sizes, rows, tm)
    tiles = tile_expert.shape[0]
    assert tiles == max_tiles(rows, E, tm)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    lhs = jax.random.normal(ks[0], (tiles * tm, K), jnp.float32)
    rhs = jax.random.normal(ks[1], (E, K, N), jnp.float32) * 0.1
    expert_of_row = np.full(tiles * tm, -1)
    for e, (start, n) in enumerate(zip(np.asarray(row_start),
                                       np.asarray(sizes))):
        expert_of_row[start:start + n] = e
    return (lhs.astype(dtype), rhs.astype(dtype), tile_expert, used,
            expert_of_row)


def _gate_stack(dtype, seed=7):
    return (jax.random.normal(jax.random.PRNGKey(seed), (E, K, N),
                              jnp.float32) * 0.1).astype(dtype)


def _two_calls_and_a_fusion(mm, lhs, gate, up, tile_expert, used, layer=None):
    """A gated expert's first half as `parallel/moe` made it before the
    gated call: a call a matrix, the gating in float32 between them."""
    g, u = (mm(lhs, w, tile_expert, used, layer).astype(jnp.float32)
            for w in (gate, up))
    return (jax.nn.silu(g) * u).astype(lhs.dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("groups", sorted(GROUPS))
def test_kernel_matches_jnp_path_and_per_row_matmul(groups, dtype):
    tm = tile_rows(ROWS, E, dtype)
    assert tm == 16         # the mean group is 10 rows
    lhs, rhs, tile_expert, used, expert_of_row = _laid_out(GROUPS[groups],
                                                           tm, dtype)
    got = moe_grouped_matmul(lhs, rhs, tile_expert, used, interpret=True)
    ref = reference_grouped_matmul(lhs, rhs, tile_expert, used)
    live = np.arange(lhs.shape[0]) < int(used[0]) * tm
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               rtol=tol, atol=tol)
    assert not np.asarray(ref, np.float32)[~live].any()
    real = expert_of_row >= 0
    assert real.sum() == sum(GROUPS[groups])
    want = np.einsum("rk,rkn->rn", np.asarray(lhs, np.float32)[real],
                     np.asarray(rhs, np.float32)[expert_of_row[real]])
    np.testing.assert_allclose(np.asarray(got, np.float32)[real], want,
                               rtol=tol, atol=10 * tol)


# the gated call over the same groups, and over row tiles of 32 and 128
# (a prefill chunk's): rows per expert, the tile, the assignments
GATED = {**{name: (sizes, 16, ROWS) for name, sizes in GROUPS.items()},
         "tile-32-two-tiles": ([40, 0, 17, 33], 32, 90),
         "tile-128-two-tiles": ([200, 0, 130, 90], 128, 420),
         "tile-128-fewer-than-the-bound": ([3, 0, 0, 1], 128, 420)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("groups", sorted(GATED))
def test_gated_call_matches_jnp_path_two_calls_and_per_row(groups, dtype):
    """``gate=``: silu(rows @ gate) * (rows @ up) in one pass. Against the
    `jnp` twin, against a product per row, and BIT FOR BIT against the two
    calls and the float32 fusion it replaces; what no tile writes (past
    ``used``) may hold anything, NaN here, and is never read into a live
    row."""
    sizes, tm, rows = GATED[groups]
    assert tile_rows(rows, E, dtype) == tm
    lhs, up, tile_expert, used, expert_of_row = _laid_out(sizes, tm, dtype,
                                                         rows=rows)
    gate = _gate_stack(dtype)
    live = np.arange(lhs.shape[0]) < int(used[0]) * tm
    assert int(used[0]) <= tile_expert.shape[0]
    # rows of tiles past `used` hold NaN: an unwritten tile reads them not
    lhs = jnp.where(live[:, None], lhs, jnp.nan)
    got = moe_grouped_matmul(lhs, up, tile_expert, used, gate=gate,
                             interpret=True)
    assert got.dtype == lhs.dtype and got.shape == (lhs.shape[0], N)
    two = _two_calls_and_a_fusion(
        lambda *a: moe_grouped_matmul(*a, interpret=True), lhs, gate, up,
        tile_expert, used)
    np.testing.assert_array_equal(np.asarray(got, np.float32)[live],
                                  np.asarray(two, np.float32)[live])
    ref = reference_grouped_matmul(jnp.where(live[:, None], lhs, 0), up,
                                   tile_expert, used, gate=gate)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(ref, np.float32)[live],
                               rtol=tol, atol=tol)
    assert not np.asarray(ref, np.float32)[~live].any()
    real = expert_of_row >= 0
    assert real.sum() == sum(sizes)
    x = np.asarray(lhs, np.float32)[real]
    g, u = (np.einsum("rk,rkn->rn", x,
                      np.asarray(w, np.float32)[expert_of_row[real]])
            for w in (gate, up))
    want = g / (1 + np.exp(-g)) * u
    np.testing.assert_allclose(np.asarray(got, np.float32)[real], want,
                               rtol=tol, atol=10 * tol)


def test_the_jnp_twin_gated_is_its_two_products_and_the_gating():
    """The CPU path of a SwiGLU layer computes what it computed: the gated
    twin is the twin a matrix and the float32 gating, bit for bit."""
    tm = 16
    lhs, up, tile_expert, used, _ = _laid_out(GROUPS["uneven"], tm,
                                              jnp.bfloat16)
    gate = _gate_stack(jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(reference_grouped_matmul(lhs, up, tile_expert, used,
                                            gate=gate), np.float32),
        np.asarray(_two_calls_and_a_fusion(reference_grouped_matmul, lhs,
                                           gate, up, tile_expert, used),
                   np.float32))


def test_a_layer_of_a_weight_stack_is_read_in_place():
    """(L, E, K, N) with a traced layer index gives what that layer's
    (E, K, N) gives: the kernel addresses the stack, it is handed no slice."""
    tm = tile_rows(ROWS, E, jnp.float32)
    lhs, rhs, tile_expert, used, _ = _laid_out(GROUPS["uneven"], tm,
                                               jnp.float32)
    stack = jnp.stack([rhs * 0.5, rhs, rhs * 2.0])
    want = moe_grouped_matmul(lhs, rhs, tile_expert, used, interpret=True)

    @jax.jit
    def at(layer):
        return (moe_grouped_matmul(lhs, stack, tile_expert, used, layer,
                                   interpret=True),
                reference_grouped_matmul(lhs, stack, tile_expert, used,
                                         layer))

    kernel, ref = at(jnp.int32(1))
    live = np.arange(lhs.shape[0]) < int(used[0]) * tm
    np.testing.assert_array_equal(np.asarray(kernel)[live],
                                  np.asarray(want)[live])
    np.testing.assert_allclose(np.asarray(ref)[live], np.asarray(want)[live],
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="needs its layer"):
        moe_grouped_matmul(lhs, stack, tile_expert, used, interpret=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_gated_layer_of_two_stacks_is_read_in_place(dtype):
    """Both (L, E, K, N) stacks of a gated call are addressed at the traced
    layer: what that layer's two (E, K, N) banks give, bit for bit."""
    tm = 16
    lhs, up, tile_expert, used, _ = _laid_out(GROUPS["uneven"], tm, dtype)
    gate = _gate_stack(dtype)
    ups = jnp.stack([up * 0.5, up, up * 2.0])
    gates = jnp.stack([gate * 2.0, gate, gate * 0.5])
    want = moe_grouped_matmul(lhs, up, tile_expert, used, gate=gate,
                              interpret=True)

    @jax.jit
    def at(layer):
        return (moe_grouped_matmul(lhs, ups, tile_expert, used, layer,
                                   gate=gates, interpret=True),
                reference_grouped_matmul(lhs, ups, tile_expert, used, layer,
                                         gate=gates))

    kernel, ref = at(jnp.int32(1))
    live = np.arange(lhs.shape[0]) < int(used[0]) * tm
    np.testing.assert_array_equal(np.asarray(kernel, np.float32)[live],
                                  np.asarray(want, np.float32)[live])
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(ref, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               rtol=tol, atol=tol)
    other, _ = at(jnp.int32(2))
    assert not np.array_equal(np.asarray(other, np.float32)[live],
                              np.asarray(want, np.float32)[live])


def test_tile_rows_follow_the_mean_group():
    assert tile_rows(16 * 8, 64, jnp.bfloat16) == 16     # a decode step
    assert tile_rows(256 * 8, 64, jnp.bfloat16) == 32    # a prefill chunk
    assert tile_rows(4096 * 2, 8, jnp.bfloat16) == 128   # never past the MXU
    assert max_tiles(128, 64, 16) == 72


MIB = 2 ** 20
# an expert's (hidden or latent, expert width) in bfloat16, the block the
# gate/up side takes (gated: two matrices a call) and the down side, and the
# VMEM each call asks for: its weight buffers and 16 MiB beside them
BLOCKS = {
    # 4 MiB a matrix
    "olmoe-1b-7b": (2048, 1024, True, 1024, 2048, 32 * MIB, 24 * MIB),
    # 7 MiB
    "lfm2-8b-a1b": (2048, 1792, True, 1792, 2048, 44 * MIB, 30 * MIB),
    # 10 MiB
    "solar-open2": (4096, 1280, True, 1280, 4096, 56 * MIB, 36 * MIB),
    # 5.25 MiB, not gated: one matrix a call both sides
    "nemotron-3-super": (1024, 2688, False, 2688, 1024, 26.5 * MIB,
                         26.5 * MIB),
    # 24 MiB: over a gated call's 16 MiB a block, under a one-matrix
    # call's 32; the gate/up side falls back to column blocks of 12 MiB
    "longcat-flash": (6144, 2048, True, 1024, 6144, 64 * MIB, 64 * MIB),
}


@pytest.mark.parametrize("config", sorted(BLOCKS))
def test_the_weight_block_is_a_whole_matrix_where_it_fits(config):
    H, F, gated, up_cols, down_cols, up_vmem, down_vmem = BLOCKS[config]
    matrices = 2 if gated else 1
    bf16 = jnp.bfloat16
    assert _weight_block_cols(H, F, bf16, matrices) == up_cols
    assert _weight_block_cols(F, H, bf16) == down_cols
    assert _vmem_bytes(H, up_cols, bf16, matrices) == up_vmem
    assert _vmem_bytes(F, down_cols, bf16, 1) == down_vmem
    assert max(up_vmem, down_vmem) <= 80 * MIB      # of a core's 128


def test_the_block_rule_at_its_threshold():
    """Both sides of it: a matrix of exactly a call's share is one block, a
    lane more of columns falls back to the widest divisor that fits; a
    float32 matrix weighs twice; a width that is no multiple of the lanes
    is never cut."""
    bf16 = jnp.bfloat16
    assert _weight_block_cols(8192, 1024, bf16, 2) == 1024      # 16 MiB
    assert _weight_block_cols(8192, 1024 + 128, bf16, 2) == 384
    assert _weight_block_cols(8192, 2048, bf16) == 2048         # 32 MiB
    assert _weight_block_cols(8192, 2048 + 128, bf16) == 128    # 17 x 128
    assert _weight_block_cols(8192, 2048, jnp.float32) == 1024
    assert _weight_block_cols(8192, 4096, bf16, 2) == 1024
    assert _weight_block_cols(65536, 200, bf16, 2) == 200

"""`moe_grouped_matmul` (interpret mode on CPU) against its `jnp` path and
against a matmul per row with that row's expert, over uneven groups: an
empty expert, one expert holding every row, groups that are no multiple of
the tile, no row at all."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.moe_grouped_matmul import (group_layout, max_tiles,
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


def _laid_out(sizes, tm, dtype, seed=0):
    """Rows sorted by expert, each group padded to the tile with junk that
    must not reach another group; (lhs, rhs, layout, the expert per row)."""
    sizes = jnp.asarray(sizes, jnp.int32)
    row_start, tile_expert, used = group_layout(sizes, ROWS, tm)
    tiles = tile_expert.shape[0]
    assert tiles == max_tiles(ROWS, E, tm)
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    lhs = jax.random.normal(ks[0], (tiles * tm, K), jnp.float32)
    rhs = jax.random.normal(ks[1], (E, K, N), jnp.float32) * 0.1
    expert_of_row = np.full(tiles * tm, -1)
    for e, (start, n) in enumerate(zip(np.asarray(row_start),
                                       np.asarray(sizes))):
        expert_of_row[start:start + n] = e
    return (lhs.astype(dtype), rhs.astype(dtype), tile_expert, used,
            expert_of_row)


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


def test_tile_rows_follow_the_mean_group():
    assert tile_rows(16 * 8, 64, jnp.bfloat16) == 16     # a decode step
    assert tile_rows(256 * 8, 64, jnp.bfloat16) == 32    # a prefill chunk
    assert tile_rows(4096 * 2, 8, jnp.bfloat16) == 128   # never past the MXU
    assert max_tiles(128, 64, 16) == 72

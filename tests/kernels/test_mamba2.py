"""The three forms of the Mamba-2 recurrence (`ops/mamba2.py`) against each
other, float32 against float32: the chunked form and the decode step's
`jax.numpy` twin against the token-by-token recurrence at 1e-5 or tighter, the
Pallas decode kernel (interpret mode here; the chip's compiler is
`test_tpu_compile.py`'s and the chip itself
`scripts/check_nemotron_h_on_chip.py`'s) against its twin, and a state kept in
bfloat16 between tokens FAILING the same limit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import mamba2 as ssm

F32 = jnp.float32
TOL = 1e-5


def _operands(rng, B, S, H, P, G, N, step="mixed"):
    """x, dt, A, B, C as the mixer makes them: dt a softplus of the published
    init's range (0.001 to 0.1, `mixed`: some heads far above it, so a decay
    of exp(-16 x 5) is in the test), A = -U(1, 16)."""
    x = jnp.asarray(rng.standard_normal((B, S, H, P)), F32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (B, S, H)))
    if step == "mixed":
        dt[..., ::3] *= 50.0
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (H,)), F32)
    Bm = jnp.asarray(rng.standard_normal((B, S, G, N)), F32)
    Cm = jnp.asarray(rng.standard_normal((B, S, G, N)), F32)
    return x, jnp.asarray(dt, F32), A, Bm, Cm


@pytest.mark.parametrize("step", ["published", "mixed"])
@pytest.mark.parametrize("tokens,chunk", [(37, 16), (128, 128), (5, 128),
                                          (200, 64)])
def test_chunked_form_against_the_recurrence(tokens, chunk, step):
    rng = np.random.default_rng(tokens + chunk)
    x, dt, A, Bm, Cm = _operands(rng, 2, tokens, 8, 16, 2, 16, step)
    start = jnp.asarray(rng.standard_normal((2, 8, 16, 16)), F32)
    want_y, want_s = ssm.mamba2_recurrence(x, dt, A, Bm, Cm, start)
    got_y, got_s = jax.jit(lambda *a: ssm.mamba2_chunk(*a, chunk=chunk))(
        x, dt, A, Bm, Cm, start)
    scale = float(np.abs(want_y).max())
    assert np.abs(got_y - want_y).max() < TOL * scale
    assert np.abs(got_s - want_s).max() < TOL * float(np.abs(want_s).max())


@pytest.mark.parametrize("form", ["recurrence", "chunk"])
def test_a_token_with_no_step_writes_nothing(form):
    """dt 0: a ragged chunk's padding. The state after 20 real tokens and 12
    such is the state after the 20."""
    rng = np.random.default_rng(4)
    x, dt, A, Bm, Cm = _operands(rng, 2, 32, 8, 16, 2, 16)
    dt = dt.at[:, 20:].set(0.0)
    start = jnp.asarray(rng.standard_normal((2, 8, 16, 16)), F32)
    fn = ssm.mamba2_recurrence if form == "recurrence" else ssm.mamba2_chunk
    _, padded = fn(x, dt, A, Bm, Cm, start)
    _, alone = fn(x[:, :20], dt[:, :20], A, Bm[:, :20], Cm[:, :20], start)
    np.testing.assert_allclose(padded, alone, atol=1e-6, rtol=1e-6)


def test_the_pools_layout_is_a_relabelling():
    state = jnp.asarray(np.random.default_rng(0).standard_normal(
        (3, 2, 8, 16, 32)), F32)
    packed = ssm.pack_states(state, groups=2)
    assert packed.shape == (3, 2, 2, 32, 4 * 16)
    # head 4 g + k, channel p, state n lies at group g, row n, lane 16 k + p
    assert packed[1, 1, 1, 5, 16 + 3] == state[1, 1, 5, 3, 5]
    np.testing.assert_array_equal(ssm.unpack_states(packed, 16), state)


def test_the_chunked_form_takes_the_state_as_the_pool_holds_it():
    rng = np.random.default_rng(9)
    x, dt, A, Bm, Cm = _operands(rng, 2, 40, 8, 16, 2, 16)
    start = jnp.asarray(rng.standard_normal((2, 8, 16, 16)), F32)
    want_y, want_s = ssm.mamba2_chunk(x, dt, A, Bm, Cm, start, chunk=16)
    got_y, got_s = ssm.mamba2_chunk(x, dt, A, Bm, Cm,
                                    ssm.pack_states(start, 2), chunk=16,
                                    packed=True)
    np.testing.assert_array_equal(got_y, want_y)
    np.testing.assert_array_equal(ssm.unpack_states(got_s, 16), want_s)


def _step_case(rng, R, H, P, G, N, L, SLOTS):
    x, dt, A, Bm, Cm = (a[:, 0] if a.ndim > 1 else a for a in
                        _operands(rng, R, 1, H, P, G, N))
    pool = jnp.asarray(rng.standard_normal((L, SLOTS, G, N, H // G * P)), F32)
    return x, dt, A, Bm, Cm, pool


def test_the_twin_is_one_token_of_the_recurrence():
    rng = np.random.default_rng(2)
    x, dt, A, Bm, Cm, pool = _step_case(rng, 3, 8, 16, 2, 16, 2, 4)
    slots = jnp.asarray([2, 0, 3], jnp.int32)
    y, new = ssm.reference_mamba2_decode_step(x, dt, A, Bm, Cm, pool, 1,
                                              slots)
    want_y, want_s = ssm.mamba2_recurrence(
        x[:, None], dt[:, None], A, Bm[:, None], Cm[:, None],
        ssm.unpack_states(pool[1, slots], 16))
    np.testing.assert_allclose(y, want_y[:, 0], atol=TOL, rtol=TOL)
    np.testing.assert_allclose(ssm.unpack_states(new[1, slots], 16), want_s,
                               atol=TOL, rtol=TOL)
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[1, 1], pool[1, 1])


@pytest.mark.parametrize("shape", ["published-group", "half-a-register"])
def test_decode_kernel_against_its_reference(shape):
    """Rows at their own slots of one layer of the pool, two rows sharing
    the scratch slot; the layer a traced scalar, as the layer scan's. At the
    published head dim and state (64, 128) with 16 heads a group (1,024
    lanes a grid step, walked a register's width at a time), and with a
    group of one head (64 lanes)."""
    rng = np.random.default_rng(len(shape))
    R, L, SLOTS = 5, 3, 7
    H, G = (32, 2) if shape == "published-group" else (2, 2)
    x, dt, A, Bm, Cm, pool = _step_case(rng, R, H, 64, G, 128, L, SLOTS)
    slots = jnp.asarray([3, 0, 6, 1, 6], jnp.int32)
    want_y, want_pool = ssm.reference_mamba2_decode_step(
        x, dt, A, Bm, Cm, pool, 1, slots)
    got_y, got_pool = jax.jit(
        lambda *a: ssm.mamba2_decode_step(*a, interpret=True))(
            x, dt, A, Bm, Cm, pool, jnp.int32(1), slots)
    live = np.asarray([0, 1, 3])            # rows with a slot of their own
    np.testing.assert_allclose(np.asarray(got_y)[live],
                               np.asarray(want_y)[live], atol=1e-4, rtol=1e-5)
    own = np.asarray(slots)[live]
    np.testing.assert_allclose(np.asarray(got_pool)[1, own],
                               np.asarray(want_pool)[1, own],
                               atol=1e-5, rtol=1e-5)
    # nothing else of the pool moved: the other layers, the slots no row has
    for layer in (0, 2):
        np.testing.assert_array_equal(np.asarray(got_pool)[layer],
                                      np.asarray(pool)[layer])
    for slot in (2, 4, 5):
        np.testing.assert_array_equal(np.asarray(got_pool)[1, slot],
                                      np.asarray(pool)[1, slot])


def test_decode_steps_one_after_another_are_the_recurrence():
    """T decode steps of one row through the kernel, the pool handed from
    step to step, against T tokens of the recurrence from a zero state; and
    the same steps with the pool kept in bfloat16 between them, which the
    limit must tell from it."""
    rng = np.random.default_rng(6)
    T, H, P, G, N = 24, 4, 64, 2, 128
    x, dt, A, Bm, Cm = _operands(rng, 1, T, H, P, G, N, "published")
    want_y, want_s = ssm.mamba2_recurrence(x, dt, A, Bm, Cm,
                                           jnp.zeros((1, H, P, N), F32))
    scale = float(np.abs(want_y).max())
    slots = jnp.zeros((1,), jnp.int32)
    step = jax.jit(lambda *a: ssm.mamba2_decode_step(*a, interpret=True))
    twin = jax.jit(ssm.reference_mamba2_decode_step)
    for fn, dtype, holds in ((step, F32, True), (twin, F32, True),
                             (twin, jnp.bfloat16, False)):
        pool = jnp.zeros((1, 2, G, N, H // G * P), dtype)
        off = 0.0
        for t in range(T):
            y, pool = fn(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], pool,
                         jnp.int32(0), slots)
            off = max(off, float(np.abs(y - want_y[:, t]).max()))
        assert (off < TOL * scale) == holds, (dtype, off, scale)
    del want_s


def test_ops_registry_resolves_the_step_by_platform():
    from deepspeed_tpu import ops

    assert "mamba2_decode_step" in ops.available_ops()
    # the CPU has no Mosaic: the registry hands out the jnp twin
    assert (ops.get_op("mamba2_decode_step")
            is ssm.reference_mamba2_decode_step)

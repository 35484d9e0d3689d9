"""The three forms of the Mamba-1 selective scan (`ops/mamba1.py`) against
each other, float32 against float32 at 1e-5: the decode step's `jax.numpy`
twin and the two Pallas kernels (interpret mode here; the chip's compiler is
`test_tpu_compile.py`'s and the chip itself
`scripts/check_phi4flash_on_chip.py`'s) against the token-by-token
recurrence, and a state kept in bfloat16 between tokens FAILING the same
limit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import mamba1 as ssm

F32 = jnp.float32
TOL = 1e-5


def _operands(rng, B, S, D, N, step="mixed"):
    """x, dt, A, B, C as the mixer makes them: dt a softplus of the published
    init's range (0.001 to 0.1, `mixed`: some channels far above it, so a
    decay of exp(-16 x 5) is in the test), A = -U(1, 16) a (state, channel)."""
    x = jnp.asarray(rng.standard_normal((B, S, D)), F32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (B, S, D)))
    if step == "mixed":
        dt[..., ::3] *= 50.0
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (N, D)), F32)
    Bm = jnp.asarray(rng.standard_normal((B, S, N)), F32)
    Cm = jnp.asarray(rng.standard_normal((B, S, N)), F32)
    return x, jnp.asarray(dt, F32), A, Bm, Cm


def _by_hand(x, dt, A, Bm, Cm, state):
    """The recurrence in numpy loops: S = exp(dt A) S + dt x B^T, y = S C."""
    x, dt, A, Bm, Cm = (np.asarray(a, np.float64) for a in (x, dt, A, Bm, Cm))
    S = np.asarray(state, np.float64).copy()
    ys = np.zeros(x.shape)
    for b in range(x.shape[0]):
        for t in range(x.shape[1]):
            S[b] = (np.exp(dt[b, t][None] * A) * S[b]
                    + (dt[b, t] * x[b, t])[None] * Bm[b, t][:, None])
            ys[b, t] = (S[b] * Cm[b, t][:, None]).sum(0)
    return ys, S


def test_the_recurrence_is_the_published_one():
    rng = np.random.default_rng(1)
    x, dt, A, Bm, Cm = _operands(rng, 2, 9, 24, 4)
    start = jnp.asarray(rng.standard_normal((2, 4, 24)), F32)
    want_y, want_s = _by_hand(x, dt, A, Bm, Cm, start)
    got_y, got_s = ssm.mamba1_recurrence(x, dt, A, Bm, Cm, start)
    assert np.abs(got_y - want_y).max() < TOL * np.abs(want_y).max()
    assert np.abs(got_s - want_s).max() < TOL * np.abs(want_s).max()


@pytest.mark.parametrize("step", ["published", "mixed"])
@pytest.mark.parametrize("tokens,width", [(37, 256), (8, 1024), (5, 96)])
def test_the_chunk_kernel_against_the_recurrence(tokens, width, step):
    """A slab of 512 channels a grid step where the width has them (1,024:
    two slabs), else the whole width."""
    rng = np.random.default_rng(tokens + width)
    x, dt, A, Bm, Cm = _operands(rng, 2, tokens, width, 16, step)
    start = jnp.asarray(rng.standard_normal((2, 16, width)), F32)
    want_y, want_s = ssm.mamba1_recurrence(x, dt, A, Bm, Cm, start)
    got_y, got_s = jax.jit(lambda *a: ssm.mamba1_chunk_scan(
        *a, interpret=True))(x, dt, A, Bm, Cm, start)
    assert np.abs(got_y - want_y).max() < TOL * float(np.abs(want_y).max())
    assert np.abs(got_s - want_s).max() < TOL * float(np.abs(want_s).max())


@pytest.mark.parametrize("form", ["recurrence", "kernel"])
def test_a_token_with_no_step_writes_nothing(form):
    """dt 0: a ragged chunk's padding. The state after 20 real tokens and 12
    such is the state after the 20."""
    rng = np.random.default_rng(4)
    x, dt, A, Bm, Cm = _operands(rng, 2, 32, 128, 16)
    dt = dt.at[:, 20:].set(0.0)
    start = jnp.asarray(rng.standard_normal((2, 16, 128)), F32)
    fn = (ssm.mamba1_recurrence if form == "recurrence" else
          lambda *a: ssm.mamba1_chunk_scan(*a, interpret=True))
    _, padded = fn(x, dt, A, Bm, Cm, start)
    _, alone = fn(x[:, :20], dt[:, :20], A, Bm[:, :20], Cm[:, :20], start)
    np.testing.assert_allclose(padded, alone, atol=1e-6, rtol=1e-6)


def _step_case(seed=0, R=5, D=1024, N=16, L=3):
    rng = np.random.default_rng(seed)
    x, dt, A, Bm, Cm = _operands(rng, R, 1, D, N)
    pool = jnp.asarray(rng.standard_normal((L, R + 1, N, D)), F32)
    slots = jnp.asarray([3, 0, R, 1, 4], jnp.int32)     # row 2 holds nothing
    return (x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0]), pool, slots


@pytest.mark.parametrize("form", ["twin", "kernel"])
@pytest.mark.parametrize("layer", [0, 2])
def test_the_decode_step_advances_its_rows_states_in_the_pool(form, layer):
    operands, pool, slots = _step_case()
    x, dt, A, Bm, Cm = operands
    step = (ssm.reference_mamba1_decode_step if form == "twin" else
            lambda *a: ssm.mamba1_decode_step(*a, interpret=True))
    got_y, got_pool = jax.jit(step)(x, dt, A, Bm, Cm, pool,
                                    jnp.int32(layer), slots)
    live = np.asarray(slots) < 5
    want_y, want_s = ssm.mamba1_recurrence(
        x[:, None], dt[:, None], A, Bm[:, None], Cm[:, None],
        pool[layer, slots])
    assert np.abs(np.asarray(got_y - want_y[:, 0])[live]).max() \
        < TOL * float(np.abs(want_y).max())
    assert np.abs(np.asarray(got_pool[layer, slots] - want_s)[live]).max() \
        < TOL * float(np.abs(want_s).max())
    # nothing else of the pool moved: the other layers, the slot no row owns
    others = [l for l in range(3) if l != layer]
    np.testing.assert_array_equal(got_pool[jnp.asarray(others)],
                                  pool[jnp.asarray(others)])
    np.testing.assert_array_equal(got_pool[layer, 2], pool[layer, 2])


@pytest.mark.parametrize("form", ["twin", "kernel"])
def test_a_state_kept_in_bfloat16_fails(form):
    """Ten steps over a pool in bfloat16: the same arithmetic, the state
    rounded between tokens, is a hundred times past the limit."""
    operands, pool, slots = _step_case(seed=3)
    x, dt, A, Bm, Cm = operands
    step = (ssm.reference_mamba1_decode_step if form == "twin" else
            lambda *a: ssm.mamba1_decode_step(*a, interpret=True))
    step = jax.jit(step)
    sound, rounded = pool, pool.astype(jnp.bfloat16)
    for _ in range(10):
        want_y, sound = step(x, dt, A, Bm, Cm, sound, jnp.int32(1), slots)
        got_y, rounded = step(x, dt, A, Bm, Cm, rounded, jnp.int32(1), slots)
    assert rounded.dtype == jnp.bfloat16
    live = np.asarray(slots) < 5
    assert np.abs(np.asarray(got_y - want_y)[live]).max() \
        > 100 * TOL * float(np.abs(want_y).max())

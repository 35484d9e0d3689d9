"""The gated delta rule with per-channel decay (`ops/kda.py`): the chunked
form and the one-token Pallas kernel (interpret mode here; compiled for the
described chip in test_tpu_compile.py) against the token-by-token recurrence.

Tolerances: float32 on both sides. The chunked form sums a sub-chunk's
contributions in another order than the recurrence and solves a triangular
system where the recurrence substitutes a token at a time: with beta near 2
(an update matrix `I - beta k k^T` with an eigenvalue near -1) the two read
up to 6e-6 apart on outputs of size 1, so 5e-5; the kernel does the
recurrence's own operations a head at a time and reads 0 to 1e-6 from its
`jax.numpy` twin, so 1e-5.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import kda

F32 = jnp.float32
# log-decays: exp(g) near 1 (a channel that forgets nothing), near 0 (one
# that forgets everything at once: exp(-90) underflows a factored form), and
# the whole range between a token
DECAYS = {"near-one": (-12.0, -7.0), "near-zero": (3.0, 4.5),
          "mixed": (-9.0, 4.5)}
BETAS = {"near-two": (1.9, 2.0), "small": (0.0, 0.3), "any": (0.0, 2.0)}


def _operands(rng, B, S, H, d, decay, beta):
    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), F32)

    q, k, v = normal(B, S, H, d), normal(B, S, H, d), normal(B, S, H, d)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    g = -jnp.exp(jnp.asarray(rng.uniform(*DECAYS[decay], (B, S, H, d)), F32))
    b = jnp.asarray(rng.uniform(*BETAS[beta], (B, S, H)), F32)
    return q, k, v, g, b


@pytest.mark.parametrize("tokens", [1, 16, 45, 64])
@pytest.mark.parametrize("beta", sorted(BETAS))
@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_chunked_form_gives_the_recurrence(decay, beta, tokens):
    rng = np.random.default_rng(hash((decay, beta, tokens)) % 2 ** 31)
    B, H, d = 2, 3, 16
    q, k, v, g, b = _operands(rng, B, tokens, H, d, decay, beta)
    start = jnp.asarray(rng.standard_normal((B, H, d, d)), F32)
    want_o, want_s = kda.kda_recurrence(q, k, v, g, b, start)
    got_o, got_s = jax.jit(kda.kda_chunk)(q, k, v, g, b, start)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(got_s, want_s, atol=5e-5, rtol=5e-5)


def test_a_chunk_after_a_chunk_is_the_whole_sequence():
    """The state a chunk hands on is all the next one needs."""
    rng = np.random.default_rng(3)
    q, k, v, g, b = _operands(rng, 1, 80, 2, 16, "mixed", "any")
    zero = jnp.zeros((1, 2, 16, 16), F32)
    want_o, want_s = kda.kda_recurrence(q, k, v, g, b, zero)
    cut = 37
    o1, s1 = kda.kda_chunk(*(a[:, :cut] for a in (q, k, v, g, b)), zero)
    o2, s2 = kda.kda_chunk(*(a[:, cut:] for a in (q, k, v, g, b)), s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), want_o,
                               atol=5e-5, rtol=5e-5)
    np.testing.assert_allclose(s2, want_s, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("form", ["recurrence", "chunk"])
def test_a_token_with_beta_zero_and_decay_one_writes_nothing(form):
    """How a ragged chunk's padding is written: the state after 20 real
    tokens and 12 such tokens is the state after the 20."""
    rng = np.random.default_rng(4)
    q, k, v, g, b = _operands(rng, 2, 32, 2, 16, "mixed", "near-two")
    real = jnp.arange(32) < 20
    g = jnp.where(real[None, :, None, None], g, 0.0)
    b = jnp.where(real[None, :, None], b, 0.0)
    start = jnp.asarray(rng.standard_normal((2, 2, 16, 16)), F32)
    fn = kda.kda_recurrence if form == "recurrence" else kda.kda_chunk
    _, padded = fn(q, k, v, g, b, start)
    _, alone = fn(*(a[:, :20] for a in (q, k, v, g, b)), start)
    np.testing.assert_allclose(padded, alone, atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("beta", ["near-two", "any"])
@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_decode_kernel_against_its_reference(decay, beta):
    """Rows at their own slots of one layer of the pool, two rows sharing
    the scratch slot; the layer a traced scalar, as the layer scan's."""
    rng = np.random.default_rng(hash((decay, beta)) % 2 ** 31)
    R, H, d, L, SLOTS = 5, 4, 128, 3, 7
    q, k, v, g, b = (a[:, 0] for a in
                     _operands(rng, R, 1, H, d, decay, beta))
    pool = jnp.asarray(rng.standard_normal((L, SLOTS, H, d, d)), F32)
    slots = jnp.asarray([3, 0, 6, 1, 6], jnp.int32)
    want_o, want_pool = kda.reference_kda_decode_step(q, k, v, g, b, pool, 1,
                                                      slots)
    got_o, got_pool = jax.jit(
        lambda *a: kda.kda_decode_step(*a, interpret=True))(
            q, k, v, g, b, pool, jnp.int32(1), slots)
    live = np.asarray([0, 1, 3])                  # rows with a slot of their own
    np.testing.assert_allclose(np.asarray(got_o)[live],
                               np.asarray(want_o)[live], atol=1e-5, rtol=1e-5)
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
    step to step, against T tokens of the recurrence from a zero state."""
    rng = np.random.default_rng(6)
    T, H, d = 6, 2, 128
    q, k, v, g, b = _operands(rng, 1, T, H, d, "mixed", "near-two")
    want_o, want_s = kda.kda_recurrence(q, k, v, g, b,
                                        jnp.zeros((1, H, d, d), F32))
    pool = jnp.zeros((1, 2, H, d, d), F32)
    slots = jnp.zeros((1,), jnp.int32)
    step = jax.jit(lambda *a: kda.kda_decode_step(*a, interpret=True))
    for t in range(T):
        o, pool = step(q[:, t], k[:, t], v[:, t], g[:, t], b[:, t], pool,
                       jnp.int32(0), slots)
        np.testing.assert_allclose(o, want_o[:, t], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(pool[0, 0], want_s[0], atol=1e-5, rtol=1e-5)


def test_ops_registry_resolves_the_step_by_platform():
    from deepspeed_tpu import ops

    assert "kda_decode_step" in ops.available_ops()
    # the CPU has no Mosaic: the registry hands out the jnp twin
    assert ops.get_op("kda_decode_step") is kda.reference_kda_decode_step
    assert (ops.get_op("kda_decode_step", force_reference=True)
            is kda.reference_kda_decode_step)

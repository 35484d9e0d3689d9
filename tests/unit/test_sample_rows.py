"""``paged_kv.sample_rows``: the work follows what a batch's rows ask for.

(a) bit for bit against the function as it stood before PR 39, kept here as
the frozen two-sort reference; (b) the shape of the program: one ``cond``,
a greedy branch with no sort in it, one ``sort`` in all; (c) through
``ServingEngine`` a row's stream does not depend on the branch its
neighbours send the batch down; (d) the serving spans carry
``sampled_rows``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.config.config import ObservabilityConfig, ServingConfig
from deepspeed_tpu.inference import init_inference
from deepspeed_tpu.observability import (configure_observability,
                                         recorded_spans, reset_session)
from deepspeed_tpu.serving import ServingEngine
from deepspeed_tpu.serving.paged_kv import sample_rows


def two_sort_reference(logits, base_key, temperature, top_k, top_p, seeds,
                       steps):
    """``sample_rows`` at the parent of PR 39, line for line: every row pays
    for both sorts and the draw, and the choice comes last."""
    logits = logits.astype(jnp.float32)
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(
        desc, jnp.clip(top_k - 1, 0, V - 1)[:, None], axis=1)
    scaled = jnp.where((top_k[:, None] > 0) & (scaled < kth),
                       -jnp.inf, scaled)
    desc = jnp.sort(scaled, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs < top_p[:, None]).at[:, 0].set(True)
    cutoff = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True)
    scaled = jnp.where(scaled >= cutoff, scaled, -jnp.inf)
    keys = jax.vmap(
        lambda s, t: jax.random.fold_in(jax.random.fold_in(base_key, s), t)
    )(seeds, steps)
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temperature <= 0.0, greedy, sampled)


V = 5003                      # a prime: no power of two, no multiple of 128
BASE_KEY = jax.random.PRNGKey(11)
NEW = jax.jit(sample_rows)
OLD = jax.jit(two_sort_reference)


def tied_logits(rng, rows, kind="levels"):
    """bf16 logits with ties. `levels`: a few dozen values, so that every
    place of a row is a tie, the k-th and the nucleus's edge among them, and
    the head is one as well, which the argmax has to break the same way.
    `bf16`: a normal draw rounded to bf16, tied in the bulk (128 values an
    octave) and distinct at the head, so that the k-th place and the
    nucleus's edge fall between distinct scores, INSIDE the k kept.
    `peaked`: the same at twice the scale, a nucleus of a few tokens."""
    if kind == "levels":
        levels = rng.normal(0.0, 3.0, 48)
        x = levels[rng.integers(0, 48, (rows, V))]
        x[:, :7] = levels.max() + 1.0
    else:
        x = rng.normal(0.0, 2.0 if kind == "bf16" else 4.0, (rows, V))
    return jnp.asarray(x, jnp.bfloat16)


def temperatures(rng, rows, mix):
    t = rng.uniform(0.3, 1.6, rows).astype(np.float32)
    if mix == "greedy":
        t[:] = 0.0
    elif mix == "mixed":
        t[rng.random(rows) < 0.5] = 0.0
        t[0] = 0.9            # at least one of each, whatever the draw
        if rows > 1:
            t[-1] = 0.0
    return t


@pytest.mark.parametrize("top_p", [1.0, 0.999, 0.9, 0.3])
@pytest.mark.parametrize("top_k", [0, 1, 5, 50, V])
@pytest.mark.parametrize("rows", [1, 16])
@pytest.mark.parametrize("mix", ["greedy", "sampled", "mixed"])
def test_tokens_are_those_of_the_two_sort_function(mix, rows, top_k, top_p):
    rng = np.random.default_rng([len(mix), rows, top_k, int(top_p * 1e3)])
    for batch in ("levels", "bf16", "peaked"):
        logits = tied_logits(rng, rows, batch)
        args = (logits, BASE_KEY, jnp.asarray(temperatures(rng, rows, mix)),
                jnp.full((rows,), top_k, jnp.int32),
                jnp.full((rows,), top_p, jnp.float32),
                jnp.asarray(rng.integers(0, 2**31 - 1, rows), jnp.int32),
                jnp.asarray(rng.integers(0, 4096, rows), jnp.int32))
        np.testing.assert_array_equal(np.asarray(NEW(*args)),
                                      np.asarray(OLD(*args)),
                                      err_msg=f"batch {batch}")


def test_rows_with_knobs_of_their_own_match_too():
    """One batch in which every row sets another (temperature, k, p)."""
    rng = np.random.default_rng(5)
    rows = 16
    ks = np.asarray([0, 1, 5, 50, V, 2, 0, 7] * 2, np.int32)
    ps = np.asarray([1.0, 0.999, 0.9, 0.3] * 4, np.float32)
    args = (tied_logits(rng, rows, "bf16"), BASE_KEY,
            jnp.asarray(temperatures(rng, rows, "mixed")), jnp.asarray(ks),
            jnp.asarray(ps), jnp.arange(rows, dtype=jnp.int32),
            jnp.arange(rows, dtype=jnp.int32) * 3)
    np.testing.assert_array_equal(np.asarray(NEW(*args)),
                                  np.asarray(OLD(*args)))


# ---------------------------------------------------------------------------
# (b) the shape of the program
# ---------------------------------------------------------------------------


def primitives(jaxpr):
    """Names of the primitives of a jaxpr and of every jaxpr inside it."""
    out = []
    for eqn in jaxpr.eqns:
        out.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(primitives(sub))
    return out


@pytest.fixture(scope="module")
def sampler_jaxpr():
    rows = 4
    return jax.make_jaxpr(sample_rows)(
        jnp.zeros((rows, 259), jnp.bfloat16), BASE_KEY,
        jnp.zeros((rows,), jnp.float32), jnp.zeros((rows,), jnp.int32),
        jnp.ones((rows,), jnp.float32), jnp.zeros((rows,), jnp.int32),
        jnp.zeros((rows,), jnp.int32)).jaxpr


def test_the_sampler_holds_one_cond_and_one_sort(sampler_jaxpr):
    names = primitives(sampler_jaxpr)
    assert names.count("cond") == 1
    assert names.count("sort") == 1
    # and nothing of the sampling path stands outside the conditional
    top = [e.primitive.name for e in sampler_jaxpr.eqns]
    assert not {"sort", "cumsum", "random_bits", "exp", "div"} & set(top)


def test_the_greedy_branch_sorts_and_draws_nothing(sampler_jaxpr):
    (cond,) = [e for e in sampler_jaxpr.eqns if e.primitive.name == "cond"]
    by_sort = {("sort" in primitives(b.jaxpr)): primitives(b.jaxpr)
               for b in cond.params["branches"]}
    assert set(by_sort) == {True, False}
    heavy = {"sort", "cumsum", "random_bits", "exp", "div", "reduce_max",
             "argmax"}
    assert not heavy & set(by_sort[False])      # the argmax is shared: it
    #   stands before the cond, once
    assert {"sort", "cumsum", "random_bits", "exp"} <= set(by_sort[True])


# ---------------------------------------------------------------------------
# (c), (d) through the engine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_engine():
    return init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)


def serving(tiny_engine, **cfg):
    return ServingEngine(tiny_engine, ServingConfig(
        block_size=16, num_blocks=32, max_seqs=4, max_model_len=128,
        prefill_chunk=16, max_queue=64, prefix_cache=False, **cfg))


SAMPLED = dict(max_new_tokens=10, temperature=0.8, top_k=20, top_p=0.9,
               seed=5)
GREEDY = dict(max_new_tokens=10)
OTHERS = {"greedy": [GREEDY, dict(max_new_tokens=7)],
          "sampled": [dict(max_new_tokens=10, temperature=1.3, top_p=0.7,
                           seed=6),
                      dict(max_new_tokens=7, temperature=0.5, top_k=5,
                           seed=7)]}


def stream_of(tiny_engine, first, others):
    """The tokens of a request served beside `others`, which join its
    batch a prefill later and send its steps down the branch they choose."""
    rng = np.random.RandomState(17)
    prompt = rng.randint(0, 250, (21,)).astype(np.int32)
    srv = serving(tiny_engine)
    try:
        h = srv.submit(prompt, **first)
        for i, kw in enumerate(others):
            srv.submit(rng.randint(0, 250, (9 + 13 * i,)).astype(np.int32),
                       **kw)
        srv.run()
        return list(h.result())
    finally:
        srv.close()


@pytest.fixture(scope="module")
def alone(tiny_engine):
    return {"sampled": stream_of(tiny_engine, SAMPLED, []),
            "greedy": stream_of(tiny_engine, GREEDY, [])}


@pytest.mark.parametrize("beside", ["greedy", "sampled"])
@pytest.mark.parametrize("kind", ["sampled", "greedy"])
def test_a_rows_stream_does_not_depend_on_its_neighbours_branch(
        tiny_engine, alone, kind, beside):
    first = SAMPLED if kind == "sampled" else GREEDY
    assert stream_of(tiny_engine, first, OTHERS[beside]) == alone[kind]
    assert len(alone[kind]) == 10


def test_greedy_beside_sampled_is_offline_generate(tiny_engine, alone):
    prompt = np.random.RandomState(17).randint(0, 250, (21,)).astype(np.int32)
    want = np.asarray(tiny_engine.generate(prompt[None],
                                           max_new_tokens=10))[0]
    np.testing.assert_array_equal(alone["greedy"], want)
    assert alone["sampled"] != alone["greedy"]


@pytest.fixture
def obs_on(tmp_path):
    reset_session()
    configure_observability(ObservabilityConfig(
        enabled=True, output_dir=str(tmp_path / "obs"),
        flight_recorder=False))
    yield
    reset_session()


@pytest.mark.parametrize("mix,sampled", [("greedy", 0), ("mixed", 2)])
def test_spans_carry_sampled_rows(tiny_engine, obs_on, mix, sampled):
    srv = serving(tiny_engine)
    try:
        kws = [GREEDY] + (OTHERS["sampled"] if mix == "mixed"
                          else OTHERS["greedy"])
        for i, kw in enumerate(kws):
            srv.submit(np.arange(1, 12 + i, dtype=np.int32),
                       **dict(kw, max_new_tokens=6))
        srv.run()
    finally:
        srv.close()
    spans = recorded_spans()
    dec = [s["attrs"] for s in spans if s["name"] == "serving/decode"]
    # the greedy request is admitted first and leaves first: every other
    # row of a mixed step is a sampled one
    assert max(a["rows"] for a in dec) == 3
    assert max(a["sampled_rows"] for a in dec) == sampled
    assert all(a["sampled_rows"] in ((0,) if mix == "greedy"
                                     else (a["rows"] - 1, a["rows"]))
               for a in dec)
    chunks = [s["attrs"]["sampled_rows"] for s in spans
              if s["name"] == "serving/prefill_chunk"]
    assert sorted(chunks) == [0] * (3 - sampled) + [1] * sampled


def test_the_verify_span_carries_sampled_rows(tiny_engine, obs_on):
    srv = serving(tiny_engine,
                  speculative={"mode": "ngram", "num_draft_tokens": 3})
    try:
        srv.submit(np.arange(1, 14, dtype=np.int32), max_new_tokens=6)
        srv.submit(np.arange(2, 20, dtype=np.int32), max_new_tokens=6,
                   temperature=0.7, top_k=8, seed=3)
        srv.run()
    finally:
        srv.close()
    ver = [s["attrs"] for s in recorded_spans()
           if s["name"] == "serving/verify" and "rows" in s["attrs"]]
    # the greedy request is admitted a chunk ahead of the sampled one
    both = [a["sampled_rows"] for a in ver if a["rows"] == 2]
    assert both and set(both) == {1}
    assert all(a["sampled_rows"] <= a["rows"] for a in ver)

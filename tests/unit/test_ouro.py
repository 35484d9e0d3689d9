"""Ouro (`ouro`: a LOOPED stack: the same layers run `loop_passes` times, a
norm behind each half of a layer, the final norm closing every pass, an exit
gate a pass, one pool of pages a (pass, layer)) against its plain float32
reference. CPU, float32, seeded weights, `tiny-ouro`: 4 layers x 3 passes,
hidden 64, 4 heads of 16.

Tolerance: float32 on both sides, so the program and the reference differ by
rounding alone: the served log-probabilities read 5e-7 from the reference's.
The limit is 1e-5 and every control must read a hundred times the limit or
more (a pass fewer 0.52, a pass reading the pool before its own 0.43, every
pass reading the last pass's pool 0.26, no norm behind the halves 0.48, the
closing norm once at the end 0.41).
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceConfig, InferenceEngine
from deepspeed_tpu.inference.kv_cache import (init_paged_cache,
                                              paged_cache_memory_bytes,
                                              paged_pools)
from deepspeed_tpu.models import create_model
from deepspeed_tpu.models.transformer import (TransformerConfig, forward,
                                              param_axes)
from deepspeed_tpu.serving import ServingConfig, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOL = 1e-5
SEED = 3
REF_ARGS = dict(total_ut_steps=3, early_exit_threshold=1, rms_norm_eps=1e-6,
                rope_theta=1e6, num_attention_heads=4, num_key_value_heads=4)


def _reference():
    path = os.path.join(REPO, "benchmarks", "references", "ouro.py")
    spec = importlib.util.spec_from_file_location("reference_ouro", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


@pytest.fixture(scope="module")
def tiny():
    model = create_model("tiny-ouro")
    params = model.init(jax.random.PRNGKey(SEED))
    ids = np.random.default_rng(0).integers(0, 256, (2, 61)).astype(np.int32)
    return model, params, ids


def _serving(model, params, **kw):
    engine = InferenceEngine(model, InferenceConfig(dtype=jnp.float32,
                                                    seed=3), params=params)
    shape = dict(num_blocks=64, block_size=4, max_seqs=4, prefill_chunk=12,
                 max_model_len=128)
    shape.update(kw)
    return ServingEngine(engine, ServingConfig(**shape))


def _ref(fn, params, ids, **changed):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p, i: getattr(REF, fn)(
            p, i, **dict(REF_ARGS, **changed)))(params, ids))


def test_the_published_sizes_count_the_published_parameters():
    """2.668 B by the shapes alone (nothing is allocated), and 1.5 MiB of
    keys and values a token: 4 passes x 48 layers x K and V x 16 heads of
    128 in bfloat16."""
    model = create_model("ouro-2.6b", dtype=jnp.bfloat16)
    cfg = model.config
    assert (cfg.loop_passes, cfg.loop_exit_threshold, cfg.norm_position) \
        == (4, 1.0, "sandwich")
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    count = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert count(shapes["layers"]) == 48 * layer
    total = count(shapes)
    assert total == 48 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert 2.667e9 < total < 2.669e9
    assert paged_pools(cfg) == 4 * 48
    assert paged_cache_memory_bytes(cfg, 1, 1, jnp.bfloat16) == 1_572_864
    assert paged_cache_memory_bytes(cfg, 321, 16, jnp.bfloat16) \
        == 321 * 24 * 2 ** 20
    assert set(jax.tree.structure(param_axes(cfg)).node_data()[1]) \
        == set(jax.tree.structure(shapes).node_data()[1])
    axes = param_axes(cfg)
    assert set(axes["layers"]) == set(shapes["layers"])
    assert set(axes["exit_gate"]) == {"w", "b"}


def test_one_pass_is_every_other_model():
    """`loop_passes` 1 adds no leaf and no pool; a norm behind the halves is
    its own switch."""
    plain = create_model("tiny-ouro", loop_passes=1, norm_position="pre")
    shapes = jax.eval_shape(plain.init, jax.random.PRNGKey(0))
    assert "exit_gate" not in shapes
    assert set(shapes["layers"]) == {"attn", "ln1", "ln2", "mlp"}
    assert paged_pools(plain.config) == 4
    with pytest.raises(AssertionError, match="looped stack"):
        create_model("tiny-ouro", moe_num_experts=4)
    with pytest.raises(AssertionError, match="looped stack"):
        create_model("tiny-ouro", final_norm=False)
    with pytest.raises(AssertionError, match="parallel-residual"):
        create_model("tiny-ouro", parallel_residual=True)


@pytest.mark.parametrize("chunk", [12, 64], ids=["many-chunks", "two-chunks"])
def test_served_scores_against_the_reference(tiny, chunk):
    """`score_logprobs` (the harness's `correct`): chunks, then the last
    token a step, over a row several chunks long."""
    model, params, _ = tiny
    served = _serving(model, params, prefill_chunk=chunk)
    assert served._arena["k"].shape == (3 * 4, 65, 4, 4 * 16)
    seq = np.random.default_rng(1).integers(0, 256, 100).astype(np.int32)
    got = served.score_logprobs(seq)
    want = _ref("next_token_logprobs", params, seq[None])[0]
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("control", REF.CONTROLS)
def test_a_wrong_model_fails(tiny, control):
    model, params, _ = tiny
    served = _serving(model, params)
    seq = np.random.default_rng(1).integers(0, 256, 100).astype(np.int32)
    got = served.score_logprobs(seq)
    wrong = _ref("next_token_logprobs", params, seq[None],
                 controls=(control,))[0]
    assert np.abs(got - wrong).max() > 100 * TOL


def _paged_logits(cfg, params, seq, chunks, width, cache, table):
    """`seq` through `forward` in paged mode as the serving programs call
    it: the prompt in the ragged `chunks` (each padded to `width`), then a
    token a step; the logits of every position."""
    @jax.jit
    def run(cache, tokens, pos, mask, start, n):
        run_of = {} if tokens.shape[1] == 1 else {"paged_run": (start, n)}
        logits, cache, _ = forward(params, tokens, cfg, cache=cache,
                                   positions=pos, block_table=table,
                                   paged_write_mask=mask, **run_of)
        return logits, cache

    out, start = [], 0
    for n in chunks:
        chunk = np.zeros((1, width), np.int32)
        chunk[0, :n] = seq[start:start + n]
        mask = (np.arange(width) < n)[None]
        pos = np.where(mask, start + np.arange(width)[None], -1)
        logits, cache = run(cache, jnp.asarray(chunk), jnp.asarray(pos),
                            jnp.asarray(mask), jnp.int32(start), jnp.int32(n))
        out.append(np.asarray(logits)[0, :n])
        start += n
    for p in range(start, len(seq)):
        logits, cache = run(cache, jnp.asarray(seq[p:p + 1])[None],
                            jnp.asarray([[p]]), jnp.ones((1, 1), bool),
                            jnp.int32(p), jnp.int32(1))
        out.append(np.asarray(logits)[0])
    return np.concatenate(out), cache


@pytest.mark.parametrize("chunks,width", [((16, 16, 9), 16), ((14, 2, 1, 7), 16),
                                          ((3,), 16)])
def test_ragged_chunks_then_decode_against_the_full_pass(tiny, chunks,
                                                         width):
    """Prefill in ragged chunks (a prompt over two chunks and more), then
    decoding through the pools, LOGITS against the reference's full forward
    pass; every pass's pools hold the row, and the pools differ."""
    model, params, ids = tiny
    cfg = model.config
    cache = init_paged_cache(cfg, 20, 4, jnp.float32)
    assert cache["k"].shape == (12, 20, 4, 64)      # 3 passes x 4 layers
    table = jnp.asarray([list(range(1, 17)) + [0] * 4], jnp.int32)
    with jax.default_matmul_precision("highest"):
        for row in (0, 1):
            seq = ids[row]
            got, cache = _paged_logits(cfg, params, seq, chunks, width,
                                       cache, table)
            want = _ref("logits", params, seq[None])[0]
            assert np.abs(got - want).max() < TOL
    pools = np.asarray(cache["k"])[:, 1:17].reshape(3, 4, -1)
    assert pools.any(axis=-1).all()
    # a layer's keys differ from pass to pass: its input does
    assert np.abs(pools[0] - pools[1]).max() > 0.01
    assert np.abs(pools[1] - pools[2]).max() > 0.01
    assert not np.asarray(cache["k"])[:, 17:].any()


@pytest.mark.parametrize("threshold", [0.3, 0.5, 0.7])
def test_an_exit_threshold_under_one_picks_the_references_pass(tiny,
                                                               threshold):
    """Under 1 the head reads, a token, the first pass whose cumulated exit
    probability reaches the threshold: the same pass as the reference, at
    every position, through chunks and steps (every pass still runs: a
    token that has left owes the later passes its keys). The gate's weight
    is drawn wide here, so that the passes the tokens leave at DIFFER."""
    model, params, ids = tiny
    cfg = dataclasses.replace(model.config, loop_exit_threshold=threshold)
    wide = dict(params, exit_gate={
        "w": 25.0 * params["exit_gate"]["w"], "b": params["exit_gate"]["b"]})
    seq = ids[0]
    left = _ref("exit_passes", wide, seq[None],
                early_exit_threshold=threshold)[0]
    assert len(np.unique(left)) >= 2, np.bincount(left)
    want = _ref("logits", wide, seq[None], early_exit_threshold=threshold)[0]
    table = jnp.asarray([list(range(1, 17)) + [0] * 4], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = _paged_logits(cfg, wide, seq, (16, 16, 9), 16,
                               init_paged_cache(cfg, 20, 4, jnp.float32),
                               table)
        whole = np.asarray(forward(wide, jnp.asarray(seq[None]), cfg)[0])[0]
    assert np.abs(got - want).max() < TOL
    assert np.abs(whole - want).max() < TOL
    # and the last pass's logits are another answer for the tokens that left
    last = _ref("logits", wide, seq[None])[0]
    assert np.abs(last - want)[left < 2].max() > 100 * TOL


def test_served_sequences_against_the_reference(tiny):
    """Through `init_serving`'s engine: more requests than rows, prompts of
    one to six ragged chunks; every greedy token is the reference's best by
    its LOGITS. The prefix cache is on: a block is a run of tokens in every
    pool, and a second request with the first's prompt shares its blocks."""
    model, params, _ = tiny
    served = _serving(model, params)
    assert served.prefix is not None
    rng = np.random.default_rng(0)
    sent = []
    for n in (45, 70, 10, 33, 64, 5):
        prompt = rng.integers(0, 256, n).astype(np.int32)
        sent.append((prompt, served.submit(
            prompt, max_new_tokens=int(rng.integers(5, 30)))))
    served.run()
    again = served.submit(sent[1][0], max_new_tokens=8)
    served.run()
    assert served.prefix.cached_blocks > 0
    np.testing.assert_array_equal(again.result(), sent[1][1].result()[:8])
    for prompt, handle in sent:
        full = np.concatenate([prompt, np.asarray(handle.result(), np.int32)])
        want = _ref("logits", params, full[None])[0]
        best = want[len(prompt) - 1:-1]
        chosen = best[np.arange(len(best)), full[len(prompt):]]
        assert (best.max(-1) - chosen).max() < TOL


def test_a_preempted_sequence_is_recomputed_to_the_same_tokens(tiny):
    """A pool far too small for the load: eviction and recompute, every
    pass's pools written again from the first page on."""
    model, params, _ = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 250, rng.integers(20, 60)).astype(np.int32)
               for _ in range(6)]
    small = _serving(model, params, num_blocks=36)
    handles = [small.submit(p, max_new_tokens=12) for p in prompts]
    small.run()
    assert small.sched.preemption_count > 0
    roomy = _serving(model, params)
    for p, h in zip(prompts, handles):
        want = roomy.submit(p, max_new_tokens=12)
        roomy.run()
        np.testing.assert_array_equal(h.result(), want.result())
    small.prefix.clear()        # what is left is the prefix cache's
    assert small.alloc.blocks_in_use == 0


def test_the_spans_say_what_the_loop_ran(tiny, tmp_path):
    """`serving/decode` and `serving/prefill_chunk` carry the passes the
    program made and the pools it wrote; the registry counts the passes. A
    stack that runs once says nothing of either."""
    from deepspeed_tpu.config.config import ObservabilityConfig
    from deepspeed_tpu.observability import (configure_observability,
                                             get_registry, recorded_spans,
                                             reset_session)

    model, params, _ = tiny
    reset_session()
    configure_observability(ObservabilityConfig(
        enabled=True, output_dir=str(tmp_path / "obs"),
        flight_recorder=False))
    try:
        counter = get_registry().counter("serving/loop_passes_run")
        before = counter.value()
        served = _serving(model, params)
        assert served._loop_counts == {"loop_passes": 3, "pools": 12}
        served.submit(np.arange(20, dtype=np.int32), max_new_tokens=4)
        served.run()
        recorded = recorded_spans()
        spans = [s for s in recorded
                 if s["name"] in ("serving/decode", "serving/prefill_chunk")
                 and "loop_passes" in s["attrs"]]
        assert {s["name"] for s in spans} == {"serving/decode",
                                              "serving/prefill_chunk"}
        assert all(s["attrs"]["loop_passes"] == 3 and s["attrs"]["pools"] == 12
                   for s in spans)
        steps = [s for s in recorded if s["name"] == "serving/decode"
                 and s["attrs"].get("rows")]
        assert steps and all(s["attrs"]["loop_passes"] == 3 for s in steps)
        programs = sum(s["name"].endswith("/dispatch") for s in recorded)
        assert programs == 2 + 3        # two chunks, three steps
        assert counter.value() - before == 3 * programs
        plain = create_model("tiny-llama")
        once = _serving(plain, plain.init(jax.random.PRNGKey(0)))
        assert once._loop_counts == {}
    finally:
        reset_session()


def test_the_dense_cache_and_the_stage_executors_refuse_by_name(tiny):
    """The dense cache keeps one pool a layer; the pipeline, parameter
    offload and the per-layer profiler run the stack once: each refuses a
    looped stack by the name of the mechanism. Without a cache the whole
    forward runs, and so does its gradient."""
    model, params, ids = tiny
    engine = InferenceEngine(model, InferenceConfig(dtype=jnp.float32),
                             params=params)
    with pytest.raises(NotImplementedError, match="looped stack"):
        engine.generate(np.arange(20, dtype=np.int32)[None],
                        max_new_tokens=2)
    from deepspeed_tpu.models.transformer import require_one_pass

    with pytest.raises(NotImplementedError, match="loop_passes"):
        require_one_pass(model.config, "pipeline parallelism")
    require_one_pass(TransformerConfig(), "anything")
    batch = {"input_ids": jnp.asarray(ids[:, :24])}
    loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
    with jax.default_matmul_precision("highest"):
        want = REF.loss(params, batch["input_ids"], **REF_ARGS)
    assert abs(float(loss) - float(want)) < 1e-5
    # the same weights in every pass: a layer's gradient sums over passes
    assert float(jnp.abs(grads["layers"]["attn"]["wq"]).max()) > 0
    assert not np.asarray(grads["exit_gate"]["w"]).any()  # threshold 1

"""OLMoE (softmax top-k of many experts without renormalisation, q/k RMSNorm)
against its plain float32 reference, and the dropless grouped expert path
against the capacity path it replaces at inference. CPU, float32, seeded
weights, `tiny-olmoe` (3 experts a token of 8: neither 1 nor 2).

Tolerance: float32 on both sides, so the program and the reference differ by
rounding alone (order of summation in the matmuls, the expert mix summed
over 3 chosen experts here and over all 8 there): the training path's
logits read 1.8e-7 from the reference's and the served log-probabilities
9.5e-7 (the GPT-NeoX fixture of tests/benchmark_harness reads the same). The
limit is 1e-4, as there: a hundred times the reading, and a tenth of what
the nearest wrong model reads (the controls below, logits and served
log-probabilities: renormalised weights 5.8e-3 and 2.6e-3, one expert a
token 1.7e-2 and 8.3e-3, no q/k norm 0.27 and 0.11).

Near-ties: where a token's k-th and (k+1)-th router probabilities are closer
than float32 rounding of the router's input, the program and the reference
may pick different experts, and the outputs then differ by a whole expert's
contribution, not by rounding. The weights' seed and the token ids are
chosen once so that no (token, layer) of the test sequences comes closer
than `MARGIN`, and `test_router_margin` asserts it: a change that moves the
weights fails there, and the tolerance stays what it is.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine import InferenceConfig, InferenceEngine
from deepspeed_tpu.models import create_model
from deepspeed_tpu.models.transformer import forward
from deepspeed_tpu.observability import recorded_spans, reset_session
from deepspeed_tpu.parallel.moe import moe_mlp
from deepspeed_tpu.serving import ServingConfig, ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TOL = 1e-4
MARGIN = 1e-3       # least gap between the logs of the k-th and the
#                     (k+1)-th router probability: a thousand roundings
SEED = 11
TOP_K = 3
REF_ARGS = dict(num_heads=4, num_experts_per_tok=TOP_K, rope_theta=10000.0,
                rms_norm_eps=1e-5, norm_topk_prob=False)
# the wrong models that the tolerance has to tell from the right one
CONTROLS = {"renormalised": dict(norm_topk_prob=True),
            "top-1": dict(num_experts_per_tok=1),
            "no-qk-norm": dict(qk_norm=False)}


def _reference():
    path = os.path.join(REPO, "benchmarks", "references", "olmoe.py")
    spec = importlib.util.spec_from_file_location("reference_olmoe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


REF = _reference()


@pytest.fixture(scope="module")
def tiny():
    model = create_model("tiny-olmoe")
    assert model.config.moe_top_k == TOP_K and model.config.qk_norm
    assert not model.config.moe_norm_topk_prob
    params = model.init(jax.random.PRNGKey(SEED))
    # norm scales other than 1, so that leaving the q/k norm out shows; a
    # router 12 times wider than the init's, which chooses clearly (the
    # margin) without giving all the weight to one expert (renormalising
    # the three must still change the result)
    ks = jax.random.split(jax.random.PRNGKey(SEED + 1), 3)
    a = params["layers"]["attn"]
    a["q_norm"] = 1.0 + 0.3 * jax.random.normal(ks[0], a["q_norm"].shape)
    a["k_norm"] = 1.0 + 0.3 * jax.random.normal(ks[1], a["k_norm"].shape)
    params["layers"]["router"] = 0.25 * jax.random.normal(
        ks[2], params["layers"]["router"].shape)
    ids = jax.random.randint(jax.random.PRNGKey(SEED + 2), (2, 48), 0,
                             model.config.vocab_size)
    return model, params, ids


def _ref_logits(params, ids, **changed):
    return np.asarray(REF.logits(params, jnp.asarray(ids),
                                 **dict(REF_ARGS, **changed)))


def test_router_margin(tiny):
    """The seeded weights keep every token's 3rd and 4th router probability
    apart in every layer, so no test below hangs on a tie."""
    _, params, ids = tiny
    logp = np.sort(np.log(np.asarray(REF.router_probabilities(
        params, ids, **REF_ARGS))), axis=-1)
    gap = logp[..., -TOP_K] - logp[..., -TOP_K - 1]
    assert gap.min() > MARGIN, gap.min()


def test_training_forward_matches_reference(tiny):
    """(a) the training path (no cache, the capacity plan with no drops)."""
    model, params, ids = tiny
    nodrop = dataclasses.replace(model.config, moe_drop_tokens=False)
    got, _, aux = forward(params, ids, nodrop)
    assert np.abs(np.asarray(got) - _ref_logits(params, ids)).max() < TOL
    assert float(aux) > 0


@pytest.fixture(scope="module")
def served(tiny):
    """One sequence through `ServingEngine`: prefill in chunks of 16, then
    greedy decode steps, every step's logits kept."""
    model, params, ids = tiny
    engine = InferenceEngine(
        model, InferenceConfig(dtype=jnp.float32, seed=SEED,
                               max_out_tokens=128), params=params)
    serving = ServingEngine(engine, ServingConfig(
        num_blocks=40, block_size=8, max_seqs=4, prefill_chunk=16,
        max_model_len=128, prefix_cache=False))
    yield serving
    serving.close()


def test_paged_prefill_and_decode_match_reference(tiny, served):
    """(b) `score_logprobs` (chunked prefill through the paged cache) and
    streamed greedy steps against the reference's full forward pass."""
    model, params, ids = tiny
    seq = np.asarray(ids[0])
    want = np.asarray(REF.next_token_logprobs(params, seq[None],
                                              **REF_ARGS))[0]
    got = served.score_logprobs(seq)
    assert np.abs(got - want).max() < TOL

    prompt, steps = seq[:21], 12     # 2 chunks (16 + 5), then decode
    handle = served.submit(prompt, max_new_tokens=steps)
    served.run()
    out = np.asarray(handle.result())
    full = np.concatenate([prompt, out])
    # greedy: each streamed token is the argmax of the reference's logits at
    # the position before it, and the margin there is no tie
    logits = _ref_logits(params, full[None])[0]
    for i, tok in enumerate(out):
        row = logits[len(prompt) - 1 + i]
        assert tok == row.argmax()
    # and the decode steps' own log-probabilities, scored through the cache
    got = served.score_logprobs(full)
    want = np.asarray(REF.next_token_logprobs(params, full[None],
                                              **REF_ARGS))[0]
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_controls_fail_the_tolerance(tiny, served, control):
    """(c) a reference that renormalises the weights, takes one expert or
    leaves out the q/k norm is further from the program than the tolerance,
    on the training path and through the paged cache."""
    model, params, ids = tiny
    got, _, _ = forward(params, ids, model.config)
    wrong = _ref_logits(params, ids, **CONTROLS[control])
    assert np.abs(np.asarray(got) - wrong).max() > 10 * TOL
    seq = np.asarray(ids[1])
    wrong = np.asarray(REF.next_token_logprobs(
        params, seq[None], **dict(REF_ARGS, **CONTROLS[control])))[0]
    assert np.abs(served.score_logprobs(seq) - wrong).max() > 10 * TOL


def _bank(E=8, H=32, F=48, T=40, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (2, T // 2, H), jnp.float32)
    router = jax.random.normal(ks[1], (H, E), jnp.float32)
    experts = {"w_up": jax.random.normal(ks[2], (E, H, F)) * 0.1,
               "w_down": jax.random.normal(ks[3], (E, F, H)) * 0.1,
               "w_gate": jax.random.normal(ks[4], (E, H, F)) * 0.1}
    return x, router, experts


@pytest.mark.parametrize("act", ["gelu", "swiglu"])
@pytest.mark.parametrize("top_k", [1, 2, 3])
def test_grouped_path_equals_capacity_path(top_k, act):
    """(d) at inference the grouped path gives what the (E, C, H) einsum
    path gives when it drops nothing, to float32 rounding; k = 1 and 2 keep
    the GShard weights (top-2 renormalised)."""
    x, router, experts = _bank()
    dense, _ = moe_mlp(x, router, experts, act, top_k=top_k,
                       drop_tokens=False, dispatch_impl="einsum")
    grouped, aux, counts = moe_mlp(x, router, experts, act, top_k=top_k,
                                   infer=True, with_counts=True)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(dense),
                               rtol=1e-5, atol=1e-6)
    assert float(aux) == 0.0
    assert int(counts[0]) == x.shape[0] * x.shape[1] * top_k


def test_padding_rows_are_not_routed():
    """(e) rows that the mask takes out (decode rows with no request, a
    chunk's padding) change neither the real rows' outputs nor the counts,
    whatever they hold; and they come back zero."""
    x, router, experts = _bank()
    B, S, _ = x.shape
    mask = jnp.arange(S)[None, :] < jnp.array([[S], [7]])
    alone, _, counts_alone = moe_mlp(x[:, :], router, experts, "swiglu",
                                     top_k=3, infer=True, row_mask=mask,
                                     with_counts=True)
    junk = jnp.where(mask[..., None], x, 1e4 * jnp.ones_like(x))
    padded, _, counts_padded = moe_mlp(junk, router, experts, "swiglu",
                                       top_k=3, infer=True, row_mask=mask,
                                       with_counts=True)
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(padded))
    np.testing.assert_array_equal(np.asarray(counts_alone),
                                  np.asarray(counts_padded))
    assert int(counts_alone[0]) == 3 * (S + 7)
    assert not np.asarray(padded)[1, 7:].any()
    # the real rows read what they read with no padding at all
    whole, _ = moe_mlp(x, router, experts, "swiglu", top_k=3, infer=True)
    np.testing.assert_allclose(np.asarray(alone)[0], np.asarray(whole)[0],
                               rtol=1e-6, atol=1e-7)


def test_served_counts_are_of_real_rows(tiny, served, tmp_path):
    """(e) through the engine, under a profiler capture as the benchmark's
    `--trace 1` opens it: the spans carry the counts of the rows that hold a
    request, 3 assignments a token and layer."""
    model, params, ids = tiny
    cfg = model.config
    reset_session()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        handle = served.submit(np.asarray(ids[0])[:21], max_new_tokens=4)
        served.run()
        handle.result()
    finally:
        jax.profiler.stop_trace()
    recorded = recorded_spans()
    reset_session()
    chunks = [s["attrs"] for s in recorded
              if s["name"] == "serving/prefill_chunk"]
    steps = [s["attrs"] for s in recorded if s["name"] == "serving/decode"
             and s["attrs"].get("rows")]
    assert [c["tokens"] for c in chunks] == [16, 5]
    for c in chunks:
        assert c["moe_assignments"] == TOP_K * cfg.num_layers * c["tokens"]
    assert steps
    for s in steps:   # 1 live row of 4: the 3 empty rows are not routed
        assert s["moe_assignments"] == TOP_K * cfg.num_layers * s["rows"]
        assert s["moe_experts_touched"] == TOP_K * cfg.num_layers
        assert s["moe_max_expert_rows"] == cfg.num_layers
        assert s["moe_experts_total"] == (cfg.moe_num_experts
                                          * cfg.num_layers)


@pytest.mark.parametrize("top_k", [0, 9])
def test_unsupported_top_k_raises(top_k):
    """(f) a number of experts a token that cannot be honoured is an error
    on every path, never a quiet top-1."""
    x, router, experts = _bank()
    for kw in (dict(infer=True), dict(infer=False)):
        with pytest.raises(ValueError, match="top_k"):
            moe_mlp(x, router, experts, "gelu", top_k=top_k, **kw)

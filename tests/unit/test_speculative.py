"""Speculative decoding + parallel-sampling fork tests (ISSUE-10).

Coverage map:
  * n-gram drafter host semantics (longest-first lookup, most recent
    occurrence, cap/no-match behavior);
  * greedy speculative serving bit-identical to offline ``generate()``
    across ragged batches AND under mid-stream preemption/recompute;
  * the RNG satellite: spec-on and spec-off streams bit-identical at
    temperature (token keys derive from the emitted-token index, not the
    iteration count);
  * rejection-sampling statistical test: verify-sampled tokens follow the
    target softmax (deterministic seeds — no flake);
  * fork-then-diverge COW: shared-block refcounts, sibling isolation
    (bit-equality with solo submits), mid-stream fork inheritance;
  * scheduler integration: rollback block accounting, pool-pressure
    auto-disable, EOS/budget mid-verify;
  * draft-model drafter: draft==target accepts everything under greedy,
    state released, same bit-identity;
  * jit stability: ONE verify program across occupancy/acceptance mixes;
  * the acceptance smoke: 16 concurrent requests with a repetitive-text
    workload, --spec ngram bit-identical to the plain path, one verify
    compile, emitted-tokens-per-dispatch > 1.5.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.config.base import ConfigError
from deepspeed_tpu.config.config import (ObservabilityConfig, ServingConfig,
                                         SpeculativeConfig)
from deepspeed_tpu.inference import init_inference
from deepspeed_tpu.observability import (configure_observability,
                                         get_registry, reset_session)
from deepspeed_tpu.serving import ServingEngine
from deepspeed_tpu.serving.speculative import (Drafter, NgramDrafter,
                                               request_stream)


@pytest.fixture(scope="module")
def tiny_engine():
    return init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)


@pytest.fixture(scope="module")
def draft_tiny_engine():
    # a second engine over the SAME preset: the ideal drafter (acceptance
    # 1.0 under greedy) and a vocab-compatible stand-in for a small model
    return init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)


@pytest.fixture
def obs_session(tmp_path):
    reset_session()
    sess = configure_observability(ObservabilityConfig(
        enabled=True, output_dir=str(tmp_path / "obs"),
        flight_recorder=False))
    yield sess
    reset_session()


def serving(tiny_engine, spec="off", draft_engine=None, **cfg):
    defaults = dict(block_size=16, num_blocks=64, max_seqs=4,
                    max_model_len=128, prefill_chunk=16, max_queue=64)
    defaults.update(cfg)
    speculative = (spec if isinstance(spec, dict)
                   else {"mode": spec, "num_draft_tokens": 4})
    return ServingEngine(tiny_engine,
                         ServingConfig(speculative=speculative, **defaults),
                         draft_engine=draft_engine)


def mixed_prompts(n=8, repetitive=4, seed=0):
    """Ragged prompt mix: ``repetitive`` tiled-pattern prompts (the
    speculation workload) + random-token prompts."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(repetitive):
        pat = rng.randint(0, 250, (rng.randint(4, 8),))
        out.append(np.tile(pat, 6)[: rng.randint(18, 40)])
    for _ in range(n - repetitive):
        out.append(rng.randint(0, 250, (rng.randint(5, 30),)))
    return out


# ---------------------------------------------------------------------------
# n-gram drafter (host-side)
# ---------------------------------------------------------------------------


class TestNgramDrafter:
    def _prop(self, ctx, k=4, **kw):
        return NgramDrafter(**kw)._lookup(np.asarray(ctx, np.int32), k)

    def test_repetitive_context_proposes_continuation(self):
        #        0  1  2  3  4  5  6  7  8
        ctx = [7, 8, 9, 1, 7, 8, 9, 1, 7]   # suffix [1, 7] seen at 3..4
        assert self._prop(ctx, k=3).tolist() == [8, 9, 1]

    def test_longest_ngram_wins(self):
        # suffix tried at n=3 first: [5, 6, 7] matches once; a 1-gram
        # match elsewhere must not shadow it
        ctx = [5, 6, 7, 0, 7, 2, 5, 6, 7]
        assert self._prop(ctx, k=2, ngram_max=3).tolist() == [0, 7]

    def test_most_recent_occurrence_preferred(self):
        ctx = [3, 1, 3, 2, 3]          # 1-gram "3" at 0 and 2: use 2
        assert self._prop(ctx, k=1, ngram_max=1).tolist() == [2]

    def test_no_match_proposes_nothing(self):
        assert self._prop([1, 2, 3, 4, 5], k=4).size == 0

    def test_cap_respected_and_tail_truncates(self):
        ctx = [4, 4, 4, 4]
        assert self._prop(ctx, k=2, ngram_max=1).size <= 2

    def test_propose_uses_full_stream(self):
        from deepspeed_tpu.serving.scheduler import Request

        r = Request(rid=0, prompt=np.array([1, 2, 3]), max_new_tokens=8)
        r.generated = [4, 5]
        assert request_stream(r).tolist() == [1, 2, 3, 4, 5]
        props = NgramDrafter().propose([r], [3])
        assert len(props) == 1

    def test_bad_ngram_bounds_rejected(self):
        with pytest.raises(ValueError):
            NgramDrafter(ngram_max=2, ngram_min=3)


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------


class TestSpeculativeConfig:
    def test_bad_mode_rejected(self):
        with pytest.raises(ConfigError):
            SpeculativeConfig(mode="beam").validate()

    def test_k_bounds(self):
        with pytest.raises(ConfigError):
            SpeculativeConfig(mode="ngram", num_draft_tokens=0).validate()

    def test_nested_dict_coerces(self):
        cfg = ServingConfig(speculative={"mode": "ngram",
                                         "num_draft_tokens": 2})
        cfg.validate()
        assert isinstance(cfg.speculative, SpeculativeConfig)
        assert cfg.speculative.num_draft_tokens == 2

    def test_k_must_fit_model_len(self):
        with pytest.raises(ConfigError):
            ServingConfig(max_model_len=16, block_size=16, prefill_chunk=16,
                          speculative={"mode": "ngram",
                                       "num_draft_tokens": 16}).validate()

    def test_draft_needs_draft_engine(self, tiny_engine):
        with pytest.raises(ValueError):
            serving(tiny_engine, spec="draft")


# ---------------------------------------------------------------------------
# bit-identity: greedy speculation == generate(), spec-on == spec-off
# ---------------------------------------------------------------------------


class TestSpecBitIdentity:
    # tier-1 budget: the 16-request acceptance smoke (below) covers greedy
    # ngram bit-identity at larger scale; this staggered-admission variant
    # rides the slow suite
    @pytest.mark.slow
    def test_greedy_ngram_matches_generate_ragged(self, tiny_engine):
        prompts = mixed_prompts(8, repetitive=4)
        want = [np.asarray(tiny_engine.generate(p[None],
                                                max_new_tokens=8))[0]
                for p in prompts]
        srv = serving(tiny_engine, spec="ngram")
        handles = []
        for i, p in enumerate(prompts):      # staggered admissions
            handles.append(srv.submit(p, max_new_tokens=8))
            if i % 3 == 2:
                srv.step()
        srv.run()
        for i, h in enumerate(handles):
            np.testing.assert_array_equal(h.result(), want[i],
                                          err_msg=f"request {i}")
        assert srv._spec_dispatches > 0

    def test_greedy_spec_survives_preemption_recompute(self, tiny_engine):
        """A pool far too small for the load forces mid-stream eviction +
        recompute WITH speculation on — outputs must stay bit-identical
        (the stored pending token + positional rollback contract)."""
        prompts = mixed_prompts(6, repetitive=3, seed=3)
        want = [np.asarray(tiny_engine.generate(p[None],
                                                max_new_tokens=10))[0]
                for p in prompts]
        srv = serving(tiny_engine, spec="ngram", num_blocks=7, max_seqs=3,
                      max_model_len=64, prefix_cache=False)
        handles = [srv.submit(p, max_new_tokens=10) for p in prompts]
        srv.run()
        assert srv.sched.preemption_count > 0, \
            "pool was meant to be too small — no preemption exercised"
        for i, h in enumerate(handles):
            np.testing.assert_array_equal(h.result(), want[i],
                                          err_msg=f"request {i}")

    def test_temperature_stream_bit_stable_spec_on_off(self, tiny_engine):
        """The RNG satellite: same (engine seed, request seed) through the
        spec-off and spec-on paths produces the SAME sampled stream —
        token keys derive from the emitted-token index, so accepting K at
        a time cannot shift anyone's draws."""
        prompts = mixed_prompts(6, repetitive=4, seed=7)
        outs = {}
        for mode in ("off", "ngram"):
            srv = serving(tiny_engine, spec=mode)
            hs = [srv.submit(p, max_new_tokens=8, temperature=0.8,
                             top_k=20, seed=100 + i)
                  for i, p in enumerate(prompts)]
            srv.run()
            outs[mode] = [h.result() for h in hs]
            if mode == "ngram":
                assert srv._spec_accepted > 0, \
                    "no draft ever accepted — the bit-stability claim " \
                    "was not exercised at temperature"
        for i, (a, b) in enumerate(zip(outs["off"], outs["ngram"])):
            np.testing.assert_array_equal(a, b, err_msg=f"request {i}")


# ---------------------------------------------------------------------------
# rejection sampling: spec-sampled tokens follow the target softmax
# ---------------------------------------------------------------------------


class TestRejectionStatistics:
    @pytest.mark.slow   # 512 verify dispatches — statistical, not a gate
    def test_verify_samples_match_target_softmax(self, tiny_engine):
        """512 verify draws (8 rows × 64 dispatches, distinct seeds) at one
        fixed context, temperature=1/top_k=5: the empirical distribution
        must match softmax(top-5 logits). Keys are deterministic — this
        test cannot flake."""
        import jax

        from deepspeed_tpu.models.transformer import forward as fwd
        from deepspeed_tpu.serving import paged_kv

        eng = tiny_engine
        cfg = eng.model.config
        BS, NB, R = 16, 16, 8
        arena = paged_kv.init_paged_cache(cfg, NB + 1, BS, jnp.float32)
        alloc = paged_kv.BlockAllocator(NB)
        prompt = (np.arange(12) * 3) % 250
        n = prompt.size
        MAXB = 64 // BS
        blocks = alloc.alloc(2)
        bt1 = np.zeros((1, MAXB), np.int32)
        bt1[0, :2] = blocks
        prefill = paged_kv.build_prefill_program(cfg, 16)
        chunk = np.zeros((1, 16), np.int32)
        chunk[0, :n] = prompt
        key = jax.random.PRNGKey(0)
        z1, zi, o1 = (np.zeros((1,), np.float32), np.zeros((1,), np.int32),
                      np.ones((1,), np.float32))
        tok, _, arena = prefill(
            eng.params, arena,
            paged_kv.pack_chunk(bt1, chunk, 0, n, z1, zi, o1, zi), key)
        pending = int(np.asarray(tok)[0])

        # target distribution after the pending token: plain (cache-free)
        # forward over prompt+pending, last position, temp 1 / top-5
        logits = np.asarray(fwd(
            eng.params, np.asarray([list(prompt) + [pending]], np.int32),
            cfg)[0][0, -1], np.float64)
        top5 = np.argsort(logits)[::-1][:5]
        z = logits[top5] - logits[top5].max()
        probs = np.exp(z) / np.exp(z).sum()

        verify = paged_kv.build_verify_program(cfg, 2)
        btR = np.tile(bt1, (R, 1))
        lengths = np.full((R,), n, np.int32)
        tokens = np.zeros((R, 2), np.int32)
        tokens[:, 0] = pending        # every row: plain decode semantics
        n_valid = np.ones((R,), np.int32)
        temps = np.ones((R,), np.float32)
        topks = np.full((R,), 5, np.int32)
        topps = np.ones((R,), np.float32)
        steps = np.zeros((R,), np.int32)
        counts = {int(t): 0 for t in top5}
        draws = 0
        for it in range(64):
            seeds = np.arange(it * R, (it + 1) * R, dtype=np.int32)
            # base-key reuse is the verify contract: randomness comes from
            # fold_in(seeds, token_index), and seeds change per iteration
            sampled, arena = verify(  # tpulint: disable=key-reuse
                eng.params, arena,
                paged_kv.pack_verify_rows(btR, lengths, tokens, n_valid,
                                          temps, topks, topps, seeds, steps),
                key)
            for t in np.asarray(sampled)[:, 0]:
                counts[int(t)] = counts.get(int(t), 0) + 1
                draws += 1
        assert draws == 512
        for t, p_want in zip(top5, probs):
            p_got = counts[int(t)] / draws
            assert abs(p_got - p_want) < 0.06, \
                (f"token {t}: empirical {p_got:.3f} vs softmax "
                 f"{p_want:.3f} — spec sampling is off-distribution")
        # nothing outside the top-5 support may ever be drawn
        assert sum(counts[int(t)] for t in top5) == draws


# ---------------------------------------------------------------------------
# parallel-sampling fork (COW)
# ---------------------------------------------------------------------------


class TestForkCOW:
    def test_submit_n_greedy_identical_and_shared(self, tiny_engine):
        srv = serving(tiny_engine)
        p = mixed_prompts(1, repetitive=0, seed=11)[0]
        want = np.asarray(tiny_engine.generate(p[None],
                                               max_new_tokens=6))[0]
        handles = srv.submit(p, max_new_tokens=6, n=3)
        assert len(handles) == 3
        # step until the fork lands, then assert the sharing is real
        for _ in range(200):
            srv.step()
            if srv._forks == 2:
                break
        assert srv._forks == 2
        parent = handles[0]._req
        shared = [b for b in parent.blocks if srv.alloc.refcount(b) >= 3]
        assert shared, "fork did not share the parent's blocks"
        srv.run()
        for h in handles:   # greedy: every sibling == the parent == offline
            np.testing.assert_array_equal(h.result(), want)

    @pytest.mark.slow   # tier-1 keeps the greedy-vs-oracle variant above
    def test_fork_siblings_bit_identical_to_solo_seeds(self, tiny_engine):
        """Sibling i (seed s+i) must produce EXACTLY what a separately
        submitted request with seed s+i produces — shared blocks, COW and
        scheduling are invisible to the sampled stream."""
        srv = serving(tiny_engine)
        p = mixed_prompts(1, repetitive=0, seed=12)[0]
        handles = srv.submit(p, max_new_tokens=6, temperature=0.9,
                             top_k=30, seed=40, n=3)
        srv.run()
        outs = [h.result() for h in handles]
        assert srv._cow_copies > 0, "no divergent write ever went COW"
        solo = serving(tiny_engine)
        for i, o in enumerate(outs):
            h = solo.submit(p, max_new_tokens=6, temperature=0.9,
                            top_k=30, seed=40 + i)
            solo.run()
            np.testing.assert_array_equal(o, h.result(),
                                          err_msg=f"sibling {i}")
        # at temperature the samples should actually be distinct
        assert len({tuple(o.tolist()) for o in outs}) > 1

    def test_siblings_never_observe_each_others_writes(self, tiny_engine):
        """Greedy + n=4 over a SHARED prompt: if any sibling's write leaked
        into another's blocks, the deterministic outputs would diverge
        from the offline oracle."""
        srv = serving(tiny_engine, max_seqs=6)
        p = mixed_prompts(1, repetitive=1, seed=13)[0]
        want = np.asarray(tiny_engine.generate(p[None],
                                               max_new_tokens=8))[0]
        handles = srv.submit(p, max_new_tokens=8, n=4)
        srv.run()
        for i, h in enumerate(handles):
            np.testing.assert_array_equal(h.result(), want,
                                          err_msg=f"sibling {i}")

    def test_midstream_fork_inherits_and_diverges(self, tiny_engine):
        srv = serving(tiny_engine)
        p = mixed_prompts(1, repetitive=0, seed=14)[0]
        parent = srv.submit(p, max_new_tokens=8, temperature=0.7, seed=3)
        got = []
        for t in parent.stream():
            got.append(t)
            if len(got) == 3:
                sibs = parent.fork(2)
                break
        srv.run()
        pout = parent.result()
        for i, sh in enumerate(sibs):
            sout = sh.result()
            assert sout[:3].tolist() == pout[:3].tolist(), \
                f"sibling {i} lost the inherited tokens"
            assert len(sout) == 8
        # divergence is expected at temperature with distinct seeds
        assert any(sh.result().tolist() != pout.tolist() for sh in sibs)

    def test_fork_requires_decoding_parent(self, tiny_engine):
        srv = serving(tiny_engine)
        h = srv.submit(mixed_prompts(1)[0], max_new_tokens=4)
        with pytest.raises(ValueError):
            h.fork(2)       # still queued
        srv.run()
        with pytest.raises(ValueError):
            h.fork(2)       # already finished

    def test_fork_rejects_short_seeds_list_before_any_sibling(
            self, tiny_engine):
        srv = serving(tiny_engine)
        h = srv.submit(mixed_prompts(1, seed=16)[0], max_new_tokens=8,
                       temperature=0.5, seed=3)
        while not h._req.generated:
            srv.step()
        before = srv.in_flight()
        with pytest.raises(ValueError, match="seeds"):
            h.fork(3, seeds=[7])   # must fail BEFORE creating sibling 0
        assert srv.in_flight() == before
        assert srv._forks == 0
        srv.run()
        assert len(h.result()) == 8

    def test_fork_only_report_has_no_speculation_line(self, tiny_engine,
                                                      obs_session, tmp_path):
        """Parallel sampling without speculation is COW sharing — forks
        belong on the sharing line, not a phantom speculation line."""
        from deepspeed_tpu.observability.report import report

        # the registry is a process singleton and this test renders a
        # report from its ABSOLUTE contents — spec/fork counters left by
        # earlier test modules (rlhf rollouts speculate) would paint a
        # phantom speculation line. Render from a pristine registry.
        get_registry().reset()
        srv = serving(tiny_engine)   # spec off
        handles = srv.submit(mixed_prompts(1, seed=17)[0],
                             max_new_tokens=4, n=2)
        srv.run()
        [h.result() for h in handles]
        srv.close()
        path = str(tmp_path / "metrics.jsonl")
        get_registry().dump_jsonl(path)
        out = report([path])
        assert "speculation:" not in out
        assert "forks=1" in out

    def test_cancel_parent_before_fork_cancels_siblings(self, tiny_engine):
        from deepspeed_tpu.serving import RequestCancelled

        srv = serving(tiny_engine)
        handles = srv.submit(mixed_prompts(1)[0], max_new_tokens=4, n=3)
        assert handles[0].cancel()
        for h in handles:
            assert h.done
            with pytest.raises(RequestCancelled):
                h.result()
        assert srv.in_flight() == 0

    def test_no_block_leak_after_forked_run(self, tiny_engine):
        srv = serving(tiny_engine, prefix_cache=False)
        handles = srv.submit(mixed_prompts(1, seed=15)[0],
                             max_new_tokens=6, temperature=0.5, n=3)
        srv.run()
        [h.result() for h in handles]
        assert srv.alloc.blocks_in_use == 0
        assert srv.alloc.blocks_free == srv.alloc.capacity

    def test_pending_forks_hold_queue_capacity(self, tiny_engine):
        from deepspeed_tpu.serving import QueueFull

        srv = serving(tiny_engine, max_queue=4)
        p = mixed_prompts(1)[0]
        handles = srv.submit(p, max_new_tokens=4, n=3)
        # 1 queued parent + 2 pending siblings = 4 - 1 slots taken: one
        # more fits, the next must shed — pending siblings are in flight
        assert srv.in_flight() == 3
        h4 = srv.submit(p, max_new_tokens=4)
        with pytest.raises(QueueFull):
            srv.submit(p, max_new_tokens=4)
        with pytest.raises(QueueFull):
            srv.submit(p, max_new_tokens=4, n=1)
        srv.run()
        [h.result() for h in handles + [h4]]

    def test_forked_siblings_report_ttft(self, tiny_engine):
        srv = serving(tiny_engine)
        handles = srv.submit(mixed_prompts(1, seed=21)[0],
                             max_new_tokens=5, temperature=0.7, n=3)
        srv.run()
        for h in handles:
            h.result()
            assert h._req.first_token_s is not None
            assert h._req.ttft_s is not None and h._req.ttft_s >= 0
        # the sibling's TTFT clock starts at the client's submit: it
        # cannot beat the parent, whose prefill it waited through
        parent = handles[0]._req
        for h in handles[1:]:
            assert h._req.ttft_s >= parent.ttft_s

    def test_cancel_counters_balance_with_forks(self, tiny_engine,
                                                obs_session):
        srv = serving(tiny_engine)
        p = mixed_prompts(1)[0]
        # parent cancel cascades to 2 pending siblings: 3 cancellations
        handles = srv.submit(p, max_new_tokens=4, n=3)
        assert handles[0].cancel()
        # a pre-fork sibling cancelled directly also counts
        h2 = srv.submit(p, max_new_tokens=4, n=2)
        assert h2[1].cancel()
        srv.run()
        h2[0].result()
        assert srv.sched.cancelled_count == 4
        c = get_registry().counter("serving/requests_cancelled")
        assert c is not None and c.value() == 4
        sub = get_registry().counter(
            "serving/requests_submitted").value(tenant="default")
        done = get_registry().counter(
            "serving/requests_completed").value(tenant="default")
        assert sub == done + c.value()   # the ledger balances


# ---------------------------------------------------------------------------
# scheduler integration: rollback, pressure, EOS/budget
# ---------------------------------------------------------------------------


class _WrongDrafter(Drafter):
    """Adversarial drafter: always proposes an off-by-one token — every
    draft must be rejected, every verify must still emit exactly the
    non-speculative token."""

    name = "wrong"

    def propose(self, reqs, caps):
        return [np.full((k,), int(request_stream(r)[-1] + 1) % 7, np.int32)
                if k > 0 else np.zeros((0,), np.int32)
                for r, k in zip(reqs, caps)]


class TestSpecScheduling:
    def test_always_rejected_drafter_still_lossless(self, tiny_engine):
        prompts = mixed_prompts(4, repetitive=2, seed=21)
        want = [np.asarray(tiny_engine.generate(p[None],
                                                max_new_tokens=6))[0]
                for p in prompts]
        srv = serving(tiny_engine, spec="ngram")
        srv._drafter = _WrongDrafter()
        srv.sched.on_release = srv._drafter.release
        handles = [srv.submit(p, max_new_tokens=6) for p in prompts]
        srv.run()
        assert srv._spec_proposed > 0 and srv._spec_accepted == 0
        for i, h in enumerate(handles):
            np.testing.assert_array_equal(h.result(), want[i],
                                          err_msg=f"request {i}")
        # rollback returned every speculative block: nothing may leak
        cached = (srv.prefix.cached_blocks if srv.prefix else 0)
        assert srv.alloc.blocks_in_use == cached

    def test_verify_respects_max_new_budget(self, tiny_engine):
        srv = serving(tiny_engine, spec="ngram")
        p = np.tile(np.array([5, 6, 7]), 10)     # highly repetitive
        h = srv.submit(p, max_new_tokens=3)
        srv.run()
        assert len(h.result()) == 3

    def test_eos_mid_verify_stops_exactly_like_generate(self, tiny_engine):
        p = np.tile(np.array([5, 6, 7]), 8)
        full = np.asarray(tiny_engine.generate(p[None],
                                               max_new_tokens=10))[0]
        eos = int(full[4])     # an actual mid-stream token as EOS
        want = list(full[:list(full).index(eos) + 1])
        srv = serving(tiny_engine, spec="ngram")
        h = srv.submit(p, max_new_tokens=10, eos_token_id=eos)
        srv.run()
        assert h.result().tolist() == want

    def test_pool_pressure_disables_rows_not_correctness(self, tiny_engine):
        """min_free_blocks above the whole pool: speculation globally
        backs off (caps 0 → plain decode inside the verify program) and
        output stays exact."""
        prompts = mixed_prompts(3, repetitive=2, seed=22)
        want = [np.asarray(tiny_engine.generate(p[None],
                                                max_new_tokens=6))[0]
                for p in prompts]
        srv = serving(tiny_engine,
                      spec={"mode": "ngram", "num_draft_tokens": 4,
                            "min_free_blocks": 10_000})
        handles = [srv.submit(p, max_new_tokens=6) for p in prompts]
        srv.run()
        assert srv._spec_proposed == 0      # the guard held
        assert srv._spec_dispatches > 0     # the verify still decoded
        for i, h in enumerate(handles):
            np.testing.assert_array_equal(h.result(), want[i])

    def test_truncate_blocks_rollback_accounting(self):
        from deepspeed_tpu.serving.paged_kv import BlockAllocator
        from deepspeed_tpu.serving.scheduler import Request, Scheduler

        sched = Scheduler(ServingConfig(
            block_size=4, num_blocks=16, max_seqs=2, max_model_len=32,
            prefill_chunk=4, max_queue=8))
        r = Request(rid=0, prompt=np.arange(4), max_new_tokens=8)
        r.blocks = sched.alloc.alloc(5)
        assert sched.truncate_blocks(r, 9) == 2     # 9 tokens → 3 blocks
        assert len(r.blocks) == 3
        assert sched.alloc.blocks_in_use == 3
        assert sched.truncate_blocks(r, 12) == 0    # already covered

    def test_try_extend_blocks_never_preempts(self):
        from deepspeed_tpu.serving.scheduler import Request, Scheduler

        sched = Scheduler(ServingConfig(
            block_size=4, num_blocks=8, max_seqs=2, max_model_len=32,
            prefill_chunk=4, max_queue=8))
        victim = Request(rid=0, prompt=np.arange(4), max_new_tokens=8)
        victim.blocks = sched.alloc.alloc(8)
        sched.running[0] = victim
        victim.row = 0
        victim.state = "decode"
        sched._admit_index[victim.rid] = 0
        asker = Request(rid=1, prompt=np.arange(4), max_new_tokens=8)
        assert not sched.try_extend_blocks(asker, 8)
        assert victim.state == "decode"             # nobody was evicted
        assert len(victim.blocks) == 8
        assert sched.preemption_count == 0


# ---------------------------------------------------------------------------
# draft-model drafter
# ---------------------------------------------------------------------------


class TestDraftModelDrafter:
    def test_draft_equals_target_accepts_everything(self, tiny_engine,
                                                    draft_tiny_engine):
        prompts = mixed_prompts(5, repetitive=2, seed=31)
        want = [np.asarray(tiny_engine.generate(p[None],
                                                max_new_tokens=8))[0]
                for p in prompts]
        srv = serving(tiny_engine, spec="draft",
                      draft_engine=draft_tiny_engine)
        srv._drafter.engine.params = tiny_engine.params   # identical draft
        handles = [srv.submit(p, max_new_tokens=8) for p in prompts]
        srv.run()
        for i, h in enumerate(handles):
            np.testing.assert_array_equal(h.result(), want[i],
                                          err_msg=f"request {i}")
        assert srv._spec_proposed > 0
        assert srv._spec_accepted == srv._spec_proposed, \
            "an identical draft model must be accepted in full under greedy"
        assert srv._spec_emitted / srv._spec_dispatches > 2.0

    @pytest.mark.slow   # draft-path coverage gates via accepts_everything
    def test_draft_state_and_blocks_released(self, tiny_engine,
                                             draft_tiny_engine):
        srv = serving(tiny_engine, spec="draft",
                      draft_engine=draft_tiny_engine, prefix_cache=False)
        hs = [srv.submit(p, max_new_tokens=5)
              for p in mixed_prompts(3, repetitive=1, seed=32)]
        srv.run()
        [h.result() for h in hs]
        assert srv._drafter._state == {}
        assert srv.alloc.blocks_in_use == 0

    @pytest.mark.slow   # the ngram preemption/recompute variant gates
    def test_draft_survives_preemption(self, tiny_engine,
                                       draft_tiny_engine):
        """Draft KV shares the pool: under pressure the drafter backs off
        and preempted requests recompute — output must stay exact and the
        pool must balance afterwards."""
        prompts = mixed_prompts(4, repetitive=2, seed=33)
        want = [np.asarray(tiny_engine.generate(p[None],
                                                max_new_tokens=8))[0]
                for p in prompts]
        srv = serving(tiny_engine, spec="draft",
                      draft_engine=draft_tiny_engine, num_blocks=12,
                      max_seqs=2, max_model_len=64, prefix_cache=False)
        srv._drafter.engine.params = tiny_engine.params
        handles = [srv.submit(p, max_new_tokens=8) for p in prompts]
        srv.run()
        for i, h in enumerate(handles):
            np.testing.assert_array_equal(h.result(), want[i],
                                          err_msg=f"request {i}")
        assert srv.alloc.blocks_in_use == 0

    def test_vocab_mismatch_rejected(self, tiny_engine):
        from deepspeed_tpu.serving.speculative import make_drafter

        class FakeEngine:
            class model:
                class config:
                    vocab_size = 17
            class config:
                dtype = jnp.float32

        cfg = ServingConfig(speculative={"mode": "draft"})
        cfg.validate()
        with pytest.raises(ValueError):
            make_drafter(cfg, tiny_engine, None, 8,
                         draft_engine=FakeEngine())


# ---------------------------------------------------------------------------
# jit stability + the acceptance smoke
# ---------------------------------------------------------------------------


class TestSpecJit:
    def test_one_verify_program_across_acceptance_mixes(self, tiny_engine,
                                                        obs_session):
        """Occupancy, proposal counts and acceptance mixes are DATA: the
        verify program must compile exactly once (recompile-watchdog
        counter), exactly like the plain decode program."""
        compiles = get_registry().counter("xla/compiles")
        before = compiles.value(where="serving/verify")
        srv = serving(tiny_engine, spec="ngram")
        prompts = mixed_prompts(7, repetitive=4, seed=41)
        handles = []
        for i, p in enumerate(prompts):
            handles.append(srv.submit(
                p, max_new_tokens=5, temperature=0.0 if i % 2 else 0.5,
                top_k=0 if i % 3 else 7, seed=i))
            srv.step()
        srv.run()
        assert compiles.value(where="serving/verify") - before == 1
        steady = get_registry().counter("xla/steady_state_recompiles")
        assert steady.value(where="serving/verify") == 0


class TestSpecSmoke:
    def test_sixteen_request_spec_acceptance(self, tiny_engine, obs_session,
                                             tmp_path):
        """The ISSUE-10 acceptance smoke: the 16-request serving smoke
        re-run with --spec ngram on a repetitive-text workload — greedy
        outputs bit-identical to the non-speculative path (== offline
        generate()), ONE verify program across every per-row acceptance
        mix, emitted-tokens-per-target-dispatch > 1.5, and the speculation
        metrics render in the report CLI."""
        compiles = get_registry().counter("xla/compiles")
        before = compiles.value(where="serving/verify")
        srv = serving(tiny_engine, spec="ngram", block_size=16,
                      num_blocks=64, max_seqs=8, max_model_len=128,
                      prefill_chunk=16, max_queue=64)
        prompts = mixed_prompts(16, repetitive=16, seed=5)
        want = [np.asarray(tiny_engine.generate(p[None],
                                                max_new_tokens=8))[0]
                for p in prompts]
        handles = []
        for i, p in enumerate(prompts):          # staggered arrivals
            handles.append(srv.submit(p, max_new_tokens=8,
                                      tenant=f"t{i % 3}"))
            if i % 4 == 3:
                srv.step()
        srv.run()

        # 1) bit-identical to the non-speculative path (== generate())
        for i, (p, h) in enumerate(zip(prompts, handles)):
            np.testing.assert_array_equal(
                h.result(), want[i], err_msg=f"request {i} diverged")

        # 2) ONE verify program across varying per-row acceptance counts
        assert compiles.value(where="serving/verify") - before == 1

        # 3) the speculative win on repetitive text
        epd = srv._spec_emitted / srv._spec_dispatches
        assert epd > 1.5, f"emitted/dispatch {epd:.2f} <= 1.5"
        assert srv._spec_accepted > 0

        # 4) metrics flow and render
        reg = get_registry()
        assert reg.gauge("serving/spec_emitted_per_dispatch").value() > 1.5
        srv.close()
        from deepspeed_tpu.observability.report import report

        path = str(tmp_path / "metrics.jsonl")
        reg.dump_jsonl(path)
        out = report([path])
        assert "speculation:" in out
        assert "emitted_per_dispatch" in out


# ---------------------------------------------------------------------------
# audit integration
# ---------------------------------------------------------------------------


class TestSpecAudit:
    # tier-1's tpucost repo gate already traces all three spec entries
    # against the committed baseline; the direct audit run rides slow
    @pytest.mark.slow
    def test_verify_and_draft_entries_registered_clean(self, tiny_engine,
                                                       draft_tiny_engine):
        from tools.tpuaudit.core import run_audit
        from tools.tpuaudit.registry import get_entry_points

        srv = serving(tiny_engine, spec="draft",
                      draft_engine=draft_tiny_engine)
        names = ["serving/verify", "serving/draft_decode",
                 "serving/draft_prefill"]
        eps = get_entry_points(names)
        assert [ep.name for ep in eps] == names
        assert all(ep.donate_argnums == (1,) for ep in eps)  # arenas
        findings = run_audit(eps, publish_metrics=False)
        assert findings == [], [f"{f.entry}:{f.check}" for f in findings]
        del srv

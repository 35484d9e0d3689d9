"""Serving-layer tests — continuous batching over the paged KV arena.

Coverage map (the ISSUE-6 checklist):
  * block allocator alloc/free/eviction invariants (no double free,
    occupancy accounting exact);
  * scheduler admission / multi-tenant fairness / deadline ordering with an
    injectable clock (sleep-free, per the hangdetect.py convention);
  * chunked-prefill equivalence — chunked prefill produces a bit-identical
    first token (and continuation) vs whole-prompt prefill on CPU;
  * streaming / cancellation lifecycle + backpressure;
  * jit stability — the decode program compiles exactly once across
    varying batch occupancy (recompile-watchdog counter);
  * the acceptance smoke: 16 concurrent requests, staggered arrivals and
    mixed prompt lengths, every output bit-identical to a sequential
    ``generate()``, decode compiled once, and peak arena blocks strictly
    under the sum of per-request T_max rows (paging actually shares HBM).
"""

import time

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.config.base import ConfigError
from deepspeed_tpu.config.config import ObservabilityConfig, ServingConfig
from deepspeed_tpu.inference import init_inference
from deepspeed_tpu.observability import (configure_observability, get_registry,
                                         get_session, reset_session)
from deepspeed_tpu.serving import (BlockAllocator, BlockAllocatorError,
                                   QueueFull, Request, RequestCancelled,
                                   Scheduler, ServingEngine)
from deepspeed_tpu.serving.scheduler import DECODE, PREFILL, QUEUED


class FakeClock:
    """Injectable scheduler clock (sleep-free tests)."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(scope="module")
def tiny_engine():
    return init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)


def serving(tiny_engine, clock=None, mixed=False, **cfg):
    """A serving engine over ``tiny_engine``. ``mixed=False``: held to the
    chunk and decode programs, as an engine is whose layers do not mix
    (``paged_kv.mixes``; most of the tests below fix the order of those two
    programs' enqueues and fetches); ``mixed=True``: as it was built, with
    the mixed step where its configuration mixes (``TestMixedStep``)."""
    defaults = dict(block_size=16, num_blocks=32, max_seqs=4,
                    max_model_len=128, prefill_chunk=16, max_queue=64)
    defaults.update(cfg)
    srv = ServingEngine(tiny_engine, ServingConfig(**defaults),
                        **({"clock": clock} if clock else {}))
    if not mixed:
        srv._mixed = None
    return srv


# ---------------------------------------------------------------------------
# block allocator invariants
# ---------------------------------------------------------------------------


class TestBlockAllocator:
    def test_occupancy_accounting_exact(self):
        a = BlockAllocator(10)
        ids1 = a.alloc(3)
        ids2 = a.alloc(4)
        assert a.blocks_in_use == 7 and a.blocks_free == 3
        assert a.blocks_in_use + a.blocks_free == a.capacity
        a.free(ids1)
        assert a.blocks_in_use == 4 and a.blocks_free == 6
        a.free(ids2)
        assert a.blocks_in_use == 0 and a.blocks_free == 10

    def test_ids_unique_nonzero_in_range(self):
        a = BlockAllocator(8)
        ids = a.alloc(8)
        assert sorted(ids) == list(range(1, 9))  # 0 is the scratch block

    def test_exhaustion_returns_none_no_partial(self):
        a = BlockAllocator(4)
        a.alloc(3)
        before = (a.blocks_in_use, a.blocks_free)
        assert a.alloc(2) is None
        assert (a.blocks_in_use, a.blocks_free) == before  # nothing leaked

    def test_double_free_raises(self):
        a = BlockAllocator(4)
        ids = a.alloc(2)
        a.free(ids)
        with pytest.raises(BlockAllocatorError):
            a.free(ids)

    def test_foreign_block_free_raises(self):
        a = BlockAllocator(4)
        with pytest.raises(BlockAllocatorError):
            a.free([3])

    def test_no_block_handed_out_twice(self):
        a = BlockAllocator(6)
        ids = a.alloc(4)
        a.free(ids[:2])
        more = a.alloc(2)
        held = set(ids[2:]) | set(more)
        assert len(held) == 4  # freed ids may recycle; live ids never collide

    def test_peak_tracking(self):
        a = BlockAllocator(10)
        ids = a.alloc(6)
        a.free(ids)
        a.alloc(2)
        assert a.peak_in_use == 6


# ---------------------------------------------------------------------------
# scheduler policy (device-free, injectable clock)
# ---------------------------------------------------------------------------


def mk_sched(clock, **cfg):
    defaults = dict(block_size=4, num_blocks=16, max_seqs=2,
                    max_model_len=32, prefill_chunk=4, max_queue=8)
    defaults.update(cfg)
    return Scheduler(ServingConfig(**defaults), clock=clock)


def mk_req(rid, n=6, tenant="default", deadline=None, max_new=4):
    return Request(rid=rid, prompt=np.arange(n) % 7, max_new_tokens=max_new,
                   tenant=tenant, deadline_s=deadline)


class TestSchedulerPolicy:
    def test_fcfs_admission_order(self):
        clk = FakeClock()
        s = mk_sched(clk, fairness="fcfs", max_seqs=4)
        for rid in (0, 1, 2):
            s.submit(mk_req(rid))
            clk.advance(1.0)
        s.admit()
        assert list(s.admitted_log) == [0, 1, 2]

    def test_fair_least_service_tenant_first(self):
        clk = FakeClock()
        s = mk_sched(clk, max_seqs=1)
        # tenant A floods first; B arrives later
        for rid in range(3):
            s.submit(mk_req(rid, tenant="A"))
            clk.advance(0.1)
        s.submit(mk_req(10, tenant="B"))
        s.admit()                      # one row: A wins the empty ledger tie
        assert list(s.admitted_log) == [0]
        req = s.running[0]
        s.note_service(req, 100)       # A has now consumed service
        s.finish(req)
        s.admit()                      # B is the least-served tenant
        assert list(s.admitted_log) == [0, 10]

    def test_deadline_edf_within_tenant(self):
        clk = FakeClock()
        s = mk_sched(clk, max_seqs=4)
        s.submit(mk_req(0, deadline=30.0))
        s.submit(mk_req(1, deadline=10.0))
        s.submit(mk_req(2, deadline=20.0))
        s.admit()
        assert list(s.admitted_log) == [1, 2, 0]

    def test_no_deadline_sorts_after_deadlines(self):
        clk = FakeClock()
        s = mk_sched(clk, max_seqs=4)
        s.submit(mk_req(0))                     # no deadline
        s.submit(mk_req(1, deadline=50.0))
        s.admit()
        assert list(s.admitted_log) == [1, 0]

    def test_backpressure_queue_full(self):
        s = mk_sched(FakeClock(), max_queue=2)
        s.submit(mk_req(0))
        s.submit(mk_req(1))
        with pytest.raises(QueueFull):
            s.submit(mk_req(2))

    def test_budget_overflow_rejected(self):
        s = mk_sched(FakeClock())
        with pytest.raises(ValueError):
            s.submit(mk_req(0, n=30, max_new=10))   # 40 > max_model_len=32

    def test_admission_allocates_first_chunk_blocks(self):
        s = mk_sched(FakeClock())
        s.submit(mk_req(0, n=6))
        (req,) = s.admit()
        assert req.state == PREFILL and req.row is not None
        assert len(req.blocks) == 1        # first chunk = 4 tokens = 1 block
        assert s.alloc.blocks_in_use == 1

    def test_admission_never_preempts(self):
        s = mk_sched(FakeClock(), num_blocks=8, max_seqs=2)
        s.submit(mk_req(0, n=6))
        (a,) = s.admit()
        a.state = DECODE
        assert s.ensure_blocks(a, 32)      # a takes the whole pool
        s.submit(mk_req(1, n=6))
        assert s.admit() == []             # pool dry: no eviction for entry
        assert a.state == DECODE and s.queue_depth() == 1

    def test_preemption_lifo_victim_recompute_state(self):
        clk = FakeClock()
        s = mk_sched(clk, num_blocks=8, max_seqs=3)
        s.submit(mk_req(0, n=4)); s.submit(mk_req(1, n=4))
        a, b = s.admit()
        for r in (a, b):
            r.state = DECODE
            r.length = 4
            r.generated = [5, 6]
            r.pending_token = 6
        assert s.ensure_blocks(a, 28)      # 7 blocks for a (+1 b's): 8/8
        assert s.alloc.blocks_free == 0
        # growing a further must evict b (most recently admitted)
        assert s.ensure_blocks(a, 32)
        assert b.state == QUEUED and b.blocks == [] and b.row is None
        assert b.resume and b.pending_token == 6
        # recompute source: prompt + generated-minus-pending
        np.testing.assert_array_equal(
            b.prompt, np.concatenate([np.arange(4) % 7, [5]]))
        assert b.prefill_pos == 0 and b.length == 0
        assert s.preemption_count == 1 and b.preemptions == 1

    def test_ensure_blocks_fails_with_no_victim(self):
        s = mk_sched(FakeClock(), num_blocks=8, max_seqs=1)
        s.submit(mk_req(0, n=4))
        (a,) = s.admit()
        a.state = DECODE
        assert s.ensure_blocks(a, 32)
        assert not s.ensure_blocks(a, 36)  # nothing else to evict

    def test_cancel_releases_row_and_blocks(self):
        s = mk_sched(FakeClock())
        s.submit(mk_req(0)); s.submit(mk_req(1))
        (a, b) = s.admit()
        assert s.cancel(a)
        assert s.alloc.blocks_in_use == len(b.blocks)
        assert a.row is None and not s.cancel(a)   # second cancel no-ops
        s.submit(mk_req(2))
        s.cancel(s.queued[0])                       # cancel while queued
        assert s.queue_depth() == 0

    def test_cancel_queued_with_blocks_frees_them(self):
        """A request evicted mid-iteration can transiently be QUEUED while
        holding blocks — cancelling it must not leak them."""
        s = mk_sched(FakeClock())
        r = mk_req(0)
        s.submit(r)
        r.blocks = s.alloc.alloc(2)
        assert s.cancel(r)
        assert s.alloc.blocks_in_use == 0 and r.blocks == []

    def test_max_new_tokens_must_be_positive(self):
        s = mk_sched(FakeClock())
        with pytest.raises(ValueError):
            s.submit(mk_req(0, max_new=0))
        with pytest.raises(ValueError):
            s.submit(mk_req(1, max_new=-3))

    def test_ttft_tpot_clock_math(self):
        clk = FakeClock()
        s = mk_sched(clk)
        req = mk_req(0, max_new=3)
        s.submit(req)
        clk.advance(2.0)
        req.first_token_s = clk()
        req.generated = [1, 2, 3]
        clk.advance(4.0)
        s.running[0] = req; req.row = 0
        s.finish(req)
        assert req.ttft_s == pytest.approx(2.0)
        assert req.tpot_s == pytest.approx(2.0)    # 4s / (3-1) tokens


# ---------------------------------------------------------------------------
# serving config validation
# ---------------------------------------------------------------------------


class TestServingConfig:
    def test_block_divisibility_enforced(self):
        with pytest.raises(ConfigError):
            ServingConfig(block_size=16, max_model_len=100).validate()

    def test_chunk_block_alignment_enforced(self):
        with pytest.raises(ConfigError):
            ServingConfig(block_size=16, max_model_len=128,
                          prefill_chunk=24).validate()

    def test_pool_must_hold_one_sequence(self):
        with pytest.raises(ConfigError):
            ServingConfig(block_size=16, max_model_len=128,
                          num_blocks=4).validate()

    def test_unknown_fairness_rejected(self):
        with pytest.raises(ConfigError):
            ServingConfig(fairness="lottery").validate()

    def test_full_provisioning_default(self):
        cfg = ServingConfig(block_size=16, max_model_len=128, max_seqs=4)
        cfg.validate()
        assert cfg.pool_blocks() == 4 * 8


# ---------------------------------------------------------------------------
# paged-path satellites
# ---------------------------------------------------------------------------


class TestKvCacheSatellites:
    def test_init_cache_dtype_is_mandatory(self):
        """The dtype-plumbing satellite: no bf16 default to silently
        mismatch an fp32 engine's arena."""
        from deepspeed_tpu.inference import kv_cache
        from deepspeed_tpu.models import create_model

        cfg = create_model("tiny", dtype=jnp.float32).config
        with pytest.raises(TypeError):
            kv_cache.init_cache(cfg, 1, 64)    # noqa — missing dtype
        c = kv_cache.init_cache(cfg, 1, 64, jnp.float32)
        assert c["k"].dtype == jnp.float32

    def test_paged_block_divisibility_asserted(self):
        from deepspeed_tpu.inference import kv_cache

        with pytest.raises(ValueError):
            kv_cache.assert_block_divisible(100, 16)
        assert kv_cache.assert_block_divisible(128, 16) == 8

    def test_engine_bucket_unified_with_block_size(self, tiny_engine):
        """The _bucket satellite: wrapping an engine pins its prompt bucket
        to the serving block size, so generate() buckets no longer imply
        arena blocks the true prompt can't use."""
        srv = serving(tiny_engine, block_size=16)
        assert tiny_engine.config.prompt_bucket == 16
        tiny_engine.generate(np.arange(5)[None], max_new_tokens=2)
        assert (1, 16) in tiny_engine._prefill_cache   # not (1, 64)
        del srv


# ---------------------------------------------------------------------------
# end-to-end serving (tiny model, CPU)
# ---------------------------------------------------------------------------


class TestServingEngine:
    def test_single_request_matches_generate(self, tiny_engine):
        srv = serving(tiny_engine)
        prompt = np.random.RandomState(0).randint(0, 250, (11,))
        got = srv.submit(prompt, max_new_tokens=8).result()
        want = np.asarray(tiny_engine.generate(prompt[None],
                                               max_new_tokens=8))[0]
        np.testing.assert_array_equal(got, want)

    def test_chunked_prefill_bit_identical_first_token(self, tiny_engine):
        """Chunked-prefill equivalence: a 40-token prompt prefilled in
        16-token chunks produces the SAME first token (and continuation) as
        the whole-prompt prefill inside generate()."""
        srv = serving(tiny_engine, prefill_chunk=16)
        prompt = np.random.RandomState(1).randint(0, 250, (40,))
        got = srv.submit(prompt, max_new_tokens=6).result()
        want = np.asarray(tiny_engine.generate(prompt[None],
                                               max_new_tokens=6))[0]
        assert got[0] == want[0]
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("first_turn", [5, 11, 16])
    def test_a_chunk_that_starts_inside_a_page(self, tiny_engine, first_turn):
        """``prefill_pos`` is any length once a context has grown by
        something other than whole chunks (a session's earlier turn; a
        prefix hit that recomputes its last token). The chunks behind it
        begin inside a page and are written as whole pages, over what the
        earlier turn left in their first page: the tokens are those of the
        unpaged whole-prompt path."""
        from deepspeed_tpu.serving import paged_kv

        srv = serving(tiny_engine, prefill_chunk=16)
        prompt = np.random.RandomState(3).randint(0, 250, (53,))
        h = srv.submit(prompt, max_new_tokens=6)
        req = srv.sched.admit()[0]
        # the earlier turn, sent the way _step_prefill sends a chunk
        assert srv.sched.ensure_blocks(req, first_turn)
        chunk = np.zeros((1, 16), np.int32)
        chunk[0, :first_turn] = prompt[:first_turn]
        srv._run_program(
            get_session(), "serving/prefill_chunk", srv._prefill,
            paged_kv.pack_chunk(srv._table_for([req]), chunk, 0, first_turn,
                                *srv._sampling_arrays([req])),
            srv._base_rng)
        req.prefill_pos = req.length = first_turn
        starts = []
        while req.state == PREFILL:
            starts.append(req.prefill_pos)
            assert srv._step_prefill()
        assert starts == list(range(first_turn, 53, 16))
        srv.run()
        want = np.asarray(tiny_engine.generate(prompt[None],
                                               max_new_tokens=6))[0]
        np.testing.assert_array_equal(h.result(), want)

    def test_prompt_shorter_than_chunk(self, tiny_engine):
        srv = serving(tiny_engine, prefill_chunk=32)
        prompt = np.random.RandomState(2).randint(0, 250, (5,))
        got = srv.submit(prompt, max_new_tokens=4).result()
        want = np.asarray(tiny_engine.generate(prompt[None],
                                               max_new_tokens=4))[0]
        np.testing.assert_array_equal(got, want)

    def test_eos_stops_early_and_frees(self, tiny_engine):
        srv = serving(tiny_engine)
        prompt = np.arange(8)
        ref = srv.submit(prompt, max_new_tokens=10).result()
        eos = int(ref[2])
        got = srv.submit(prompt, max_new_tokens=10,
                         eos_token_id=eos).result()
        assert got[-1] == eos and len(got) <= 10
        assert srv.alloc.blocks_in_use == 0      # everything released

    def test_temperature_deterministic_per_engine_stream(self, tiny_engine):
        p = np.arange(9)
        a = serving(tiny_engine).submit(p, max_new_tokens=6,
                                        temperature=0.8, top_k=20).result()
        b = serving(tiny_engine).submit(p, max_new_tokens=6,
                                        temperature=0.8, top_k=20).result()
        np.testing.assert_array_equal(a, b)
        assert len(a) == 6

    def test_per_request_seed_schedule_independent(self, tiny_engine):
        """Sampling draws depend on (engine seed, request seed, token
        index) only: the same request re-submitted later on the SAME
        engine (different scheduler iterations) reproduces its stream, and
        a different seed diverges."""
        srv = serving(tiny_engine)
        p = np.arange(9)
        a = srv.submit(p, max_new_tokens=6, temperature=1.0, seed=1).result()
        b = srv.submit(p, max_new_tokens=6, temperature=1.0, seed=2).result()
        c = srv.submit(p, max_new_tokens=6, temperature=1.0, seed=1).result()
        np.testing.assert_array_equal(a, c)
        assert not np.array_equal(a, b)

    def test_finished_handles_pruned(self, tiny_engine):
        """Server-lifetime memory: the engine drops its handle reference
        when a request reaches a terminal state (the client keeps its own)."""
        srv = serving(tiny_engine)
        h = srv.submit(np.arange(5), max_new_tokens=3)
        h.result()
        assert srv._handles == {}
        h2 = srv.submit(np.arange(5), max_new_tokens=30)
        srv.step()
        h2.cancel()
        assert srv._handles == {}

    def test_streaming_yields_incrementally(self, tiny_engine):
        srv = serving(tiny_engine)
        h = srv.submit(np.arange(6), max_new_tokens=5)
        seen = []
        for tok in h.stream():
            seen.append(tok)
            assert len(h.tokens) >= len(seen)
        assert seen == h.tokens and len(seen) == 5
        assert h.state == "finished"

    def test_cancel_mid_flight_releases_and_raises(self, tiny_engine):
        srv = serving(tiny_engine)
        h = srv.submit(np.arange(6), max_new_tokens=50)
        for _ in range(5):
            srv.step()
        assert 0 < len(h.tokens) < 50
        assert h.cancel()
        assert srv.alloc.blocks_in_use == 0 and srv.in_flight() == 0
        with pytest.raises(RequestCancelled):
            h.result()
        assert list(h.stream()) == h.tokens     # stream drains, then ends

    def test_backpressure_raises_queuefull(self, tiny_engine):
        srv = serving(tiny_engine, max_queue=2)
        srv.submit(np.arange(4), max_new_tokens=4)
        srv.submit(np.arange(4), max_new_tokens=4)
        with pytest.raises(QueueFull):
            srv.submit(np.arange(4), max_new_tokens=4)
        srv.run()

    def test_preemption_recompute_bit_identical(self, tiny_engine):
        """Pool far too small for the load: eviction + recompute must not
        change any output (greedy). prefix_cache=False isolates the
        preemption machinery — with sharing on, cache eviction relieves
        most of the pressure before any request is preempted (tested
        separately in TestPrefixSharing)."""
        srv = serving(tiny_engine, num_blocks=10, max_seqs=4,
                      prefix_cache=False)
        rng = np.random.RandomState(3)
        prompts = [rng.randint(0, 250, (rng.randint(20, 60),))
                   for _ in range(6)]
        handles = [srv.submit(p, max_new_tokens=10) for p in prompts]
        srv.run()
        assert srv.sched.preemption_count > 0    # pressure actually happened
        for p, h in zip(prompts, handles):
            want = np.asarray(tiny_engine.generate(p[None],
                                                   max_new_tokens=10))[0]
            np.testing.assert_array_equal(h.result(), want)
        assert srv.alloc.blocks_in_use == 0

    def test_threaded_driver(self, tiny_engine):
        srv = serving(tiny_engine)
        srv.start()
        try:
            h = srv.submit(np.arange(7), max_new_tokens=5)
            got = h.result(timeout_s=60.0)
            assert len(got) == 5
        finally:
            srv.stop()
        want = np.asarray(tiny_engine.generate(np.arange(7)[None],
                                               max_new_tokens=5))[0]
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# jit stability + the acceptance smoke
# ---------------------------------------------------------------------------


@pytest.fixture
def obs_session(tmp_path):
    reset_session()
    sess = configure_observability(ObservabilityConfig(
        enabled=True, output_dir=str(tmp_path / "obs"),
        flight_recorder=False))
    yield sess
    reset_session()


class TestServingJit:
    def test_decode_compiles_once_across_occupancy(self, tiny_engine,
                                                   obs_session):
        """Varying batch occupancy, request mix and sampling settings are
        DATA: the decode program must compile exactly once (the CUDA-graph
        discipline as a jit-cache assertion, measured by the recompile
        watchdog's per-span compile counter)."""
        compiles = get_registry().counter("xla/compiles")
        before = compiles.value(where="serving/decode")
        srv = serving(tiny_engine, max_seqs=4)
        rng = np.random.RandomState(4)
        handles = []
        for i in range(7):   # staggered → occupancy 1..4, mixed sampling
            handles.append(srv.submit(
                rng.randint(0, 250, (rng.randint(3, 30),)),
                max_new_tokens=5, temperature=0.0 if i % 2 else 0.5,
                top_k=0 if i % 3 else 7))
            srv.step()
        srv.run()
        [h.result() for h in handles if h.state == "finished"]
        assert compiles.value(where="serving/decode") - before == 1
        steady = get_registry().counter("xla/steady_state_recompiles")
        assert steady.value(where="serving/decode") == 0


class TestServingSmoke:
    def test_sixteen_concurrent_requests_acceptance(self, tiny_engine,
                                                    obs_session, tmp_path):
        """The ISSUE-6 acceptance smoke: >= 16 concurrent requests with
        staggered arrivals and mixed prompt lengths; every output
        bit-identical to a sequential generate(); decode compiled exactly
        once; peak arena blocks allocated strictly under the sum of
        per-request T_max rows; serving metrics flow through the registry
        and render in the report CLI."""
        compiles = get_registry().counter("xla/compiles")
        before = compiles.value(where="serving/decode")
        srv = serving(tiny_engine, block_size=16, num_blocks=64, max_seqs=8,
                      max_model_len=128, prefill_chunk=16, max_queue=64)
        rng = np.random.RandomState(5)
        prompts = [rng.randint(0, 250, (rng.randint(4, 40),))
                   for _ in range(16)]
        handles = []
        for i, p in enumerate(prompts):          # staggered arrivals
            handles.append(srv.submit(p, max_new_tokens=8,
                                      tenant=f"tenant{i % 3}"))
            if i % 4 == 3:
                srv.step()
        srv.run()

        # 1) bit-identical to sequential offline generation
        for i, (p, h) in enumerate(zip(prompts, handles)):
            want = np.asarray(tiny_engine.generate(p[None],
                                                   max_new_tokens=8))[0]
            np.testing.assert_array_equal(
                h.result(), want, err_msg=f"request {i} diverged")

        # 2) ONE decode program across the whole run
        assert compiles.value(where="serving/decode") - before == 1

        # 3) paging shares HBM: peak blocks strictly under the sum of
        #    per-request full T_max rows the flat arena would reserve
        flat_blocks = len(prompts) * (128 // 16)
        assert 0 < srv.alloc.peak_in_use < flat_blocks

        # 4) metrics flow through the registry ...
        reg = get_registry()
        # the registry is process-global: scope the count to THIS test's
        # tenant labels (earlier serving tests observe under 'default')
        ttft_n = sum(r["count"]
                     for r in reg.histogram("serving/ttft_ms").records()
                     if str(r["labels"].get("tenant", "")
                            ).startswith("tenant"))
        assert ttft_n == 16
        assert reg.gauge("serving/kv_blocks_peak").value() \
            == srv.alloc.peak_in_use
        assert reg.gauge("serving/queue_depth").value() == 0
        srv.close()   # publishes the percentile gauges
        assert reg.gauge("serving/ttft_p50_ms").value() is not None

        # ... and render in the report CLI
        from deepspeed_tpu.observability.report import report

        path = str(tmp_path / "metrics.jsonl")
        reg.dump_jsonl(path)
        out = report([path])
        assert "== serving ==" in out
        assert "ttft_ms" in out and "tokens_per_sec" in out


@pytest.mark.slow
def test_tensor_parallel_serving_matches(devices8):
    """tp=2 serving == tp=1 serving on the virtual mesh (same weights):
    the paged programs partition under GSPMD without changing tokens."""
    import jax

    from deepspeed_tpu.parallel import mesh as mesh_mod

    scfg = dict(block_size=16, num_blocks=24, max_seqs=2,
                max_model_len=64, prefill_chunk=16)
    e1 = init_inference("tiny-llama", dtype=jnp.float32, max_out_tokens=64)
    s1 = ServingEngine(e1, ServingConfig(**scfg))
    p = np.arange(10)
    t1 = s1.submit(p, max_new_tokens=6).result()
    mesh_mod.reset_mesh()
    e2 = init_inference("tiny-llama", dtype=jnp.float32, max_out_tokens=64,
                        tensor_parallel=2)
    e2.params = jax.tree.map(
        lambda x, s: jax.device_put(np.asarray(x), s), e1.params,
        e2.param_shardings)
    s2 = ServingEngine(e2, ServingConfig(**scfg))
    t2 = s2.submit(p, max_new_tokens=6).result()
    np.testing.assert_array_equal(t1, t2)


# ---------------------------------------------------------------------------
# audit integration
# ---------------------------------------------------------------------------


class TestServingAudit:
    def test_serving_entries_registered_and_clean(self, tiny_engine):
        from tools.tpuaudit.core import run_audit
        from tools.tpuaudit.registry import get_entry_points

        srv = serving(tiny_engine)
        eps = get_entry_points(["serving/prefill_chunk", "serving/decode",
                                "serving/cow_copy"])
        assert [ep.name for ep in eps] == ["serving/prefill_chunk",
                                           "serving/decode",
                                           "serving/cow_copy"]
        assert all(ep.donate_argnums == (1,) for ep in eps[:2])  # arena
        assert eps[2].donate_argnums == (0,)
        findings = run_audit(eps, publish_metrics=False)
        assert findings == [], [f"{f.entry}:{f.check}" for f in findings]
        del srv


# ---------------------------------------------------------------------------
# prefix sharing: refcounts, COW, prefix-hit admission
# ---------------------------------------------------------------------------


class TestRefcountedAllocator:
    def test_incref_free_lifecycle(self):
        a = BlockAllocator(4)
        ids = a.alloc(2)
        a.incref(ids)                      # a second holder appears
        assert a.blocks_shared == 2
        a.free(ids)                        # first holder drops out
        assert a.blocks_in_use == 2 and a.blocks_shared == 0
        a.free(ids)                        # LAST reference → recycled
        assert a.blocks_in_use == 0 and a.blocks_free == 4
        with pytest.raises(BlockAllocatorError):
            a.free([ids[0]])               # double free still raises

    def test_incref_unallocated_raises(self):
        a = BlockAllocator(2)
        with pytest.raises(BlockAllocatorError):
            a.incref([1])

    def test_occupancy_invariant_under_sharing(self):
        a = BlockAllocator(6)
        ids = a.alloc(3)
        a.incref(ids[:2])
        a.free(ids)
        a.incref(ids[:1])
        assert a.blocks_in_use + a.blocks_free == 6
        a.free(ids[:2])
        a.free(ids[:1])
        assert a.blocks_in_use == 0 and a.blocks_free == 6


class TestPrefixCacheHost:
    def _cache(self, cap=8, bs=4):
        from deepspeed_tpu.serving import PrefixCache

        alloc = BlockAllocator(cap)
        return alloc, PrefixCache(alloc, bs)

    def test_match_insert_chain(self):
        alloc, pc = self._cache()
        prompt = np.arange(12)             # 3 full blocks of 4
        ids = alloc.alloc(3)
        for i in range(3):
            assert pc.insert(prompt, i, ids[i])
        assert alloc.refcount(ids[0]) == 2   # owner + cache pin
        got, n = pc.match(prompt)
        assert got == ids and n == 11        # capped at len(prompt) - 1
        # a different first token shares nothing (chain hash)
        other = np.concatenate([[99], np.arange(1, 12)])
        assert pc.match(other) == ([], 0)
        # divergence after two blocks → only those two shared
        part = np.concatenate([np.arange(8), [77, 77, 77, 77]])
        got3, n3 = pc.match(part)
        assert got3 == ids[:2] and n3 == 8

    def test_insert_is_idempotent(self):
        alloc, pc = self._cache()
        prompt = np.arange(4)
        ids = alloc.alloc(1)
        assert pc.insert(prompt, 0, ids[0])
        assert not pc.insert(prompt, 0, ids[0])   # no double pin
        assert alloc.refcount(ids[0]) == 2

    def test_evict_respects_pinned_blocks(self):
        alloc, pc = self._cache(cap=4)
        ids = alloc.alloc(2)
        prompt = np.arange(8)
        pc.insert(prompt, 0, ids[0])
        pc.insert(prompt, 1, ids[1])
        alloc.free([ids[1]])     # owner gone → cache is sole holder
        # ids[0] still request-owned (refcount 2) → pinned, never evicted
        assert pc.evict(5) == 1
        assert alloc.refcount(ids[1]) == 0
        assert alloc.refcount(ids[0]) == 2
        assert pc.cached_blocks == 1


class TestPrefixSharing:
    def test_second_request_skips_shared_chunks(self, tiny_engine):
        """The acceptance criterion: an identical cached prompt prefix
        consumes ZERO new prefill chunks for the shared blocks — only the
        capped final token re-prefills (and its shared block goes COW)."""
        srv = serving(tiny_engine)
        rng = np.random.RandomState(7)
        prompt = rng.randint(0, 250, (48,))       # exactly 3 full blocks
        h1 = srv.submit(prompt, max_new_tokens=6)
        srv.run()
        assert srv.prefix.cached_blocks == 3
        h2 = srv.submit(prompt, max_new_tokens=6)
        req = srv.sched.admit()[0]
        # all shared blocks skipped: prefill restarts at the LAST prompt
        # token (its logits seed the first sampled token)
        assert req.prefill_pos == 47
        assert srv.sched.prefix_hit_tokens == 47
        assert srv.sched.prefix_hits == 1
        prefill_steps = 0
        while req.state == PREFILL:
            assert srv._step_prefill()
            prefill_steps += 1
        assert prefill_steps == 1                  # 1 chunk, not 3
        assert srv._cow_copies >= 1                # shared block was copied
        srv.run()
        want = np.asarray(tiny_engine.generate(prompt[None],
                                               max_new_tokens=6))[0]
        np.testing.assert_array_equal(h1.result(), want)
        np.testing.assert_array_equal(h2.result(), want)

    def test_partial_tail_block_stays_private(self, tiny_engine):
        """A prompt with a partial tail block shares only the FULL blocks;
        the tail re-prefills into a fresh private block — no COW needed."""
        srv = serving(tiny_engine)
        rng = np.random.RandomState(8)
        prompt = rng.randint(0, 250, (40,))       # 2 full blocks + 8
        h1 = srv.submit(prompt, max_new_tokens=4)
        srv.run()
        assert srv.prefix.cached_blocks == 2
        h2 = srv.submit(prompt, max_new_tokens=4)
        req = srv.sched.admit()[0]
        assert req.prefill_pos == 32
        cow_before = srv._cow_copies
        srv.run()
        assert srv._cow_copies == cow_before
        want = np.asarray(tiny_engine.generate(prompt[None],
                                               max_new_tokens=4))[0]
        np.testing.assert_array_equal(h1.result(), want)
        np.testing.assert_array_equal(h2.result(), want)

    def test_cancel_releases_shared_blocks_exactly_once(self, tiny_engine):
        srv = serving(tiny_engine)
        rng = np.random.RandomState(9)
        prompt = rng.randint(0, 250, (48,))
        srv.submit(prompt, max_new_tokens=4)
        srv.run()
        h2 = srv.submit(prompt, max_new_tokens=4)
        srv.step()                                 # admit + first chunk
        shared = [b for b in h2._req.blocks if srv.alloc.refcount(b) > 1]
        assert shared                              # really sharing
        before = {b: srv.alloc.refcount(b) for b in shared}
        assert h2.cancel()
        for b in shared:
            assert srv.alloc.refcount(b) == before[b] - 1   # exactly once
        assert not h2.cancel()                     # second cancel: no-op
        # cache pins survive the cancel; no block was force-freed
        assert srv.alloc.blocks_in_use == srv.prefix.cached_blocks

    def test_shared_pressure_stress_outputs_exact(self, tiny_engine):
        """Six requests sharing a 2-block prefix through a pool too small
        to hold them privately: cache eviction + preemption + COW all fire
        and every output stays bit-identical to offline generate()."""
        srv = serving(tiny_engine, num_blocks=14, max_seqs=4)
        rng = np.random.RandomState(10)
        shared = rng.randint(0, 250, (32,))
        prompts = [np.concatenate([shared,
                                   rng.randint(0, 250,
                                               (rng.randint(1, 16),))])
                   for _ in range(6)]
        handles = [srv.submit(p, max_new_tokens=6) for p in prompts]
        srv.run()
        for i, (p, h) in enumerate(zip(prompts, handles)):
            want = np.asarray(tiny_engine.generate(p[None],
                                                   max_new_tokens=6))[0]
            np.testing.assert_array_equal(h.result(), want,
                                          err_msg=f"request {i} diverged")
        # every request reference released — only cache pins remain
        assert srv.alloc.blocks_in_use == srv.prefix.cached_blocks

    def test_prefix_metrics_published(self, tiny_engine, obs_session):
        srv = serving(tiny_engine)
        rng = np.random.RandomState(11)
        prompt = rng.randint(0, 250, (48,))
        srv.submit(prompt, max_new_tokens=4)
        srv.run()
        srv.submit(prompt, max_new_tokens=4)
        srv.run()
        reg = get_registry()
        assert reg.gauge("serving/prefix_hit_rate").value() > 0
        assert reg.gauge("serving/prefix_cache_blocks").value() >= 3
        assert reg.counter("serving/cow_copies").value() >= 1


class TestPagedReadOracle:
    def test_concurrent_requests_match_plain_forward(self, tiny_engine):
        """The 16-request acceptance smoke against the oracle the benchmark
        uses: every served greedy token is the argmax of the plain forward
        over the whole sequence (no cache, no table) at the position before
        it."""
        rng = np.random.RandomState(12)
        prompts = [rng.randint(0, 250, (rng.randint(4, 40),))
                   for _ in range(16)]
        srv = serving(tiny_engine, num_blocks=64, max_seqs=8)
        handles = []
        for i, p in enumerate(prompts):
            handles.append(srv.submit(p, max_new_tokens=8))
            if i % 4 == 3:
                srv.step()
        srv.run()
        for i, (p, h) in enumerate(zip(prompts, handles)):
            got = h.result()
            # right-padded to one shape: causal, so the pad changes nothing
            seq = np.zeros((1, 48), np.int32)
            seq[0, :len(p) + 8] = np.concatenate([p, got])
            want = np.asarray(tiny_engine.forward(seq))[0].argmax(-1)
            np.testing.assert_array_equal(
                got, want[len(p) - 1:len(p) + 7],
                err_msg=f"request {i} diverged")

    def test_custom_attention_impl_refused(self):
        """The paged read has no operand for a custom attention: the engine
        says so when it is built, not by serving another model."""
        from deepspeed_tpu.models.transformer import dot_product_attention

        engine = init_inference("tiny", dtype=jnp.float32,
                                max_out_tokens=128,
                                attention_impl=dot_product_attention)
        with pytest.raises(NotImplementedError, match="attention_impl"):
            serving(engine)


# ---------------------------------------------------------------------------
# deadline enforcement at decode time (ISSUE-12 satellite): an expired
# request must stop consuming rows/blocks, finish as deadline_exceeded, and
# keep the request ledger balanced
# ---------------------------------------------------------------------------


class TestDeadlineEnforcement:
    def test_running_request_expires_and_frees_blocks(self, tiny_engine):
        from deepspeed_tpu.serving import DeadlineExceeded

        clk = FakeClock()
        srv = serving(tiny_engine, clock=clk, prefix_cache=False)
        try:
            h = srv.submit(np.arange(1, 40, dtype=np.int32),
                           max_new_tokens=64, deadline_s=5.0)
            for _ in range(4):
                srv.step()
            assert len(h.tokens) > 0 and not h.done   # mid-stream
            assert srv.alloc.blocks_in_use > 0
            clk.advance(10.0)                         # past the deadline
            progress = srv.step()
            assert progress                            # expiry IS progress
            assert h.state == "deadline_exceeded" and h.done
            # the bugfix: rows and blocks free NOW, not at token budget
            assert srv.alloc.blocks_in_use == 0
            assert srv.sched.queue_depth() == 0
            assert len(srv.sched.running) == 0
            assert srv.sched.deadline_exceeded_count == 1
            with pytest.raises(DeadlineExceeded):
                h.result()
        finally:
            srv.close()

    def test_queued_request_expires_before_admission(self, tiny_engine):
        clk = FakeClock()
        srv = serving(tiny_engine, clock=clk, max_seqs=1,
                      prefix_cache=False)
        try:
            # one request holds the only row; the second queues (admit h0
            # FIRST — EDF would otherwise prefer the deadline-bearing h1)
            h0 = srv.submit(np.arange(1, 20, dtype=np.int32),
                            max_new_tokens=32)
            srv.step()
            h1 = srv.submit(np.arange(1, 20, dtype=np.int32),
                            max_new_tokens=4, deadline_s=2.0)
            srv.step()
            assert h1.state == "queued"
            clk.advance(5.0)
            srv.step()
            assert h1.state == "deadline_exceeded"
            assert len(h1.tokens) == 0      # never decoded a token
            h0.result()                     # the survivor is unaffected
        finally:
            srv.close()

    def test_pending_fork_siblings_expire_with_parent(self, tiny_engine):
        clk = FakeClock()
        srv = serving(tiny_engine, clock=clk, prefix_cache=False,
                      prefill_chunk=16)
        try:
            # long prompt: parent still prefilling when the deadline hits,
            # so the n=3 siblings are still waiting for their fork point
            hs = srv.submit(np.arange(1, 100, dtype=np.int32),
                            max_new_tokens=8, deadline_s=3.0, n=3)
            srv.step()
            assert srv._pending_fork_count() == 2
            clk.advance(5.0)
            srv.step()
            assert all(h.state == "deadline_exceeded" for h in hs)
            assert srv._pending_fork_count() == 0
            assert srv.sched.deadline_exceeded_count == 3
            assert srv.alloc.blocks_in_use == 0
        finally:
            srv.close()

    def test_ledger_balances_across_terminal_states(self, tiny_engine):
        clk = FakeClock()
        srv = serving(tiny_engine, clock=clk, prefix_cache=False)
        try:
            done = srv.submit(np.arange(1, 20, dtype=np.int32),
                              max_new_tokens=2)
            gone = srv.submit(np.arange(1, 20, dtype=np.int32),
                              max_new_tokens=8)
            late = srv.submit(np.arange(1, 20, dtype=np.int32),
                              max_new_tokens=8, deadline_s=1.0)
            srv.step()
            gone.cancel()
            clk.advance(2.0)
            srv.run()
            done.result()
            s = srv.sched
            assert (s.finished_count, s.cancelled_count,
                    s.deadline_exceeded_count) == (1, 1, 1)
            # submitted == completed + cancelled + deadline_exceeded
            assert (s.finished_count + s.cancelled_count
                    + s.deadline_exceeded_count) == 3
            assert srv.in_flight() == 0
        finally:
            srv.close()

    def test_no_deadline_never_expires(self, tiny_engine):
        clk = FakeClock()
        srv = serving(tiny_engine, clock=clk, prefix_cache=False)
        try:
            h = srv.submit(np.arange(1, 20, dtype=np.int32),
                           max_new_tokens=4)
            clk.advance(1e6)
            out = h.result()
            assert out.size == 4 and h.state == "finished"
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# deferred delivery: the driver thread hands out a step's tokens behind the
# next enqueue; step() before it returns. Same tokens, same order, either way
# ---------------------------------------------------------------------------


def never_ahead(srv, chunks=True):
    """Hold the driver thread's form to the iteration that fetches what it
    enqueued (what the engine does whenever somebody waits for the device:
    ``TestStepAhead`` has the states that say so, ``TestChunkAhead`` those
    of a chunk, which ``chunks=False`` leaves to the engine): the order of
    enqueue, delivery and fetch around ONE program is what these tests
    fix."""
    srv._ahead_held_by = lambda flight: "queued"
    if chunks:
        srv._chunk_held_by = lambda req, *at: "pages"
    return srv


def drive_on_this_thread(srv, iterations=None):
    """The driver thread's loop (``ServingEngine._drive``) on the calling
    thread, so a test can stop between two iterations: with ``iterations``
    that many and NO idle flush, else until nothing is in flight."""
    n = 0
    while srv.in_flight() and (iterations is None or n < iterations):
        srv._iterate(defer=True)
        n += 1
    if iterations is None:
        srv._flush_locked()
    return n


DELIVERY_CASES = {
    # name: (engine config, [(prompt length, submit kwargs)])
    "greedy": ({}, [(7, dict(max_new_tokens=9)), (23, dict(max_new_tokens=6)),
                    (40, dict(max_new_tokens=8)), (3, dict(max_new_tokens=10)),
                    (18, dict(max_new_tokens=7))]),
    "sampled": ({}, [(9, dict(max_new_tokens=8, temperature=0.8, top_k=20,
                              top_p=0.9, seed=5)),
                     (30, dict(max_new_tokens=8, temperature=1.3, top_p=0.7,
                               seed=6)),
                     (12, dict(max_new_tokens=8, temperature=0.5, top_k=5,
                               seed=7))]),
    "eos": ({}, [(11, dict(max_new_tokens=12, eos_at=3)),
                 (26, dict(max_new_tokens=12, eos_at=5)),
                 (5, dict(max_new_tokens=12))]),
    "one_token": ({}, [(8, dict(max_new_tokens=1)),
                       (19, dict(max_new_tokens=1)),
                       (33, dict(max_new_tokens=4))]),
    "preempted": (dict(num_blocks=10, prefix_cache=False),
                  [(n, dict(max_new_tokens=10))
                   for n in (27, 44, 58, 20, 39, 51)]),
}


def delivery_requests(tiny_engine, case):
    """The case's prompts and submit() arguments; ``eos_at`` becomes the
    token the greedy stream holds at that index, so EOS hits mid-stream."""
    cfg, specs = DELIVERY_CASES[case]
    rng = np.random.RandomState(sum(map(ord, case)))
    out = []
    for n, kw in specs:
        prompt = rng.randint(0, 250, (n,)).astype(np.int32)
        kw = dict(kw)
        at = kw.pop("eos_at", None)
        if at is not None:
            greedy = np.asarray(tiny_engine.generate(
                prompt[None], max_new_tokens=kw["max_new_tokens"]))[0]
            kw["eos_token_id"] = int(greedy[at])
        out.append((prompt, kw))
    return cfg, out


class TestDeferredDelivery:
    @pytest.mark.parametrize("case", sorted(DELIVERY_CASES))
    def test_threaded_and_step_driven_streams_are_identical(self,
                                                            tiny_engine,
                                                            case):
        cfg, reqs = delivery_requests(tiny_engine, case)
        streams = {}
        for mode in ("step", "thread", "driver_loop"):
            srv = serving(tiny_engine, **cfg)
            try:
                # all queued before the first iteration: the schedule, and
                # with it the preemptions, are the same in every mode
                handles = [srv.submit(p, **kw) for p, kw in reqs]
                if mode == "step":
                    srv.run()
                elif mode == "thread":
                    srv.start()
                else:
                    drive_on_this_thread(srv)
                streams[mode] = [list(h.result(timeout_s=120.0))
                                 for h in handles]
                assert all(h.done and h.state == "finished"
                           for h in handles)
                assert [len(h._req.generated) for h in handles] \
                    == [len(s) for s in streams[mode]]
                if case == "preempted":
                    assert srv.sched.preemption_count > 0
                if case == "eos":
                    assert len(streams[mode][0]) < 12
                srv.stop()
                assert not srv.sched.running and not srv._handles
                assert not srv._undelivered
            finally:
                srv.close()
        assert streams["thread"] == streams["step"]
        assert streams["driver_loop"] == streams["step"]

    @pytest.mark.parametrize("how", ["cancel", "deadline"])
    def test_a_stream_cut_with_a_delivery_pending_keeps_its_tokens(
            self, tiny_engine, how):
        """Four iterations in the driver's form leave the fourth's token
        applied and undelivered; the cancel, or the expiry at the next
        admit, streams it before it ends the stream: what a step-driven
        engine cut at the same iteration streams."""
        from deepspeed_tpu.serving import DeadlineExceeded

        got = {}
        for mode in ("step", "driver_loop"):
            clk = FakeClock()
            srv = never_ahead(serving(tiny_engine, clock=clk,
                                      prefix_cache=False))
            try:
                h = srv.submit(np.arange(1, 30, dtype=np.int32),
                               max_new_tokens=40, deadline_s=5.0)
                other = srv.submit(np.arange(3, 20, dtype=np.int32),
                                   max_new_tokens=40)
                if mode == "step":
                    for _ in range(4):
                        srv.step()
                    assert not srv._undelivered
                else:
                    assert drive_on_this_thread(srv, iterations=4) == 4
                    assert {r.rid for r, *_ in srv._undelivered} \
                        == {h.request_id, other.request_id}
                    assert len(h.tokens) == len(h._req.generated) - 1
                if how == "cancel":
                    assert h.cancel()
                    with pytest.raises(RequestCancelled):
                        h.result()
                else:
                    clk.advance(10.0)
                    srv.step() if mode == "step" else srv._iterate(defer=True)
                    assert h.state == "deadline_exceeded"
                    with pytest.raises(DeadlineExceeded):
                        h.result()
                assert h.done and h.tokens == h._req.generated
                assert list(h.stream()) == h.tokens
                got[mode] = h.tokens
                other.cancel()
                assert other.tokens == other._req.generated
                assert srv.alloc.blocks_in_use == 0
            finally:
                srv.close()
        assert got["driver_loop"] == got["step"] and len(got["step"]) >= 3

    @staticmethod
    def _spy(monkeypatch, srv):
        """Every dispatch (entry to ``_enqueue``), fetch (return from
        ``_fetch``), push and return of an iteration, in order."""
        from deepspeed_tpu.serving.session import RequestHandle

        events = []
        enqueue, fetch, push, iterate = (srv._enqueue, srv._fetch,
                                         RequestHandle._push, srv._iterate)

        def spy_enqueue(obs, name, *a, **kw):
            events.append(("dispatch", name.split("/")[1]))
            return enqueue(obs, name, *a, **kw)

        def spy_fetch(obs, sent):
            out = fetch(obs, sent)
            events.append(("fetched", sent.name.split("/")[1]))
            return out

        def spy_push(self, token, last=False):
            events.append(("push", self.request_id, len(self._tokens)))
            return push(self, token, last)

        def spy_iterate(defer):
            out = iterate(defer)
            events.append(("iteration_end",))
            return out

        monkeypatch.setattr(srv, "_enqueue", spy_enqueue)
        monkeypatch.setattr(srv, "_fetch", spy_fetch)
        monkeypatch.setattr(srv, "_iterate", spy_iterate)
        monkeypatch.setattr(RequestHandle, "_push", spy_push)
        return events

    def test_driver_thread_pushes_behind_the_next_dispatch(self, tiny_engine,
                                                           monkeypatch):
        srv = never_ahead(serving(tiny_engine))
        events = self._spy(monkeypatch, srv)
        srv.start()
        try:
            h = srv.submit(np.arange(7), max_new_tokens=4)
            assert len(h.result(timeout_s=60.0)) == 4
        finally:
            srv.stop()
        r = h.request_id
        assert events == [
            # the first token at once: before its iteration's decode dispatch
            ("dispatch", "prefill_chunk"), ("fetched", "prefill_chunk"),
            ("push", r, 0),
            ("dispatch", "decode"), ("fetched", "decode"),
            ("iteration_end",),
            # step n's token behind step n+1's dispatch, before its fetch
            ("dispatch", "decode"), ("push", r, 1), ("fetched", "decode"),
            ("iteration_end",),
            ("dispatch", "decode"), ("push", r, 2), ("fetched", "decode"),
            ("iteration_end",),
            # the request finished at apply; nothing is enqueued any more:
            # the driver flushes before it idles
            ("push", r, 3)]
        srv.close()

    def test_a_late_request_s_chunk_shadows_the_pending_pushes(
            self, tiny_engine, monkeypatch):
        srv = never_ahead(serving(tiny_engine))
        events = self._spy(monkeypatch, srv)
        try:
            a = srv.submit(np.arange(7), max_new_tokens=8)
            drive_on_this_thread(srv, iterations=2)
            b = srv.submit(np.arange(9), max_new_tokens=8)
            del events[:]
            drive_on_this_thread(srv, iterations=1)
            ra, rb = a.request_id, b.request_id
            assert events == [
                ("dispatch", "prefill_chunk"), ("push", ra, 2),
                ("fetched", "prefill_chunk"), ("push", rb, 0),
                ("dispatch", "decode"), ("fetched", "decode"),
                ("iteration_end",)]
            drive_on_this_thread(srv)
            assert len(a.tokens) == len(b.tokens) == 8
        finally:
            srv.close()

    def test_step_delivers_before_it_returns(self, tiny_engine, monkeypatch):
        srv = serving(tiny_engine)
        events = self._spy(monkeypatch, srv)
        try:
            h = srv.submit(np.arange(7), max_new_tokens=3)
            while srv.in_flight():
                srv.step()
                assert len(h.tokens) == len(h._req.generated)
                assert not srv._undelivered
            r = h.request_id
            assert events == [
                ("dispatch", "prefill_chunk"), ("fetched", "prefill_chunk"),
                ("push", r, 0),
                ("dispatch", "decode"), ("fetched", "decode"), ("push", r, 1),
                ("iteration_end",),
                ("dispatch", "decode"), ("fetched", "decode"), ("push", r, 2),
                ("iteration_end",)]
            assert h.done
        finally:
            srv.close()

    def test_a_stream_never_ends_a_token_short(self, tiny_engine,
                                               monkeypatch):
        """Between apply (the scheduler's state turns) and delivery the
        handle is not done and its stream goes on waiting."""
        import threading

        srv = serving(tiny_engine)
        n = 6
        at_apply, at_done = [], []
        apply_ = srv._apply

        def spy_apply(req, token, first=False):
            apply_(req, token, first)
            if req.done:
                at_apply.append((h.done, len(h.tokens), h.state))

        monkeypatch.setattr(srv, "_apply", spy_apply)

        def poll():
            while not h.done:
                pass
            at_done.append(len(h.tokens))

        srv.start()
        try:
            h = srv.submit(np.arange(7), max_new_tokens=n)
            poller = threading.Thread(target=poll, daemon=True)
            poller.start()
            streamed = list(h.stream(timeout_s=60.0))
            poller.join(timeout=60.0)
        finally:
            srv.stop()
        assert at_apply == [(False, n - 1, "finished")]
        assert at_done == [n] and len(streamed) == n
        assert streamed == list(h.result()) == h._req.generated
        srv.close()

    @pytest.mark.parametrize("how", ["stop", "close", "idle"])
    def test_whatever_is_pending_is_flushed(self, tiny_engine, how):
        srv = never_ahead(serving(tiny_engine))
        h = srv.submit(np.arange(7), max_new_tokens=3)
        drive_on_this_thread(srv, iterations=3)
        # the scheduler is through with the request; its last token waits
        assert srv.in_flight() == 0 and h.state == "finished"
        assert len(srv._undelivered) == 1 and not h.done
        assert len(h.tokens) == 2
        if how == "idle":
            srv.start()
            assert len(h.result(timeout_s=60.0)) == 3   # no stop() needed
            srv.stop()
        else:
            import threading

            srv._stop.set()         # the driver leaves before its first poll
            srv._thread = threading.Thread(target=srv._drive, daemon=True)
            srv._thread.start()
            srv.stop() if how == "stop" else srv.close()
        assert h.done and len(h.tokens) == 3 and not srv._undelivered
        srv.close()

    @pytest.mark.parametrize("mode", ["thread", "step"])
    def test_the_deferred_count_and_the_shadow_counter(self, tiny_engine,
                                                       obs_session, mode):
        from deepspeed_tpu.observability import recorded_spans

        reg = get_registry()     # the process's: read what this test adds
        out_c = reg.counter("serving/tokens_out")
        shadow_c = reg.counter("serving/tokens_delivered_in_shadow")
        out0, shadow0 = out_c.value(), shadow_c.value()
        srv = serving(tiny_engine)
        if mode == "thread":
            srv.start()
        h = srv.submit(np.arange(7), max_new_tokens=5)
        assert len(h.result(timeout_s=60.0)) == 5
        srv.stop()
        emits = [s["attrs"] for s in recorded_spans()
                 if s["name"] == "serving/emit"]
        assert sum(a["tokens"] for a in emits) == 5
        assert sum(a["finished"] for a in emits) == 1
        assert out_c.value() - out0 == 5
        shadow = shadow_c.value() - shadow0
        if mode == "thread":
            # the first token at once, the last at the idle flush
            assert [a["deferred"] for a in emits] == [0, 1, 1, 1, 0]
            assert shadow == 3
        else:
            assert [a["deferred"] for a in emits] == [0] * 5
            assert shadow == 0
        srv.close()


# ---------------------------------------------------------------------------
# a step ahead: the driver thread enqueues decode step n+1 before step n's
# tokens are on the host, whenever nobody waits for the device. Same tokens
# as a step-driven engine, whatever ends a request under a step in flight
# ---------------------------------------------------------------------------


def watch_steps(srv):
    """Every decode step's operands, in the order of enqueue: (ahead, the
    rids of its rows, its packed tokens column, requests queued). And no
    step in flight ever sees a preemption or a cache eviction."""
    log = []
    operands, preempt = srv._decode_operands, srv.sched._preempt_one

    def spy_operands(ready, ahead=False):
        packed = operands(ready, ahead)
        log.append((bool(ahead), [r.rid for r in ready],
                    packed[:, srv.blocks_per_seq + 1].copy(),
                    len(srv.sched.queued)))
        return packed

    def spy_preempt(exclude):
        assert srv._flight is None, "preempted under a step in flight"
        return preempt(exclude)

    srv._decode_operands = spy_operands
    srv.sched._preempt_one = spy_preempt
    return log


def steps_with(log, handle, ahead=None):
    return sum(handle.request_id in rids for a, rids, *_ in log
               if ahead is None or a == ahead)


def stream_with_a_new_token(tiny_engine):
    """(prompt, sampling kwargs, the stream of 12 tokens, the index of its
    first token from the third decode step on that no earlier one equals):
    an ``eos_token_id`` that ends the request there and nowhere before."""
    prompt = np.arange(3, 14, dtype=np.int32)
    sampled = dict(temperature=1.2, seed=5)
    whole = serving(tiny_engine, prefix_cache=False)
    stream = list(whole.submit(prompt, max_new_tokens=12, **sampled).result())
    whole.close()
    at = next(i for i in range(3, 12) if stream[i] not in stream[:i])
    return prompt, sampled, stream, at


@pytest.fixture(scope="module")
def tiny_moe_engine():
    return init_inference("tiny-olmoe", dtype=jnp.float32,
                          max_out_tokens=128)


@pytest.fixture(scope="module")
def tiny_recurrent_engine():
    return init_inference("tiny-nemotron-3-super", dtype=jnp.float32,
                          max_out_tokens=128)


AHEAD_CASES = {
    # name: (engine fixture, engine config, [(prompt length, submit kwargs)],
    #        whether some step must have gone ahead)
    "greedy": ("tiny_engine", {}, DELIVERY_CASES["greedy"][1], True),
    "sampled": ("tiny_engine", {}, DELIVERY_CASES["sampled"][1], True),
    "eos": ("tiny_engine", {}, DELIVERY_CASES["eos"][1], True),
    "budget": ("tiny_engine", {},
               [(9, dict(max_new_tokens=3)), (14, dict(max_new_tokens=11)),
                (21, dict(max_new_tokens=2)), (6, dict(max_new_tokens=7))],
               True),
    "one_token": ("tiny_engine", {}, DELIVERY_CASES["one_token"][1], True),
    # every page taken: a row that needs its next one finds the free list
    # empty, and the step that evicts for it is never one ahead
    "small_pool": ("tiny_engine", dict(num_blocks=10, prefix_cache=False),
                   DELIVERY_CASES["preempted"][1], None),
    # more requests than rows: while one waits, no step goes ahead
    "queued": ("tiny_engine", dict(max_seqs=2),
               DELIVERY_CASES["greedy"][1], True),
    "recurrent": ("tiny_recurrent_engine", dict(prefix_cache=False),
                  [(13, dict(max_new_tokens=7)), (37, dict(max_new_tokens=5)),
                   (8, dict(max_new_tokens=9, temperature=0.9, seed=4))],
                  True),
    "moe": ("tiny_moe_engine", {},
            [(13, dict(max_new_tokens=7)), (37, dict(max_new_tokens=5)),
             (8, dict(max_new_tokens=9, temperature=0.9, seed=4))], True),
}


class TestStepAhead:
    @pytest.mark.parametrize("case", sorted(AHEAD_CASES))
    def test_streams_are_those_of_a_step_driven_engine(self, request, case):
        fixture, cfg, specs, must_go_ahead = AHEAD_CASES[case]
        engine = request.getfixturevalue(fixture)
        if case in DELIVERY_CASES:
            _, reqs = delivery_requests(engine, case)
        else:
            rng = np.random.RandomState(sum(map(ord, case)))
            reqs = [(rng.randint(0, 250, (n,)).astype(np.int32), kw)
                    for n, kw in specs]
        streams, ahead = {}, {}
        for mode in ("step", "thread", "driver_loop"):
            srv = serving(engine, **cfg)
            log = watch_steps(srv)
            try:
                handles = [srv.submit(p, **kw) for p, kw in reqs]
                if mode == "step":
                    srv.run()
                elif mode == "thread":
                    srv.start()
                else:
                    drive_on_this_thread(srv)
                streams[mode] = [list(h.result(timeout_s=120.0))
                                 for h in handles]
                srv.stop()
                assert all(h.done and h.state == "finished"
                           and h.tokens == h._req.generated for h in handles)
                assert srv._flight is None and not srv._undelivered
                assert not srv.sched.running and not srv._handles
                # every page came back, once (a double free raises)
                assert srv.alloc.blocks_in_use == (
                    srv.prefix.cached_blocks if srv.prefix else 0)
                ahead[mode] = sum(a for a, *_ in log)
                for a, rids, tokens, queued in log:
                    rows = [h._req for h in handles if h.request_id in rids]
                    assert len(rows) == len(rids)
                    if a:
                        # nobody waited, and every token stays on the device
                        assert queued == 0
                        assert sorted(tokens)[:len(rids)] == [-1] * len(rids)
                        assert (tokens <= 0).all()
                    else:
                        assert (tokens >= 0).all()
                if mode != "step":
                    for h, (_, kw) in zip(handles, reqs):
                        # a row sits in one step a decoded token; under the
                        # driver an end by eos_token_id adds the one step
                        # that was ahead of it, whose token is dropped
                        decoded = len(h.tokens) - 1
                        by_eos = len(h.tokens) < kw["max_new_tokens"]
                        assert decoded <= steps_with(log, h) \
                            <= decoded + by_eos
            finally:
                srv.close()
        assert streams["thread"] == streams["step"]
        assert streams["driver_loop"] == streams["step"]
        assert ahead["step"] == 0
        if must_go_ahead:
            assert ahead["thread"] > 0 and ahead["driver_loop"] > 0

    def test_an_end_by_eos_drops_the_token_of_the_step_ahead(self,
                                                             tiny_engine):
        prompt, sampled, greedy, at = stream_with_a_new_token(tiny_engine)
        srv = serving(tiny_engine, prefix_cache=False)
        log = watch_steps(srv)
        applied = []
        apply_ = srv._apply
        srv._apply = lambda req, token, first=False: (
            applied.append(token), apply_(req, token, first))[1]
        try:
            h = srv.submit(prompt, max_new_tokens=12,
                           eos_token_id=int(greedy[at]), **sampled)
            other = srv.submit(np.arange(40, 60, dtype=np.int32),
                               max_new_tokens=12)
            for _ in range(12):
                if not h._req.done:
                    srv._iterate(defer=True)
            assert h._req.done
            # the step ahead of the one that brought the eos holds the row
            flight = srv._flight
            assert flight is not None
            assert h._req in [r for r, _ in flight.rows]
            assert h._req.row is None and not h._req.blocks
            n_applied = len(applied)
            drive_on_this_thread(srv)
            assert h.tokens == greedy[:at + 1]
            assert steps_with(log, h) == at + 1     # `at` decoded + 1 dropped
            # the dropped token was never applied: the rest are `other`'s
            assert len(other.tokens) == 12
            assert len(applied) == at + 1 + 12 and n_applied < len(applied)
            assert srv.alloc.blocks_in_use == 0
        finally:
            srv.close()

    def test_a_row_that_ends_by_its_budget_is_left_out_of_the_step_ahead(
            self, tiny_engine):
        srv = serving(tiny_engine)
        log = watch_steps(srv)
        try:
            short = srv.submit(np.arange(9), max_new_tokens=4)
            long = srv.submit(np.arange(5, 19), max_new_tokens=10)
            drive_on_this_thread(srv)
            assert len(short.tokens) == 4 and len(long.tokens) == 10
            # one step a decoded token, none dropped
            assert steps_with(log, short) == 3 and steps_with(log, long) == 9
            assert steps_with(log, short, ahead=True) > 0
            assert steps_with(log, long, ahead=True) > steps_with(
                log, short, ahead=True)
        finally:
            srv.close()

    @pytest.mark.parametrize("how", ["cancel", "deadline"])
    def test_a_stream_cut_with_a_step_in_flight(self, tiny_engine, how):
        """The cancel brings the step home and streams its token before it
        ends the stream; the deadline is seen at the next iteration, which
        fetches first. Either way the stream is the uncut one's beginning,
        and every page comes back."""
        from deepspeed_tpu.serving import DeadlineExceeded

        prompt = np.arange(1, 30, dtype=np.int32)
        whole = serving(tiny_engine, prefix_cache=False)
        want = list(whole.submit(prompt, max_new_tokens=40).result())
        whole.close()
        clk = FakeClock()
        srv = serving(tiny_engine, clock=clk, prefix_cache=False)
        try:
            h = srv.submit(prompt, max_new_tokens=40, deadline_s=5.0)
            other = srv.submit(np.arange(3, 20, dtype=np.int32),
                               max_new_tokens=40)
            for _ in range(8):
                srv._iterate(defer=True)
                if srv._flight is not None \
                        and srv._flight.since is not None:
                    break
            in_flight = srv._flight             # a step that went ahead
            assert [r for r, _ in in_flight.rows] == [h._req, other._req]
            seen = len(h.tokens)
            assert seen == len(h._req.generated)    # delivered at once
            if how == "cancel":
                assert h.cancel()
                assert srv._flight is None
                assert len(h.tokens) == seen + 1    # the step's token came
                with pytest.raises(RequestCancelled):
                    h.result()
            else:
                clk.advance(10.0)
                srv._iterate(defer=True)        # not ahead: fetch alone
                assert srv._flight is None and h.state == "decode"
                assert len(h._req.generated) == seen + 1
                srv._iterate(defer=True)        # today's: the expiry
                assert h.state == "deadline_exceeded"
                with pytest.raises(DeadlineExceeded):
                    h.result()
            assert h.done and h.tokens == h._req.generated
            assert h.tokens == want[:len(h.tokens)] and len(h.tokens) >= 3
            other.cancel()
            assert other.tokens == other._req.generated
            assert srv.alloc.blocks_in_use == 0
        finally:
            srv.close()

    def test_an_admission_finds_the_enqueues_in_today_s_order(
            self, tiny_engine, monkeypatch):
        """With a step in flight and a request queued, the iteration
        fetches and enqueues nothing; the next is today's: the chunk on an
        idle device, the kept tokens delivered behind its enqueue, the
        decode step behind its fetch. The step behind THAT goes ahead."""
        srv = serving(tiny_engine)
        events = TestDeferredDelivery._spy(monkeypatch, srv)
        try:
            a = srv.submit(np.arange(7), max_new_tokens=12)
            drive_on_this_thread(srv, iterations=3)
            assert srv._flight is not None and len(a.tokens) == 3
            b = srv.submit(np.arange(9), max_new_tokens=8)
            del events[:]
            drive_on_this_thread(srv, iterations=4)
            ra, rb = a.request_id, b.request_id
            assert events == [
                ("fetched", "decode"), ("iteration_end",),
                ("dispatch", "prefill_chunk"), ("push", ra, 3),
                ("fetched", "prefill_chunk"),
                ("push", rb, 0), ("dispatch", "decode"), ("iteration_end",),
                ("dispatch", "decode"), ("fetched", "decode"),
                ("push", ra, 4), ("push", rb, 1), ("iteration_end",),
                ("dispatch", "decode"), ("fetched", "decode"),
                ("push", ra, 5), ("push", rb, 2), ("iteration_end",)]
            drive_on_this_thread(srv)
            assert len(a.tokens) == 12 and len(b.tokens) == 8
        finally:
            srv.close()

    @pytest.mark.parametrize("how", ["stop", "close", "idle", "step",
                                     "score_logprobs"])
    def test_whoever_needs_a_settled_engine_brings_the_step_home(
            self, tiny_engine, how):
        srv = serving(tiny_engine, prefix_cache=False)
        h = srv.submit(np.arange(7), max_new_tokens=6)
        drive_on_this_thread(srv, iterations=3)
        # the prompt's chunk and a step, then two steps ahead: the second
        # of them is enqueued, the first's token delivered
        assert srv._flight is not None and len(h.tokens) == 3
        if how == "idle":
            # a request that ends by its eos_token_id under a step ahead
            # leaves that step in flight with nothing else to do
            prompt, sampled, stream, at = stream_with_a_new_token(
                tiny_engine)
            srv.cancel(h)
            e = srv.submit(prompt, max_new_tokens=12,
                           eos_token_id=stream[at], **sampled)
            for _ in range(12):
                if not e._req.done:
                    srv._iterate(defer=True)
            assert srv._flight is not None and srv.in_flight() == 0
            srv.start()
            assert e.result(timeout_s=60.0).tolist() == stream[:at + 1]
            for _ in range(500):
                if srv._flight is None:
                    break
                time.sleep(0.01)
            assert srv._flight is None      # and no stop() was needed
            srv.stop()
        elif how == "step":
            srv.step()                  # the fetch, and a whole iteration
            assert srv._flight is None and len(h.tokens) == 5
        elif how == "score_logprobs":
            srv.score_logprobs(np.arange(20))
            assert srv._flight is None and len(h.tokens) == 4
        else:
            srv.stop() if how == "stop" else srv.close()
            assert srv._flight is None and len(h.tokens) == 4
        srv.run()
        assert h.done and not srv._undelivered
        assert len(h.tokens) == (4 if how == "idle" else 6)
        assert srv._flight is None and srv.alloc.blocks_in_use == 0
        srv.close()

    @pytest.mark.parametrize("model", ["dense", "moe"])
    def test_the_ahead_count_the_counter_and_the_one_transfer(
            self, request, obs_session, model):
        from deepspeed_tpu.observability import recorded_spans

        engine = request.getfixturevalue(
            "tiny_engine" if model == "dense" else "tiny_moe_engine")
        counter = get_registry().counter("serving/steps_enqueued_ahead")
        before = counter.value()
        srv = serving(engine)
        srv.start()
        h = srv.submit(np.arange(7), max_new_tokens=6)
        assert len(h.result(timeout_s=60.0)) == 6
        srv.stop()
        spans = recorded_spans()
        steps = [s["attrs"] for s in spans if s["name"] == "serving/decode"
                 and s["attrs"].get("rows")]
        # five decoded tokens: the first step behind the prompt's chunk,
        # four ahead, each of one row
        assert [a["ahead"] for a in steps] == [0, 1, 1, 1, 1]
        assert counter.value() - before == 4
        dispatches = [s["attrs"] for s in spans
                      if s["name"] == "serving/decode/dispatch"]
        assert len(dispatches) == 5
        assert all(a["host_operands"] == 1 for a in dispatches)
        if model == "moe":
            # the counts behind the tokens, read where each step is fetched
            routed = [s["attrs"] for s in spans
                      if s["name"] == "serving/decode"
                      and "moe_assignments" in s["attrs"]]
            assert len(routed) == 5
            assert all(a["moe_assignments"] > 0 for a in routed)
        srv.close()


# ---------------------------------------------------------------------------
# the host's causes: a step enqueued behind a program in flight says whether
# it came too late, a step that was not says which rule held it, and with
# nothing recording none of it is asked. The decisions are the parent's
# ---------------------------------------------------------------------------

# the enqueues (c: a chunk's, d: a decode step's) and fetches (C, D) of
# ``AHEAD_CASES`` under the driver's loop, as the commit before the rules had
# names made them (PR 57: written down from a checkout of PR 56's tree)
PARENT_ORDER = {
    "budget": "cCdDcCdDcdCDcCdDcCdDddDdDdDdDdDD",
    "eos": "cCcCcCcCdDddDdDdDdDdDdDdDdDdDD",
    "greedy": "cCdDcdCDcCdDcdCDcdCDcCdDcCdDcdCDcCdDddDdDdDDddDD",
    "moe": "cCdDcdCDcdCDcCdDcCddDdDDdDddDdDdDD",
    "one_token": "cCcCcCcCcCcCdDddDD",
    "queued": "cCdDcdCDcCdDdDdDdDdDcdCDcCcCdDcCdDdDdDdDdDdDcdCDcCdDddDDddDdDD",
    "recurrent": "cCdDcdCDcdCDcCdDcCddDdDDdDddDdDdDD",
    "sampled": "cCdDcdCDcCdDcCddDdDdDdDDddDD",
    "small_pool": "cCcCdDcdCDcdCDcCdDcdCDcdCDcdCDcCdDcdCDcdCDcdCDcCdDcdCDcCdD"
                  "cdCDcdCDcCdDcCdDdDdDcdCDcdCDcdCDcCdDddDDddDdDdDdDdDD",
}


# the chunks of each case that go ahead of their decode step's fetch: the
# chunks but the first of every prompt whose step went behind the chunk before
CHUNKS_AHEAD = {"budget": 1, "eos": 0, "greedy": 4, "moe": 2, "one_token": 0,
                "queued": 3, "recurrent": 2, "sampled": 1, "small_pool": 13}

ORDER_LETTER = {("dispatch", "prefill_chunk"): "c",
                ("dispatch", "decode"): "d",
                ("fetched", "prefill_chunk"): "C",
                ("fetched", "decode"): "D"}


def decode_spans(mark=0):
    from deepspeed_tpu.observability import recorded_spans

    return [s["attrs"] for s in recorded_spans()[mark:]
            if s["name"] in ("serving/decode", "serving/verify")]


def _grab_free_pages(srv, leave=0):
    return srv.alloc.alloc(srv.alloc.blocks_free - leave)


# name -> (engine config, what brings about the state its rule's docstring
# describes, from two rows that decode with a step in flight ahead; returns
# what to undo afterwards, or None). The iteration behind it fetches the
# step in flight with ``held_by`` the name
def _held_row_freed(srv, rows):
    srv.sched.rows_released += 1        # as a request's end counts it


def _held_queued(srv, rows):
    srv.submit(np.arange(9, dtype=np.int32), max_new_tokens=2)


def _held_fork(srv, rows):
    srv._pending_forks[10 ** 6] = [rows[0]._req]    # a sibling waits
    return lambda: srv._pending_forks.pop(10 ** 6)


def _held_prefill(srv, rows):
    # admitted, not yet a row of any step: what a request in prefill is
    srv.submit(np.arange(40, dtype=np.int32), max_new_tokens=2)
    srv.sched.admit()


def _held_drafter(srv, rows):
    srv.spec_suspended = False          # the fleet's ladder steps back up
    return lambda: setattr(srv, "spec_suspended", True)


def _held_deadline(srv, rows):
    srv.clock.advance(100.0)            # past the first row's deadline


def _held_ends(srv, rows):
    for h in rows:                      # every row ends at the step in flight
        h._req.max_new_tokens = len(h._req.generated) + 1


def _held_pages(srv, rows):
    # until the position behind the step in flight needs a new page
    while rows[0]._req.length + 2 <= 16 * len(rows[0]._req.blocks):
        srv._iterate(defer=True)
    assert srv._flight is not None and srv._flight.since is not None
    ids = _grab_free_pages(srv)
    return lambda: srv.alloc.free(ids)


def _held_cow(srv, rows):
    shared = [rows[0]._req.blocks[-1]]  # another holder of its write block
    srv.alloc.incref(shared)
    return lambda: srv.alloc.free(shared)


HELD_BY_CASES = {
    "row_freed": ({}, _held_row_freed),
    "queued": ({}, _held_queued),
    "fork": ({}, _held_fork),
    "prefill": ({}, _held_prefill),
    "drafter": (dict(speculative={"mode": "ngram", "num_draft_tokens": 2}),
                _held_drafter),
    "deadline": ({}, _held_deadline),
    "ends": ({}, _held_ends),
    "pages": (dict(prefix_cache=False), _held_pages),
    "cow": ({}, _held_cow),
}


class TestHostCauses:
    @pytest.mark.parametrize("recording", [False, True],
                             ids=["untraced", "recorded"])
    @pytest.mark.parametrize("case", sorted(AHEAD_CASES))
    def test_the_order_of_enqueues_and_fetches_is_the_parents(
            self, request, tmp_path, monkeypatch, case, recording):
        """Name for name, with nothing recording and with every span
        recorded: a rule that gives its name decides as the bare one did."""
        fixture, cfg, specs, _ = AHEAD_CASES[case]
        engine = request.getfixturevalue(fixture)
        if case in DELIVERY_CASES:
            _, reqs = delivery_requests(engine, case)
        else:
            rng = np.random.RandomState(sum(map(ord, case)))
            reqs = [(rng.randint(0, 250, (n,)).astype(np.int32), kw)
                    for n, kw in specs]
        reset_session()
        if recording:
            configure_observability(ObservabilityConfig(
                enabled=True, output_dir=str(tmp_path / "obs"),
                flight_recorder=False))
        try:
            srv = serving(engine, **cfg)
            events = TestDeferredDelivery._spy(monkeypatch, srv)
            handles = [srv.submit(p, **kw) for p, kw in reqs]
            drive_on_this_thread(srv)
            assert all(h.done for h in handles)
            srv.close()
        finally:
            reset_session()
        order = "".join(ORDER_LETTER[e] for e in events if e in ORDER_LETTER)
        # since PR 58 a continuing prompt's next chunk is enqueued between
        # its chunk's fetch and the fetch of the step behind that chunk:
        # put behind that fetch again, every enqueue and fetch is where the
        # parent had it
        assert order.count("CcD") == CHUNKS_AHEAD[case]
        assert order.replace("CcD", "CDc") == PARENT_ORDER[case]

    @pytest.mark.parametrize("name", sorted(HELD_BY_CASES))
    def test_held_by_names_the_rule_that_held_the_step(self, tiny_engine,
                                                       obs_session, name):
        from deepspeed_tpu.serving.api import HELD_BY

        cfg, arrange = HELD_BY_CASES[name]
        clock = FakeClock()
        srv = serving(tiny_engine, clock=clock, **cfg)
        srv.spec_suspended = True       # a drafter, where there is one,
        #   proposes only once its case says so
        try:
            rows = [srv.submit(np.arange(3, 18, dtype=np.int32),
                               max_new_tokens=40, deadline_s=50.0),
                    srv.submit(np.arange(4, 19, dtype=np.int32),
                               max_new_tokens=40)]
            drive_on_this_thread(srv, iterations=5)
            assert srv._flight is not None and srv._flight.since is not None
            assert all(h.state == "decode" for h in rows)
            undo = arrange(srv, rows)
            mark = len(decode_spans())
            srv._iterate(defer=True)
            (fetched,) = decode_spans()[mark:]
            assert fetched["held_by"] == name and name in HELD_BY
            # the span holds the fetch alone: it dispatched nothing
            assert "rows" not in fetched and "late" not in fetched
            assert srv._flight is None
            if undo:
                undo()
        finally:
            for h in rows:
                h.cancel()
            srv.close()

    def test_held_by_step_mode_and_the_chunks_own_prefill(self, tiny_engine,
                                                          obs_session):
        """``step()`` fetches what it enqueued: ``step_mode``. And a step
        enqueued behind its chunk is fetched in its own iteration, a request
        being in prefill: ``prefill``, without the rules being asked."""
        srv = serving(tiny_engine, prefix_cache=False)
        a = srv.submit(np.arange(7, dtype=np.int32), max_new_tokens=40)
        srv.step()
        srv.step()
        steps = decode_spans()
        assert [s.get("held_by") for s in steps] == ["step_mode"] * 2
        assert [s["chunk_first_by"] for s in steps[:1]] == ["step_mode"]
        assert "chunk_first_by" not in steps[1]     # no chunk ran
        asked = []
        held = srv._ahead_held_by
        srv._ahead_held_by = lambda flight: asked.append(1) or held(flight)
        srv.submit(np.arange(40, dtype=np.int32), max_new_tokens=2)
        mark = len(steps)
        srv._iterate(defer=True)
        behind, fetched = decode_spans()[mark:]
        assert behind["behind_chunk"] == 1 and "held_by" not in behind
        assert fetched["held_by"] == "prefill" and not asked
        a.cancel()
        srv.close()

    @pytest.mark.parametrize("name", ["last_chunk", "pages", "no_rows",
                                      "drafter", "more_chunks", "step_mode"])
    def test_chunk_first_by_names_the_rule_that_had_the_chunk_fetched_first(
            self, tiny_engine, obs_session, name):
        from deepspeed_tpu.serving.api import CHUNK_FIRST_BY

        spec = (dict(speculative={"mode": "ngram", "num_draft_tokens": 2})
                if name == "drafter" else {})
        srv = never_ahead(serving(tiny_engine, prefix_cache=False, **spec))
        srv.spec_suspended = True
        try:
            if name != "no_rows":
                # one row that decodes, its next token in need of a page
                a = srv.submit(np.arange(3, 18, dtype=np.int32),
                               max_new_tokens=40)
                drive_on_this_thread(srv, iterations=1)
                assert a.state == "decode" and a._req.length == 16
            prompt = np.arange(40 if name in ("pages", "no_rows", "drafter")
                               else 9, dtype=np.int32)
            srv.submit(prompt, max_new_tokens=4)
            ids = []
            if name == "pages":
                ids = _grab_free_pages(srv, leave=1)    # the chunk's own
            elif name == "more_chunks":
                srv.prefill_chunks_per_iter = 2
            elif name == "drafter":
                srv.spec_suspended = False
            mark = len(decode_spans())
            if name == "step_mode":
                srv.step()
            else:
                srv._iterate(defer=True)
            srv.alloc.free(ids)
            assert srv._chunk_first == name and name in CHUNK_FIRST_BY
            steps = decode_spans()[mark:]
            if name == "no_rows":
                assert not steps        # no step: the name is the engine's
            else:
                dispatched = [s for s in steps if s.get("rows")]
                assert [s["chunk_first_by"] for s in dispatched] == [name]
                assert not any(s.get("behind_chunk") for s in steps)
        finally:
            srv.close()

    def test_a_step_behind_its_chunk_or_ahead_carries_neither(
            self, tiny_engine, obs_session):
        srv = serving(tiny_engine, prefix_cache=False)
        a, b = decoding_pair(srv)
        srv.submit(np.arange(50, 90, dtype=np.int32), max_new_tokens=2)
        drive_on_this_thread(srv)
        went = [s for s in decode_spans()
                if s.get("ahead") or s.get("behind_chunk")]
        assert sum(s["ahead"] for s in went) > 3
        assert sum(s["behind_chunk"] for s in went) == 2
        assert not any("chunk_first_by" in s for s in went)
        # a span that enqueued ahead fetches the step BEFORE: nothing held
        # that one's successor
        assert not any("held_by" in s for s in went)
        srv.close()

    @pytest.mark.parametrize("told", [False, True], ids=["in_time", "late"])
    def test_late_says_whether_the_program_in_flight_had_ended(
            self, tiny_engine, obs_session, monkeypatch, told):
        """``is_ready()`` of the tokens the engine holds, told what to say:
        ``late`` on the spans with ``ahead`` or ``behind_chunk`` and on no
        other, and the counter beside ``serving/steps_enqueued_ahead``. A
        chunk enqueued ahead asks once too, and says so on both its spans:
        ``TestChunkAhead``."""
        from deepspeed_tpu.observability import recorded_spans

        srv = serving(tiny_engine, prefix_cache=False)
        asked = []
        monkeypatch.setattr(type(srv._last_tokens), "is_ready",
                            lambda self: asked.append(1) or told)
        late = get_registry().counter("serving/steps_enqueued_late")
        before = late.value()
        a, b = decoding_pair(srv)
        srv.submit(np.arange(50, 90, dtype=np.int32), max_new_tokens=2)
        drive_on_this_thread(srv)
        steps = decode_spans()
        behind = [s for s in steps if s.get("ahead") or s.get("behind_chunk")]
        chunks = [s["attrs"] for s in recorded_spans()
                  if s["name"] == "serving/prefill_chunk"
                  and s["attrs"]["ahead"] and "tokens" in s["attrs"]]
        assert len(chunks) == 2
        assert len(behind) > 5 and len(asked) == len(behind) + len(chunks)
        assert all(s["late"] == int(told) for s in behind + chunks)
        assert not any("late" in s for s in steps if s not in behind)
        assert late.value() - before == (len(behind) if told else 0)
        srv.close()

    def test_nothing_recording_asks_nothing(self, tiny_engine, monkeypatch):
        """No clock of the tracer's, no ``is_ready()``, no walk over the
        operands and no allocator statistics: each fails the test if it
        runs. The streams are those of the recorded run above."""
        from deepspeed_tpu.serving import api

        reset_session()
        assert not get_session().enabled
        srv = serving(tiny_engine, prefix_cache=False)
        for mod, attr in ((time, "thread_time_ns"), (api, "_host_operands"),
                          (api, "hbm_counts")):
            monkeypatch.setattr(mod, attr, lambda *a, _n=attr: pytest.fail(
                f"{_n} ran with nothing recording"))
        monkeypatch.setattr(
            type(srv._last_tokens), "is_ready",
            lambda self: pytest.fail("is_ready() with nothing recording"))
        a, b = decoding_pair(srv, max_new_tokens=12)
        c = srv.submit(np.arange(50, 90, dtype=np.int32), max_new_tokens=2)
        drive_on_this_thread(srv)
        assert [len(h.tokens) for h in (a, b, c)] == [12, 12, 2]
        srv.close()

    def test_the_operands_are_counted_before_the_dispatch_opens(
            self, tiny_engine, obs_session, monkeypatch):
        """The parameters' and the arena's leaves once a program name, the
        step's own at every dispatch, and none of it inside
        ``<name>/dispatch``."""
        from deepspeed_tpu.observability import recorded_spans
        from deepspeed_tpu.serving import api

        walked = []
        count = api._host_operands

        def spy(call_args):
            tracer = get_session().tracer
            walked.append((len(call_args), tracer.current_name(),
                           [s.name for s in tracer._local.stack][-1:]))
            return count(call_args)

        monkeypatch.setattr(api, "_host_operands", spy)
        srv = serving(tiny_engine, prefix_cache=False)
        h = srv.submit(np.arange(7, dtype=np.int32), max_new_tokens=5)
        drive_on_this_thread(srv)
        assert len(h.tokens) == 5
        dispatches = [s for s in recorded_spans()
                      if s["name"].endswith("/dispatch")]
        assert len(dispatches) == 5         # a chunk and four steps
        assert all(s["attrs"]["host_operands"] == 1
                   and s["attrs"]["host_operand_bytes"] > 0
                   for s in dispatches)
        # (params, arena) twice in all; (packed, key[, tokens]) a dispatch
        assert sorted(n for n, *_ in walked) == [2, 2, 2, 3, 3, 3, 3]
        assert not any(open_[-1:] == [name + "/dispatch"]
                       for _, name, open_ in walked)
        srv.close()

    def test_the_allocators_statistics_every_sixteenth_iteration(
            self, tiny_engine, obs_session, monkeypatch):
        from deepspeed_tpu.observability import recorded_spans
        from deepspeed_tpu.serving import api

        monkeypatch.setattr(api, "hbm_counts", lambda: {
            "hbm_bytes_in_use": 5, "hbm_peak_bytes": 7})
        srv = serving(tiny_engine, prefix_cache=False)
        h = srv.submit(np.arange(7, dtype=np.int32), max_new_tokens=40)
        drive_on_this_thread(srv)
        its = [s["attrs"] for s in recorded_spans()
               if s["name"] == "serving/iteration"]
        assert len(its) == 40
        assert [a["it"] for a in its if "hbm_peak_bytes" in a] == [0, 16, 32]
        assert all("gc_collections" in a and a["gc_pause_us"] >= 0
                   for a in its)
        srv.close()


# ---------------------------------------------------------------------------
# a decode step behind its chunk: under the driver thread an iteration whose
# chunk is not its prompt's last enqueues its decode step with the chunk
# still in flight, and fetches and applies the chunk in that step's shadow.
# Same programs, same operands, same order on the device, same tokens
# ---------------------------------------------------------------------------


def watch_chunks(srv):
    """Every decode step's rids and whether it was enqueued behind a chunk
    in flight, in the order of enqueue. And with a chunk in flight nobody
    is preempted and no block copied."""
    log = []
    operands, preempt, cow = (srv._decode_operands, srv.sched._preempt_one,
                              srv._make_writable)

    def spy_operands(ready, ahead=False):
        log.append((srv._chunk is not None, [r.rid for r in ready]))
        return operands(ready, ahead)

    def spy_preempt(exclude):
        assert srv._chunk is None, "preempted under a chunk in flight"
        return preempt(exclude)

    def spy_cow(req, start, end, optional=False):
        copies = srv._cow_copies
        out = cow(req, start, end, optional)
        assert srv._chunk is None or srv._cow_copies == copies, \
            "a block copied under a chunk in flight"
        return out

    srv._decode_operands = spy_operands
    srv.sched._preempt_one = spy_preempt
    srv._make_writable = spy_cow
    return log


@pytest.fixture(scope="module")
def tiny_kda_engine():
    return init_inference("tiny-solar-open2", dtype=jnp.float32,
                          max_out_tokens=128)


@pytest.fixture(scope="module")
def tiny_mamba1_engine():
    return init_inference("tiny-phi4flash", dtype=jnp.float32,
                          max_out_tokens=128)


def decoding_pair(srv, n=(7, 9), max_new_tokens=40):
    """Two requests of one chunk each, driven until both decode."""
    rows = [srv.submit(np.arange(3 + i, 3 + i + k, dtype=np.int32),
                       max_new_tokens=max_new_tokens)
            for i, k in enumerate(n)]
    drive_on_this_thread(srv, iterations=len(n) + 1)
    assert all(h.state == "decode" for h in rows)
    return rows


# prompts of 2-4 chunks of 16 beside short ones, more requests than rows
BEHIND_PROMPTS = [(40, dict(max_new_tokens=9)), (7, dict(max_new_tokens=12)),
                  (55, dict(max_new_tokens=6)),
                  (23, dict(max_new_tokens=8, temperature=0.9, seed=4)),
                  (37, dict(max_new_tokens=7)), (64, dict(max_new_tokens=5))]

BEHIND_CASES = {
    # name: (engine fixture, engine config[, prompts])
    "dense": ("tiny_engine", {}),
    "dense_no_cache": ("tiny_engine", dict(prefix_cache=False)),
    "moe": ("tiny_moe_engine", {}),
    "kda": ("tiny_kda_engine", dict(prefix_cache=False)),
    "mamba2": ("tiny_recurrent_engine", dict(prefix_cache=False)),
    "mamba1": ("tiny_mamba1_engine", dict(prefix_cache=False)),
    # rule 4: a pool so small that decode rows must preempt to grow. Where
    # they would, the chunk is fetched and applied first, as today
    "small_pool": ("tiny_engine", dict(num_blocks=10, prefix_cache=False),
                   DELIVERY_CASES["preempted"][1]),
}


class TestDecodeBehindChunk:
    @pytest.mark.parametrize("case", sorted(BEHIND_CASES))
    def test_streams_are_those_of_a_step_driven_engine(self, request, case):
        fixture, cfg, *prompts = BEHIND_CASES[case]
        engine = request.getfixturevalue(fixture)
        rng = np.random.RandomState(sum(map(ord, case)))
        reqs = [(rng.randint(0, 250, (n,)).astype(np.int32), kw)
                for n, kw in (prompts[0] if prompts else BEHIND_PROMPTS)]
        streams, behind = {}, {}
        for mode in ("step", "thread", "driver_loop"):
            srv = serving(engine, **cfg)
            log = watch_chunks(srv)
            try:
                handles = [srv.submit(p, **kw) for p, kw in reqs]
                if mode == "step":
                    srv.run()
                elif mode == "thread":
                    srv.start()
                else:
                    drive_on_this_thread(srv)
                streams[mode] = [list(h.result(timeout_s=120.0))
                                 for h in handles]
                srv.stop()
                assert all(h.done and h.state == "finished"
                           and h.tokens == h._req.generated for h in handles)
                assert srv._chunk is None and srv._flight is None
                assert not srv.sched.running and not srv._undelivered
                assert srv.alloc.blocks_in_use == (
                    srv.prefix.cached_blocks if srv.prefix else 0)
                behind[mode] = sum(b for b, _ in log)
                if case == "small_pool":
                    assert srv.sched.preemption_count > 0
            finally:
                srv.close()
        assert streams["thread"] == streams["step"]
        assert streams["driver_loop"] == streams["step"]
        assert behind["step"] == 0
        assert behind["thread"] > 0 and behind["driver_loop"] > 0

    def test_the_step_is_dispatched_before_a_chunk_that_is_not_the_last_is_fetched(
            self, tiny_engine, monkeypatch):
        """A prompt of three chunks beside two rows that decode: the decode
        step's enqueue comes before the chunk's fetch for chunks one and two
        (and the kept tokens are delivered with both programs enqueued), and
        behind the third's, whose token is a first token."""
        srv = never_ahead(serving(tiny_engine, prefix_cache=False))
        events = TestDeferredDelivery._spy(monkeypatch, srv)
        log = watch_chunks(srv)
        try:
            a, b = decoding_pair(srv)
            c = srv.submit(np.arange(50, 90, dtype=np.int32),
                           max_new_tokens=4)
            na, nb = len(a.tokens), len(b.tokens)
            del events[:], log[:]
            drive_on_this_thread(srv, iterations=3)
            ra, rb, rc = a.request_id, b.request_id, c.request_id
            want = []
            for it in range(2):
                want += [("dispatch", "prefill_chunk"), ("dispatch", "decode"),
                         ("push", ra, na + it), ("push", rb, nb + it),
                         ("fetched", "prefill_chunk"), ("fetched", "decode"),
                         ("iteration_end",)]
            want += [("dispatch", "prefill_chunk"),
                     ("push", ra, na + 2), ("push", rb, nb + 2),
                     ("fetched", "prefill_chunk"), ("push", rc, 0),
                     ("dispatch", "decode"), ("fetched", "decode"),
                     ("iteration_end",)]
            assert events == want
            assert [b for b, _ in log] == [True, True, False]
            assert [rids for _, rids in log] == [[ra, rb], [ra, rb],
                                                 [ra, rb, rc]]
            drive_on_this_thread(srv)
            assert len(c.tokens) == 4
        finally:
            srv.close()

    def test_step_keeps_today_s_order(self, tiny_engine, monkeypatch):
        """Rule 1: ``step()`` fetches and applies every chunk before it
        prepares the decode step, and leaves a settled engine."""
        srv = serving(tiny_engine, prefix_cache=False)
        events = TestDeferredDelivery._spy(monkeypatch, srv)
        log = watch_chunks(srv)
        try:
            a = srv.submit(np.arange(3, 10, dtype=np.int32),
                           max_new_tokens=40)
            srv.step()
            c = srv.submit(np.arange(50, 90, dtype=np.int32),
                           max_new_tokens=4)
            del events[:], log[:]
            for _ in range(3):
                srv.step()
                assert srv._chunk is None and srv._flight is None
                assert not srv._undelivered
            order = [e for e in events if e[0] != "push"]
            assert order == [("dispatch", "prefill_chunk"),
                             ("fetched", "prefill_chunk"),
                             ("dispatch", "decode"), ("fetched", "decode"),
                             ("iteration_end",)] * 3
            assert [b for b, _ in log] == [False] * 3
            assert len(c.tokens) == 2 and len(a.tokens) == 5
        finally:
            srv.close()

    def test_a_drafter_keeps_today_s_order(self, tiny_engine, monkeypatch):
        """Rule 3: a verify step's drafter runs on the host before its
        enqueue; every chunk is fetched first. With the drafter suspended
        the plain decode step goes behind the chunk like any other."""
        srv = never_ahead(serving(
            tiny_engine, prefix_cache=False,
            speculative={"mode": "ngram", "num_draft_tokens": 2}))
        events = TestDeferredDelivery._spy(monkeypatch, srv)
        try:
            a = srv.submit(np.arange(3, 10, dtype=np.int32),
                           max_new_tokens=40)
            drive_on_this_thread(srv, iterations=2)
            srv.submit(np.arange(50, 122, dtype=np.int32), max_new_tokens=4)
            del events[:]
            drive_on_this_thread(srv, iterations=2)
            order = [e for e in events if e[0] != "push"]
            assert order == [("dispatch", "prefill_chunk"),
                             ("fetched", "prefill_chunk"),
                             ("dispatch", "verify"), ("fetched", "verify"),
                             ("iteration_end",)] * 2
            srv.spec_suspended = True
            del events[:]
            drive_on_this_thread(srv, iterations=2)
            order = [e for e in events if e[0] != "push"]
            assert order == [("dispatch", "prefill_chunk"),
                             ("dispatch", "decode"),
                             ("fetched", "prefill_chunk"),
                             ("fetched", "decode"), ("iteration_end",)] * 2
            drive_on_this_thread(srv)
            assert a.done
        finally:
            srv.close()

    def test_only_the_iteration_s_last_chunk_waits(self, tiny_engine,
                                                   monkeypatch):
        """Rule 3: with two chunks an iteration (the live tuner's knob) the
        first is fetched as today; the second waits for the decode step."""
        srv = never_ahead(serving(tiny_engine, prefix_cache=False))
        srv.prefill_chunks_per_iter = 2
        events = TestDeferredDelivery._spy(monkeypatch, srv)
        try:
            decoding_pair(srv)
            srv.submit(np.arange(50, 122, dtype=np.int32), max_new_tokens=4)
            del events[:]
            drive_on_this_thread(srv, iterations=2)
            order = [e for e in events if e[0] != "push"]
            assert order == [("dispatch", "prefill_chunk"),
                             ("fetched", "prefill_chunk"),
                             ("dispatch", "prefill_chunk"),
                             ("dispatch", "decode"),
                             ("fetched", "prefill_chunk"),
                             ("fetched", "decode"), ("iteration_end",)] * 2
        finally:
            srv.close()

    def test_a_row_that_would_preempt_keeps_today_s_order(self, tiny_engine,
                                                          monkeypatch):
        """Rule 4: the pool's last free page goes to the chunk; the decode
        row that needs one has to preempt, so the chunk is fetched and
        applied before the step is prepared, and only then is its request
        evicted. With room again the step goes behind the chunk."""
        srv = never_ahead(serving(tiny_engine, prefix_cache=False,
                                  num_blocks=8))
        events = TestDeferredDelivery._spy(monkeypatch, srv)
        try:
            # 15 prompt tokens: the row needs its second page one step on
            a = srv.submit(np.arange(3, 18, dtype=np.int32),
                           max_new_tokens=40)
            drive_on_this_thread(srv, iterations=1)
            c = srv.submit(np.arange(50, 146, dtype=np.int32),
                           max_new_tokens=4)      # six pages, chunk by chunk
            spare = srv.alloc.alloc(srv.alloc.blocks_free - 1)
            del events[:]
            applied = []
            progress = srv.sched.note_prefill_progress
            srv.sched.note_prefill_progress = lambda req, old, new: (
                applied.append((req.rid, new, srv.sched.preemption_count)),
                progress(req, old, new))[1]
            srv._iterate(defer=True)        # admits c: the last free page
            order = [e for e in events if e[0] != "push"]
            assert order == [("dispatch", "prefill_chunk"),
                             ("fetched", "prefill_chunk"),
                             ("dispatch", "decode"), ("fetched", "decode"),
                             ("iteration_end",)]
            # the chunk's progress was applied, THEN a's page cost c its row
            assert applied == [(c.request_id, 16, 0)]
            assert srv.sched.preemption_count == 1 and c.state == "queued"
            assert a.state == "decode" and len(a._req.blocks) == 2
            srv.alloc.free(spare)
            del events[:]
            srv._iterate(defer=True)        # room again: behind the chunk
            assert [e for e in events if e[0] != "push"][:3] == [
                ("dispatch", "prefill_chunk"), ("dispatch", "decode"),
                ("fetched", "prefill_chunk")]
            assert srv.sched.preemption_count == 1
        finally:
            srv.close()

    def test_unpinned_cache_entries_are_room_and_shared_pages_are_not(self):
        from deepspeed_tpu.serving.paged_kv import PrefixCache

        clock = FakeClock()
        alloc = BlockAllocator(6)
        cache = PrefixCache(alloc, 16)
        sched = Scheduler(ServingConfig(block_size=16, num_blocks=6,
                                        max_seqs=4, max_model_len=64,
                                        prefill_chunk=16),
                          allocator=alloc, clock=clock, prefix_cache=cache)
        row = mk_req(0, n=16, max_new=8)
        row.blocks, row.length = alloc.alloc(1), 16     # needs a 2nd page
        held = alloc.alloc(5)
        assert alloc.blocks_free == 0
        assert not sched.grows_without_preemption([row])
        # two entries only the cache holds: evictable, so room
        for i, bid in enumerate(held[:2]):
            cache.insert_key(bytes([i]), bid)
        alloc.free(held[:2])
        assert cache.can_evict(2) and not cache.can_evict(3)
        assert sched.grows_without_preemption([row])
        in_use = alloc.blocks_in_use
        assert sched.grows_without_preemption([row, row])
        assert not sched.grows_without_preemption([row] * 3)
        assert alloc.blocks_in_use == in_use and cache.cached_blocks == 2
        # an entry a request holds too is pinned
        alloc.incref(held[:1])
        assert not cache.can_evict(2)
        alloc.free(held[:1])
        # the page the next token is written to is shared: a copy first
        row.length = 15
        assert sched.grows_without_preemption([row])
        alloc.incref(row.blocks)
        assert not sched.grows_without_preemption([row])

    @pytest.mark.parametrize("how", ["cancel", "deadline", "preempt"])
    def test_a_request_that_ends_under_its_chunk_gets_no_progress(
            self, tiny_engine, how):
        """Rule 5: between the chunk's enqueue and its apply the request is
        cancelled, expires, or loses its row. Its progress is applied to
        nobody, its pages come back once, and the rows that decode stream
        what they stream without it."""
        want = {}
        for cut in (False, True):
            clk = FakeClock()
            srv = never_ahead(serving(tiny_engine, clock=clk,
                                      prefix_cache=False))
            try:
                a, b = decoding_pair(srv, max_new_tokens=12)
                c = srv.submit(np.arange(50, 90, dtype=np.int32),
                               max_new_tokens=4, deadline_s=5.0)
                settle, cuts = srv._settle, []

                def spy_settle(obs, deferred=False):
                    settle(obs, deferred)
                    if cut and srv._chunk is not None and not cuts:
                        # both programs are enqueued, the chunk not fetched
                        cuts.append(c._req.row)
                        if how == "cancel":
                            assert srv.sched.cancel(c._req)
                        elif how == "deadline":
                            clk.advance(10.0)
                            assert srv._expire_deadlines() == 1
                        else:
                            srv.sched.preempt(c._req)

                srv._settle = spy_settle
                chunks, tokens = srv.prefill_chunks_run, srv.prefill_tokens_run
                srv._iterate(defer=True)
                assert srv._chunk is None and srv._flight is None
                # the chunk ran on the device, whoever it was for
                assert srv.prefill_chunks_run == chunks + 1
                assert srv.prefill_tokens_run == tokens + 16
                if cut:
                    assert len(cuts) == 1
                    assert c._req.prefill_pos == 0 and c._req.length == 0
                    assert c._req.row is None and not c._req.blocks
                    if how == "preempt":
                        assert c.state == "queued"
                        drive_on_this_thread(srv)
                        assert len(c.tokens) == 4
                    else:
                        assert c._req.done
                        srv.cancel(c)       # wakes the handle
                else:
                    assert c._req.prefill_pos == 16
                drive_on_this_thread(srv)
                want[cut] = (a.tokens, b.tokens)
                assert len(a.tokens) == len(b.tokens) == 12
                assert srv.alloc.blocks_in_use == 0
            finally:
                srv.close()
        assert want[True] == want[False]

    @pytest.mark.parametrize("model", ["dense", "moe", "mamba2"])
    def test_the_behind_chunk_count_and_the_counter(self, request,
                                                    obs_session, model):
        from deepspeed_tpu.observability import recorded_spans

        engine = request.getfixturevalue(
            {"dense": "tiny_engine", "moe": "tiny_moe_engine",
             "mamba2": "tiny_recurrent_engine"}[model])
        counter = get_registry().counter(
            "serving/decodes_enqueued_behind_chunk")
        before = counter.value()
        srv = never_ahead(serving(engine, prefix_cache=False))
        log = watch_chunks(srv)
        a, b = decoding_pair(srv)
        c = srv.submit(np.arange(50, 90, dtype=np.int32), max_new_tokens=4)
        del log[:]
        mark = len(recorded_spans())
        drive_on_this_thread(srv, iterations=3)
        spans = recorded_spans()[mark:]
        steps = [s["attrs"] for s in spans if s["name"] == "serving/decode"
                 and s["attrs"].get("rows")]
        assert [a["behind_chunk"] for a in steps] == [1, 1, 0]
        assert [a["ahead"] for a in steps] == [0, 0, 0]
        assert [a["rows"] for a in steps] == [2, 2, 3]
        assert counter.value() - before == 2 == sum(b for b, _ in log)
        # neither program's span inside the other's, and a chunk's counts
        # on the span that holds its fetch, once
        by_id = {s["id"]: s for s in spans}
        top = [s for s in spans
               if s["name"] in ("serving/decode", "serving/prefill_chunk")]
        assert all(by_id[s["parent_id"]]["name"] == "serving/iteration"
                   for s in top)
        kids = {s["id"]: [k["name"].rsplit("/", 1)[1] for k in spans
                          if k.get("parent_id") == s["id"]
                          and k["cat"] == "phase"] for s in top}
        shapes = [(s["name"].split("/")[1], kids[s["id"]]) for s in top]
        half = [("prefill_chunk", ["prepare", "dispatch"]),
                ("decode", ["prepare", "dispatch"]),
                ("prefill_chunk", ["fetch", "apply"]),
                ("decode", ["fetch", "apply"])]
        assert shapes == half * 2 + [
            ("prefill_chunk", ["prepare", "dispatch", "fetch", "apply"]),
            ("decode", ["prepare", "dispatch", "fetch", "apply"])]
        chunks = [s["attrs"] for s in top
                  if s["name"] == "serving/prefill_chunk"]
        assert [a.get("tokens") for a in chunks] == [None, 16, None, 16, 8]
        assert {a["rid"] for a in chunks} == {c.request_id}
        assert [a["chunk_start"] for a in chunks] == [0, 0, 16, 16, 32]
        counted = "moe_assignments" if model == "moe" else (
            "ssm_rows" if model == "mamba2" else None)
        if counted:
            assert [counted in a for a in chunks] == [False, True, False,
                                                      True, True]
            fetched = [s["attrs"] for s in top
                       if s["name"] == "serving/decode"
                       and "fetch" in kids[s["id"]]]
            assert len(fetched) == 3 and all(a[counted] > 0 for a in fetched)
        # the deferred delivery lies with both programs enqueued
        emits = [s for s in spans if s["name"] == "serving/emit"
                 and s["attrs"]["deferred"]]
        assert [by_id[s["parent_id"]]["name"] for s in emits] == [
            "serving/decode", "serving/decode", "serving/prefill_chunk"]
        drive_on_this_thread(srv)
        assert len(c.tokens) == 4
        srv.close()

    @pytest.mark.parametrize("telemetry", ["accountant", "reqtrace"])
    def test_no_second_is_counted_twice(self, tiny_engine, tmp_path,
                                        telemetry):
        """The chunk's interval runs from its enqueue to its fetch, the
        step's behind it from the chunk's fetch to its own."""
        configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path / "obs"),
            recompile_watchdog=False, flight_recorder=False,
            hang_watchdog=False, request_tracing=True,
            trace_sample_rate=1.0, serve_goodput=True))
        try:
            ticks = iter(range(10_000))
            srv = never_ahead(serving(tiny_engine, prefix_cache=False,
                                      clock=lambda: float(next(ticks))))
            a, b = decoding_pair(srv)
            srv.submit(np.arange(50, 90, dtype=np.int32), max_new_tokens=4)
            noted, intervals = [], []
            acct = srv._accountant()
            note, rt = acct.note_phase, get_session().reqtrace
            interval, note_decode = rt.interval, rt.note_decode
            acct.note_phase = lambda phase, s: (noted.append((phase, s)),
                                                note(phase, s))[1]
            rt.interval = lambda tr, phase, t0, t1, **kw: (
                intervals.append((phase, t0, t1)),
                interval(tr, phase, t0, t1, **kw))[1]
            rt.note_decode = lambda tr, t0, t1, **kw: (
                intervals.append(("decode", t0, t1)),
                note_decode(tr, t0, t1, **kw))[1]
            t_begin = float(next(ticks))
            srv._iterate(defer=True)
            t_end = float(next(ticks))
            if telemetry == "accountant":
                phases = dict((p, s) for p, s in noted
                              if p in ("prefill", "decode"))
                assert set(phases) == {"prefill", "decode"}
                assert phases["prefill"] > 0 and phases["decode"] > 0
                assert phases["prefill"] + phases["decode"] \
                    <= t_end - t_begin
            else:
                (p, p0, p1), = [i for i in intervals if i[0] == "prefill"]
                steps = {(t0, t1) for ph, t0, t1 in intervals
                         if ph == "decode"}
                (d0, d1), = steps           # one step, two rows
                assert t_begin < p0 < p1 == d0 < d1 < t_end
            srv.close()
        finally:
            reset_session()


# ---------------------------------------------------------------------------
# a chunk ahead of its decode step's fetch: under the driver thread an
# iteration whose step went behind its chunk enqueues the prompt's NEXT chunk
# behind that step, once the chunk is applied and before the step is fetched.
# The chunk is in flight across the iteration's end; the next iteration's
# step goes behind it, or it is landed first. Same programs, same operands,
# same order on the device, same tokens
# ---------------------------------------------------------------------------


def watch_chunks_ahead(srv):
    """What ``_chunk_ahead`` answered, call by call: None where the chunk
    went ahead, else the rule's name."""
    log, ahead = [], srv._chunk_ahead

    def spy(obs, req, step):
        log.append(ahead(obs, req, step))
        if log[-1] is None:
            assert srv._chunk is not None and srv._chunk.ahead
            assert srv._flight is step
        return log[-1]

    srv._chunk_ahead = spy
    return log


def long_prompt_beside_rows(srv, n=40, max_new_tokens=4, **kw):
    """Two rows that decode, then a prompt of ``n`` tokens beside them."""
    a, b = decoding_pair(srv)
    return a, b, srv.submit(np.arange(50, 50 + n, dtype=np.int32),
                            max_new_tokens=max_new_tokens, **kw)


def chunk_spans(mark=0):
    from deepspeed_tpu.observability import recorded_spans

    return [s["attrs"] for s in recorded_spans()[mark:]
            if s["name"] == "serving/prefill_chunk"]


# prompts of 3-7 chunks of 16 beside short ones that decode meanwhile
AHEAD_PROMPTS = [(9, dict(max_new_tokens=14)),
                 (100, dict(max_new_tokens=6)),
                 (52, dict(max_new_tokens=7, temperature=0.9, top_k=20,
                           seed=4)),
                 (7, dict(max_new_tokens=12, temperature=1.2, seed=9)),
                 (112, dict(max_new_tokens=5)), (70, dict(max_new_tokens=8))]

CHUNK_AHEAD_CASES = {
    # name: (engine fixture, engine config)
    "dense": ("tiny_engine", {}),
    "dense_no_cache": ("tiny_engine", dict(prefix_cache=False)),
    "moe": ("tiny_moe_engine", {}),
    # a state slot a row, which the chunk ahead reads behind the step
    "kda": ("tiny_kda_engine", dict(prefix_cache=False)),
    "mamba2": ("tiny_recurrent_engine", dict(prefix_cache=False)),
    # a slot, a window's ring, one shared pool and a ``last`` flag
    "mamba1": ("tiny_mamba1_engine", dict(prefix_cache=False)),
    # a pool so small that chunks and rows preempt to grow: no chunk goes
    # ahead where its pages are not on the free list
    "small_pool": ("tiny_engine", dict(num_blocks=11, prefix_cache=False)),
    "two_rows": ("tiny_engine", dict(max_seqs=2)),
}


class TestChunkAhead:
    @pytest.mark.parametrize("case", sorted(CHUNK_AHEAD_CASES))
    def test_streams_are_those_of_a_step_driven_engine(self, request, case):
        fixture, cfg = CHUNK_AHEAD_CASES[case]
        engine = request.getfixturevalue(fixture)
        rng = np.random.RandomState(sum(map(ord, case)))
        reqs = [(rng.randint(0, 250, (n,)).astype(np.int32), kw)
                for n, kw in AHEAD_PROMPTS]
        streams, went = {}, {}
        for mode in ("step", "thread", "driver_loop"):
            srv = serving(engine, **cfg)
            watch_chunks(srv)
            log = watch_chunks_ahead(srv)
            try:
                handles = [srv.submit(p, **kw) for p, kw in reqs]
                if mode == "step":
                    srv.run()
                elif mode == "thread":
                    srv.start()
                else:
                    drive_on_this_thread(srv)
                streams[mode] = [list(h.result(timeout_s=120.0))
                                 for h in handles]
                srv.stop()
                assert all(h.done and h.state == "finished"
                           and h.tokens == h._req.generated for h in handles)
                assert srv._chunk is None and srv._flight is None
                assert not srv.sched.running and not srv._undelivered
                assert srv.alloc.blocks_in_use == (
                    srv.prefix.cached_blocks if srv.prefix else 0)
                went[mode] = log
                if case == "small_pool":
                    assert srv.sched.preemption_count > 0
            finally:
                srv.close()
        assert streams["thread"] == streams["step"]
        assert streams["driver_loop"] == streams["step"]
        assert went["step"] == []
        for mode in ("thread", "driver_loop"):
            assert went[mode].count(None) >= 3, went[mode]
        if case == "small_pool":
            assert "pages" in went["driver_loop"]

    def test_the_next_chunk_is_dispatched_before_the_step_is_fetched(
            self, tiny_engine, monkeypatch):
        """A prompt of three chunks beside two rows that decode. Chunk two
        is enqueued between chunk one's fetch and the fetch of the step
        behind it, and that step's tokens are delivered at once; the next
        iteration starts at its decode step. Chunk three, the prompt's
        last, goes ahead too and is fetched, and its first token delivered,
        before its iteration's step is prepared."""
        srv = never_ahead(serving(tiny_engine, prefix_cache=False),
                          chunks=False)
        events = TestDeferredDelivery._spy(monkeypatch, srv)
        log = watch_chunks(srv)
        went = watch_chunks_ahead(srv)
        try:
            a, b, c = long_prompt_beside_rows(srv)
            na, nb = len(a.tokens), len(b.tokens)
            del events[:], log[:]
            drive_on_this_thread(srv, iterations=3)
            ra, rb, rc = a.request_id, b.request_id, c.request_id
            assert events == [
                ("dispatch", "prefill_chunk"), ("dispatch", "decode"),
                ("push", ra, na), ("push", rb, nb),
                ("fetched", "prefill_chunk"), ("dispatch", "prefill_chunk"),
                ("fetched", "decode"),
                ("push", ra, na + 1), ("push", rb, nb + 1),
                ("iteration_end",),
                ("dispatch", "decode"),
                ("fetched", "prefill_chunk"), ("dispatch", "prefill_chunk"),
                ("fetched", "decode"),
                ("push", ra, na + 2), ("push", rb, nb + 2),
                ("iteration_end",),
                ("fetched", "prefill_chunk"), ("push", rc, 0),
                ("dispatch", "decode"), ("fetched", "decode"),
                ("iteration_end",)]
            assert went == [None, None]
            assert [b for b, _ in log] == [True, True, False]
            assert [rids for _, rids in log] == [[ra, rb], [ra, rb],
                                                 [ra, rb, rc]]
            assert srv._chunk_first == "last_chunk"
            assert srv._chunk is None and srv._flight is None
            assert srv.prefill_chunks_run == 2 + 3
            drive_on_this_thread(srv)
            assert len(c.tokens) == 4
        finally:
            srv.close()

    @pytest.mark.parametrize("how", ["step", "drafter", "two_chunks"])
    def test_today_s_order_is_kept(self, tiny_engine, monkeypatch, how):
        """Under ``step()``, with a drafter proposing, or with two chunks an
        iteration (the live tuner's knob) no chunk goes ahead; ``step()``
        and the drafter never come to ask."""
        cfg = (dict(speculative={"mode": "ngram", "num_draft_tokens": 2})
               if how == "drafter" else {})
        srv = never_ahead(serving(tiny_engine, prefix_cache=False, **cfg),
                          chunks=False)
        went = watch_chunks_ahead(srv)
        if how == "two_chunks":
            srv.prefill_chunks_per_iter = 2
        try:
            a = srv.submit(np.arange(3, 10, dtype=np.int32),
                           max_new_tokens=40)
            srv.step()
            c = srv.submit(np.arange(50, 122, dtype=np.int32),
                           max_new_tokens=4)
            for _ in range(2):
                srv.step() if how == "step" else srv._iterate(defer=True)
                assert srv._chunk is None and srv._flight is None
            assert went == (["more_chunks"] * 2 if how == "two_chunks"
                            else [])
            assert c._req.prefill_pos == (64 if how == "two_chunks" else 32)
            srv.prefill_chunks_per_iter = 1
            drive_on_this_thread(srv)
            assert len(c.tokens) == 4 and a.done
        finally:
            srv.close()

    @pytest.mark.parametrize("how", ["step", "more_chunks", "drafter"])
    def test_a_rule_that_comes_to_hold_lands_the_chunk_first(
            self, tiny_engine, monkeypatch, how):
        """With a chunk ahead in flight the next iteration asks today's
        rules: ``step()``, a second chunk an iteration or a drafter that
        proposes again each have the chunk fetched and applied before
        anything else is enqueued."""
        cfg = (dict(speculative={"mode": "ngram", "num_draft_tokens": 2})
               if how == "drafter" else {})
        srv = never_ahead(serving(tiny_engine, prefix_cache=False, **cfg),
                          chunks=False)
        srv.spec_suspended = True
        events = TestDeferredDelivery._spy(monkeypatch, srv)
        watch_chunks(srv)
        try:
            a, b, c = long_prompt_beside_rows(srv, n=72)
            srv._iterate(defer=True)
            assert srv._chunk is not None and srv._chunk.ahead
            assert c._req.prefill_pos == 16
            if how == "more_chunks":
                srv.prefill_chunks_per_iter = 2
            srv.spec_suspended = how != "drafter"
            del events[:]
            srv.step() if how == "step" else srv._iterate(defer=True)
            order = [e for e in events if e[0] != "push"]
            chunk = [("dispatch", "prefill_chunk"),
                     ("fetched", "prefill_chunk")]
            assert order == [("fetched", "prefill_chunk")] + {
                # brought home, then an iteration of its own
                "step": chunk + [("dispatch", "decode"),
                                 ("fetched", "decode")],
                # the iteration's first chunk; its second waits for the step
                "more_chunks": [chunk[0], ("dispatch", "decode"), chunk[1],
                                ("fetched", "decode")],
                "drafter": [("dispatch", "verify"), ("fetched", "verify")],
            }[how] + [("iteration_end",)]
            assert c._req.prefill_pos == (32 if how == "drafter" else 48)
            assert srv._chunk is None and srv._flight is None
            srv.prefill_chunks_per_iter = 1
            srv.spec_suspended = True
            drive_on_this_thread(srv)
            assert len(c.tokens) == 4 and srv.alloc.blocks_in_use == 0
        finally:
            srv.close()

    def test_a_row_that_would_preempt_has_the_chunk_ahead_landed_first(
            self, tiny_engine, monkeypatch):
        """The chunk ahead took its page; the decode row that needs one an
        iteration on has to preempt, so the chunk in flight is fetched and
        applied before the step is prepared, and only then is its request
        evicted: nobody loses a row with a chunk's progress not applied."""
        srv = never_ahead(serving(tiny_engine, prefix_cache=False,
                                  num_blocks=8), chunks=False)
        events = TestDeferredDelivery._spy(monkeypatch, srv)
        watch_chunks(srv)
        try:
            # 14 prompt tokens: the row needs its second page two steps on
            a = srv.submit(np.arange(3, 17, dtype=np.int32),
                           max_new_tokens=40)
            drive_on_this_thread(srv, iterations=1)
            c = srv.submit(np.arange(50, 146, dtype=np.int32),
                           max_new_tokens=4)      # six pages, chunk by chunk
            srv._iterate(defer=True)
            assert srv._chunk is not None and srv._chunk.ahead
            assert len(c._req.blocks) == 2 and a._req.length == 16
            spare = _grab_free_pages(srv)
            applied = []
            progress = srv.sched.note_prefill_progress
            srv.sched.note_prefill_progress = lambda req, old, new: (
                applied.append((req.rid, new, srv.sched.preemption_count)),
                progress(req, old, new))[1]
            del events[:]
            srv._iterate(defer=True)
            order = [e for e in events if e[0] != "push"]
            assert order == [("fetched", "prefill_chunk"),
                             ("dispatch", "decode"), ("fetched", "decode"),
                             ("iteration_end",)]
            assert srv._chunk_first == "pages"
            assert applied == [(c.request_id, 32, 0)]
            assert srv.sched.preemption_count == 1 and c.state == "queued"
            assert a.state == "decode" and len(a._req.blocks) == 2
            srv.alloc.free(spare)
            drive_on_this_thread(srv)
            assert len(c.tokens) == 4 and srv.alloc.blocks_in_use == 0
        finally:
            srv.close()

    @pytest.mark.parametrize("how", ["cancel", "deadline", "preempt",
                                     "stop"])
    def test_a_request_that_ends_under_its_chunk_ahead(self, tiny_engine,
                                                       obs_session, how):
        """Between two iterations, with the prompt's second chunk in flight
        behind the first iteration's step: the request is cancelled, it
        expires, it loses its row, or the engine is stopped. The engine is
        settled by whoever needs it so, the request's pages and row come
        back once, nothing is delivered to it, the chunk's progress goes to
        nobody it is not for, and the rows that decode stream what they
        stream without any of it."""
        from deepspeed_tpu.observability import recorded_spans

        want = {}
        for cut in (False, True):
            clk = FakeClock()
            srv = never_ahead(serving(tiny_engine, clock=clk,
                                      prefix_cache=False), chunks=False)
            try:
                a, b, c = long_prompt_beside_rows(srv, deadline_s=5.0)
                srv._iterate(defer=True)
                sent = srv._chunk
                assert sent.ahead and sent.rows == [(c._req, c._req.row)]
                assert (sent.start, c._req.prefill_pos) == (16, 16)
                free, chunks = srv.alloc.blocks_free, srv.prefill_chunks_run
                mark = len(recorded_spans())
                if cut and how == "cancel":
                    assert c.cancel()
                    assert srv._chunk is None and not srv._undelivered
                    # brought home for the cancel: applied, then given back
                    assert c._req.prefill_pos == 32
                elif cut and how == "deadline":
                    clk.advance(10.0)
                    srv._iterate(defer=True)
                    assert c.state == "deadline_exceeded"
                    assert c._req.prefill_pos == 16
                elif cut and how == "preempt":
                    srv.sched.preempt(c._req)
                    srv._iterate(defer=True)    # admits it again, at 0
                    assert c.state == "prefill" and c._req.preemptions == 1
                    assert c._req.prefill_pos == 0
                elif cut:
                    srv.stop()
                    assert srv._chunk is None and c._req.prefill_pos == 32
                if cut and how in ("deadline", "preempt"):
                    # the chunk ran for nobody, the step went behind it, and
                    # nothing went ahead of that step's fetch
                    landed, = [s for s in chunk_spans(mark) if "tokens" in s]
                    assert landed["dropped_rows"] == 1 and landed["ahead"]
                    steps = [s for s in decode_spans() if "held_by" in s]
                    assert steps[-1]["chunk_held_by"] == "dropped"
                    assert srv._chunk is None
                if cut:
                    assert srv.prefill_chunks_run == chunks + 1
                if cut and how in ("cancel", "deadline"):
                    assert c._req.row is None and not c._req.blocks
                    assert srv.alloc.blocks_free == free + 2
                    assert c.tokens == [] and c._req.done
                    srv.cancel(c)       # wakes the handle; frees nothing
                    assert srv.alloc.blocks_free == free + 2
                drive_on_this_thread(srv)
                if not (cut and how in ("cancel", "deadline")):
                    assert len(c.tokens) == 4
                    want.setdefault("c", c.tokens)
                    assert c.tokens == want["c"]
                want[cut] = (a.tokens, b.tokens)
                assert len(a.tokens) == len(b.tokens) == 40
                assert srv._chunk is None and srv._flight is None
                assert srv.alloc.blocks_in_use == 0
            finally:
                srv.close()
        assert want[True] == want[False]

    def test_an_iteration_that_raises_runs_its_chunk_ahead_again(
            self, tiny_engine, monkeypatch):
        """The fetch of the step raises with the prompt's next chunk
        enqueued ahead of it: the iteration ends, the chunk in flight is
        forgotten, not applied, and the next iteration runs it again."""
        streams = {}
        for fails in (False, True):
            srv = never_ahead(serving(tiny_engine, prefix_cache=False),
                              chunks=False)
            try:
                a, b, c = long_prompt_beside_rows(srv)
                fetch, raised = srv._fetch, []

                def failing_fetch(obs, sent):
                    if (fails and not raised and sent.name == "serving/decode"
                            and srv._chunk is not None):
                        raised.append(srv._chunk.start)
                        raise RuntimeError("the device is gone")
                    return fetch(obs, sent)

                monkeypatch.setattr(srv, "_fetch", failing_fetch)
                if fails:
                    with pytest.raises(RuntimeError, match="device is gone"):
                        srv._iterate(defer=True)
                    assert raised == [16] and srv._chunk is None
                    assert c._req.prefill_pos == 16
                    srv._flight = None      # what the driver's loop drops
                else:
                    srv._iterate(defer=True)
                    assert srv._chunk.start == 16
                drive_on_this_thread(srv)
                streams[fails] = c.tokens
                assert len(c.tokens) == 4 and srv.alloc.blocks_in_use == 0
            finally:
                srv.close()
        assert streams[True] == streams[False]

    def test_prefix_sharing_of_a_prompt_whose_chunks_went_ahead(
            self, tiny_engine):
        """The pages of a prompt whose chunks went ahead are offered to the
        prefix cache as each chunk is applied; a second request with the
        same prefix maps them and skips those chunks, under the driver
        thread as under ``step()``."""
        prompt = np.arange(20, 100, dtype=np.int32)        # five chunks
        got = {}
        for mode in ("step", "driver_loop"):
            srv = never_ahead(serving(tiny_engine), chunks=False)
            went = watch_chunks_ahead(srv)
            watch_chunks(srv)
            try:
                decoding_pair(srv)
                first = srv.submit(prompt, max_new_tokens=5)
                srv.run() if mode == "step" else drive_on_this_thread(srv)
                chunks = srv.prefill_chunks_run
                assert went == ([] if mode == "step" else [None] * 4)
                decoding_pair(srv)
                second = srv.submit(np.concatenate([prompt[:70], [7, 8, 9]]),
                                    max_new_tokens=5, temperature=0.7,
                                    seed=3)
                srv.run() if mode == "step" else drive_on_this_thread(srv)
                # four full pages shared: one chunk of 9 tokens is left
                assert srv.sched.prefix_hit_tokens == 64
                got[mode] = (first.tokens, second.tokens,
                             srv.prefill_chunks_run - chunks,
                             srv.prefix.cached_blocks)
                assert srv._chunk is None
            finally:
                srv.close()
        assert got["driver_loop"] == got["step"]

    @pytest.mark.parametrize("model", ["dense", "moe", "mamba2"])
    def test_the_ahead_count_the_late_count_and_the_counter(
            self, request, obs_session, monkeypatch, model):
        """``ahead`` is 1 on both spans of chunks two and three and 0 on the
        prompt's first; ``late`` says what ``is_ready()`` of the step's
        tokens said at the enqueue, on both; the registry counts the two;
        each chunk's ``tokens`` and program counts lie once, on the span
        that holds its fetch; neither program's span inside the other's."""
        from deepspeed_tpu.observability import recorded_spans

        engine = request.getfixturevalue(
            {"dense": "tiny_engine", "moe": "tiny_moe_engine",
             "mamba2": "tiny_recurrent_engine"}[model])
        counter = get_registry().counter("serving/chunks_enqueued_ahead")
        before = counter.value()
        srv = never_ahead(serving(engine, prefix_cache=False), chunks=False)
        told = iter([0, 1, 0, 1, 0, 1, 0])
        a, b, c = long_prompt_beside_rows(srv)
        monkeypatch.setattr(type(srv._last_tokens), "is_ready",
                            lambda self: next(told))
        mark = len(recorded_spans())
        drive_on_this_thread(srv, iterations=3)
        spans = recorded_spans()[mark:]
        by_id = {s["id"]: s for s in spans}
        top = [s for s in spans
               if s["name"] in ("serving/decode", "serving/prefill_chunk")]
        its = sorted({s["parent_id"] for s in top})
        assert all(by_id[i]["name"] == "serving/iteration" for i in its)
        kids = {s["id"]: [k["name"].rsplit("/", 1)[1] for k in spans
                          if k.get("parent_id") == s["id"]
                          and k["cat"] == "phase"] for s in top}
        shapes = [[(s["name"].split("/")[1], kids[s["id"]]) for s in top
                   if s["parent_id"] == i] for i in its]
        early, late_half = ["prepare", "dispatch"], ["fetch", "apply"]
        assert shapes == [
            [("prefill_chunk", early), ("decode", early),
             ("prefill_chunk", late_half), ("prefill_chunk", early),
             ("decode", late_half)],
            [("decode", early), ("prefill_chunk", late_half),
             ("prefill_chunk", early), ("decode", late_half)],
            [("prefill_chunk", late_half), ("decode", early + late_half)]]
        chunks = [s["attrs"] for s in top
                  if s["name"] == "serving/prefill_chunk"]
        assert [a["chunk_start"] for a in chunks] == [0, 0, 16, 16, 32, 32]
        assert [a["ahead"] for a in chunks] == [0, 0, 1, 1, 1, 1]
        assert [a.get("tokens") for a in chunks] == [None, 16, None, 16,
                                                     None, 8]
        # asked: the step behind chunk one (0), chunk two behind that step
        # (1), the step behind chunk two (0), chunk three (1)
        assert [a.get("late") for a in chunks] == [None, None, 1, 1, 1, 1]
        assert {a["rid"] for a in chunks} == {c.request_id}
        assert counter.value() - before == 2
        counted = "moe_assignments" if model == "moe" else (
            "ssm_rows" if model == "mamba2" else None)
        if counted:
            assert [counted in a for a in chunks] == [False, True] * 3
        steps = [s["attrs"] for s in top if s["name"] == "serving/decode"]
        assert [a.get("behind_chunk") for a in steps] == [1, None, 1, None, 0]
        assert [a.get("held_by") for a in steps] == [
            None, "prefill", None, "prefill", "queued"]
        assert not any("chunk_held_by" in a for a in steps)
        assert steps[-1]["chunk_first_by"] == "last_chunk"
        # what the step behind a chunk ahead brought is delivered at once,
        # in the span that fetched it
        emits = [s for s in spans if s["name"] == "serving/emit"]
        assert [(by_id[s["parent_id"]]["name"], s["attrs"]["deferred"])
                for s in emits] == [
            ("serving/decode", 1), ("serving/decode", 1),
            ("serving/decode", 1), ("serving/prefill_chunk/apply", 0)]
        drive_on_this_thread(srv)
        assert len(c.tokens) == 4
        srv.close()

    @pytest.mark.parametrize("name", ["pages", "cow"])
    def test_chunk_held_by_names_the_rule_that_kept_the_chunk_back(
            self, tiny_engine, obs_session, name):
        """The free list is short of the chunk's page, or the chunk would
        write into a page it shares: nothing is taken, evicted or copied on
        behalf of a chunk ahead, the span that fetches the step says which
        rule it was, and the next iteration runs the chunk as it always
        did."""
        from deepspeed_tpu.serving.api import CHUNK_HELD_BY

        assert name in CHUNK_HELD_BY
        srv = never_ahead(serving(tiny_engine, prefix_cache=False),
                          chunks=False)
        went = watch_chunks_ahead(srv)
        try:
            a, b, c = long_prompt_beside_rows(srv, n=72)
            srv._iterate(defer=True)
            assert went == [None] and len(c._req.blocks) == 2
            if name == "pages":
                spare = _grab_free_pages(srv)
                undo = lambda: srv.alloc.free(spare)
            else:
                # the page the NEXT chunk writes is there, and shared
                assert srv.sched.ensure_blocks(c._req, 48)
                shared = [c._req.blocks[2]]
                srv.alloc.incref(shared)
                undo = lambda: srv.alloc.free(shared)
            copies, in_use = srv._cow_copies, srv.alloc.blocks_in_use
            srv._iterate(defer=True)
            assert went == [None, name] and srv._chunk is None
            assert (srv._cow_copies, srv.alloc.blocks_in_use) == (copies,
                                                                  in_use)
            step = [s for s in decode_spans() if "held_by" in s][-1]
            assert step["chunk_held_by"] == name
            assert step["held_by"] == "prefill"
            assert c._req.prefill_pos == 32
            if name == "pages":
                undo()
            srv._iterate(defer=True)
            assert c._req.prefill_pos == 48 and went[2] is None
            assert srv._cow_copies == copies + (name == "cow")
            if name == "cow":
                undo()
            drive_on_this_thread(srv)
            assert len(c.tokens) == 4 and srv.alloc.blocks_in_use == 0
        finally:
            srv.close()

    @pytest.mark.parametrize("telemetry", ["accountant", "reqtrace"])
    def test_no_second_is_counted_twice(self, tiny_engine, tmp_path,
                                        telemetry):
        """The chunk ahead's interval runs from the fetch of the step it was
        enqueued behind to its own fetch, the next step's from there."""
        configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path / "obs"),
            recompile_watchdog=False, flight_recorder=False,
            hang_watchdog=False, request_tracing=True,
            trace_sample_rate=1.0, serve_goodput=True))
        try:
            ticks = iter(range(10_000))
            srv = never_ahead(serving(tiny_engine, prefix_cache=False,
                                      clock=lambda: float(next(ticks))),
                              chunks=False)
            a, b, c = long_prompt_beside_rows(srv, n=72)
            noted, intervals = [], []
            acct = srv._accountant()
            note, rt = acct.note_phase, get_session().reqtrace
            interval, note_decode = rt.interval, rt.note_decode
            acct.note_phase = lambda phase, s: (noted.append((phase, s)),
                                                note(phase, s))[1]
            rt.interval = lambda tr, phase, t0, t1, **kw: (
                intervals.append((phase, t0, t1)),
                interval(tr, phase, t0, t1, **kw))[1]
            rt.note_decode = lambda tr, t0, t1, **kw: (
                intervals.append(("decode", t0, t1)),
                note_decode(tr, t0, t1, **kw))[1]
            t_begin = float(next(ticks))
            srv._iterate(defer=True)
            srv._iterate(defer=True)
            t_end = float(next(ticks))
            assert srv._chunk is not None and srv._chunk.start == 32
            if telemetry == "accountant":
                device = [s for p, s in noted if p in ("prefill", "decode")]
                assert len(device) == 4 and all(s > 0 for s in device)
                assert sum(device) <= t_end - t_begin
            else:
                chunks = [(t0, t1) for ph, t0, t1 in intervals
                          if ph == "prefill"]
                steps = sorted({(t0, t1) for ph, t0, t1 in intervals
                                if ph == "decode"})
                (p0, p1), (q0, q1) = chunks
                (d0, d1), (e0, e1) = steps
                assert t_begin < p0 < p1 == d0 < d1 == q0 < q1 == e0 < e1 \
                    < t_end
            srv.close()
        finally:
            reset_session()

    def test_unpinned_cache_entries_are_room_for_a_chunk_ahead(
            self, tiny_engine):
        """A pool that has run for a while has no free page: what requests
        gave back lives on as prefix-cache entries. A chunk ahead takes its
        page from an entry no request holds, as the chunk of the next
        iteration would have, and nobody is preempted."""
        srv = never_ahead(serving(tiny_engine), chunks=False)
        went = watch_chunks_ahead(srv)
        watch_chunks(srv)
        try:
            srv.submit(np.arange(100, 180, dtype=np.int32), max_new_tokens=2)
            srv.run()
            assert srv.prefix.cached_blocks == 5
            a, b, c = long_prompt_beside_rows(srv, n=72)
            spare = _grab_free_pages(srv)
            cached = srv.prefix.cached_blocks
            assert srv.alloc.blocks_free == 0 and srv.prefix.can_evict(5)
            for _ in range(4):
                srv._iterate(defer=True)
            assert went == [None] * 4 and srv.sched.preemption_count == 0
            assert srv.prefix.cached_blocks < cached + 4
            srv.alloc.free(spare)
            drive_on_this_thread(srv)
            assert len(c.tokens) == 4
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# the mixed step: a configuration whose layers mix (``paged_kv.mixes``) sends
# a chunk that is NOT its prompt's last and the iteration's decode rows as
# ONE program, and the next one ahead of its fetch. Same tokens as the two
# programs; whenever a rule of CHUNK_FIRST_BY names itself, the two programs
# ---------------------------------------------------------------------------


def watch_mixed(srv):
    """Every mixed step's (rids of its rows, rid of its chunk, start,
    tokens, prompt size), in the order of enqueue; and no last chunk ever
    rides one."""
    log, program = [], srv._mixed

    def spy(params, arena, rows, chunk, key, last):
        req, _, start, tokens = srv._mix_sent
        assert start + tokens < int(req.prompt.size), "a last chunk mixed"
        assert req.state == PREFILL
        log.append((start, tokens))
        return program(params, arena, rows, chunk, key, last)

    spy._cache_size = program._cache_size
    enqueue = srv._enqueue_step

    def spy_step(obs, dec, ahead, chunk, mix):
        srv._mix_sent = mix
        return enqueue(obs, dec, ahead, chunk, mix)

    srv._mixed = spy
    srv._enqueue_step = spy_step
    return log


# prompts of 1-5 chunks of 16 beside short ones, more requests than rows
MIXED_PROMPTS = [(9, dict(max_new_tokens=14)),
                 (70, dict(max_new_tokens=6)),
                 (52, dict(max_new_tokens=7, temperature=0.9, top_k=20,
                           seed=4)),
                 (7, dict(max_new_tokens=12, temperature=1.2, seed=9)),
                 (80, dict(max_new_tokens=5)), (33, dict(max_new_tokens=8)),
                 (16, dict(max_new_tokens=3)), (64, dict(max_new_tokens=9))]

MIXED_CASES = {
    "dense": {},
    "dense_no_cache": dict(prefix_cache=False),
    # chunks and rows preempt to grow: a step mixes only where its pages
    # are on the free list
    "small_pool": dict(num_blocks=11, prefix_cache=False),
    "two_rows": dict(max_seqs=2),
}


def _mixed_events(srv, handles, how, clock):
    """Something that ends or moves a request under the traffic: called
    between two iterations of the driver's loop."""
    if how == "cancel":
        srv.cancel(handles[1])          # in prefill, maybe under a step
        srv.cancel(handles[0])          # a row that decodes
    elif how == "deadline":
        clock.advance(100.0)            # handles[4] has one
    elif how == "preempt":
        victim = next((r for r in srv.sched.running.values()
                       if r.state == PREFILL), None)
        if victim is not None:
            srv._bring_home()
            srv.sched.preempt(victim)
    elif how == "fork":
        h = next((h for h in handles if h.state == "decode"), None)
        if h is not None:
            return srv.fork(h, 2)
    return []


class TestMixedStep:
    @pytest.mark.parametrize("mode", ["thread", "driver_loop"])
    @pytest.mark.parametrize("case", sorted(MIXED_CASES))
    def test_streams_are_those_of_the_two_programs(self, tiny_engine, case,
                                                   mode):
        rng = np.random.RandomState(sum(map(ord, case)))
        reqs = [(rng.randint(0, 250, (n,)).astype(np.int32), kw)
                for n, kw in MIXED_PROMPTS]
        streams, steps = {}, {}
        for mixed in (False, True):
            srv = serving(tiny_engine, mixed=mixed, **MIXED_CASES[case])
            log = watch_mixed(srv) if mixed else []
            try:
                handles = [srv.submit(p, **kw) for p, kw in reqs]
                if mode == "thread":
                    srv.start()
                else:
                    drive_on_this_thread(srv)
                streams[mixed] = [list(h.result(timeout_s=120.0))
                                  for h in handles]
                srv.stop()
                assert all(h.done and h.state == "finished"
                           and h.tokens == h._req.generated for h in handles)
                assert srv._chunk is None and srv._flight is None
                assert srv._mix is None
                assert not srv.sched.running and not srv._undelivered
                assert srv.alloc.blocks_in_use == (
                    srv.prefix.cached_blocks if srv.prefix else 0)
                steps[mixed] = log
                if case == "small_pool":
                    assert srv.sched.preemption_count > 0
            finally:
                srv.close()
        assert streams[True] == streams[False]
        assert len(steps[True]) >= 3, steps[True]

    @pytest.mark.parametrize("how", ["cancel", "deadline", "preempt", "fork"])
    def test_a_request_that_ends_or_moves_under_the_traffic(self, tiny_engine,
                                                            how):
        """A cancellation, a deadline, a preemption and a copy-on-write fork
        beside a prompt in prefill, at the same iteration of the driver's
        loop with and without the mixed step: every request, the forked
        ones too, ends with the tokens of the two programs."""
        rng = np.random.RandomState(7)
        reqs = [(rng.randint(0, 250, (n,)).astype(np.int32), kw)
                for n, kw in MIXED_PROMPTS[:6]]
        reqs[4][1].update(deadline_s=50.0) if how == "deadline" else None
        streams = {}
        for mixed in (False, True):
            clock = FakeClock()
            srv = serving(tiny_engine, clock=clock, mixed=mixed)
            log = watch_mixed(srv) if mixed else []
            try:
                handles = [srv.submit(p, **dict(kw)) for p, kw in reqs]
                drive_on_this_thread(srv, iterations=5)
                handles += _mixed_events(srv, handles, how, clock)
                drive_on_this_thread(srv)
                streams[mixed] = [(h.state, list(h.tokens)) for h in handles]
                assert srv._chunk is None and srv._flight is None
                assert not srv.sched.running and not srv._undelivered
                assert not mixed or len(log) >= 2
            finally:
                srv.close()
        assert len(streams[True]) == len(streams[False])
        for (state, tokens), (want_state, want) in zip(streams[True],
                                                       streams[False]):
            assert state == want_state
            if state == "finished":
                assert tokens == want
            else:
                # cut at the same iteration, which is not the same token:
                # a mixed step's progress lands a fetch later
                short, long = sorted((tokens, want), key=len)
                assert long[:len(short)] == short
        if how in ("cancel", "deadline"):
            assert any(state != "finished" for state, _ in streams[True])

    def test_the_next_mixed_step_is_dispatched_before_the_one_in_flight_is_fetched(
            self, tiny_engine, monkeypatch, obs_session):
        """A prompt of four chunks beside two rows that decode: chunks one
        to three ride mixed steps, the second and third enqueued AHEAD of
        their predecessor's fetch (no host round between them), and the
        fourth, the last, is a chunk program of its own behind the third
        step, landed at the head of the next iteration with its first
        token."""
        srv = serving(tiny_engine, mixed=True, prefix_cache=False)
        events = TestDeferredDelivery._spy(monkeypatch, srv)
        log = watch_mixed(srv)
        try:
            a, b = decoding_pair(srv)
            c = srv.submit(np.arange(50, 110, dtype=np.int32),
                           max_new_tokens=4)
            ra, rb, rc = a.request_id, b.request_id, c.request_id
            del events[:]
            mark, chunks = len(decode_spans()), len(chunk_spans())
            drive_on_this_thread(srv, iterations=6)
            programs = [e for e in events if e[0] != "push"]
            assert programs == [
                # the rows' step in flight is landed for the admission
                ("fetched", "decode"), ("iteration_end",),
                # from a settled engine: admission, then the first mixed step
                ("dispatch", "decode"), ("iteration_end",),
                # each successor ahead of its predecessor's fetch
                ("dispatch", "decode"), ("fetched", "decode"),
                ("iteration_end",),
                ("dispatch", "decode"), ("fetched", "decode"),
                ("iteration_end",),
                # the last chunk: its own program behind the third step
                ("dispatch", "prefill_chunk"), ("fetched", "decode"),
                ("iteration_end",),
                ("fetched", "prefill_chunk"), ("dispatch", "decode"),
                ("iteration_end",)]
            assert log == [(0, 16), (16, 16), (32, 16)]
            assert c.state == "decode" and len(c.tokens) == 1
            steps = [s for s in decode_spans()[mark:] if s.get("rows")]
            assert [(s["mixed"], s["chunk_tokens"], s["ahead"])
                    for s in steps] == [(1, 16, 0), (1, 16, 1), (1, 16, 1),
                                        (0, 0, 0)]
            assert not any(s["behind_chunk"] for s in steps)
            ran = [s for s in chunk_spans()[chunks:] if "tokens" in s]
            assert [(s["chunk_start"], s["tokens"], s["ahead"])
                    for s in ran] == [(0, 16, 0), (16, 16, 1), (32, 16, 1),
                                      (48, 12, 1)]
            assert get_registry().counter("serving/mixed_steps").value() == 3
            drive_on_this_thread(srv)
            assert len(c.tokens) == 4
        finally:
            srv.close()

    @pytest.mark.parametrize("name", ["last_chunk", "pages", "no_rows",
                                      "drafter", "more_chunks", "step_mode"])
    def test_a_rule_of_chunk_first_by_keeps_the_two_programs(
            self, tiny_engine, obs_session, name):
        """``TestHostCauses``' states, in an engine that mixes: the
        iteration is the chunk program, fetched first, and the decode
        program, as each name says."""
        from deepspeed_tpu.serving.api import CHUNK_FIRST_BY

        spec = (dict(speculative={"mode": "ngram", "num_draft_tokens": 2})
                if name == "drafter" else {})
        srv = never_ahead(serving(tiny_engine, mixed=True,
                                  prefix_cache=False, **spec))
        srv.spec_suspended = True
        log = watch_mixed(srv)
        try:
            if name != "no_rows":
                a = srv.submit(np.arange(3, 18, dtype=np.int32),
                               max_new_tokens=40)
                drive_on_this_thread(srv, iterations=1)
                assert a.state == "decode" and a._req.length == 16
            prompt = np.arange(40 if name in ("pages", "no_rows", "drafter")
                               else 9, dtype=np.int32)
            srv.submit(prompt, max_new_tokens=4)
            ids = []
            if name == "pages":
                ids = _grab_free_pages(srv, leave=1)    # the chunk's own
            elif name == "more_chunks":
                srv.prefill_chunks_per_iter = 2
            elif name == "drafter":
                srv.spec_suspended = False
            mark = len(decode_spans())
            if name == "step_mode":
                srv.step()
            else:
                srv._iterate(defer=True)
            srv.alloc.free(ids)
            assert srv._chunk_first == name and name in CHUNK_FIRST_BY
            assert log == [] and srv._mix is None
            assert not any(s.get("mixed") for s in decode_spans()[mark:])
        finally:
            srv.close()

    @pytest.mark.parametrize("name", ["dropped", "more_chunks", "pages",
                                      "cow", "ends"])
    def test_a_rule_that_holds_lands_the_mixed_step_with_nothing_ahead(
            self, tiny_engine, obs_session, name):
        """With a mixed step in flight, the names of ``CHUNK_HELD_BY`` for
        its prompt's next chunk and those of ``HELD_BY`` for its rows: the
        step is fetched with that name and nothing enqueued ahead of the
        fetch, and the iteration goes on as a settled engine's."""
        from deepspeed_tpu.serving.api import CHUNK_HELD_BY, HELD_BY

        clock = FakeClock()
        srv = serving(tiny_engine, clock=clock, mixed=True)
        log = watch_mixed(srv)
        try:
            a, b, c = long_prompt_beside_rows(
                srv, n=70, max_new_tokens=3,
                **({"deadline_s": 50.0} if name == "dropped" else {}))
            # the rows' step in flight is landed (``queued``), then the
            # prompt's first chunk rides a mixed step from a settled engine
            drive_on_this_thread(srv, iterations=2)
            flight = srv._flight
            assert flight is not None and flight.chunk is not None
            assert log == [(0, 16)] and c._req.prefill_pos == 0
            undo = None
            if name == "dropped":
                # the request expires at the admission that runs in the
                # step's shadow: its chunk's progress is applied to nobody
                clock.advance(100.0)
            elif name == "more_chunks":
                srv.prefill_chunks_per_iter = 2
            elif name == "pages":
                undo = _grab_free_pages(srv)
            elif name == "cow":
                # the chunk ahead would write into a block another holds
                srv.sched.ensure_blocks(c._req, 32)
                undo = [c._req.blocks[1]]
                srv.alloc.incref(undo)
            elif name == "ends":
                for h in (a, b):
                    h._req.max_new_tokens = len(h._req.generated) + 1
            mark = len(decode_spans())
            srv._iterate(defer=True)
            assert srv._flight is not flight
            spans = decode_spans()[mark:]
            landed = [s for s in spans if "held_by" in s]
            assert landed[0]["held_by"] == name
            assert name in CHUNK_HELD_BY + HELD_BY
            assert not any(s.get("ahead") for s in spans)
            if name == "dropped":
                assert c.state == "deadline_exceeded" and c._req.prefill_pos == 0
            else:
                assert c._req.prefill_pos >= 16    # the progress, applied
            if undo:
                srv.alloc.free(undo)
            srv.prefill_chunks_per_iter = 1
            drive_on_this_thread(srv)
            assert all(h.done for h in (a, b, c))
        finally:
            srv.close()

    @pytest.mark.parametrize("what", ["queued", "row_freed", "fork"])
    def test_an_admission_runs_in_the_mixed_step_s_shadow(self, tiny_engine,
                                                          obs_session, what):
        """What holds a decode step's successor for the sake of a request at
        the door (``queued``, ``row_freed``, ``fork``) does not hold a mixed
        step's: the successor carries the same prompt's next chunk, and
        nobody's first chunk comes before that prompt's last. The admission
        itself runs first, with the step in flight, as it does with a chunk
        in flight."""
        srv = serving(tiny_engine, mixed=True)
        log = watch_mixed(srv)
        try:
            a, b, c = long_prompt_beside_rows(srv, n=70, max_new_tokens=3)
            drive_on_this_thread(srv, iterations=2)
            flight = srv._flight
            assert flight is not None and flight.chunk is not None
            late = None
            if what == "queued":
                late = srv.submit(np.arange(9, dtype=np.int32),
                                  max_new_tokens=2)
            elif what == "row_freed":
                srv.sched.rows_released += 1    # as a request's end counts it
            else:
                srv._pending_forks[10 ** 6] = [a._req]
            mark = len(decode_spans())
            srv._iterate(defer=True)
            srv._pending_forks.pop(10 ** 6, None)
            step, = [s for s in decode_spans()[mark:] if s.get("rows")]
            assert (step["ahead"], step["mixed"]) == (1, 1)
            assert log == [(0, 16), (16, 16)]
            assert srv._flight is not flight and srv._flight.chunk is not None
            assert late is None or late.state == "prefill"    # admitted
            drive_on_this_thread(srv)
            assert all(h.done for h in (a, b, c))
            assert late is None or late.done
        finally:
            srv.close()

    def test_unpinned_cache_entries_are_room_for_a_step_ahead(
            self, tiny_engine):
        """A pool that has run for a while has no free page: released
        blocks live on as prefix-cache entries. The rows of a plain step
        ahead take their next pages from entries no request holds, as the
        step would have after its predecessor's fetch, and nobody is
        preempted; with nothing to evict either, the rule is `pages`."""
        srv = serving(tiny_engine)
        try:
            srv.submit(np.arange(100, 180, dtype=np.int32), max_new_tokens=2)
            srv.run()
            assert srv.prefix.cached_blocks == 5
            # rows of 7 and 9 tokens: each needs a second page in some steps
            a, b = decoding_pair(srv)
            drive_on_this_thread(srv, iterations=1)
            assert srv._flight is not None and srv._flight.chunk is None
            assert len(a._req.blocks) == 1 and len(b._req.blocks) == 1
            spare = _grab_free_pages(srv)
            cached = srv.prefix.cached_blocks
            assert srv.alloc.blocks_free == 0 and srv.prefix.can_evict(2)
            ahead = []
            for _ in range(10):
                before = srv._flight
                srv._iterate(defer=True)
                ahead.append(srv._flight is not None
                             and srv._flight is not before)
            assert ahead == [True] * 10 and srv.sched.preemption_count == 0
            assert len(a._req.blocks) == 2 and len(b._req.blocks) == 2
            assert srv.prefix.cached_blocks == cached - 2
            srv.alloc.free(spare)
            drive_on_this_thread(srv)
            assert a.done and b.done
        finally:
            srv.close()

    def test_unpinned_cache_entries_are_room_for_a_mixed_step_ahead(
            self, tiny_engine):
        """A pool that has run for a while has no free page: the rows and
        the chunk of a mixed step ahead take theirs from prefix-cache
        entries no request holds, as the two programs of the next iteration
        would have, and nobody is preempted."""
        srv = serving(tiny_engine, mixed=True)
        log = watch_mixed(srv)
        try:
            srv.submit(np.arange(100, 180, dtype=np.int32), max_new_tokens=2)
            srv.run()
            assert srv.prefix.cached_blocks == 5
            # rows of 13 and 15 tokens: each needs a second page soon
            a, b = decoding_pair(srv, n=(13, 15))
            c = srv.submit(np.arange(50, 122, dtype=np.int32),
                           max_new_tokens=4)
            drive_on_this_thread(srv, iterations=2)
            assert srv._flight is not None and srv._flight.chunk is not None
            spare = _grab_free_pages(srv)
            cached = srv.prefix.cached_blocks
            assert srv.alloc.blocks_free == 0 and srv.prefix.can_evict(5)
            ahead = []
            for _ in range(3):
                before = srv._flight
                srv._iterate(defer=True)
                ahead.append(srv._flight is not None
                             and srv._flight.since is not None
                             and srv._flight is not before)
            assert ahead == [True] * 3 and srv.sched.preemption_count == 0
            assert log == [(0, 16), (16, 16), (32, 16), (48, 16)]
            assert srv.prefix.cached_blocks < cached + 4
            assert len(a._req.blocks) == 2 and len(b._req.blocks) == 2
            srv.alloc.free(spare)
            drive_on_this_thread(srv)
            assert len(c.tokens) == 4
        finally:
            srv.close()

    def test_whoever_needs_a_settled_engine_brings_the_mixed_step_home(
            self, tiny_engine):
        srv = serving(tiny_engine, mixed=True)
        try:
            a, b, c = long_prompt_beside_rows(srv, n=70)
            drive_on_this_thread(srv, iterations=2)
            flight = srv._flight
            assert flight is not None and flight.chunk is not None
            na, pos = len(a.tokens), c._req.prefill_pos
            srv._bring_home()
            assert srv._flight is None and srv._chunk is None
            assert not srv._undelivered
            assert c._req.prefill_pos == pos + 16 and len(a.tokens) > na
            # step() finds nothing in flight across its end either
            drive_on_this_thread(srv, iterations=1)
            assert srv._flight is not None
            srv.step()
            assert srv._flight is None and srv._chunk is None
            srv.run()
            assert all(h.done for h in (a, b, c))
        finally:
            srv.close()

    @pytest.mark.parametrize("fixture", ["tiny_moe_engine", "tiny_kda_engine",
                                         "tiny_recurrent_engine",
                                         "tiny_mamba1_engine",
                                         "tiny_ouro_engine"])
    def test_a_configuration_that_does_not_mix_builds_no_third_program(
            self, request, fixture):
        """Experts, a recurrent, ring or cross kind, a looped stack: the
        engine holds the two programs, registers the parent's entries with
        the auditor and runs a long prompt beside rows as it did."""
        from tools.tpuaudit.registry import clear_registry, get_entry_points

        engine = request.getfixturevalue(fixture)
        clear_registry()
        srv = serving(engine, mixed=True, prefix_cache=False)
        try:
            assert srv._mixed is None
            names = {e.name for e in get_entry_points()
                     if e.name.startswith("serving/")}
            assert "serving/mixed_step" not in names
            assert {"serving/decode", "serving/prefill_chunk"} <= names
            a, b, c = long_prompt_beside_rows(srv, n=40)
            drive_on_this_thread(srv)
            assert all(h.done for h in (a, b, c)) and srv._mix is None
        finally:
            srv.close()

    def test_an_engine_that_mixes_registers_its_third_program(self,
                                                              tiny_engine):
        from tools.tpuaudit.core import run_audit
        from tools.tpuaudit.registry import get_entry_points

        srv = serving(tiny_engine, mixed=True)
        try:
            entry, = get_entry_points(["serving/mixed_step"])
            assert entry.donate_argnums == (1,)     # the arena
            program, args, _ = entry.build()
            assert program is srv._mixed
            assert args[2].shape == (4, 8 + 7) and args[3].shape == (8 + 16
                                                                     + 6,)
            findings = run_audit([entry], publish_metrics=False)
            assert findings == [], [f"{f.entry}:{f.check}" for f in findings]
        finally:
            srv.close()


@pytest.fixture(scope="module")
def tiny_ouro_engine():
    return init_inference("tiny-ouro", dtype=jnp.float32, max_out_tokens=128)

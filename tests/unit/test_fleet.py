"""Serving-fleet tests — the data-plane router over N engine replicas.

Coverage map (the ISSUE-11 checklist):
  * FleetConfig validation + ReplicaHealth snapshot semantics;
  * routing policies: round-robin cycling, least-queue, KV-occupancy,
    session affinity (warm-replica follow + overload/death fallbacks), and
    the headline: affinity's prefix-cache hit rate strictly beats
    round-robin on a shared-system-prompt workload;
  * resilience: deterministic ``replica_kill`` fault mid-stream → drain +
    resubmission bit-identical to an uninterrupted single engine,
    resubmission-budget exhaustion, fleet-unavailable;
  * prefill/decode disaggregation: jitted kv_export/kv_import roundtrip,
    handoff of a request whose last block is COW-shared with the prefix
    cache, cancel racing a handoff, decode-pool preemption AFTER adoption
    (recompute on the destination), full-pool fallback to decoding in
    place;
  * the acceptance smoke: ≥12 staggered mixed-length requests through a
    3-replica fleet AND a disaggregated 1-prefill+1-decode pair, outputs
    bit-identical to one ``ServingEngine`` — including with a replica kill
    injected mid-stream — at temperature (the sampling stream depends only
    on (engine seed, request seed, token index), never on which replica
    runs it);
  * the ``== fleet serving ==`` report section (device-free).
"""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.config.base import ConfigError
from deepspeed_tpu.config.config import FleetConfig, ServingConfig
from deepspeed_tpu.inference import init_inference
from deepspeed_tpu.serving import RequestCancelled, ServingEngine
from deepspeed_tpu.serving.fleet import (ROLE_DECODE, ROLE_MIXED,
                                         ROLE_PREFILL, ArenaHandoff,
                                         FleetRouter, FleetUnavailable,
                                         Replica, build_replicas)
from deepspeed_tpu.serving.fleet.disagg import HandoffGeometryError

SCFG = dict(block_size=16, num_blocks=32, max_seqs=4, max_model_len=128,
            prefill_chunk=16, max_queue=64)


@pytest.fixture(scope="module")
def tiny_engine():
    return init_inference("tiny", dtype=jnp.float32, max_out_tokens=128)


def mk_fleet(engine, n=3, roles=None, policy="kv_occupancy", fault_plan=None,
             fleet_cfg=None, clock=None, **cfg):
    kwargs = dict(SCFG)
    kwargs.update(cfg)
    replicas = build_replicas(engine, ServingConfig(**kwargs), n,
                              roles=roles, clock=clock)
    fc = fleet_cfg or FleetConfig(policy=policy)
    rkw = {"clock": clock} if clock is not None else {}
    return (FleetRouter(replicas, fc, fault_plan=fault_plan, **rkw),
            replicas)


class FakeClock:
    """Injectable router/engine clock (sleep-free lifecycle tests)."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def mk_prompts(n, lo=4, hi=40, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 50, size=rng.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


def oracle_outputs(engine, prompts, n_new=12, temperature=0.0, **cfg):
    kwargs = dict(SCFG)
    kwargs.update(cfg)
    solo = ServingEngine(engine, ServingConfig(**kwargs))
    outs = []
    try:
        for i, p in enumerate(prompts):
            outs.append(solo.submit(p, max_new_tokens=n_new, seed=i,
                                    temperature=temperature).result())
    finally:
        solo.close()
    return outs


def run_staggered(router, prompts, n_new=12, stagger=2, temperature=0.0):
    """Submit one request every ``stagger`` router iterations while the
    fleet keeps stepping — deterministic mid-stream arrivals."""
    handles = []
    i, it = 0, 0
    while i < len(prompts) or router.in_flight():
        if i < len(prompts) and it % stagger == 0:
            handles.append(router.submit(prompts[i], max_new_tokens=n_new,
                                         seed=i, temperature=temperature))
            i += 1
        router.step()
        it += 1
        assert it < 10_000, "fleet made no progress"
    return handles


# ---------------------------------------------------------------------------
# config + health (device-free where possible)
# ---------------------------------------------------------------------------


class TestFleetConfig:
    def test_bad_policy_rejected(self):
        with pytest.raises(ConfigError, match="policy"):
            FleetConfig(policy="random").validate()

    def test_bounds(self):
        with pytest.raises(ConfigError):
            FleetConfig(affinity_overload=0.0).validate()
        with pytest.raises(ConfigError):
            FleetConfig(affinity_overload=1.5).validate()
        with pytest.raises(ConfigError):
            FleetConfig(max_resubmits=-1).validate()
        FleetConfig().validate()   # defaults valid

    def test_replica_role_rejected(self):
        with pytest.raises(ValueError, match="role"):
            Replica(engine=None, index=0, role="verifier")


class TestReplicaHealth:
    def test_load_key_orders_by_occupancy_then_queue(self):
        from deepspeed_tpu.serving.fleet import ReplicaHealth

        low = ReplicaHealth(index=1, role=ROLE_MIXED, alive=True,
                            arena_occupancy=0.1, in_flight=9)
        high = ReplicaHealth(index=0, role=ROLE_MIXED, alive=True,
                             arena_occupancy=0.9, in_flight=0)
        assert low.load_key < high.load_key
        tie_a = ReplicaHealth(index=0, role=ROLE_MIXED, alive=True,
                              arena_occupancy=0.5, in_flight=2)
        tie_b = ReplicaHealth(index=1, role=ROLE_MIXED, alive=True,
                              arena_occupancy=0.5, in_flight=1)
        assert tie_b.load_key < tie_a.load_key

    def test_snapshot_tracks_engine(self, tiny_engine):
        router, replicas = mk_fleet(tiny_engine, n=1)
        try:
            r = replicas[0]
            h0 = r.health()
            assert h0.alive and h0.in_flight == 0 and h0.kv_blocks_in_use == 0
            router.submit(np.arange(1, 20, dtype=np.int32),
                          max_new_tokens=4)
            router.step()
            h1 = r.health()
            assert h1.in_flight == 1 and h1.kv_blocks_in_use > 0
            assert 0.0 < h1.arena_occupancy <= 1.0
            r.kill("test")
            assert not r.health().alive
        finally:
            router.close()


# ---------------------------------------------------------------------------
# routing policies
# ---------------------------------------------------------------------------


class TestRoutingPolicies:
    def test_round_robin_cycles(self, tiny_engine):
        router, replicas = mk_fleet(tiny_engine, n=3, policy="round_robin")
        try:
            prompts = mk_prompts(6, lo=18, hi=20)
            hs = [router.submit(p, max_new_tokens=2) for p in prompts]
            picked = [h._fr.replica.index for h in hs]
            assert picked == [0, 1, 2, 0, 1, 2]
            for h in hs:
                h.result()
        finally:
            router.close()

    def test_least_queue_picks_emptiest(self, tiny_engine):
        router, replicas = mk_fleet(tiny_engine, n=3, policy="least_queue")
        try:
            p = np.arange(1, 20, dtype=np.int32)
            h0 = router.submit(p, max_new_tokens=4)
            assert h0._fr.replica.index == 0           # all empty → index tie
            h1 = router.submit(p, max_new_tokens=4)
            assert h1._fr.replica.index == 1           # 0 now has one in flight
            h2 = router.submit(p, max_new_tokens=4)
            assert h2._fr.replica.index == 2
            for h in (h0, h1, h2):
                h.result()
        finally:
            router.close()

    def test_kv_occupancy_avoids_full_replica(self, tiny_engine):
        router, replicas = mk_fleet(tiny_engine, n=2, policy="kv_occupancy")
        try:
            long_p = np.arange(1, 65, dtype=np.int32)   # 4 blocks resident
            h0 = router.submit(long_p, max_new_tokens=2)
            router.step()                               # blocks land on 0
            h1 = router.submit(np.arange(1, 20, dtype=np.int32),
                               max_new_tokens=2)
            assert h1._fr.replica.index == 1            # 0 is occupied
            for h in (h0, h1):
                h.result()
        finally:
            router.close()

    def test_affinity_follows_warm_replica(self, tiny_engine):
        router, replicas = mk_fleet(tiny_engine, n=3, policy="affinity")
        try:
            sys_prompt = np.arange(1, 40, dtype=np.int32)   # > one block
            h0 = router.submit(sys_prompt, max_new_tokens=2)
            first = h0._fr.replica.index
            h0.result()
            # same first block → same replica, counted as a warm decision
            h1 = router.submit(
                np.concatenate([sys_prompt[:16],
                                np.arange(50, 70, dtype=np.int32)]),
                max_new_tokens=2)
            assert h1._fr.replica.index == first
            h1.result()
            assert router._decisions[("affinity", "affinity_warm")] == 1
            assert router._decisions[("affinity", "affinity_cold")] == 1
            # short prompts can't key a block → load-based fallback reason
            router.submit(np.arange(1, 5, dtype=np.int32),
                          max_new_tokens=2).result()
            assert router._decisions[("affinity", "affinity_short")] == 1
        finally:
            router.close()

    def test_affinity_overload_spills(self, tiny_engine):
        router, replicas = mk_fleet(
            tiny_engine, n=2,
            fleet_cfg=FleetConfig(policy="affinity",
                                  affinity_overload=0.01))
        try:
            sys_prompt = np.arange(1, 40, dtype=np.int32)
            h0 = router.submit(sys_prompt, max_new_tokens=4)
            first = h0._fr.replica.index
            router.step()                      # warm replica now > 1% full
            h1 = router.submit(sys_prompt, max_new_tokens=4)
            assert h1._fr.replica.index != first
            assert router._decisions[("affinity", "affinity_overload")] == 1
            for h in (h0, h1):
                h.result()
        finally:
            router.close()

    def test_affinity_prefix_hits_beat_round_robin(self, tiny_engine):
        """The cross-replica admission hint pays: on a shared-system-prompt
        workload, affinity routing lands every request on the replica whose
        prefix cache is warm, so its fleet-wide prefix-hit tokens strictly
        exceed round-robin's over the SAME workload."""
        sys_prompt = np.arange(1, 49, dtype=np.int32)      # 3 full blocks
        rng = np.random.RandomState(7)
        prompts = [np.concatenate([sys_prompt,
                                   rng.randint(50, 90, size=6 + i)
                                   .astype(np.int32)])
                   for i in range(6)]
        hits = {}
        for policy in ("round_robin", "affinity"):
            router, replicas = mk_fleet(tiny_engine, n=2, policy=policy)
            try:
                for i, p in enumerate(prompts):
                    router.submit(p, max_new_tokens=4, seed=i).result()
                hits[policy] = sum(r.engine.sched.prefix_hit_tokens
                                   for r in replicas)
            finally:
                router.close()
        assert hits["affinity"] > hits["round_robin"]

    def test_fleet_unavailable_when_all_dead(self, tiny_engine):
        router, replicas = mk_fleet(tiny_engine, n=2)
        try:
            router.kill_replica(0)
            router.kill_replica(1)
            with pytest.raises(FleetUnavailable):
                router.submit(np.arange(1, 20, dtype=np.int32),
                              max_new_tokens=2)
        finally:
            router.close()

    def test_mismatched_geometry_rejected(self, tiny_engine):
        a = ServingEngine(tiny_engine, ServingConfig(**SCFG))
        b_cfg = dict(SCFG)
        b_cfg["block_size"] = 8
        b = ServingEngine(tiny_engine, ServingConfig(**b_cfg))
        try:
            with pytest.raises(ValueError, match="geometry"):
                FleetRouter([Replica(a, 0), Replica(b, 1)], FleetConfig())
        finally:
            a.close()
            b.close()

    def test_disagg_needs_both_pools(self, tiny_engine):
        srv = ServingEngine(tiny_engine, ServingConfig(**SCFG))
        try:
            with pytest.raises(ValueError, match="prefill"):
                FleetRouter([Replica(srv, 0, role=ROLE_DECODE)],
                            FleetConfig())
        finally:
            srv.close()


# ---------------------------------------------------------------------------
# resilience: replica death → drain → bit-exact resubmission
# ---------------------------------------------------------------------------


class TestReplicaDeath:
    def test_mid_stream_kill_resubmits_bit_exact(self, tiny_engine):
        prompts = mk_prompts(6, seed=1)
        want = oracle_outputs(tiny_engine, prompts, n_new=12)
        router, replicas = mk_fleet(
            tiny_engine, n=2, policy="round_robin",
            fault_plan=[{"kind": "replica_kill", "step": 5, "replica": 1}])
        try:
            hs = [router.submit(p, max_new_tokens=12, seed=i)
                  for i, p in enumerate(prompts)]
            outs = [h.result() for h in hs]
            # the replica really died mid-run (auto-revive may have since
            # rebuilt it — deaths are the durable evidence)
            assert replicas[1].deaths == 1
            assert replicas[1].death_reason == "fault"
            assert sum(h.resubmits for h in hs) > 0
            for got, exp in zip(outs, want):
                np.testing.assert_array_equal(got, exp)
            # the drained replica's requests now live on the survivor;
            # no fleet request was lost or duplicated
            assert all(h.state == "finished" for h in hs)
        finally:
            router.close()

    @pytest.mark.slow   # tier-1 keeps the fault-plan kill variant above
    def test_step_exception_marks_dead_and_resubmits(self, tiny_engine):
        prompts = mk_prompts(4, seed=2)
        want = oracle_outputs(tiny_engine, prompts, n_new=8)
        router, replicas = mk_fleet(tiny_engine, n=2, policy="round_robin")
        try:
            hs = [router.submit(p, max_new_tokens=8, seed=i)
                  for i, p in enumerate(prompts)]
            orig_step = replicas[0].engine.step
            calls = {"n": 0}

            def exploding_step():
                calls["n"] += 1
                if calls["n"] == 3:
                    raise RuntimeError("synthetic device loss")
                return orig_step()

            replicas[0].engine.step = exploding_step
            outs = [h.result() for h in hs]
            assert replicas[0].deaths == 1
            assert replicas[0].death_reason == "step-exception"
            for got, exp in zip(outs, want):
                np.testing.assert_array_equal(got, exp)
        finally:
            router.close()

    def test_resubmit_budget_exhaustion_cancels(self, tiny_engine):
        router, replicas = mk_fleet(
            tiny_engine, n=2, policy="round_robin",
            fleet_cfg=FleetConfig(policy="round_robin", max_resubmits=0),
            fault_plan=[{"kind": "replica_kill", "step": 3, "replica": 0}])
        try:
            h = router.submit(mk_prompts(1, seed=3)[0], max_new_tokens=16)
            assert h._fr.replica.index == 0
            with pytest.raises(RequestCancelled):
                h.result()
            assert h.state == "cancelled"
        finally:
            router.close()


# ---------------------------------------------------------------------------
# disaggregation: KV handoff
# ---------------------------------------------------------------------------


class TestKVHandoffPrograms:
    def test_export_import_roundtrip(self, tiny_engine):
        """The jitted gather/scatter pair moves exactly the named blocks —
        every layer, both k and v — and touches nothing else."""
        from deepspeed_tpu.serving import paged_kv

        src = ServingEngine(tiny_engine, ServingConfig(**SCFG))
        dst = ServingEngine(tiny_engine, ServingConfig(**SCFG))
        try:
            rng = np.random.RandomState(0)
            shape = src._arena["k"].shape        # (L, 1+N, BS, K*D)
            src._arena = {
                "k": jnp.asarray(rng.randn(*shape).astype(np.float32)),
                "v": jnp.asarray(rng.randn(*shape).astype(np.float32))}
            blocks = [5, 2, 9]                   # deliberately out of order
            handoff = ArenaHandoff()
            dst_before = np.asarray(dst._arena["k"]).copy()
            dst_ids = handoff.transfer(src, dst, blocks)
            assert dst_ids is not None and len(dst_ids) == 3
            src_k = np.asarray(src._arena["k"])
            dst_k = np.asarray(dst._arena["k"])
            dst_v = np.asarray(dst._arena["v"])
            src_v = np.asarray(src._arena["v"])
            for s, d in zip(blocks, dst_ids):
                np.testing.assert_array_equal(dst_k[:, d], src_k[:, s])
                np.testing.assert_array_equal(dst_v[:, d], src_v[:, s])
            # blocks NOT in the transfer kept their old content
            untouched = [b for b in range(dst_k.shape[1])
                         if b not in dst_ids and b != 0]
            np.testing.assert_array_equal(dst_k[:, untouched],
                                          dst_before[:, untouched])
        finally:
            src.close()
            dst.close()

    def test_destination_dry_returns_none_no_leak(self, tiny_engine):
        small = dict(SCFG)
        small["num_blocks"] = 8
        src = ServingEngine(tiny_engine, ServingConfig(**SCFG))
        dst = ServingEngine(tiny_engine, ServingConfig(**small))
        try:
            dst.alloc.alloc(7)       # 1 free block left, need 2
            before = dst.alloc.blocks_in_use
            assert ArenaHandoff().transfer(src, dst, [1, 2]) is None
            assert dst.alloc.blocks_in_use == before
        finally:
            src.close()
            dst.close()

    def test_geometry_mismatch_raises(self, tiny_engine):
        other = dict(SCFG)
        other["block_size"] = 8
        other["max_model_len"] = 64
        src = ServingEngine(tiny_engine, ServingConfig(**SCFG))
        dst = ServingEngine(tiny_engine, ServingConfig(**other))
        try:
            with pytest.raises(HandoffGeometryError):
                ArenaHandoff().transfer(src, dst, [1])
        finally:
            src.close()
            dst.close()


class TestDisaggregation:
    def test_prefill_decode_split_bit_exact(self, tiny_engine):
        prompts = mk_prompts(6, seed=4)
        want = oracle_outputs(tiny_engine, prompts, n_new=10)
        router, replicas = mk_fleet(tiny_engine, n=2,
                                    roles=[ROLE_PREFILL, ROLE_DECODE])
        try:
            hs = [router.submit(p, max_new_tokens=10, seed=i)
                  for i, p in enumerate(prompts)]
            outs = [h.result() for h in hs]
            for got, exp in zip(outs, want):
                np.testing.assert_array_equal(got, exp)
            assert sum(h.handoffs for h in hs) == len(prompts)
            assert replicas[0].engine.sched.handoffs_out == len(prompts)
            # the prefill engine released every handed-off request; only
            # prefix-cache pins may remain
            alloc = replicas[0].engine.alloc
            cache = replicas[0].engine.sched.prefix
            held = cache.cached_blocks if cache else 0
            assert alloc.blocks_in_use == held
        finally:
            router.close()

    def test_handoff_with_cow_shared_last_block(self, tiny_engine):
        """Two identical full-block prompts: the second admission maps the
        prefix cache's blocks (refcount > 1, last block COW-shared) — its
        handoff must export private-or-shared content correctly and release
        exactly one reference on the source."""
        prompt = np.arange(1, 33, dtype=np.int32)      # exactly 2 blocks
        want = oracle_outputs(tiny_engine, [prompt, prompt], n_new=8)
        router, replicas = mk_fleet(tiny_engine, n=2,
                                    roles=[ROLE_PREFILL, ROLE_DECODE])
        try:
            h0 = router.submit(prompt, max_new_tokens=8, seed=0)
            np.testing.assert_array_equal(h0.result(), want[0])
            pre = replicas[0].engine.sched
            assert pre.prefix is not None and pre.prefix.cached_blocks > 0
            h1 = router.submit(prompt, max_new_tokens=8, seed=1)
            np.testing.assert_array_equal(h1.result(), want[1])
            assert pre.prefix_hits >= 1          # admission reused blocks
            assert h1.handoffs == 1
            alloc = replicas[0].engine.alloc
            assert alloc.blocks_in_use == pre.prefix.cached_blocks
            # cache entries survive with exactly their own pin reference
            for b in list(pre.prefix._entries.values()):
                assert alloc.refcount(b) == 1
        finally:
            router.close()

    def test_cancel_racing_handoff(self, tiny_engine):
        """Cancel issued the moment the handoff lands: the fleet handle is
        already rebound to the decode replica, and cancelling must free the
        imported blocks there (and nothing on the prefill side twice)."""
        router, replicas = mk_fleet(tiny_engine, n=2,
                                    roles=[ROLE_PREFILL, ROLE_DECODE],
                                    prefix_cache=False)
        try:
            h = router.submit(np.arange(1, 40, dtype=np.int32),
                              max_new_tokens=32)
            while h.handoffs == 0 and not h.done:
                router.step()
            assert h._fr.replica.index == 1
            assert h.cancel() is True
            with pytest.raises(RequestCancelled):
                h.result()
            router.step()
            assert replicas[0].engine.alloc.blocks_in_use == 0
            assert replicas[1].engine.alloc.blocks_in_use == 0
            # ledger: the handoff is not a completion, the cancel is one
            assert replicas[0].engine.sched.handoffs_out == 1
            assert replicas[1].engine.sched.cancelled_count == 1
        finally:
            router.close()

    def test_cancel_during_prefill_before_handoff(self, tiny_engine):
        router, replicas = mk_fleet(tiny_engine, n=2,
                                    roles=[ROLE_PREFILL, ROLE_DECODE],
                                    prefix_cache=False)
        try:
            h = router.submit(np.arange(1, 120, dtype=np.int32),
                              max_new_tokens=8)
            router.step()                       # first chunk only (of 8)
            assert h.handoffs == 0
            assert h.cancel() is True
            router.step()
            assert replicas[0].engine.alloc.blocks_in_use == 0
            assert replicas[0].engine.sched.handoffs_out == 0
        finally:
            router.close()

    def test_deadline_survives_handoff(self, tiny_engine):
        """The remaining deadline crosses the handoff: the adopted request
        must keep its EDF priority on the decode replica, not sort last as
        deadline-less."""
        router, replicas = mk_fleet(tiny_engine, n=2,
                                    roles=[ROLE_PREFILL, ROLE_DECODE])
        try:
            h = router.submit(np.arange(1, 40, dtype=np.int32),
                              max_new_tokens=8, deadline_s=60.0)
            for _ in range(200):
                router.step()
                if h.handoffs:
                    break
            assert h.handoffs == 1
            dec = replicas[1].engine.sched
            adopted = (list(dec.queued) + list(dec.running.values()))
            assert len(adopted) == 1
            assert adopted[0].deadline_s is not None
            assert adopted[0].deadline_s <= replicas[1].engine.clock() + 60.0
            h.result()
        finally:
            router.close()

    @pytest.mark.slow   # tier-1 keeps the disagg smoke + COW-handoff
    def test_decode_pool_preemption_after_adoption_bit_exact(self,
                                                            tiny_engine):
        """Pressure on the decode pool preempts ADOPTED requests: the
        recompute source (original prompt + streamed tokens) was carried
        through the handoff, so eviction+recompute on the destination still
        reproduces the uninterrupted stream bit-exactly."""
        prompts = [np.arange(1, 40 + 7 * i, dtype=np.int32)
                   for i in range(4)]
        want = oracle_outputs(tiny_engine, prompts, n_new=24)
        # decode pool sized to admit all four, then run dry as they grow
        replicas = [
            Replica(ServingEngine(tiny_engine, ServingConfig(**SCFG)),
                    0, role=ROLE_PREFILL),
            Replica(ServingEngine(
                tiny_engine,
                ServingConfig(**{**SCFG, "num_blocks": 16,
                                 "prefix_cache": False})),
                1, role=ROLE_DECODE)]
        router = FleetRouter(replicas, FleetConfig())
        try:
            hs = [router.submit(p, max_new_tokens=24, seed=i)
                  for i, p in enumerate(prompts)]
            outs = [h.result() for h in hs]
            for got, exp in zip(outs, want):
                np.testing.assert_array_equal(got, exp)
            dec = replicas[1].engine.sched
            assert sum(h.handoffs for h in hs) >= 1
            assert dec.preemption_count >= 1     # pressure actually hit
        finally:
            router.close()

    def test_full_decode_pool_falls_back_in_place(self, tiny_engine):
        """A handoff the decode pool cannot take decodes on the prefill
        replica — degraded but live, and still bit-exact."""
        prompt = np.arange(1, 40, dtype=np.int32)
        want = oracle_outputs(tiny_engine, [prompt], n_new=8)
        replicas = [
            Replica(ServingEngine(tiny_engine, ServingConfig(**SCFG)),
                    0, role=ROLE_PREFILL),
            Replica(ServingEngine(
                tiny_engine,
                ServingConfig(**{**SCFG, "num_blocks": 8,
                                 "prefix_cache": False})),
                1, role=ROLE_DECODE)]
        router = FleetRouter(replicas, FleetConfig())
        try:
            replicas[1].engine.alloc.alloc(8)    # decode pool fully booked
            h = router.submit(prompt, max_new_tokens=8, seed=0)
            np.testing.assert_array_equal(h.result(), want[0])
            assert h.handoffs == 0
            assert router._handoff_fallbacks == 1
        finally:
            router.close()

    def test_parallel_sampling_rejected_on_disagg(self, tiny_engine):
        router, _ = mk_fleet(tiny_engine, n=2,
                             roles=[ROLE_PREFILL, ROLE_DECODE])
        try:
            with pytest.raises(NotImplementedError):
                router.submit(np.arange(1, 20, dtype=np.int32),
                              max_new_tokens=4, n=2)
        finally:
            router.close()


# ---------------------------------------------------------------------------
# the acceptance smoke (ISSUE-11): ≥12 staggered mixed-length requests,
# 3-replica fleet AND disaggregated pair, bit-identical to a single engine,
# including with a deterministic mid-stream replica kill
# ---------------------------------------------------------------------------


class TestFleetAcceptanceSmoke:
    N_REQ = 12
    N_NEW = 12
    TEMP = 0.7   # the sampling stream must survive rebinding, not just argmax

    def _prompts(self):
        return mk_prompts(self.N_REQ, lo=4, hi=60, seed=11)

    def test_three_replica_fleet_with_kill_bit_exact(self, tiny_engine):
        prompts = self._prompts()
        want = oracle_outputs(tiny_engine, prompts, n_new=self.N_NEW,
                              temperature=self.TEMP)
        router, replicas = mk_fleet(
            tiny_engine, n=3, policy="kv_occupancy",
            fault_plan=[{"kind": "replica_kill", "step": 9, "replica": 1}])
        try:
            hs = run_staggered(router, prompts, n_new=self.N_NEW,
                               temperature=self.TEMP)
            assert replicas[1].deaths == 1        # the fault actually fired
            resubmitted = sum(h.resubmits for h in hs)
            assert resubmitted > 0                # ... mid-stream
            for i, (h, exp) in enumerate(zip(hs, want)):
                np.testing.assert_array_equal(
                    np.asarray(h.tokens, np.int32), exp,
                    err_msg=f"request {i} diverged from the single engine")
            # every alive replica's pool drained back to its cache pins
            for r in replicas:
                if r.alive:
                    held = (r.engine.sched.prefix.cached_blocks
                            if r.engine.sched.prefix else 0)
                    assert r.engine.alloc.blocks_in_use == held
        finally:
            router.close()

    def test_disaggregated_pair_bit_exact(self, tiny_engine):
        prompts = self._prompts()
        want = oracle_outputs(tiny_engine, prompts, n_new=self.N_NEW,
                              temperature=self.TEMP)
        router, replicas = mk_fleet(tiny_engine, n=2,
                                    roles=[ROLE_PREFILL, ROLE_DECODE])
        try:
            hs = run_staggered(router, prompts, n_new=self.N_NEW,
                               temperature=self.TEMP)
            for i, (h, exp) in enumerate(zip(hs, want)):
                np.testing.assert_array_equal(
                    np.asarray(h.tokens, np.int32), exp,
                    err_msg=f"request {i} diverged across the handoff")
            assert sum(h.handoffs for h in hs) == self.N_REQ
        finally:
            router.close()


# ---------------------------------------------------------------------------
# fault plan + report section (device-free)
# ---------------------------------------------------------------------------


class TestReplicaKillFault:
    def test_fires_once_at_scheduled_iteration(self):
        from deepspeed_tpu.observability.faultinject import FaultInjector

        inj = FaultInjector(plan=[{"kind": "replica_kill", "step": 3,
                                   "replica": 2}], rank=0, restart=0)
        killed = []
        for it in range(6):
            inj.before_router_step(it, killed.append)
        assert killed == [2]

    def test_not_applied_by_train_step_hook(self):
        from deepspeed_tpu.observability.faultinject import FaultInjector

        inj = FaultInjector(plan=[{"kind": "replica_kill", "step": 0,
                                   "replica": 0}], rank=0, restart=0)
        inj.before_step(0, engine=None)      # train-side hook: not its fault
        killed = []
        inj.before_router_step(0, killed.append)
        assert killed == [0]


class TestFleetServingReport:
    def _records(self):
        lbl = {"replica": "0", "role": "prefill"}
        lbl2 = {"replica": "1", "role": "decode"}
        return [
            {"type": "gauge", "name": "fleet_serving/replicas_alive",
             "labels": {}, "value": 2},
            {"type": "gauge", "name": "fleet_serving/requests_in_flight",
             "labels": {}, "value": 0},
            {"type": "gauge", "name": "fleet_serving/queue_depth",
             "labels": lbl, "value": 1},
            {"type": "gauge", "name": "fleet_serving/arena_occupancy",
             "labels": lbl, "value": 0.5},
            {"type": "gauge", "name": "fleet_serving/arena_occupancy",
             "labels": lbl2, "value": 0.25},
            {"type": "gauge", "name": "fleet_serving/kv_blocks_in_use",
             "labels": lbl2, "value": 8},
            {"type": "counter", "name": "fleet_serving/routing_decisions",
             "labels": {"policy": "affinity", "reason": "affinity_warm",
                        "replica": "0"}, "value": 5},
            {"type": "counter", "name": "fleet_serving/routing_decisions",
             "labels": {"policy": "affinity", "reason": "disagg_decode",
                        "replica": "1"}, "value": 6},
            {"type": "counter", "name": "fleet_serving/handoffs",
             "labels": {}, "value": 6},
            {"type": "histogram", "name": "fleet_serving/handoff_ms",
             "labels": {}, "count": 6, "mean": 2.5, "min": 1.0, "max": 9.0},
            {"type": "gauge", "name": "fleet_serving/handoff_p50_ms",
             "labels": {}, "value": 2.0},
            {"type": "gauge", "name": "fleet_serving/handoff_p99_ms",
             "labels": {}, "value": 8.8},
            {"type": "counter", "name": "fleet_serving/replica_deaths",
             "labels": {"reason": "fault"}, "value": 1},
            {"type": "counter", "name": "fleet_serving/resubmits",
             "labels": {}, "value": 3},
            # the self-healing / overload block (ISSUE-12)
            {"type": "gauge", "name": "fleet_serving/health_state",
             "labels": lbl, "value": 1},
            {"type": "gauge", "name": "fleet_serving/health_state",
             "labels": lbl2, "value": 3},
            {"type": "counter", "name": "fleet_serving/health_verdicts",
             "labels": {"verdict": "slow"}, "value": 2},
            {"type": "counter", "name": "fleet_serving/quarantines",
             "labels": {"reason": "slow"}, "value": 2},
            {"type": "counter", "name": "fleet_serving/revivals",
             "labels": {}, "value": 1},
            {"type": "counter",
             "name": "fleet_serving/probation_graduations",
             "labels": {}, "value": 1},
            {"type": "counter", "name": "fleet_serving/handoff_failures",
             "labels": {}, "value": 1},
            {"type": "counter", "name": "fleet_serving/shed",
             "labels": {"reason": "deadline_infeasible"}, "value": 4},
            {"type": "counter", "name": "fleet_serving/shed",
             "labels": {"reason": "degraded"}, "value": 2},
            {"type": "gauge", "name": "fleet_serving/degraded_mode",
             "labels": {}, "value": 2},
        ]

    def test_section_renders_everything(self):
        from deepspeed_tpu.observability.report import summarize_fleet_serving

        text = summarize_fleet_serving(self._records())
        assert "== fleet serving ==" in text
        assert "replicas_alive=2" in text
        assert "prefill" in text and "decode" in text
        assert "affinity/disagg_decode=6" in text
        assert "affinity/affinity_warm=5" in text
        assert "handoffs: count=6" in text
        assert "p50=2.00ms" in text and "p99=8.80ms" in text
        assert "1 replica death(s)" in text and "fault=1" in text
        assert "3 in-flight request(s) resubmitted" in text
        # the self-healing / overload block
        assert "serving" in text and "probation" in text  # state column
        assert "verdicts: slow=2" in text
        assert "quarantines=2" in text and "revivals=1" in text
        assert "graduations=1" in text
        assert "handoff_failures=1" in text
        assert "6 request(s) shed under overload" in text
        assert "deadline_infeasible=4" in text and "degraded=2" in text
        assert "degraded_mode=2" in text
        assert "affinity hints off" in text

    def test_absent_without_fleet_metrics(self):
        from deepspeed_tpu.observability.report import summarize_fleet_serving

        assert summarize_fleet_serving(
            [{"type": "gauge", "name": "serving/queue_depth",
              "labels": {}, "value": 1}]) == ""

    def test_report_cli_end_to_end(self, tmp_path):
        from deepspeed_tpu.observability.report import report

        path = tmp_path / "metrics.jsonl"
        path.write_text("\n".join(json.dumps(r) for r in self._records()))
        assert "== fleet serving ==" in report([str(path)])


# ---------------------------------------------------------------------------
# replica construction
# ---------------------------------------------------------------------------


class TestBuildReplicas:
    def test_shares_compiled_programs(self, tiny_engine):
        replicas = build_replicas(tiny_engine, ServingConfig(**SCFG), 3)
        try:
            first = replicas[0].engine
            for r in replicas[1:]:
                assert r.engine._prefill is first._prefill
                assert r.engine._decode is first._decode
                assert r.engine is not first
                assert r.engine.alloc is not first.alloc
        finally:
            for r in replicas:
                r.engine.close()

    def test_roles_length_checked(self, tiny_engine):
        with pytest.raises(ValueError, match="roles"):
            build_replicas(tiny_engine, ServingConfig(**SCFG), 2,
                           roles=[ROLE_MIXED])


# ---------------------------------------------------------------------------
# close-time telemetry
# ---------------------------------------------------------------------------


class TestFleetCloseGauges:
    def test_close_publishes_fleet_wide_latency(self, tiny_engine, tmp_path):
        """Every replica's close() sets the same unlabeled serving/* latency
        gauges; the router must publish the POOLED reservoirs last so the
        dump describes the fleet, not whichever replica closed last."""
        from deepspeed_tpu.config.config import ObservabilityConfig
        from deepspeed_tpu.observability import (configure_observability,
                                                 get_registry, reset_session)
        from deepspeed_tpu.serving.api import _percentile

        reset_session()
        configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path / "obs"),
            flight_recorder=False))
        try:
            router, replicas = mk_fleet(tiny_engine, n=2,
                                        policy="round_robin")
            hs = [router.submit(p, max_new_tokens=6, seed=i)
                  for i, p in enumerate(mk_prompts(4, seed=5))]
            router.run()
            [h.result() for h in hs]
            per_replica = [list(r.engine._ttft_samples) for r in replicas]
            assert all(per_replica)      # round-robin spread the load
            pooled = [s for xs in per_replica for s in xs]
            router.close()
            got = get_registry().gauge("serving/ttft_p50_ms").value()
            assert got == _percentile(pooled, 0.50)
            # the pooled median must differ from at least one replica's own
            # close-time value, or this test could not catch last-writer-wins
            assert any(_percentile(xs, 0.50) != got for xs in per_replica) \
                or len(set(pooled)) == 1
        finally:
            reset_session()


# ---------------------------------------------------------------------------
# ISSUE-12: replica lifecycle — quarantine → probation → graduation,
# revival, circuit breaker (sleep-free: fault-injected step-time penalties
# ride the health data-plane, never the wall clock)
# ---------------------------------------------------------------------------


# warmup 3 swallows every compile-heavy first dispatch (prefill, decode —
# an SLO of 2s with ms-scale real steps then only ever convicts the
# injected 10s penalty); lifecycle tests also run prefix_cache=False so a
# late COW-program compile can never land in a sampled step
HEAL_CFG = dict(policy="round_robin", health_window=2, step_time_slo_s=2.0,
                health_warmup_steps=3, quarantine_iterations=4,
                revive_after_iterations=2, probation_requests=2,
                probation_share=0.25, breaker_incidents=4)


class TestReplicaLifecycle:
    def test_slow_replica_quarantined_then_graduates(self, tiny_engine):
        """The full state machine on one replica: a step-time SLO breach
        quarantines it (alive, no new traffic), the backoff expires into
        probation, and clean completions graduate it back to full
        weight."""
        router, replicas = mk_fleet(
            tiny_engine, n=2, fleet_cfg=FleetConfig(**HEAL_CFG),
            prefix_cache=False,
            fault_plan=[{"kind": "replica_slow", "step": 0, "steps": 7,
                         "replica": 1, "sleep_s": 10.0}])
        try:
            hs = [router.submit(np.arange(1, 20, dtype=np.int32),
                                max_new_tokens=6, seed=i) for i in range(4)]
            it = 0
            while not replicas[1].quarantined:
                router.step()
                it += 1
                assert it < 50, "slow replica never quarantined"
            assert replicas[1].alive                  # quarantined ≠ dead
            assert replicas[1].quarantine_reason == "step_slo"
            assert router._quarantine_count == 1
            # no NEW traffic routes to it while quarantined...
            h_new = router.submit(np.arange(1, 20, dtype=np.int32),
                                  max_new_tokens=4)
            assert h_new._fr.replica.index == 0
            # ...but its own in-flight work keeps stepping to completion
            for h in hs:
                h.result()
            while replicas[1].quarantined:
                router.step()
                it += 1
                assert it < 200, "quarantine never expired"
            # on probation now — its own work completing during probation
            # may already have earned clean-completion credit
            assert 0 <= replicas[1].probation_left <= 2
            # clean completions graduate it (bounded: the fault window is
            # over, so probation must resolve — never re-convict)
            h_new.result()
            for _ in range(20):
                if replicas[1].probation_left == 0:
                    break
                router.submit(np.arange(1, 20, dtype=np.int32),
                              max_new_tokens=4).result()
            assert router._graduation_count == 1
            assert replicas[1].routable()
        finally:
            router.close()

    def test_probation_traffic_share_bounded(self, tiny_engine):
        """A probation replica's concurrent share stays under
        probation_share × fleet in-flight (floor one): with share 0.25 and
        ~6 in flight, at most one lands on it at a time."""
        router, replicas = mk_fleet(
            tiny_engine, n=2, policy="least_queue",
            fleet_cfg=FleetConfig(**{**HEAL_CFG, "policy": "least_queue"}))
        try:
            replicas[1].probation_left = 3           # force probation
            hs = [router.submit(np.arange(1, 20, dtype=np.int32),
                                max_new_tokens=8, seed=i)
                  for i in range(6)]
            on_probation = [h for h in hs if h._fr.replica.index == 1]
            # least_queue would have split 3/3; the probation cap allows
            # at most max(1, int(0.25 × in_flight)) concurrent
            assert len(on_probation) <= 1
            for h in hs:
                h.result()
        finally:
            router.close()

    def test_flapping_replica_respects_breaker_budget(self, tiny_engine):
        """replica_flap kills every revived incarnation; the per-replica
        circuit breaker must retire it after breaker_incidents incidents —
        revivals never exceed the budget and the fleet finishes all work
        on the survivor."""
        cfg = dict(HEAL_CFG)
        cfg.update(breaker_incidents=2, revive_after_iterations=1)
        router, replicas = mk_fleet(
            tiny_engine, n=2, fleet_cfg=FleetConfig(**cfg),
            fault_plan=[{"kind": "replica_flap", "step": 1, "steps": 60,
                         "replica": 1}])
        try:
            prompts = mk_prompts(6, seed=21)
            want = oracle_outputs(tiny_engine, prompts, n_new=10)
            hs = [router.submit(p, max_new_tokens=10, seed=i)
                  for i, p in enumerate(prompts)]
            outs = [h.result() for h in hs]
            # drive past the flap window so the breaker resolves
            for _ in range(70):
                router.step()
            assert replicas[1].retired
            assert replicas[1].death_reason.startswith("breaker")
            assert replicas[1].revivals <= cfg["breaker_incidents"]
            # retired means retired: no more revivals, ever
            revivals_at_retirement = replicas[1].revivals
            for _ in range(30):
                router.step()
            assert replicas[1].revivals == revivals_at_retirement
            for got, exp in zip(outs, want):
                np.testing.assert_array_equal(got, exp)
        finally:
            router.close()

    def test_revived_replica_streams_bit_exact(self, tiny_engine):
        """Requests served by a revived replica (post-kill rebuild sharing
        the survivor's compiled programs) are bit-identical to the
        single-engine oracle — revival is invisible to clients."""
        prompts = mk_prompts(8, seed=31)
        want = oracle_outputs(tiny_engine, prompts, n_new=8,
                              temperature=0.7)
        cfg = dict(HEAL_CFG)
        cfg.update(revive_after_iterations=1, probation_requests=1,
                   probation_share=1.0)
        router, replicas = mk_fleet(
            tiny_engine, n=2, fleet_cfg=FleetConfig(**cfg),
            fault_plan=[{"kind": "replica_kill", "step": 2, "replica": 1}])
        try:
            # first half rides through the kill + revival
            hs = [router.submit(p, max_new_tokens=8, seed=i,
                                temperature=0.7)
                  for i, p in enumerate(prompts[:4])]
            outs = [h.result() for h in hs]
            assert replicas[1].revivals == 1
            # second half: round_robin lands half on the REVIVED replica
            hs2 = [router.submit(p, max_new_tokens=8, seed=4 + i,
                                 temperature=0.7)
                   for i, p in enumerate(prompts[4:])]
            outs += [h.result() for h in hs2]
            assert any(h._fr.replica.index == 1 for h in hs2)
            assert router._graduation_count >= 1
            for i, (got, exp) in enumerate(zip(outs, want)):
                np.testing.assert_array_equal(
                    got, exp, err_msg=f"request {i} diverged after revival")
            # revival reuses the compile set: the rebuilt engine's jitted
            # callables ARE the survivor's
            assert replicas[1].engine._decode is replicas[0].engine._decode
        finally:
            router.close()

    def test_prefill_replica_graduates_via_handoffs(self, tiny_engine):
        """In a disaggregated fleet every request rebinds to a decode
        replica at handoff, so a probation PREFILL replica's service is
        its completed handoffs — it must still be able to graduate."""
        router, replicas = mk_fleet(
            tiny_engine, n=2, roles=[ROLE_PREFILL, ROLE_DECODE],
            fleet_cfg=FleetConfig(**HEAL_CFG))
        try:
            replicas[0].probation_left = 2        # prefill on probation
            for i in range(3):
                h = router.submit(np.arange(1, 40, dtype=np.int32),
                                  max_new_tokens=4, seed=i)
                h.result()
                assert h.handoffs == 1            # served via handoff
            assert replicas[0].probation_left == 0
            assert router._graduation_count == 1
        finally:
            router.close()

    def test_revival_keeps_dead_incarnations_latency_samples(
            self, tiny_engine, tmp_path):
        """Close-time fleet-wide latency gauges must pool the REPLACED
        engine's reservoirs too — a revival must not erase the requests
        its dead incarnation served."""
        from deepspeed_tpu.config.config import ObservabilityConfig
        from deepspeed_tpu.observability import (configure_observability,
                                                 get_registry,
                                                 reset_session)

        reset_session()
        configure_observability(ObservabilityConfig(
            enabled=True, output_dir=str(tmp_path / "obs"),
            flight_recorder=False))
        try:
            cfg = dict(HEAL_CFG)
            cfg.update(revive_after_iterations=1, probation_requests=1)
            router, replicas = mk_fleet(tiny_engine, n=2,
                                        fleet_cfg=FleetConfig(**cfg))
            hs = [router.submit(p, max_new_tokens=6, seed=i)
                  for i, p in enumerate(mk_prompts(4, seed=71))]
            [h.result() for h in hs]
            served_before = list(replicas[1].engine._ttft_samples)
            assert served_before          # round_robin spread the load
            router.kill_replica(1)
            router.step()                 # drain + revive (backoff 1)
            while not replicas[1].alive:
                router.step()
            assert replicas[1].revivals == 1
            router.close()
            # the dead incarnation's samples survived into the pool
            assert get_registry().gauge("serving/ttft_p50_ms").value() \
                is not None
            pooled_n = len(router._replaced_engines[0]._ttft_samples)
            assert pooled_n == len(served_before)
        finally:
            reset_session()

    def test_manual_revive_refused_for_retired(self, tiny_engine):
        from deepspeed_tpu.serving.fleet.replica import ReplicaRetired

        router, replicas = mk_fleet(
            tiny_engine, n=2,
            fleet_cfg=FleetConfig(**{**HEAL_CFG, "auto_revive": False}))
        try:
            replicas[1].retire()
            with pytest.raises(ReplicaRetired):
                router.revive_replica(1)
        finally:
            router.close()

    def test_auto_revive_off_keeps_dead_replica_dead(self, tiny_engine):
        router, replicas = mk_fleet(
            tiny_engine, n=2,
            fleet_cfg=FleetConfig(**{**HEAL_CFG, "auto_revive": False}))
        try:
            router.kill_replica(1)
            h = router.submit(np.arange(1, 20, dtype=np.int32),
                              max_new_tokens=4)
            h.result()
            for _ in range(20):
                router.step()
            assert not replicas[1].alive and replicas[1].revivals == 0
        finally:
            router.close()


# ---------------------------------------------------------------------------
# ISSUE-12: overload control — deadline-infeasibility admission shedding +
# the degraded-mode ladder
# ---------------------------------------------------------------------------


class TestOverloadControl:
    def test_infeasible_deadline_shed_at_admission(self, tiny_engine):
        from deepspeed_tpu.serving.fleet import Overloaded

        router, replicas = mk_fleet(tiny_engine, n=2)
        try:
            # one finished request seeds the TPOT estimator
            router.submit(np.arange(1, 20, dtype=np.int32),
                          max_new_tokens=8).result()
            assert router._tpot_estimate() is not None
            with pytest.raises(Overloaded) as exc:
                router.submit(np.arange(1, 20, dtype=np.int32),
                              max_new_tokens=64, deadline_s=1e-9)
            assert exc.value.retry_after_s > 0
            assert router._shed_count == 1
            # the shed request never reached an engine
            assert all(r.engine.in_flight() == 0 for r in replicas)
            # a feasible deadline still admits
            h = router.submit(np.arange(1, 20, dtype=np.int32),
                              max_new_tokens=4, deadline_s=3600.0)
            h.result()
        finally:
            router.close()

    def test_parallel_sampling_scales_feasibility_estimate(self,
                                                           tiny_engine):
        """submit(n=8) decodes 8× the budget — a deadline feasible for one
        sample but not eight must shed."""
        from deepspeed_tpu.serving.fleet import Overloaded

        router, _ = mk_fleet(tiny_engine, n=2)
        try:
            router.submit(np.arange(1, 20, dtype=np.int32),
                          max_new_tokens=8).result()
            tpot = router._tpot_estimate()
            assert tpot is not None
            # feasible for one sample (queue empty): est = tpot × 8
            deadline = tpot * 8 * 4
            h = router.submit(np.arange(1, 20, dtype=np.int32),
                              max_new_tokens=8, deadline_s=deadline)
            h.result()
            with pytest.raises(Overloaded):
                router.submit(np.arange(1, 20, dtype=np.int32),
                              max_new_tokens=8, deadline_s=deadline, n=8)
        finally:
            router.close()

    def test_shed_submission_does_not_pollute_affinity(self, tiny_engine):
        """An admission-shed request must not leave an affinity hint —
        later prefix-sharers would follow it to a cold replica."""
        from deepspeed_tpu.serving.fleet import Overloaded

        router, _ = mk_fleet(tiny_engine, n=2, policy="affinity")
        try:
            router.submit(np.arange(50, 90, dtype=np.int32),
                          max_new_tokens=8).result()   # seeds TPOT
            sys_prompt = np.arange(1, 40, dtype=np.int32)
            with pytest.raises(Overloaded):
                router.submit(sys_prompt, max_new_tokens=64,
                              deadline_s=1e-9)
            key = router._affinity_key(sys_prompt)
            assert key not in router._affinity    # no hint committed
            # the next (admitted) submission is a genuine cold start
            router.submit(sys_prompt, max_new_tokens=2).result()
            assert router._decisions[("affinity", "affinity_cold")] >= 1
            assert router._decisions[("affinity", "affinity_warm")] == 0
        finally:
            router.close()

    def test_revive_before_drain_resubmits_stranded_requests(self,
                                                            tiny_engine):
        """A manual revive racing the step loop (kill not yet drained)
        must drain the dead incarnation's requests first — they would
        otherwise stay bound to the discarded engine forever."""
        fc = FleetConfig(policy="round_robin", auto_revive=False)
        router, replicas = mk_fleet(tiny_engine, n=2, fleet_cfg=fc,
                                    policy="round_robin")
        try:
            prompts = mk_prompts(2, lo=18, hi=20, seed=61)
            want = oracle_outputs(tiny_engine, prompts, n_new=6)
            hs = [router.submit(p, max_new_tokens=6, seed=i)
                  for i, p in enumerate(prompts)]
            router.step()
            router.kill_replica(1)
            # revive BEFORE any step could drain the dead incarnation
            assert not replicas[1].drained
            assert router.revive_replica(1) is True
            outs = [h.result() for h in hs]
            assert all(h.state == "finished" for h in hs)
            assert hs[1].resubmits == 1
            for got, exp in zip(outs, want):
                np.testing.assert_array_equal(got, exp)
        finally:
            router.close()

    def test_no_tpot_data_admits(self, tiny_engine):
        """The estimator sheds only on MEASURED evidence — a cold fleet
        admits every deadline."""
        router, _ = mk_fleet(tiny_engine, n=1)
        try:
            h = router.submit(np.arange(1, 20, dtype=np.int32),
                              max_new_tokens=4, deadline_s=1e-9)
            assert h is not None     # admitted (it will expire, not shed)
        finally:
            router.close()

    def test_degraded_ladder_climbs_sheds_and_recovers(self, tiny_engine):
        from deepspeed_tpu.serving.fleet import Overloaded

        fc = FleetConfig(policy="round_robin", overload_queue_depth=1,
                         overload_up_iterations=1,
                         overload_down_iterations=2)
        # 2 rows per replica: a 10-request burst queues deep
        router, replicas = mk_fleet(tiny_engine, n=2, fleet_cfg=fc,
                                    max_seqs=2)
        try:
            hs = [router.submit(np.arange(1, 30, dtype=np.int32),
                                max_new_tokens=8, seed=i,
                                deadline_s=(None if i % 2 else 3600.0))
                  for i in range(10)]
            seen_rungs = set()
            it = 0
            while router.in_flight():
                router.step()
                seen_rungs.add(router.degraded_mode)
                if router.degraded_mode >= 1:
                    # rung 1+: speculation suspended fleet-wide
                    assert all(r.engine.spec_suspended
                               for r in replicas if r.alive)
                it += 1
                assert it < 500
            assert 3 in seen_rungs                # the ladder reached shed
            shed = [h for h in hs if h.state == "shed"]
            assert shed                           # rung 3 shed queued work
            assert router.shed_count_total == len(shed)
            # no-deadline work was shed first (lowest priority)
            assert all(h._fr.deadline_abs is None for h in shed) \
                or len(shed) > sum(1 for h in hs
                                   if h._fr.deadline_abs is None)
            for h in shed:
                with pytest.raises(Overloaded) as exc:
                    h.result()
                assert exc.value.retry_after_s > 0
            # calm iterations walk the ladder back down, spec resumes
            for _ in range(3 * fc.overload_down_iterations + 3):
                router.step()
            assert router.degraded_mode == 0
            assert all(not r.engine.spec_suspended
                       for r in replicas if r.alive)
            # ledger: submitted == finished + cancelled + shed + deadline
            assert router.submitted_count == (
                router.finished_count + router.cancelled_count
                + router.shed_count_total
                + router.deadline_exceeded_count)
        finally:
            router.close()

    def test_rung2_spills_affinity(self, tiny_engine):
        """Degraded rung 2 stops following warm prefix-affinity hints —
        the request routes by load with reason degraded_spill."""
        router, replicas = mk_fleet(tiny_engine, n=2, policy="affinity")
        try:
            sys_prompt = np.arange(1, 40, dtype=np.int32)
            router.submit(sys_prompt, max_new_tokens=2).result()
            router._degraded = 2
            router.submit(sys_prompt, max_new_tokens=2).result()
            assert router._decisions[("affinity", "degraded_spill")] == 1
            assert router._decisions[("affinity", "affinity_warm")] == 0
        finally:
            router.close()


# ---------------------------------------------------------------------------
# ISSUE-12: handoff fault tolerance — a transfer that dies mid-flight
# retries once on another decode replica, then falls back to decoding in
# place; both sides' blocks freed exactly once
# ---------------------------------------------------------------------------


class TestHandoffFaultTolerance:
    def test_transfer_failure_frees_destination_blocks(self, tiny_engine):
        from deepspeed_tpu.serving.fleet import HandoffTransferError

        src = ServingEngine(tiny_engine, ServingConfig(**SCFG))
        dst = ServingEngine(tiny_engine, ServingConfig(**SCFG))
        try:
            handoff = ArenaHandoff()
            handoff.inject_fail_next = 1
            before = dst.alloc.blocks_in_use
            with pytest.raises(HandoffTransferError):
                handoff.transfer(src, dst, [1, 2, 3])
            assert dst.alloc.blocks_in_use == before   # freed exactly once
            # the seam is one-shot: the next transfer succeeds
            assert handoff.transfer(src, dst, [1, 2, 3]) is not None
        finally:
            src.close()
            dst.close()

    def test_failed_handoff_retries_on_other_decode_replica(self,
                                                           tiny_engine):
        prompts = mk_prompts(3, seed=41)
        want = oracle_outputs(tiny_engine, prompts, n_new=8)
        router, replicas = mk_fleet(
            tiny_engine, n=3,
            roles=[ROLE_PREFILL, ROLE_DECODE, ROLE_DECODE],
            fault_plan=[{"kind": "handoff_fail", "step": 0}])
        try:
            hs = [router.submit(p, max_new_tokens=8, seed=i)
                  for i, p in enumerate(prompts)]
            outs = [h.result() for h in hs]
            assert router._handoff_failures == 1     # the fault fired
            # the retry landed every request on SOME decode replica
            assert sum(h.handoffs for h in hs) == len(prompts)
            assert router._handoff_fallbacks == 0
            for got, exp in zip(outs, want):
                np.testing.assert_array_equal(got, exp)
            for r in replicas:
                held = (r.engine.sched.prefix.cached_blocks
                        if r.engine.sched.prefix else 0)
                assert r.engine.alloc.blocks_in_use == held
        finally:
            router.close()

    def test_failed_handoff_falls_back_in_place(self, tiny_engine):
        """Single decode replica: the failed transfer has nowhere to
        retry — the request decodes on its prefill replica, bit-exact,
        with zero leaked blocks on either side."""
        prompt = np.arange(1, 40, dtype=np.int32)
        want = oracle_outputs(tiny_engine, [prompt], n_new=8)
        router, replicas = mk_fleet(
            tiny_engine, n=2, roles=[ROLE_PREFILL, ROLE_DECODE],
            prefix_cache=False,
            fault_plan=[{"kind": "handoff_fail", "step": 0}])
        try:
            h = router.submit(prompt, max_new_tokens=8, seed=0)
            np.testing.assert_array_equal(h.result(), want[0])
            assert h.handoffs == 0
            assert router._handoff_failures == 1
            assert router._handoff_fallbacks == 1
            router.step()
            assert replicas[0].engine.alloc.blocks_in_use == 0
            assert replicas[1].engine.alloc.blocks_in_use == 0
        finally:
            router.close()

    def test_import_exception_falls_back_no_leak(self, tiny_engine):
        """Not just the injected fault: ANY exception out of the transfer
        (kv_import raising) takes the same retry/fallback path."""
        prompt = np.arange(1, 40, dtype=np.int32)
        want = oracle_outputs(tiny_engine, [prompt], n_new=6)
        router, replicas = mk_fleet(tiny_engine, n=2,
                                    roles=[ROLE_PREFILL, ROLE_DECODE],
                                    prefix_cache=False)
        try:
            orig = router.handoff.transfer

            def exploding_transfer(src, dst, blocks):
                raise RuntimeError("synthetic kv_import device loss")

            router.handoff.transfer = exploding_transfer
            h = router.submit(prompt, max_new_tokens=6, seed=0)
            np.testing.assert_array_equal(h.result(), want[0])
            assert router._handoff_failures >= 1
            assert h.handoffs == 0
            router.handoff.transfer = orig
            router.step()
            assert replicas[1].engine.alloc.blocks_in_use == 0
        finally:
            router.close()


# ---------------------------------------------------------------------------
# ISSUE-12 satellite: a resubmission that finds every survivor full PARKS
# and retries on later iterations — it must not burn max_resubmits in one
# iteration
# ---------------------------------------------------------------------------


class TestParkedResubmission:
    def test_queuefull_parks_instead_of_cancelling(self, tiny_engine):
        prompts = mk_prompts(4, lo=18, hi=20, seed=51)
        want = oracle_outputs(tiny_engine, prompts, n_new=6, max_queue=2)
        fc = FleetConfig(policy="round_robin", max_resubmits=1,
                         auto_revive=False)
        router, replicas = mk_fleet(tiny_engine, n=2, fleet_cfg=fc,
                                    policy="round_robin", max_queue=2)
        try:
            hs = [router.submit(p, max_new_tokens=6, seed=i)
                  for i, p in enumerate(prompts)]
            # round_robin: replica 0 holds #0/#2, replica 1 holds #1/#3 —
            # the survivor is FULL (max_queue=2) when replica 1 dies
            assert [h._fr.replica.index for h in hs] == [0, 1, 0, 1]
            router.kill_replica(1)
            router.step()
            # both victims parked (not cancelled), one death each on the
            # budget ledger
            assert len(router._parked) == 2
            assert all(h._fr.resubmits == 1 for h in hs[1::2])
            outs = [h.result() for h in hs]
            # the parked pair resubmitted once survivor capacity freed,
            # without spending further budget
            assert all(h.state == "finished" for h in hs)
            assert all(h._fr.resubmits == 1 for h in hs[1::2])
            assert router.cancelled_count == 0
            for got, exp in zip(outs, want):
                np.testing.assert_array_equal(got, exp)
        finally:
            router.close()

    def test_parked_request_expires_if_deadline_passes(self, tiny_engine):
        clk = FakeClock()
        from deepspeed_tpu.serving import DeadlineExceeded

        fc = FleetConfig(policy="round_robin", auto_revive=False)
        router, replicas = mk_fleet(tiny_engine, n=2, fleet_cfg=fc,
                                    policy="round_robin", max_queue=1,
                                    clock=clk)
        try:
            h0 = router.submit(np.arange(1, 20, dtype=np.int32),
                               max_new_tokens=32)
            h1 = router.submit(np.arange(1, 20, dtype=np.int32),
                               max_new_tokens=8, deadline_s=5.0)
            assert h1._fr.replica.index == 1
            router.kill_replica(1)
            router.step()
            assert len(router._parked) == 1       # survivor full
            clk.advance(10.0)                     # deadline passes, parked
            router.step()
            assert h1.state == "deadline_exceeded"
            with pytest.raises(DeadlineExceeded):
                h1.result()
            h0.result()
            assert router.deadline_exceeded_count == 1
        finally:
            router.close()


# ---------------------------------------------------------------------------
# ISSUE-12: new fault kinds (device-free injector unit tests)
# ---------------------------------------------------------------------------


class TestNewFleetFaults:
    def test_replica_slow_penalty_window(self):
        from deepspeed_tpu.observability.faultinject import FaultInjector

        inj = FaultInjector(plan=[{"kind": "replica_slow", "step": 3,
                                   "steps": 2, "replica": 1,
                                   "sleep_s": 5.0}], rank=0, restart=0)
        assert inj.slow_penalty(2, 1) == 0.0
        assert inj.slow_penalty(3, 1) == 5.0
        assert inj.slow_penalty(4, 1) == 5.0
        assert inj.slow_penalty(5, 1) == 0.0      # window over
        assert inj.slow_penalty(3, 0) == 0.0      # other replica untouched
        assert len(inj.applied) == 1              # noted once

    def test_replica_flap_fires_across_window(self):
        from deepspeed_tpu.observability.faultinject import FaultInjector

        inj = FaultInjector(plan=[{"kind": "replica_flap", "step": 2,
                                   "steps": 3, "replica": 0}],
                            rank=0, restart=0)
        killed = []
        for it in range(8):
            inj.before_router_step(it, killed.append)
        assert killed == [0, 0, 0]                # every window iteration
        assert len(inj.applied) == 1              # noted once

    def test_handoff_fail_consumed_once(self):
        from deepspeed_tpu.observability.faultinject import FaultInjector

        inj = FaultInjector(plan=[{"kind": "handoff_fail", "step": 4}],
                            rank=0, restart=0)
        assert not inj.take_handoff_fail(3)       # not due yet
        assert inj.take_handoff_fail(6)           # due (at/after step)
        assert not inj.take_handoff_fail(7)       # consumed

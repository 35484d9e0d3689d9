"""KV handoff — the prefill/decode disaggregation seam.

DistServe-style disaggregation (Zhong et al., OSDI '24) splits serving into
a compute-bound prefill pool (flash prefill, batched by token budget) and a
bandwidth-bound decode pool (paged gather, batched by rows), so each scales
and batches independently. The seam between them is the **KV handoff**: a
sequence prefilled on engine A must continue decoding on engine B, which
means A's resident arena blocks become B's.

:class:`KVHandoff` is the transport interface; :class:`ArenaHandoff` is the
shared-mesh implementation — two jitted programs over the existing paged
arena abstraction:

* ``serving/kv_export`` gathers the request's blocks out of the source
  arena into a dense ``(L, MAXB, BLOCK, K*D)`` transfer buffer (source
  arena NOT donated — its other requests keep decoding from it);
* ``serving/kv_import`` scatters the buffer into freshly allocated blocks
  of the (donated) destination arena.

Both are shape-static: the block lists ride as int32 operands padded to
``MAXB`` with the scratch block 0, so ONE compiled program pair serves any
residency. On one mesh the pair is an in-HBM copy; a cross-host transport
later replaces only the buffer's journey between the two programs — the
``transfer()`` signature (and everything in ``router.py``) is unchanged.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ...parallel import mesh as mesh_mod
from ...utils.logging import logger
from .. import paged_kv

__all__ = ["KVHandoff", "ArenaHandoff", "HandoffGeometryError",
           "HandoffTransferError", "register_handoff_audit_entries"]


class HandoffGeometryError(ValueError):
    """Source and destination engines disagree on arena geometry — their
    blocks are not interchangeable."""


class HandoffTransferError(RuntimeError):
    """The KV transfer itself failed mid-flight (a cross-host link drop, a
    device error out of kv_import — or the chaos harness's ``handoff_fail``
    fault standing in for either). Destination blocks are already freed
    when this propagates; the router retries on another decode replica,
    then falls back to decoding in place."""


def _check_geometry(src, dst) -> None:
    scfg, dcfg = src.engine.model.config, dst.engine.model.config
    s = (scfg.num_layers, scfg.num_kv_heads, scfg.head_dim,
         src.config.block_size, src.blocks_per_seq, src._dtype)
    d = (dcfg.num_layers, dcfg.num_kv_heads, dcfg.head_dim,
         dst.config.block_size, dst.blocks_per_seq, dst._dtype)
    if s != d:
        raise HandoffGeometryError(
            f"KV handoff needs identical arena geometry "
            f"(L, KV heads, head dim, block size, blocks/seq, dtype): "
            f"source {s} vs destination {d} — a fleet config this "
            f"mismatched is caught statically by `python -m tools.tpushard` "
            f"(finding serving/kv_export::cross-program-mismatch)")


class KVHandoff:
    """Transport interface: move ``blocks`` (source-engine block ids) into
    the destination engine's arena. Returns the destination block ids —
    same count, request-order preserved — or None when the destination
    pool cannot take them right now (the router's fallback signal). A
    transfer that starts and then FAILS raises ``HandoffTransferError``
    with the destination blocks already freed.
    Implementations own their device programs; the router owns policy.

    ``inject_fail_next`` is the chaos seam: each unit makes the next
    ``transfer`` fail AFTER destination allocation (and, for
    ``ArenaHandoff``, after the export) — exercising the exact
    free-on-failure path a real mid-flight loss takes. The router arms it
    from the ``handoff_fail`` fault plan."""

    inject_fail_next: int = 0

    def transfer(self, src, dst, blocks: List[int],
                 trace=None) -> Optional[List[int]]:
        """``trace`` (an ``observability.reqtrace.ReqTrace``, or None) is
        the request-trace context riding the seam: implementations record
        the export → transfer → import stages onto it so a handoff's
        timeline carries BOTH replicas."""
        raise NotImplementedError

    def _maybe_inject_failure(self) -> None:
        if self.inject_fail_next > 0:
            self.inject_fail_next -= 1
            raise HandoffTransferError(
                "injected handoff_fail fault (chaos harness)")


class ArenaHandoff(KVHandoff):
    """Shared-mesh handoff: jitted gather out of the source arena, jitted
    scatter into the destination arena (an in-HBM copy on one mesh)."""

    def __init__(self):
        self._export = paged_kv.build_kv_export_program()
        self._import = paged_kv.build_kv_import_program()
        self.transfers = 0
        self.inject_fail_next = 0

    def transfer(self, src, dst, blocks: List[int],
                 trace=None) -> Optional[List[int]]:
        """``src``/``dst`` are ServingEngines (callers hold whatever locks
        protect them — the router runs this inside its iteration). The
        destination blocks come from PLAIN allocation: a handoff never
        evicts or preempts the decode pool's residents. When ``trace`` is
        set, the export and import stages land on the request's trace with
        their replica identities — the handoff timeline spans both ends of
        the seam."""
        _check_geometry(_EngineView(src), _EngineView(dst))
        # pages are all this transfer moves (ServingEngine._no_state_snapshot)
        src._no_state_snapshot("kv_export (handing a prefilled sequence to "
                               "another engine)")
        dst_ids = dst.alloc.alloc(len(blocks))
        if dst_ids is None:
            return None
        maxb = src.blocks_per_seq
        src_pad = np.zeros((maxb,), np.int32)
        src_pad[:len(blocks)] = blocks
        dst_pad = np.zeros((maxb,), np.int32)
        dst_pad[:len(dst_ids)] = dst_ids
        from ...observability import get_session

        obs = get_session()
        rt = obs.reqtrace if trace is not None else None
        clock = src.clock
        try:
            with obs.span("fleet/kv_handoff", blocks=len(blocks)):
                t0 = clock() if rt is not None else 0.0
                with mesh_mod.ambient(src.engine.mesh):
                    buf_k, buf_v = self._export(src._arena, src_pad)
                    if rt is not None:
                        import jax

                        # tpusync: disable=blocking-under-lock — tracing
                        # mode only; the sync buys stage-honest export/
                        # import timings and the handoff must be atomic
                        # with arena state anyway
                        jax.block_until_ready(buf_k)   # stage-honest split
                if rt is not None:
                    t1 = clock()
                    rt.interval(trace, "handoff", t0, t1,
                                kind="handoff_export",
                                replica=src.trace_tag, blocks=len(blocks))
                # mid-flight: after the export left the source, before the
                # import commits to the destination — the window a real
                # cross-host transfer dies in
                self._maybe_inject_failure()
                t2 = clock() if rt is not None else 0.0
                with mesh_mod.ambient(dst.engine.mesh):
                    dst._arena = self._import(dst._arena, buf_k, buf_v,
                                              dst_pad)
                import jax

                # tpusync: disable=blocking-under-lock — the import must
                # commit before the request rebinds to the decode replica;
                # a torn arena is worse than a stalled lock, and the copy
                # is bounded (one request's blocks, layer-chunked)
                jax.block_until_ready(dst._arena["k"])   # honest latency
                if rt is not None:
                    rt.interval(trace, "handoff", t2, clock(),
                                kind="handoff_import",
                                replica=dst.trace_tag, blocks=len(dst_ids))
        except Exception:
            # a failed transfer must not leak destination blocks; a partial
            # import is harmless garbage once its blocks return to the pool
            dst.alloc.free(dst_ids)
            raise
        self.transfers += 1
        return dst_ids


class _EngineView:
    """Geometry-check adapter (``_check_geometry`` predates the router's
    Replica wrapper and is also used engine-to-engine)."""

    def __init__(self, engine):
        self.engine = engine.engine
        self.config = engine.config
        self.blocks_per_seq = engine.blocks_per_seq
        self._dtype = engine._dtype


def register_handoff_audit_entries(engine, handoff: ArenaHandoff
                                   ) -> List[str]:
    """Register ``serving/kv_export`` / ``serving/kv_import`` with tpuaudit
    (and therefore tpucost): pure block gather/scatter along the replicated
    block axis — zero collectives whatever the engine's TP/EP layout; the
    import donates the destination arena. ``engine`` supplies the arena
    shapes (source and destination pools share geometry by construction)."""
    try:
        from tools.tpuaudit.registry import (StaleEntryError,
                                             register_entry_point)
    except ImportError:
        return []
    try:
        import weakref

        import jax
        import jax.numpy as jnp

        weng = weakref.ref(engine)
        maxb = engine.blocks_per_seq
        cfg = engine.engine.model.config
        bs = engine.config.block_size

        def _shapes(eng):
            arena = eng._arena_sds()
            buf = jax.ShapeDtypeStruct(
                (cfg.num_layers, maxb, bs, cfg.num_kv_heads * cfg.head_dim),
                eng._dtype)
            ids = jax.ShapeDtypeStruct((maxb,), jnp.int32)
            return arena, buf, ids

        def build_export():
            eng = weng()
            if eng is None:
                raise StaleEntryError("serving/kv_export: engine gone")
            arena, _, ids = _shapes(eng)
            return handoff._export, (arena, ids), {}

        def build_import():
            eng = weng()
            if eng is None:
                raise StaleEntryError("serving/kv_import: engine gone")
            arena, buf, ids = _shapes(eng)
            return handoff._import, (arena, buf, buf, ids), {}

        # no params in these programs — the "handoff" tag is tpushard's
        # geometry seam: export OUTPUT buffers must land exactly like
        # import's staging-buffer ARGS (args 1, 2), else the fleet would
        # reshard every migrated request's KV mid-flight
        register_entry_point(
            "serving/kv_export", build=build_export,
            expected_collectives=(), mesh=engine.engine.mesh,
            tags={"engine": "FleetRouter", "max_blocks": maxb,
                  "block_size": bs,
                  "handoff": {"role": "export"}})
        register_entry_point(
            "serving/kv_import", build=build_import, donate_argnums=(0,),
            expected_collectives=(), mesh=engine.engine.mesh,
            tags={"engine": "FleetRouter", "max_blocks": maxb,
                  "block_size": bs,
                  "handoff": {"role": "import", "buffer_args": (1, 2)}})
        return ["serving/kv_export", "serving/kv_import"]
    except Exception:   # registration must never take serving down
        logger.warning("tpuaudit handoff registration failed", exc_info=True)
        return []

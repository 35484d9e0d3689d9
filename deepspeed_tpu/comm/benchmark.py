"""Collective micro-benchmark sweep — the ``ds_bench`` analog.

Reference: ``bin/ds_bench`` shells out to DeepSpeedExamples'
``benchmarks/communication`` suite (all_reduce / all_gather /
reduce_scatter / all_to_all / broadcast / pt2pt swept over message sizes,
reporting algbw + busbw with the NCCL-tests conventions the reference's
``utils/comms_logging.py`` get_bw also uses). Here the suite is
self-contained: each op is a jitted ``shard_map`` over a mesh axis, timed
with a device fence, with bandwidth math shared with
``comm/comms_logging.py`` (one formula set, no drift).

Usage (CLI: ``bin/ds-tpu-bench``)::

    ds-tpu-bench --op all_reduce --axis data --maxsize 26   # 2^26 B max
    ds-tpu-bench --op all                                    # full suite
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..parallel import mesh as mesh_mod
from . import comm
from .comms_logging import calc_bw_log

OPS = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
       "broadcast", "pt2pt")


def _op_fn(op: str, axis: str):
    """The per-device collective body (runs inside shard_map)."""
    if op == "all_reduce":
        return lambda x: comm.all_reduce(x, axis=axis)
    if op == "all_gather":
        return lambda x: comm.all_gather(x, axis=axis)
    if op == "reduce_scatter":
        return lambda x: comm.reduce_scatter(x, axis=axis)
    if op == "all_to_all":
        return lambda x: comm.all_to_all(x, axis=axis)
    if op == "broadcast":
        return lambda x: comm.broadcast(x, src=0, axis=axis)
    if op == "pt2pt":
        return lambda x: comm.send_next(x, axis=axis)
    raise ValueError(f"unknown op '{op}' (expected one of {OPS})")


def _build(op: str, axis: str, mesh, elems: int, dtype):
    """Jitted program + per-device input for one (op, size) cell.

    Input/output shardings mirror each op's natural layout; ``elems`` is
    the per-rank MESSAGE buffer (NCCL-tests convention): the per-device
    input for all_reduce/all_gather/all_to_all/broadcast/pt2pt, the
    per-rank result shard for reduce_scatter (whose input is the
    replicated (n*elems,) buffer)."""
    n = int(mesh.shape.get(axis, 0))
    if n < 2:
        raise ValueError(
            f"axis '{axis}' has size {n} in mesh {dict(mesh.shape)} — a "
            "collective sweep needs an axis of >= 2 devices (build the mesh "
            "with that degree, e.g. --dp for 'data')")
    fn = _op_fn(op, axis)
    if op in ("all_reduce", "broadcast", "pt2pt"):
        # distinct (elems,) block per device; all_reduce's psum result is
        # replicated, the other two keep per-device outputs
        in_spec = P(axis)
        out_spec = P() if op == "all_reduce" else P(axis)
        global_shape = (n * elems,)
    elif op == "all_gather":
        in_spec, out_spec = P(axis), P()      # (elems,) per dev -> replicated
        global_shape = (n * elems,)
    elif op == "reduce_scatter":
        # replicated (n*elems,) in -> (elems,) shard out, so the per-rank
        # RESULT shard is `elems` and calc_bw_log's size*n convention (the
        # NCCL-tests recvcount basis) matches all_gather's accounting
        in_spec, out_spec = P(), P(axis)
        global_shape = (n * elems,)
    elif op == "all_to_all":
        in_spec, out_spec = P(axis), P(axis)  # exchange along dim 0
        global_shape = (n * elems,)
    x = jnp.zeros(global_shape, dtype) + 1
    prog = jax.jit(shard_map(fn, mesh=mesh, in_specs=in_spec,
                                 out_specs=out_spec, check_vma=False))
    return prog, x


def run_comm_benchmark(ops: Optional[List[str]] = None, axis: str = "data",
                       minsize_log2: int = 12, maxsize_log2: int = 26,
                       trials: int = 10, warmups: int = 2,
                       dtype=jnp.bfloat16, mesh=None,
                       quiet: bool = False) -> List[Dict[str, Any]]:
    """Sweep each op over per-device message sizes 2^min..2^max bytes.

    Returns one record per (op, size): latency p50, algbw, busbw — busbw
    uses the same factors as the comms logger (all_reduce 2(n-1)/n etc.),
    so sweep numbers and training-time logs are directly comparable."""
    if mesh is None:
        mesh = mesh_mod.get_mesh()
    n = int(mesh.shape.get(axis, 0))
    if n < 2:
        raise ValueError(
            f"axis '{axis}' has size {n} in mesh {dict(mesh.shape)} — a "
            "collective sweep needs an axis of >= 2 devices")
    itemsize = jnp.dtype(dtype).itemsize
    results: List[Dict[str, Any]] = []
    for op in (ops or list(OPS)):
        size = 1 << minsize_log2
        while size <= (1 << maxsize_log2):
            # round up to a multiple of the axis size: reduce_scatter /
            # all_to_all shard the message evenly across the axis
            elems = max(size // itemsize, n)
            elems = ((elems + n - 1) // n) * n
            prog, x = _build(op, axis, mesh, elems, dtype)
            for _ in range(warmups):
                jax.block_until_ready(prog(x))
            ts = []
            for _ in range(trials):
                t0 = time.perf_counter()
                jax.block_until_ready(prog(x))
                ts.append(time.perf_counter() - t0)
            lat = sorted(ts)[len(ts) // 2]
            msg_bytes = elems * itemsize
            _, algbw, busbw = calc_bw_log(op if op != "pt2pt" else "p2p",
                                          msg_bytes, lat, n)
            rec = {"op": op, "axis": axis, "world": n,
                   "msg_bytes": msg_bytes, "latency_ms": round(lat * 1e3, 4),
                   "algbw_gbps": round(algbw, 6),
                   "busbw_gbps": round(busbw, 6)}
            results.append(rec)
            if not quiet:
                print(f"{op:<16}{msg_bytes:>12}B  {rec['latency_ms']:>10.3f} ms"
                      f"  algbw {rec['algbw_gbps']:>9.2f} Gbps"
                      f"  busbw {rec['busbw_gbps']:>9.2f} Gbps")
            size <<= 1
    return results


def cli_main(argv: Optional[List[str]] = None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="ds-tpu-bench",
        description="Collective benchmark sweep (the ds_bench analog) over "
                    "a mesh axis.")
    p.add_argument("--op", default="all",
                   help=f"one of {', '.join(OPS)} or 'all'")
    p.add_argument("--axis", default="data")
    p.add_argument("--minsize", type=int, default=12,
                   help="log2 of the smallest per-device message in bytes")
    p.add_argument("--maxsize", type=int, default=26,
                   help="log2 of the largest per-device message in bytes")
    p.add_argument("--trials", type=int, default=10)
    p.add_argument("--warmups", type=int, default=2)
    p.add_argument("--dtype", default="bf16",
                   choices=["bf16", "fp16", "fp32", "int8"])
    p.add_argument("--json", action="store_true",
                   help="emit one JSON line with every record")
    p.add_argument("--dp", type=int, default=0,
                   help="data-parallel degree (default: all devices)")
    args = p.parse_args(argv)

    from ..config.config import ParallelConfig

    dtype = {"bf16": jnp.bfloat16, "fp16": jnp.float16,
             "fp32": jnp.float32, "int8": jnp.int8}[args.dtype]
    if args.axis != "data":
        p.error(f"--axis {args.axis}: the CLI builds a data-only mesh; "
                "sweep other axes via run_comm_benchmark(mesh=...) with a "
                "mesh that has that degree")
    dp = args.dp or len(jax.devices())
    mesh = mesh_mod.build_mesh(ParallelConfig(data_parallel_size=dp),
                               devices=jax.devices()[:dp])
    ops = list(OPS) if args.op == "all" else [args.op]
    results = run_comm_benchmark(ops=ops, axis=args.axis,
                                 minsize_log2=args.minsize,
                                 maxsize_log2=args.maxsize,
                                 trials=args.trials, warmups=args.warmups,
                                 dtype=dtype, mesh=mesh, quiet=args.json)
    if args.json:
        print(json.dumps(results))
    return 0

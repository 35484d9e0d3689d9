#!/usr/bin/env python3
"""The paged prefill kernel alone on the chip, at the serving cells' shapes:
what a call costs, what of it is the products, the exponent and statistics,
and the copies, and what each form of a tile step costs (PERF.md section 6,
PR 69). Run by hand, by no cell:

    chiprun --timeout 1800 -- python scripts/time_paged_prefill_attention_on_chip.py
        [--shapes opt,lfm2] [--calls 192] [--rounds 5]
        [--parent /path/to/another/tree] [--tiny]

For each shape (a cell's chunk, heads, page and the `start`s its chunks
stand at: `opt` is `opt-1.3b.serve-prefill`'s, `lfm2` the LFM2 cell's,
`olmoe` a control with heads of 128, `phi` the windowed call as
`paged_attention` hands it down) these forms, every one
`ops.paged_decode_attention.paged_prefill_attention` over the SAME operands:

- `parent`: the kernel of the tree `--parent` names (first AND last: a
  first form reads a few percent slow), and `tree`, this tree's as derived;
- either with a phase off, switched at trace time and nowhere in the
  kernel: `-noproducts` (a product is its left operand's first column
  plus a ramp along the lanes), `-noexp` (no exponent, and a row's max and sum are its
  first column), `-nocopies` (no page is copied; a tile holds what was
  there);
- `tree[QB n,KB n,sums s,tile n]`: this tree's kernel with its derived
  query block and key sub-block (`_chunk_blocks`; `KB T`: the whole tile,
  so a visit computes all of a tile or nothing), the place of the running
  sum (`_sums_in_slab`; `vpu`: a sum of its own and ONE value product for
  the group's stacked heads) or a tile of n keys at most
  (`_CHUNK_TILE_KEYS`) forced.

A form is timed as ONE program of `--calls` calls (a `fori_loop`; call i
stands at the i-th of the shape's starts and reads layer i of the arena,
chained through a scalar of its result), `--rounds` times in turn with the
others, and printed as the median microseconds a call with the quartiles
over the rounds. A call a dispatch would time the host. Every whole form's
result is held against the parent's (or the first's) on the real queries.
Each tree's kernel is also traced and lowered once on this host (a cell's
`setup_s` pays that for every program that holds it). One JSON object on
the last line, and in chiprun_out/paged_prefill_timing.json. `--tiny`
rehearses the script on the CPU in interpret mode: no number of it is a
time. `--describe` compiles every form for a described v5e here (a form
that ABORTS the compiler would end a chip call with nothing read: the last
line printed names it).
"""

import argparse
import contextlib
import importlib
import importlib.util
import json
import os
import statistics
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# name: chunk, heads, KV heads, head size, page, pages a row's table holds,
# window, the starts a call stands at in turn (real tokens: the whole chunk)
SHAPES = {
    "opt": (256, 32, 32, 64, 16, 128, None, range(0, 1792, 256)),
    "lfm2": (1024, 32, 8, 64, 16, 520, None, range(0, 8192, 1024)),
    "olmoe": (256, 16, 16, 128, 16, 128, None, range(0, 1792, 256)),
    # under a window the table starts at the window's first page: a chunk
    # deep in a long row stands at 511-526 of the keys it is handed
    "phi": (256, 40, 10, 128, 16, 50, 512, range(511, 527)),
}
TINY = (32, 4, 4, 64, 16, 12, None, range(0, 160, 32))
# (QB, KB, sums, tile) forced on the tree's kernel; None: as derived; "T":
# the whole tile
FORMS = [(None, None, "vpu", None), (None, "T", None, None),
         (None, 256, None, None), (256, None, None, None),
         (512, None, None, None), (None, 256, None, 512),
         (None, "T", None, 512)]

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--calls", type=int, default=192)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--parent", default=None)
    ap.add_argument("--parts", default="opt,lfm2",
                    help="shapes that also get the phases-off and the "
                         "forced forms")
    ap.add_argument("--only", default="",
                    help="forms whose name holds this, and no others")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--describe", action="store_true",
                    help="compile every form for a described v5e, here, "
                         "and time nothing: what the chip's compiler "
                         "refuses costs no chip time")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    tree = importlib.import_module("deepspeed_tpu.ops.paged_decode_attention")
    interpret = args.tiny
    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        v5e = jax.sharding.SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])
    elif not interpret and jax.default_backend() != "tpu":
        raise SystemExit("no TPU here: a time comes from the chip alone "
                         "(--tiny rehearses on the CPU)")
    parent = None
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "deepspeed_tpu.ops._parent_pda", os.path.join(
                args.parent, "deepspeed_tpu/ops/paged_decode_attention.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    f32 = jnp.float32

    @contextlib.contextmanager
    def patched(*patches):
        """(object, name, value) set for as long as a kernel is traced."""
        old = [(o, n, getattr(o, n)) for o, n, _ in patches]
        for o, n, v in patches:
            setattr(o, n, v)
        try:
            yield
        finally:
            for o, n, v in old:
                setattr(o, n, v)

    def no_product(a, b, dims, **_):
        free = [d for d in range(b.ndim) if d not in dims[0][1]]
        shape = (a.shape[0], b.shape[free[0]])
        # not a plain broadcast: the compiler aborts on a row reduction of
        # a value it knows to be replicated along the lanes
        return (a.astype(f32)[:, :1]
                + 1e-3 * lax.broadcasted_iota(jnp.int32, shape, 1).astype(f32))

    def first_column(x, axis=None, keepdims=False):
        assert axis == 1 and keepdims
        return x[:, :1]

    def phase_off(mod, phase):
        if phase == "noproducts":
            return [(lax, "dot_general", no_product)]
        if phase == "noexp":
            return [(jnp, "exp", lambda x: x), (jnp, "max", first_column),
                    (jnp, "sum", first_column)]
        assert phase == "nocopies", phase
        return [(mod, "_page_copies",
                 lambda *a, **k: lambda *a, **k: None)]

    def forced(qb, kb, sums, tile):
        """The patches that force a form on the tree's kernel."""
        out = []
        if tile is not None:
            out.append((tree, "_CHUNK_TILE_KEYS", tile))
        if qb is not None or kb is not None:
            derived = tree._chunk_blocks

            def blocks(chunk, heads, pages, block_size):
                QB, PB = derived(chunk, heads, pages, block_size)
                return (QB if qb is None else qb,
                        PB if kb is None else pages if kb == "T"
                        else max(kb // block_size, 1))
            out.append((tree, "_chunk_blocks", blocks))
        if sums is not None:
            out.append((tree, "_sums_in_slab", lambda hp: sums == "slab"))
        return out

    report = {"device": str(jax.devices()[0].device_kind),
              "calls": 4 if args.tiny else args.calls, "rounds": args.rounds,
              "shapes": {}}
    names = ["tiny"] if args.tiny else args.shapes.split(",")
    calls = 4 if args.tiny else args.calls
    for name in names:
        C, N, K, D, BS, maxb, window, starts = (TINY if args.tiny
                                                else SHAPES[name])
        L, dtype = 3, jnp.bfloat16
        NB = maxb + 1
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        q = jax.random.normal(keys[0], (1, C, N, D), f32).astype(dtype)
        ka, va = (jax.random.normal(k, (L, NB, BS, K * D), f32).astype(dtype)
                  for k in keys[1:])
        table = jnp.asarray(np.random.default_rng(args.seed).permutation(
            np.arange(1, NB))[None].astype(np.int32))
        starts = jnp.asarray(np.asarray(list(starts), np.int32))

        operands = (q, ka, va, table, starts)

        def one(mod, i, q, ka, va, table, starts):
            at = starts[i % starts.shape[0]][None]
            return mod.paged_prefill_attention(
                q, ka, va, i % L, table, at, at + C, interpret=interpret,
                **({} if window is None else {"window": window}))

        def program(mod):
            def run(*operands):
                def body(i, acc):
                    return acc + one(mod, i, *operands)[0, 0, 0, 0].astype(f32)
                return lax.fori_loop(0, calls, body, f32(0))
            return jax.jit(run)

        def outputs(mod):
            return jax.jit(lambda *operands: jnp.stack(
                [one(mod, i, *operands) for i in range(starts.shape[0])]))

        parts = name in args.parts.split(",") or args.tiny
        forms = {}
        if parent is not None:
            forms["parent"] = (parent, [])
        forms["tree"] = (tree, [])
        if parts:
            for tag, mod in (("parent", parent), ("tree", tree)):
                for phase in ("noproducts", "noexp", "nocopies"):
                    if mod is not None:
                        forms[f"{tag}-{phase}"] = (mod, phase_off(mod, phase))
            for qb, kb, sums, tile in FORMS:
                if qb is not None and (C % qb or qb == tree._chunk_geometry(
                        C, N, K, BS, K * D, dtype)[2]):
                    continue
                label = ",".join(
                    f"{n} {x}" for n, x in (("QB", qb), ("KB", kb),
                                            ("sums", sums), ("tile", tile))
                    if x is not None)
                forms[f"tree[{label}]"] = (tree, forced(qb, kb, sums, tile))
        if parent is not None:
            forms["parent-again"] = (parent, [])
        forms = {tag: f for tag, f in forms.items() if args.only in tag}

        if args.describe:
            shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e)
                      for a in operands]
            for tag, (mod, patches) in forms.items():
                print(f"{name:6s} {tag:24s} compiles ...", flush=True)
                try:
                    with patched(*patches):
                        program(mod).lower(*shapes).compile()
                except Exception as e:
                    print(f"{name:6s} {tag:24s} REFUSED {type(e).__name__}: "
                          f"{str(e)[:300]}", flush=True)
            continue
        runs, first, diffs, failed = {}, None, {}, {}
        for tag, (mod, patches) in forms.items():
            try:
                with patched(*patches):
                    runs[tag] = program(mod).lower(*operands).compile()
                    whole = not any(tag.endswith(p) for p in (
                        "-noproducts", "-noexp", "-nocopies"))
                    if whole and tag != "parent-again":
                        out = np.asarray(outputs(mod)(*operands).astype(f32))
                        first = out if first is None else first
                        diffs[tag] = float(np.abs(out - first).max())
            except Exception as e:          # a form the compiler refuses
                failed[tag] = f"{type(e).__name__}: {str(e)[:300]}"
                print(f"{name:6s} {tag:24s} FAILED {failed[tag]}", flush=True)
        for run in runs.values():           # warm
            run(*operands).block_until_ready()
        times = {tag: [] for tag in runs}
        for _ in range(args.rounds):
            for tag, run in runs.items():
                t0 = time.perf_counter()
                run(*operands).block_until_ready()
                times[tag].append((time.perf_counter() - t0) / calls * 1e6)
        shape_report = report["shapes"][name] = {
            "chunk": C, "heads": N, "kv_heads": K, "head_dim": D,
            "window": window, "starts": [int(s) for s in starts],
            "forms": {}, "failed": failed}
        for tag, ts in times.items():
            qs = (statistics.quantiles(ts, n=4) if len(ts) > 1
                  else [ts[0]] * 3)
            shape_report["forms"][tag] = {
                "us_a_call": [round(v, 2) for v in qs],
                "maxdiff_to_first": diffs.get(tag)}
            print(f"{name:6s} {tag:24s} {qs[1]:9.2f} us  "
                  f"[{qs[0]:.2f}, {qs[2]:.2f}]  diff {diffs.get(tag)}",
                  flush=True)
        # what a program that holds the kernel pays before any compile
        for tag, mod in (("parent", parent), ("tree", tree)):
            if mod is None:
                continue
            fn = jax.jit(lambda i, mod=mod: one(mod, i, *operands))
            t0 = time.perf_counter()
            traced = fn.trace(jax.ShapeDtypeStruct((), jnp.int32))
            t1 = time.perf_counter()
            traced.lower()
            shape_report[f"{tag}_trace_s"] = round(t1 - t0, 3)
            shape_report[f"{tag}_lower_s"] = round(time.perf_counter() - t1,
                                                   3)
            print(f"{name:6s} {tag}: trace {t1 - t0:.3f} s, lower "
                  f"{time.perf_counter() - t1:.3f} s", flush=True)
        del runs, ka, va

    if args.describe:
        return
    os.makedirs(os.path.join(REPO_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO_ROOT, "chiprun_out",
                           "paged_prefill_timing.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()

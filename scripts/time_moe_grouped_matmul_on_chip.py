#!/usr/bin/env python3
"""The expert matmul alone on the chip, at the serving cells' shapes: what a
call costs under each block rule, and what of it is the weight copies and
what the products (PERF.md section 6, PR 68). Run by hand, by no cell:

    chiprun --timeout 1800 -- python scripts/time_moe_grouped_matmul_on_chip.py
        [--shapes olmoe-decode,solar-decode] [--calls 240] [--rounds 5]
        [--parent /path/to/another/tree] [--tiny]

For each shape (a cell's rows, experts a token, held experts, widths; the
groups drawn like a step's: every row picks its experts among the router's
outputs, those past the held ones reach nobody) and each side of a layer
(`up`: one (H, F) matrix an expert; `down`: (F, H); `gated`: gate and up),
these forms, every one a `pallas_call` over the SAME operands:

- `cols`: the column blocks that 2 MiB a block allow (the rule the kernel
  had before PR 68); for the gated side, two such calls and the float32
  `silu(gate) * up` between them, as `parallel/moe` made them;
- `tree`: `ops.moe_grouped_matmul` as this tree has it (`gate=` for the
  gated side); `parent`: the same from the tree `--parent` names;
- `cols` and `tree` again with the product off (`-copies`: a block's first
  row is broadcast into the result, so every copy is still made) and with
  the weight index map pinned to one block (`-products`: nothing is copied
  after the first step).

A form is timed as ONE program of `--calls` calls (a `fori_loop`; each call
reads another layer of a `(L, E, K, N)` stack, chained through a scalar of
its result), `--rounds` times in turn with the others, and printed as the
median microseconds a call with the quartiles over the rounds. A call a
dispatch would time the host (0.6 ms a call). Every form's result is held
against the first's on the rows that hold an assignment. The tree's kernel
is also traced and lowered once on this host (a cell's `setup_s` pays that
for every program that holds it). One JSON object on the last line, and in
chiprun_out/moe_gmm_timing.json. `--tiny` rehearses the script on the CPU
in interpret mode: no number of it is a time.
"""

import argparse
import functools
import importlib
import importlib.util
import json
import os
import statistics
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# name: rows of a step or chunk, experts a token, the router's outputs, the
# held experts, hidden (or latent) width, expert width, gated, layers timed
SHAPES = {
    "olmoe-decode": (16, 8, 64, 64, 2048, 1024, True, 12),
    "olmoe-chunk": (256, 8, 64, 64, 2048, 1024, True, 12),
    "lfm2-decode": (16, 4, 32, 32, 2048, 1792, True, 6),
    "lfm2-chunk": (1024, 4, 32, 32, 2048, 1792, True, 6),
    "solar-decode": (64, 8, 320, 40, 4096, 1280, True, 4),
    "nemotron-decode": (64, 22, 512, 64, 1024, 2688, False, 8),
    "longcat-decode": (32, 12, 768, 16, 6144, 2048, True, 4),
}
TINY = (16, 2, 8, 4, 256, 128, True, 2)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--calls", type=int, default=240)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--parent", default=None)
    ap.add_argument("--parts", default="olmoe-decode,solar-decode",
                    help="shapes that also get the -copies and -products "
                         "forms")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # the package exports the function under the module's name
    tree = importlib.import_module("deepspeed_tpu.ops.moe_grouped_matmul")

    interpret = args.tiny
    if not interpret and jax.default_backend() != "tpu":
        raise SystemExit("no TPU here: a time comes from the chip alone "
                         "(--tiny rehearses on the CPU)")
    parent = None
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "deepspeed_tpu.ops._parent_gmm", os.path.join(
                args.parent, "deepspeed_tpu/ops/moe_grouped_matmul.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    f32 = jnp.float32

    def cols_2mib(K, N, dtype):
        """The block rule before PR 68: 2 MiB a block."""
        budget = 2 * 2 ** 20 // (K * jnp.dtype(dtype).itemsize)
        fit = [c for c in range(128, N + 1, 128) if N % c == 0 and c <= budget]
        return fit[-1] if fit and N % 128 == 0 else N

    def call(lhs, stacks, te, used, layer, tn, product=True, pinned=False):
        """`ops.moe_grouped_matmul`'s call with the block given and the
        product or the copies switched off."""
        rows, K = lhs.shape
        N = stacks[0].shape[-1]
        tiles = te.shape[0]
        tm = rows // tiles

        def kernel(te_ref, used_ref, layer_ref, x_ref, *refs):
            if product:                     # the tree's own body
                return tree._gmm_kernel(te_ref, used_ref, layer_ref, x_ref,
                                        *refs)
            *w_refs, o_ref = refs

            @pl.when(pl.program_id(1) < used_ref[0])
            def _tile():
                o_ref[...] = jnp.broadcast_to(
                    sum(w[0:1, :] for w in w_refs), o_ref.shape)

        def tile(i, used_ref):
            return jnp.minimum(i, jnp.maximum(used_ref[0] - 1, 0))

        weights = pl.BlockSpec(
            (None, None, K, tn),
            (lambda n, i, te, used, layer: (layer[0], 0, 0, 0)) if pinned
            else (lambda n, i, te, used, layer: (layer[0], te[i], 0, n)))
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=(N // tn, tiles),
                in_specs=[pl.BlockSpec(
                    (tm, K), lambda n, i, te, used, layer: (tile(i, used), 0))
                ] + [weights] * len(stacks),
                out_specs=pl.BlockSpec(
                    (tm, tn),
                    lambda n, i, te, used, layer: (tile(i, used), n))),
            out_shape=jax.ShapeDtypeStruct((rows, N), lhs.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=tree._vmem_bytes(K, tn, stacks[0].dtype,
                                                  len(stacks))),
            name="moe_grouped_matmul", interpret=interpret,
        )(te, used, jnp.asarray(layer, jnp.int32).reshape(1), lhs, *stacks)

    def module_form(mod):
        def form(lhs, stacks, te, used, layer):
            if len(stacks) == 1:
                return mod.moe_grouped_matmul(lhs, stacks[0], te, used, layer,
                                              interpret=interpret)
            if "gate" in mod.moe_grouped_matmul.__code__.co_varnames:
                return mod.moe_grouped_matmul(lhs, stacks[1], te, used, layer,
                                              interpret=interpret,
                                              gate=stacks[0])
            return two_calls(lambda x, w, *a: mod.moe_grouped_matmul(
                x, w, *a, interpret=interpret))(lhs, stacks, te, used, layer)
        return form

    def two_calls(mm):
        """gate and up as a call each and the fusion between them."""
        def form(lhs, stacks, te, used, layer):
            gate, up = (mm(lhs, w, te, used, layer).astype(f32)
                        for w in stacks)
            return (jax.nn.silu(gate) * up).astype(lhs.dtype)
        return form

    def forms_of(side, K, N, dtype, parts):
        gated = side == "gated"
        tn = cols_2mib(K, N, dtype)
        new = tree._weight_block_cols(K, N, dtype, 2 if gated else 1)

        def cols(lhs, stacks, te, used, layer, **kw):
            return call(lhs, stacks, te, used, layer, tn, **kw)

        one = lambda x, w, te, used, layer: call(x, [w], te, used, layer, tn)
        forms = {f"cols[{tn}]": two_calls(one) if gated else cols,
                 f"tree[{new}]": module_form(tree)}
        if parent is not None:
            forms["parent"] = module_form(parent)
        if parts:
            if gated:
                forms[f"gated-cols[{tn}]"] = cols
            for kw, tag in (({"product": False}, "copies"),
                            ({"pinned": True}, "products")):
                forms[f"cols[{tn}]-{tag}"] = functools.partial(cols, **kw)
                forms[f"tree[{new}]-{tag}"] = functools.partial(
                    lambda *a, **k: call(*a, new, **k), **kw)
        return forms

    def program(form, calls):
        @jax.jit
        def run(lhs, stacks, te, used):
            L = stacks[0].shape[0]

            def body(i, acc):
                out = form(lhs, stacks, te, used, i % L)
                return acc + out[0, 0].astype(f32)
            return lax.fori_loop(0, calls, body, f32(0))
        return run

    report = {"device": str(jax.devices()[0].device_kind), "calls": 4 if args.tiny else args.calls,
              "rounds": args.rounds, "shapes": {}}
    rng = np.random.default_rng(args.seed)
    names = ["tiny"] if args.tiny else args.shapes.split(",")
    calls = 4 if args.tiny else args.calls
    for name in names:
        T, k, router, held, H, F, gated, L = TINY if args.tiny else SHAPES[name]
        dtype = jnp.bfloat16
        picks = np.stack([rng.choice(router, k, replace=False)
                          for _ in range(T)]).reshape(-1)
        sizes = np.bincount(picks[picks < held], minlength=held)
        tm = tree.tile_rows(T * k, held, dtype)
        _, te, used = tree.group_layout(jnp.asarray(sizes, jnp.int32), T * k,
                                        tm)
        rows = te.shape[0] * tm
        live = np.arange(rows) < int(used[0]) * tm
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 5)
        shape_report = report["shapes"][name] = {
            "assignments": int(sizes.sum()), "touched": int((sizes > 0).sum()),
            "held": held, "tile_rows": tm, "tiles": int(te.shape[0]),
            "used": int(used[0]), "sides": {}}
        sides = {"up": (H, F, 1), "down": (F, H, 1)}
        if gated:
            sides["gated"] = (H, F, 2)
        for side, (K, N, matrices) in sides.items():
            lhs = jax.random.normal(keys[0], (rows, K), f32).astype(dtype)
            stacks = [(jax.random.normal(keys[1 + m], (L, held, K, N), dtype)
                       * K ** -0.5).astype(dtype) for m in range(matrices)]
            forms = forms_of(side, K, N, dtype, name in args.parts.split(",")
                             or args.tiny)
            runs = {tag: program(form, calls) for tag, form in forms.items()}
            first, diffs = None, {}
            for tag, form in forms.items():
                if tag.endswith(("-copies", "-products")):
                    continue
                out = np.asarray(jax.jit(form)(lhs, stacks, te, used, L - 1
                                               ).astype(f32))[live]
                first = out if first is None else first
                diffs[tag] = float(np.abs(out - first).max())
            for run in runs.values():           # compile, and warm
                run(lhs, stacks, te, used).block_until_ready()
            times = {tag: [] for tag in runs}
            for _ in range(args.rounds):
                for tag, run in runs.items():
                    t0 = time.perf_counter()
                    run(lhs, stacks, te, used).block_until_ready()
                    times[tag].append((time.perf_counter() - t0) / calls * 1e6)
            touched = int((sizes > 0).sum())
            weight_us = (touched * matrices * K * N * 2) / 819e9 * 1e6
            side_report = shape_report["sides"][side] = {
                "K": K, "N": N, "weights_at_819GBs_us": round(weight_us, 1),
                "forms": {}}
            for tag, ts in times.items():
                q = (statistics.quantiles(ts, n=4) if len(ts) > 1
                     else [ts[0]] * 3)
                side_report["forms"][tag] = {
                    "us_a_call": [round(v, 1) for v in q],
                    "maxdiff_to_first": diffs.get(tag)}
                print(f"{name:16s} {side:6s} {tag:28s} "
                      f"{q[1]:9.1f} us  [{q[0]:.1f}, {q[2]:.1f}]  "
                      f"weights {weight_us:.1f} us at 819 GB/s  "
                      f"diff {diffs.get(tag)}", flush=True)
            del stacks, runs
        # what a program that holds the kernel pays before any compile
        K, N = H, F
        shapes = [jax.ShapeDtypeStruct((rows, K), dtype),
                  jax.ShapeDtypeStruct((L, held, K, N), dtype),
                  jax.ShapeDtypeStruct(te.shape, jnp.int32),
                  jax.ShapeDtypeStruct((1,), jnp.int32),
                  jax.ShapeDtypeStruct((), jnp.int32)]
        fn = jax.jit(lambda x, w, te, used, layer: tree.moe_grouped_matmul(
            x, w, te, used, layer, interpret=interpret,
            **({"gate": w} if gated else {})))
        t0 = time.perf_counter()
        traced = fn.trace(*shapes)
        t1 = time.perf_counter()
        traced.lower()
        shape_report["trace_s"] = round(t1 - t0, 3)
        shape_report["lower_s"] = round(time.perf_counter() - t1, 3)

    os.makedirs(os.path.join(REPO_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO_ROOT, "chiprun_out", "moe_gmm_timing.json"),
              "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""OLMoE at the benchmark's configuration against its plain float32
reference, on the chip, outside any timed window (model-configs section 3):

    chiprun -- python scripts/check_olmoe_on_chip.py [--config olmoe-1b-7b-d12]
        [--sequences 3] [--seed 1] [--tiny]

For each of a seeded sample of sequences (a prompt of 448-896 tokens, 64-128
greedy tokens after it, through `ServingEngine`: prefill in chunks, then the
decode program, both over the paged cache):

- `logprob_maxdiff`: the served log-probabilities of the whole sequence
  (`score_logprobs`) against the reference's full forward pass under
  `jax.default_matmul_precision("highest")`;
- `greedy_deficit_max`: how far below the reference's best logit at its
  position the token that the decode program chose lies, in the reference's
  own logits (0 where both choose alike): the decode steps' logits judged
  without comparing tokens;
- `topk_sets_differ_share`: the share of (token, layer) pairs in which the
  program's set of chosen experts is not the reference's (a bf16 router input
  flips near-ties), read from the program's own `route_topk` through a host
  callback while the sequence is scored;
- the same three for the reference's wrong models (renormalised weights, one
  expert a token, no q/k norm), which have to read far above the tolerance.

`--tiny` runs the configuration's small float32 model (on the CPU too): the
rehearsal of this script, not a measurement. Prints one JSON object and
writes it to chiprun_out/olmoe_check.json.
"""

import argparse
import gc
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

CONTROLS = {"renormalised": {"norm_topk_prob": True},
            "top-1": {"num_experts_per_tok": 1},
            "no-qk-norm": {"qk_norm": False}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="olmoe-1b-7b-d12")
    ap.add_argument("--sequences", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.inference.engine import InferenceConfig
    from deepspeed_tpu.parallel import moe

    from benchmarks.harness import program
    from benchmarks.harness.serve_cell import serving_config
    from benchmarks.harness.spec import Spec

    sys.path.insert(0, os.path.join(REPO_ROOT, "tests", "benchmark_harness"))
    cell = Spec(REPO_ROOT).cell(f"{args.config}.serve-decode")
    if args.tiny:
        import bench_tiny

        cell.config = bench_tiny.tiny_config(cell.config)
        lo, hi, new_lo, new_hi = 40, 90, 8, 24
    else:
        lo, hi, new_lo, new_hi = 448, 896, 64, 128
    s = cell.config["serving"]
    dtype = getattr(jnp, cell.config["model"]["dtype"])
    model = program.build_model(cell)
    cfg = model.config
    serving = deepspeed_tpu.init_serving(
        model=model, config=InferenceConfig(dtype=dtype, seed=args.seed),
        serving_config=serving_config(cell, jax.devices())(cfg))

    # the program's own routing decisions, in the order they are made: the
    # score program's chunks, and within a chunk the layers
    chosen = []
    route = moe.route_topk

    def recording(gates, choice, k, normalize):
        idx, weight = route(gates, choice, k, normalize)
        jax.debug.callback(lambda i: chosen.append(np.asarray(i)), idx,
                           ordered=True)
        return idx, weight

    rng = np.random.default_rng(args.seed)
    served = []
    for _ in range(args.sequences):
        prompt = rng.integers(0, cfg.vocab_size, rng.integers(lo, hi + 1))
        handle = serving.submit(prompt.astype(np.int32),
                                max_new_tokens=int(rng.integers(new_lo,
                                                                new_hi + 1)))
        serving.run()
        full = np.concatenate([prompt, np.asarray(handle.result())])
        served.append((len(prompt), full.astype(np.int32)))
    moe.route_topk = recording
    scored = []
    C = int(s["prefill_chunk"])
    try:
        for _, full in served:
            chosen.clear()
            logp = serving.score_logprobs(full)
            jax.effects_barrier()
            L = cfg.num_layers
            assert len(chosen) == L * -(-len(full) // C), len(chosen)
            per_layer = [np.concatenate(chosen[layer::L])[:len(full)]
                         for layer in range(L)]
            scored.append((logp, np.stack(per_layer)))      # (L, S, k)
    finally:
        moe.route_topk = route
    params = serving.engine.params
    serving.close()
    del serving
    gc.collect()

    reference = program.reference_module(cell)
    ref_args = program.reference_args(cell)

    def ref_pass(**changed):
        kw = dict(ref_args, **changed)

        @jax.jit
        def run(p, ids):
            logits, router = reference._forward(p, ids, **kw)
            lp = jax.nn.log_softmax(logits, axis=-1)
            nxt = jnp.take_along_axis(lp[:, :-1], ids[:, 1:, None],
                                      axis=-1)[0, :, 0]
            _, top = jax.lax.top_k(router[:, 0], kw["num_experts_per_tok"])
            return (nxt, logits[0].max(-1),
                    jnp.take_along_axis(logits[0, :-1], ids[0, 1:, None],
                                        axis=-1)[:, 0], top)

        return run

    out = {"config": args.config, "tiny": args.tiny, "dtype": str(dtype),
           "device": jax.devices()[0].device_kind, "sequences": []}
    with jax.default_matmul_precision("highest"):
        passes = {"reference": ref_pass(),
                  **{name: ref_pass(**kw) for name, kw in CONTROLS.items()}}
        for (n_prompt, full), (logp, picked) in zip(served, scored):
            row = {"tokens": int(len(full)), "prompt": int(n_prompt)}
            for name, run in passes.items():
                nxt, best, of_next, top = (np.asarray(a) for a in
                                           run(params, full[None]))
                r = {"logprob_maxdiff": float(np.abs(logp - nxt).max()),
                     # decode steps: the token at p+1 was chosen from the
                     # program's logits at p, for p >= n_prompt - 1
                     "greedy_deficit_max": float(
                         (best[:-1] - of_next)[n_prompt - 1:].max())}
                if name == "reference":
                    same = (np.sort(picked, -1) == np.sort(top, -1)).all(-1)
                    r["topk_sets_differ_share"] = float(1.0 - same.mean())
                    r["pairs"] = int(same.size)
                row[name] = r
            out["sequences"].append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(os.path.join(REPO_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO_ROOT, "chiprun_out", "olmoe_check.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

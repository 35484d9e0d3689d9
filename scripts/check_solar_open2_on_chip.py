#!/usr/bin/env python3
"""Solar Open 2 at the benchmark's configuration against its plain float32
reference, on the chip, outside any timed window (model-configs section 3):

    chiprun -- python scripts/check_solar_open2_on_chip.py
        [--config solar-open2-250b-ep8-d4] [--sequences 3] [--seed 1] [--tiny]

Under the cell's own traffic (prompts 32-512, answers of 128 tokens and
more, greedy), for each of a seeded sample of sequences, all served at once
through `ServingEngine` (prefill in ragged chunks, then the decode program,
both over the pages AND the state pools):

- `logprob_maxdiff`: the served log-probabilities of the whole sequence
  (`score_logprobs`: the chunk programs' path, what the harness's `correct`
  reads) against the reference's full forward pass under
  `jax.default_matmul_precision("highest")`;
- `decode_logprob_maxdiff`: the same sequence once more, teacher-forced:
  its prompt through the engine's own prefill-chunk program, then every
  served token through the model's one-token paged step (the decode
  program's own call of `forward`, here returning logits instead of a
  sample: the `kda_decode_step` kernel, the paged decode kernel and the
  state pools), its log-probabilities of the served tokens against the same
  reference: prefill in chunks and then decoding, logits and not tokens;
- `greedy_deficit_max`: how far below the reference's best logit at its
  position the token that the decode PROGRAM chose lies, in the reference's
  own logits (0 where both choose alike);
- the same for the reference's controls, each of which leaves out or
  cheapens one thing (every matrix and every layer's input in float8's 3
  bits of mantissa, the precision below the stated one; the state kept in
  bfloat16 between tokens; beta without its factor 2; the decay dropped;
  the convolution dropped; the shared expert dropped; the weights
  renormalised over the held experts only): each has to read over the
  traffic file's limit, the sound model under it. `UNTOLD` names the one
  that no limit on logits can tell in a bfloat16 model (PERF.md section 7
  q): it is read and reported, and does not decide `ok`.

`--tiny` runs the configuration's small float32 model (on the CPU too): the
rehearsal of this script, not a measurement. Prints one JSON object and
writes it to chiprun_out/solar_open2_check.json.
"""

import argparse
import gc
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

def controls(jnp):
    return {"all-in-float8": {"mantissa_bits": 3},
            "state-in-bfloat16": {"state_dtype": jnp.bfloat16},
            "beta-without-its-2": {"beta_scale": 1.0},
            "no-decay": {"decay": False},
            "no-convolution": {"conv": False},
            "no-shared-expert": {"shared": False},
            "renormalised-over-held": {"renorm_over_held": True}}


# what of this check is a family's own (scripts/check_nemotron_h_on_chip.py
# brings another): the configuration and its cell's traffic, the reference's
# controls, those of them that no limit on logits can tell, the output file
FAMILY = {"config": "solar-open2-250b-ep8-d4", "traffic": "serve-decode-r64",
          "controls": controls, "untold": ("state-in-bfloat16",),
          "out": "solar_open2_check.json"}


def main(argv=None, family=FAMILY):
    controls, UNTOLD, CELL = (family["controls"], family["untold"],
                              family["traffic"])
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default=family["config"])
    ap.add_argument("--sequences", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.inference.engine import InferenceConfig
    from deepspeed_tpu.inference.kv_cache import cache_slots
    from deepspeed_tpu.models.transformer import (forward,
                                                  gather_target_logprobs)
    from deepspeed_tpu.parallel import mesh as mesh_mod
    from deepspeed_tpu.serving import paged_kv

    from benchmarks.harness import program
    from benchmarks.harness.serve_cell import serving_config
    from benchmarks.harness.spec import Spec

    sys.path.insert(0, os.path.join(REPO_ROOT, "tests", "benchmark_harness"))
    cell = Spec(REPO_ROOT).cell(f"{args.config}.{CELL}")
    if args.tiny:
        import bench_tiny

        cell.config = bench_tiny.tiny_config(cell.config)
        lo, hi, new_lo, new_hi, pad_to = 10, 70, 6, 20, 96
    else:
        # a family may ask for longer sequences (a window several deep)
        lo, hi, new_lo, new_hi, pad_to = family.get(
            "lengths", (32, 512, 128, 384, 1024))
    limit = float(cell.traffic["reference"]["logprob_atol"])
    dtype = getattr(jnp, cell.config["model"]["dtype"])
    model = program.build_model(cell)
    cfg = model.config
    serving = deepspeed_tpu.init_serving(
        model=model, config=InferenceConfig(dtype=dtype, seed=args.seed),
        serving_config=serving_config(cell, jax.devices())(cfg))

    # all at once, as the cell's callers send them
    rng = np.random.default_rng(args.seed)
    sent = []
    for _ in range(args.sequences):
        prompt = rng.integers(0, cfg.vocab_size,
                              rng.integers(lo, hi + 1)).astype(np.int32)
        sent.append((prompt, serving.submit(
            prompt, max_new_tokens=int(rng.integers(new_lo, new_hi + 1)))))
    serving.run()
    served = [(len(p), np.concatenate([p, np.asarray(h.result(), np.int32)]))
              for p, h in sent]
    scored = [serving.score_logprobs(full) for _, full in served]

    # teacher-forced: the prompt through the engine's prefill program, the
    # served tokens through the model's one-token paged step, row 0
    R, C = serving.config.max_seqs, serving.config.prefill_chunk
    params = serving.engine.params

    def one_token(params, cache, table, lengths, tokens, targets):
        live = (lengths > 0)[:, None]
        # a model with neither a recurrent layer nor a window's ring keeps
        # no slots (``kv_cache.cache_slots``: 0)
        n_slots = cache_slots(cache)
        slots = (jnp.where(lengths > 0, jnp.arange(R, dtype=jnp.int32),
                           n_slots - 1) if n_slots else None)
        logits, cache, _ = forward(
            params, tokens[:, None], cfg, cache=cache,
            positions=lengths[:, None], block_table=table,
            paged_write_mask=live, state_slots=slots)
        return gather_target_logprobs(logits[:, 0], targets), cache

    step = jax.jit(one_token, donate_argnums=(1,))
    decoded = []
    with mesh_mod.ambient(serving.engine.mesh):
        for n_prompt, full in served:
            blocks = serving.alloc.alloc(-(-len(full)
                                           // serving.config.block_size))
            table = np.zeros((R, serving.blocks_per_seq), np.int32)
            table[0, :len(blocks)] = blocks
            one = np.ones((1,), np.float32)
            for start in range(0, n_prompt, C):
                n = min(C, n_prompt - start)
                chunk = np.zeros((1, C), np.int32)
                chunk[0, :n] = full[start:start + n]
                zero = np.zeros((1,), np.int32)
                _, _, serving._arena = serving._prefill(
                    params, serving._arena,
                    paged_kv.pack_chunk(
                        table[:1], chunk, start, n, 0 * one, zero, one, zero,
                        state_slot=zero if serving.state_slots else None,
                        # a stack with ``tail_runs``: the chunk says whether
                        # it is its prompt's last
                        **({"last": [start + n == n_prompt]}
                           if serving._chunk_says_last else {})),
                    serving._base_rng)
            logp = []
            for p in range(n_prompt, len(full) - 1):
                # new arrays a step: a dispatched call may still read them
                lengths, tokens, targets = (
                    np.zeros((R,), np.int32) for _ in range(3))
                lengths[0], tokens[0], targets[0] = p, full[p], full[p + 1]
                lp, serving._arena = step(params, serving._arena, table,
                                          lengths, tokens, targets)
                logp.append(lp[0])
            decoded.append(np.asarray(jnp.stack(logp)))
            serving.alloc.free(blocks)
    serving.close()
    del serving, step
    gc.collect()

    reference = program.reference_module(cell)
    ref_args = program.reference_args(cell)

    def ref_pass(**changed):
        kw = dict(ref_args, **changed)

        if hasattr(reference, "next_token_stats"):
            # a reference whose vocabulary is too wide for a sequence's
            # logits at once reads the three statistics in blocks itself
            return jax.jit(lambda p, ids: tuple(
                a[0] for a in reference.next_token_stats(p, ids, **kw)))

        @jax.jit
        def run(p, ids):
            logits = reference.logits(p, ids, **kw)[0]
            lp = jax.nn.log_softmax(logits, axis=-1)
            nxt = jnp.take_along_axis(lp[:-1], ids[0, 1:, None], axis=-1)
            return nxt[:, 0], logits.max(-1), jnp.take_along_axis(
                logits[:-1], ids[0, 1:, None], axis=-1)[:, 0]

        return run

    out = {"config": args.config, "tiny": args.tiny, "dtype": str(dtype),
           "device": jax.devices()[0].device_kind, "limit": limit,
           "sequences": []}
    with jax.default_matmul_precision("highest"):
        passes = {"reference": ref_pass(),
                  **{name: ref_pass(**kw)
                     for name, kw in controls(jnp).items()}}
        for (n_prompt, full), logp, dec in zip(served, scored, decoded):
            T = len(full)
            # causal: what follows a position cannot move it, so every
            # sequence is padded to one length and compiles once a pass
            ids = np.zeros((1, max(pad_to, T)), np.int32)
            ids[0, :T] = full
            row = {"tokens": int(T), "prompt": int(n_prompt)}
            for name, run in passes.items():
                nxt, best, of_next = (np.asarray(a)[:T - 1]
                                      for a in run(params, ids))
                row[name] = {
                    "logprob_maxdiff": float(np.abs(logp - nxt).max()),
                    # the step at position p gives the token at p + 1
                    "decode_logprob_maxdiff": float(
                        np.abs(dec - nxt[n_prompt:]).max()),
                    "greedy_deficit_max": float(
                        (best - of_next)[n_prompt - 1:].max())}
            out["sequences"].append(row)
            print(json.dumps(row), flush=True)
    worst = {name: max(max(r[name]["logprob_maxdiff"],
                           r[name]["decode_logprob_maxdiff"])
                       for r in out["sequences"]) for name in passes}
    least = {name: min(r[name]["logprob_maxdiff"]
                       for r in out["sequences"]) for name in passes}
    out["sound_largest"] = worst["reference"]
    out["controls_least_served"] = {n: v for n, v in least.items()
                                    if n != "reference"}
    out["controls_under_the_limit"] = sorted(
        n for n, v in out["controls_least_served"].items() if v <= limit)
    out["ok"] = bool(worst["reference"] < limit and set(
        out["controls_under_the_limit"]) <= set(UNTOLD))
    os.makedirs(os.path.join(REPO_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO_ROOT, "chiprun_out", family["out"]),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Phi-4-mini-flash-reasoning at the benchmark's configuration on the chip,
outside any timed window (model-configs section 3):

    chiprun -- python scripts/check_phi4flash_on_chip.py
        [--config phi-4-mini-flash-reasoning] [--sequences 3] [--seed 1]
        [--tiny]

First `mamba1_decode_step` ALONE at the published shapes (64 rows, inner
width 5,120, state 16, a pool of 9 layers x 65 slots) against its
`jax.numpy` twin on the same chip: `kernel_y_maxdiff` and
`kernel_state_maxdiff` (float32 against float32: both are exact elementwise
arithmetic and differ by the order of a sum and the exponential's last
bits), that nothing else of the pool moved, and the kernel's time a call
over 20 calls beside the bytes it has to move (`kernel_gb_s`); and
`mamba1_chunk_scan` against the token-by-token recurrence over a chunk of
256. Then everything `scripts/check_solar_open2_on_chip.py` reads of a model
with recurrent layers (served log-probabilities through `score_logprobs`,
the one-token steps teacher-forced, the greedy deficit), for this family's
reference and its controls, over sequences two to three windows deep: every
matrix and layer input in float8's 3 bits of mantissa (the precision below
the stated one), no window, a window of one key more and of one key less,
cross layers on their own keys, `m` taken behind the gate, `lam0` of another
layer, no `(1 - lam0)`, no `D x`. Prints the kernels' JSON line, then that
script's, and writes chiprun_out/phi4flash_check.json (and ..._kernel.json).
"""

import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))


def controls(jnp):
    return {"all-in-float8": {"mantissa_bits": 3},
            "no-window": {"window": False},
            "a-window-of-one-key-more": {"window_shift": 1},
            "a-window-of-one-key-less": {"window_shift": -1},
            "cross-layers-on-their-own-keys": {"cross_own_kv": True},
            "memory-behind-the-gate": {"memory_after_gate": True},
            "lam0-of-another-layer": {"lam0_shift": 2},
            "no-one-minus-lam0": {"one_minus_lam0": False},
            "no-skip-term": {"skip": False}}


FAMILY = {"config": "phi-4-mini-flash-reasoning",
          "traffic": "serve-reason-r64", "controls": controls,
          # one key more or less of 512 moves a bfloat16 model's logits by
          # about its own rounding, and at this init a cross layer's input
          # is nearly the full layer's, so its own keys are nearly the shared
          # ones (0.27-0.53 for the sound model's 0.15-0.20; my chip run, PR
          # 55): read and reported, decided by the CPU tests (float32 against
          # float32, a window of 8)
          "untold": ("a-window-of-one-key-more", "a-window-of-one-key-less",
                     "cross-layers-on-their-own-keys"),
          # prompts 64-512 and answers of 900-1,200 tokens: two to three
          # windows deep; every sequence padded to 1,792 for the reference
          "lengths": (64, 512, 900, 1200, 1792),
          "out": "phi4flash_check.json"}


def kernels_alone(tiny: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops import mamba1 as ssm

    R, D, N, L, S = (4, 256, 16, 2, 24) if tiny else (64, 5120, 16, 9, 256)
    on_cpu = jax.default_backend() == "cpu"
    rng = np.random.default_rng(0)
    f32 = jnp.float32
    x = jnp.asarray(rng.standard_normal((R, D)), f32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (R, D))),
                     f32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (N, D)), f32)
    Bm, Cm = (jnp.asarray(rng.standard_normal((R, N)), f32) for _ in range(2))
    pool = jax.random.normal(jax.random.PRNGKey(0), (L, R + 1, N, D), f32)
    slots = jnp.arange(R, dtype=jnp.int32).at[R // 2].set(R)   # one idle row
    layer = jnp.int32(L - 1)
    kernel = jax.jit(lambda *a: ssm.mamba1_decode_step(*a, interpret=on_cpu))
    twin = jax.jit(ssm.reference_mamba1_decode_step)
    want_y, want_pool = twin(x, dt, A, Bm, Cm, pool, layer, slots)
    got_y, got_pool = kernel(x, dt, A, Bm, Cm, pool, layer, slots)
    live = np.asarray(slots) < R
    out = {
        "shape": {"rows": R, "inner": D, "state": N, "layers": L,
                  "chunk": S},
        "device": jax.devices()[0].device_kind,
        "kernel_y_maxdiff": float(np.abs(
            np.asarray(got_y - want_y))[live].max()),
        "kernel_y_scale": float(np.abs(np.asarray(want_y)).max()),
        "kernel_state_maxdiff": float(np.abs(np.asarray(
            got_pool[L - 1, :R] - want_pool[L - 1, :R]))[live].max()),
        "other_layers_untouched": bool(
            (np.asarray(got_pool[:L - 1]) == np.asarray(pool[:L - 1])).all()),
    }
    del want_pool, got_pool, want_y
    donating = jax.jit(lambda *a: ssm.mamba1_decode_step(
        *a, interpret=on_cpu), donate_argnums=(5,))
    y, pool = donating(x, dt, A, Bm, Cm, pool, layer, slots)
    jax.block_until_ready(pool)
    calls = 2 if on_cpu else 20
    t0 = time.perf_counter()
    for _ in range(calls):
        y, pool = donating(x, dt, A, Bm, Cm, pool, layer, slots)
    jax.block_until_ready((y, pool))
    seconds = (time.perf_counter() - t0) / calls
    moved = 4 * (R * (2 * N * D + 3 * D + 2 * N) + N * D)
    out.update(kernel_ms_a_call=1e3 * seconds, bytes_a_call=moved,
               kernel_gb_s=moved / seconds / 1e9)
    del pool
    # the chunk scan: one row's chunk, some of it padding (dt 0)
    xs = jnp.asarray(rng.standard_normal((1, S, D)), f32)
    dts = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                         (1, S, D))), f32)
    dts = dts.at[:, S - S // 8:].set(0.0)
    Bs, Cs = (jnp.asarray(rng.standard_normal((1, S, N)), f32)
              for _ in range(2))
    s0 = jax.random.normal(jax.random.PRNGKey(1), (1, N, D), f32)
    scan = jax.jit(lambda *a: ssm.mamba1_chunk_scan(*a, interpret=on_cpu))
    want_y, want_s = jax.jit(ssm.mamba1_recurrence)(xs, dts, A, Bs, Cs, s0)
    got_y, got_s = scan(xs, dts, A, Bs, Cs, s0)
    jax.block_until_ready(got_s)
    t0 = time.perf_counter()
    for _ in range(calls):
        got_y, got_s = scan(xs, dts, A, Bs, Cs, s0)
    jax.block_until_ready(got_s)
    out.update(
        chunk_y_maxdiff=float(np.abs(np.asarray(got_y - want_y)).max()),
        chunk_y_scale=float(np.abs(np.asarray(want_y)).max()),
        chunk_state_maxdiff=float(np.abs(np.asarray(got_s - want_s)).max()),
        chunk_ms_a_call=1e3 * (time.perf_counter() - t0) / calls)
    out["ok"] = bool(out["kernel_y_maxdiff"] < 1e-4 * out["kernel_y_scale"]
                     and out["kernel_state_maxdiff"] < 1e-4
                     and out["other_layers_untouched"]
                     and out["chunk_y_maxdiff"] < 1e-4 * out["chunk_y_scale"]
                     and out["chunk_state_maxdiff"] < 1e-4)
    os.makedirs(os.path.join(REPO_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO_ROOT, "chiprun_out",
                           "phi4flash_check_kernel.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return out["ok"]


def main(argv=None):
    import check_solar_open2_on_chip as served

    argv = sys.argv[1:] if argv is None else argv
    ok = kernels_alone("--tiny" in argv)
    return served.main(argv, family=FAMILY) or (0 if ok else 1)


if __name__ == "__main__":
    sys.exit(main())

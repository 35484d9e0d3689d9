#!/usr/bin/env python3
"""Nemotron 3 Super at the benchmark's configuration on the chip, outside
any timed window (model-configs section 3):

    chiprun -- python scripts/check_nemotron_h_on_chip.py
        [--config nemotron-3-super-120b-a12b-ep8-d11] [--sequences 3]
        [--seed 1] [--tiny]

First `mamba2_decode_step` ALONE at the published shapes (64 rows, 128 heads
of 64 in 8 groups of state 128, a pool of 5 layers x 65 slots) against its
`jax.numpy` twin on the same chip: `kernel_y_maxdiff` and
`kernel_state_maxdiff` (float32 against float32; the twin has no matmul, so
both are exact elementwise arithmetic and differ by the order of a sum), that
nothing else of the pool moved, and the kernel's time a call over 20 calls
beside the bytes it has to move (`kernel_gb_s`). Then everything
`scripts/check_solar_open2_on_chip.py` reads of a model with recurrent
layers (served log-probabilities, the one-token steps teacher-forced, the
greedy deficit), for this family's reference and its controls: every matrix
and layer input in float8's 3 bits of mantissa, the SSM state kept in
bfloat16 between tokens, no `D x`, no convolution, no
`routed_scaling_factor`, the weights renormalised over the held experts
only (the second and the fifth are `untold`: no limit on a bfloat16 model's
logits tells them at this size and init; they are read and reported). Prints the kernel's JSON line, then that script's, and writes
chiprun_out/nemotron_h_check.json (and ..._kernel.json).
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
            "state-in-bfloat16": {"state_dtype": jnp.bfloat16},
            "no-skip": {"skip": False},
            "no-convolution": {"conv": False},
            "no-routed-scale": {"routed_scaling_factor": 1},
            "renormalised-over-held": {"renorm_over_held": True}}


FAMILY = {"config": "nemotron-3-super-120b-a12b-ep8-d11",
          "traffic": "serve-decode-r64-ssm", "controls": controls,
          # at this init the factor of 5 moves the logits by 0.35-0.46, under
          # the limit: read and reported, decided by the CPU tests
          "untold": ("state-in-bfloat16", "no-routed-scale"),
          "out": "nemotron_h_check.json"}


def kernel_alone(tiny: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops import mamba2 as ssm

    R, H, P, G, N, L = (4, 8, 64, 2, 128, 2) if tiny else (64, 128, 64, 8,
                                                           128, 5)
    on_cpu = jax.default_backend() == "cpu"
    rng = np.random.default_rng(0)
    f32 = jnp.float32
    x = jnp.asarray(rng.standard_normal((R, H, P)), f32)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1), (R, H))),
                     f32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (H,)), f32)
    Bm, Cm = (jnp.asarray(rng.standard_normal((R, G, N)), f32)
              for _ in range(2))
    pool = jax.random.normal(jax.random.PRNGKey(0),
                             (L, R + 1, G, N, H // G * P), f32)
    slots = jnp.arange(R, dtype=jnp.int32).at[R // 2].set(R)   # one idle row
    layer = jnp.int32(L - 1)
    kernel = jax.jit(lambda *a: ssm.mamba2_decode_step(*a,
                                                       interpret=on_cpu))
    twin = jax.jit(ssm.reference_mamba2_decode_step)
    want_y, want_pool = twin(x, dt, A, Bm, Cm, pool, layer, slots)
    got_y, got_pool = kernel(x, dt, A, Bm, Cm, pool, layer, slots)
    live = np.asarray(slots) < R
    out = {
        "shape": {"rows": R, "heads": H, "head_dim": P, "groups": G,
                  "state": N, "layers": L},
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
    donating = jax.jit(lambda *a: ssm.mamba2_decode_step(
        *a, interpret=on_cpu), donate_argnums=(5,))
    y, pool = donating(x, dt, A, Bm, Cm, pool, layer, slots)
    jax.block_until_ready(pool)
    calls = 2 if on_cpu else 20
    t0 = time.perf_counter()
    for _ in range(calls):
        y, pool = donating(x, dt, A, Bm, Cm, pool, layer, slots)
    jax.block_until_ready((y, pool))
    seconds = (time.perf_counter() - t0) / calls
    moved = R * 4 * (H * (2 * P * N + 3 * P) + G * 2 * N)
    out.update(kernel_ms_a_call=1e3 * seconds, bytes_a_call=moved,
               kernel_gb_s=moved / seconds / 1e9)
    out["ok"] = bool(out["kernel_y_maxdiff"] < 1e-4 * out["kernel_y_scale"]
                     and out["kernel_state_maxdiff"] < 1e-4
                     and out["other_layers_untouched"])
    os.makedirs(os.path.join(REPO_ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO_ROOT, "chiprun_out",
                           "nemotron_h_check_kernel.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return out["ok"]


def main(argv=None):
    import check_solar_open2_on_chip as served

    argv = sys.argv[1:] if argv is None else argv
    ok = kernel_alone("--tiny" in argv)
    return served.main(argv, family=FAMILY) or (0 if ok else 1)


if __name__ == "__main__":
    sys.exit(main())

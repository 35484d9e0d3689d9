#!/usr/bin/env python
"""Run every bench + chip-validation script and commit raw JSON artifacts.

README's numbers must cite driver-auditable files, not builder prose.
Writes bench_results/r{N}/<name>.json with the bench's
own JSON line plus run metadata; validation scripts get their stdout
captured verbatim. Skips (with a recorded reason) anything that needs a
real accelerator when only CPU is present. This parent stays off JAX — one
process holds the chip at a time, and each child here needs it.

Usage: python scripts/run_bench_suite.py r04 [filter-substring]
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SUITE = [
    ("bench", ["python", "bench.py"], {}),
    ("bench_infer_bf16", ["python", "bench_infer.py"], {}),
    ("bench_infer_int8", ["python", "bench_infer.py"],
     {"BENCH_INFER_DTYPE": "int8"}),
    ("bench_infer_int4", ["python", "bench_infer.py"],
     {"BENCH_INFER_DTYPE": "int4"}),
    # W8A8: s8xs8 MXU decode (the weight-only kernel is VPU-convert-bound;
    # this removes the convert entirely)
    ("bench_infer_w8a8", ["python", "bench_infer.py"],
     {"BENCH_INFER_DTYPE": "w8a8"}),
    ("bench_infer_w4a8", ["python", "bench_infer.py"],
     {"BENCH_INFER_DTYPE": "w4a8"}),
    # MoE expert-parallel inference + BLOOM-7B kernel-injected inference as
    # tracked config #5 names it
    ("bench_infer_moe8e", ["python", "bench_infer.py"],
     {"BENCH_INFER_MODEL": "moe-gpt-125m-8e"}),
    ("bench_infer_bloom7b", ["python", "bench_infer.py"],
     {"BENCH_INFER_MODEL": "bloom-7b"}),
    # bf16 bloom-7b (14.1 GB weights + 250k-vocab logits) is borderline on
    # 16 GB — the int8 variant is the reference's kernel-injected headline
    ("bench_infer_bloom7b_int8", ["python", "bench_infer.py"],
     {"BENCH_INFER_MODEL": "bloom-7b", "BENCH_INFER_DTYPE": "int8"}),
    # tracked config #2 as specified: resident (no-offload) partitioned-Adam
    # ZeRO — 1.3B records the honest single-chip OOM caveat, 125m the number
    ("bench_zero2_resident_opt1.3b", ["python", "bench_zero.py"],
     {"BENCH_ZERO_OFFLOAD": "none"}),
    ("bench_zero2_resident_opt125m", ["python", "bench_zero.py"],
     {"BENCH_ZERO_OFFLOAD": "none", "BENCH_ZERO_MODEL": "opt-125m",
      "BENCH_ZERO_BATCH": "16"}),
    ("bench_moe_sparse", ["python", "bench_moe.py"], {}),
    ("bench_moe_einsum", ["python", "bench_moe.py"],
     {"BENCH_MOE_DISPATCH": "einsum"}),
    ("bench_zero_optim_offload", ["python", "bench_zero.py"], {}),
    ("bench_zero_param_offload_7b", ["python", "bench_zero.py"],
     {"BENCH_ZERO_PARAM_OFFLOAD": "cpu", "BENCH_ZERO_MODEL": "llama-7b",
      "BENCH_WARMUP": "1", "BENCH_STEPS": "1"}),
    ("bench_zero_param_offload_9.8b", ["python", "bench_zero.py"],
     {"BENCH_ZERO_PARAM_OFFLOAD": "cpu", "BENCH_ZERO_MODEL": "llama-13b",
      "BENCH_ZERO_LAYERS": "30", "BENCH_WARMUP": "1", "BENCH_STEPS": "1"}),
    ("bench_rlhf", ["python", "bench_rlhf.py"], {}),
    # (kernel parity on the chip is chip_smoke.py's kernels phase)
    ("validate_offload", ["python", "scripts/validate_offload_tpu.py"], {}),
    # fetch-vs-compute overlap + h2d utilization evidence
    ("validate_offload_overlap",
     ["python", "scripts/validate_offload_overlap.py"], {}),
    ("validate_offload_overlap_1.3b",
     ["python", "scripts/validate_offload_overlap.py"],
     {"BENCH_OVERLAP_MODEL": "opt-1.3b", "BENCH_OVERLAP_BATCH": "4"}),
]


def main() -> None:
    tag = sys.argv[1] if len(sys.argv) > 1 else "r04"
    filt = sys.argv[2] if len(sys.argv) > 2 else ""
    outdir = os.path.join(REPO, "bench_results", tag)
    os.makedirs(outdir, exist_ok=True)

    # the parent never initialises a JAX backend: a chip belongs to one
    # process at a time, and every child below needs it. A throwaway child
    # asks, and has exited (released the chip) before the first bench starts
    probe = subprocess.run(
        [sys.executable, "-c", "import jax; print(jax.default_backend())"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    if probe.returncode != 0:
        sys.exit(f"backend probe failed:\n{probe.stderr[-2000:]}")
    on_accel = probe.stdout.strip().splitlines()[-1] != "cpu"
    for name, cmd, env in SUITE:
        if filt and filt not in name:
            continue
        if not on_accel:
            record = {"name": name, "skipped":
                      "needs a real accelerator (backend is cpu)"}
            with open(os.path.join(outdir, f"{name}.json"), "w") as f:
                json.dump(record, f, indent=1)
            print(f"[skip] {name}: cpu backend", flush=True)
            continue
        t0 = time.time()
        try:
            proc = subprocess.run(cmd, cwd=REPO, capture_output=True,
                                  text=True, env={**os.environ, **env},
                                  timeout=60 * 30)
        except subprocess.TimeoutExpired:
            record = {"name": name, "cmd": cmd, "env_overrides": env,
                      "wall_seconds": round(time.time() - t0, 1),
                      "returncode": "timeout(30m)"}
            with open(os.path.join(outdir, f"{name}.json"), "w") as f:
                json.dump(record, f, indent=1)
            print(f"[TIMEOUT] {name}", flush=True)
            continue
        dt = round(time.time() - t0, 1)
        record = {"name": name, "cmd": cmd, "env_overrides": env,
                  "wall_seconds": dt, "returncode": proc.returncode}
        # the benches print ONE JSON line (last); validators print text
        lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
        parsed = None
        for line in reversed(lines):
            try:
                parsed = json.loads(line)
                break
            except ValueError:
                continue
        if parsed is not None:
            record["result"] = parsed
        else:
            record["stdout_tail"] = lines[-30:]
        if proc.returncode != 0:
            record["stderr_tail"] = proc.stderr.strip().splitlines()[-15:]
        path = os.path.join(outdir, f"{name}.json")
        with open(path, "w") as f:
            json.dump(record, f, indent=1)
        status = "ok" if proc.returncode == 0 else "FAIL"
        print(f"[{status}] {name}: {dt}s -> {path}", flush=True)


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""SPMD-efficiency regression guard: run the multichip dryrun in a
subprocess and fail if XLA logs an involuntary full rematerialization
(a full-tensor replication in the hot loop — the class of silent perf bug
that sank the round-2 zero3×TP×SP config).

Usage: python scripts/check_spmd_clean.py [n_devices]
"""

import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    n = sys.argv[1] if len(sys.argv) > 1 else "8"
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (env.get("XLA_FLAGS", "")
                      + f" --xla_force_host_platform_device_count={n}"),
        "PYTHONPATH": ROOT + os.pathsep + env.get("PYTHONPATH", ""),
    })
    proc = subprocess.run(
        [sys.executable, "-c",
         f"import __graft_entry__ as g; g.dryrun_multichip({n})"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    out = proc.stdout + proc.stderr
    bad = [l for l in out.splitlines() if "Involuntary full remat" in l]
    if proc.returncode != 0:
        sys.stderr.write(out[-4000:])
        print(f"FAIL: dryrun exited {proc.returncode}")
        return 1
    if bad:
        for l in bad:
            print(l)
        print(f"FAIL: {len(bad)} involuntary full rematerialization(s) — "
              "a sharding transition is replicating a tensor in the hot loop")
        return 1
    print("OK: dryrun clean of involuntary rematerialization")
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Does the runtime let a decode step run AHEAD of its predecessor's fetch?

    chiprun --chips 1 -- python scripts/probe_step_ahead.py [<serving cell>]

Enqueues the cell's decode program twice back to back, the second fed the
first's tokens from the device, fetches the FIRST call's tokens, and reads
from a capture, as medians over the pairs: (a) how long after the first
program's end the second starts, and (b) how long after ITS OWN end the
first's tokens are on the host, beside how long before the second's end
that was. The serving engine's step ahead (`docs/serving.md`) rests on (a)
being tens of microseconds and (b) being no more than the wake of a lone
program (the `alone` line). A throwaway capture comes first: a machine's
first has shown the device's clock a millisecond early (`PERF.md`, PR 40).
"""
import glob
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
PAIRS = 40


def main(cell_name="opt-1.3b.serve-decode"):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from benchmarks.harness import trace as trace_mod
    from benchmarks.harness.program import build_model
    from benchmarks.harness.serve_cell import serving_config
    from benchmarks.harness.spec import Spec
    from deepspeed_tpu.inference.engine import InferenceConfig
    from deepspeed_tpu.parallel import mesh as mesh_mod

    cell = Spec(ROOT).cell(cell_name)
    model = build_model(cell)
    srv = deepspeed_tpu.init_serving(
        model=model,
        serving_config=serving_config(cell, jax.devices())(model.config),
        config=InferenceConfig(
            dtype=getattr(jnp, cell.config["model"]["dtype"]), seed=0))
    packed = srv._decode_operands([])
    packed[:, srv.blocks_per_seq + 1] = -1      # every token from `last`
    last = [srv._last_tokens]

    def call():
        last[0], srv._arena = srv._decode(srv.engine.params, srv._arena,
                                          packed, srv._base_rng, last[0])
        return last[0]

    def rounds(ahead):
        for _ in range(PAIRS):
            a = call()
            b = call() if ahead else None
            with jax.profiler.TraceAnnotation("serve/probe_first"):
                np.asarray(a)
            if ahead:
                np.asarray(b)
            time.sleep(0.004)

    med = lambda xs: 1e3 * statistics.median(xs)
    with mesh_mod.ambient(srv.engine.mesh):
        rounds(True)                            # compiled, warm
        for ahead in (True, True, False):       # the first is thrown away
            out = tempfile.mkdtemp(prefix="probe_step_ahead_")
            with jax.profiler.trace(out):
                rounds(ahead)
            tr = trace_mod.load(glob.glob(
                out + "/plugins/profile/*/*.xplane.pb")[0])
            runs = sorted((s, s + d) for _, s, d in
                          tr.module_events(tr.devices[0], "jit_decode"))
            got = sorted(s + d for evs in tr.host.values()
                         for n, s, d in evs if n == "serve/probe_first")
            step = 2 if ahead else 1
            assert len(runs) == step * PAIRS == step * len(got), (
                len(runs), len(got))
            first, second = runs[0::step], runs[step - 1::step]
            print(f"[{cell_name}] {'ahead' if ahead else 'alone'}: program "
                  f"{med(e - s for s, e in runs):.3f} ms; first's tokens on "
                  f"the host {med(g - e for g, (_, e) in zip(got, first)):.3f}"
                  " ms after its end"
                  + (f", {med(e - g for g, (_, e) in zip(got, second)):.3f} "
                     "ms before the second's end; the second starts "
                     f"{med(b[0] - a[1] for a, b in zip(first, second)):.3f} "
                     "ms after the first's end" if ahead else ""),
                  flush=True)
    srv.close()


if __name__ == "__main__":
    main(*sys.argv[1:2])

"""Shared bench-harness guard — watchdogged child, evidence-first kills,
structured skip records.

Both bench entrypoints (`bench.py` train, `bench_infer.py` TTFT/decode) run
their measurement in a watchdogged child process so a hung backend cannot
eat the round. A bare SIGKILL leaves a skip with no evidence of where the
child was stuck. This guard kills in two phases instead:

1. **SIGUSR1** to the child's process group and a short grace wait
   (``BENCH_SIGUSR1_GRACE``, default 20 s): the child's observability
   session installs a SIGUSR1 handler that dumps its flight record — ring
   of recent spans/metrics/compiles, per-thread Python stacks, open-span
   stack, device memory (`deepspeed_tpu/observability/flightrecorder.py`);
2. **SIGKILL** only after the grace window.

The skip record then carries the crash-bundle path and the stalled span name
in ``reason``, plus a structured ``failure_kind`` field:

* ``"hang"``         — the watchdog expired (child killed);
* ``"backend-init"`` — the TPU backend never came up / budget spent waiting;
* ``"crash"``        — the backend dropped mid-run twice despite healthy
  probes.

Parent-side code deliberately imports neither jax nor deepspeed_tpu (a
parent that initialises a backend holds the chip its child needs), so the
bundle lookup re-reads MANIFEST.json with stdlib json.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import time
from typing import Dict, Optional, Tuple

# Substrings marking "the backend is down", as opposed to a bug in
# the bench itself. Matched against child stderr.
BACKEND_DOWN_MARKERS = (
    "UNAVAILABLE",
    "Unable to initialize backend",
    "TPU backend setup",
    "DEADLINE_EXCEEDED",
    "connection dropped",
    "Socket closed",
    "failed to connect",
)


def skip(metric: str, unit: str, reason: str, failure_kind: str,
         predicted_mfu: Optional[float] = None) -> None:
    """Print the structured skip record and exit 0 (the driver still gets a
    parseable result). ``failure_kind``: hang | backend-init | crash.
    ``predicted_mfu`` carries the STATIC roofline number (computed host-side,
    no TPU) so a backend-outage round still reports what the program should
    have achieved — the measured-vs-predicted pairing just loses its
    measured half."""
    print(json.dumps({
        "metric": metric, "value": None, "unit": unit,
        "vs_baseline": None, "skipped": True,
        "failure_kind": failure_kind, "reason": reason[-700:],
        "predicted_mfu": predicted_mfu,
    }))
    sys.exit(0)


def static_prediction(script: str,
                      timeout_s: float = 180.0) -> Optional[float]:
    """The bench's analytic predicted-MFU, computed in a throwaway CPU-only
    subprocess (``BENCH_PREDICT=1`` child mode — the parent stays jax-free
    by design, and forcing ``JAX_PLATFORMS=cpu`` keeps the probe off the
    very backend whose outage we are annotating). None when the probe fails
    or times out — a skip record must never block on its annotation."""
    env = dict(os.environ, BENCH_PREDICT="1", JAX_PLATFORMS="cpu")
    env.pop("BENCH_CHILD", None)
    try:
        r = subprocess.run([sys.executable, script], env=env,
                           timeout=timeout_s, capture_output=True, text=True)
        if r.returncode != 0:
            return None
        for line in reversed((r.stdout or "").strip().splitlines()):
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            v = rec.get("predicted_mfu")
            return float(v) if v is not None else None
    except (subprocess.TimeoutExpired, OSError, ValueError):
        return None
    return None


def cost_vector_record(entry: str) -> Optional[Dict]:
    """Static cost vector for a registered audit entry, flattened for
    embedding in a BENCH_*.json record (child-side only — pulls in jax and
    the tools/ tree). The next on-chip round reports measured-vs-predicted
    MFU side by side from this. None when the tools tree is absent, the
    entry never registered, or extraction fails — the bench number itself
    must never depend on the annotation."""
    try:
        import jax

        from tools.tpucost import registry_cost_vector

        vec = registry_cost_vector(
            entry, device_kind=jax.devices()[0].device_kind)
    except Exception:                               # noqa: BLE001
        return None
    if vec is None:
        return None
    m = vec.metrics
    rec = {
        "entry": entry,
        "flops": m.get("flops"),
        "bytes_accessed": m.get("bytes_accessed"),
        "peak_hbm_bytes": m.get("peak_hbm_bytes"),
        "collective_bytes": m.get("collective_bytes"),
        "predicted_step_ms": round(vec.predicted_step_s * 1e3, 4),
        "predicted_mfu": round(vec.mfu_ceiling, 4),
        "bound": vec.bound,
        "program_hash": vec.program_hash[:12],
    }
    if vec.predicted_tokens_per_sec is not None:
        rec["predicted_tokens_per_sec"] = round(
            vec.predicted_tokens_per_sec, 1)
    return rec


def probe_backend(attempts: int = 5, probe_timeout: int = 75,
                  cwd: Optional[str] = None) -> Optional[str]:
    """Try to bring up the jax backend in a throwaway subprocess.

    Returns None on success, else the last failure reason. Backend init on
    a backend can HANG as well as raise, so every attempt gets its own
    process + timeout.
    """
    last = "unknown"
    for i in range(attempts):
        try:
            r = subprocess.run(
                [sys.executable, "-c",
                 "import jax; jax.devices(); print(jax.default_backend())"],
                timeout=probe_timeout, capture_output=True, text=True,
                cwd=cwd)
            if r.returncode == 0:
                return None
            last = (r.stderr or r.stdout or "probe failed").strip()[-500:]
        except subprocess.TimeoutExpired:
            last = f"backend-init probe timed out after {probe_timeout}s"
        if i < attempts - 1:
            time.sleep(8 * (i + 1))
    return last


def crash_bundle_info(crash_dir: Optional[str],
                      newer_than: Optional[float] = None
                      ) -> Optional[Dict[str, str]]:
    """Newest flight-record bundle under ``crash_dir`` → its path and the
    stalled span from MANIFEST.json (stdlib-only duplicate of
    ``flightrecorder.find_latest_bundle`` so the parent stays jax-free).
    ``newer_than`` (wall seconds) rejects bundles left over from a previous
    round — a child that wedged inside native code dumps nothing, and
    attributing an old bundle to THIS hang would be fabricated evidence."""
    if not crash_dir:
        return None
    try:
        bundles = [os.path.join(crash_dir, d) for d in os.listdir(crash_dir)
                   if os.path.isfile(os.path.join(crash_dir, d,
                                                  "MANIFEST.json"))]
        if newer_than is not None:
            bundles = [b for b in bundles
                       if os.path.getmtime(b) >= newer_than]
        if not bundles:
            return None
        bundle = max(bundles, key=os.path.getmtime)
        with open(os.path.join(bundle, "MANIFEST.json")) as fh:
            manifest = json.load(fh)
        return {"bundle": bundle,
                "stalled_span": manifest.get("stalled_span") or "<none open>"}
    except OSError:
        return None


def fleet_skew_from_metrics(path: Optional[str]) -> Optional[float]:
    """``fleet/step_time_median_s{agg=skew}`` from a metrics JSONL dump —
    the fleet-health smoke field the bench records carry as
    ``step_time_skew`` ((max-median)/median across ranks; 0.0 on a one-rank
    fleet). Stdlib-only (parent-side safe); None when the file or the gauge
    is absent (fleet health off)."""
    if not path or not os.path.exists(path):
        return None
    skew = None
    try:
        with open(path) as fh:
            for line in fh:
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if (rec.get("type") == "gauge"
                        and rec.get("name") == "fleet/step_time_median_s"
                        and rec.get("labels", {}).get("agg") == "skew"):
                    skew = float(rec["value"])   # latest record wins
    except OSError:
        return None
    return skew


def _signal_group(pid: int, sig: int) -> None:
    try:
        os.killpg(pid, sig)
    except (ProcessLookupError, PermissionError):
        pass


def run_child(script: str, timeout_s: float,
              grace_s: float) -> Tuple[Optional[int], str, str, bool]:
    """Run ``script`` with BENCH_CHILD=1 in its own process GROUP so a
    watchdog kill cannot orphan a hung grandchild holding the TPU.

    Returns (returncode, stdout, stderr, hung). On watchdog expiry the child
    gets SIGUSR1 (flight-record dump) + ``grace_s`` to write it, then
    SIGKILL; ``hung`` is True for that whole path even if the child died of
    the SIGUSR1 itself (no handler ≈ no observability session — still a
    hang, just an evidence-free one)."""
    env = dict(os.environ, BENCH_CHILD="1")
    proc = subprocess.Popen([sys.executable, script],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, env=env, start_new_session=True)
    try:
        out, err = proc.communicate(timeout=timeout_s)
        sys.stderr.write(err or "")   # forward child diagnostics
        return proc.returncode, out, err, False
    except subprocess.TimeoutExpired:
        _signal_group(proc.pid, signal.SIGUSR1)
        try:
            out, err = proc.communicate(timeout=grace_s)
        except subprocess.TimeoutExpired:
            _signal_group(proc.pid, signal.SIGKILL)
            # collect whatever the child managed to write before the kill —
            # it shows WHERE it hung (backend init vs mid-bench)
            out, err = proc.communicate()
        return None, out or "", err or "", True


def run_watchdogged(metric: str, unit: str, script: str,
                    crash_dir: Optional[str] = None) -> None:
    """Parent mode: run the measurement child immediately; probe/retry only
    after a backend-down failure (a healthy backend pays zero extra init).

    The WHOLE parent is bounded by BENCH_TOTAL_BUDGET (default 1500 s) so
    the structured skip record always lands before any outer runner's
    timeout — run_bench_suite.py gives each entry 30 min."""
    start = time.monotonic()
    start_wall = time.time()   # bundle mtimes are wall-clock
    budget = float(os.environ.get("BENCH_TOTAL_BUDGET", 1500))
    grace = float(os.environ.get("BENCH_SIGUSR1_GRACE", 20))

    def remaining() -> float:
        return budget - (time.monotonic() - start)

    _prediction: list = []   # lazy one-shot cache: probe only when skipping

    def _skip(reason: str, kind: str) -> None:
        if not _prediction:
            t = min(max(remaining(), 0.0), 180.0)
            _prediction.append(static_prediction(script, t)
                               if t >= 30 else None)
        skip(metric, unit, reason, kind, predicted_mfu=_prediction[0])

    first_timeout = float(os.environ.get("BENCH_WATCHDOG_TIMEOUT",
                                         budget * 0.6))
    err = ""
    for attempt in range(2):  # one mid-run backend drop gets one retry
        timeout_s = (min(first_timeout, remaining()) if attempt == 0
                     else max(remaining(), 60))
        rc, out, errtxt, hung = run_child(script, timeout_s, grace)
        if hung:
            tail = (errtxt or "").strip().splitlines()[-3:]
            reason = (f"bench run exceeded {timeout_s:.0f}s watchdog; "
                      f"child stderr tail: "
                      f"{' | '.join(tail) if tail else '<empty>'}")
            info = crash_bundle_info(crash_dir, newer_than=start_wall)
            if info:
                reason += (f"; flight record: {info['bundle']} "
                           f"(stalled span: {info['stalled_span']})")
            else:
                reason += "; no flight record found (BENCH_OBS=0, or the " \
                          "child hung before its observability session)"
            _skip(reason, "hang")
        if rc == 0:
            sys.stdout.write(out)
            return
        err = (errtxt or "")[-2000:]
        if not any(m in err for m in BACKEND_DOWN_MARKERS):
            # real bug: surface it — INCLUDING the child's stdout, which may
            # hold a structured partial record (bench_infer's OOM JSON with
            # its single_chip_caveat prints before the re-raise)
            sys.stdout.write(out or "")
            sys.stderr.write(errtxt or "")
            sys.exit(rc)
        if attempt == 0:
            # probe ladder capped at 3 attempts (~4.3 min worst case) to
            # stay inside the budget
            down = probe_backend(attempts=3,
                                 cwd=os.path.dirname(os.path.abspath(script)))
            if down is not None:
                _skip(f"TPU backend unavailable after bounded retries: "
                      f"{down}", "backend-init")
            if remaining() < 120:
                _skip("TPU backend recovered but the run budget is spent; "
                      f"first failure: {err[-300:]}", "backend-init")
    _skip(f"TPU backend dropped twice despite a healthy probe: {err[-400:]}",
          "crash")

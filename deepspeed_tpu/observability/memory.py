"""Device-memory and host-memory gauges.

HBM pressure is the second silently-dominant cost on real TPU jobs (the first,
recompilation, lives in ``recompile.py``). ``record_memory`` polls
``device.memory_stats()`` on every addressable device — the PJRT per-device
allocator stats (``bytes_in_use`` / ``peak_bytes_in_use`` / ``bytes_limit``)
— into labeled gauges. On backends without allocator stats (the XLA CPU
backend returns ``None``) the device side is a guarded no-op; the host RSS
gauge (stdlib ``resource``) records everywhere, so a CPU smoke run still
produces memory telemetry and the tier-1 suite exercises the code path.

Polling reads host-side allocator counters — it does NOT sync the device or
touch array contents — but it is still per-device Python work, so the engine
polls at ``memory_poll_steps`` cadence, not every step.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

_STAT_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
              "largest_free_block_bytes")


def host_rss_bytes() -> Optional[int]:
    """Peak resident set size of this process, bytes (linux ru_maxrss is KiB)."""
    try:
        import resource
        import sys

        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return rss if sys.platform == "darwin" else rss * 1024
    except Exception:
        return None


def device_memory_stats() -> Dict[str, Dict[str, int]]:
    """``{device_label: stats}`` for every local device that reports stats."""
    out: Dict[str, Dict[str, int]] = {}
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return out
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        out[f"{d.platform}:{d.id}"] = {
            k: int(stats[k]) for k in _STAT_KEYS if k in stats}
    return out


def hbm_counts() -> Dict[str, int]:
    """The first reporting device's ``hbm_bytes_in_use`` / ``hbm_peak_bytes``
    as counts for a span (``serving/iteration``, ``train_batch``): peak HBM
    by phase, read only while the span records. Empty on stat-less backends."""
    for stats in device_memory_stats().values():
        return {"hbm_bytes_in_use": stats.get("bytes_in_use", 0),
                "hbm_peak_bytes": stats.get("peak_bytes_in_use", 0)}
    return {}


def record_memory(registry: Optional[Any] = None) -> bool:
    """Poll memory into gauges. Returns True if any *device* stats were
    recorded (False on stat-less backends — the CPU no-op contract)."""
    from .metrics import get_registry

    reg = registry if registry is not None else get_registry()
    rss = host_rss_bytes()
    if rss is not None:
        reg.gauge("mem/host_rss_bytes",
                  help="peak process resident set size").set(rss)
    per_device = device_memory_stats()
    for label, stats in per_device.items():
        for key, val in stats.items():
            reg.gauge(f"mem/device/{key}",
                      help="PJRT allocator stat").set(val, device=label)
    return bool(per_device)

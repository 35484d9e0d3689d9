"""Process-local metrics registry — the numeric half of the observability layer.

One ``MetricsRegistry`` per process holds labeled **counters** (monotonic),
**gauges** (last value wins) and **histograms** (count/sum/min/max running
stats). Everything the stack measures — training loss, comm bytes, compile
seconds, device memory — publishes here, and the pre-existing monitor writers
(``monitor/monitor.py`` CSV/TensorBoard/WandB) are *exporters* of this registry
rather than a parallel event path: ``publish(step)`` scalarizes a snapshot and
fans it out to every attached exporter via the same ``write_events`` contract
the writers already speak.

Design constraints:

* **Zero device interaction.** Recording is a dict update; nothing here ever
  touches a ``jax.Array`` (callers convert to float first, choosing when to
  pay the sync). Safe to call at step cadence.
* **Labels are kwargs** (``counter.inc(3, op="all_reduce")``); each label
  combination is a separate series, keyed by the sorted kwarg tuple.
* **Dump is JSONL** (one record per series) so the ``report`` CLI and the
  bench harness can read it with nothing but ``json``.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

LabelKey = Tuple[Tuple[str, str], ...]


def _label_key(labels: Dict[str, Any]) -> LabelKey:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


class _Metric:
    kind = "metric"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._series: Dict[LabelKey, Any] = {}
        self._lock = threading.Lock()

    def series(self) -> Dict[LabelKey, Any]:
        with self._lock:
            return dict(self._series)

    def records(self) -> List[Dict[str, Any]]:
        raise NotImplementedError

    def scalars(self) -> List[Tuple[str, float]]:
        """(flattened name, value) pairs for exporter fan-out."""
        raise NotImplementedError

    @staticmethod
    def _flat(name: str, key: LabelKey, suffix: str = "") -> str:
        label_part = "/".join(f"{k}={v}" for k, v in key)
        parts = [name] + ([label_part] if label_part else []) + \
            ([suffix] if suffix else [])
        return "/".join(parts)


class Counter(_Metric):
    """Monotonically increasing count (calls, bytes, compiles...)."""

    kind = "counter"

    def inc(self, amount: float = 1.0, **labels: Any) -> None:
        if amount < 0:
            raise ValueError(f"counter '{self.name}' cannot decrease "
                             f"(inc({amount}))")
        key = _label_key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount

    def value(self, **labels: Any) -> float:
        with self._lock:
            return self._series.get(_label_key(labels), 0.0)

    def records(self) -> List[Dict[str, Any]]:
        return [{"type": "counter", "name": self.name,
                 "labels": dict(k), "value": v}
                for k, v in self.series().items()]

    def scalars(self) -> List[Tuple[str, float]]:
        return [(self._flat(self.name, k), v) for k, v in self.series().items()]


class Gauge(_Metric):
    """Last-write-wins value (loss, lr, bytes_in_use, occupancy...)."""

    kind = "gauge"

    def set(self, value: float, **labels: Any) -> None:
        key = _label_key(labels)
        with self._lock:
            self._series[key] = float(value)

    def value(self, **labels: Any) -> Optional[float]:
        with self._lock:
            return self._series.get(_label_key(labels))

    def records(self) -> List[Dict[str, Any]]:
        return [{"type": "gauge", "name": self.name,
                 "labels": dict(k), "value": v}
                for k, v in self.series().items()]

    def scalars(self) -> List[Tuple[str, float]]:
        return [(self._flat(self.name, k), v) for k, v in self.series().items()]


class Histogram(_Metric):
    """Running count/sum/min/max (latencies, compile seconds, msg sizes).
    Keeps scalars only — no reservoir — so step-cadence observation is O(1)
    and the JSONL stays small."""

    kind = "histogram"

    def observe(self, value: float, **labels: Any) -> None:
        value = float(value)
        key = _label_key(labels)
        with self._lock:
            stats = self._series.get(key)
            if stats is None:
                self._series[key] = {"count": 1, "sum": value,
                                     "min": value, "max": value}
            else:
                stats["count"] += 1
                stats["sum"] += value
                stats["min"] = min(stats["min"], value)
                stats["max"] = max(stats["max"], value)

    def stats(self, **labels: Any) -> Optional[Dict[str, float]]:
        with self._lock:
            s = self._series.get(_label_key(labels))
            return dict(s) if s else None

    def records(self) -> List[Dict[str, Any]]:
        out = []
        for k, s in self.series().items():
            rec = {"type": "histogram", "name": self.name, "labels": dict(k)}
            rec.update(s)
            rec["mean"] = s["sum"] / max(s["count"], 1)
            out.append(rec)
        return out

    def scalars(self) -> List[Tuple[str, float]]:
        out = []
        for k, s in self.series().items():
            out.append((self._flat(self.name, k, "mean"),
                        s["sum"] / max(s["count"], 1)))
            out.append((self._flat(self.name, k, "count"), float(s["count"])))
        return out


class MetricsRegistry:
    """Named metric store + exporter fan-out. Metrics are memoized by name:
    ``registry.counter("comm/bytes")`` returns the same object everywhere."""

    def __init__(self):
        self._metrics: Dict[str, _Metric] = {}
        self._exporters: List[Any] = []
        self._lock = threading.Lock()
        # optional (step, events) callback — the flight recorder notes each
        # publish in its ring; None (default) costs one attribute check
        self.on_publish: Optional[Any] = None

    def _get(self, name: str, cls, help: str) -> _Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help)
            elif not isinstance(m, cls):
                raise TypeError(f"metric '{name}' already registered as "
                                f"{m.kind}, requested {cls.kind}")
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(name, Counter, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(name, Gauge, help)

    def histogram(self, name: str, help: str = "") -> Histogram:
        return self._get(name, Histogram, help)

    def metrics(self) -> List[_Metric]:
        with self._lock:
            return list(self._metrics.values())

    # -- export -----------------------------------------------------------
    def attach_exporter(self, exporter: Any) -> None:
        """``exporter`` implements ``write_events(List[(name, value, step)])``
        — the monitor-writer contract (``monitor/monitor.py``)."""
        with self._lock:
            if exporter not in self._exporters:
                self._exporters.append(exporter)

    def detach_exporter(self, exporter: Any) -> None:
        with self._lock:
            if exporter in self._exporters:
                self._exporters.remove(exporter)

    def publish(self, step: int,
                names: Optional[Iterable[str]] = None) -> List[Tuple[str, float, int]]:
        """Scalarize (a subset of) the registry and fan out to exporters.
        ``names`` restricts to those metric names (None = everything)."""
        wanted = set(names) if names is not None else None
        events: List[Tuple[str, float, int]] = []
        for m in self.metrics():
            if wanted is not None and m.name not in wanted:
                continue
            events.extend((n, v, step) for n, v in m.scalars())
        with self._lock:
            exporters = list(self._exporters)
        for ex in exporters:
            ex.write_events(events)
        if self.on_publish is not None:
            self.on_publish(step, events)
        return events

    def snapshot(self) -> List[Dict[str, Any]]:
        recs: List[Dict[str, Any]] = []
        for m in self.metrics():
            recs.extend(m.records())
        return recs

    def dump_jsonl(self, path: str, extra: Optional[Dict[str, Any]] = None,
                   append: bool = False) -> str:
        """Write one record per series (plus an optional header record) —
        a run's per-phase breakdown beside its result. The default truncates:
        the file is a *snapshot*, and accumulating full-registry snapshots
        across runs would double-count every series for consumers that
        don't replicate the report CLI's latest-record-wins dedup. Pass
        ``append=True`` to build a multi-run trajectory deliberately."""
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "a" if append else "w") as fh:
            if extra:
                fh.write(json.dumps({"type": "meta", "wall_time": time.time(),
                                     **extra}) + "\n")
            for rec in self.snapshot():
                fh.write(json.dumps(rec) + "\n")
        return path

    def reset(self) -> None:
        with self._lock:
            self._metrics.clear()
            self._exporters.clear()


_REGISTRY: Optional[MetricsRegistry] = None


def get_registry() -> MetricsRegistry:
    """The process-wide registry. Always available (recording is cheap);
    the ObservabilityConfig gate controls *files and exporters*, not whether
    a counter object exists."""
    global _REGISTRY
    if _REGISTRY is None:
        _REGISTRY = MetricsRegistry()
    return _REGISTRY

"""Metrics registry: counters, gauges, fixed-bucket histograms.

Instruments belong to labeled *families*: ``registry().histogram(
"serve.decode_gap_ms", replica=0)`` returns the ``{replica=0}`` member
of the ``serve.decode_gap_ms`` family, creating it on first use.  The
snapshot is a plain JSON document (one entry per labeled instrument,
keyed ``name{k=v,...}``) that round-trips through
:meth:`Registry.from_snapshot` — what ``bench.py --otrace`` attaches to
the trace dump.

Histograms use fixed bucket upper bounds (defaults suit millisecond
latencies); p50/p95/p99 are estimated by linear interpolation inside
the covering bucket — the standard fixed-bucket estimator, exact at
bucket edges, and deterministic from the snapshot alone (so a
round-tripped snapshot reports identical quantiles).

Thread safety: instrument creation and histogram/counter updates take
the registry lock — observation rates here are per-round / per-request,
not per-token, so a coarse lock is simpler than striping.
"""

from __future__ import annotations

import math
import threading
from typing import Dict, List, Mapping, Optional, Tuple

__all__ = ["Counter", "Gauge", "Histogram", "Registry", "registry",
           "reset_metrics", "DEFAULT_BUCKETS_MS"]

# upper bounds (ms-flavored); +inf is implicit as the overflow bucket
DEFAULT_BUCKETS_MS: Tuple[float, ...] = (
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
    1000.0, 2500.0, 5000.0, 10000.0)


def _label_key(labels: Mapping[str, object]) -> str:
    if not labels:
        return ""
    inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return "{" + inner + "}"


class Counter:
    __slots__ = ("_lock", "value")

    def __init__(self, lock: threading.Lock):
        self._lock = lock
        self.value = 0.0

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self.value += n

    def _snap(self) -> dict:
        return {"type": "counter", "value": self.value}


class Gauge:
    __slots__ = ("_lock", "value")

    def __init__(self, lock: threading.Lock):
        self._lock = lock
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)

    def inc(self, n: float = 1.0) -> None:
        with self._lock:
            self.value += n

    def dec(self, n: float = 1.0) -> None:
        self.inc(-n)

    def _snap(self) -> dict:
        return {"type": "gauge", "value": self.value}


class Histogram:
    __slots__ = ("_lock", "bounds", "counts", "count", "sum", "min", "max")

    def __init__(self, lock: threading.Lock,
                 buckets: Tuple[float, ...] = DEFAULT_BUCKETS_MS):
        self._lock = lock
        self.bounds = tuple(sorted(float(b) for b in buckets))
        if not self.bounds:
            raise ValueError("histogram needs at least one bucket bound")
        self.counts = [0] * (len(self.bounds) + 1)   # +1 = overflow
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf

    def observe(self, v: float) -> None:
        v = float(v)
        with self._lock:
            i = 0
            for i, b in enumerate(self.bounds):
                if v <= b:
                    break
            else:
                i = len(self.bounds)
            self.counts[i] += 1
            self.count += 1
            self.sum += v
            if v < self.min:
                self.min = v
            if v > self.max:
                self.max = v

    def quantile(self, q: float) -> float:
        """Fixed-bucket estimate: rank-interpolated inside the covering
        bucket, clamped to the observed min/max."""
        if self.count == 0:
            return float("nan")
        target = q * self.count
        seen = 0.0
        lo = 0.0
        for i, b in enumerate(self.bounds):
            n = self.counts[i]
            if seen + n >= target and n > 0:
                frac = (target - seen) / n
                est = lo + frac * (b - lo)
                return max(self.min, min(self.max, est))
            seen += n
            lo = b
        return self.max                      # landed in the overflow bucket

    def _snap(self) -> dict:
        d = {"type": "histogram", "bounds": list(self.bounds),
             "counts": list(self.counts), "count": self.count,
             "sum": self.sum}
        if self.count:
            d["min"] = self.min
            d["max"] = self.max
            d["p50"] = self.quantile(0.50)
            d["p95"] = self.quantile(0.95)
            d["p99"] = self.quantile(0.99)
        return d


class Registry:
    """A namespace of labeled instrument families."""

    def __init__(self):
        self._lock = threading.Lock()
        # name -> label_key -> (labels dict, instrument)
        self._families: Dict[str, Dict[str, tuple]] = {}
        # bumped by reset(): a caller that holds instruments across calls
        # fetches them again when it moves
        self.generation = 0

    def _get(self, kind, name: str, labels: Mapping[str, object],
             factory):
        key = _label_key(labels)
        with self._lock:
            fam = self._families.setdefault(name, {})
            ent = fam.get(key)
            if ent is None:
                ent = (dict(labels), factory())
                fam[key] = ent
            inst = ent[1]
        if not isinstance(inst, kind):
            raise TypeError(
                f"metric {name!r} already registered as "
                f"{type(inst).__name__}, requested {kind.__name__}")
        return inst

    def counter(self, name: str, **labels) -> Counter:
        return self._get(Counter, name, labels,
                         lambda: Counter(self._lock))

    def gauge(self, name: str, **labels) -> Gauge:
        return self._get(Gauge, name, labels, lambda: Gauge(self._lock))

    def histogram(self, name: str,
                  buckets: Optional[Tuple[float, ...]] = None,
                  **labels) -> Histogram:
        return self._get(
            Histogram, name, labels,
            lambda: Histogram(self._lock, buckets or DEFAULT_BUCKETS_MS))

    # -- snapshot ---------------------------------------------------------------

    def snapshot(self) -> dict:
        """JSON document: ``{"name{k=v}": {labels, type, ...}}``."""
        out: Dict[str, dict] = {}
        with self._lock:
            for name in sorted(self._families):
                for key in sorted(self._families[name]):
                    labels, inst = self._families[name][key]
                    entry = inst._snap()
                    entry["labels"] = dict(labels)
                    out[name + key] = entry
        return out

    @classmethod
    def from_snapshot(cls, snap: Mapping[str, dict]) -> "Registry":
        """Rebuild a registry whose :meth:`snapshot` equals ``snap``."""
        reg = cls()
        for full_name, entry in snap.items():
            name = full_name.split("{", 1)[0]
            labels = entry.get("labels", {})
            kind = entry["type"]
            if kind == "counter":
                reg.counter(name, **labels).value = entry["value"]
            elif kind == "gauge":
                reg.gauge(name, **labels).value = entry["value"]
            elif kind == "histogram":
                h = reg.histogram(name, buckets=tuple(entry["bounds"]),
                                  **labels)
                h.counts = list(entry["counts"])
                h.count = entry["count"]
                h.sum = entry["sum"]
                h.min = entry.get("min", math.inf)
                h.max = entry.get("max", -math.inf)
            else:
                raise ValueError(f"unknown instrument type {kind!r}")
        return reg

    def reset(self) -> None:
        with self._lock:
            self._families = {}
            self.generation += 1


_registry = Registry()


def registry() -> Registry:
    """The process-wide registry (always on — counters are just floats)."""
    return _registry


def reset_metrics() -> None:
    """Clear the process registry (bench presets and tests isolate runs)."""
    _registry.reset()

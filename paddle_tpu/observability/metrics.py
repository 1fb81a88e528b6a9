"""Unified metrics registry: counters / gauges / histograms.

Reference frame: the reference scatters runtime counters across ad-hoc
statics (kernel-factory hit counts, GC meta, allocator stats exposed one
pybind getter at a time); production XLA-stack services converge on a
single registry with Prometheus text exposition. Here every runtime
subsystem (dispatch cache, async engine, autograd, collectives, optimizer,
serving) publishes through ONE registry, so `observability.summary()`,
the distress dumps and any scrape endpoint all read the same numbers.

Concurrency note: updates are plain Python int/float ops under the GIL —
no locks on the hot path. A racing `+=` can in principle drop a tick
across threads; that is the standard metrics trade (lossy-but-cheap), and
the single-threaded eager hot loop is exact.
"""
from __future__ import annotations

import bisect
import math
import threading
from collections import deque
from typing import Dict, Iterable, Optional, Sequence, Tuple

# default latency buckets (seconds): sub-10us host blips .. 30s hangs
DEFAULT_BUCKETS = (1e-5, 5e-5, 1e-4, 5e-4, 1e-3, 5e-3, 1e-2, 5e-2,
                   0.1, 0.5, 1.0, 5.0, 30.0)

# ring of raw observations kept per histogram for exact p50/p99 snapshots
_OBS_WINDOW = 1024


def _label_key(labels: Optional[dict]) -> Tuple[Tuple[str, str], ...]:
    if not labels:
        return ()
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _label_str(key: Tuple[Tuple[str, str], ...]) -> str:
    if not key:
        return ""
    body = ",".join(f'{k}="{v}"' for k, v in key)
    return "{" + body + "}"


class Metric:
    kind = "untyped"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help

    def reset(self):  # pragma: no cover - overridden
        raise NotImplementedError

    def expose(self) -> Iterable[str]:  # pragma: no cover - overridden
        raise NotImplementedError

    def snapshot(self):  # pragma: no cover - overridden
        raise NotImplementedError


class Counter(Metric):
    kind = "counter"

    def __init__(self, name, help=""):
        super().__init__(name, help)
        self._values: Dict[tuple, float] = {}

    def inc(self, n: float = 1, labels: Optional[dict] = None):
        key = _label_key(labels)
        self._values[key] = self._values.get(key, 0) + n

    def value(self, labels: Optional[dict] = None) -> float:
        """One label-set's count; with labels=None, the sum over all sets."""
        if labels is None:
            return sum(self._values.values()) if self._values else 0
        return self._values.get(_label_key(labels), 0)

    def reset(self):
        self._values.clear()

    def expose(self):
        if not self._values:
            yield f"{self.name} 0"
        for key, v in sorted(self._values.items()):
            yield f"{self.name}{_label_str(key)} {_fmt(v)}"

    def snapshot(self):
        return {_label_str(k) or "": v for k, v in self._values.items()} \
            if self._values else {"": 0}


class Gauge(Metric):
    kind = "gauge"

    def __init__(self, name, help=""):
        super().__init__(name, help)
        self._values: Dict[tuple, float] = {}

    def set(self, v: float, labels: Optional[dict] = None):
        self._values[_label_key(labels)] = v

    def set_max(self, v: float, labels: Optional[dict] = None):
        key = _label_key(labels)
        if v > self._values.get(key, float("-inf")):
            self._values[key] = v

    def inc(self, n: float = 1, labels: Optional[dict] = None):
        key = _label_key(labels)
        self._values[key] = self._values.get(key, 0) + n

    def dec(self, n: float = 1, labels: Optional[dict] = None):
        self.inc(-n, labels)

    def value(self, labels: Optional[dict] = None) -> float:
        return self._values.get(_label_key(labels), 0)

    def reset(self):
        self._values.clear()

    def expose(self):
        if not self._values:
            yield f"{self.name} 0"
        for key, v in sorted(self._values.items()):
            yield f"{self.name}{_label_str(key)} {_fmt(v)}"

    def snapshot(self):
        return {_label_str(k) or "": v for k, v in self._values.items()} \
            if self._values else {"": 0}


class Histogram(Metric):
    kind = "histogram"

    def __init__(self, name, help="", buckets: Sequence[float] = None):
        super().__init__(name, help)
        self.buckets = tuple(buckets or DEFAULT_BUCKETS)
        self._counts = [0] * (len(self.buckets) + 1)  # +1 = +Inf
        self._sum = 0.0
        self._n = 0
        self._window = deque(maxlen=_OBS_WINDOW)

    def observe(self, v: float):
        self._counts[bisect.bisect_left(self.buckets, v)] += 1
        self._sum += v
        self._n += 1
        self._window.append(v)

    @property
    def count(self) -> int:
        return self._n

    def percentile(self, q: float) -> float:
        """Exact percentile over the last `_OBS_WINDOW` observations."""
        if not self._window:
            return 0.0
        ordered = sorted(self._window)
        idx = min(len(ordered) - 1,
                  max(0, math.ceil(q / 100.0 * len(ordered)) - 1))
        return ordered[idx]

    def reset(self):
        self._counts = [0] * (len(self.buckets) + 1)
        self._sum = 0.0
        self._n = 0
        self._window.clear()

    def expose(self):
        cum = 0
        for le, c in zip(self.buckets, self._counts):
            cum += c
            yield f'{self.name}_bucket{{le="{_fmt(le)}"}} {cum}'
        yield f'{self.name}_bucket{{le="+Inf"}} {self._n}'
        yield f"{self.name}_sum {_fmt(self._sum)}"
        yield f"{self.name}_count {self._n}"

    def snapshot(self):
        return {
            "count": self._n,
            "sum": round(self._sum, 9),
            "p50": round(self.percentile(50), 9),
            "p99": round(self.percentile(99), 9),
            "max": round(max(self._window), 9) if self._window else 0.0,
        }


def _fmt(v) -> str:
    if isinstance(v, float) and v.is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(v) if isinstance(v, float) else str(v)


class Registry:
    """Name -> Metric. Creation is idempotent (same name returns the same
    instance); kind mismatch on re-registration is a programming error."""

    def __init__(self):
        self._metrics: "Dict[str, Metric]" = {}
        self._lock = threading.Lock()

    def _get_or_create(self, cls, name, help, **kw):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = cls(name, help, **kw)
                self._metrics[name] = m
            elif not isinstance(m, cls):
                raise TypeError(f"metric {name!r} already registered as "
                                f"{m.kind}, not {cls.kind}")
            return m

    def counter(self, name, help="") -> Counter:
        return self._get_or_create(Counter, name, help)

    def gauge(self, name, help="") -> Gauge:
        return self._get_or_create(Gauge, name, help)

    def histogram(self, name, help="", buckets=None) -> Histogram:
        return self._get_or_create(Histogram, name, help, buckets=buckets)

    def get(self, name) -> Optional[Metric]:
        return self._metrics.get(name)

    def value(self, name, labels: Optional[dict] = None) -> float:
        """Counter/gauge value by name (0 when the metric never fired)."""
        m = self._metrics.get(name)
        if m is None or isinstance(m, Histogram):
            return 0
        return m.value(labels)

    def names(self):
        return sorted(self._metrics)

    def reset(self, prefix: Optional[str] = None):
        """Zero matching metrics (all when prefix is None). The metric
        objects stay registered — live references keep working."""
        for name, m in self._metrics.items():
            if prefix is None or name.startswith(prefix):
                m.reset()

    def prometheus_text(self) -> str:
        """Prometheus text exposition format 0.0.4."""
        lines = []
        for name in sorted(self._metrics):
            m = self._metrics[name]
            if m.help:
                lines.append(f"# HELP {name} {m.help}")
            lines.append(f"# TYPE {name} {m.kind}")
            lines.extend(m.expose())
        return "\n".join(lines) + "\n"

    def snapshot(self) -> dict:
        """JSON-safe dump of every metric."""
        out = {}
        for name in sorted(self._metrics):
            m = self._metrics[name]
            if isinstance(m, Histogram):
                out[name] = {"type": m.kind, **m.snapshot()}
            else:
                out[name] = {"type": m.kind, "values": m.snapshot()}
        return out

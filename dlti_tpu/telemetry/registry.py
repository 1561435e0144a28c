"""Metrics registry: labeled counters / gauges / histograms + Prometheus
text exposition.

The single backing store for the server's ``/stats`` and ``/metrics``
endpoints (previously a hand-rolled exposition loop inlined in
``serving/server.py``). Design constraints, in priority order:

1. **Name stability.** The pre-existing ``/metrics`` names
   (``dlti_requests``, ``dlti_free_blocks``, ...) are scraped by external
   dashboards; the registry's scalar exposition reproduces them
   byte-for-byte (``# TYPE`` line + ``name value`` line, sorted by name).
   Engine counters stay owned by the engine (its ``stats`` dict is the
   source of truth, registered here as a *scalar source* callback) so the
   hot decode path never takes a registry lock.
2. **Histograms for request-lifecycle latencies.** TTFT / TPOT /
   queue-time distributions are observed on-engine and exposed in the
   standard Prometheus histogram format (``_bucket{le=...}`` cumulative
   counts + ``_sum`` + ``_count``), so external loadgen percentiles can be
   cross-checked against the engine's own view.
3. **Thread safety.** ``observe``/``inc``/``set`` are called from the
   engine stepper thread while HTTP handler threads render; every mutation
   and snapshot is lock-protected (one lock per metric — contention is
   per-scrape, not per-token).
"""

from __future__ import annotations

import math
import threading
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

# Latency buckets (seconds) sized for LLM serving: sub-ms host paths up to
# multi-minute stragglers. Used for TTFT and queue time.
LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0)
# Per-output-token latency: milliseconds for a decode step up to seconds
# for a request stalled behind compiles or long prefills.
TPOT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                0.5, 1.0, 2.5, 5.0)


def _fmt_labels(labels: Tuple[Tuple[str, str], ...]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in labels)
    return "{" + inner + "}"


class _Metric:
    """Shared label-child machinery: a metric with no labels uses its
    default child; ``.labels(k=v)`` returns (creating on first use) the
    child for that label set."""

    kind = "untyped"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()
        self._children: Dict[Tuple[Tuple[str, str], ...], object] = {}

    def _new_child(self):
        raise NotImplementedError

    def labels(self, **labels: str):
        key = tuple(sorted((k, str(v)) for k, v in labels.items()))
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._children[key] = self._new_child()
            return child

    def _default(self):
        return self.labels()

    def samples(self) -> List[Tuple[str, str, object]]:
        """[(name_with_labels, labels_str, value_snapshot)] under lock."""
        with self._lock:
            return [(self.name, _fmt_labels(key), child)
                    for key, child in sorted(self._children.items())]


class _CounterChild:
    __slots__ = ("_value", "_lock")

    def __init__(self):
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value += amount

    @property
    def value(self) -> float:
        return self._value


class Counter(_Metric):
    kind = "counter"

    def _new_child(self):
        return _CounterChild()

    def inc(self, amount: float = 1.0) -> None:
        self._default().inc(amount)

    @property
    def value(self) -> float:
        return self._default().value


class _GaugeChild(_CounterChild):
    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    def dec(self, amount: float = 1.0) -> None:
        self.inc(-amount)


class Gauge(_Metric):
    kind = "gauge"

    def _new_child(self):
        return _GaugeChild()

    def set(self, value: float) -> None:
        self._default().set(value)

    def inc(self, amount: float = 1.0) -> None:
        self._default().inc(amount)

    def dec(self, amount: float = 1.0) -> None:
        self._default().inc(-amount)

    @property
    def value(self) -> float:
        return self._default().value


class _Read:
    """A value read from its writer at exposition time."""

    __slots__ = ("value",)

    def __init__(self, value: float):
        self.value = value


class ReadCounter(_Metric):
    """A counter family whose numbers live with their one writer and are
    read when asked for: ``fn()`` gives ``{label value: number}`` (or
    ``{"": number}`` for a series without a label; with a tuple of label
    names, ``{tuple of values: number}``). For what a hot loop books many
    times a round (the stepper's phase clock, the collector's pauses): the
    writer adds to a plain float, no lock and no child lookup, and a scrape
    copies it."""

    kind = "counter"

    def __init__(self, name: str, fn: Callable[[], dict],
                 label=None, help: str = ""):
        super().__init__(name, help)
        self._fn = fn
        self._labels = (label,) if isinstance(label, str) else label

    def samples(self) -> List[Tuple[str, str, object]]:
        names = self._labels or ()
        out = []
        for key, value in sorted(self._fn().items()):
            values = key if len(names) > 1 else (key,)
            out.append((self.name,
                        _fmt_labels(tuple(zip(names, map(str, values)))),
                        _Read(value)))
        return out


class ReadGauge(ReadCounter):
    """The same for point-in-time values (a pool's blocks in use)."""

    kind = "gauge"


class Histogram:
    """Fixed-bucket histogram (Prometheus semantics: ``le`` upper bounds,
    cumulative on exposition). Unlabeled — one instance per series is all
    the engine needs, and it keeps ``observe()`` a couple of adds."""

    kind = "histogram"

    def __init__(self, name: str, buckets: Sequence[float] = LATENCY_BUCKETS,
                 help: str = "", stats_key: Optional[str] = None):
        self.name = name
        self.help = help
        # ``/stats`` key for the summary dict (default: the metric name).
        self.stats_key = stats_key or name
        self.buckets = tuple(sorted(float(b) for b in buckets))
        self._counts = [0] * (len(self.buckets) + 1)  # [+Inf] is last
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        v = float(value)
        i = 0
        for b in self.buckets:  # tiny linear scan beats bisect at n<=16
            if v <= b:
                break
            i += 1
        with self._lock:
            self._counts[i] += 1
            self._sum += v
            self._count += 1

    def snapshot(self) -> Tuple[List[int], float, int]:
        with self._lock:
            return list(self._counts), self._sum, self._count

    def percentile(self, p: float) -> float:
        """Bucket-interpolated percentile estimate (p in [0, 100])."""
        counts, _, total = self.snapshot()
        if total == 0:
            return 0.0
        target = (p / 100.0) * total
        cum = 0
        lo = 0.0
        for i, c in enumerate(counts):
            prev = cum
            cum += c
            if cum >= target:
                hi = self.buckets[i] if i < len(self.buckets) else lo
                if c == 0 or hi <= lo:
                    return hi
                return lo + (hi - lo) * (target - prev) / c
            lo = self.buckets[i] if i < len(self.buckets) else lo
        return self.buckets[-1] if self.buckets else 0.0

    def summary(self) -> dict:
        """Compact ``/stats`` view of the distribution."""
        _, s, n = self.snapshot()
        return {
            "count": n,
            "sum": round(s, 6),
            "mean": round(s / n, 6) if n else 0.0,
            "p50": round(self.percentile(50), 6),
            "p90": round(self.percentile(90), 6),
            "p99": round(self.percentile(99), 6),
        }

    def render(self) -> List[str]:
        counts, s, n = self.snapshot()
        lines = [f"# TYPE {self.name} {self.kind}"]
        cum = 0
        for b, c in zip(self.buckets, counts):
            cum += c
            le = format(b, "g")
            lines.append(f'{self.name}_bucket{{le="{le}"}} {cum}')
        lines.append(f'{self.name}_bucket{{le="+Inf"}} {n}')
        lines.append(f"{self.name}_sum {s}")
        lines.append(f"{self.name}_count {n}")
        return lines


class _ScalarSource:
    """A callback yielding a dict of raw scalars (e.g. the engine's
    ``stats`` dict plus derived gauges), exposed under ``prefix``."""

    def __init__(self, fn: Callable[[], dict], gauge_keys: Sequence[str],
                 prefix: str):
        self.fn = fn
        self.gauge_keys = frozenset(gauge_keys)
        self.prefix = prefix


class MetricsRegistry:
    """Registry of metrics + scalar sources; renders Prometheus text and a
    raw ``/stats`` dict from one shared store."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics: Dict[str, object] = {}
        self._sources: List[_ScalarSource] = []

    # -- registration ---------------------------------------------------
    def _get_or_create(self, cls, name: str, **kwargs):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, **kwargs)
            elif not isinstance(m, cls):
                raise ValueError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, not {cls.__name__}")
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get_or_create(Counter, name, help=help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get_or_create(Gauge, name, help=help)

    def histogram(self, name: str, buckets: Sequence[float] = LATENCY_BUCKETS,
                  help: str = "") -> Histogram:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = Histogram(name, buckets, help=help)
            elif not isinstance(m, Histogram):
                raise ValueError(f"metric {name!r} is not a histogram")
            return m

    def register(self, metric) -> None:
        """Attach an externally created metric (e.g. the engine's
        request-lifecycle histograms) for exposition."""
        with self._lock:
            existing = self._metrics.get(metric.name)
            if existing is not None and existing is not metric:
                raise ValueError(f"metric {metric.name!r} already registered")
            self._metrics[metric.name] = metric

    def add_scalar_source(self, fn: Callable[[], dict],
                          gauge_keys: Sequence[str] = (),
                          prefix: str = "") -> None:
        """Register a callback producing ``{key: number}``; keys in
        ``gauge_keys`` expose as gauges, the rest as counters. Non-numeric
        and bool values are skipped on exposition (kept verbatim in
        :meth:`stats_dict`)."""
        self._sources.append(_ScalarSource(fn, gauge_keys, prefix))

    def metric_names(self) -> List[str]:
        """Every exposition name this registry serves: registered metric
        objects plus the (prefixed) scalar-source keys. The naming-
        convention guard (``tests/test_metric_naming.py``) walks this."""
        with self._lock:
            names = set(self._metrics)
        for name, _, _ in self._scalar_samples():
            names.add(name)
        return sorted(names)

    # -- collection -----------------------------------------------------
    def _scalar_samples(self) -> List[Tuple[str, str, float]]:
        """[(exposition_name, kind, value)] from every scalar source."""
        out = []
        for src in self._sources:
            for k, v in src.fn().items():
                if isinstance(v, bool) or not isinstance(v, (int, float)):
                    continue
                kind = "gauge" if k in src.gauge_keys else "counter"
                out.append((f"{src.prefix}{k}", kind, v))
        return out

    def stats_dict(self) -> dict:
        """Raw (unprefixed) scalars + per-histogram summaries — the
        ``/stats`` payload."""
        out: dict = {}
        for src in self._sources:
            for k, v in src.fn().items():
                out[k] = v
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            if isinstance(m, Histogram):
                if m.stats_key not in out:
                    out[m.stats_key] = m.summary()
            elif isinstance(m, _Metric):
                for name, labels, child in m.samples():
                    key = name + labels
                    if key not in out:
                        out[key] = child.value
        return out

    def render_prometheus(self) -> str:
        """Full text exposition (version 0.0.4), sorted by metric name.

        Scalar-source lines reproduce the legacy inline exposition
        byte-for-byte: ``# TYPE <name> <kind>`` then ``<name> <value>``
        with Python's default int/float formatting."""
        blocks: List[Tuple[str, List[str]]] = []
        for name, kind, v in self._scalar_samples():
            blocks.append((name, [f"# TYPE {name} {kind}", f"{name} {v}"]))
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            if isinstance(m, Histogram):
                blocks.append((m.name, m.render()))
            else:
                lines = [f"# TYPE {m.name} {m.kind}"]
                for name, labels, child in m.samples():
                    val = child.value
                    lines.append(f"{name}{labels} {val}")
                if len(lines) > 1:
                    blocks.append((m.name, lines))
        blocks.sort(key=lambda b: b[0])
        lines = [line for _, blk in blocks for line in blk]
        return "\n".join(lines) + "\n"

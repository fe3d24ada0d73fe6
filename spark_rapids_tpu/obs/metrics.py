"""Process-wide metrics registry: the CONTINUOUS half of the
observability story.

The flight recorder (obs/tracer.py) answers "what happened inside THAT
query"; this registry answers "how is the ENGINE doing" — monotonically
increasing counters, point-in-time gauges and fixed-bucket histograms
that every subsystem feeds (spill tier moves, arena utilization, shuffle
bytes, ICI path decisions, bridge round trips, fetch crossings, query
outcomes) and that obs/health.py exposes in Prometheus text format plus
a derived JSON health snapshot.

Design constraints, in order:

* **Hot-path cheap.**  An increment is one dict lookup plus one locked
  integer add; with the registry disabled
  (``spark.rapids.tpu.metrics.enabled=false``) every mutation
  short-circuits before taking a lock.  Nothing here ever touches the
  device or allocates per call.
* **Thread-safe and exact.**  Operators run partitions from multiple
  threads; counters use a per-child lock so concurrent increments never
  lose updates (the GIL does NOT make ``+=`` atomic).
* **Bounded cardinality.**  Every family has a hard cap on distinct
  label sets (default ``DEFAULT_MAX_SERIES``).  Past the cap, new label
  sets collapse into one ``_overflow`` series and the eviction is
  counted — a runaway label (say, per-query ids used as labels by
  mistake) degrades that family's resolution, never process memory.
  This is the registry analog of the tracer's ``maxSpans`` bound.
* **Fixed histogram buckets.**  Bucket boundaries are part of the
  family's identity, chosen at creation and immutable, so series from
  run N and run N−1 are always comparable (no adaptive re-bucketing).

Naming follows the Prometheus conventions the reference's
SQL-UI/Dropwizard metrics map onto: ``tpu_<subsystem>_<what>_<unit>``
with ``_total`` for counters.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEFAULT_MAX_SERIES = 64

# fixed latency ladder (seconds), dense in the 10ms-1s decades
DEFAULT_LATENCY_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                           0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0)

# fixed byte-size ladder for payload histograms
DEFAULT_BYTES_BUCKETS = (1 << 10, 1 << 14, 1 << 17, 1 << 20, 1 << 23,
                         1 << 26, 1 << 29, 1 << 32)

COUNTER = "counter"
GAUGE = "gauge"
HISTOGRAM = "histogram"

# label values of the single series that absorbs over-cap label sets
OVERFLOW_LABEL = "_overflow"


class _Child:
    """One (family, label-set) series."""

    __slots__ = ("_lock", "value")

    def __init__(self):
        self._lock = threading.Lock()
        self.value = 0

    # counter ---------------------------------------------------------------
    def inc(self, v=1) -> None:
        if v < 0:
            raise ValueError("counters only go up")
        with self._lock:
            self.value += v

    # gauge -----------------------------------------------------------------
    def set(self, v) -> None:
        with self._lock:
            self.value = v

    def dec(self, v=1) -> None:
        with self._lock:
            self.value -= v

    def gauge_inc(self, v=1) -> None:
        with self._lock:
            self.value += v


class _HistChild:
    """One histogram series: per-bucket counts + sum + count."""

    __slots__ = ("_lock", "bounds", "bucket_counts", "sum", "count")

    def __init__(self, bounds: Tuple[float, ...]):
        self._lock = threading.Lock()
        self.bounds = bounds
        self.bucket_counts = [0] * (len(bounds) + 1)  # last = +Inf
        self.sum = 0.0
        self.count = 0

    def observe(self, v) -> None:
        i = 0
        for i, b in enumerate(self.bounds):
            if v <= b:
                break
        else:
            i = len(self.bounds)
        with self._lock:
            self.bucket_counts[i] += 1
            self.sum += v
            self.count += 1

    def cumulative(self) -> List[Tuple[float, int]]:
        """(upper_bound, cumulative_count) pairs, +Inf last — the
        Prometheus ``_bucket{le=...}`` contract."""
        with self._lock:
            out = []
            acc = 0
            for b, c in zip(self.bounds, self.bucket_counts):
                acc += c
                out.append((b, acc))
            acc += self.bucket_counts[-1]
            out.append((float("inf"), acc))
            return out


class _NullChild:
    """What a disabled registry hands out: every mutation is a no-op."""

    __slots__ = ()

    def inc(self, v=1):
        pass

    def set(self, v):
        pass

    def dec(self, v=1):
        pass

    def gauge_inc(self, v=1):
        pass

    def observe(self, v):
        pass


_NULL = _NullChild()


class MetricFamily:
    """One named metric with a fixed label schema and a hard series cap."""

    def __init__(self, registry: "MetricsRegistry", name: str, kind: str,
                 doc: str, labelnames: Tuple[str, ...],
                 max_series: int = DEFAULT_MAX_SERIES,
                 buckets: Optional[Tuple[float, ...]] = None):
        self.registry = registry
        self.name = name
        self.kind = kind
        self.doc = doc
        self.labelnames = labelnames
        self.max_series = max_series
        self.buckets = buckets
        self._lock = threading.Lock()
        self._children: Dict[Tuple[str, ...], object] = {}
        self.overflowed = 0  # label sets evicted into the overflow series

    # -- child acquisition ---------------------------------------------------
    def _new_child(self):
        if self.kind == HISTOGRAM:
            return _HistChild(self.buckets)
        return _Child()

    def labels(self, **kv):
        """The series for this label set (creating it, or the overflow
        series past the cardinality cap)."""
        if not self.registry.enabled:
            return _NULL
        if set(kv) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: labels {sorted(kv)} != declared "
                f"{sorted(self.labelnames)}")
        key = tuple(str(kv[n]) for n in self.labelnames)
        with self._lock:
            ch = self._children.get(key)
            if ch is not None:
                return ch
            if len(self._children) >= self.max_series:
                # hard cap: the new label set never materializes; its
                # updates land in ONE shared overflow series (at most
                # max_series real series + this one exist, ever)
                self.overflowed += 1
                okey = (OVERFLOW_LABEL,) * len(self.labelnames)
                ch = self._children.get(okey)
                if ch is None:
                    ch = self._new_child()
                    self._children[okey] = ch
                return ch
            ch = self._new_child()
            self._children[key] = ch
            return ch

    def _default_child(self):
        """The unlabeled series (only for label-less families)."""
        if not self.registry.enabled:
            return _NULL
        if self.labelnames:
            raise ValueError(f"{self.name} requires labels "
                             f"{self.labelnames}")
        return self.labels()

    # -- unlabeled conveniences ---------------------------------------------
    def inc(self, v=1):
        self._default_child().inc(v)

    def set(self, v):
        self._default_child().set(v)

    def dec(self, v=1):
        self._default_child().dec(v)

    def gauge_inc(self, v=1):
        self._default_child().gauge_inc(v)

    def observe(self, v):
        self._default_child().observe(v)

    # -- read side -----------------------------------------------------------
    def series(self) -> List[Tuple[Dict[str, str], object]]:
        """(labels_dict, child) snapshot, insertion-ordered."""
        with self._lock:
            return [(dict(zip(self.labelnames, key)), ch)
                    for key, ch in self._children.items()]

    def value(self, **kv):
        """Point read of one series (0 when the series does not exist);
        histograms return (count, sum)."""
        key = tuple(str(kv[n]) for n in self.labelnames)
        with self._lock:
            ch = self._children.get(key)
        if ch is None:
            return 0
        if isinstance(ch, _HistChild):
            return (ch.count, ch.sum)
        return ch.value

    def total(self):
        """Sum over every series (including the overflow series) — the
        label-blind read a caller uses when it cares about the family's
        aggregate, not a particular label set (counters/gauges only)."""
        with self._lock:
            children = list(self._children.values())
        out = 0
        for ch in children:
            if isinstance(ch, _HistChild):
                raise ValueError(f"{self.name}: total() on a histogram")
            out += ch.value
        return out


class MetricsRegistry:
    """Process-wide singleton; families are created idempotently so any
    module can say ``metrics.counter(name, doc)`` without coordination.
    """

    _instance: Optional["MetricsRegistry"] = None
    _ilock = threading.Lock()

    def __init__(self):
        self._lock = threading.Lock()
        self._families: Dict[str, MetricFamily] = {}
        self.enabled = True

    @classmethod
    def get(cls) -> "MetricsRegistry":
        with cls._ilock:
            if cls._instance is None:
                cls._instance = MetricsRegistry()
            return cls._instance

    @classmethod
    def reset_for_tests(cls) -> "MetricsRegistry":
        """Drop every family (tests and the CI metrics gate need a
        known-empty registry; production never calls this)."""
        with cls._ilock:
            cls._instance = MetricsRegistry()
            return cls._instance

    # -- family creation (idempotent) ----------------------------------------
    def _family(self, name: str, kind: str, doc: str,
                labelnames: Sequence[str],
                max_series: int = DEFAULT_MAX_SERIES,
                buckets: Optional[Sequence[float]] = None) -> MetricFamily:
        labelnames = tuple(labelnames)
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if fam.kind != kind or fam.labelnames != labelnames:
                    raise ValueError(
                        f"metric {name} re-registered as {kind}"
                        f"{labelnames}, was {fam.kind}{fam.labelnames}")
                return fam
            bounds = None
            if kind == HISTOGRAM:
                bounds = tuple(sorted(buckets or
                                      DEFAULT_LATENCY_BUCKETS))
            fam = MetricFamily(self, name, kind, doc, labelnames,
                               max_series=max_series, buckets=bounds)
            self._families[name] = fam
            return fam

    def counter(self, name: str, doc: str = "",
                labelnames: Sequence[str] = (),
                max_series: int = DEFAULT_MAX_SERIES) -> MetricFamily:
        return self._family(name, COUNTER, doc, labelnames, max_series)

    def gauge(self, name: str, doc: str = "",
              labelnames: Sequence[str] = (),
              max_series: int = DEFAULT_MAX_SERIES) -> MetricFamily:
        return self._family(name, GAUGE, doc, labelnames, max_series)

    def histogram(self, name: str, doc: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Optional[Sequence[float]] = None,
                  max_series: int = DEFAULT_MAX_SERIES) -> MetricFamily:
        return self._family(name, HISTOGRAM, doc, labelnames, max_series,
                            buckets=buckets)

    # -- read side -----------------------------------------------------------
    def families(self) -> List[MetricFamily]:
        with self._lock:
            return sorted(self._families.values(), key=lambda f: f.name)

    def overflow_total(self) -> int:
        with self._lock:
            return sum(f.overflowed for f in self._families.values())


# ---------------------------------------------------------------------------
# module-level conveniences — what the instrumented subsystems call
# ---------------------------------------------------------------------------

def registry() -> MetricsRegistry:
    return MetricsRegistry.get()


def set_enabled(flag: bool) -> None:
    MetricsRegistry.get().enabled = bool(flag)


def enabled() -> bool:
    return MetricsRegistry.get().enabled


def counter(name: str, doc: str = "",
            labelnames: Sequence[str] = ()) -> MetricFamily:
    return MetricsRegistry.get().counter(name, doc, labelnames)


def gauge(name: str, doc: str = "",
          labelnames: Sequence[str] = ()) -> MetricFamily:
    return MetricsRegistry.get().gauge(name, doc, labelnames)


def histogram(name: str, doc: str = "", labelnames: Sequence[str] = (),
              buckets: Optional[Iterable[float]] = None) -> MetricFamily:
    return MetricsRegistry.get().histogram(name, doc, labelnames,
                                           buckets=buckets)

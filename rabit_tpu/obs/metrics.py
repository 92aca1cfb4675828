"""Metrics registry: counters, gauges and log2-bucket latency histograms.

The measurement substrate for every engine (tentpole of the telemetry
subsystem): zero dependencies beyond the stdlib, thread-safe, and cheap
enough that the engines leave the *call sites* compiled in and gate them
with a single bool (`rabit_obs` / `rabit_obs_dir`, doc/observability.md)
— when telemetry is off no instrument is ever touched.

Histograms use **fixed log2 buckets**: a value lands in the bucket of
its binary exponent (`math.frexp`), so bucket boundaries are powers of
two and a percentile estimate is accurate within one octave.  On top of
the buckets a Welford accumulator tracks exact count/sum/mean/std and
min/max (reference's only aggregation was the speed test's hand-rolled
sum/sum² allreduce, test/speed_test.cc:53-70).
"""
from __future__ import annotations

import math
import threading

# Bucket i spans [2**(i + _EXP0), 2**(i + _EXP0 + 1)); _EXP0 puts the
# bottom bucket at ~1 ns so latencies and byte sizes both fit.
_EXP0 = -40
_NBUCKET = 64


class Counter:
    """Monotonic counter (op counts, byte totals)."""

    __slots__ = ("_lock", "value")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.value = 0

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self.value += n


class Gauge:
    """Last-write-wins instantaneous value."""

    __slots__ = ("_lock", "value")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self.value = float(v)


class Histogram:
    """Log2-bucketed distribution with exact Welford mean/std and min/max."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._buckets = [0] * _NBUCKET
        self.count = 0
        self.sum = 0.0
        self._mean = 0.0
        self._m2 = 0.0
        self.min = math.inf
        self.max = -math.inf

    def observe(self, v: float) -> None:
        v = float(v)
        with self._lock:
            self.count += 1
            self.sum += v
            delta = v - self._mean
            self._mean += delta / self.count
            self._m2 += delta * (v - self._mean)
            if v < self.min:
                self.min = v
            if v > self.max:
                self.max = v
            self._buckets[self._index(v)] += 1

    @staticmethod
    def _index(v: float) -> int:
        if v <= 0.0:
            return 0
        e = math.frexp(v)[1] - 1  # v in [2**e, 2**(e+1))
        return min(max(e - _EXP0, 0), _NBUCKET - 1)

    @staticmethod
    def bucket_bound(i: int) -> float:
        """Lower bound of bucket ``i``."""
        return 2.0 ** (i + _EXP0)

    @property
    def mean(self) -> float:
        return self.sum / self.count if self.count else 0.0

    @property
    def std(self) -> float:
        if self.count < 2:
            return 0.0
        return math.sqrt(self._m2 / self.count)

    def _percentile_locked(self, q: float) -> float:
        if not self.count:
            return 0.0
        target = self.count * q / 100.0
        acc = 0
        for i, n in enumerate(self._buckets):
            acc += n
            if acc >= target:
                hi = self.bucket_bound(i + 1)
                return min(max(hi, self.min), self.max)
        return self.max

    def percentile(self, q: float) -> float:
        """Estimate the q-th percentile from the log2 buckets (upper
        bucket bound, clamped to the exact observed min/max — accurate
        within one octave)."""
        with self._lock:
            return self._percentile_locked(q)

    def snapshot(self) -> dict:
        # One locked section so count/min/max/percentiles are mutually
        # consistent even against concurrent observe().
        with self._lock:
            return {
                "count": self.count, "sum": self.sum,
                "mean": self.mean, "std": self.std,
                "min": self.min if self.count else 0.0,
                "max": self.max if self.count else 0.0,
                "p50": self._percentile_locked(50),
                "p90": self._percentile_locked(90),
                "p99": self._percentile_locked(99),
                "buckets": {str(i + _EXP0): n
                            for i, n in enumerate(self._buckets) if n},
            }


class Metrics:
    """Named-instrument registry; instruments are created on first use."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._histograms: dict[str, Histogram] = {}

    def _get(self, store: dict, name: str, cls):
        inst = store.get(name)
        if inst is None:
            with self._lock:
                inst = store.setdefault(name, cls())
        return inst

    def counter(self, name: str) -> Counter:
        return self._get(self._counters, name, Counter)

    def gauge(self, name: str) -> Gauge:
        return self._get(self._gauges, name, Gauge)

    def histogram(self, name: str) -> Histogram:
        return self._get(self._histograms, name, Histogram)

    def snapshot(self) -> dict:
        """JSON-able dump: {"counters": {}, "gauges": {}, "histograms": {}}."""
        # Copy the registries under the lock (a concurrent first-use
        # registration mutates the dicts); instrument reads take each
        # instrument's own lock.
        with self._lock:
            counters = sorted(self._counters.items())
            gauges = sorted(self._gauges.items())
            histograms = sorted(self._histograms.items())
        return {
            "counters": {n: c.value for n, c in counters},
            "gauges": {n: g.value for n, g in gauges},
            "histograms": {n: h.snapshot() for n, h in histograms},
        }


def flatten_snapshot(snap: dict) -> dict[str, float]:
    """Flatten a ``Metrics.snapshot()`` into scalar series for
    cross-rank aggregation (histograms contribute their summary stats)."""
    out: dict[str, float] = {}
    for name, v in snap.get("counters", {}).items():
        out[name] = float(v)
    for name, v in snap.get("gauges", {}).items():
        out[name] = float(v)
    for name, h in snap.get("histograms", {}).items():
        for k in ("count", "sum", "mean", "std", "max", "p50", "p90", "p99"):
            out[f"{name}.{k}"] = float(h.get(k, 0.0))
    return out


def aggregate_snapshots(snaps: list[dict]) -> dict[str, dict[str, float]]:
    """min/mean/max across ranks for every flattened metric (the shape
    the tracker writes into its per-job obs report)."""
    flats = [flatten_snapshot(s) for s in snaps]
    keys = sorted({k for f in flats for k in f})
    out: dict[str, dict[str, float]] = {}
    for k in keys:
        vals = [f[k] for f in flats if k in f]
        out[k] = {"min": min(vals), "mean": sum(vals) / len(vals),
                  "max": max(vals)}
    return out

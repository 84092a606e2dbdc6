"""Process-local metrics: counters, gauges, fixed-bucket histograms.

Design constraints (the observability plane's contract):

  * NO wall clock in the data path — a metric records what the caller
    hands it; wall-clock quantities enter only as observed values (TTFT
    seconds), never as implicit timestamps, so a deterministic run
    produces a deterministic snapshot.
  * fixed bucket edges — histograms are declared with their edges and
    never rebucket, so merging partial histograms (per-replica -> fleet)
    is exact integer addition and ORDER-INVARIANT (hypothesis-tested).
  * labels are part of the identity — ``counter("rejections",
    reason="queue_full")`` and ``reason="max_new"`` are separate series;
    a snapshot key renders as ``rejections{reason=queue_full}``.

``throughput_summary`` is the ONE derivation of tok/s, TTFT and
occupancy: the serving engine's report and the fixed-batch benchmark
baseline both call it, so benchmark-vs-engine metric skew is impossible
by construction (the dedup the benchmarks satellite pinned).
"""

from __future__ import annotations

import bisect
import json
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "throughput_summary"]

LabelKey = Tuple[Tuple[str, str], ...]


def _label_key(labels: Dict[str, object]) -> LabelKey:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _render(name: str, labels: LabelKey) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={v}" for k, v in labels)
    return f"{name}{{{inner}}}"


class Counter:
    """Monotone event count."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0

    def inc(self, n: int = 1) -> None:
        if n < 0:
            raise ValueError(f"counters only go up, got inc({n})")
        self.value += n


class Gauge:
    """Last-write-wins instantaneous value."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)


class Histogram:
    """Fixed-edge histogram: ``edges`` split the line into
    ``len(edges) + 1`` buckets (``(-inf, e0], (e0, e1], ..., (en, inf)``).

    ``merge`` adds bucket counts / totals of a same-shaped histogram;
    because counts are integers and addition commutes, merging any
    permutation of partials yields the identical histogram.
    """

    __slots__ = ("edges", "counts", "total", "n")

    def __init__(self, edges: Sequence[float]):
        e = tuple(float(x) for x in edges)
        if not e or list(e) != sorted(set(e)):
            raise ValueError(
                f"histogram edges must be non-empty, strictly increasing, "
                f"got {edges!r}")
        self.edges = e
        self.counts = [0] * (len(e) + 1)
        self.total = 0.0
        self.n = 0

    def observe(self, v: float) -> None:
        self.counts[bisect.bisect_left(self.edges, float(v))] += 1
        self.total += float(v)
        self.n += 1

    def merge(self, other: "Histogram") -> "Histogram":
        if self.edges != other.edges:
            raise ValueError(
                f"cannot merge histograms with different edges: "
                f"{self.edges} vs {other.edges}")
        for i, c in enumerate(other.counts):
            self.counts[i] += c
        self.total += other.total
        self.n += other.n
        return self

    @property
    def mean(self) -> float:
        return self.total / self.n if self.n else 0.0

    def snapshot(self) -> Dict[str, object]:
        return {"edges": list(self.edges), "counts": list(self.counts),
                "total": self.total, "count": self.n}


class MetricsRegistry:
    """Name+labels -> instrument, with a deterministic JSON snapshot."""

    def __init__(self):
        self._counters: Dict[Tuple[str, LabelKey], Counter] = {}
        self._gauges: Dict[Tuple[str, LabelKey], Gauge] = {}
        self._hists: Dict[Tuple[str, LabelKey], Histogram] = {}

    def counter(self, name: str, **labels) -> Counter:
        key = (name, _label_key(labels))
        c = self._counters.get(key)
        if c is None:
            c = self._counters[key] = Counter()
        return c

    def gauge(self, name: str, **labels) -> Gauge:
        key = (name, _label_key(labels))
        g = self._gauges.get(key)
        if g is None:
            g = self._gauges[key] = Gauge()
        return g

    def histogram(self, name: str, edges: Optional[Sequence[float]] = None,
                  **labels) -> Histogram:
        key = (name, _label_key(labels))
        h = self._hists.get(key)
        if h is None:
            if edges is None:
                raise ValueError(
                    f"first use of histogram {name!r} must declare edges")
            h = self._hists[key] = Histogram(edges)
        elif edges is not None and tuple(float(e) for e in edges) != h.edges:
            raise ValueError(
                f"histogram {name!r} already declared with edges "
                f"{h.edges}, got {tuple(edges)!r}")
        return h

    # -- read side ------------------------------------------------------
    def counter_value(self, name: str, **labels) -> int:
        return self.counter(name, **labels).value

    def counter_total(self, name: str) -> int:
        """Sum over every label combination of ``name``."""
        return sum(c.value for (n, _), c in self._counters.items()
                   if n == name)

    def snapshot(self) -> Dict[str, Dict[str, object]]:
        return {
            "counters": {_render(n, lk): c.value for (n, lk), c
                         in sorted(self._counters.items())},
            "gauges": {_render(n, lk): g.value for (n, lk), g
                       in sorted(self._gauges.items())},
            "histograms": {_render(n, lk): h.snapshot() for (n, lk), h
                           in sorted(self._hists.items())},
        }

    def write_json(self, path) -> str:
        with open(path, "w") as f:
            json.dump(self.snapshot(), f, indent=2, sort_keys=True)
        return str(path)


def throughput_summary(*, useful_tokens: int, wall_s: float,
                       ttfts_s: Iterable[float],
                       occupancy_sum: float, decode_steps: int
                       ) -> Dict[str, float]:
    """The one tok/s + TTFT + occupancy derivation.

    ``occupancy_sum`` accumulates (active rows / total rows) per decode
    step (the engine's running sum; a fixed batch contributes its useful
    fraction once per step), so occupancy is the mean over decode steps.
    """
    ttfts: List[float] = [float(t) for t in ttfts_s]
    return {
        "tokens_per_sec": useful_tokens / max(wall_s, 1e-9),
        "ttft_mean_s": (sum(ttfts) / len(ttfts)) if ttfts else 0.0,
        "occupancy": (occupancy_sum / decode_steps) if decode_steps else 0.0,
        "useful_tokens": int(useful_tokens),
        "wall_s": float(wall_s),
        "decode_steps": int(decode_steps),
    }

"""Serve-engine observability: counters, latency/depth histograms, spans.

The serving runtime answers operational questions the incident log alone
cannot: how many sessions per second, what a p99 window costs, how deep
the admission queue runs, how often the state cache spills, and which
phase of a cohort step the time went to. Counters and histograms are
plain host-side Python (thread-safe) and export as one flat dict
(`ServeMetrics.snapshot()`).

Histograms keep exact samples in a bounded ring (newest-wins, default
4096): percentiles are true order statistics over the retained window
rather than bucket interpolations.

Spans time the engine's phases (`serve.step` and its children,
`serve.open`, a window's `serve.queue` wait). The span log lives in
`repro.spanlog`, which the core layer logs to as well; its names are
re-exported here (`span`, `record`, `spans`, `dropped`, `clear_spans`,
`SpanRecord`).
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Dict, List

from repro.spanlog import (SpanRecord, clear_spans, dropped, record, span,
                           spans)

_MAX_SAMPLES = 4096


class Histogram:
    """Bounded-sample histogram with exact quantiles over the window."""

    def __init__(self, max_samples: int = _MAX_SAMPLES):
        self._max = max_samples
        self._samples: List[float] = []
        self._next = 0                      # ring cursor once full
        self.count = 0
        self.total = 0.0

    def observe(self, value: float) -> None:
        value = float(value)
        self.count += 1
        self.total += value
        if len(self._samples) < self._max:
            self._samples.append(value)
        else:
            self._samples[self._next] = value
            self._next = (self._next + 1) % self._max

    def quantile(self, q: float) -> float:
        """Exact order statistic over the retained samples (0 when empty)."""
        if not self._samples:
            return 0.0
        s = sorted(self._samples)
        i = min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))
        return s[i]

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    def snapshot(self) -> Dict[str, float]:
        return {"count": self.count, "mean": self.mean,
                "p50": self.quantile(0.50), "p99": self.quantile(0.99),
                "max": max(self._samples) if self._samples else 0.0}


@dataclasses.dataclass
class ServeMetrics:
    """All counters + histograms one engine instance maintains.

    Counters (monotonic):
      sessions_opened/closed/finished, chunks_admitted, chunks_rejected
      (backpressure), windows_run, session_windows (slot-windows actually
      served), steps_run (timesteps x sessions), cache_hits/misses,
      cache_evictions, cache_restores, pool_grows (state-pool doublings,
      each a retrace of the step), fresh_resets (sessions whose first
      window read the initial state inside the step).
    Histograms:
      window_latency_s   wall clock of one engine.step() that served a
                         cohort, scheduling included (its `serve.step`
                         span)
      queue_depth        ready-session count sampled at each step
      occupancy          served-slots / capacity per window (0..1)
    """

    sessions_opened: int = 0
    sessions_closed: int = 0
    sessions_finished: int = 0
    chunks_admitted: int = 0
    chunks_rejected: int = 0
    windows_run: int = 0
    session_windows: int = 0
    steps_run: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    cache_evictions: int = 0
    cache_restores: int = 0
    pool_grows: int = 0
    fresh_resets: int = 0
    window_latency_s: Histogram = dataclasses.field(default_factory=Histogram)
    queue_depth: Histogram = dataclasses.field(default_factory=Histogram)
    occupancy: Histogram = dataclasses.field(default_factory=Histogram)
    _lock: threading.Lock = dataclasses.field(default_factory=threading.Lock,
                                              repr=False)

    def bump(self, name: str, by: int = 1) -> None:
        with self._lock:
            setattr(self, name, getattr(self, name) + by)

    @property
    def cache_hit_rate(self) -> float:
        n = self.cache_hits + self.cache_misses
        return self.cache_hits / n if n else 1.0

    def snapshot(self) -> Dict[str, object]:
        """One flat dict: every counter, every histogram's summary."""
        out: Dict[str, object] = {}
        for f in dataclasses.fields(self):
            if f.name.startswith("_"):
                continue
            v = getattr(self, f.name)
            if isinstance(v, Histogram):
                out[f.name] = v.snapshot()
            else:
                out[f.name] = v
        out["cache_hit_rate"] = self.cache_hit_rate
        return out


__all__ = ["Histogram", "ServeMetrics", "SpanRecord", "span", "record",
           "spans", "dropped", "clear_spans"]

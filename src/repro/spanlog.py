"""The span log: timed phases of the runtime, on the host's clock.

`span(name, **ids)` reads `time.perf_counter_ns()` at entry and exit and
opens a `jax.profiler.TraceAnnotation` of the same name, so a profiler
trace an operator starts (`jax.profiler.trace`, or the profiler server)
shows each phase beside the device's operations. While such a trace is
active, and only then, every finished span is also kept in a process-wide
log that `spans()` returns; `record()` adds an interval known only
afterwards. The log is bounded (the oldest records fall off and are
counted by `dropped()`) and thread-safe, like the kernel incident log.
With no profiler active a span costs two clock reads and an inactive
annotation.

The serve engine (`serve.*`) and the stream loop (`sim.*`) both log here;
`repro.serve.metrics` re-exports these names.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Deque, Dict, NamedTuple, Tuple

import jax

_MAX_SPANS = 1 << 18


class SpanRecord(NamedTuple):
    """One finished span: its perf_counter_ns bounds and its ids."""

    name: str
    start_ns: int
    end_ns: int
    ids: Dict[str, Any]


_Annotation = jax.profiler.TraceAnnotation
_SPAN_LOCK = threading.Lock()
_SPANS: Deque[SpanRecord] = deque()
_DROPPED = 0


def _keep(rec: SpanRecord) -> None:
    global _DROPPED
    with _SPAN_LOCK:
        _SPANS.append(rec)
        while len(_SPANS) > _MAX_SPANS:
            _SPANS.popleft()
            _DROPPED += 1


class _Span:
    __slots__ = ("name", "ids", "start_ns", "end_ns", "_ann", "_kept")

    def __init__(self, name: str, ids: Dict[str, Any]):
        self.name = name
        self.ids = ids
        self.start_ns = self.end_ns = 0

    def __enter__(self) -> "_Span":
        self._kept = _Annotation.is_enabled()
        self._ann = _Annotation(self.name, **self.ids)
        self._ann.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        self.end_ns = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        if self._kept:
            _keep(SpanRecord(self.name, self.start_ns, self.end_ns,
                             dict(self.ids)))

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


def span(name: str, **ids: Any) -> _Span:
    """Context manager timing one phase. `ids` go to the profiler
    annotation at entry; ids added to `.ids` inside the span reach the
    log only. After exit, `.start_ns` / `.end_ns` / `.seconds` hold the
    span's own clock reads whether or not a profiler is active."""
    return _Span(name, ids)


def record(name: str, start_ns: int, end_ns: int, **ids: Any) -> None:
    """Log an interval measured elsewhere (perf_counter_ns bounds) while a
    profiler trace is active; it does not reach the profiler."""
    if _Annotation.is_enabled():
        _keep(SpanRecord(name, int(start_ns), int(end_ns), ids))


def spans() -> Tuple[SpanRecord, ...]:
    """A copy of the span log, in order of completion."""
    with _SPAN_LOCK:
        return tuple(_SPANS)


def dropped() -> int:
    """Records that fell off the bounded log since the last clear."""
    with _SPAN_LOCK:
        return _DROPPED


def clear_spans() -> None:
    global _DROPPED
    with _SPAN_LOCK:
        _SPANS.clear()
        _DROPPED = 0


__all__ = ["SpanRecord", "span", "record", "spans", "dropped", "clear_spans"]

"""Rolling serving metrics: p50/p99 batch latency + sustained KGPS.

One accounting surface shared by the engine, the serving loop and the
trigger CLI, so every consumer reports the same numbers the same way:

* latencies are *per dispatched batch*, measured host-handoff ->
  logits realized on the host;
* events are the VALID (un-padded) events in the batch — padding rows
  added to reach a compile bucket never inflate throughput;
* KGPS (thousand graphs = events per second) is events / wall over the
  union of dispatch windows, not the sum of latencies — with several
  batches in flight the pipeline sustains more than 1/latency batches
  per second.
* fault-tolerance events (shed requests, path demotions/re-promotions,
  watchdog timeouts, non-finite batches, ...) land in monotonic named
  COUNTERS (:meth:`ServingMetrics.incr`) — the health state machine
  (:mod:`repro.serving.resilient`) and ``trigger_serve --health`` read
  them off the same snapshot as the latency percentiles.
* instantaneous levels (queue depth, in-flight dispatches, free decode
  slots, ...) land in GAUGES (:meth:`ServingMetrics.gauge`) — set, not
  summed — so the event loop and the LM slot scheduler surface their
  current occupancy in the same ``snapshot()`` / ``--health`` report as
  the monotonic counters; each gauge also remembers its high-water mark
  (``<name>_max``), which is what backlog tests and capacity planning
  actually read.
* counter and gauge mutation is LOCKED: the sentinel's shadow worker
  (:mod:`repro.serving.sentinel`) increments from its own thread while
  the serve thread records batches — ``Counter.__iadd__`` is a
  read-modify-write, and a lost ``shadow_disagreements`` increment is a
  lost corruption signal.
* SPANS (:meth:`ServingMetrics.span`), off by default, name where the
  host's time goes at each layer boundary (``loop.dispatch`` >
  ``engine.prep`` / ``engine.launch``, ``loop.realize`` >
  ``engine.wait`` / ``engine.d2h``, ``loop.backpressure``).  Between
  :meth:`~ServingMetrics.start_spans` and
  :meth:`~ServingMetrics.stop_spans` each span is kept in memory and
  also entered as a ``jax.profiler.TraceAnnotation`` of the same name,
  so a profiler trace shows it on the device's clock; per-request
  SAMPLES (:meth:`ServingMetrics.sample`) ride the same window.  While
  off, a boundary costs one attribute check and records nothing.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time

import numpy as np


def percentile(xs, q: float) -> float:
    """float percentile of a sequence (empty -> nan)."""
    if len(xs) == 0:
        return float("nan")
    return float(np.percentile(np.asarray(xs), q))


def kgps(events: int, wall_s: float) -> float:
    """Sustained thousand-events-per-second (nan when wall is degenerate)."""
    return events / wall_s / 1e3 if wall_s > 0 else float("nan")


@dataclasses.dataclass(frozen=True)
class BatchRecord:
    latency_s: float
    events: int          # valid events (padding excluded)
    bucket: int          # compile-bucket batch size the events rode in


@dataclasses.dataclass(frozen=True)
class SpanWindow:
    """What one span window recorded (:meth:`ServingMetrics.stop_spans`).

    ``spans``: ``(name, start_ns, end_ns, span_id, parent_id, attrs)`` per
    closed span, in closing order; a child carries its parent's ``seq``
    attribute, so every span of one plan shares it.  ``samples``: name ->
    per-request values.  ``counters``: counter increments over the
    window (zero ones left out).
    """

    spans: list
    samples: dict
    counters: dict


class _NoSpan:
    """The span handed out while spans are off: one shared, inert object."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def close(self) -> None:
        pass


_NO_SPAN = _NoSpan()


class _Span:
    """One open span; closing it (``with`` exit or :meth:`close`) records
    it.  Closing twice is harmless."""

    __slots__ = ("_owner", "name", "attrs", "id", "parent", "start", "_ann")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def close(self) -> None:
        owner, self._owner = self._owner, None
        if owner is not None:
            owner._close(self)


class ServingMetrics:
    """Rolling window of per-batch records with percentile / KGPS views,
    named counters and gauges, and (when started) spans and samples."""

    def __init__(self, window: int = 4096, *, clock=time.perf_counter_ns):
        self._records: collections.deque[BatchRecord] = collections.deque(
            maxlen=window)
        self._wall_s = 0.0       # accumulated busy wall time (union of windows)
        self._wall_events = 0    # valid events covered by _wall_s
        self._counters: collections.Counter[str] = collections.Counter()
        self._gauges: dict[str, float] = {}
        self._gauge_peaks: dict[str, float] = {}
        self._lock = threading.Lock()
        #: span clock in ns (the profiler's and ``time.perf_counter``'s)
        self.clock = clock
        #: True between start_spans() and stop_spans(); callers check it
        #: before any per-request work done only for samples
        self.spans_on = False
        self._span_thread = None      # spans come from the serve thread
        self._stack: list[_Span] = []
        self._spans: list = []
        self._samples: dict[str, list] = {}
        self._counters_at_start: dict[str, int] = {}
        self._next_span_id = 0
        self._annotation = None

    def record_batch(self, latency_s: float, events: int, bucket: int) -> None:
        self._records.append(BatchRecord(latency_s, events, bucket))

    def incr(self, name: str, n: int = 1) -> None:
        """Bump a monotonic named counter (shed / demotion / timeout /
        ... — the fault-tolerance layer's accounting surface).
        Thread-safe: shadow-verification threads increment concurrently
        with the serve thread."""
        with self._lock:
            self._counters[name] += n

    def counter(self, name: str) -> int:
        return self._counters[name]

    @property
    def counters(self) -> dict:
        """Copy of all non-zero counters (stable for snapshotting)."""
        return {k: v for k, v in sorted(self._counters.items()) if v}

    def gauge(self, name: str, value: float) -> None:
        """Set an instantaneous level (queue depth, inflight count, free
        slots, ...).  Unlike :meth:`incr` the value REPLACES the previous
        one; the high-water mark is tracked alongside as ``<name>_max``."""
        value = float(value)
        with self._lock:
            self._gauges[name] = value
            peak = self._gauge_peaks.get(name)
            if peak is None or value > peak:
                self._gauge_peaks[name] = value

    def gauge_value(self, name: str, default: float = 0.0) -> float:
        return self._gauges.get(name, default)

    def gauge_max(self, name: str, default: float = 0.0) -> float:
        """High-water mark of ``name`` since this metrics object was
        created (backlog tests / capacity planning read this)."""
        return self._gauge_peaks.get(name, default)

    @property
    def gauges(self) -> dict:
        """Copy of the current gauge levels (stable for snapshotting)."""
        return dict(sorted(self._gauges.items()))

    # -- spans ----------------------------------------------------------------

    def start_spans(self) -> None:
        """Open a span window on the calling thread (the serve thread):
        spans, samples, and a baseline for the counter deltas."""
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self._span_thread = threading.get_ident()
        self._stack, self._spans, self._samples = [], [], {}
        with self._lock:
            self._counters_at_start = dict(self._counters)
        self.spans_on = True

    def stop_spans(self) -> SpanWindow:
        """Close the window: what it recorded, and the counters' growth
        since :meth:`start_spans` (so warm-up is left out).  Spans still
        open are dropped."""
        self.spans_on = False
        with self._lock:
            base = self._counters_at_start
            grown = {k: v - base.get(k, 0) for k, v in self._counters.items()
                     if v != base.get(k, 0)}
        out = SpanWindow(self._spans, self._samples, dict(sorted(
            grown.items())))
        self._stack, self._spans, self._samples = [], [], {}
        return out

    def span(self, name: str, **attrs):
        """A span named ``name`` from now until it is closed (use it as a
        context manager, or call ``close()``).  Off, or off the serve
        thread: the shared no-op span.  A span without ``seq`` takes its
        parent's."""
        if not self.spans_on or threading.get_ident() != self._span_thread:
            return _NO_SPAN
        parent = self._stack[-1] if self._stack else None
        if parent is not None and "seq" not in attrs and "seq" in parent.attrs:
            attrs["seq"] = parent.attrs["seq"]
        self._next_span_id += 1
        sp = _Span()
        sp._owner, sp.name, sp.attrs = self, name, attrs
        sp.id = self._next_span_id
        sp.parent = None if parent is None else parent.id
        sp._ann = self._annotation(name)
        sp._ann.__enter__()
        sp.start = self.clock()
        self._stack.append(sp)
        return sp

    def close_span(self, name: str) -> None:
        """Close the innermost open span if it is ``name``: for a span
        that a caller opens and a callee ends (``engine.prep`` ends where
        the compiled call starts)."""
        if (self.spans_on and self._stack and self._stack[-1].name == name
                and threading.get_ident() == self._span_thread):
            self._stack[-1].close()

    def _close(self, sp: _Span) -> None:
        end = self.clock()
        sp._ann.__exit__(None, None, None)
        if self._stack and self._stack[-1] is sp:
            self._stack.pop()
        elif sp in self._stack:
            self._stack.remove(sp)
        else:
            return                    # opened before the window was stopped
        self._spans.append((sp.name, sp.start, end, sp.id, sp.parent,
                            sp.attrs))

    def sample(self, name: str, value) -> None:
        """Keep one per-request value in the span window (dropped while
        spans are off)."""
        if self.spans_on:
            self._samples.setdefault(name, []).append(value)

    def record_wall(self, wall_s: float, events: int) -> None:
        """Fold a measured stream segment into the sustained-KGPS estimate."""
        self._wall_s += wall_s
        self._wall_events += events

    @property
    def batches(self) -> int:
        return len(self._records)

    @property
    def events(self) -> int:
        return sum(r.events for r in self._records)

    def latencies_s(self) -> list[float]:
        return [r.latency_s for r in self._records]

    def snapshot(self) -> dict:
        """One dict with everything the CLI / benchmark prints."""
        lats = self.latencies_s()
        evs = [r.events for r in self._records]
        mean_events = float(np.mean(evs)) if evs else float("nan")
        p50_us = percentile(lats, 50) * 1e6
        p99_us = percentile(lats, 99) * 1e6
        return {
            "batches": self.batches,
            "events": self.events,
            "p50_us": p50_us,
            "p99_us": p99_us,
            "per_event_p50_us": p50_us / mean_events if evs else float("nan"),
            "per_event_p99_us": p99_us / mean_events if evs else float("nan"),
            "kgps": kgps(self._wall_events, self._wall_s),
            "buckets": sorted({r.bucket for r in self._records}),
            "counters": self.counters,
            "gauges": self.gauges,
        }

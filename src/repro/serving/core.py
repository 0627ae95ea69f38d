"""Workload-agnostic execution core: the serving fabric's bottom layer.

The bucketed trigger engine (:mod:`repro.serving.engine`) and the LM
slot-recycling driver used to be two unrelated serving stacks — same
compile caching, same padding discipline, same metrics questions,
zero shared code.  This module is the split that unifies them: the
machinery that is identical for EVERY workload lives in
:class:`ExecutionCore`, and everything workload-specific — how to
build a compiled callable for a bucket, how to pad a request, what the
bucket ladder is — is declared by a :class:`Workload`.

``ExecutionCore`` owns, for any workload:

* **warm compile cache** — callables cached per workload cache key
  (built on miss, fault-injectable at the ``compile`` seam);
* **pad-to-bucket dispatch** — requests padded up the workload's
  ladder so arbitrary request counts reuse a handful of compilations;
* **async in-flight window** — :meth:`infer` with ``sync=False``
  returns a :class:`PendingResult`; oversized requests pipeline chunks
  with at most :data:`MAX_INFLIGHT_CHUNKS` outstanding;
* **watchdog** — realization with a ``timeout_s`` budget raises
  :class:`WatchdogTimeout` instead of blocking forever on a wedged
  dispatch;
* **wall-union metrics** — KGPS wall time is the UNION of dispatch
  windows (overlap-safe in any realization order), recorded into a
  shared :class:`~repro.serving.metrics.ServingMetrics`, with counters
  of rows served (``rows_valid`` / ``rows_bucket`` / ``rows_kernel``,
  and ``rows_lane_packed``, the kernel rows whose tile packs several
  edges into the lanes), of particle pairs evaluated (``pairs_kernel``)
  and cache misses (``compiles``), and — while spans are on — the
  ``engine.launch``, ``engine.wait`` and ``engine.d2h`` spans;
* **fault seams** — an optional
  :class:`~repro.serving.faults.FaultInjector` is consulted at the
  compile / dispatch / input / output boundaries.

:class:`~repro.serving.engine.ServingEngine` is the trigger
instantiation (a :class:`Workload` wrapping a
:class:`~repro.core.paths.PathSpec` + data-parallel mesh);
:class:`~repro.serving.lm.LMEngine` is the LM-decode instantiation.
Both are driven by the same live front-end
(:class:`~repro.serving.loop.ServingLoop`).
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.metrics import ServingMetrics, kgps

# In-flight dispatch depth for chunked infer(): enough to hide pad/H2D
# behind compute, small enough that a huge request can't pin unbounded
# device buffers.
MAX_INFLIGHT_CHUNKS = 4

# Retained merged busy-window intervals for overlap-safe KGPS wall
# accounting — far more than any realistic number of concurrently
# outstanding PendingResults, small enough that a long-running engine
# stays O(1) per dispatch.
_MAX_WALL_WINDOWS = 64


class WatchdogTimeout(RuntimeError):
    """A dispatched result failed to become ready within the watchdog
    budget (``PendingResult.result(timeout_s=...)``).  The serve loop
    must never block forever on a wedged dispatch — the resilience
    layer catches this, counts it, and re-serves via the fallback
    chain."""


class Workload:
    """What a workload must declare for :class:`ExecutionCore` to serve it.

    A workload is the *what* of serving — the compiled computation, its
    input shape discipline and its bucket policy; the core is the *how*
    — caching, padding, dispatch, accounting, fault tolerance.  The
    trigger workload wraps a forward-path :class:`~repro.core.paths.
    PathSpec` over a device mesh; the LM workload wraps prefill +
    decode-step over a slot-batched KV cache.  Subclasses override the
    hooks below; the defaults cover the common dense-batch case.

    ``name`` labels compile-cache keys, fault-injection seams and
    metrics, so one injector can target exactly one workload/path.
    """

    name: str = "workload"

    # -- bucket policy ------------------------------------------------------

    def bucket_ladder(self, max_batch: int) -> list[int]:
        """The pad-to-bucket ladder this workload earns for ``max_batch``."""
        raise NotImplementedError

    def validate_buckets(self, bucket_sizes: list[int]) -> None:
        """Veto a ladder the workload cannot serve (e.g. a bucket that
        does not divide the data mesh).  Default: anything goes."""

    # -- compilation --------------------------------------------------------

    def cache_key(self, bucket) -> tuple:
        """Everything a compiled callable's identity depends on."""
        return (self.name, bucket)

    def build(self, bucket):
        """A jitted async-dispatch callable for one bucket shape."""
        raise NotImplementedError

    # -- request shaping ----------------------------------------------------

    def pad(self, x: np.ndarray, bucket: int) -> np.ndarray:
        """Pad a request's leading axis up to ``bucket`` rows."""
        n = x.shape[0]
        if n == bucket:
            return x
        return np.concatenate(
            [x, np.zeros((bucket - n, *x.shape[1:]), x.dtype)], axis=0)

    def placeholder(self, bucket: int) -> np.ndarray:
        """A zero input of the bucket's shape (for :meth:`ExecutionCore.
        warm`)."""
        raise NotImplementedError

    def kernel_rows(self, bucket: int) -> int:
        """Rows the compiled call computes for a ``bucket``-row input
        (a kernel may pad to its own tile).  Default: the bucket."""
        return bucket

    def kernel_lane_pack(self, bucket: int) -> int:
        """Edges the compiled call's kernel packs into one row of lanes
        for a ``bucket``-row input.  Default: 1, no packing."""
        return 1

    def kernel_pairs(self, bucket: int) -> int:
        """Particle pairs the compiled call evaluates for a
        ``bucket``-row input.  Default: 0, none counted."""
        return 0

    # -- silent fault seams (optional) --------------------------------------

    def corrupted(self, seam: str, factor: float, bucket):
        """A compiled callable built from silently corrupted params, for
        the ``scale_drift`` / ``weight_corrupt`` fault seams — or
        ``None`` when the corruption does not apply to this workload
        (no params, nothing to drift).  Default: not corruptible; the
        fault then does not fire (see ``FaultInjector.corrupt_build``).
        """
        return None


def serve_stream(fwd, stream, *, warmup: int = 2, metrics=None, bucket=None):
    """Double-buffered device-feed loop; returns per-batch latencies.

    ``fwd`` must be an async-dispatch callable (jitted) taking a host or
    device array; latencies are seconds from host handoff to
    logits-ready.  Batch k+1's ``device_put`` is issued while batch k is
    still computing, so H2D transfer hides behind compute.  The first
    ``warmup`` batches (compile + cache warm) are excluded from stats;
    a stream no longer than ``warmup`` yields empty stats, not a crash.

    When ``metrics`` is given every post-warmup batch is recorded there
    (``bucket`` labels the records; defaults to the batch row count).
    """
    latencies = []
    events = 0
    it = iter(stream)

    # prime the pipeline: first transfer issued before the loop body
    try:
        nxt = jax.device_put(next(it))
    except StopIteration:
        return latencies, events, 0.0

    # wall time starts at the last warmup batch; with no warmup it starts
    # here, so KGPS is well-defined for any stream length
    t_start = time.perf_counter() if warmup == 0 else None
    k = 0
    while nxt is not None:
        cur = nxt
        t0 = time.perf_counter()
        out = fwd(cur)                      # async dispatch
        try:
            nxt = jax.device_put(next(it))  # overlap next H2D with compute
        except StopIteration:
            nxt = None
        jax.block_until_ready(out)
        t1 = time.perf_counter()
        k += 1
        if k <= warmup:                     # exclude compile from stats
            t_start = time.perf_counter()
            continue
        latencies.append(t1 - t0)
        events += cur.shape[0]
        if metrics is not None:
            metrics.record_batch(t1 - t0, cur.shape[0],
                                 bucket or cur.shape[0])
    wall = (time.perf_counter() - t_start) if t_start else 0.0
    return latencies, events, wall


class PendingResult:
    """In-flight inference: dispatched to the device, not yet waited on.

    Holds the un-blocked device buffers of one :meth:`ExecutionCore.infer`
    call.  ``result()`` blocks (once), records metrics per chunk, and
    returns the host logits.  Recorded latency is dispatch-to-REALIZATION
    (an upper bound on dispatch-to-ready: the host has no device-side
    completion timestamp) — realize promptly, or the caller's idle time
    lands in the percentiles.  Wall time for KGPS is overlap-safe in any
    realization order (see ``ExecutionCore._record_wall_window``).
    """

    def __init__(self, engine, chunks, *, record: bool = True):
        self._engine = engine
        self._chunks = chunks            # [(device_out, n_valid, bucket, t0)]
        self._record = record
        self._out = None

    @property
    def ready(self) -> bool:
        """True when every dispatched buffer is done (non-blocking); a
        host array (a fault seam's output) is always ready."""
        return all(not hasattr(out, "is_ready") or out.is_ready()
                   for out, *_ in self._chunks)

    @staticmethod
    def _wait_ready(out, deadline: float | None) -> None:
        """Block until ``out`` is ready; with a ``deadline`` (absolute
        ``perf_counter`` time), raise :class:`WatchdogTimeout` past it —
        a wedged dispatch must park the watchdog, not the whole serve
        loop.  The timed wait blocks in a daemon thread (the efficient
        runtime wait, zero poll-quantization overhead on the fast path);
        on timeout the thread is abandoned with the wedged buffer.
        Results without a readiness probe (plain host arrays) block
        directly."""
        if deadline is None or getattr(out, "is_ready", None) is None:
            jax.block_until_ready(out)
            return
        done = threading.Event()
        threading.Thread(
            target=lambda: (jax.block_until_ready(out), done.set()),
            daemon=True).start()
        if not done.wait(max(0.0, deadline - time.perf_counter())):
            raise WatchdogTimeout(
                "dispatched result not ready within the watchdog "
                "budget; abandoning the in-flight buffer")

    def result(self, *, timeout_s: float | None = None) -> np.ndarray:
        if self._out is None:
            metrics = self._engine.metrics
            deadline = (None if timeout_s is None
                        else time.perf_counter() + timeout_s)
            ready = []
            with metrics.span("engine.wait"):
                for out, *_ in self._chunks:
                    self._wait_ready(out, deadline)
                    ready.append(time.perf_counter())
            with metrics.span("engine.d2h"):
                self._out = np.concatenate(
                    [np.asarray(out)[:n_valid]
                     for out, n_valid, *_ in self._chunks], axis=0)
            t_first, t_last, events = None, None, 0
            for (_, n_valid, bucket, t0), t1 in zip(self._chunks, ready):
                if self._record:
                    metrics.record_batch(t1 - t0, n_valid, bucket)
                t_first = t0 if t_first is None else t_first
                t_last, events = t1, events + n_valid
            if self._record and t_first is not None:
                # ONE wall window for the whole dispatch, merged into the
                # engine's busy-time union: overlapped chunks AND
                # overlapped concurrent dispatches — realized in ANY
                # order — must not double-count elapsed time (KGPS is
                # events/wall, not events/sum-of-latencies)
                self._engine._record_wall_window(t_first, t_last, events)
            self._chunks = ()            # free device buffers
        return self._out


class PendingPlan:
    """A dispatched :class:`~repro.serving.batcher.BatchPlan` awaiting
    realization: ``result()`` blocks and reassembles per-request logits."""

    def __init__(self, pending: PendingResult, requests):
        self._pending = pending
        self._requests = requests

    @property
    def ready(self) -> bool:
        return self._pending.ready

    def result(self, *, timeout_s: float | None = None) -> dict:
        logits = self._pending.result(timeout_s=timeout_s)
        out: dict[int, list] = {}
        for rid, start, stop in self._requests:
            out.setdefault(rid, []).append(logits[start:stop])
        return {rid: np.concatenate(parts, axis=0)
                for rid, parts in out.items()}


class ExecutionCore:
    """Bucketed, metered, fault-injectable execution over one workload."""

    def __init__(self, workload: Workload, *, bucket_sizes=None,
                 max_batch: int = 1024,
                 metrics: ServingMetrics | None = None, injector=None):
        self.workload = workload
        self.metrics = metrics if metrics is not None else ServingMetrics()
        # Fault-injection seams (serving/faults.py): None in production.
        # The injector is consulted at compile, dispatch, input and
        # output boundaries — see the seam calls below.
        self.injector = injector
        if bucket_sizes is None:
            bucket_sizes = workload.bucket_ladder(max_batch)
        self.bucket_sizes = sorted(int(b) for b in bucket_sizes)
        workload.validate_buckets(self.bucket_sizes)
        # merged busy-time intervals (perf_counter): KGPS wall is the
        # UNION of dispatch windows, never a double-counted sum
        self._wall_windows: list[tuple[float, float]] = []
        self._cache: dict[tuple, object] = {}
        # bucket -> (rows computed, of them lane-packed, pairs evaluated)
        self._kernel_rows: dict[int, tuple[int, int, int]] = {}

    # -- compile-cache management ------------------------------------------

    def compiled_for(self, bucket):
        """The cached jitted callable for one bucket shape (built on miss).

        ``bucket`` is passed through to the workload verbatim, so it can
        be a row-count rung (trigger) or any hashable shape descriptor
        (the LM workload keys ``("prefill", L)`` / ``("decode", slots)``
        through the same cache).
        """
        key = self.workload.cache_key(bucket)
        fn = self._cache.get(key)
        if fn is None:
            self.metrics.incr("compiles")
            if self.injector is not None:
                # compile seam: fires only on a cache MISS — a warm
                # callable never recompiles, so it cannot re-fail here
                self.injector.check("compile", path=self.workload.name,
                                    bucket=bucket)
                # silent build seams (scale_drift / weight_corrupt):
                # the cached callable is built from corrupted params —
                # finite wrong answers persist until the entry is
                # rebuilt (evict()), exactly like a poisoned cache
                fn = self.injector.corrupt_build(self.workload, bucket)
            if fn is None:
                fn = self.workload.build(bucket)
            if self.injector is not None:
                # stale_cache seam: the entry replays the previous
                # dispatch's output — real logits, wrong events
                fn = self.injector.wrap_stale(
                    fn, path=self.workload.name, bucket=bucket)
            self._cache[key] = fn
        return fn

    def evict(self, bucket) -> None:
        """Drop one bucket's cached callable so the next dispatch
        rebuilds it.  The sentinel's quarantine calls this on a silent-
        corruption trip: a poisoned compiled entry must be rebuilt from
        source params, never re-trusted."""
        self._cache.pop(self.workload.cache_key(bucket), None)

    @property
    def cache_size(self) -> int:
        return len(self._cache)

    def _record_wall_window(self, t0: float, t1: float, events: int) -> None:
        """Record ``events`` over the part of [t0, t1] not already counted.

        Maintains the union of busy windows, so overlapping dispatches
        realized in any order contribute exactly their NEW coverage to
        the KGPS wall — never a double-counted sum, never dropped time.
        The merged list stays tiny: contiguous serving collapses to one
        interval.
        """
        segs = [(t0, t1)]
        for s, e in self._wall_windows:        # subtract existing coverage
            nxt = []
            for a, b in segs:
                if e <= a or s >= b:
                    nxt.append((a, b))
                    continue
                if a < s:
                    nxt.append((a, s))
                if e < b:
                    nxt.append((e, b))
            segs = nxt
        self._wall_windows.append((t0, t1))
        self._wall_windows.sort()
        merged = []
        for s, e in self._wall_windows:        # compact
            if merged and s <= merged[-1][1]:
                merged[-1] = (merged[-1][0], max(merged[-1][1], e))
            else:
                merged.append((s, e))
        # bound the list: out-of-order realization is bounded by the
        # outstanding PendingResults, so ancient windows can be dropped —
        # a pathologically stale realization then at worst over-counts a
        # little wall, it never corrupts unboundedly
        self._wall_windows = merged[-_MAX_WALL_WINDOWS:]
        self.metrics.record_wall(sum(b - a for a, b in segs), events)

    def bucket_for(self, n_events: int) -> int:
        """Smallest bucket holding ``n_events`` (largest if none do)."""
        from repro.kernels import autotune
        return autotune.bucket_for(self.bucket_sizes, n_events)

    def _count_rows(self, n_valid: int, bucket: int, times: int = 1) -> None:
        """Rows served: valid, padded to the bucket, as the kernel
        computes them, and of those the rows of a lane-packed tile (its
        tile decision for the bucket, asked once); and the particle
        pairs the step evaluates."""
        rows = self._kernel_rows.get(bucket)
        if rows is None:
            n = int(self.workload.kernel_rows(bucket))
            packed = self.workload.kernel_lane_pack(bucket) > 1
            rows = self._kernel_rows[bucket] = (
                n, n if packed else 0, int(self.workload.kernel_pairs(bucket)))
        self.metrics.incr("rows_valid", n_valid * times)
        self.metrics.incr("rows_bucket", bucket * times)
        self.metrics.incr("rows_kernel", rows[0] * times)
        self.metrics.incr("rows_lane_packed", rows[1] * times)
        self.metrics.incr("pairs_kernel", rows[2] * times)

    def warm(self, buckets=None) -> None:
        """Pre-compile (and pre-run once) the given buckets — compile cost
        paid before traffic arrives, not on the first unlucky request."""
        for b in buckets if buckets is not None else self.bucket_sizes:
            jax.block_until_ready(
                self.compiled_for(b)(jnp.asarray(self.workload.placeholder(b))))

    # -- inference ----------------------------------------------------------

    def _pad(self, x: np.ndarray, bucket: int) -> np.ndarray:
        return self.workload.pad(x, bucket)

    def infer(self, x, *, record: bool = True, sync: bool = True,
              timeout_s: float | None = None, bucket: int | None = None):
        """Serve ``x`` (n, ...): pad to bucket, dispatch, slice back.

        Requests larger than the top bucket are chunked through it; chunk
        k+1's pad + dispatch overlaps chunk k's compute, with at most
        :data:`MAX_INFLIGHT_CHUNKS` dispatches outstanding so an
        arbitrarily large request keeps bounded device memory (the old
        block-per-chunk loop pinned exactly one buffer; this pins a small
        pipeline's worth).

        ``sync=True`` (default) blocks and returns the logits array;
        ``sync=False`` returns a :class:`PendingResult` immediately after
        dispatch, letting the caller (e.g. a batcher loop) overlap the
        next flush with this one's in-flight compute.  Metrics are
        recorded when the result is realized, never on dispatch.
        ``timeout_s`` arms the realization watchdog (sync path only;
        async callers pass it to ``PendingResult.result``).
        ``bucket`` PINS the compile bucket instead of resolving it from
        the row count — the sentinel's canaries use this to ride a
        specific bucket's cached callable with a small probe batch.
        """
        x = np.asarray(x)
        pin = bucket
        if pin is not None:
            if pin not in self.bucket_sizes:
                raise ValueError(
                    f"pinned bucket {pin} not in ladder {self.bucket_sizes}")
            if x.shape[0] > pin:
                raise ValueError(
                    f"request of {x.shape[0]} rows cannot ride pinned "
                    f"bucket {pin}")
        top = self.bucket_sizes[-1]
        chunks = []
        for i in range(0, x.shape[0], top):
            if len(chunks) >= MAX_INFLIGHT_CHUNKS:
                # throttle: wait for the oldest in-flight chunk before
                # enqueueing more (its latency is still stamped at
                # realization, where the wait is then a no-op)
                jax.block_until_ready(chunks[-MAX_INFLIGHT_CHUNKS][0])
            chunk = x[i:i + top]
            n_valid = chunk.shape[0]
            bucket = self.bucket_for(n_valid) if pin is None else pin
            if self.injector is not None:
                self.injector.check("dispatch", path=self.workload.name,
                                    bucket=bucket)
                chunk = self.injector.corrupt_input(
                    chunk, path=self.workload.name, bucket=bucket)
            fn = self.compiled_for(bucket)
            t0 = time.perf_counter()
            xd = jnp.asarray(self._pad(chunk, bucket))
            # the caller's engine.prep span (run_plan) ends at the call
            self.metrics.close_span("engine.prep")
            with self.metrics.span("engine.launch"):
                out = fn(xd)                                  # async dispatch
            if record:
                self._count_rows(n_valid, bucket)
            if self.injector is not None:
                out = self.injector.wrap_output(out, path=self.workload.name,
                                                bucket=bucket)
            chunks.append((out, n_valid, bucket, t0))
        pending = PendingResult(self, chunks, record=record)
        return pending.result(timeout_s=timeout_s) if sync else pending

    def run_plan(self, plan, *, sync: bool = True):
        """Execute one :class:`~repro.serving.batcher.BatchPlan`; returns
        ``{rid: (n_i, ...) outputs}`` reassembled per request.

        ``sync=False`` returns a :class:`PendingPlan` right after
        dispatch; realize it with ``.result()`` once the next plans are
        in flight.  The ``engine.prep`` span runs from here to the
        compiled call."""
        with self.metrics.span("engine.prep"):
            pending = PendingPlan(self.infer(plan.x, sync=False),
                                  plan.requests)
        return pending.result() if sync else pending

    def run_stream(self, stream, *, warmup: int = 2) -> dict:
        """Pump a fixed-size batch stream through the double-buffered feed
        loop (the trigger CLI's hot path).  All batches must share one
        size; each is padded to its ladder bucket before dispatch."""
        stream = list(stream)
        if not stream:
            return {"latencies": [], "events": 0, "wall_s": 0.0,
                    "bucket": None, "kgps": float("nan")}
        sizes = {b.shape[0] for b in stream}
        if len(sizes) != 1:
            raise ValueError(f"stream batches differ in size: {sorted(sizes)}")
        n_valid = sizes.pop()
        if n_valid > self.bucket_sizes[-1]:
            raise ValueError(
                f"stream batch size {n_valid} exceeds the top bucket "
                f"{self.bucket_sizes[-1]}; build the engine with "
                f"max_batch >= {n_valid} or chunk through infer()")
        bucket = self.bucket_for(n_valid)
        fwd = self.compiled_for(bucket)
        padded = [self._pad(np.asarray(b), bucket) for b in stream]
        lat, _, wall = serve_stream(fwd, padded, warmup=warmup)
        # KGPS counts VALID events only — padding rows are not throughput.
        events = n_valid * len(lat)
        for t in lat:
            self.metrics.record_batch(t, n_valid, bucket)
        self.metrics.record_wall(wall, events)
        self._count_rows(n_valid, bucket, len(lat))
        return {"latencies": lat, "events": events, "wall_s": wall,
                "bucket": bucket, "kgps": kgps(events, wall)}

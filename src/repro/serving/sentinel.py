"""Online silent-corruption sentinel: canaries, shadows, quarantine.

The degradation ladder (:mod:`repro.serving.resilient`) catches loud
failures — exceptions, NaN logits, watchdog timeouts.  It is blind to
the failure mode a Level-1 trigger fears most: *finite wrong answers*.
A drifted int8 ``w_scale``, a corrupted weight tensor, or a stale
compile-cache entry (the silent seams of :mod:`repro.serving.faults`)
produces logits that are shaped, finite, and wrong — ``health()`` reads
``healthy`` while physics is misclassified.  The sentinel is the online
correctness layer that closes that gap, with three mechanisms:

**Golden canaries.**  At construction the sentinel draws one small
fixed canary batch and precomputes *golden* logits per constructible
chain rung from the rung spec's own ``ref`` fn (the registry's
numerical oracle).  On a request-count / time cadence — and on the
FIRST request a bucket ever serves — the canary batch is injected
through the *live* serve path (pinned to the bucket's compiled
callable via ``infer(bucket=...)``, so a 4-event probe exercises the
big bucket's cache entry) and compared against the golden logits
within ``tolerance_slack x PathSpec.tolerance``.  Build-time
corruption is therefore caught on the bucket's first canary — one
observed batch of detection latency.

**Shadow re-execution.**  A duty-cycled sample of live requests
(deterministic stride ``round(1/shadow_rate)`` — like the fault
injector, never a random draw) re-runs asynchronously on the chain's
terminal non-Pallas rung (:func:`repro.core.paths.terminal_rung`), the
one rung plain XLA guarantees servable.  Per-bucket agreement
statistics — EWMA max-|Δlogit| and argmax-disagreement rate — land in
:class:`~repro.serving.metrics.ServingMetrics` gauges.  The trip
threshold is calibrated from the golden table itself
(``slack x max(|golden[rung] - golden[terminal]|, tolerance)``) so a
quantized rung's legitimate quantization gap to the fp32 oracle never
trips it.  The worker thread only *records* trips; the serve thread
applies them at its next ``observe()`` — no cross-thread engine
mutation.

**Canary-gated quarantine.**  A sentinel trip evicts the poisoned
rung's compile-cache entry for that bucket (build-time corruption
lives in the cached callable — see ``FaultInjector.corrupt_build``),
demotes the bucket below the rung, and marks it ``quarantined``.
Unlike the loud ladder's single live probe, a quarantined rung only
re-promotes after ``promote_after`` CONSECUTIVE clean canaries, each
one exercising the rebuilt callable at the quarantined rung; a dirty
canary re-evicts and zeroes the streak.  ``health()`` reports the new
``quarantined`` state (worse than ``shedding``, better than ``down``)
with per-bucket detail.

The sentinel owns no wall clock: it reads time only through the
engine's injectable clock seam, so every cadence decision is
freezable in tests.
"""

from __future__ import annotations

import dataclasses
import queue
import threading

import jax
import numpy as np

from repro.core import paths as forward_paths


@dataclasses.dataclass
class SentinelConfig:
    """Knobs for one :class:`Sentinel`.

    ``canary_every`` is a per-bucket request-count cadence (the first
    request a bucket serves always canaries); ``canary_interval_s``
    optionally adds a time cadence on the engine's clock.
    ``shadow_rate`` is the duty cycle of terminal-rung shadow
    re-execution (0 disables it); ``shadow_sync`` runs shadow jobs
    inline on the serve thread — deterministic for tests, and what the
    post-stream verification uses.  ``promote_after`` is K, the clean
    canary streak a quarantined rung needs to re-promote.
    ``tolerance_slack`` scales ``PathSpec.tolerance`` into the canary
    trip threshold (live-vs-ref tolerances are tight; corruption is
    orders of magnitude away).
    """

    canary_every: int = 64
    canary_interval_s: float | None = None
    shadow_rate: float = 1 / 16
    shadow_sync: bool = False
    shadow_queue: int = 64
    promote_after: int = 3
    tolerance_slack: float = 8.0
    canary_events: int = 4
    ewma_alpha: float = 0.5
    seed: int = 0


class Sentinel:
    """Online correctness monitor bound to one ResilientEngine."""

    def __init__(self, engine, config: SentinelConfig | None = None, *,
                 clock=None):
        self.config = config if config is not None else SentinelConfig()
        self._engine = engine
        self._clock = clock if clock is not None else engine._clock
        cfg = engine.cfg
        # decorrelate the canary draw from common user seeds: live
        # traffic drawn from RandomState(0) must never alias the canary
        # batch, or a stale-cache entry replaying that traffic would
        # pass the canary by construction
        rng = np.random.RandomState((self.config.seed ^ 0xC0FFEE) & 0xFFFFFFFF)
        self._canary_x = rng.normal(
            0.0, 1.0, (self.config.canary_events, cfg.n_objects,
                       cfg.n_features)).astype(np.float32)
        self.terminal_level = len(engine.chain) - 1

        # golden logits per constructible rung, from the rung's own ref
        # fn on ITS prepared params (int8 rungs are compared against the
        # int8 oracle, so PathSpec.tolerance is the right yardstick)
        self._golden: dict[int, np.ndarray] = {}
        for lvl, name in enumerate(engine.chain):
            if lvl in engine._construct_failed:
                continue
            spec = forward_paths.get(name)
            try:
                prepared = spec.prepare_params(engine._params)
                # the oracle at full fp32 matmul precision, as the rungs
                # serve it (a TPU's default is one bf16 pass)
                with jax.default_matmul_precision("highest"):
                    golden = spec.ref(prepared, cfg, self._canary_x)
                self._golden[lvl] = np.asarray(golden, np.float32)
            except Exception:   # noqa: BLE001 — a rung without a golden
                pass            # just cannot canary (counted per canary)

        # shadow trip threshold per rung: the rung's OWN legitimate gap
        # to the terminal oracle (e.g. int8 quantization loss), slacked
        golden_t = self._golden.get(self.terminal_level)
        self._shadow_thr: dict[int, float] = {}
        for lvl, g in self._golden.items():
            base = (float(np.abs(g - golden_t).max())
                    if golden_t is not None else 0.0)
            tol = forward_paths.get(engine.chain[lvl]).tolerance
            self._shadow_thr[lvl] = (
                self.config.tolerance_slack * max(base, tol))

        self._since: dict[int, int] = {}       # requests since last canary
        self._last_canary: dict[int, float] = {}
        self._shadow_count = 0
        self._ewma: dict[int, tuple[float, float]] = {}  # bucket -> (dev, arg)
        self._stats_lock = threading.Lock()
        self._pending: list[tuple[int, int]] = []        # (bucket, level)
        self._pending_lock = threading.Lock()
        self._queue: queue.Queue | None = None
        self._worker: threading.Thread | None = None

    # -- serve-thread surface ------------------------------------------------

    def observe(self, x, out, bucket: int, level: int) -> None:
        """One recorded live serve happened on ``bucket`` at ``level``.

        Called by the engine on the serve thread after a successful
        rung serve: applies any shadow-worker trips, duty-cycles the
        request into shadow re-execution, and runs the canary when the
        bucket's cadence is due."""
        self._apply_pending()
        if self._should_shadow(bucket, level):
            self._submit_shadow(np.asarray(x), np.asarray(out), bucket,
                                level)
        cnt = self._since.get(bucket, self.config.canary_every)
        due = cnt >= self.config.canary_every
        if not due and self.config.canary_interval_s is not None:
            last = self._last_canary.get(bucket)
            due = (last is None
                   or self._clock() - last >= self.config.canary_interval_s)
        if due:
            self.canary(bucket)
        else:
            self._since[bucket] = cnt + 1

    def canary(self, bucket: int) -> bool | None:
        """Inject the golden canary through ``bucket``'s live rung.

        Quarantined buckets canary their QUARANTINED rung (that is the
        requalification gate); healthy buckets canary the active rung.
        Returns True (clean), False (mismatch -> quarantine), or None
        (no golden / rung raised — loud failures are the ladder's job).
        """
        eng = self._engine
        st = eng._bucket_state(bucket)
        lvl = st.q_level if st.quarantined else st.level
        m = eng.metrics
        m.incr("canaries")
        self._since[bucket] = 0
        self._last_canary[bucket] = self._clock()
        golden = self._golden.get(lvl)
        if golden is None:
            m.incr("canary_errors")
            return None
        n = min(self._canary_x.shape[0], bucket)
        try:
            # no watchdog thread: the canary rides a rung that just
            # served a live request successfully (wedges trip the loud
            # ladder there), and the spawn costs ~0.3 ms per canary —
            # a third of the whole canary budget on fast paths
            live = eng._engine_for(lvl).infer(
                self._canary_x[:n], record=False, bucket=bucket)
        except Exception:   # noqa: BLE001 — loud canary failure: not a
            m.incr("canary_errors")   # silent trip, but never a clean pass
            if st.quarantined:
                st.clean = 0
            return None
        dev = float(np.abs(np.asarray(live, np.float32) - golden[:n]).max())
        m.gauge(f"canary_dev_b{bucket}", dev)
        tol = forward_paths.get(eng.chain[lvl]).tolerance
        if np.isfinite(dev) and dev <= self.config.tolerance_slack * tol:
            if st.quarantined:
                st.clean += 1
                if st.clean >= self.config.promote_after:
                    eng._requalify(bucket)
            return True
        m.incr("canary_mismatches")
        eng._quarantine(bucket, lvl)
        return False

    def verify_stream(self, stream, bucket: int, level: int) -> None:
        """Post-hoc sentinel pass over a served fixed-size stream.

        The double-buffered stream loop is the latency-critical path —
        it is left untouched.  After the stream returns, a duty-cycled
        sample of its ticks re-runs through the live rung's compiled
        callable and shadows against the terminal oracle (synchronously
        — the stream is already over, there is nothing to overlap), and
        the bucket canaries on its normal ``canary_every`` cadence with
        every tick counted as one observed request (a bucket's FIRST
        stream still always canaries, preserving the one-batch
        detection guarantee for build-time corruption; later short
        streams amortize the canary instead of each paying one).  This
        is the overhead the ≤5% stream budget in EXPERIMENTS.md
        §Sentinel measures: the elapsed verification wall lands in the
        ``sentinel_verify_s`` gauge so the benchmark can report it
        against the stream's wall."""
        t0 = self._clock()
        if self.config.shadow_rate > 0 and level < self.terminal_level:
            stride = max(1, int(round(1.0 / self.config.shadow_rate)))
            try:
                eng = self._engine._engine_for(level)
            except Exception:   # noqa: BLE001 — rung gone: canary only
                eng = None
            if eng is not None:
                for i in range(stride - 1, len(stream), stride):
                    x = np.asarray(stream[i])
                    try:
                        out = eng.infer(x, record=False)
                    except Exception:   # noqa: BLE001 — loud: ladder's job
                        continue
                    self._shadow_job(x, np.asarray(out), bucket, level)
        cnt = self._since.get(bucket, self.config.canary_every)
        for _ in range(len(stream)):
            cnt += 1
            if cnt >= self.config.canary_every:
                self.canary(bucket)
                cnt = 0
        self._since[bucket] = cnt
        self._apply_pending()
        self._engine.metrics.gauge("sentinel_verify_s", self._clock() - t0)

    def detail(self) -> dict:
        """Sentinel block for ``health()``."""
        with self._stats_lock:
            ewma = {b: {"dev": d, "argmax_disagree": a}
                    for b, (d, a) in sorted(self._ewma.items())}
        return {
            "canary_every": self.config.canary_every,
            "shadow_rate": self.config.shadow_rate,
            "promote_after": self.config.promote_after,
            "golden_rungs": sorted(self._golden),
            "shadow_ewma": ewma,
        }

    # -- shadow re-execution -------------------------------------------------

    def _should_shadow(self, bucket: int, level: int) -> bool:
        if self.config.shadow_rate <= 0 or level >= self.terminal_level:
            return False
        st = self._engine._state.get(bucket)
        if st is not None and st.quarantined:
            return False        # already caught; canaries gate recovery
        stride = max(1, int(round(1.0 / self.config.shadow_rate)))
        self._shadow_count += 1
        return self._shadow_count % stride == 0

    def _submit_shadow(self, x, out, bucket: int, level: int) -> None:
        if self.config.shadow_sync:
            self._shadow_job(x, out, bucket, level)
            return
        if self._worker is None:
            self._queue = queue.Queue(maxsize=self.config.shadow_queue)
            self._worker = threading.Thread(
                target=self._worker_loop, name="sentinel-shadow",
                daemon=True)
            self._worker.start()
        try:
            self._queue.put_nowait((np.array(x, copy=True),
                                    np.array(out, copy=True),
                                    bucket, level))
        except queue.Full:
            self._engine.metrics.incr("shadow_dropped")

    def _worker_loop(self) -> None:
        while True:
            item = self._queue.get()
            try:
                if item is None:
                    return
                self._shadow_job(*item)
            finally:
                self._queue.task_done()

    def _shadow_job(self, x, out, bucket: int, level: int) -> None:
        """Re-run ``x`` on the terminal rung; fold agreement stats into
        metrics; RECORD (never apply) a trip on disagreement beyond the
        rung's calibrated threshold."""
        m = self._engine.metrics
        m.incr("shadow_requests")
        try:
            ref = self._engine._engine_for(self.terminal_level).infer(
                x, record=False)
        except Exception:   # noqa: BLE001 — oracle unavailable: no verdict
            m.incr("shadow_errors")
            return
        ref = np.asarray(ref, np.float32)
        out = np.asarray(out, np.float32)
        dev = float(np.abs(out - ref).max())
        disagree = float(np.mean(np.argmax(out, axis=-1)
                                 != np.argmax(ref, axis=-1)))
        a = self.config.ewma_alpha
        with self._stats_lock:
            prev = self._ewma.get(bucket)
            ewma = ((dev, disagree) if prev is None else
                    (a * dev + (1 - a) * prev[0],
                     a * disagree + (1 - a) * prev[1]))
            self._ewma[bucket] = ewma
        m.gauge(f"shadow_dev_ewma_b{bucket}", ewma[0])
        m.gauge(f"shadow_argmax_ewma_b{bucket}", ewma[1])
        thr = self._shadow_thr.get(level)
        if thr is not None and (not np.isfinite(dev) or dev > thr):
            m.incr("shadow_disagreements")
            with self._pending_lock:
                self._pending.append((bucket, level))

    def _apply_pending(self) -> None:
        """Serve-thread application of shadow-worker trips."""
        with self._pending_lock:
            trips, self._pending = self._pending, []
        for bucket, level in trips:
            st = self._engine._bucket_state(bucket)
            if st.quarantined and st.q_level == level:
                continue        # already quarantined on this rung
            self._engine._quarantine(bucket, level)

    def drain(self) -> None:
        """Block until every queued shadow job has run, then apply any
        trips they recorded (tests + orderly shutdown)."""
        if self._queue is not None:
            self._queue.join()
        self._apply_pending()

    def close(self) -> None:
        if self._worker is not None:
            self._queue.put(None)
            self._worker.join(timeout=5.0)
            self._worker = None
            self._queue = None

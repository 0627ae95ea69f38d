"""Silent-corruption sentinel suite: canaries, shadows, quarantine.

The coverage target is the failure mode the chaos suite cannot see:
finite, shaped, WRONG logits (a drifted int8 scale, a corrupted weight
tensor, a stale compile-cache entry).  These tests drive the sentinel's
three mechanisms deterministically on CPU — golden canaries through the
live pinned-bucket path, duty-cycled terminal-rung shadow re-execution,
and canary-gated quarantine/requalification — plus the thread-safety
satellite on :class:`ServingMetrics`.

All tests carry the ``sentinel`` marker: they run in tier-1 and
standalone in CI's chaos job (``pytest -m "chaos or sentinel"``).
"""

import threading

import jax
import numpy as np
import pytest

from repro.core import paths
from repro.core.interaction_net import JediNetConfig, forward_sr, init
from repro.serving import (
    FaultInjector,
    ResilientEngine,
    SentinelConfig,
    ServingLoop,
    ServingMetrics,
)
from repro.serving.trigger import serve_ticks

pytestmark = pytest.mark.sentinel

#: (path, seam, factor) triples covering every silent seam, each on a
#: path where the corruption actually bites (scale_drift needs int8).
SILENT_CASES = [
    ("int8_fused_full", "scale_drift", 8.0),
    ("fused_full", "weight_corrupt", 8.0),
    ("fused_full", "stale_cache", 1.0),
]


@pytest.fixture(scope="module")
def jedi8():
    cfg = JediNetConfig(n_objects=8, n_features=16)
    params = init(jax.random.PRNGKey(0), cfg, scale="lecun")
    rng = np.random.RandomState(0)
    x = rng.normal(0, 1, (5, 8, 16)).astype(np.float32)
    ref = np.asarray(forward_sr(params, cfg, x))
    return cfg, params, x, ref


def _engine(jedi, injector=None, sentinel=None, **kw):
    cfg, params, _, _ = jedi
    kw.setdefault("forward", "fused_full")
    kw.setdefault("interpret", True)
    kw.setdefault("max_batch", 16)
    if sentinel is None:
        sentinel = SentinelConfig(canary_every=4, promote_after=2,
                                  shadow_rate=0.25, shadow_sync=True)
    return ResilientEngine(params, cfg, injector=injector,
                           sentinel=sentinel, **kw)


# -- registry helper ------------------------------------------------------


def test_terminal_rung_resolves_chain_bottom():
    for name in paths.available():
        term = paths.terminal_rung(name)
        assert term == paths.fallback_chain(name)[-1]
        assert not paths.get(term).pallas


# -- canary detection -----------------------------------------------------


@pytest.mark.parametrize("path,seam,factor", SILENT_CASES)
def test_canary_detects_and_quarantines_each_silent_seam(
        jedi8, path, seam, factor):
    """Every silent seam is caught by the FIRST canary (build-time
    corruption lives in the cached callable, and a bucket's first
    observed request always canaries) — one batch of detection
    latency, zero exceptions, and never a ``healthy`` report while
    the corruption serves."""
    cfg, params, x, _ = jedi8
    inj = FaultInjector()
    inj.arm(seam, path=path, factor=factor)          # persistent corruption
    eng = _engine(jedi8, inj, forward=path)
    out = eng.infer(x)                               # must never raise
    assert out.shape == (5, cfg.n_targets) and np.isfinite(out).all()
    h = eng.health()
    assert h["state"] == "quarantined"
    assert h["counters"]["sentinel_trips"] >= 1
    assert h["counters"]["quarantines"] == 1
    b = h["buckets"][eng.bucket_for(5)]
    assert b["quarantined"] and b["quarantined_path"] == path


@pytest.mark.parametrize("path,seam,factor", SILENT_CASES)
def test_quarantine_requalifies_after_clean_canaries(jedi8, path, seam,
                                                     factor):
    """A one-shot corruption (times=1): the trip evicts the poisoned
    cache entry, the rebuild is clean, and ``promote_after``
    consecutive clean canaries re-promote — the self-healing story."""
    cfg, params, x, _ = jedi8
    inj = FaultInjector()
    inj.arm(seam, path=path, times=1, factor=factor)
    eng = _engine(jedi8, inj, forward=path)
    states = []
    for _ in range(12):
        out = eng.infer(x)
        assert np.isfinite(out).all()
        states.append(eng.health()["state"])
    assert states[0] == "quarantined"                # caught on request 1
    assert states[-1] == "healthy"                   # ...and healed
    # no healthy report in between: quarantined until requalification
    first_healthy = states.index("healthy")
    assert all(s == "quarantined" for s in states[:first_healthy])
    c = eng.metrics.counters
    assert c["requalifications"] == 1
    assert c["canary_mismatches"] == 1
    assert eng.active_path(eng.bucket_for(5)) == path


def test_persistent_corruption_never_requalifies(jedi8):
    """times=inf: every post-eviction rebuild re-corrupts, so every
    requalification canary is dirty and the bucket stays quarantined —
    serving the clean fallback rung the whole time."""
    cfg, params, x, ref = jedi8
    inj = FaultInjector()
    inj.arm("weight_corrupt", path="fused_full", factor=8.0)
    eng = _engine(jedi8, inj)
    eng.infer(x)          # request 1 serves corrupted (1-batch detection)
    assert eng.health()["state"] == "quarantined"
    for _ in range(15):
        out = eng.infer(x)
        # the fallback rung (sr_split) serves CORRECT answers throughout
        assert np.abs(out - ref).max() < 1e-3
    h = eng.health()
    assert h["state"] == "quarantined"
    assert h["counters"]["sentinel_trips"] >= 2      # re-tripped on requal
    assert "requalifications" not in h["counters"]


def test_quarantined_bucket_never_probes_live_traffic(jedi8):
    """Re-promotion out of quarantine is canary-gated: the backoff
    probe machinery must NOT route live requests at the quarantined
    rung (it could LOOK healthy to a probe on non-canary input)."""
    cfg, params, x, _ = jedi8
    t = [0.0]
    inj = FaultInjector()
    inj.arm("weight_corrupt", path="fused_full", factor=8.0)
    eng = _engine(jedi8, inj, clock=lambda: t[0], probe_initial_s=0.01)
    for _ in range(8):
        eng.infer(x)
        t[0] += 10.0                                 # way past any backoff
    assert eng.health()["state"] == "quarantined"
    assert "probes" not in eng.metrics.counters


def test_sentinel_quarantines_weight_corrupt_under_serving_loop(jedi8):
    """The CLI's path: ticks through the serving loop, several plans in
    flight, a persistent weight corruption on the primary.  The first
    canary quarantines the bucket; plans dispatched before the trip and
    realized after it do not lift the quarantine, so every later plan is
    served right by the fallback."""
    cfg, params, _, _ = jedi8
    inj = FaultInjector()
    inj.arm("weight_corrupt", path="fused_full", factor=8.0)
    eng = _engine(jedi8, inj)
    rng = np.random.RandomState(5)
    xs = [rng.normal(0, 1, (16, 8, 16)).astype(np.float32)
          for _ in range(12)]
    futures = serve_ticks(ServingLoop(eng), xs)
    h = eng.health()
    assert h["state"] == "quarantined"
    (b,) = h["buckets"].values()
    assert b["quarantined_path"] == "fused_full" and b["path"] == "sr_split"
    assert h["counters"]["quarantines"] == 1
    assert "promotions" not in h["counters"]
    for fut, x in zip(futures[-6:], xs[-6:]):
        ref = np.asarray(forward_sr(params, cfg, x))
        assert np.abs(fut.result() - ref).max() < 1e-3


# -- shadow re-execution --------------------------------------------------


def test_shadow_reexecution_feeds_agreement_stats(jedi8):
    """Fault-free serving: the duty-cycled shadow sample re-runs on the
    terminal rung and lands EWMA agreement gauges; nothing trips."""
    cfg, params, x, _ = jedi8
    eng = _engine(jedi8, sentinel=SentinelConfig(
        canary_every=100, shadow_rate=0.5, shadow_sync=True))
    for _ in range(8):
        eng.infer(x)
    m = eng.metrics
    b = eng.bucket_for(5)
    assert m.counter("shadow_requests") >= 3
    assert m.gauge_value(f"shadow_dev_ewma_b{b}") < 1e-2
    assert m.gauge_value(f"shadow_argmax_ewma_b{b}") == 0.0
    assert "shadow_disagreements" not in m.counters
    assert eng.health()["state"] == "healthy"


def test_shadow_trips_quarantine_when_canary_is_blind(jedi8):
    """The shadow path is an independent detector: with the golden
    table emptied (canaries can only error out), live-vs-terminal
    disagreement alone must still quarantine the corrupted rung."""
    cfg, params, x, _ = jedi8
    inj = FaultInjector()
    inj.arm("weight_corrupt", path="fused_full", factor=8.0)
    eng = _engine(jedi8, inj, sentinel=SentinelConfig(
        canary_every=1000, shadow_rate=1.0, shadow_sync=True))
    eng.sentinel._golden.clear()                     # blind the canaries
    for _ in range(4):
        eng.infer(x)
    h = eng.health()
    assert h["state"] == "quarantined"
    assert h["counters"]["shadow_disagreements"] >= 1
    assert h["counters"]["quarantines"] == 1


def test_shadow_worker_thread_applies_trips_on_serve_thread(jedi8):
    """Async mode: the worker only RECORDS trips; the serve thread
    applies them at its next observe (or an explicit drain)."""
    cfg, params, x, _ = jedi8
    inj = FaultInjector()
    inj.arm("weight_corrupt", path="fused_full", factor=8.0)
    eng = _engine(jedi8, inj, sentinel=SentinelConfig(
        canary_every=1000, shadow_rate=1.0, shadow_sync=False))
    eng.sentinel._golden.clear()
    try:
        for _ in range(4):
            eng.infer(x)
        eng.sentinel.drain()                         # join queue + apply
        assert eng.health()["state"] == "quarantined"
        assert eng.metrics.counter("shadow_requests") >= 1
    finally:
        eng.sentinel.close()


def test_quantized_rung_does_not_false_trip_against_fp32_oracle(jedi8):
    """int8 live vs fp32 terminal differ by real quantization loss; the
    golden-calibrated threshold must absorb it (no trips, no
    quarantine) on a fault-free engine."""
    cfg, params, x, _ = jedi8
    eng = _engine(jedi8, forward="int8_fused_full",
                  sentinel=SentinelConfig(canary_every=2, shadow_rate=0.5,
                                          shadow_sync=True))
    for _ in range(8):
        eng.infer(x)
    h = eng.health()
    assert h["state"] == "healthy"
    assert "shadow_disagreements" not in h["counters"]
    assert "canary_mismatches" not in h["counters"]
    assert h["counters"]["shadow_requests"] >= 2


# -- health surface -------------------------------------------------------


def test_health_reports_sentinel_detail(jedi8):
    eng = _engine(jedi8)
    eng.infer(jedi8[2])
    h = eng.health()
    s = h["sentinel"]
    assert s["canary_every"] == 4 and s["promote_after"] == 2
    assert s["golden_rungs"] == [0, 1]               # fused_full, sr_split
    b = h["buckets"][eng.bucket_for(5)]
    assert {"quarantined", "quarantined_path", "clean_canaries"} <= set(b)


def test_health_state_ordering_quarantined_beats_shedding(jedi8):
    cfg, params, x, _ = jedi8
    inj = FaultInjector()
    inj.arm("weight_corrupt", path="fused_full", factor=8.0)
    eng = _engine(jedi8, inj)
    eng.infer(x)                                     # -> quarantined
    eng.infer(x, deadline=eng._clock() - 1.0)        # -> a recent shed
    assert eng.metrics.counter("shed_requests") == 1
    assert eng.health()["state"] == "quarantined"


# -- metrics thread-safety (satellite) ------------------------------------


def test_metrics_concurrent_increments_lose_nothing():
    """The sentinel's shadow worker increments counters concurrently
    with the serve thread; the metrics lock must make every increment
    land (Counter.__iadd__ is read-modify-write)."""
    m = ServingMetrics()
    n_threads, n_incr = 8, 2000

    def pump():
        for _ in range(n_incr):
            m.incr("shadow_requests")
            m.gauge("inflight", 1.0)

    threads = [threading.Thread(target=pump) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert m.counter("shadow_requests") == n_threads * n_incr
    assert m.gauge_max("inflight") == 1.0

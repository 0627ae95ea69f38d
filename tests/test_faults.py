"""Fault-injection (chaos) suite for the resilient serving layer.

Every degraded-mode transition the ISSUE's acceptance demands, driven
deterministically on CPU through :mod:`repro.serving.faults`:
demote-on-compile-failure, demote-on-NaN, watchdog on stuck dispatches,
exponential-backoff re-promotion probes, deadline shedding, bounded
in-flight backpressure, the health state machine, and the headline
guarantee — with faults firing, every non-shed request is served via a
fallback path with ZERO exceptions escaping the serve loop.

All tests carry the ``chaos`` marker: they run in tier-1 and standalone
in CI's dedicated chaos job (``pytest -m chaos``), which is kept out of
the serialized perf-gate job so injected sleeps never pollute the
benchmark calibration window.
"""

import math

import jax
import numpy as np
import pytest

from repro.core import paths
from repro.core.interaction_net import JediNetConfig, forward_sr, init
from repro.serving import (
    DeadlineBatcher,
    FaultInjector,
    InjectedFault,
    ResilientEngine,
    ServingEngine,
    WatchdogTimeout,
)
from repro.serving.faults import StuckBuffer

pytestmark = pytest.mark.chaos


@pytest.fixture(scope="module")
def jedi8():
    cfg = JediNetConfig(n_objects=8, n_features=16)
    params = init(jax.random.PRNGKey(0), cfg, scale="lecun")
    rng = np.random.RandomState(0)
    x = rng.normal(0, 1, (5, 8, 16)).astype(np.float32)
    ref = np.asarray(forward_sr(params, cfg, x))
    return cfg, params, x, ref


def _engine(jedi, injector=None, **kw):
    cfg, params, _, _ = jedi
    kw.setdefault("forward", "fused_full")
    kw.setdefault("interpret", True)
    kw.setdefault("max_batch", 16)
    return ResilientEngine(params, cfg, injector=injector, **kw)


# -- injector unit behavior ----------------------------------------------


def test_injector_times_budget_and_log():
    inj = FaultInjector()
    f = inj.arm("compile", path="p", bucket=8, times=2)
    for _ in range(2):
        with pytest.raises(InjectedFault):
            inj.check("compile", path="p", bucket=8)
    inj.check("compile", path="p", bucket=8)        # budget spent: no raise
    assert not f.armed and f.fired == 2
    assert inj.log == [("compile", "p", 8)] * 2
    assert inj.fired("compile") == 2 and inj.fired("dispatch") == 0


def test_injector_scoping_by_path_and_bucket():
    inj = FaultInjector()
    inj.arm("dispatch", path="a", bucket=16)
    inj.check("dispatch", path="b", bucket=16)      # other path: no fire
    inj.check("dispatch", path="a", bucket=8)       # other bucket: no fire
    with pytest.raises(InjectedFault):
        inj.check("dispatch", path="a", bucket=16)


def test_injector_rejects_unknown_seam():
    with pytest.raises(ValueError):
        FaultInjector().arm("segfault")


def test_injector_input_nan_and_output_nan():
    inj = FaultInjector()
    inj.arm("input_nan", times=1)
    x = np.ones((3, 2), np.float32)
    bad = inj.corrupt_input(x)
    assert np.isnan(bad[0]).all() and np.isfinite(bad[1:]).all()
    assert np.isfinite(x).all()                     # original untouched
    assert inj.corrupt_input(x) is x                # budget spent

    inj.arm("output_nan", times=1)
    out = inj.wrap_output(np.zeros((4, 2), np.float32))
    assert out.shape == (4, 2) and np.isnan(out).all()


def test_stuck_buffer_ready_transition():
    t = [0.0]
    buf = StuckBuffer(np.arange(6.0).reshape(2, 3), ready_at=5.0,
                      clock=lambda: t[0])
    assert not buf.is_ready()
    t[0] = 5.0
    assert buf.is_ready()
    assert np.asarray(buf).shape == (2, 3)
    assert buf.shape == (2, 3)


# -- ServingEngine seams + watchdog --------------------------------------


def test_engine_compile_seam_fires_on_cache_miss_only(jedi8):
    cfg, params, x, ref = jedi8
    inj = FaultInjector()
    inj.arm("compile", path="sr", times=1)
    eng = ServingEngine(params, cfg, forward="sr", max_batch=16,
                        injector=inj)
    with pytest.raises(InjectedFault):
        eng.infer(x)                                 # cold cache: seam fires
    out = eng.infer(x)                               # budget spent: compiles
    assert np.abs(out - ref).max() < 1e-4
    inj.arm("compile", path="sr", times=math.inf)
    out = eng.infer(x)                               # warm cache: cannot fire
    assert np.abs(out - ref).max() < 1e-4
    assert inj.fired("compile") == 1


def test_engine_watchdog_times_out_stuck_dispatch(jedi8):
    cfg, params, x, _ = jedi8
    inj = FaultInjector()
    inj.arm("stuck", times=1, delay_s=60.0)
    eng = ServingEngine(params, cfg, forward="sr", max_batch=16,
                        injector=inj)
    with pytest.raises(WatchdogTimeout):
        eng.infer(x, timeout_s=0.05)
    # next dispatch is clean and still serves
    assert eng.infer(x, timeout_s=5.0).shape == (5, cfg.n_targets)


# -- degradation ladder ---------------------------------------------------


def test_compile_failure_demotes_and_fallback_serves(jedi8):
    cfg, params, x, ref = jedi8
    inj = FaultInjector()
    inj.arm("compile", path="fused_full", times=math.inf)
    eng = _engine(jedi8, inj)
    out = eng.infer(x)
    assert np.abs(out - ref).max() < 1e-4
    h = eng.health()
    assert h["state"] == "degraded"
    (detail,) = h["buckets"].values()
    assert detail["path"] == "sr_split" and detail["demotions"] == 1
    # the failure is kept, not just counted: rung, type and message
    assert detail["last_error"].startswith(
        "fused_full: InjectedFault: injected compile fault")
    assert h["construct_errors"] == {}
    assert eng.metrics.counter("compile_failures") == 1
    assert eng.metrics.counter("demotions") == 1
    assert eng.metrics.counter("fallback_batches") == 1


def test_unconstructible_primary_is_skipped_and_named(jedi8):
    """A primary rung that cannot even be built for the config (int8
    paths compute in fp32 only) is skipped for good; health() keeps its
    error so an operator sees why the base path moved."""
    cfg, params, x, _ = jedi8
    eng = ResilientEngine(params, cfg.with_(compute_dtype="bfloat16"),
                          forward="int8_fused_full", interpret=True,
                          max_batch=8)
    h = eng.health()
    assert h["base_path"] == "fused_full"
    (err,) = h["construct_errors"].values()
    assert err.startswith("int8_fused_full: ValueError:")
    assert "compute dtypes" in err
    assert eng.metrics.counter("construct_failures") == 1
    assert np.isfinite(eng.infer(x)).all()


def test_jedi_linear_full_demotes_to_xla_same_model(jedi8):
    """The jedi-linear ladder's first rung down is the SAME model in
    XLA: a kernel compile failure degrades latency, not predictions."""
    from repro.kernels.jedi_linear import ref as jl_ref

    cfg, params, x, _ = jedi8
    inj = FaultInjector()
    inj.arm("compile", path="jedi_linear_full", times=math.inf)
    eng = _engine(jedi8, inj, forward="jedi_linear_full")
    out = eng.infer(x)
    ref = np.asarray(jl_ref.forward_jedi_linear(params, cfg, x))
    assert np.abs(out - ref).max() < paths.get("jedi_linear").tolerance
    (detail,) = eng.health()["buckets"].values()
    assert detail["path"] == "jedi_linear" and detail["demotions"] == 1


def test_int8_jedi_ladder_walks_two_rungs(jedi8):
    """Both Pallas rungs of the int8 jedi chain failing to compile
    walks the ladder to the XLA rung in a single serve."""
    cfg, params, x, _ = jedi8
    inj = FaultInjector()
    inj.arm("compile", path="int8_jedi_linear_full", times=math.inf)
    inj.arm("compile", path="jedi_linear_full", times=math.inf)
    eng = _engine(jedi8, inj, forward="int8_jedi_linear_full")
    out = eng.infer(x)
    assert np.isfinite(out).all() and out.shape == (5, cfg.n_targets)
    (detail,) = eng.health()["buckets"].values()
    assert detail["path"] == "jedi_linear" and detail["demotions"] == 2
    assert eng.health()["state"] == "degraded"


def test_resilient_chains_match_registry_for_jedi_paths(jedi8):
    """ResilientEngine's ladder is exactly the registry chain, and every
    jedi chain terminates on a non-Pallas rung it can always serve."""
    for name in ("jedi_linear", "jedi_linear_full", "int8_jedi_linear_full"):
        eng = _engine(jedi8, forward=name)
        assert eng.chain == paths.fallback_chain(name)
        assert not paths.get(eng.chain[-1]).pallas


def test_nonfinite_output_demotes_and_reserves(jedi8):
    cfg, params, x, ref = jedi8
    inj = FaultInjector()
    inj.arm("output_nan", path="fused_full", times=1)
    eng = _engine(jedi8, inj)
    out = eng.infer(x)
    assert np.isfinite(out).all()
    assert np.abs(out - ref).max() < 1e-4
    assert eng.metrics.counter("nonfinite_batches") == 1
    assert eng.active_path(eng.bucket_for(5)) == "sr_split"


def test_path_scoped_input_nan_recovers_on_fallback(jedi8):
    """A NaN batch poisoning ONE path (bad scale, DMA flip) must not
    poison the fallback: outputs match the reference after demotion."""
    cfg, params, x, ref = jedi8
    inj = FaultInjector()
    inj.arm("input_nan", path="fused_full", times=math.inf)
    eng = _engine(jedi8, inj)
    out = eng.infer(x)
    assert np.abs(out - ref).max() < 1e-4
    assert eng.metrics.counter("nonfinite_batches") >= 1


def test_stuck_dispatch_watchdog_demotes(jedi8):
    cfg, params, x, ref = jedi8
    inj = FaultInjector()
    inj.arm("stuck", path="fused_full", times=1, delay_s=60.0)
    eng = _engine(jedi8, inj, watchdog_s=0.05)
    out = eng.infer(x)
    assert np.abs(out - ref).max() < 1e-4
    assert eng.metrics.counter("watchdog_timeouts") == 1
    assert eng.health()["state"] == "degraded"


def test_whole_ladder_failure_is_down_not_raise(jedi8):
    cfg, params, x, _ = jedi8
    t = [0.0]
    inj = FaultInjector()
    inj.arm("dispatch", times=math.inf)             # every path, every bucket
    eng = _engine(jedi8, inj, clock=lambda: t[0])
    out = eng.infer(x)                              # must NOT raise
    assert out.shape == (5, cfg.n_targets) and np.isnan(out).all()
    assert eng.health()["state"] == "down"
    assert eng.metrics.counter("failed_requests") == 1
    # faults cleared + probe due: the next serve recovers and clears down
    inj.disarm()
    t[0] = 100.0
    assert np.isfinite(eng.infer(x)).all()
    assert eng.health()["state"] != "down"


# -- re-promotion probes --------------------------------------------------


def test_exponential_backoff_repromotion(jedi8):
    cfg, params, x, ref = jedi8
    t = [0.0]
    inj = FaultInjector(clock=lambda: t[0])
    inj.arm("output_nan", path="fused_full", times=2)
    eng = _engine(jedi8, inj, probe_initial_s=1.0, probe_max_s=8.0,
                  clock=lambda: t[0])
    bucket = eng.bucket_for(5)

    eng.infer(x)                                     # fault 1: demote
    st = eng._bucket_state(bucket)
    assert eng.active_path(bucket) == "sr_split"
    assert st.next_probe == pytest.approx(1.0) and st.backoff_s == 2.0

    t[0] = 0.5
    eng.infer(x)                                     # probe not due yet
    assert eng.metrics.counter("probes") == 0

    t[0] = 1.5
    eng.infer(x)                                     # probe: fault 2 burns it
    assert eng.metrics.counter("probes") == 1
    assert eng.active_path(bucket) == "sr_split"     # still demoted
    assert st.next_probe == pytest.approx(1.5 + 2.0) # backoff doubled
    assert st.backoff_s == 4.0

    t[0] = 4.0
    out = eng.infer(x)                               # probe: budget spent -> ok
    assert np.abs(out - ref).max() < 1e-4
    assert eng.active_path(bucket) == "fused_full"   # re-promoted
    assert eng.metrics.counter("promotions") == 1
    assert st.backoff_s == 1.0                       # backoff reset
    assert eng.health()["state"] == "healthy"


# -- deadline enforcement + shedding -------------------------------------


def test_expired_request_is_shed_never_dispatched(jedi8):
    cfg, params, x, _ = jedi8
    t = [10.0]
    eng = _engine(jedi8, clock=lambda: t[0])
    out = eng.infer(x, deadline=9.0)
    assert out is None
    assert eng.metrics.counter("shed_requests") == 1
    assert eng.metrics.counter("shed_events") == 5
    assert eng.metrics.batches == 0                  # nothing dispatched
    assert eng.health()["state"] == "shedding"
    # shedding decays back to healthy outside the window
    t[0] += eng.shed_window_s + 1
    assert eng.health()["state"] == "healthy"


def test_run_plan_sheds_expired_segments_serves_rest(jedi8):
    cfg, params, _, _ = jedi8
    t = [0.0]
    eng = _engine(jedi8, clock=lambda: t[0])
    bat = DeadlineBatcher(eng.bucket_sizes, deadline_s=1.0,
                          clock=lambda: t[0])
    rng = np.random.RandomState(1)
    xs = {1: rng.normal(0, 1, (2, 8, 16)).astype(np.float32),
          2: rng.normal(0, 1, (3, 8, 16)).astype(np.float32)}
    bat.submit(1, xs[1], deadline_s=0.5)             # will expire
    bat.submit(2, xs[2], deadline_s=60.0)            # plenty of budget
    t[0] = 2.0                                       # rid 1 now expired
    (plan,) = bat.flush()
    res = eng.run_plan(plan)
    assert res[1] is None                            # shed
    ref2 = np.asarray(forward_sr(params, cfg, xs[2]))
    assert np.abs(res[2] - ref2).max() < 1e-4        # served
    assert eng.metrics.counter("shed_events") == 2


def test_run_plan_without_deadlines_serves_everything(jedi8):
    cfg, params, _, _ = jedi8
    eng = _engine(jedi8)
    bat = DeadlineBatcher(eng.bucket_sizes, clock=lambda: 0.0)
    x = np.random.RandomState(2).normal(0, 1, (4, 8, 16)).astype(np.float32)
    bat.submit(7, x)
    (plan,) = bat.flush()
    res = eng.run_plan(plan)
    assert res[7].shape == (4, cfg.n_targets)
    assert eng.metrics.counter("shed_requests") == 0


# -- async path: bounded inflight + realization-time recovery ------------


def test_async_inflight_is_bounded_backpressure(jedi8):
    cfg, params, x, _ = jedi8
    eng = _engine(jedi8, max_inflight=2)
    handles = [eng.infer(x, sync=False) for _ in range(5)]
    assert len(eng._inflight) <= 2                   # queue stayed bounded
    outs = [h.result() for h in handles]
    assert all(o.shape == (5, cfg.n_targets) for o in outs)
    assert len(eng._inflight) == 0


def test_async_realization_recovers_from_stuck(jedi8):
    cfg, params, x, ref = jedi8
    inj = FaultInjector()
    inj.arm("stuck", path="fused_full", times=1, delay_s=60.0)
    eng = _engine(jedi8, inj, watchdog_s=0.05)
    h = eng.infer(x, sync=False)
    out = h.result()                                 # watchdog + fallback
    assert np.abs(out - ref).max() < 1e-4
    assert eng.metrics.counter("watchdog_timeouts") == 1
    assert h.result() is out                         # idempotent


def test_async_dispatch_failure_falls_back_at_dispatch(jedi8):
    cfg, params, x, ref = jedi8
    inj = FaultInjector()
    inj.arm("compile", path="fused_full", times=math.inf)
    eng = _engine(jedi8, inj)
    out = eng.infer(x, sync=False).result()
    assert np.abs(out - ref).max() < 1e-4
    assert eng.metrics.counter("compile_failures") >= 1


# -- the headline guarantee ----------------------------------------------


def test_zero_exceptions_under_rotating_faults(jedi8):
    """ISSUE acceptance: with NaN batches, forced compile failures and
    stuck dispatches injected, every non-shed request is served via a
    fallback with zero raised exceptions, and the shed/demotion/
    re-promotion counts land in metrics."""
    cfg, params, _, _ = jedi8
    rng = np.random.RandomState(3)
    inj = FaultInjector()
    inj.arm("output_nan", path="fused_full", times=2)
    inj.arm("compile", path="fused_full", bucket=16, times=1)
    inj.arm("stuck", path="fused_full", times=1, delay_s=60.0)
    inj.arm("dispatch", path="fused_full", times=1)
    eng = _engine(jedi8, inj, watchdog_s=0.05, probe_initial_s=0.0)

    served = shed = 0
    for i in range(30):
        n = 1 + (i % 11)
        x = rng.normal(0, 1, (n, 8, 16)).astype(np.float32)
        deadline = eng._clock() - 1.0 if i % 10 == 9 else None
        out = eng.infer(x, deadline=deadline)        # must never raise
        if out is None:
            shed += 1
            continue
        served += 1
        ref = np.asarray(forward_sr(params, cfg, x))
        assert out.shape == (n, cfg.n_targets)
        assert np.isfinite(out).all()
        assert np.abs(out - ref).max() < 1e-3, f"request {i}"
    assert served == 27 and shed == 3
    c = eng.metrics.counters
    assert c["shed_requests"] == 3
    assert c["demotions"] >= 1 and c["probes"] >= 1
    assert c.get("promotions", 0) >= 1               # ladder healed itself
    assert inj.fired() >= 4                          # the drills really ran


def test_run_stream_demotes_on_compile_failure(jedi8):
    cfg, params, _, _ = jedi8
    inj = FaultInjector()
    inj.arm("compile", path="fused_full", times=math.inf)
    eng = _engine(jedi8, inj)
    stream = [np.random.RandomState(i).normal(0, 1, (8, 8, 16))
              .astype(np.float32) for i in range(4)]
    res = eng.run_stream(stream, warmup=1)
    assert len(res["latencies"]) == 3                # stream still served
    assert eng.active_path(eng.bucket_for(8)) == "sr_split"
    assert eng.metrics.counter("compile_failures") == 1


# -- health + registry contract ------------------------------------------


def test_health_snapshot_shape(jedi8):
    eng = _engine(jedi8)
    h = eng.health()
    assert h["state"] in ("healthy", "degraded", "shedding", "down")
    assert h["chain"] == ["fused_full", "sr_split"]
    assert h["base_path"] == "fused_full"
    assert isinstance(h["counters"], dict)


def test_resilient_engine_rejects_chain_without_terminal():
    cfg = JediNetConfig(n_objects=8, n_features=16)
    params = init(jax.random.PRNGKey(0), cfg, scale="lecun")
    spec = paths.get("fused_full")
    # a Pallas path whose chain dead-ends in itself must be refused
    paths.register(
        paths.PathSpec(name="_chaos_orphan", forward=spec.forward,
                       ref=spec.ref, fused_level="full", pallas=True),
        overwrite=True)
    try:
        with pytest.raises(ValueError, match="non-Pallas"):
            ResilientEngine(params, cfg, forward="_chaos_orphan",
                            interpret=True, max_batch=8)
    finally:
        paths._REGISTRY.pop("_chaos_orphan", None)


def test_drill_cli_serves_and_reports_health(capsys):
    from repro.launch import trigger_serve
    trigger_serve.main([
        "--forward", "fused_full", "--interpret",
        "--batch", "4", "--batches", "4", "--drill", "output_nan:99"])
    out = capsys.readouterr().out
    assert "DRILL" in out and "served=4" in out and "shed=0" in out
    assert "[health]" in out and "state=degraded" in out
    assert "demotions=1" in out and "nonfinite_batches=" in out


# -- silent seams: the gap, and the sentinel closing it ------------------


def test_silent_seams_invisible_without_sentinel(jedi8):
    """The gap proof: every silent seam strikes (finite, shaped, WRONG
    logits — deviation orders of magnitude past tolerance) yet no PR-6
    detector fires and ``health()`` keeps reading ``healthy``.  This is
    the blind spot :mod:`repro.serving.sentinel` exists for."""
    cfg, params, _, _ = jedi8
    rotation = list(zip(("scale_drift", "weight_corrupt", "stale_cache"),
                        (8, 16, 32)))
    inj = FaultInjector()
    for seam, bucket in rotation:
        inj.arm(seam, path="int8_fused_full", bucket=bucket, factor=8.0)
    eng = _engine(jedi8, inj, forward="int8_fused_full", max_batch=64)
    rng = np.random.RandomState(7)
    worst = 0.0
    for seam, bucket in rotation:
        for _ in range(4):                   # vary inputs: a stale-cache
            n = bucket - 3                   # replay is observably wrong
            x = rng.normal(0, 1, (n, 8, 16)).astype(np.float32)
            out = eng.infer(x)               # never raises
            assert out.shape == (n, cfg.n_targets)
            assert np.isfinite(out).all()
            ref = np.asarray(forward_sr(params, cfg, x))
            worst = max(worst, float(np.abs(out - ref).max()))
    assert worst > 1.0                       # the corruption is real...
    assert inj.fired() == 3                  # ...and every seam struck
    h = eng.health()
    assert h["state"] == "healthy"           # ...and the ladder is blind
    for k in ("compile_failures", "watchdog_timeouts", "nonfinite_batches",
              "dispatch_failures", "demotions", "quarantines"):
        assert k not in h["counters"], k


def test_rotating_silent_seams_detected_quarantined_recovered(jedi8):
    """The acceptance loop: the same rotation WITH the sentinel armed.
    Every silent seam is detected (first canary — one observed batch),
    quarantined, and recovered via clean-canary requalification, with
    zero exceptions and never a ``healthy`` report while the corrupted
    entry could serve."""
    from repro.serving import SentinelConfig

    cfg, params, _, _ = jedi8
    rotation = list(zip(("scale_drift", "weight_corrupt", "stale_cache"),
                        (8, 16, 32)))
    inj = FaultInjector()
    for seam, bucket in rotation:
        inj.arm(seam, path="int8_fused_full", bucket=bucket, times=1,
                factor=8.0)
    eng = _engine(jedi8, inj, forward="int8_fused_full", max_batch=64,
                  sentinel=SentinelConfig(canary_every=3, promote_after=2,
                                          shadow_rate=0.25,
                                          shadow_sync=True))
    rng = np.random.RandomState(11)
    for seam, bucket in rotation:
        n = bucket - 3
        states = []
        for _ in range(14):      # bounded: detect @1, requalify @~7
            x = rng.normal(0, 1, (n, 8, 16)).astype(np.float32)
            served_by = eng.active_path(bucket)   # pre-serve: quarantine
            out = eng.infer(x)               # never raises    # trips AFTER
            assert np.isfinite(out).all()
            if served_by != "int8_fused_full":
                # quarantined: the fp32 fallback serves CORRECT answers
                ref = np.asarray(forward_sr(params, cfg, x))
                assert np.abs(out - ref).max() < 1e-3
            states.append(eng.health()["state"])
        assert states[0] == "quarantined", seam      # 1-batch detection
        assert states[-1] == "healthy", seam         # ...and recovered
        first_ok = states.index("healthy")
        assert all(s == "quarantined" for s in states[:first_ok]), seam
    c = eng.metrics.counters
    assert c["quarantines"] == 3 and c["requalifications"] == 3
    assert c["sentinel_trips"] == 3 and c["canary_mismatches"] == 3
    assert inj.fired() == 3
    assert eng.health()["state"] == "healthy"

"""Serving subsystem: engine equivalence, batcher semantics, metrics,
bucket ladder, and the sharded (8 fake device) path."""

import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.interaction_net import JediNetConfig, forward_sr, init
from repro.kernels.autotune import bucket_ladder, pick_block_b
from repro.serving import DeadlineBatcher, ServingEngine, ServingMetrics

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def jedi30():
    cfg = JediNetConfig(n_objects=30, n_features=16)
    params = init(jax.random.PRNGKey(0), cfg, scale="lecun")
    return cfg, params


@pytest.fixture(scope="module")
def engine30(jedi30):
    cfg, params = jedi30
    return ServingEngine(params, cfg, forward="fused_full", interpret=True,
                         max_batch=32)


# -- engine --------------------------------------------------------------


def test_engine_matches_sr_every_bucket(jedi30, engine30):
    """Acceptance: engine output == forward_sr to <1e-5 in fp32 for every
    bucket size, including non-bucket-aligned request counts (padding)."""
    cfg, params = jedi30
    rng = np.random.RandomState(0)
    for bucket in engine30.bucket_sizes:
        for n in (bucket, max(1, bucket - 3)):     # aligned + padded
            x = rng.normal(0, 1, (n, 30, 16)).astype(np.float32)
            got = engine30.infer(x)
            ref = np.asarray(forward_sr(params, cfg, jnp.asarray(x)))
            assert got.shape == (n, cfg.n_targets)
            assert np.abs(got - ref).max() < 1e-5, f"bucket={bucket} n={n}"


def test_engine_compile_cache_warm(jedi30, engine30):
    cfg, params = jedi30
    engine30.warm()
    n_compiled = engine30.cache_size
    assert n_compiled == len(engine30.bucket_sizes)
    # arbitrary request counts after warm() never add cache entries
    rng = np.random.RandomState(1)
    for n in (1, 5, 9, 17, 31):
        engine30.infer(rng.normal(0, 1, (n, 30, 16)).astype(np.float32))
    assert engine30.cache_size == n_compiled


def test_engine_chunks_oversized_requests(jedi30, engine30):
    cfg, params = jedi30
    top = engine30.bucket_sizes[-1]
    x = np.random.RandomState(2).normal(
        0, 1, (top + 7, 30, 16)).astype(np.float32)
    got = engine30.infer(x)
    ref = np.asarray(forward_sr(params, cfg, jnp.asarray(x)))
    assert got.shape[0] == top + 7
    assert np.abs(got - ref).max() < 1e-5


def test_engine_run_stream_pads_and_counts_valid_events(jedi30):
    cfg, params = jedi30
    eng = ServingEngine(params, cfg, forward="sr", max_batch=32)
    stream = [np.random.RandomState(i).normal(0, 1, (13, 30, 16))
              .astype(np.float32) for i in range(5)]
    res = eng.run_stream(stream, warmup=2)
    assert res["bucket"] == eng.bucket_for(13)
    assert len(res["latencies"]) == 3
    assert res["events"] == 3 * 13            # valid events, not padded rows
    snap = eng.metrics.snapshot()
    assert snap["events"] == 3 * 13
    assert snap["batches"] == 3


def test_engine_rejects_unknown_path(jedi30):
    cfg, params = jedi30
    with pytest.raises(ValueError):
        ServingEngine(params, cfg, forward="nope")


def test_engine_roofline_per_bucket(jedi30, engine30):
    roof = engine30.roofline()
    assert set(roof) == set(engine30.bucket_sizes)
    for b, m in roof.items():
        assert m["fused_level"] == "full"
        assert m["per_event_us"] == pytest.approx(m["step_us"] / b)
    # amortization: per-event cost never increases with bucket size
    # (tolerance for float wobble once the path turns compute-bound)
    per_event = [roof[b]["per_event_us"] for b in sorted(roof)]
    for smaller, larger in zip(per_event, per_event[1:]):
        assert larger <= smaller * (1 + 1e-9)


# -- bucket ladder -------------------------------------------------------


def test_bucket_ladder_covers_and_aligns():
    per_sample = 80_000                        # ~30p full-kernel working set
    for max_batch in (4, 8, 100, 256, 1009):
        ladder = bucket_ladder(max_batch, per_sample)
        assert ladder == sorted(set(ladder))
        assert ladder[-1] >= max_batch         # top rung covers max_batch
        tile = pick_block_b(max_batch, per_sample)
        for b in ladder:
            # every rung is budget-whole (one grid step) or a tile multiple
            assert b <= tile or b % tile == 0, (max_batch, tile, ladder)


def test_bucket_ladder_tiny_batch():
    # the smallest rung is one whole sublane tile
    assert bucket_ladder(1, 80_000) == [8]
    assert bucket_ladder(3, 80_000) == [8]


# -- batcher -------------------------------------------------------------


def test_batcher_flushes_on_full_bucket():
    bat = DeadlineBatcher([8, 16], deadline_s=1.0, clock=lambda: 0.0)
    x = np.zeros((6, 4, 2), np.float32)
    assert bat.submit(0, x, now=0.0) == []
    plans = bat.submit(1, x, now=0.0)          # 12 pending < 16
    assert plans == [] and bat.pending_events == 12
    plans = bat.submit(2, x, now=0.0)          # 18 >= 16: cut a full bucket
    assert len(plans) == 1
    (p,) = plans
    assert p.bucket == 16 and p.n_valid == 16 and p.reason == "full"
    assert [(r[0], r[2] - r[1]) for r in p.requests] == [(0, 6), (1, 6), (2, 4)]
    assert bat.pending_events == 2             # request 2's tail stays queued


def test_batcher_deadline_flush_and_bucket_choice():
    bat = DeadlineBatcher([8, 16], deadline_s=0.010, clock=lambda: 0.0)
    bat.submit(7, np.ones((5, 3), np.float32), now=1.000)
    assert bat.poll(now=1.005) == []           # deadline not reached
    plans = bat.poll(now=1.011)
    assert len(plans) == 1
    (p,) = plans
    assert p.reason == "deadline"
    assert p.bucket == 8                       # smallest rung holding 5
    assert p.n_valid == 5
    assert p.oldest_wait_s == pytest.approx(0.011)
    assert bat.pending_events == 0
    assert bat.poll(now=2.0) == []             # empty queue never flushes


def test_batcher_forced_flush_chunks_backlog():
    bat = DeadlineBatcher([8], deadline_s=10.0, clock=lambda: 0.0)
    bat.submit(0, np.ones((3, 2), np.float32), now=0.0)
    # 12 pending >= bucket 8: submit cuts the full bucket immediately
    plans = bat.submit(1, np.ones((9, 2), np.float32), now=0.0)
    assert [p.n_valid for p in plans] == [8]
    assert plans[0].reason == "full"
    plans += bat.flush(now=0.0)                # remaining 4 forced out
    assert [p.n_valid for p in plans] == [8, 4]
    assert plans[1].reason == "forced"
    # request 1 straddles both plans; segments reassemble to 9 events
    seg_events = sum(stop - start for p in plans
                     for rid, start, stop in p.requests if rid == 1)
    assert seg_events == 9


def test_batcher_run_plan_reassembles_per_request(jedi30, engine30):
    cfg, params = jedi30
    bat = DeadlineBatcher(engine30.bucket_sizes, deadline_s=1.0,
                          clock=lambda: 0.0)
    rng = np.random.RandomState(3)
    xs = {rid: rng.normal(0, 1, (n, 30, 16)).astype(np.float32)
          for rid, n in ((10, 3), (11, 5), (12, 2))}
    for rid, x in xs.items():
        bat.submit(rid, x, now=0.0)
    (plan,) = bat.flush(now=0.0)
    results = engine30.run_plan(plan)
    assert set(results) == set(xs)
    for rid, x in xs.items():
        ref = np.asarray(forward_sr(params, cfg, jnp.asarray(x)))
        assert results[rid].shape == (x.shape[0], cfg.n_targets)
        assert np.abs(results[rid] - ref).max() < 1e-5


def test_batcher_rejects_empty_request():
    bat = DeadlineBatcher([8])
    with pytest.raises(ValueError):
        bat.submit(0, np.zeros((0, 2), np.float32))


def test_batcher_full_bucket_and_deadline_same_tick_flush_once():
    """Race corner: a submission that fills the bucket at the exact tick
    the oldest request's deadline expires must flush exactly once — the
    full-bucket cut wins, and the same-tick poll sees an empty queue
    instead of re-flushing the same events."""
    bat = DeadlineBatcher([8], deadline_s=0.010, clock=lambda: 0.0)
    bat.submit(0, np.ones((4, 2), np.float32), now=1.000)
    # t = 1.010: deadline expired AND this submission reaches 8 events
    plans = bat.submit(1, np.ones((4, 2), np.float32), now=1.010)
    assert [p.n_valid for p in plans] == [8]
    assert plans[0].reason == "full"
    assert bat.pending_events == 0
    assert bat.poll(now=1.010) == []           # nothing left to re-flush
    # every event landed in exactly one plan
    segs = [(rid, stop - start) for p in plans
            for rid, start, stop in p.requests]
    assert segs == [(0, 4), (1, 4)]


def test_batcher_full_cut_tail_keeps_its_own_deadline():
    """When the same-tick cut leaves a tail (the filling request
    straddles the bucket), the tail is NOT double-flushed at that tick —
    it waits on its own submit-time fuse and drains exactly once when
    THAT expires."""
    bat = DeadlineBatcher([8], deadline_s=0.010, clock=lambda: 0.0)
    bat.submit(0, np.ones((4, 2), np.float32), now=1.000)
    plans = bat.submit(1, np.ones((7, 2), np.float32), now=1.010)
    assert [p.n_valid for p in plans] == [8] and bat.pending_events == 3
    assert bat.poll(now=1.010) == []           # tail submitted at 1.010:
    plans += bat.poll(now=1.020)               # its fuse burns at 1.020
    assert [p.n_valid for p in plans] == [8, 3]
    assert plans[1].reason == "deadline"
    assert bat.poll(now=1.020) == []
    assert sum(stop - start for p in plans
               for rid, start, stop in p.requests if rid == 1) == 7


def test_batcher_zero_deadline_flushes_on_first_poll():
    """deadline_s=0 means "never hold a request": the poll at the very
    same tick as the submission flushes it."""
    bat = DeadlineBatcher([8], deadline_s=0.0, clock=lambda: 0.0)
    bat.submit(0, np.ones((2, 2), np.float32), now=5.0)
    (plan,) = bat.poll(now=5.0)
    assert plan.n_valid == 2 and plan.reason == "deadline"
    assert plan.oldest_wait_s == 0.0


def test_batcher_negative_deadline_flushes_immediately():
    """A negative budget (clock skew, already-late request) must behave
    like zero — flush on the next poll, not wedge the queue forever."""
    bat = DeadlineBatcher([8], deadline_s=-1.0, clock=lambda: 0.0)
    bat.submit(0, np.ones((3, 2), np.float32), now=2.0)
    (plan,) = bat.poll(now=2.0)
    assert plan.n_valid == 3 and plan.reason == "deadline"


# -- metrics -------------------------------------------------------------


def test_metrics_snapshot_accounting():
    m = ServingMetrics()
    for lat_ms in (1.0, 2.0, 3.0, 4.0):
        m.record_batch(lat_ms * 1e-3, events=10, bucket=16)
    m.record_wall(0.01, 40)
    snap = m.snapshot()
    assert snap["batches"] == 4 and snap["events"] == 40
    assert snap["p50_us"] == pytest.approx(2500.0)
    assert snap["per_event_p50_us"] == pytest.approx(250.0)
    assert snap["kgps"] == pytest.approx(4.0)   # 40 events / 10 ms
    assert snap["buckets"] == [16]


def test_metrics_empty_snapshot_is_nan_not_crash():
    snap = ServingMetrics().snapshot()
    assert snap["batches"] == 0 and snap["events"] == 0
    assert np.isnan(snap["p50_us"]) and np.isnan(snap["kgps"])
    assert snap["gauges"] == {}


def test_metrics_gauges_replace_and_track_peak():
    m = ServingMetrics()
    m.gauge("queue_depth", 3)
    m.gauge("queue_depth", 7)
    m.gauge("queue_depth", 1)           # gauges REPLACE, unlike counters
    assert m.gauge_value("queue_depth") == 1
    assert m.gauge_max("queue_depth") == 7
    assert m.gauge_value("missing", default=-1.0) == -1.0
    assert m.gauge_max("missing") == 0.0
    m.gauge("inflight", 2)
    snap = m.snapshot()
    assert snap["gauges"] == {"inflight": 2.0, "queue_depth": 1.0}


def test_engine_counts_rows_as_the_kernel_computes_them(jedi30):
    """A 12-row bucket is not a whole number of the kernel's 8-row
    sublane tiles: the kernel computes 16 rows, and rows_kernel says so;
    each bucket's cache miss is one compile."""
    cfg, params = jedi30
    eng = ServingEngine(params, cfg, forward="fused_full", interpret=True,
                        bucket_sizes=[12])
    assert eng.workload.kernel_rows(12) == 16
    x = np.random.RandomState(2).normal(0, 1, (5, 30, 16)).astype(
        np.float32)
    eng.infer(x)
    eng.infer(x)
    m = eng.metrics
    assert m.counter("compiles") == 1
    assert (m.counter("rows_valid"), m.counter("rows_bucket"),
            m.counter("rows_kernel")) == (10, 24, 32)
    eng.infer(x, record=False)                    # canaries, warm-up
    assert m.counter("rows_valid") == 10


def test_engine_rows_kernel_is_the_bucket_without_a_kernel(jedi30):
    cfg, params = jedi30
    eng = ServingEngine(params, cfg, forward="sr_split", bucket_sizes=[12])
    assert eng.workload.kernel_rows(12) == 12


def test_engine_counts_lane_packed_rows():
    """Width-50 f_R (the 50p widths) packs two edges per row: every
    kernel row is a lane-packed row.  A path without a packing kernel
    counts none."""
    cfg = JediNetConfig(n_objects=10, n_features=16, fr_hidden=(50, 50),
                        fo_hidden=(20,), phi_hidden=(20,))
    params = init(jax.random.PRNGKey(0), cfg, scale="lecun")
    x = np.random.RandomState(3).normal(0, 1, (5, 10, 16)).astype(
        np.float32)
    eng = ServingEngine(params, cfg, forward="fused_full", interpret=True,
                        bucket_sizes=[8])
    assert eng.workload.kernel_lane_pack(8) == 2
    eng.infer(x)
    m = eng.metrics
    assert m.counter("rows_lane_packed") == m.counter("rows_kernel") == 8
    plain = ServingEngine(params, cfg, forward="sr_split", bucket_sizes=[8])
    plain.infer(x)
    assert plain.metrics.counter("rows_kernel") == 8
    assert plain.metrics.counter("rows_lane_packed") == 0


def test_run_plan_spans_prep_launch_wait_d2h(jedi30, engine30):
    """ExecutionCore.run_plan: engine.prep runs up to the compiled call,
    engine.launch is the call, and realization is engine.wait then
    engine.d2h; with spans off the same plan records nothing."""
    t = [0]

    def tick():
        t[0] += 1
        return t[0]

    m = ServingMetrics(clock=tick)
    shared, engine30.metrics = engine30.metrics, m
    b = DeadlineBatcher(engine30.bucket_sizes, deadline_s=0.0)
    rng = np.random.RandomState(4)
    b.submit(0, rng.normal(0, 1, (5, 30, 16)).astype(np.float32), now=0.0)
    (plan,) = b.poll(now=1.0)
    try:
        engine30.run_plan(plan)
        assert m.stop_spans().spans == []
        m.start_spans()
        out = engine30.run_plan(plan)
        spans = m.stop_spans().spans
    finally:
        engine30.metrics = shared
    assert out[0].shape == (5, 5)
    order = sorted(spans, key=lambda s: s[1])
    assert [s[0] for s in order] == ["engine.prep", "engine.launch",
                                     "engine.wait", "engine.d2h"]
    assert all(s[4] is None for s in spans)       # no loop above them
    for a, b_ in zip(order, order[1:]):
        assert a[2] < b_[1]                       # one after the other


def test_spans_come_from_the_serve_thread_only():
    """A worker thread (the sentinel's shadow) neither records spans nor
    closes the serve thread's open one."""
    import threading

    m = ServingMetrics()
    m.start_spans()
    prep = m.span("engine.prep")
    seen = []

    def worker():
        seen.append(m.span("engine.launch"))
        m.close_span("engine.prep")

    t = threading.Thread(target=worker)
    t.start()
    t.join(timeout=10)
    assert not t.is_alive()
    with m.span("engine.launch"):
        pass
    prep.close()
    window = m.stop_spans()
    assert seen[0] is m.span("anything")          # the shared no-op span
    assert [s[0] for s in window.spans] == ["engine.launch", "engine.prep"]
    assert window.spans[0][4] == window.spans[1][3]   # launch inside prep


# -- sharded path (subprocess with 8 fake CPU devices) -------------------


def test_engine_shards_batch_axis_over_mesh():
    """Engine shard_maps the batch axis over the host mesh and still
    matches forward_sr — for the XLA path and the fused Pallas path."""
    prog = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            + textwrap.dedent("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.interaction_net import JediNetConfig, init, forward_sr
        from repro.serving import ServingEngine

        cfg = JediNetConfig(n_objects=30, n_features=16)
        params = init(jax.random.PRNGKey(0), cfg, scale="lecun")
        x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (100, 30, 16)))
        ref = np.asarray(forward_sr(params, cfg, jnp.asarray(x)))
        # max_batch=100 does not divide the 8-way mesh: the per-device
        # ladder must round UP so the top bucket still covers it
        for fwd, n, mb in (("sr_split", 100, 100), ("fused_full", 20, 64)):
            eng = ServingEngine(params, cfg, forward=fwd, max_batch=mb)
            assert eng.n_shards == 8, eng.n_shards
            assert all(b % 8 == 0 for b in eng.bucket_sizes)
            assert eng.bucket_sizes[-1] >= mb, eng.bucket_sizes
            err = np.abs(eng.infer(x[:n]) - ref[:n]).max()
            print(fwd.upper() + "_ERR", err)
    """))
    # the child always runs on the CPU: on a machine with a TPU the
    # pytest process may hold the chip
    env = {"PYTHONPATH": "src", "PATH": os.environ.get("PATH", ""),
           "HOME": os.environ.get("HOME", str(REPO)), "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=600, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert float(out.stdout.split("SR_SPLIT_ERR")[1].split()[0]) < 1e-5
    assert float(out.stdout.split("FUSED_FULL_ERR")[1].split()[0]) < 1e-5

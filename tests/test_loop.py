"""Event-loop front-end (serving/loop.py) + LM fabric (serving/lm.py).

Covers the fabric's live-serving contracts: loop-served logits match
direct engine inference, deadline shedding under backlog, backpressure
bounds the in-flight window, out-of-order plan completion delivers to
the right futures, and the LM port's slot-recycling decode reproduces
the pre-refactor ``launch/serve.py`` greedy token streams exactly
(including a single prefill compile across mixed prompt lengths).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.interaction_net import JediNetConfig, init
from repro.serving import (
    LMEngine,
    RequestFuture,
    ResilientEngine,
    ServingLoop,
    ServingMetrics,
)
from repro.serving.lm import prompt_bucket_ladder, tiny_config


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


@pytest.fixture(scope="module")
def jedi8():
    cfg = JediNetConfig(n_objects=8, n_features=4)
    params = init(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def lm_setup():
    from repro.configs.registry import get_arch
    from repro.models import transformer as tfm
    cfg = tiny_config(get_arch("h2o-danube-1.8b").model)
    params = tfm.init(jax.random.PRNGKey(0), cfg)
    return cfg, params, tfm


# -- numerics: loop-served == direct infer ----------------------------------


def test_loop_matches_direct_infer(jedi8):
    cfg, params = jedi8
    eng = ResilientEngine(params, cfg, forward="sr_split",
                          bucket_sizes=[4, 8])
    loop = ServingLoop(eng, deadline_s=1e9, max_inflight=2)
    rng = np.random.default_rng(0)
    xs = [rng.normal(size=(n, 8, 4)).astype(np.float32)
          for n in (3, 5, 2, 8, 1)]
    futs = [loop.submit(x) for x in xs]
    loop.drain()
    assert loop.idle
    for fut, x in zip(futs, xs):
        assert fut.done and not fut.shed
        out = fut.result()
        assert out.shape[0] == x.shape[0]
        np.testing.assert_allclose(out, eng.infer(x), rtol=1e-5, atol=1e-6)
    assert eng.metrics.counter("loop_requests") == len(xs)
    assert eng.metrics.counter("loop_completed") == len(xs)


def test_loop_request_split_across_plans_reassembles(jedi8):
    cfg, params = jedi8
    eng = ResilientEngine(params, cfg, forward="sr_split", bucket_sizes=[4])
    loop = ServingLoop(eng, deadline_s=1e9)
    rng = np.random.default_rng(1)
    # 10 events through a 4-bucket ladder: the request straddles 3 plans
    x = rng.normal(size=(10, 8, 4)).astype(np.float32)
    fut = loop.submit(x)
    loop.drain()
    out = fut.result()
    assert out.shape[0] == 10
    np.testing.assert_allclose(out, eng.infer(x), rtol=1e-5, atol=1e-6)


# -- deadline shedding under backlog ----------------------------------------


def test_loop_sheds_expired_requests_under_backlog(jedi8):
    cfg, params = jedi8
    clk = FakeClock()
    eng = ResilientEngine(params, cfg, forward="sr_split",
                          bucket_sizes=[4, 8], clock=clk)
    loop = ServingLoop(eng, deadline_s=0.5, clock=clk)
    rng = np.random.default_rng(2)
    # backlog: the request waits in the batcher past its serve-by budget
    late = loop.submit(rng.normal(size=(2, 8, 4)).astype(np.float32),
                       deadline_s=1.0)
    clk.t += 10.0                       # backlog delay >> deadline
    loop.poll()                         # fuse fires -> dispatch -> shed
    assert late.done and late.shed
    assert late.result() is None
    assert eng.metrics.counter("shed_requests") == 1
    assert eng.metrics.counter("shed_events") == 2
    # a fresh request still serves (shedding is per-request, not global)
    ok = loop.submit(rng.normal(size=(2, 8, 4)).astype(np.float32),
                     deadline_s=1e9)
    loop.drain()
    assert ok.result() is not None


# -- backpressure + out-of-order delivery (deterministic stub engine) -------


class StubHandle:
    def __init__(self, engine, plan):
        self._engine = engine
        self._plan = plan
        self.ready = False

    def result(self):
        self.ready = True
        self._engine.outstanding.remove(self)
        return {rid: np.full((stop - start, 1), float(rid))
                for rid, start, stop in self._plan.requests}


class StubEngine:
    """Engine-shaped test double: handles complete only when told to."""

    def __init__(self, bucket_sizes=(4,)):
        self.bucket_sizes = sorted(bucket_sizes)
        self.metrics = ServingMetrics()
        self.outstanding: list[StubHandle] = []
        self.max_outstanding = 0

    def run_plan(self, plan, *, sync=True):
        assert not sync
        h = StubHandle(self, plan)
        self.outstanding.append(h)
        self.max_outstanding = max(self.max_outstanding,
                                   len(self.outstanding))
        return h


def test_backpressure_bounds_inflight():
    eng = StubEngine(bucket_sizes=[4])
    loop = ServingLoop(eng, deadline_s=1e9, max_inflight=2)
    for i in range(6):                  # 6 full buckets -> 6 plans
        loop.submit(np.zeros((4, 2), np.float32))
    # the loop realized older plans rather than exceeding the window
    assert eng.max_outstanding <= 2
    assert loop.inflight <= 2
    loop.drain()
    assert loop.idle and not eng.outstanding
    assert eng.metrics.gauge_max("inflight_plans") <= 2


def test_out_of_order_completion_delivers_to_right_futures():
    eng = StubEngine(bucket_sizes=[4])
    loop = ServingLoop(eng, deadline_s=1e9, max_inflight=8)
    futs = [loop.submit(np.zeros((4, 2), np.float32)) for _ in range(3)]
    assert len(eng.outstanding) == 3
    # plan 2 (newest) completes first; plan 0 last
    eng.outstanding[2].ready = True
    loop.poll()
    assert futs[2].done and not futs[0].done and not futs[1].done
    np.testing.assert_array_equal(futs[2].result(),
                                  np.full((4, 1), 2.0))
    eng.outstanding[0].ready = True     # plans 0,1 remain; 0 is oldest
    loop.poll()
    assert futs[0].done and not futs[1].done
    np.testing.assert_array_equal(futs[0].result(), np.full((4, 1), 0.0))
    loop.drain()
    np.testing.assert_array_equal(futs[1].result(), np.full((4, 1), 1.0))


def test_future_result_before_done_raises():
    eng = StubEngine(bucket_sizes=[4])
    loop = ServingLoop(eng, deadline_s=1e9)
    fut = loop.submit(np.zeros((4, 2), np.float32))
    with pytest.raises(RuntimeError, match="in flight"):
        fut.result()
    loop.drain()
    fut.result()


def test_loop_gauges_track_queue_and_inflight():
    eng = StubEngine(bucket_sizes=[8])
    loop = ServingLoop(eng, deadline_s=1e9)
    loop.submit(np.zeros((3, 2), np.float32))   # below the bucket: queued
    assert loop.queue_depth == 3
    assert eng.metrics.gauge_value("queue_depth") == 3
    assert eng.metrics.gauge_value("queue_requests") == 1
    loop.submit(np.zeros((5, 2), np.float32))   # fills the bucket: cut
    assert eng.metrics.gauge_max("queue_depth") == 8
    loop.drain()
    assert eng.metrics.gauge_value("queue_depth") == 0
    assert eng.metrics.gauge_value("inflight_plans") == 0


# -- spans, samples and counters ---------------------------------------------


def _tick_clock():
    """A span clock that moves one ns per reading: every span has a
    strictly ordered start and end."""
    t = [0]

    def clock():
        t[0] += 1
        return t[0]
    return clock


def _served_loop(jedi8, *, max_inflight=4, clock=None):
    cfg, params = jedi8
    metrics = ServingMetrics(clock=clock or _tick_clock())
    eng = ResilientEngine(params, cfg, forward="sr_split",
                          bucket_sizes=[4, 8], metrics=metrics)
    eng.warm()
    return eng, ServingLoop(eng, deadline_s=1e9, max_inflight=max_inflight)


def _requests(n_list, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(n, 8, 4)).astype(np.float32) for n in n_list]


def test_spans_off_record_nothing_and_never_annotate(jedi8, monkeypatch):
    class Refuse:
        def __init__(self, *a, **k):
            raise AssertionError("TraceAnnotation entered with spans off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refuse)
    eng, loop = _served_loop(jedi8, max_inflight=1)
    futs = [loop.submit(x) for x in _requests((3, 5, 8, 2))]
    loop.drain()
    assert all(f.done and not f.shed for f in futs)
    m = eng.metrics
    assert m.span("loop.dispatch", seq=0) is m.span("engine.wait")
    assert not loop._uncut
    window = m.stop_spans()
    assert window.spans == [] and window.samples == {}


def test_each_plan_has_one_dispatch_and_one_realize_tree(jedi8):
    eng, loop = _served_loop(jedi8, max_inflight=1)
    m = eng.metrics
    m.start_spans()
    # the 20-event request cuts two full plans in one call: the second
    # waits on the first (backpressure)
    xs = _requests((3, 5, 20, 2, 7, 1, 4))
    futs = [loop.submit(x) for x in xs]
    loop.drain()
    window = m.stop_spans()
    assert all(f.done for f in futs)
    n_plans = window.counters["loop_plans"]
    by_id = {s[3]: s for s in window.spans}
    names = [s[0] for s in window.spans]
    for name in ("loop.dispatch", "engine.prep", "engine.launch",
                 "loop.realize", "engine.wait", "engine.d2h"):
        assert names.count(name) == n_plans, name
    assert names.count("loop.backpressure") >= 1

    def parent(s):
        return by_id[s[4]] if s[4] is not None else None

    for s in window.spans:
        name, start, end, _, _, attrs = s
        assert start < end
        p = parent(s)
        if name in ("engine.prep", "engine.launch"):
            assert p[0] == "loop.dispatch"
        elif name in ("engine.wait", "engine.d2h"):
            assert p[0] == "loop.realize"
        elif name == "loop.realize":
            assert p is None or p[0] == "loop.backpressure"
        else:
            assert p is None, name
        if p is not None:                         # children nest in time
            assert p[1] < start and end < p[2]
        if name != "loop.backpressure":
            assert "seq" in attrs and (p is None or name == "loop.realize"
                                       or p[5]["seq"] == attrs["seq"])
    for seq in range(n_plans):
        mine = sorted((s for s in window.spans if s[5].get("seq") == seq),
                      key=lambda s: s[1])
        assert [s[0] for s in mine] == [
            "loop.dispatch", "engine.prep", "engine.launch", "loop.realize",
            "engine.wait", "engine.d2h"]
        disp = mine[0][5]
        assert disp["reason"] in ("full", "forced")
        assert disp["n_valid"] <= disp["bucket"]
        prep, launch = mine[1], mine[2]
        assert prep[2] < launch[1]                # prep ends, then launch


def test_row_and_flush_counters_cover_the_window_only(jedi8):
    eng, loop = _served_loop(jedi8)
    m = eng.metrics
    assert m.counter("compiles") >= 2             # warm: one per bucket
    for x in _requests((6, 3)):                   # traffic before the window
        loop.submit(x)
    loop.drain()
    before = m.counter("rows_valid")
    assert before == 9
    m.start_spans()
    xs = _requests((3, 5, 8, 2, 7, 1), seed=3)
    for x in xs:
        loop.submit(x)
    loop.poll()
    loop.drain()
    c = m.stop_spans().counters
    assert c["rows_valid"] == sum(x.shape[0] for x in xs)
    assert (c.get("flush_full", 0) + c.get("flush_deadline", 0)
            + c.get("flush_forced", 0)) == c["loop_plans"]
    assert c["rows_kernel"] >= c["rows_bucket"] >= c["rows_valid"]
    assert "compiles" not in c                    # warm-up is outside
    assert m.counter("rows_valid") == before + c["rows_valid"]


def test_batch_wait_sample_is_submit_to_cut(jedi8):
    cfg, params = jedi8
    clk = FakeClock()
    metrics = ServingMetrics(clock=lambda: int(round(clk.t * 1e9)))
    eng = ResilientEngine(params, cfg, forward="sr_split",
                          bucket_sizes=[4, 8], metrics=metrics, clock=clk)
    loop = ServingLoop(eng, deadline_s=0.5, clock=clk)
    metrics.start_spans()
    a, b, c = _requests((3, 2, 8))
    loop.submit(a)                                # t=0, waits for the fuse
    clk.t = 0.3
    loop.submit(b)
    clk.t = 0.7
    loop.poll()                                   # fuse: a and b cut at 0.7
    loop.submit(c)                                # a full bucket: cut at once
    loop.drain()
    window = metrics.stop_spans()
    assert sorted(window.samples["batch_wait_ns"]) == [0, 400_000_000,
                                                       700_000_000]
    assert window.counters["flush_deadline"] == 1
    assert window.counters["flush_full"] == 1


# -- LM fabric ---------------------------------------------------------------


def test_prompt_bucket_ladder():
    assert prompt_bucket_ladder(64) == [16, 32, 64]
    assert prompt_bucket_ladder(100) == [16, 32, 64, 100]
    assert prompt_bucket_ladder(8) == [8]
    with pytest.raises(ValueError):
        prompt_bucket_ladder(0)


def _reference_serve(tfm, cfg, params, prompts, slots, max_seq, max_new):
    """The pre-refactor launch/serve.py loop, inlined verbatim as the
    golden reference for the fabric port's scheduling + numerics."""

    class R:
        def __init__(self, rid, prompt):
            self.rid, self.prompt, self.out = rid, prompt, []

    queue = [R(i, p) for i, p in enumerate(prompts)]
    done = []
    cache = tfm.init_cache(cfg, slots, max_seq)
    slot_req = [None] * slots
    prefill = jax.jit(lambda p, t: tfm.forward(p, cfg, t, return_cache=True))
    decode = jax.jit(lambda p, c, t: tfm.decode_step(p, cfg, c, t))

    def admit(slot, req):
        nonlocal cache
        logits, _, pc = prefill(params, jnp.asarray(req.prompt[None]))
        t, pl = cache["k"].shape[2], req.prompt.shape[0]
        for key in ("k", "v"):
            upd = jnp.zeros_like(cache[key][:, slot])
            upd = upd.at[:, :pl].set(pc[key][:, 0])
            cache[key] = cache[key].at[:, slot].set(upd)
        sp = jnp.full((t,), -1, jnp.int32).at[:pl].set(jnp.arange(pl))
        cache["slot_pos"] = cache["slot_pos"].at[slot].set(sp)
        cache["pos"] = cache["pos"].at[slot].set(pl)
        req.out.append(int(jnp.argmax(logits[0, -1])))
        slot_req[slot] = req

    while queue or any(slot_req):
        for s in range(slots):
            if slot_req[s] is None and queue:
                admit(s, queue.pop(0))
        toks = jnp.asarray([
            (slot_req[s].out[-1] if slot_req[s] else 0)
            for s in range(slots)], jnp.int32)
        logits, cache = decode(params, cache, toks)
        nxt = np.asarray(jnp.argmax(logits, -1))
        for s in range(slots):
            req = slot_req[s]
            if req is None:
                continue
            req.out.append(int(nxt[s]))
            if len(req.out) >= max_new:
                done.append(req)
                slot_req[s] = None
    return {r.rid: r.out for r in done}


def test_lm_fabric_reproduces_prerefactor_tokens(lm_setup):
    cfg, params, tfm = lm_setup
    slots, max_seq, max_new = 3, 64, 5
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, pl)
               for pl in (7, 13, 9, 16, 5)]
    ref = _reference_serve(tfm, cfg, params, prompts, slots, max_seq,
                           max_new)
    eng = LMEngine(params, cfg, slots=slots, max_seq=max_seq)
    for p in prompts:
        eng.submit(p, max_new)
    report = eng.run()
    got = {r.rid: r.out for r in report["done"]}
    assert got == ref                   # EXACT greedy token streams


def test_lm_single_prefill_compile_across_mixed_lengths(lm_setup):
    cfg, params, tfm = lm_setup
    eng = LMEngine(params, cfg, slots=2, max_seq=64)
    assert eng.bucket_sizes == [16, 32, 64]
    for pl in (3, 7, 11, 16):           # all pad to the 16 rung
        eng.submit(np.arange(pl) % cfg.vocab_size, 2)
    report = eng.run()
    assert report["prefill_compiles"] == 1
    assert eng.metrics.counter("prefills") == 4
    # a longer prompt earns exactly one more rung
    eng.submit(np.arange(20) % cfg.vocab_size, 2)
    eng.run()
    assert sum(1 for k in eng._cache if k[1] != "decode") == 2


def test_lm_deadline_sheds_queued_requests(lm_setup):
    cfg, params, tfm = lm_setup
    clk = FakeClock()
    eng = LMEngine(params, cfg, slots=1, max_seq=32, clock=clk)
    a = eng.submit(np.arange(4), 3)                       # no deadline
    b = eng.submit(np.arange(5), 3, deadline_s=0.5)       # queued behind a
    clk.t += 10.0                       # b expires while a holds the slot
    report = eng.run()
    assert not a.shed and len(a.out) == 3
    assert b.shed and b.out == []
    assert report["shed"] == 1
    assert eng.health()["state"] == "shedding"
    assert eng.metrics.counter("lm_shed_requests") == 1


def test_lm_rejects_oversized_prompt(lm_setup):
    cfg, params, tfm = lm_setup
    eng = LMEngine(params, cfg, slots=1, max_seq=16)
    with pytest.raises(ValueError, match="exceeds max_seq"):
        eng.submit(np.arange(17), 1)


def test_request_future_partial_shed_is_none():
    fut = RequestFuture(0, 4)
    fut._deliver(0, np.zeros((2, 1)))
    assert not fut.done
    fut._deliver_shed(2)
    assert fut.done and fut.shed
    assert fut.result() is None

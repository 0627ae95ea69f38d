"""The reduction from trace and records to metrics.

``data/a_backlog.xplane.pb`` is a profiler trace recorded on one TPU v5e
by a traced run of the ``jedi50p-fused-backlog`` cell over a short
window: 36 calls of the ``fused_full`` kernel, the benchmark's window,
dispatch and realize spans.
"""

import pathlib
import re
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import harness, loadgen, measures  # noqa: E402
from chipbench import proxy as P  # noqa: E402
from chipbench import trace  # noqa: E402
from chipbench.configs import jedinet  # noqa: E402

FIXTURE = pathlib.Path(__file__).resolve().parent / "data" / \
    "a_backlog.xplane.pb"
CFG_50P = {"n_objects": 50, "n_features": 16, "d_e": 8, "d_o": 24,
           "n_targets": 5, "fr_hidden": [50] * 3, "fo_hidden": [50] * 3,
           "phi_hidden": [50] * 3, "compute_dtype": "float32"}
PEAK = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
KERNEL = "fused_forward_full"


def _is_kernel(name):
    return name.startswith("%" + KERNEL) and "custom-call" in name


@pytest.fixture(scope="module")
def tr():
    got = trace.read(str(FIXTURE), 1)
    assert got is not None
    return got


def _run(log=None, plans=(), done=None, t0=0.0, tr=None, chips=1):
    return harness.Run(cfg=CFG_50P, traffic={}, chips=chips, peak=PEAK,
                       work=jedinet, log=log, plans=list(plans),
                       done=done, t0=t0, trace=tr)


def _plans_from_trace(tr):
    """One plan record per kernel call, its bucket the call's rows."""
    rows = [int(re.search(r"custom-call\(f32\[\d+,(\d+),", n).group(1))
            for _, _, n in tr.devices[0].ops if _is_kernel(n)]
    return [[0.0, 0.0, r, r, (), 1.0] for r in rows]


# -- the trace fixture ---------------------------------------------------------

def test_fixture_window_and_planes(tr):
    assert 0.1 < tr.window_s < 2.0
    assert tr.devices[0].name == "/device:TPU:0"
    assert sum(_is_kernel(n) for _, _, n in tr.devices[0].ops) == 36
    names = {n for _, _, n in tr.host_spans}
    assert names == set(trace.HOST_SPANS)


def test_busy_is_a_union_within_the_window(tr):
    busy = tr.busy_s()
    assert 0 < busy <= tr.window_s
    # the kernel alone cannot take more than the busy time
    assert tr.op_seconds(_is_kernel) <= busy + 1e-9
    # doubling every op interval must not double the busy time
    dev = tr.devices[0]
    twice = trace.Trace(tr.window, [trace.Device(dev.name, dev.ops * 2,
                                                 dev.modules)], [])
    assert twice.busy_s() == pytest.approx(busy)


def test_idle_gaps_add_up_to_idle_time(tr):
    gaps = tr.idle_gaps(10)
    per_activity = sum(t for name, t in gaps
                       if not name.startswith("longest_gap:"))
    assert per_activity == pytest.approx(tr.window_s - tr.busy_s(),
                                         rel=1e-6)
    assert all(t >= 0 for _, t in gaps) and len(gaps) <= 10


def test_device_ops_ranked(tr):
    ops = tr.device_ops(10)
    assert ops[0][0].startswith(KERNEL)
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert len(ops) <= 10


def test_shares_bounded_by_100(tr):
    run = _run(plans=_plans_from_trace(tr), tr=tr)
    share, bound = measures.kernel_roofline(run, KERNEL)
    assert 0 < share <= 100 and bound == "compute"
    assert 0 < measures.step_mfu(run) <= 100
    idle = measures.device_idle_share(run)
    assert 0 <= idle <= 100


def test_readers_return_nothing_without_data(tr):
    run = _run(plans=_plans_from_trace(tr), tr=None)
    assert measures.kernel_roofline(run, KERNEL) is None
    assert measures.step_mfu(run) is None
    assert measures.device_idle_share(run) is None
    run = _run(plans=_plans_from_trace(tr), tr=tr)
    assert measures.kernel_roofline(run, "no_such_kernel") is None


def test_metric_readers_found_by_name(tr):
    run = _run(plans=_plans_from_trace(tr), tr=tr)
    for name in ("device_idle_share.backlog", "fused_full_roofline.stream",
                 "step_mfu"):
        got = harness.reader(name).read(run)
        value = got["value"] if isinstance(got, dict) else got
        assert 0 <= value <= 100


# -- metric arithmetic -------------------------------------------------------

def _log(due, submit, jets):
    return loadgen.RequestLog(due=np.asarray(due, float),
                              submit=np.asarray(submit, float),
                              jets=np.asarray(jets), offsets=None,
                              outputs=None)


def test_p99_counts_a_stall_from_due_times():
    """The generator stalls for 50 ms: the requests due in it are sent
    late and served fast.  Timed from when they were due, the stall
    shows in the tail; timed from submit, it would vanish."""
    due = np.arange(1000) * 1e-3
    submit = due.copy()
    stalled = (due >= 0.5) & (due < 0.55)
    submit[stalled] = 0.55
    done = submit + 1e-3
    run = _run(log=_log(due, submit, np.ones(1000, int)), done=done)
    assert measures.latency_ms(run, 99) > 20
    assert np.percentile(done - submit, 99) * 1e3 < 2
    assert measures.gen_lag_p99_ms(run) > 20


def test_events_per_s_over_the_whole_window():
    """1000 jets answered in the first second, 1000 more over the next
    two: the rate is 2000 / 3 s, not the mean of the two stretches."""
    done = np.concatenate([np.linspace(0.001, 1.0, 1000),
                           np.linspace(1.002, 3.0, 1000)])
    run = _run(log=_log(np.zeros(2000), np.zeros(2000),
                        np.ones(2000, int)), done=done)
    assert measures.events_per_s(run) == pytest.approx(2000 / 3.0)


def test_unanswered_requests_are_not_counted_as_served():
    done = np.array([0.5, np.nan, 1.0])
    run = _run(log=_log([0, 0, 0], [0, 0, 0], [10, 10, 10]), done=done)
    assert measures.events_per_s(run) == pytest.approx(20.0)


def test_done_and_queue_times_from_plan_records():
    # request 0 split over plans 0 and 1; request 1 in plan 1 only
    plans = [[0.010, 0.011, 8, 8, (0,), 0.020],
             [0.012, 0.013, 8, 16, (0, 1), 0.030]]
    done = measures.done_times(plans, 3)
    assert done[0] == 0.030 and done[1] == 0.030 and np.isnan(done[2])
    disp = measures.last_dispatch(plans, 2)
    assert list(disp) == [0.012, 0.012]
    run = _run(log=_log([0.0, 0.002], [0.0, 0.002], [12, 4]), plans=plans,
               done=done[:2])
    assert measures.queue_wait_p99_ms(run) == pytest.approx(
        np.percentile([12.0, 10.0], 99))
    assert measures.pad_share(run) == pytest.approx(100 * 8 / 24)
    assert measures.dispatch_us_per_plan(run) == pytest.approx(1000.0)


def test_loop_blocked_share_over_the_window():
    log = _log([0.0, 0.5], [0.0, 0.5], [1, 1])
    log.stalls = [(0.1, 0.1), (0.6, 0.1)]
    run = _run(log=log, done=np.array([0.2, 1.0]))
    assert measures.loop_blocked_share(run) == pytest.approx(20.0)
    log.stalls = []
    assert measures.loop_blocked_share(run) == 0.0


def test_pad_share_bounded():
    full = [[0, 0, 16, 16, (), 1]] * 3
    assert measures.pad_share(_run(plans=full)) == 0
    sparse = [[0, 0, 1, 1024, (), 1]]
    assert 0 < measures.pad_share(_run(plans=sparse)) < 100
    assert measures.pad_share(_run(plans=[])) is None


def test_open_schedule_same_work_for_every_seed():
    traffic = {"loop": "open", "rate_per_s": 5000, "jets": [1, 8]}
    a = loadgen.schedule(traffic, np.random.RandomState(1), 2.0, 4096)
    b = loadgen.schedule(traffic, np.random.RandomState(2), 2.0, 4096)
    assert len(a.jets) == len(b.jets) == 10000
    assert a.jets.sum() == b.jets.sum()
    assert not np.array_equal(a.due, b.due)       # another order
    assert a.due[-1] == pytest.approx(b.due[-1], rel=0.05)
    assert np.bincount(a.jets).tolist() == np.bincount(b.jets).tolist()
    assert (a.offsets + a.jets <= 4096).all()


def test_proxy_records_dispatch_and_realization():
    class Plan:
        n_valid, bucket, requests = 3, 8, ((7, 0, 3),)

    class Handle:
        ready = True

        def result(self):
            return {7: np.zeros((3, 5))}

    class Engine:
        bucket_sizes, metrics, _clock = [8], None, None

        def run_plan(self, plan, sync=False):
            return Handle()

    prox = P.EngineProxy(Engine())
    h = prox.run_plan(Plan(), sync=False)
    assert prox.plans[0][P.T_REALIZED] is None
    h.result()
    h.result()
    rec = prox.plans[0]
    assert rec[P.N_VALID] == 3 and rec[P.BUCKET] == 8 and rec[P.RIDS] == (7,)
    assert rec[P.T_DISPATCH] <= rec[P.T_DISPATCHED] <= rec[P.T_REALIZED]
    assert prox.realized_jets == 3

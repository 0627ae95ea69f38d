"""Knee sweep of an open-loop cell: the highest offered rate the system
sustains, measured once on the chip to fix the cell's ``rate_per_s``.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 5000,10000,...

One process: the system is built and warmed once, then each rate gets a
fresh front end and a window of ``--seconds`` of the cell's traffic mix
at that rate.  Per rate it prints the offered and served jet rates, the
backlog (requests due and not yet answered) one second into the window
and at its close, and the latency percentiles.  The knee is the highest
rate whose backlog did not grow (see ``GROWTH``) and whose served rate
matches the offered one within 2 %.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


#: The backlog "grew" when it rose, between one second in and the close,
#: by more than this share of the requests offered in between: the
#: instantaneous backlog is a few dozen requests that come and go.
GROWTH = 0.01


def backlog_at(t: float, due: np.ndarray, done: np.ndarray) -> int:
    return int(np.sum((due <= t) & ~(done <= t)))


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    from chipbench import harness
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax

    peaks = json.loads((harness.CHIPBENCH / "peaks.json").read_text())
    problem = harness.device_problem(jax.devices(), cell.chips,
                                     peaks["devices"])
    if problem:
        harness.log(f"sweep: {problem}")
        return 1
    harness.use_cache()

    from chipbench import loadgen, measures, proxy as proxy_mod

    cfg, traffic = cell.cfg, dict(cell.traffic)
    work, params, pool, _, _ = harness.materials(cell, args.seed,
                                                 args.seconds)
    system = harness.load_module(harness.CHIPBENCH / "systems"
                                 / f"{cfg['system']}.py")
    engine = system.build(cfg, params, cell.chips)
    s_sched = np.random.SeedSequence(args.seed + 1)
    print(f"# {cell.name} setup_s={time.perf_counter() - t_start:.1f} "
          f"ladder={engine.bucket_sizes}", flush=True)
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        traffic["rate_per_s"] = rate
        sched = loadgen.schedule(
            traffic, np.random.RandomState(np.random.MT19937(s_sched)),
            args.seconds, int(traffic["pool_jets"]))
        prox = proxy_mod.EngineProxy(engine)
        loop = system.front_end(prox)
        t0 = time.perf_counter()
        with harness.GcWatch() as gcw:
            rlog = loadgen.run_open(loop, prox, pool, sched, t0)
        run = harness.Run(cfg=cfg, traffic=traffic, chips=cell.chips,
                          peak={}, work=work, log=rlog, plans=prox.plans,
                          done=measures.done_times(prox.plans,
                                                   len(rlog.due)), t0=t0)
        offered = float(rlog.jets.sum()) / args.seconds
        served = measures.events_per_s(run) or 0.0
        row = {"rate_per_s": rate, "offered_jets_s": offered,
               "served_jets_s": served,
               "backlog_1s": backlog_at(t0 + 1.0, rlog.due, run.done),
               "backlog_close": backlog_at(t0 + args.seconds, rlog.due,
                                           run.done),
               "p50_ms": measures.latency_ms(run, 50),
               "p99_ms": measures.latency_ms(run, 99),
               "gen_lag_p99_ms": measures.gen_lag_p99_ms(run),
               "queue_wait_p99_ms": measures.queue_wait_p99_ms(run),
               "dispatch_us_per_plan": measures.dispatch_us_per_plan(run),
               "gc": gcw.summary(),
               "stalls": [len(rlog.stalls), sum(d for _, d in rlog.stalls),
                          max((d for _, d in rlog.stalls), default=0.0)],
               "plans": len(prox.plans),
               "pad_share": measures.pad_share(run)}
        grew = row["backlog_close"] - row["backlog_1s"]
        row["sustained"] = (grew <= GROWTH * rate * (args.seconds - 1.0)
                            and served >= 0.98 * offered)
        rows.append(row)
        print(json.dumps(row), flush=True)
    ok = [r["rate_per_s"] for r in rows if r["sustained"]]
    print(json.dumps({"knee_rate_per_s": max(ok) if ok else None}))
    return 0


if __name__ == "__main__":
    import pathlib
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    sys.exit(main())

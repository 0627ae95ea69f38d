"""One run of one benchmark cell, driven by ``BENCHMARK.json``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``configs[].file``, a JSON file of
sizes that also names its plain reference module beside it and the
system adapter under ``chipbench/systems/``) and a traffic mix
(``chipbench/traffic/<name>.json``, read by ``loadgen``).  Per-layer
metrics are readers under ``chipbench/metrics/<name>.py``; a metric
split by kind of cell (``step_mfu.stream``) is read by its base
quantity's reader (``step_mfu.py``).  Nothing here names a cell,
configuration, mix or metric.

A run: check the device (a TPU, its kind in ``peaks.json``, as many
chips as the cell asks), make the configuration's weights on the device
from its fixed ``weights_seed`` (so every run after a configuration's
first finds its compiled programs in the cache), build and warm the system (every rung of its bucket ladder), make the
jet pool and the request schedule, then serve the window through the
system's front end.  Set-up is process start to the first due request.
After the window: read the device's peak memory, free the system, and
compare a seeded sample of the answers (the largest request always in
it) with the plain reference at float32 ``HIGHEST``.  The last line of
stdout is the result as one JSON object; the numbers compared, each
beside its limit, are the last lines of stderr.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import glob
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

import numpy as np

CHIPBENCH = pathlib.Path(__file__).resolve().parent
ROOT = CHIPBENCH.parent

#: Share of the answered requests compared with the reference (drawn
#: from the seed; the largest request is always among them).
COMPARE_SHARE = 0.1
#: Jets per reference block (host memory; the reference chunks again).
COMPARE_BLOCK = 16384

#: What a run may count as a chip.
PLATFORM = "tpu"

E2E = ("events_per_s", "p50_latency_ms", "p90_latency_ms", "setup_s")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: pathlib.Path):
    """A module from a file whose name need not be an identifier."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The reader of a per-layer metric: ``metrics/<name>.py``, by the
    base quantity of a metric split by kind of cell (``step_mfu.stream``
    is read by ``step_mfu.py``)."""
    return load_module(CHIPBENCH / "metrics" / f"{metric.split('.')[0]}.py")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: pathlib.Path | None = None) -> Cell:
    bench = json.loads((bench_path or ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (CHIPBENCH / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    layer = [m for m in bench["per_layer"] if _reports(m, name)]
    return Cell(name, int(w["chips"]), cfg, traffic, e2e, layer)


def device_problem(devices, chips: int, peaks: dict) -> str | None:
    """Why these devices cannot run the cell, or ``None``."""
    if not devices or devices[0].platform != PLATFORM:
        found = devices[0].platform if devices else "none"
        return f"needs a {PLATFORM.upper()}, found platform {found!r}"
    if devices[0].device_kind not in peaks:
        return (f"device kind {devices[0].device_kind!r} is not in "
                f"chipbench/peaks.json ({sorted(peaks)})")
    if len(devices) < chips:
        return f"the cell needs {chips} chips, found {len(devices)}"
    return None


class GcWatch:
    """The garbage collector's pauses while it is entered."""

    def __init__(self):
        self.pauses: list[tuple[int, float]] = []   # (generation, s)
        self.starts: list[float] = []
        self._t = 0.0

    def _watch(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))
            self.starts.append(self._t)

    def __enter__(self):
        gc.callbacks.append(self._watch)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._watch)

    def summary(self) -> str:
        full = [t for g, t in self.pauses if g == 2]
        return (f"{len(self.pauses)} collections, "
                f"{sum(t for _, t in self.pauses) * 1e3:.3f} ms in all, "
                f"longest {max((t for _, t in self.pauses), default=0) * 1e3:.3f}"
                f" ms; {len(full)} full, longest "
                f"{max(full, default=0) * 1e3:.3f} ms")


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""
    cfg: dict
    traffic: dict
    chips: int
    peak: dict
    work: object            # the configuration's reference module
    log: object             # loadgen.RequestLog
    plans: list             # proxy plan records
    done: np.ndarray        # per request: answer on the host (nan: never)
    t0: float               # window opened (first due request)
    trace: object = None    # trace.Trace in a traced run


def pick_sample(answered, jets, seed_seq) -> list:
    """The requests to compare: the largest, then a seeded draw of the
    rest, ``COMPARE_SHARE`` of the answered ones in all."""
    if not answered:
        return []
    rng = np.random.RandomState(np.random.MT19937(seed_seq))
    largest = max(answered, key=lambda k: jets[k])
    rest = [int(k) for k in rng.permutation(answered) if k != largest]
    return [largest] + rest[:max(0, int(len(answered) * COMPARE_SHARE) - 1)]


def blocks(take, jets):
    """``take`` cut into runs of at most ``COMPARE_BLOCK`` jets."""
    block, n = [], 0
    for k in take:
        if block and n + jets[k] > COMPARE_BLOCK:
            yield block
            block, n = [], 0
        block.append(k)
        n += int(jets[k])
    if block:
        yield block


def inputs(pool, log, block) -> np.ndarray:
    return np.concatenate([pool[log.offsets[k]:log.offsets[k] + log.jets[k]]
                           for k in block])


def _compare(work, params, pool, log, outputs, take, limit: float):
    """(max |served - reference| over the requests ``take``, their jets,
    how many of them lie above ``limit``)."""
    worst, bad, n_jets = 0.0, 0, 0
    for block in blocks(take, log.jets):
        want = work.reference(params, inputs(pool, log, block))
        pos = 0
        for k in block:
            got, m = outputs[k], int(log.jets[k])
            ok = got.shape == want[pos:pos + m].shape and np.isfinite(
                got).all()
            err = (float(np.max(np.abs(got - want[pos:pos + m]))) if ok
                   else float("inf"))
            worst = max(worst, err)
            bad += err > limit
            pos += m
        n_jets += pos
    return worst, n_jets, bad


def weights(cfg: dict, work):
    """The configuration's weights, the same in every run: the program
    compiles them into its programs, so weights drawn per run would
    recompile the ladder in every run."""
    import jax

    key = jax.random.PRNGKey(int(cfg["weights_seed"]))
    params = jax.jit(lambda k: work.init_params(cfg, k))(key)
    return jax.block_until_ready(params)


def materials(cell: Cell, seed: int, seconds: float):
    """Everything a run needs before the window: the reference module,
    the configuration's weights (one jitted call on the device, from its
    fixed ``weights_seed``), and what the run's seed decides: the jet
    pool, the request schedule and the seed of the answer sample."""
    import jax

    from chipbench import jets as jetgen
    from chipbench import loadgen

    cfg, traffic = cell.cfg, cell.traffic
    work = load_module(CHIPBENCH / "configs" / f"{cfg['reference']}.py")
    s_pool, s_sched, s_sample = np.random.SeedSequence(seed).spawn(3)
    params = weights(cfg, work)
    pool_jets = int(traffic["pool_jets"])
    pool = jetgen.make_jets(np.random.RandomState(np.random.MT19937(s_pool)),
                            pool_jets, cfg["n_objects"], cfg["n_features"])
    sched = loadgen.schedule(
        traffic, np.random.RandomState(np.random.MT19937(s_sched)), seconds,
        pool_jets)
    return work, params, pool, sched, s_sample


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, peak: dict, plant=None) -> dict:
    """Serve one window of ``cell`` and return the result object.
    ``plant`` (tests only) is called with the built engine, to break
    the timed path underneath."""
    import jax

    from chipbench import loadgen, measures, proxy as proxy_mod
    from chipbench import trace as trace_mod

    compiles, jax_events = [], []

    def listener(event, secs, **kw):
        # tracing, lowering, compiling or loading a program: none of it
        # may happen inside the window
        if event.startswith(("/jax/core/compile", "/jax/compilation_cache")):
            jax_events.append((time.perf_counter(), event, secs))
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listener)
    cfg, traffic = cell.cfg, cell.traffic
    stamps = [("start", time.perf_counter())]
    work, params, pool, sched, s_sample = materials(cell, seed, seconds)
    stamps.append(("weights_pool_schedule", time.perf_counter()))
    system = load_module(CHIPBENCH / "systems" / f"{cfg['system']}.py")
    engine = system.build(cfg, params, cell.chips)
    stamps.append(("build_and_warm", time.perf_counter()))
    setup_compiles = len(compiles)
    if plant is not None:
        plant(engine)
    prox = proxy_mod.EngineProxy(engine, trace=trace)
    loop = system.front_end(prox)
    annotate = proxy_mod.annotator(trace)

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the benchmark's spans only
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    t0 = time.perf_counter()
    setup_s = t0 - t_start
    stamps.append(("profiler_start", t0))
    try:
        with GcWatch() as gcw, annotate(trace_mod.WINDOW):
            if traffic["loop"] == "open":
                rlog = loadgen.run_open(loop, prox, pool, sched, t0)
            else:
                rlog = loadgen.run_closed(loop, prox, pool, sched,
                                          int(traffic["clients"]), t0,
                                          seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(listener)
    n_compiles = len(compiles) - setup_compiles
    in_window = [(t - t0, e.rsplit("/", 1)[-1], d) for t, e, d in jax_events
                 if t >= t0]

    devices = jax.devices()[:cell.chips]
    mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
              for d in devices)
    checks, health = system.health_checks(engine, cfg)
    n = len(rlog.outputs)
    outputs = rlog.outputs
    plans = prox.plans
    del loop, prox, engine
    gc.collect()

    tr = None
    if trace:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        tr = trace_mod.read(files[0], cell.chips) if files else None
        shutil.rmtree(trace_dir, ignore_errors=True)

    run = Run(cfg=cfg, traffic=traffic, chips=cell.chips, peak=peak,
              work=work, log=rlog, plans=plans,
              done=measures.done_times(plans, n), t0=t0, trace=tr)

    answered = [k for k in range(n) if outputs[k] is not None]
    limit = float(cfg["limits"]["max_abs_err"])
    take = pick_sample(answered, rlog.jets, s_sample)
    worst, jets_cmp, bad = _compare(work, params, pool, rlog, outputs, take,
                                    limit)
    n_cmp = len(take)
    unanswered = n - len(answered)
    checks = {"max_abs_err": (worst if take else float("inf"), limit),
              "unanswered": (unanswered, 0),
              **{k: (v, 0) for k, v in checks.items()}}
    healthy = all(checks[k][0] <= 0 for k in
                  ("off_path_buckets", "fault_counters", "interpreted_on_tpu"))
    failed = n if not healthy else unanswered + bad
    correct = failed == 0 and all(v <= lim for v, lim in checks.values())

    e2e = {"events_per_s": measures.events_per_s(run),
           "p50_latency_ms": measures.latency_ms(run, 50),
           "p90_latency_ms": measures.latency_ms(run, 90),
           "setup_s": setup_s}
    metrics = {}
    if not trace:
        for m in cell.end_to_end:
            # a metric split by kind of cell ("p50_latency_ms.stream")
            # is computed as its base quantity
            v = e2e.get(m["name"].split(".")[0])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            v = reader(m["name"]).read(run)
            if v is None:
                continue
            entry = dict(v) if isinstance(v, dict) else {"value": v}
            metrics[m["name"]] = {"value": float(entry.pop("value")),
                                  "unit": m["unit"], **entry}

    all_devices = jax.devices()
    device = {"platform": all_devices[0].platform,
              "kind": all_devices[0].device_kind,
              "count": len(all_devices), "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": n, "failed": int(failed),
              "metrics": metrics, "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.device_ops(10),
                               "idle_gaps": tr.idle_gaps(10)}

    log(f"[chipbench] cell={cell.name} seed={seed} seconds={seconds} "
        f"trace={int(trace)} platform={device['platform']} "
        f"kind={device['kind']} count={device['count']}")
    log("[chipbench] setup_s=" + f"{setup_s:.3f} (imports_and_device "
        f"{stamps[0][1] - t_start:.3f}, " + ", ".join(
            f"{name} {t - prev:.3f}" for (_, prev), (name, t)
            in zip(stamps, stamps[1:])) + f"; {setup_compiles} compiles "
        f"{sum(compiles[:setup_compiles]):.3f} s)")
    log(f"[chipbench] requests={n} jets={int(rlog.jets.sum())} "
        f"plans={len(plans)} compiles_in_window={n_compiles} "
        f"jax_compile_events_in_window={len(in_window)} "
        f"compared={n_cmp} requests / {jets_cmp} jets "
        f"memory_peak_bytes={mem}")
    log(f"[chipbench] gc in window: {gcw.summary()}; full at (s after "
        "open) " + str([round(st - t0, 3) for st, (g, _) in
                        zip(gcw.starts, gcw.pauses) if g == 2]))
    if in_window:
        log(f"[chipbench] jax compile events in window (s after open, "
            f"event, s): {in_window[:8]}")
    if rlog.stalls:
        top = sorted(rlog.stalls, key=lambda sd: -sd[1])[:5]
        log(f"[chipbench] generator held > {loadgen.STALL_S * 1e3:g} ms by "
            f"{len(rlog.stalls)} server calls, "
            f"{sum(d for _, d in rlog.stalls) * 1e3:.3f} ms in all; longest "
            "(s after open, ms): " + ", ".join(
                f"({st - t0:.3f}, {d * 1e3:.3f})" for st, d in top))
    p99 = measures.latency_ms(run, 99)
    log("[chipbench] " + " ".join(
        f"{k}={v:.6g}" for k, v in e2e.items() if v is not None)
        + ("" if p99 is None else f" p99_latency_ms={p99:.6g}"))
    log(f"[chipbench] health {json.dumps(health, default=str)}")
    # a number that could not be computed is printed as null (JSON has
    # no infinity); the run is then not correct
    result["checks"] = {k: {"value": v if np.isfinite(v) else None,
                            "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k} = {v:.6g} (limit {lim:.6g})")
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def use_cache() -> None:
    """JAX's persistent compilation cache in a fixed directory inside
    the checkout: only a cell's first run there compiles."""
    import jax

    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def _devices():
    import jax
    return jax.devices()


def main(argv=None, *, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    try:
        cell = load_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        log(f"chipbench: {e}")
        return 2
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        log(f"chipbench: no program at {src / 'repro'}; run from a checkout "
            "of the repository")
        return 2
    sys.path.insert(0, str(src))

    peaks = json.loads((CHIPBENCH / "peaks.json").read_text())["devices"]
    devices = _devices()
    problem = device_problem(devices, cell.chips, peaks)
    if problem:
        log(f"chipbench: {problem}; no result")
        return 1

    use_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=t_start, peak=peaks[devices[0].device_kind])
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0

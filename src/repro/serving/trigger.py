"""Trigger-tier CLI driver: the logic behind ``repro.launch.trigger_serve``.

The launch module is deliberately a THIN shell — argparse plus one call
in here (``tests/test_thin_cli.py`` enforces that with an AST guard) —
so every behavior an operator reaches from the command line lives
inside the serving package:

* :func:`make_stream` — synthetic event stream, fully materialized so
  generation stays off the timed path;
* :func:`serve_ticks` — the driver: each tick submitted to a
  :class:`~repro.serving.loop.ServingLoop`, the loop pumped between
  ticks and drained at the end;
* :func:`run_trigger_cli` — the whole serve flow: registry listing, then
  :func:`serve_ticks` over the fault-tolerant engine (the path the
  benchmark measures), with or without armed fault drills, and the
  report with roofline context and the health view.  Outside a drill
  it returns 1, naming the error, when any bucket ended off the
  requested path: the ladder
  keeps serving, but an operator who asked for a kernel must not be
  handed its fallback silently;
* :func:`print_health` — the health state machine's operator view.

Output formats are part of the CLI contract (tests assert on them);
change them here, not in the launch shell.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from repro.common.compile_cache import setup_compile_cache
from repro.configs.registry import TRIGGER_FAMILIES, archs, get_arch
from repro.core import particle_transformer, paths
from repro.core.interaction_net import init
from repro.data.jets import make_jets
from repro.serving.faults import SILENT_SEAMS, FaultInjector
from repro.serving.loop import ServingLoop
from repro.serving.metrics import ServingMetrics, kgps
from repro.serving.resilient import ResilientEngine
from repro.serving.sentinel import SentinelConfig


def make_stream(rng, n_batches: int, batch: int, n_objects: int,
                n_features: int):
    """Pre-generated synthetic event stream, fully materialized so the
    per-jet numpy generation loop stays OFF the timed serving path — the
    latencies below must measure transfer+compute, not the generator."""
    return [make_jets(rng, batch, n_objects, n_features)[0]
            for _ in range(n_batches)]


def init_weights(cfg, key, *, scale: str = "fan_in"):
    """Seeded weights of a trigger model at ``cfg``'s widths, by its
    family; ``scale`` is JEDI-net's variance-scaling rule."""
    if cfg.family == "part":
        return particle_transformer.init_params(key, cfg)
    return init(key, cfg, scale=scale)


def print_health(engine) -> None:
    """The health state machine's operator view (``--health``)."""
    h = engine.health()
    print(f"[health] state={h['state']} base={h['base_path']} "
          f"chain={'>'.join(h['chain'])} inflight={h['inflight']}")
    for err in h["construct_errors"].values():
        print(f"  construct error: {err}")
    for bucket, st in h["buckets"].items():
        probe = ("-" if st["next_probe_in_s"] is None
                 else f"{st['next_probe_in_s']:.2f}s")
        quarantine = ""
        if st.get("quarantined"):
            quarantine = (f" QUARANTINED[{st['quarantined_path']}] "
                          f"clean_canaries={st['clean_canaries']}")
        print(f"  bucket {bucket:>5}: path={st['path']} level={st['level']} "
              f"demotions={st['demotions']} next_probe_in={probe}"
              f"{quarantine}{' DOWN' if st['down'] else ''}")
        if st["last_error"]:
            print(f"    last error: {st['last_error']}")
    if h.get("sentinel"):
        s = h["sentinel"]
        print(f"  sentinel: canary_every={s['canary_every']} "
              f"shadow_rate={s['shadow_rate']:g} "
              f"promote_after={s['promote_after']}")
    if h["counters"]:
        print("  counters: " + " ".join(f"{k}={v}"
                                        for k, v in h["counters"].items()))
    else:
        print("  counters: (none)")
    if h.get("gauges"):
        print("  gauges:   " + " ".join(f"{k}={v:g}"
                                        for k, v in h["gauges"].items()))


def parse_drills(specs, injector, path) -> None:
    """Arm ``SEAM[:TIMES[:MAGNITUDE]]`` drill specs against ``path``.

    The third field is seam-dependent: a delay in seconds for the timed
    loud seams (``latency``, ``stuck``), a corruption factor for the
    silent seams (``scale_drift``, ``weight_corrupt``)."""
    for spec in specs:
        parts = spec.split(":")
        seam = parts[0]
        times = float(parts[1]) if len(parts) > 1 else 1.0
        if seam in SILENT_SEAMS:
            factor = float(parts[2]) if len(parts) > 2 else 4.0
            injector.arm(seam, path=path, times=times, factor=factor)
        else:
            delay = float(parts[2]) if len(parts) > 2 else 0.05
            injector.arm(seam, path=path, times=times, delay_s=delay)


def build_trigger_cli(ap) -> None:
    """Install the trigger-serve arguments on an ``argparse`` parser."""
    ap.add_argument("--arch", default="jedinet-30p",
                    choices=archs(*TRIGGER_FAMILIES),
                    help="jet tagger at its published widths "
                         "(configs/registry.py); pair it with a --forward "
                         "path of its family (--list-paths)")
    ap.add_argument("--batch", type=int, default=256,
                    help="events per stream tick (the trigger's time slice)")
    ap.add_argument("--batches", type=int, default=40)
    ap.add_argument("--warmup", type=int, default=2,
                    help="ticks served first and left out of the report "
                         "(a --drill reports every tick)")
    ap.add_argument("--forward", default="fused_full",
                    choices=paths.available())
    ap.add_argument("--compute-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--interpret", action="store_true",
                    help="force Pallas interpret mode (auto-enabled off-TPU)")
    ap.add_argument("--list-paths", action="store_true",
                    help="print the forward-path registry and exit")
    ap.add_argument("--health", action="store_true",
                    help="print the engine health report after the run")
    ap.add_argument("--drill", action="append", default=None,
                    metavar="SEAM[:TIMES[:MAGNITUDE]]",
                    help="arm a fault against the primary path (repeatable); "
                         "the report covers every tick. "
                         "Loud seams: compile, dispatch, input_nan, "
                         "output_nan, latency, stuck (MAGNITUDE = delay "
                         "seconds).  Silent seams: scale_drift, "
                         "weight_corrupt, stale_cache (MAGNITUDE = "
                         "corruption factor) — pair them with --sentinel "
                         "or they serve wrong answers undetected")
    ap.add_argument("--sentinel", action="store_true",
                    help="arm the silent-corruption sentinel: golden "
                         "canaries, terminal-rung shadow re-execution, "
                         "canary-gated quarantine (see --health)")
    ap.add_argument("--shadow-rate", type=float, default=1 / 16,
                    help="sentinel shadow re-execution duty cycle "
                         "(fraction of live requests; 0 disables shadows)")
    ap.add_argument("--canary-every", type=int, default=16,
                    help="sentinel canary cadence in requests per bucket")
    ap.add_argument("--watchdog-s", type=float, default=30.0,
                    help="stuck-dispatch watchdog budget")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-tick serve-by budget; the engine sheds an "
                         "expired tick instead of dispatching it")
    ap.add_argument("--seed", type=int, default=0)


def serve_ticks(loop, ticks, deadline_s=None) -> list:
    """Submit each tick, pump the loop between ticks, drain at the end;
    returns the ticks' futures, every one of them done."""
    futures = []
    for tick in ticks:
        futures.append(loop.submit(tick, deadline_s=deadline_s))
        loop.poll()
    loop.drain()
    return futures


def _off_path(engine, forward: str) -> list[str]:
    """One error line per bucket that ended off ``forward`` (the rule of
    the benchmark's health checks: ``level != 0`` or another path)."""
    h = engine.health()
    off = []
    for bucket, st in h["buckets"].items():
        if st["level"] != 0 or st["path"] != forward:
            error = next((e for e in (*h["construct_errors"].values(),
                                      st["last_error"]) if e), "none kept")
            off.append(f"bucket {bucket} was served by {st['path']!r}, not "
                       f"the requested path {forward!r}; error: {error}")
    return off


def run_trigger_cli(args) -> int:
    """Serve a synthetic stream per parsed ``args`` through a
    :class:`~repro.serving.loop.ServingLoop` (the path the benchmark
    measures) and print the report.  Returns the process exit code."""
    cfg = get_arch(args.arch).model.with_(compute_dtype=args.compute_dtype)
    params = init_weights(cfg, jax.random.PRNGKey(args.seed))
    if args.list_paths:
        # Registry table PLUS each path's resolved bucket policy (per-
        # sample VMEM model, weight residency, the ladder it earns) for
        # this CLI's config — the operator-facing answer to "why does
        # the quantized path get deeper buckets than fp32?".
        print(paths.describe(cfg=cfg, params=params,
                             max_batch=max(args.batch, 1)))
        return 0

    setup_compile_cache()
    injector = None
    if args.drill:
        injector = FaultInjector()
        parse_drills(args.drill, injector, args.forward)
    sentinel = None
    if getattr(args, "sentinel", False):
        # sync shadows: the CLI's verdict (quarantines= in --health)
        # must be complete when the run prints, not racing a worker
        sentinel = SentinelConfig(canary_every=args.canary_every,
                                  shadow_rate=args.shadow_rate,
                                  shadow_sync=True)
    engine = ResilientEngine(params, cfg, forward=args.forward,
                             interpret=args.interpret or None,
                             max_batch=max(args.batch, 1),
                             injector=injector,
                             watchdog_s=args.watchdog_s,
                             sentinel=sentinel)

    print(f"[trigger_serve] arch={args.arch} platform={engine.platform} "
          f"interpret={engine.interpret} devices={engine.n_shards}")
    rng = np.random.RandomState(args.seed)
    stream = make_stream(rng, args.batches, args.batch, cfg.n_objects,
                         cfg.n_features)
    deadline_s = None if args.deadline_ms is None else args.deadline_ms * 1e-3

    # warm-up ticks compile and are left out of the report; a drill
    # reports every tick, since a fault may strike a warm-up tick
    warmup = 0 if args.drill else min(args.warmup, len(stream))
    serve_ticks(ServingLoop(engine), stream[:warmup])
    engine.metrics = ServingMetrics()
    t0 = time.perf_counter()
    futures = serve_ticks(ServingLoop(engine), stream[warmup:], deadline_s)
    wall = time.perf_counter() - t0
    shed = sum(f.shed for f in futures)
    events = sum(f.n_events for f in futures if not f.shed)
    snap = engine.metrics.snapshot()

    off = [] if args.drill else _off_path(engine, args.forward)
    for line in off:
        print(f"[trigger_serve] FAILED: {line}")
    if off:
        print_health(engine)
        return 1
    if args.drill:
        print(f"[trigger_serve] DRILL faults={','.join(args.drill)} "
              "(every tick reported)")
    if not futures:
        print("[trigger_serve] stream too short for stats "
              f"(need > warmup={warmup} batches, got {args.batches})")
        if args.health:
            print_health(engine)
        return 0

    buckets = snap["buckets"]
    print(f"[trigger_serve] forward={args.forward} "
          f"n_objects={cfg.n_objects} batch={args.batch} "
          f"buckets={','.join(map(str, buckets)) or '-'} "
          f"dtype={args.compute_dtype} shards={engine.n_shards}")
    print(f"  ticks      {len(futures)} after {warmup} warm-up: "
          f"served={len(futures) - shed} shed={shed}")
    print(f"  sustained  {kgps(events, wall):8.1f} KGPS  "
          f"({events} events / {wall:.3f} s)")
    print(f"  latency    p50 {snap['p50_us']:8.1f} us   "
          f"p99 {snap['p99_us']:8.1f} us  per batch")
    print(f"  per-event  p50 {snap['per_event_p50_us']:8.3f} us")
    if buckets:
        model = engine.roofline([buckets[-1]])[buckets[-1]]
        print(f"  roofline   modeled {model['step_us']:.1f} us/step at "
              f"bucket {buckets[-1]} ({model['bound']}-bound, "
              f"{model['hbm_bytes'] / 1e6:.2f} MB HBM, "
              f"level={model['fused_level']})")
    served_by = sorted({st["path"]
                        for st in engine.health()["buckets"].values()})
    print(f"  serving    path={','.join(served_by) or '-'} "
          f"(chain {'>'.join(engine.chain)})")
    if args.health or args.drill:
        print_health(engine)
    return 0

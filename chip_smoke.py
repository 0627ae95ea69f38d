"""Bring-up check: the trigger serving path end to end on a TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --only part-jetclass:part   # one combination
    python3 chip_smoke.py --chips 4   # the data-parallel engine on four chips

One chip: for each forward path and jet-tagging model below, at the
model's published widths with seeded random weights, build a
``ResilientEngine`` (``interpret=False``, no mesh, sentinel on), warm
every bucket of its ladder (printing the compile seconds of each and the
device's peak memory after it), push
a few hundred seeded requests through ``ServingLoop``, check every
request's logits against the path's fp32 reference within the path's
tolerance, and check the engine's health: every bucket still on the
requested path, and no demotion, failure, quarantine or sentinel trip.

Four chips: serve the same seeded requests through a ``ServingEngine``
sharded over all four chips and through a one-chip engine on device 0;
the logits must agree and the sharded output must span the four chips.

The script needs a TPU: on any other platform, or without the ``src/``
tree of this repository beside it, it exits non-zero and prints no
result.  It prints no speed.  Its last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

SEED = 0
N_REQUESTS = 300          # 1..64 events each
N_OVERSIZE = 3            # requests larger than the top bucket
REF_CHUNK = 256           # reference batch (bounds the O(N^2) oracle)

#: (model, forward path) pairs served on one chip.
ONE_CHIP = (
    ("jedinet-50p", "fused_full"),
    ("jedinet-50p", "jedi_linear_full"),
    ("jedinet-50p", "int8_fused_full"),
    ("jedinet-50p", "int8_jedi_linear_full"),
    ("jedinet-30p", "fused_full"),
    ("part-jetclass", "part"),
)

#: Health counters that must stay 0 on a clean run.
ZERO_COUNTERS = ("demotions", "compile_failures", "construct_failures",
                 "dispatch_failures", "nonfinite_batches",
                 "watchdog_timeouts", "quarantines", "sentinel_trips")


def log(msg: str) -> None:
    print(msg, flush=True)


def requests(cfg, top_bucket: int, n_requests: int, n_oversize: int):
    """Seeded request inputs: mostly 1..64 events, a few past the top
    bucket (chunked through it by the engine)."""
    import numpy as np

    from repro.data.jets import make_jets

    rng = np.random.RandomState(SEED)
    sizes = list(rng.randint(1, 65, size=n_requests))
    sizes += [top_bucket + 1 + 37 * i for i in range(n_oversize)]
    x, _ = make_jets(rng, sum(sizes), cfg.n_objects, cfg.n_features)
    return np.split(x, np.cumsum(sizes)[:-1])


def reference(spec, params, cfg, x):
    """The path's own oracle (``spec.ref``) at full fp32 matmul
    precision, in fixed-size chunks."""
    import jax
    import numpy as np

    def ref(p, xb):
        with jax.default_matmul_precision("highest"):
            return spec.ref(p, cfg, xb)

    fn = jax.jit(ref)
    out = []
    for i in range(0, x.shape[0], REF_CHUNK):
        xb = x[i:i + REF_CHUNK]
        pad = np.zeros((REF_CHUNK - xb.shape[0], *xb.shape[1:]), xb.dtype)
        out.append(np.asarray(fn(params, np.concatenate([xb, pad])))
                   [:xb.shape[0]])
    return np.concatenate(out)


def planned_memory(cfg, params, path: str, buckets) -> list[str]:
    """For a path whose ladder is sized against HBM: the chip compiler's
    plan of each rung's step (``Compiled.memory_analysis``: arguments,
    output, temporaries), beside the path's model of a jet's working set
    and its HBM budget, which the whole plan must fit."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import paths
    from repro.serving.engine import TriggerWorkload

    spec = paths.get(path)
    per_jet = spec.bucket_bytes(cfg, params)
    work = TriggerWorkload(params, cfg, forward=path, interpret=False,
                           mesh=None)
    fails = []
    for b in buckets:
        step = work.build(b)
        fn, args = ((step.func, step.args)
                    if isinstance(step, functools.partial) else (step, ()))
        x = jax.ShapeDtypeStruct((b, cfg.n_objects, cfg.n_features),
                                 jnp.float32)
        m = fn.lower(*args, x).compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes)
        log(f"  bucket {b:>5} planned: arguments="
            f"{m.argument_size_in_bytes} output={m.output_size_in_bytes} "
            f"temp={m.temp_size_in_bytes} code="
            f"{m.generated_code_size_in_bytes} temp/jet="
            f"{m.temp_size_in_bytes / b:.0f} model/jet={per_jet}")
        if total > spec.memory_budget_bytes:
            fails.append(f"{path} bucket {b}: the compiled step plans "
                         f"{total} bytes, over the {spec.memory_budget_bytes}"
                         " the ladder was sized for")
    return fails


def serve_one_chip(arch: str, path: str) -> tuple[list[str], float]:
    """One (model, path) combination; returns (failures, setup seconds)."""
    import jax
    import numpy as np

    from repro.configs.registry import get_arch
    from repro.core import paths
    from repro.serving import ResilientEngine, ServingLoop
    from repro.serving.sentinel import SentinelConfig
    from repro.serving.trigger import init_weights

    fails = []
    cfg = get_arch(arch).model
    spec = paths.get(path)
    params = init_weights(cfg, jax.random.PRNGKey(SEED), scale="lecun")
    t0 = time.perf_counter()
    engine = ResilientEngine(params, cfg, forward=path, interpret=False,
                             mesh=None,
                             sentinel=SentinelConfig(shadow_sync=True))
    log(f"[chip_smoke] {arch} {path}: interpret={engine.interpret} "
        f"platform={engine.platform} ladder={engine.bucket_sizes}")
    dev = jax.devices()[0]
    for b in engine.bucket_sizes:
        tb = time.perf_counter()
        engine.warm([b])
        stats = dev.memory_stats() or {}
        log(f"  bucket {b:>5} compile_s={time.perf_counter() - tb:.3f} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"largest_alloc_size={stats.get('largest_alloc_size')}")
    setup_s = time.perf_counter() - t0
    if spec.memory_budget_bytes is not None:
        fails += planned_memory(cfg, params, path, engine.bucket_sizes)
    log(f"  setup_s={setup_s:.3f}")
    if engine.interpret:
        fails.append(f"{arch} {path}: engine runs in interpret mode")

    reqs = requests(cfg, engine.bucket_sizes[-1], N_REQUESTS, N_OVERSIZE)
    loop = ServingLoop(engine)
    futures = [loop.submit(x) for x in reqs]
    loop.drain()
    engine.sentinel.drain()
    outs = [f.result() for f in futures]
    if any(o is None for o in outs):
        fails.append(f"{arch} {path}: a request was shed")
    else:
        want = reference(spec, spec.prepare_params(params), cfg,
                         np.concatenate(reqs))
        got = np.concatenate(outs)
        err = float(np.max(np.abs(got - want)))
        log(f"  requests={len(reqs)} events={got.shape[0]} "
            f"max_abs_err={err:.3e} tolerance={spec.tolerance:.0e}")
        if not (np.isfinite(got).all() and err <= spec.tolerance):
            fails.append(f"{arch} {path}: max |err| {err:.3e} > "
                         f"{spec.tolerance:.0e}")

    h = engine.health()
    counters = {k: h["counters"].get(k, 0) for k in ZERO_COUNTERS}
    off = {b: st for b, st in h["buckets"].items()
           if st["level"] != 0 or st["path"] != path}
    log(f"  health state={h['state']} buckets={len(h['buckets'])} "
        f"off_path={sorted(off)} "
        + " ".join(f"{k}={v}" for k, v in counters.items()))
    for err in h["construct_errors"].values():
        fails.append(f"{arch} {path}: construct error: {err}")
    for b, st in off.items():
        fails.append(f"{arch} {path}: bucket {b} on {st['path']}: "
                     f"{st['last_error']}")
    fails += [f"{arch} {path}: {k}={v}" for k, v in counters.items() if v]
    engine.sentinel.close()
    return fails, setup_s


def serve_four_chips() -> list[str]:
    """The data-parallel engine over four chips vs one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_arch
    from repro.core import paths
    from repro.core.interaction_net import init
    from repro.launch.mesh import make_host_mesh
    from repro.serving import ServingEngine

    arch, path, buckets = "jedinet-50p", "fused_full", [32, 256]
    cfg = get_arch(arch).model
    params = init(jax.random.PRNGKey(SEED), cfg, scale="lecun")
    sharded = ServingEngine(params, cfg, forward=path, interpret=False,
                            mesh=make_host_mesh(), bucket_sizes=buckets)
    single = ServingEngine(params, cfg, forward=path, interpret=False,
                           mesh=None, bucket_sizes=buckets)
    log(f"[chip_smoke] {arch} {path}: buckets={buckets} "
        f"shards={sharded.n_shards} vs 1")
    fails = []
    if sharded.n_shards != 4:
        fails.append(f"the host mesh has {sharded.n_shards} devices, not 4")
    for eng in (sharded, single):
        tb = time.perf_counter()
        eng.warm()
        log(f"  {eng.n_shards} chip(s) warm compile_s="
            f"{time.perf_counter() - tb:.3f}")
    reqs = requests(cfg, buckets[-1], 64, 1)
    got = np.concatenate([sharded.infer(x) for x in reqs])
    want = np.concatenate([single.infer(x) for x in reqs])
    err = float(np.max(np.abs(got - want)))
    tol = paths.get(path).tolerance
    log(f"  events={got.shape[0]} max_abs_err(sharded - one chip)="
        f"{err:.3e} tolerance={tol:.0e}")
    if not (np.isfinite(got).all() and err <= tol):
        fails.append(f"sharded vs one-chip logits differ by {err:.3e}")
    x = jnp.asarray(np.zeros((buckets[-1], cfg.n_objects, cfg.n_features),
                             np.float32))
    out = sharded.compiled_for(buckets[-1])(x)
    spread = len(out.sharding.device_set)
    log(f"  output sharding spans {spread} devices: {out.sharding}")
    if spread != 4:
        fails.append(f"sharded output spans {spread} devices, not 4")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip data-parallel phase")
    ap.add_argument("--only", action="append", metavar="MODEL:PATH",
                    help="one chip: serve only these combinations "
                         "(repeatable; default: all of them)")
    args = ap.parse_args(argv)

    src = pathlib.Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        log(f"chip_smoke: no repro package at {src}; run this script from "
            "a checkout of the repository")
        return 2
    sys.path.insert(0, str(src))

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"chip_smoke: needs a TPU, found platform {dev.platform!r}")
        return 1
    log(f"[chip_smoke] device platform={dev.platform} "
        f"kind={dev.device_kind} count={len(devices)}")
    if len(devices) < args.chips:
        log(f"chip_smoke: --chips {args.chips} needs {args.chips} devices")
        return 1

    from repro.common.compile_cache import setup_compile_cache
    log(f"[chip_smoke] compile cache: {setup_compile_cache()}")

    t0 = time.perf_counter()
    if args.chips == 4:
        fails = serve_four_chips()
    else:
        fails, setup = [], 0.0
        combos = ONE_CHIP if not args.only else [
            tuple(c.split(":", 1)) for c in args.only]
        for arch, path in combos:
            f, s = serve_one_chip(arch, path)
            fails += f
            setup += s
        log(f"[chip_smoke] total setup_s={setup:.3f}")
    log(f"[chip_smoke] wall_s={time.perf_counter() - t0:.3f}")
    for f in fails:
        log(f"FAIL {f}")
    if fails:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

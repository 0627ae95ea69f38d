"""Shared benchmark utilities."""

from __future__ import annotations

import time

import jax
import numpy as np

# Forward-path selection for path-parametrized benchmarks, set by
# ``benchmarks.run --paths``: None = each module's default subset,
# ["all"] = every JEDI-net path, anything else = explicit names.
PATH_FILTER: list[str] | None = None


def select_paths(default=None) -> list[str]:
    """Resolve the benchmark's path list against the registry.

    ``default`` is the module's own subset (None = every JEDI-net path,
    the model the benchmark modules measure); the ``--paths`` CLI
    filter overrides it.  Names are validated
    through ``paths.get`` so a typo fails loudly, not by measuring
    nothing.
    """
    from repro.core import paths
    if PATH_FILTER is None:
        names = (list(default) if default is not None
                 else paths.available(model="jedinet"))
    elif PATH_FILTER == ["all"]:
        names = paths.available(model="jedinet")
    else:
        names = list(PATH_FILTER)
    for n in names:
        paths.get(n)
    return names


def calibration_us(iters: int = 12) -> float:
    """Median wall time of a fixed jitted XLA workload (microseconds).

    A machine-speed yardstick stamped into every BENCH_*.json payload:
    the regression gate divides fresh wall-clocks by the fresh/baseline
    calibration ratio, normalizing away global runner-speed differences
    (CI hardware generations, CPU throttling) while per-path regressions
    — which move relative to the yardstick — still trip the gate.  The
    workload is a jitted matmul so the yardstick exercises the same XLA
    runtime/threadpool the benchmarks do, not just BLAS.
    """
    import jax.numpy as jnp
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.normal(0, 1, (512, 512)).astype(np.float32))
    fn = jax.jit(lambda x: x @ x + x)
    return time_fn(fn, a, warmup=3, iters=iters)


def time_fn(fn, *args, warmup: int = 2, iters: int = 10) -> float:
    """Median wall-time per call in microseconds (blocks on async)."""
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def row(name: str, us: float, derived: str = "") -> dict:
    return {"name": name, "us_per_call": us, "derived": derived}


def print_rows(rows):
    print("name,us_per_call,derived")
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.2f},{r['derived']}")

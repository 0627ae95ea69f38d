"""The control of the comparison that decides ``correct``, on the chip.

    python3 chipbench/control.py --workload <cell> --seeds <a,b,c> --seconds <s>

For each seed, one whole run of the cell as ``run.py`` makes it (the
same weights, jet pool, schedule, window, answer sample and
comparison), with the configuration's own path replaced, at every rung
of the engine's ladder, by the plain reference computed at the nearest
precision below the configuration's (``--precision high``: three
bfloat16 passes on a TPU).  Each seed prints one JSON line with the
run's ``correct`` and the numbers it compared; a sound limit makes
``correct`` false on every seed.  The benchmark's own runs do not run
this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time


def reference_in_place(cfg: dict, work, precision: str):
    """A ``plant`` for ``harness.run_cell``: every compiled bucket of
    the engine's own rung becomes the plain reference at ``precision``
    on the configuration's weights, compiled and run once before the
    window opens."""
    import jax
    import jax.numpy as jnp

    from chipbench import harness

    params = harness.weights(cfg, work)
    fwd = jax.jit(lambda x: work.forward(params, x, work.DOTS[precision]))

    def plant(engine):
        rung = engine._engines[0]
        for key in list(rung._cache):
            bucket = key[1]
            jax.block_until_ready(fwd(jnp.zeros(
                (bucket, cfg["n_objects"], cfg["n_features"]), jnp.float32)))
            rung._cache[key] = fwd

    return plant


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--precision", default="high")
    args = ap.parse_args(argv)

    from chipbench import harness
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    import jax

    peaks = json.loads((harness.CHIPBENCH / "peaks.json").read_text())
    devices = jax.devices()
    problem = harness.device_problem(devices, cell.chips, peaks["devices"])
    if problem:
        harness.log(f"control: {problem}")
        return 1
    harness.use_cache()
    work = harness.load_module(harness.CHIPBENCH / "configs"
                               / f"{cell.cfg['reference']}.py")
    plant = reference_in_place(cell.cfg, work, args.precision)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(cell, seed, args.seconds, False,
                               t_start=time.perf_counter(),
                               peak=peaks["devices"][devices[0].device_kind],
                               plant=plant)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "precision": args.precision,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    sys.exit(main())

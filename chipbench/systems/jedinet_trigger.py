"""The system under test for JEDI-net configurations: the trigger
serving path as ``trigger_serve`` users run it.

``ServingLoop.submit`` -> ``DeadlineBatcher`` -> ``ResilientEngine.
run_plan(plan, sync=False)`` (sentinel off, its default) -> the
configuration's forward path -> ``RequestFuture.result()``, with the
program's defaults everywhere else (2 ms deadline fuse, 4 plans in
flight, no serve-by deadline, the engine's default ladder).  One chip,
no mesh.
"""

from __future__ import annotations

#: Health counters that must stay 0: any of them means a request was
#: not served by the configuration's path as compiled for the chip.
FAULT_COUNTERS = ("demotions", "compile_failures", "construct_failures",
                  "dispatch_failures", "nonfinite_batches",
                  "watchdog_timeouts", "failed_requests", "shed_requests",
                  "fallback_batches", "quarantines", "sentinel_trips")


def model_config(cfg: dict):
    from repro.core.interaction_net import JediNetConfig

    return JediNetConfig(
        n_objects=cfg["n_objects"], n_features=cfg["n_features"],
        d_e=cfg["d_e"], d_o=cfg["d_o"], n_targets=cfg["n_targets"],
        fr_hidden=tuple(cfg["fr_hidden"]), fo_hidden=tuple(cfg["fo_hidden"]),
        phi_hidden=tuple(cfg["phi_hidden"]), activation=cfg["activation"],
        compute_dtype=cfg["compute_dtype"])


def build(cfg: dict, params, chips: int):
    """The engine, every rung of its bucket ladder compiled and run once."""
    from repro.serving import ResilientEngine

    if chips != 1:
        raise ValueError(f"this system runs on one chip, not {chips}")
    engine = ResilientEngine(params, model_config(cfg), forward=cfg["forward"],
                             mesh=None)
    engine.warm()
    return engine


def front_end(engine):
    """The serving loop over ``engine`` (or a proxy of it)."""
    from repro.serving import ServingLoop

    return ServingLoop(engine)


def health_checks(engine, cfg: dict) -> tuple[dict, dict]:
    """Numbers that must read 0 for the run to count as served by the
    configuration's path (buckets off it, fault counters, Pallas
    interpreted on a TPU), and the health detail behind them."""
    h = engine.health()
    off = [b for b, st in h["buckets"].items()
           if st["level"] != 0 or st["path"] != cfg["forward"]]
    faults = sum(int(h["counters"].get(k, 0)) for k in FAULT_COUNTERS)
    interpreted = int(engine.interpret and engine.platform == "tpu")
    detail = {"state": h["state"], "off_path_buckets": off,
              "counters": {k: h["counters"][k] for k in FAULT_COUNTERS
                           if h["counters"].get(k)},
              "construct_errors": h["construct_errors"],
              "last_errors": {b: st["last_error"]
                              for b, st in h["buckets"].items()
                              if st["last_error"]}}
    return {"off_path_buckets": len(off) + len(h["construct_errors"]),
            "fault_counters": faults,
            "interpreted_on_tpu": interpreted}, detail

"""Streaming L1-trigger serving CLI over the serving engine.

    PYTHONPATH=src python -m repro.launch.trigger_serve \
        --arch jedinet-30p --batch 256 --batches 40 --forward fused_full

The LHC L1 trigger is a hard-real-time stream: events arrive at a fixed
rate and every event must be classified within the trigger latency budget
(the paper targets < 1 us per jet on the FPGA).  A TPU trigger tier plays
a different position in the same pipeline: it amortizes weight traffic
over a batch of events, so the serving question becomes *sustained
throughput at bounded tail latency* rather than single-jet latency.

ALL the behavior lives in :mod:`repro.serving.trigger` — this module is
the thin shell (argparse + one call), and ``tests/test_thin_cli.py``
keeps it that way with an AST guard: no batching, engine or scheduling
logic may creep back in here.

Every tick is submitted to a :class:`~repro.serving.loop.ServingLoop`
over the fault-tolerant :class:`~repro.serving.resilient.ResilientEngine`
— the path the benchmark measures; the degradation ladder, deadline
shedding and watchdog are always armed.  ``--health`` prints the health
state machine's report after the run; ``--drill SEAM[:TIMES[:DELAY_S]]``
arms the fault-injection harness (:mod:`repro.serving.faults`) on the
same path (see EXPERIMENTS.md §Fault drills); ``--list-paths`` prints
the forward-path registry with each path's fallback chain and bucket
policy.  The exit code is 1 when any bucket ended off the requested
path (outside ``--drill``).
"""

from __future__ import annotations

import argparse

from repro.serving.trigger import build_trigger_cli, run_trigger_cli


def main(argv=None):
    ap = argparse.ArgumentParser()
    build_trigger_cli(ap)
    return run_trigger_cli(ap.parse_args(argv))


if __name__ == "__main__":
    raise SystemExit(main())

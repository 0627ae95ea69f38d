"""The comparison that decides ``correct``: its control fails it, and a
run with the timed path broken underneath comes out not correct.

The control (the reference at three bfloat16 passes, ``Precision.HIGH``
on a TPU) is read at the configurations' own widths on a few thousand
jets (the more jets, the wider the widest gap); on a CPU
``Precision.HIGH`` computes in full float32, so the test uses the same
three-pass product written out (``bf16x3``).

The fault runs drive a whole run of a JEDI-net small enough for the
Pallas interpreter (``data/tiny.json``), with the chip check skipped,
and break the configuration's own rung of the engine after it is built.
"""

import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import control, harness  # noqa: E402
from chipbench.configs import jedinet  # noqa: E402
from chipbench.jets import make_jets  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
PEAK = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 1234567


@pytest.mark.parametrize("name,jets", [("jedinet-50p", 1024),
                                       ("jedinet-30p", 4096)])
def test_control_fails_the_limit(name, jets):
    cfg = json.loads((ROOT / "chipbench" / "configs"
                      / f"{name}.json").read_text())
    params = harness.weights(cfg, jedinet)
    x = make_jets(np.random.RandomState(0), jets, cfg["n_objects"], 16)
    want = jedinet.reference(params, x, chunk=256)
    control = jedinet.reference(params, x, precision="bf16x3", chunk=256)
    err = float(np.max(np.abs(control - want)))
    assert err > cfg["limits"]["max_abs_err"], err


def _cell(traffic: str) -> harness.Cell:
    return harness.Cell(
        "tiny", 1, json.loads((DATA / "tiny.json").read_text()),
        json.loads((DATA / f"{traffic}.json").read_text()),
        [{"name": n, "unit": "x"} for n in harness.E2E], [])


@pytest.fixture(autouse=True)
def _short_ladder(monkeypatch):
    """The tiny configuration on a ladder the interpreter warms quickly
    (the engine's default ladder runs to 1024 rows)."""
    import functools

    import repro.serving

    monkeypatch.setattr(repro.serving, "ResilientEngine", functools.partial(
        repro.serving.ResilientEngine, max_batch=16))


def _run(traffic="tiny-open", plant=None):
    return harness.run_cell(_cell(traffic), SEED, 1.0, False,
                            t_start=time.perf_counter(), peak=PEAK,
                            plant=plant)


def _wrap_rung(fn):
    """Plant: every compiled bucket of the configuration's own rung
    returns ``fn(logits, x)`` instead of its logits."""
    def plant(engine):
        rung = engine._engines[0]
        for key, call in list(rung._cache.items()):
            rung._cache[key] = (lambda c: lambda x: fn(c(x), x))(call)
    return plant


def _raise(out, x):
    raise RuntimeError("planted dispatch failure")


FAULTS = {
    # an answer altered where it is produced
    "answer_altered": _wrap_rung(lambda out, x: out.at[0, 0].add(1.0)),
    # every answer moved to the next jet of the plan
    "answers_shifted": _wrap_rung(lambda out, x: jnp.roll(out, 1, axis=0)),
    # the second half of every plan's rows left out (zeros)
    "half_left_out": _wrap_rung(
        lambda out, x: out.at[out.shape[0] // 2:].set(0.0)),
    # the configuration's path fails: the ladder serves right answers
    # from its fallback rung, which is not the path under test
    "path_demoted": _wrap_rung(_raise),
}


@pytest.mark.parametrize("traffic", ["tiny-open", "tiny-closed"])
def test_sound_run_is_correct(traffic):
    res = _run(traffic)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert {"events_per_s", "p90_latency_ms", "setup_s"} <= set(
        res["metrics"])


def test_reference_in_place_below_the_precision_is_not_correct():
    """The control as the chip runs it: the reference, one precision
    step down, served in the program's place through the whole run.
    (On a CPU one bfloat16 pass stands for the step down: ``HIGH`` is
    full float32 there.)"""
    work = harness.load_module(ROOT / "chipbench" / "configs" / "jedinet.py")
    cell = _cell("tiny-open")
    res = _run("tiny-open", control.reference_in_place(cell.cfg, work,
                                                       "bf16"))
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_abs_err"]["value"] > res["checks"][
        "max_abs_err"]["limit"]
    assert res["checks"]["off_path_buckets"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_path_is_not_correct(fault):
    traffic = "tiny-closed" if fault == "half_left_out" else "tiny-open"
    res = _run(traffic, FAULTS[fault])
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0

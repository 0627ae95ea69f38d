"""The Particle Transformer configuration (``configs/part-jetclass.json``):
its work count, the control that fails its limit, and whole runs of a
small ParT (``data/tiny-part.json``) on the CPU, one sound and one with
the reference computed below its precision.

The control is the reference at three bfloat16 passes
(``Precision.HIGH`` on a TPU); on a CPU ``Precision.HIGH`` computes in
full float32, so the test uses the same three-pass product written out
(``bf16x3``).
"""

import functools
import json
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import control, harness  # noqa: E402
from chipbench.configs import part  # noqa: E402
from chipbench.jets import make_jets  # noqa: E402

CONFIGS = ROOT / "chipbench" / "configs"
DATA = pathlib.Path(__file__).resolve().parent / "data"
PEAK = {"flops_per_s": {"bfloat16": 197e12}, "hbm_bytes_per_s": 819e9}
SEED = 2**31 + 7654321


def _cfg():
    return json.loads((CONFIGS / "part-jetclass.json").read_text())


def test_flops_per_jet_pinned():
    assert part.flops_per_jet(_cfg()) == 667_225_600


def test_flops_split_into_the_published_parts():
    """ParT at JetClass widths: 34.1 M particle embedding, 145.7 M pair
    embedding over the 8,128 unordered pairs, 469.8 M in the 8 particle
    blocks, 17.7 M in the 2 class blocks and the head."""
    parts = part.flops_parts(_cfg())
    assert parts == {"particle_embed": 34_111_488,
                     "pair_embed": 145_653_760,
                     "particle_blocks": 469_762_048,
                     "class_blocks": 17_698_304}
    pair_flops = 2 * (4 * 64 + 64 * 64 + 64 * 64 + 64 * 8)
    assert parts["pair_embed"] == 128 * 127 // 2 * pair_flops
    assert parts["particle_blocks"] == 8 * 58_720_256
    assert parts["class_blocks"] == 2 * 8_847_872 + 2 * 128 * 10


def test_call_bytes_counts_weights_once_plus_io():
    cfg = _cfg()
    w = part.weight_bytes(cfg)
    assert w == 4 * 2_143_896
    assert part.call_bytes(cfg, 0) == w
    assert part.call_bytes(cfg, 8) - w == 8 * (128 * 17 * 4 + 10 * 4)


def test_control_fails_the_limit():
    cfg = _cfg()
    params = harness.weights(cfg, part)
    x = make_jets(np.random.RandomState(0), 64, cfg["n_objects"],
                  cfg["n_features"])
    want = part.reference(params, x, chunk=16)
    got = part.reference(params, x, precision="bf16x3", chunk=16)
    err = float(np.max(np.abs(got - want)))
    assert err > cfg["limits"]["max_abs_err"], err


@pytest.fixture(autouse=True)
def _short_ladder(monkeypatch):
    """The tiny configuration on a short ladder (the engine's default
    ladder runs to 1024 rows)."""
    import repro.serving

    monkeypatch.setattr(repro.serving, "ResilientEngine", functools.partial(
        repro.serving.ResilientEngine, max_batch=16))


def _cell(traffic: str) -> harness.Cell:
    return harness.Cell(
        "tiny-part", 1, json.loads((DATA / "tiny-part.json").read_text()),
        json.loads((DATA / f"{traffic}.json").read_text()),
        [{"name": n, "unit": "x"} for n in harness.E2E], [])


def _run(traffic, plant=None):
    return harness.run_cell(_cell(traffic), SEED, 1.0, False,
                            t_start=time.perf_counter(), peak=PEAK,
                            plant=plant)


@pytest.mark.parametrize("traffic", ["tiny-open", "tiny-closed"])
def test_sound_run_is_correct(traffic):
    """The ``part`` path through the whole run, against the plain
    reference of ``configs/part.py``."""
    res = _run(traffic)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    # float32 at HIGHEST on both sides: a few ulps of the logits
    assert res["checks"]["max_abs_err"]["value"] < 1e-5


def test_reference_in_place_below_the_precision_is_not_correct():
    cell = _cell("tiny-open")
    res = _run("tiny-open", control.reference_in_place(cell.cfg, part,
                                                       "bf16"))
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_abs_err"]["value"] > res["checks"][
        "max_abs_err"]["limit"]
    assert res["checks"]["off_path_buckets"]["value"] == 0

"""The benchmark's work count, and its refusal to run anywhere but a TPU
it knows."""

import json
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402
from chipbench.configs import jedinet  # noqa: E402

CONFIGS = ROOT / "chipbench" / "configs"
DATA = pathlib.Path(__file__).resolve().parent / "data"


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,flops", [("jedinet-50p", 27_372_900),
                                        ("jedinet-30p", 1_817_160)])
def test_flops_per_jet_pinned(name, flops):
    assert jedinet.flops_per_jet(_cfg(name)) == flops


def test_flops_split_into_the_published_parts():
    """50p: 26,460,000 edge layers + 160,000 split first layer +
    740,000 f_O + 12,900 phi_O."""
    cfg = _cfg("jedinet-50p")
    d = jedinet.mlp_dims(cfg)
    n = cfg["n_objects"]
    assert sum(2 * a * b for a, b in d["fr"][1:]) * n * (n - 1) == 26_460_000
    assert 2 * d["fr"][0][0] * d["fr"][0][1] * n == 160_000
    assert sum(2 * a * b for a, b in d["fo"]) * n == 740_000
    assert sum(2 * a * b for a, b in d["phi"]) == 12_900


def test_call_bytes_counts_weights_once_plus_io():
    cfg = _cfg("jedinet-30p")
    w = jedinet.weight_bytes(cfg)
    assert jedinet.call_bytes(cfg, 0) == w
    assert jedinet.call_bytes(cfg, 8) - w == 8 * (30 * 16 * 4 + 5 * 4)


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


PEAKS = json.loads((ROOT / "chipbench" / "peaks.json").read_text())["devices"]


@pytest.mark.parametrize("devices,chips,why", [
    ([_Dev("cpu", "cpu")], 1, "platform"),
    ([_Dev("tpu", "TPU v99 imaginary")], 1, "not in"),
    ([_Dev("tpu", "TPU v5 lite")], 4, "needs 4 chips"),
])
def test_device_problem(devices, chips, why):
    assert why in harness.device_problem(devices, chips, PEAKS)


def test_known_tpu_is_accepted():
    assert harness.device_problem([_Dev("tpu", "TPU v5 lite")] * 4, 4,
                                  PEAKS) is None


@pytest.mark.parametrize("kind", ["TPU v99 imaginary", "cpu"])
def test_main_refuses_unknown_device_without_a_result(monkeypatch, capsys,
                                                      kind):
    platform = "cpu" if kind == "cpu" else "tpu"
    monkeypatch.setattr(harness, "_devices", lambda: [_Dev(platform, kind)])
    rc = harness.main(["--workload", "jedi50p-fused-backlog", "--seed", "1",
                       "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_run_py_off_tpu_exits_nonzero_with_no_metrics_line():
    res = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "jedi50p-fused-backlog", "--seed", str(2**31 + 11), "--seconds",
         "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**_env(), "JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    assert res.stdout.strip() == ""
    assert "needs a TPU" in res.stderr


def test_run_py_without_the_program_exits_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "jedi50p-fused-backlog", "--seed", "5", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env={**_env(), "JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    assert res.stdout.strip() == ""


def _env():
    import os
    return {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}


BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_finds_its_files(cell):
    """Each cell's configuration, traffic mix, reference, system and
    per-layer readers are found by name, and each of its end-to-end
    metrics is a quantity the harness computes."""
    c = harness.load_cell(cell)
    assert (harness.CHIPBENCH / "configs" / f"{c.cfg['reference']}.py").is_file()
    assert (harness.CHIPBENCH / "systems" / f"{c.cfg['system']}.py").is_file()
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.end_to_end:
        assert m["name"].split(".")[0] in harness.E2E, m["name"]
    for m in c.per_layer:
        assert callable(harness.reader(m["name"]).read), m["name"]


def test_weights_do_not_depend_on_the_seed():
    """The program compiles the weights into its programs: the same
    weights in every run keep every run after the first in the cache."""
    cell = harness.Cell("tiny", 1,
                        json.loads((DATA / "tiny.json").read_text()),
                        json.loads((DATA / "tiny-open.json").read_text()),
                        [], [])
    a = harness.materials(cell, 2**31 + 5, 0.5)
    b = harness.materials(cell, 7, 0.5)
    for u, v in zip(jax.tree_util.tree_leaves(a[1]),
                    jax.tree_util.tree_leaves(b[1])):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
    assert not np.array_equal(a[2], b[2])       # the jet pools differ

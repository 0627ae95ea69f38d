"""Sharding rules: logical->physical mapping, divisibility fallback,
state/cache shardings, and a real multi-device pjit run on a fake mesh.

Uses a subprocess-free trick: tests in this file create a 4-device CPU
mesh via jax.sharding over the single device? No — JAX needs real devices.
Instead these tests run structure-level assertions (specs) which don't
need devices, plus one guarded multi-device test that only runs when the
test session was started with XLA_FLAGS device_count>1 (see
tests/test_multidevice.py for the subprocess-based version).
"""

import os
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.parallel import sharding as shd


class FakeMesh:
    """Duck-typed mesh: axis_names + shape dict (enough for spec logic)."""

    def __init__(self, shape: dict):
        self.axis_names = tuple(shape)
        self.shape = dict(shape)


def test_filter_axes_drops_missing():
    m = FakeMesh({"data": 16, "model": 16})
    assert shd._filter_axes(("pod", "data"), m) == "data"
    assert shd._filter_axes(("pod",), m) is None
    assert shd._filter_axes(("data", "model"), m) == ("data", "model")


def test_divisible_entry_prefix_rule():
    m = FakeMesh({"pod": 2, "data": 16, "model": 16})
    # full product divides
    assert shd._divisible_entry(512, ("pod", "data", "model"), m) == \
        ("pod", "data", "model")
    # only pod*data divides 32
    assert shd._divisible_entry(32, ("pod", "data", "model"), m) == \
        ("pod", "data")
    # nothing divides 7
    assert shd._divisible_entry(7, ("pod", "data", "model"), m) is None
    # 8 kv heads on 16-way model -> dropped
    assert shd._divisible_entry(8, ("model",), m) is None


def test_logical_to_spec_known_axes():
    m = FakeMesh({"data": 16, "model": 16})
    spec = shd.logical_to_spec(("batch", None, "heads"), m,
                               shd.DEFAULT_RULES)
    assert spec == P("data", None, "model")
    with pytest.raises(KeyError):
        shd.logical_to_spec(("nope",), m, shd.DEFAULT_RULES)


def _abstract(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_param_rules_lm_paths():
    """Param path regexes give TP+FSDP for attention/FFN, EP for experts."""
    m = FakeMesh({"data": 16, "model": 16})
    spec = shd._spec_for_path("layers/attn/wq/w", 3, m, shd.DEFAULT_RULES,
                              (4, 2048, 2048))
    assert tuple(spec) == (None, "data", "model")
    spec = shd._spec_for_path("layers/moe/experts/w_gate", 4, m,
                              shd.DEFAULT_RULES, (4, 128, 2048, 4864))
    assert tuple(spec) == (None, "data", None, "model")
    spec = shd._spec_for_path("embed/w", 2, m, shd.DEFAULT_RULES,
                              (32000, 4096))
    assert tuple(spec) == ("model", "data")
    # non-dividing fan-in falls back (1433 % 16 != 0)
    spec = shd._spec_for_path("gnn_layers/0/w", 2, m, shd.DEFAULT_RULES,
                              (1433, 16))
    assert tuple(spec) == (None, None)


def _run_with_fake_devices(code: str) -> str:
    """NamedSharding needs a real Mesh; run spec checks in a subprocess
    with 256 fake devices so 16x16 meshes exist."""
    import subprocess
    import sys
    import textwrap
    prog = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=256'\n"
            + textwrap.dedent(code))
    # the child always runs on the CPU: on a machine with a TPU the
    # pytest process may hold the chip
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=600, cwd=pathlib.Path(__file__).resolve().parents[1],
        env={"PYTHONPATH": "src", "PATH": os.environ.get("PATH", ""),
             "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_train_state_shardings_structure():
    """Adafactor factored accs inherit the param spec minus reduced dim."""
    out = _run_with_fake_devices("""
        import jax, jax.numpy as jnp
        from repro.parallel import sharding as shd
        from repro.training import make_optimizer
        from repro.training.schedule import constant

        mesh = jax.make_mesh((16, 16), ("data", "model"))
        params = {"layers": {"attn": {"wq": {
            "w": jax.ShapeDtypeStruct((4, 2048, 2048), jnp.float32)}}}}
        opt = make_optimizer("adafactor", constant(1e-3))
        opt_state = jax.eval_shape(opt.init, params)
        state = {"params": params, "opt": opt_state,
                 "step": jax.ShapeDtypeStruct((), jnp.int32)}
        sh = shd.train_state_shardings(state, mesh)
        print("P", tuple(sh["params"]["layers"]["attn"]["wq"]["w"].spec))
        acc = sh["opt"]["acc"]["layers"]["attn"]["wq"]["w"]
        print("R", tuple(acc["r"].spec))
        print("C", tuple(acc["c"].spec))
    """)
    assert "P (None, 'data', 'model')" in out
    assert "R (None, 'data')" in out          # minus last dim
    assert "C (None, 'model')" in out         # minus second-to-last


def test_kv_cache_shardings_fallback():
    """kv=8 heads on a 16-way model axis -> seq-sharded cache."""
    out = _run_with_fake_devices("""
        import jax, jax.numpy as jnp
        from repro.parallel import sharding as shd

        mesh = jax.make_mesh((16, 16), ("data", "model"))
        def sds(shape, dt=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dt)
        cache = {
            "k": sds((32, 128, 32768, 8, 128)),
            "v": sds((32, 128, 32768, 8, 128)),
            "slot_pos": sds((128, 32768), jnp.int32),
            "pos": sds((128,), jnp.int32),
        }
        sh = shd.kv_cache_shardings(cache, mesh)
        print("A", tuple(sh["k"].spec))
        cache["k"] = sds((32, 128, 32768, 16, 128))
        cache["v"] = cache["k"]
        sh = shd.kv_cache_shardings(cache, mesh)
        print("B", tuple(sh["k"].spec))
    """)
    assert "A (None, 'data', 'model', None, None)" in out  # seq-sharded
    assert "B (None, 'data', None, 'model', None)" in out  # head-sharded


def test_constrain_is_noop_without_context():
    x = jnp.ones((4, 4))
    y = shd.constrain(x, "batch", None)
    assert y is x

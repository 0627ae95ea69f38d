"""Multi-device distribution tests, run in subprocesses with 8 fake CPU
devices (this process must keep seeing 1 device — see conftest note)."""

import os
import pathlib
import subprocess
import sys
import textwrap

REPO = pathlib.Path(__file__).resolve().parents[1]


def subprocess_env() -> dict:
    """Minimal env for test subprocesses.  The children always run on
    the CPU: on a machine with a TPU, the pytest process may hold the
    chip, and a child that reached for it would fail or hang."""
    return {"PYTHONPATH": "src", "PATH": os.environ.get("PATH", ""),
            "HOME": os.environ.get("HOME", str(REPO)),
            "JAX_PLATFORMS": "cpu"}


def run_py(code: str, timeout=600) -> str:
    """Run code in a fresh python with 8 fake devices; return stdout."""
    prog = ("import os\n"
            "os.environ['XLA_FLAGS'] = "
            "'--xla_force_host_platform_device_count=8'\n"
            + textwrap.dedent(code))
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        timeout=timeout, env=subprocess_env(),
        cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """A pjit'd jedinet train step on a 4x2 mesh == unsharded step."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import interaction_net as inet
        from repro.training import make_optimizer, init_state, make_train_step
        from repro.training.schedule import constant
        from repro.parallel.sharding import axis_rules, train_state_shardings, batch_shardings

        cfg = inet.JediNetConfig(n_objects=8, n_features=4, fr_hidden=(8,),
                                 fo_hidden=(8,), phi_hidden=(8,))
        opt = make_optimizer("adamw", constant(1e-3))
        state = init_state(jax.random.PRNGKey(0), lambda k: inet.init(k, cfg), opt)
        step = make_train_step(lambda p, b: inet.loss_fn(p, cfg, b), opt)
        x = jax.random.normal(jax.random.PRNGKey(1), (16, 8, 4))
        y = jnp.zeros((16,), jnp.int32)
        batch = {"x": x, "y": y}

        ref_state, ref_m = jax.jit(step)(state, batch)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        with mesh, axis_rules(mesh):
            st_sh = train_state_shardings(state, mesh)
            b_sh = batch_shardings(batch, mesh, {"x": ("batch", None, None),
                                                 "y": ("batch",)})
            f = jax.jit(step, in_shardings=(st_sh, b_sh),
                        out_shardings=(st_sh, None))
            got_state, got_m = f(state, batch)

        err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
            jax.tree_util.tree_leaves(ref_state["params"]),
            jax.tree_util.tree_leaves(got_state["params"])))
        print("MAXERR", err)
        print("LOSSDIFF", abs(float(ref_m["loss"]) - float(got_m["loss"])))
    """)
    maxerr = float(out.split("MAXERR")[1].split()[0])
    lossdiff = float(out.split("LOSSDIFF")[1].split()[0])
    assert maxerr < 1e-4
    assert lossdiff < 1e-4


def test_ef_compressed_psum_convergence():
    """int8 error-feedback all-reduce: quantized DP training tracks exact
    DP training on a quadratic objective."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.training.grad_compression import ef_compressed_psum

        mesh = jax.make_mesh((8,), ("data",))
        target = jax.random.normal(jax.random.PRNGKey(0), (32,))

        def local_grad(w, xs):
            # per-shard quadratic losses with different data
            return jax.grad(lambda w_: jnp.mean((xs @ w_ - xs @ target) ** 2))(w)

        xs_all = jax.random.normal(jax.random.PRNGKey(1), (64, 32))

        @jax.jit
        @partial(shard_map, mesh=mesh, in_specs=(P(), P("data"), P("data")),
                 out_specs=(P(), P("data")), check_vma=False)
        def compressed_step(w, xs, resid):
            g = local_grad(w, xs)
            gm, new_r = ef_compressed_psum({"g": g}, {"g": resid[0]}, "data")
            return gm["g"], new_r["g"][None, :]

        @jax.jit
        @partial(shard_map, mesh=mesh, in_specs=(P(), P("data")),
                 out_specs=P(), check_vma=False)
        def exact_step(w, xs):
            return jax.lax.pmean(local_grad(w, xs), "data")

        wq = jnp.zeros((32,)); we = jnp.zeros((32,))
        resid = jnp.zeros((8, 32))   # per-shard residual
        for i in range(80):
            ge = exact_step(we, xs_all); we = we - 0.1 * ge
            gq, resid = compressed_step(wq, xs_all, resid); wq = wq - 0.1 * gq
        print("EXACT_DIST", float(jnp.linalg.norm(we - target)))
        print("QUANT_DIST", float(jnp.linalg.norm(wq - target)))
    """)
    exact = float(out.split("EXACT_DIST")[1].split()[0])
    quant = float(out.split("QUANT_DIST")[1].split()[0])
    # |target| ~ sqrt(32) ~ 5.6 at init: both must have converged most of
    # the way, and error feedback must keep quantized DP tracking exact DP.
    assert exact < 1.0
    assert quant < 2.0 * exact + 0.1


def test_elastic_checkpoint_restore_across_meshes():
    """Save sharded on a (4,2) mesh, restore onto a (2,) mesh (pod loss)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from repro.checkpoint import CheckpointManager
        from repro.parallel.sharding import param_shardings
        from jax.sharding import NamedSharding, PartitionSpec as P

        params = {"layers": {"attn": {"wq": {"w": jnp.arange(4*64*64, dtype=jnp.float32).reshape(4, 64, 64)}}}}
        mesh_a = jax.make_mesh((4, 2), ("data", "model"))
        sh_a = param_shardings(params, mesh_a)
        p_a = jax.tree_util.tree_map(jax.device_put, params,
                                     jax.tree_util.tree_map(lambda s: s, sh_a))
        with tempfile.TemporaryDirectory() as td:
            cm = CheckpointManager(td)
            cm.save(3, {"params": p_a, "step": jnp.asarray(3)})
            # "lose a pod": restore onto a smaller mesh
            mesh_b = jax.make_mesh((2,), ("data",))
            sh_b = param_shardings(params, mesh_b)
            restored, step = cm.restore(
                shardings={"params": sh_b, "step": None})
            w = restored["params"]["layers"]["attn"]["wq"]["w"]
            print("STEP", step)
            print("OK", bool(np.allclose(np.asarray(w), np.asarray(params["layers"]["attn"]["wq"]["w"]))))
            print("NSHARDS", len(w.sharding.device_set))
    """)
    assert "STEP 3" in out
    assert "OK True" in out
    assert "NSHARDS 2" in out


def test_train_driver_crash_restart():
    """Fault tolerance: injected crash at step 30, restart resumes from the
    step-25 checkpoint and finishes."""
    import tempfile, os
    with tempfile.TemporaryDirectory() as td:
        env = subprocess_env()
        cmd = [sys.executable, "-m", "repro.launch.train",
               "--arch", "jedinet-30p", "--steps", "60", "--batch", "32",
               "--ckpt-dir", td, "--ckpt-every", "25"]
        r1 = subprocess.run(cmd + ["--fail-at-step", "30"],
                            capture_output=True, text=True, timeout=600,
                            cwd=REPO, env=env)
        assert r1.returncode != 0
        assert "injected failure" in r1.stderr
        # checkpoint from step 25 must exist
        assert any(d.startswith("step_") for d in os.listdir(td))
        r2 = subprocess.run(cmd, capture_output=True, text=True,
                            timeout=600, cwd=REPO, env=env)
        assert r2.returncode == 0, r2.stderr[-2000:]
        assert "restored checkpoint at step 25" in r2.stdout
        assert "final checkpoint at step 60" in r2.stdout


def test_a2a_moe_dispatch_matches_global():
    """shard_map all-to-all MoE dispatch (§Perf cell B b3) == the global
    sort-based dispatch, bit-exact with ample capacity."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.configs.base import MoEConfig
        from repro.models import moe as moe_lib
        from repro.parallel.moe_dispatch import a2a_moe

        mesh = jax.make_mesh((8,), ("data",))
        moe = MoEConfig(n_experts=8, top_k=2, capacity_factor=8.0)
        params = moe_lib.init_moe(jax.random.PRNGKey(0), moe, 16, 32)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
        ref, _ = moe_lib.moe_apply(params, moe, x,
                                   compute_dtype=jnp.float32)
        got = a2a_moe(x, params, moe, mesh)
        print("A2A_ERR", float(jnp.max(jnp.abs(ref - got))))
    """)
    assert float(out.split("A2A_ERR")[1].split()[0]) < 1e-5

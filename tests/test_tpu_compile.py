"""Ahead-of-time compiles of the serving kernels for a TPU v5e.

The TPU compiler is installed without a chip: it compiles for a
described ``v5e:2x2`` topology and refuses what the chip would refuse —
primitives Mosaic cannot lower, blocks that break the (8, 128) tiling,
kernels past the scoped-VMEM limit — none of which interpret mode sees.
Each Pallas path compiles at jedinet-30p and jedinet-50p (published
widths) at three rungs of its own bucket ladder: the first, the first
whose grid has several batch tiles, and the top.

Only one process may load the TPU library at a time, so the topology is
described inside a fixture (never at import or collection) and every
compile runs in this test process.
"""

import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_arch
from repro.core import paths
from repro.core.interaction_net import init

PATHS = ("fused_full", "int8_fused_full", "jedi_linear_full",
         "int8_jedi_linear_full")
ARCHS = ("jedinet-30p", "jedinet-50p")
RUNGS = ("first", "multistep", "top")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")      # no compiler logs on disk
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()
    mp.undo()


def _rung(spec, cfg, params, which: str) -> int:
    ladder = spec.bucket_ladder(cfg, params, 1024)
    if which == "first":
        return ladder[0]
    if which == "top":
        return ladder[-1]
    return next(b for b in ladder
                if spec.residency_model(cfg, params, b)["grid"][0] > 1)


@pytest.mark.parametrize("rung", RUNGS)
@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("path", PATHS)
def test_kernel_compiles_for_v5e(one_chip, path, arch, rung):
    cfg = get_arch(arch).model
    spec = paths.get(path)
    params = spec.prepare_params(init(jax.random.PRNGKey(0), cfg))
    bucket = _rung(spec, cfg, params, rung)
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    x = jax.ShapeDtypeStruct((bucket, cfg.n_objects, cfg.n_features),
                             jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda p, xv: spec.forward(p, cfg, xv, interpret=False))
    t0 = time.perf_counter()
    compiled = fn.lower(shapes, x).compile()
    seconds = time.perf_counter() - t0
    assert "tpu_custom_call" in compiled.as_text()
    # compile time stays flat in the bucket: the kernel body holds one
    # batch tile, bigger buckets only add grid steps
    assert seconds < 30, (path, arch, bucket, seconds)

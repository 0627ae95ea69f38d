"""The ``part_pair_embed`` kernel (ParT's pair embedding) on the CPU.

The kernel runs in interpret mode here.  Its GELU is held to
``jax.nn.gelu(approximate=False)`` and its erfc to ``jax.lax.erf``; its
U to the XLA pair embedding and to the unordered-pair reference, with
padded particles, at block sizes that split the batch and at a particle
count whose packed pairs are not a whole number of lane tiles; the
packing of each unordered pair into one lane to its index map, and
pack -> kernel -> unfold to the reference at N = 128, 8 and 7 (an odd N
served with one padding particle); its tile model to the traced
``pallas_call``, through the kernel-contract auditor.

Tolerances: the GELU to 1e-6 absolute on [-10, 10], two float32 ulps of
the largest value there; U to 1e-5, as the path's other U checks
(``test_particle_transformer.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import particle_transformer as pt
from repro.core import paths
from repro.data.jets import make_jets
from repro.kernels.part_pair import kernel as K
from repro.kernels.part_pair import ops

SMALL = pt.ParticleTransformerConfig(
    n_objects=16, embed_dims=(32, 64, 32), pair_embed_dims=(8, 8, 8, 4),
    n_heads=4, n_blocks=1, n_cls_blocks=1, ffn_dim=64)


def _jets(cfg, n, seed=1, n_padding=0):
    x = make_jets(np.random.RandomState(seed), n, cfg.n_objects,
                  cfg.n_features)[0].copy()
    if n_padding:
        x[1, cfg.n_objects - n_padding:] = 0.0
    return jnp.asarray(x)


def test_gelu_matches_the_exact_gelu():
    x = jnp.linspace(-10.0, 10.0, 400_001, dtype=jnp.float32)
    want = jax.jit(lambda v: jax.nn.gelu(v, approximate=False))(x)
    got = jax.jit(K.gelu)(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=0)


def test_erfc_matches_lax_erf():
    z = jnp.linspace(0.0, 10.0, 200_001, dtype=jnp.float32)
    got = 1.0 - jax.jit(K.erfc_nonneg)(z)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jax.lax.erf(z)), atol=1e-6, rtol=0)


@pytest.mark.parametrize("cfg,batch,block_b", [
    (SMALL, 4, 1),
    (SMALL, 4, 2),
    (SMALL.with_(n_objects=12), 3, 3),    # 72 packed pairs: 128 lanes
])
def test_kernel_matches_the_xla_pair_embedding(cfg, batch, block_b):
    params = pt.init_params(jax.random.PRNGKey(0), cfg)
    x = _jets(cfg, batch, n_padding=3)
    mask = pt.particle_mask(x)
    u = ops.part_pair_embed(
        pt._pair_features_channel_major, x, mask,
        pt._fold_pair_layers(params["pair"]), block_b=block_b,
        interpret=True)
    with jax.default_matmul_precision("highest"):
        want = pt.pair_bias(params, cfg, x, mask)
        ref = pt.pair_bias_unordered(params, cfg, x, mask)
    assert u.shape == (batch, cfg.n_heads, cfg.n_objects, cfg.n_objects)
    np.testing.assert_allclose(np.asarray(u), np.asarray(want), atol=1e-5,
                               rtol=0)
    np.testing.assert_allclose(np.asarray(u), np.asarray(ref), atol=1e-5,
                               rtol=0)
    assert np.all(np.asarray(u)[1, :, -3:, :] == 0)


@pytest.mark.parametrize("n", [128, 8, 7])
def test_index_map_covers_each_unordered_pair_once(n):
    """Every pair i < j of the N particles is in exactly one lane; the
    other lanes hold a particle with itself (D's diagonal) or, at an odd
    N, the padding particle N; the lanes fill N^2/2 (N + 1 when odd)."""
    i, j = ops.pair_index(n)
    h = -(-n // 2)
    assert i.shape == j.shape == (h, 2 * h)
    assert ops.padded_pairs(n) >= i.size == 2 * h * h
    real = (i != j) & (i < n) & (j < n)
    pairs = sorted(zip(np.minimum(i, j)[real].tolist(),
                       np.maximum(i, j)[real].tolist()))
    assert pairs == [(a, b) for a in range(n) for b in range(a + 1, n)]
    assert (i == j).sum() == h
    assert (~real).sum() == h + (2 * h - 1 if n % 2 else 0)


@pytest.mark.parametrize("n", [128, 8, 7])
def test_pack_puts_each_pair_in_the_lane_its_index_map_names(n):
    """:func:`ops.pack_pairs` lays out, lane for lane, the pairs
    :func:`ops.pair_index` names, with keep 1.0 exactly on a pair of two
    distinct real particles."""
    x = jnp.arange(2 * n, dtype=jnp.float32).reshape(2, n, 1) % n + 1.0
    mask = jnp.ones((2, n)).at[1, -2:].set(0.0)

    def ids(xi, xj):
        one = jnp.ones((xi.shape[0], 1, xi.shape[1], xj.shape[1]))
        return jnp.concatenate([xi[:, None, :, None, 0] * one,
                                xj[:, None, None, :, 0] * one,
                                one, one], axis=1)

    slab = np.asarray(ops.pack_pairs(ids, x, mask))
    i, j = ops.pair_index(n)
    lanes = i.size
    assert slab.shape == (2, K.IN_ROWS, ops.padded_pairs(n))
    for b, n_real in ((0, n), (1, n - 2)):
        real = (i < n) & (j < n)
        np.testing.assert_array_equal(slab[b, 0, :lanes][real.ravel()],
                                      (i + 1)[real])
        np.testing.assert_array_equal(slab[b, 1, :lanes][real.ravel()],
                                      (j + 1)[real])
        keep = (i != j) & (i < n_real) & (j < n_real)
        np.testing.assert_array_equal(slab[b, -1, :lanes], keep.ravel())
    assert np.all(slab[:, -1, lanes:] == 0)


@pytest.mark.parametrize("n", [128, 8, 7])
def test_packed_kernel_gives_the_unordered_pair_reference(n):
    """pack -> kernel (interpret mode) -> unfold gives U of the
    unordered-pair reference within 1e-5, exactly symmetric, 0 on the
    diagonal and on every row and column of a padding particle."""
    cfg = SMALL.with_(n_objects=n)
    params = pt.init_params(jax.random.PRNGKey(0), cfg)
    x = _jets(cfg, 2, n_padding=2)
    mask = pt.particle_mask(x)
    folded = pt._fold_pair_layers(params["pair"])
    slab = ops.pack_pairs(pt._pair_features_channel_major, x, mask)
    packed = K.pair_embed_call(slab, K.kernel_operands(folded), block_b=1,
                               chunk=ops.chunk_for(slab.shape[-1]),
                               interpret=True)
    u = np.asarray(ops.unfold(packed, n))
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(pt.pair_bias_unordered(params, cfg, x, mask))
    assert u.shape == (2, cfg.n_heads, n, n)
    np.testing.assert_allclose(u, ref, atol=1e-5, rtol=0)
    np.testing.assert_array_equal(u, np.swapaxes(u, -1, -2))
    np.testing.assert_array_equal(np.diagonal(u, axis1=-2, axis2=-1), 0.0)
    assert np.all(u[1, :, -2:, :] == 0) and np.all(u[1, :, :, -2:] == 0)
    assert np.abs(u[0]).max() > 0.1


@pytest.mark.parametrize("batch", [1, 8, 13, 64, 1024])
def test_tile_divides_every_batch_and_fits(batch):
    cfg = pt.ParticleTransformerConfig()
    tiles = ops.modeled_residency(cfg, None, batch)
    assert batch % tiles["block_b"] == 0
    assert tiles["grid"] == (batch // tiles["block_b"],)
    assert tiles["fits"]
    assert tiles["block_b"] * tiles["per_sample_bytes"] \
        <= tiles["effective_budget"]


def test_weight_model_counts_the_kernel_operands():
    cfg = pt.ParticleTransformerConfig()
    params = pt.init_params(jax.random.PRNGKey(0), cfg)
    operands = K.kernel_operands(pt._fold_pair_layers(params["pair"]))
    assert ops.weight_bytes(cfg) == sum(a.size * 4 for a in operands)
    assert operands[0].shape == (64, 4)


def test_part_path_falls_back_to_xla():
    assert paths.fallback_chain("part") == ["part", "part_xla"]
    assert paths.get("part").pallas and not paths.get("part_xla").pallas


def test_kernel_audit_of_the_part_path(capsys):
    """``python -m repro.analysis --audit-only`` at the published widths:
    the auditor traces ``part`` at every rung of its ladder and checks
    the ``pallas_call`` against :func:`ops.modeled_residency`."""
    import json

    from repro.analysis.__main__ import main
    rc = main(["--audit-only", "--arch", "part_jetclass", "--json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0 and doc["findings"] == [], doc["findings"]

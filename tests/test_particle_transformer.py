"""Particle Transformer on the trigger path, at a small size on the CPU.

The ``part`` path (its pair embedding the ``part_pair_embed`` kernel, in
interpret mode here) is held to the plain per-jet reference of the test
tree (``part_reference.py``, nothing of the program) on seeded random
weights, through the normal serving path (``ResilientEngine`` and
``ServingLoop``); the model's symmetries (particle permutation, zero
padding, a symmetric pair bias, on both paths) and the pair features'
exact massless forms are checked on their own; each path's ladder keeps
within its HBM model.

Tolerances: the program and the reference compute the same float32
arithmetic in different orders (BatchNorms folded into the pair
Linears, ordered against unordered pairs, batched against per-jet
matmuls), so logits agree to a few float32 ulps of their O(1) size;
1e-5 leaves ~10x room over the 1e-6 measured here and is ~1000x below
what one bfloat16 pass gives (1.9e-2 at these widths).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import part_reference
from repro.core import particle_transformer as pt
from repro.core import paths
from repro.data.jets import make_jets
from repro.serving import ResilientEngine, ServingEngine, ServingLoop

#: |served - reference| over logits of O(1) (see the module docstring).
TOL = 1e-5

#: A size small enough for the CPU: N 16, d 32, 4 heads, pair widths
#: (8, 8, 8, 4), one particle block and one class block.
SMALL = pt.ParticleTransformerConfig(
    n_objects=16, embed_dims=(32, 64, 32), pair_embed_dims=(8, 8, 8, 4),
    n_heads=4, n_blocks=1, n_cls_blocks=1, ffn_dim=64)


@pytest.fixture(scope="module")
def model():
    return SMALL, pt.init_params(jax.random.PRNGKey(0), SMALL)


def _jets(n, seed=1, cfg=SMALL):
    return make_jets(np.random.RandomState(seed), n, cfg.n_objects,
                     cfg.n_features)[0]


def _reference(params, x):
    return np.asarray(jax.jit(part_reference.forward)(params, x))


def _forward(params, cfg, x):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(
            lambda p, v: pt.forward(p, cfg, v))(params, x))


@pytest.mark.parametrize("sizes", [
    [8, 16],            # exact buckets
    [3, 11, 5],         # padded up to their buckets
    [13, 7, 23],        # prime sizes; 23 > the top bucket is chunked
])
def test_part_path_through_the_loop_matches_the_reference(model, sizes):
    cfg, params = model
    engine = ResilientEngine(params, cfg, forward="part", mesh=None,
                             max_batch=16)
    assert engine.chain == ["part", "part_xla"]
    loop = ServingLoop(engine, deadline_s=1e9)
    xs = np.split(_jets(sum(sizes)), np.cumsum(sizes)[:-1])
    futures = [loop.submit(x) for x in xs]
    loop.drain()
    got = np.concatenate([f.result() for f in futures])
    want = _reference(params, np.concatenate(xs))
    assert got.shape == (sum(sizes), cfg.n_targets)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    h = engine.health()
    assert all(b["level"] == 0 and b["path"] == "part"
               for b in h["buckets"].values())
    assert not h["construct_errors"]


def test_logits_do_not_change_when_particles_are_permuted(model):
    cfg, params = model
    x = _jets(4)
    perm = np.random.RandomState(2).permutation(cfg.n_objects)
    np.testing.assert_allclose(_forward(params, cfg, x[:, perm]),
                               _forward(params, cfg, x), atol=TOL, rtol=0)


def test_zero_padding_rows_leave_the_logits_unchanged(model):
    """A jet of 10 particles padded with 6 rows of zeros gives the logits
    of the same jet served alone at N = 10 (the weights do not depend
    on N)."""
    cfg, params = model
    jet = _jets(3)[:, :10]
    padded = np.concatenate(
        [jet, np.zeros((3, 6, cfg.n_features), np.float32)], axis=1)
    alone = _forward(params, cfg.with_(n_objects=10), jet)
    np.testing.assert_allclose(_forward(params, cfg, padded), alone,
                               atol=TOL, rtol=0)
    np.testing.assert_allclose(_reference(params, padded), alone,
                               atol=TOL, rtol=0)


#: U of each path: the kernel (interpret mode) and plain XLA.
BIAS_FNS = {"part": lambda *a: pt.pair_bias_kernel(*a, interpret=True),
            "part_xla": pt.pair_bias}


@pytest.mark.parametrize("path", sorted(BIAS_FNS))
def test_pair_bias_is_symmetric_with_a_zero_diagonal(model, path):
    cfg, params = model
    x = _jets(3).copy()
    x[1, 12:] = 0.0                               # 4 padding particles
    x = jnp.asarray(x)
    mask = pt.particle_mask(x)
    with jax.default_matmul_precision("highest"):
        u = np.asarray(BIAS_FNS[path](params, cfg, x, mask))
        ref = np.asarray(pt.pair_bias_unordered(params, cfg, x, mask))
    assert u.shape == (3, cfg.n_heads, cfg.n_objects, cfg.n_objects)
    # the pair features are exactly symmetric; the matmul may sum a
    # row's products in another order at another position: ulps of O(1)
    np.testing.assert_allclose(u, np.swapaxes(u, -1, -2), atol=1e-6, rtol=0)
    np.testing.assert_array_equal(np.diagonal(u, axis1=-2, axis2=-1), 0.0)
    assert np.all(u[1, :, 12:, :] == 0) and np.all(u[1, :, :, 12:] == 0)
    assert np.abs(u[0]).max() > 0.1
    np.testing.assert_allclose(u, ref, atol=1e-5, rtol=0)
    want = jax.vmap(lambda xj: part_reference.pair_bias(
        params, xj, part_reference.dot_highest))(x)
    np.testing.assert_allclose(u, np.asarray(want), atol=1e-5, rtol=0)


def test_stable_m2_is_the_massless_invariant_mass():
    """ln m^2 of :func:`pair_features` (float32, cancellation-free form)
    against (E_i + E_j)^2 - |p_i + p_j|^2 in float64, for massless
    particles with pT = exp(x0), eta = x1, phi = x2.  Pairs whose
    float64 difference itself cancels beyond 1e-6 of E^2 are left out of
    the float64 side's trust region."""
    rng = np.random.RandomState(3)
    x = np.zeros((2, 12, 17), np.float32)
    x[..., 0] = rng.normal(0, 1.5, (2, 12))
    x[..., 1] = rng.normal(0, 1.5, (2, 12))
    x[..., 2] = rng.uniform(-7, 7, (2, 12))        # wraps several times
    got = np.exp(np.asarray(pt.pair_features(jnp.asarray(x)),
                            np.float64)[..., 3])
    x64 = x.astype(np.float64)
    pt_, eta, phi = np.exp(x64[..., 0]), x64[..., 1], x64[..., 2]
    p4 = np.stack([pt_ * np.cosh(eta), pt_ * np.cos(phi),
                   pt_ * np.sin(phi), pt_ * np.sinh(eta)], -1)
    s = p4[:, :, None] + p4[:, None, :]
    m2 = s[..., 0] ** 2 - (s[..., 1:] ** 2).sum(-1)
    e2 = s[..., 0] ** 2
    off = ~np.eye(12, dtype=bool)[None] & (m2 > 1e-6 * e2)
    assert off.sum() > 200
    np.testing.assert_allclose(got[off], m2[off], rtol=5e-5)
    # the float64 stable form equals the direct one to float64 rounding
    deta = eta[:, :, None] - eta[:, None, :]
    dphi = phi[:, :, None] - phi[:, None, :]
    stable = 4 * pt_[:, :, None] * pt_[:, None, :] * (
        np.sinh(deta / 2) ** 2 + np.sin(dphi / 2) ** 2)
    np.testing.assert_allclose(stable[off], m2[off], rtol=1e-9)


@pytest.mark.parametrize("path,model_bytes,per_jet", [
    ("part", pt.hbm_bytes_per_sample, 1_474_560),        # 1.4 MiB
    ("part_xla", pt.hbm_bytes_per_sample_xla, 8_912_896),  # 8.5 MiB
])
def test_ladder_respects_its_hbm_model(path, model_bytes, per_jet):
    """At the published widths the ladder is sized against the HBM
    budget: every rung's working set fits, and the top rung is the
    largest that does, or max_batch."""
    cfg = pt.ParticleTransformerConfig()
    params = pt.init_params(jax.random.PRNGKey(0), cfg)
    spec = paths.get(path)
    per = spec.bucket_bytes(cfg, params)
    assert per == model_bytes(cfg) == per_jet
    reserved = spec.reserved_vmem_bytes(cfg, params)
    room = pt.HBM_BUDGET_BYTES - reserved
    ladder = spec.bucket_ladder(cfg, params, 1024)
    assert ladder == [8, 16, 32, 64, 128, 256, 512, 1024]
    assert all(b * per <= room for b in ladder)
    # a budget of 500 jets: the top rung is the largest that fits, and a
    # request above it is chunked through it
    small = paths.PathSpec(**{**spec.__dict__, "name": "_t",
                              "memory_budget_bytes": 500 * per})
    top = small.bucket_ladder(cfg, params, 1024)[-1]
    assert top * per <= small.memory_budget_bytes - reserved
    assert (top + 8) * per > small.memory_budget_bytes - reserved
    assert top % 8 == 0 and top < 1024


@pytest.mark.parametrize("path,pairs_per_jet", [
    ("part", SMALL.n_objects ** 2 // 2),      # each unordered pair once
    ("part_xla", SMALL.n_objects ** 2),       # every ordered pair
])
def test_pairs_kernel_counts_the_pairs_each_plan_evaluates(model, path,
                                                           pairs_per_jet):
    cfg, params = model
    engine = ServingEngine(params, cfg, forward=path, mesh=None,
                           bucket_sizes=[8, 16])
    engine.infer(_jets(5))
    engine.infer(_jets(12))
    assert engine.metrics.counter("pairs_kernel") == (8 + 16) * pairs_per_jet
    assert engine.metrics.counter("rows_kernel") == 8 + 16


def test_part_path_refuses_a_jedinet_config():
    from repro.core.interaction_net import JediNetConfig, init
    cfg = JediNetConfig(n_objects=8, n_features=4)
    with pytest.raises(ValueError, match="serves part models"):
        ServingEngine(init(jax.random.PRNGKey(0), cfg), cfg, forward="part",
                      mesh=None)


def test_published_widths_count():
    """2,143,896 learned numbers; the program's FLOPs exceed the
    model's 667,225,600 by the pairs a path evaluates beyond the 8,128
    unordered ones: on ``part`` the 64 masked lanes of its packing, on
    ``part_xla`` the redundant half of all ordered pairs."""
    cfg = pt.ParticleTransformerConfig()
    params = jax.eval_shape(lambda k: pt.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(v.shape)) for v in jax.tree.leaves(params)) \
        == pt.weight_count(cfg) == 2_143_896
    pair_layer_flops = 2 * (4 * 64 + 64 * 64 + 64 * 64 + 64 * 8)
    unordered = 128 * 127 // 2
    assert pt.pairs_per_sample(cfg) == 8192
    assert pt.flops_per_sample(cfg) == 667_225_600 + pair_layer_flops * (
        8192 - unordered)
    assert pt.flops_per_sample(cfg, pt.pairs_per_sample_xla) \
        == 667_225_600 + pair_layer_flops * (128 * 128 - unordered)
    assert paths.get("part").flops_for(cfg, 2) == 2 * pt.flops_per_sample(cfg)
    assert paths.get("part_xla").flops_for(cfg, 2) \
        == 2 * pt.flops_per_sample(cfg, pt.pairs_per_sample_xla)

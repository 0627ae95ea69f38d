"""JEDI-linear path: the O(N_o) pooling identity, the fused kernel,
the int8 in-kernel dequant variant, and the linear live-set VMEM model.

The registry-parametrized suites in test_paths.py already check every
jedi path against its registered edge-sum oracle at serving shapes;
this file pins down the properties that make the path worth having —
the identity holds as N_o grows (incl. the 128-track regime the grid
kernel's VMEM model rejects outright), prime batches pad instead of
degrading the tile, and the bytes model really is linear in N_o.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.interaction_net import JediNetConfig, init
from repro.core.int8_path import dequantize_params, quantize_params_int8
from repro.core.jedi_linear_path import (
    JEDI_LINEAR_FUSED_TOLERANCE,
    JEDI_LINEAR_TOLERANCE,
)
from repro.kernels.fused_jedinet import autotune as grid_autotune
from repro.kernels.jedi_linear import autotune, ops, ref


def _setup(n_objects, batch, seed=0):
    cfg = JediNetConfig(n_objects=n_objects, n_features=16)
    params = init(jax.random.PRNGKey(seed), cfg, scale="lecun")
    rng = np.random.RandomState(seed + 1)
    x = jnp.asarray(rng.normal(0, 1, (batch, n_objects, 16)).astype(np.float32))
    return cfg, params, x


def _widths(params):
    return (autotune.mlp_widths(params["fr"]),
            autotune.mlp_widths(params["fo"]),
            autotune.mlp_widths(params["phi"]))


# -- the O(N_o) identity --------------------------------------------------


@pytest.mark.parametrize("n_objects", [8, 30, 50, 128])
def test_pooled_identity_matches_edge_sum_oracle(n_objects):
    """The telescoped (pooled) aggregation equals the explicit masked
    edge-grid sum at every graph size, including 128 tracks where the
    recombination multiplies u_r by 127."""
    cfg, params, x = _setup(n_objects, 4)
    pooled = ref.forward_jedi_linear(params, cfg, x)
    oracle = ref.forward_jedi_linear_edge_sum(params, cfg, x)
    assert pooled.shape == (4, cfg.n_targets)
    err = float(jnp.max(jnp.abs(pooled - oracle)))
    assert err < JEDI_LINEAR_TOLERANCE, (n_objects, err)


def test_identity_is_not_trivially_zero():
    """Guard against a degenerate pass: logits vary across jets and the
    aggregation actually contributes (zeroing u_s changes the output)."""
    cfg, params, x = _setup(30, 4)
    out = ref.forward_jedi_linear(params, cfg, x)
    assert float(jnp.std(out)) > 0
    u_r, u_s, b1 = ref.first_layer_split(params, cfg, x)
    h_no_send = (cfg.n_objects - 1) * (u_r + b1)
    different = ref._tail(params, cfg, x, h_no_send)
    assert float(jnp.max(jnp.abs(out - different))) > 1e-3


# -- the fused kernel -----------------------------------------------------


@pytest.mark.parametrize("n_objects,batch", [(8, 8), (30, 5), (128, 3)])
def test_fused_kernel_matches_oracle(n_objects, batch):
    cfg, params, x = _setup(n_objects, batch)
    got = ops.jedi_linear_forward_full(params, cfg, x, interpret=True)
    oracle = ref.forward_jedi_linear_edge_sum(params, cfg, x)
    err = float(jnp.max(jnp.abs(got - oracle)))
    assert err < JEDI_LINEAR_FUSED_TOLERANCE, (n_objects, batch, err)


def test_pinned_block_b_pads_prime_batch():
    """A pinned tile that does not divide the batch pads up and slices
    back — prime batches keep the caller's tile choice."""
    cfg, params, x = _setup(30, 7)
    got = ops.jedi_linear_forward_full(params, cfg, x, interpret=True,
                                       block_b=4)
    want = ops.jedi_linear_forward_full(params, cfg, x, interpret=True,
                                        block_b=7)
    assert got.shape == (7, cfg.n_targets)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5


def test_int8_in_kernel_dequant_matches_boundary_dequant():
    """int8 weights riding the same kernel (scales folded into the fp32
    accumulator) agree with dequantize-at-the-boundary + fp32 kernel to
    kernel fidelity — the quantization error itself cancels out."""
    cfg, params, x = _setup(30, 5)
    qp = quantize_params_int8(params)
    got = ops.jedi_linear_forward_full(qp, cfg, x, interpret=True)
    want = ops.jedi_linear_forward_full(dequantize_params(qp), cfg, x,
                                        interpret=True)
    err = float(jnp.max(jnp.abs(got - want)))
    assert err < JEDI_LINEAR_FUSED_TOLERANCE, err


# -- the linear live-set model --------------------------------------------


def test_bytes_model_is_linear_in_graph_size():
    cfg, params, _ = _setup(16, 1)
    fr, fo, phi = _widths(params)

    def per(n_o):
        return autotune.linear_forward_bytes_per_sample(n_o, 16, fr, fo, phi)

    # doubling N_o at most doubles the live set (+ the O(1) phi term)
    assert per(128) <= 2 * per(64)
    assert per(64) <= 2 * per(32)
    # and strictly grows
    assert per(32) < per(64) < per(128)


def test_linear_model_fits_where_grid_model_rejects():
    """The headline: at 128 tracks with the widened (256-wide) MLPs the
    untiled grid working set blows the VMEM budget — the slab alone is
    N_o^2 * 256 * 4 B = 16.8 MB — while the linear live set stays under
    a MB: graph size is no longer a VMEM constraint for this path."""
    fr, fo, phi = [256, 256, 256, 8], [256, 256, 256, 24], [256, 256, 256, 5]
    grid = grid_autotune.full_forward_bytes_per_sample(128, 16, fr, fo, phi)
    lin = autotune.linear_forward_bytes_per_sample(128, 16, fr, fo, phi)
    assert not autotune.fits_vmem(grid)
    assert autotune.fits_vmem(lin)
    assert lin * 10 < grid
    # the paper-width 30p config keeps a 10x+ gap too, both fitting
    nfr, nfo, nphi = _widths(_setup(30, 1)[1])
    assert autotune.linear_forward_bytes_per_sample(
        128, 16, nfr, nfo, nphi) * 10 < grid_autotune.\
        full_forward_bytes_per_sample(128, 16, nfr, nfo, nphi)


def test_linear_model_earns_bigger_batch_tiles():
    """No sender slab -> smaller per-sample set than even the smallest
    sender tile of the grid kernel -> a strictly deeper batch tile under
    the same budget."""
    fr, fo, phi = _widths(_setup(30, 1)[1])
    lin = autotune.linear_forward_bytes_per_sample(30, 16, fr, fo, phi)
    tiled = grid_autotune.full_forward_tiled_bytes_per_sample(
        30, 16, fr, fo, phi, block_s=grid_autotune.sender_tile_candidates(30)[0])
    assert lin < tiled
    bb_lin = autotune.pick_block_b_linear(4096, 30, 16, fr, fo, phi)
    bb_grid, _ = grid_autotune.pick_block_b_s(4096, 30, 16, fr, fo, phi)
    assert bb_lin >= bb_grid
    assert bb_lin * lin <= autotune.VMEM_BUDGET_BYTES


def test_kernel_rejects_non_divisible_batch():
    """The raw kernel call is strict — padding is the wrapper's job, and
    the error names the contract."""
    cfg, params, x = _setup(8, 5)
    cdt = jnp.float32
    from repro.kernels.fused_jedinet import full_kernel as FK
    from repro.kernels.fused_jedinet import kernel as K
    from repro.kernels.jedi_linear import linear_kernel as LK
    frs = K.split_first_layer(params["fr"], cfg.n_features, dtype=cdt)
    with pytest.raises(ValueError, match="pad_batch"):
        LK.jedi_linear_kernel_call(
            jnp.transpose(x, (1, 0, 2)), [frs[0], frs[1], frs[2], *frs[3]],
            FK.flatten_mlp(params["fo"], cdt),
            FK.flatten_mlp(params["phi"], cdt),
            activation=cfg.activation, n_targets=cfg.n_targets,
            block_b=4, interpret=True)

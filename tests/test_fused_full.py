"""Whole-network fused kernel: equivalence, tiling/padding, precision.

The acceptance bar for ``forward_fused_full`` is max abs err < 1e-4 vs
``forward_sr`` in fp32 interpret mode.  Tests use LeCun-init weights and
the standardized jet generator so logits sit at trained-model scale
(O(1)-O(10)); He init on an UNTRAINED net blows activations up ~N_o-fold
per message hop, which turns fp32 reordering noise into O(1e-4) absolute
differences that say nothing about the kernel.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import codesign, interaction_net as inet
from repro.data.jets import make_jets
from repro.kernels.fused_jedinet import autotune
from repro.kernels.fused_jedinet import ops as fj_ops


def _setup(n_o, fr_hidden, fo_hidden, batch, **cfg_kw):
    cfg = inet.JediNetConfig(n_objects=n_o, n_features=16,
                             fr_hidden=fr_hidden, fo_hidden=fo_hidden,
                             **cfg_kw)
    params = inet.init(jax.random.PRNGKey(0), cfg, scale="lecun")
    x, _ = make_jets(np.random.RandomState(1), batch, n_o)
    return cfg, params, jnp.asarray(x)


# --- equivalence vs forward_sr (the acceptance criterion) -------------------

@pytest.mark.parametrize("n_o,fr,fo,batch", [
    (30, (20, 20, 20), (20, 20, 20), 4),     # paper 30p
    (50, (8, 8), (32, 32, 32), 4),           # paper U4-like 50p
])
def test_fused_full_equals_sr_fp32(n_o, fr, fo, batch):
    cfg, params, x = _setup(n_o, fr, fo, batch)
    sr = inet.forward_sr(params, cfg, x)
    full = inet.forward_fused_full(params, cfg, x, interpret=True)
    assert full.dtype == jnp.float32
    err = float(jnp.max(jnp.abs(sr - full)))
    assert err < 1e-4, f"max abs err {err:.2e} >= 1e-4"


@pytest.mark.parametrize("n_o,fr,block_s,lane_pack", [
    (30, (20, 20, 20), 6, 6),      # 30p widths: 120 of 128 lanes, 5 steps
    (30, (20, 20, 20), 30, 6),     # 30p widths, one sender step
    (50, (50, 50, 50), 2, 2),      # 50p widths: one packed row per step
    (50, (50, 50, 50), 10, 2),
    (50, (50, 50, 50), 50, 2),
])
def test_lane_packed_fused_full_equals_sr_fp32(n_o, fr, block_s, lane_pack):
    """The kernel packs k edges per 128-lane row at the published
    widths; at every sender tile it may run, it meets the same bar
    against forward_sr as the unpacked kernel did."""
    cfg, params, x = _setup(n_o, fr, fr, 3)
    tiles = autotune.modeled_residency(cfg, params, 3, block_s=block_s)
    assert (tiles["lane_pack"], tiles["block_s"]) == (lane_pack, block_s)
    sr = inet.forward_sr(params, cfg, x)
    full = fj_ops.fused_forward_full(params, cfg, x, interpret=True,
                                     block_s=block_s)
    err = float(jnp.max(jnp.abs(sr - full)))
    assert err < 1e-4, f"max abs err {err:.2e} >= 1e-4"


@pytest.mark.parametrize("batch", [1, 3, 7, 13, 17])
def test_fused_full_odd_prime_batches(batch):
    """Non-divisible batches are padded to the tile, never degraded."""
    cfg, params, x = _setup(30, (20, 20, 20), (20, 20, 20), batch)
    sr = inet.forward_sr(params, cfg, x)
    full = inet.forward_fused_full(params, cfg, x, interpret=True)
    assert full.shape == (batch, cfg.n_targets)
    np.testing.assert_allclose(np.asarray(sr), np.asarray(full),
                               rtol=1e-4, atol=1e-4)


def test_fused_full_explicit_block_b_padding():
    """block_b > batch and block_b ∤ batch both work via padding."""
    cfg, params, x = _setup(13, (16, 12), (10,), 7)
    base = fj_ops.fused_forward_full(params, cfg, x, interpret=True,
                                     block_b=1)
    for bb in (2, 4, 8, 16):
        out = fj_ops.fused_forward_full(params, cfg, x, interpret=True,
                                        block_b=bb)
        np.testing.assert_allclose(np.asarray(base), np.asarray(out),
                                   rtol=1e-5, atol=1e-6)


def test_fused_full_bf16_vs_fp32():
    """bf16 compute with fp32 accumulation: ~1e-2 of fp32, not garbage."""
    cfg, params, x = _setup(30, (20, 20, 20), (20, 20, 20), 6)
    fp32 = inet.forward_fused_full(params, cfg, x, interpret=True)
    bcfg = cfg.with_(compute_dtype="bfloat16")
    bf16 = inet.forward_fused_full(params, bcfg, x, interpret=True)
    assert bf16.dtype == jnp.float32          # fp32 accumulation out
    err = float(jnp.max(jnp.abs(fp32 - bf16)))
    scale = float(jnp.max(jnp.abs(fp32)))
    assert err < 5e-2 * max(scale, 1.0), (err, scale)
    # and bf16 really changed the numerics (the cast path is live)
    assert err > 0.0


def test_fused_edge_block_bf16_compute_dtype():
    """cfg.compute_dtype threads into the edge kernel too."""
    cfg, params, x = _setup(30, (20, 20), (20,), 4)
    fp32 = fj_ops.fused_edge_block(params["fr"], cfg, x, interpret=True)
    bcfg = cfg.with_(compute_dtype="bfloat16")
    bf16 = fj_ops.fused_edge_block(params["fr"], bcfg, x, interpret=True)
    err = float(jnp.max(jnp.abs(fp32 - bf16)))
    scale = float(jnp.max(jnp.abs(fp32)))
    assert 0.0 < err < 5e-2 * max(scale, 1.0), (err, scale)


def test_path_registered_in_registry():
    from repro.core import paths
    assert "fused_full" in paths.available()
    assert paths.get("fused_full").forward is inet.forward_fused_full


# --- autotuner --------------------------------------------------------------

def test_pick_block_b_prime_batch_not_degraded():
    """The old divisor rule forced block_b=1 on B=1009; the autotuner keeps
    a near-VMEM-optimal balanced tile and relies on padding."""
    per_sample = 30 * 30 * 20 * 4                       # ~72 KB
    bb = autotune.pick_block_b(1009, per_sample)
    assert bb > 1
    assert bb * per_sample <= autotune.VMEM_BUDGET_BYTES
    assert autotune.padded_batch(1009, bb) % bb == 0
    assert autotune.padded_batch(1009, bb) - 1009 < bb  # sub-tile waste


def test_pick_block_b_respects_budget_and_batch():
    # capped by the batch, rounded up to one whole sublane tile
    assert autotune.pick_block_b(4, 1024) == 8
    huge = autotune.VMEM_BUDGET_BYTES                   # 1 sample fills VMEM
    assert autotune.pick_block_b(1024, huge) == 8       # one-tile floor
    # whole batch fits -> one grid step, padded to a sublane multiple
    assert autotune.pick_block_b(100, 1024) == 104
    assert autotune.pick_block_b(1024, 1) == 1024


def test_pick_block_b_balances_steps():
    """Budget tile 96 on B=256: 3 steps either way, so the tile balances
    down to 88 (8 padded rows) instead of 96 (32 padded rows)."""
    per_sample = autotune.VMEM_BUDGET_BYTES // 96
    bb = autotune.pick_block_b(256, per_sample)
    assert bb * per_sample <= autotune.VMEM_BUDGET_BYTES
    steps = autotune.padded_batch(256, bb) // bb
    assert steps == 3
    assert autotune.padded_batch(256, bb) - 256 <= 8
    assert bb % 8 == 0                                  # aligned fits here


def test_pad_batch_shapes_and_zeros():
    x = jnp.ones((7, 5, 3))
    xp = autotune.pad_batch(x, 4)
    assert xp.shape == (8, 5, 3)
    np.testing.assert_array_equal(np.asarray(xp[7]), 0.0)
    assert autotune.pad_batch(x, 7) is x                # exact multiple: no-op


def test_working_set_full_exceeds_edge():
    fr, fo, phi = [20, 20, 20, 8], [20, 20, 20, 24], [20, 20, 20, 5]
    edge = autotune.edge_block_bytes_per_sample(30, 16, fr)
    full = autotune.full_forward_bytes_per_sample(30, 16, fr, fo, phi)
    assert full > edge > 0


# --- codesign model: fusion levels ------------------------------------------

@pytest.mark.parametrize("n_o", [30, 50])
def test_tpu_model_full_strictly_lower_hbm(n_o):
    cfg = inet.JediNetConfig(n_objects=n_o, n_features=16)
    pt = codesign.TPUDesignPoint(cfg=cfg, batch=1024)
    none = codesign.TPUModel.evaluate(pt, "none")
    edge = codesign.TPUModel.evaluate(pt, "edge")
    full = codesign.TPUModel.evaluate(pt, "full")
    assert full["hbm_bytes"] < edge["hbm_bytes"] < none["hbm_bytes"]
    assert full["fused_level"] == "full"
    # the legacy bool levels are gone — False used to coerce silently
    for legacy in (True, False, "both"):
        with pytest.raises(ValueError):
            codesign.TPUModel.evaluate(pt, legacy)
    # quantized weight precision cuts HBM below the same level's fp bill
    int8 = codesign.TPUModel.evaluate(pt, "full", weight_bytes=1)
    assert int8["hbm_bytes"] < full["hbm_bytes"]
    assert int8["weight_bytes"] == 1 and full["weight_bytes"] == 2


def test_explore_uses_full_level_by_default():
    base = inet.JediNetConfig()
    out = codesign.explore(base, max_candidates=40,
                           fr_nl=(1,), fr_size=(8,), fo_first=(16,),
                           n_fr_opts=(29,), r_fo_opts=(1,))
    assert out["n_survivors"] > 0
    for c in out["survivors"]:
        assert c.tpu["fused_level"] == "full"

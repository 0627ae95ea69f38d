"""Sender-tiled whole-network kernel: (block_b, block_s) corner-case
numerics, in-kernel int8 dequant vs the HBM-boundary scheme, the 2D
working-set autotuner, the quantization-aware bucket policy, and the
large-graph (N_o=128) regime the untiled kernel's model rejects."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import interaction_net as inet
from repro.core import paths
from repro.core.int8_path import dequantize_params, quantize_params_int8
from repro.data.jets import TRACKS_N, make_jets, make_tracks
from repro.kernels import autotune as shared_autotune
from repro.kernels.fused_jedinet import autotune
from repro.kernels.fused_jedinet import full_kernel as FK
from repro.kernels.fused_jedinet import ops as fj_ops


def _setup(n_o, fr_hidden, fo_hidden, batch, **cfg_kw):
    cfg = inet.JediNetConfig(n_objects=n_o, n_features=16,
                             fr_hidden=fr_hidden, fo_hidden=fo_hidden,
                             **cfg_kw)
    params = inet.init(jax.random.PRNGKey(0), cfg, scale="lecun")
    x, _ = make_jets(np.random.RandomState(1), batch, n_o)
    return cfg, params, jnp.asarray(x)


# --- (block_b, block_s) corner-case numerics vs the spec reference ----------


@pytest.mark.parametrize("block_s", [
    5,       # below every candidate (f_R packs k = 6): rounds up to 6
    6,       # a candidate, 6 | 12 and k | 6: 2 sender steps
    12,      # block_s == N_o: degenerate single sender step (untiled)
    16,      # block_s > N_o: clamped to N_o
])
@pytest.mark.parametrize("block_b", [1, 3, 4])
def test_tiled_matches_reference_across_corner_tiles(block_s, block_b):
    """Every (block_b, block_s) combination — several sender steps,
    degenerate full-axis tiles, non-dividing batch tiles — matches the
    path's declared reference within its declared tolerance."""
    spec = paths.get("fused_full")
    cfg, params, x = _setup(12, (16, 12), (10,), 7)
    ref = spec.ref(params, cfg, x)
    out = fj_ops.fused_forward_full(params, cfg, x, interpret=True,
                                    block_b=block_b, block_s=block_s)
    err = float(jnp.max(jnp.abs(ref - out)))
    assert err < spec.tolerance, (block_b, block_s, err)


@pytest.mark.parametrize("batch", [1, 3, 7, 11])
def test_tiled_prime_batches_with_sender_remainder(batch):
    """Prime batches (padded batch tiles) x a non-dividing pinned sender
    tile (8 rounds down to 6, the largest candidate of 30 below it)."""
    spec = paths.get("fused_full")
    cfg, params, x = _setup(30, (20, 20, 20), (20, 20, 20), batch)
    ref = spec.ref(params, cfg, x)
    out = fj_ops.fused_forward_full(params, cfg, x, interpret=True,
                                    block_b=4, block_s=8)   # 30 = 5*6
    assert out.shape == (batch, cfg.n_targets)
    err = float(jnp.max(jnp.abs(ref - out)))
    assert err < spec.tolerance, (batch, err)


def test_block_s_degenerate_equals_untiled_summand_order():
    """block_s = N_o is ONE sender step, the untiled kernel; tilings
    with several sender steps agree with it to fp32 reassociation noise
    only."""
    cfg, params, x = _setup(12, (16, 12), (10,), 4)
    full = fj_ops.fused_forward_full(params, cfg, x, interpret=True,
                                     block_b=4, block_s=12)
    k = autotune.lane_pack(autotune.mlp_widths(params["fr"]), 12)
    for bs in autotune.sender_tile_candidates(12, k)[:-1]:
        tiled = fj_ops.fused_forward_full(params, cfg, x, interpret=True,
                                          block_b=4, block_s=bs)
        np.testing.assert_allclose(np.asarray(full), np.asarray(tiled),
                                   rtol=1e-5, atol=1e-6)


def test_tiled_bf16_compute_dtype_threads_through():
    cfg, params, x = _setup(12, (16, 12), (10,), 4)
    fp32 = fj_ops.fused_forward_full(params, cfg, x, interpret=True,
                                     block_s=4)
    bcfg = cfg.with_(compute_dtype="bfloat16")
    bf16 = fj_ops.fused_forward_full(params, bcfg, x, interpret=True,
                                     block_s=4)
    assert bf16.dtype == jnp.float32
    err = float(jnp.max(jnp.abs(fp32 - bf16)))
    scale = float(jnp.max(jnp.abs(fp32)))
    assert 0.0 < err < 5e-2 * max(scale, 1.0), (err, scale)


def test_unpadded_batch_raises_with_tile_and_vmem_context():
    """The kernel-call guard names the chosen (block_b, block_s) and the
    modeled VMEM bytes — not the bare (bsz, block_b) tuple — so a caller
    that skipped autotune.pad_batch sees what to pad to and why."""
    cfg, params, x = _setup(12, (16, 12), (10,), 7)
    cdt = jnp.dtype(cfg.compute_dtype)
    from repro.kernels.fused_jedinet import kernel as K
    fr = K.split_first_layer(params["fr"], cfg.n_features, dtype=cdt)
    with pytest.raises(ValueError) as ei:
        FK.fused_forward_full_kernel_call(
            jnp.transpose(x, (1, 0, 2)), [fr[0], fr[1], fr[2], *fr[3]],
            FK.flatten_mlp(params["fo"], cdt),
            FK.flatten_mlp(params["phi"], cdt),
            activation=cfg.activation, n_targets=cfg.n_targets,
            block_b=4, block_s=6, interpret=True)
    msg = str(ei.value)
    assert "block_b=4" in msg and "block_s=6" in msg
    assert "VMEM" in msg and "pad_batch" in msg


# --- int8: in-kernel dequant vs the PR-4 HBM-boundary scheme ----------------


@pytest.fixture(scope="module")
def qsetup():
    cfg = inet.JediNetConfig(n_objects=12, n_features=16,
                             fr_hidden=(16, 12), fo_hidden=(10,))
    params = inet.init(jax.random.PRNGKey(0), cfg, scale="lecun")
    x, _ = make_jets(np.random.RandomState(1), 5, 12)
    return cfg, quantize_params_int8(params), jnp.asarray(x)


def test_int8_weights_reach_the_kernel_as_int8(qsetup):
    """The quantized params are passed VERBATIM: flatten/split keep the
    int8 dtype all the way to the kernel operands (1 B/element HBM)."""
    cfg, qp, _ = qsetup
    from repro.kernels.fused_jedinet import kernel as K
    fr = K.split_first_layer(qp["fr"], cfg.n_features, dtype=jnp.float32)
    assert fr[0].dtype == jnp.int8 and fr[1].dtype == jnp.int8
    flat = FK.flatten_mlp(qp["fo"], jnp.float32)
    assert flat[0].dtype == jnp.int8          # weight stays int8
    assert flat[1].dtype == jnp.float32       # bias stays fp32
    assert fj_ops.is_quantized_params(qp)


@pytest.mark.parametrize("block_s", [4, 12])
def test_int8_in_kernel_matches_hbm_boundary_dequant(qsetup, block_s):
    """In-kernel dequant ((h @ W_q) * scale on the fp32 accumulator) vs
    the PR-4 scheme (dequantize at the HBM boundary, kernel sees fp32
    weights): same quantized weights, fp32-reassociation-level agreement
    — and both within the spec tolerance of the XLA reference."""
    cfg, qp, x = qsetup
    spec = paths.get("int8_fused_full")
    in_kernel = fj_ops.fused_forward_full(qp, cfg, x, interpret=True,
                                          block_s=block_s)
    boundary = fj_ops.fused_forward_full(dequantize_params(qp), cfg, x,
                                         interpret=True, block_s=block_s)
    np.testing.assert_allclose(np.asarray(in_kernel), np.asarray(boundary),
                               rtol=1e-4, atol=1e-5)
    ref = spec.ref(qp, cfg, x)
    assert float(jnp.max(jnp.abs(in_kernel - ref))) < spec.tolerance


@pytest.mark.parametrize("arch", ["jedinet-30p", "jedinet-50p"])
def test_int8_lane_packed_matches_its_reference(arch):
    """At the published widths int8_fused_full runs packed (k = 6 at
    30p, 2 at 50p): block-diagonal int8 tensors keep one scale each,
    and the result stays within the path's tolerance of its reference."""
    from repro.configs.registry import get_arch
    cfg = get_arch(arch).model
    spec = paths.get("int8_fused_full")
    qp = spec.prepare_params(inet.init(jax.random.PRNGKey(0), cfg,
                                       scale="lecun"))
    x, _ = make_jets(np.random.RandomState(1), 3, cfg.n_objects)
    x = jnp.asarray(x)
    assert spec.residency_model(cfg, qp, 3)["lane_pack"] > 1
    err = float(jnp.max(jnp.abs(spec.forward(qp, cfg, x, interpret=True)
                                - spec.ref(qp, cfg, x))))
    assert err < spec.tolerance, err


def test_partially_quantized_params_rejected_at_boundary(qsetup):
    """Mixed fp32/int8 pytrees would push fp32 weights through the int8
    scale plumbing — the wrapper rejects them with a clear error."""
    cfg, qp, x = qsetup
    params = inet.init(jax.random.PRNGKey(0), cfg, scale="lecun")
    mixed = {"fr": qp["fr"], "fo": params["fo"], "phi": params["phi"]}
    with pytest.raises(ValueError, match="partially quantized"):
        fj_ops.is_quantized_params(mixed)
    with pytest.raises(ValueError, match="partially quantized"):
        fj_ops.fused_forward_full(mixed, cfg, x, interpret=True)


def test_edge_kernel_rejects_quantized_params(qsetup):
    """The edge-only kernel has no scale plumbing — int8 params must be
    rejected at the boundary, not matmul'd unscaled."""
    cfg, qp, x = qsetup
    with pytest.raises(ValueError, match="fused_forward_full"):
        fj_ops.fused_edge_block(qp["fr"], cfg, x, interpret=True)


def test_int8_path_forward_skips_fp32_materialization(qsetup):
    """The registered path hands the int8 pytree straight to the fused
    wrapper and still meets its tolerance end to end."""
    cfg, qp, x = qsetup
    spec = paths.get("int8_fused_full")
    out = spec.forward(qp, cfg, x, interpret=True)
    err = float(jnp.max(jnp.abs(out - spec.ref(qp, cfg, x))))
    assert err < spec.tolerance


# --- 2D autotuner -----------------------------------------------------------


def _w50():
    return [20, 20, 20, 8], [20, 20, 20, 24], [20, 20, 20, 5]


def test_tiled_live_set_shrinks_with_block_s():
    fr, fo, phi = _w50()
    per = [autotune.full_forward_tiled_bytes_per_sample(50, 16, fr, fo, phi,
                                                        bs)
           for bs in (8, 16, 50)]
    assert per[0] < per[1] < per[2]
    # block_s = N_o reproduces the untiled estimate exactly
    assert per[2] == autotune.full_forward_bytes_per_sample(50, 16, fr, fo,
                                                            phi)


def test_pick_block_b_s_grows_block_b_at_50p():
    """The sender-tiled live set buys >= 1.2x the untiled batch tile at
    N_o=50 (the PR's acceptance ratio; actual gain is ~4x)."""
    fr, fo, phi = _w50()
    untiled_bb = autotune.pick_block_b(
        1024, autotune.full_forward_bytes_per_sample(50, 16, fr, fo, phi))
    bb, bs = autotune.pick_block_b_s(1024, 50, 16, fr, fo, phi)
    assert bs < 50
    assert bb >= 1.2 * untiled_bb, (bb, untiled_bb)


def test_pick_block_b_s_degenerates_to_untiled_for_small_batches():
    """When the whole batch fits untiled, one grid step is the fewest
    and ties break to block_s = N_o — zero sender-loop overhead."""
    fr, fo, phi = _w50()
    bb, bs = autotune.pick_block_b_s(4, 16, 16, fr, fo, phi)
    assert (bb, bs) == (8, 16)


def test_sender_tile_candidates_cover_remainders():
    """Candidates divide N_o, so no sender tile is a clamped remainder."""
    assert autotune.sender_tile_candidates(50) == [1, 2, 5, 10, 25, 50]
    assert autotune.sender_tile_candidates(128) == [1, 2, 4, 8, 16, 32,
                                                    64, 128]
    assert autotune.sender_tile_candidates(5) == [1, 5]


@pytest.mark.parametrize("batch", [1, 2, 4])
def test_pick_block_b_s_never_returns_a_non_fitting_tile(batch):
    """At tiny batches every sender tile ties at block_b = batch, and the
    larger-block_s tie-break used to hand back the UNTILED candidate —
    whose single-sample working set busts the budget on large graphs
    (would OOM VMEM on real hardware; interpret mode hides it).  The
    picker must only tie-break among candidates that actually fit."""
    fr, fo, phi = [128, 128, 8], [64, 64, 24], [32, 32, 5]
    bb, bs = autotune.pick_block_b_s(batch, 128, 16, fr, fo, phi)
    per = autotune.full_forward_tiled_bytes_per_sample(128, 16, fr, fo, phi,
                                                       bs)
    assert autotune.fits_vmem(per)
    assert bb * per <= autotune.VMEM_BUDGET_BYTES


@pytest.mark.parametrize("widths,n_o,k", [
    ([50, 50, 50, 8], 50, 2),      # jedinet-50p: 100 of 128 lanes
    ([20, 20, 20, 8], 30, 6),      # jedinet-30p: 120 of 128 lanes
    ([128, 128, 8], 128, 1),       # tracks-128: the lanes are full
    ([32, 32, 8], 30, 3),          # 128 // 32 = 4 does not divide 30
])
def test_lane_pack_rule(widths, n_o, k):
    """k = 128 // max(f_R widths), lowered until it divides N_o."""
    assert autotune.lane_pack(widths, n_o) == k


def test_sender_tile_candidates_are_multiples_of_the_lane_pack():
    assert autotune.sender_tile_candidates(50, 2) == [2, 10, 50]
    assert autotune.sender_tile_candidates(30, 6) == [6, 30]
    assert autotune.sender_tile_candidates(30, 3) == [3, 6, 15, 30]
    assert autotune.sender_tile_candidates(128, 1) == \
        autotune.sender_tile_candidates(128)
    # a pinned tile rounds to a candidate: down where one is below it,
    # else up to the smallest
    assert autotune.sender_tile(8, 30, 6) == 6
    assert autotune.sender_tile(5, 30, 6) == 6
    assert autotune.sender_tile(25, 50, 2) == 10


@pytest.mark.parametrize("arch,k", [("jedinet-30p", 6), ("jedinet-50p", 2),
                                    ("jedinet-tracks-128", 1)])
def test_modeled_residency_returns_the_lane_pack(arch, k):
    """The tile decision carries k, bills the packed weights, and the
    traced kernel holds a (N_o, block_b, k*D_e) accumulator."""
    from repro.analysis import kernel_audit as KA
    from repro.configs.registry import get_arch
    cfg = get_arch(arch).model
    params = inet.init(jax.random.PRNGKey(0), cfg, scale="lecun")
    spec = paths.get("fused_full")
    model = spec.residency_model(cfg, params, 64)
    assert model["lane_pack"] == k
    assert model["block_s"] % k == 0
    unpacked = shared_autotune.weight_vmem_bytes(params, cfg.compute_dtype)
    assert model["weight_residency_bytes"] == \
        autotune.packed_weight_bytes(params, cfg.compute_dtype, k)
    assert (model["weight_residency_bytes"] > unpacked) == (k > 1)
    (kern,) = [KA.TracedKernel(e) for e in KA.find_pallas_calls(
        KA.trace_forward(spec, cfg, params, 64).jaxpr)]
    assert kern.grid == tuple(model["grid"])
    assert [a.shape for a in kern.scratch_avals] == \
        [(cfg.n_objects, model["block_b"], k * cfg.d_e)]


def test_width_128_f_r_traces_the_unpacked_kernel():
    """k = 1 is the kernel as it was before lane packing: the f_R
    operands go in as split_first_layer returns them, the accumulator is
    (N_o, block_b, D_e), and the body has no lane select or fold."""
    from repro.analysis import kernel_audit as KA
    from repro.kernels.fused_jedinet import kernel as K
    cfg, params, _ = _setup(16, (128,), (10,), 8)
    spec = paths.get("fused_full")
    model = spec.residency_model(cfg, params, 8)
    assert model["lane_pack"] == 1
    assert model["weight_residency_bytes"] == \
        shared_autotune.weight_vmem_bytes(params, cfg.compute_dtype)
    fr = K.split_first_layer(params["fr"], cfg.n_features,
                             dtype=jnp.float32)
    fr = [fr[0], fr[1], fr[2], *fr[3]]
    assert all(a is b for a, b in zip(FK.pack_fr_operands(fr, 1), fr))
    (kern,) = [KA.TracedKernel(e) for e in KA.find_pallas_calls(
        KA.trace_forward(spec, cfg, params, 8).jaxpr)]
    blocks = [tuple(KA._dim(d) for d in bm.block_shape)
              for bm in kern.weight_blocks[:len(fr)]]
    assert blocks == [a.shape for a in fr]
    assert [a.shape for a in kern.scratch_avals] == \
        [(16, model["block_b"], cfg.d_e)]
    prims = {e.primitive.name for e in KA._iter_eqns(kern.kernel_jaxpr)}
    assert not prims & {"iota", "select_n", "slice"}, prims


@pytest.mark.parametrize("arch,ladder", [
    ("jedinet-50p", [8, 16, 32, 64, 128, 256, 512, 1024]),
    ("jedinet-30p", [8, 16, 32, 56, 112, 224, 448, 896, 1064]),
])
def test_serving_ladders_keep_their_rungs(arch, ladder):
    """The serving ladder is the front end's and is derived from the
    unpacked per-sample model: lane packing leaves every rung where it
    was, so plan sizes and padding do not move with the kernel."""
    from repro.configs.registry import get_arch
    cfg = get_arch(arch).model
    params = inet.init(jax.random.PRNGKey(0), cfg, scale="lecun")
    for name in ("fused_full", "int8_fused_full"):
        spec = paths.get(name)
        assert spec.bucket_ladder(cfg, spec.prepare_params(params),
                                  1024) == ladder, name


def test_pick_block_s_fits_beside_pinned_block_b():
    """The one-knob-pinned complement: pinning block_b must tune block_s
    under it (and vice versa via the wrapper), never reuse a partner
    jointly tuned for a different tile."""
    fr, fo, phi = [128, 128, 8], [64, 64, 24], [32, 32, 5]
    for bb in (1, 4, 12):
        bs = autotune.pick_block_s(bb, 128, 16, fr, fo, phi)
        per = autotune.full_forward_tiled_bytes_per_sample(128, 16, fr, fo,
                                                           phi, bs)
        assert bb * per <= autotune.VMEM_BUDGET_BYTES, (bb, bs)
    # an OVERSUBSCRIBED pinned block_b (no sender tile fits beside it)
    # degrades to the smallest live set rather than a larger one
    assert autotune.pick_block_s(1000, 128, 16, fr, fo, phi) == \
        autotune.sender_tile_candidates(128)[0]
    # small graphs: a tiny pinned block_b affords the untiled degenerate
    assert autotune.pick_block_s(1, 30, 16, *_w50()) == 30


def test_untiled_model_rejects_large_graphs_tiled_fits():
    """N_o=128 with f_R width 128: the untiled grid exceeds the VMEM
    budget for a SINGLE sample; the tiled model fits with a real tile."""
    fr, fo, phi = [128, 128, 8], [64, 24], [32, 5]
    untiled = autotune.full_forward_bytes_per_sample(128, 16, fr, fo, phi)
    assert not autotune.fits_vmem(untiled)
    bb, bs = autotune.pick_block_b_s(64, 128, 16, fr, fo, phi)
    tiled = autotune.full_forward_tiled_bytes_per_sample(128, 16, fr, fo,
                                                         phi, bs)
    assert autotune.fits_vmem(tiled)
    assert bb > 1


def test_reserved_bytes_shrink_the_tile():
    fr, fo, phi = _w50()
    bb_free, _ = autotune.pick_block_b_s(1024, 50, 16, fr, fo, phi)
    bb_res, _ = autotune.pick_block_b_s(1024, 50, 16, fr, fo, phi,
                                        reserved_bytes=4 * 2**20)
    assert bb_res < bb_free


# --- quantization-aware bucket policy ---------------------------------------


def test_weight_vmem_bytes_counts_actual_dtypes():
    cfg = inet.JediNetConfig(n_objects=16, n_features=16)
    params = inet.init(jax.random.PRNGKey(0), cfg, scale="lecun")
    fp = shared_autotune.weight_vmem_bytes(params)
    q = shared_autotune.weight_vmem_bytes(quantize_params_int8(params))
    assert 0 < q < fp
    # int8 weights + fp32 biases/scales: well under half the fp32 bill
    assert q < 0.5 * fp
    # fp weights bill at the SHIPPED dtype: bf16 compute halves the
    # weight share (biases stay fp32), int weights are verbatim
    bf16 = shared_autotune.weight_vmem_bytes(params, "bfloat16")
    assert q < bf16 < fp
    assert shared_autotune.weight_vmem_bytes(
        quantize_params_int8(params), "float32") == q


def test_quantized_path_earns_deeper_ladder_when_weights_dominate():
    """With weights big enough to matter against the VMEM budget, the
    int8 path's smaller reservation yields a strictly deeper ladder
    than the fp32 twin's — the per-path policy, resolved through the
    same spec.bucket_ladder the engine uses."""
    cfg = inet.JediNetConfig(n_objects=50, n_features=16,
                             fr_hidden=(256, 256), fo_hidden=(512, 512),
                             phi_hidden=(512, 512))
    params = inet.init(jax.random.PRNGKey(0), cfg, scale="lecun")
    fp_spec, q_spec = paths.get("fused_full"), paths.get("int8_fused_full")
    qparams = q_spec.prepare_params(params)
    fp_ladder = fp_spec.bucket_ladder(cfg, params, 4096)
    q_ladder = q_spec.bucket_ladder(cfg, qparams, 4096)
    q_res = q_spec.reserved_vmem_bytes(cfg, qparams)
    fp_res = fp_spec.reserved_vmem_bytes(cfg, params)
    assert q_res < fp_res
    # same per-sample model, smaller reservation -> larger VMEM tile,
    # and the tile is a rung of the ladder the engine serves
    per = fp_spec.bucket_bytes(cfg, params)
    assert per == q_spec.bucket_bytes(cfg, qparams)

    def tile(reserved):
        return shared_autotune.pick_block_b(
            4096, per, shared_autotune.effective_budget(
                shared_autotune.VMEM_BUDGET_BYTES, reserved))

    assert tile(q_res) > tile(fp_res)
    assert tile(q_res) in q_ladder and tile(fp_res) in fp_ladder


def test_path_bucket_policy_surface():
    """codesign.path_bucket_policy is the one-stop operator view: ladder,
    VMEM model, reservation and per-rung roofline all from the spec."""
    from repro.core import codesign
    cfg = inet.JediNetConfig(n_objects=30, n_features=16)
    params = inet.init(jax.random.PRNGKey(0), cfg, scale="lecun")
    pol = codesign.path_bucket_policy(paths.get("int8_fused_full"), cfg,
                                      params, max_batch=64)
    assert pol["path"] == "int8_fused_full"
    assert pol["weight_bytes"] == 1
    assert pol["bucket_ladder"] == sorted(pol["bucket_ladder"])
    assert set(pol["roofline"]) == set(pol["bucket_ladder"])
    assert pol["reserved_vmem_bytes"] > 0
    for m in pol["roofline"].values():
        assert m["weight_bytes"] == 1


def test_describe_with_cfg_prints_resolved_policy():
    cfg = inet.JediNetConfig(n_objects=16, n_features=16)
    params = inet.init(jax.random.PRNGKey(0), cfg, scale="lecun")
    table = paths.describe(cfg=cfg, params=params, max_batch=32)
    assert "bucket policy" in table and "ladder" in table
    assert "reservedB" in table
    for n in paths.available():
        assert table.count(n) >= 2        # static row + policy row


def test_trigger_serve_list_paths_prints_policy(capsys):
    from repro.launch import trigger_serve
    trigger_serve.main(["--list-paths", "--batch", "32"])
    out = capsys.readouterr().out
    assert "wB" in out                     # weight-bytes column
    assert "float32" in out                # compute dtypes
    assert "bucket policy" in out and "ladder" in out
    assert "int8_fused_full" in out


# --- large-graph regime (N_o=128 tracks) ------------------------------------


def test_make_tracks_shapes_and_classes():
    x, y = make_tracks(np.random.RandomState(0), 6)
    assert x.shape == (6, TRACKS_N, 16) and x.dtype == np.float32
    assert y.shape == (6,) and set(np.unique(y)) <= set(range(5))
    assert np.isfinite(x).all()


def test_tracks128_runs_through_tiled_kernel_only():
    """The registered large-graph config: untiled model rejects even one
    sample, the tiled kernel serves it (interpret mode on CPU) and
    matches the XLA reference."""
    from repro.configs.jedi_tracks_128 import MODEL as cfg
    widths = ([*cfg.fr_hidden, cfg.d_e], [*cfg.fo_hidden, cfg.d_o],
              [*cfg.phi_hidden, cfg.n_targets])
    untiled = autotune.full_forward_bytes_per_sample(
        cfg.n_objects, cfg.n_features, *widths)
    assert not autotune.fits_vmem(untiled)

    params = inet.init(jax.random.PRNGKey(0), cfg, scale="lecun")
    x, _ = make_tracks(np.random.RandomState(1), 3)
    x = jnp.asarray(x)
    spec = paths.get("fused_full")
    out = fj_ops.fused_forward_full(params, cfg, x, interpret=True)
    ref = spec.ref(params, cfg, x)
    assert out.shape == (3, cfg.n_targets)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < spec.tolerance, err

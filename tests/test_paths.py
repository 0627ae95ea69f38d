"""Forward-path registry: PathSpec contract, registry-driven numerics
(every registered path vs its own declared reference — no hand-listed
path names), complexity-class metadata + per-path FLOPs hooks, the int8
quantized path end-to-end, per-bucket engine coverage of every
fully-fused path, and the CI gate's baseline bootstrap."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import interaction_net as inet
from repro.core import particle_transformer as pt
from repro.core import paths
from repro.core.int8_path import dequantize_params, quantize_params_int8
from repro.data.jets import make_jets
from repro.serving import PendingPlan, PendingResult, ServingEngine

SEED_PATHS = ("dense", "sr", "sr_split", "fused", "fused_full")


@pytest.fixture(scope="module")
def jedi():
    cfg = inet.JediNetConfig(n_objects=16, n_features=16)
    params = inet.init(jax.random.PRNGKey(0), cfg, scale="lecun")
    x, _ = make_jets(np.random.RandomState(1), 4, 16)
    return cfg, params, jnp.asarray(x)


@pytest.fixture(scope="module")
def part():
    cfg = pt.ParticleTransformerConfig(
        n_objects=16, embed_dims=(32, 64, 32), pair_embed_dims=(8, 8, 8, 4),
        n_heads=4, n_blocks=1, n_cls_blocks=1, ffn_dim=64)
    params = pt.init_params(jax.random.PRNGKey(0), cfg)
    x, _ = make_jets(np.random.RandomState(1), 4, 16, cfg.n_features)
    return cfg, params, jnp.asarray(x)


@pytest.fixture
def model_inputs(request, jedi, part):
    """(cfg, params, x) of the model family a path serves."""
    return {"jedinet": jedi, "part": part}[
        paths.get(request.node.callspec.params["name"]).model]


def _call(spec, params, cfg, x):
    """Invoke a path the way consumers do: interpret mode iff Pallas."""
    if spec.pallas:
        return spec.forward(params, cfg, x, interpret=True)
    return spec.forward(params, cfg, x)


# -- registry ------------------------------------------------------------


def test_registry_has_seed_paths_and_registered_extensions():
    names = paths.available()
    for n in SEED_PATHS:
        assert n in names
    for n in ("int8_fused_full", "jedi_linear", "jedi_linear_full",
              "int8_jedi_linear_full"):
        assert n in names


def test_get_unknown_path_lists_choices():
    with pytest.raises(ValueError, match="fused_full"):
        paths.get("nope")


def test_tag_filters():
    assert paths.available(quantized=True) == [
        "int8_fused_full", "int8_jedi_linear_full"]
    assert set(paths.available(pallas=True)) == {
        "fused", "fused_full", "int8_fused_full",
        "jedi_linear_full", "int8_jedi_linear_full", "part"}
    assert set(paths.available(fused_level="full")) == {
        "fused_full", "int8_fused_full",
        "jedi_linear_full", "int8_jedi_linear_full"}
    with pytest.raises(ValueError, match="filter"):
        paths.available(is_quantized=True)


def test_complexity_is_a_tag_filter():
    assert set(paths.available(complexity="O(N)")) == {
        "jedi_linear", "jedi_linear_full", "int8_jedi_linear_full"}
    # everything else declares the dense edge-grid class
    assert set(paths.available(complexity="O(N^2)")) \
        == set(paths.available()) - set(paths.available(complexity="O(N)"))


def test_register_rejects_duplicates_and_bad_level():
    spec = paths.get("sr")
    with pytest.raises(ValueError, match="already registered"):
        paths.register(spec)
    with pytest.raises(ValueError, match="fused_level"):
        paths.PathSpec(name="x", forward=lambda *a: None,
                       ref=lambda *a: None, fused_level="both")
    # complexity is a validated vocabulary, not free text
    with pytest.raises(ValueError, match="complexity"):
        paths.PathSpec(name="x", forward=lambda *a: None,
                       ref=lambda *a: None, complexity="linear")


def test_legacy_view_surfaces_are_gone():
    """The pre-registry API is retired for real: no forward-fn dict on
    interaction_net, no lazy path-name snapshot on the serving modules.
    (tests/test_repo_hygiene.py greps the names out of the source too.)"""
    from repro import serving
    from repro.serving import engine
    legacy_dict = "FORWARD" + "_FNS"          # dodge the hygiene grep
    legacy_snap = "PALLAS" + "_PATHS"
    assert not hasattr(inet, legacy_dict)
    assert not hasattr(serving, legacy_snap)
    assert not hasattr(engine, legacy_snap)


def test_flops_hook_defaults_to_dense_and_overrides_for_linear():
    """The per-path FLOPs hook: O(N^2) paths bill the dense edge-grid
    model, O(N) paths their own linear model — and the gap grows with
    N_o (that is the whole point of JEDI-linear)."""
    from repro.core import codesign
    big = inet.JediNetConfig(n_objects=128, n_features=16)
    small = inet.JediNetConfig(n_objects=30, n_features=16)
    dense, lin = paths.get("fused_full"), paths.get("jedi_linear_full")
    assert dense.flops_for(big, 4) == codesign.TPUModel.flops(big, 4)
    assert lin.flops_for(big, 4) == codesign.jedi_linear_flops(big, 4)
    ratio_small = dense.flops_for(small, 1) / lin.flops_for(small, 1)
    ratio_big = dense.flops_for(big, 1) / lin.flops_for(big, 1)
    assert ratio_small > 2.0            # already ahead at N_o=30
    assert ratio_big > ratio_small * 2  # and pulling away at N_o=128


def test_roofline_uses_path_flops_model():
    """spec.roofline_for threads the FLOPs hook into TPUModel, so the
    O(N) path's compute term — and any compute-bound bucket — reflects
    linear aggregation, not the dense grid."""
    cfg = inet.JediNetConfig(n_objects=128, n_features=16)
    lin = paths.get("jedi_linear_full").roofline_for(cfg, [1024])[1024]
    dense = paths.get("fused_full").roofline_for(cfg, [1024])[1024]
    assert lin["flops"] < dense["flops"] / 10
    assert lin["hbm_bytes"] == dense["hbm_bytes"]   # same "full" traffic
    assert lin["step_us"] <= dense["step_us"]


def test_describe_mentions_every_path_and_complexity():
    table = paths.describe()
    for n in paths.available():
        assert n in table
    assert "cmplx" in table
    jl_row = next(ln for ln in table.splitlines()
                  if ln.startswith("jedi_linear_full"))
    assert "O(N)" in jl_row


# -- fallback chains (the serving degradation ladder's contract) ---------


def _temp_spec(name, *, pallas=False, fallback=None):
    base = paths.get("sr")
    return paths.PathSpec(name=name, forward=base.forward, ref=base.ref,
                          pallas=pallas, fallback=fallback)


@pytest.fixture
def scratch_registry():
    """Register-and-cleanup helper for chain-shape tests."""
    added = []

    def add(name, **kw):
        paths.register(_temp_spec(name, **kw), overwrite=True)
        added.append(name)

    yield add
    for name in added:
        paths._REGISTRY.pop(name, None)


def test_fallback_chain_of_builtin_paths():
    assert paths.fallback_chain("fused_full") == ["fused_full", "sr_split"]
    assert paths.fallback_chain("int8_fused_full") == [
        "int8_fused_full", "fused_full", "sr_split"]
    # the jedi ladder demotes to the SAME model in XLA before crossing
    # back to the O(N^2) reference
    assert paths.fallback_chain("jedi_linear_full") == [
        "jedi_linear_full", "jedi_linear", "sr_split"]
    assert paths.fallback_chain("int8_jedi_linear_full") == [
        "int8_jedi_linear_full", "jedi_linear_full", "jedi_linear",
        "sr_split"]
    # a terminal non-Pallas path is its own one-rung chain
    assert paths.fallback_chain("sr") == ["sr"]


def test_every_registered_chain_validates():
    """Registry-wide invariant the resilient engine relies on: every
    path's chain resolves and bottoms out in a non-Pallas rung."""
    chains = paths.validate_fallbacks()
    assert set(chains) == set(paths.available())
    for chain in chains.values():
        assert not paths.get(chain[-1]).pallas


def test_fallback_chain_rejects_cycles(scratch_registry):
    scratch_registry("_t_a", fallback="_t_b")
    scratch_registry("_t_b", fallback="_t_a")
    with pytest.raises(ValueError, match="cycle"):
        paths.fallback_chain("_t_a")


def test_fallback_chain_rejects_unknown_link(scratch_registry):
    scratch_registry("_t_dangling", fallback="_t_no_such_path")
    with pytest.raises(ValueError, match="unknown forward path"):
        paths.fallback_chain("_t_dangling")


def test_fallback_chain_rejects_pallas_terminal(scratch_registry):
    scratch_registry("_t_kernel_only", pallas=True)
    with pytest.raises(ValueError, match="non-Pallas"):
        paths.fallback_chain("_t_kernel_only")


def test_describe_prints_fallback_chains():
    table = paths.describe()
    assert "fallback chain" in table
    fused_row = next(ln for ln in table.splitlines()
                     if ln.startswith("fused_full"))
    assert "sr_split" in fused_row
    int8_row = next(ln for ln in table.splitlines()
                    if ln.startswith("int8_fused_full"))
    assert "fused_full>sr_split" in int8_row


# -- numerics: every registered path vs its spec-declared reference ------


@pytest.mark.parametrize("name", paths.available())
def test_path_matches_its_reference_within_tolerance(name, model_inputs):
    """The registry IS the test matrix: any newly registered path gets
    checked against its own ref fn at its own declared tolerance, on a
    small model of the family it serves."""
    cfg, params, x = model_inputs
    spec = paths.get(name)
    pparams = spec.prepare_params(params)
    got = _call(spec, pparams, cfg, x)
    ref = spec.ref(pparams, cfg, x)
    assert got.shape == (x.shape[0], cfg.n_targets)
    err = float(jnp.max(jnp.abs(got - ref)))
    assert err < spec.tolerance, (
        f"{name}: |forward - ref| = {err:.2e} >= tol {spec.tolerance:.0e}")


@pytest.mark.parametrize("name", paths.available(transform_params=None))
def test_untransformed_paths_accept_raw_params(name, model_inputs):
    """Paths with no transform hook must run on raw init() params
    (prepare_params is the identity)."""
    cfg, params, x = model_inputs
    spec = paths.get(name)
    assert spec.prepare_params(params) is params
    _call(spec, params, cfg, x)


# -- int8 quantized path -------------------------------------------------


def test_int8_quantize_roundtrip_structure(jedi):
    cfg, params, _ = jedi
    qp = quantize_params_int8(params)
    for mlp_name, mlp in qp.items():
        for i, layer in enumerate(mlp["layers"]):
            assert layer["w"].dtype == jnp.int8
            assert float(layer["w_scale"]) > 0
            w = params[mlp_name]["layers"][i]["w"]
            assert layer["w"].shape == w.shape
            # dequantized weights within half a quantization step
            dq = np.asarray(layer["w"], np.float32) * float(layer["w_scale"])
            assert np.abs(dq - np.asarray(w)).max() <= \
                0.5001 * float(layer["w_scale"])
    # dequantize_params restores the {"w", "b"} layer shape
    fp = dequantize_params(qp)
    assert set(fp["fr"]["layers"][0]) == {"w", "b"}


def test_int8_quantization_changes_numerics_but_stays_close(jedi):
    """Quantization loss vs fp32 is real (the int8 path is live) yet
    bounded: per-tensor 8-bit error compounds across the nine MLP layers
    of an UNTRAINED net to O(10%) of logit scale, not garbage."""
    cfg, params, x = jedi
    spec = paths.get("int8_fused_full")
    q_out = _call(spec, spec.prepare_params(params), cfg, x)
    fp_out = inet.forward_sr(params, cfg, x)
    err = float(jnp.max(jnp.abs(q_out - fp_out)))
    scale = float(jnp.max(jnp.abs(fp_out)))
    assert err > 0.0
    assert err < 0.15 * max(scale, 1.0), (err, scale)


def test_int8_roofline_bills_one_byte_weights(jedi):
    """The kernel loads int8 weights into VMEM and dequantizes on-chip,
    so the spec declares weight_bytes=1 and the roofline bills 1-byte
    weight traffic — strictly below the fp path at the same level."""
    cfg, _, _ = jedi
    spec = paths.get("int8_fused_full")
    assert spec.weight_bytes == 1
    int8 = spec.roofline_for(cfg, [8])[8]
    fp = paths.get("fused_full").roofline_for(cfg, [8])[8]
    assert int8["fused_level"] == fp["fused_level"] == "full"
    assert int8["hbm_bytes"] < fp["hbm_bytes"]
    assert int8["weight_bytes"] == 1 and fp["weight_bytes"] == 2


def test_engine_serves_int8_with_zero_wiring(jedi):
    """Acceptance: the int8 path registered in its own module is fully
    servable — engine buckets, padding, reassembly — and agrees with its
    spec reference within the spec tolerance."""
    cfg, params, _ = jedi
    eng = ServingEngine(params, cfg, forward="int8_fused_full",
                        interpret=True, max_batch=16)
    spec = eng.spec
    assert spec.quantized
    # the engine holds transformed (int8) params
    assert eng.params["fr"]["layers"][0]["w"].dtype == jnp.int8
    rng = np.random.RandomState(0)
    for bucket in eng.bucket_sizes:
        for n in (bucket, max(1, bucket - 3)):
            x = rng.normal(0, 1, (n, 16, 16)).astype(np.float32)
            got = eng.infer(x)
            ref = np.asarray(spec.ref(eng.params, cfg, jnp.asarray(x)))
            assert np.abs(got - ref).max() < spec.tolerance


@pytest.mark.parametrize("name", paths.available(fused_level="full"))
def test_engine_serves_every_full_path_across_buckets(name, jedi):
    """Registry-parametrized acceptance: EVERY fully-fused path (the
    O(N^2) grid kernels and the O(N) jedi-linear family alike) is
    servable across its whole bucket ladder — exact-fit, padded, and
    prime batch sizes — and agrees with its own declared reference at
    its own declared tolerance."""
    cfg, params, _ = jedi
    eng = ServingEngine(params, cfg, forward=name, interpret=True,
                        max_batch=16)
    spec = eng.spec
    rng = np.random.RandomState(3)
    for bucket in eng.bucket_sizes:
        # exact fit, pad-by-a-few, and a prime that fits nothing evenly
        for n in {bucket, max(1, bucket - 3), min(bucket, 7)}:
            x = rng.normal(0, 1, (n, 16, 16)).astype(np.float32)
            got = eng.infer(x)
            ref = np.asarray(spec.ref(eng.params, cfg, jnp.asarray(x)))
            assert got.shape == (n, cfg.n_targets)
            assert np.abs(got - ref).max() < spec.tolerance, (
                f"{name} bucket={bucket} n={n}")


def test_engine_rejects_unsupported_compute_dtype(jedi):
    cfg, params, _ = jedi
    bcfg = cfg.with_(compute_dtype="bfloat16")
    with pytest.raises(ValueError, match="compute dtypes"):
        ServingEngine(params, bcfg, forward="int8_fused_full",
                      interpret=True, max_batch=8)


def test_loss_fn_resolves_registry_paths(jedi):
    cfg, params, x = jedi
    batch = {"x": x, "y": jnp.zeros((x.shape[0],), jnp.int32)}
    for fwd in ("sr", "int8_fused_full"):
        loss, aux = inet.loss_fn(params, cfg, batch, forward=fwd)
        assert np.isfinite(float(loss))
        assert 0.0 <= float(aux["accuracy"]) <= 1.0


def test_loss_fn_warns_on_quantized_path(jedi):
    """Training through a quantized path silently kills gradients (the
    round has no straight-through estimator) — loss_fn must SAY so,
    naming the path and pointing at the ROADMAP QAT item, and stay
    quiet on fp32 paths."""
    import warnings

    cfg, params, x = jedi
    batch = {"x": x, "y": jnp.zeros((x.shape[0],), jnp.int32)}
    with pytest.warns(UserWarning, match="int8_fused_full.*MXU pipeline"):
        inet.loss_fn(params, cfg, batch, forward="int8_fused_full")
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # fp32 path: no warning
        inet.loss_fn(params, cfg, batch, forward="sr")


# -- async engine dispatch ----------------------------------------------


def test_infer_async_matches_sync(jedi):
    cfg, params, _ = jedi
    eng = ServingEngine(params, cfg, forward="sr", max_batch=8)
    x = np.random.RandomState(2).normal(0, 1, (11, 16, 16)).astype(np.float32)
    pending = eng.infer(x, sync=False)
    assert isinstance(pending, PendingResult)
    got = pending.result()
    assert pending.result() is got                  # idempotent realization
    ref = np.asarray(inet.forward_sr(params, cfg, jnp.asarray(x)))
    assert got.shape == (11, cfg.n_targets)
    assert np.abs(got - ref).max() < 1e-5


def test_async_metrics_recorded_at_result_not_dispatch(jedi):
    cfg, params, _ = jedi
    eng = ServingEngine(params, cfg, forward="sr", max_batch=8)
    x = np.zeros((5, 16, 16), np.float32)
    pending = eng.infer(x, sync=False)
    assert eng.metrics.snapshot()["batches"] == 0   # nothing until realized
    pending.result()
    snap = eng.metrics.snapshot()
    assert snap["batches"] == 1 and snap["events"] == 5
    pending.result()                                # no double counting
    assert eng.metrics.snapshot()["batches"] == 1


def test_async_chunked_wall_not_double_counted(jedi):
    """An oversized request dispatches every chunk before the first
    wait; the recorded wall must be ONE window over the whole dispatch,
    not the sum of overlapping per-chunk latencies."""
    cfg, params, _ = jedi
    eng = ServingEngine(params, cfg, forward="sr", max_batch=8)
    top = eng.bucket_sizes[-1]
    x = np.zeros((3 * top, 16, 16), np.float32)
    t0 = time.perf_counter()
    eng.infer(x)
    elapsed = time.perf_counter() - t0
    snap = eng.metrics.snapshot()
    assert snap["batches"] == 3 and snap["events"] == 3 * top
    # events / kgps-implied-wall <= true elapsed (sum of overlapped
    # latencies would exceed it once chunks overlap)
    implied_wall_s = snap["events"] / (snap["kgps"] * 1e3)
    assert implied_wall_s <= elapsed * 1.05


def test_overlapping_dispatches_wall_is_union_not_sum(jedi):
    """Two sync=False dispatches in flight together: recorded wall is the
    union of their busy windows, so KGPS cannot under-report because the
    caller used the overlap the API advertises."""
    cfg, params, _ = jedi
    eng = ServingEngine(params, cfg, forward="sr", max_batch=8)
    x = np.zeros((8, 16, 16), np.float32)
    t0 = time.perf_counter()
    a = eng.infer(x, sync=False)
    b = eng.infer(x, sync=False)
    a.result(), b.result()
    elapsed = time.perf_counter() - t0
    snap = eng.metrics.snapshot()
    assert snap["events"] == 16
    implied_wall_s = snap["events"] / (snap["kgps"] * 1e3)
    assert implied_wall_s <= elapsed * 1.05


def test_wall_union_handles_out_of_order_realization(jedi):
    """Realizing overlapping dispatches in reverse order must neither
    double-count nor drop busy time (interval-union accounting)."""
    cfg, params, _ = jedi
    eng = ServingEngine(params, cfg, forward="sr", max_batch=8)
    # synthetic windows: A=[0,10] realized AFTER B=[1,11]
    eng._record_wall_window(1.0, 11.0, events=10)
    eng._record_wall_window(0.0, 10.0, events=10)
    assert eng.metrics._wall_s == pytest.approx(11.0)   # union: not 20, not 10
    # a later disjoint window adds exactly its own span
    eng._record_wall_window(20.0, 25.0, events=5)
    assert eng.metrics._wall_s == pytest.approx(16.0)
    assert eng._wall_windows == [(0.0, 11.0), (20.0, 25.0)]
    assert eng.metrics.snapshot()["kgps"] == pytest.approx(25 / 16.0 / 1e3)


def test_infer_bounds_inflight_chunks(jedi):
    """A request many times the top bucket still completes correctly with
    the throttled dispatch pipeline."""
    from repro.serving import engine as engine_mod
    cfg, params, _ = jedi
    eng = ServingEngine(params, cfg, forward="sr", max_batch=4)
    top = eng.bucket_sizes[-1]
    n = top * (engine_mod.MAX_INFLIGHT_CHUNKS + 3) + 1
    x = np.random.RandomState(5).normal(0, 1, (n, 16, 16)).astype(np.float32)
    got = eng.infer(x)
    ref = np.asarray(inet.forward_sr(params, cfg, jnp.asarray(x)))
    assert got.shape == (n, cfg.n_targets)
    assert np.abs(got - ref).max() < 1e-5


def test_run_stream_rejects_oversized_batches(jedi):
    cfg, params, _ = jedi
    eng = ServingEngine(params, cfg, forward="sr", max_batch=8)
    big = np.zeros((eng.bucket_sizes[-1] + 1, 16, 16), np.float32)
    with pytest.raises(ValueError, match="top bucket"):
        eng.run_stream([big, big, big])


def test_run_plan_async_overlaps_flushes(jedi):
    """Two batcher flushes in flight at once, realized afterwards —
    the batcher-overlap pattern the sync escape hatch disables."""
    from repro.serving import DeadlineBatcher
    cfg, params, _ = jedi
    eng = ServingEngine(params, cfg, forward="sr", max_batch=8)
    bat = DeadlineBatcher(eng.bucket_sizes, deadline_s=1.0, clock=lambda: 0.0)
    rng = np.random.RandomState(3)
    xs = {rid: rng.normal(0, 1, (n, 16, 16)).astype(np.float32)
          for rid, n in ((0, 3), (1, 5))}
    in_flight = []
    for rid, x in xs.items():
        bat.submit(rid, x, now=0.0)
        for plan in bat.flush(now=0.0):
            in_flight.append(eng.run_plan(plan, sync=False))
    assert all(isinstance(p, PendingPlan) for p in in_flight)
    results = {}
    for p in in_flight:
        results.update(p.result())
    for rid, x in xs.items():
        ref = np.asarray(inet.forward_sr(params, cfg, jnp.asarray(x)))
        assert np.abs(results[rid] - ref).max() < 1e-5


# -- CI gate: baseline bootstrap for newly registered paths --------------


def _fused_doc(path_entries, calibration=100.0):
    return {"schema": 1, "backend": "cpu", "calibration_us": calibration,
            "configs": {"30p": {"n_objects": 30, "paths": path_entries}}}


def test_check_regression_bootstraps_new_path(tmp_path):
    check_regression = pytest.importorskip("benchmarks.check_regression")
    fresh_dir, base_dir = tmp_path / "fresh", tmp_path / "base"
    fresh_dir.mkdir(), base_dir.mkdir()
    base = _fused_doc({"sr": {"wall_us": 100.0}}, calibration=100.0)
    # fresh machine is 2x slower (calibration 200): times halve on merge
    fresh = _fused_doc({"sr": {"wall_us": 210.0},
                        "int8_fused_full": {"wall_us": 300.0,
                                            "modeled_hbm_bytes": 7085.0}},
                       calibration=200.0)
    (base_dir / "BENCH_fused.json").write_text(json.dumps(base))
    (base_dir / "BENCH_serving.json").write_text(json.dumps(
        {"schema": 1, "backend": "cpu", "configs": {}}))
    for name, doc in (("BENCH_fused.json", fresh),
                      ("BENCH_serving.json",
                       {"schema": 1, "backend": "cpu", "configs": {}})):
        (fresh_dir / name).write_text(json.dumps(doc))

    rc = check_regression.main(["--fresh-dir", str(fresh_dir),
                                "--baseline-dir", str(base_dir),
                                "--bootstrap"])
    assert rc == 0
    merged = json.loads((base_dir / "BENCH_fused.json").read_text())
    entry = merged["configs"]["30p"]["paths"]["int8_fused_full"]
    # speed-normalized into baseline-machine units; modeled bytes untouched
    assert entry["wall_us"] == pytest.approx(150.0)
    assert entry["modeled_hbm_bytes"] == pytest.approx(7085.0)
    # the pre-existing entry is NOT rewritten by bootstrap
    assert merged["configs"]["30p"]["paths"]["sr"]["wall_us"] == 100.0


def test_check_regression_bootstrap_seeds_missing_baseline_file(tmp_path):
    check_regression = pytest.importorskip("benchmarks.check_regression")
    fresh_dir, base_dir = tmp_path / "fresh", tmp_path / "base"
    fresh_dir.mkdir(), base_dir.mkdir()
    for name in ("BENCH_fused.json", "BENCH_serving.json"):
        (fresh_dir / name).write_text(json.dumps(_fused_doc({})))
    rc = check_regression.main(["--fresh-dir", str(fresh_dir),
                                "--baseline-dir", str(base_dir),
                                "--bootstrap"])
    assert rc == 0
    for name in ("BENCH_fused.json", "BENCH_serving.json"):
        assert (base_dir / name).exists()


def test_check_regression_still_gates_existing_entries(tmp_path):
    """Bootstrap only seeds NEW entries — a regression on a gated path
    still fails even with --bootstrap."""
    check_regression = pytest.importorskip("benchmarks.check_regression")
    fresh_dir, base_dir = tmp_path / "fresh", tmp_path / "base"
    fresh_dir.mkdir(), base_dir.mkdir()
    (base_dir / "BENCH_fused.json").write_text(json.dumps(
        _fused_doc({"sr": {"wall_us": 100.0}})))
    (fresh_dir / "BENCH_fused.json").write_text(json.dumps(
        _fused_doc({"sr": {"wall_us": 500.0}})))
    for d in (base_dir, fresh_dir):
        (d / "BENCH_serving.json").write_text(json.dumps(
            {"schema": 1, "backend": "cpu", "configs": {}}))
    rc = check_regression.main(["--fresh-dir", str(fresh_dir),
                                "--baseline-dir", str(base_dir),
                                "--bootstrap"])
    assert rc == 1


def test_check_regression_names_unseeded_paths_in_recipe(tmp_path, capsys):
    """Introducing a path without --bootstrap must not fail the gate,
    but the printed recipe names the unseeded entry explicitly — it
    cannot linger as an ignorable info line."""
    check_regression = pytest.importorskip("benchmarks.check_regression")
    fresh_dir, base_dir = tmp_path / "fresh", tmp_path / "base"
    fresh_dir.mkdir(), base_dir.mkdir()
    (base_dir / "BENCH_fused.json").write_text(json.dumps(
        _fused_doc({"fused_full": {"wall_us": 100.0}})))
    (fresh_dir / "BENCH_fused.json").write_text(json.dumps(
        _fused_doc({"fused_full": {"wall_us": 100.0},
                    "jedi_linear_full": {"wall_us": 40.0}})))
    for d in (base_dir, fresh_dir):
        (d / "BENCH_serving.json").write_text(json.dumps(
            {"schema": 1, "backend": "cpu", "configs": {}}))
    rc = check_regression.main(["--fresh-dir", str(fresh_dir),
                                "--baseline-dir", str(base_dir)])
    out = capsys.readouterr().out
    assert rc == 0                          # growth is not a regression
    assert "30p/jedi_linear_full" in out    # ...but it IS named
    assert "--bootstrap" in out


def test_check_regression_missing_baseline_fails_with_recipe(tmp_path,
                                                             capsys):
    """No committed baseline and no --bootstrap: the gate must FAIL (a
    silently green gate hides regressions forever) and print the exact
    bootstrap command instead of a raw traceback."""
    check_regression = pytest.importorskip("benchmarks.check_regression")
    fresh_dir, base_dir = tmp_path / "fresh", tmp_path / "base"
    fresh_dir.mkdir(), base_dir.mkdir()
    for name in ("BENCH_fused.json", "BENCH_serving.json"):
        (fresh_dir / name).write_text(json.dumps(_fused_doc({})))
    rc = check_regression.main(["--fresh-dir", str(fresh_dir),
                                "--baseline-dir", str(base_dir)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "no committed baseline" in out
    assert "--bootstrap" in out          # the remedy, spelled out
    assert "Traceback" not in out


def test_check_regression_corrupt_baseline_warns_and_fails(tmp_path,
                                                           capsys):
    """A truncated/garbage baseline file is a clear verdict with a
    regeneration recipe, never a JSONDecodeError traceback."""
    check_regression = pytest.importorskip("benchmarks.check_regression")
    fresh_dir, base_dir = tmp_path / "fresh", tmp_path / "base"
    fresh_dir.mkdir(), base_dir.mkdir()
    for name in ("BENCH_fused.json", "BENCH_serving.json"):
        (fresh_dir / name).write_text(json.dumps(_fused_doc({})))
        (base_dir / name).write_text("{ not json")
    rc = check_regression.main(["--fresh-dir", str(fresh_dir),
                                "--baseline-dir", str(base_dir)])
    out = capsys.readouterr().out
    assert rc == 1
    assert "not valid JSON" in out and "benchmarks.run" in out


def test_check_regression_warns_on_calibration_mismatch(tmp_path, capsys):
    """Calibration stamps >1.5x apart mean the two payloads were NOT
    measured in the same quiet window — the gate still runs (the
    yardstick normalizes), but it must warn LOUDLY with the regenerate
    recipe rather than quietly leaning on the normalization."""
    check_regression = pytest.importorskip("benchmarks.check_regression")
    fresh_dir, base_dir = tmp_path / "fresh", tmp_path / "base"
    fresh_dir.mkdir(), base_dir.mkdir()
    (base_dir / "BENCH_fused.json").write_text(json.dumps(
        _fused_doc({"sr": {"wall_us": 100.0}}, calibration=100.0)))
    (fresh_dir / "BENCH_fused.json").write_text(json.dumps(
        _fused_doc({"sr": {"wall_us": 250.0}}, calibration=250.0)))
    for d in (base_dir, fresh_dir):
        (d / "BENCH_serving.json").write_text(json.dumps(
            {"schema": 1, "backend": "cpu", "configs": {}}))
    rc = check_regression.main(["--fresh-dir", str(fresh_dir),
                                "--baseline-dir", str(base_dir)])
    out = capsys.readouterr().out
    assert rc == 0                    # normalized 250/2.5 = 100: no regress
    assert "WARN: calibration stamps differ by 2.50x" in out
    assert "SAME QUIET WINDOW" in out


def test_check_regression_quiet_when_calibration_close(tmp_path, capsys):
    """Stamps within 1.5x: no banner — the warning must stay a signal,
    not ambient noise on every healthy run."""
    check_regression = pytest.importorskip("benchmarks.check_regression")
    fresh_dir, base_dir = tmp_path / "fresh", tmp_path / "base"
    fresh_dir.mkdir(), base_dir.mkdir()
    (base_dir / "BENCH_fused.json").write_text(json.dumps(
        _fused_doc({"sr": {"wall_us": 100.0}}, calibration=100.0)))
    (fresh_dir / "BENCH_fused.json").write_text(json.dumps(
        _fused_doc({"sr": {"wall_us": 120.0}}, calibration=120.0)))
    for d in (base_dir, fresh_dir):
        (d / "BENCH_serving.json").write_text(json.dumps(
            {"schema": 1, "backend": "cpu", "configs": {}}))
    rc = check_regression.main(["--fresh-dir", str(fresh_dir),
                                "--baseline-dir", str(base_dir)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "SAME QUIET WINDOW" not in out

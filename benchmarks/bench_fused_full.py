"""Fused-path trajectory benchmark: per-path wall-clock + modeled HBM bytes.

Measures every registered forward path (:mod:`repro.core.paths`) on the
paper's 30p / 50p configs and pairs each wall-clock with the TPUModel's
modeled HBM traffic at the path's declared fusion level and weight
precision — both read off the :class:`~repro.core.paths.PathSpec`, so a
newly registered path (e.g. the int8 quantized one) lands in this
benchmark, the emitted ``BENCH_fused.json`` and the CI regression gate
with zero edits here.  Each path's numerical error against its own
spec-declared reference fn rides along in the payload so the JSON
records correctness next to speed.

Whole-network ("full") Pallas paths additionally record their autotuned
``(block_b, block_s)`` against the UNTILED model's ``block_b`` at the
modeled batch: the sender-tiled kernel's live set shrinks ~N_o/block_s,
so the batch tile — and with it weight-traffic amortization — grows by
the ratio (``block_b_gain`` in the payload is the cross-PR acceptance
number for the tiling rework).

A large-graph entry (``tracks128``: N_o=128 track-level events,
``configs/jedi_tracks_128``) proves the tiled kernel serves graphs the
untiled working-set model REJECTS (even block_b=1 exceeds the VMEM
budget — ``untiled_rejected`` in the payload); it runs the fp32
``fused_full`` path as ``fp32_fused_full_large``, interpret-mode on CPU.

Pallas paths run in interpret mode off-TPU: their wall-clock is a CPU
emulation (flagged ``"interpret": true`` in the JSON) — the HBM model is
the cross-PR comparable number there, exactly as in bench_fusion.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, select_paths, time_fn
from repro.core import codesign, paths
from repro.core import interaction_net as inet
from repro.data.jets import make_tracks
from repro.kernels.fused_jedinet import autotune as fj_autotune
from repro.kernels.jedi_linear import autotune as jl_autotune

# filled by run(); benchmarks/run.py serializes it to BENCH_fused.json
JSON_PAYLOAD: dict = {}


def _measure(spec, params, cfg, x, interpret: bool):
    if spec.pallas:
        call = jax.jit(lambda p, x_: spec.forward(p, cfg, x_,
                                                  interpret=interpret))
    else:
        call = jax.jit(lambda p, x_: spec.forward(p, cfg, x_))
    iters = 3 if interpret else 10
    return time_fn(call, params, x, warmup=1, iters=iters)


def _entry(spec, us, batch, interpret, hbm, model_batch, err):
    """One payload entry, shared by the per-config loop and tracks128 so
    the schema the regression gate parses cannot diverge between them."""
    return {
        "wall_us": us,
        "batch": batch,
        "interpret": interpret,
        "fused_level": spec.fused_level,
        "quantized": spec.quantized,
        "modeled_hbm_bytes": hbm,
        "modeled_hbm_batch": model_batch,
        "max_abs_err_vs_ref": err,
        "ref_tolerance": spec.tolerance,
    }


def _widths(params):
    return (fj_autotune.mlp_widths(params["fr"]),
            fj_autotune.mlp_widths(params["fo"]),
            fj_autotune.mlp_widths(params["phi"]))


def _linear_tiling(cfg, params, batch: int) -> dict:
    """Batch tile + per-sample live set under the LINEAR model — the
    O(N) kernel has no sender axis, so the grid autotuner's
    (block_b, block_s) numbers do not describe it."""
    fr_w, fo_w, phi_w = _widths(params)
    return {
        "autotuned_block_b": jl_autotune.pick_block_b_linear(
            batch, cfg.n_objects, cfg.n_features, fr_w, fo_w, phi_w,
            reserved_bytes=jl_autotune.weight_vmem_bytes(
                params, cfg.compute_dtype)),
        "linear_per_sample_bytes": jl_autotune.linear_forward_bytes_per_sample(
            cfg.n_objects, cfg.n_features, fr_w, fo_w, phi_w),
    }


def _tiling(cfg, params, batch: int) -> dict:
    """Autotuned tiled (block_b, block_s) vs the untiled model's block_b
    at the same batch — the sender-tiling acceptance numbers.  BOTH
    sides run under the same weight-reserved budget, so block_b_gain
    isolates the tiling effect (not the reservation policy)."""
    fr_w, fo_w, phi_w = _widths(params)
    tiles = fj_autotune.modeled_residency(cfg, params, batch)
    reserved = tiles["reserved_bytes"]
    budget = fj_autotune.effective_budget(
        fj_autotune.VMEM_BUDGET_BYTES, reserved)
    untiled_per = fj_autotune.full_forward_bytes_per_sample(
        cfg.n_objects, cfg.n_features, fr_w, fo_w, phi_w)
    untiled_fits = fj_autotune.fits_vmem(untiled_per, budget)
    untiled_bb = fj_autotune.pick_block_b(batch, untiled_per, budget)
    bb, bs = tiles["block_b"], tiles["block_s"]
    return {
        "autotuned_block_b": bb,
        "autotuned_block_s": bs,
        "lane_pack": tiles["lane_pack"],
        "untiled_block_b": untiled_bb,
        "untiled_per_sample_bytes": untiled_per,
        "untiled_rejected": not untiled_fits,
        "block_b_gain": bb / max(untiled_bb, 1),
    }


def run():
    on_tpu = jax.default_backend() == "tpu"
    rows = []
    payload = {"schema": 1, "backend": jax.default_backend(), "configs": {}}
    names = select_paths()                 # default: the whole registry

    for cname, n_o, batch, ibatch in (("30p", 30, 256, 16),
                                      ("50p", 50, 128, 8)):
        cfg = inet.JediNetConfig(n_objects=n_o, n_features=16)
        params = inet.init(jax.random.PRNGKey(0), cfg, scale="lecun")
        entry = {"n_objects": n_o, "paths": {}}

        for name in names:
            spec = paths.get(name)
            pparams = spec.prepare_params(params)
            interpret = spec.pallas and not on_tpu
            b = ibatch if interpret else batch
            x = jax.random.normal(jax.random.PRNGKey(1), (b, n_o, 16))
            us = _measure(spec, pparams, cfg, x, interpret)
            hbm = codesign.TPUModel.hbm_bytes(
                cfg, batch, 2, spec.fused_level,
                weight_bytes=spec.weight_bytes)
            # path-vs-own-reference error rides along (the spec contract:
            # both fns see the transformed params)
            xq = jax.random.normal(jax.random.PRNGKey(2), (8, n_o, 16))
            fwd = (spec.forward(pparams, cfg, xq, interpret=True)
                   if spec.pallas and not on_tpu
                   else spec.forward(pparams, cfg, xq))
            err = float(jnp.max(jnp.abs(fwd - spec.ref(pparams, cfg, xq))))
            entry["paths"][name] = _entry(spec, us, b, interpret, hbm,
                                          batch, err)
            derived = (f"level={spec.fused_level} "
                       f"modeled_hbm={hbm / 1e6:.2f}MB err={err:.1e}")
            if spec.pallas and spec.fused_level == "full":
                if spec.complexity == "O(N)":
                    tiling = _linear_tiling(cfg, pparams, batch)
                    entry["paths"][name].update(tiling)
                    derived += (f" block_b={tiling['autotuned_block_b']} "
                                "(linear live set, no sender axis)")
                else:
                    tiling = _tiling(cfg, pparams, batch)
                    entry["paths"][name].update(tiling)
                    derived += (f" block_b={tiling['autotuned_block_b']}"
                                f"(x{tiling['block_b_gain']:.1f} vs untiled "
                                f"{tiling['untiled_block_b']})"
                                f" block_s={tiling['autotuned_block_s']}")
            rows.append(row(
                f"fused_paths_{cname}_{name}", us,
                derived + (" (interpret)" if interpret else "")))
        payload["configs"][cname] = entry

    # --- large-graph regime: N_o=128 track-level events ------------------
    # The untiled whole-network kernel cannot hold even ONE sample's
    # (N_o, N_o, H1) grid in the VMEM budget here; the sender-tiled
    # kernel runs it (interpret-mode emulation off-TPU, tiny batch).
    from repro.configs.jedi_tracks_128 import MODEL as large_cfg
    lparams = inet.init(jax.random.PRNGKey(0), large_cfg, scale="lecun")
    lbatch = 512 if on_tpu else 4       # measured batch (interpret is slow)
    model_batch = 512                   # modeled numbers stay backend-
    spec = paths.get("fused_full")      # independent, like 30p/50p above
    tiling = _tiling(large_cfg, lparams, model_batch)
    assert tiling["untiled_rejected"], (
        "tracks128 must exceed the untiled VMEM model "
        f"({tiling['untiled_per_sample_bytes']} B/sample) — "
        "it exists to prove the tiled kernel opens this regime")
    # standardized track-level events (the workload this config models);
    # raw unit-normal inputs would inflate the 127-way sender sums past
    # trained-logit scale and the abs-err column would measure noise
    x = jnp.asarray(make_tracks(np.random.RandomState(1), lbatch,
                                large_cfg.n_objects,
                                large_cfg.n_features)[0])
    us = _measure(spec, lparams, large_cfg, x, not on_tpu)
    xq = x[:2]
    fwd = spec.forward(lparams, large_cfg, xq, interpret=not on_tpu)
    err = float(jnp.max(jnp.abs(fwd - spec.ref(lparams, large_cfg, xq))))
    hbm = codesign.TPUModel.hbm_bytes(large_cfg, model_batch, 2, "full")
    payload["configs"]["tracks128"] = {
        "n_objects": large_cfg.n_objects,
        "paths": {"fp32_fused_full_large": {
            **_entry(spec, us, lbatch, not on_tpu, hbm, model_batch, err),
            **tiling,
        }},
    }
    rows.append(row(
        "fp32_fused_full_large", us,
        f"N_o={large_cfg.n_objects} untiled_rejected="
        f"{tiling['untiled_rejected']} block_b={tiling['autotuned_block_b']} "
        f"block_s={tiling['autotuned_block_s']} err={err:.1e}"
        + ("" if on_tpu else " (interpret)")))

    # head-to-head: the O(N) JEDI-linear kernel in the SAME regime.  128
    # tracks is deep into its scaling win (the f_R grid the fused_full
    # kernel tiles over simply does not exist), so this pair of entries
    # is the measured N_o-scaling crossover record for EXPERIMENTS.md
    # §JEDI-linear.  Different model — its own ref/err, not comparable
    # accuracy-wise, explicitly comparable wall-clock-wise.
    jspec = paths.get("jedi_linear_full")
    jus = _measure(jspec, lparams, large_cfg, x, not on_tpu)
    jfwd = jspec.forward(lparams, large_cfg, xq, interpret=not on_tpu)
    jerr = float(jnp.max(jnp.abs(jfwd - jspec.ref(lparams, large_cfg, xq))))
    jhbm = jspec.roofline_for(large_cfg, [model_batch])[model_batch][
        "hbm_bytes"]
    jtiling = _linear_tiling(large_cfg, lparams, model_batch)
    payload["configs"]["tracks128"]["paths"]["jedi_linear_full_large"] = {
        **_entry(jspec, jus, lbatch, not on_tpu, jhbm, model_batch, jerr),
        **jtiling,
        "speedup_vs_fused_full": us / jus,
    }
    rows.append(row(
        "jedi_linear_full_large", jus,
        f"N_o={large_cfg.n_objects} O(N) "
        f"block_b={jtiling['autotuned_block_b']} err={jerr:.1e} "
        f"speedup_vs_fused_full={us / jus:.1f}x"
        + ("" if on_tpu else " (interpret)")))

    JSON_PAYLOAD.clear()
    JSON_PAYLOAD.update(payload)
    return rows


if __name__ == "__main__":
    from benchmarks.common import print_rows
    print_rows(run())

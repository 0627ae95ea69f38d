"""Static-analysis subsystem: repo-clean gates + broken-fixture bites.

Two families:

* tier-1 wiring — the full lint pass and the full kernel-contract
  audit report ZERO findings on this repo (the same gate CI's
  ``analysis`` job runs via ``python -m repro.analysis``);
* the auditor must BITE — deliberately broken kernels (bf16
  accumulator, BlockSpec/bytes-model 2x disagreement, partially
  quantized pytree) and broken ladder models each produce findings
  with actionable messages.  A checker that cannot detect the bug
  class it exists for is worse than none.

The mini Pallas kernels below live in a test file, outside
``src/repro/kernels/`` — exactly what the ``pallas-containment`` rule
forbids — so this file is sanctioned in ``analysis.toml``.
"""

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.config import AnalysisConfig
from repro.analysis.findings import Finding
from repro.analysis.kernel_audit import audit_path, audit_registry
from repro.analysis.lint import run_lint
from repro.analysis.rules import ALL_RULES
from repro.configs.jedi_30p import MODEL as CFG
from repro.core import interaction_net, paths

REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def params():
    return interaction_net.init(jax.random.PRNGKey(0), CFG)


def _part_model():
    from repro.configs.part_jetclass import MODEL
    from repro.core import particle_transformer as pt
    return MODEL, pt.init_params(jax.random.PRNGKey(0), MODEL)


# ---------------------------------------------------------------------------
# Repo-clean gates (the tier-1 wiring of `python -m repro.analysis`).
# ---------------------------------------------------------------------------

def test_lint_pass_reports_zero_findings():
    findings = run_lint(REPO, ALL_RULES, AnalysisConfig.load(REPO))
    assert findings == [], "\n".join(f.render() for f in findings)


def test_kernel_audit_reports_zero_findings(params):
    findings = audit_registry(CFG, params, max_batch=1024)
    assert findings == [], "\n".join(f.render() for f in findings)


@pytest.mark.parametrize("family", ["jedinet", "part"])
def test_every_pallas_path_is_audited_at_every_rung(params, family):
    """The drift check actually covers each Pallas path's whole ladder:
    the residency model must answer (consistently) at every rung, for
    each model family at its published widths."""
    cfg, params = {"jedinet": (CFG, params), "part": _part_model()}[family]
    specs = paths.specs(pallas=True, model=family)
    assert specs
    for spec in specs:
        assert spec.residency_model is not None, spec.name
        tparams = spec.prepare_params(params)
        ladder = spec.bucket_ladder(cfg, tparams, 1024)
        assert ladder, spec.name
        for rung in ladder:
            model = spec.residency_model(cfg, tparams, rung)
            assert model["fits"], (spec.name, rung)
            assert model["block_b"] * model["per_sample_bytes"] <= \
                model["effective_budget"], (spec.name, rung)


def test_cli_runs_clean_with_json(capsys):
    from repro.analysis.__main__ import main
    rc = main(["--json", "--root", str(REPO)])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert doc["count"] == 0 and doc["findings"] == []
    assert set(doc["timings"]) == {"lint_s", "audit_s"}


# ---------------------------------------------------------------------------
# Broken-kernel fixtures: a mini Pallas kernel with tunable defects.
# ---------------------------------------------------------------------------

_D_OUT = 16


def _mini_forward(wparams, cfg, x, *, block_b=8, accum_dtype=jnp.float32):
    """One-matmul Pallas 'network': x (B, N_o, P) -> (B, D) logits.
    ``accum_dtype`` poisons the accumulator path when set to bf16."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w = wparams["w"]
    batch = x.shape[0]
    feat = x.shape[1] * x.shape[2]
    bb = min(block_b, batch)

    def kernel(x_ref, w_ref, o_ref, acc_ref):
        xv = x_ref[...].astype(accum_dtype)
        wv = w_ref[...].astype(accum_dtype)
        acc_ref[...] = jnp.dot(xv, wv, preferred_element_type=accum_dtype)
        o_ref[...] = acc_ref[...]

    return pl.pallas_call(
        kernel,
        grid=(batch // bb,),
        in_specs=[pl.BlockSpec((bb, feat), lambda i: (i, 0)),
                  pl.BlockSpec(w.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec((bb, _D_OUT), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, _D_OUT), accum_dtype),
        scratch_shapes=[pltpu.VMEM((bb, _D_OUT), accum_dtype)],
    )(x.reshape(batch, feat), w)


def _mini_params():
    feat = CFG.n_objects * CFG.n_features
    return {"w": jnp.zeros((feat, _D_OUT), jnp.float32)}


_MINI_PER_SAMPLE = 8192           # generous upper bound on any live tensor


def _mini_residency(cfg, wparams, batch, *, block_b=8, weight_scale=1.0,
                    fits=True):
    return {"kernel": "mini", "block_b": block_b, "block_s": None,
            "grid": (max(batch, block_b) // block_b,),
            "per_sample_bytes": _MINI_PER_SAMPLE,
            "reserved_bytes": int(wparams["w"].nbytes * weight_scale),
            "effective_budget": 4 * 1024 * 1024,
            "weight_residency_bytes": int(wparams["w"].nbytes * weight_scale),
            "fits": fits}


def _mini_spec(name, forward, residency):
    return paths.PathSpec(
        name=name, forward=forward, ref=forward, fused_level="full",
        pallas=True, complexity="O(N)", fallback=None,
        per_sample_bytes=lambda cfg, p: _MINI_PER_SAMPLE,
        residency_model=residency, description="broken-kernel fixture")


def test_auditor_detects_bf16_accumulator():
    def fwd(p, cfg, x, **kw):
        return _mini_forward(p, cfg, x, accum_dtype=jnp.bfloat16)

    findings = audit_path(_mini_spec("bad_bf16", fwd, _mini_residency),
                          CFG, _mini_params(), max_batch=16)
    rules = {f.rule for f in findings}
    assert "audit-accum-dtype" in rules
    text = "\n".join(f.message for f in findings)
    assert "bfloat16" in text and "float32" in text
    # actionable: says what to change, and names both failure sites
    assert "scratch" in text and "dot_general" in text


def test_auditor_detects_blockspec_bytes_model_2x_disagreement():
    def fwd(p, cfg, x, **kw):
        # kernel tiles at 16; the model below claims 8 — and claims the
        # weights occupy HALF the VMEM their BlockSpec actually asks for
        return _mini_forward(p, cfg, x, block_b=16)

    def residency(cfg, p, batch, **kw):
        return _mini_residency(cfg, p, batch, block_b=8, weight_scale=0.5)

    findings = audit_path(_mini_spec("bad_2x", fwd, residency),
                          CFG, _mini_params(), max_batch=16)
    rules = {f.rule for f in findings}
    assert "audit-tile-mismatch" in rules
    assert "audit-vmem-drift" in rules
    tile = next(f for f in findings if f.rule == "audit-tile-mismatch"
                and "batch tile is 16" in f.message)
    assert "block_b=8" in tile.message
    drift = next(f for f in findings if f.rule == "audit-vmem-drift")
    assert "100% drift" in drift.message


def test_auditor_detects_partially_quantized_pytree(params):
    from repro.core.int8_path import quantize_params_int8

    def half_quantize(p):
        q = quantize_params_int8(p)
        return {"fr": q["fr"], "fo": p["fo"], "phi": p["phi"]}

    spec = dataclasses.replace(paths.get("int8_fused_full"),
                               name="int8_partial",
                               transform_params=half_quantize)
    findings = audit_path(spec, CFG, params, max_batch=64)
    assert any(f.rule == "audit-trace-failure"
               and "partially quantized" in f.message for f in findings), \
        "\n".join(f.render() for f in findings)


def test_auditor_detects_ladder_rung_over_budget():
    def fwd(p, cfg, x, **kw):
        return _mini_forward(p, cfg, x)

    def residency(cfg, p, batch, **kw):
        return _mini_residency(cfg, p, batch, fits=False)

    findings = audit_path(_mini_spec("bad_ladder", fwd, residency),
                          CFG, _mini_params(), max_batch=16)
    assert any(f.rule == "audit-ladder-budget" for f in findings)


def test_auditor_flags_pallas_path_without_residency_model(params):
    spec = dataclasses.replace(paths.get("fused_full"),
                               name="no_model", residency_model=None)
    findings = audit_path(spec, CFG, params, max_batch=64)
    assert [f.rule for f in findings] == ["audit-no-residency-model"]


# ---------------------------------------------------------------------------
# Lint rules bite on synthetic trees.
# ---------------------------------------------------------------------------

def _lint_tmp(tmp_path, rule, config=None):
    return run_lint(tmp_path, [rule], config or AnalysisConfig())


def test_pallas_containment_rule_bites(tmp_path):
    from repro.analysis.rules.pallas_containment import PallasContainmentRule
    (tmp_path / "rogue.py").write_text(
        "import jax.experimental.pallas as pl\n"
        "out = pl.pallas_call(lambda r: None, grid=(1,))\n")
    findings = _lint_tmp(tmp_path, PallasContainmentRule())
    assert [f.rule for f in findings] == ["pallas-containment"]
    assert "src/repro/kernels/" in findings[0].message


def test_wall_clock_rule_distinguishes_seams_from_calls(tmp_path):
    from repro.analysis.rules.wall_clock import WallClockRule
    pkg = tmp_path / "src" / "repro" / "serving"
    pkg.mkdir(parents=True)
    (pkg / "hot.py").write_text(
        "import time\n"
        "from time import perf_counter\n"
        "def step(clock=time.monotonic):   # seam: attribute ref, legal\n"
        "    t0 = clock()\n"
        "    t1 = time.time()              # direct call: finding\n"
        "    t2 = perf_counter()           # direct call: finding\n"
        "    return t1 - t0 + t2\n")
    findings = _lint_tmp(tmp_path, WallClockRule())
    assert sorted(f.line for f in findings) == [5, 6]
    assert all("injectable clock seam" in f.message for f in findings)


def test_register_path_decl_rule_bites(tmp_path):
    from repro.analysis.rules.register_path_decl import RegisterPathDeclRule
    pkg = tmp_path / "src" / "repro" / "core"
    pkg.mkdir(parents=True)
    (pkg / "newpath.py").write_text(
        "from repro.core.paths import register_path\n"
        "@register_path(name='mystery', fused_level='none')\n"
        "def forward_mystery(p, cfg, x):\n"
        "    return x\n")
    findings = _lint_tmp(tmp_path, RegisterPathDeclRule())
    assert [f.rule for f in findings] == ["register-path-decl"]
    assert "complexity" in findings[0].message
    assert "fallback" in findings[0].message


def test_retired_names_rule_honors_analysis_toml_allowlist(tmp_path):
    from repro.analysis.rules.retired_names import RetiredNamesRule
    name = "FORWARD" + "_FNS"
    (tmp_path / "sanctioned.md").write_text(f"history: removed {name}\n")
    (tmp_path / "offender.py").write_text(f"{name} = {{}}\n")
    (tmp_path / "analysis.toml").write_text(
        '[rules.retired-names]\nallow = ["sanctioned.md", "analysis.toml"]\n')
    findings = _lint_tmp(tmp_path, RetiredNamesRule(),
                         AnalysisConfig.load(tmp_path))
    assert [f.location for f in findings] == ["offender.py"]


# ---------------------------------------------------------------------------
# Config loader.
# ---------------------------------------------------------------------------

def test_allowlist_glob_matching():
    cfg = AnalysisConfig(allow={"r": ["docs/*.md", "exact.py"]})
    assert cfg.allowed("r", "docs/notes.md")
    assert cfg.allowed("r", "exact.py")
    assert not cfg.allowed("r", "src/exact.py")


def test_findings_are_json_round_trippable():
    f = Finding(rule="r", location="a.py", line=3, message="m")
    assert json.loads(json.dumps(f.as_dict())) == {
        "rule": "r", "location": "a.py", "line": 3, "message": "m"}
    assert f.render() == "[r] a.py:3: m"

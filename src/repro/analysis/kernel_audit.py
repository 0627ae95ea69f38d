"""Kernel-contract auditor: the static half of the autotuner story.

LL-GNN's co-design flow works because hardware constraints — on-chip
residency, accumulator precision — are checked BEFORE synthesis.  This
module is the jax_pallas analogue: for every registered Pallas path it
traces the forward at each rung of the path's own bucket ladder with
``jax.make_jaxpr`` (abstract shapes only — no kernel ever executes),
digs the ``pallas_call`` equations out of the jaxpr, and cross-checks
what the kernel ACTUALLY asks the compiler for against what the
autotuner bytes model CLAIMS it asks for:

* **grid/tile agreement** — the traced grid and the x-operand block
  shape must equal the :attr:`PathSpec.residency_model` hook's decision
  exactly (the hook mirrors the wrapper's tuner invocation, so drift
  here means the hand-written bytes model and the kernel BlockSpecs
  disagree — the silent-drift bug class this auditor exists for);
* **weight residency** — the summed BlockSpec bytes of the non-x
  inputs must match the model's ``weight_residency_bytes`` within
  ``DRIFT_TOLERANCE`` (5%).  This doubles as the int8 proof: weights
  shipped as fp32 instead of int8 would show 4x drift;
* **fp32 accumulation** — every ``dot_general`` inside the kernel, every
  VMEM scratch allocation, and every kernel output must be float32;
* **int8 operand discipline** — quantized paths ship integer dtypes
  into VMEM (every non-x matrix input is integer), carry exactly one
  fp32 scale vector, and fold each scale exactly once (the scales ref
  is read exactly once per integer tensor);
* **intermediate bound** — the largest single tensor materialized inside
  the kernel, per sample, must not exceed the model's
  ``per_sample_bytes`` (within tolerance): the model must be an upper
  bound on any one live tensor or ``fits_vmem`` acceptance is a lie;
* **ladder/budget closure** — every rung the path's bucket ladder hands
  to serving must fit ``effective_budget`` under the model
  (``block_b * per_sample_bytes <= effective_budget`` and ``fits``
  true), closing the gap where a hand-pinned bucket exceeds the weight
  reservation;
* **containment** — non-Pallas paths trace to ZERO pallas_calls, and
  Pallas paths to at least one (the ``pallas=True`` tag is load-bearing
  for serving's interpret-mode fallback, so it must be true).

Findings use ``rule="audit-<check>"`` ids so the same ``analysis.toml``
allowlist machinery scopes sanctioned exceptions.
"""

from __future__ import annotations

import numpy as np

from repro.analysis.findings import Finding

#: Relative VMEM-model drift that fails the audit.
DRIFT_TOLERANCE = 0.05


# ---------------------------------------------------------------------------
# Jaxpr spelunking.
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    """All equations in ``jaxpr`` and every jaxpr nested in its params
    (pjit bodies, scan carries, pallas kernel jaxprs...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in _sub_jaxprs(val):
                yield from _iter_eqns(sub)


def _sub_jaxprs(val):
    from jax.extend import core as jcore
    if isinstance(val, jcore.ClosedJaxpr):
        yield val.jaxpr
    elif isinstance(val, jcore.Jaxpr):
        yield val
    elif isinstance(val, (list, tuple)):
        for item in val:
            yield from _sub_jaxprs(item)


def find_pallas_calls(jaxpr):
    """Every ``pallas_call`` equation reachable from ``jaxpr``."""
    return [e for e in _iter_eqns(jaxpr) if e.primitive.name == "pallas_call"]


def trace_forward(spec, cfg, params, batch: int):
    """``jax.make_jaxpr`` of the path's forward at abstract shapes —
    runs the wrapper's tuner and BlockSpec construction for real, never
    the kernel body."""
    import jax
    import jax.numpy as jnp
    x = jax.ShapeDtypeStruct((batch, cfg.n_objects, cfg.n_features),
                             jnp.float32)
    return jax.make_jaxpr(lambda xv: spec.forward(params, cfg, xv))(x)


def _aval_bytes(aval) -> int:
    return int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize


def _dim(d) -> int:
    """A BlockSpec block dimension as an int (JAX wraps them in
    ``Blocked``, which carries ``block_size``)."""
    return int(getattr(d, "block_size", d))


def _block_bytes(bm) -> int:
    shape = tuple(_dim(d) for d in bm.block_shape)
    return int(np.prod(shape, dtype=np.int64)) * bm.array_aval.dtype.itemsize


class TracedKernel:
    """Structured view of one traced ``pallas_call`` equation."""

    def __init__(self, eqn):
        gm = eqn.params["grid_mapping"]
        self.name = str(eqn.params.get("name_and_src_info", "pallas_call"))
        self.grid = tuple(int(g) for g in gm.grid)
        self.in_blocks = list(gm.block_mappings[:gm.num_inputs])
        self.out_blocks = list(
            gm.block_mappings[gm.num_inputs:gm.num_inputs + gm.num_outputs])
        self.num_scratch = int(gm.num_scratch_operands)
        self.kernel_jaxpr = eqn.params["jaxpr"]
        self.out_avals = list(eqn.params["out_avals"])
        invars = self.kernel_jaxpr.invars
        n_io = len(self.in_blocks) + len(self.out_blocks)
        self.scratch_avals = [v.aval for v in invars[n_io:]]
        # kernel-side refs, for read counting (scale-fold discipline)
        self.in_refs = invars[:len(self.in_blocks)]

    # x is always the kernel's first operand (repo-wide kernel idiom:
    # the batch tensor leads, weights broadcast behind it).
    @property
    def x_block(self):
        return self.in_blocks[0]

    def batch_tile(self, model) -> int:
        """The x block's extent on the batch axis the model names
        (``batch_axis``: 1 for the node-major ``(N_o, B, P)`` whole-
        network kernels, 0 — the default — for batch-major x)."""
        return _dim(self.x_block.block_shape[model.get("batch_axis", 0)])

    @property
    def weight_blocks(self):
        return self.in_blocks[1:]

    def scalar_f32_read_count(self) -> int:
        """Scalar fp32 ``get``s anywhere in the kernel (cond branches
        included — ``pl.when`` tails re-bind refs, so identity-based
        attribution undercounts).  In these kernels the ONLY scalar
        fp32 ref reads are dequant-scale folds, so this count IS the
        number of scale folds."""
        import jax.numpy as jnp
        return sum(1 for e in _iter_eqns(self.kernel_jaxpr)
                   if e.primitive.name == "get"
                   and e.outvars[0].aval.shape == ()
                   and e.outvars[0].aval.dtype == jnp.float32)


# ---------------------------------------------------------------------------
# Per-check helpers (each returns a list of Findings).
# ---------------------------------------------------------------------------

def _loc(spec, batch: int | None = None) -> str:
    return (f"path={spec.name}" if batch is None
            else f"path={spec.name} bucket={batch}")


def _drift(actual: float, modeled: float) -> float:
    if modeled == 0:
        return float("inf") if actual else 0.0
    return abs(actual - modeled) / modeled


def _check_tiling(spec, batch, kernels, model):
    findings = []
    grids = [k.grid for k in kernels]
    if model["grid"] is not None and tuple(model["grid"]) not in grids:
        findings.append(Finding(
            "audit-tile-mismatch", _loc(spec, batch), 0,
            f"traced pallas_call grid(s) {grids} never match the "
            f"autotuner model's grid {tuple(model['grid'])} "
            f"(block_b={model['block_b']}, block_s={model['block_s']}) — "
            "the kernel wrapper and the residency_model hook have drifted; "
            "re-mirror the tuner invocation in the autotune module"))
    for k in kernels:
        bb = k.batch_tile(model)
        if bb != int(model["block_b"]):
            findings.append(Finding(
                "audit-tile-mismatch", _loc(spec, batch), 0,
                f"kernel {k.name}: x BlockSpec batch tile is {bb}, the "
                f"autotuner model picked block_b={model['block_b']} — "
                "BlockSpec and bytes model disagree; whichever is right, "
                "make the other match"))
    return findings


def _check_weight_residency(spec, batch, kernels, model):
    findings = []
    for k in kernels:
        traced = sum(_block_bytes(bm) for bm in k.weight_blocks)
        drift = _drift(traced, model["weight_residency_bytes"])
        if drift > DRIFT_TOLERANCE:
            findings.append(Finding(
                "audit-vmem-drift", _loc(spec, batch), 0,
                f"kernel {k.name}: traced weight-operand BlockSpecs "
                f"occupy {traced} B of VMEM but the model reserves "
                f"{model['weight_residency_bytes']} B "
                f"({drift:.0%} drift > {DRIFT_TOLERANCE:.0%}) — "
                "weight_vmem_bytes and the kernel's weight BlockSpecs "
                "have diverged (a quantized path shipping fp32 weights "
                "shows up here as ~4x drift)"))
    return findings


def _check_intermediates(spec, batch, kernels, model):
    findings = []
    per_cap = model["per_sample_bytes"] * (1 + DRIFT_TOLERANCE)
    for k in kernels:
        bb = max(1, k.batch_tile(model))
        largest, largest_eqn = 0, None
        for eqn in _iter_eqns(k.kernel_jaxpr):
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None and getattr(aval, "shape", None) is not None:
                    b = _aval_bytes(aval)
                    if b > largest:
                        largest, largest_eqn = b, eqn.primitive.name
        per_sample = largest / bb
        if per_sample > per_cap:
            findings.append(Finding(
                "audit-vmem-drift", _loc(spec, batch), 0,
                f"kernel {k.name}: largest traced intermediate "
                f"({largest_eqn}, {largest} B / block_b={bb} -> "
                f"{per_sample:.0f} B/sample) exceeds the model's "
                f"per_sample_bytes={model['per_sample_bytes']} — the bytes "
                "model no longer upper-bounds the kernel's live set, so "
                "fits_vmem acceptance is unsound; grow the model or "
                "shrink the tensor"))
    return findings


def _check_fp32_accumulation(spec, batch, kernels):
    import jax.numpy as jnp
    findings = []
    for k in kernels:
        for aval in k.scratch_avals:
            dt = getattr(aval, "dtype", None)
            if dt is not None and dt != jnp.float32:
                findings.append(Finding(
                    "audit-accum-dtype", _loc(spec, batch), 0,
                    f"kernel {k.name}: VMEM scratch accumulator is {dt}, "
                    "must be float32 — bf16/int accumulation breaks the "
                    "declared tolerance class; allocate scratch as "
                    "jnp.float32 and cast at the edges"))
        for eqn in _iter_eqns(k.kernel_jaxpr):
            if eqn.primitive.name != "dot_general":
                continue
            out_dt = eqn.outvars[0].aval.dtype
            if out_dt != jnp.float32:
                findings.append(Finding(
                    "audit-accum-dtype", _loc(spec, batch), 0,
                    f"kernel {k.name}: dot_general accumulates in {out_dt}, "
                    "must be float32 — pass "
                    "preferred_element_type=jnp.float32 and fold scales/"
                    "casts after the accumulate"))
        for aval in k.out_avals:
            if aval.dtype != jnp.float32:
                findings.append(Finding(
                    "audit-accum-dtype", _loc(spec, batch), 0,
                    f"kernel {k.name}: kernel output is {aval.dtype}, "
                    "must be float32 — logits leave the kernel at full "
                    "precision"))
    return findings


def _check_int8_discipline(spec, batch, kernels):
    import jax.numpy as jnp
    findings = []
    for k in kernels:
        int_inputs, scale_rows = [], []
        for bm in k.weight_blocks:
            dt = bm.array_aval.dtype
            shape = bm.array_aval.shape
            if jnp.issubdtype(dt, jnp.integer):
                int_inputs.append(bm)
            elif dt == jnp.float32 and len(shape) == 2 and shape[0] == 1:
                scale_rows.append(bm)
            elif dt == jnp.float32 and len(shape) == 1:
                pass                      # biases stay fp32 by design
            else:
                findings.append(Finding(
                    "audit-int8-operands", _loc(spec, batch), 0,
                    f"kernel {k.name}: quantized path ships a "
                    f"{dt}{list(shape)} operand into VMEM — int8 paths "
                    "carry integer weight matrices, fp32 biases, and one "
                    "fp32 scale row only; quantize this tensor or fold it "
                    "into the scales"))
        if not int_inputs:
            findings.append(Finding(
                "audit-int8-operands", _loc(spec, batch), 0,
                f"kernel {k.name}: quantized path traced ZERO integer "
                "VMEM operands — the weights are being dequantized on the "
                "host, which forfeits the 4x residency win the path's "
                "weight_bytes=1 declaration claims"))
            continue
        if len(scale_rows) != 1:
            findings.append(Finding(
                "audit-int8-operands", _loc(spec, batch), 0,
                f"kernel {k.name}: expected exactly one fp32 scale row "
                f"operand, traced {len(scale_rows)} — per-tensor scales "
                "ship as a single (1, n_tensors) fp32 input"))
            continue
        n_scales = int(scale_rows[0].array_aval.shape[-1])
        reads = k.scalar_f32_read_count()
        if n_scales != len(int_inputs) or reads != len(int_inputs):
            findings.append(Finding(
                "audit-int8-operands", _loc(spec, batch), 0,
                f"kernel {k.name}: scale-fold discipline broken — "
                f"{len(int_inputs)} integer tensors, {n_scales} scales, "
                f"{reads} scale reads; each tensor's scale must fold "
                "exactly once (after the fp32 accumulate), so all three "
                "counts must agree"))
    return findings


def _check_ladder(spec, cfg, params, max_batch):
    """Satellite (f): every rung the path's bucket ladder hands to
    serving must fit effective_budget under the model."""
    findings = []
    ladder = spec.bucket_ladder(cfg, params, max_batch)
    if not ladder:
        findings.append(Finding(
            "audit-ladder-budget", _loc(spec), 0,
            "bucket_ladder is empty — even one sample does not fit the "
            "VMEM budget after the weight reservation; the path cannot "
            "serve at all"))
        return findings, ladder
    for rung in ladder:
        model = spec.residency_model(cfg, params, rung)
        tile = model["block_b"] * model["per_sample_bytes"]
        if not model["fits"] or tile > model["effective_budget"]:
            findings.append(Finding(
                "audit-ladder-budget", _loc(spec, rung), 0,
                f"ladder rung {rung} does not fit: block tile "
                f"{model['block_b']} x {model['per_sample_bytes']} B = "
                f"{tile} B vs effective_budget "
                f"{model['effective_budget']} B (fits={model['fits']}) — "
                "bucket_ladder and the kernel tuner disagree about the "
                "weight reservation; a hand-pinned bucket is exceeding "
                "what fits_vmem accepts"))
    return findings, ladder


# ---------------------------------------------------------------------------
# Entry points.
# ---------------------------------------------------------------------------

def audit_path(spec, cfg, params, *, max_batch: int = 1024):
    """Full kernel-contract audit of one path.  ``params`` are raw;
    the path's own transform hook is applied first so the audit sees
    the serving-time pytree (quantized, split, ...)."""
    findings: list[Finding] = []
    tparams = spec.prepare_params(params)

    if not spec.pallas:
        # Containment: an XLA path must not smuggle a pallas_call.
        jaxpr = trace_forward(spec, cfg, tparams, 8)
        if find_pallas_calls(jaxpr.jaxpr):
            findings.append(Finding(
                "audit-containment", _loc(spec), 0,
                "path is registered pallas=False but its trace contains a "
                "pallas_call — fix the tag (serving's interpret-mode "
                "fallback keys on it) or move the kernel behind a "
                "pallas=True path"))
        return findings

    if spec.residency_model is None:
        findings.append(Finding(
            "audit-no-residency-model", _loc(spec), 0,
            "Pallas path declares no residency_model hook — the auditor "
            "cannot cross-check its BlockSpecs against a bytes model; "
            "expose modeled_residency() from the kernel's autotune module "
            "and wire it into the PathSpec"))
        return findings

    ladder_findings, ladder = _check_ladder(spec, cfg, tparams, max_batch)
    findings.extend(ladder_findings)

    for rung in ladder:
        model = spec.residency_model(cfg, tparams, rung)
        try:
            jaxpr = trace_forward(spec, cfg, tparams, rung)
        except Exception as exc:
            findings.append(Finding(
                "audit-trace-failure", _loc(spec, rung), 0,
                f"forward does not trace at bucket {rung}: "
                f"{type(exc).__name__}: {exc}"))
            continue
        kernels = [TracedKernel(e) for e in find_pallas_calls(jaxpr.jaxpr)]
        if not kernels:
            findings.append(Finding(
                "audit-containment", _loc(spec, rung), 0,
                "path is registered pallas=True but its trace contains no "
                "pallas_call — the tag is load-bearing for serving's "
                "interpret-mode fallback; fix it or restore the kernel"))
            continue
        findings.extend(_check_tiling(spec, rung, kernels, model))
        findings.extend(_check_weight_residency(spec, rung, kernels, model))
        findings.extend(_check_intermediates(spec, rung, kernels, model))
        findings.extend(_check_fp32_accumulation(spec, rung, kernels))
        if spec.quantized:
            findings.extend(_check_int8_discipline(spec, rung, kernels))
    return findings


def audit_registry(cfg, params, *, max_batch: int = 1024,
                   names=None):
    """Audit every registered path of ``cfg``'s model family (or the
    named subset) plus the
    registry-level invariants: fallback chains resolve acyclically and
    every Pallas path carries a residency model."""
    from repro.core import paths as registry
    findings: list[Finding] = []
    try:
        registry.validate_fallbacks()
    except Exception as exc:
        findings.append(Finding(
            "audit-fallback-chain", "registry", 0,
            f"fallback-chain validation failed: {exc}"))
    for name in (names or registry.available(model=cfg.family)):
        spec = registry.get(name)
        findings.extend(audit_path(spec, cfg, params, max_batch=max_batch))
    return findings

"""JEDI-linear forward paths: O(N_o) aggregation, registered end-to-end.

JEDI-linear (arXiv 2508.15468) keeps f_R's first layer linear so the
pairwise message sum commutes with it: the N_o x (N_o-1) edge grid that
dominates JEDI-net's FLOPs at N_o=128+ collapses into globally-pooled
sender projections — O(N_o) aggregation (``kernels/jedi_linear/``).
This is a *different model* from JEDI-net (the first nonlinearity sees
the aggregated message), so the paths here carry their own reference —
the O(N_o^2) edge-sum oracle, which evaluates the same model WITHOUT
the pooling rearrangement and therefore independently validates the
identity — their own tolerance, and their own accuracy story
(EXPERIMENTS.md §JEDI-linear).

Three paths, one degradation ladder::

    int8_jedi_linear_full -> jedi_linear_full -> jedi_linear -> sr_split

* ``jedi_linear``           — the O(N_o) pooled forward in plain XLA: the
  non-Pallas rung every jedi kernel demotes to, and a servable
  production fallback in its own right.
* ``jedi_linear_full``      — the fused Pallas kernel: x -> logits
  on-chip, batch-tiled under the LINEAR live-set model (no sender
  axis), fp32 accumulation.
* ``int8_jedi_linear_full`` — int8 weights dequantized in-kernel
  (scales folded into the fp32 accumulator), ``weight_bytes=1``.

All three declare ``complexity="O(N)"`` and plug
:func:`~repro.core.codesign.jedi_linear_flops` into the per-path FLOPs
hook, so rooflines and codesign bill O(N_o) compute — at N_o=128 the
edge-grid model would overcharge them ~40x.  Like ``int8_path``, this
module is pure registration: the engine, ResilientEngine ladder and
CLIs pick the paths up by introspection with zero consumer edits.
"""

from __future__ import annotations

from repro.core.int8_path import (
    INT8_TOLERANCE,
    dequantize_params,
    quantize_params_int8,
)
from repro.core.paths import register_path

#: Engine-vs-ref acceptance bar for the fp32/bf16 jedi_linear paths.
#: The pooled identity is exact in exact arithmetic; fp32 accumulation
#: leaves only summation-order noise (measured < 3e-5 at N_o=128), so
#: the XLA path holds the reference-class bar and the Pallas kernel the
#: fused-kernel-class bar.
JEDI_LINEAR_TOLERANCE = 2e-4
JEDI_LINEAR_FUSED_TOLERANCE = 5e-4


def _jedi_flops(cfg, batch):
    """PathSpec.flops_model hook -> :func:`codesign.jedi_linear_flops`
    (imported lazily: codesign pulls in the DSE machinery)."""
    from repro.core.codesign import jedi_linear_flops
    return jedi_linear_flops(cfg, batch)


def _linear_per_sample_bytes(cfg, params):
    """PathSpec.per_sample_bytes hook: the LINEAR live-set model — no
    sender axis, so the serving bucket ladder deepens by ~block_s vs
    the grid kernels' sender-tiled estimate."""
    from repro.kernels.autotune import mlp_widths
    from repro.kernels.jedi_linear.autotune import (
        linear_forward_bytes_per_sample)
    return linear_forward_bytes_per_sample(
        cfg.n_objects, cfg.n_features, mlp_widths(params["fr"]),
        mlp_widths(params["fo"]), mlp_widths(params["phi"]))


def _linear_residency(cfg, params, batch, **kw):
    """PathSpec.residency_model hook: the kernel autotuner's tiling
    decision as data, for the static kernel-contract auditor."""
    from repro.kernels.jedi_linear.autotune import modeled_residency
    return modeled_residency(cfg, params, batch, **kw)


def _ref_edge_sum(params, cfg, x):
    """Reference: the O(N_o^2) edge-sum oracle of the SAME model."""
    from repro.kernels.jedi_linear.ref import forward_jedi_linear_edge_sum
    return forward_jedi_linear_edge_sum(params, cfg, x)


def _ref_edge_sum_int8(qparams, cfg, x):
    """Reference for the int8 path: edge-sum oracle on dequantized
    weights (spec contract: ref sees the transformed params, so the
    declared tolerance measures kernel fidelity, not quantization
    loss)."""
    from repro.kernels.jedi_linear.ref import forward_jedi_linear_edge_sum
    return forward_jedi_linear_edge_sum(dequantize_params(qparams), cfg, x)


@register_path(
    name="jedi_linear",
    ref=_ref_edge_sum,
    # "edge": no B/E edge tensors exist to round-trip (there is no edge
    # grid at all), but Ebar and O still cross XLA fusion boundaries —
    # the same traffic band as the edge-fused kernel, and nothing like
    # the "none" tier's N_E-sized round-trips.
    fused_level="edge",
    tolerance=JEDI_LINEAR_TOLERANCE,
    complexity="O(N)",
    flops_model=_jedi_flops,
    per_sample_bytes=_linear_per_sample_bytes,
    # Non-Pallas rung of the jedi ladder; bottoms out in the O(N^2)
    # XLA reference so a jedi-specific numerical surprise still serves.
    fallback="sr_split",
    description="JEDI-linear O(N) pooled aggregation (XLA)",
)
def forward_jedi_linear(params, cfg, x):
    """O(N_o) JEDI-linear forward in plain XLA (see kernels/jedi_linear)."""
    from repro.kernels.jedi_linear.ref import forward_jedi_linear as fwd
    return fwd(params, cfg, x)


@register_path(
    name="jedi_linear_full",
    ref=_ref_edge_sum,
    fused_level="full",
    pallas=True,
    tolerance=JEDI_LINEAR_FUSED_TOLERANCE,
    complexity="O(N)",
    flops_model=_jedi_flops,
    per_sample_bytes=_linear_per_sample_bytes,
    # Degradation ladder: a failing jedi kernel demotes to the SAME
    # model in XLA first (accuracy story unchanged), then to sr_split.
    fallback="jedi_linear",
    residency_model=_linear_residency,
    description="JEDI-linear whole-network Pallas kernel, O(N) on-chip",
)
def forward_jedi_linear_full(params, cfg, x, *, interpret: bool = False):
    """Fused JEDI-linear forward: the whole x -> logits pipeline in one
    Pallas kernel per batch tile."""
    from repro.kernels.jedi_linear import ops as jl_ops
    return jl_ops.jedi_linear_forward_full(params, cfg, x,
                                           interpret=interpret)


@register_path(
    name="int8_jedi_linear_full",
    ref=_ref_edge_sum_int8,
    fused_level="full",
    pallas=True,
    compute_dtypes=("float32",),      # int8 weights dequantize to fp32 compute
    transform_params=quantize_params_int8,
    tolerance=max(JEDI_LINEAR_FUSED_TOLERANCE, INT8_TOLERANCE),
    quantized=True,
    weight_bytes=1,                   # in-kernel dequant: 1 B/weight HBM
    complexity="O(N)",
    flops_model=_jedi_flops,
    per_sample_bytes=_linear_per_sample_bytes,
    fallback="jedi_linear_full",
    residency_model=_linear_residency,
    description="int8-weight JEDI-linear kernel, in-VMEM dequant",
)
def forward_int8_jedi_linear_full(qparams, cfg, x, *, interpret: bool = False):
    """Fused JEDI-linear forward with int8 weights dequantized in-kernel
    (``qparams`` from :func:`quantize_params_int8`, applied by the
    spec's transform hook wherever the path resolves through the
    registry)."""
    from repro.kernels.jedi_linear import ops as jl_ops
    return jl_ops.jedi_linear_forward_full(qparams, cfg, x,
                                           interpret=interpret)

"""int8 weight-quantized whole-network fused path.

The FPGA side of the paper is a fixed-point datapath: ap_fixed weights
sized per layer by the quantization-aware co-design loop (Sec. 4.2).
The TPU analogue is a quantized MXU path — weights stored in int8 with
symmetric per-tensor scales, activations and accumulation kept in fp32.
At trigger-tier batch sizes the step is weight-traffic bound (see
EXPERIMENTS.md §Roofline), so 4 bytes -> 1 byte of weight HBM is the
latency lever, exactly like the paper trading DSP precision for
initiation interval.

Since the sender-tiled kernel rework the dequantization happens
IN-KERNEL: the quantized layers' int8 tensors travel to VMEM at
1 byte/element (``fused_forward_full`` detects the ``"w_scale"`` keys
and threads the scales in), the MXU multiplies the raw integer values
upcast to the compute dtype, and each per-tensor scale folds into the
fp32 ACCUMULATOR — so the spec honestly declares ``weight_bytes=1`` and
the roofline bills 1-byte weight traffic everywhere at once.  The
quantized weights also reserve ~4x less VMEM residency, which the
per-path bucket policy (``PathSpec.bucket_ladder``) converts into a
deeper serving ladder than the fp32 twin earns.

This module is also the registry's proof of extension: the path is
registered ONLY here via :func:`~repro.core.paths.register_path`, yet
the serving engine, deadline batcher, ``trigger_serve --forward``
choices and ``chip_smoke.py`` all pick it up with zero edits — everything they need (params
quantizer, reference fn, tolerance, roofline weight bytes) rides on the
:class:`~repro.core.paths.PathSpec`.

Quantization scheme
-------------------
Per weight tensor W: ``scale = max|W| / 127``; ``W_q = round(W / scale)``
clipped to [-127, 127], stored as int8 next to the fp32 scale.  Biases
stay fp32.  The kernel computes ``(h @ W_q) * scale`` with fp32
accumulation — numerically the dequantized matmul (integer values up to
+-127 are exact in fp32), so the numerics are bit-identical to an
int8-weight MXU pass with an fp32 accumulator.  The reference fn sees
the SAME quantized params (spec contract: ``ref`` and ``forward`` both
receive the transformed params), so the declared tolerance measures
kernel fidelity, not quantization loss — the quantization loss itself
is characterized in the numerics tests.  :func:`dequantize_params`
survives as the HBM-boundary dequant (the PR-4 wrapper's scheme): it
feeds the XLA reference and the in-kernel-vs-boundary equivalence
tests.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.paths import register_path

#: Engine-vs-ref acceptance bar for the int8 path (fp32 accumulation:
#: same fidelity class as the fp32 fused kernel).
INT8_TOLERANCE = 5e-4


def quantize_params_int8(params):
    """Symmetric per-tensor int8 quantization of every MLP weight.

    Returns a pytree of the same ``{"fr"/"fo"/"phi": {"layers": [...]}}``
    shape with each layer's ``"w"`` replaced by the int8 tensor plus a
    ``"w_scale"`` fp32 scalar.  Keeping the ``"w"`` key means
    shape-driven helpers (``autotune.mlp_widths``) keep working on
    quantized params unchanged.
    """
    def qlayer(layer):
        w = jnp.asarray(layer["w"], jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-12) / 127.0
        wq = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
        out = {"w": wq, "w_scale": scale}
        if "b" in layer:
            out["b"] = jnp.asarray(layer["b"], jnp.float32)
        return out

    return {name: {"layers": [qlayer(lp) for lp in mlp["layers"]]}
            for name, mlp in params.items()}


def dequantize_params(qparams):
    """fp32 view of int8-quantized params (``w = w_q * w_scale``).

    The PR-4 HBM-boundary dequant scheme: running the fused kernel on
    THIS output reads fp32 weights from HBM (4 B/element) — kept as the
    numerical twin the in-kernel dequant is tested against, and as the
    bridge for consumers that need fp32 weights (XLA reference paths).
    """
    def dqlayer(layer):
        out = {"w": layer["w"].astype(jnp.float32) * layer["w_scale"]}
        if "b" in layer:
            out["b"] = layer["b"]
        return out

    return {name: {"layers": [dqlayer(lp) for lp in mlp["layers"]]}
            for name, mlp in qparams.items()}


def _ref_int8(qparams, cfg, x):
    """Reference: strength-reduced XLA forward on the dequantized weights."""
    from repro.core.interaction_net import forward_sr
    return forward_sr(dequantize_params(qparams), cfg, x)


def _int8_residency(cfg, qparams, batch, **kw):
    # Same sender-tiled kernel and tuner as fused_full; ``qparams`` is
    # already quantized, so weight_vmem_bytes bills int8 tensors at
    # 1 byte and the model reflects the smaller residency honestly.
    from repro.kernels.fused_jedinet.autotune import modeled_residency
    return modeled_residency(cfg, qparams, batch, **kw)


@register_path(
    name="int8_fused_full",
    ref=_ref_int8,
    fused_level="full",
    pallas=True,
    compute_dtypes=("float32",),      # int8 weights dequantize to fp32 compute
    transform_params=quantize_params_int8,
    tolerance=INT8_TOLERANCE,
    quantized=True,
    # The kernel loads int8 into VMEM and dequantizes on-chip (scale
    # folded after the fp32 accumulate), so the roofline honestly bills
    # 1 byte/weight of HBM traffic — this one field flips the model for
    # every consumer (engine roofline, codesign, --list-paths).
    weight_bytes=1,
    # Degradation ladder (serving/resilient.py): a failing int8 kernel
    # demotes to the fp32 fused kernel, which itself bottoms out in the
    # XLA reference — int8_fused_full -> fused_full -> sr_split.
    fallback="fused_full",
    complexity="O(N^2)",
    residency_model=_int8_residency,
    description="int8-weight whole-network kernel, in-VMEM dequant",
)
def forward_int8_fused_full(qparams, cfg, x, *, interpret: bool = False):
    """Whole-network fused forward with int8 weights dequantized in-kernel.

    ``qparams`` is the output of :func:`quantize_params_int8` (the
    spec's params-transform hook applies it automatically wherever the
    path is resolved through the registry).  The int8 tensors are passed
    to the kernel VERBATIM — no fp32 materialization outside VMEM.
    """
    from repro.kernels.fused_jedinet import ops as fused_ops
    return fused_ops.fused_forward_full(qparams, cfg, x, interpret=interpret)

"""Jit'd public wrapper for the fused JEDI-linear kernel.

:func:`jedi_linear_forward_full` — the whole x -> logits pipeline in one
Pallas kernel per batch tile (``linear_kernel.py``), with the batch tile
chosen from the LINEAR live-set model (``autotune.py``): no sender axis
exists, so the only tiling knob is ``block_b`` and the per-sample
working set is O(N_o * H1).  int8-quantized params (layers carrying
``"w_scale"``, see ``core/int8_path.py``) are detected here and served
with in-kernel dequantization, reusing the fused_jedinet scale plumbing
verbatim — w1's split halves share w1's per-tensor scale.

Non-divisible batches PAD to the next tile multiple instead of
degrading the tile (``autotune.pad_batch``), same contract as the
fused_jedinet wrappers: a prime batch keeps its VMEM-optimal tile.
The MXU compute dtype is ``cfg.compute_dtype``; accumulation, the
sender pool and the node-sum stay fp32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.fused_jedinet import full_kernel as FK
from repro.kernels.fused_jedinet.ops import whole_network_operands
from repro.kernels.jedi_linear import autotune
from repro.kernels.jedi_linear import linear_kernel as LK


@partial(jax.jit, static_argnames=("cfg", "interpret", "block_b"))
def jedi_linear_forward_full(params, cfg, x, *, interpret: bool = False,
                             block_b: int | None = None):
    """Fused JEDI-linear forward. x: (B, N_o, P) -> logits (B, n_targets).

    ``params`` may be raw fp32/bf16 MLPs or int8-quantized ones
    (``quantize_params_int8``); quantized layers keep their int8 weights
    all the way into VMEM.  ``block_b`` defaults to the linear-model
    autotuner; pass it explicitly to pin the tile (tests).
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    fr_arrays, fo_arrays, phi_arrays, scales = whole_network_operands(
        params, cfg)
    block_b = autotune.modeled_residency(cfg, params, x.shape[0],
                                         block_b=block_b)["block_b"]
    out = LK.jedi_linear_kernel_call(
        FK.node_major(x, cdt, block_b), fr_arrays, fo_arrays, phi_arrays,
        activation=cfg.activation, n_targets=cfg.n_targets,
        block_b=block_b, compute_dtype=cdt, scales=scales,
        interpret=interpret)
    return out[:x.shape[0]]

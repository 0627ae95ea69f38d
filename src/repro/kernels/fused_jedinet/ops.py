"""Jit'd public wrappers for the fused JEDI-net kernels.

Two entry points:

* :func:`fused_edge_block` — edge-only fusion (B-construct + f_R + MMM3 in
  VMEM); Ebar returns to XLA for f_O / phi_O.
* :func:`fused_forward_full` — whole-network fusion (x -> logits in one
  kernel); the only HBM traffic is weights + x in, logits out.  The
  sender axis is tiled (``block_s``) with an fp32 VMEM accumulator, so
  the batch tile is chosen from the TILED live set — much larger than
  the untiled kernel allowed — and graphs past N_o ~ 100 fit at all.
  int8-quantized params (layers carrying ``"w_scale"``, see
  ``core/int8_path.py``) are detected here and served with IN-KERNEL
  dequantization: the kernel reads 1-byte weights from HBM and folds
  the scales into the fp32 accumulator.

Both pick their batch tile from the working-set autotuner (autotune.py)
and PAD non-divisible batches to the next tile multiple instead of
degrading the tile size — a prime batch (B=1009) keeps its VMEM-optimal
tile and pays <1% padded compute rather than running a 1009-step grid.

The MXU compute dtype is ``cfg.compute_dtype`` (the paper's precision /
latency co-design knob): weights and x are cast down, accumulation and
the two reductions stay fp32.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.fused_jedinet import autotune
from repro.kernels.fused_jedinet import full_kernel as FK
from repro.kernels.fused_jedinet import kernel as K


def is_quantized_params(params) -> bool:
    """True when the MLP layers carry int8 weights + dequant scales.

    Quantization is all-or-nothing (``quantize_params_int8`` quantizes
    every layer): a mixed pytree would send some fp32 weights through
    the int8 scale plumbing, so it is rejected here at the boundary
    instead of failing opaquely inside the kernel.
    """
    flags = [("w_scale" in lp)
             for mlp in params.values() for lp in mlp["layers"]]
    if any(flags) and not all(flags):
        raise ValueError(
            "partially quantized params: every MLP layer must carry "
            "'w_scale' (quantize_params_int8 quantizes all layers); "
            "mixed fp32/int8 pytrees are not supported")
    return all(flags) and bool(flags)


def whole_network_operands(params, cfg):
    """``(fr_arrays, fo_arrays, phi_arrays, scales)`` for the whole-
    network kernels: f_R's first layer split into receiver/sender halves,
    weights cast to the compute dtype (int8 weights verbatim), biases
    fp32, and — for int8 params — the per-tensor dequant scales in
    weight order (both halves of the split w1 share w1's scale)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    fr = K.split_first_layer(params["fr"], cfg.n_features, dtype=cdt)
    scales = None
    if is_quantized_params(params):
        s_fr = FK.mlp_scales(params["fr"])
        scales = [s_fr[0], s_fr[0], *s_fr[1:],
                  *FK.mlp_scales(params["fo"]), *FK.mlp_scales(params["phi"])]
    return ([fr[0], fr[1], fr[2], *fr[3]], FK.flatten_mlp(params["fo"], cdt),
            FK.flatten_mlp(params["phi"], cdt), scales)


@partial(jax.jit, static_argnames=("cfg", "interpret", "block_b"))
def fused_edge_block(params_fr, cfg, x, *, interpret: bool = False,
                     block_b: int | None = None):
    """Ebar = aggregated f_R messages. x: (B, N_o, P) -> (B, N_o, D_e)."""
    if any("w_scale" in lp for lp in params_fr["layers"]):
        # the edge kernel has no dequant-scale plumbing: int8 weights
        # would matmul unscaled (and truncate activations to int8) —
        # reject at the boundary, like fused_forward_full's
        # is_quantized_params guard
        raise ValueError(
            "fused_edge_block does not support int8-quantized params; "
            "serve quantized weights through fused_forward_full "
            "(in-kernel dequant) or dequantize_params first")
    cdt = jnp.dtype(cfg.compute_dtype)
    w1r, w1s, b1, rest = K.split_first_layer(params_fr, cfg.n_features,
                                             dtype=cdt)
    widths = [w1r.shape[-1]] + [r.shape[-1] for r in rest[::2]]
    bb = block_b or autotune.pick_block_b(
        x.shape[0],
        autotune.edge_block_bytes_per_sample(cfg.n_objects, cfg.n_features,
                                             widths))
    bsz = x.shape[0]
    xp = autotune.pad_batch(x.astype(cdt), bb)
    out = K.fused_edge_block_kernel_call(
        xp, w1r, w1s, b1, rest,
        activation=cfg.activation, block_b=bb, interpret=interpret)
    return out[:bsz]


@partial(jax.jit, static_argnames=("cfg", "interpret", "block_b", "block_s"))
def fused_forward_full(params, cfg, x, *, interpret: bool = False,
                       block_b: int | None = None,
                       block_s: int | None = None):
    """Whole-network fused forward. x: (B, N_o, P) -> logits (B, n_targets).

    ``params`` may be raw fp32/bf16 MLPs or int8-quantized ones
    (``quantize_params_int8``); quantized layers keep their int8 weights
    all the way into VMEM.  ``(block_b, block_s)`` default to the 2D
    working-set autotuner, decided once in
    ``autotune.modeled_residency``; pass either explicitly to pin it
    (tests).  A pinned ``block_s`` rounds down to a sender-tile
    candidate.  f_R runs packed ``tiles["lane_pack"]`` edges per slab
    row, a factor the tile model reads off the f_R widths.
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    fr_arrays, fo_arrays, phi_arrays, scales = whole_network_operands(
        params, cfg)
    tiles = autotune.modeled_residency(cfg, params, x.shape[0],
                                       block_b=block_b, block_s=block_s)
    block_b, block_s = tiles["block_b"], tiles["block_s"]
    out = FK.fused_forward_full_kernel_call(
        FK.node_major(x, cdt, block_b), fr_arrays, fo_arrays, phi_arrays,
        activation=cfg.activation, n_targets=cfg.n_targets,
        block_b=block_b, block_s=block_s, lane_pack=tiles["lane_pack"],
        compute_dtype=cdt, scales=scales, interpret=interpret)
    return out[:x.shape[0]]

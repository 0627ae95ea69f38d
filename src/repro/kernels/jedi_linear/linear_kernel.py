"""Pallas TPU kernel: fused JEDI-linear forward (x -> logits, O(N_o)).

The whole-network JEDI-net kernel (``fused_jedinet/full_kernel.py``)
must materialize a slab of the receiver x sender f_R grid and therefore
grids over (batch, sender) tiles with a cross-step VMEM accumulator.
JEDI-linear has no grid: the linear first f_R layer commutes with the
sender sum (see ``ref.py``), so one program instance owns a batch tile
and computes

    u_r = x @ W_r,  u_s = x @ W_s            (per-node projections)
    pooled = sum_j u_s[j]                    (ONE global pool)
    Ebar1_i = (N_o-1)(u_r_i + b1) + (pooled - u_s_i)
        -> remaining f_R layers PER NODE -> C = [x ‖ Ebar]
        -> f_O -> node-sum -> phi_O -> logits

entirely in VMEM, in one grid step — no sender loop, no scratch
accumulator, no mask.  The live set is O(block_b * N_o * H1) (the
linear model in ``autotune.py``), so batch tiles grow ~``block_s``-fold
over the sender-tiled kernel and N_o stops constraining VMEM at all.

Every matmul goes through the shared ``_mmq`` helper: operands cast to
the compute dtype, fp32 accumulation via ``preferred_element_type``,
and — for int8 weights (``core/int8_path.py``) — the per-tensor dequant
scale folded into the ACCUMULATED fp32 result, so quantized weights
travel HBM -> VMEM at 1 byte/element exactly as in the fused_jedinet
kernels.  The (N_o-1)-fold recombination and both reductions (sender
pool, node-sum) stay fp32.  x arrives node-major, ``(N_o, B, P)``, as
in the fused_jedinet kernel (see its "Node-major layout"): every matmul
is 2-D and both reductions add whole ``(block_b, width)`` tiles.

Grid: ``(batch tiles,)``; weights and scales broadcast to every step.
``block_b`` comes from the linear working-set model via the shared
picker (``autotune.pick_block_b_linear``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_jedinet.full_kernel import (
    _edge_mlp, _mmq, _readout, _unpack_weights, check_scales)
from repro.nn.core import ACTIVATIONS


def _linear_forward_kernel(x_ref, *rest_refs, activation: str,
                           n_fr: int, n_fo: int, n_o: int,
                           quantized: bool, compute_dtype):
    """rest_refs = [scales?] + [w1r, w1s, b1, (fr w/b)*, (fo w/b)*,
    (phi w/b)*] + [out_ref].

    ``x_ref`` — (N_o, block_b, P) fp32: the batch tile, node-major, read
    once; both projections and the pool are computed from this block.
    Weight refs arrive pre-cast to the compute dtype (or int8 when
    ``quantized``); biases are fp32.  Scale bookkeeping matches the
    fused_jedinet kernel: w1's split halves share w1's scale.
    """
    out_ref = rest_refs[-1]
    (w1r, s1r, w1s, s1s, b1), fr_rest, fo, phi = _unpack_weights(
        rest_refs[:-1], quantized, n_fr, n_fo)
    act = ACTIVATIONS[activation]
    _, bb, p = x_ref.shape
    x2 = x_ref[...].reshape(n_o * bb, p)

    # --- f_R layer 1, pooled: two per-node projections, one global
    # sender pool, per-node recombination.  All fp32 after _mmq.
    u_r = _mmq(x2, w1r, s1r, compute_dtype)            # (N_o*bb, H1)
    u_s = _mmq(x2, w1s, s1s, compute_dtype)            # (N_o*bb, H1)
    h1 = u_r.shape[-1]
    u_s = u_s.reshape(n_o, bb, h1)
    pooled = jnp.sum(u_s, axis=0, keepdims=True)       # (1, bb, H1)
    h = ((n_o - 1) * (u_r.reshape(n_o, bb, h1) + b1[...])
         + (pooled - u_s))

    # --- remaining f_R layers run per NODE, then C = [x ‖ Ebar], f_O,
    # node-sum, phi_O — all in the same step
    ebar = _edge_mlp(h.reshape(n_o * bb, h1), fr_rest, act, compute_dtype)
    logits = _readout(x2, ebar, n_o, fo, phi, act, compute_dtype)
    out_ref[...] = logits.astype(out_ref.dtype)        # (bb, n_targets)


def jedi_linear_kernel_call(x, fr_arrays, fo_arrays, phi_arrays, *,
                            activation: str, n_targets: int, block_b: int,
                            compute_dtype=jnp.float32, scales=None,
                            interpret: bool = False):
    """x: (N_o, B, P) fp32 node-major (``full_kernel.node_major``) ->
    logits (B, n_targets) fp32.

    ``B % block_b == 0`` (callers pad via ``node_major``).
    ``fr_arrays = [w1r, w1s, b1, w2, b2, ...]`` from split_first_layer.
    ``scales`` — fp32 vector of per-weight-tensor dequant scales, in
    weight order [w1r, w1s, w2.., fo.., phi..], required iff any weight
    array is an integer dtype (in-kernel int8 dequant).
    """
    n_o, bsz, p = x.shape
    n_fr = 1 + (len(fr_arrays) - 3) // 2
    n_fo = len(fo_arrays) // 2
    weights = [*fr_arrays, *fo_arrays, *phi_arrays]

    if bsz % block_b != 0:
        from repro.kernels.jedi_linear import autotune as jl_autotune
        fr_w = [int(w.shape[-1]) for w in fr_arrays[0:1] + fr_arrays[3::2]]
        fo_w = [int(w.shape[-1]) for w in fo_arrays[0::2]]
        phi_w = [int(w.shape[-1]) for w in phi_arrays[0::2]]
        modeled = jl_autotune.linear_forward_bytes_per_sample(
            n_o, p, fr_w, fo_w, phi_w)
        raise ValueError(
            f"batch {bsz} is not a multiple of the batch tile: autotuned "
            f"block_b={block_b} at modeled {modeled} VMEM bytes/sample — "
            f"pad the batch with autotune.pad_batch(x, {block_b}) (kernel "
            f"wrappers do this automatically)")
    scales = check_scales(weights, scales)

    def wmap(ndim):
        def m(i):
            return (0,) * ndim
        return m

    in_specs = [pl.BlockSpec((n_o, block_b, p), lambda i: (0, i, 0))]
    operands = [x]
    if scales is not None:
        in_specs.append(pl.BlockSpec(scales.shape, wmap(scales.ndim)))
        operands.append(scales)
    for w in weights:
        in_specs.append(pl.BlockSpec(w.shape, wmap(w.ndim)))
    operands.extend(weights)

    kernel = functools.partial(
        _linear_forward_kernel, activation=activation, n_fr=n_fr, n_fo=n_fo,
        n_o=n_o, quantized=scales is not None,
        compute_dtype=jnp.dtype(compute_dtype))
    return pl.pallas_call(
        kernel,
        grid=(bsz // block_b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, n_targets), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, n_targets), jnp.float32),
        interpret=interpret,
    )(*operands)

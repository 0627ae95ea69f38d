"""Pallas TPU kernel: whole-network fused JEDI-net forward (x -> logits).

The edge-only kernel (``kernel.py``) fuses MMM1/2 + f_R + MMM3 but still
bounces Ebar, C and O through XLA/HBM for f_O, the node-sum and phi_O.
This kernel extends the paper's Sec 3.5 "divide, conquer, fuse" to ALL
sub-layers: one program instance owns a batch tile and computes

    bilinear-split f_R  ->  dense-grid aggregation  ->  C = [x ‖ Ebar]
        ->  f_O  ->  sum_i O[i]  ->  phi_O  ->  logits

entirely in VMEM.  No intermediate (B, E, Ebar, C, O) ever touches HBM —
the only HBM traffic is the weights + x in and the (batch, n_targets)
logits out, the TPU analogue of the paper's fully-fused layer-wise
architecture where every stage hand-off is an on-chip stream.

Two-level tiling (sender axis)
------------------------------
The f_R interaction grid is the VMEM hog: materializing the full
receiver x sender grid costs ``O(block_b * N_o^2 * H1)`` fp32, which at
N_o=50 already forces tiny batch tiles and past N_o~100 cannot hold even
ONE sample — exactly the regime real-time track-graph building targets
(Neu et al., 2307.07289; JEDI-linear, 2508.15468).  The kernel therefore
grids over (batch tiles, sender tiles): each program step computes the
``(N_o, block_s, block_b, H1)`` slab of the grid for one chunk of
``block_s`` senders and folds its sender-sum into an fp32 VMEM scratch
accumulator ``acc[N_o, block_b, D_e]`` that persists across the sender
steps.  Only after the LAST sender tile does the trailing network
(f_O, node-sum, phi_O) run and write logits.  ``block_s`` divides N_o
(``autotune.sender_tile_candidates``), so every sender step is a whole
tile: no clamped remainder, no bounds mask.  The grid includes each
node's self-edge; the tail subtracts it once, from the same per-node
projections.  ``block_s = N_o`` is the untiled kernel (one sender step).

Node-major layout
-----------------
The wrapper hands the kernel x as ``(N_o, B, P)``: nodes and senders on
the untiled leading axes, the batch on sublanes, features on lanes.
Every per-node or per-edge tensor is then a stack of ``(block_b, width)``
tiles, so the kernel's reshapes only merge or split leading axes
(``block_b`` is a multiple of 8), each matmul is one 2-D
``(rows, width) @ (width, width')`` MXU call, the sender chunk is a ref
slice along a leading axis, and both reductions (sender-sum, node-sum)
are adds of whole tiles.  x crosses HBM once per batch tile.

In-kernel int8 weights
----------------------
Weight refs may arrive as int8 (symmetric per-tensor quantization,
``core/int8_path.py``): the kernel then loads 1-byte weights from HBM
into VMEM, runs the matmul on the raw integer values upcast to the
compute dtype, and folds the fp32 ``scale`` into the ACCUMULATED fp32
result — numerically the dequantized matmul, billed at 1 B/weight HBM
traffic (``PathSpec.weight_bytes = 1``).  Scales ride in one small
``(1, n_weights)`` fp32 input; biases stay fp32 and are added after the
scale fold, exactly as in the fp path.

Precision co-design (the paper tunes FPGA word lengths; we tune the MXU
input dtype): every matmul casts its operands to ``compute_dtype`` and
accumulates in fp32 via ``preferred_element_type``; biases, activations
and both reductions (sender-sum, node-sum) stay fp32.  fp32 operands
run at ``Precision.HIGHEST``: the MXU's default would round them to one
bf16 pass.

The two beyond-paper transformations of the edge kernel (bilinear
first-layer split; dense grid + diagonal correction instead of a
gather) are inherited — see kernel.py's docstring and EXPERIMENTS.md
§Perf.

Grid: ``(batch tiles, sender tiles)``, sender innermost; weights and
scales broadcast to every step.  ``(block_b, block_s)`` come from the 2D
working-set autotuner (autotune.pick_block_b_s), which models the TILED
live set at whole 128-lane rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import pad_batch
from repro.nn.core import ACTIVATIONS


def _is_int(w) -> bool:
    return jnp.issubdtype(w.dtype, jnp.integer)


def _mmq(h, w, scale, compute_dtype):
    """2-D matmul with fp32 accumulation; int weights fold ``scale`` AFTER.

    ``h`` casts to the weight's compute representation (int8 weights
    upcast to ``compute_dtype`` — their integer values are exact in
    fp32/bf16 up to +-127, so the MXU sees the same operands an int8
    datapath would); the per-tensor dequant scale multiplies the fp32
    ACCUMULATOR, not the weight, so the weight block in VMEM stays
    1 byte/element.  fp32 operands are multiplied at full precision.
    """
    wv = w[...]
    if _is_int(wv):
        wv = wv.astype(compute_dtype)
    precision = (jax.lax.Precision.HIGHEST if wv.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    out = jax.lax.dot_general(
        h.astype(wv.dtype), wv, (((1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32)
    if scale is not None:
        out = out * scale
    return out


def _unpack_weights(wrefs, quantized: bool, n_fr: int, n_fo: int):
    """Split a kernel's weight refs ``[scales?, w1r, w1s, b1, (w, b)*]``
    into the first f_R layer ``(w1r, s1r, w1s, s1s, b1)`` and the
    remaining f_R, f_O and phi_O layers as ``(w, b, scale)`` triples.

    Each dequant scale is read here, once per weight tensor, in weight
    order (both halves of the split w1 share w1's scale)."""
    if quantized:
        scales_ref, wrefs = wrefs[0], wrefs[1:]

        def scale(k):
            return scales_ref[0, k]
    else:
        def scale(k):
            return None
    first = (wrefs[0], scale(0), wrefs[1], scale(1), wrefs[2])
    layers = [(w, b, scale(2 + i))
              for i, (w, b) in enumerate(zip(wrefs[3::2], wrefs[4::2]))]
    n_rest = n_fr - 1
    return (first, layers[:n_rest], layers[n_rest:n_rest + n_fo],
            layers[n_rest + n_fo:])


def _mlp(h, layers, act, compute_dtype):
    """Dense layers on ``(rows, width)``; activation between layers, the
    last layer linear."""
    for i, (w, b, scale) in enumerate(layers):
        h = _mmq(h, w, scale, compute_dtype) + b[...]
        if i < len(layers) - 1:
            h = act(h)
    return h


def _edge_mlp(h, fr_rest, act, compute_dtype):
    """f_R after its (split) first layer: ``h`` is that layer's
    pre-activation; the f_R output layer is linear."""
    if fr_rest:
        h = _mlp(act(h), fr_rest, act, compute_dtype)
    return h


def _readout(x2, ebar, n_o: int, fo, phi, act, compute_dtype):
    """C = [x ‖ Ebar] -> f_O -> node-sum -> phi_O, for node-major rows
    ``(N_o * block_b, .)``; returns ``(block_b, n_targets)`` logits."""
    o = _mlp(jnp.concatenate([x2, ebar], axis=-1), fo, act, compute_dtype)
    o_sum = jnp.sum(o.reshape(n_o, -1, o.shape[-1]), axis=0)
    return _mlp(o_sum, phi, act, compute_dtype)


def _tiled_forward_kernel(x_ref, *rest_refs, activation: str,
                          n_fr: int, n_fo: int, n_o: int, block_s: int,
                          quantized: bool, compute_dtype):
    """rest_refs = [scales?] + [w1r, w1s, b1, (fr w/b)*, (fo w/b)*,
    (phi w/b)*] + [out_ref, acc_ref].

    ``x_ref``   — (N_o, block_b, P) fp32: every node of the batch tile,
                  resident across sender steps (its index map ignores
                  j), so x crosses HBM ONCE per batch tile.  Each sender
                  step slices its ``block_s`` nodes out of this block.
    ``acc_ref`` — (N_o, block_b, D_e) fp32 VMEM scratch: the Ebar
                  accumulator, carried across the sender steps of one
                  batch tile.
    Weight refs arrive pre-cast to the compute dtype (or int8 when
    ``quantized``); biases are fp32.
    """
    out_ref, acc_ref = rest_refs[-2], rest_refs[-1]
    (w1r, s1r, w1s, s1s, b1), fr_rest, fo, phi = _unpack_weights(
        rest_refs[:-2], quantized, n_fr, n_fo)
    act = ACTIVATIONS[activation]
    j = pl.program_id(1)
    _, bb, p = x_ref.shape

    x2 = x_ref[...].reshape(n_o * bb, p)
    xs = x_ref[pl.ds(j * block_s, block_s)].reshape(block_s * bb, p)

    # --- f_R layer 1, bilinear split: receiver projection over ALL N_o
    # nodes (recomputed per sender step, so no second scratch), sender
    # projection over THIS tile only.
    u_r = _mmq(x2, w1r, s1r, compute_dtype)             # (N_o*bb, H1)
    u_s = _mmq(xs, w1s, s1s, compute_dtype)             # (bs*bb, H1)
    h1 = u_r.shape[-1]

    # --- dense receiver x sender-tile slab (regular access, no gather)
    h = u_r.reshape(n_o, 1, bb, h1) + u_s.reshape(1, block_s, bb, h1)
    h = _edge_mlp(h.reshape(n_o * block_s * bb, h1) + b1[...],
                  fr_rest, act, compute_dtype)           # (N_o*bs*bb, D_e)
    d_e = h.shape[-1]
    contrib = jnp.sum(h.reshape(n_o, block_s, bb, d_e), axis=1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += contrib

    # --- after the LAST sender tile: drop the self-edges the dense grid
    # summed, then C = [x ‖ Ebar], f_O, node-sum, phi_O — all still in
    # VMEM, once per batch tile.
    @pl.when(j == pl.num_programs(1) - 1)
    def _tail():
        u_self = _mmq(x2, w1s, s1s, compute_dtype)
        self_edge = _edge_mlp(u_r + u_self + b1[...], fr_rest, act,
                              compute_dtype)
        ebar = acc_ref[...].reshape(n_o * bb, d_e) - self_edge
        logits = _readout(x2, ebar, n_o, fo, phi, act, compute_dtype)
        out_ref[...] = logits.astype(out_ref.dtype)     # (bb, n_targets)


def flatten_mlp(params, dtype):
    """[w0, b0, w1, b1, ...] with weights cast to ``dtype``, biases fp32.

    int8-quantized layers (``{"w": int8, "w_scale": fp32, "b": fp32}``)
    keep their int8 weights verbatim — the kernel dequantizes in VMEM.
    """
    flat = []
    for lp in params["layers"]:
        w = lp["w"]
        flat.append(w if _is_int(w) else w.astype(dtype))
        flat.append(lp["b"].astype(jnp.float32))
    return flat


def mlp_scales(params) -> list:
    """Per-layer dequant scales of a quantized MLP (fp32 scalars)."""
    return [lp["w_scale"] for lp in params["layers"]]


def node_major(x, compute_dtype, block_b: int):
    """(B, N_o, P) -> fp32 (N_o, B', P), the layout the whole-network
    kernels read: B padded to a ``block_b`` multiple, values rounded to
    ``compute_dtype`` (x feeds the MXU and, through C = [x ‖ Ebar], the
    f_O input at that precision)."""
    x = x.astype(compute_dtype).astype(jnp.float32)
    return jnp.transpose(pad_batch(x, block_b), (1, 0, 2))


def check_scales(weights, scales):
    """The per-tensor dequant scales as the kernels read them: one
    ``(1, n_weight_tensors)`` fp32 row, or ``None`` for fp weights."""
    if not any(_is_int(w) for w in weights):
        return None
    n_w = len(weights) // 2 + 1                  # +1: w1 split in two
    if scales is None:
        raise ValueError(
            "int8 weight arrays need their dequant scales: pass "
            "scales=[s_w1r, s_w1s, s_w2, ...] (one per weight tensor)")
    scales = jnp.asarray(scales, jnp.float32).reshape(1, -1)
    if scales.shape[1] != n_w:
        raise ValueError(
            f"got {scales.shape[1]} scales for {n_w} weight tensors")
    return scales


def fused_forward_full_kernel_call(x, fr_arrays, fo_arrays, phi_arrays, *,
                                   activation: str, n_targets: int,
                                   block_b: int, block_s: int | None = None,
                                   compute_dtype=jnp.float32,
                                   scales=None, interpret: bool = False):
    """x: (N_o, B, P) fp32 node-major (:func:`node_major`) -> logits
    (B, n_targets) fp32.

    ``B % block_b == 0`` (callers pad via :func:`node_major`).
    ``fr_arrays = [w1r, w1s, b1, w2, b2, ...]`` from split_first_layer.
    ``block_s`` tiles the sender axis and must divide N_o (default N_o =
    untiled).  ``scales`` — fp32 vector of per-weight-tensor dequant
    scales, in weight order [w1r, w1s, w2.., fo.., phi..], required iff
    any weight array is an integer dtype (in-kernel int8 dequant).
    """
    n_o, bsz, p = x.shape
    block_s = n_o if block_s is None else int(block_s)
    n_fr = 1 + (len(fr_arrays) - 3) // 2
    n_fo = len(fo_arrays) // 2
    weights = [*fr_arrays, *fo_arrays, *phi_arrays]
    d_e = fr_arrays[-2].shape[-1] if n_fr > 1 else fr_arrays[0].shape[-1]

    if bsz % block_b != 0:
        from repro.kernels.fused_jedinet import autotune as fj_autotune
        fr_w = [int(w.shape[-1]) for w in fr_arrays[0:1] + fr_arrays[3::2]]
        fo_w = [int(w.shape[-1]) for w in fo_arrays[0::2]]
        phi_w = [int(w.shape[-1]) for w in phi_arrays[0::2]]
        modeled = fj_autotune.full_forward_tiled_bytes_per_sample(
            n_o, p, fr_w, fo_w, phi_w, block_s)
        raise ValueError(
            f"batch {bsz} is not a multiple of the batch tile: autotuned "
            f"(block_b={block_b}, block_s={block_s}) at modeled {modeled} "
            f"VMEM bytes/sample — pad the batch with autotune.pad_batch(x, "
            f"{block_b}) (kernel wrappers do this automatically)")
    if n_o % block_s != 0:
        raise ValueError(
            f"sender tile block_s={block_s} does not divide N_o={n_o}; "
            "pick one of autotune.sender_tile_candidates(N_o)")
    scales = check_scales(weights, scales)

    def wmap(ndim):
        def m(i, j):
            return (0,) * ndim
        return m

    in_specs = [pl.BlockSpec((n_o, block_b, p), lambda i, j: (0, i, 0))]
    operands = [x]
    if scales is not None:
        in_specs.append(pl.BlockSpec(scales.shape, wmap(scales.ndim)))
        operands.append(scales)
    for w in weights:
        in_specs.append(pl.BlockSpec(w.shape, wmap(w.ndim)))
    operands.extend(weights)

    kernel = functools.partial(
        _tiled_forward_kernel, activation=activation, n_fr=n_fr, n_fo=n_fo,
        n_o=n_o, block_s=block_s, quantized=scales is not None,
        compute_dtype=jnp.dtype(compute_dtype))
    return pl.pallas_call(
        kernel,
        grid=(bsz // block_b, n_o // block_s),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, n_targets), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, n_targets), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_o, block_b, d_e), jnp.float32)],
        interpret=interpret,
        # the op's HLO name, whatever wraps this call: the benchmark's
        # fused_full_roofline reader finds the kernel by it
        name="fused_forward_full",
    )(*operands)

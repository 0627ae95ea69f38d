"""Pallas TPU kernel: ParT's pair embedding, pair features -> U, on chip.

The pair embedding of the Particle Transformer maps the 4 features of
each particle pair through Linear layers of widths 64, 64, 64 (exact
GELU after each) and 8, giving one attention bias per head.  As XLA
fusions, each layer reads and writes a ``(B, N, N, 64)`` float32 tensor
in HBM.  This kernel runs the whole MLP per block of pairs in VMEM and
writes its output once.

Channel-major layout
--------------------
Pairs run along the lanes and channels down the sublanes: a jet's input
is one ``(5, P)`` slab (rows: the 4 pair features, then the pair's keep
flag), every activation a ``(64, lanes)`` array and the output an
``(8, P)`` slab, one bias per head and pair.  The wrapper (``ops``)
gives each unordered pair of a jet one of its P = N^2/2 lanes and
unfolds the output into the symmetric U.  Each
64-wide layer is one MXU product ``W^T @ h`` whose 128-lane operand
tiles hold 128 pairs, so the layers fill the MXU's lanes without
packing or block-diagonal weights.  The first layer, 4 wide, is four
broadcast multiply-adds on the VPU.  GELU, the biases and the keep mask
are elementwise on whole vregs, and no transpose is needed anywhere.
(Measured on one TPU v5 lite: two pairs packed per 128-lane row with
block-diagonal weights, the layout of ``fused_jedinet``, ran 23 % slower
than this layout.)

The grid runs over blocks of ``block_b`` jets; inside a step a loop
walks the jets and, within each, chunks of ``CHUNK`` pairs: one chunk's
``(64, CHUNK)`` activations are the largest values the kernel holds.
The kernel is bound by the MXU's six passes at ``HIGHEST``; wider
chunks load each weight into the MXU for more pairs (measured on one
TPU v5 lite, 1024 jets: 45.4, 39.4, 36.2 and 35.3 ms at 512, 1024,
2048 and 4096 lanes).

Precision: float32 operands at ``Precision.HIGHEST`` with float32
accumulation, the path's stated precision.  GELU is exact (erf-based):
Mosaic has no ``erf``, so :func:`gelu` computes it from ``exp`` with a
Chebyshev fit of erfc (on a TPU v5 lite within 8.2e-7 of a float64
GELU on [-10, 10]; Mosaic's ``exp`` is good to 5e-6 relative).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

#: Pairs per inner-loop chunk: the lanes of one chunk's activations.
CHUNK = 4096

#: Rows of the input slab: the pair features, then the keep flag.
N_PAIR_FEATURES = 4
IN_ROWS = N_PAIR_FEATURES + 1

_SQRT_HALF = np.float32(0.7071067811865476)
#: erfc(z) = t exp(-z^2 + P(t)), t = 1 / (1 + z/2), z >= 0: relative
#: error under 1.2e-7 (Numerical Recipes, 2nd ed., Sec. 6.2, erfcc).
#: Highest power first.
_ERFC_COEFFS = (0.17087277, -0.82215223, 1.48851587, -1.13520398,
                0.27886807, -0.18628806, 0.09678418, 0.37409196,
                1.00002368, -1.26551223)


def erfc_nonneg(z):
    """erfc(z) for z >= 0 from ops Mosaic lowers (``exp``, a division),
    to a relative error of 1.2e-7."""
    t = 1.0 / (1.0 + 0.5 * z)
    p = jnp.float32(_ERFC_COEFFS[0])
    for c in _ERFC_COEFFS[1:]:
        p = p * t + c
    return t * jnp.exp(p - z * z)


def gelu(x):
    """x Phi(x), the exact GELU: 0.5 x erfc(-x / sqrt 2), with erfc of a
    negative argument taken as 2 - erfc(|.|), so neither tail cancels."""
    h = 0.5 * x * erfc_nonneg(jnp.abs(x) * _SQRT_HALF)
    return jnp.where(x >= 0, x - h, h)


def _mm(w, h):
    """``w @ h`` on the MXU: float32 operands at full precision."""
    return jax.lax.dot_general(
        w, h, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _pair_embed_kernel(in_ref, *refs, chunk: int):
    """refs = [w1t, b1, w2t, b2, ..., out_ref].

    ``in_ref``  -- (block_b, 5, P): per jet the 4 pair features and the
                   keep flag, pairs on the lanes.
    ``w*t``     -- transposed weights (out, in); ``b*`` (out, 1).
    ``out_ref`` -- (block_b, H, P): U, masked.
    """
    out_ref = refs[-1]
    (w1, b1), *layers = zip(refs[:-1:2], refs[1:-1:2])
    block_b, _, n_pairs = in_ref.shape
    n_chunks = n_pairs // chunk

    def body(it, carry):
        b = it // n_chunks
        lanes = pl.ds(pl.multiple_of((it % n_chunks) * chunk, chunk), chunk)
        x = in_ref[b, :, lanes]                            # (5, chunk)
        # the first layer (K = 4) on the VPU: on the MXU it would stream
        # as many weight rows per pair tile as a 64-wide layer
        w = w1[...]
        h = b1[...]
        for k in range(N_PAIR_FEATURES):
            h = h + w[:, k:k + 1] * x[k:k + 1]
        for w, bias in layers:
            h = _mm(w[...], gelu(h)) + bias[...]
        out_ref[b, :, lanes] = h * x[N_PAIR_FEATURES:]
        return carry

    jax.lax.fori_loop(0, block_b * n_chunks, body, 0)


def kernel_operands(folded) -> list:
    """``[w1t, b1, w2t, b2, ...]`` from the folded ``(w, b)`` layers:
    weights transposed to (out, in), biases as (out, 1) columns."""
    ops = []
    for w, b in folded:
        ops += [jnp.asarray(w, jnp.float32).T,
                jnp.asarray(b, jnp.float32).reshape(-1, 1)]
    return ops


def pair_embed_call(slab, operands, *, block_b: int, chunk: int = CHUNK,
                    interpret: bool = False):
    """slab (B, 5, P) float32 -> U (B, H, P) float32.

    ``B % block_b == 0`` and ``P % chunk == 0``; ``operands`` from
    :func:`kernel_operands`."""
    bsz, rows, n_pairs = slab.shape
    if rows != IN_ROWS or bsz % block_b or n_pairs % chunk:
        raise ValueError(
            f"slab {slab.shape}: need {IN_ROWS} rows, a batch that is a "
            f"multiple of block_b={block_b} and pairs a multiple of "
            f"chunk={chunk}")
    heads = operands[-2].shape[0]

    def whole(a):
        return pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)

    return pl.pallas_call(
        functools.partial(_pair_embed_kernel, chunk=chunk),
        grid=(bsz // block_b,),
        in_specs=[pl.BlockSpec((block_b, rows, n_pairs),
                               lambda i: (i, 0, 0)),
                  *[whole(a) for a in operands]],
        out_specs=pl.BlockSpec((block_b, heads, n_pairs),
                               lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, heads, n_pairs), jnp.float32),
        interpret=interpret,
        # the op's HLO name, whatever wraps this call
        name="part_pair_embed",
    )(slab, *operands)

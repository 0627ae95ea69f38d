"""Public wrapper of the ``part_pair_embed`` kernel and its tile model.

:func:`part_pair_embed` lays the pair features and the keep mask out as
the kernel's channel-major input slab, ``(B, 5, N*N)``, calls the kernel
and returns U as ``(B, H, N, N)``: a reshape of the kernel's output, no
copy.  :func:`modeled_residency` is the one place the tile is chosen; the
wrapper takes it from there, and the kernel-contract auditor checks the
traced ``pallas_call`` against it.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.autotune import VMEM_BUDGET_BYTES, effective_budget
from repro.kernels.part_pair import kernel as K
from repro.kernels.part_pair.kernel import IN_ROWS, N_PAIR_FEATURES

_LANE = 128


def padded_pairs(n_objects: int) -> int:
    """N*N rounded up to whole 128-lane tiles."""
    return -(-n_objects * n_objects // _LANE) * _LANE


def chunk_for(n_pairs: int) -> int:
    """The inner loop's chunk: :data:`kernel.CHUNK` lanes, or the
    largest whole number of lane tiles dividing ``n_pairs`` below it."""
    lanes = n_pairs // _LANE
    return _LANE * max(d for d in range(1, K.CHUNK // _LANE + 1)
                       if lanes % d == 0)


def bytes_per_sample(n_objects: int, n_heads: int) -> int:
    """VMEM one jet of a block holds: its input slab and its U block,
    each double-buffered by the pipeline."""
    return 2 * 4 * (IN_ROWS + n_heads) * padded_pairs(n_objects)


def _layer_widths(cfg):
    widths = (N_PAIR_FEATURES, *cfg.pair_embed_dims)
    return list(zip(widths[:-1], widths[1:]))


def weight_bytes(cfg) -> int:
    """VMEM of the kernel's weight operands
    (:func:`kernel.kernel_operands`): each layer's weight and bias."""
    return sum(4 * b * (a + 1) for a, b in _layer_widths(cfg))


def modeled_residency(cfg, params, batch: int, *,
                      budget_bytes: int = VMEM_BUDGET_BYTES) -> dict:
    """The tiling decision for ``batch`` jets, as data: ``block_b`` is the
    largest divisor of ``batch`` whose blocks fit the VMEM budget left
    after the weights (one jet at least), so no bucket is padded."""
    per = bytes_per_sample(cfg.n_objects, cfg.n_heads)
    reserved = weight_bytes(cfg)
    budget = effective_budget(budget_bytes, reserved)
    cap = max(1, budget // per)
    block_b = max(d for d in range(1, min(cap, batch) + 1) if batch % d == 0)
    return {
        "kernel": "part_pair.embed",
        "block_b": int(block_b),
        "block_s": None,
        "grid": (batch // block_b,),
        "per_sample_bytes": int(per),
        "reserved_bytes": int(reserved),
        "effective_budget": int(budget),
        "weight_residency_bytes": int(reserved),
        "fits": block_b * per <= budget,
    }


def part_pair_embed(feats, keep, folded, *, block_b: int,
                    interpret: bool = False):
    """U = the pair MLP of every pair, masked.

    ``feats`` (B, 4, N, N) float32 pair features, channel-major;
    ``keep`` (B, N, N) 1.0 where U is kept, 0.0 where it is zeroed (the
    diagonal, pairs with a padding particle); ``folded`` the ``(w, b)``
    layers with every BatchNorm folded in.  Returns (B, H, N, N)."""
    bsz, n_feat, n, _ = feats.shape
    n_pairs, padded = n * n, padded_pairs(n)
    slab = jnp.concatenate([feats.reshape(bsz, n_feat, n_pairs),
                            keep.reshape(bsz, 1, n_pairs)], axis=1)
    if padded != n_pairs:
        slab = jnp.pad(slab, ((0, 0), (0, 0), (0, padded - n_pairs)))
    u = K.pair_embed_call(slab, K.kernel_operands(folded), block_b=block_b,
                          chunk=chunk_for(padded), interpret=interpret)
    if padded != n_pairs:
        u = u[:, :, :n_pairs]
    return u.reshape(bsz, u.shape[1], n, n)

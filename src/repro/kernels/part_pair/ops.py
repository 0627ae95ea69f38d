"""Public wrapper of the ``part_pair_embed`` kernel and its tile model.

:func:`part_pair_embed` packs each unordered pair of a jet into one lane
of the kernel's channel-major input slab (:func:`pack_pairs`), calls the
kernel and unfolds its output into U, ``(B, H, N, N)``
(:func:`unfold`).  :func:`modeled_residency` is the one place the tile
is chosen; the wrapper takes it from there, and the kernel-contract
auditor checks the traced ``pallas_call`` against it.

Packed layout
-------------
U is symmetric with a zero diagonal, so the kernel evaluates each
unordered pair once, in a rectangular full packing: with h = N/2, a
jet's lanes are an (h, N) array P, N^2/2 lanes, whose row a holds two
(h, h) blocks side by side, a and b in [0, h):

- S[a, b] = P[a, b], the pair (a, h + b): all of its pairs have i < j;
- D[a, b] = P[a, h + b], the pair (a, b) where a < b, from U's top-left
  block, and (h + a, h + b) where a >= b, from its bottom-right block;
  its diagonal carries keep = 0.

Every pair i < j is in exactly one lane (:func:`pair_index`).  A lane
pairs a particle of the row's half with one of the particle axis rolled
by h or not, so P is two broadcasts of slices of the particle axis and
a select: no gather.  The unfold reads each pair's lane into both of its
places of U (:func:`lane_of_pair`), one row gather of the kernel's
output.  An odd N is served as N + 1 with one all-zero padding
particle, masked.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.autotune import VMEM_BUDGET_BYTES, effective_budget
from repro.kernels.part_pair import kernel as K
from repro.kernels.part_pair.kernel import IN_ROWS, N_PAIR_FEATURES

_LANE = 128


def _half(n_objects: int) -> int:
    """h: particles in each half of a jet, an odd N padded by one."""
    return -(-n_objects // 2)


def padded_pairs(n_objects: int) -> int:
    """Lanes a jet carries: its 2 h^2 packed pairs rounded up to whole
    128-lane tiles (8192 at N = 128)."""
    h = _half(n_objects)
    return -(-2 * h * h // _LANE) * _LANE


def _first(h: int) -> np.ndarray:
    """(h, 2h) bool: lanes whose pair is (a, (c + h) mod 2h), the rest
    holding (h + a, c)."""
    a, c = np.indices((h, 2 * h))
    return (c < h) | (c > h + a)


def pair_index(n_objects: int) -> tuple[np.ndarray, np.ndarray]:
    """(i, j), each (h, 2h): the pair each packed lane holds.  At an odd
    N, index N is the padding particle."""
    h = _half(n_objects)
    a, c = np.indices((h, 2 * h))
    first = _first(h)
    return (np.where(first, a, h + a), np.where(first, (c + h) % (2 * h), c))


def chunk_for(n_pairs: int) -> int:
    """The inner loop's chunk: :data:`kernel.CHUNK` lanes, or the
    largest whole number of lane tiles dividing ``n_pairs`` below it."""
    lanes = n_pairs // _LANE
    return _LANE * max(d for d in range(1, K.CHUNK // _LANE + 1)
                       if lanes % d == 0)


def bytes_per_sample(n_objects: int, n_heads: int) -> int:
    """VMEM one jet of a block holds: its input slab and its packed U,
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


def pack_pairs(features, x, mask):
    """The kernel's input slab, (B, 5, :func:`padded_pairs`): per lane
    the 4 features of one unordered pair and its keep flag.

    ``features(xi, xj)`` gives the (B, 4, Ni, Nj) float32 features of
    every pair of a particle of ``xi`` (B, Ni, ...) with one of ``xj``,
    channel-major, symmetric in i, j; ``x`` (B, N, ...) the particles;
    ``mask`` (B, N) 1.0 for a particle, 0.0 for padding."""
    bsz, n = mask.shape
    h = _half(n)
    if 2 * h != n:
        x = jnp.pad(x, [(0, 0), (0, 1)] + [(0, 0)] * (x.ndim - 2))
        mask = jnp.pad(mask, ((0, 0), (0, 1)))
    first = _first(h)
    feats = jnp.where(first, features(x[:, :h], jnp.roll(x, h, axis=1)),
                      features(x[:, h:], x))
    keep = jnp.where(first,
                     mask[:, :h, None] * jnp.roll(mask, h, axis=1)[:, None],
                     mask[:, h:, None] * mask[:, None, :])
    a, c = np.indices((h, 2 * h))
    keep = jnp.where(c == h + a, 0.0, keep)
    slab = jnp.concatenate([feats, keep[:, None]], axis=1)
    slab = slab.reshape(bsz, IN_ROWS, 2 * h * h)
    return jnp.pad(slab, ((0, 0), (0, 0), (0, padded_pairs(n) - 2 * h * h)))


def lane_of_pair(n_objects: int) -> np.ndarray:
    """(N, N) int32: the packed lane that holds the pair (i, j), the same
    for (j, i); a particle with itself maps to a lane of D's diagonal,
    whose keep is 0."""
    h = _half(n_objects)
    i, j = pair_index(n_objects)
    lanes = np.arange(2 * h * h, dtype=np.int32).reshape(h, 2 * h)
    lane = np.empty((2 * h, 2 * h), np.int32)
    lane[i, j] = lanes
    lane[j, i] = lanes
    a = np.arange(h)
    lane[a, a] = lanes[a, h + a]     # the top half's diagonal: D's
    return lane[:n_objects, :n_objects]


def unfold(u, n_objects: int):
    """U (B, H, N, N) from the kernel's packed output (B, H, lanes): each
    unordered pair's value at both of its places, so U is symmetric."""
    lane = lane_of_pair(n_objects)
    return jnp.take(u, lane.ravel(), axis=-1).reshape(
        *u.shape[:2], n_objects, n_objects)


def part_pair_embed(features, x, mask, folded, *, block_b: int,
                    interpret: bool = False):
    """U = the pair MLP of every pair, masked, evaluated once per
    unordered pair.

    ``features``, ``x`` and ``mask`` as :func:`pack_pairs` takes them;
    ``folded`` the ``(w, b)`` layers with every BatchNorm folded in.
    Returns (B, H, N, N): symmetric, 0 on the diagonal and on every pair
    with a padding particle."""
    with jax.named_scope("pack"):
        slab = pack_pairs(features, x, mask)
    u = K.pair_embed_call(slab, K.kernel_operands(folded), block_b=block_b,
                          chunk=chunk_for(slab.shape[-1]),
                          interpret=interpret)
    with jax.named_scope("unfold"):
        return unfold(u, mask.shape[1])

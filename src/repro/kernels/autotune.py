"""Shared VMEM batch-tile autotuning helpers for batch-gridded kernels.

Model-agnostic pieces used by every kernel package that grids over the
batch axis only (fused_jedinet, fm_interaction): pick a batch tile from
a per-sample VMEM working set, and pad non-divisible batches to the
tile instead of degrading the tile.  Per-kernel working-set estimators
stay with their kernels (e.g. fused_jedinet/autotune.py).
"""

from __future__ import annotations

import jax.numpy as jnp

# Half of the ~16 MB/core VMEM: the other half covers Mosaic's
# input/output double buffering and the broadcast weight blocks.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

# fp32 sublane count.  The JEDI kernels put the batch on the sublane
# axis, where Mosaic accepts only blocks of whole (8, 128) tiles, so
# every batch tile is a multiple of this.
_SUBLANE = 8

# Lane count of one vreg: a VMEM array's minor dimension occupies whole
# 128-lane tiles, so a width-20 activation costs as much VMEM as 128.
_LANE = 128


def lanes(width: int) -> int:
    """``width`` rounded up to whole 128-lane tiles — the VMEM footprint
    of one row of a ``(rows, width)`` array."""
    return -(-int(width) // _LANE) * _LANE


def effective_budget(budget_bytes: int, reserved_bytes: int) -> int:
    """Budget left for batch rows after ``reserved_bytes`` of VMEM
    residency (a path's weight blocks, :func:`weight_vmem_bytes`) is
    spoken for, floored at 1/8 of the budget so a pathologically heavy
    reservation degrades the tile instead of zeroing it.  THE one
    definition of the reservation policy — the serving ladder
    (:func:`bucket_ladder`) and the kernel-side 2D tile picker
    (``fused_jedinet.autotune.pick_block_b_s``) must stay in lockstep,
    or the engine pads to buckets the kernel tiles differently for."""
    return max(budget_bytes - max(int(reserved_bytes), 0), budget_bytes // 8)


def mlp_widths(params) -> list[int]:
    """Output widths of each layer of a ``{"layers": [{"w", "b"}, ...]}`` MLP."""
    return [int(lp["w"].shape[-1]) for lp in params["layers"]]


def weight_vmem_bytes(params, compute_dtype=None) -> int:
    """VMEM residency of a params pytree at the dtypes the kernels SHIP:
    integer (quantized) weights verbatim — 1 B/element where their fp32
    twins bill 4, which is how quantized paths reserve less of the
    budget and earn deeper bucket ladders (see :func:`bucket_ladder`'s
    ``reserved_bytes``) — fp weights at ``compute_dtype`` (the wrappers
    cast them down before the kernel; ``None`` bills the stored dtype),
    and biases/scales at their stored fp32."""
    import jax
    cbytes = None if compute_dtype is None \
        else jnp.dtype(compute_dtype).itemsize

    def leaf_bytes(path, x):
        item = jnp.dtype(x.dtype).itemsize
        is_w = any(getattr(k, "key", None) == "w" for k in path)
        if is_w and cbytes is not None \
                and not jnp.issubdtype(x.dtype, jnp.integer):
            item = cbytes
        return x.size * item

    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return int(sum(leaf_bytes(path, x) for path, x in flat))


def pick_block_b(batch: int, per_sample_bytes: int,
                 budget_bytes: int = VMEM_BUDGET_BYTES) -> int:
    """Largest useful batch tile whose working set fits the VMEM budget.

    Always a whole number of sublane tiles (a multiple of 8), and never
    constrained to divide ``batch`` — pad with :func:`pad_batch`
    instead.  Three cases:

    * the batch, rounded up to a sublane tile, fits the budget -> one
      grid step;
    * otherwise take the budget-limited grid-step count and BALANCE the
      tile to it (``ceil(batch / steps)`` rounded up to a sublane tile),
      which minimizes padded rows for that step count (e.g. B=256 at
      budget-tile 96: 3 steps of 88 pads 8 rows, vs 3 steps of 96
      padding 32);
    * one sublane tile is the floor, even where it busts the budget.
    """
    cap = max(_SUBLANE, budget_bytes // max(per_sample_bytes, 1)
              // _SUBLANE * _SUBLANE)
    whole = padded_batch(max(int(batch), 1), _SUBLANE)
    if whole <= cap:
        return whole
    steps = -(-whole // cap)
    return padded_batch(-(-whole // steps), _SUBLANE)


def bucket_ladder(max_batch: int, per_sample_bytes: int,
                  budget_bytes: int = VMEM_BUDGET_BYTES, *,
                  reserved_bytes: int = 0) -> list[int]:
    """Serving pad-to-bucket batch sizes derived from the VMEM tile.

    Requests are padded UP to the nearest bucket so every bucket compiles
    exactly once (a warm cache) and an arbitrary request count never
    triggers a fresh trace.  The ladder is shaped so padding can never
    force a tile-degenerate kernel either:

    * below the VMEM-optimal tile: sublane-aligned doublings (8, 16, 32,
      ...) — each fits the budget whole, so the kernel runs one grid step
      with ``block_b == bucket``;
    * at and above the tile: whole-tile doublings (t, 2t, 4t, ...) — each
      bucket is an exact tile multiple, so the grid tiles it with zero
      intra-kernel padding.

    The last bucket always covers ``max_batch`` (larger requests are
    chunked by the caller).

    ``reserved_bytes`` is VMEM spoken for before any batch row arrives —
    the path's weight blocks (:func:`weight_vmem_bytes`).  It shrinks
    the effective budget, so a path whose weights are int8 (1 B/element
    resident) keeps a larger tile — and therefore a deeper ladder — than
    the same network in fp32: the quantization-aware per-path bucket
    policy (``PathSpec.bucket_ladder`` threads it through).
    """
    max_batch = max(int(max_batch), 1)
    budget_bytes = effective_budget(budget_bytes, reserved_bytes)
    tile = pick_block_b(max_batch, per_sample_bytes, budget_bytes)
    ladder: list[int] = []
    b = _SUBLANE
    while b < min(tile, max_batch):
        ladder.append(b)
        b *= 2
    t = tile
    while t < max_batch:
        ladder.append(t)
        t *= 2
    ladder.append(min(t, padded_batch(max_batch, tile)))
    return sorted(set(ladder))


def bucket_for(bucket_sizes, n_events: int) -> int:
    """Smallest bucket holding ``n_events`` (largest if none do — callers
    chunk oversized requests through it).  ``bucket_sizes`` ascending."""
    for b in bucket_sizes:
        if n_events <= b:
            return b
    return bucket_sizes[-1]


def padded_batch(batch: int, block_b: int) -> int:
    """``batch`` rounded up to the next multiple of ``block_b``."""
    return ((batch + block_b - 1) // block_b) * block_b


def pad_batch(x, block_b: int):
    """Zero-pad axis 0 of ``x`` up to the next ``block_b`` multiple.

    Returns the (possibly aliased) padded array; callers slice kernel
    output back to ``x.shape[0]`` rows.
    """
    pad = padded_batch(x.shape[0], block_b) - x.shape[0]
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths)

"""VMEM working-set estimators for the fused JEDI-net kernels.

Both fused kernels are gridded over the batch axis (the whole-network
kernel additionally over sender tiles): one program instance owns
``block_b`` jets and every intermediate for those jets lives in VMEM.
Choosing the tile sizes is therefore a pure working-set computation —
the per-sample VMEM bytes of the LARGEST live intermediate chain — fed
to the shared tile picker in ``repro.kernels.autotune``.

Three estimators:

* :func:`edge_block_bytes_per_sample` — edge-only kernel (f_R grid
  dominates; x and Ebar tiles ride along).
* :func:`full_forward_bytes_per_sample` — UNTILED whole-network kernel:
  the full ``(N_o, N_o, H1)`` receiver x sender grid is live at once.
  Kept as the rejection model for large graphs — past N_o ~ 100 even
  ``block_b = 1`` exceeds the budget (:func:`fits_vmem`), which is the
  regime the sender-tiled kernel exists for.
* :func:`full_forward_tiled_bytes_per_sample` — sender-tiled kernel:
  only a ``(N_o, block_s, H1)`` slab of the grid plus the fp32 Ebar
  accumulator is live, so the per-sample set shrinks ~``N_o/block_s``
  and ``block_b`` grows by the ratio.

Lane packing
------------
An f_R width of 50 fills 50 of a vreg's 128 lanes, and its weight tile
15 % of the MXU.  The kernel therefore packs ``k`` sender edges of one
receiver side by side in the lanes (:func:`lane_pack`): the slab is
``(N_o, block_s / k, block_b, k*H)`` and each f_R layer runs on a
block-diagonal weight.  The models bill the packed layout: ``block_s/k``
slab rows per receiver, each ``lanes(k*H)`` wide.  ``lane_pack=1`` is
the unpacked kernel, and what the serving ladder is derived from
(``PathSpec.bucket_bytes``).

The whole-network models bill every row at whole 128-lane tiles
(:func:`~repro.kernels.autotune.lanes`): that is what the arrays occupy
in VMEM, and it bounds the rows one grid step holds — which is also
what bounds the kernel's compile time, since Mosaic emits code per vreg.

:func:`pick_block_b_s` searches the 2D ``(block_b, block_s)`` space.
Sender tiles divide N_o (:func:`sender_tile_candidates`), so no tile
needs a clamp or a mask.  Every candidate does the same slab work in
total; what differs is the fixed cost per grid step and the receiver
projection each step recomputes, so the picker minimizes the number of
grid steps and breaks ties toward the larger ``block_s``.  For batches
small enough that the whole batch fits untiled, that is ``block_s =
N_o`` — one sender step, no sender-loop overhead.
"""

from __future__ import annotations

# Re-exported so kernel wrappers and tests have one import surface.
from repro.kernels.autotune import (  # noqa: F401
    VMEM_BUDGET_BYTES,
    _LANE,
    _SUBLANE,
    effective_budget,
    lanes,
    mlp_widths,
    pad_batch,
    padded_batch,
    pick_block_b,
    weight_vmem_bytes,
)


def edge_block_bytes_per_sample(n_objects: int, n_features: int,
                                fr_widths: list[int],
                                acc_bytes: int = 4) -> int:
    """Per-jet VMEM working set of the edge-only kernel (fp32 accumulation).

    Dominated by the dense (N_o, N_o, width) interaction grid; the x tile
    and the Ebar output tile ride along.
    """
    n_o = n_objects
    grid = n_o * n_o * max(fr_widths + [_SUBLANE])
    x_tile = n_o * n_features
    out_tile = n_o * fr_widths[-1]
    return (grid + x_tile + out_tile) * acc_bytes


def full_forward_bytes_per_sample(n_objects: int, n_features: int,
                                  fr_widths: list[int],
                                  fo_widths: list[int],
                                  phi_widths: list[int],
                                  acc_bytes: int = 4) -> int:
    """Per-jet VMEM working set of the UNTILED whole-network kernel.

    The full (N_o, N_o, H1) f_R grid is live at once; C = [x ‖ Ebar],
    the f_O activations and the (per-tile negligible) phi_O activations
    are live in the same program, so they count against the same budget.
    This is the model that REJECTS large graphs (see :func:`fits_vmem`);
    the tiled estimate below is what the kernel actually runs under.
    """
    return full_forward_tiled_bytes_per_sample(
        n_objects, n_features, fr_widths, fo_widths, phi_widths,
        block_s=n_objects, acc_bytes=acc_bytes)


#: Slab-sized values Mosaic keeps live at once along the f_R chain
#: (pre-activation, activation, the next matmul's output, ...).  The
#: v5e compiler's scoped-VMEM stack for this kernel measured 3.0-3.9
#: slabs plus the other terms below (jedinet-30p, block_s 10 and 30).
SLAB_LIVE_COPIES = 4


def full_forward_tiled_bytes_per_sample(n_objects: int, n_features: int,
                                        fr_widths: list[int],
                                        fo_widths: list[int],
                                        phi_widths: list[int],
                                        block_s: int,
                                        acc_bytes: int = 4,
                                        lane_pack: int = 1) -> int:
    """Per-jet VMEM working set of the sender-tiled whole-network kernel.

    Live at any instant: one (N_o, block_s/k, k*H1) slab of the f_R
    grid (``k = lane_pack`` edges per row), the bilinear-split
    projections u_r (N_o, k*H1) / u_s (block_s, k*H1) feeding it, the
    fp32 Ebar accumulator scratch (N_o, k*D_e), the receiver x tile plus
    this step's sender-chunk slice, and — only after the last sender
    tile — C and the f_O / phi_O activations.  The tail intermediates
    share the budget because they coexist with the accumulator and x.
    Every row is billed at whole 128-lane tiles, and the slab
    :data:`SLAB_LIVE_COPIES` times.  ``block_s = N_o`` reproduces the
    untiled estimate exactly.
    """
    n_o, k = n_objects, max(int(lane_pack), 1)
    block_s = max(k, min(int(block_s), n_o))
    h1 = lanes(k * fr_widths[0])
    slab = SLAB_LIVE_COPIES * n_o * (block_s // k) * lanes(k * max(fr_widths))
    u_r = n_o * h1
    u_s = block_s * h1
    x_tile = n_o * lanes(n_features)
    xs_tile = block_s * lanes(n_features)
    ebar_acc = n_o * lanes(k * fr_widths[-1])
    c_tile = n_o * lanes(n_features + fr_widths[-1])
    fo_acts = n_o * lanes(max(fo_widths))
    phi_acts = lanes(max(phi_widths))
    return (slab + u_r + u_s + x_tile + xs_tile + ebar_acc + c_tile
            + fo_acts + phi_acts) * acc_bytes


def fits_vmem(per_sample_bytes: int,
              budget_bytes: int = VMEM_BUDGET_BYTES) -> bool:
    """Can even ONE sample's working set hold the budget?  ``False``
    means the kernel under that model OOMs VMEM at any batch tile —
    the untiled whole-network kernel past N_o ~ 100."""
    return per_sample_bytes <= budget_bytes


def lane_pack(fr_widths: list[int], n_objects: int) -> int:
    """Sender edges the kernel packs side by side in one slab row:
    ``128 // max(f_R widths)``, lowered until it divides N_o (every
    sender tile is then a whole number of packed rows).  2 at width 50
    and N_o 50, 6 at width 20 and N_o 30, 1 at width 64 and above."""
    k = max(_LANE // max(int(w) for w in fr_widths), 1)
    while n_objects % k:
        k -= 1
    return k


def sender_tile_candidates(n_objects: int, lane_pack: int = 1) -> list[int]:
    """Sender-axis tile sizes worth searching: the divisors of N_o that
    are multiples of ``lane_pack``, so every sender step covers a whole
    tile of whole packed rows (no clamped remainder, no bounds mask).
    Ascending; the last is N_o itself, the untiled degenerate."""
    return [d for d in range(lane_pack, n_objects + 1, lane_pack)
            if n_objects % d == 0]


def packed_weight_bytes(params, compute_dtype, lane_pack: int) -> int:
    """VMEM the whole-network kernel's weight operands occupy with f_R
    packed ``lane_pack`` edges per row: the first layer's two halves
    tiled ``k`` times along the lanes, every later f_R weight
    block-diagonal (``k**2`` its size), each f_R bias tiled ``k``
    times; f_O, phi_O and the dequant scales as
    :func:`~repro.kernels.autotune.weight_vmem_bytes` bills them."""
    total = weight_vmem_bytes(params, compute_dtype)
    k = int(lane_pack)
    for i, lp in enumerate(params["fr"]["layers"]):
        w = weight_vmem_bytes({"w": lp["w"]}, compute_dtype)
        total += w * (k ** (1 if i == 0 else 2) - 1)
        total += weight_vmem_bytes({"b": lp["b"]}) * (k - 1)
    return total


def pick_block_b_s(batch: int, n_objects: int, n_features: int,
                   fr_widths: list[int], fo_widths: list[int],
                   phi_widths: list[int],
                   budget_bytes: int = VMEM_BUDGET_BYTES,
                   reserved_bytes: int = 0,
                   lane_pack: int = 1) -> tuple[int, int]:
    """Jointly pick ``(block_b, block_s)`` for the tiled kernel.

    For each candidate sender tile the per-sample live set is modeled
    (:func:`full_forward_tiled_bytes_per_sample`) and the shared picker
    chooses the batch tile.  The winner needs the fewest grid steps
    (``batch tiles x sender tiles``): the slab work is the same for
    every candidate, while each step pays a fixed cost and recomputes
    the receiver projection.  Ties go to the LARGER ``block_s``; for
    small batches that is ``block_s = N_o``, the untiled kernel.

    ``reserved_bytes`` (e.g. the weight blocks' VMEM residency,
    :func:`~repro.kernels.autotune.weight_vmem_bytes`) is subtracted
    from the budget — the quantization-aware knob: int8 weights reserve
    4x less, leaving more VMEM for batch rows.  ``lane_pack`` is the
    kernel's edges per slab row (:func:`lane_pack`).
    """
    budget = effective_budget(budget_bytes, reserved_bytes)
    best = fallback = None
    for bs in sender_tile_candidates(n_objects, lane_pack):
        per = full_forward_tiled_bytes_per_sample(
            n_objects, n_features, fr_widths, fo_widths, phi_widths, bs,
            lane_pack=lane_pack)
        bb = pick_block_b(batch, per, budget)
        # pick_block_b floors block_b at one sublane tile even when that
        # busts the budget, so a non-fitting candidate could still win on
        # steps — skip it, keeping the smallest live set as the fallback.
        if bb * per > budget:
            if fallback is None:
                fallback = (bb, bs)
            continue
        steps = (padded_batch(batch, bb) // bb) * (n_objects // bs)
        if best is None or (steps, -bs) < (best[0], -best[2]):
            best = (steps, bb, bs)
    return (best[1], best[2]) if best is not None else fallback


def modeled_residency(cfg, params, batch: int, *,
                      block_b: int | None = None,
                      block_s: int | None = None,
                      budget_bytes: int = VMEM_BUDGET_BYTES) -> dict:
    """The tiling decision for ``batch`` samples, as data: THE one place
    it is made — :func:`ops.fused_forward_full` builds its BlockSpecs
    from this dict, and the kernel-contract auditor
    (``repro.analysis.kernel_audit``) cross-checks it against the
    *traced* ``pallas_call``.  Pinned knobs (tests) are honored; a
    pinned ``block_s`` rounds down to a sender-tile candidate.

    Returns ``{kernel, block_b, block_s, lane_pack, batch_axis, grid,
    per_sample_bytes, reserved_bytes, effective_budget,
    weight_residency_bytes, fits}``;
    ``lane_pack`` is read off the f_R widths (:func:`lane_pack`);
    ``weight_residency_bytes`` is the VMEM the weight blocks (and, for
    quantized params, the dequant-scale vector) occupy at the dtypes
    and packing the kernel ships — what the traced input BlockSpecs
    must add up to.
    """
    fr_w = mlp_widths(params["fr"])
    fo_w = mlp_widths(params["fo"])
    phi_w = mlp_widths(params["phi"])
    n_o, n_f = cfg.n_objects, cfg.n_features
    k = lane_pack(fr_w, n_o)
    reserved = packed_weight_bytes(params, cfg.compute_dtype, k)
    budget = effective_budget(budget_bytes, reserved)
    if block_b is None and block_s is None:
        block_b, block_s = pick_block_b_s(
            batch, n_o, n_f, fr_w, fo_w, phi_w,
            budget_bytes=budget_bytes, reserved_bytes=reserved,
            lane_pack=k)
    elif block_b is None:
        block_s = sender_tile(block_s, n_o, k)
        per = full_forward_tiled_bytes_per_sample(
            n_o, n_f, fr_w, fo_w, phi_w, block_s, lane_pack=k)
        block_b = pick_block_b(batch, per, budget)
    elif block_s is None:
        block_s = pick_block_s(block_b, n_o, n_f, fr_w, fo_w, phi_w,
                               budget_bytes=budget_bytes,
                               reserved_bytes=reserved, lane_pack=k)
    else:
        block_s = sender_tile(block_s, n_o, k)
    per = full_forward_tiled_bytes_per_sample(
        n_o, n_f, fr_w, fo_w, phi_w, block_s, lane_pack=k)
    return {
        "kernel": "fused_jedinet.full",
        "block_b": int(block_b),
        "block_s": int(block_s),
        "lane_pack": int(k),
        "batch_axis": 1,                  # x is node-major (N_o, B, P)
        "grid": (padded_batch(batch, block_b) // block_b,
                 n_o // block_s),
        "per_sample_bytes": int(per),
        "reserved_bytes": int(reserved),
        "effective_budget": int(budget),
        "weight_residency_bytes": int(reserved),
        "fits": fits_vmem(per, budget),
    }


def modeled_residency_edge(cfg, params, batch: int, *,
                           block_b: int | None = None,
                           budget_bytes: int = VMEM_BUDGET_BYTES) -> dict:
    """:func:`modeled_residency` twin for the edge-only kernel
    (:func:`ops.fused_edge_block`): batch-gridded only, tile picked from
    :func:`edge_block_bytes_per_sample` with NO weight reservation
    (mirroring the wrapper), and only the f_R weights ship to VMEM."""
    fr_w = mlp_widths(params["fr"])
    per = edge_block_bytes_per_sample(cfg.n_objects, cfg.n_features, fr_w)
    if block_b is None:
        block_b = pick_block_b(batch, per, budget_bytes)
    weights = weight_vmem_bytes({"fr": params["fr"]}, cfg.compute_dtype)
    return {
        "kernel": "fused_jedinet.edge",
        "block_b": int(block_b),
        "block_s": None,
        "grid": (padded_batch(batch, block_b) // block_b,),
        "per_sample_bytes": int(per),
        "reserved_bytes": 0,
        "effective_budget": int(budget_bytes),
        "weight_residency_bytes": int(weights),
        "fits": fits_vmem(per, budget_bytes),
    }


def pick_block_s(block_b: int, n_objects: int, n_features: int,
                 fr_widths: list[int], fo_widths: list[int],
                 phi_widths: list[int],
                 budget_bytes: int = VMEM_BUDGET_BYTES,
                 reserved_bytes: int = 0, lane_pack: int = 1) -> int:
    """Largest sender tile that fits the budget ALONGSIDE a pinned batch
    tile — the one-knob-pinned complement of :func:`pick_block_b_s`.
    Falls back to the smallest candidate when none fit (the caller's
    ``block_b`` is then oversubscribed either way; the smallest live set
    is the least-bad tile to run it with)."""
    budget = effective_budget(budget_bytes, reserved_bytes)
    cands = sender_tile_candidates(n_objects, lane_pack)
    best = cands[0]
    for bs in cands:                       # per-sample grows with bs, so
        per = full_forward_tiled_bytes_per_sample(   # the last fit wins
            n_objects, n_features, fr_widths, fo_widths, phi_widths, bs,
            lane_pack=lane_pack)
        if max(int(block_b), 1) * per <= budget:
            best = bs
    return best


def sender_tile(block_s: int | None, n_objects: int,
                lane_pack: int = 1) -> int:
    """A pinned sender tile as the kernel runs it: the largest candidate
    (:func:`sender_tile_candidates`) not above ``block_s``, or the
    smallest when none is (``None`` means untiled)."""
    if block_s is None:
        return n_objects
    cands = sender_tile_candidates(n_objects, lane_pack)
    return max([d for d in cands if d <= int(block_s)] or cands[:1])

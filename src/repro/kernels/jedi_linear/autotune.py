"""Linear-live-set VMEM model for the JEDI-linear fused kernel.

The sender-tiled whole-network kernel's working set is
``O(block_b * N_o * block_s * H1)`` — the f_R grid slab.  JEDI-linear
has NO grid and therefore no sender axis to tile: the largest live
intermediates are the per-node projections and activations,
``O(block_b * N_o * H1)``, a factor ``block_s`` smaller.  The batch
tile grows by the same factor (weight HBM traffic amortizes over more
jets per step), and graph size stops being a VMEM constraint at all:
the per-sample set is linear in N_o, so :func:`fits_vmem` accepts
N_o=128 tracks — and far beyond — where the untiled grid model rejects
even one sample.

The shared 1D picker (:func:`repro.kernels.autotune.pick_block_b`)
consumes this model directly; :func:`pick_block_b_linear` is the
one-call convenience mirroring ``fused_jedinet.autotune.pick_block_b_s``
minus the sender knob.
"""

from __future__ import annotations

# Re-exported so kernel wrappers and tests have one import surface.
from repro.kernels.autotune import (  # noqa: F401
    VMEM_BUDGET_BYTES,
    effective_budget,
    lanes,
    mlp_widths,
    pad_batch,
    padded_batch,
    pick_block_b,
    weight_vmem_bytes,
)
from repro.kernels.fused_jedinet.autotune import fits_vmem  # noqa: F401


def linear_forward_bytes_per_sample(n_objects: int, n_features: int,
                                    fr_widths: list[int],
                                    fo_widths: list[int],
                                    phi_widths: list[int],
                                    acc_bytes: int = 4) -> int:
    """Per-jet VMEM working set of the JEDI-linear whole-network kernel.

    Live at any instant: the two first-layer projections u_r / u_s
    (each (N_o, H1) fp32), the (1, H1) sender pool, the per-NODE f_R
    activations (the widest (N_o, width) tensor — no edge grid), the x
    tile, the Ebar result, C = [x ‖ Ebar] and the f_O / phi_O
    activations.  Every term is linear in N_o — the whole point.  Rows
    are billed at whole 128-lane tiles, as they sit in VMEM.
    """
    n_o = n_objects
    h1 = lanes(fr_widths[0])
    u_proj = 2 * n_o * h1
    pooled = h1
    fr_acts = n_o * lanes(max(fr_widths))
    x_tile = n_o * lanes(n_features)
    ebar = n_o * lanes(fr_widths[-1])
    c_tile = n_o * lanes(n_features + fr_widths[-1])
    fo_acts = n_o * lanes(max(fo_widths))
    phi_acts = lanes(max(phi_widths))
    return (u_proj + pooled + fr_acts + x_tile + ebar + c_tile
            + fo_acts + phi_acts) * acc_bytes


def modeled_residency(cfg, params, batch: int, *,
                      block_b: int | None = None,
                      budget_bytes: int = VMEM_BUDGET_BYTES) -> dict:
    """The tiling decision for ``batch`` samples, as data: the one place
    it is made — :func:`ops.jedi_linear_forward_full` builds its
    BlockSpecs from it, and the kernel-contract auditor
    (``repro.analysis.kernel_audit``) cross-checks it against the traced
    ``pallas_call``; same contract as
    ``fused_jedinet.autotune.modeled_residency``."""
    fr_w = mlp_widths(params["fr"])
    fo_w = mlp_widths(params["fo"])
    phi_w = mlp_widths(params["phi"])
    per = linear_forward_bytes_per_sample(
        cfg.n_objects, cfg.n_features, fr_w, fo_w, phi_w)
    reserved = weight_vmem_bytes(params, cfg.compute_dtype)
    budget = effective_budget(budget_bytes, reserved)
    if block_b is None:
        block_b = pick_block_b(batch, per, budget)
    return {
        "kernel": "jedi_linear.full",
        "block_b": int(block_b),
        "block_s": None,
        "batch_axis": 1,                  # x is node-major (N_o, B, P)
        "grid": (padded_batch(batch, block_b) // block_b,),
        "per_sample_bytes": int(per),
        "reserved_bytes": int(reserved),
        "effective_budget": int(budget),
        "weight_residency_bytes": int(reserved),
        "fits": fits_vmem(per, budget),
    }


def pick_block_b_linear(batch: int, n_objects: int, n_features: int,
                        fr_widths: list[int], fo_widths: list[int],
                        phi_widths: list[int],
                        budget_bytes: int = VMEM_BUDGET_BYTES,
                        reserved_bytes: int = 0) -> int:
    """Batch tile for the JEDI-linear kernel under the linear live set.

    The 1D analogue of ``fused_jedinet.autotune.pick_block_b_s``: same
    budget/reservation policy (``effective_budget``), no sender axis to
    search — the linear model leaves only the batch knob.
    """
    per = linear_forward_bytes_per_sample(
        n_objects, n_features, fr_widths, fo_widths, phi_widths)
    return pick_block_b(batch, per,
                        effective_budget(budget_bytes, reserved_bytes))

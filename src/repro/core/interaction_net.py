"""JEDI-net interaction network (the paper's end-to-end application).

Three functionally identical forward paths are provided:

* ``forward_dense``   — the paper-[5] baseline: explicit dense MMMs with the
  one-hot relation matrices Rr / Rs.  Kept as the faithful *unoptimized*
  reference (and the oracle for tests / op-count benchmarks).
* ``forward_sr``      — the paper's contribution mapped to TPU: strength
  reduction (Sec 3.1), edge-major a.k.a. "column-major" layout (Sec 3.2) and
  outer-product-style aggregation as a reshape+reduce (Sec 3.3).  All three
  MMMs are eliminated; only the MLP GEMMs remain, exactly as on the FPGA
  where only the MLPs consume DSPs.
* ``forward_fused``   — the Sec 3.5 "divide, conquer, fuse" step: a Pallas
  kernel fuses B-construction + f_R + the incoming-edge reduction in VMEM so
  the (N_E x D_e) edge-message matrix E never round-trips through HBM.
  This is the TPU analogue of removing the ping-pong buffers between
  coarse-grained pipeline stages.
* ``forward_fused_full`` — fusion extended end-to-end: ONE Pallas kernel
  computes x -> logits (f_R grid, aggregation, f_O, node-sum, phi_O) per
  batch tile, so the only HBM traffic is weights + x in and logits out —
  the TPU analogue of the paper's fully-fused layer-wise architecture
  where every stage hand-off is an on-chip stream.

Layout convention: inputs are (batch, N_o, P) node-major, i.e. each node's
feature vector is contiguous (minor-most) — the TPU translation of the
paper's column-major order.  The original (P, N_o) single-jet layout of [5]
is exposed through the dense baseline for fidelity.
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import ClassVar, Sequence

import jax
import jax.numpy as jnp

from repro.core import adjacency, paths
from repro.nn import core as nn


@dataclasses.dataclass(frozen=True)
class JediNetConfig:
    """JEDI-net model hyper-parameters (Table 2 of the paper).

    ``fr_hidden`` etc. follow the paper's (NL, S) notation: NL hidden layers,
    each of size S.  ``d_e = 8`` is backed out of Fig. 8 (6,960 = D_e * N_E
    remaining adds for the 30p model => D_e = 8).
    """

    family: ClassVar[str] = "jedinet"   # the paths that serve it (PathSpec.model)

    n_objects: int = 30          # N_o: particles per jet (30p / 50p datasets)
    n_features: int = 16         # P: features per particle
    d_e: int = 8                 # f_R output (edge hidden features)
    d_o: int = 24                # f_O output (per-node post-interaction repr)
    n_targets: int = 5           # jet classes: g, q, W, Z, t
    fr_hidden: Sequence[int] = (20, 20, 20)
    fo_hidden: Sequence[int] = (20, 20, 20)
    phi_hidden: Sequence[int] = (20, 20, 20)
    activation: str = "relu"
    compute_dtype: str = "float32"

    @property
    def n_edges(self) -> int:
        return self.n_objects * (self.n_objects - 1)

    def with_(self, **kw) -> "JediNetConfig":
        return dataclasses.replace(self, **kw)


def init(key, cfg: JediNetConfig, *, scale: str = "fan_in"):
    """``scale``: variance-scaling rule (see nn.dense_init).  "lecun" keeps
    activations O(1) through the N_o-way message sums of an untrained net —
    useful for numerics tests where He init would blow logits up ~N_o-fold.
    """
    kfr, kfo, kphi = jax.random.split(key, 3)
    return {
        "fr": nn.mlp_init(kfr, 2 * cfg.n_features, cfg.fr_hidden, cfg.d_e,
                          scale=scale),
        "fo": nn.mlp_init(kfo, cfg.n_features + cfg.d_e, cfg.fo_hidden,
                          cfg.d_o, scale=scale),
        "phi": nn.mlp_init(kphi, cfg.d_o, cfg.phi_hidden, cfg.n_targets,
                           scale=scale),
    }


def _cdt(cfg: JediNetConfig):
    return jnp.dtype(cfg.compute_dtype)


# ---------------------------------------------------------------------------
# Paper-[5] baseline: explicit dense MMMs with Rr / Rs.
# ---------------------------------------------------------------------------

def forward_dense(params, cfg: JediNetConfig, x):
    """Baseline JEDI-net with explicit adjacency MMMs.

    x: (batch, N_o, P).  Internally transposed to the paper's (P, N_o) layout
    so MMM1/2/3 appear exactly as in [5]: B1 = I@Rr, B2 = I@Rs, Ebar = E@Rr^T.
    """
    cdt = _cdt(cfg)
    rr_np, rs_np = adjacency.dense_relation_matrices(cfg.n_objects)
    rr = jnp.asarray(rr_np, dtype=cdt)
    rs = jnp.asarray(rs_np, dtype=cdt)

    i_mat = jnp.swapaxes(x.astype(cdt), -1, -2)            # (B, P, N_o)
    b1 = i_mat @ rr                                        # MMM1: (B, P, N_E)
    b2 = i_mat @ rs                                        # MMM2: (B, P, N_E)
    b = jnp.concatenate([b1, b2], axis=-2)                 # (B, 2P, N_E)

    # f_R applied per column of B -> transpose to edge-major for the GEMM.
    b_cols = jnp.swapaxes(b, -1, -2)                       # (B, N_E, 2P)
    e_cols = nn.mlp_apply(params["fr"], b_cols, activation=cfg.activation,
                          compute_dtype=cdt)               # (B, N_E, D_e)
    e_mat = jnp.swapaxes(e_cols, -1, -2)                   # (B, D_e, N_E)

    ebar = e_mat @ rr.T                                    # MMM3: (B, D_e, N_o)

    c = jnp.concatenate([i_mat, ebar], axis=-2)            # (B, P+D_e, N_o)
    c_cols = jnp.swapaxes(c, -1, -2)                       # (B, N_o, P+D_e)
    o = nn.mlp_apply(params["fo"], c_cols, activation=cfg.activation,
                     compute_dtype=cdt)                    # (B, N_o, D_o)
    o_sum = jnp.sum(o, axis=-2)                            # (B, D_o)
    logits = nn.mlp_apply(params["phi"], o_sum, activation=cfg.activation,
                          compute_dtype=cdt)               # (B, n_targets)
    return logits.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Strength-reduced, edge-major path (the paper's technique on TPU).
# ---------------------------------------------------------------------------

def build_b_matrix(cfg: JediNetConfig, x):
    """Strength-reduced MMM1/MMM2: build B (B, N_E, 2P) with zero FLOPs.

    B1 (receiver features) is a broadcast over the k axis; B2 (sender
    features) is one static gather whose index map is a compile-time
    constant — the paper's Algorithm 1.
    """
    n_o, p = cfg.n_objects, cfg.n_features
    send_idx = jnp.asarray(adjacency.sender_index_matrix(n_o))   # (N_o, N_o-1)
    # B1: receiver i's features, repeated for each of its N_o-1 incoming edges.
    b1 = jnp.broadcast_to(x[..., :, None, :], (*x.shape[:-2], n_o, n_o - 1, p))
    # B2: sender features via static gather (XLA folds the index constant).
    b2 = jnp.take(x, send_idx.reshape(-1), axis=-2)
    b2 = b2.reshape(*x.shape[:-2], n_o, n_o - 1, p)
    b = jnp.concatenate([b1, b2], axis=-1)                       # (..., N_o, N_o-1, 2P)
    return b.reshape(*x.shape[:-2], cfg.n_edges, 2 * p)


def aggregate_incoming(cfg: JediNetConfig, e_cols):
    """Strength-reduced MMM3: Ebar = E @ Rr^T as a reshape + sum over k.

    e_cols: (..., N_E, D_e) edge-major.  Receiver-major edge ordering makes
    the incoming edges of node i contiguous, so the one-hot MMM collapses to
    a contraction over a length-(N_o-1) axis: D_e*N_E adds, zero mults —
    matching the paper's 3.3%-of-additions figure.
    """
    n_o = cfg.n_objects
    e_r = e_cols.reshape(*e_cols.shape[:-2], n_o, n_o - 1, e_cols.shape[-1])
    return jnp.sum(e_r, axis=-2)                                  # (..., N_o, D_e)


def forward_sr(params, cfg: JediNetConfig, x, *, return_intermediates: bool = False):
    """Strength-reduced JEDI-net forward. x: (batch, N_o, P)."""
    cdt = _cdt(cfg)
    x = x.astype(cdt)
    b = build_b_matrix(cfg, x)                                    # (B, N_E, 2P)
    e_cols = nn.mlp_apply(params["fr"], b, activation=cfg.activation,
                          compute_dtype=cdt)                      # (B, N_E, D_e)
    ebar = aggregate_incoming(cfg, e_cols)                        # (B, N_o, D_e)
    c = jnp.concatenate([x, ebar], axis=-1)                       # (B, N_o, P+D_e)
    o = nn.mlp_apply(params["fo"], c, activation=cfg.activation,
                     compute_dtype=cdt)                           # (B, N_o, D_o)
    o_sum = jnp.sum(o, axis=-2)
    logits = nn.mlp_apply(params["phi"], o_sum, activation=cfg.activation,
                          compute_dtype=cdt)
    logits = logits.astype(jnp.float32)
    if return_intermediates:
        return logits, {"b": b, "e": e_cols, "ebar": ebar, "c": c, "o": o}
    return logits


# ---------------------------------------------------------------------------
# Fused path: Pallas kernel for B-construct + f_R + aggregate (Sec 3.5).
# ---------------------------------------------------------------------------

def forward_fused(params, cfg: JediNetConfig, x, *, interpret: bool = False):
    """JEDI-net forward using the fused Pallas edge kernel.

    The kernel computes Ebar directly from x without materializing B or E in
    HBM — the Sec 3.5 sub-layer fusion.  f_O / phi_O (the paper's DP_tail)
    remain in XLA, which fuses these small GEMMs well.
    """
    from repro.kernels.fused_jedinet import ops as fused_ops

    cdt = _cdt(cfg)
    x = x.astype(cdt)
    ebar = fused_ops.fused_edge_block(params["fr"], cfg, x, interpret=interpret)
    c = jnp.concatenate([x, ebar.astype(cdt)], axis=-1)
    o = nn.mlp_apply(params["fo"], c, activation=cfg.activation, compute_dtype=cdt)
    o_sum = jnp.sum(o, axis=-2)
    logits = nn.mlp_apply(params["phi"], o_sum, activation=cfg.activation,
                          compute_dtype=cdt)
    return logits.astype(jnp.float32)


def forward_fused_full(params, cfg: JediNetConfig, x, *,
                       interpret: bool = False):
    """JEDI-net forward as ONE whole-network Pallas kernel (x -> logits).

    Extends the Sec 3.5 fusion to every sub-layer: bilinear-split f_R,
    dense-grid aggregation, f_O, the node-sum and phi_O all execute in a
    single kernel per batch tile, so no intermediate (B, E, Ebar, C, O)
    ever touches HBM — only weights + x in, logits out.  The MXU compute
    dtype follows ``cfg.compute_dtype`` with fp32 accumulation (the
    precision/latency co-design knob).  See kernels/fused_jedinet/
    full_kernel.py and EXPERIMENTS.md §Perf.
    """
    from repro.kernels.fused_jedinet import ops as fused_ops

    return fused_ops.fused_forward_full(params, cfg, x, interpret=interpret)


# ---------------------------------------------------------------------------
# Beyond-paper optimized path (pure XLA; see EXPERIMENTS.md §Perf).
# ---------------------------------------------------------------------------

def forward_sr_split(params, cfg: JediNetConfig, x, *, grid: bool = True):
    """Strength reduction + bilinear first-layer split (+ dense grid).

    Two optimizations beyond the paper (same ones as the Pallas kernel,
    expressed in XLA so the dry-run/roofline sees them):

    * f_R's first layer splits over the [x_r ‖ x_s] concatenation, so the
      two projections run once per NODE (N_o rows) instead of once per
      EDGE (N_o(N_o-1) rows) — and the B matrix (N_E x 2P) is never
      materialized.
    * ``grid=True``: compute the full N_o x N_o interaction grid and
      subtract the self-edge diagonal after aggregation — regular access,
      no gather, ~1/(N_o-1) extra compute.  ``grid=False`` keeps the
      paper-style static gather of the (N_o, N_o-1) sender table.
    """
    cdt = _cdt(cfg)
    x = x.astype(cdt)
    act = nn.ACTIVATIONS[cfg.activation]
    layers = params["fr"]["layers"]
    w1 = layers[0]["w"].astype(cdt)
    b1 = layers[0]["b"].astype(cdt)
    p = cfg.n_features
    u_r = x @ w1[:p]                                       # (B, N_o, H1)
    u_s = x @ w1[p:]                                       # (B, N_o, H1)

    if grid:
        h = u_r[:, :, None, :] + u_s[:, None, :, :] + b1   # (B, N_o, N_o, H1)
    else:
        send_idx = jnp.asarray(adjacency.sender_index_matrix(cfg.n_objects))
        h = u_r[:, :, None, :] + u_s[:, send_idx, :] + b1  # (B, N_o, N_o-1, H1)
    if len(layers) > 1:
        h = act(h)
    for i, lp in enumerate(layers[1:]):
        h = h @ lp["w"].astype(cdt) + lp["b"].astype(cdt)
        if i < len(layers) - 2:
            h = act(h)

    if grid:
        total = jnp.sum(h, axis=2)                         # (B, N_o, D_e)
        diag = jnp.einsum("brsd,rs->brd", h,
                          jnp.eye(cfg.n_objects, dtype=h.dtype))
        ebar = total - diag
    else:
        ebar = jnp.sum(h, axis=2)

    c = jnp.concatenate([x, ebar.astype(cdt)], axis=-1)
    o = nn.mlp_apply(params["fo"], c, activation=cfg.activation,
                     compute_dtype=cdt)
    o_sum = jnp.sum(o, axis=-2)
    logits = nn.mlp_apply(params["phi"], o_sum, activation=cfg.activation,
                          compute_dtype=cdt)
    return logits.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Path registration: one PathSpec per forward path (see core/paths.py).
# Every consumer (serving engine, batcher, CLI, chip benchmark,
# numerics tests) discovers these through the registry.
# ---------------------------------------------------------------------------

def _fused_residency(cfg, params, batch, **kw):
    from repro.kernels.fused_jedinet.autotune import modeled_residency_edge
    return modeled_residency_edge(cfg, params, batch, **kw)


def _fused_full_residency(cfg, params, batch, **kw):
    from repro.kernels.fused_jedinet.autotune import modeled_residency
    return modeled_residency(cfg, params, batch, **kw)


paths.register(paths.PathSpec(
    name="dense", forward=forward_dense, ref=forward_sr,
    fused_level="none", tolerance=2e-4,
    complexity="O(N^2)", fallback=None,
    description="paper-[5] baseline: explicit Rr/Rs MMMs"))
paths.register(paths.PathSpec(
    name="sr", forward=forward_sr, ref=forward_dense,
    fused_level="none", tolerance=2e-4,
    complexity="O(N^2)", fallback=None,
    description="strength reduction + edge-major layout (Sec 3.1-3.3)"))
paths.register(paths.PathSpec(
    name="sr_split", forward=forward_sr_split, ref=forward_sr,
    fused_level="none", tolerance=2e-4,
    complexity="O(N^2)", fallback=None,
    description="SR + bilinear first-layer split + dense grid (XLA)"))
paths.register(paths.PathSpec(
    name="fused", forward=forward_fused, ref=forward_sr,
    fused_level="edge", pallas=True, tolerance=5e-4,
    complexity="O(N^2)", fallback="sr",
    residency_model=_fused_residency,
    description="Pallas edge kernel: B-construct + f_R + MMM3 in VMEM"))
paths.register(paths.PathSpec(
    name="fused_full", forward=forward_fused_full, ref=forward_sr,
    fused_level="full", pallas=True, tolerance=5e-4,
    complexity="O(N^2)", fallback="sr_split",
    residency_model=_fused_full_residency,
    description="whole-network Pallas kernel: x -> logits on-chip"))


def loss_fn(params, cfg: JediNetConfig, batch, *, forward: str = "sr"):
    """Softmax cross-entropy over the 5 jet classes.

    ``forward`` names any registered path; its params-transform hook
    (e.g. int8 quantization) is applied before the forward call, and
    Pallas-backed paths fall back to interpret mode off-TPU.

    NOTE: transform hooks are inference-time.  Training THROUGH a
    quantized path gets degenerate gradients (round() is flat — there
    is no straight-through estimator here); train on an fp32 path and
    quantize the trained weights at serving time.  Doing it anyway
    warns (see the ROADMAP "Full low-precision MXU pipeline" item for
    the planned STE/QAT trainer).
    """
    spec = paths.get(forward)
    if spec.quantized:
        warnings.warn(
            f"loss_fn through quantized path {forward!r}: the params "
            "transform rounds weights with no straight-through "
            "estimator, so gradients through the quantizer are "
            "degenerate (flat).  Train on an fp32 path and quantize at "
            "serving time — QAT/STE is the ROADMAP 'Full low-precision "
            "MXU pipeline' item.",
            UserWarning, stacklevel=2)
    kw = {}
    if spec.pallas and jax.default_backend() != "tpu":
        kw["interpret"] = True
    logits = spec.forward(spec.prepare_params(params), cfg, batch["x"], **kw)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["y"][..., None], axis=-1)[..., 0]
    acc = jnp.mean((jnp.argmax(logits, -1) == batch["y"]).astype(jnp.float32))
    return jnp.mean(nll), {"accuracy": acc}

"""Plain JEDI-net: the benchmark's own weights, reference and work count.

JEDI-net (Moreno et al., arXiv:1908.05318), as LL-GNN accelerates it:
every ordered pair of distinct particles (receiver i, sender j) is an
edge; f_R maps [x_i || x_j] to an edge message, the messages into i are
summed (Ebar_i), f_O maps [x_i || Ebar_i] to a node vector, the node
vectors are summed, and phi_O maps that sum to the class logits.  Every
MLP has ReLU between layers and none after the last.

This file imports nothing of the program under test.  The reference
computes every edge explicitly (including i == j, masked to zero before
the sum) in float32 at ``Precision.HIGHEST``.  The control of the
comparison (``chipbench/control.py``) computes the same thing with each
matmul as three bfloat16 passes (``precision="high"``, bf16_3x), the
nearest precision below the configuration's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: Row-chunk of the reference driver: bounds the (chunk, N, N, width)
#: edge activations (50p: 512 x 2500 x 50 x 4 B = 256 MB per layer).
REF_CHUNK = 512


def mlp_dims(cfg: dict) -> dict:
    """(in, out) of every linear layer of f_R, f_O and phi_O."""
    p, d_e, d_o = cfg["n_features"], cfg["d_e"], cfg["d_o"]

    def dims(d_in, hidden, d_out):
        ws = [d_in, *hidden, d_out]
        return list(zip(ws[:-1], ws[1:]))

    return {"fr": dims(2 * p, cfg["fr_hidden"], d_e),
            "fo": dims(p + d_e, cfg["fo_hidden"], d_o),
            "phi": dims(d_o, cfg["phi_hidden"], cfg["n_targets"])}


def init_params(cfg: dict, key):
    """Seeded float32 weights in the layout the serving engine takes:
    ``{mlp: {"layers": [{"w": (in, out), "b": (out,)}, ...]}}``.
    LeCun-normal weights (keeps the N_o-fold message sums O(1)) and
    small non-zero biases, so the bias path is compared too.  Call it
    under ``jax.jit``: one program makes every leaf on the device."""
    out = {}
    for name, dims in mlp_dims(cfg).items():
        key, sub = jax.random.split(key)
        keys = jax.random.split(sub, 2 * len(dims))
        out[name] = {"layers": [
            {"w": jax.random.normal(keys[2 * i], (a, b), jnp.float32)
             / np.sqrt(a),
             "b": 0.1 * jax.random.normal(keys[2 * i + 1], (b,), jnp.float32)}
            for i, (a, b) in enumerate(dims)]}
    return out


def dot_highest(a, w):
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def dot_high(a, w):
    """XLA's ``Precision.HIGH``: three bfloat16 passes on a TPU (the
    CPU computes it in full float32)."""
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGH)


def dot_bf16x3(a, w):
    """bf16_3x written out: a*w ~ hi*hi + hi*lo + lo*hi, each product
    exact in fp32 and accumulated in fp32 -- ``Precision.HIGH`` as a
    TPU computes it, on any backend.  (On a TPU, XLA's excess-precision
    rewrites fold the hi/lo split away, so there use ``dot_high``.)"""
    def split(v):
        hi = v.astype(jnp.bfloat16)
        return hi, (v - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    def mm(u, v):
        return jnp.matmul(u, v, preferred_element_type=jnp.float32)

    a_hi, a_lo = split(a)
    w_hi, w_lo = split(w)
    return mm(a_hi, w_hi) + mm(a_hi, w_lo) + mm(a_lo, w_hi)


def _mlp(layers, x, dot):
    for i, lp in enumerate(layers):
        x = dot(x, lp["w"]) + lp["b"]
        if i < len(layers) - 1:
            x = jnp.maximum(x, 0.0)
    return x


def forward(params, x, dot=dot_highest):
    """x (B, N, P) float32 -> logits (B, n_targets) float32."""
    b, n, p = x.shape
    recv = jnp.broadcast_to(x[:, :, None, :], (b, n, n, p))
    send = jnp.broadcast_to(x[:, None, :, :], (b, n, n, p))
    e = _mlp(params["fr"]["layers"], jnp.concatenate([recv, send], -1), dot)
    off_diag = 1.0 - jnp.eye(n, dtype=jnp.float32)
    ebar = jnp.sum(e * off_diag[None, :, :, None], axis=2)
    o = _mlp(params["fo"]["layers"], jnp.concatenate([x, ebar], -1), dot)
    return _mlp(params["phi"]["layers"], jnp.sum(o, axis=1), dot)


def dot_bf16(a, w):
    """One bfloat16 pass accumulated in float32, on any backend."""
    return jnp.matmul(a.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


DOTS = {"highest": dot_highest, "high": dot_high, "bf16x3": dot_bf16x3,
        "bf16": dot_bf16, "default": jnp.matmul}


@functools.partial(jax.jit, static_argnames=("precision",))
def _forward_jit(params, x, precision):
    return forward(params, x, DOTS[precision])


def reference(params, x: np.ndarray, *, precision: str = "highest",
              chunk: int = REF_CHUNK) -> np.ndarray:
    """Logits of ``x`` in fixed-size chunks (one compiled shape).

    The chunked driver follows ``chip_smoke.reference`` of this
    repository (pad the last chunk, slice it back)."""
    out = []
    for i in range(0, x.shape[0], chunk):
        xb = x[i:i + chunk]
        pad = np.zeros((chunk - xb.shape[0], *xb.shape[1:]), xb.dtype)
        out.append(np.asarray(_forward_jit(
            params, np.concatenate([xb, pad]), precision))[:xb.shape[0]])
    return np.concatenate(out) if out else np.zeros((0, 0), np.float32)


# -- work count ---------------------------------------------------------------

def flops_per_jet(cfg: dict) -> int:
    """Least matmul FLOPs exact JEDI-net needs for one jet: f_R's first
    layer as two per-node projections (W_r x_r + W_s x_s), f_R's later
    layers per edge over N(N-1) edges, f_O per node, phi_O once."""
    n, dims = cfg["n_objects"], mlp_dims(cfg)
    (d_in, h1), *fr_rest = dims["fr"]
    first = 2 * d_in * h1 * n            # both halves, each (P -> h1)
    edges = sum(2 * a * b for a, b in fr_rest) * n * (n - 1)
    f_o = sum(2 * a * b for a, b in dims["fo"]) * n
    phi = sum(2 * a * b for a, b in dims["phi"])
    return first + edges + f_o + phi


def weight_bytes(cfg: dict, bytes_per_weight: int = 4) -> int:
    """Every weight and bias, once, at the serving dtype."""
    return bytes_per_weight * sum(a * b + b for dims in mlp_dims(cfg).values()
                                  for a, b in dims)


def call_bytes(cfg: dict, rows: int, bytes_per_weight: int = 4) -> int:
    """Least HBM traffic of one whole-network call on ``rows`` jets:
    the weights once, x in (float32), logits out (float32)."""
    x = rows * cfg["n_objects"] * cfg["n_features"] * 4
    return weight_bytes(cfg, bytes_per_weight) + x + rows * cfg["n_targets"] * 4

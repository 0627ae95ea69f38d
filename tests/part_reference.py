"""Plain Particle Transformer on one jet: the tests' reference.

A copy of the forward of ``chipbench/configs/part.py`` (the benchmark's
reference), kept in the test tree so the tests hold the program to a
model written independently of it: one jet at a time, every BatchNorm on
its own, the pair embedding over the unordered pairs i < j, in float32
at ``Precision.HIGHEST``.  Nothing here is imported from the program.
The equations are in that file's docstring and in
``repro/core/particle_transformer.py``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-5
PAIR_EPS = 1e-8
NEG = -1e30


def dot_highest(a, w):
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def _layer_norm(p, x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * p["g"] + p["b"]


def _batch_norm(p, x):
    return (x - p["mean"]) / jnp.sqrt(p["var"] + EPS) * p["g"] + p["b"]


def _gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / math.sqrt(2.0)))


def _linear(p, x, dot):
    return dot(x, p["w"]) + p["b"]


def pair_bias(params, x, dot):
    """U of one jet (H, N, N) from its unordered pairs i < j."""
    n = x.shape[0]
    iu, ju = np.triu_indices(n, 1)
    pt = jnp.exp(x[:, 0])
    eta, phi = x[:, 1], x[:, 2]
    pi_, pj = pt[iu], pt[ju]
    deta = eta[iu] - eta[ju]
    dphi = jnp.mod(phi[iu] - phi[ju] + math.pi, 2 * math.pi) - math.pi
    delta = jnp.sqrt(deta ** 2 + dphi ** 2)
    ptmin = jnp.minimum(pi_, pj)
    m2 = 4 * pi_ * pj * (jnp.sinh(deta / 2) ** 2 + jnp.sin(dphi / 2) ** 2)
    u = jnp.log(jnp.maximum(
        jnp.stack([ptmin * delta, ptmin / (pi_ + pj), delta, m2], -1),
        PAIR_EPS))
    u = _batch_norm(params["pair"]["bn"], u)
    for layer in params["pair"]["layers"]:
        u = _linear(layer, u, dot)
        if "bn" in layer:
            u = _gelu(_batch_norm(layer["bn"], u))
    # (i, j) -> the row of pair (min, max); the diagonal -> a zero row
    index = np.full((n, n), len(iu))
    index[iu, ju] = index[ju, iu] = np.arange(len(iu))
    full = jnp.concatenate([u, jnp.zeros((1, u.shape[1]), u.dtype)])[index]
    m = (jnp.any(x != 0, axis=-1)).astype(jnp.float32)
    return jnp.transpose(full, (2, 0, 1)) * (m[:, None] * m[None, :])


def _attention(p, q_in, kv, bias, key_mask, dot):
    h = p["head_scale"].shape[0]
    dh = q_in.shape[-1] // h

    def heads(y):                       # (T, d) -> (H, T, dh)
        return jnp.transpose(y.reshape(y.shape[0], h, dh), (1, 0, 2))

    q = heads(_linear(p["q"], q_in, dot))
    k = heads(_linear(p["k"], kv, dot))
    v = heads(_linear(p["v"], kv, dot))
    logits = dot(q, jnp.transpose(k, (0, 2, 1))) / math.sqrt(dh)
    if bias is not None:
        logits = logits + bias
    logits = jnp.where(key_mask[None, None, :] > 0, logits, NEG)
    att = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    att = att / jnp.sum(att, axis=-1, keepdims=True)
    a = jnp.transpose(dot(att, v), (1, 0, 2)).reshape(q_in.shape[0], h * dh)
    a = _linear(p["o"], a, dot)
    a = (a.reshape(-1, h, dh) * p["head_scale"][None, :, None]).reshape(
        a.shape)
    return _layer_norm(p["ln_attn_out"], a)


def _ffn(p, x, dot):
    f = _gelu(_linear(p["fc1"], _layer_norm(p["ln_ffn_in"], x), dot))
    f = _linear(p["fc2"], _layer_norm(p["ln_ffn_mid"], f), dot)
    return p["resid_scale"] * x + f


def forward_jet(params, x, dot=dot_highest):
    """x (N, 17) float32 -> logits (n_targets,) float32."""
    m = (jnp.any(x != 0, axis=-1)).astype(jnp.float32)
    bias = pair_bias(params, x, dot)
    h = _batch_norm(params["embed"]["bn"], x)
    for layer in params["embed"]["layers"]:
        h = _gelu(_linear(layer, _layer_norm(layer["ln"], h), dot))
    for p in params["blocks"]:
        u = _layer_norm(p["ln_attn_in"], h)
        h = _ffn(p, h + _attention(p, u, u, bias, m, dot), dot)
    c = params["cls_token"][None, :]
    key_mask = jnp.concatenate([jnp.ones((1,), jnp.float32), m])
    for p in params["cls_blocks"]:
        u = _layer_norm(p["ln_attn_in"], jnp.concatenate([c, h]))
        c = _ffn(p, c + _attention(p, u[:1], u, None, key_mask, dot), dot)
    return _linear(params["head"], _layer_norm(params["norm"], c[0]), dot)


def forward(params, x, dot=dot_highest):
    """x (B, N, 17) float32 -> logits (B, n_targets): each jet alone."""
    return jax.vmap(lambda xj: forward_jet(params, xj, dot))(x)

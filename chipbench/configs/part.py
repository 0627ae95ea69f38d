"""Plain Particle Transformer: the benchmark's own weights, reference and
work count.

ParT (Qu, Li and Qian, arXiv:2202.03772, Sec. 3) on one jet X (N, 17):

- mask m_i = 0 where row i is all zeros (a padding particle), else 1;
- kinematics: pT = exp(X_0), eta = X_1, phi = X_2, a massless particle;
- pair features of the unordered pairs i < j: ln kT, ln z, ln Delta,
  ln m^2, each ln max(., 1e-8), with m^2 = 4 pT_i pT_j (sinh^2(deta/2)
  + sin^2(dphi/2)) and dphi wrapped into [-pi, pi);
- pair embedding: BatchNorm, then Linear -> BatchNorm -> GELU at widths
  64, 64, 64, then a plain Linear to one bias per head; U is filled in
  symmetrically, 0 on the diagonal and on pairs with a padding particle;
- particle embedding: BatchNorm, then 3 x [LayerNorm -> Linear -> GELU]
  at widths (128, 512, 128);
- 8 particle-attention blocks: a = LN(scale_h * (W_o softmax(q k / 4 +
  U, masked keys) v)) on LN(x); x += a; x = lambda * x + W_2 LN(GELU(
  W_1 LN(x)));
- 2 class-attention blocks: the same block, the class token c as the
  only query, keys and values from [c; x] (c never masked, no U);
- logits = W LN(c) + b.

GELU is the exact erf form; LayerNorm and BatchNorm eps 1e-5; a masked
key's logit is -1e30 (finite, so an all-padding row stays finite; exactly
as -inf wherever a jet has a particle).

This file imports nothing of the program under test.  The reference
runs one jet at a time (mapped over a chunk of jets), every BatchNorm
on its own, in float32 at ``Precision.HIGHEST``.  The control of the
comparison (``chipbench/control.py``) computes the same with every
matmul at ``precision="high"`` (three bfloat16 passes on a TPU).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: Jets per reference call: bounds the (chunk, 8128, 64) float32 pair
#: activations to 532 MB a layer.
REF_CHUNK = 256
EPS = 1e-5
PAIR_EPS = 1e-8
NEG = -1e30
N_PAIR = 4


# -- weights --------------------------------------------------------------------

class _Draws:
    """Consecutive slices of one normal draw: every leaf of the weights
    comes from a single ``jax.random.normal`` call, which compiles in a
    few seconds on a TPU (hundreds of separate draws took minutes)."""

    def __init__(self, z=None):
        self.z, self.at = z, 0

    def normal(self, shape):
        n = int(np.prod(shape))
        out = (jnp.zeros(shape, jnp.float32) if self.z is None
               else self.z[self.at:self.at + n].reshape(shape))
        self.at += n
        return out


def _lin(r, a, b):
    return {"w": r.normal((a, b)) / math.sqrt(a), "b": 0.1 * r.normal((b,))}


def _ln(r, d):
    return {"g": 1.0 + 0.1 * r.normal((d,)), "b": 0.1 * r.normal((d,))}


def _bn(r, d, mean_std=0.1):
    return {"mean": mean_std * r.normal((d,)),
            "var": jnp.exp(0.2 * r.normal((d,))),
            "g": 1.0 + 0.1 * r.normal((d,)), "b": 0.1 * r.normal((d,))}


def _block(r, d, f, h):
    return {"ln_attn_in": _ln(r, d), "q": _lin(r, d, d), "k": _lin(r, d, d),
            "v": _lin(r, d, d), "o": _lin(r, d, d),
            "head_scale": 1.0 + 0.1 * r.normal((h,)),
            "ln_attn_out": _ln(r, d), "ln_ffn_in": _ln(r, d),
            "fc1": _lin(r, d, f), "ln_ffn_mid": _ln(r, f),
            "fc2": _lin(r, f, d), "resid_scale": 1.0 + 0.1 * r.normal((d,))}


def _params(cfg: dict, r: _Draws) -> dict:
    emb = [cfg["n_features"], *cfg["embed_dims"]]
    pair = [N_PAIR, *cfg["pair_embed_dims"]]
    d, f, h = emb[-1], cfg["ffn_dim"], cfg["n_heads"]
    embed = {"bn": _bn(r, emb[0]),
             "layers": [{"ln": _ln(r, a), **_lin(r, a, b)}
                        for a, b in zip(emb[:-1], emb[1:])]}
    pair_in = _bn(r, N_PAIR, mean_std=1.0)
    pair_layers = []
    for i, (a, b) in enumerate(zip(pair[:-1], pair[1:])):
        layer = _lin(r, a, b)
        if i < len(pair) - 2:
            layer["bn"] = _bn(r, b)
        pair_layers.append(layer)
    return {"embed": embed, "pair": {"bn": pair_in, "layers": pair_layers},
            "blocks": [_block(r, d, f, h) for _ in range(cfg["n_blocks"])],
            "cls_blocks": [_block(r, d, f, h)
                           for _ in range(cfg["n_cls_blocks"])],
            "cls_token": 0.1 * r.normal((d,)), "norm": _ln(r, d),
            "head": _lin(r, d, cfg["n_targets"])}


def init_params(cfg: dict, key):
    """Seeded float32 weights in the layout the serving engine takes
    (``embed``, ``pair``, ``blocks``, ``cls_blocks``, ``cls_token``,
    ``norm``, ``head``).  LeCun-normal weights, biases and norm offsets
    N(0, 0.1^2), gains, head scales and residual scales 1 + N(0, 0.1^2);
    BatchNorm running means N(0, 0.1^2) (the pair features' input
    BatchNorm N(0, 1)) and variances exp(N(0, 0.2^2)).  Call it under
    ``jax.jit``: one program makes every leaf on the device."""
    count = _Draws()
    _params(cfg, count)
    return _params(cfg, _Draws(jax.random.normal(key, (count.at,),
                                                 jnp.float32)))


# -- matmul precisions ------------------------------------------------------------

def dot_highest(a, w):
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def dot_high(a, w):
    """XLA's ``Precision.HIGH``: three bfloat16 passes on a TPU."""
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGH)


def dot_bf16x3(a, w):
    """bf16_3x written out: a*w ~ hi*hi + hi*lo + lo*hi, each product
    exact in fp32 and accumulated in fp32 -- ``Precision.HIGH`` as a
    TPU computes it, on any backend (the CPU computes ``HIGH`` in full
    float32)."""
    def split(v):
        hi = v.astype(jnp.bfloat16)
        return hi, (v - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    def mm(u, v):
        return jnp.matmul(u, v, preferred_element_type=jnp.float32)

    a_hi, a_lo = split(a)
    w_hi, w_lo = split(w)
    return mm(a_hi, w_hi) + mm(a_hi, w_lo) + mm(a_lo, w_hi)


def dot_bf16(a, w):
    """One bfloat16 pass accumulated in float32, on any backend."""
    return jnp.matmul(a.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


DOTS = {"highest": dot_highest, "high": dot_high, "bf16x3": dot_bf16x3,
        "bf16": dot_bf16, "default": jnp.matmul}


# -- one jet ------------------------------------------------------------------------

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


@functools.partial(jax.jit, static_argnames=("precision",))
def _forward_jit(params, x, precision):
    return forward(params, x, DOTS[precision])


def reference(params, x: np.ndarray, *, precision: str = "highest",
              chunk: int = REF_CHUNK) -> np.ndarray:
    """Logits of ``x`` in fixed-size chunks (one compiled shape; the
    last chunk is padded with zero jets and sliced back)."""
    out = []
    for i in range(0, x.shape[0], chunk):
        xb = x[i:i + chunk]
        pad = np.zeros((chunk - xb.shape[0], *xb.shape[1:]), xb.dtype)
        out.append(np.asarray(_forward_jit(
            params, np.concatenate([xb, pad]), precision))[:xb.shape[0]])
    return np.concatenate(out) if out else np.zeros((0, 0), np.float32)


# -- work count -----------------------------------------------------------------

def flops_parts(cfg: dict) -> dict:
    """Matmul FLOPs the model needs for one jet, by part: the pair
    embedding over the N(N-1)/2 unordered pairs, the class-attention
    part with the head.  (Norms, GELU, softmax and the pair features
    are not counted.)"""
    n, d, f = cfg["n_objects"], cfg["embed_dims"][-1], cfg["ffn_dim"]
    emb = [cfg["n_features"], *cfg["embed_dims"]]
    pair = [N_PAIR, *cfg["pair_embed_dims"]]

    def mlp(ws):
        return sum(2 * a * b for a, b in zip(ws[:-1], ws[1:]))

    block = n * (4 * 2 * d * d + 2 * 2 * d * f) + 2 * 2 * n * n * d
    cls = (2 * 2 * d * d                  # q from c, out projection
           + 2 * 2 * (n + 1) * d * d      # k, v from [c; x]
           + 2 * 2 * (n + 1) * d          # q k and att v
           + 2 * 2 * d * f)               # the FFN on c
    return {"particle_embed": n * mlp(emb),
            "pair_embed": n * (n - 1) // 2 * mlp(pair),
            "particle_blocks": cfg["n_blocks"] * block,
            "class_blocks": cfg["n_cls_blocks"] * cls
            + 2 * d * cfg["n_targets"]}


def flops_per_jet(cfg: dict) -> int:
    """Matmul FLOPs ParT needs for one jet (667,225,600 at the published
    JetClass widths)."""
    return sum(flops_parts(cfg).values())


def weight_bytes(cfg: dict, bytes_per_weight: int = 4) -> int:
    """Every learned number once (BatchNorm statistics included)."""
    leaves = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    return bytes_per_weight * sum(int(np.prod(v.shape))
                                  for v in jax.tree.leaves(leaves))


def call_bytes(cfg: dict, rows: int, bytes_per_weight: int = 4) -> int:
    """Least HBM traffic of one whole-network call on ``rows`` jets:
    the weights once, x in (float32), logits out (float32)."""
    x = rows * cfg["n_objects"] * cfg["n_features"] * 4
    return weight_bytes(cfg, bytes_per_weight) + x + rows * cfg["n_targets"] * 4

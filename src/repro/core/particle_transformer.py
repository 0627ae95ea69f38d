"""Particle Transformer (ParT) for jet tagging, served on the trigger path.

Qu, Li and Qian, "Particle Transformer for Jet Tagging" (arXiv:2202.03772,
ICML 2022), Sec. 3 and Figs. 2-3, at its published JetClass widths: 128
particles of 17 features, a particle embedding of widths (128, 512,
128), a pair embedding of widths (64, 64, 64, 8) that gives each of the
8 heads a learned bias U_ij on its attention logits, 8 particle-attention
blocks (d = 128, 8 heads of 16, feed-forward 512), 2 class-attention
blocks and 10 classes.

The input is one ``(batch, N, 17)`` array.  A padding particle is a row
of zeros; a jet of fewer particles is served padded, with no second
input.  The kinematics are read from the first three channels (ln pT,
eta, phi of a massless particle), and the four pair features are
computed in exact forms free of cancellation (:func:`pair_features`).

The program's form: the pair embedding's BatchNorms are folded into its
Linears (the particle embedding's input BatchNorm, followed by a
LayerNorm, stays an affine), and the three stages carry the named
scopes ``part.pair_embed``, ``part.particle_blocks`` and
``part.class_blocks``.  Two paths serve it (registered below): ``part``
runs the pair embedding as one Pallas kernel (``kernels/part_pair``,
:func:`pair_bias_kernel`) whose 64-wide activations never leave VMEM,
over each unordered pair once (N^2/2 packed lanes a jet, the 8,128
pairs i < j and 64 masked lanes at N = 128), and unfolds the symmetric
U from them; ``part_xla``, its fallback, is the plain XLA program, over
all N x N ordered pairs.  The other activations live in HBM, so both
ladders are sized against an HBM budget (:func:`hbm_bytes_per_sample`,
:func:`hbm_bytes_per_sample_xla`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.paths import register_path
from repro.kernels.part_pair import ops as part_pair

#: LayerNorm and BatchNorm epsilon (PyTorch's default, as weaver-core uses).
NORM_EPS = 1e-5
#: Floor of the pair features before their logarithm.
PAIR_EPS = 1e-8
#: The logit a masked key takes.  Finite, so an all-padding jet (a
#: bucket's padding row) gives finite logits; wherever a jet has a
#: particle, exp(NEG - max) is exactly 0, as -inf would give.
NEG = -1e30
#: Pair features: ln kT, ln z, ln Delta, ln m^2 (weaver-core's order).
N_PAIR_FEATURES = 4

#: HBM the ``part`` path's bucket ladder may fill with one bucket's
#: working set: 12 GiB of a v5e chip's 16 GB, the rest left to the
#: runtime, the plans in flight and the compiled programs.
HBM_BUDGET_BYTES = 12 * 2**30


@dataclasses.dataclass(frozen=True)
class ParticleTransformerConfig:
    """ParT hyper-parameters; the defaults are the published JetClass
    model (arXiv:2202.03772, Sec. 3; weaver-core ``ParticleTransformer``)."""

    family: ClassVar[str] = "part"

    n_objects: int = 128                 # N: particles per jet
    n_features: int = 17                 # particle features
    embed_dims: tuple = (128, 512, 128)  # particle embedding widths
    pair_embed_dims: tuple = (64, 64, 64, 8)  # the last is one per head
    n_heads: int = 8
    n_blocks: int = 8                    # particle-attention blocks
    n_cls_blocks: int = 2                # class-attention blocks
    ffn_dim: int = 512
    n_targets: int = 10
    compute_dtype: str = "float32"

    @property
    def d_model(self) -> int:
        return self.embed_dims[-1]

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def __post_init__(self):
        if self.pair_embed_dims[-1] != self.n_heads:
            raise ValueError(
                f"the pair embedding's last width "
                f"{self.pair_embed_dims[-1]} must be n_heads={self.n_heads}")

    def with_(self, **kw) -> "ParticleTransformerConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Weights.
# ---------------------------------------------------------------------------

def _linear(key, d_in: int, d_out: int) -> dict:
    """LeCun-normal weight, bias N(0, 0.1^2): small non-zero biases so
    the bias path is compared too."""
    kw, kb = jax.random.split(key)
    return {"w": jax.random.normal(kw, (d_in, d_out), jnp.float32)
            / math.sqrt(d_in),
            "b": 0.1 * jax.random.normal(kb, (d_out,), jnp.float32)}


def _norm(key, d: int) -> dict:
    kg, kb = jax.random.split(key)
    return {"g": 1.0 + 0.1 * jax.random.normal(kg, (d,), jnp.float32),
            "b": 0.1 * jax.random.normal(kb, (d,), jnp.float32)}


def _batchnorm(key, d: int, mean_std: float = 0.1) -> dict:
    """Inference BatchNorm: running statistics and affine from the seed."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {"mean": mean_std * jax.random.normal(k1, (d,), jnp.float32),
            "var": jnp.exp(0.2 * jax.random.normal(k2, (d,), jnp.float32)),
            "g": 1.0 + 0.1 * jax.random.normal(k3, (d,), jnp.float32),
            "b": 0.1 * jax.random.normal(k4, (d,), jnp.float32)}


def _block(key, cfg: ParticleTransformerConfig) -> dict:
    d, f = cfg.d_model, cfg.ffn_dim
    ks = jax.random.split(key, 12)
    return {"ln_attn_in": _norm(ks[0], d),
            "q": _linear(ks[1], d, d), "k": _linear(ks[2], d, d),
            "v": _linear(ks[3], d, d), "o": _linear(ks[4], d, d),
            "head_scale": 1.0 + 0.1 * jax.random.normal(
                ks[5], (cfg.n_heads,), jnp.float32),
            "ln_attn_out": _norm(ks[6], d),
            "ln_ffn_in": _norm(ks[7], d),
            "fc1": _linear(ks[8], d, f),
            "ln_ffn_mid": _norm(ks[9], f),
            "fc2": _linear(ks[10], f, d),
            "resid_scale": 1.0 + 0.1 * jax.random.normal(
                ks[11], (d,), jnp.float32)}


def init_params(key, cfg: ParticleTransformerConfig) -> dict:
    """Seeded float32 weights of the whole model.

    Layout (shared with the plain references of the benchmark and the
    tests): ``embed`` {``bn``, ``layers`` [{``ln``, ``w``, ``b``}]},
    ``pair`` {``bn``, ``layers`` [{``w``, ``b``, ``bn``}, ..., {``w``,
    ``b``}]}, ``blocks`` and ``cls_blocks`` (lists of :func:`_block`),
    ``cls_token``, ``norm``, ``head`` {``w``, ``b``}."""
    k_emb, k_pair, k_blk, k_cls, k_tok, k_norm, k_head = jax.random.split(
        key, 7)
    emb_in = (cfg.n_features, *cfg.embed_dims[:-1])
    ke = jax.random.split(k_emb, 2 * len(cfg.embed_dims) + 1)
    embed = {"bn": _batchnorm(ke[0], cfg.n_features),
             "layers": [{"ln": _norm(ke[1 + 2 * i], a),
                         **_linear(ke[2 + 2 * i], a, b)}
                        for i, (a, b) in enumerate(zip(emb_in,
                                                       cfg.embed_dims))]}
    pair_w = (N_PAIR_FEATURES, *cfg.pair_embed_dims)
    kp = jax.random.split(k_pair, 2 * len(pair_w))
    layers = []
    for i, (a, b) in enumerate(zip(pair_w[:-1], pair_w[1:])):
        layer = _linear(kp[1 + 2 * i], a, b)
        if i < len(pair_w) - 2:                  # the last layer: no BN
            layer["bn"] = _batchnorm(kp[2 + 2 * i], b)
        layers.append(layer)
    pair = {"bn": _batchnorm(kp[0], N_PAIR_FEATURES, mean_std=1.0),
            "layers": layers}
    return {"embed": embed, "pair": pair,
            "blocks": [_block(k, cfg)
                       for k in jax.random.split(k_blk, cfg.n_blocks)],
            "cls_blocks": [_block(k, cfg)
                           for k in jax.random.split(k_cls, cfg.n_cls_blocks)],
            "cls_token": 0.1 * jax.random.normal(k_tok, (cfg.d_model,),
                                                 jnp.float32),
            "norm": _norm(k_norm, cfg.d_model),
            "head": _linear(k_head, cfg.d_model, cfg.n_targets)}


# ---------------------------------------------------------------------------
# Forward.
# ---------------------------------------------------------------------------

def _layer_norm(p, x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + NORM_EPS) * p["g"] + p["b"]


def _gelu(x):
    return jax.nn.gelu(x, approximate=False)


def _bn_affine(bn):
    """(scale, shift) of an inference BatchNorm."""
    scale = bn["g"] * jax.lax.rsqrt(bn["var"] + NORM_EPS)
    return scale, bn["b"] - bn["mean"] * scale


def _fold_pair_layers(pair) -> list[tuple]:
    """The pair embedding as (w, b) pairs with every BatchNorm folded
    in: the input BN into the first Linear, each hidden BN into the
    Linear before it."""
    scale, shift = _bn_affine(pair["bn"])
    out = []
    for i, layer in enumerate(pair["layers"]):
        w, b = layer["w"], layer["b"]
        if i == 0:
            w, b = scale[:, None] * w, b + shift @ w
        if "bn" in layer:
            s, t = _bn_affine(layer["bn"])
            w, b = w * s, b * s + t
        out.append((w, b))
    return out


def _wrapped_abs_dphi(phi_i, phi_j):
    """|phi_i - phi_j| wrapped into [0, pi], (B, Ni, Nj).  Built from the
    exact |difference|, so it is exactly symmetric in i, j."""
    d = jnp.abs(phi_i[:, :, None] - phi_j[:, None, :])
    return jnp.abs(jnp.mod(d + jnp.pi, 2 * jnp.pi) - jnp.pi)


def pair_features(x, y=None):
    """ln kT, ln z, ln Delta, ln m^2 of every ordered pair of a particle
    of ``x`` with one of ``y`` (``x`` itself by default), (B, Ni, Nj, 4).

    Channels 0, 1, 2 of a particle are ln pT, eta and phi of a massless
    particle.  m^2 = 4 pT_i pT_j (sinh^2(deta/2) + sin^2(dphi/2)) is the
    massless (E_i + E_j)^2 - |p_i + p_j|^2 without its cancellation."""
    y = x if y is None else y
    deta = x[..., 1][:, :, None] - y[..., 1][:, None, :]
    dphi = _wrapped_abs_dphi(x[..., 2], y[..., 2])
    pt_i = jnp.exp(x[..., 0])[:, :, None]
    pt_j = jnp.exp(y[..., 0])[:, None, :]
    pt_min = jnp.minimum(pt_i, pt_j)
    delta = jnp.sqrt(deta * deta + dphi * dphi)
    m2 = 4.0 * pt_i * pt_j * (jnp.square(jnp.sinh(0.5 * deta))
                              + jnp.square(jnp.sin(0.5 * dphi)))
    feats = jnp.stack([pt_min * delta, pt_min / (pt_i + pt_j), delta, m2],
                      axis=-1)
    return jnp.log(jnp.maximum(feats, PAIR_EPS))


def particle_mask(x):
    """1.0 for a particle, 0.0 for a padding row (all zeros), (B, N)."""
    return jnp.any(x != 0, axis=-1).astype(jnp.float32)


def pair_keep(mask):
    """1.0 for a pair of two distinct particles, 0.0 on the diagonal and
    for a pair with a padding particle, (B, N, N)."""
    n = mask.shape[1]
    return (mask[:, :, None] * mask[:, None, :]
            * (1.0 - jnp.eye(n, dtype=jnp.float32)))


def pair_bias(params, cfg: ParticleTransformerConfig, x, mask):
    """U, one attention bias per head for every pair, (B, H, N, N):
    symmetric, 0 on the diagonal and on every pair with a padding
    particle.  Plain XLA: each pair layer's activations go through HBM."""
    u = pair_features(x)
    folded = _fold_pair_layers(params["pair"])
    for i, (w, b) in enumerate(folded):
        u = u @ w + b
        if i < len(folded) - 1:
            u = _gelu(u)
    return jnp.transpose(u, (0, 3, 1, 2)) * pair_keep(mask)[:, None]


def _pair_features_channel_major(xi, xj):
    return jnp.moveaxis(pair_features(xi, xj), -1, 1)


def pair_bias_kernel(params, cfg: ParticleTransformerConfig, x, mask, *,
                     interpret: bool = False):
    """:func:`pair_bias` through the ``part_pair_embed`` kernel: the pair
    features of each unordered pair once, channel-major, in; U unfolded
    by symmetry, masked."""
    tiles = part_pair.modeled_residency(cfg, params, x.shape[0])
    return part_pair.part_pair_embed(
        _pair_features_channel_major, x, mask,
        _fold_pair_layers(params["pair"]), block_b=tiles["block_b"],
        interpret=interpret)


def pair_bias_unordered(params, cfg: ParticleTransformerConfig, x, mask):
    """U as the model defines it: the pair embedding over the N(N-1)/2
    unordered pairs i < j, every BatchNorm applied on its own, then
    filled in symmetrically (the path's numerical reference)."""
    n = x.shape[1]
    iu, ju = np.triu_indices(n, 1)
    u = pair_features(x)[:, iu, ju]
    scale, shift = _bn_affine(params["pair"]["bn"])
    u = u * scale + shift
    for layer in params["pair"]["layers"]:
        u = u @ layer["w"] + layer["b"]
        if "bn" in layer:
            s, t = _bn_affine(layer["bn"])
            u = _gelu(u * s + t)
    full = jnp.zeros((x.shape[0], n, n, cfg.n_heads), jnp.float32)
    full = full.at[:, iu, ju].set(u).at[:, ju, iu].set(u)
    keep = mask[:, :, None] * mask[:, None, :]
    return jnp.transpose(full, (0, 3, 1, 2)) * keep[:, None]


def _attention(p, cfg, q_in, kv_in, bias, key_mask):
    """Multi-head attention with the NormFormer head scale: q from
    ``q_in`` (B, Q, d), keys and values from ``kv_in`` (B, K, d),
    ``bias`` (B, H, Q, K) or None, ``key_mask`` (B, K)."""
    h, dh = cfg.n_heads, cfg.head_dim

    def heads(y):
        return y.reshape(*y.shape[:2], h, dh)

    q = heads(q_in @ p["q"]["w"] + p["q"]["b"])
    k = heads(kv_in @ p["k"]["w"] + p["k"]["b"])
    v = heads(kv_in @ p["v"]["w"] + p["v"]["b"])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
    if bias is not None:
        logits = logits + bias
    logits = jnp.where(key_mask[:, None, None, :] > 0, logits, NEG)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, axis=-1), v)
    a = a.reshape(*a.shape[:2], h * dh) @ p["o"]["w"] + p["o"]["b"]
    a = (a.reshape(*a.shape[:2], h, dh)
         * p["head_scale"][:, None]).reshape(a.shape)
    return _layer_norm(p["ln_attn_out"], a)


def _ffn_residual(p, x):
    f = _gelu(_layer_norm(p["ln_ffn_in"], x) @ p["fc1"]["w"] + p["fc1"]["b"])
    f = _layer_norm(p["ln_ffn_mid"], f) @ p["fc2"]["w"] + p["fc2"]["b"]
    return p["resid_scale"] * x + f


def _particle_block(p, cfg, x, bias, mask):
    u = _layer_norm(p["ln_attn_in"], x)
    x = x + _attention(p, cfg, u, u, bias, mask)
    return _ffn_residual(p, x)


def _class_block(p, cfg, c, x, mask):
    """c (B, 1, d) attends over [c; x]; the class token is never masked."""
    u = _layer_norm(p["ln_attn_in"], jnp.concatenate([c, x], axis=1))
    key_mask = jnp.concatenate([jnp.ones_like(mask[:, :1]), mask], axis=1)
    c = c + _attention(p, cfg, u[:, :1], u, None, key_mask)
    return _ffn_residual(p, c)


def _embed(p, x):
    scale, shift = _bn_affine(p["bn"])
    x = x * scale + shift
    for layer in p["layers"]:
        x = _gelu(_layer_norm(layer["ln"], x) @ layer["w"] + layer["b"])
    return x


def forward(params, cfg: ParticleTransformerConfig, x, *,
            bias_fn=pair_bias):
    """x (B, N, n_features) -> logits (B, n_targets), in float32."""
    x = x.astype(jnp.float32)
    mask = particle_mask(x)
    with jax.named_scope("part.pair_embed"):
        bias = bias_fn(params, cfg, x, mask)
    with jax.named_scope("part.particle_blocks"):
        h = _embed(params["embed"], x)
        h = _scan_blocks(params["blocks"], h,
                         lambda p, h: _particle_block(p, cfg, h, bias, mask))
    with jax.named_scope("part.class_blocks"):
        c = jnp.broadcast_to(params["cls_token"], (x.shape[0], 1, cfg.d_model))
        c = _scan_blocks(params["cls_blocks"], c,
                         lambda p, c: _class_block(p, cfg, c, h, mask))
        c = _layer_norm(params["norm"], c[:, 0])
        return c @ params["head"]["w"] + params["head"]["b"]


def _scan_blocks(blocks, carry, step):
    """``step`` over a list of blocks of one structure, as one
    ``lax.scan`` over their stacked weights: the compiled step holds one
    block body, not one per block (a third of the compile time)."""
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *blocks)
    return jax.lax.scan(lambda c, p: (step(p, c), None), carry, stacked)[0]


# ---------------------------------------------------------------------------
# Work and memory models.
# ---------------------------------------------------------------------------

def reference(params, cfg: ParticleTransformerConfig, x):
    """The ``part`` path's reference: :func:`forward` with U from
    :func:`pair_bias_unordered`."""
    return forward(params, cfg, x, bias_fn=pair_bias_unordered)


def pairs_per_sample(cfg: ParticleTransformerConfig) -> int:
    """Pairs the ``part`` path's pair kernel evaluates per jet: its
    packed lanes, N^2/2 (8,192 at N = 128: the 8,128 unordered pairs
    and 64 masked lanes)."""
    return part_pair.padded_pairs(cfg.n_objects)


def pairs_per_sample_xla(cfg: ParticleTransformerConfig) -> int:
    """Pairs the ``part_xla`` path's pair embedding evaluates per jet:
    all N x N ordered pairs, the diagonal included."""
    return cfg.n_objects * cfg.n_objects


def flops_per_sample(cfg: ParticleTransformerConfig,
                     pairs=pairs_per_sample) -> int:
    """Matmul FLOPs of one jet as a path computes it: the pair embedding
    over ``pairs(cfg)`` pairs, the ``part`` path's by default."""
    n, d, f, h = cfg.n_objects, cfg.d_model, cfg.ffn_dim, cfg.n_heads
    emb_w = (cfg.n_features, *cfg.embed_dims)
    embed = n * sum(2 * a * b for a, b in zip(emb_w[:-1], emb_w[1:]))
    pair_w = (N_PAIR_FEATURES, *cfg.pair_embed_dims)
    pair = pairs(cfg) * sum(
        2 * a * b for a, b in zip(pair_w[:-1], pair_w[1:]))
    block = n * 2 * (4 * d * d + 2 * d * f) + 2 * 2 * n * n * d
    cls = 2 * (2 * d * d + 2 * (n + 1) * d * d + 2 * d * f) \
        + 2 * 2 * (n + 1) * d
    return (embed + pair + cfg.n_blocks * block + cfg.n_cls_blocks * cls
            + 2 * d * cfg.n_targets)


def hbm_bytes_per_sample(cfg: ParticleTransformerConfig) -> int:
    """HBM working set of one jet in the ``part`` step, whose pair MLP
    runs in VMEM: the kernel's packed (5, N^2/2) input slab and (H,
    N^2/2) output, the unfolded U and the attention logits, float32.
    (Compiled for a v5e at the published widths, the step's temporaries
    come to 1.2 MiB a jet at rung 1024; this gives 1.4.)"""
    n = cfg.n_objects
    return 4 * (part_pair.padded_pairs(n) * (part_pair.IN_ROWS + cfg.n_heads)
                + n * n * 2 * cfg.n_heads)


def hbm_bytes_per_sample_xla(cfg: ParticleTransformerConfig) -> int:
    """HBM working set of one jet in the ``part_xla`` step, at its peak
    in the pair embedding: the input and the output of the widest pair
    layer, (N, N, max pair width) float32 each, and U's (H, N, N)
    float32 as margin.  (Compiled for a v5e at the published widths,
    the step's temporaries come to 8.1-8.4 MiB a jet; this gives 8.5.)"""
    n = cfg.n_objects
    return 4 * n * n * (2 * max(cfg.pair_embed_dims) + cfg.n_heads)


def _flops(cfg, batch, pairs=pairs_per_sample):
    return float(flops_per_sample(cfg, pairs) * batch)


def _hbm_traffic(cfg, batch, compute_bytes):
    """Least HBM traffic of one ``part`` step: the weights once, the
    input in, the logits out, and the kernel's packed input slab, its
    packed U and the unfolded U each written and read once."""
    n = cfg.n_objects
    acts = (n * cfg.n_features + cfg.n_targets
            + 2 * part_pair.padded_pairs(n)
            * (part_pair.IN_ROWS + cfg.n_heads)
            + 2 * n * n * cfg.n_heads)
    return float(weight_count(cfg) * compute_bytes + acts * batch * 4)


def _hbm_traffic_xla(cfg, batch, compute_bytes):
    """Least HBM traffic of one ``part_xla`` step: the weights once, the
    input in, the logits out, and each pair layer's activations written
    and read once (the XLA pair embedding cannot keep them on chip)."""
    n = cfg.n_objects
    pair_w = (N_PAIR_FEATURES, *cfg.pair_embed_dims)
    acts = 2 * n * n * sum(pair_w) + n * cfg.n_features + cfg.n_targets
    return float(weight_count(cfg) * compute_bytes + acts * batch * 4)


def weight_count(cfg: ParticleTransformerConfig) -> int:
    """Learned numbers of the model (BatchNorm running statistics
    included)."""
    d, f, h = cfg.d_model, cfg.ffn_dim, cfg.n_heads
    emb_w = (cfg.n_features, *cfg.embed_dims)
    embed = 4 * cfg.n_features + sum(2 * a + a * b + b for a, b
                                     in zip(emb_w[:-1], emb_w[1:]))
    pair_w = (N_PAIR_FEATURES, *cfg.pair_embed_dims)
    pair = 4 * N_PAIR_FEATURES + sum(a * b + b for a, b
                                     in zip(pair_w[:-1], pair_w[1:]))
    pair += 4 * sum(cfg.pair_embed_dims[:-1])
    block = 4 * (d * d + d) + h + 6 * d + d * f + f + 2 * f + f * d + d + d
    return (embed + pair + (cfg.n_blocks + cfg.n_cls_blocks) * block
            + d + 2 * d + d * cfg.n_targets + cfg.n_targets)


#: Fields the two ParT paths share: the model, its reference, an
#: HBM-sized ladder, and weights passed as a jit argument (8.6 MB of
#: them: as constants they would fill every rung's program and its
#: compile-cache entry).
_PART_PATH = dict(
    ref=reference,
    model="part",
    fused_level="none",
    complexity="O(N^2)",              # the pair embedding over N x N pairs
    compute_dtypes=("float32",),
    # the reference-class bar of the XLA paths; measured 1.7e-6 on one
    # TPU v5 lite at the published widths, ~1e-6 on the CPU
    tolerance=2e-4,
    memory_budget_bytes=HBM_BUDGET_BYTES,
    params_as_args=True,
)


@register_path(
    name="part",
    pallas=True,
    fallback="part_xla",
    per_sample_bytes=lambda cfg, params: hbm_bytes_per_sample(cfg),
    bytes_model=_hbm_traffic,
    residency_model=part_pair.modeled_residency,
    pairs_per_sample=pairs_per_sample,
    flops_model=_flops,
    description="Particle Transformer, pair embedding in one Pallas "
                "kernel (part_pair_embed)",
    **_PART_PATH,
)
def forward_part(params, cfg, x, *, interpret: bool = False):
    """The ``part`` path: :func:`forward` with U from
    :func:`pair_bias_kernel`."""
    return forward(params, cfg, x, bias_fn=functools.partial(
        pair_bias_kernel, interpret=interpret))


@register_path(
    name="part_xla",
    fallback=None,                    # terminal: plain XLA
    per_sample_bytes=lambda cfg, params: hbm_bytes_per_sample_xla(cfg),
    bytes_model=_hbm_traffic_xla,
    pairs_per_sample=pairs_per_sample_xla,
    flops_model=functools.partial(_flops, pairs=pairs_per_sample_xla),
    description="Particle Transformer (XLA), activations in HBM",
    **_PART_PATH,
)
def forward_part_xla(params, cfg, x):
    """The ``part_xla`` path: :func:`forward`."""
    return forward(params, cfg, x)

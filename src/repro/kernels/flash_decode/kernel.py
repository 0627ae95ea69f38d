"""Pallas TPU kernel: flash decode — one-token GQA attention over a KV cache.

The decode_32k / long_500k serving cells are memory-bound: each step reads
the whole (B, S, Hkv, D) cache once to produce (B, H, D) outputs.  The
roofline goal is therefore to touch every cache byte exactly once at full
HBM bandwidth.  The kernel tiles the cache sequence axis into VMEM-sized
chunks and keeps the FlashAttention online-softmax carry (m, l, acc) in
VMEM scratch across sequential grid steps — the (G, S) score matrix never
exists in HBM, and each (b, h) stream is one pass over its cache shard.

Grid: (B, Hkv, S/chunk); the chunk axis is the innermost (sequential on
TPU), so scratch carries are valid; (B, Hkv) are parallel.

This is the serving-path cousin of the paper's fusion argument: the FPGA
design fuses pipeline stages to avoid ping-pong buffers between them; here
we fuse score/softmax/weighted-sum to avoid HBM round-trips between them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, qpos_ref, kvpos_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, n_chunks: int, window):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    q = q_ref[0, 0].astype(jnp.float32)                 # (G, D), pre-scaled
    k = k_ref[0, :, 0].astype(jnp.float32)              # (C, D)
    v = v_ref[0, :, 0].astype(jnp.float32)              # (C, D)
    qp = qpos_ref[0]                                    # scalar int32
    kp = kvpos_ref[0]                                   # (C,) int32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G, C)
    ok = (kp >= 0) & (kp <= qp)
    if window is not None:
        ok &= (qp - kp) < window
    s = jnp.where(ok[None, :], s, NEG_INF)

    m_old = m_scr[...]                                  # (G, 1)
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                              # (G, C)
    corr = jnp.exp(m_old - m_new)                       # (G, 1)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)             # (G, D)
    m_scr[...] = m_new

    @pl.when(c == n_chunks - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_kernel_call(q, k, v, q_pos, kv_pos, *, chunk: int,
                             window=None, interpret: bool = False):
    """q: (B, Hkv, G, D) pre-scaled; k/v: (B, S, Hkv, D); S % chunk == 0."""
    b, hkv, g, d = q.shape
    s_len = k.shape[1]
    assert s_len % chunk == 0, (s_len, chunk)
    n_chunks = s_len // chunk
    grid = (b, hkv, n_chunks)

    kernel = functools.partial(_decode_kernel, n_chunks=n_chunks,
                               window=window)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, h_, c_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, chunk, 1, d), lambda b_, h_, c_: (b_, c_, h_, 0)),
            pl.BlockSpec((1, chunk, 1, d), lambda b_, h_, c_: (b_, c_, h_, 0)),
            pl.BlockSpec((1,), lambda b_, h_, c_: (b_,)),
            pl.BlockSpec((1, chunk), lambda b_, h_, c_: (b_, c_)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, h_, c_: (b_, h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, q_pos, kv_pos)

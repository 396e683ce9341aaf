"""Flash-decoding Pallas kernel: one query token vs a long KV cache.

Grid = (batch*kv_heads, T/block_k): the innermost axis streams KV-cache
blocks; the ``group`` query heads that share a kv head ride along as the
sublane axis of a single (group, D) query tile, so decode GQA costs one pass
over the cache per kv head (the memory-bound roofline optimum). A boolean
validity mask handles ragged/ring-buffer caches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale              # (G, D)
    k = k_ref[0].astype(jnp.float32)                      # (bk, D)
    v = v_ref[0].astype(jnp.float32)
    valid = valid_ref[0] != 0                             # (1, bk)
    s = q @ k.T                                           # (G, bk)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(valid, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention_grouped(q, k, v, valid, *, block_k: int = 512,
                             interpret: bool = True):
    """q: (BHkv, G, D); k, v: (BHkv, T, D); valid: (BHkv, T) bool.

    The mask travels as a (BHkv, 1, T) int32 array so that its block's
    last two dimensions are (1, block_k): the TPU lowering tiles the
    second-to-last axis by 8 unless the block spans the whole axis."""
    BHkv, G, D = q.shape
    T = k.shape[1]
    block_k = min(block_k, T)
    assert T % block_k == 0
    grid = (BHkv, T // block_k)
    kernel = functools.partial(_kernel, scale=D ** -0.5)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G, D), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bh, ki: (bh, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, G, D), lambda bh, ki: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((BHkv, G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, valid.astype(jnp.int32)[:, None, :])

"""Mamba-2 SSD (state-space duality) chunked-scan Pallas kernel.

Grid = (batch*heads, S/chunk): the innermost axis walks chunks sequentially,
carrying the (P, N) inter-chunk state in VMEM scratch. Each chunk does the
dual quadratic form — (chunk x chunk) decay-masked C·Bᵀ "attention" plus the
incoming-state contribution — entirely in VMEM with MXU-shaped matmuls
(chunk and N are 128-multiples for the full-size configs; P=64 rides the
sublane axis). This is the TPU-native adaptation of the paper's CUDA
chunk-parallel SSD: instead of warp-level shuffles, the intra-chunk work is
expressed as dense matmuls and the sequential dependency is confined to the
innermost grid axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_out_ref,
            state_ref, *, chunk: int):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)                      # (c, P)
    dt = dt_ref[0].astype(jnp.float32)                    # (1, c) row
    A = a_ref[pl.program_id(0)]                           # scalar (SMEM)
    Bm = b_ref[0].astype(jnp.float32)                     # (c, N)
    Cm = c_ref[0].astype(jnp.float32)                     # (c, N)

    # Prefix sums of dA as a column (over i) and a row (over j) by masked
    # reductions of a (c, c) tile: the TPU lowering has no cumsum, and
    # both orientations are needed for the pairwise decay below.
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = rows >= cols                                   # j <= i
    dA_r = jnp.broadcast_to(dt * A, (chunk, chunk))       # [i, j] = dA_j
    dA_c = dA_r.T                                         # [i, j] = dA_i
    seg_c = jnp.sum(jnp.where(mask, dA_r, 0.0), axis=1,
                    keepdims=True)                        # (c, 1)
    seg_r = jnp.sum(jnp.where(rows <= cols, dA_c, 0.0), axis=0,
                    keepdims=True)                        # (1, c)
    dt_c = jnp.broadcast_to(dt, (chunk, chunk)).T[:, :1]  # (c, 1)
    # intra-chunk attention-like dual form
    delta = jnp.where(mask, seg_c - seg_r, 0.0)
    decay = jnp.where(mask, jnp.exp(-delta), 0.0)         # (c, c)
    att = (Cm @ Bm.T) * decay * dt
    y = att @ x                                           # (c, P)
    # incoming-state contribution: y_i += exp(-seg_i) * C_i . S_prev
    state = state_ref[...]                                # (P, N)
    y = y + jnp.exp(-seg_c) * (Cm @ state.T)
    y_ref[0] = y.astype(y_ref.dtype)
    # state update: S' = exp(-sum dA) S + sum_j exp(-(seg_last-seg_j)) dt_j x_j B_j^T
    total = jnp.sum(dt * A)
    w = jnp.exp(-(total - seg_c)) * dt_c                  # (c, 1)
    state_new = (jnp.exp(-total) * state
                 + (x * w).T @ Bm)                        # (P, N)
    state_ref[...] = state_new

    @pl.when(ci == nc - 1)
    def _final():
        state_out_ref[0] = state_new.astype(state_out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_kernel(x, dt, A, B, C, *, chunk: int = 128,
                    interpret: bool = True):
    """x (b,s,h,p); dt (b,s,h); A (h,); B,C (b,s,g,n).

    Returns (y (b,s,h,p) fp32, final_state (b,h,p,n) fp32)."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    assert s % chunk == 0
    nc = s // chunk
    # layouts: head-major so each grid cell streams contiguous chunks
    xk = x.transpose(0, 2, 1, 3).reshape(b * h, s, p)
    dtk = dt.transpose(0, 2, 1).reshape(b * h, 1, s)
    Ak = jnp.broadcast_to(A[None], (b, h)).reshape(b * h)
    Bk = B.transpose(0, 2, 1, 3).reshape(b * g, s, n)
    Ck = C.transpose(0, 2, 1, 3).reshape(b * g, s, n)

    kernel = functools.partial(_kernel, chunk=chunk)
    y, state = pl.pallas_call(
        kernel,
        grid=(b * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, ci: (bh, 0, ci)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, n), lambda bh, ci: (bh // rep, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, ci: (bh // rep, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, p, n), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((b * h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xk, dtk, Ak, Bk, Ck)
    y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
    state = state.reshape(b, h, p, n)
    return y, state

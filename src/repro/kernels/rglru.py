"""RG-LRU linear-recurrence Pallas kernel (RecurrentGemma/Griffin).

The recurrence h_t = a_t * h_{t-1} + sqrt(1-a_t^2) * x_t is elementwise over
the width axis, so the kernel tiles width across the grid's first axis (fully
parallel, lane-aligned blocks of 128) and walks the innermost grid axis over
sequence chunks, carrying the running state in VMEM scratch. Inside a chunk
the time loop is a ``fori_loop`` over VREG rows — sequential in time but with
``block_w`` lanes of parallel ALU work per step, which is the right shape for
the VPU (there is no matmul here for the MXU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, a_ref, h0_ref, y_ref, hlast_ref, h_ref, a_sc, g_sc, *,
            block_s: int):
    si = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)        # (1, bw)

    # gate the whole chunk at once, then walk it row by row: the TPU
    # lowering reads a dynamic row from a ref, not from a value
    x = x_ref[0].astype(jnp.float32)                      # (bs, bw)
    a = jnp.exp(a_ref[0].astype(jnp.float32))
    a_sc[...] = a
    g_sc[...] = jnp.sqrt(jnp.clip(1.0 - a * a, 1e-9, 1.0)) * x

    def step(t, h):
        row = pl.ds(t, 1)
        h = a_sc[row, :] * h + g_sc[row, :]               # (1, bw)
        y_ref[0, row, :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_s, step, h_ref[...])
    h_ref[...] = h

    @pl.when(si == ns - 1)
    def _final():
        hlast_ref[0] = h_ref[...].astype(hlast_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_w", "block_s",
                                             "interpret"))
def rglru_scan_kernel(x, log_a, h0, *, block_w: int = 128,
                      block_s: int = 256, interpret: bool = True):
    """x, log_a: (B, S, W) fp32; h0: (B, W) fp32.

    Returns (ys (B,S,W) fp32, h_last (B,W) fp32)."""
    B, S, W = x.shape
    block_w = min(block_w, W)
    block_s = min(block_s, S)
    assert W % block_w == 0 and S % block_s == 0
    grid = (B * (W // block_w), S // block_s)
    nw = W // block_w

    kernel = functools.partial(_kernel, block_s=block_s)
    ys, h_last = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_w),
                         lambda bw, si: (bw // nw, si, bw % nw)),
            pl.BlockSpec((1, block_s, block_w),
                         lambda bw, si: (bw // nw, si, bw % nw)),
            pl.BlockSpec((1, 1, block_w),
                         lambda bw, si: (bw // nw, 0, bw % nw)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_s, block_w),
                         lambda bw, si: (bw // nw, si, bw % nw)),
            pl.BlockSpec((1, 1, block_w),
                         lambda bw, si: (bw // nw, 0, bw % nw)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32),
                        pltpu.VMEM((block_s, block_w), jnp.float32),
                        pltpu.VMEM((block_s, block_w), jnp.float32)],
        interpret=interpret,
    )(x, log_a, h0[:, None, :])
    return ys, h_last[:, 0, :]

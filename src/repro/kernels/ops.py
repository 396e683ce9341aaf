"""Jit'd public wrappers around the Pallas kernels.

These present the model-layer calling conventions ((B,S,H,D) attention
layouts etc.), handle layout shuffling into kernel-friendly shapes, and
pick the execution mode from the default backend: compiled on a TPU,
interpreted on the CPU (tests), and refused anywhere else, so a kernel
never turns into interpreted HLO on an accelerator unnoticed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention_grouped
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.rglru import rglru_scan_kernel
from repro.kernels.rmsnorm import rmsnorm_kernel
from repro.kernels.ssd import ssd_scan_kernel


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels compile for a TPU and are interpreted on the "
        f"CPU; the default backend here is {backend!r}")


def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int = 128, block_k: int = 128):
    """q: (B,S,H,D); k,v: (B,S,Hkv,D) -> (B,S,H,D)."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    qk = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kk = k.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    vk = v.transpose(0, 2, 1, 3).reshape(B * Hkv, S, D)
    out = flash_attention_bhsd(qk, kk, vk, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=_interpret())
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def decode_attention(q, k_cache, v_cache, valid, *, block_k: int = 512):
    """q: (B,1,H,D); caches: (B,T,Hkv,D); valid: (B,T) -> (B,1,H,D)."""
    B, _, H, D = q.shape
    T, Hkv = k_cache.shape[1], k_cache.shape[2]
    G = H // Hkv
    qk = q.reshape(B, Hkv, G, D).reshape(B * Hkv, G, D)
    kk = k_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)
    vk = v_cache.transpose(0, 2, 1, 3).reshape(B * Hkv, T, D)
    vmask = jnp.broadcast_to(valid[:, None, :], (B, Hkv, T)).reshape(
        B * Hkv, T)
    out = decode_attention_grouped(qk, kk, vk, vmask, block_k=block_k,
                                   interpret=_interpret())
    return out.reshape(B, Hkv, G, D).reshape(B, 1, H, D)


def rglru_scan(x, log_a, h0, *, block_w: int = 128, block_s: int = 256):
    """x, log_a (B,S,W) fp32; h0 (B,W) -> (ys, h_last) fp32."""
    B, S, W = x.shape
    bs = block_s
    while S % bs:
        bs //= 2
    bw = block_w if W % block_w == 0 else W
    return rglru_scan_kernel(x.astype(jnp.float32),
                             log_a.astype(jnp.float32),
                             h0.astype(jnp.float32),
                             block_w=bw, block_s=max(bs, 1),
                             interpret=_interpret())


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128):
    """Chunked SSD. Shapes per repro.kernels.ref.ssd_scan."""
    s = x.shape[1]
    ck = chunk
    while s % ck:
        ck //= 2
    return ssd_scan_kernel(x.astype(jnp.float32), dt.astype(jnp.float32),
                           A.astype(jnp.float32), B.astype(jnp.float32),
                           C.astype(jnp.float32), chunk=max(ck, 1),
                           interpret=_interpret())


def rmsnorm(x, weight, *, eps: float = 1e-6):
    return rmsnorm_kernel(x, weight, eps=eps, interpret=_interpret())

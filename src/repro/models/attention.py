"""Attention building blocks: GQA/MQA prefill + decode, sliding-window ring
buffer, MLA (deepseek), cross-attention (whisper). Reference paths are pure
jnp; the Pallas kernels in ``repro.kernels`` are dispatched when
``cfg.use_pallas`` is set (interpret mode on CPU, compiled on TPU).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import (ModelConfig, apply_rope, dense_init,
                                 rms_norm, split_keys)

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Core scaled-dot-product helpers (reference paths)
# ---------------------------------------------------------------------------

def gqa_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                  mask: Optional[jnp.ndarray], *,
                  causal: bool = False,
                  use_pallas: bool = False) -> jnp.ndarray:
    """q: (B,S,H,D); k,v: (B,T,Hkv,D); mask: broadcastable (B,1,S,T) bool.

    Grouped-query: H = G*Hkv query heads share each kv head.
    """
    if use_pallas and causal and mask is None and q.shape[1] == k.shape[1]:
        from repro.kernels import ops as kops
        return kops.flash_attention(q, k, v, causal=True)
    B, S, H, D = q.shape
    T = k.shape[1]
    Hkv = k.shape[2]
    G = H // Hkv
    qg = q.reshape(B, S, Hkv, G, D).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scores = jnp.einsum("bshgd,bthd->bhgst", qg, kf) * (D ** -0.5)
    if causal:
        cm = jnp.tril(jnp.ones((S, T), dtype=bool), k=T - S)
        scores = jnp.where(cm[None, None, None], scores, NEG_INF)
    if mask is not None:
        scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask,
                           scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, vf)
    return out.reshape(B, S, H, D).astype(q.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, valid: jnp.ndarray, *,
                     use_pallas: bool = False) -> jnp.ndarray:
    """Single-token attention. q: (B,1,H,D); caches: (B,T,Hkv,D);
    valid: (B,T) bool marking live cache slots."""
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.decode_attention(q, k_cache, v_cache, valid)
    mask = valid[:, None, None, :]                        # (B,1,1,T)
    return gqa_attention(q, k_cache, v_cache, mask)


def flash_attention_jnp(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, window: int = 0,
                        block_k: int = 512,
                        unroll: bool = False) -> jnp.ndarray:
    """Memory-bounded reference attention: lax.scan over KV blocks with a
    running (m, l, acc) streaming softmax — the jnp analogue of the Pallas
    flash kernel. Peak temp is O(S*block_k) instead of O(S*T), which is what
    lets the 32k prefill shapes fit per-device HBM (§Perf iteration 1).

    q: (B,S,H,Dk); k: (B,T,Hkv,Dk); v: (B,T,Hkv,Dv). Query/key absolute
    positions are their indices (prefill convention)."""
    B, S, H, D = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // Hkv
    scale = D ** -0.5
    qg = (q.reshape(B, S, Hkv, G, D).astype(jnp.float32)) * scale
    nb = -(-T // block_k)
    pad = nb * block_k - T
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = jnp.moveaxis(k.reshape(B, nb, block_k, Hkv, D), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, block_k, Hkv, Dv), 1, 0)
    rows = jnp.arange(S)

    def body(carry, blk):
        m, l, acc, j0 = carry
        kj, vj = blk
        s = jnp.einsum("bshgd,bthd->bshgt", qg, kj.astype(jnp.float32))
        cols = j0 + jnp.arange(block_k)
        mask = cols[None, :] < T
        if causal:
            mask = mask & (cols[None, :] <= rows[:, None])
        if window:
            mask = mask & (cols[None, :] > rows[:, None] - window)
        s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, :, None, None, :], p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bshgt,bthd->bshgd", p, vj.astype(jnp.float32))
        return (m_new, l_new, acc_new, j0 + block_k), None

    m0 = jnp.full((B, S, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, S, Hkv, G), jnp.float32)
    acc0 = jnp.zeros((B, S, Hkv, G, Dv), jnp.float32)
    if unroll:
        # python loop: exact XLA cost accounting (scan bodies are costed
        # once); used by the roofline cost-extrapolation variants
        carry = (m0, l0, acc0, 0)
        for i in range(nb):
            carry, _ = body(carry, (kb[i], vb[i]))
        m, l, acc, _ = carry
    else:
        (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, acc0, 0), (kb, vb))
    l = jnp.where(l == 0.0, 1.0, l)
    out = acc / l[..., None]
    return out.reshape(B, S, H, Dv).astype(q.dtype)


# threshold above which full-sequence attention switches to the chunked
# (flash-style) reference path; small shapes keep the naive path, whose
# numerics the kernel tests pin down exactly.
CHUNKED_ATTENTION_MIN_SEQ = 1024


# ---------------------------------------------------------------------------
# Param init
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, *, num_kv: Optional[int] = None):
    """Standard fused-proj GQA attention params."""
    num_kv = cfg.num_kv_heads if num_kv is None else num_kv
    dt = cfg.weight_dtype
    kq, kk, kv, ko = split_keys(key, 4)
    p = {
        "wq": dense_init(kq, (cfg.d_model, cfg.num_heads * cfg.head_dim), dt),
        "wk": dense_init(kk, (cfg.d_model, num_kv * cfg.head_dim), dt),
        "wv": dense_init(kv, (cfg.d_model, num_kv * cfg.head_dim), dt),
        "wo": dense_init(ko, (cfg.num_heads * cfg.head_dim, cfg.d_model), dt),
    }
    if cfg.use_qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), dt)
        p["k_norm"] = jnp.ones((cfg.head_dim,), dt)
    return p


def _project_qkv(p, cfg: ModelConfig, x: jnp.ndarray, num_kv: int):
    B, S, _ = x.shape
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = (x @ p["wk"].astype(x.dtype)).reshape(B, S, num_kv, cfg.head_dim)
    v = (x @ p["wv"].astype(x.dtype)).reshape(B, S, num_kv, cfg.head_dim)
    if cfg.use_qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


# ---------------------------------------------------------------------------
# KV cache containers
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    """Per-layer decode cache. Full mode: length = max_len; window mode:
    ring buffer of length = window, indexed with pos % window."""
    k: jnp.ndarray        # (B, T, Hkv, D)
    v: jnp.ndarray        # (B, T, Hkv, D)


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  *, num_kv: Optional[int] = None,
                  head_dim: Optional[int] = None) -> KVCache:
    num_kv = cfg.num_kv_heads if num_kv is None else num_kv
    head_dim = cfg.head_dim if head_dim is None else head_dim
    length = cfg.attention_window or max_len
    shape = (batch, length, num_kv, head_dim)
    z = jnp.zeros(shape, cfg.activation_dtype)
    return KVCache(k=z, v=z)


def cache_positions(cfg: ModelConfig, cache_len: int, pos: jnp.ndarray):
    """valid-slot mask for a decode step at absolute position ``pos``
    (number of tokens already in cache). Handles ring-buffer windows."""
    idx = jnp.arange(cache_len)
    if cfg.attention_window:
        # slots hold absolute positions pos-1, pos-2, ... (wrapped); a slot i
        # is valid if it has been written: i < pos (before wrap) or always
        # after the buffer has wrapped once.
        return (idx[None, :] < jnp.minimum(pos, cache_len)[:, None])
    return idx[None, :] < pos[:, None]


# ---------------------------------------------------------------------------
# Attention forward: full-sequence (train / prefill) and decode step
# ---------------------------------------------------------------------------

def attention_forward(p, cfg: ModelConfig, x: jnp.ndarray,
                      positions: jnp.ndarray, *,
                      num_kv: Optional[int] = None,
                      window: int = 0,
                      cache_len: Optional[int] = None
                      ) -> Tuple[jnp.ndarray, KVCache]:
    """Causal self-attention over a whole sequence. Returns output and the
    cache that a subsequent decode would consume (prefill contract)."""
    num_kv = cfg.num_kv_heads if num_kv is None else num_kv
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, cfg, x, num_kv)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    use_chunked = (cfg.ref_attention == "chunked"
                   and S >= CHUNKED_ATTENTION_MIN_SEQ
                   and not cfg.use_pallas)
    if use_chunked:
        out = flash_attention_jnp(q, k, v, causal=True, window=window,
                                  unroll=cfg.unroll_layers)
    elif window:
        # banded causal mask: j in (i-window, i]
        i = jnp.arange(S)[:, None]
        j = jnp.arange(S)[None, :]
        band = (j <= i) & (j > i - window)
        out = gqa_attention(q, k, v, band[None, None], use_pallas=False)
    else:
        out = gqa_attention(q, k, v, None, causal=True,
                            use_pallas=cfg.use_pallas)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    y = out @ p["wo"].astype(out.dtype)
    cache = _cache_from_prefill(cfg, k, v, window, cache_len)
    return y, cache


def _cache_from_prefill(cfg: ModelConfig, k, v, window: int,
                        cache_len: Optional[int] = None) -> KVCache:
    if window or cfg.attention_window:
        w = window or cfg.attention_window
        S = k.shape[1]
        if S >= w:
            k = jax.lax.dynamic_slice_in_dim(k, S - w, w, axis=1)
            v = jax.lax.dynamic_slice_in_dim(v, S - w, w, axis=1)
            # ring layout: slot (S - w + i) % w == written order; we re-roll so
            # that slot j holds absolute position with j == pos % w.
            shift = (S - w) % w
            k = jnp.roll(k, shift, axis=1)
            v = jnp.roll(v, shift, axis=1)
        else:
            pad = w - S
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    elif cache_len is not None and cache_len > k.shape[1]:
        pad = cache_len - k.shape[1]
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return KVCache(k=k, v=v)


def _write_cache(cache_arr: jnp.ndarray, new_vals: jnp.ndarray,
                 slot: jnp.ndarray,
                 layer: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Write each row's one new entry where it lives, and nothing else:
    ``cache_arr[layer, b, slot[b]] = new_vals[b, 0]``. cache (B,T,...), or
    with ``layer`` the stacked (L,B,T,...); new (B,1,...); slot (B,)."""
    new = new_vals[:, 0].astype(cache_arr.dtype)
    # index every dim but the last: an update window over (Hkv, D) would
    # pin Hkv next to D, and where the compiler lays T out between them
    # (phi3's 10 KV heads) the whole cache is relaid out around the scan
    rows, *mid = jnp.ix_(jnp.arange(new.shape[0]),
                         *map(jnp.arange, new.shape[1:-1]))
    idx = (rows, slot.reshape(rows.shape), *mid)
    if layer is not None:
        idx = (layer,) + idx
    return cache_arr.at[idx].set(new, indices_are_sorted=True,
                                 unique_indices=True)


def _layer_of(cache_arr: jnp.ndarray,
              layer: Optional[jnp.ndarray]) -> jnp.ndarray:
    """The layer's (B,T,...) view of a stacked cache, or the cache itself."""
    if layer is None:
        return cache_arr
    return jax.lax.dynamic_index_in_dim(cache_arr, layer, 0, keepdims=False)


def attention_decode(p, cfg: ModelConfig, x: jnp.ndarray, cache: KVCache,
                     pos: jnp.ndarray, *,
                     num_kv: Optional[int] = None,
                     window: int = 0,
                     layer: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, KVCache]:
    """One-token decode. x: (B,1,d_model); pos: (B,) int32 tokens-so-far.

    ``cache`` holds one layer (B,T,Hkv,D), or with ``layer`` every layer
    (L,B,T,Hkv,D); either way the returned cache differs from it only in the
    one slot per row that this step writes."""
    num_kv = cfg.num_kv_heads if num_kv is None else num_kv
    B = x.shape[0]
    q, k, v = _project_qkv(p, cfg, x, num_kv)
    if cfg.use_rope:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k = apply_rope(k, pos[:, None], cfg.rope_theta)
    w = window or cfg.attention_window
    cache_len = cache.k.shape[-3]
    slot = jnp.mod(pos, cache_len) if w else jnp.minimum(pos, cache_len - 1)
    k_new = _write_cache(cache.k, k, slot, layer)
    v_new = _write_cache(cache.v, v, slot, layer)
    valid = cache_positions(cfg.replace(attention_window=w), cache_len,
                            pos + 1)
    out = decode_attention(q, _layer_of(k_new, layer),
                           _layer_of(v_new, layer), valid,
                           use_pallas=cfg.use_pallas)
    out = out.reshape(B, 1, cfg.num_heads * cfg.head_dim)
    y = out @ p["wo"].astype(out.dtype)
    return y, KVCache(k=k_new, v=v_new)


# ---------------------------------------------------------------------------
# Cross attention (whisper decoder -> encoder states)
# ---------------------------------------------------------------------------

def init_cross_attention(key, cfg: ModelConfig):
    return init_attention(key, cfg, num_kv=cfg.num_kv_heads)


def cross_attention(p, cfg: ModelConfig, x: jnp.ndarray,
                    enc_k: jnp.ndarray, enc_v: jnp.ndarray) -> jnp.ndarray:
    """x: (B,S,d); enc_k/enc_v: (B,T,Hkv,D) precomputed from encoder."""
    B, S, _ = x.shape
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, cfg.num_heads,
                                              cfg.head_dim)
    out = gqa_attention(q, enc_k, enc_v, None)
    out = out.reshape(B, S, cfg.num_heads * cfg.head_dim)
    return out @ p["wo"].astype(out.dtype)


def encoder_kv(p, cfg: ModelConfig, enc_out: jnp.ndarray):
    B, T, _ = enc_out.shape
    k = (enc_out @ p["wk"].astype(enc_out.dtype)).reshape(
        B, T, cfg.num_kv_heads, cfg.head_dim)
    v = (enc_out @ p["wv"].astype(enc_out.dtype)).reshape(
        B, T, cfg.num_kv_heads, cfg.head_dim)
    return k, v


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2) with compressed KV cache
# ---------------------------------------------------------------------------

class MLACache(NamedTuple):
    c_kv: jnp.ndarray     # (B, T, kv_lora_rank) compressed latents
    k_rope: jnp.ndarray   # (B, T, qk_rope_head_dim) shared rope key


def init_mla(key, cfg: ModelConfig):
    dt = cfg.weight_dtype
    H = cfg.num_heads
    qk_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    ks = split_keys(key, 5)
    return {
        "wq": dense_init(ks[0], (cfg.d_model, H * qk_dim), dt),
        "w_dkv": dense_init(ks[1], (cfg.d_model,
                                    cfg.kv_lora_rank + cfg.qk_rope_head_dim), dt),
        "kv_norm": jnp.ones((cfg.kv_lora_rank,), dt),
        "w_uk": dense_init(ks[2], (cfg.kv_lora_rank,
                                   H * cfg.qk_nope_head_dim), dt),
        "w_uv": dense_init(ks[3], (cfg.kv_lora_rank, H * cfg.v_head_dim), dt),
        "wo": dense_init(ks[4], (H * cfg.v_head_dim, cfg.d_model), dt),
    }


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int) -> MLACache:
    dt = cfg.activation_dtype
    return MLACache(
        c_kv=jnp.zeros((batch, max_len, cfg.kv_lora_rank), dt),
        k_rope=jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dt))


def _mla_qkv(p, cfg: ModelConfig, x, positions):
    """Project q (nope+rope split) and compressed kv latents."""
    B, S, _ = x.shape
    H = cfg.num_heads
    qk_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, H, qk_dim)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    ckv = x @ p["w_dkv"].astype(x.dtype)                   # (B,S,rank+rope)
    c_kv, k_rope = jnp.split(ckv, [cfg.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]        # (B,S,rope)
    return q_nope, q_rope, c_kv, k_rope


def _mla_attend(p, cfg: ModelConfig, q_nope, q_rope, c_kv, k_rope, mask):
    """Attention over (possibly cached) latents; up-projects K/V lazily."""
    B, T = c_kv.shape[:2]
    H = cfg.num_heads
    k_nope = (c_kv @ p["w_uk"].astype(c_kv.dtype)).reshape(
        B, T, H, cfg.qk_nope_head_dim)
    v = (c_kv @ p["w_uv"].astype(c_kv.dtype)).reshape(B, T, H, cfg.v_head_dim)
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    s_nope = jnp.einsum("bshd,bthd->bhst", q_nope.astype(jnp.float32),
                        k_nope.astype(jnp.float32))
    s_rope = jnp.einsum("bshd,btd->bhst", q_rope.astype(jnp.float32),
                        k_rope.astype(jnp.float32))
    scores = (s_nope + s_rope) * scale
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v.astype(jnp.float32))
    out = out.reshape(B, -1, H * cfg.v_head_dim).astype(q_nope.dtype)
    return out @ p["wo"].astype(out.dtype)


def _mla_attend_chunked(p, cfg: ModelConfig, q_nope, q_rope, c_kv, k_rope):
    """Flash-style MLA attention: concat (nope, rope) into one key space so
    the chunked streaming-softmax path applies; O(S*block) temps instead of
    the O(S*T) score matrix (critical for the 32k prefill shapes)."""
    B, T = c_kv.shape[:2]
    H = cfg.num_heads
    k_nope = (c_kv @ p["w_uk"].astype(c_kv.dtype)).reshape(
        B, T, H, cfg.qk_nope_head_dim)
    v = (c_kv @ p["w_uv"].astype(c_kv.dtype)).reshape(B, T, H,
                                                      cfg.v_head_dim)
    q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_cat = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, T, H, cfg.qk_rope_head_dim))],
        axis=-1)
    out = flash_attention_jnp(q_cat, k_cat, v, causal=True,
                              unroll=cfg.unroll_layers)
    out = out.reshape(B, -1, H * cfg.v_head_dim)
    return out @ p["wo"].astype(out.dtype)


def mla_forward(p, cfg: ModelConfig, x, positions,
                cache_len: Optional[int] = None
                ) -> Tuple[jnp.ndarray, MLACache]:
    B, S, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, cfg, x, positions)
    if (cfg.ref_attention == "chunked"
            and S >= CHUNKED_ATTENTION_MIN_SEQ):
        y = _mla_attend_chunked(p, cfg, q_nope, q_rope, c_kv, k_rope)
    else:
        causal = jnp.tril(jnp.ones((S, S), bool))[None, None]
        y = _mla_attend(p, cfg, q_nope, q_rope, c_kv, k_rope, causal)
    if cache_len is not None and cache_len > S:
        pad = ((0, 0), (0, cache_len - S), (0, 0))
        c_kv = jnp.pad(c_kv, pad)
        k_rope = jnp.pad(k_rope, pad)
    return y, MLACache(c_kv=c_kv, k_rope=k_rope)


def mla_decode(p, cfg: ModelConfig, x, cache: MLACache,
               pos: jnp.ndarray, *,
               layer: Optional[jnp.ndarray] = None
               ) -> Tuple[jnp.ndarray, MLACache]:
    """One-token MLA decode; ``cache`` and ``layer`` as in
    ``attention_decode``."""
    T = cache.c_kv.shape[-2]
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, cfg, x, pos[:, None])
    slot = jnp.minimum(pos, T - 1)
    c_new = _write_cache(cache.c_kv, c_kv, slot, layer)
    kr_new = _write_cache(cache.k_rope, k_rope, slot, layer)
    valid = (jnp.arange(T)[None] < (pos + 1)[:, None])[:, None, None]
    y = _mla_attend(p, cfg, q_nope, q_rope, _layer_of(c_new, layer),
                    _layer_of(kr_new, layer), valid)
    return y, MLACache(c_kv=c_new, k_rope=kr_new)

"""Plain float32 reference of the dense decoder that the configurations
state, and its int8 control.

A configuration file names the module that recomputes its block
(``"reference": "<module>"``, a file ``chipbench/<module>.py``; this one
where it names none), and ``chipbench.check`` calls it with the
benchmark's weights (``chipbench.weights``) and the inputs that the
timed path was given. Every such module imports nothing of the program
and gives:

- ``decode(weights, conf, tokens, cache, pos, old, control=False) ->
  (logits, written)``: K consecutive decode calls of one device batch.
  ``tokens`` and ``pos`` (K, B): each call's input tokens and the
  position each row writes. ``cache``: the program's cache after the
  last call, in the program's structure (``{"prefix": [layer, ...],
  "scanned": stacked}``, a stacked leaf (L, B, T, ...), a prefix
  layer's (B, T, ...)). ``old``: the same structure, each leaf's slot
  axis T replaced by what each call's slot held before it: (L, K, B,
  ...), or (K, B, ...) for a prefix layer. Each call's attention reads
  ``cache`` for the earlier positions and the reference's own entry for
  the position it writes, so the reference follows the inputs the timed
  path was given, not its results. Returns logits (K, B, V) and
  ``written``, what the reference writes to each call's slot, in
  ``old``'s structure.
- ``prefill(weights, conf, tokens, control=False)``: logits (S, V) of
  one causal forward over a token row ``tokens`` (S,).

``control=True`` computes the same in the precision below the one the
configuration serves in.

This module computes layer by layer, so that it fits beside the
weights. The model: token embedding; per layer RMSNorm -> Q/K/V
projections -> rotary embedding (rotate-half) on Q and K ->
grouped-query causal attention -> output projection -> residual; RMSNorm
-> FFN (SwiGLU when gated, tanh-GELU otherwise) -> residual; final
RMSNorm -> head (the embedding's transpose when tied). Its cache is one
stack of K and V (``.k``, ``.v``), with no prefix layers.

Its control runs every matmul of the layers and the head in int8
(symmetric, per output channel for weights and per token for
activations, W8A8), the precision below the bf16 that the
configurations serve in.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _int8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


def _mm(a, w, control):
    a = a.astype(F32)
    w = w.astype(F32)
    if control:
        a = _int8(a, -1)
        w = _int8(w, 0)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def _rope(x, pos, theta):
    """x (..., H, D) at positions ``pos`` (...), rotate-half layout."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = pos.astype(F32)[..., None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _ffn(lw, h, gated, control):
    up = _mm(h, lw["w_in"], control)
    if gated:
        a = jax.nn.silu(_mm(h, lw["w_gate"], control)) * up
    else:
        a = jax.nn.gelu(up, approximate=True)
    return _mm(a, lw["w_out"], control)


def _qkv(lw, h, heads, kv_heads, hd, control):
    lead = h.shape[:-1]
    q = _mm(h, lw["wq"], control).reshape(lead + (heads, hd))
    k = _mm(h, lw["wk"], control).reshape(lead + (kv_heads, hd))
    v = _mm(h, lw["wv"], control).reshape(lead + (kv_heads, hd))
    return q, k, v


def _attend(q, k, v, mask):
    """q (..., S, H, D); k, v (..., T, Hkv, D); mask (..., S, T)."""
    h, hkv, d = q.shape[-2], k.shape[-2], q.shape[-1]
    g = h // hkv
    qg = q.reshape(q.shape[:-2] + (hkv, g, d))
    s = jnp.einsum("...shgd,...thd->...hgst", qg, k,
                   precision=HIGHEST) * d ** -0.5
    s = jnp.where(mask[..., None, None, :, :], s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    o = jnp.einsum("...hgst,...thd->...shgd", p, v, precision=HIGHEST)
    return o.reshape(o.shape[:-3] + (h * d,))


@functools.partial(jax.jit, static_argnames=("dims", "control"))
def _decode_layer(lw, kc, vc, x, pos, old_k, old_v, *, dims, control):
    """One layer for K consecutive decode calls, last call first.

    kc, vc (B, T, Hkv, D): the program's cache after the last call.
    x (K, B, d): each call's input to this layer. pos (K, B): the
    position each row writes. old_k, old_v (K, B, Hkv, D): what each
    call's slot held before it. Returns the layer's output and the
    reference's K/V for each call's slot."""
    heads, kv_heads, hd, eps, theta, gated = dims
    t = kc.shape[1]
    rows = jnp.arange(kc.shape[0])

    def call(carry, inp):
        kc, vc = carry
        xk, p, ok, ov = inp
        h = _rms(xk, lw["attn_norm"], eps)
        q, k, v = _qkv(lw, h, heads, kv_heads, hd, control)
        q, k = _rope(q, p, theta), _rope(k, p, theta)
        slot = jnp.minimum(p, t - 1)
        keys = kc.astype(F32).at[rows, slot].set(k)
        vals = vc.astype(F32).at[rows, slot].set(v)
        mask = (jnp.arange(t)[None, :] <= p[:, None])[:, None, :]
        o = _attend(q[:, None], keys, vals, mask)[:, 0]
        xk = xk + _mm(o, lw["wo"], control)
        xk = xk + _ffn(lw, _rms(xk, lw["ffn_norm"], eps), gated, control)
        # the cache this call was given is the next one's, with this
        # call's slots as they were before it
        kc = kc.at[rows, slot].set(ok)
        vc = vc.at[rows, slot].set(ov)
        return (kc, vc), (xk, k, v)

    _, (x, k, v) = jax.lax.scan(call, (kc, vc), (x, pos, old_k, old_v),
                                reverse=True)
    return x, k, v


@functools.partial(jax.jit, static_argnames=("dims", "control"))
def _prefill_layer(lw, x, *, dims, control):
    heads, kv_heads, hd, eps, theta, gated = dims
    n = x.shape[-2]
    pos = jnp.broadcast_to(jnp.arange(n), x.shape[:-1])
    h = _rms(x, lw["attn_norm"], eps)
    q, k, v = _qkv(lw, h, heads, kv_heads, hd, control)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    mask = jnp.tril(jnp.ones((n, n), bool))
    x = x + _mm(_attend(q, k, v, mask), lw["wo"], control)
    return x + _ffn(lw, _rms(x, lw["ffn_norm"], eps), gated, control)


@functools.partial(jax.jit, static_argnames=("eps", "tied", "control"))
def _head(x, norm, table, *, eps, tied, control):
    h = _rms(x, norm, eps)
    w = table.T if tied else table
    return _mm(h, w, control)


def _dims(conf) -> tuple:
    heads = conf["num_attention_heads"]
    eps = conf.get("rms_norm_eps", conf.get("norm_epsilon"))
    return (heads, conf["num_key_value_heads"],
            conf["hidden_size"] // heads, float(eps),
            float(conf["rope_theta"]), conf["hidden_act"] == "silu")


def _layer(weights, i) -> Dict[str, jnp.ndarray]:
    lw = weights["layers"]
    flat = {"attn_norm": lw["attn_norm"], "ffn_norm": lw["ffn_norm"]}
    flat.update(lw["attn"])
    flat.update(lw["ffn"])
    return {k: a[i] for k, a in flat.items()}


def _logits(weights, conf, x, control):
    tied = conf["tie_word_embeddings"]
    table = weights["embed"] if tied else weights["lm_head"]
    return _head(x, weights["final_norm"], table, eps=_dims(conf)[3],
                 tied=tied, control=control)


def decode(weights, conf, tokens, cache, pos, old, control=False):
    """Reference logits (K, B, V) and what it writes to each call's slot
    for K consecutive decode calls (the module's interface)."""
    if cache["prefix"]:
        raise ValueError("the dense decoder has no prefix layers")
    kv, old_kv = cache["scanned"], old["scanned"]
    dims = _dims(conf)
    x = jnp.take(weights["embed"], jnp.asarray(tokens), axis=0).astype(F32)
    pos = jnp.asarray(pos)
    ks: List = []
    vs: List = []
    for i in range(conf["num_hidden_layers"]):
        x, k, v = _decode_layer(_layer(weights, i), kv.k[i], kv.v[i], x,
                                pos, old_kv.k[i], old_kv.v[i], dims=dims,
                                control=control)
        ks.append(k)
        vs.append(v)
    written = {"prefix": [],
               "scanned": old_kv._replace(k=jnp.stack(ks), v=jnp.stack(vs))}
    return _logits(weights, conf, x, control), written


def prefill(weights, conf, tokens, control=False):
    """Reference logits (S, V) of a causal forward over ``tokens`` (S,)."""
    dims = _dims(conf)
    x = jnp.take(weights["embed"], jnp.asarray(tokens), axis=0).astype(F32)
    for i in range(conf["num_hidden_layers"]):
        x = _prefill_layer(_layer(weights, i), x, dims=dims,
                           control=control)
    return _logits(weights, conf, x, control)

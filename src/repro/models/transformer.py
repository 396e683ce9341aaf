"""Decoder-only transformer assembly covering the dense, MoE and VLM
(early-fusion) families. Layers are stacked with a leading ``layer`` axis and
executed via ``jax.lax.scan``; heterogeneous prefixes (e.g. DeepSeek's
first-k-dense FFN layers) are unrolled separately.

Model contract (shared by every family in the zoo):
    init(key)                          -> params
    forward(params, tokens)            -> logits (B,S,V)      [training]
    prefill(params, tokens)            -> (logits, cache)
    init_cache(batch, max_len)         -> cache pytree (zeros)
    decode_step(params, token, cache, pos) -> (logits, cache)
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import blocks
from repro.models.common import (ModelConfig, dense_init, rms_norm,
                                 scan_layers, softmax_cross_entropy,
                                 split_keys)


class DecoderOnlyLM:
    """Dense / MoE / early-fusion-VLM decoder LM."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.n_prefix = cfg.first_k_dense if cfg.num_experts else 0
        self.n_scanned = cfg.num_layers - self.n_prefix

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------
    def _init_layer(self, key, *, moe: bool):
        cfg = self.cfg
        ka, kf = jax.random.split(key)
        p = {"attn_norm": jnp.ones((cfg.d_model,), cfg.weight_dtype),
             "ffn_norm": jnp.ones((cfg.d_model,), cfg.weight_dtype)}
        if cfg.use_mla:
            p["attn"] = attn.init_mla(ka, cfg)
        else:
            p["attn"] = attn.init_attention(ka, cfg)
        if moe:
            p["moe"] = blocks.init_moe(kf, cfg)
        else:
            p["ffn"] = blocks.init_ffn(kf, cfg)
        return p

    def init(self, key) -> Any:
        cfg = self.cfg
        ks = split_keys(key, 4 + self.n_prefix)
        params = {
            "embed": dense_init(ks[0], (cfg.vocab_size, cfg.d_model),
                                cfg.weight_dtype, scale=0.02),
            "final_norm": jnp.ones((cfg.d_model,), cfg.weight_dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(
                ks[1], (cfg.d_model, cfg.vocab_size), cfg.weight_dtype)
        # unrolled prefix (dense-FFN) layers
        params["prefix"] = [
            self._init_layer(ks[3 + i], moe=False)
            for i in range(self.n_prefix)]
        # scanned homogeneous stack
        layer_keys = jax.random.split(ks[2], self.n_scanned)
        moe = bool(cfg.num_experts)
        params["layers"] = jax.vmap(
            lambda k: self._init_layer(k, moe=moe))(layer_keys)
        return params

    # ------------------------------------------------------------------
    # layer bodies
    # ------------------------------------------------------------------
    def _layer_full(self, lp, x, positions, *, moe: bool,
                    cache_len=None):
        """Full-sequence layer (train/prefill). Returns (x, cache, aux)."""
        cfg = self.cfg
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps, cfg.use_pallas)
        if cfg.use_mla:
            a, cache = attn.mla_forward(lp["attn"], cfg, h, positions,
                                        cache_len=cache_len)
        else:
            a, cache = attn.attention_forward(
                lp["attn"], cfg, h, positions, window=cfg.attention_window,
                cache_len=cache_len)
        x = x + a
        h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps, cfg.use_pallas)
        if moe:
            f, aux = blocks.moe_forward(lp["moe"], cfg, h)
            aux = aux.load_balance_loss
        else:
            f = blocks.ffn_forward(lp["ffn"], cfg, h)
            aux = jnp.zeros((), jnp.float32)
        return x + f, cache, aux

    def _layer_decode(self, lp, x, cache, pos, *, moe: bool, layer=None):
        """One layer's decode; with ``layer``, ``cache`` is the stacked
        cache of every scanned layer and comes back with this layer's one
        slot per row written."""
        cfg = self.cfg
        h = rms_norm(x, lp["attn_norm"], cfg.norm_eps, cfg.use_pallas)
        if cfg.use_mla:
            a, new_cache = attn.mla_decode(lp["attn"], cfg, h, cache, pos,
                                           layer=layer)
        else:
            a, new_cache = attn.attention_decode(
                lp["attn"], cfg, h, cache, pos, window=cfg.attention_window,
                layer=layer)
        x = x + a
        h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps, cfg.use_pallas)
        if moe:
            f, _ = blocks.moe_forward(lp["moe"], cfg, h)
        else:
            f = blocks.ffn_forward(lp["ffn"], cfg, h)
        return x + f, new_cache

    # ------------------------------------------------------------------
    # public api
    # ------------------------------------------------------------------
    def _embed(self, params, tokens):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        return x.astype(cfg.activation_dtype)

    def _unembed(self, params, x):
        cfg = self.cfg
        x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.use_pallas)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        return x @ head.astype(x.dtype)

    def _run_stack(self, params, x, positions, *, collect_cache: bool,
                   cache_len=None):
        cfg = self.cfg
        moe = bool(cfg.num_experts)
        aux_total = jnp.zeros((), jnp.float32)
        prefix_caches = []
        for lp in params["prefix"]:
            x, c, aux = self._layer_full(lp, x, positions, moe=False,
                                         cache_len=cache_len)
            aux_total = aux_total + aux
            prefix_caches.append(c)

        def body(carry, lp):
            h, acc = carry
            h, cache, aux = self._layer_full(lp, h, positions, moe=moe,
                                             cache_len=cache_len)
            return (h, acc + aux), (cache if collect_cache else 0)

        body_fn = jax.checkpoint(body) if cfg.remat else body
        (x, aux_total), caches = scan_layers(
            body_fn, (x, aux_total), params["layers"],
            unroll=cfg.unroll_layers)
        return x, aux_total, prefix_caches, caches

    def forward(self, params, tokens, positions: Optional[jnp.ndarray] = None):
        B, S = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x = self._embed(params, tokens)
        x, aux, _, _ = self._run_stack(params, x, positions,
                                       collect_cache=False)
        return self._unembed(params, x), aux

    def loss(self, params, tokens, labels, mask=None):
        logits, aux = self.forward(params, tokens)
        return softmax_cross_entropy(logits, labels, mask) + 0.01 * aux

    def prefill(self, params, tokens, max_len=None):
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        x = self._embed(params, tokens)
        x, _, prefix_caches, caches = self._run_stack(
            params, x, positions, collect_cache=True, cache_len=max_len)
        logits = self._unembed(params, x[:, -1:])
        return logits, {"prefix": prefix_caches, "scanned": caches}

    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        if cfg.use_mla:
            one = lambda: attn.init_mla_cache(cfg, batch, max_len)  # noqa: E731
        else:
            one = lambda: attn.init_kv_cache(cfg, batch, max_len)  # noqa: E731
        stacked = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *([one()] * self.n_scanned)) if self.n_scanned else one()
        return {"prefix": [one() for _ in range(self.n_prefix)],
                "scanned": stacked}

    def decode_step(self, params, token, cache, pos):
        """token: (B,1) int32; pos: (B,) tokens already in cache."""
        cfg = self.cfg
        moe = bool(cfg.num_experts)
        x = self._embed(params, token)
        new_prefix = []
        for lp, c in zip(params["prefix"], cache["prefix"]):
            x, nc = self._layer_decode(lp, x, c, pos, moe=False)
            new_prefix.append(nc)

        # the stacked cache rides in the carry, so each layer writes its
        # slots in the donated buffer; as scanned xs/ys every layer's cache
        # would be sliced out of the stack and written back whole
        def body(carry, inp):
            h, c = carry
            lp, layer = inp
            h, c = self._layer_decode(lp, h, c, pos, moe=moe, layer=layer)
            return (h, c), None

        (x, new_caches), _ = scan_layers(
            body, (x, cache["scanned"]),
            (params["layers"], jnp.arange(self.n_scanned)),
            unroll=cfg.unroll_layers)
        logits = self._unembed(params, x)
        return logits, {"prefix": new_prefix, "scanned": new_caches}

"""Equivalence tests for the §Perf optimization variants: every optimized
path must match its baseline formulation bit-for-bit (up to float tolerance)
— 'keep the speedup, prove the semantics'."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.attention as attention_mod
from repro.configs import get_config
from repro.kernels import ref
from repro.models import blocks, build_model
from repro.models.attention import flash_attention_jnp, gqa_attention


class TestChunkedAttention:
    @pytest.mark.parametrize("B,S,H,Hkv,D,block", [
        (2, 256, 4, 2, 64, 64),
        (1, 200, 4, 1, 32, 64),       # non-multiple of block
        (2, 128, 8, 8, 64, 32),
    ])
    def test_matches_naive_causal(self, B, S, H, Hkv, D, block):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, D))
        k = jax.random.normal(ks[1], (B, S, Hkv, D))
        v = jax.random.normal(ks[2], (B, S, Hkv, D))
        a = flash_attention_jnp(q, k, v, causal=True, block_k=block)
        b = ref.flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    def test_matches_naive_banded(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q = jax.random.normal(ks[0], (2, 256, 4, 64))
        k = jax.random.normal(ks[1], (2, 256, 2, 64))
        v = jax.random.normal(ks[2], (2, 256, 2, 64))
        i = jnp.arange(256)[:, None]
        j = jnp.arange(256)[None, :]
        band = (j <= i) & (j > i - 64)
        a = flash_attention_jnp(q, k, v, causal=True, window=64, block_k=64)
        b = gqa_attention(q, k, v, band[None, None])
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    def test_unrolled_matches_scan(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q = jax.random.normal(ks[0], (1, 128, 2, 32))
        k = jax.random.normal(ks[1], (1, 128, 2, 32))
        v = jax.random.normal(ks[2], (1, 128, 2, 32))
        a = flash_attention_jnp(q, k, v, causal=True, block_k=32)
        b = flash_attention_jnp(q, k, v, causal=True, block_k=32,
                                unroll=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)

    def test_mixed_value_head_dim(self):
        """Dv != Dk (the MLA folding case)."""
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        q = jax.random.normal(ks[0], (1, 64, 4, 48))
        k = jax.random.normal(ks[1], (1, 64, 4, 48))
        v = jax.random.normal(ks[2], (1, 64, 4, 32))
        a = flash_attention_jnp(q, k, v, causal=True, block_k=16)
        # naive reference with distinct Dv
        s = jnp.einsum("bshd,bthd->bhst", q, k) * (48 ** -0.5)
        mask = jnp.tril(jnp.ones((64, 64), bool))
        s = jnp.where(mask[None, None], s, -1e30)
        p = jax.nn.softmax(s, -1)
        b = jnp.einsum("bhst,bthd->bshd", p, v)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b",
                                      "chameleon-34b"])
    def test_model_level_chunked_matches_naive(self, arch, monkeypatch):
        monkeypatch.setattr(attention_mod, "CHUNKED_ATTENTION_MIN_SEQ", 8)
        cfg = get_config(arch).reduced()
        m1 = build_model(cfg)
        m2 = build_model(cfg.replace(ref_attention="chunked"))
        params = m1.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                    cfg.vocab_size)
        l1, _ = m1.forward(params, tokens)
        l2, _ = m2.forward(params, tokens)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=3e-4, atol=3e-4)


class TestCapacityMoE:
    @pytest.mark.parametrize("arch", ["llama4-scout-17b-a16e",
                                      "deepseek-v2-lite-16b"])
    def test_no_drop_capacity_matches_dense(self, arch):
        cfg = get_config(arch).reduced().replace(
            capacity_factor=float(get_config(arch).reduced().num_experts))
        p = blocks.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
        y1, a1 = blocks.moe_forward_dense(p, cfg, x)
        y2, a2 = blocks.moe_forward_capacity(p, cfg, x)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(float(a1.load_balance_loss),
                                   float(a2.load_balance_loss), rtol=1e-4)

    def test_tight_capacity_drops_but_finite(self):
        cfg = get_config("deepseek-v2-lite-16b").reduced().replace(
            capacity_factor=0.5)
        p = blocks.init_moe(jax.random.PRNGKey(2), cfg)
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.d_model))
        y, _ = blocks.moe_forward_capacity(p, cfg, x)
        assert bool(jnp.all(jnp.isfinite(y)))

    def test_capacity_grad_finite(self):
        cfg = get_config("llama4-scout-17b-a16e").reduced().replace(
            moe_dispatch="capacity")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0,
                                    cfg.vocab_size)
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, tokens[:, :-1], tokens[:, 1:]))(params)
        assert jnp.isfinite(loss)
        assert all(bool(jnp.all(jnp.isfinite(g)))
                   for g in jax.tree.leaves(grads))


def _ragged_prefill(model, params, prompts, cap):
    """Prefill each prompt alone and join the rows' caches on the batch
    axis, the one axis on which they differ from a cache of every row."""
    prefill = jax.jit(model.prefill, static_argnames="max_len")
    caches = [prefill(params, p[None], max_len=cap)[1] for p in prompts]
    whole = jax.eval_shape(lambda: model.init_cache(len(prompts), cap))

    def join(ref, *rows):
        axis = next(i for i, (a, b) in enumerate(zip(ref.shape, rows[0].shape))
                    if a != b)
        out = jnp.concatenate(rows, axis=axis)
        assert out.shape == ref.shape and out.dtype == ref.dtype
        return out

    return jax.tree.map(join, whole, *caches)


def _kv_leaves(cache):
    """(name, leaf) for every K/V array of a cache: KVCache and MLACache
    fields, per layer (B,T,...) or stacked (L,B,T,...)."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        name = getattr(path[-1], "name", None)
        if name in ("k", "v", "c_kv", "k_rope"):
            out.append((jax.tree_util.keystr(path), leaf))
    return out


class TestOneSlotDecode:
    """Decode writes one K/V slot per row and layer, in place: the logits
    follow the full forward, and the returned cache equals the input
    everywhere but the slots written."""

    @pytest.mark.parametrize("arch", ["tinyllama-1.1b",
                                      "deepseek-v2-lite-16b",
                                      "recurrentgemma-9b"])
    def test_decode_matches_forward_and_writes_one_slot(self, arch):
        cfg = get_config(arch).reduced()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        lens, cap, steps = (5, 9, 7), 16, 4
        toks = np.asarray(jax.random.randint(
            jax.random.PRNGKey(1), (len(lens), max(lens) + steps), 0,
            cfg.vocab_size))
        cache = _ragged_prefill(model, params,
                                [jnp.asarray(t[:n]) for t, n in
                                 zip(toks, lens)], cap)
        # causal: the forward's logits at a position see no later token
        full = np.asarray(jax.jit(model.forward)(params, toks)[0])
        rows = np.arange(len(lens))
        pos = np.array(lens, np.int32)
        step = jax.jit(model.decode_step)
        for _ in range(steps):
            logits, new = step(params, jnp.asarray(toks[rows, pos][:, None]),
                               cache, jnp.asarray(pos))
            np.testing.assert_allclose(np.asarray(logits[:, 0]),
                                       full[rows, pos], rtol=1e-3, atol=1e-3)
            assert jax.tree.structure(new) == jax.tree.structure(cache)
            for a, c in zip(jax.tree.leaves(new), jax.tree.leaves(cache)):
                assert a.shape == c.shape and a.dtype == c.dtype
            old_kv, new_kv = _kv_leaves(cache), _kv_leaves(new)
            assert old_kv and [n for n, _ in old_kv] == [n for n, _ in new_kv]
            for (name, old), (_, got) in zip(old_kv, new_kv):
                old, got = np.asarray(old), np.asarray(got)
                b_axis = old.ndim - (4 if name.endswith((".k", ".v"))
                                     else 3)
                written = np.zeros(old.shape, bool)
                for b, p in enumerate(pos):
                    at = [slice(None)] * old.ndim
                    at[b_axis], at[b_axis + 1] = b, p % old.shape[b_axis + 1]
                    written[tuple(at)] = True
                np.testing.assert_array_equal(got[~written], old[~written],
                                              err_msg=name)
                assert np.any(got[written] != old[written]), name
            cache, pos = new, pos + 1

    @pytest.mark.parametrize("arch", ["tinyllama-1.1b",
                                      "deepseek-v2-lite-16b"])
    def test_unrolled_decode_matches_scan(self, arch):
        """``unroll_layers`` (the cost extrapolation's path) returns the
        same pytree and numbers as the scan."""
        cfg = get_config(arch).reduced().replace(num_layers=3)
        m_scan = build_model(cfg)
        m_unroll = build_model(cfg.replace(unroll_layers=True))
        params = m_scan.init(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                    cfg.vocab_size)
        _, cache = m_scan.prefill(params, tokens, max_len=12)
        pos = jnp.array([6, 3], jnp.int32)
        l1, c1 = jax.jit(m_scan.decode_step)(params, tokens[:, :1], cache,
                                             pos)
        l2, c2 = jax.jit(m_unroll.decode_step)(params, tokens[:, :1], cache,
                                               pos)
        assert jax.tree.structure(c1) == jax.tree.structure(c2)
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   rtol=1e-5, atol=1e-5)
        for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)

    def test_decode_temp_does_not_grow_with_depth(self):
        """With the cache donated, the compiled step holds no copy of a
        layer's cache: its scratch bytes stay the same as layers are added,
        and the whole cache aliases the output."""
        def compiled(layers):
            cfg = get_config("starcoder2-7b").reduced().replace(
                num_layers=layers)
            model = build_model(cfg)
            params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
            cache = jax.eval_shape(lambda: model.init_cache(4, 256))
            step = jax.jit(model.decode_step, donate_argnums=(2,)).lower(
                params, jax.ShapeDtypeStruct((4, 1), jnp.int32), cache,
                jax.ShapeDtypeStruct((4,), jnp.int32)).compile()
            cache_bytes = sum(a.size * a.dtype.itemsize
                              for a in jax.tree.leaves(cache))
            return step.memory_analysis(), cache_bytes

        (m4, c4), (m8, c8) = compiled(4), compiled(8)
        assert m4.temp_size_in_bytes == m8.temp_size_in_bytes
        assert m8.temp_size_in_bytes < c8 / 2
        assert (m4.alias_size_in_bytes, m8.alias_size_in_bytes) == (c4, c8)

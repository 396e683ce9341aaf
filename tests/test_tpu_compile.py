"""Compile for a TPU v5e that is described, not attached: the five Pallas
kernels at real widths and one llama3-3b ``use_pallas=True`` decode step.
The chip's own compiler refuses what interpret mode accepts (unaligned
blocks, primitives with no TPU lowering), so these guard every change to
the kernels without a chip. Nothing runs; each compiled program must hold
its kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_grouped
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.rglru import rglru_scan_kernel
from repro.kernels.rmsnorm import rmsnorm_kernel
from repro.kernels.ssd import ssd_scan_kernel
from repro.models import build_model

LLAMA = get_config("llama3-3b")
MAMBA = get_config("mamba2-1.3b")
GRIFFIN = get_config("recurrentgemma-9b")


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with JAX's persistent compilation
    cache off: a compile for a described chip is written to the cache but
    cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_flash_attention_compiles(one_chip):
    H, Hkv, D = LLAMA.num_heads, LLAMA.num_kv_heads, LLAMA.head_dim
    for S in (64, 1024):
        text = _kernel_text(
            lambda q, k, v: flash_attention_bhsd(q, k, v, interpret=False),
            _sds(one_chip, (H, S, D)), _sds(one_chip, (Hkv, S, D)),
            _sds(one_chip, (Hkv, S, D)))
        assert "tpu_custom_call" in text


def test_decode_attention_compiles(one_chip):
    B, G = 8, LLAMA.num_heads // LLAMA.num_kv_heads
    BH, D = B * LLAMA.num_kv_heads, LLAMA.head_dim
    for T in (256, 2048):
        text = _kernel_text(
            lambda q, k, v, m: decode_attention_grouped(q, k, v, m,
                                                        interpret=False),
            _sds(one_chip, (BH, G, D)), _sds(one_chip, (BH, T, D)),
            _sds(one_chip, (BH, T, D)), _sds(one_chip, (BH, T), jnp.bool_))
        assert "tpu_custom_call" in text


def test_rmsnorm_compiles(one_chip):
    text = _kernel_text(lambda x, w: rmsnorm_kernel(x, w, interpret=False),
                        _sds(one_chip, (8, 64, LLAMA.d_model)),
                        _sds(one_chip, (LLAMA.d_model,)))
    assert "tpu_custom_call" in text


def test_ssd_compiles(one_chip):
    b, s, f32 = 2, 512, jnp.float32
    h, p, n = MAMBA.ssm_nheads, MAMBA.ssm_head_dim, MAMBA.ssm_state
    g = MAMBA.ssm_ngroups
    text = _kernel_text(
        lambda x, dt, A, B, C: ssd_scan_kernel(
            x, dt, A, B, C, chunk=MAMBA.ssm_chunk, interpret=False),
        _sds(one_chip, (b, s, h, p), f32), _sds(one_chip, (b, s, h), f32),
        _sds(one_chip, (h,), f32), _sds(one_chip, (b, s, g, n), f32),
        _sds(one_chip, (b, s, g, n), f32))
    assert "tpu_custom_call" in text


def test_rglru_compiles(one_chip):
    B, S, W, f32 = 2, 512, GRIFFIN.lru_width, jnp.float32
    text = _kernel_text(
        lambda x, a, h: rglru_scan_kernel(x, a, h, interpret=False),
        _sds(one_chip, (B, S, W), f32), _sds(one_chip, (B, S, W), f32),
        _sds(one_chip, (B, W), f32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("use_pallas", [True, False])
def test_llama3_decode_step_compiles(use_pallas, one_chip, monkeypatch):
    """The kernel-path decode step, and the reference step ``serve
    --backend jax`` runs, at the batch and cache it serves `normal` with."""
    # the model reaches the kernels through ops, which asks the default
    # backend (the CPU here); compile them as the chip would run them
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    model = build_model(LLAMA.replace(use_pallas=use_pallas))
    B, T = 8, 2048

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(B, T)))
    compiled = jax.jit(model.decode_step, donate_argnums=(2,)).lower(
        params, _sds(one_chip, (B, 1), jnp.int32), cache,
        _sds(one_chip, (B,), jnp.int32)).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_pallas
    # the whole step fits one 16 GB chip
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes) < 16e9

#!/usr/bin/env python3
"""Smoke run of the served model path on one TPU chip.

    python chip_smoke.py

One process that starts no other. Its phases run in order, and a failure
in any of them raises, so the script exits non-zero:

1. device: the default JAX device must be a TPU; there is no fallback.
2. kernels: flash_attention, decode_attention and rmsnorm, compiled for
   the chip at llama3-3b's widths, against ``repro.kernels.ref`` at the
   bf16 tolerances of ``tests/test_kernels.py``. Then a ``use_pallas=True``
   llama3-3b decode step: its compiled program must hold the kernels
   (``tpu_custom_call``), and its logits must be finite and agree with the
   reference decode step.
3. serve: ``repro.launch.serve --backend jax`` serves eight ``normal``
   requests with llama3-3b at its published widths (random weights from
   the seed) under AGFT. Every request must finish with all its tokens,
   energy must be priced, AGFT must run a round, and nothing may compile
   while serving.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "llama3-3b"
SERVE_ARGV = ["--backend", "jax", "--arch", ARCH, "--hardware", "tpu-v5e",
              "--policy", "agft", "--workload", "normal", "--requests", "8"]
#: bf16 tolerances of tests/test_kernels.py
TOL = dict(rtol=2e-2, atol=2e-2)
#: relative L2 gap allowed between the kernel and reference decode steps:
#: bf16 rounding differs between the paths and compounds over 28 layers;
#: a wrong mask or head grouping gives a gap of order one
DECODE_REL_TOL = 0.1


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def device_phase() -> dict:
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: the default JAX device is "
                         f"{dev.platform!r}; this smoke run needs a TPU")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def _kernel_run(fn, *args):
    """Compile ``fn`` for the default device, require a Pallas kernel in
    the program, and run it."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"no Pallas kernel in the compiled {fn.__name__}")
    return compiled(*args)


def _max_err(name: str, got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, **TOL, err_msg=name)
    err = float(np.max(np.abs(got - want)))
    print(f"kernel {name}: shape={list(got.shape)} max_abs_err={err!r}")
    return err


def kernels_phase() -> dict:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels import ops, ref
    check(not ops._interpret(), "the kernels would run interpreted")
    cfg = get_config(ARCH)
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(0), 9)

    def rand(key, shape):
        return jax.random.normal(key, shape, jnp.float32).astype(
            jnp.bfloat16)

    errs = {}
    q, k, v = (rand(ks[0], (1, 64, H, D)), rand(ks[1], (1, 64, Hkv, D)),
               rand(ks[2], (1, 64, Hkv, D)))
    errs["flash_attention"] = _max_err(
        "flash_attention", _kernel_run(ops.flash_attention, q, k, v),
        ref.flash_attention(q, k, v))
    B, T = 8, 2048
    q, kc, vc = (rand(ks[3], (B, 1, H, D)), rand(ks[4], (B, T, Hkv, D)),
                 rand(ks[5], (B, T, Hkv, D)))
    lengths = jax.random.randint(ks[6], (B,), 1, T + 1)
    valid = jnp.arange(T)[None] < lengths[:, None]
    errs["decode_attention"] = _max_err(
        "decode_attention",
        _kernel_run(ops.decode_attention, q, kc, vc, valid),
        ref.decode_attention(q, kc, vc, valid))
    x = rand(ks[7], (B, 64, cfg.d_model))
    w = (1.0 + 0.1 * jax.random.normal(ks[8], (cfg.d_model,))).astype(
        jnp.bfloat16)
    errs["rmsnorm"] = _max_err("rmsnorm", _kernel_run(ops.rmsnorm, x, w),
                               ref.rmsnorm(x, w))
    return errs


def pallas_decode_phase() -> float:
    """One llama3-3b decode step through the kernels at batch 8 against a
    random 2048-slot cache, compared with the reference step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config(ARCH)
    model = build_model(cfg)
    kernel_model = build_model(cfg.replace(use_pallas=True))
    B, T = 8, 2048
    kp, kc, kt = jax.random.split(jax.random.PRNGKey(1), 3)
    params = jax.jit(model.init)(kp)

    def random_cache(key):
        leaves, tree = jax.tree.flatten(model.init_cache(B, T))
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(tree, [
            jax.random.normal(k, z.shape, z.dtype)
            for k, z in zip(keys, leaves)])

    cache = jax.jit(random_cache)(kc)
    token = jax.random.randint(kt, (B, 1), 0, cfg.vocab_size)
    pos = jnp.arange(B, dtype=jnp.int32) * (T // B) + 7
    # logits only: the updated cache stays inside the program, so the two
    # steps need no room for a second and third cache
    step = jax.jit(lambda *a: kernel_model.decode_step(*a)[0]).lower(
        params, token, cache, pos).compile()
    n_calls = step.as_text().count("tpu_custom_call")
    check(n_calls > 0, "no Pallas kernel in the use_pallas decode step")
    logits = step(params, token, cache, pos)
    want = jax.jit(lambda *a: model.decode_step(*a)[0])(
        params, token, cache, pos)
    got = np.asarray(logits, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == (B, 1, cfg.vocab_size),
          f"decode logits have shape {got.shape}")
    check(bool(np.isfinite(got).all()), "decode logits are not finite")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    print(f"pallas decode step: tpu_custom_call={n_calls} "
          f"logits={list(got.shape)} rel_l2_vs_reference={rel!r}")
    check(rel <= DECODE_REL_TOL,
          f"kernel decode step is {rel} (relative L2) from the reference")
    return rel


def serve_phase(argv=SERVE_ARGV) -> dict:
    from repro.launch import serve
    summary, eng = serve.run(argv)
    n = int(argv[argv.index("--requests") + 1])
    check(len(eng.finished) == n,
          f"{len(eng.finished)} of {n} requests finished")
    short = [r for r in eng.finished if r.generated != r.output_len]
    check(not short, f"{len(short)} requests stopped short of output_len")
    check(summary["energy_j"] > 0, "no energy was priced")
    check(summary["tuner"]["rounds"] >= 1, "AGFT ran no round")
    check(summary["serve_compiles"] == 0,
          f"{summary['serve_compiles']} compilation events while serving")
    return summary


def main() -> None:
    device = device_phase()
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    kernels_phase()
    pallas_decode_phase()
    summary = serve_phase()
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    print(f"serve: decode_ms_median={summary['decode_ms_median']!r} "
          f"compile_s={summary['compile_s']!r} "
          f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()

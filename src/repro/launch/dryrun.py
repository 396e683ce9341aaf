"""Multi-pod dry-run: prove every (architecture x input-shape x mesh)
combination lowers, SPMD-partitions, and compiles.

A host-CPU tool: importing it asks for 512 placeholder CPU devices (added
to any ``XLA_FLAGS`` already set) and, unless ``JAX_PLATFORMS`` says
otherwise, keeps JAX on the CPU, so it never takes an attached chip.

For each combination this builds the jitted step (train_step / prefill /
serve_step) with explicit in/out shardings, lowers it against
ShapeDtypeStruct stand-ins (no device allocation), compiles, and reports
``memory_analysis()`` (proves it fits) + ``cost_analysis()`` (FLOPs/bytes
for the roofline) + collective-transfer bytes parsed from the HLO.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import os

os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count=512"]))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse      # noqa: E402
import functools     # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (ASSIGNED_ARCHS, config_for_shape,   # noqa: E402
                           get_shape)
from repro.distributed.sharding import (batch_pspec, cache_pspecs,  # noqa: E402
                                        logits_pspec, param_pspecs,
                                        with_sharding)
from repro.launch.mesh import make_debug_mesh, make_production_mesh  # noqa: E402
from repro.models import build_model                    # noqa: E402
from repro.training.optimizer import AdamWConfig, init_adamw  # noqa: E402
from repro.training.train_loop import make_train_step   # noqa: E402

from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "s32": 4,
                "u64": 8, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"(\w+[\d.\-]*)\s*=\s*([a-z0-9]+)\[([\d,]*)\][^=]*?"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"[\s(]")


def collective_bytes(hlo_text: str) -> dict:
    """Sum result bytes of collective ops in the (SPMD-partitioned) HLO.
    Convention: all-reduce counted 2x (ring send+recv), others 1x."""
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    for line in hlo_text.splitlines():
        s = line.strip()
        m = re.match(r"^%?[\w.\-]+ = ([a-z0-9]+)\[([\d,]*)\]", s)
        if not m:
            continue
        op = None
        for cand in out:
            if re.search(rf"\b{cand}(-start|-done)?\(", s):
                op = cand
                break
        if op is None:
            continue
        dt, dims = m.group(1), m.group(2)
        nb = _DTYPE_BYTES.get(dt, 4)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[op] += n * nb * (2 if op == "all-reduce" else 1)
    out["total"] = sum(v for k, v in out.items())
    return out


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; never allocates)
# ---------------------------------------------------------------------------

def input_specs(cfg, shape):
    """Model inputs for the given InputShape (tokens/labels/frames...)."""
    B, S = shape.global_batch, shape.seq_len
    sds = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt)   # noqa: E731
    if shape.kind == "train":
        batch = {"tokens": sds((B, S), jnp.int32),
                 "labels": sds((B, S), jnp.int32)}
        if cfg.is_encoder_decoder:
            batch["frames"] = sds((B, cfg.encoder_seq, cfg.d_model),
                                  cfg.activation_dtype)
        return batch
    if shape.kind == "prefill":
        out = {"tokens": sds((B, S), jnp.int32)}
        if cfg.is_encoder_decoder:
            out["frames"] = sds((B, cfg.encoder_seq, cfg.d_model),
                                cfg.activation_dtype)
        return out
    # decode: one token against a seq_len-deep cache
    return {"token": sds((B, 1), jnp.int32),
            "pos": sds((B,), jnp.int32)}


def _shard_batch(tree, mesh, B):
    def one(path, leaf):
        extra = len(leaf.shape) - 1
        return with_sharding(
            leaf, batch_pspec(mesh, B, extra_dims=extra), mesh)
    return jax.tree_util.tree_map_with_path(
        lambda p, l: jax.ShapeDtypeStruct(
            l.shape, l.dtype,
            sharding=NamedSharding(mesh, batch_pspec(
                mesh, B, extra_dims=len(l.shape) - 1))), tree)


# ---------------------------------------------------------------------------
# build the lowerable function per shape kind
# ---------------------------------------------------------------------------

def build_lowering(arch: str, shape_name: str, mesh, *, seed: int = 0,
                   cfg_override=None, donate: bool = False):
    shape = get_shape(shape_name)
    cfg = cfg_override or config_for_shape(arch, shape_name)
    # dry-run uses the pure-jnp reference path (kernels are TPU-target)
    cfg = cfg.replace(use_pallas=False)
    model = build_model(cfg)
    B, S = shape.global_batch, shape.seq_len

    key = jax.random.PRNGKey(seed)
    params_sds = jax.eval_shape(model.init, key)
    p_specs = param_pspecs(params_sds, mesh)
    params_in = with_sharding(params_sds, p_specs, mesh)
    inputs = input_specs(cfg, shape)
    inputs_in = _shard_batch(inputs, mesh, B)
    repl = NamedSharding(mesh, P())

    if shape.kind == "train":
        opt_sds = jax.eval_shape(functools.partial(init_adamw), params_sds)
        o_specs = param_pspecs_like_opt(opt_sds, p_specs)
        opt_in = with_sharding(opt_sds, o_specs, mesh)
        step = make_train_step(model, AdamWConfig())
        fn = jax.jit(
            step,
            in_shardings=(jax.tree.map(lambda s: s.sharding, params_in),
                          jax.tree.map(lambda s: s.sharding, opt_in),
                          jax.tree.map(lambda s: s.sharding, inputs_in)),
            out_shardings=(
                jax.tree.map(lambda s: s.sharding, params_in),
                jax.tree.map(lambda s: s.sharding, opt_in),
                {"loss": repl, "grad_norm": repl, "step": repl}),
            donate_argnums=(0, 1) if donate else (),
        )
        return fn, (params_in, opt_in, inputs_in)

    if shape.kind == "prefill":
        def prefill_fn(params, batch):
            if cfg.is_encoder_decoder:
                return model.prefill(params, batch["tokens"],
                                     batch["frames"], max_len=S)
            return model.prefill(params, batch["tokens"], max_len=S)

        cache_sds = jax.eval_shape(
            lambda: _prefill_cache_shape(model, cfg, B, S))
        fn = jax.jit(
            prefill_fn,
            in_shardings=(jax.tree.map(lambda s: s.sharding, params_in),
                          jax.tree.map(lambda s: s.sharding, inputs_in)),
        )
        return fn, (params_in, inputs_in)

    # decode
    cache_sds = jax.eval_shape(lambda: model.init_cache(B, S))
    c_specs = cache_pspecs(cache_sds, mesh, B)
    cache_in = with_sharding(cache_sds, c_specs, mesh)

    def serve_step(params, token, cache, pos):
        return model.decode_step(params, token, cache, pos)

    fn = jax.jit(
        serve_step,
        in_shardings=(jax.tree.map(lambda s: s.sharding, params_in),
                      inputs_in["token"].sharding,
                      jax.tree.map(lambda s: s.sharding, cache_in),
                      inputs_in["pos"].sharding),
        out_shardings=(NamedSharding(mesh, logits_pspec(mesh, B, cfg.vocab_size)),
                       jax.tree.map(lambda s: s.sharding, cache_in)),
        donate_argnums=(2,) if donate else (),
    )
    return fn, (params_in, inputs_in["token"], cache_in, inputs_in["pos"])


def _prefill_cache_shape(model, cfg, B, S):
    return 0  # placeholder: prefill out_shardings left to GSPMD


def param_pspecs_like_opt(opt_sds, p_specs):
    """Optimizer state: step replicated; moments shard like params."""
    return type(opt_sds)(step=P(), m=p_specs, v=p_specs)


# ---------------------------------------------------------------------------
# cost extrapolation: XLA's cost_analysis counts a lax.scan body ONCE
# regardless of trip count. For exact roofline terms we compile two small
# UNROLLED variants (scan length u1, u2), fit the linear cost-in-depth
# model, and extrapolate to the real depth. The full-scan compile still
# provides the lowering proof + memory analysis.
# ---------------------------------------------------------------------------

def _scan_length(cfg) -> int:
    if cfg.arch_type == "hybrid":
        pat = len(cfg.block_pattern or ("rec", "rec", "attn"))
        return cfg.num_layers // pat
    prefix = cfg.first_k_dense if cfg.num_experts else 0
    return cfg.num_layers - prefix


def _cost_variant(cfg, u: int):
    if cfg.arch_type == "hybrid":
        pat = len(cfg.block_pattern or ("rec", "rec", "attn"))
        tail = cfg.num_layers % pat
        return cfg.replace(num_layers=pat * u + tail, unroll_layers=True)
    if cfg.is_encoder_decoder:
        return cfg.replace(num_layers=u, encoder_layers=u,
                           unroll_layers=True)
    prefix = cfg.first_k_dense if cfg.num_experts else 0
    return cfg.replace(num_layers=prefix + u, unroll_layers=True)


def _compile_cost(arch, shape_name, mesh, cfg, donate: bool = False):
    fn, args = build_lowering(arch, shape_name, mesh, cfg_override=cfg,
                              donate=donate)
    compiled = fn.lower(*args).compile()
    cost = compiled.cost_analysis() or {}
    coll = collective_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    return {"flops": cost.get("flops", 0.0),
            "bytes": cost.get("bytes accessed", 0.0),
            "coll": coll,
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "arg_bytes": getattr(mem, "argument_size_in_bytes", 0)}


def cost_extrapolated(arch, shape_name, mesh, cfg_transform=None,
                      donate: bool = False) -> dict:
    cfg = config_for_shape(arch, shape_name).replace(use_pallas=False)
    if cfg_transform is not None:
        cfg = cfg_transform(cfg)
    U = _scan_length(cfg)
    u1, u2 = 1, 2
    c1 = _compile_cost(arch, shape_name, mesh, _cost_variant(cfg, u1),
                       donate=donate)
    c2 = _compile_cost(arch, shape_name, mesh, _cost_variant(cfg, u2),
                       donate=donate)

    def lin(a, b):
        slope = (b - a) / (u2 - u1)
        return max(a + slope * (U - u1), 0.0)

    coll = {k: lin(c1["coll"][k], c2["coll"][k]) for k in c1["coll"]}
    return {"flops": lin(c1["flops"], c2["flops"]),
            "bytes_accessed": lin(c1["bytes"], c2["bytes"]),
            "collective_bytes": coll,
            "scan_length": U,
            # u=2 variant's allocation footprint (NOT extrapolated; use for
            # relative comparisons e.g. donation / remat variants)
            "u2_temp_bytes": c2["temp_bytes"],
            "u2_arg_bytes": c2["arg_bytes"],
            "note": "linear-in-depth extrapolation from unrolled u=1,2"}


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            debug_mesh: bool = False, verbose: bool = True,
            extrapolate: bool = False) -> dict:
    t0 = time.time()
    if debug_mesh:
        mesh = make_debug_mesh(multi_pod=multi_pod)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    with mesh:
        fn, args = build_lowering(arch, shape_name, mesh)
        lowered = fn.lower(*args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        coll = collective_bytes(compiled.as_text())
        extra = cost_extrapolated(arch, shape_name, mesh) \
            if extrapolate else None
    n_dev = 1
    for v in mesh.shape.values():
        n_dev *= v
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(str(v) for v in mesh.shape.values()),
        "devices": n_dev,
        "flops": cost.get("flops", 0.0) if cost else 0.0,
        "bytes_accessed": cost.get("bytes accessed", 0.0) if cost else 0.0,
        "collective_bytes": coll,
        "memory": {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_size_bytes": getattr(
                mem, "generated_code_size_in_bytes", 0),
        },
        "compile_s": round(time.time() - t0, 2),
    }
    if extra is not None:
        result["extrapolated"] = extra
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x mesh={result['mesh']}: "
              f"OK ({result['compile_s']}s)")
        print(f"  memory_analysis: {result['memory']}")
        print(f"  cost_analysis: flops={result['flops']:.3e} "
              f"bytes={result['bytes_accessed']:.3e}")
        print(f"  collectives: { {k: f'{v:.2e}' for k, v in coll.items()} }")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    help="architecture id or 'all'")
    ap.add_argument("--shape", default="train_4k",
                    help="input shape name or 'all'")
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape)")
    ap.add_argument("--debug-mesh", action="store_true",
                    help="small 2x4 mesh (tests)")
    ap.add_argument("--out", default="",
                    help="write JSON results to this path")
    ap.add_argument("--cost-extrapolate", action="store_true",
                    help="add exact depth-extrapolated roofline costs")
    args = ap.parse_args()

    archs = ASSIGNED_ARCHS if (args.all or args.arch == "all") \
        else [args.arch]
    shapes = ["train_4k", "prefill_32k", "decode_32k", "long_500k"] \
        if (args.all or args.shape == "all") else [args.shape]
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]

    results, failures = [], []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    results.append(run_one(
                        arch, shape, multi_pod=mp,
                        debug_mesh=args.debug_mesh,
                        extrapolate=args.cost_extrapolate))
                except Exception as e:  # noqa: BLE001
                    failures.append({"arch": arch, "shape": shape,
                                     "multi_pod": mp, "error": str(e)[:500]})
                    print(f"[dryrun] FAIL {arch} x {shape} x mp={mp}: "
                          f"{str(e)[:200]}")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"results": results, "failures": failures}, f,
                      indent=1)
    print(f"\n[dryrun] {len(results)} ok, {len(failures)} failed")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()

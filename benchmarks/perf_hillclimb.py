import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""§Perf hillclimbing harness (deliverable g + the perf-iteration log).

For a chosen (arch x shape) pair this measures the depth-extrapolated
roofline terms of the BASELINE lowering, then re-lowers each candidate
variant (config/sharding/donation change) and reports the per-term delta —
the hypothesis -> change -> measure -> validate loop, driven from the
compiled HLO because this container has no TPU clock.

  PYTHONPATH=src python -m benchmarks.perf_hillclimb \
      --pairs llama4-scout-17b-a16e:train_4k phi3-medium-14b:decode_32k \
      --variants chunked_attention capacity_moe
"""
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402

from repro.launch.dryrun import cost_extrapolated   # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
CHIPS = 256


def terms(costs: dict) -> dict:
    return {
        "compute_s": costs["flops"] / PEAK_FLOPS,
        "memory_s": costs["bytes_accessed"] / HBM_BW,
        "collective_s": costs["collective_bytes"]["total"] / (CHIPS * ICI_BW),
        "temp_gb": costs.get("u2_temp_bytes", 0) / 1e9,
    }


# ---------------------------------------------------------------------------
# candidate variants (name, hypothesis, cfg_transform, donate)
# ---------------------------------------------------------------------------

VARIANTS = {
    "no_remat": (
        "training without activation checkpointing: compute term down "
        "~25-30% (no recompute) at the cost of activation memory",
        lambda c: c.replace(remat=False), False),
    "donate_train_state": (
        "donate params+optimizer buffers in train step: removes the "
        "copy-on-write of the updated state -> memory term down",
        None, True),
    "top1_router": (
        "MoE top-1 instead of top-6 (deepseek): active-expert FLOPs and "
        "expert all-reduce traffic scale ~1/6 (quality trade-off, measures "
        "the routing-cost share)",
        lambda c: c.replace(top_k=1), False),
    "chunked_attention": (
        "flash-style chunked reference attention (lax.scan over KV blocks, "
        "streaming softmax): removes the O(S*T) score materialization -> "
        "memory term down by ~2*S*T*H*4B/HBM_bw; also what makes 32k "
        "prefill fit per-device HBM",
        lambda c: c.replace(ref_attention="chunked"), False),
    "capacity_moe": (
        "capacity-based scatter/gather MoE dispatch instead of all-experts "
        "dense einsum: FFN FLOPs scale with routed tokens -> compute term "
        "down ~E/(top_k*cap_factor)",
        lambda c: c.replace(moe_dispatch="capacity"), False),
    "capacity_moe_ep": (
        "capacity dispatch + explicit expert-parallel sharding constraint "
        "on the dispatch buffers (GSPMD cannot infer sharding through the "
        "data-dependent scatter; the constraint should restore the "
        "E/(top_k*cap) per-device FLOPs reduction)",
        lambda c: c.replace(moe_dispatch="capacity",
                            moe_ep_constraint=True), False),
    "capacity_moe_chunked_attn": (
        "both MoE capacity dispatch and chunked attention",
        lambda c: c.replace(moe_dispatch="capacity",
                            ref_attention="chunked"), False),
    "all_opts": (
        "chunked attention + capacity MoE + donation",
        lambda c: c.replace(moe_dispatch="capacity",
                            ref_attention="chunked"), True),
}


def run_pair(arch: str, shape: str, variant_names, multi_pod=False):
    mesh = make_production_mesh(multi_pod=multi_pod)
    out = {"arch": arch, "shape": shape, "iterations": []}
    with mesh:
        t0 = time.time()
        base = cost_extrapolated(arch, shape, mesh)
        bt = terms(base)
        out["baseline"] = {**bt, "dominant": max(bt, key=bt.get),
                           "compile_s": round(time.time() - t0, 1)}
        print(f"[perf] {arch} x {shape} baseline: " + " ".join(
            f"{k}={v:.3e}" for k, v in bt.items())
            + f" dominant={out['baseline']['dominant']}")
        for name in variant_names:
            hypo, transform, donate = VARIANTS[name]
            t0 = time.time()
            try:
                cost = cost_extrapolated(arch, shape, mesh,
                                         cfg_transform=transform,
                                         donate=donate)
                vt = terms(cost)
                deltas = {k: 100 * (vt[k] / bt[k] - 1) if bt[k] else 0.0
                          for k in vt}
                rec = {"variant": name, "hypothesis": hypo, **vt,
                       "delta_pct": deltas,
                       "compile_s": round(time.time() - t0, 1)}
                print(f"[perf]   {name}: " + " ".join(
                    f"{k.split('_')[0]}{d:+.1f}%"
                    for k, d in deltas.items()))
            except Exception as e:  # noqa: BLE001
                rec = {"variant": name, "hypothesis": hypo,
                       "error": str(e)[:300]}
                print(f"[perf]   {name}: FAILED {str(e)[:120]}")
            out["iterations"].append(rec)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pairs", nargs="+", required=True,
                    help="arch:shape entries")
    ap.add_argument("--variants", nargs="+", required=True,
                    choices=sorted(VARIANTS))
    ap.add_argument("--out", default="results/perf_hillclimb.json")
    args = ap.parse_args()

    results = []
    for pair in args.pairs:
        arch, shape = pair.split(":")
        results.append(run_pair(arch, shape, args.variants))
    existing = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            existing = json.load(f)
    with open(args.out, "w") as f:
        json.dump(existing + results, f, indent=1)


if __name__ == "__main__":
    main()

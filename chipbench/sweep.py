#!/usr/bin/env python3
"""Find a cell's knee: the highest Poisson rate whose completed output
follows the offered load.

    python3 chipbench/sweep.py --workload <cell> --rates 2,2.5,3 \\
        --seeds 7,8 --seconds 40

One process builds the cell's engine once, then serves the cell's mix at
every rate and seed, each on a fresh engine, for the ramp and
``--seconds``. For each it prints one JSON line: the output tokens
completed in the window against the output of the requests due in it
(``served_share``), the scheduler's queue growth over the window (least
squares over a sample after every step) and the TTFT p90 of the requests
that started. A rate holds when its ``served_share``, averaged over the
seeds, is at least ``FOLLOWS``. Every rate is run; the last line gives
the knee, the highest rate that holds where every lower rate holds too.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

#: share of the offered output that a sustained rate completes
FOLLOWS = 0.95


def main(argv=None) -> int:
    import argparse
    import json
    import numpy as np
    from chipbench import harness, spec, traffic_gen
    from chipbench.serve_loop import Client
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    bench = spec.Bench(ROOT)
    cell = bench.cell(args.workload)
    conf = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    if mix["kind"] != "poisson":
        raise SystemExit("the sweep offers Poisson load")
    device, hw = harness.device_check(cell["chips"])
    harness.compile_cache(ROOT)
    from repro.policies import get_policy
    _, backend, _ = harness.build(conf, hw, seeds[0])
    w0 = mix["ramp_s"]
    w1 = w0 + args.seconds
    rates = sorted(float(r) for r in args.rates.split(","))
    shares = {}
    for rate in rates:
        for seed in seeds:
            eng = harness.engine(conf, hw, backend)
            client = Client(eng, get_policy("agft", hardware=hw),
                            traffic_gen.schedule(dict(mix, rate=rate),
                                                 seed, [w0, args.seconds]),
                            mix["template_frac"])
            samples = []

            def done():
                t = client.now()
                if t >= w0:
                    samples.append((t, len(eng.sched.waiting)))
                return t >= w1

            client.run_until(done)
            t, q = np.array(samples).T
            client.submit_due()
            due = client.due_in(w0, w1)
            ttft = [r.first_token - r.due for r in due if r.first_token]
            completed = sum(s.tokens for s in client.steps
                            if w0 <= s.end < w1)
            offered = sum(r.output_len for r in due)
            row = {"rate": rate, "seed": seed, "due": len(due),
                   "served_share": completed / offered,
                   "output_tokens_per_s": completed / args.seconds,
                   "offered_tokens_per_s": offered / args.seconds,
                   "queue_growth_per_s": float(np.polyfit(t, q, 1)[0]),
                   "queue_end": int(q[-1]),
                   "ttft_p90_ms": float(np.percentile(ttft, 90)) * 1e3
                   if ttft else None}
            shares.setdefault(rate, []).append(row["served_share"])
            print(json.dumps(row), flush=True)
    knee = None
    for rate in rates:
        if np.mean(shares[rate]) < FOLLOWS:
            break
        knee = rate
    print(json.dumps({"workload": args.workload, "knee": knee,
                      "served_share": {str(r): float(np.mean(shares[r]))
                                       for r in rates},
                      "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [p for p in sys.path if p != here]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())

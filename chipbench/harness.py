"""One run of one cell: set-up, ramp, measured window, drain, the check
of the timed path's outputs, and the result line.

A run:

1. builds one ``InferenceEngine`` over a ``JaxBackend`` for the cell's
   configuration (device batch and cache length from the configuration
   file), puts the benchmark's weights from ``--seed`` in place of the
   program's, and attaches ``get_policy("agft")`` at the device's own
   spec: this is ``setup_s``, timed from the process's start;
2. serves the cell's traffic with the open-loop client
   (``chipbench.serve_loop``): a ramp of ``ramp_s`` seconds brings the
   batch to a steady state, then the window of ``--seconds`` seconds is
   measured. Requests due in the window are the sample. Compilations
   from the ramp's start to the window's close are counted;
3. keeps serving, with the same load, until every request due in the
   window has finished (``DRAIN_CAP_S`` at most), reads the device's
   peak memory, then captures decode and prefill calls of the timed
   path and compares them with the configuration's reference
   (``chipbench.check``);
4. with ``--trace 1``, profiles the last ``TRACE_S`` seconds of the
   window and reports the per-layer metrics instead of the end-to-end
   ones.

With ``control`` (``chipbench/control.py``, never a benchmark run), the
int8 reference is also read in the program's place on the same captured
calls, and judged by the same limits.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import glob
import json
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import List, Optional

from chipbench import check, spec, traffic_gen, xtrace
from chipbench.roofline import Shape
from chipbench.serve_loop import DRAIN_CAP_S, SPANS, Client

#: seconds at the end of the window that a traced run profiles
TRACE_S = 6.0
#: longest the check may wait for the calls it captures
CAPTURE_CAP_S = 30.0


@dataclasses.dataclass
class Run:
    """What the metric readers (``chipbench/metrics/*.py``) read."""
    shape: Shape
    batch: int
    device_kind: str
    window: tuple                 # (start, end) on the client's clock
    reqs: list                    # requests due in the window
    steps: list                   # engine steps ending in the window
    execs: list                   # backend calls
    policy_calls: list
    setup_s: float
    traced: Optional[tuple] = None  # traced window on the client's clock
    trace: Optional[dict] = None    # ``xtrace.reduce`` of it
    prefill_max: int = 0


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_check(chips: int):
    """The chip's description and hardware spec. Exits with an error,
    and prints no result, on any platform but a TPU or with fewer chips
    than the cell asks for."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < chips:
        raise SystemExit(f"chipbench: needs {chips} TPU chip(s); JAX "
                         f"finds {len(devices)} {dev.platform} device(s)")
    from repro.energy import hardware_for_device
    return ({"platform": dev.platform, "kind": dev.device_kind,
             "count": len(devices)}, hardware_for_device(dev.device_kind))


def compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache, inside the checkout at a fixed
    path unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program is
    kept, so that only a cell's first run compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@contextlib.contextmanager
def count_compiles():
    """Collect the name of every JAX trace, lowering and compilation
    inside the block (as ``repro.launch.serve.count_compiles``)."""
    import jax.monitoring
    events: List[str] = []

    def on_event(name, _secs, **_kw):
        if name.startswith("/jax/core/compile/"):
            events.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield events
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)


@contextlib.contextmanager
def gc_pauses():
    """Collect (start, seconds) on the ``time.perf_counter`` clock of
    every garbage collection inside the block."""
    pauses: List[tuple] = []
    started = [0.0]

    def on_gc(phase, _info):
        if phase == "start":
            started[0] = time.perf_counter()
        else:
            pauses.append((started[0], time.perf_counter() - started[0]))

    gc.callbacks.append(on_gc)
    try:
        yield pauses
    finally:
        gc.callbacks.remove(on_gc)


def window_summary(client: Client, w0: float, w1: float,
                   pauses: List[tuple]) -> dict:
    """What the host saw in the window, for standard error: engine steps
    (milliseconds: quartiles, p99, the longest with when they ended),
    the share of steps that ran prefill, the longest host gap between
    consecutive steps, and the garbage collector's pauses."""
    import numpy as np
    steps = [s for s in client.steps if w0 <= s.end < w1]
    if not steps:
        return {"steps": 0}
    ms = np.array([(s.end - s.start) * 1e3 for s in steps])
    longest = sorted(steps, key=lambda s: s.start - s.end)[:5]
    execs = [e for e in client.execs if w0 <= e.end < w1]
    gaps = [(b.start - a.end) * 1e3 for a, b in zip(steps, steps[1:])]
    gcs = [d * 1e3 for t, d in pauses
           if w0 <= t - client.t0 < w1]
    return {
        "steps": len(steps),
        "step_ms_q": [round(float(q), 3)
                      for q in np.percentile(ms, [25, 50, 75, 99, 100])],
        "longest_steps": [[round(s.end - w0, 3),
                           round((s.end - s.start) * 1e3, 3)]
                          for s in longest],
        "prefill_share": (sum(1 for e in execs if e.prefill_tokens)
                          / max(1, len(execs))),
        "host_gap_ms_max": round(max(gaps), 3) if gaps else 0.0,
        "gc": [len(gcs), round(sum(gcs), 3),
               round(max(gcs), 3) if gcs else 0.0],
    }


def build_backend(cfg, hw, batch: int, cache_len: int):
    from repro.serving import JaxBackend
    return JaxBackend(cfg, hw, max_batch=batch, cache_len=cache_len)


def load_weights(backend, conf: dict, seed: int):
    """Put the benchmark's weights from ``seed`` in place of the
    program's own, freeing those first."""
    import jax
    from chipbench import weights
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          backend.params)
    backend.params = None
    gc.collect()
    backend.params = weights.make(shapes, conf["initializer_range"], seed)
    jax.block_until_ready(backend.params)
    return backend.params


def engine(conf: dict, hw, backend):
    """A fresh engine over ``backend``: ``max_num_seqs`` is the device
    batch, with KV blocks for every slot of every row."""
    from repro.serving import EngineConfig, InferenceEngine
    dep = conf["deployment"]
    return InferenceEngine(
        backend.cfg,
        EngineConfig(max_num_seqs=dep["max_batch"],
                     num_kv_blocks=dep["max_batch"] * dep["cache_len"]
                     // 16),
        hardware=hw, backend=backend, initial_frequency=hw.f_max)


def build(conf: dict, hw, seed: int):
    """Engine, backend and weights of one configuration."""
    dep = conf["deployment"]
    backend = build_backend(spec.model_config(conf), hw, dep["max_batch"],
                            dep["cache_len"])
    w = load_weights(backend, conf, seed)
    return engine(conf, hw, backend), backend, w


def capture(client: Client, backend) -> check.Capture:
    """Capture the check's calls from the timed path while the client
    keeps serving the mix."""
    cap = check.Capture(backend)
    cap.install()
    t_end = client.now() + CAPTURE_CAP_S
    client.run_until(lambda: len(cap.prefill) >= check.PREFILL_CALLS
                     or client.now() > t_end)
    cap.install_decode()
    client.run_until(lambda: cap.final is not None or client.now() > t_end)
    cap.uninstall()
    if not cap.done:
        raise RuntimeError("the check captured too few calls: "
                           f"{len(cap.decode)} decode, "
                           f"{len(cap.prefill)} prefill")
    return cap


def wrong_length(reqs) -> int:
    return sum(1 for r in reqs
               if r.request.finish_time is None
               or r.request.generated != r.output_len
               or r.tokens != r.output_len)


def run_cell(bench: spec.Bench, name: str, seed: int, seconds: float,
             trace: bool, t_start: float, device: dict, hw,
             control: bool = False) -> dict:
    import jax
    cell = bench.cell(name)
    conf = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    spec.check_fits(conf, mix)
    eng, backend, weights = build(conf, hw, seed)
    from repro.policies import get_policy
    policy = get_policy("agft", hardware=hw)
    w0 = float(mix["ramp_s"])
    w1 = w0 + seconds
    arrivals = traffic_gen.schedule(
        mix, seed, [w0, seconds, DRAIN_CAP_S + 2 * CAPTURE_CAP_S])
    setup_s = time.perf_counter() - t_start
    phases = {"setup": setup_s}

    client = Client(eng, policy, arrivals, mix["template_frac"],
                    spans=trace)
    traced = summary = None
    with count_compiles() as compiles, gc_pauses() as pauses:
        if trace:
            client.run_until(lambda: client.now() >= w1 - TRACE_S)
            tmp = tempfile.TemporaryDirectory()
            jax.profiler.start_trace(tmp.name)
            t0 = client.now()
            with jax.profiler.TraceAnnotation(xtrace.WINDOW):
                client.run_until(lambda: client.now() >= w1)
            traced = (t0, client.now())
            jax.profiler.stop_trace()
        else:
            client.run_until(lambda: client.now() >= w1)
    window_compiles = len(compiles)
    if trace:
        path = glob.glob(f"{tmp.name}/**/*.xplane.pb", recursive=True)[0]
        summary = xtrace.reduce(xtrace.extract(path, SPANS))
        tmp.cleanup()

    # every request due in the window is in the sample, also one that
    # fell due during the window's last step
    client.submit_due()
    due = client.due_in(w0, w1)
    client.run_until(lambda: client.all_finished(due)
                     or client.now() >= w1 + DRAIN_CAP_S)
    phases["ramp_and_window"] = w1
    phases["drain"] = client.now() - w1
    print("window " + json.dumps(window_summary(client, w0, w1, pauses)),
          file=sys.stderr)
    peak = jax.devices()[0].memory_stats() or {}
    run = Run(shape=spec.shape(conf),
              batch=conf["deployment"]["max_batch"],
              device_kind=device["kind"], window=(w0, w1), reqs=due,
              steps=[s for s in client.steps if w0 <= s.end < w1],
              execs=client.execs, policy_calls=client.policy_calls,
              setup_s=setup_s, traced=traced, trace=summary,
              prefill_max=backend.PREFILL_MAX)

    t = time.perf_counter()
    cap = capture(client, backend)
    phases["capture"] = time.perf_counter() - t
    del client, eng, policy
    backend.cache = None
    gc.collect()
    t = time.perf_counter()
    reference = bench.reference(conf)
    numbers = check.readings(cap, weights, conf, reference)
    phases["reference"] = time.perf_counter() - t
    print("seconds " + json.dumps(phases), file=sys.stderr)
    print("readings " + json.dumps(numbers), file=sys.stderr)
    numbers["requests_wrong_length"] = wrong_length(due)
    numbers["window_compiles"] = window_compiles
    checks = check.verdict(numbers, conf["check_limits"])
    if control:
        ctl = check.readings(cap, weights, conf, reference, control=True)
        print("control_readings " + json.dumps(ctl), file=sys.stderr)
        ctl_checks = check.verdict(ctl, conf["check_limits"])

    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench.metrics_for(name, kind):
        value = bench.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=peak.get("peak_bytes_in_use"))
    out = {"correct": check.passed(checks), "attempted": len(due),
           "failed": numbers["requests_wrong_length"], "metrics": metrics,
           "device": dev}
    if control:
        out["control_correct"] = check.passed(ctl_checks)
        out["control_checks"] = ctl_checks
    if trace:
        dev["busy_s"] = summary["busy_s"]
        dev["window_s"] = summary["window_s"]
        out["breakdown"] = {"device_ops": summary["device_ops"],
                            "idle_gaps": summary["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None, t_start: Optional[float] = None,
         root: Optional[Path] = None, control: bool = False) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    root = Path(__file__).resolve().parents[1] if root is None else root
    args = parse(argv)
    bench = spec.Bench(root)
    cell = bench.cell(args.workload)
    device, hw = device_check(cell["chips"])
    compile_cache(root)
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_start, device, hw, control)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0

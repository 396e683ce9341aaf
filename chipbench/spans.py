#!/usr/bin/env python3
"""Serve one cell as ``chipbench/run.py`` does, with the program's own
tracer (``repro.tracing``) on, and print what its spans and counters show.

    python3 chipbench/spans.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The tracer is on from the client's first step until every request due in
the window has finished, since a request due late in the window may wait
past its close. The run skips the check of the timed path, so it reports
no ``correct``. Its last line on standard output is one JSON object:

- ``metrics``: every end-to-end reader of ``BENCHMARK.json`` on the run
  (the window's numbers, with the tracer on), with ``--trace 1`` also the
  cell's per-layer readers, and the in-program numbers of ``READERS``;
- ``device`` and, with ``--trace 1``, ``busy_s`` and ``window_s`` as the
  harness reads them, the split's ``idle_s``, ``clock_offset`` (ns, ns,
  share of programs) and ``traced_iterations``.

On standard error, ``window`` is the harness's summary of the window's
steps; ``stalls`` lists the window's five longest iterations (from one
``engine.iteration`` start to the next: seconds into the window, ms, and
the program span, or ``outside``, holding most of that time, with its
ms).

With ``--trace 1`` it profiles the window's last ``TRACE_S`` seconds as
the harness does and splits the chip's idle time there by the innermost
program span the host was in (``idle_by_span``). The profile stamps the
chip's events a millisecond or two off the host's, as long as the gaps
between calls, so the chip's clock is first moved onto the host's: by
the middle of the shifts that put every program inside the host call
that ran it (``clock_offset``). Standard error gives the split at the
least, the middle and the greatest such shift (``idle_by_span <at>``),
and ``traced_stalls``, the traced window's five longest iterations as
``stalls`` has them, each with the ms the chip ran in it.
"""
from __future__ import annotations

import bisect
import dataclasses
import math
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]

#: the program's scoped spans (``repro.tracing.span`` call sites); none
#: is a client span (``chipbench.serve_loop.SPANS``)
PROGRAM_SPANS = ("engine.iteration", "sched.plan", "sched.complete",
                 "device.prefill", "device.prefill.launch",
                 "device.prefill.wait", "device.decode",
                 "device.decode.inputs", "device.decode.launch",
                 "device.decode.wait", "agft.decide")
#: time under no program span: the client's own code and sleeps
OUTSIDE = "outside"
#: the device calls, whose self time is launch work
CALLS = ("device.prefill", "device.decode")
#: a part of a device program's name -> the host call that runs it
CALL_OF = {"decode_step": "device.decode", "lambda": "device.prefill"}
#: how far (ns) apart a program and the call that ran it may be stamped
REACH = 20_000_000
PLANNED = "device.prefill_tokens_planned"
COMPUTED = "device.prefill_tokens_computed"


def bucket(name: str) -> str:
    """``wait`` (blocked on the device), ``launch`` (a call's inputs,
    launch and own time), ``host`` (the engine, scheduler and AGFT) or
    ``outside``."""
    if name.endswith(".wait"):
        return "wait"
    if name.startswith("device."):
        return "launch"
    return OUTSIDE if name == OUTSIDE else "host"


def innermost(spans, w0: float, w1: float) -> List[Tuple[float, float,
                                                          str]]:
    """Partition of ``[w0, w1]`` into ``(start, end, name)`` pieces, each
    named by the innermost of ``spans`` (``(name, start, end)``, nested
    as one thread's are) that holds it, ``OUTSIDE`` where none does."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []     # (end, name), innermost last
    at = [w0]

    def emit(upto, name):
        a, b = max(at[0], w0), min(upto, w1)
        if b > a:
            out.append((a, b, name))
        at[0] = max(at[0], upto)

    for s, e, n in sorted(((s, e, n) for n, s, e in spans),
                          key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            emit(*stack.pop())
        emit(s, stack[-1][1] if stack else OUTSIDE)
        stack.append((e, n))
    while stack:
        emit(*stack.pop())
    emit(w1, OUTSIDE)
    return out


def clock_offset(dev: dict, host) -> Optional[Tuple[float, float, float]]:
    """``(lo, hi, share)``: the shifts (ns) from ``lo`` to ``hi`` which,
    added to the times of chip ``dev``, put the largest ``share`` of its
    programs inside a host call that runs them (``CALL_OF``; ``host`` as
    ``xtrace.extract`` gives it); None where that is under half of them.

    A profile stamps the chip's events and the host's on clocks that it
    aligns to a millisecond or so only, and an idle gap between two calls
    is as short: a gap is named after the shift. Each program may pair
    with a call of its kind that starts within ``REACH`` of it; of the
    stretches of shifts that fit the most pairs, the one nearest to no
    shift is taken."""
    fits = []                      # (lo, hi) shifts of one pair
    n = 0
    for needle, call in CALL_OF.items():
        calls = sorted((s, s + d) for name, s, d in host if name == call)
        starts = [c[0] for c in calls]
        for name, s, d in dev["modules"]:
            if needle not in name:
                continue
            n += 1
            i = bisect.bisect_left(starts, s - REACH)
            for c0, c1 in calls[i:bisect.bisect_right(starts, s + REACH)]:
                if c0 - s <= c1 - s - d:
                    fits.append((c0 - s, c1 - s - d))
    # sweep: a stretch's start before any end at one shift
    edges = sorted([(lo, 0) for lo, _ in fits] + [(hi, 1) for _, hi in fits])
    best, held, out = 0, 0, None
    for (x, end), nxt in zip(edges, edges[1:] + [(math.inf, 1)]):
        held += -1 if end else 1
        if not end and (held > best or held == best and out is not None
                        and abs(x + nxt[0]) < abs(out[0] + out[1])):
            best, out = held, (x, nxt[0])
    if out is None or best < n / 2:
        return None
    return out[0], out[1], best / n


def busy(dev: dict, shift: float, w0: float, w1: float):
    """The union of chip ``dev``'s operation intervals moved by ``shift``,
    inside ``[w0, w1]``."""
    from chipbench import xtrace
    return xtrace._union([(a, b) for _, a, b in xtrace._clip(
        [(n, s + shift, d) for n, s, d in dev["ops"]], w0, w1)])


def shifts(ex: dict, at: float) -> Optional[List[float]]:
    """Each chip's shift onto the host's clock: the point ``at`` (0 the
    lowest, 1 the highest) of its ``clock_offset`` range; None where a
    chip has none."""
    out = []
    for dev in ex["devices"]:
        off = clock_offset(dev, ex["host"])
        if off is None:
            return None
        lo, hi, _ = off
        out.append(lo + at * (hi - lo))
    return out


def idle_by_span(ex: dict, at: float = 0.5) -> Optional[Dict[str, float]]:
    """Idle seconds of the traced window by innermost program span
    (``OUTSIDE`` under none), averaged over the chips, each chip moved
    onto the host's clock by ``shifts(ex, at)``; None where a chip cannot
    be. ``ex`` is ``xtrace.extract(path, PROGRAM_SPANS)``."""
    from chipbench import xtrace
    w0, w1 = xtrace.window(ex)
    moved = shifts(ex, at)
    if moved is None:
        return None
    parts = innermost([(n, s, s + d) for n, s, d in ex["host"]
                       if n != xtrace.WINDOW], w0, w1)
    starts = [a for a, _, _ in parts]
    idle: Dict[str, float] = defaultdict(float)
    for dev, shift in zip(ex["devices"], moved):
        iv = busy(dev, shift, w0, w1)
        edges = [w0] + [x for ab in iv for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            i = max(0, bisect.bisect_right(starts, a) - 1)
            while i < len(parts) and parts[i][0] < b:
                lo, hi = max(a, parts[i][0]), min(b, parts[i][1])
                if hi > lo:
                    idle[parts[i][2]] += (hi - lo) * 1e-9
                i += 1
    return {k: v / len(ex["devices"]) for k, v in idle.items()}


def longest(spans, w0: float, w1: float, ms: float, top: int = 5,
            device=None) -> List[list]:
    """The ``top`` longest iterations in ``[w0, w1)``, each from one
    ``engine.iteration`` start to the next, of ``spans`` (``(name, start,
    end)``; ``ms`` milliseconds a unit of their clock): seconds into the
    window, ms, the program span (or ``OUTSIDE``) holding most of it and
    its ms, and with ``device`` (busy intervals on the same clock) the
    ms in which the chip ran."""
    starts = sorted(a for n, a, _ in spans
                    if n == "engine.iteration" and w0 <= a < w1)
    periods = sorted(zip(starts, starts[1:]), key=lambda p: p[0] - p[1])
    out = []
    for a, b in periods[:top]:
        held: Dict[str, float] = defaultdict(float)
        for s, e, n in innermost([x for x in spans if x[1] < b and x[2] > a],
                                 a, b):
            held[n] += e - s
        name = max(held, key=held.get)
        row = [round((a - w0) * ms / 1e3, 3), round((b - a) * ms, 3), name,
               round(held[name] * ms, 3)]
        if device is not None:
            row.append(round(sum(max(0, min(b, e) - max(a, s))
                                 for s, e in device) * ms, 3))
        out.append(row)
    return out


@dataclasses.dataclass
class Program:
    """What the program's tracer recorded, on the client's clock."""
    spans: List[tuple]            # (name, start, end, parent)
    requests: List[tuple]         # (name, request_id, start, end)
    window_counters: Dict[str, int]   # counted inside the window
    idle: Optional[Dict[str, float]] = None   # ``idle_by_span``
    traced_iterations: int = 0    # engine.iteration starts in the trace


def on_clock(rec: dict, t0: float, c0: dict, c1: dict) -> Program:
    """The tracer's records on the clock of a client started at ``t0``
    (``time.perf_counter``), with the counters' growth from ``c0`` to
    ``c1``."""
    def s(ns):
        return ns * 1e-9 - t0
    return Program(
        spans=[(n, s(a), s(b), p) for n, a, b, p in rec["spans"]],
        requests=[(n, k, s(a), s(b)) for n, k, a, b in rec["requests"]],
        window_counters={k: c1.get(k, 0) - c0.get(k, 0) for k in c1})


def _in_window(run, prog: Program, name: str) -> List[int]:
    w0, w1 = run.window
    return [i for i, (n, _, e, _) in enumerate(prog.spans)
            if n == name and w0 <= e < w1]


def _idle_ms_per_iter(kind: str):
    def read(run, prog: Program) -> Optional[float]:
        if prog.idle is None or not prog.traced_iterations:
            return None
        s = sum(v for k, v in prog.idle.items() if bucket(k) == kind)
        return s / prog.traced_iterations * 1e3
    return read


def engine_host_ms_per_iter(run, prog: Program) -> Optional[float]:
    """Mean ``engine.iteration`` less its device calls, over the window's
    iterations: the in-program twin of ``host_ms_per_iter``."""
    its = _in_window(run, prog, "engine.iteration")
    if not its:
        return None
    calls: Dict[int, float] = defaultdict(float)
    for n, a, b, parent in prog.spans:
        if n in CALLS:
            calls[parent] += b - a
    host = [prog.spans[i][2] - prog.spans[i][1] - calls[i] for i in its]
    return sum(host) / len(host) * 1e3


def agft_ms_per_decision(run, prog: Program) -> Optional[float]:
    """Mean ``agft.decide`` over the window: the twin of
    ``controller_ms_per_decision``."""
    d = [prog.spans[i][2] - prog.spans[i][1]
         for i in _in_window(run, prog, "agft.decide")]
    return sum(d) / len(d) * 1e3 if d else None


def _wait_p90(name: str):
    def read(run, prog: Program) -> Optional[float]:
        from chipbench.metrics._common import pct
        due = {r.request.request_id for r in run.reqs}
        return pct([(b - a) * 1e3 for n, k, a, b in prog.requests
                    if n == name and k in due], 90)
    return read


def prefill_computed_share(run, prog: Program) -> Optional[float]:
    """Prompt tokens the device computed over those the scheduler planned,
    in the window (%)."""
    c = prog.window_counters
    planned = c.get(PLANNED, 0)
    return 100.0 * c.get(COMPUTED, 0) / planned if planned else None


#: the in-program numbers: name -> ``read(run, prog)``
READERS = {
    "idle_wait_ms_per_iter": _idle_ms_per_iter("wait"),
    "idle_launch_ms_per_iter": _idle_ms_per_iter("launch"),
    "idle_host_ms_per_iter": _idle_ms_per_iter("host"),
    "engine_host_ms_per_iter": engine_host_ms_per_iter,
    "agft_ms_per_decision": agft_ms_per_decision,
    "sched_wait_ms_p90": _wait_p90("request.queued"),
    "prefill_wait_ms_p90": _wait_p90("request.prefill"),
    "prefill_computed_share": prefill_computed_share,
}


def stalls(run, prog: Program, top: int = 5) -> List[list]:
    """``longest`` of the window's iterations in the tracer's records."""
    w0, w1 = run.window
    return longest([(n, a, b) for n, a, b, _ in prog.spans], w0, w1, 1e3,
                   top)


def traced_stalls(ex: dict, top: int = 5) -> Optional[List[list]]:
    """``longest`` of the traced window's iterations in the profile, with
    the first chip's busy ms (moved by the middle of its
    ``clock_offset``)."""
    from chipbench import xtrace
    moved = shifts(ex, 0.5)
    if moved is None:
        return None
    w0, w1 = xtrace.window(ex)
    return longest([(n, s, s + d) for n, s, d in ex["host"]
                    if n != xtrace.WINDOW], w0, w1, 1e-6, top,
                   busy(ex["devices"][0], moved[0], w0, w1))


def run_cell(bench, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, device: dict, hw) -> dict:
    import glob
    import json
    import tempfile

    import jax
    from chipbench import harness, spec, traffic_gen, xtrace
    from chipbench.serve_loop import DRAIN_CAP_S, SPANS, Client
    from repro import tracing
    from repro.policies import get_policy
    cell = bench.cell(name)
    conf = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    spec.check_fits(conf, mix)
    eng, backend, _ = harness.build(conf, hw, seed)
    w0 = float(mix["ramp_s"])
    w1 = w0 + seconds
    arrivals = traffic_gen.schedule(
        mix, seed, [w0, seconds, DRAIN_CAP_S + 2 * harness.CAPTURE_CAP_S])
    setup_s = time.perf_counter() - t_start
    client = Client(eng, get_policy("agft", hardware=hw), arrivals,
                    mix["template_frac"], spans=trace)
    tracing.reset()
    tracing.enable()
    with harness.gc_pauses() as pauses:
        client.run_until(lambda: client.now() >= w0)
        c0 = dict(tracing.TRACER.counters)
        traced = summary = ex = None
        if trace:
            client.run_until(lambda: client.now() >= w1 - harness.TRACE_S)
            tmp = tempfile.TemporaryDirectory()
            jax.profiler.start_trace(tmp.name)
            t = client.now()
            with jax.profiler.TraceAnnotation(xtrace.WINDOW):
                client.run_until(lambda: client.now() >= w1)
            traced = (t, client.now())
            jax.profiler.stop_trace()
        else:
            client.run_until(lambda: client.now() >= w1)
        c1 = dict(tracing.TRACER.counters)
    if trace:
        path = glob.glob(f"{tmp.name}/**/*.xplane.pb", recursive=True)[0]
        summary = xtrace.reduce(xtrace.extract(path, SPANS))
        ex = xtrace.extract(path, PROGRAM_SPANS)
        tmp.cleanup()
    client.submit_due()
    due = client.due_in(w0, w1)
    client.run_until(lambda: client.all_finished(due)
                     or client.now() >= w1 + DRAIN_CAP_S)
    tracing.disable()
    prog = on_clock(tracing.records(), client.t0, c0, c1)
    tracing.reset()
    print("window " + json.dumps(harness.window_summary(client, w0, w1,
                                                         pauses)),
          file=sys.stderr)
    run = harness.Run(shape=spec.shape(conf),
                      batch=conf["deployment"]["max_batch"],
                      device_kind=device["kind"], window=(w0, w1), reqs=due,
                      steps=[s for s in client.steps if w0 <= s.end < w1],
                      execs=client.execs, policy_calls=client.policy_calls,
                      setup_s=setup_s, traced=traced, trace=summary,
                      prefill_max=backend.PREFILL_MAX)
    dev = dict(device)
    if trace:
        prog.idle = idle_by_span(ex)
        wa, wb = xtrace.window(ex)
        prog.traced_iterations = sum(
            1 for n, s, _ in ex["host"]
            if n == "engine.iteration" and wa <= s < wb)
        dev.update(busy_s=summary["busy_s"], window_s=summary["window_s"],
                   idle_s=sum(prog.idle.values()) if prog.idle else None,
                   clock_offset=clock_offset(ex["devices"][0], ex["host"]),
                   traced_iterations=prog.traced_iterations)
        for at in (0.0, 0.5, 1.0):
            idle = idle_by_span(ex, at) or {}
            print(f"idle_by_span {at} " + json.dumps(
                sorted(idle.items(), key=lambda kv: -kv[1])),
                  file=sys.stderr)
        print("traced_stalls " + json.dumps(traced_stalls(ex)),
              file=sys.stderr)
    print("stalls " + json.dumps(stalls(run, prog)), file=sys.stderr)
    metrics = {}
    names = [m["name"] for m in bench.spec["end_to_end"]]
    if trace:
        names += [m["name"] for m in bench.metrics_for(name, "per_layer")]
    for m in names:
        metrics[m] = bench.reader(m)(run)
    for m, read in READERS.items():
        metrics[m] = read(run, prog)
    return {"workload": name, "seed": seed, "trace": int(trace),
            "metrics": metrics, "device": dev}


def main(argv=None) -> int:
    import json
    from chipbench import harness, spec
    args = harness.parse(argv)
    bench = spec.Bench(ROOT)
    device, hw = harness.device_check(bench.cell(args.workload)["chips"])
    harness.compile_cache(ROOT)
    out = run_cell(bench, args.workload, args.seed, args.seconds,
                   bool(args.trace), T_START, device, hw)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [p for p in sys.path if p != here]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())

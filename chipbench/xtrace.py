"""Reduction of a profiler trace to the device metrics of a traced run.

``extract`` reads a ``.xplane.pb`` with nothing but JAX into plain lists:
the device's programs (``XLA Modules``) and operations (``XLA Ops``) on
every TPU plane, and the client's own annotations on the host. ``reduce``
then works on those lists inside the traced window:

- busy seconds: the union of the intervals in which an operation ran,
  averaged over the chips;
- each program's device seconds and number of calls, by program name;
- the operations that took the most device time;
- the idle gaps, each named by the innermost client span that the host
  was in at the gap's middle, and summed by name.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

#: the annotation that brackets the traced window
WINDOW = "traced_window"
#: how deep the client's spans nest (engine.step > backend.execute)
NEST = 4

_SUFFIX = re.compile(r"\(\d+\)$")


def program_name(module: str) -> str:
    """``jit_decode_step(123)`` -> ``jit_decode_step``."""
    return _SUFFIX.sub("", module)


def op_name(op: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``%fusion.3``."""
    return op.split(" = ", 1)[0]


def extract(path: str, spans: Iterable[str]) -> dict:
    from jax.profiler import ProfileData
    names = set(spans) | {WINDOW}
    pd = ProfileData.from_file(path)
    out = {"devices": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules",
                       "XLA Ops": "ops"}.get(line.name)
                if key:
                    dev[key] += [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
            out["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events if e.name in names]
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float,
                                                                float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(events, w0, w1):
    for name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            yield name, a, b


def window(ex: dict) -> Tuple[float, float]:
    marks = [(s, s + d) for n, s, d in ex["host"] if n == WINDOW]
    if len(marks) != 1:
        raise ValueError(f"{len(marks)} traced-window marks in the trace")
    return marks[0]


def reduce(ex: dict, top: int = 10) -> dict:
    """Device metrics of the traced window (seconds)."""
    w0, w1 = window(ex)
    if not ex["devices"] or not any(d["ops"] for d in ex["devices"]):
        raise ValueError("no device operation in the trace")
    busy = 0.0
    programs: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    ops: Dict[str, float] = defaultdict(float)
    gaps_at: List[Tuple[float, float]] = []
    for dev in ex["devices"]:
        iv = _union([(a, b) for _, a, b in _clip(dev["ops"], w0, w1)])
        busy += sum(b - a for a, b in iv)
        edges = [w0] + [x for ab in iv for x in ab] + [w1]
        gaps_at += [(a, b) for a, b in zip(edges[::2], edges[1::2])
                    if b > a]
        for name, a, b in _clip(dev["modules"], w0, w1):
            p = programs[program_name(name)]
            p[0] += (b - a) * 1e-9
            p[1] += 1
        mods = sorted((s, s + d, program_name(n))
                      for n, s, d in dev["modules"])
        starts = [m[0] for m in mods]
        for name, a, b in _clip(dev["ops"], w0, w1):
            i = bisect.bisect_right(starts, a) - 1
            prog = mods[i][2] if i >= 0 and a < mods[i][1] else "?"
            ops[f"{prog}/{op_name(name)}"] += (b - a) * 1e-9
    n_dev = len(ex["devices"])
    spans = sorted((s, s + d, n) for n, s, d in ex["host"] if n != WINDOW)
    span_starts = [s for s, _, _ in spans]
    idle: Dict[str, float] = defaultdict(float)
    for a, b in gaps_at:
        mid = (a + b) / 2
        # the client's spans nest at most NEST deep and follow each other
        # in time, so the innermost one holding ``mid`` is among the last
        # few that start before it
        i = bisect.bisect_right(span_starts, mid)
        name = next((n for s, e, n in reversed(spans[max(0, i - NEST):i])
                     if mid < e), "none")
        idle[name] += (b - a) * 1e-9 / n_dev
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy * 1e-9 / n_dev,
        "programs": {k: {"device_s": v[0] / n_dev,
                         "calls": v[1] / n_dev}
                     for k, v in programs.items()},
        "device_ops": sorted(([k, v / n_dev] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top],
    }

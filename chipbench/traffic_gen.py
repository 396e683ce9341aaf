"""The one traffic generator: a mix's data file in, an arrival schedule
out.

A schedule is cut into segments (for a run: the ramp, the measured
window and what follows it). Each segment holds a fixed number of
arrivals, its length times the mix's rate, and its lengths and
inter-arrival gaps come from a fixed set that depends only on the mix
and that number: the stratified quantiles of the mix's distributions,
with the gaps scaled so that they fill the segment. The seed only
shuffles them within the segment. Every seed therefore serves the same
work in every segment, the measured window included, so runs with
different seeds differ in the order of the work and not in its amount.

Arrival processes (``kind``):

- ``poisson``: exponential gaps at ``rate`` requests/s.
- ``onoff``: exponential gaps at ``on_rate`` during ``on_s`` seconds,
  then nothing for ``off_s`` seconds (mean rate
  ``on_rate * on_s / (on_s + off_s)``), as in bursty traces.

Lengths are uniform over the inclusive ``prompt`` and ``output`` ranges,
as in the paper's workload prototypes (``repro.workloads.prototypes``),
and each request carries one of ``templates`` prompt templates sharing
``template_frac`` of its prompt.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Sequence, Union

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    due: float          # seconds after the schedule's start
    prompt: int
    output: int
    template: int


def mean_rate(mix: dict) -> float:
    if mix["kind"] == "poisson":
        return float(mix["rate"])
    if mix["kind"] == "onoff":
        return mix["on_rate"] * mix["on_s"] / (mix["on_s"] + mix["off_s"])
    raise ValueError(f"unknown arrival kind {mix['kind']!r}")


def _uniform_ints(lo: int, hi: int, n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return lo + np.floor(q * (hi - lo + 1)).astype(np.int64)


def _exp_gaps(rate: float, n: int) -> np.ndarray:
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


def _active(mix: dict, t: float) -> float:
    """Seconds of arrival time ("on" time) from 0 to wall time ``t``."""
    if mix["kind"] != "onoff":
        return t
    period = mix["on_s"] + mix["off_s"]
    return (math.floor(t / period) * mix["on_s"]
            + min(math.fmod(t, period), mix["on_s"]))


def _wall(mix: dict, a: np.ndarray) -> np.ndarray:
    """Wall time of arrival time ``a`` (the inverse of ``_active``)."""
    if mix["kind"] != "onoff":
        return a
    period = mix["on_s"] + mix["off_s"]
    return np.floor(a / mix["on_s"]) * period + np.mod(a, mix["on_s"])


def schedule(mix: dict, seed: int,
             segments: Union[float, Sequence[float]]) -> List[Arrival]:
    """Arrivals for ``seed`` over consecutive segments of the given
    lengths in seconds (one number: a single segment)."""
    if isinstance(segments, (int, float)):
        segments = [segments]
    mean_rate(mix)                              # refuses an unknown kind
    rate = mix["rate"] if mix["kind"] == "poisson" else mix["on_rate"]
    rng = np.random.default_rng(seed)
    out: List[Arrival] = []
    start = 0.0
    for length in segments:
        a0, a1 = _active(mix, start), _active(mix, start + length)
        n = max(1, round(rate * (a1 - a0)))
        prompts = rng.permutation(_uniform_ints(*mix["prompt"], n))
        outputs = rng.permutation(_uniform_ints(*mix["output"], n))
        templates = rng.permutation(
            (len(out) + np.arange(n)) % mix["templates"])
        gaps = rng.permutation(_exp_gaps(rate, n))
        # the first arrival opens the segment; the last gap leads into
        # the next one
        offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        due = _wall(mix, a0 + offsets * (a1 - a0) / gaps.sum())
        out += [Arrival(float(t), int(p), int(o), int(k))
                for t, p, o, k in zip(due, prompts, outputs, templates)]
        start += length
    return out

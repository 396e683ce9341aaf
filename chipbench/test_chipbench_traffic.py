"""The traffic generator: deterministic per seed, the same work for every
seed, and the on/off mix's mean rate."""
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from chipbench import traffic_gen
from chipbench.spec import Bench

ROOT = Path(__file__).resolve().parents[1]
BENCH = Bench(ROOT)
SEED = 3_000_000_019          # larger than 32 signed bits hold
#: the issue's bursty mix: the normal lengths at 7.2 req/s for 1 s, then
#: nothing for 2 s (a mean of 2.4 req/s)
BURSTY = dict(BENCH.traffic("chat"), kind="onoff", on_rate=7.2, on_s=1.0,
              off_s=2.0)


def mix(name):
    return BURSTY if name == "bursty" else BENCH.traffic(name)


@pytest.mark.parametrize("name", ["chat", "longctx", "bursty"])
def test_schedule_is_deterministic_per_seed(name):
    m = mix(name)
    a = traffic_gen.schedule(m, SEED, 120.0)
    assert a == traffic_gen.schedule(m, SEED, 120.0)
    assert a != traffic_gen.schedule(m, SEED + 1, 120.0)
    dues = [x.due for x in a]
    assert dues == sorted(dues)
    assert all(m["prompt"][0] <= x.prompt <= m["prompt"][1] for x in a)
    assert all(m["output"][0] <= x.output <= m["output"][1] for x in a)


@pytest.mark.parametrize("name", ["chat", "bursty"])
def test_every_seed_serves_the_same_work(name):
    m = mix(name)
    runs = [traffic_gen.schedule(m, s, 200.0) for s in (1, 2, SEED)]
    for key in ("prompt", "output", "template"):
        sets = [Counter(getattr(x, key) for x in r) for r in runs]
        assert sets[0] == sets[1] == sets[2]
    assert all(0.0 <= r[-1].due < 200.0 for r in runs)
    # a horizon that ends in an on-period holds a part of its arrivals
    slack = m["on_rate"] * m["on_s"] if m["kind"] == "onoff" else 0.5
    assert abs(len(runs[0]) - traffic_gen.mean_rate(m) * 200.0) <= slack


@pytest.mark.parametrize("name", ["chat", "longctx", "bursty"])
def test_every_segment_holds_the_same_work_for_every_seed(name):
    """A run's schedule is cut at the ramp and the window: each segment
    holds the same arrivals and lengths for every seed, in another
    order."""
    m = mix(name)
    cuts = [m["ramp_s"], 40.0, 60.0]
    edges = np.cumsum([0.0] + cuts)
    runs = [traffic_gen.schedule(m, s, cuts) for s in (1, 2, SEED)]
    for a, b in zip(edges, edges[1:]):
        seg = [[x for x in r if a <= x.due < b] for r in runs]
        for key in ("prompt", "output"):
            sets = [Counter(getattr(x, key) for x in s) for s in seg]
            assert sets[0] == sets[1] == sets[2]
        gaps = [sorted(np.round(np.diff([x.due for x in s]), 9))
                for s in seg]
        assert len(seg[0]) >= 8
        if m["kind"] == "poisson":
            # the same gaps but the last, which leads into the next
            # segment
            assert len({len(g) for g in gaps}) == 1
    assert runs[0] != runs[1]


def test_stratified_lengths_cover_the_range_evenly():
    m = dict(BENCH.traffic("chat"), rate=10.0)
    out = np.array([x.output for x in traffic_gen.schedule(m, 5, 100.0)])
    assert out.min() == 100 and out.max() == 350
    assert out.mean() == pytest.approx(225.0, abs=0.5)


def test_onoff_mean_rate_and_quiet_periods():
    m = BURSTY
    assert traffic_gen.mean_rate(m) == pytest.approx(
        m["on_rate"] * m["on_s"] / (m["on_s"] + m["off_s"]))
    horizon = 600.0
    arr = traffic_gen.schedule(m, SEED, horizon)
    period = m["on_s"] + m["off_s"]
    phase = np.array([x.due % period for x in arr])
    assert np.all(phase < m["on_s"] + 1e-9)
    assert len(arr) / arr[-1].due == pytest.approx(
        traffic_gen.mean_rate(m), rel=0.02)


def test_poisson_mean_rate():
    m = BENCH.traffic("chat")
    arr = traffic_gen.schedule(m, SEED, 500.0)
    assert len(arr) / arr[-1].due == pytest.approx(m["rate"], rel=0.02)

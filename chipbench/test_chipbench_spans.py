"""``chipbench/spans.py``: the split of the device's idle time by program
span, by hand on a made-up extraction and on a trace recorded on a TPU
v5e (``testdata/program_spans_trace.json``); each in-program reader on a
made-up run; and the whole tool on the CPU at the rehearsal's tiny
cell."""
import io
import json
import re
import time
from contextlib import redirect_stderr
from pathlib import Path

import jax
import pytest

from chipbench import spans, spec, xtrace
from chipbench.harness import Run
from chipbench.serve_loop import SPANS, Req
from chipbench.test_chipbench_rehearsal import (SEED, cpu_chip,  # noqa: F401
                                                root)
from repro.energy import TPU_V5E

ROOT = Path(__file__).resolve().parents[1]
TRACE = Path(__file__).resolve().parent / "testdata" / \
    "program_spans_trace.json"


def test_program_spans_are_the_programs_and_not_the_clients():
    found = set()
    for path in (ROOT / "src" / "repro").rglob("*.py"):
        found |= set(re.findall(r'tracing\.span\("([^"]+)"\)',
                                path.read_text()))
    assert found == set(spans.PROGRAM_SPANS)
    assert not found & set(SPANS)


def made_up():
    """One iteration with nested spans, then time under no span."""
    return {
        "devices": [{"name": "/device:TPU:0",
                     "modules": [("jit_decode_step(1)", 10, 85)],
                     "ops": [("a", 12, 8), ("b", 40, 50), ("c", 96, 2)]}],
        "host": [(xtrace.WINDOW, 0, 120),
                 ("engine.iteration", 0, 100), ("sched.plan", 2, 8),
                 ("device.decode", 10, 85), ("device.decode.inputs", 12, 8),
                 ("device.decode.launch", 20, 5),
                 ("device.decode.wait", 25, 65),
                 ("sched.complete", 95, 4), ("agft.decide", 105, 5)],
    }


def test_innermost_partitions_the_window():
    ex = made_up()
    parts = spans.innermost([(n, s, s + d) for n, s, d in ex["host"][1:]],
                            0, 120)
    assert parts == [(0, 2, "engine.iteration"), (2, 10, "sched.plan"),
                     (10, 12, "device.decode"),
                     (12, 20, "device.decode.inputs"),
                     (20, 25, "device.decode.launch"),
                     (25, 90, "device.decode.wait"),
                     (90, 95, "device.decode"), (95, 99, "sched.complete"),
                     (99, 100, "engine.iteration"), (100, 105, "outside"),
                     (105, 110, "agft.decide"), (110, 120, "outside")]


def test_idle_split_by_hand():
    idle = spans.idle_by_span(made_up())
    # busy [12, 20], [40, 90], [96, 98] of a 120 ns window
    want = {"engine.iteration": 3, "sched.plan": 8, "device.decode": 7,
            "device.decode.launch": 5, "device.decode.wait": 15,
            "sched.complete": 2, "outside": 15, "agft.decide": 5}
    assert idle == pytest.approx({k: v * 1e-9 for k, v in want.items()})
    r = xtrace.reduce(made_up())
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    buckets = {}
    for k, v in idle.items():
        buckets[spans.bucket(k)] = buckets.get(spans.bucket(k), 0) + v
    assert buckets == pytest.approx({"wait": 15e-9, "launch": 12e-9,
                                     "host": 18e-9, "outside": 15e-9})


def early(ex, ns):
    """``ex`` with the chip's clock ``ns`` behind the host's."""
    for dev in ex["devices"]:
        for key in ("modules", "ops"):
            dev[key] = [(n, s - ns, d) for n, s, d in dev[key]]
    return ex


def test_clock_offset_puts_each_program_inside_its_call():
    ex = made_up()
    # the one program fills its call: no other shift fits
    assert spans.clock_offset(ex["devices"][0], ex["host"]) == (0, 0, 1.0)
    ex = early(made_up(), 3)
    assert spans.clock_offset(ex["devices"][0], ex["host"]) == (3, 3, 1.0)
    assert spans.idle_by_span(ex) == pytest.approx(
        spans.idle_by_span(made_up()))
    # calls of two kinds, the first program's call before the profile:
    # the decode call fits shifts 4-5, the prefill call 3-4
    dev = {"modules": [("jit_decode_step(1)", -60, 40),
                       ("jit_decode_step(1)", 6, 84),
                       ("jit__lambda(2)", 97, 49)], "ops": []}
    host = [("device.decode", 10, 85), ("device.prefill", 100, 50)]
    assert spans.clock_offset(dev, host) == (4, 4, pytest.approx(2 / 3))
    # a program longer than its call fits no shift: one of three fits
    dev["modules"][1] = ("jit_decode_step(1)", 6, 90)
    assert spans.clock_offset(dev, host) is None
    assert spans.idle_by_span({"devices": [dev], "host": [
        (xtrace.WINDOW, 0, 150)] + host}) is None
    # of two stretches that fit as many programs, the one nearest to none
    dev = {"modules": [("jit_decode_step(1)", 0, 10),
                       ("jit_decode_step(1)", 100, 10)], "ops": []}
    host = [("device.decode", 5, 12), ("device.decode", 90, 12)]
    assert spans.clock_offset(dev, host) == (5, 7, 0.5)


def test_recorded_tpu_trace():
    ex = json.loads(TRACE.read_text())
    r = xtrace.reduce(ex)
    assert r["window_s"] == pytest.approx(0.1)
    # every one of the three decode programs fits its call at one shift
    lo, hi, share = spans.clock_offset(ex["devices"][0], ex["host"])
    assert -2e6 < lo < hi < 2e6 and share == 1.0
    for at in (0, 0.5, 1):
        idle = spans.idle_by_span(ex, at)
        assert set(idle) <= set(spans.PROGRAM_SPANS) | {spans.OUTSIDE}
        # the chip's record starts with the profile: moved by a shift, the
        # window's idle time changes by that shift at most
        shift = spans.shifts(ex, at)[0] * 1e-9
        assert abs(sum(idle.values()) - (r["window_s"] - r["busy_s"])) \
            <= abs(shift) + 1e-12
        buckets = {}
        for k, v in idle.items():
            buckets[spans.bucket(k)] = buckets.get(spans.bucket(k), 0) + v
        # the host's work between two calls leaves the chip idle as well
        assert buckets["host"] > 0 and buckets["launch"] > 0
        assert buckets["wait"] > buckets["host"]
    stalls = spans.traced_stalls(ex)
    assert [s[2] for s in stalls] == ["device.decode.wait"] * 2
    assert all(40 < s[4] < s[1] < 44 for s in stalls)   # busy < long


def test_readers_by_hand():
    shape = spec.shape(spec.Bench(ROOT).config("starcoder2-7b"))
    reqs = [Req(due=10.0 + i, output_len=8, request=type(
        "R", (), {"request_id": i})()) for i in range(3)]
    run = Run(shape=shape, batch=32, device_kind="TPU v5 lite",
              window=(10.0, 14.0), reqs=reqs, steps=[], execs=[],
              policy_calls=[], setup_s=1.0)
    its = [("engine.iteration", t, t + 0.035, None)
           for t in (10.0, 10.05, 10.09)]
    prog = spans.Program(
        spans=its + [("device.decode", 10.001, 10.031, 0),
                     ("device.prefill", 10.051, 10.061, 1),
                     ("device.decode", 10.062, 10.082, 1),
                     ("agft.decide", 10.2, 10.2004, None),
                     ("agft.decide", 20.0, 21.0, None)],
        requests=[("request.queued", 0, 10.0, 10.0),
                  ("request.queued", 1, 11.0, 11.1),
                  ("request.queued", 7, 11.0, 19.0),   # not due in window
                  ("request.prefill", 0, 10.0, 10.3),
                  ("request.prefill", 1, 11.1, 11.2),
                  ("request.prefill", 2, 12.0, 12.5)],
        window_counters={spans.PLANNED: 1000, spans.COMPUTED: 250},
        idle={"device.decode.wait": 0.006, "device.prefill.wait": 0.003,
              "device.decode.launch": 0.0015, "device.decode": 0.0015,
              "sched.plan": 0.0003, "outside": 0.1},
        traced_iterations=3)
    read = {k: f(run, prog) for k, f in spans.READERS.items()}
    assert read["idle_wait_ms_per_iter"] == pytest.approx(3.0)
    assert read["idle_launch_ms_per_iter"] == pytest.approx(1.0)
    assert read["idle_host_ms_per_iter"] == pytest.approx(0.1)
    # 35 ms iterations less 30, 30 and 0 ms of device calls
    assert read["engine_host_ms_per_iter"] == pytest.approx(
        (5 + 5 + 35) / 3)
    assert read["agft_ms_per_decision"] == pytest.approx(0.4)
    # queued 0 and 100 ms; prefill 300, 100 and 500 ms: linear p90
    assert read["sched_wait_ms_p90"] == pytest.approx(90.0)
    assert read["prefill_wait_ms_p90"] == pytest.approx(460.0)
    assert read["prefill_computed_share"] == pytest.approx(25.0)
    # untraced, nothing planned: nothing to read
    empty = spans.Program(spans=[], requests=[], window_counters={})
    assert all(f(run, empty) is None for f in spans.READERS.values())
    # iterations from one start to the next: 50 ms, 30 of them in the
    # decode call; 40 ms, 20 in it
    assert spans.stalls(run, prog) == [[0.0, 50.0, "device.decode", 30.0],
                                       [0.05, 40.0, "device.decode", 20.0]]


def test_tool_on_the_cpu(root):
    bench = spec.Bench(root)
    device = {"platform": "cpu", "kind": jax.devices()[0].device_kind,
              "count": 1}
    err = io.StringIO()
    with redirect_stderr(err):
        out = spans.run_cell(bench, "tiny.chat", SEED, 1.5, False,
                             time.perf_counter(), device, TPU_V5E)
    m = out["metrics"]
    assert set(spans.READERS) <= set(m)
    assert m["requests_due"] == round(30.0 * 1.5)
    assert m["tpot_p50_ms"] > 0
    assert m["engine_host_ms_per_iter"] > 0
    assert m["agft_ms_per_decision"] > 0
    assert m["sched_wait_ms_p90"] >= 0
    assert m["prefill_wait_ms_p90"] > 0
    assert 0 < m["prefill_computed_share"] <= 100
    assert m["idle_wait_ms_per_iter"] is None       # untraced
    lines = {ln.split(" ", 1)[0]: json.loads(ln.split(" ", 1)[1])
             for ln in err.getvalue().splitlines()
             if ln.startswith(("stalls ", "window "))}
    assert len(lines["stalls"]) == 5
    assert all(s[2] in spans.PROGRAM_SPANS + (spans.OUTSIDE,)
               for s in lines["stalls"])

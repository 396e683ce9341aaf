"""The reduction from a profiler trace to device metrics: by hand on a
made-up extraction, and on a trace recorded on a TPU v5e: the tiny
decoder served by the client, as ``xtrace.extract`` read it, cut to the
first 0.1 s of its traced window with operation names shortened
(``testdata/tiny_trace.json``)."""
import json
from pathlib import Path

import pytest

from chipbench import xtrace
from chipbench.serve_loop import SPANS

TRACE = Path(__file__).resolve().parent / "testdata" / "tiny_trace.json"


def made_up():
    return {
        "devices": [{
            "name": "/device:TPU:0",
            "modules": [("jit_decode_step(1)", 0, 100),
                        ("jit__lambda_(2)", 150, 50)],
            "ops": [("fusion.1", 0, 60), ("fusion.2", 50, 40),
                    ("dot.3", 150, 50), ("late", 290, 40)]}],
        "host": [(xtrace.WINDOW, 0, 300), ("engine.step", 0, 210),
                 ("backend.execute", 5, 200),
                 ("wait_for_request", 220, 60)],
    }


def test_reduction_by_hand():
    r = xtrace.reduce(made_up())
    assert r["window_s"] == pytest.approx(300e-9)
    # [0, 90] and [150, 200] and [290, 300] (clipped at the window)
    assert r["busy_s"] == pytest.approx(90e-9 + 50e-9 + 10e-9)
    assert r["programs"]["jit_decode_step"] == pytest.approx(
        {"device_s": 100e-9, "calls": 1})
    assert r["programs"]["jit__lambda_"]["device_s"] == pytest.approx(
        50e-9)
    ops = dict(r["device_ops"])
    assert ops["jit_decode_step/fusion.1"] == pytest.approx(60e-9)
    assert ops["jit__lambda_/dot.3"] == pytest.approx(50e-9)
    # gap [90, 150] inside backend.execute, [200, 290] split: its middle
    # (245) is in wait_for_request
    gaps = dict(r["idle_gaps"])
    assert gaps["backend.execute"] == pytest.approx(60e-9)
    assert gaps["wait_for_request"] == pytest.approx(90e-9)


def test_two_chips_are_averaged():
    ex = made_up()
    other = dict(ex["devices"][0], name="/device:TPU:1", ops=[])
    ex["devices"].append(other)
    r = xtrace.reduce(ex)
    assert r["busy_s"] == pytest.approx(150e-9 / 2)


def test_no_window_or_no_device_work_is_an_error():
    ex = made_up()
    ex["host"] = ex["host"][1:]
    with pytest.raises(ValueError):
        xtrace.reduce(ex)
    ex = made_up()
    ex["devices"][0]["ops"] = []
    with pytest.raises(ValueError):
        xtrace.reduce(ex)


def test_recorded_tpu_trace():
    ex = json.loads(TRACE.read_text())
    assert [d["name"] for d in ex["devices"]] == ["/device:TPU:0"]
    r = xtrace.reduce(ex)
    assert r["window_s"] == pytest.approx(0.1)
    assert 0 < r["busy_s"] < r["window_s"]
    progs = r["programs"]
    assert progs["jit_decode_step"]["calls"] == 52
    assert progs["jit__lambda"]["calls"] == 4
    # every operation runs inside a program, which may idle between them
    assert r["busy_s"] <= sum(p["device_s"] for p in progs.values()) \
        <= r["window_s"]
    named = dict(r["idle_gaps"])
    assert set(named) <= set(SPANS) | {"none"}
    assert sum(named.values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert named["backend.execute"] > named["engine.step"]
    assert len(r["device_ops"]) == 10
    assert all(n.startswith(("jit_decode_step/%", "jit__lambda/%"))
               for n, _ in r["device_ops"])


def test_extract_reads_the_client_spans(tmp_path):
    """On the CPU the trace holds no TPU plane, so a traced run there has
    no device work to reduce; the client's spans are read all the same."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(xtrace.WINDOW):
        with jax.profiler.TraceAnnotation("engine.step"):
            jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    ex = xtrace.extract(str(path), SPANS)
    assert ex["devices"] == []
    assert {n for n, _, _ in ex["host"]} == {xtrace.WINDOW, "engine.step"}
    with pytest.raises(ValueError, match="no device operation"):
        xtrace.reduce(ex)

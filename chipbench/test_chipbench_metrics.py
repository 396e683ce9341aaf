"""The metric readers' arithmetic on a hand-made run."""
import json
import shutil
from pathlib import Path

import pytest

from chipbench import roofline, spec
from chipbench.harness import Run
from chipbench.serve_loop import ExecCall, PolicyCall, Req, Step

ROOT = Path(__file__).resolve().parents[1]
BENCH = spec.Bench(ROOT)


def read(name, run):
    return BENCH.reader(name)(run)


def req(due, first, last, tokens, submit=None, scheduled=None):
    r = Req(due=due, output_len=tokens, request=None,
            submit=due if submit is None else submit, scheduled=scheduled)
    r.first_token, r.last_token, r.tokens = first, last, tokens
    return r


@pytest.fixture
def run():
    conf = BENCH.config("starcoder2-7b")
    reqs = [req(10.0, 10.2, 12.2, 101, submit=10.001, scheduled=10.05),
            req(11.0, 11.5, 15.5, 101, submit=11.003, scheduled=11.3),
            req(12.0, 12.1, 12.7, 4)]      # too few gaps for a TPOT
    steps = [Step(10.0 + 0.04 * i, 10.0 + 0.04 * i + 0.035, 32)
             for i in range(100)]
    execs = [ExecCall(10.0 + 0.04 * i + 0.001, 10.0 + 0.04 * i + 0.034,
                      [1000] * 16, 64 if i % 4 == 0 else 0)
             for i in range(100)]
    calls = [PolicyCall(10.5, 0.002, True), PolicyCall(10.6, 0.0001, False),
             PolicyCall(11.0, 0.004, True), PolicyCall(99.0, 1.0, True)]
    trace = {"window_s": 2.0, "busy_s": 1.8,
             "programs": {"jit_decode_step": {"device_s": 1.6,
                                              "calls": 50},
                          "jit__lambda_": {"device_s": 0.2, "calls": 13}}}
    return Run(shape=spec.shape(conf), batch=32,
               device_kind="TPU v5 lite", window=(10.0, 14.0), reqs=reqs,
               steps=steps, execs=execs, policy_calls=calls, setup_s=31.5,
               traced=(10.0, 12.0), trace=trace, prefill_max=64)


def test_end_to_end(run):
    assert read("setup_s", run) == 31.5
    # TTFTs 200, 500, 100 ms: linear 90th percentile
    assert read("ttft_p90_ms", run) == pytest.approx(440.0)
    # TPOTs 20 ms and 40 ms; the 4-token request has too few gaps
    assert read("tpot_p50_ms", run) == pytest.approx(30.0)
    assert read("tpot_p90_ms", run) == pytest.approx(38.0)


def test_host_layers(run):
    assert read("gen_lag_ms_p99", run) == pytest.approx(
        2.96, rel=1e-6)   # lags 0, 1, 3 ms: 1 + 0.98 * 2
    # waits 50 and 300 ms; the third request was never scheduled
    assert read("queue_wait_ms_p90", run) == pytest.approx(
        50 + 0.9 * 250)
    # steps of 35 ms around calls of 33 ms
    assert read("host_ms_per_iter", run) == pytest.approx(2.0)
    # the decided calls in the window: 2 ms and 4 ms
    assert read("controller_ms_per_decision", run) == pytest.approx(3.0)


def test_device_layers(run):
    assert read("device_idle_share", run) == pytest.approx(10.0)
    assert read("decode_call_ms", run) == pytest.approx(32.0)
    assert read("prefill_call_ms", run) == pytest.approx(200 / 13)
    flops, nbytes = roofline.decode_work(run.shape, [1000] * 16)
    least = nbytes / 819e9
    assert read("decode_roofline", run) == pytest.approx(
        100 * least / 0.032)
    traced = [e for e in run.execs if e.end <= 12.0]
    total = len(traced) * flops + sum(
        1 for e in traced if e.prefill_tokens) * roofline.prefill_flops(
            run.shape, 64)
    assert read("mfu", run) == pytest.approx(100 * total / (2.0 * 197e12))


def test_throughput_cell(run):
    # 100 steps of 32 tokens in a window of 4 s
    assert read("output_tokens_per_s", run) == pytest.approx(800.0)
    # every decode call served 16 of 32 rows
    assert read("batch_occupancy", run) == pytest.approx(50.0)
    assert read("mfu.overload", run) == read("mfu", run)
    assert read("decode_roofline.overload", run) == read("decode_roofline",
                                                         run)


@pytest.mark.parametrize("split, whole", [
    ("tpot_p90_ms.chat", "tpot_p90_ms"),
    ("ttft_p90_ms.longctx", "ttft_p90_ms"),
    ("ttft_p90_ms.bursty", "ttft_p90_ms"),
])
def test_a_split_metric_reads_as_the_whole(run, split, whole):
    """A quantity reported per layer in some cells and end to end in
    others reads the same under both names."""
    assert read(split, run) == read(whole, run) is not None


def test_nothing_to_read_gives_nothing(run):
    run.trace["programs"] = {}
    run.execs = []
    run.reqs = []
    for name in ("decode_call_ms", "prefill_call_ms", "decode_roofline",
                 "tpot_p50_ms", "host_ms_per_iter", "batch_occupancy"):
        assert read(name, run) is None, name


def test_a_per_layer_metric_added_as_a_file(tmp_path, run):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "chipbench/metrics/steps_in_window.py").write_text(
        "def read(run):\n    return float(len(run.steps))\n")
    s = json.loads((ROOT / "BENCHMARK.json").read_text())
    s["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "engine and scheduler",
                           "moves": "tpot_p50_ms",
                           "workloads": ["starcoder2-7b.chat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))
    bench = spec.Bench(tmp_path)
    names = [m["name"] for m in bench.metrics_for("starcoder2-7b.chat",
                                                  "per_layer")]
    assert names[-1] == "steps_in_window"
    assert "steps_in_window" not in [
        m["name"] for m in bench.metrics_for("phi3-medium-14b.longctx",
                                             "per_layer")]
    assert bench.reader("steps_in_window")(run) == 100.0

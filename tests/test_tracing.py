"""The program's tracer (``repro.tracing``): off it records nothing and
changes no decision; on, the served path through ``JaxBackend`` with AGFT
records one ``engine.iteration`` per iteration with its children, one
queued and one prefill span per request, and the prompt tokens planned and
computed; and a profile's host plane holds each scoped span on a clock
that differs from the in-memory one by a constant offset."""
import json
import os
import sys
from collections import Counter, defaultdict

import pytest

from repro import tracing
from repro.configs import get_config
from repro.core import AGFTConfig, AGFTTuner
from repro.energy import A6000
from repro.serving import EngineConfig, InferenceEngine, JaxBackend
from repro.workloads import PROTOTYPES, generate_requests

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import generate_golden  # noqa: E402

PARENT = {"device.prefill": "engine.iteration",
          "device.prefill.launch": "device.prefill",
          "device.prefill.wait": "device.prefill",
          "device.decode": "engine.iteration",
          "device.decode.inputs": "device.decode",
          "device.decode.launch": "device.decode",
          "device.decode.wait": "device.decode",
          "sched.plan": "engine.iteration",
          "sched.complete": "engine.iteration"}


@pytest.fixture(autouse=True)
def fresh_tracer():
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


@pytest.mark.parametrize("on", [False, True])
def test_goldens_hold_and_off_records_nothing(on):
    if on:
        tracing.enable()
    fresh = generate_golden.render(generate_golden.generate())
    with open(generate_golden.GOLDEN) as f:
        assert fresh == f.read()
    rec = tracing.records()
    if on:
        names = Counter(n for n, *_ in rec["spans"])
        assert names["engine.iteration"] == names["sched.plan"] > 0
        assert names["agft.decide"] > 0
    else:
        assert rec == {"spans": [], "requests": [], "counters": {}}


@pytest.fixture(scope="module")
def served():
    """A reduced tinyllama served through ``JaxBackend`` with AGFT, the
    tracer on for the whole drain."""
    cfg = get_config("tinyllama-1.1b").reduced()
    backend = JaxBackend(cfg, A6000, max_batch=4, cache_len=64)
    eng = InferenceEngine(cfg, EngineConfig(max_num_seqs=4,
                                            max_batched_tokens=256,
                                            prefill_chunk=128),
                          hardware=A6000, backend=backend,
                          initial_frequency=A6000.f_max)
    reqs = generate_requests(PROTOTYPES["normal"], 6, base_rate=50.0, seed=3)
    for r in reqs:
        r.prompt_len = min(r.prompt_len, 40 + 20 * (r.request_id % 3))
        r.output_len = min(r.output_len, 6)
    eng.submit(reqs)
    tuner = AGFTTuner(A6000, AGFTConfig(sampling_period_s=0.05))
    tracing.reset()
    tracing.enable()
    try:
        eng.drain(policy=tuner, max_iters=2000)
    finally:
        tracing.disable()
    rec = tracing.records()
    tracing.reset()
    return eng, reqs, rec


def test_one_iteration_span_per_iteration_with_its_children(served):
    eng, _, rec = served
    spans = rec["spans"]
    names = Counter(n for n, *_ in spans)
    assert names["engine.iteration"] == eng.metrics.c.iterations_total
    assert names["sched.plan"] == names["sched.complete"] \
        == names["engine.iteration"]
    assert names["device.decode"] > 0 and names["device.prefill"] > 0
    assert names["agft.decide"] > 0
    for name, start, end, parent in spans:
        assert start <= end
        if name in ("engine.iteration", "agft.decide"):
            assert parent is None
            continue
        p_name, p_start, p_end, _ = spans[parent]
        assert p_name == PARENT[name]
        assert p_start <= start and end <= p_end
    # every device span of an iteration lies inside it, in program order
    kids = defaultdict(list)
    for name, _, _, parent in spans:
        if parent is not None:
            kids[parent].append(name)
    for i, (name, *_rest) in enumerate(spans):
        if name == "device.decode":
            assert kids[i] == ["device.decode.inputs", "device.decode.launch",
                               "device.decode.wait"]
        if name == "engine.iteration":
            assert kids[i][0] == "sched.plan" and \
                kids[i][-1] == "sched.complete"


def test_one_queued_and_one_prefill_span_per_request(served):
    _, reqs, rec = served
    by_name = defaultdict(Counter)
    for name, key, start, end in rec["requests"]:
        assert end >= start
        by_name[name][key] += 1
    ids = {r.request_id: 1 for r in reqs}
    assert by_name["request.queued"] == ids
    assert by_name["request.prefill"] == ids
    c = rec["counters"]
    assert c["device.prefill_tokens_planned"] == sum(r.prompt_len
                                                     for r in reqs)
    # a chunk of up to 128 planned tokens computes at most 64 of them
    assert 0 < c["device.prefill_tokens_computed"] \
        < c["device.prefill_tokens_planned"]


def test_profile_holds_each_span_at_one_offset(tmp_path):
    """The in-memory spans and the profile's host plane share one clock
    up to a constant offset (the profile stamps from its own start)."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    tracing.enable()
    jax.profiler.start_trace(str(tmp_path))
    for i in range(20):
        with tracing.span("engine.iteration"):
            with tracing.span("device.decode"):
                jnp.ones(64 + i).block_until_ready()
            with tracing.span("sched.complete"):
                json.dumps(list(range(100 * i)))
    jax.profiler.stop_trace()
    tracing.disable()
    mem = defaultdict(list)
    for name, start, end, _ in tracing.records()["spans"]:
        mem[name].append((start, end))
    path = next(tmp_path.glob("**/*.xplane.pb"))
    prof = defaultdict(list)
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in mem:
                        prof[e.name].append((e.start_ns,
                                             e.start_ns + e.duration_ns))
    offsets = []
    for name, spans in mem.items():
        got = sorted(prof[name])
        assert len(got) == len(spans), name
        for (s, e), (ps, pe) in zip(spans, got):
            offsets += [s - ps, e - pe]
    assert max(offsets) - min(offsets) < 50_000

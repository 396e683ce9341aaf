"""Integration: the serving engine driving REAL JAX forward passes (reduced
tinyllama) through the JaxBackend, with AGFT attached — proves the tuner is
backend-agnostic (it only sees metrics + set_frequency)."""
import pytest

from repro import tracing
from repro.configs import get_config
from repro.core import AGFTConfig, AGFTTuner
from repro.energy import A6000
from repro.launch.serve import count_compiles
from repro.serving import EngineConfig, InferenceEngine, JaxBackend
from repro.serving.scheduler import BatchPlan
from repro.workloads import PROTOTYPES, generate_requests


@pytest.fixture(scope="module")
def backend():
    cfg = get_config("tinyllama-1.1b").reduced()
    return JaxBackend(cfg, A6000, max_batch=4, cache_len=64)


def test_engine_with_real_jax_execution(backend):
    cfg = backend.cfg
    eng = InferenceEngine(cfg, EngineConfig(max_num_seqs=4,
                                            max_batched_tokens=256,
                                            prefill_chunk=64),
                          hardware=A6000, backend=backend,
                          initial_frequency=A6000.f_max)
    reqs = generate_requests(PROTOTYPES["normal"], 6, base_rate=50.0, seed=0)
    for r in reqs:
        r.prompt_len = min(r.prompt_len, 48)
        r.output_len = min(r.output_len, 8)
    eng.submit(reqs)
    tuner = AGFTTuner(A6000, AGFTConfig(sampling_period_s=0.2))
    # every program was compiled in the backend's warm-up
    tracing.reset()
    tracing.enable()
    try:
        with count_compiles() as compiles:
            eng.drain(policy=tuner, max_iters=2000)
    finally:
        tracing.disable()
    spans = [n for n, *_ in tracing.records()["spans"]]
    tracing.reset()
    assert compiles == []
    assert backend.compile_s > 0
    assert "device.decode" in spans
    assert len(eng.finished) == 6
    assert eng.metrics.c.energy_joules_total > 0
    assert all(r.generated == r.output_len for r in eng.finished)
    # the tuner must have acted through the same interface as in sim mode
    assert tuner.round >= 0
    assert eng.frequency >= A6000.f_min


def test_decode_beyond_device_batch_is_refused(backend):
    reqs = generate_requests(PROTOTYPES["normal"], backend.max_batch + 1,
                             seed=1)
    with pytest.raises(ValueError, match="device batch"):
        backend.execute(BatchPlan(prefill=[], decode=reqs), A6000.f_max)

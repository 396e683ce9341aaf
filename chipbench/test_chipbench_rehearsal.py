"""A CPU rehearsal of a whole run at a tiny configuration: the client
loop, the check of the timed path and the metric arithmetic, with the
look for a chip skipped. Then the same run with the timed path broken
underneath, which must come out not correct, and the int8 control,
which must fail the configuration's limits.

The tiny cell lives in a temporary checkout together with a configuration,
a traffic mix and an end-to-end metric that the repository does not have,
each added as a new file and a new entry only."""
import io
import json
import shutil
from contextlib import redirect_stdout
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness
from repro.energy import TPU_V5E

ROOT = Path(__file__).resolve().parents[1]
SEED = 2 ** 31 + 11
#: limits of the tiny configuration, from its CPU readings over six
#: seeds (program at most, int8 control at least): decode_logit_rel
#: 0.0100 / 0.0310, cache_write_gap 0.0100 / 0.0286
TINY_LIMITS = {"decode_logit_rel": 0.018, "cache_write_gap": 0.017}

DUMMY_METRIC = '''"""Requests due in the window (a metric added as a file)."""


def read(run):
    return float(len(run.reqs))
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__",
                                                  "testdata"))
    (root / "chipbench/metrics/requests_due.py").write_text(DUMMY_METRIC)
    conf = json.loads(
        (ROOT / "chipbench/configs/phi3-medium-14b.json").read_text())
    conf.update(name="tiny", hidden_size=256, intermediate_size=512,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, vocab_size=1024,
                initializer_range=0.1, check_limits=TINY_LIMITS,
                deployment={"max_batch": 4, "cache_len": 128})
    (root / "chipbench/configs/tiny.json").write_text(json.dumps(conf))
    (root / "chipbench/traffic/tinychat.json").write_text(json.dumps(
        {"kind": "poisson", "rate": 30.0, "prompt": [8, 64],
         "output": [8, 40], "templates": 50, "template_frac": 0.9,
         "ramp_s": 0.5}))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][1], name="tiny",
                                file="chipbench/configs/tiny.json"))
    spec["workloads"].append({"name": "tiny.chat", "config": "tiny",
                              "traffic": "tinychat", "chips": 1,
                              "why": "CPU rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.chat")
    spec["end_to_end"].append({"name": "requests_due", "unit": "requests",
                               "better": "higher", "bound": 0.25,
                               "source": "host_clock",
                               "workloads": ["tiny.chat"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(autouse=True)
def cpu_chip(monkeypatch):
    monkeypatch.setattr(harness, "device_check", lambda chips: (
        {"platform": "cpu", "kind": jax.devices()[0].device_kind,
         "count": 1}, TPU_V5E))
    monkeypatch.setattr(harness, "compile_cache", lambda root: None)


def serve(root, seed=SEED, control=False):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = harness.main(["--workload", "tiny.chat", "--seed", str(seed),
                           "--seconds", "1.5", "--trace", "0"], root=root,
                          control=control)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_rehearsal_run(root):
    res = serve(root)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(m) == {"ttft_p90_ms", "tpot_p50_ms", "tpot_p90_ms",
                      "output_tokens_per_s", "setup_s", "requests_due"}
    assert m["output_tokens_per_s"] > 0
    # the window holds the same number of requests on every seed: its
    # rate times its length, also one due during its last step
    assert m["requests_due"] == res["attempted"] == round(30.0 * 1.5)
    assert 0 < m["tpot_p50_ms"] <= m["tpot_p90_ms"]
    assert m["ttft_p90_ms"] > 0 and m["setup_s"] > 0
    assert res["checks"]["window_compiles"]["value"] == 0
    assert res["checks"]["cache_other_slots_changed"]["value"] == 0
    assert res["device"]["platform"] == "cpu"


def _state_unchanged(real, params, token, cache, pos):
    logits, _ = real(params, token, jax.tree.map(jnp.copy, cache), pos)
    return logits, cache


def _half_batch(real, params, token, cache, pos):
    logits, new = real(params, token, cache, pos)
    h = logits.shape[0] // 2
    mean = jnp.mean(logits[:h], axis=0, keepdims=True)
    return logits.at[h:].set(jnp.broadcast_to(mean, logits[h:].shape)), new


def _token_altered(real, params, token, cache, pos):
    logits, new = real(params, token, cache, pos)
    top = jnp.argmax(logits[0, 0])
    return logits.at[0, 0, top].add(-100.0), new


def _wrong_slot(real, params, token, cache, pos):
    return real(params, token, cache, jnp.maximum(pos - 1, 0))


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered, _wrong_slot])
def test_a_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    build = harness.build_backend

    def broken(*args):
        b = build(*args)
        real = b._decode
        b._decode = lambda *a: fault(real, *a)
        return b

    monkeypatch.setattr(harness, "build_backend", broken)
    res = serve(root)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("seed", [7, 2 ** 33 + 8])
def test_int8_control_fails_the_limits(root, seed):
    res = serve(root, seed, control=True)
    assert res["correct"] is True, res["checks"]
    assert res["control_correct"] is False, res["control_checks"]
    assert list(res)[-1] == "checks"

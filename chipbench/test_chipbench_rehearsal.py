"""A CPU rehearsal of a whole run at a tiny configuration: the client
loop, the check of the timed path and the metric arithmetic, with the
look for a chip skipped. Then the same run with the timed path broken
underneath, which must come out not correct, and the int8 control,
which must fail the configuration's limits.

The tiny cell lives in a temporary checkout together with a configuration,
a traffic mix and an end-to-end metric that the repository does not have,
each added as a new file and a new entry only. So does a second tiny
cell of a block with routed and shared experts, latent attention and a
leading dense layer, with a reference module of its own."""
import io
import json
import shutil
from contextlib import redirect_stdout
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from chipbench import harness
from chipbench.test_chipbench_layout import check_layout
from repro.energy import TPU_V5E

ROOT = Path(__file__).resolve().parents[1]
SEED = 2 ** 31 + 11
#: limits of the tiny configuration, from its CPU readings over six
#: seeds (program at most, int8 control at least): decode_logit_rel
#: 0.0100 / 0.0310, cache_write_gap 0.0100 / 0.0286
TINY_LIMITS = {"decode_logit_rel": 0.018, "cache_write_gap": 0.017}

#: limit of the tiny configuration with experts and latent attention,
#: from its CPU readings over six seeds (program at most 0.0421, a
#: routing flip; the planted latent-cache faults of three seeds at least
#: 0.712). Those faults read decode_logit_rel 0.048 at most, under three
#: times the program's 0.0167, so that number is not compared.
TINYMOE_LIMITS = {"cache_write_gap": 0.2}

TINYMOE = {
    "name": "tinymoe", "arch": "deepseek-v2-lite-16b",
    "reference": "reference_tinymoe", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "first_k_dense_replace": 1, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "n_shared_experts": 1,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "scoring_func": "softmax", "kv_lora_rank": 32, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": None, "vocab_size": 512, "tie_word_embeddings": False,
    "initializer_range": 0.1, "torch_dtype": "bfloat16", "reduced": [],
    "check_limits": TINYMOE_LIMITS,
    "deployment": {"max_batch": 4, "cache_len": 128}}

#: the tiny block's reference: the program's own decode and forward in
#: float32, which checks the harness's plumbing, not the model
TINYMOE_REFERENCE = '''"""The program's decode and forward in float32."""
import jax
import jax.numpy as jnp

from chipbench import spec
from repro.models import build_model


def _model(conf):
    return build_model(spec.model_config(conf).replace(
        dtype="float32", param_dtype="float32"))


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _each(fn, *trees):
    """fn(stacked, *leaves) over the cache's prefix layers and stack."""
    return {part: jax.tree.map(lambda *a: fn(part == "scanned", *a),
                               *[t[part] for t in trees])
            for part in ("prefix", "scanned")}


def decode(weights, conf, tokens, cache, pos, old, control=False):
    if control:
        raise NotImplementedError("the rehearsal plants its faults")
    step = jax.jit(_model(conf).decode_step)
    params = _f32(weights)
    rows = jnp.arange(tokens.shape[1])
    slots = jnp.minimum(jnp.asarray(pos), conf["deployment"]["cache_len"] - 1)
    old = _f32(old)
    state = _f32(cache)
    logits, written = [], []
    for j in reversed(range(tokens.shape[0])):
        s = slots[j]
        # the cache as call j found it
        state = _each(lambda st, c, o: c.at[:, rows, s].set(o[:, j]) if st
                      else c.at[rows, s].set(o[j]), state, old)
        out, new = step(params, jnp.asarray(tokens[j])[:, None], state,
                        jnp.asarray(pos[j]))
        logits.insert(0, out[:, 0])
        written.insert(0, _each(lambda st, c: c[:, rows, s] if st
                                else c[rows, s], new))
    return jnp.stack(logits), _each(
        lambda st, *a: jnp.stack(a, axis=1 if st else 0), *written)


def prefill(weights, conf, tokens, control=False):
    if control:
        raise NotImplementedError("the rehearsal plants its faults")
    logits, _ = jax.jit(_model(conf).forward)(_f32(weights),
                                              jnp.asarray(tokens)[None])
    return logits[0]
'''

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
    (root / "chipbench/configs/tinymoe.json").write_text(
        json.dumps(TINYMOE))
    (root / "chipbench/reference_tinymoe.py").write_text(TINYMOE_REFERENCE)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][1], name="tiny",
                                file="chipbench/configs/tiny.json"))
    spec["configs"].append({
        "name": "tinymoe", "source": "arXiv:2405.04434",
        "file": "chipbench/configs/tinymoe.json", "reduced": [],
        "why": "routed and shared experts, latent attention, one leading "
               "dense layer"})
    for cell in ("tiny", "tinymoe"):
        spec["workloads"].append({"name": f"{cell}.chat", "config": cell,
                                  "traffic": "tinychat", "chips": 1,
                                  "why": "CPU rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] += ["tiny.chat", "tinymoe.chat"]
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


def serve(root, seed=SEED, control=False, cell="tiny.chat"):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = harness.main(["--workload", cell, "--seed", str(seed),
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


def test_the_rehearsal_checkout_follows_the_rules(root):
    """Its tiny block's reference runs the program's decode, which checks
    the plumbing only, so it alone may use the program."""
    check_layout(root, plumbing={"reference_tinymoe"})
    with pytest.raises(AssertionError, match="reference_tinymoe"):
        check_layout(root)


def test_rehearsal_run_of_experts_and_latent_attention(root):
    res = serve(root, cell="tinymoe.chat")
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0
    assert res["checks"]["window_compiles"]["value"] == 0
    assert res["checks"]["cache_other_slots_changed"]["value"] == 0
    assert set(res["checks"]) >= set(TINYMOE_LIMITS)


def _part_unchanged(part, real, params, token, cache, pos):
    before = jax.tree.map(jnp.copy, cache[part])
    logits, new = real(params, token, cache, pos)
    return logits, dict(new, **{part: before})


def _part_wrong_slot(part, real, params, token, cache, pos):
    logits, new = real(params, token, jax.tree.map(jnp.copy, cache), pos)
    _, wrong = real(params, token, cache, jnp.maximum(pos - 1, 0))
    return logits, dict(new, **{part: wrong[part]})


@pytest.mark.parametrize("part", ["prefix", "scanned"])
@pytest.mark.parametrize("fault", [_part_unchanged, _part_wrong_slot])
def test_a_broken_latent_cache_is_not_correct(root, monkeypatch, fault,
                                             part):
    """A fault in the latent cache of the leading dense layer alone, or
    of the stacked layers with experts alone; the logits as served."""
    build = harness.build_backend

    def broken(*args):
        b = build(*args)
        real = b._decode
        b._decode = lambda *a: fault(part, real, *a)
        return b

    monkeypatch.setattr(harness, "build_backend", broken)
    res = serve(root, cell="tinymoe.chat")
    assert res["correct"] is False
    gap = res["checks"]["cache_write_gap"]
    assert gap["value"] > gap["limit"]
    # the leading layer's latent depends on the token and the position
    # alone, and every served token is 0: a write one slot early mostly
    # leaves the value that slot held, so only the stack's must show
    if fault is _part_wrong_slot and part == "scanned":
        assert res["checks"]["cache_other_slots_changed"]["value"] > 0

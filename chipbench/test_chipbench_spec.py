"""A configuration file into the program's ``ModelConfig``: every size by
one table from the published keys, and a key whose value the program
only implies either passed to a field of its name or refused."""
import dataclasses
import json
from pathlib import Path

import pytest

from chipbench import spec
from repro.models.common import ModelConfig

ROOT = Path(__file__).resolve().parents[1]
DEEPSEEK = Path(__file__).resolve().parent / "testdata/deepseek-v2-lite.json"


def deepseek(**changes) -> dict:
    """DeepSeek-V2-Lite's published configuration, with ``changes``."""
    conf = json.loads(DEEPSEEK.read_text())
    conf.update(changes)
    return conf


#: what the program runs today: plain RoPE and renormalised top-k weights
RUNNABLE = {"rope_scaling": None, "norm_topk_prob": True}


def test_deepseek_v2_lite_builds_from_its_file():
    cfg = spec.model_config(deepseek(**RUNNABLE))
    assert (cfg.num_experts, cfg.top_k, cfg.num_shared_experts) == (64, 6, 2)
    assert (cfg.moe_d_ff, cfg.d_ff, cfg.first_k_dense) == (1408, 10944, 1)
    assert cfg.use_mla and cfg.kv_lora_rank == 512
    assert (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (128, 64, 128)
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads) == (27, 2048, 16)
    assert cfg.head_dim == 2048 // 16          # the file gives no head_dim
    assert (cfg.vocab_size, cfg.tie_embeddings) == (102400, False)
    assert cfg.ffn_activation == "swiglu" and cfg.norm_eps == 1e-6


def test_sizes_come_from_the_file_not_the_registry():
    conf = deepseek(**RUNNABLE, num_hidden_layers=9, n_routed_experts=8,
                    head_dim=96)
    cfg = spec.model_config(conf)
    assert (cfg.num_layers, cfg.num_experts, cfg.head_dim) == (9, 8, 96)
    # a file that names no experts and no latent runs neither, whatever
    # the registry's entry for its arch holds
    dense = json.loads(
        (ROOT / "chipbench/configs/starcoder2-7b.json").read_text())
    dense["arch"] = "deepseek-v2-lite-16b"
    cfg = spec.model_config(dense)
    assert cfg.num_experts == 0 and not cfg.use_mla
    assert cfg.ffn_activation == "gelu"


@pytest.mark.parametrize("name", ["starcoder2-7b", "phi3-medium-14b"])
def test_the_dense_files_build_what_they_built_before(name):
    """The dense configurations' ``ModelConfig``, built as before the
    table: the registry's entry with the dense sizes from the file."""
    conf = spec.Bench(ROOT).config(name)
    from repro.configs import get_config
    before = get_config(conf["arch"]).replace(
        num_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["hidden_size"] // conf["num_attention_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        tie_embeddings=conf["tie_word_embeddings"],
        rope_theta=float(conf["rope_theta"]), norm_eps=spec.norm_eps(conf),
        attention_window=spec.attention_window(conf),
        dtype=conf["torch_dtype"], param_dtype=conf["torch_dtype"])
    assert spec.model_config(conf) == before


def program(monkeypatch, *names, **registry):
    """Install a stand-in for the program: a frozen dataclass with
    ``ModelConfig``'s fields less every key of ``spec.IMPLIED``, plus a
    field for each of ``names`` whose default is ``IMPLIED``'s value. Its
    registry entry for an arch is the real one's, with ``registry``'s
    values in those fields."""
    import repro.configs
    real = repro.configs.get_config
    kept = [f for f in dataclasses.fields(ModelConfig)
            if f.name not in spec.IMPLIED]
    stand_in = dataclasses.make_dataclass(
        "Program",
        [(f.name, f.type, dataclasses.field(
            default=f.default, default_factory=f.default_factory))
         for f in kept]
        + [(name, object, dataclasses.field(default=spec.IMPLIED[name]))
           for name in names],
        frozen=True,
        namespace={"replace": lambda self, **kw: dataclasses.replace(
            self, **kw)})
    monkeypatch.setattr(repro.configs, "get_config", lambda arch: stand_in(
        **{f.name: getattr(real(arch), f.name) for f in kept}, **registry))


@pytest.mark.parametrize("key, value", [
    ("rope_scaling", None), ("norm_topk_prob", None),
    ("scoring_func", "sigmoid"), ("q_lora_rank", 1536),
    ("routed_scaling_factor", 2.5), ("use_bias", True),
    ("norm_type", "layer_norm")])
def test_a_value_the_program_cannot_run_is_refused(monkeypatch, key, value):
    """The published YaRN scaling and ``norm_topk_prob: false`` as the
    file states them (``None``), or another value, on a program that has
    no field for the key."""
    program(monkeypatch)
    conf = deepseek(**RUNNABLE)
    conf[key] = deepseek()[key] if value is None else value
    with pytest.raises(ValueError, match=key):
        spec.model_config(conf)


def test_a_field_of_the_keys_name_takes_the_files_value(monkeypatch):
    """A scalar and an object, such as YaRN's ``rope_scaling``, go to the
    field as the file states them."""
    program(monkeypatch, "rope_scaling", "norm_topk_prob")
    cfg = spec.model_config(deepseek(rope_scaling=None))
    assert cfg.norm_topk_prob is False
    assert cfg.rope_scaling is None
    cfg = spec.model_config(deepseek())
    assert cfg.rope_scaling == deepseek()["rope_scaling"]
    assert cfg.rope_scaling["type"] == "yarn"


def test_the_published_deepseek_file_builds_on_a_program_with_the_fields(
        monkeypatch):
    """The unchanged file, YaRN and ``norm_topk_prob: false`` included,
    builds once the program has a field for every implied key, and each
    field holds the file's value (the implied one for ``norm_type`` and
    ``use_bias``, which the file leaves out)."""
    program(monkeypatch, *spec.IMPLIED)
    published = deepseek()
    cfg = spec.model_config(published)
    assert cfg.rope_scaling == published["rope_scaling"]
    assert cfg.norm_topk_prob is False
    for key, implied in spec.IMPLIED.items():
        assert getattr(cfg, key) == published.get(key, implied), key
    assert (cfg.num_experts, cfg.kv_lora_rank) == (64, 512)


def test_moonlight_router_values_reach_fields_of_their_names(monkeypatch):
    """Moonlight-16B-A3B's router (catalog ``Moonlight-16B-A3B``): sigmoid
    scores, ``noaux_tc`` top-k and a scaling of 2.446."""
    router = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
              "routed_scaling_factor": 2.446}
    program(monkeypatch, *router)
    cfg = spec.model_config(deepseek(**RUNNABLE, **router))
    assert {k: getattr(cfg, k) for k in router} == router


@pytest.mark.parametrize("key, registry", [
    ("norm_topk_prob", False),
    ("rope_scaling", deepseek()["rope_scaling"]),
    ("scoring_func", "sigmoid"), ("routed_scaling_factor", 2.446),
    ("q_lora_rank", 1536), ("use_bias", True)])
def test_a_key_the_file_leaves_out_takes_the_implied_value(
        monkeypatch, key, registry):
    """Not the registry entry's value: a file on an arch whose entry runs
    another value runs the implied one, as it says by leaving the key
    out."""
    import repro.configs
    program(monkeypatch, key, **{key: registry})
    conf = deepseek(**RUNNABLE)
    conf.pop(key, None)
    assert getattr(repro.configs.get_config(conf["arch"]), key) == registry
    assert getattr(spec.model_config(conf), key) == spec.IMPLIED[key]

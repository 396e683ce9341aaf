"""A configuration file into the program's ``ModelConfig``: every size by
one table from the published keys, and a key whose value the program
only implies either passed to a field of its name or refused."""
import dataclasses
import json
from pathlib import Path
from typing import Optional

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


@pytest.mark.parametrize("key, value", [
    ("rope_scaling", None), ("norm_topk_prob", None),
    ("scoring_func", "sigmoid"), ("q_lora_rank", 1536),
    ("routed_scaling_factor", 2.5), ("use_bias", True),
    ("norm_type", "layer_norm")])
def test_a_value_the_program_cannot_run_is_refused(key, value):
    """The published YaRN scaling and ``norm_topk_prob: false`` as the
    file states them (``None``), or another value the program has no
    field for."""
    conf = deepseek(**RUNNABLE)
    conf[key] = deepseek()[key] if value is None else value
    with pytest.raises(ValueError, match=key):
        spec.model_config(conf)


@dataclasses.dataclass(frozen=True)
class _WithFields(ModelConfig):
    """A program that has the two fields."""
    rope_scaling: Optional[object] = None
    norm_topk_prob: bool = True


def test_a_field_of_the_keys_name_takes_the_files_value(monkeypatch):
    """A scalar goes to the field as the file states it; an object, such
    as YaRN's ``rope_scaling``, is still refused: its translation into
    the field's type comes with the field."""
    import repro.configs
    base = repro.configs.get_config("deepseek-v2-lite-16b")
    monkeypatch.setattr(repro.configs, "get_config", lambda arch: _WithFields(
        **{f.name: getattr(base, f.name) for f in dataclasses.fields(base)}))
    cfg = spec.model_config(deepseek(rope_scaling=None))
    assert cfg.norm_topk_prob is False
    assert cfg.rope_scaling is None
    with pytest.raises(ValueError, match="rope_scaling"):
        spec.model_config(deepseek())

"""Find a cell's configuration, traffic and metrics by the names in
``BENCHMARK.json``, and turn a configuration file into the program's
``ModelConfig`` and into the shape that the counts and the reference use.

A configuration file holds the published ``config.json`` keys with the
values that are run, plus ``reduced`` (the keys changed from the
source), ``published`` (their source values), ``assumed`` and
``deployment`` (device batch and cache length). A traffic file holds the
parameters of ``chipbench.traffic_gen``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, List

from chipbench.roofline import Shape

#: published activation name -> the program's FFN activation
ACTIVATIONS = {"gelu_pytorch_tanh": "gelu", "silu": "swiglu"}


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Bench:
    """``BENCHMARK.json`` at ``root`` and the files it names."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.spec = load_json(self.root / "BENCHMARK.json")
        self.dir = self.root / self.spec["paths"][0]

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return load_json(self.root / c["file"])
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return load_json(self.dir / "traffic" / f"{name}.json")

    def metrics_for(self, cell: str, kind: str) -> List[dict]:
        """The metrics of ``kind`` ("end_to_end" or "per_layer") that
        ``cell`` reports."""
        return [m for m in self.spec[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str) -> Callable:
        """``read(run)`` of ``chipbench/metrics/<metric>.py``."""
        path = self.dir / "metrics" / f"{metric}.py"
        mod_spec = importlib.util.spec_from_file_location(
            f"chipbench_metric_{metric.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read


def shape(conf: dict) -> Shape:
    return Shape(
        layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        heads=conf["num_attention_heads"],
        kv_heads=conf["num_key_value_heads"],
        head_dim=conf["hidden_size"] // conf["num_attention_heads"],
        d_ff=conf["intermediate_size"], gated=conf["hidden_act"] == "silu",
        vocab=conf["vocab_size"], tied=conf["tie_word_embeddings"])


def norm_eps(conf: dict) -> float:
    return conf.get("rms_norm_eps", conf.get("norm_epsilon"))


def attention_window(conf: dict) -> int:
    """The window the program runs: a published window that no cached
    context can reach changes nothing, so it is run as full attention."""
    w = conf.get("sliding_window") or 0
    return w if 0 < w < conf["deployment"]["cache_len"] else 0


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file. Raises
    where the program cannot run what the file states."""
    from repro.configs import get_config
    base = get_config(conf["arch"])
    act = ACTIVATIONS[conf["hidden_act"]]
    if act != base.ffn_activation:
        raise ValueError(f"{conf['arch']} runs {base.ffn_activation}, "
                         f"the file states {conf['hidden_act']}")
    if conf.get("norm_type", "rms_norm") != "rms_norm":
        raise ValueError("the program's decoder has RMSNorm only")
    if conf.get("use_bias", False) or conf.get("rope_scaling"):
        raise ValueError("the program's decoder has no biases and no "
                         "RoPE scaling")
    s = shape(conf)
    return base.replace(
        num_layers=s.layers, d_model=s.d_model, num_heads=s.heads,
        num_kv_heads=s.kv_heads, head_dim=s.head_dim, d_ff=s.d_ff,
        vocab_size=s.vocab, tie_embeddings=s.tied,
        rope_theta=float(conf["rope_theta"]), norm_eps=norm_eps(conf),
        attention_window=attention_window(conf),
        dtype=conf["torch_dtype"], param_dtype=conf["torch_dtype"])


def check_fits(conf: dict, traffic: dict) -> None:
    """Every request of the mix must fit the configuration's cache."""
    longest = traffic["prompt"][1] + traffic["output"][1]
    if longest > conf["deployment"]["cache_len"]:
        raise ValueError(
            f"the mix's longest request ({longest} tokens) does not fit "
            f"the cache of {conf['deployment']['cache_len']}")

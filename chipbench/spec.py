"""Find a cell's configuration, traffic and metrics by the names in
``BENCHMARK.json``, and turn a configuration file into the program's
``ModelConfig`` and into the shape that the counts and the reference use.

A configuration file holds the published ``config.json`` keys with the
values that are run, plus ``reduced`` (the keys changed from the
source), ``published`` (their source values), ``assumed``,
``deployment`` (device batch and cache length), ``check_limits`` and
optionally ``reference``: the module under the benchmark's directory
that recomputes the configuration's block for ``correct`` (default
``reference``, the dense decoder). A traffic file holds the parameters
of ``chipbench.traffic_gen``.
"""
from __future__ import annotations

import dataclasses
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
        return load_module(self.dir / "metrics" / f"{metric}.py",
                           f"chipbench_metric_{metric}").read

    def reference(self, conf: dict):
        """The reference module that ``conf`` names (``reference``
        unless it names another): ``chipbench/<module>.py``."""
        name = conf.get("reference", "reference")
        return load_module(self.dir / f"{name}.py",
                           f"chipbench_reference_{name}")


def load_module(path: Path, name: str):
    """Import the file at ``path`` as a module called ``name``."""
    mod_spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


#: published ``config.json`` key -> the program's ``ModelConfig`` field.
#: A key that a file leaves out gives the field its class default (no
#: experts, no latent attention), never the program registry's value.
FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "tie_word_embeddings": "tie_embeddings",
    "n_routed_experts": "num_experts",
    "num_experts_per_tok": "top_k",
    "n_shared_experts": "num_shared_experts",
    "moe_intermediate_size": "moe_d_ff",
    "first_k_dense_replace": "first_k_dense",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
}

#: published keys whose value the program implies while it has no field
#: for them: a file stating another value raises. Where ``ModelConfig`` has
#: a field of the key's name, the field takes the file's value as stated,
#: whatever its type (an object such as YaRN's ``rope_scaling`` too: how the
#: program holds it is decided with the field), and the value here where
#: the file leaves the key out, never the program registry's.
IMPLIED = {
    "rope_scaling": None, "norm_topk_prob": True,
    "scoring_func": "softmax", "topk_method": "greedy",
    "routed_scaling_factor": 1, "q_lora_rank": None, "moe_layer_freq": 1,
    "n_group": 1, "topk_group": 1, "norm_type": "rms_norm",
    "use_bias": False, "attention_bias": False,
}


def fields(conf: dict) -> dict:
    """The ``ModelConfig`` fields that a configuration file states, by
    ``FIELDS``; ``head_dim`` is ``hidden_size / num_attention_heads``
    where the file gives none, and latent attention is on where it gives
    a ``kv_lora_rank``."""
    out = {FIELDS[k]: v for k, v in conf.items()
           if k in FIELDS and v is not None}
    out.setdefault("head_dim",
                   conf["hidden_size"] // conf["num_attention_heads"])
    out["rope_theta"] = float(conf["rope_theta"])
    out["use_mla"] = out.get("kv_lora_rank", 0) > 0
    return out


def shape(conf: dict) -> Shape:
    """The sizes that the counts need. A file with experts states how
    many each token is routed to and their width; one with latent
    attention, its widths."""
    f = fields(conf)
    moe = f.get("num_experts", 0) > 0
    mla = f["use_mla"]
    return Shape(
        layers=f["num_layers"], d_model=f["d_model"], heads=f["num_heads"],
        kv_heads=f["num_kv_heads"], head_dim=f["head_dim"], d_ff=f["d_ff"],
        gated=conf["hidden_act"] == "silu", vocab=f["vocab_size"],
        tied=f["tie_embeddings"],
        dense_prefix=f.get("first_k_dense", 0) if moe else 0,
        experts=f.get("num_experts", 0),
        experts_per_token=f["top_k"] if moe else 0,
        shared_experts=f.get("num_shared_experts", 0) if moe else 0,
        expert_ff=f["moe_d_ff"] if moe else 0,
        kv_lora_rank=f["kv_lora_rank"] if mla else 0,
        qk_rope_dim=f["qk_rope_head_dim"] if mla else 0,
        qk_nope_dim=f["qk_nope_head_dim"] if mla else 0,
        v_head_dim=f["v_head_dim"] if mla else 0)


def norm_eps(conf: dict) -> float:
    return conf.get("rms_norm_eps", conf.get("norm_epsilon"))


def attention_window(conf: dict) -> int:
    """The window the program runs: a published window that no cached
    context can reach changes nothing, so it is run as full attention."""
    w = conf.get("sliding_window") or 0
    return w if 0 < w < conf["deployment"]["cache_len"] else 0


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registry's entry for ``arch`` with every field of ``FIELDS`` and every
    field named by an ``IMPLIED`` key taken from the file. Raises where
    the file states what the program cannot run."""
    from repro.configs import get_config
    base = get_config(conf["arch"])
    default = {f.name: f.default for f in dataclasses.fields(base)}
    kw = {field: default[field] for field in FIELDS.values()}
    kw.update(fields(conf))
    for key, implied in IMPLIED.items():
        value = conf.get(key, implied)
        if key in default:
            kw[key] = value
        elif value != implied:
            raise ValueError(f"the program cannot run {key!r} as the file "
                             f"states it ({value!r}): it runs {implied!r}")
    return base.replace(
        **kw, ffn_activation=ACTIVATIONS[conf["hidden_act"]],
        norm_eps=norm_eps(conf), attention_window=attention_window(conf),
        dtype=conf["torch_dtype"], param_dtype=conf["torch_dtype"])


def check_fits(conf: dict, traffic: dict) -> None:
    """Every request of the mix must fit the configuration's cache."""
    longest = traffic["prompt"][1] + traffic["output"][1]
    if longest > conf["deployment"]["cache_len"]:
        raise ValueError(
            f"the mix's longest request ({longest} tokens) does not fit "
            f"the cache of {conf['deployment']['cache_len']}")

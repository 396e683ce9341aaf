"""``BENCHMARK.json`` against the benchmark's contract and its own files:
every name it uses has its file, every per-layer metric moves one
end-to-end metric that its cells report, and the entry refuses to run
without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import spec

ROOT = Path(__file__).resolve().parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def bench(root=ROOT):
    return spec.Bench(root)


def check_layout(root: Path) -> None:
    """Raise AssertionError where ``root``'s benchmark breaks a rule."""
    b = bench(root)
    s = b.spec
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= s["run_seconds"] <= 51
    e2e = {m["name"]: m for m in s["end_to_end"]}
    cells = {w["name"]: w for w in s["workloads"]}
    confs = {c["name"]: c for c in s["configs"]}
    names = list(e2e) + [m["name"] for m in s["per_layer"]]
    assert len(set(names)) == len(names)
    for n in names + list(cells) + list(confs):
        assert NAME.match(n), n
    for m in s["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in s["end_to_end"] + s["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert (b.dir / "metrics" / f"{m['name']}.py").is_file(), m
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in s["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for name, w in cells.items():
        assert w["config"] in confs and w["chips"] in (1, 4)
        conf = b.config(w["config"])
        spec.check_fits(conf, b.traffic(w["traffic"]))
        reported = {m["name"] for m in b.metrics_for(name, "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        assert b.metrics_for(name, "per_layer")
    for entry in list(cells.values()) + list(confs.values()):
        why = entry["why"]
        assert 1 <= len(why) <= 200 and not set(why) & {"\n", "\t"}, why
    pairs = [(w["config"], w["traffic"]) for w in cells.values()]
    assert len(set(pairs)) == len(pairs)
    for c in confs.values():
        assert c["file"].startswith(s["paths"][0] + "/")
        assert b.config(c["name"])["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in cells.values())


def test_benchmark_follows_its_rules():
    check_layout(ROOT)


def test_model_configs_build_at_published_widths():
    for name in ("starcoder2-7b", "phi3-medium-14b"):
        conf = bench().config(name)
        cfg = spec.model_config(conf)
        assert cfg.d_model == conf["hidden_size"]
        assert cfg.num_layers == conf["num_hidden_layers"]
        assert cfg.vocab_size == conf["vocab_size"]
        assert cfg.attention_window == 0   # no context reaches a window


def test_a_missing_file_is_caught(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    check_layout(tmp_path)
    (tmp_path / "chipbench/traffic/longctx.json").unlink()
    with pytest.raises(FileNotFoundError):
        check_layout(tmp_path)


def test_a_metric_moving_an_unreported_metric_is_caught(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    s = json.loads((ROOT / "BENCHMARK.json").read_text())
    ttft = next(m for m in s["end_to_end"] if m["name"] == "ttft_p90_ms")
    ttft["workloads"] = ["phi3-medium-14b.longctx"]
    q = next(m for m in s["per_layer"] if m["name"] == "queue_wait_ms_p90")
    q["workloads"] = ["starcoder2-7b.chat"]    # chat reports no TTFT now
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))
    with pytest.raises(AssertionError):
        check_layout(tmp_path)


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "starcoder2-7b.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_run_refuses_a_platform_that_is_not_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_run_needs_the_program(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    env = {"PYTHONPATH": ""}
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""

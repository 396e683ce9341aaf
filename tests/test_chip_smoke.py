"""CPU rehearsal of ``chip_smoke.py`` and of ``serve --backend jax``: the
smoke run's serve phase in-process at a reduced tinyllama with interpreted
kernels, its refusal to run off a TPU, the backend's refusals, and the
compile-cache location."""
import importlib.util
import os
import types

import jax
import pytest

from repro.configs import get_config
from repro.energy import TPU_V5E
from repro.launch import compile_cache, serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fake_device(monkeypatch, platform, kind):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])


def test_serve_phase_reduced_on_cpu(chip_smoke, monkeypatch):
    monkeypatch.setattr(
        serve, "get_config",
        lambda arch: get_config(arch).reduced().replace(use_pallas=True))
    # interpreted decode attention over the 2048 slots `normal` needs
    # takes most of a second a step on the CPU; 256 slots keep the run
    # short (positions saturate, as the backend allows)
    monkeypatch.setattr(serve, "cache_len_for", lambda *a: 256)
    argv = ["tinyllama-1.1b" if a == chip_smoke.ARCH else a
            for a in chip_smoke.SERVE_ARGV]
    summary = chip_smoke.serve_phase(argv)
    assert summary["finished"] == 8
    assert summary["platform"] == "cpu"
    assert summary["device_count"] == len(jax.devices())
    assert summary["cache_len"] == 256
    assert summary["max_batch"] == serve.JAX_MAX_BATCH
    assert summary["compile_s"] > 0
    assert summary["serve_compiles"] == 0
    assert summary["decode_steps"] > 0


def test_main_refuses_cpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main()
    assert e.value.code not in (0, None)
    assert '"ok": true' not in capsys.readouterr().out


@pytest.mark.parametrize("argv", [
    ["--nodes", "2"],
    ["--faults", "crash"],
    ["--policy-tick-mode", "tick"],
])
def test_serve_jax_refuses_cluster_paths(argv, capsys):
    with pytest.raises(SystemExit) as e:
        serve.run(["--backend", "jax", "--requests", "2", *argv])
    assert e.value.code == 2
    assert "one node" in capsys.readouterr().err


@pytest.mark.parametrize("hardware,kind,message", [
    ("a6000", "TPU v5 lite", "disagrees with the device"),
    ("tpu-v5e", "TPU v9", "no hardware spec"),
    (None, "TPU v9", "no hardware spec"),
])
def test_serve_jax_refuses_unpriced_device(hardware, kind, message,
                                           monkeypatch, capsys):
    _fake_device(monkeypatch, "tpu", kind)
    argv = ["--backend", "jax", "--requests", "2"]
    if hardware:
        argv += ["--hardware", hardware]
    with pytest.raises(SystemExit) as e:
        serve.run(argv)
    assert e.value.code == 2
    assert message in capsys.readouterr().err


@pytest.mark.parametrize("requested,platform,kind,want", [
    ("tpu-v5e", "tpu", "TPU v5 lite", TPU_V5E),
    (None, "tpu", "TPU v5 lite", TPU_V5E),
    ("tpu-v5e", "cpu", "cpu", TPU_V5E),
    (None, "cpu", "cpu", serve.resolve_hardware("a6000")),
])
def test_device_hardware(requested, platform, kind, want):
    assert serve.device_hardware(requested, platform, kind) is want


def test_cache_len_covers_longest_request():
    assert serve.cache_len_for("normal", []) == 2048
    assert serve.cache_len_for("long_generation", []) == 1024
    reqs = [types.SimpleNamespace(prompt_len=3000, output_len=100)]
    assert serve.cache_len_for("azure", reqs) == 4096


def test_compile_cache_honours_env(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", path)]
    assert path == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()

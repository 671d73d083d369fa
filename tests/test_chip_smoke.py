"""``chip_smoke.py``: its CPU rehearsal runs every phase in-process, and
without a TPU (or without the rest of the repository) it fails loudly."""
import importlib.util
import json
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def chip_smoke():
    """The script as a module, with the process's compile-cache setting
    restored afterwards (its main() points the cache at the repository)."""
    from jax.experimental.compilation_cache import compilation_cache

    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # its dataclasses resolve their module
    spec.loader.exec_module(mod)
    old = jax.config.jax_compilation_cache_dir
    yield mod
    jax.config.update("jax_compilation_cache_dir", old)
    compilation_cache.reset_cache()
    sys.modules.pop("chip_smoke", None)


def test_rehearsal_runs_every_phase_on_cpu(chip_smoke, capsys):
    assert chip_smoke.main(["--rehearse"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    last = json.loads(out[-1])
    assert last["ok"] is True
    assert last["device"]["platform"] == jax.devices()[0].platform == "cpu"
    assert last["device"]["count"] == len(jax.devices())
    text = "\n".join(out)
    assert text.count("bit-exact vs xq@wq") == 7  # every DA backend
    assert text.count("fused vs gather") == 6  # 3 page formats x T=1, 16
    assert "0 step compiles inside run()" in text
    assert "device time: not measured" in text


def test_without_tpu_exits_nonzero(chip_smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""  # no result line


def test_compile_cache_defaults_to_repo_dir(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    old = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
        compilation_cache.reset_cache()
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()


def test_compile_cache_env_dir_is_left_to_jax(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    old = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == old  # nothing set in code


def test_alone_without_repository_fails(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout

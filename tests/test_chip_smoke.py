"""``chip_smoke.py`` at smoke size on the CPU, and the compile-cache helper
the entry points share."""
import importlib.util
import json
import os

import jax
import pytest

from repro.launch import perf_env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def chip_smoke(monkeypatch, tmp_path):
    """The script as a module.  With ``JAX_COMPILATION_CACHE_DIR`` set the
    script leaves the (already initialised) cache config alone, so running
    it here changes no process-wide JAX setting."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    cache_dir = jax.config.jax_compilation_cache_dir
    precision = jax.config.jax_default_matmul_precision
    yield mod
    assert jax.config.jax_compilation_cache_dir == cache_dir
    assert jax.config.jax_default_matmul_precision == precision


def test_chip_smoke_smoke_mode_passes(chip_smoke, capsys):
    assert chip_smoke.main(["--smoke", "--seed", "3"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": jax.device_count()}}
    phases = [json.loads(ln).get("phase") for ln in lines[:-1]]
    assert "a_lstm_paper" in phases and "b_mamba2" in phases


def test_chip_smoke_refuses_without_a_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_compile_cache_left_to_jax_when_env_set():
    before = jax.config.jax_compilation_cache_dir
    env = {"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}
    assert perf_env.configure_compile_cache(env=env) is None
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_path():
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_compilation_cache_dir
    try:
        path = perf_env.configure_compile_cache(env={})
        assert path == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert perf_env.configure_compile_cache(env={}) == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()

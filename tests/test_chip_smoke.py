"""chip_smoke.py on the CPU: its serve-and-compare body at a reduced
size, its refusal to report without a TPU, and the compile-cache helper.
"""
import importlib.util
import pathlib

import jax
import pytest

from repro.configs import get_config, reduced_config
from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serve_and_check_reduced(smoke):
    """The whole one-chip body at reduced width: concurrent == solo,
    16 tokens each, mixed steps, no fallback, no worker error."""
    report = smoke.serve_and_check(reduced_config(get_config("qwen2-1.5b")),
                                   log=lambda *a: None)
    assert report["requests"] == 9
    assert report["stats"]["mixed_steps"] > 0
    assert report["stats"]["dense_fallbacks"] == 0
    assert not report["tpu_custom_call"]        # the CPU runs the reference


def test_kernel_checks_reduced(smoke):
    """Interpret-mode Pallas kernels pass the same comparisons the chip
    run makes, at reduced widths."""
    smoke.check_kernels(reduced_config(get_config("qwen2-1.5b")),
                        log=lambda *a: None)


def test_mellum_body_reduced(smoke):
    """The ``--mellum`` body on the benchmark's CPU-sized Mellum2 cell:
    window and full layers, a share of the experts, served tokens held to
    the family's reference."""
    import sys
    sys.path.insert(0, str(ROOT))
    from bench.tests import test_bench_mellum
    report = smoke.serve_mellum_and_check(test_bench_mellum.load(),
                                          log=lambda *a: None)
    assert len(report["gaps"]) == 8 and max(report["gaps"]) <= 0.01
    assert report["stats"]["mixed_steps"] > 0
    assert not report["kernels"]                # the CPU runs the reference


def test_main_fails_without_tpu(smoke, capsys):
    assert jax.default_backend() != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_env_set(monkeypatch, tmp_path):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself; the helper sets no
    other directory."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_env_unset(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

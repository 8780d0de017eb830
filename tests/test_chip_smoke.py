"""CPU checks of ``chip_smoke.py`` and of what it relies on: it refuses a
host without a TPU, its serving phase serves cleanly at a reduced size, and
the compile cache is placed where the entry points say."""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from repro.configs import ParallelConfig, get_config  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402


def test_refuses_without_tpu(capsys, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert "FAIL: JAX finds no TPU" in out
    assert '"ok"' not in out


def test_serving_phase_reduced():
    cfg = get_config("yi-9b").reduced()
    rep = chip_smoke.one_chip(cfg, n_requests=4, on_tpu=False)
    assert rep["finish_reasons"] == ["length"] * 4
    assert [len(t) for t in rep["tokens"]] == [32] * 4
    # kernels are a TPU matter: on the CPU the reference path serves
    assert set(rep["kernels"]) == {"decode", "mixed", "chunk"}


def test_kernels_follow_platform():
    from repro.kernels import ops

    assert ops.interpret()            # CPU backend: interpreted kernels
    ctx = M.ModelCtx.make(get_config("yi-9b").reduced(), ParallelConfig())
    assert ctx.parallel.use_pallas is False
    ctx = M.ModelCtx.make(get_config("yi-9b").reduced(),
                          ParallelConfig(use_pallas=True))
    assert ctx.parallel.use_pallas is True


def test_compile_cache_placement(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.use_compile_cache()
        root = os.path.join(os.path.dirname(__file__), "..")
        assert got == os.path.join(os.path.realpath(root), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

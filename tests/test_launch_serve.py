"""The serving entry point (``repro.launch.serve``) and ``chip_smoke.py`` on
the CPU: the compile-cache location, one reduced serve through ``serve()``,
and the smoke's refusal to run without a TPU."""
import os
import sys

import jax
import numpy as np
import pytest

from repro.launch import serve

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_setting():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_fixed_repo_path(monkeypatch, cache_dir_setting):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = serve.use_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert serve.use_compile_cache() == path  # the same path on every call


def test_compile_cache_follows_environment(monkeypatch, cache_dir_setting, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert serve.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None  # JAX's own setting


def test_serve_reduced_pair_answers_every_request():
    models = ["tinyllama-1.1b", "gemma2-2b"]
    eng, responses = serve.serve(models, requests=2, prompt_len=8, max_new=3,
                                 reduced=True)
    assert sorted(eng.workers) == sorted(models)
    assert sorted(r.model for r in responses) == sorted(models * 2)
    for r in responses:
        assert r.error is None
        assert np.asarray(r.tokens).shape == (3,)
        assert 0 <= np.min(r.tokens) and np.max(r.tokens) < eng.workers[r.model].cfg.vocab_size
        assert r.ttft_s is not None and 0 <= r.ttft_s <= r.latency_s


def test_chip_smoke_refuses_to_run_without_a_tpu(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)
    sys.modules.pop("chip_smoke", None)

"""Launcher CLIs (train/serve) exercised in-process with tiny settings."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.launch import compile_cache, serve, train

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_calls(monkeypatch):
    """train.main turns the persistent compile cache on for its process;
    in-process here, record the call and leave this process's JAX config
    alone."""
    calls = []
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: calls.append(1))
    return calls


@pytest.mark.usefixtures("cache_calls")
class TestTrainCLI:
    def test_fed_mode(self, tmp_path, capsys, cache_calls):
        rc = train.main([
            "--mode", "fed", "--framework", "fedgroup", "--dataset",
            "synthetic", "--rounds", "2", "--k", "6", "--epochs", "2",
            "--groups", "2", "--alpha", "2", "--clients", "20",
            "--out", str(tmp_path)])
        assert rc == 0
        out = capsys.readouterr().out
        assert "max_acc=" in out
        assert os.path.exists(tmp_path / "model.npz")
        assert os.path.exists(tmp_path / "history.json")
        assert cache_calls == [1]

    def test_lm_mode(self, tmp_path, capsys):
        rc = train.main([
            "--mode", "lm", "--arch", "gemma-2b", "--smoke", "--steps", "3",
            "--batch", "2", "--seq", "16", "--out", str(tmp_path)])
        assert rc == 0
        out = capsys.readouterr().out
        assert "loss=" in out
        assert os.path.exists(tmp_path / "state.npz")

    def test_fed_madc_measure(self, capsys):
        rc = train.main([
            "--mode", "fed", "--framework", "fedgroup", "--dataset",
            "synthetic", "--rounds", "1", "--k", "4", "--epochs", "1",
            "--groups", "2", "--alpha", "2", "--clients", "12",
            "--measure", "madc"])
        assert rc == 0


class TestServeCLI:
    def test_dense_decode(self, capsys):
        rc = serve.main(["--arch", "gemma-2b", "--smoke", "--batch", "2",
                         "--prompt-len", "4", "--gen", "4",
                         "--temperature", "0"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "tok/s" in out

    def test_windowed_decode(self, capsys):
        rc = serve.main(["--arch", "glm4-9b", "--smoke", "--batch", "1",
                         "--prompt-len", "4", "--gen", "4", "--window", "8"])
        assert rc == 0

    def test_encoder_only_refuses(self, capsys):
        rc = serve.main(["--arch", "hubert-xlarge", "--smoke"])
        assert rc == 1
        assert "encoder-only" in capsys.readouterr().out


class TestCompileCache:
    def test_env_dir_wins_and_config_is_untouched(self, monkeypatch,
                                                  tmp_path):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_a_fixed_ignored_path_in_the_checkout(self,
                                                            monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            assert compile_cache.enable_compile_cache() == \
                compile_cache.DEFAULT_DIR
            assert jax.config.jax_compilation_cache_dir == \
                compile_cache.DEFAULT_DIR
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        assert compile_cache.DEFAULT_DIR == os.path.join(_REPO, ".jax_cache")
        with open(os.path.join(_REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_entries_land_in_the_env_dir(self, tmp_path):
        cache = tmp_path / "cache"
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(cache),
                   JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                   PYTHONPATH=os.path.join(_REPO, "src"))
        code = ("from repro.launch.compile_cache import enable_compile_cache\n"
                "print(enable_compile_cache())\n"
                "import jax, jax.numpy as jnp\n"
                "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()")
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              cwd=tmp_path, capture_output=True, text=True,
                              timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert proc.stdout.strip() == str(cache)
        assert any(p.name.endswith("-cache") for p in cache.iterdir())

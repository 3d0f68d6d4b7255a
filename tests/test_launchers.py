"""Launcher CLIs run end-to-end (subprocess: fresh jax state per run)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=420):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    return subprocess.run(
        [sys.executable, "-m"] + args,
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO)


@pytest.mark.slow
def test_train_launcher_recsys():
    p = _run(["repro.launch.train", "--arch", "fm", "--steps", "10",
              "--batch", "32"])
    assert p.returncode == 0, p.stderr[-1500:]
    assert "done: fm" in p.stdout


@pytest.mark.slow
def test_train_launcher_lm_with_checkpoint(tmp_path):
    p = _run(["repro.launch.train", "--arch", "granite-20b", "--steps", "6",
              "--batch", "2", "--seq", "32", "--ckpt-dir", str(tmp_path)])
    assert p.returncode == 0, p.stderr[-1500:]
    assert any(f.startswith("ckpt_") for f in os.listdir(tmp_path))


@pytest.mark.slow
def test_serve_launcher():
    p = _run(["repro.launch.serve", "--smoke", "--files", "32",
              "--batch", "4", "--requests", "2"])
    assert p.returncode == 0, p.stderr[-1500:]
    assert "recall 8/8" in p.stdout


@pytest.mark.slow
def test_serve_launcher_sharded():
    p = _run(["repro.launch.serve", "--smoke", "--files", "64",
              "--shards", "2", "--batch", "4", "--requests", "2"])
    assert p.returncode == 0, p.stderr[-1500:]
    assert "2 shards over the 'files' axis" in p.stdout
    assert "recall 8/8" in p.stdout


@pytest.mark.parametrize("env_dir", [None, "/cache/from/env"])
def test_compile_cache_dir(env_dir, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, stands as JAX read it;
    otherwise the cache goes to the git-ignored .jax_cache/ of the
    checkout."""
    import jax

    from repro.launch import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        want = env_dir
    try:
        assert compile_cache.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == (
            prev if env_dir else want)
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()

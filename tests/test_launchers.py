"""End-to-end launcher coverage: train.py and serve.py drive real (reduced)
models through the public CLI in subprocesses."""
import os
import subprocess
import sys

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_module(mod, *args, timeout=900, **extra_env):
    env = dict(os.environ, **extra_env)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-m", mod, *args],
                         capture_output=True, text=True, env=env,
                         timeout=timeout, cwd=REPO)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    return out.stdout + out.stderr


def test_train_cli_runs_and_reports_loss(tmp_path):
    out = run_module("repro.launch.train", "--arch", "qwen3-4b", "--reduce",
                     "--steps", "8", "--batch", "2", "--seq", "32",
                     "--ckpt-dir", str(tmp_path), "--ckpt-every", "4")
    assert "loss" in out and "done:" in out


def test_train_cli_resume(tmp_path):
    run_module("repro.launch.train", "--arch", "qwen3-4b", "--reduce",
               "--steps", "6", "--batch", "2", "--seq", "32",
               "--ckpt-dir", str(tmp_path), "--ckpt-every", "3")
    out = run_module("repro.launch.train", "--arch", "qwen3-4b", "--reduce",
                     "--steps", "9", "--batch", "2", "--seq", "32",
                     "--ckpt-dir", str(tmp_path), "--ckpt-every", "3",
                     "--resume")
    assert "resumed from step" in out


def test_serve_cli_generates(tmp_path):
    out = run_module("repro.launch.serve", "--arch", "qwen3-4b", "--reduce",
                     "--batch", "2", "--prompt-len", "4", "--max-new", "4")
    assert "generated" in out


def test_serve_cli_compile_cache_follows_env(tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is where the CLI's compiles
    land (every compile is cached here: the minimum compile time is 0)."""
    cache = tmp_path / "jax_cache"
    run_module("repro.launch.serve", "--arch", "qwen3-4b", "--reduce",
               "--batch", "1", "--prompt-len", "4", "--max-new", "2",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert cache.is_dir() and any(cache.iterdir())


def test_compile_cache_defaults_to_checkout(monkeypatch):
    """With the variable unset the cache sits at one fixed path inside the
    checkout, whatever the working directory."""
    from repro.launch.compile_cache import use_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.chdir("/")
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_dryrun_cli_single_cell():
    """The dry-run CLI itself (512 host devices) on the smallest cell."""
    out = run_module("repro.launch.dryrun", "--arch", "granite-moe-1b-a400m",
                     "--shape", "decode_32k", "--mesh", "single",
                     timeout=1200)
    assert " ok " in out

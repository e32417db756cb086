"""What every entry point does before it touches a device: place the
compile cache (``utils.compile_cache``) and — for the benchmarks — refuse to
measure on anything but a TPU, in-process, with one structured line."""

import json
import os
import subprocess
import sys

import jax

from opencv_facerecognizer_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cache_dir_env_wins_and_config_is_untouched(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself, the helper
    names no other directory."""
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_the_checkout_from_any_cwd(monkeypatch,
                                                         tmp_path):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.chdir(tmp_path)
        first = compile_cache.enable()
        monkeypatch.chdir("/")
        second = compile_cache.enable()
        assert first == second == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_bench_refuses_a_cpu_backend_with_one_structured_line(tmp_path):
    """bench.py decides in-process: not a TPU -> one JSON line
    (error=backend_unavailable) and rc 3, no subprocess probe, no hang."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                          capture_output=True, text=True, timeout=120,
                          env=env, cwd=REPO)
    assert proc.returncode == 3, proc.stderr[-2000:]
    payload = json.loads(proc.stdout.strip().splitlines()[-1])
    assert payload["error"] == "backend_unavailable"
    assert payload["value"] is None and "cpu" in payload["reason"]

"""Entry-point set-up: the persistent compile cache and the dry-run's
CPU-only environment."""
import os
import pathlib
import subprocess
import sys

import jax

from repro.launch import cache

REPO = pathlib.Path(__file__).resolve().parent.parent


def test_compile_cache_dir(monkeypatch):
    """$JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it
    the cache goes to the one fixed, gitignored path in the checkout."""
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = cache.enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_dryrun_stays_on_cpu_and_keeps_xla_flags():
    """Importing the dry-run pins its process (and so its sweep's children)
    to the CPU and appends its device count to the caller's XLA_FLAGS."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"),
               XLA_FLAGS="--xla_cpu_enable_fast_math=false")
    env.pop("JAX_PLATFORMS", None)
    code = ("import os, repro.launch.dryrun; "
            "print(os.environ['JAX_PLATFORMS']); "
            "print(os.environ['XLA_FLAGS'])")
    out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                         capture_output=True, text=True, timeout=300)
    platforms, flags = out.stdout.split("\n")[:2]
    assert platforms == "cpu"
    assert flags == ("--xla_cpu_enable_fast_math=false "
                     "--xla_force_host_platform_device_count=512")

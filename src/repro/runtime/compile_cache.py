"""Where JAX keeps its persistent compilation cache.

Each entry point (`launch/train.py`, `launch/serve.py`, `chip_smoke.py`)
calls `enable_compile_cache()` once at start-up, before its first
compile. The cache key includes the directory, so the directory must be
the same on every run for a run to reuse an earlier run's programs:

* `JAX_COMPILATION_CACHE_DIR`, when set, wins. JAX already reads that
  variable itself, so the helper sets no directory of its own.
* Otherwise the cache lives at `DEFAULT_DIR`, a fixed directory inside
  the checkout (`<repo>/.jax_cache`, listed in `.gitignore`).
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def cache_dir() -> str:
  """The directory the cache uses: the environment's, else DEFAULT_DIR."""
  return os.environ.get(ENV_VAR) or DEFAULT_DIR


def enable_compile_cache() -> str:
  """Point JAX's persistent compilation cache at `cache_dir()`; returns it."""
  path = cache_dir()
  if not os.environ.get(ENV_VAR):
    jax.config.update("jax_compilation_cache_dir", path)
  return path

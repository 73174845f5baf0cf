"""The persistent compile cache goes where JAX_COMPILATION_CACHE_DIR says,
and otherwise to one fixed directory inside the checkout."""
import os
import pathlib
import subprocess
import sys

from repro.runtime import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


def _enabled_dir(env_value):
  """Run enable_compile_cache() in a fresh interpreter (so this process's
  JAX config is left alone) and return (returned dir, JAX's config dir)."""
  env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
  env.pop(compile_cache.ENV_VAR, None)
  if env_value is not None:
    env[compile_cache.ENV_VAR] = env_value
  code = ("import jax\n"
          "from repro.runtime.compile_cache import enable_compile_cache\n"
          "print(enable_compile_cache())\n"
          "print(jax.config.jax_compilation_cache_dir)\n")
  out = subprocess.run([sys.executable, "-c", code], env=env, check=True,
                       capture_output=True, text=True).stdout.split("\n")
  return out[0], out[1]


def test_env_var_wins(monkeypatch, tmp_path):
  monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
  assert compile_cache.cache_dir() == str(tmp_path)
  assert _enabled_dir(str(tmp_path)) == (str(tmp_path), str(tmp_path))


def test_default_is_one_fixed_path_in_the_checkout(monkeypatch):
  monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
  first = compile_cache.cache_dir()
  assert first == compile_cache.cache_dir() == compile_cache.DEFAULT_DIR
  assert first == str(REPO / ".jax_cache")
  assert _enabled_dir(None) == (first, first)
  assert _enabled_dir(None) == (first, first)


def test_default_dir_is_gitignored():
  ignored = (REPO / ".gitignore").read_text().split()
  assert ".jax_cache/" in ignored

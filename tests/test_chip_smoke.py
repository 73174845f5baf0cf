"""chip_smoke.py, rehearsed on the CPU at a small width.

The script itself only runs on a TPU; these tests drive its phases with
the Pallas kernels in interpret mode, at widths of at least 128 so that
every kernel regime is live, and check that without a TPU it refuses to
run."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

from repro import configs

REPO = pathlib.Path(__file__).resolve().parents[1]


def _load():
  spec = importlib.util.spec_from_file_location("chip_smoke",
                                                REPO / "chip_smoke.py")
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


cs = _load()


def small_cfg():
  """The smoke DS2 widened so every GEMM clears the 128-lane gate."""
  return configs.get_smoke(cs.ARCH).with_(gru_dims=(128, 128, 256),
                                          fc_dim=256, d_model=256)


def test_refuses_to_run_without_a_tpu(capsys):
  assert jax.default_backend() != "tpu"
  assert cs.main([]) != 0
  assert '"ok"' not in capsys.readouterr().out


def test_fails_alone_in_a_directory(tmp_path):
  shutil.copy(REPO / "chip_smoke.py", tmp_path)
  env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
  proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                        env=env, capture_output=True, text=True)
  assert proc.returncode != 0
  assert '"ok"' not in proc.stdout


def test_train_phase():
  out = cs.train_phase(small_cfg(), 0, cs.CompileTimer(), stage1_steps=2,
                       stage2_steps=2)
  assert len(out["stage1"]) == len(out["stage2"]) == 2
  assert out["stage2_params"] < out["stage1_params"]


def test_serve_phase_routes_every_tier_and_matches_jnp():
  out = cs.serve_phase(small_cfg(), 0, cs.CompileTimer(), interpret=True,
                       rank=128)
  assert out["float"]["regimes"]["gru_cell"] > 0
  assert out["float"]["regimes"]["decode_matvec"] > 0
  assert out["lowrank"]["regimes"]["lowrank_gemm"] > 0
  assert set(out["int8"]["regimes"]) == {"int8_gemm"}
  for tier in out.values():
    assert tier["max_abs_logprob_diff"] <= cs.LOGPROB_TOL


def test_serve_tier_fails_on_unrouted_gemm():
  """A tier whose GEMMs land on jnp (here: the float weights served as if
  they were the low-rank tier) is a failure, not a pass."""
  cfg = small_cfg()
  tiers = cs.serve_tiers(cfg, seed=0, rank=128)
  utts = cs.serve_utterances(cfg, seed=0)[:2]
  with pytest.raises(cs.SmokeFailure, match="routed to 'decode_matvec'"):
    cs.serve_tier(cfg, "int8", tiers["float"], utts, interpret=True)


def test_sharded_phase_on_four_virtual_devices():
  code = textwrap.dedent(f"""
      import os
      os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
      import importlib.util, jax
      spec = importlib.util.spec_from_file_location(
          "chip_smoke", {str(REPO / "chip_smoke.py")!r})
      cs = importlib.util.module_from_spec(spec)
      spec.loader.exec_module(cs)
      from repro import configs
      cfg = configs.get_smoke(cs.ARCH).with_(gru_dims=(128, 128, 256),
                                             fc_dim=256, d_model=256)
      runs = cs.sharded_phase(cfg, 0, jax.devices(), cs.CompileTimer(),
                              steps=2)
      assert len(runs["sharded"]) == len(runs["one_chip"]) == 2
      print("sharded ok")
  """)
  env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
  proc = subprocess.run([sys.executable, "-c", code], env=env,
                        capture_output=True, text=True, timeout=600)
  assert proc.returncode == 0, proc.stderr[-3000:]
  assert "sharded ok" in proc.stdout

"""Multi-device tests (subprocess with forced 8-device CPU topology):
sharding rules produce valid shardings, a sharded train step runs and
matches single-device numerics, compressed psum works under shard_map,
and checkpoints reshard elastically (save sharded, load resharded)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_in_subprocess(body: str) -> str:
  code = textwrap.dedent("""
      import os
      os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
      import jax
      import jax.numpy as jnp
      import numpy as np
      assert len(jax.devices()) == 8
  """) + textwrap.dedent(body)
  env = dict(os.environ,
             PYTHONPATH=os.path.join(ROOT, "src"),
             XLA_FLAGS="--xla_force_host_platform_device_count=8")
  out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
  assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
  return out.stdout


def test_sharded_train_step_matches_single_device():
  run_in_subprocess("""
      from repro import configs
      from repro.dist.mesh import make_mesh
      from repro.dist.sharding import make_constraint
      from repro.data.lm import LMDataConfig, batch_at
      from repro.models.api import get_model

      cfg = configs.get_smoke("llama3-8b").with_(vocab_size=64,
                                                 dtype=jnp.float32)
      api = get_model(cfg)
      params = api.init(jax.random.PRNGKey(0), cfg)
      dc = LMDataConfig(vocab_size=64, seq_len=32, global_batch=8)
      batch = {k: jnp.asarray(v) for k, v in batch_at(dc, 0).items()}

      mesh = make_mesh((4, 2), ("data", "model"), devices=jax.devices())
      cs = make_constraint(mesh, cfg, 8)
      with mesh:
          sharded = jax.jit(
              lambda p, b: api.loss_fn(p, b, cfg, cs)[0])(params, batch)
      plain = jax.jit(lambda p, b: api.loss_fn(p, b, cfg)[0])(params, batch)
      np.testing.assert_allclose(float(sharded), float(plain), rtol=2e-4)
      print("sharded loss ok", float(sharded))
  """)


def test_param_shardings_cover_tree():
  run_in_subprocess("""
      from repro import configs
      from repro.dist.mesh import make_mesh
      from repro.dist.sharding import param_shardings
      from repro.models.api import get_model

      mesh = make_mesh((4, 2), ("data", "model"), devices=jax.devices())
      for arch in ["llama3-8b", "deepseek-v2-lite", "zamba2-7b",
                   "xlstm-350m", "deepspeech2-wsj"]:
          cfg = configs.get_smoke(arch)
          sds = configs.param_specs(cfg)
          sh = param_shardings(sds, mesh)
          n = len(jax.tree.leaves(sh))
          m = len(jax.tree.leaves(sds))
          assert n == m, (arch, n, m)
      print("coverage ok")
  """)


def test_compressed_psum_shard_map():
  run_in_subprocess("""
      from functools import partial
      from jax.sharding import PartitionSpec as P
      from repro.dist.mesh import make_mesh
      from repro.optim.compress import compressed_psum

      mesh = make_mesh((8,), ("pod",), devices=jax.devices())
      x = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16) / 7.0
      err0 = jnp.zeros((8, 16), jnp.float32)

      @partial(jax.shard_map, mesh=mesh, in_specs=(P("pod"), P("pod")),
               out_specs=(P("pod"), P("pod")))
      def f(xs, es):
          m, e = compressed_psum(xs[0], "pod", es[0])
          return m[None], e[None]

      mean, err = f(x, err0)
      want = jnp.mean(x, axis=0)
      got = mean[0]
      rel = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
      assert rel < 0.02, rel
      # error feedback: residual equals what quantization dropped
      assert float(jnp.max(jnp.abs(err))) < float(jnp.max(jnp.abs(x))) / 50
      print("compressed psum ok", rel)
  """)


def test_elastic_checkpoint_reshard():
  run_in_subprocess("""
      import tempfile
      from jax.sharding import NamedSharding, PartitionSpec as P
      from repro.checkpoint import CheckpointManager
      from repro.dist.mesh import make_mesh

      d = tempfile.mkdtemp()
      mesh8 = make_mesh((8,), ("data",), devices=jax.devices())
      x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                         NamedSharding(mesh8, P("data", None)))
      mgr = CheckpointManager(d)
      mgr.save(0, {"x": x})

      # reload onto a DIFFERENT topology (4 devices, model axis)
      mesh4 = make_mesh((4,), ("model",), devices=jax.devices()[:4])
      tgt = NamedSharding(mesh4, P(None, "model"))
      restored, _ = mgr.restore({"x": x}, shardings={"x": tgt})
      np.testing.assert_allclose(np.asarray(restored["x"]), np.asarray(x))
      assert restored["x"].sharding == tgt
      print("elastic reshard ok")
  """)


def test_decode_state_shardings_long_context():
  run_in_subprocess("""
      from repro import configs
      from repro.dist.mesh import make_mesh
      from repro.dist.sharding import state_shardings
      from repro.layers.common import SHAPES

      mesh = make_mesh((4, 2), ("data", "model"), devices=jax.devices())
      cfg = configs.get_config("zamba2-7b")
      shape = SHAPES["long_500k"]
      sds = configs.decode_state_specs(cfg, shape)
      sh = state_shardings(sds, mesh, shape)
      flat = jax.tree.leaves(sh)
      # at least the KV caches must shard the 524288-long axis
      specs = [s.spec for s in flat]
      assert any(any(p is not None for p in (spec or ())) for spec in specs)
      print("state shardings ok")
  """)


def test_mini_dryrun_cell():
  """CI-sized dry-run: lower+compile one train cell and one decode cell on
  an 8-device (4, 2) mesh through the real dryrun builders, and check the
  roofline extraction produces sane terms."""
  run_in_subprocess("""
      from repro import configs
      from repro.dist import hlo_cost
      from repro.dist.mesh import make_mesh
      from repro.launch import dryrun
      from repro.layers.common import ShapeConfig

      mesh = make_mesh((4, 2), ("data", "model"), devices=jax.devices())
      cfg = configs.get_smoke("llama3-8b")
      train = ShapeConfig("train_mini", "train", 64, 8)
      fn, args, in_sh, out_sh = dryrun.build_train(cfg, train, mesh, "adamw",
                                                   microbatches=2)
      with mesh:
          compiled = jax.jit(fn, in_shardings=in_sh,
                             out_shardings=out_sh).lower(*args).compile()
      rep = hlo_cost.analyze_module(compiled.as_text(), 8)
      assert rep.flops > 0 and rep.hbm_bytes > 0
      roof = hlo_cost.roofline_from_report(rep)
      assert roof.dominant in ("compute", "memory", "collective")

      decode = ShapeConfig("decode_mini", "decode", 64, 8)
      fn, args, in_sh, out_sh = dryrun.build_decode(cfg, decode, mesh, False)
      with mesh:
          compiled = jax.jit(fn, in_shardings=in_sh,
                             out_shardings=out_sh).lower(*args).compile()
      rep2 = hlo_cost.analyze_module(compiled.as_text(), 8)
      assert rep2.flops >= 0 and rep2.hbm_bytes > 0
      print("mini dryrun ok", roof.dominant)
  """)

"""§Perf hillclimb harness: lower a cell under named optimization variants
and report the three roofline terms side by side.

Each variant is a (description, overrides) pair; overrides mutate the
ModelConfig / step-builder knobs (attention blocking, wedge scheduling,
remat policy, microbatch count, serving parallelism, collective dtype).
The harness records hypothesis -> before -> after rows which EXPERIMENTS.md
§Perf quotes directly.

Usage:
  XLA_FLAGS must NOT be set here — run through launch/dryrun's env:
  PYTHONPATH=src python -m benchmarks.perf_iterate --cell llama3_train
"""
import os
# lowers on 512 virtual CPU devices, never on an attached accelerator
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

import argparse
import json

import jax

from repro import configs
from repro.dist import hlo_cost
from repro.layers.common import SHAPES

jax.config.update("jax_platforms", "cpu")

OUT = os.path.join(os.path.dirname(__file__), "..", "experiments", "perf")


def factored_param_specs(cfg, rank_frac=None, min_dim=512):
  """ShapeDtypeStruct tree with every large GEMM in factored W = UV form:
  rank_frac=None gives the stage-1 full-rank form (paper eq. 3 training);
  rank_frac=0.25 models a stage-2 model truncated at 1/4 rank."""
  from repro.core.factored import FactoredLinear, map_factored_leaves
  sds = configs.param_specs(cfg)
  def f(leaf):
    if leaf.is_factored:
      return leaf
    shape = leaf.w.shape
    m, n = shape[-2], shape[-1]
    if min(m, n) < min_dim:
      return leaf
    r = min(m, n) if rank_frac is None else \
        max(128, int(min(m, n) * rank_frac) // 128 * 128)
    stack = shape[:-2]
    return FactoredLinear(
        w=None,
        u=jax.ShapeDtypeStruct(stack + (m, r), leaf.w.dtype),
        v=jax.ShapeDtypeStruct(stack + (r, n), leaf.w.dtype),
        name=leaf.name, group=leaf.group)
  return map_factored_leaves(f, sds)


def lower_cell(arch, shape_name, mesh, *, cfg_patch=None, optimizer=None,
               microbatches=8, builder_patch=None,
               sharding_overrides=None, rule_overrides=None,
               params_sds_override=None):
  from repro.launch import dryrun
  cfg = configs.get_config(arch)
  if cfg_patch:
    cfg = cfg.with_(**cfg_patch)
  shape = SHAPES[shape_name]
  cfg = dryrun._with_groups(cfg, mesh)
  opt = optimizer or dryrun.pick_optimizer(arch)
  if shape.kind == "train":
    fn, args, in_sh, out_sh = dryrun.build_train(
        cfg, shape, mesh, opt, microbatches=microbatches,
        sharding_overrides=sharding_overrides,
        rule_overrides=rule_overrides,
        params_sds_override=params_sds_override)
  elif shape.kind == "prefill":
    params_sds = configs.param_specs(cfg)
    fsdp = dryrun.needs_fsdp_serving(cfg, params_sds, mesh)
    fn, args, in_sh, out_sh = dryrun.build_prefill(cfg, shape, mesh, fsdp)
  else:
    params_sds = configs.param_specs(cfg)
    fsdp = dryrun.needs_fsdp_serving(cfg, params_sds, mesh)
    if builder_patch == "no_fsdp":
      fsdp = False
    fn, args, in_sh, out_sh = dryrun.build_decode(
        cfg, shape, mesh, fsdp, sharding_overrides=sharding_overrides,
        rule_overrides=rule_overrides,
        params_sds_override=params_sds_override)
  with mesh:
    compiled = jax.jit(fn, in_shardings=in_sh,
                       out_shardings=out_sh).lower(*args).compile()
  import numpy as np
  n_dev = int(np.prod(list(mesh.shape.values())))
  rep = hlo_cost.analyze_module(compiled.as_text(), n_dev)
  mf = dryrun.model_flops(cfg, shape) / n_dev
  roof = hlo_cost.roofline_from_report(rep, model_flops=mf)
  mem = {}
  try:
    ma = compiled.memory_analysis()
    mem = {"temp_gb": getattr(ma, "temp_size_in_bytes", 0) / 1e9,
           "arg_gb": getattr(ma, "argument_size_in_bytes", 0) / 1e9}
  except Exception:
    pass
  return rep, roof, mem


def attention_tile_bytes(rep) -> float:
  """Measured HBM bytes attributable to attention score/probability tiles
  — the traffic the Pallas flash kernel (kernels/flash_attention.py) keeps
  in VMEM scratch. Tiles are identified from the per-shape traffic table:
  rank>=4 f32 tensors with small leading (batch, heads) dims and a tile
  face of >= 128x128 — the (b, h, q, k) score/prob/mask family that only
  exists because the XLA path materializes the online-softmax chain. The
  kernel substitution removes exactly these classes (qkv reads and the
  output write are shared by both paths and stay counted)."""
  total = 0.0
  for shape_str, b in rep.hbm_by_shape.items():
    dims = hlo_cost._first_array_dims(shape_str) or []
    if (len(dims) >= 4 and shape_str.startswith("f32")
        and dims[-1] >= 128 and dims[-2] >= 128
        and dims[0] * dims[1] <= 4096):
      total += b
  return total


def report(tag, rep, roof, mem, extra=""):
  print(f"{tag:34s} compute={roof.compute_s:8.4f}s "
        f"memory={roof.memory_s:8.4f}s coll={roof.collective_s:8.4f}s "
        f"dom={roof.dominant:10s} temp={mem.get('temp_gb', 0):6.2f}GB "
        f"ncoll={rep.n_collectives} {extra}")
  return {"tag": tag, "compute_s": roof.compute_s,
          "memory_s": roof.memory_s, "collective_s": roof.collective_s,
          "dominant": roof.dominant, "useful": roof.useful_flop_fraction,
          "n_collectives": rep.n_collectives, **mem, "extra": extra}


# ---------------------------------------------------------------------------
# CLI: replay the recorded §Perf iterations (EXPERIMENTS.md §Perf).
# ---------------------------------------------------------------------------

def _cell_llama3(results):
  from repro.dist.mesh import make_mesh
  from repro.launch import dryrun
  mesh = dryrun.production_meshes(multi_pod=False)["single"]
  wedge = {"causal_wedge": True, "attn_block_q": 1024, "attn_block_kv": 1024}
  rep, roof, mem = lower_cell("llama3-8b", "train_4k", mesh)
  results.append(report("A0 baseline", rep, roof, mem))
  rep, roof, mem = lower_cell("llama3-8b", "train_4k", mesh,
                              cfg_patch={"causal_wedge": True})
  results.append(report("A2 causal wedge", rep, roof, mem))
  rep, roof, mem = lower_cell("llama3-8b", "train_4k", mesh, cfg_patch=wedge)
  t = attention_tile_bytes(rep)
  results.append(report("A3/A4 wedge+1024 (+flash adj)", rep, roof, mem,
                        extra=f"adj_memory={roof.memory_s - t/819e9:.3f}s"))
  m128 = make_mesh((128, 2), ("data", "model"),
                   devices=jax.devices("cpu")[:256])
  rep, roof, mem = lower_cell("llama3-8b", "train_4k", m128, cfg_patch=wedge)
  t = attention_tile_bytes(rep)
  results.append(report("A7 +mesh(128,2)", rep, roof, mem,
                        extra=f"adj_memory={roof.memory_s - t/819e9:.3f}s"))
  cfg = configs.get_config("llama3-8b").with_(**wedge)
  for tag, frac in (("A8 stage1 full-rank", None),
                    ("A9 stage2 quarter-rank", 0.25)):
    sds = factored_param_specs(cfg, rank_frac=frac)
    rep, roof, mem = lower_cell("llama3-8b", "train_4k", m128,
                                cfg_patch=wedge, params_sds_override=sds)
    t = attention_tile_bytes(rep)
    results.append(report(tag, rep, roof, mem,
                          extra=f"adj_memory={roof.memory_s - t/819e9:.3f}s"))


def _cell_dsv3(results):
  from repro.launch import dryrun
  mesh = dryrun.production_meshes(multi_pod=False)["single"]
  # the 2D-EP serving layout is the shipped default; both states lowerable
  rep, roof, mem = lower_cell("deepseek-v3-671b", "decode_32k", mesh)
  results.append(report("B2 2D-EP default", rep, roof, mem))


def _cell_ds2(results):
  from repro.dist.mesh import make_mesh
  from repro.launch import dryrun
  mesh = dryrun.production_meshes(multi_pod=False)["single"]
  rep, roof, mem = lower_cell("deepspeech2-wsj", "train_4k", mesh)
  results.append(report("C0 baseline TP=16", rep, roof, mem))
  dp = make_mesh((256, 1), ("data", "model"),
                 devices=jax.devices("cpu")[:256])
  rep, roof, mem = lower_cell("deepspeech2-wsj", "train_4k", dp)
  results.append(report("C2 pure-DP (256,1)", rep, roof, mem))


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--cell", default="all",
                  choices=["all", "llama3", "dsv3", "ds2"])
  args = ap.parse_args()
  results = []
  if args.cell in ("all", "llama3"):
    _cell_llama3(results)
  if args.cell in ("all", "dsv3"):
    _cell_dsv3(results)
  if args.cell in ("all", "ds2"):
    _cell_ds2(results)
  os.makedirs(OUT, exist_ok=True)
  with open(os.path.join(OUT, f"replay_{args.cell}.json"), "w") as f:
    json.dump(results, f, indent=1)


if __name__ == "__main__":
  main()

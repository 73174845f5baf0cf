import os
# The dry-run compiles on 512 virtual CPU devices, never on an attached
# accelerator (which another process may need).
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the REAL step program — train_step = fwd + bwd +
optimizer update; prefill = full-sequence forward (last-token logits);
decode = one cached serve step — with production shardings, compiles it
for the 16x16 single-pod mesh and the 2x16x16 multi-pod mesh, prints the
memory/cost analyses, and extracts roofline terms via dist.hlo_cost.

Failures here (sharding mismatch, OOM at compile, unsupported collective)
are bugs in the framework. Results land in experiments/dryrun/*.json and
feed EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
      --shape train_4k --mesh single           # one cell
  PYTHONPATH=src python -m repro.launch.dryrun --all                # 40 cells
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.dist import hlo_cost
from repro.dist.mesh import dp_size, make_mesh, model_size
from repro.dist.sharding import (_path_tokens, batch_shardings,
                                 make_constraint, param_shardings,
                                 state_shardings)
from repro.layers.common import ModelConfig, ShapeConfig
from repro.models import deepspeech
from repro.models.api import get_model
from repro.optim import AdamWConfig, make_optimizer

jax.config.update("jax_platforms", "cpu")

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def production_meshes(multi_pod: Optional[bool] = None) -> dict:
  devs = jax.devices("cpu")
  assert len(devs) >= 512, "dry-run needs the 512-device XLA_FLAGS header"
  meshes = {}
  if multi_pod is not True:
    meshes["single"] = make_mesh((16, 16), ("data", "model"),
                                 devices=devs[:256])
  if multi_pod is not False:
    meshes["multi"] = make_mesh((2, 16, 16), ("pod", "data", "model"),
                                devices=devs[:512])
  return meshes


def pick_optimizer(arch: str) -> str:
  # int8-state Adam is the fit strategy for the 671B config (DESIGN §5)
  return "q_adam" if arch == "deepseek-v3-671b" else "adamw"


def needs_fsdp_serving(cfg: ModelConfig, params_sds: Any, mesh) -> bool:
  """Model-parallel-only weights must fit ~8 GB/chip; else 2D-shard them."""
  total = sum(np.prod(x.shape) * x.dtype.itemsize
              for x in jax.tree.leaves(params_sds))
  return total / model_size(mesh) > 8e9


def _with_groups(cfg: ModelConfig, mesh) -> ModelConfig:
  if cfg.moe is None or cfg.moe.dispatch_groups != 1:
    return cfg          # explicit group choice wins (perf iterations)
  return cfg.with_(moe=dataclasses.replace(
      cfg.moe, dispatch_groups=dp_size(mesh)))


# ---------------------------------------------------------------------------
# Step builders: (fn, example_args_sds, in_shardings, out_shardings)
# ---------------------------------------------------------------------------

def train_param_policy(cfg: ModelConfig, mesh) -> str:
  """'zero1': params live TP-resident P(None, model); the optimizer state
  is 2D-sharded and grads are reduce-scattered once per microbatch — the
  per-layer FSDP weight re-gathering (which multiplies with microbatch
  count) disappears. Chosen whenever the TP-resident params fit (<6 GB per
  chip) — every assigned arch except deepseek-v3-671b, which keeps full
  FSDP with per-layer all-gathers inside the scan body."""
  params_sds = configs.param_specs(cfg)
  total = sum(np.prod(x.shape) * x.dtype.itemsize
              for x in jax.tree.leaves(params_sds))
  return "zero1" if total / model_size(mesh) < 6e9 else "fsdp"


def _apply_overrides(shard_tree, overrides, mesh):
  """Perf-iteration hook: {path-substring: PartitionSpec} overrides."""
  if not overrides:
    return shard_tree
  def f(path, s):
    pstr = "/".join(_path_tokens(path))
    for frag, spec in overrides.items():
      if frag in pstr:
        return jax.sharding.NamedSharding(mesh, spec)
    return s
  return jax.tree_util.tree_map_with_path(
      f, shard_tree, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding))


def build_train(cfg: ModelConfig, shape: ShapeConfig, mesh, optimizer: str,
                microbatches: int = 8, sharding_overrides=None,
                rule_overrides=None, params_sds_override=None):
  api = get_model(cfg)
  cs = make_constraint(mesh, cfg, shape.global_batch,
                       rule_overrides=rule_overrides)
  opt_init, opt_apply = make_optimizer(optimizer)
  adam = AdamWConfig(max_grad_norm=1.0)
  k = microbatches
  while shape.global_batch % (k * dp_size(mesh)) and k > 1:
    k //= 2
  policy = train_param_policy(cfg, mesh)

  params_sds = params_sds_override or configs.param_specs(cfg)
  opt_sds = jax.eval_shape(opt_init, params_sds)
  batch_sds = configs.input_specs(cfg, shape)
  pshard = param_shardings(params_sds, mesh, fsdp=(policy == "fsdp"))
  gshard = param_shardings(params_sds, mesh, fsdp=True)  # 2D grads (ZeRO)
  oshard = param_shardings(opt_sds, mesh, fsdp=True)     # 2D moments
  bshard = batch_shardings(batch_sds, mesh, shape)
  # overrides: bare keys hit params+grads+opt; "grads:<frag>" grads only
  def _split(pref):
    out = {}
    for k, v in (sharding_overrides or {}).items():
      if ":" not in k:
        out[k] = v
      elif k.startswith(pref + ":"):
        out[k.split(":", 1)[1]] = v
    return out
  pshard = _apply_overrides(pshard, _split("params"), mesh)
  gshard = _apply_overrides(gshard, _split("grads"), mesh)
  oshard = _apply_overrides(oshard, _split("opt"), mesh)

  def constrain_grads(g):
    return jax.tree.map(jax.lax.with_sharding_constraint, g, gshard)

  def train_step(params, opt_state, batch):
    def loss_fn(p, mb):
      loss, _ = api.loss_fn(p, mb, cfg, cs)
      return loss
    if k <= 1:
      loss, grads = jax.value_and_grad(loss_fn)(params, batch)
      grads = constrain_grads(grads)
    else:
      # gradient accumulation: per-microbatch activations live 1/k as long;
      # the accumulator is 2D-sharded, so each microbatch's grads arrive
      # via reduce-scatter (ZeRO) rather than all-reduce.
      def slice_mb(x, i):
        m = x.shape[0] // k
        return jax.lax.dynamic_slice_in_dim(x, i * m, m, axis=0)
      def body(carry, i):
        acc_l, acc_g = carry
        mb = jax.tree.map(lambda x: slice_mb(x, i), batch)
        l, g = jax.value_and_grad(loss_fn)(params, mb)
        g = constrain_grads(g)
        acc_g = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                             acc_g, g)
        return (acc_l + l, acc_g), None
      zero = jax.tree.map(
          lambda p, s: jax.lax.with_sharding_constraint(
              jnp.zeros(p.shape, jnp.float32), s), params, gshard)
      (loss, gsum), _ = jax.lax.scan(
          body, (jnp.zeros((), jnp.float32), zero), jnp.arange(k))
      loss = loss / k
      grads = jax.tree.map(lambda g: g / k, gsum)
    params, opt_state, _ = opt_apply(params, grads, opt_state,
                                     jnp.float32(1e-3), adam)
    return params, opt_state, loss

  in_sh = (pshard, oshard, bshard)
  out_sh = (pshard, oshard, jax.sharding.NamedSharding(
      mesh, jax.sharding.PartitionSpec()))
  args = (params_sds, opt_sds, batch_sds)
  return train_step, args, in_sh, out_sh


def build_prefill(cfg: ModelConfig, shape: ShapeConfig, mesh, fsdp: bool):
  api = get_model(cfg)
  cs = make_constraint(mesh, cfg, shape.global_batch)

  if cfg.family == "whisper":
    def prefill(params, batch):
      return api.encode(params, batch["frames"], cfg, cs)
  elif cfg.family == "deepspeech":
    def prefill(params, batch):
      return api.forward(params, batch["feats"], cfg, cs)
  else:
    def prefill(params, batch):
      logits, _ = api.forward(params, batch["tokens"], cfg, cs,
                              last_only=True)
      return logits

  params_sds = configs.param_specs(cfg)
  batch_sds = configs.input_specs(cfg, shape)
  pshard = param_shardings(params_sds, mesh, fsdp=fsdp)
  bshard = batch_shardings(batch_sds, mesh, shape)
  return prefill, (params_sds, batch_sds), (pshard, bshard), None


def build_decode(cfg: ModelConfig, shape: ShapeConfig, mesh, fsdp: bool,
                 sharding_overrides=None, rule_overrides=None,
                 params_sds_override=None):
  api = get_model(cfg)
  cs = make_constraint(mesh, cfg, shape.global_batch, decode=True,
                       rule_overrides=rule_overrides)
  params_sds = params_sds_override or configs.param_specs(cfg)
  batch_sds = configs.input_specs(cfg, shape)
  pshard = param_shardings(params_sds, mesh, fsdp=fsdp, expert_2d=True)
  pshard = _apply_overrides(pshard, sharding_overrides, mesh)
  bshard = batch_shardings(batch_sds, mesh, shape)

  if cfg.family == "deepspeech":
    def step(params, state, batch):
      return deepspeech.decode_step(params, state, batch["x_t"], cfg, cs)
    state_sds = jax.eval_shape(
        lambda: deepspeech.init_decode_state(cfg, shape.global_batch))
  else:
    def step(params, state, batch):
      return api.decode_step(params, state, batch["token"],
                             batch["positions"], cfg, cs)
    state_sds = configs.decode_state_specs(cfg, shape)

  sshard = state_shardings(state_sds, mesh, shape)
  in_sh = (pshard, sshard, bshard)
  out_sh = (None, sshard)
  args = (params_sds, state_sds, batch_sds)
  return step, args, in_sh, out_sh


def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, optimizer: str):
  if shape.kind == "train":
    return build_train(cfg, shape, mesh, optimizer)
  params_sds = configs.param_specs(cfg)
  fsdp = needs_fsdp_serving(cfg, params_sds, mesh)
  if shape.kind == "prefill":
    return build_prefill(cfg, shape, mesh, fsdp)
  return build_decode(cfg, shape, mesh, fsdp)


# ---------------------------------------------------------------------------
# Model-FLOPs estimate (6ND / 2ND with MoE-active correction).
# ---------------------------------------------------------------------------

def param_counts(cfg: ModelConfig) -> tuple[float, float]:
  """(total, active) param counts from the eval_shape tree."""
  sds = configs.param_specs(cfg)
  flat = jax.tree_util.tree_flatten_with_path(sds)[0]
  total = active = 0.0
  for path, leaf in flat:
    n = float(np.prod(leaf.shape))
    toks = [getattr(k, "key", getattr(k, "name", "")) for k in path]
    total += n
    if (cfg.moe and "moe" in "".join(str(t) for t in toks) and
        any(str(t) in ("w_gate", "w_up", "w_down") for t in toks) and
        cfg.moe.num_experts in leaf.shape):
      active += n * cfg.moe.top_k / cfg.moe.num_experts
    else:
      active += n
  return total, active


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
  total, active = param_counts(cfg)
  if shape.kind == "train":
    tokens = shape.global_batch * shape.seq_len
    if cfg.family == "whisper":
      tokens = shape.global_batch * (shape.seq_len + shape.seq_len // 4)
    return 6.0 * active * tokens
  if shape.kind == "prefill":
    return 2.0 * active * shape.global_batch * shape.seq_len
  return 2.0 * active * shape.global_batch          # one token / sequence


# ---------------------------------------------------------------------------
# Runner.
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape: ShapeConfig, mesh_name: str, mesh,
             optimizer: Optional[str] = None, *, save: bool = True,
             verbose: bool = True, cfg_override=None) -> dict:
  cfg = cfg_override or configs.get_config(arch)
  cfg = _with_groups(cfg, mesh)
  opt = optimizer or pick_optimizer(arch)
  t0 = time.time()
  fn, args, in_sh, out_sh = build_cell(cfg, shape, mesh, opt)
  with mesh:
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
    lowered = jitted.lower(*args)
    compiled = lowered.compile()
  compile_s = time.time() - t0

  n_dev = int(np.prod(list(mesh.shape.values())))
  txt = compiled.as_text()
  rep = hlo_cost.analyze_module(txt, n_dev)
  mf = model_flops(cfg, shape) / n_dev        # per-device share
  roof = hlo_cost.roofline_from_report(rep, model_flops=mf)

  mem = {}
  try:
    ma = compiled.memory_analysis()
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
      v = getattr(ma, attr, None)
      if v is not None:
        mem[attr] = int(v)
  except Exception as e:          # backend may not implement it
    mem["error"] = repr(e)
  cost = {}
  try:
    ca = compiled.cost_analysis()
    cost = {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and k in
            ("flops", "bytes accessed", "optimal_seconds")}
  except Exception as e:
    cost["error"] = repr(e)

  result = {
      "arch": arch, "shape": shape.name, "mesh": mesh_name,
      "devices": n_dev, "optimizer": opt if shape.kind == "train" else None,
      "compile_s": round(compile_s, 1),
      "flops": rep.flops, "dot_flops": rep.dot_flops,
      "hbm_bytes": rep.hbm_bytes,
      "collective_bytes": rep.collective_bytes,
      "collective_wire_bytes": rep.collective_wire_bytes,
      "collective_by_kind": rep.collective_by_kind,
      "n_collectives": rep.n_collectives,
      "compute_s": roof.compute_s, "memory_s": roof.memory_s,
      "collective_s": roof.collective_s,
      "dominant": roof.dominant,
      "model_flops_per_dev": mf,
      "useful_flop_fraction": roof.useful_flop_fraction,
      "roofline_fraction": roof.roofline_fraction,
      "memory_analysis": mem, "cost_analysis": cost,
  }
  if verbose:
    print(f"[{arch} x {shape.name} x {mesh_name}] compile {compile_s:.0f}s "
          f"dominant={roof.dominant} compute={roof.compute_s:.4f}s "
          f"memory={roof.memory_s:.4f}s coll={roof.collective_s:.4f}s "
          f"useful={roof.useful_flop_fraction:.2f} "
          f"arg={mem.get('argument_size_in_bytes', 0)/1e9:.2f}GB "
          f"temp={mem.get('temp_size_in_bytes', 0)/1e9:.2f}GB")
  if save:
    os.makedirs(OUT_DIR, exist_ok=True)
    fname = f"{arch}__{shape.name}__{mesh_name}.json"
    with open(os.path.join(OUT_DIR, fname), "w") as f:
      json.dump(result, f, indent=1)
  return result


def main() -> None:
  ap = argparse.ArgumentParser()
  ap.add_argument("--arch", default=None)
  ap.add_argument("--shape", default=None)
  ap.add_argument("--mesh", default=None, choices=[None, "single", "multi"])
  ap.add_argument("--all", action="store_true")
  ap.add_argument("--optimizer", default=None)
  args = ap.parse_args()

  meshes = production_meshes()
  if args.mesh:
    meshes = {args.mesh: meshes[args.mesh]}
  archs = configs.ARCH_NAMES if (args.all or not args.arch) else [args.arch]

  failures = []
  for arch in archs:
    for shape in configs.shapes_for(arch):
      if args.shape and shape.name != args.shape:
        continue
      for mesh_name, mesh in meshes.items():
        try:
          run_cell(arch, shape, mesh_name, mesh, args.optimizer)
        except Exception as e:
          failures.append((arch, shape.name, mesh_name, repr(e)))
          print(f"FAILED [{arch} x {shape.name} x {mesh_name}]: {e}")
          traceback.print_exc()
  if failures:
    print(f"\n{len(failures)} FAILURES:")
    for f in failures:
      print(" ", f)
    raise SystemExit(1)
  print("\nAll dry-run cells compiled successfully.")


if __name__ == "__main__":
  main()

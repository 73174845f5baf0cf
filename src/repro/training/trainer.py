"""Training loop: microbatched grad accumulation, the paper's two-stage
schedule (stage-1 trace-norm training -> truncated-SVD warmstart ->
stage-2 fine-tune), trace-norm diagnostics, checkpoint/restart.

The step function is a single jit containing fwd+bwd (scanned over
microbatches), the regularizer, and the optimizer update — the same
program the dry-run lowers for the production mesh. Stage transitions
re-jit (params change structure: full-rank factored -> truncated).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.core.compress import FactorizationPlan, to_stage1, to_stage2
from repro.core.schedule import TwoStageSchedule
from repro.core.tracenorm import (RegularizerConfig, regularization_loss,
                                  trace_norm_metrics)
from repro.dist.sharding import (Constraint, identity_constraint,
                                 make_constraint, param_shardings)
from repro.layers.common import ModelConfig
from repro.models.api import ModelApi, get_model
from repro.optim import AdamWConfig, make_optimizer


@dataclasses.dataclass(frozen=True)
class TrainConfig:
  lr: Callable[[jax.Array], jax.Array] | float = 1e-3
  optimizer: str = "adamw"
  adam: AdamWConfig = AdamWConfig(max_grad_norm=1.0)
  microbatches: int = 1
  regularizer: RegularizerConfig = RegularizerConfig()
  checkpoint_dir: Optional[str] = None
  checkpoint_every: int = 0          # steps; 0 = off
  async_checkpoint: bool = True


def _lr_at(lr, step):
  return lr(step) if callable(lr) else jnp.asarray(lr, jnp.float32)


def make_train_step(model_cfg: ModelConfig, train_cfg: TrainConfig,
                    api: Optional[ModelApi] = None,
                    cs: Constraint = identity_constraint,
                    reg: Optional[RegularizerConfig] = None,
                    donate: bool = True):
  """Build the jitted (params, opt_state, batch, step) -> ... function."""
  api = api or get_model(model_cfg)
  reg = train_cfg.regularizer if reg is None else reg
  opt_init, opt_apply = make_optimizer(train_cfg.optimizer)

  def loss_fn(params, batch):
    loss, metrics = api.loss_fn(params, batch, model_cfg, cs)
    if reg.kind != "none":
      r = regularization_loss(params, reg)
      metrics = dict(metrics, reg=r)
      loss = loss + r
    return loss, metrics

  def grads_of(params, batch):
    k = train_cfg.microbatches
    if k <= 1:
      (loss, metrics), grads = jax.value_and_grad(
          loss_fn, has_aux=True)(params, batch)
      return loss, metrics, grads
    # microbatch accumulation: scan over k slices of the leading dim
    def slice_mb(x, i):
      mb = x.shape[0] // k
      return jax.lax.dynamic_slice_in_dim(x, i * mb, mb, axis=0)
    def body(carry, i):
      acc_loss, acc_g = carry
      mb = jax.tree.map(lambda x: slice_mb(x, i), batch)
      (loss, metrics), g = jax.value_and_grad(
          loss_fn, has_aux=True)(params, mb)
      acc_g = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                           acc_g, g)
      return (acc_loss + loss, acc_g), metrics
    zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss_sum, gsum), metrics = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), zero_g), jnp.arange(k))
    grads = jax.tree.map(lambda g: g / k, gsum)
    metrics = jax.tree.map(lambda m: m[-1], metrics)
    return loss_sum / k, metrics, grads

  def step_fn(params, opt_state, batch, step):
    loss, metrics, grads = grads_of(params, batch)
    lr = _lr_at(train_cfg.lr, step)
    params, opt_state, opt_metrics = opt_apply(
        params, grads, opt_state, lr, train_cfg.adam)
    metrics = dict(metrics, loss=loss, lr=lr, **opt_metrics)
    return params, opt_state, metrics

  return opt_init, jax.jit(step_fn, donate_argnums=(0, 1) if donate else ())


class Trainer:
  """Drives make_train_step with the two-stage schedule + checkpoints."""

  def __init__(self, model_cfg: ModelConfig, train_cfg: TrainConfig, *,
               schedule: Optional[TwoStageSchedule] = None,
               plan: Optional[FactorizationPlan] = None,
               mesh=None, batch_size: int = 0, rng=None):
    self.model_cfg = model_cfg
    self.train_cfg = train_cfg
    self.schedule = schedule
    self.plan = plan or FactorizationPlan()
    self.api = get_model(model_cfg)
    self.mesh = mesh
    self.cs = make_constraint(mesh, model_cfg, batch_size)
    rng = jax.random.PRNGKey(0) if rng is None else rng
    params = self.api.init(rng, model_cfg)
    if schedule is not None and schedule.regularizer.kind == "trace":
      params = to_stage1(params, self.plan)     # full-rank factored form
    self.params = self._place(params)
    self.step = 0
    self.stage = 1 if schedule is not None else 0
    self._lr_scale = 1.0
    self._build(reg=self._current_reg())
    self.opt_state = self._opt_init(self.params)
    self.ckpt = (CheckpointManager(train_cfg.checkpoint_dir)
                 if train_cfg.checkpoint_dir else None)
    self.metrics_history: list[dict] = []

  def _place(self, params):
    """Lay params out over the mesh by the sharding rules (they would
    otherwise sit whole on the default device until the first step)."""
    if self.mesh is None:
      return params
    return jax.device_put(params, param_shardings(params, self.mesh))

  def _current_reg(self) -> RegularizerConfig:
    if self.schedule is None:
      return self.train_cfg.regularizer
    return self.schedule.regularizer_at(self.step)

  def _build(self, reg: RegularizerConfig) -> None:
    tc = self.train_cfg
    if self._scaled_lr() is not tc.lr:
      tc = dataclasses.replace(tc, lr=self._scaled_lr())
    self._opt_init, self._step_fn = make_train_step(
        self.model_cfg, tc, self.api, self.cs, reg=reg)

  def _scaled_lr(self):
    base = self.train_cfg.lr
    if self._lr_scale == 1.0:
      return base
    if callable(base):
      return lambda s: base(s) * self._lr_scale
    return base * self._lr_scale

  # -- two-stage transition ---------------------------------------------------

  def maybe_transition(self) -> bool:
    """Stage-1 -> stage-2 at the schedule's transition step (paper §3.2.3)."""
    if (self.schedule is None or self.stage != 1 or
        self.step < self.schedule.transition_step):
      return False
    self.params = self._place(to_stage2(self.params, self.plan,
                                        self.schedule.truncation))
    self.stage = 2
    self._lr_scale = self.schedule.stage2_lr_scale()
    self._build(reg=RegularizerConfig(kind="none"))
    self.opt_state = self._opt_init(self.params)   # moments reset: shapes changed
    return True

  # -- stepping ---------------------------------------------------------------

  def train_step(self, batch: dict) -> dict:
    self.maybe_transition()
    t0 = time.perf_counter()
    self.params, self.opt_state, metrics = self._step_fn(
        self.params, self.opt_state, batch, jnp.asarray(self.step))
    metrics = {k: float(v) for k, v in metrics.items()}
    metrics["step"] = self.step
    metrics["stage"] = self.stage
    metrics["wall_s"] = time.perf_counter() - t0
    self.metrics_history.append(metrics)
    self.step += 1
    if (self.ckpt and self.train_cfg.checkpoint_every and
        self.step % self.train_cfg.checkpoint_every == 0):
      self.save()
    return metrics

  def tracenorm_report(self) -> dict:
    """SVD diagnostics (nu, trace norm, rank90) per factored GEMM."""
    return {k: {kk: float(vv) for kk, vv in m.items()}
            for k, m in trace_norm_metrics(self.params).items()}

  # -- checkpointing ----------------------------------------------------------

  def save(self, blocking: Optional[bool] = None) -> None:
    if self.ckpt is None:
      return
    blocking = (not self.train_cfg.async_checkpoint
                if blocking is None else blocking)
    self.ckpt.save(self.step, {"params": self.params,
                               "opt": self.opt_state},
                   extra={"step": self.step, "stage": self.stage},
                   blocking=blocking)

  def restore(self, step: Optional[int] = None) -> None:
    if self.ckpt is None:
      raise ValueError("no checkpoint dir configured")
    self.ckpt.wait()
    template = {"params": self.params, "opt": self.opt_state}
    tree, extra = self.ckpt.restore(template, step=step)
    self.params = tree["params"]
    self.opt_state = tree["opt"]
    self.step = int(extra.get("step", 0))
    self.stage = int(extra.get("stage", self.stage))

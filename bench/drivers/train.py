"""Stage-1 trace-norm training through `Trainer.train_step`.

The Trainer is built as `launch/train.py --two-stage` builds its stage 1
(trace-norm regulariser at lambda_rec / lambda_nonrec, AdamW with
global-norm clipping), but without a schedule, so that it makes no
weights of its own through host SVDs: the benchmark hands it full-rank
factored weights from the seed (`ds2_weights`, form "factored_full") and
a fresh optimizer state. Each step feeds one batch from a seeded pool,
host to device, as a loader would; the batches are `batch` utterances
from the mix's length set in a seed-drawn order, zero-padded to `frames`.

Set-up drives the same Trainer through its first `check.steps` steps
and records the losses, the first gradient as AdamW's first moment holds
it, and the parameters' change; the window continues from there.

End-to-end: `train_audio_s_per_s`, real (unpadded) audio seconds of the
window's steps over all its wall seconds.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench import traffic_gen
from bench import harness
from bench.harness import BenchError
from bench.kernels import ds2_step
from bench.models import ds2_weights

FORM = "factored_full"


def leaf_name(path) -> str:
  """Program-tree path -> the reference's leaf name ("gru0/rec.u")."""
  keys = [str(getattr(k, "key", getattr(k, "name", k))) for k in path]
  keys = [k for k in keys if k != "grus"]
  if keys[-1] in ("u", "v", "w"):
    return "/".join(keys[:-1]) + "." + keys[-1]
  return "/".join(keys)


class Run:
  def __init__(self, cell, seed: int, seconds: float, tracing: bool,
               variant: str = ""):
    self.cell, self.seed, self.seconds = cell, seed, seconds
    self.config, self.mix = cell.config, cell.traffic
    self.variant = variant
    self.span = harness.spans(tracing)
    self.rate = float(self.mix["frame_rate"])

  # -- set-up ---------------------------------------------------------------

  def setup(self) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import ds2
    from repro.core.compress import FactorizationPlan
    from repro.core.tracenorm import RegularizerConfig
    from repro.optim import AdamWConfig, adamw
    from repro.training import TrainConfig, Trainer

    if self.variant:
      raise BenchError("the training cell has no program variant")
    cfg = ds2.program_config(self.config)
    opt, reg = self.mix["optimizer"], self.mix["regularizer"]
    tcfg = TrainConfig(
        lr=opt["lr"],
        adam=AdamWConfig(b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                         weight_decay=opt["weight_decay"],
                         max_grad_norm=opt["max_grad_norm"]),
        regularizer=RegularizerConfig(kind="trace",
                                      lambda_rec=reg["lambda_rec"],
                                      lambda_nonrec=reg["lambda_nonrec"]))
    self.pool, self.real_s, self.flops = self._pool()
    trainer = Trainer(cfg, tcfg, plan=FactorizationPlan(
        min_dim=self.config["min_dim"], exclude=("*embed*",)),
        batch_size=self.mix["batch"], rng=ds2_weights.jax_key(self.seed))
    trainer.params = ds2_weights.to_program(
        ds2_weights.make(self.config, self.seed, FORM), self.config)
    trainer.opt_state = adamw.init(trainer.params)
    jax.block_until_ready(trainer.params)
    self.trainer = trainer

    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))
    steps = self.mix["check"]["steps"]
    self.losses = []
    for i in range(steps):
      m = self.step(i)
      self.losses.append(m["loss"])
      if i == 0:
        # AdamW's first moment after one step is (1 - b1) g
        self.grad_norms = self._named(jax.device_get(norms(
            trainer.opt_state.m)), scale=1.0 / (1.0 - opt["b1"]))
    start = ds2_weights.to_program(
        ds2_weights.make(self.config, self.seed, FORM), self.config)
    diff = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))
    self.change_norms = self._named(jax.device_get(diff(trainer.params,
                                                         start)))
    del start
    self.next = steps
    return {"pool": len(self.pool), "steps_in_setup": steps,
            "losses": self.losses}

  def _named(self, tree, scale: float = 1.0) -> dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {leaf_name(p): float(v) * scale for p, v in flat}

  def _pool(self) -> tuple:
    """`pool` padded batches; (batches, real audio s, model flops) each."""
    mix = self.mix
    b, t_max, l_max = mix["batch"], mix["frames"], mix["label_max"]
    batches, real, flops = [], [], []
    for i in range(mix["pool"]):
      frames = traffic_gen.lengths_in_order(mix["length_s"], self.seed,
                                            f"batch{i}", self.rate)[:b]
      utts = traffic_gen.utterances(mix["audio"], self.seed, f"batch{i}",
                                    self.config["feat_dim"], frames)
      feats = np.zeros((b, t_max, self.config["feat_dim"]), np.float32)
      labels = np.zeros((b, l_max), np.int32)
      lab_len = np.zeros((b,), np.int32)
      for r, (f, lab) in enumerate(utts):
        if f.shape[0] > t_max or len(lab) > l_max:
          raise BenchError(f"utterance of {f.shape[0]} frames / "
                           f"{len(lab)} labels exceeds the batch shape")
        feats[r, :f.shape[0]] = f
        labels[r, :len(lab)] = lab
        lab_len[r] = len(lab)
      batches.append({"feats": feats,
                      "feat_lengths": np.asarray(frames, np.int32),
                      "labels": labels, "label_lengths": lab_len})
      real.append(sum(frames) / self.rate)
      flops.append(sum(ds2_step.train_flops(self.config, FORM, t)
                       for t in frames))
    return batches, real, flops

  def step(self, i: int) -> dict:
    with self.span("bench.train_step"):
      return self.trainer.train_step(self.pool[i % len(self.pool)])

  # -- the window -----------------------------------------------------------

  def window(self) -> dict:
    start = now = time.perf_counter()
    audio, flops, n, slowest = 0.0, 0.0, 0, 0.0
    while now - start < self.seconds:
      i = self.next + n
      self.step(i)
      audio += self.real_s[i % len(self.pool)]
      flops += self.flops[i % len(self.pool)]
      n += 1
      last, now = now, time.perf_counter()
      slowest = max(slowest, now - last)
    window_s = now - start
    return {"e2e": {"train_audio_s_per_s": audio / window_s},
            "attempted": n, "failed": 0, "window_s": window_s,
            "audio_s": audio, "model_flops": flops, "steps": n,
            "step_max_ms": slowest * 1e3}

  def release(self) -> None:
    del self.trainer

  # -- correctness ----------------------------------------------------------

  def verify(self) -> list:
    from bench.models import ds2_ref
    steps = self.mix["check"]["steps"]
    w0 = ds2_weights.make(self.config, self.seed, FORM)
    ref = ds2_ref.adamw_steps(w0, self.pool[:steps], self.config,
                              self.mix["optimizer"],
                              self.mix["regularizer"])
    return compare_training(self.losses, self.grad_norms, self.change_norms,
                            ref, self.cell.limits)


def worst_leaf_gap(got: dict, want: dict, keep=None) -> tuple:
  """(worst |got - want| / max(want, median of want), its leaf)."""
  names = [k for k in want if keep is None or k in keep]
  med = float(np.median([want[k] for k in names]))
  worst, where = 0.0, ""
  for k in names:
    g = got.get(k, float("nan"))
    gap = abs(g - want[k]) / max(want[k], med, 1e-30)
    if not gap <= worst:
      worst, where = (gap if np.isfinite(gap) else float("inf")), k
  return worst, where


def compare_training(losses: list, grad_norms: dict, change_norms: dict,
                     ref: dict, limits: dict) -> list:
  """Checks of the program's first steps against the reference's.

  The loss is compared at the first step: from random init, Adam's first
  steps at lr 1e-3 can send the loss up several-fold by the third step on
  some seeds, and there the relative gap grows with it (see PERF.md);
  every step's gap goes to standard error beside the checks."""
  gaps = [abs(a - b) / abs(b) for a, b in zip(losses, ref["losses"])]
  print(f"bench: loss rel gap per step {gaps}", file=sys.stderr)
  grad_gap, _ = worst_leaf_gap(grad_norms, ref["grad_norms"])
  # leaves whose reference gradient is nought to rounding move under Adam
  # by round-off alone: left out of the change by a rule on the gradient
  med = float(np.median(list(ref["grad_norms"].values())))
  moving = {k for k, v in ref["grad_norms"].items() if v >= 1e-3 * med}
  change_gap, _ = worst_leaf_gap(change_norms, ref["change_norms"], moving)
  return harness.compared({"first_loss_rel_gap": gaps[0],
                           "first_grad_leaf_gap": grad_gap,
                           "change_leaf_gap": change_gap}, limits)

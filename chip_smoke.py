#!/usr/bin/env python3
"""Smoke test of the paper's DS2 train-and-serve path on one TPU chip.

Runs deepspeech2-wsj at its published widths (GRU 768/1024/1280, FC 1536,
mel-80, bf16; random weights from --seed) through the entry points a user
calls, in one process that holds the chip throughout:

  train   `Trainer` with the two-stage schedule, built as launch/train.py
          builds it: stage-1 steps on the trace-norm-regularised full-rank
          factored model, the truncated-SVD transition, stage-2 steps.
          Every loss must be finite, and the stage-2 tree must hold fewer
          parameters than the stage-1 tree.
  serve   `StreamingSpeechServer` (4 slots, 8 utterances of mixed,
          non-stride-multiple lengths) in three weight tiers: (a) full-rank
          float, (b) stage-2 low-rank from `compress.to_stage2`, (c) PTQ
          int8 from `quant.quantize_params`. In each tier every GEMM must
          route to the Pallas regime the tier exists for (only a GEMM with
          a dimension under the 128-lane MXU tile may stay on jnp), and the
          per-frame log-probs must match the same utterances served under
          the jnp-only policy within `LOGPROB_TOL`.

With --chips 4 it runs only the sharded check: a few full-width stage-1
train steps on a 4-chip (data=2, model=2) mesh next to the same steps on
one chip, with the losses compared at `SHARDED_LOSS_RTOL`.

Every line but the last is a log: per-phase wall and compile seconds,
peak device memory, losses, regime counts, differences. The last line of
stdout is `{"ok": true, "device": {...}}`. A failed check or phase exits
non-zero. Without a TPU it exits non-zero at once: there is no CPU path.

  python chip_smoke.py [--seed N]
  python chip_smoke.py --chips 4
"""
from __future__ import annotations

import argparse
import collections
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "deepspeech2-wsj"
TRAIN_BATCH = 8                 # launch/train.py's default --batch
STAGE1_STEPS = 3
STAGE2_STEPS = 3
SERVE_SLOTS = 4
SERVE_UTTERANCES = 8
SERVE_RANK = 256                # tier (b): every factored GEMM at rank 256
SHARDED_MESH = (2, 2)           # (data, model)
SHARDED_STEPS = 3

# Largest |log p(label | frame)| difference, in nats, between a tier's
# Pallas run and its jnp-only run. Both paths multiply the same bf16
# values exactly and accumulate in f32, but they round to bf16 at
# different points: the fused gru_cell keeps the recurrent product and
# the gates in f32 up to one final cast, where the jnp path rounds the
# recurrent GEMM's output to bf16 (8 mantissa bits) before the gate math.
# The bf16 hidden states then carry those one-ulp differences through
# three recurrent layers. A bf16 ulp at |logit| ~ 4 is 2^-6 ~ 0.016, so
# 0.1 nats admits about six ulps of drift and no real routing or kernel
# fault (a wrong tile, gate or scale moves log-probs by O(1)). The int8
# tier runs the same w8a8 arithmetic on both paths (exact int32
# accumulation, the same f32 dequant products); only a one-ulp change
# in an activation before its int8 rounding can separate them, so it
# gets the same bound.
LOGPROB_TOL = 0.1
# Relative difference of each stage-1 loss, sharded vs one chip. The
# sharded step reduces bf16 activations and gradients in another order,
# which moves each loss by a few bf16 ulps (2^-8 relative each) at most.
SHARDED_LOSS_RTOL = 1e-2


class SmokeFailure(AssertionError):
  """A check of this script failed."""


def check(cond: bool, msg: str) -> None:
  if not cond:
    raise SmokeFailure(msg)


def log(**fields) -> None:
  print(json.dumps(fields), flush=True)


class CompileTimer:
  """Seconds JAX spent tracing, lowering and compiling, since creation."""

  def __init__(self):
    import jax
    self.seconds = 0.0

    def on_event(event: str, secs: float, **_) -> None:
      if event.startswith("/jax/core/compile/"):
        self.seconds += secs
    jax.monitoring.register_event_duration_secs_listener(on_event)


class PhaseClock:
  """Logs wall seconds, compile seconds and peak device bytes of a phase."""

  def __init__(self, name: str, compiles: CompileTimer):
    self.name = name
    self.compiles = compiles

  def __enter__(self):
    self.t0 = time.perf_counter()
    self.c0 = self.compiles.seconds
    return self

  def __exit__(self, *exc):
    if exc[0] is None:
      log(phase=self.name, wall_s=time.perf_counter() - self.t0,
          compile_s=self.compiles.seconds - self.c0,
          peak_bytes_in_use=peak_bytes())
    return False


def peak_bytes():
  import jax
  stats = jax.devices()[0].memory_stats()
  return stats.get("peak_bytes_in_use") if stats else None


# ---------------------------------------------------------------------------
# Train.
# ---------------------------------------------------------------------------

def make_trainer(cfg, seed: int, *, stage1_steps: int, total_steps: int,
                 mesh=None):
  """The Trainer launch/train.py builds for `--two-stage`."""
  import jax

  from repro.core.compress import FactorizationPlan
  from repro.core.schedule import TwoStageSchedule, cosine_schedule
  from repro.core.svd import TruncationSpec
  from repro.core.tracenorm import RegularizerConfig
  from repro.training import TrainConfig, Trainer

  schedule = TwoStageSchedule(
      total_steps=total_steps, transition_step=stage1_steps,
      regularizer=RegularizerConfig(kind="trace", lambda_rec=1e-4,
                                    lambda_nonrec=1e-4),
      truncation=TruncationSpec(variance_threshold=0.9, round_to=8))
  tcfg = TrainConfig(lr=cosine_schedule(1e-3, total_steps // 10,
                                        total_steps))
  return Trainer(cfg, tcfg, schedule=schedule,
                 plan=FactorizationPlan(min_dim=32, exclude=("*embed*",)),
                 mesh=mesh, batch_size=TRAIN_BATCH,
                 rng=jax.random.PRNGKey(seed))


def train_batches(cfg, seed: int):
  from repro.data import speech as speech_data
  dc = speech_data.SpeechDataConfig(vocab_size=cfg.vocab_size,
                                    feat_dim=cfg.feat_dim,
                                    global_batch=TRAIN_BATCH, seed=seed)
  return lambda i: speech_data.batch_at(dc, i)


def run_steps(trainer, batch_at, steps: int, tag: str) -> list:
  losses = []
  for i in range(steps):
    m = trainer.train_step(batch_at(trainer.step))
    log(phase=tag, step=m["step"], stage=m["stage"], loss=m["loss"],
        wall_s=m["wall_s"])
    check(math.isfinite(m["loss"]),
          f"{tag}: step {m['step']} loss is not finite ({m['loss']})")
    losses.append(m["loss"])
  return losses


def train_phase(cfg, seed: int, compiles: CompileTimer,
                stage1_steps: int = STAGE1_STEPS,
                stage2_steps: int = STAGE2_STEPS) -> dict:
  """Stage-1 steps, the truncated-SVD transition, stage-2 steps."""
  from repro.core.factored import count_params

  with PhaseClock("train_init", compiles):
    trainer = make_trainer(cfg, seed, stage1_steps=stage1_steps,
                           total_steps=stage1_steps + stage2_steps)
  batch_at = train_batches(cfg, seed)
  with PhaseClock("train_stage1", compiles):
    stage1 = run_steps(trainer, batch_at, stage1_steps, "train")
  n1 = count_params(trainer.params)
  with PhaseClock("transition", compiles):
    check(trainer.maybe_transition(), "the stage-1 -> stage-2 transition "
          "did not happen at its step")
  n2 = count_params(trainer.params)
  log(phase="transition", stage1_params=n1, stage2_params=n2)
  check(n2 < n1, f"stage-2 tree ({n2} params) is not smaller than the "
        f"stage-1 tree ({n1})")
  with PhaseClock("train_stage2", compiles):
    stage2 = run_steps(trainer, batch_at, stage2_steps, "train")
  check(all(m["stage"] == 2 for m in trainer.metrics_history[stage1_steps:]),
        "stage-2 steps did not run in stage 2")
  return {"stage1": stage1, "stage2": stage2, "stage1_params": n1,
          "stage2_params": n2}


# ---------------------------------------------------------------------------
# Serve.
# ---------------------------------------------------------------------------

def _expected_regime(tier: str, name: str) -> str:
  if tier == "float":
    return "gru_cell" if name.endswith("/rec") else "decode_matvec"
  return {"lowrank": "lowrank_gemm", "int8": "int8_gemm"}[tier]


def serve_utterances(cfg, seed: int) -> list:
  """Mixed, non-stride-multiple lengths, as launch/serve.py draws them."""
  import numpy as np

  from repro.data.speech import SpeechDataConfig, batch_at
  dc = SpeechDataConfig(vocab_size=cfg.vocab_size, feat_dim=cfg.feat_dim,
                        global_batch=SERVE_SLOTS, seed=seed)
  rng = np.random.RandomState(seed)
  utts = []
  for i in range(SERVE_UTTERANCES):
    batch = np.asarray(batch_at(dc, i)["feats"])
    row = batch[i % batch.shape[0]]
    utts.append(row[:int(rng.randint(17, min(64, row.shape[0]) + 1))])
  return utts


def serve_tiers(cfg, seed: int, rank: int = SERVE_RANK) -> dict:
  """{tier: params} for the three weight tiers."""
  import jax

  from repro.core.compress import FactorizationPlan, to_stage2
  from repro.core.svd import TruncationSpec
  from repro.kernels import ops
  from repro.models.api import get_model
  from repro.quant import quantize_params

  params = get_model(cfg).init(jax.random.PRNGKey(seed), cfg)
  lowrank = to_stage2(params, FactorizationPlan(min_dim=ops.LANE),
                      TruncationSpec(variance_threshold=None,
                                     fixed_rank=rank))
  return {"float": params, "lowrank": lowrank,
          "int8": quantize_params(params)}


def serve(cfg, params, utts, policy) -> dict:
  from repro.serving import StreamingSpeechServer
  server = StreamingSpeechServer(cfg, params, batch_size=SERVE_SLOTS,
                                 kernel_policy=policy)
  for u in utts:
    server.submit(u)
  results = server.run(chunk_frames=16)
  stats = server.compile_stats()
  check(stats["frame_step"] == 1,
        f"frame_step traced {stats['frame_step']} signatures, not 1")
  return {r.uid: r for r in results}


def serve_tier(cfg, tier: str, params, utts, *, interpret: bool) -> dict:
  """Serve `utts` under the Pallas policy and under jnp_only; check the
  routing of the first and the log-prob agreement of the two."""
  import numpy as np

  from repro.core.factored import iter_factored_leaves, iter_gemm_leaves
  from repro.kernels import dispatch, ops

  if tier == "lowrank":
    ranks = {l.name: l.rank for l in iter_factored_leaves(params)
             if l.is_factored}
    check(bool(ranks) and min(ranks.values()) >= ops.LANE,
          f"lowrank tier: factored ranks {ranks} must all be >= {ops.LANE}")
  dims = {l.name: (l.in_dim, l.out_dim) for l in iter_gemm_leaves(params)}
  policy = dispatch.decode_policy(SERVE_SLOTS, interpret=interpret)
  with dispatch.record_dispatch() as records:
    got = serve(cfg, params, utts, policy)
  regimes = collections.Counter(r.regime for r in records)
  for name, regime in sorted(set(records)):
    want = _expected_regime(tier, name)
    narrow = min(dims[name]) < ops.LANE
    check(regime == want or (regime == "jnp" and narrow),
          f"{tier} tier: GEMM {name!r} routed to {regime!r}, expected "
          f"{want!r}")
  for want in sorted({_expected_regime(tier, name) for name in dims}):
    check(regimes[want] > 0, f"{tier} tier: no GEMM reached {want!r}")

  ref = serve(cfg, params, utts, dispatch.JNP_ONLY)
  check(sorted(got) == sorted(ref) == list(range(len(utts))),
        f"{tier} tier: served uids {sorted(got)} vs {sorted(ref)}")
  max_diff, frames, agree = 0.0, 0, 0
  for uid, r in got.items():
    a, b = r.log_probs, ref[uid].log_probs
    check(a.shape == b.shape and a.shape[0] > 0,
          f"{tier} tier: utterance {uid} log-prob shapes {a.shape} vs "
          f"{b.shape}")
    check(bool(np.isfinite(a).all()),
          f"{tier} tier: utterance {uid} has non-finite log-probs")
    max_diff = max(max_diff, float(np.max(np.abs(a - b))))
    frames += a.shape[0]
    agree += int((a.argmax(-1) == b.argmax(-1)).sum())
  out = {"tier": tier, "regimes": dict(sorted(regimes.items())),
         "frames": frames, "max_abs_logprob_diff": max_diff,
         "tolerance": LOGPROB_TOL, "argmax_agreement": agree / frames}
  log(phase="serve", **out)
  check(max_diff <= LOGPROB_TOL,
        f"{tier} tier: Pallas vs jnp_only log-probs differ by {max_diff} "
        f"nats > {LOGPROB_TOL}")
  return out


def serve_phase(cfg, seed: int, compiles: CompileTimer, *,
                interpret: bool = False, rank: int = SERVE_RANK) -> dict:
  utts = serve_utterances(cfg, seed)
  log(phase="serve", utterance_frames=[int(u.shape[0]) for u in utts])
  with PhaseClock("serve_weights", compiles):
    tiers = serve_tiers(cfg, seed, rank)
  out = {}
  for tier, params in tiers.items():
    with PhaseClock(f"serve_{tier}", compiles):
      out[tier] = serve_tier(cfg, tier, params, utts, interpret=interpret)
  return out


# ---------------------------------------------------------------------------
# Sharded train steps (--chips 4).
# ---------------------------------------------------------------------------

def sharded_phase(cfg, seed: int, devices, compiles: CompileTimer,
                  steps: int = SHARDED_STEPS) -> dict:
  """Stage-1 steps on a (data, model) mesh over `devices`, next to the
  same steps on one device; the losses must agree."""
  import jax

  from repro.dist.mesh import make_mesh

  batch_at = train_batches(cfg, seed)
  mesh = make_mesh(SHARDED_MESH, ("data", "model"), devices=devices)
  runs = {}
  for tag, m in (("one_chip", None), ("sharded", mesh)):
    with PhaseClock(f"train_{tag}", compiles):
      trainer = make_trainer(cfg, seed, stage1_steps=steps + 1,
                             total_steps=steps + 1, mesh=m)
      runs[tag] = run_steps(trainer, batch_at, steps, f"train_{tag}")
      if m is not None:
        spans = {len(p.sharding.device_set)
                 for p in jax.tree.leaves(trainer.params)}
        check(spans == {mesh.devices.size},
              f"sharded params span {spans} devices, not "
              f"{mesh.devices.size}")
  rel = max(abs(a - b) / max(abs(b), 1e-6)
            for a, b in zip(runs["sharded"], runs["one_chip"]))
  log(phase="sharded_compare", mesh=list(SHARDED_MESH), **runs,
      max_rel_loss_diff=rel, tolerance=SHARDED_LOSS_RTOL)
  check(rel <= SHARDED_LOSS_RTOL,
        f"sharded vs one-chip losses differ by {rel} > {SHARDED_LOSS_RTOL}")
  return runs


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                  help="4: run only the sharded train-step check")
  args = ap.parse_args(argv)

  import jax
  backend = jax.default_backend()
  if backend != "tpu":
    print(f"chip_smoke: JAX finds no TPU (backend {backend!r}); this "
          f"script runs on the chip only", file=sys.stderr)
    return 2
  devices = jax.devices()
  if len(devices) < args.chips:
    print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
          f"JAX finds {len(devices)}", file=sys.stderr)
    return 2

  from repro import configs
  from repro.runtime.compile_cache import enable_compile_cache

  device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
  log(device=device, jax=jax.__version__,
      compile_cache=enable_compile_cache())
  compiles = CompileTimer()
  cfg = configs.get_config(ARCH)
  log(arch=ARCH, gru_dims=list(cfg.gru_dims), fc_dim=cfg.fc_dim,
      feat_dim=cfg.feat_dim, dtype=jax.numpy.dtype(cfg.dtype).name)

  if args.chips == 4:
    sharded_phase(cfg, args.seed, devices[:4], compiles)
  else:
    train_phase(cfg, args.seed, compiles)
    serve_phase(cfg, args.seed, compiles)
  print(json.dumps({"ok": True, "device": device}), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())

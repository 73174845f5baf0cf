"""Training driver: --arch selects any of the 11 configs.

By default it trains the reduced (smoke) config, which runs on the CPU.
--full trains the published config; deepspeech2-wsj at full width runs on
one TPU v5e (`chip_smoke.py` drives the same Trainer path there).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch deepspeech2-wsj \
      --steps 50 --two-stage --transition 25
  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --steps 20
"""
from __future__ import annotations

import argparse
import json

import jax
import numpy as np

from repro import configs
from repro.core.compress import FactorizationPlan
from repro.core.schedule import TwoStageSchedule, cosine_schedule
from repro.core.svd import TruncationSpec
from repro.core.tracenorm import RegularizerConfig
from repro.data import lm as lm_data
from repro.data import speech as speech_data
from repro.runtime.compile_cache import enable_compile_cache
from repro.training import TrainConfig, Trainer


def main() -> None:
  ap = argparse.ArgumentParser()
  ap.add_argument("--arch", required=True, choices=configs.ARCH_NAMES)
  ap.add_argument("--steps", type=int, default=30)
  ap.add_argument("--batch", type=int, default=8)
  ap.add_argument("--seq", type=int, default=64)
  ap.add_argument("--lr", type=float, default=1e-3)
  ap.add_argument("--microbatches", type=int, default=1)
  ap.add_argument("--full", action="store_true",
                  help="use the full production config (TPU pods)")
  ap.add_argument("--two-stage", action="store_true")
  ap.add_argument("--transition", type=int, default=0)
  ap.add_argument("--lambda-rec", type=float, default=1e-4)
  ap.add_argument("--lambda-nonrec", type=float, default=1e-4)
  ap.add_argument("--reg", default="trace", choices=["trace", "l2", "none"])
  ap.add_argument("--variance", type=float, default=0.9)
  ap.add_argument("--checkpoint-dir", default=None)
  ap.add_argument("--seed", type=int, default=0)
  args = ap.parse_args()
  enable_compile_cache()

  cfg = (configs.get_config(args.arch) if args.full
         else configs.get_smoke(args.arch))

  schedule = None
  plan = FactorizationPlan(min_dim=32, exclude=("*embed*",))
  if args.two_stage:
    schedule = TwoStageSchedule(
        total_steps=args.steps,
        transition_step=args.transition or args.steps // 2,
        regularizer=RegularizerConfig(kind=args.reg,
                                      lambda_rec=args.lambda_rec,
                                      lambda_nonrec=args.lambda_nonrec),
        truncation=TruncationSpec(variance_threshold=args.variance,
                                  round_to=8),
    )

  tcfg = TrainConfig(lr=cosine_schedule(args.lr, args.steps // 10,
                                        args.steps),
                     microbatches=args.microbatches,
                     checkpoint_dir=args.checkpoint_dir,
                     checkpoint_every=max(args.steps // 4, 1)
                     if args.checkpoint_dir else 0)
  trainer = Trainer(cfg, tcfg, schedule=schedule, plan=plan,
                    rng=jax.random.PRNGKey(args.seed))

  if cfg.family == "deepspeech":
    dc = speech_data.SpeechDataConfig(vocab_size=cfg.vocab_size,
                                      feat_dim=cfg.feat_dim,
                                      global_batch=args.batch,
                                      seed=args.seed)
    gen = lambda i: speech_data.batch_at(dc, i)
  elif cfg.family == "whisper":
    dcl = lm_data.LMDataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                               global_batch=args.batch, seed=args.seed)
    def gen(i):
      b = lm_data.batch_at(dcl, i)
      frames = np.random.RandomState(i).randn(
          args.batch, args.seq, cfg.d_model).astype(np.float32)
      return {"frames": frames, "tokens": b["tokens"],
              "targets": b["targets"]}
  else:
    dcl = lm_data.LMDataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                               global_batch=args.batch, seed=args.seed)
    gen = lambda i: lm_data.batch_at(dcl, i)

  for i in range(args.steps):
    m = trainer.train_step(gen(i))
    if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
      print(f"step {m['step']:4d} stage {m['stage']} "
            f"loss {m['loss']:.4f} wall {m['wall_s']:.2f}s")

  if args.two_stage:
    print("\ntrace-norm diagnostics (first 5 GEMMs):")
    rep = trainer.tracenorm_report()
    for name in list(rep)[:5]:
      r = rep[name]
      print(f"  {name:32s} nu={r['nu']:.3f} rank90={int(r['rank90'])}")
  print(json.dumps({"final_loss": trainer.metrics_history[-1]["loss"]}))


if __name__ == "__main__":
  main()

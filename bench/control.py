#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from (not run by the
benchmark's own runs).

  python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10

For each seed, in one process on the chip: a sound run of the cell (its
checks' readings: the lower readings), then the control, the cell's
numbers computed one precision step below the configuration's bf16:

  serving   the program's own int8 path: the same traffic served from
            the weights quantised by the program's PTQ (w8a8 int8_gemm)
  training  the plain reference with every GEMM product, forward and
            backward, in per-tensor int8 (the convolutions' operands
            rounded too), put in the program's place for the first steps
            and compared with the f32 reference as the program is

and, for the training cell, the fault that needs a run: the reference
put in the program's place with half of each batch left out (the mean
taken over the rest). A state left unchanged reads 1 on the change
without a run.

Prints one JSON line per run and a last line with, for each number, the
largest sound reading and the smallest control reading.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(_ROOT / "src"), str(_ROOT)):
  if _p not in sys.path:
    sys.path.insert(0, _p)

from bench import harness  # noqa: E402
from bench import run as bench_run  # noqa: E402


def training_control(cell, seed: int, fault: str = "") -> list:
  """The int8 reference (or, with fault "half", the f32 reference on half
  of each batch) in the program's place, against the f32 reference."""
  from bench.drivers import train as train_driver
  from bench.models import ds2_ref, ds2_weights
  steps = cell.traffic["check"]["steps"]
  run = train_driver.Run(cell, seed, 0.0, False)
  batches = run._pool()[0][:steps]
  w0 = ds2_weights.make(cell.config, seed, train_driver.FORM)
  rest = (cell.config, cell.traffic["optimizer"],
          cell.traffic["regularizer"])
  ref = ds2_ref.adamw_steps(w0, batches, *rest)
  if fault == "half":
    half = [{k: v[:v.shape[0] // 2] for k, v in b.items()} for b in batches]
    ctl = ds2_ref.adamw_steps(w0, half, *rest)
  else:
    ctl = ds2_ref.adamw_steps(w0, batches, *rest, quant=True)
  return train_driver.compare_training(
      ctl["losses"], ctl["grad_norms"], ctl["change_norms"], ref,
      cell.limits)


def readings(cell, seed: int, seconds: float, control: bool,
             require_accelerator: bool = True, fault: str = "") -> dict:
  if (control or fault) and cell.traffic["driver"] == "train":
    checks = training_control(cell, seed, fault)
  else:
    args = bench_run.parse(["--workload", cell.name, "--seed", str(seed),
                            "--seconds", str(seconds)])
    _, checks, _, _ = bench_run.execute(
        args, cell=cell, require_accelerator=require_accelerator,
        t0=time.perf_counter(), variant="int8" if control else "")
  return {c.name: c.value for c in checks}


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seeds", required=True)
  ap.add_argument("--seconds", type=float, default=10.0)
  ap.add_argument("--sound", type=int, default=1,
                  help="0: control runs only")
  args = ap.parse_args(argv)
  cell = harness.Cell(args.workload)
  seeds = [int(s) for s in args.seeds.split(",")]
  kinds = (["sound"] if args.sound else []) + ["control"]
  if cell.traffic["driver"] == "train":
    kinds.append("half")
  got = {k: [] for k in kinds}
  for seed in seeds:
    for kind in kinds:
      r = readings(cell, seed, args.seconds, kind == "control",
                   fault="half" if kind == "half" else "")
      got[kind].append(r)
      print(json.dumps({"seed": seed, "kind": kind, "readings": r}),
            flush=True)
  summary = {}
  for kind, rows in got.items():
    keys = set.intersection(*(set(r) for r in rows))
    pick = max if kind == "sound" else min
    summary[kind] = {k: pick(r[k] for r in rows) for k in sorted(keys)}
  print(json.dumps(summary), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())

#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Set-up makes the cell's weights and inputs from --seed, builds the
program's objects and warms every shape the cell uses (compiles come from
the persistent cache in `<checkout>/.jax_cache` after a cell's first
run). Then it measures for --seconds seconds, frees the program's state,
checks what the timed path produced against the plain f32 reference, and
prints one JSON line: `correct`, `attempted`, `failed`, `metrics`,
`device` (with `--trace 1` also `busy_s`, `window_s` and `breakdown`),
and last `checks`, each number compared beside its limit. The same
numbers close standard error.

With --trace 0 the metrics are the cell's end-to-end metrics; with
--trace 1 its per-layer metrics, read from a profiler trace of the
window. There is no CPU fallback: without an accelerator, or with fewer
chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(_ROOT / "src"), str(_ROOT)):
  if _p not in sys.path:
    sys.path.insert(0, _p)

from bench import harness  # noqa: E402


def parse(argv=None):
  ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, required=True)
  ap.add_argument("--seconds", type=float, required=True)
  ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
  return ap.parse_args(argv)


def find_devices(chips: int, require_accelerator: bool = True):
  """The first `chips` devices, or BenchError without an accelerator."""
  import jax
  backend = jax.default_backend()
  if require_accelerator and backend not in ("tpu", "gpu"):
    raise harness.BenchError(f"JAX finds no accelerator (backend "
                             f"{backend!r}); the benchmark runs on the chip "
                             f"only")
  devices = jax.devices()
  if len(devices) < chips:
    raise harness.BenchError(f"the cell needs {chips} chips, JAX finds "
                             f"{len(devices)}")
  return devices[:chips]


def enable_cache() -> str:
  """The program's persistent compile cache (its fixed directory inside
  the checkout, or JAX_COMPILATION_CACHE_DIR), keeping every program."""
  import jax

  from repro.runtime.compile_cache import enable_compile_cache
  path = enable_compile_cache()
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
  jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
  return path


def execute(args, *, cell: harness.Cell, require_accelerator: bool = True,
            t0: float = T0, variant: str = "") -> tuple:
  """Run the cell once; returns (result line, checks, window, set-up
  info). `variant` is passed to the driver (the control runs use it)."""
  import jax

  from bench import tracereduce
  from bench.peaks import peaks_for

  clock = harness.Clock(t0)
  devices = find_devices(cell.chips, require_accelerator)
  if require_accelerator:
    enable_cache()
  compiles = harness.CompileTimer()
  kind = devices[0].device_kind
  peaks = peaks_for(kind) if require_accelerator else None

  run = cell.driver().Run(cell, args.seed, args.seconds, bool(args.trace),
                          variant=variant)
  setup_info = run.setup()
  setup_s = clock.now()
  compiles_before, phases_before = compiles.events, compiles.phases
  trace_dir = None
  if args.trace:
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(trace_dir)
  try:
    with jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN), \
        harness.GcPauses() as gc_pauses:
      window = run.window()
  finally:
    if trace_dir:
      jax.profiler.stop_trace()
  in_window = compiles.events - compiles_before
  window["compile_phases_in_window"] = compiles.phases - phases_before
  window.update(gc_pauses.as_dict())
  device = {"platform": devices[0].platform, "kind": kind,
            "count": len(devices), "memory_peak_bytes": harness.peak_bytes(
                devices)}
  run.release()

  metrics, breakdown = {}, None
  if args.trace:
    try:
      summary = tracereduce.reduce_dir(trace_dir)
    finally:
      shutil.rmtree(trace_dir, ignore_errors=True)
    device["busy_s"] = summary.busy_s
    device["window_s"] = summary.window_s
    breakdown = summary.breakdown()
    ctx = ReadContext(cell, window, summary, peaks, setup_info)
    for m in cell.per_layer():
      value = cell.metric_reader(m["name"]).read(ctx)
      if value is not None:
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
  else:
    for m in cell.end_to_end():
      value = setup_s if m["name"] == "setup_s" else window["e2e"][m["name"]]
      metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

  checks = run.verify()
  checks.append(harness.Check("compiles_in_window", in_window, 0))
  line = harness.result_line(checks=checks, attempted=window["attempted"],
                             failed=window["failed"], metrics=metrics,
                             device=device, breakdown=breakdown)
  return line, checks, window, setup_info


class ReadContext:
  """What a per-layer metric reader may read."""

  def __init__(self, cell, window: dict, trace, peaks: dict, setup: dict):
    self.cell, self.window, self.trace = cell, window, trace
    self.peaks, self.setup = peaks, setup


def main(argv=None) -> int:
  args = parse(argv)
  try:
    cell = harness.Cell(args.workload)
    line, checks, window, info = execute(args, cell=cell)
  except harness.BenchError as e:
    print(f"bench: {e}", file=sys.stderr)
    return 2
  print(f"bench: set-up {info}; window "
        f"{ {k: v for k, v in window.items() if k != 'e2e'} }",
        file=sys.stderr)
  for c in checks:
    print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
          f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
  sys.stderr.flush()
  print(line, flush=True)
  return 0


if __name__ == "__main__":
  os.environ.setdefault("TPU_STDERR_LOG_LEVEL", "2")
  sys.exit(main())

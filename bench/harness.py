"""The benchmark's machinery: find a cell's files by name, time set-up,
count compiles, read the device, and build the result line.

Everything that belongs to one configuration, traffic mix, driver,
per-layer metric or kernel lives in a file of its own, found by name:

  bench/configs/<config>.json     sizes of one model configuration
  bench/traffic/<mix>.json        parameters of one traffic mix; its
                                  "driver" key names the driver
  bench/drivers/<driver>.py       the code that drives one program surface
  bench/metrics/<metric>.py       reader of one per-layer metric
  bench/kernels/<kernel>.py       operations and bytes of one kernel call
  bench/limits/<cell>.json        the limit of each number `correct`
                                  compares in that cell

so a later change adds a cell, a mix or a metric by adding files and
entries, without editing a file that is already here.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib
import sys
import time

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class BenchError(RuntimeError):
  """The cell cannot run as specified (no result is printed)."""


def load_json(path: pathlib.Path) -> dict:
  with open(path) as f:
    return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
  return load_json(root / "BENCHMARK.json")


def load_module(path: pathlib.Path, tag: str):
  """Import the Python file at `path` under a private module name."""
  if not path.is_file():
    raise BenchError(f"no file {path}")
  name = "_bench_" + tag + "_" + "".join(
      c if c.isalnum() else "_" for c in path.stem)
  spec = importlib.util.spec_from_file_location(name, path)
  mod = importlib.util.module_from_spec(spec)
  sys.modules[name] = mod
  spec.loader.exec_module(mod)
  return mod


class Cell:
  """One workload of BENCHMARK.json with its configuration and mix."""

  def __init__(self, name: str, bench_dir: pathlib.Path = BENCH_DIR,
               spec: dict | None = None):
    bench_dir = pathlib.Path(bench_dir)
    self.bench_dir = bench_dir
    self.spec = spec if spec is not None else benchmark(bench_dir.parent)
    cells = {w["name"]: w for w in self.spec["workloads"]}
    if name not in cells:
      raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    self.workload = cells[name]
    self.name = name
    configs = {c["name"]: c for c in self.spec["configs"]}
    self.config_entry = configs[self.workload["config"]]
    self.config = load_json(bench_dir.parent / self.config_entry["file"])
    self.traffic = load_json(
        bench_dir / "traffic" / f"{self.workload['traffic']}.json")
    self.limits = load_json(bench_dir / "limits" / f"{name}.json")
    self.chips = int(self.workload["chips"])

  def driver(self):
    """The mix's driver module (loaded once per cell)."""
    if getattr(self, "_driver", None) is None:
      self._driver = load_module(self.bench_dir / "drivers" /
                                 f"{self.traffic['driver']}.py", "driver")
    return self._driver

  def end_to_end(self) -> list:
    return [m for m in self.spec["end_to_end"] if self._mine(m)]

  def per_layer(self) -> list:
    moves = {m["name"] for m in self.end_to_end()}
    return [m for m in self.spec["per_layer"]
            if m["moves"] in moves and self._mine(m)]

  def _mine(self, metric: dict) -> bool:
    return "workloads" not in metric or self.name in metric["workloads"]

  def metric_reader(self, name: str):
    return load_module(self.bench_dir / "metrics" / f"{name}.py", "metric")

  def kernel(self, name: str):
    return load_module(self.bench_dir / "kernels" / f"{name}.py", "kernel")


def spans(tracing: bool):
  """name -> context manager: a profiler `TraceAnnotation` when the run
  is traced, else nothing (the untraced run pays no span cost)."""
  if not tracing:
    return lambda name: contextlib.nullcontext()
  import jax
  return jax.profiler.TraceAnnotation


class CompileTimer:
  """Counts JAX compile events and their seconds (copied from the
  program's chip_smoke.py, which registers the same listener)."""

  def __init__(self):
    import jax
    self.seconds = 0.0
    self.events = 0
    self.phases = 0             # tracing, lowering and compiling events
    self.cache_hits = 0
    self.cache_misses = 0

    def on_duration(event: str, secs: float, **_) -> None:
      if event.startswith("/jax/core/compile/"):
        self.seconds += secs
        self.phases += 1
        if event.endswith("backend_compile_duration"):
          self.events += 1

    def on_event(event: str, **_) -> None:
      if event == "/jax/compilation_cache/cache_hits":
        self.cache_hits += 1
      elif event == "/jax/compilation_cache/cache_misses":
        self.cache_misses += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


class GcPauses:
  """Times Python's garbage collections while it is entered: a pause
  inside a timed call is host time that the call's latency holds."""

  def __init__(self):
    self.count, self.total_s, self.longest_s = 0, 0.0, 0.0
    self._t = None

  def _on(self, phase: str, info: dict) -> None:
    if phase == "start":
      self._t = time.perf_counter()
    elif self._t is not None:
      d = time.perf_counter() - self._t
      self.count += 1
      self.total_s += d
      self.longest_s = max(self.longest_s, d)
      self._t = None

  def __enter__(self):
    import gc
    gc.callbacks.append(self._on)
    return self

  def __exit__(self, *exc):
    import gc
    gc.callbacks.remove(self._on)

  def as_dict(self) -> dict:
    return {"gc_collections": self.count,
            "gc_total_ms": self.total_s * 1e3,
            "gc_longest_ms": self.longest_s * 1e3}


def peak_bytes(devices) -> int | None:
  """Peak bytes in use on the fullest chip (copied from chip_smoke.py)."""
  peaks = []
  for d in devices:
    stats = d.memory_stats()
    if stats and "peak_bytes_in_use" in stats:
      peaks.append(int(stats["peak_bytes_in_use"]))
  return max(peaks) if peaks else None


class Clock:
  """Seconds since the process started its benchmark code."""

  def __init__(self, t0: float | None = None):
    self.t0 = time.perf_counter() if t0 is None else t0

  def now(self) -> float:
    return time.perf_counter() - self.t0


def compared(readings: dict, limits: dict, more=()) -> list:
  """Checks of the readings that have a limit; the rest go to standard
  error as readings only. `more` are checks that carry their own limit."""
  for name, value in readings.items():
    if name not in limits:
      print(f"bench: reading {name} {value!r} (not compared)",
            file=sys.stderr)
  return [Check(n, v, limits[n]) for n, v in readings.items()
          if n in limits] + list(more)


class Check:
  """One number compared against its limit for `correct`."""

  def __init__(self, name: str, value: float, limit: float,
               ok: bool | None = None):
    self.name = name
    self.value = float(value)
    self.limit = float(limit)
    self.ok = (self.value <= self.limit) if ok is None else bool(ok)

  def as_dict(self) -> dict:
    return {"value": self.value, "limit": self.limit, "ok": self.ok}


def result_line(*, checks: list, attempted: int, failed: int,
                metrics: dict, device: dict, breakdown=None) -> str:
  out = {"correct": bool(checks) and all(c.ok for c in checks),
         "attempted": int(attempted), "failed": int(failed),
         "metrics": metrics, "device": device}
  if breakdown is not None:
    out["breakdown"] = breakdown
  out["checks"] = {c.name: c.as_dict() for c in checks}
  return json.dumps(out)

"""The harness finds a cell's config, mix, driver and metric files by
name, so a new cell needs new files and entries and no edit."""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap

import pytest

from bench import harness
from bench.tests.conftest import ROOT

DRIVER = textwrap.dedent('''
    from bench.harness import Check

    class Run:
      def __init__(self, cell, seed, seconds, tracing, variant=""):
        self.cell, self.seed = cell, seed

      def setup(self):
        return {"made": self.cell.config["size"] * self.cell.traffic["rate"]}

      def window(self):
        return {"e2e": {"toy_rate": float(self.seed % 7 + 1)},
                "attempted": 3, "failed": 0, "audio_s": 1.0}

      def release(self):
        pass

      def verify(self):
        return [Check("toy_gap", 0.0, self.cell.limits["toy_gap"])]
''')
METRIC = "def read(ctx):\n  return ctx.setup['made'] * 2.0\n"


@pytest.fixture
def new_bench(tmp_path):
  """A benchmark tree with one new config, mix, driver and metric."""
  bench = tmp_path / "bench"
  for d in ("configs", "traffic", "drivers", "metrics", "limits"):
    (bench / d).mkdir(parents=True)
  (bench / "limits" / "toy.steady.json").write_text(
      json.dumps({"toy_gap": 1.0}))
  (bench / "configs" / "toy.json").write_text(json.dumps({"size": 3}))
  (bench / "traffic" / "steady.json").write_text(
      json.dumps({"driver": "toyloop", "rate": 5}))
  (bench / "drivers" / "toyloop.py").write_text(DRIVER)
  (bench / "metrics" / "toy_share.layer.py").write_text(METRIC)
  spec = {
      "configs": [{"name": "toy", "file": "bench/configs/toy.json"}],
      "workloads": [{"name": "toy.steady", "config": "toy",
                     "traffic": "steady", "chips": 1}],
      "end_to_end": [
          {"name": "toy_rate", "unit": "x/s", "workloads": ["toy.steady"]},
          {"name": "setup_s", "unit": "s"}],
      "per_layer": [{"name": "toy_share.layer", "unit": "%",
                     "moves": "toy_rate"}],
  }
  (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
  return bench


def test_cell_files_found_by_name(new_bench):
  cell = harness.Cell("toy.steady", bench_dir=new_bench)
  assert cell.config == {"size": 3}
  assert cell.traffic["rate"] == 5
  run = cell.driver().Run(cell, 4, 1.0, False)
  assert run.setup() == {"made": 15}
  assert [m["name"] for m in cell.end_to_end()] == ["toy_rate", "setup_s"]
  assert [m["name"] for m in cell.per_layer()] == ["toy_share.layer"]
  ctx = type("Ctx", (), {"setup": {"made": 15}})()
  assert cell.metric_reader("toy_share.layer").read(ctx) == 30.0


def test_unknown_cell_is_an_error(new_bench):
  with pytest.raises(harness.BenchError, match="no workload"):
    harness.Cell("toy.bursty", bench_dir=new_bench)


def test_new_cell_runs_through_the_harness(new_bench):
  from bench import run as bench_run
  args = bench_run.parse(["--workload", "toy.steady", "--seed",
                          str(2 ** 40 + 3), "--seconds", "1"])
  cell = harness.Cell("toy.steady", bench_dir=new_bench)
  line, checks, _, _ = bench_run.execute(args, cell=cell,
                                         require_accelerator=False)
  out = json.loads(line)
  assert out["correct"] is True
  assert set(out["metrics"]) == {"toy_rate", "setup_s"}
  assert out["metrics"]["toy_rate"]["value"] == float((2 ** 40 + 3) % 7 + 1)
  assert list(out)[-1] == "checks"
  assert {c.name for c in checks} == {"toy_gap", "compiles_in_window"}


def test_every_cell_of_the_benchmark_resolves():
  spec = harness.benchmark()
  for w in spec["workloads"]:
    cell = harness.Cell(w["name"])
    assert cell.driver().Run
    assert cell.end_to_end() and cell.per_layer()
    for m in cell.per_layer():
      assert callable(cell.metric_reader(m["name"]).read)


def test_run_exits_nonzero_without_an_accelerator():
  proc = subprocess.run(
      [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
       "ds2-wsj.live16", "--seed", "1", "--seconds", "1", "--trace", "0"],
      cwd=ROOT, capture_output=True, text=True, timeout=300,
      env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(ROOT)})
  assert proc.returncode != 0
  assert proc.stdout.strip() == ""
  assert "no accelerator" in proc.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
  import shutil
  shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
  shutil.copytree(ROOT / "bench", tmp_path / "bench",
                  ignore=shutil.ignore_patterns("__pycache__"))
  proc = subprocess.run(
      [sys.executable, "bench/run.py", "--workload", "ds2-wsj.live16",
       "--seed", "1", "--seconds", "1", "--trace", "0"],
      cwd=tmp_path, capture_output=True, text=True, timeout=300,
      env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(tmp_path)})
  assert proc.returncode != 0
  assert proc.stdout.strip() == ""

"""A broken timed path must come out as not correct.

Each test drives a whole run of a cell at smoke widths on the CPU (the
harness's look for a chip skipped) with one fault planted in the program
underneath, and sees `correct` false against the cell's own limits:

  state      a step that returns its state unchanged
  half       half of the batch left out (odd slots never step; in
             training, the loss and gradient over the first half only)

Each fault is a jitted wrapper of the program's own step, planted as the
program builds it, so that it compiles in set-up as the real step does.
  token      a token or an answer altered where it is produced (each
             frame's top label swapped with another; in training, one
             layer's update dropped from the step's new parameters)

The sound run of the same cell, in the same test file, comes out correct.
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import pytest

from bench import run as bench_run
from bench.tests.conftest import SHORT, smoke_cell

SEED = 2 ** 34 + 99


def run_cell(name: str) -> dict:
  cell = smoke_cell(name, **SHORT[name.split(".", 1)[1]])
  args = bench_run.parse(["--workload", name, "--seed", str(SEED),
                          "--seconds", "3"])
  line, _, _, _ = bench_run.execute(args, cell=cell,
                                    require_accelerator=False)
  out = json.loads(line)
  print(name, out["checks"])
  return out


# -- serving faults: the speech server's jitted frame step ------------------

def serve_state(step, slots):
  return jax.jit(lambda p, s, x, a: (step(p, s, x, a)[0], s))


def serve_half(step, slots):
  return jax.jit(lambda p, s, x, a: step(p, s, x, a.at[1::2].set(False)))


def serve_token(step, slots):
  def altered(p, s, x, a):
    lp, new = step(p, s, x, a)
    rows = jnp.arange(lp.shape[0])
    top = jnp.argmax(lp, -1)
    alt = (top + lp.shape[-1] // 2) % lp.shape[-1]
    lp = lp.at[rows, top].set(lp[rows, alt]).at[rows, alt].set(lp[rows, top])
    return lp, new
  return jax.jit(altered)


@pytest.fixture
def plant_serving(monkeypatch):
  """Build every speech server with a faulty frame step (so the fault
  compiles in set-up, as the real step does)."""
  from repro.serving import StreamingSpeechServer

  def plant(fault):
    init = StreamingSpeechServer.__init__

    def planted(self, *a, **k):
      init(self, *a, **k)
      self._frame_step = fault(self._frame_step, self.batch)
    monkeypatch.setattr(StreamingSpeechServer, "__init__", planted)
  return plant


# -- training faults: the Trainer's jitted step -----------------------------

def train_state(step):
  return jax.jit(lambda p, o, b, i: (p, o, step(p, o, b, i)[2]))


def train_half(step):
  def first_half(p, o, b, i):
    n = b["feats"].shape[0] // 2
    return step(p, o, {k: v[:n] for k, v in b.items()}, i)
  return jax.jit(first_half)


def train_answer(step):
  def fc_not_updated(p, o, b, i):
    p1, o1, m = step(p, o, b, i)
    return dict(p1, fc=p["fc"]), o1, m
  return jax.jit(fc_not_updated)


@pytest.fixture
def plant_training(monkeypatch):
  from repro.training import trainer

  def plant(fault):
    make = trainer.make_train_step

    def planted(*a, **k):
      init, step = make(*a, **k)
      return init, fault(step)
    monkeypatch.setattr(trainer, "make_train_step", planted)
  return plant


SERVE = ["ds2-wsj.live16", "ds2-wsj.transcribe64"]


@pytest.mark.parametrize("name", SERVE + ["ds2-wsj.train32"])
def test_sound_run_is_correct(name):
  assert run_cell(name)["correct"] is True


@pytest.mark.parametrize("fault", [serve_state, serve_half, serve_token],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", SERVE)
def test_serving_fault_is_not_correct(name, fault, plant_serving):
  plant_serving(fault)
  out = run_cell(name)
  assert out["correct"] is False
  assert out["checks"]["compiles_in_window"]["ok"]


@pytest.mark.parametrize("fault", [train_state, train_half, train_answer],
                         ids=lambda f: f.__name__)
def test_training_fault_is_not_correct(fault, plant_training):
  plant_training(fault)
  out = run_cell("ds2-wsj.train32")
  assert out["correct"] is False
  assert out["checks"]["compiles_in_window"]["ok"]

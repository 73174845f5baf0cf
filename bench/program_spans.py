"""The program's own spans (`repro.runtime.tracing`) of a traced window.

The program records spans only under a profiler session, so with
`--trace 1` its buffer holds the window's spans (set-up runs untraced).
A cell's root span is the one each driver's window counts:

  lockstep  `speech.process_chunk`   window["calls"]
  fleet     `speech.run`             window["drains"]
  train     `train.step`             window["steps"]

`window_records` takes the last N root spans of that name, N as the
window counts them, and every record that descends from them. Readers
run after the window and before the check, so nothing calls the program
in between. A program without the tracer, a run without a profiler, or a
buffer that holds fewer roots (or has dropped some of their spans) gives
None, never 0.

The spans are timed under the profiler's Python tracer, which times
every Python call made inside them: a span's time is inflated by a cost
per call, not in proportion to its work (on a v5e host a span with its
calls read 61.7 ms a `live16` chunk traced against about 54 ms
untraced). A change that removes Python calls therefore shows a larger
drop here than the untraced end-to-end metrics can.
"""
from __future__ import annotations

import importlib

ROOTS = {"lockstep": ("speech.process_chunk", "calls"),
         "fleet": ("speech.run", "drains"),
         "train": ("train.step", "steps")}


def _tracing():
  try:
    return importlib.import_module("repro.runtime.tracing")
  except ImportError:          # a program that records no spans
    return None


def window_records(ctx):
  """(roots, descendants) of the window, or None."""
  tracing = _tracing()
  root_name, key = ROOTS.get(ctx.cell.traffic.get("driver"), (None, None))
  if tracing is None or root_name is None:
    return None
  n = int(ctx.window.get(key, 0))
  recs = tracing.records()
  roots = [r for r in recs if r.parent is None and r.name == root_name]
  if n <= 0 or len(roots) < n:
    return None
  roots = roots[-n:]
  if len(recs) >= tracing.MAX_RECORDS and recs[0].end_ns > roots[0].start_ns:
    return None                # the buffer may have dropped some of them
  parent = {r.id: r.parent for r in recs}
  keep = {r.id for r in roots}

  def inside(r) -> bool:
    p = r.parent
    while p is not None:
      if p in keep:
        return True
      p = parent.get(p)
    return False

  return roots, [r for r in recs if r.parent is not None and inside(r)]


def _seconds(recs) -> float:
  return sum(r.end_ns - r.start_ns for r in recs) * 1e-9


def ms_per_audio_s(ctx, span: str):
  """Summed time (ms) of the window's `span` spans per second of audio
  served."""
  got, audio = window_records(ctx), ctx.window.get("audio_s", 0.0)
  if got is None or audio <= 0:
    return None
  spans = [r for r in got[1] if r.name == span]
  if not spans:
    return None
  return 1e3 * _seconds(spans) / audio


def slot_occupancy(ctx):
  """Live slots over all slots (%) in the window's `speech.frame_step`
  spans: summed `live` over (steps x the mix's `slots`)."""
  got, slots = window_records(ctx), int(ctx.cell.traffic.get("slots", 0))
  if got is None or slots <= 0:
    return None
  steps = [r for r in got[1] if r.name == "speech.frame_step"]
  if not steps:
    return None
  return 100.0 * sum(r.counts.get("live", 0) for r in steps) / (
      len(steps) * slots)


def host_ms_per_step(ctx):
  """Mean over the window's `train.step` spans of the step's time less
  its `train.wait` (the wait for the device to finish the step), in ms:
  the host's work per step, the metrics' transfer after the wait
  included."""
  got = window_records(ctx)
  if got is None:
    return None
  roots, recs = got
  parent = {r.id: r.parent for r in recs}
  wait = {}
  for r in recs:
    if r.name == "train.wait":
      root = r.parent
      while parent.get(root) is not None:
        root = parent[root]
      wait[root] = wait.get(root, 0) + r.end_ns - r.start_ns
  if any(r.id not in wait for r in roots):
    return None
  host = sum(r.end_ns - r.start_ns - wait[r.id] for r in roots)
  return host * 1e-6 / len(roots)

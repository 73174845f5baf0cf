"""Arithmetic that the per-layer metric files share. Each reader returns
None where the run gives it nothing to read, never 0 for a share."""
from __future__ import annotations


def idle_pct(ctx):
  t = ctx.trace
  if t is None or t.window_s <= 0:
    return None
  return 100.0 * (1.0 - t.busy_s / t.window_s)


def idle_within_pct(ctx, span: str):
  """Share (%) of the host's time inside the spans named `span` in which
  no operation ran on the device; None without such a span."""
  if ctx.trace is None:
    return None
  from bench import tracereduce
  busy, total = tracereduce.busy_within(ctx.trace, span)
  if total <= 0:
    return None
  return 100.0 * (1.0 - busy / total)


def launches_per_audio_s(ctx):
  audio = ctx.window.get("audio_s", 0.0)
  if ctx.trace is None or audio <= 0 or not ctx.trace.module_counts:
    return None
  return ctx.trace.launches / audio


def _peak(ctx, key: str = "bf16_flops"):
  return ctx.peaks[key] if ctx.peaks else None


def mfu_over(ctx, seconds_key: str):
  """Model operations of the window's work over `window[seconds_key]`
  seconds, as a share (%) of the chip's bf16 peak."""
  flops, secs = ctx.window.get("model_flops", 0.0), ctx.window.get(
      seconds_key, 0.0)
  peak = _peak(ctx)
  if not flops or secs <= 0 or not peak:
    return None
  return 100.0 * flops / secs / (peak * ctx.cell.chips)


def kernel_roofline(ctx, kernel: str):
  """Least time of the kernel's calls in the window over their summed
  device time (%). A call's operations come from its own HLO instruction
  in the trace (`bench/kernels/<kernel>.py`); its bytes are its operands
  and result held in HBM plus what the copies that staged its other
  operands read from HBM, and its time runs from the earliest of those
  copies to its end (`tracereduce.kernel_calls`), against the chip's
  peaks (`bench/kernels/roofline.py`). A kernel with no call in the
  window reads None."""
  if ctx.trace is None or not ctx.peaks:
    from bench.harness import BenchError
    raise BenchError("a roofline needs a device trace and the chip's peaks")
  from bench import tracereduce
  from bench.kernels import roofline
  mod = ctx.cell.kernel(kernel)
  least, spent = 0.0, 0.0
  for call in tracereduce.kernel_calls(ctx.trace, kernel):
    ops, bytes_ = mod.from_hlo(*tracereduce.hlo_shapes(call.event.name))
    least += roofline.least_seconds(ops, bytes_ + call.staged_bytes,
                                    ctx.peaks)[0]
    spent += (call.event.end - call.start) * 1e-9
  if spent <= 0:
    return None
  return 100.0 * least / spent

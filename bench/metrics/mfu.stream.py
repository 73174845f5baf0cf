"""Model operations of the audio served, over the summed wall time of the
timed server calls, as a share (%) of the chip's bf16 peak."""
from bench.readers import mfu_over


def read(ctx):
  return mfu_over(ctx, "busy_call_s")

"""Model operations of the window's work over all the window's wall time,
as a share (%) of the chip's bf16 peak (padding and recomputation are not
counted)."""
from bench.readers import mfu_over


def read(ctx):
  return mfu_over(ctx, "window_s")

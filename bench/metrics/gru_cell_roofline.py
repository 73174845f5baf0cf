"""Share (%) of its roofline that `gru_cell` reaches: its calls' least time
on the chip (bench/kernels/gru_cell.py, bench/peaks.py) over their summed
device time in the trace."""
from bench.readers import kernel_roofline


def read(ctx):
  return kernel_roofline(ctx, "gru_cell")

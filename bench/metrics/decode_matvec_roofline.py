"""Share (%) of its roofline that `decode_matvec` reaches: its calls' least time
on the chip (bench/kernels/decode_matvec.py, bench/peaks.py) over their summed
device time in the trace."""
from bench.readers import kernel_roofline


def read(ctx):
  return kernel_roofline(ctx, "decode_matvec")

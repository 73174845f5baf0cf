"""Share (%) of its roofline that `lowrank_gemm` reaches: its calls' least time
on the chip (bench/kernels/lowrank_gemm.py, bench/peaks.py) over their summed
device time in the trace."""
from bench.readers import kernel_roofline


def read(ctx):
  return kernel_roofline(ctx, "lowrank_gemm")

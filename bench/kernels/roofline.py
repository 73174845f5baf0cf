"""Least time of a kernel call on a chip: the larger of its operations
over the peak rate and its bytes over the peak bandwidth.

`hbm_bytes` counts a call's operands and result that live in HBM at the
call. XLA may stage an operand in on-chip memory beforehand (an "S(1)"
layout); `tracereduce.kernel_calls` adds the bytes that staging read from
HBM, and the time from its start, to the call.
"""
from __future__ import annotations

ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4, "s8": 1,
            "u8": 1, "pred": 1}


def shape_bytes(shape) -> int:
  """Bytes of one (dtype, dims, memory space) shape."""
  dt, dims, _ = shape
  n = ITEMSIZE[dt]
  for d in dims:
    n *= d
  return n


def hbm_bytes(out, ins) -> float:
  """Bytes of the result and operands (from `hlo_shapes`) held in HBM."""
  return float(sum(shape_bytes(s) for s in [out] + list(ins) if s[2] == 0))


def least_seconds(ops: float, bytes_: float, peaks: dict,
                  rate_key: str = "bf16_flops") -> tuple:
  """(seconds, "compute" | "memory")."""
  tc = ops / peaks[rate_key]
  tm = bytes_ / peaks["hbm_bytes_per_s"]
  return (tc, "compute") if tc >= tm else (tm, "memory")

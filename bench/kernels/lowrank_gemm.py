"""Operations and bytes of one fused `lowrank_gemm` call:
y (b, n) = (x (b, m) @ U (m, r)) @ V (r, n), the rank-r product held in
VMEM (kernels/lowrank_gemm.py). U and V stream from HBM once each; x is
read once and y written once.
"""
from __future__ import annotations


def cost(b: int, m: int, r: int, n: int, itemsize: int = 2) -> tuple:
  """(operations, bytes moved to and from HBM)."""
  ops = 2.0 * b * r * (m + n)
  return ops, float(itemsize * (r * (m + n) + b * m + b * n))



def from_hlo(out, ins) -> tuple:
  """(operations, HBM bytes) of one call from its HLO shapes:
  x (b, m), u (m, r), v (r, n)."""
  from bench.kernels.roofline import hbm_bytes
  (_, (b, m), _), (_, (_, r), _), (_, (_, n), _) = ins[0], ins[1], ins[2]
  return cost(b, m, r, n)[0], hbm_bytes(out, ins)

"""Operations and bytes of one `decode_matvec` call: y (b, n) = x (b, m) @ W (m, n).

The kernel pads b to 8 rows and m, n to 128 lanes (kernels/ops.py) and
streams W from HBM once; x is read once, y written once. Counted at the
logical (unpadded) sizes: padding is waste, not work.
"""
from __future__ import annotations


def cost(b: int, m: int, n: int, itemsize: int = 2) -> tuple:
  """(operations, bytes moved to and from HBM)."""
  return 2.0 * b * m * n, float(itemsize * (m * n + b * m + b * n))



def from_hlo(out, ins) -> tuple:
  """(operations, HBM bytes) of one call from its HLO shapes:
  x (b, m), w (m, n)."""
  from bench.kernels.roofline import hbm_bytes
  (_, (b, m), _), (_, (_, n), _) = ins[0], ins[1]
  return cost(b, m, n)[0], hbm_bytes(out, ins)

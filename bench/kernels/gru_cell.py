"""Operations and bytes of one fused `gru_cell` call (kernels/gru_cell.py).

Inputs: xw (b, 3H), h (b, H) read twice (whole and by block), U (H, 3H),
bias (3H,) in f32; output h' (b, H). Operations: the recurrent product
h @ U (2 b H 3H) plus about 10 per gate element for the gate math.
"""
from __future__ import annotations


def cost(b: int, hidden: int, itemsize: int = 2) -> tuple:
  """(operations, bytes moved to and from HBM)."""
  h = hidden
  ops = 2.0 * b * h * 3 * h + 10.0 * b * 3 * h
  bytes_ = itemsize * (3 * h * h + b * 3 * h + 2 * b * h + b * h) + 4 * 3 * h
  return ops, float(bytes_)



def from_hlo(out, ins) -> tuple:
  """(operations, HBM bytes) of one call from its HLO shapes: h' (b, H)."""
  from bench.kernels.roofline import hbm_bytes
  _, (b, h), _ = out
  return cost(b, h)[0], hbm_bytes(out, ins)

"""Fused GRU cell: recurrent GEMM (h @ U) + gate nonlinearities in one
kernel (paper eq. 10, the sequential batch~1 GEMM the farm kernels target).

The non-recurrent projection xw = x @ W is batched across time *outside*
the cell (paper §4 / B.2 — that GEMM has no sequential dependency), so the
kernel consumes xw precomputed.

Layout trick: the three gates of output column i live at U columns
(i, H+i, 2H+i). The wrapper reshapes U (H, 3H) -> (H, 3, H) so one output
tile (B, bh) needs exactly the U block (H, 3, bh) — gate-aligned streaming
without strided reads. Grid: (H/bh,), weights visited once.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(xw_ref, h_full_ref, u_ref, b_ref, h_blk_ref, out_ref):
  hidden_blk = out_ref.shape[-1]
  hf = h_full_ref[...].astype(jnp.float32)            # (B, H)
  u = u_ref[...].astype(jnp.float32)                  # (H, 3, bh)
  u2 = u.reshape(u.shape[0], 3 * hidden_blk)
  hu = jnp.dot(hf, u2, preferred_element_type=jnp.float32)
  hu = hu.reshape(hf.shape[0], 3, hidden_blk)
  g = xw_ref[...].astype(jnp.float32) + hu + b_ref[...].astype(jnp.float32)
  z = jax.nn.sigmoid(g[:, 0])
  r = jax.nn.sigmoid(g[:, 1])
  hu_h = hu[:, 2]
  hcand = jnp.tanh(g[:, 2] - hu_h + r * hu_h)
  h_old = h_blk_ref[...].astype(jnp.float32)
  out_ref[...] = ((1.0 - z) * h_old + z * hcand).astype(out_ref.dtype)


def gru_cell(xw: jax.Array, h: jax.Array, u: jax.Array, bias: jax.Array, *,
             block_h: int = 256, interpret: bool = False) -> jax.Array:
  """xw: (b, 3H); h: (b, H); u: (H, 3H); bias: (3H,) -> h': (b, H)."""
  b, hidden = h.shape
  bh = min(block_h, hidden)
  assert hidden % bh == 0, (hidden, bh)
  nh = hidden // bh

  u3 = u.reshape(hidden, 3, hidden)          # (H, gate, H)
  xw3 = xw.reshape(b, 3, hidden)
  bias3 = bias.reshape(1, 3, hidden)

  return pl.pallas_call(
      _kernel,
      grid=(nh,),
      name="gru_cell",
      in_specs=[
          pl.BlockSpec((b, 3, bh), lambda i: (0, 0, i)),
          pl.BlockSpec((b, hidden), lambda i: (0, 0)),
          pl.BlockSpec((hidden, 3, bh), lambda i: (0, 0, i)),
          pl.BlockSpec((1, 3, bh), lambda i: (0, 0, i)),
          pl.BlockSpec((b, bh), lambda i: (0, i)),
      ],
      out_specs=pl.BlockSpec((b, bh), lambda i: (0, i)),
      out_shape=jax.ShapeDtypeStruct((b, hidden), h.dtype),
      interpret=interpret,
  )(xw3, h, u3, bias3, h)

"""w8a8 int8 GEMM with int32 MXU accumulation + fused per-channel dequant.

The TPU replacement for gemmlowp's u8 path (paper §4): the MXU consumes
signed s8 x s8 -> s32 natively, so symmetric per-channel quantization needs
no zero-point correction GEMM. Dequantization (x_scale[b] * w_scale[n])
happens in-register before the single f32 store — the int32 accumulator
never touches HBM.

Grid: (nn, nm) with the m (contracting) dimension innermost; the int32
accumulator tile lives in VMEM scratch and is dequantized+flushed on the
last m step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, xs_ref, ws_ref, y_ref, acc_ref, *, nm: int):
  j = pl.program_id(1)

  @pl.when(j == 0)
  def _init():
    acc_ref[...] = jnp.zeros_like(acc_ref)

  # s8 x s8 operands straight into the MXU; Mosaic has no s32 matmul
  acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                          preferred_element_type=jnp.int32)

  @pl.when(j == nm - 1)
  def _dequant():
    y_ref[...] = (acc_ref[...].astype(jnp.float32) *
                  xs_ref[...].astype(jnp.float32) *
                  ws_ref[...].astype(jnp.float32))


def int8_gemm(x_q: jax.Array, w_q: jax.Array, x_scale: jax.Array,
              w_scale: jax.Array, *, block_m: int = 512, block_n: int = 512,
              interpret: bool = False) -> jax.Array:
  """x_q: (b, m) s8; w_q: (m, n) s8; x_scale: (b, 1); w_scale: (1, n)
  -> f32 (b, n). The scales are 2-D so their blocks keep the (8, 128)
  tiling Mosaic requires of every operand."""
  b, m = x_q.shape
  n = w_q.shape[1]
  bm = min(block_m, m)
  bn = min(block_n, n)
  assert m % bm == 0 and n % bn == 0, (m, bm, n, bn)
  nm, nn = m // bm, n // bn

  return pl.pallas_call(
      functools.partial(_kernel, nm=nm),
      grid=(nn, nm),
      in_specs=[
          pl.BlockSpec((b, bm), lambda i, j: (0, j)),
          pl.BlockSpec((bm, bn), lambda i, j: (j, i)),
          pl.BlockSpec((b, 1), lambda i, j: (0, 0)),
          pl.BlockSpec((1, bn), lambda i, j: (0, i)),
      ],
      out_specs=pl.BlockSpec((b, bn), lambda i, j: (0, i)),
      out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
      scratch_shapes=[pltpu.VMEM((b, bn), jnp.int32)],
      interpret=interpret,
  )(x_q, w_q, x_scale, w_scale)

"""Skinny-GEMM decode kernel: y = x @ W for batch 1..16.

The direct TPU analogue of the paper's `farm` ARM kernels: low-batch GEMM
is memory-bandwidth bound (arithmetic intensity ~ batch), so the kernel's
job is to stream W from HBM exactly once at full bandwidth. The activation
x stays resident in VMEM across the whole grid; W is visited tile by tile
in (n-outer, m-inner) order; each weight tile is fetched exactly once.

Versus the paper: NEON register blocking becomes (8, 128)-aligned VMEM
blocks, and gemmlowp's u8 offset trick is unnecessary (see int8_gemm).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, y_ref, acc_ref, *, nm: int):
  j = pl.program_id(1)

  @pl.when(j == 0)
  def _init():
    acc_ref[...] = jnp.zeros_like(acc_ref)

  acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                          w_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)

  @pl.when(j == nm - 1)
  def _emit():
    y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def decode_matvec(x: jax.Array, w: jax.Array, *, block_m: int = 1024,
                  block_n: int = 256, interpret: bool = False) -> jax.Array:
  """x: (b, m) with small b; w: (m, n) -> y: (b, n)."""
  b, m = x.shape
  n = w.shape[1]
  bm = min(block_m, m)
  bn = min(block_n, n)
  assert m % bm == 0 and n % bn == 0, (m, bm, n, bn)
  nm, nn = m // bm, n // bn

  return pl.pallas_call(
      functools.partial(_kernel, nm=nm),
      grid=(nn, nm),
      name="decode_matvec",
      in_specs=[
          pl.BlockSpec((b, bm), lambda i, j: (0, j)),
          pl.BlockSpec((bm, bn), lambda i, j: (j, i)),
      ],
      out_specs=pl.BlockSpec((b, bn), lambda i, j: (0, i)),
      out_shape=jax.ShapeDtypeStruct((b, n), x.dtype),
      scratch_shapes=[pltpu.VMEM((b, bn), jnp.float32)],
      interpret=interpret,
  )(x, w)

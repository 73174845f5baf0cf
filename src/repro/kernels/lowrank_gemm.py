"""Fused low-rank GEMM: y = (x @ U) @ V with the rank-r intermediate held
in VMEM scratch — it never round-trips HBM.

This is the TPU-native form of the paper's factored inference GEMM: in the
low-batch regime the win is streaming r(m+n) weight bytes instead of mn,
and fusing the two skinny GEMMs removes the (B, r) HBM round-trip and the
second kernel launch.

Grid: (nm + nn,) — the first nm steps accumulate t = x @ U over m-tiles
into scratch; the remaining nn steps emit y n-tiles from t @ V. The output
block index stays 0 during phase 1, so nothing is flushed until the first
real write. Block shapes are (8, 128)-aligned by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, u_ref, v_ref, y_ref, t_ref, *, nm: int):
  i = pl.program_id(0)

  @pl.when(i == 0)
  def _init():
    t_ref[...] = jnp.zeros_like(t_ref)

  @pl.when(i < nm)
  def _accumulate():
    t_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                          u_ref[...].astype(jnp.float32),
                          preferred_element_type=jnp.float32)

  @pl.when(i >= nm)
  def _emit():
    y_ref[...] = jnp.dot(t_ref[...], v_ref[...].astype(jnp.float32),
                         preferred_element_type=jnp.float32
                         ).astype(y_ref.dtype)


def lowrank_gemm(x: jax.Array, u: jax.Array, v: jax.Array, *,
                 block_m: int = 512, block_n: int = 512,
                 interpret: bool = False) -> jax.Array:
  """x: (b, m), u: (m, r), v: (r, n) -> (b, n). Dims pre-padded by ops."""
  b, m = x.shape
  r = u.shape[1]
  n = v.shape[1]
  bm = min(block_m, m)
  bn = min(block_n, n)
  assert m % bm == 0 and n % bn == 0, (m, bm, n, bn)
  nm, nn = m // bm, n // bn

  return pl.pallas_call(
      functools.partial(_kernel, nm=nm),
      grid=(nm + nn,),
      name="lowrank_gemm",
      in_specs=[
          pl.BlockSpec((b, bm), lambda i: (0, jnp.minimum(i, nm - 1))),
          pl.BlockSpec((bm, r), lambda i: (jnp.minimum(i, nm - 1), 0)),
          pl.BlockSpec((r, bn), lambda i: (0, jnp.maximum(i - nm, 0))),
      ],
      out_specs=pl.BlockSpec((b, bn), lambda i: (0, jnp.maximum(i - nm, 0))),
      out_shape=jax.ShapeDtypeStruct((b, n), x.dtype),
      scratch_shapes=[pltpu.VMEM((b, r), jnp.float32)],
      interpret=interpret,
  )(x, u, v)

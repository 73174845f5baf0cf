"""Blockwise online-softmax (flash) attention kernel.

Not a paper contribution — needed so the assigned archs' 32k prefill never
materializes an S x S score matrix on the TPU target. Matches the jnp
blocking in layers/attention.py (which is its oracle).

Grid: (b*h, nq, nk) with kv innermost. Running (m, l, acc) live in VMEM
scratch across kv steps; causal tiles with kv_start > q_end are skipped
via pl.when (they still occupy grid slots but do no MXU work — the wedge
variant in layers/attention.py removes them statically for the XLA path).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            nk: int, bq: int, bk: int, scale: float, causal: bool):
  qi = pl.program_id(1)
  kj = pl.program_id(2)

  @pl.when(kj == 0)
  def _init():
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

  run = (not causal) or (kj * bk <= qi * bq + bq - 1)

  @pl.when(run)
  def _tile():
    q = q_ref[0].astype(jnp.float32)                   # (bq, d)
    k = k_ref[0].astype(jnp.float32)                   # (bk, d)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
      qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
      kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
      s = jnp.where(kpos <= qpos, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

  @pl.when(kj == nk - 1)
  def _emit():
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 512,
                    block_k: int = 512,
                    interpret: bool = False) -> jax.Array:
  """q, k, v: (b, s, h, d) with h == kv heads (GQA pre-repeated) -> same."""
  b, s, h, d = q.shape
  bq = min(block_q, s)
  bk = min(block_k, s)
  assert s % bq == 0 and s % bk == 0, (s, bq, bk)
  nq, nk = s // bq, s // bk
  scale = 1.0 / (d ** 0.5)

  # (b, s, h, d) -> (b*h, s, d) so one grid axis covers batch x heads
  qt = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
  kt = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
  vt = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)

  out = pl.pallas_call(
      functools.partial(_kernel, nk=nk, bq=bq, bk=bk, scale=scale,
                        causal=causal),
      grid=(b * h, nq, nk),
      name="flash_attention",
      in_specs=[
          pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
          pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
          pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
      ],
      out_specs=pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
      out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
      scratch_shapes=[
          pltpu.VMEM((bq,), jnp.float32),
          pltpu.VMEM((bq,), jnp.float32),
          pltpu.VMEM((bq, d), jnp.float32),
      ],
      interpret=interpret,
  )(qt, kt, vt)
  return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)

"""Jit'd public wrappers for the Pallas kernels.

Responsibilities: (8, 128)-align every matmul dim (pad + slice), pick
block shapes that fit VMEM, fall back to the jnp reference when a shape is
degenerate (dims < MXU tile), and expose an `interpret` flag so the CPU
container runs the kernel bodies in Python (the tests' default).

On this container interpret=True is forced automatically (no TPU), which
is also how the per-kernel allclose sweeps in tests/test_kernels.py run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_matvec import decode_matvec as _decode_matvec
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gru_cell import gru_cell as _gru_cell
from repro.kernels.int8_gemm import int8_gemm as _int8_gemm
from repro.kernels.lowrank_gemm import lowrank_gemm as _lowrank_gemm

LANE = 128
SUBLANE = 8

# decode_matvec's documented contract (paper §4: batch 1..16); the wrapper
# falls back to the jnp reference above this, it never silently accepts.
DECODE_BATCH_MAX = 16

# Default block shapes per kernel — THE block-size table (the wrappers'
# block args default to None, so edits here take effect everywhere). A
# caller's explicit request wins; `_fit_blocks` then clamps each block to
# its dim and halves until it divides, so every kernel shares one copy of
# the fitting logic instead of inlining it.
BLOCK_TABLE: dict[str, dict[str, int]] = {
    "lowrank_gemm": {"block_m": 512, "block_n": 512},
    "int8_gemm": {"block_m": 512, "block_n": 512},
    "decode_matvec": {"block_m": 1024, "block_n": 256},
    "gru_cell": {"block_h": 256},
    "flash_attention": {"block_q": 512, "block_k": 512},
}


def _fit_blocks(kernel: str, dims: dict[str, int],
                requested: dict[str, int] | None = None) -> dict[str, int]:
  """Pick block sizes for `kernel`: table default (or caller request),
  clamped to the padded dim, halved until it divides the dim."""
  table = BLOCK_TABLE[kernel]
  out = {}
  for key, dim in dims.items():
    blk = (requested or {}).get(key) or table[key]
    blk = min(blk, dim)
    while dim % blk:
      blk //= 2
    out[key] = blk
  return out


def _on_tpu() -> bool:
  return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
  size = x.shape[axis]
  pad = (-size) % mult
  if pad == 0:
    return x
  widths = [(0, 0)] * x.ndim
  widths[axis] = (0, pad)
  return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def lowrank_gemm(x, u, v, *, block_m: int | None = None,
                 block_n: int | None = None,
                 interpret: bool | None = None):
  """y = (x @ U) @ V fused; x: (b, m), u: (m, r), v: (r, n)."""
  interpret = (not _on_tpu()) if interpret is None else interpret
  b, m = x.shape
  r, n = v.shape
  if min(m, n, r) < LANE:
    return ref.lowrank_gemm(x, u, v)
  xp = _pad_to(_pad_to(x, 0, SUBLANE), 1, LANE)
  up = _pad_to(_pad_to(u, 0, LANE), 1, LANE)
  vp = _pad_to(_pad_to(v, 0, LANE), 1, LANE)
  blocks = _fit_blocks(
      "lowrank_gemm", {"block_m": xp.shape[1], "block_n": vp.shape[1]},
      {"block_m": block_m, "block_n": block_n})
  y = _lowrank_gemm(xp, up, vp, interpret=interpret, **blocks)
  return y[:b, :n]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def int8_gemm(x_q, w_q, x_scale, w_scale, *, block_m: int | None = None,
              block_n: int | None = None, interpret: bool | None = None):
  """w8a8 GEMM with fused dequant; returns f32 (b, n)."""
  interpret = (not _on_tpu()) if interpret is None else interpret
  b, m = x_q.shape
  n = w_q.shape[1]
  if min(m, n) < LANE:
    return ref.int8_gemm(x_q, w_q, x_scale, w_scale)
  xp = _pad_to(_pad_to(x_q, 0, SUBLANE), 1, LANE)
  wp = _pad_to(_pad_to(w_q, 0, LANE), 1, LANE)
  xsp = _pad_to(x_scale, 0, SUBLANE)[:, None]
  wsp = _pad_to(w_scale, 0, LANE)[None, :]
  blocks = _fit_blocks(
      "int8_gemm", {"block_m": xp.shape[1], "block_n": wp.shape[1]},
      {"block_m": block_m, "block_n": block_n})
  y = _int8_gemm(xp, wp, xsp, wsp, interpret=interpret, **blocks)
  return y[:b, :n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantized_matmul(x: jax.Array, w: jax.Array,
                     interpret: bool | None = None) -> jax.Array:
  """w8a8 entry point: quantize both operands then int8_gemm.

  This is the regime `kernels.dispatch` routes "int8_gemm" overrides on
  FLOAT leaves to. Jitted so the quantize+gemm program is traced once per
  shape instead of re-traced every call. The weight is re-quantized per
  call (O(mn) scan) — a numerics/code-path regime, not a perf one. The
  perf path is `repro.quant`: PTQ'd QuantizedLinear leaves classify into
  int8_gemm by type and consume their stored scales directly with zero
  weight quantize ops (see quant.kernel_apply)."""
  x_q, x_s = ref.quantize_rowwise(x)
  w_q, w_s = ref.quantize_colwise(w)
  return int8_gemm(x_q, w_q, x_s, w_s, interpret=interpret).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def decode_matvec(x, w, *, block_m: int | None = None,
                  block_n: int | None = None,
                  interpret: bool | None = None):
  """Low-batch y = x @ w; x: (b, m) with b <= DECODE_BATCH_MAX, w: (m, n).

  Batches above DECODE_BATCH_MAX are OUTSIDE the kernel's contract (its
  weight-streaming schedule assumes x fits one VMEM tile) and fall back to
  the jnp reference rather than being silently accepted."""
  interpret = (not _on_tpu()) if interpret is None else interpret
  b, m = x.shape
  n = w.shape[1]
  if b > DECODE_BATCH_MAX or min(m, n) < LANE:
    return ref.decode_matvec(x, w)
  xp = _pad_to(_pad_to(x, 0, SUBLANE), 1, LANE)
  wp = _pad_to(_pad_to(w, 0, LANE), 1, LANE)
  blocks = _fit_blocks(
      "decode_matvec", {"block_m": xp.shape[1], "block_n": wp.shape[1]},
      {"block_m": block_m, "block_n": block_n})
  y = _decode_matvec(xp, wp, interpret=interpret, **blocks)
  return y[:b, :n]


@functools.partial(jax.jit, static_argnames=("block_h", "interpret"))
def gru_cell(xw, h, u, bias, *, block_h: int | None = None,
             interpret: bool | None = None):
  """Fused GRU step; xw: (b, 3H), h: (b, H), u: (H, 3H), bias: (3H,)."""
  interpret = (not _on_tpu()) if interpret is None else interpret
  b, hidden = h.shape
  if hidden < LANE:
    return ref.gru_cell(xw, h, u, bias)
  blocks = _fit_blocks("gru_cell", {"block_h": hidden},
                       {"block_h": block_h})
  return _gru_cell(xw, h, u, bias, interpret=interpret, **blocks)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None):
  """q, k, v: (b, s, h, d); GQA callers repeat kv heads first."""
  interpret = (not _on_tpu()) if interpret is None else interpret
  b, s, h, d = q.shape
  if s < SUBLANE or d < LANE:
    return ref.flash_attention(q, k, v, causal=causal)
  blocks = _fit_blocks("flash_attention", {"block_q": s, "block_k": s},
                       {"block_q": block_q, "block_k": block_k})
  return _flash(q, k, v, causal=causal, interpret=interpret, **blocks)

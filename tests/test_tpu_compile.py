"""The Pallas kernels of the DS2 serving path compile for a TPU v5e.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: unaligned blocks, operand types the MXU lacks, operand
layouts Mosaic rejects. Each test compiles one kernel at deepspeech2-wsj
widths for one chip of a v5e:2x2 topology that is described, not
attached, and checks the kernel is in the compiled program. The topology
is described inside a fixture, so collecting this file loads no TPU
library; where it cannot be described the tests skip."""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

BATCH = 4                    # the serving slots chip_smoke.py runs
GRU_IN = 20 * 32             # post-frontend width: 20 freq bins x 32 ch
FC = 1536
RANK = 256


@pytest.fixture(scope="module")
def one_chip():
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  from jax.experimental import topologies
  from jax.experimental.compilation_cache import compilation_cache
  try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:       # no TPU compiler in this installation
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
  # a compile for a described chip cannot be read back from the
  # persistent cache without the chip, so keep it out of the cache
  enabled = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  compilation_cache.reset_cache()
  yield SingleDeviceSharding(topo.devices[0])
  jax.config.update("jax_enable_compilation_cache", enabled)
  compilation_cache.reset_cache()


def _compile_text(fn, shapes, sharding):
  args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
  return jax.jit(functools.partial(fn, interpret=False)).lower(
      *args).compile().as_text()


@pytest.mark.parametrize("hidden", [768, 1280])
def test_gru_cell(one_chip, hidden):
  bf = jnp.bfloat16
  text = _compile_text(ops.gru_cell, [
      ((BATCH, 3 * hidden), bf), ((BATCH, hidden), bf),
      ((hidden, 3 * hidden), bf), ((3 * hidden,), jnp.float32)], one_chip)
  assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,n", [(GRU_IN, 3 * 768), (1280, FC)])
def test_decode_matvec(one_chip, m, n):
  text = _compile_text(ops.decode_matvec, [
      ((BATCH, m), jnp.bfloat16), ((m, n), jnp.bfloat16)], one_chip)
  assert "tpu_custom_call" in text


def test_lowrank_gemm(one_chip):
  bf = jnp.bfloat16
  text = _compile_text(ops.lowrank_gemm, [
      ((BATCH, 1280), bf), ((1280, RANK), bf), ((RANK, 3 * 1280), bf)],
      one_chip)
  assert "tpu_custom_call" in text


@pytest.mark.parametrize("m,n", [(1280, 3 * 1280), (GRU_IN, 3 * 768)])
def test_int8_gemm(one_chip, m, n):
  text = _compile_text(ops.int8_gemm, [
      ((BATCH, m), jnp.int8), ((m, n), jnp.int8), ((BATCH,), jnp.float32),
      ((n,), jnp.float32)], one_chip)
  assert "tpu_custom_call" in text
